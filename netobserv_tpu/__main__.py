"""CLI entry: env-configured agent binary (reference analog:
`cmd/netobserv-ebpf-agent.go` — zero flags, SIGTERM-driven shutdown)."""

from __future__ import annotations

import logging
import signal
import sys
import threading

from netobserv_tpu import __version__
from netobserv_tpu.agent import FlowsAgent
from netobserv_tpu.config import EXPORT_TPU_SKETCH, load_config
from netobserv_tpu.metrics.server import start_metrics_server

log = logging.getLogger("netobserv_tpu")


def main() -> int:
    cfg = load_config()
    logging.basicConfig(
        level=getattr(logging, cfg.log_level.upper(), logging.INFO),
        format="%(asctime)s %(levelname)s %(name)s %(message)s",
        stream=sys.stderr)
    log.info("starting netobserv_tpu agent %s (export=%s)",
             __version__, cfg.export)
    if cfg.export == EXPORT_TPU_SKETCH or cfg.federation_mode == "aggregator":
        # the JAX-backed planes: persist compiled executables across
        # restarts (jax-free exporters never import jax)
        from netobserv_tpu.utils.platform import enable_compile_cache
        log.info("jax compilation cache: %s", enable_compile_cache())

    dbg = None
    if cfg.pprof_addr:
        from netobserv_tpu.server import start_debug_server
        dbg = start_debug_server(cfg.pprof_addr)

    try:
        if cfg.federation_mode == "aggregator":
            # central aggregator tier: delta ingest + device merge + the
            # cluster-wide query surface, no datapath/flow pipeline at all
            from netobserv_tpu.federation.service import (
                FederationAggregatorService,
            )
            agent = FederationAggregatorService(cfg)
        elif cfg.enable_pca:
            import os as _os

            if not cfg.target_host or not cfg.target_port:
                raise ValueError(
                    "ENABLE_PCA: TARGET_HOST and TARGET_PORT (or "
                    "PCA_SERVER_PORT) are required")
            from netobserv_tpu.agent.packets_agent import PacketsAgent
            mode = _os.environ.get("DATAPATH", "auto")
            if mode.startswith("pcap:"):
                from netobserv_tpu.datapath.replay import PcapPacketFetcher
                pkt_fetcher = PcapPacketFetcher(mode[5:])
            else:
                # self-managed kernel capture: hand-assembled PCA program,
                # verifier-loaded, no compiler required
                from netobserv_tpu.datapath.loader import \
                    load_packet_fetcher
                pkt_fetcher = load_packet_fetcher(cfg)
            agent = PacketsAgent(cfg, pkt_fetcher)
        else:
            agent = FlowsAgent.from_config(cfg)
    except (ValueError, RuntimeError) as exc:
        log.error("invalid configuration: %s", exc)
        return 2

    srv = None
    metrics = getattr(agent, "metrics", None)
    if cfg.metrics_enable and metrics is not None:
        # /healthz + /readyz ride on the metrics server when the agent
        # exposes a supervised health snapshot (FlowsAgent does)
        srv = start_metrics_server(
            metrics.registry, cfg.metrics_server_address,
            cfg.metrics_server_port, cfg.metrics_tls_cert_path,
            cfg.metrics_tls_key_path,
            health_source=getattr(agent, "health_snapshot", None),
            query_routes=getattr(agent, "query_routes", None))

    stop = threading.Event()

    def _terminate(signum, _frame):
        log.info("received %s, stopping agent", signal.Signals(signum).name)
        stop.set()

    signal.signal(signal.SIGTERM, _terminate)
    signal.signal(signal.SIGINT, _terminate)

    agent.run(stop)
    if srv is not None:
        srv.shutdown()
    if dbg is not None:
        dbg.shutdown()
    log.info("agent stopped")
    return 0


if __name__ == "__main__":
    sys.exit(main())
