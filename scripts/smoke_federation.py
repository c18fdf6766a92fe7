#!/usr/bin/env python
"""Federation smoke: two in-process agents -> local aggregator -> query.

`make smoke-federation` (non-gating CI artifact): spins up a
FederationAggregatorService on ephemeral ports, two TpuSketchExporters pushing delta frames through the REAL gRPC seam,
folds a deterministic record stream through each, flushes both windows,
and asserts the cluster-wide /federation/topk answer merges both agents'
traffic. Prints ONE JSON line with what it saw.

`--failure-path` (`make smoke-federation-chaos`, also driven by
tests/test_federation_chaos.py) runs the RAINY day instead: the agents
come up FIRST and push into nothing (cold start — their sinks walk the
retry ladder and drop), the aggregator starts late and catches up on the
next window, is then shut down and restarted once mid-run (restoring from
its checkpoint), while a query poller hammers the surface asserting it
never serves a torn snapshot (every response internally consistent, seq/
window monotonically non-decreasing across the restart thanks to the
restored window counter).
"""

from __future__ import annotations

import json
import os
import socket
import sys
import threading
import time
import urllib.error
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main() -> int:
    from netobserv_tpu.config import AgentConfig
    from netobserv_tpu.exporter.federation import FederationDeltaSink
    from netobserv_tpu.exporter.tpu_sketch import TpuSketchExporter
    from netobserv_tpu.federation.service import FederationAggregatorService
    from netobserv_tpu.model.flow import FlowKey
    from netobserv_tpu.model.record import Record
    from netobserv_tpu.sketch.state import SketchConfig
    from netobserv_tpu.utils import tracing

    # sample everything: the smoke asserts ONE cross-process trace end to
    # end (agent window span + the aggregator's continued child spans under
    # the same trace id, looked up via /debug/traces?trace= on the
    # aggregator's query surface)
    tracing.configure(sample=1.0, capacity=64)

    cfg = AgentConfig()
    cfg.sketch_cm_depth, cfg.sketch_cm_width = 2, 4096
    cfg.sketch_hll_precision, cfg.sketch_topk = 8, 128
    cfg.federation_listen_port = 0   # ephemeral
    cfg.federation_query_port = 0    # ephemeral
    cfg.federation_window = 3600.0
    reports: list[dict] = []
    svc = FederationAggregatorService(cfg, sink=reports.append)
    svc.start()

    def make_records(agent: int, n: int = 256) -> list[Record]:
        now = time.time_ns()
        out = []
        for i in range(n):
            # one shared mega-flow both agents see + per-agent chatter
            if i % 4 == 0:
                key = FlowKey.make("10.9.9.9", "10.8.8.8", 5000, 443, 6)
                nbytes = 1_000_000
            else:
                key = FlowKey.make(f"10.{agent}.0.{i % 50}",
                                   f"10.{agent}.1.{i % 20}",
                                   1024 + i, 443, 6)
                nbytes = 1000 + i
            out.append(Record(
                key=key, bytes_=nbytes, packets=3, eth_protocol=0x0800,
                tcp_flags=0x12, direction=1, if_index=1, interface="eth0",
                time_flow_start_ns=now - 10**9, time_flow_end_ns=now))
        return out

    sketch_cfg = SketchConfig(cm_depth=2, cm_width=4096, hll_precision=8,
                              topk=128)
    agents = []
    for a in range(2):
        sink = FederationDeltaSink("127.0.0.1", svc.grpc_port,
                                   metrics=svc.metrics)
        exp = TpuSketchExporter(
            batch_size=256, window_s=3600.0, sketch_cfg=sketch_cfg,
            sink=lambda obj: None, delta_sink=sink,
            agent_id=f"smoke-agent-{a}")
        exp.export_batch(make_records(a))
        exp.flush()   # closes the window and pushes the delta frame
        agents.append(exp)

    svc.aggregator.flush()  # close the aggregator window, publish

    def get(path: str) -> dict:
        url = f"http://127.0.0.1:{svc.query_port}{path}"
        with urllib.request.urlopen(url, timeout=10) as resp:
            return json.loads(resp.read())

    topk = get("/federation/topk?n=10")
    status = get("/federation/status")
    card = get("/federation/cardinality")
    freq = get("/federation/frequency?src=10.9.9.9&dst=10.8.8.8"
               "&src_port=5000&dst_port=443&proto=6")
    healthz = get("/healthz")
    fleet = get("/federation/fleet")

    ok = True
    notes = []

    # one end-to-end trace: every continued agent trace in the recorder
    # carries the SAME id as the agent window trace that stamped it; the
    # ?trace= lookup on the aggregator's query surface must return spans
    # from BOTH tiers (agent "window" + continued "federation_delta")
    cont = next((t for t in tracing.snapshot()
                 if t["kind"] == "federation_delta"), None)
    trace_kinds: list[str] = []
    journey: list[dict] = []
    if cont is None:
        ok, _ = False, notes.append("no continued federation_delta trace "
                                    "in the flight recorder")
    else:
        journey = get(f"/debug/traces?trace={cont['trace_id']}")["traces"]
        trace_kinds = sorted({t["kind"] for t in journey})
        if not {"window", "federation_delta"} <= set(trace_kinds):
            ok, _ = False, notes.append(
                f"trace {cont['trace_id']} did not span both tiers: "
                f"{trace_kinds}")
        stages = {s["stage"] for t in journey for st in [t["stages"]]
                  for s in st}
        if not {"delta_validate", "report_render"} & stages:
            ok, _ = False, notes.append(
                f"aggregator child spans missing from {cont['trace_id']}: "
                f"{sorted(stages)}")

    # fleet rollup: both agents' telemetry blocks present and sane
    fleet_agents = sorted(fleet.get("agents", {}))
    if fleet_agents != ["smoke-agent-0", "smoke-agent-1"]:
        ok, _ = False, notes.append(
            f"/federation/fleet missing agents: {fleet_agents}")
    for aid, row in fleet.get("agents", {}).items():
        tel = row.get("telemetry") or {}
        if tel.get("windows_published", 0) < 1 or \
                tel.get("shed_factor", 0) <= 0:
            ok, _ = False, notes.append(
                f"fleet telemetry for {aid} not populated: {tel}")
    if len(status["agents"]) != 2:
        ok, _ = False, notes.append("expected 2 agents in /status")
    hh = topk["topk"]
    if not hh or hh[0]["SrcAddr"] != "10.9.9.9":
        ok, _ = False, notes.append(
            "shared mega-flow is not the top heavy hitter")
    if card["records"] != 512.0:
        ok, _ = False, notes.append(f"records {card['records']} != 512")
    if freq["est_bytes"] < 2 * 64 * 1_000_000:  # both agents' shares
        ok, _ = False, notes.append("frequency underestimates the "
                                    "cluster-wide mega-flow")
    if healthz.get("status") != "Started":
        ok, _ = False, notes.append(f"healthz says {healthz.get('status')}")

    for exp in agents:
        exp.close()
    svc.shutdown()
    print(json.dumps({
        "metric": "smoke_federation", "ok": ok, "notes": notes,
        "agents": sorted(status["agents"]),
        "top1": hh[0] if hh else None,
        "records": card["records"],
        "distinct_src_estimate": card["distinct_src_estimate"],
        "megaflow_est_bytes": freq["est_bytes"],
        "megaflow_bound_bytes": freq["overestimate_bound_bytes"],
        "reports_published": len(reports),
        # CI artifact extras: the fleet snapshot + ONE rendered
        # cross-process trace (agent + aggregator spans, one id)
        "fleet": fleet,
        "trace_id": cont["trace_id"] if cont else None,
        "trace_kinds": trace_kinds,
        "trace": journey,
    }))
    return 0 if ok else 1


def run_failure_path(checkpoint_dir: str = "") -> dict:
    """Cold-start + mid-run-restart schedule; returns the result dict
    (also usable in-process by tests/test_federation_chaos.py). The
    caller owns `checkpoint_dir` cleanup; "" runs without checkpointing
    (the window counter then restarts at 0 — seq monotonicity is only
    asserted when a checkpoint dir is given)."""
    from netobserv_tpu.config import AgentConfig
    from netobserv_tpu.exporter.federation import FederationDeltaSink
    from netobserv_tpu.exporter.tpu_sketch import TpuSketchExporter
    from netobserv_tpu.federation.service import FederationAggregatorService
    from netobserv_tpu.model.flow import FlowKey
    from netobserv_tpu.model.record import Record
    from netobserv_tpu.sketch.state import SketchConfig

    # reserve a FIXED port so the restarted aggregator comes back where
    # the agents' sinks are already pointed (ephemeral would re-roll it)
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    grpc_port = s.getsockname()[1]
    s.close()

    cfg = AgentConfig()
    cfg.sketch_cm_depth, cfg.sketch_cm_width = 2, 1024
    cfg.sketch_hll_precision, cfg.sketch_topk = 6, 32
    cfg.federation_listen_port = grpc_port
    cfg.federation_query_port = 0
    cfg.federation_window = 3600.0
    cfg.federation_checkpoint_dir = checkpoint_dir

    notes: list[str] = []
    torn: list[str] = []
    reports: list[dict] = []
    query_port = [0]          # mutable: restarts re-seat the ephemeral port
    stop_poll = threading.Event()
    seen: list[tuple[int, int]] = []   # (seq, window) per good response

    def poller() -> None:
        while not stop_poll.wait(0.02):
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{query_port[0]}"
                        "/federation/topk?n=5", timeout=5) as r:
                    obj = json.loads(r.read())
            except (urllib.error.URLError, OSError, ValueError):
                continue  # down/restarting or no window yet: that is fine
            # torn = structurally inconsistent, not merely unavailable
            # (window, seq) ordering: the WINDOW counter is the durable
            # one (checkpoint-restored across restarts); seq breaks ties
            # within one process incarnation
            if not {"window", "ts_ms", "seq", "topk"} <= obj.keys():
                torn.append(f"missing keys: {sorted(obj.keys())}")
            elif seen and checkpoint_dir \
                    and (obj["window"], obj["seq"]) < seen[-1]:
                # without a checkpoint the restarted window counter
                # legitimately restarts at 0 — only a CHECKPOINTED
                # aggregator owes the poller monotonicity
                torn.append(f"snapshot went backwards: {seen[-1]} -> "
                            f"({obj['window']}, {obj['seq']})")
            else:
                seen.append((obj["window"], obj["seq"]))

    def make_records(agent: int, salt: int, n: int = 128) -> list[Record]:
        now = time.time_ns()
        out = []
        for i in range(n):
            key = FlowKey.make(f"10.{agent}.{salt}.{i % 30}",
                               f"10.{agent}.200.{i % 10}",
                               1024 + i, 443, 6)
            out.append(Record(
                key=key, bytes_=1000 + i, packets=3, eth_protocol=0x0800,
                tcp_flags=0x12, direction=1, if_index=1, interface="eth0",
                time_flow_start_ns=now - 10**9, time_flow_end_ns=now))
        return out

    sketch_cfg = SketchConfig(cm_depth=2, cm_width=1024, hll_precision=6,
                              topk=32)
    agents, sinks = [], []
    for a in range(2):
        sink = FederationDeltaSink("127.0.0.1", grpc_port, retries=2,
                                   backoff_initial_s=0.05, timeout_s=5.0)
        exp = TpuSketchExporter(
            batch_size=128, window_s=3600.0, sketch_cfg=sketch_cfg,
            sink=lambda obj: None, delta_sink=sink,
            agent_id=f"chaos-agent-{a}")
        agents.append(exp)
        sinks.append(sink)

    def push_window(salt: int) -> None:
        for a, exp in enumerate(agents):
            exp.export_batch(make_records(a, salt))
            exp.flush()

    # window 0: NOTHING is listening — cold start; ladders exhaust, frames
    # drop (per-window snapshots: the next window supersedes them)
    push_window(salt=0)

    svc = FederationAggregatorService(cfg, sink=reports.append)
    svc.start()
    query_port[0] = svc.query_port
    threading.Thread(target=poller, daemon=True).start()

    # window 1: catch-up — the late aggregator now sees both agents
    push_window(salt=1)
    svc.aggregator.flush()
    status1 = svc.aggregator.status()

    # mid-run restart (graceful here; the SIGKILL flavor is pinned by
    # tests/test_federation_chaos.py against the checkpoint semantics)
    svc.shutdown()
    svc2 = FederationAggregatorService(cfg, sink=reports.append)
    svc2.start()
    query_port[0] = svc2.query_port

    # window 2: the restarted aggregator serves on, sinks reconnect
    push_window(salt=2)
    svc2.aggregator.flush()
    status2 = svc2.aggregator.status()
    time.sleep(0.2)          # a few poller rounds against the new snapshot
    stop_poll.set()

    ok = True
    if len(status1["agents"]) != 2 or len(status2["agents"]) != 2:
        ok, _ = False, notes.append("expected 2 agents registered in both "
                                    "aggregator incarnations")
    if torn:
        ok, _ = False, notes.append(f"torn snapshots: {torn[:3]}")
    if not seen:
        ok, _ = False, notes.append("poller never saw a published window")
    # published reports: window 1 (pre-restart) + windows from svc2; the
    # cold-start window 0 must be absent everywhere (it was dropped)
    if len(reports) < 2:
        ok, _ = False, notes.append(
            f"expected >=2 published windows, saw {len(reports)}")
    per_window = 2 * 128.0
    recs = [r["Records"] for r in reports]
    if any(r > per_window for r in recs):
        ok, _ = False, notes.append(
            f"a window over-counted: {recs} (> {per_window}/window means "
            "a dropped/cold-start frame leaked back in)")
    if checkpoint_dir and status2.get("last_published_window") is not None \
            and status1.get("last_published_window") is not None \
            and status2["last_published_window"] \
            <= status1["last_published_window"]:
        ok, _ = False, notes.append(
            "restored window counter did not advance past the "
            "pre-restart one")

    for exp in agents:
        exp.close()
    svc2.shutdown()
    return {
        "metric": "smoke_federation_chaos", "ok": ok, "notes": notes,
        "agents": sorted(status2["agents"]),
        "published_windows": recs,
        "poll_responses": len(seen),
        "torn_responses": len(torn),
        "last_published_window": status2.get("last_published_window"),
        "checkpointed": bool(checkpoint_dir),
    }


def main_failure_path() -> int:
    import tempfile
    with tempfile.TemporaryDirectory(prefix="fed-ckpt-") as d:
        out = run_failure_path(checkpoint_dir=d)
    print(json.dumps(out))
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main_failure_path() if "--failure-path" in sys.argv
             else main())
