#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the served path once, through the entry points `python -m netobserv_tpu`
uses with EXPORT=tpu-sketch — load_config -> TpuSketchExporter.from_config ->
FlowsAgent -> MapTracer drain -> export_evicted -> resident staging ring ->
superbatch ladder -> jitted ingest -> window roll -> report sink and /query/*
on the metrics server — at DEFAULT geometry, and checks the answers against an
exact numpy aggregation of the same records. Only the fetcher is substituted
(FakeFetcher: the synthetic one is a fixed 1,000-flow demo, not a load).

One process; needs a TPU and says so (never a CPU stand-in); no git, no
network. It measures nothing: it prints no rate and nothing under a device
metric's name. Set-up seconds are printed so a warm compile cache shows.

    python chip_smoke.py [--seed N] [--chips N]

Last line of stdout: {"ok": true, "device": {"platform", "kind", "count"}}.
Exit 0 only if every check passed.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import logging
import math
import os
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np


@dataclasses.dataclass(frozen=True)
class Sizes:
    """The run's scale — what a user would call real (ISSUE 21 item 1)."""

    universe: int = 1 << 20          # distinct 5-tuples, 4x a lane dictionary
    zipf_a: float = 1.2
    eviction: int = 100_000          # one drain of a busy node's map
    evictions_per_window: int = 10
    windows: int = 3
    window_s: float = 30.0           # SKETCH_WINDOW; must outlast one
    #                                  window's folds (checked, not assumed)


class Failed(Exception):
    """A check did not hold; the message says which."""


class Checks:
    """Every check prints as it is made; any failure fails the run."""

    def __init__(self):
        self.failures: list[str] = []

    def check(self, ok: bool, what: str) -> None:
        print(f"  [{'ok' if ok else 'FAIL'}] {what}", flush=True)
        if not ok:
            self.failures.append(what)


# --------------------------------------------------------------------------
# traffic, all from --seed, and its exact aggregation
# --------------------------------------------------------------------------

class Traffic:
    """Zipf(a) flows over a fixed universe of distinct 5-tuples with every
    feature lane filled, plus the exact per-window aggregation the sketch
    answers are held to."""

    def __init__(self, seed: int, sizes: Sizes):
        from netobserv_tpu.model import binfmt
        from netobserv_tpu.model.columnar import pack_key_words

        self.rng = np.random.default_rng(seed)
        self.sizes = sizes
        rng, n = self.rng, sizes.universe
        n_src = max(n // 8, 16)
        n_dst = max(n // 256, 16)
        src_pool = rng.choice(1 << 24, n_src, replace=False).astype(np.uint32)
        dst_pool = rng.choice(1 << 24, n_dst, replace=False).astype(np.uint32)
        self.src_of_key = rng.integers(0, n_src, n)
        keys = np.zeros(n, binfmt.FLOW_KEY_DTYPE)
        # ~3% native v6 keys, the rest v4-mapped (::ffff:a.b.c.d)
        v6 = rng.random(n) < 0.03
        for field, pool_idx, pool, net in (
                ("src_ip", self.src_of_key, src_pool, 10),
                ("dst_ip", rng.integers(0, n_dst, n), dst_pool, 172)):
            ip = np.zeros((n, 16), np.uint8)
            addr = pool[pool_idx]
            ip[:, 12] = net
            ip[:, 13] = (addr >> 16) & 0xFF
            ip[:, 14] = (addr >> 8) & 0xFF
            ip[:, 15] = addr & 0xFF
            ip[:, 10:12] = np.where(v6[:, None], 0, 0xFF)
            ip[v6, 0] = 0x20
            ip[v6, 1] = 0x01
            keys[field] = ip
        keys["src_port"] = rng.integers(1024, 65536, n)
        keys["dst_port"] = rng.choice(
            np.array([53, 80, 443, 5432, 6443, 8080, 9092], np.uint16), n)
        keys["proto"] = np.where(rng.random(n) < 0.8, 6, 17)
        words = pack_key_words(keys)
        uniq = np.unique(words.view([("w", "u4", words.shape[1])]))
        if len(uniq) != n:
            raise Failed(f"universe holds {len(uniq)} distinct keys, "
                         f"wanted {n} (seed collision: pick another seed)")
        self.keys = keys
        self.v6 = v6
        p = np.arange(1, n + 1, dtype=np.float64) ** -sizes.zipf_a
        self.cdf = np.cumsum(p / p.sum())

    def window(self, n_records: int) -> dict:
        """One window's records (events + aligned feature lanes) and the
        exact answers over them."""
        from netobserv_tpu.model import binfmt

        rng, n = self.rng, n_records
        idx = np.minimum(np.searchsorted(self.cdf, rng.random(n)),
                         self.sizes.universe - 1)
        ev = np.zeros(n, binfmt.FLOW_EVENT_DTYPE)
        ev["key"] = self.keys[idx]
        st = ev["stats"]
        byts = rng.integers(64, 9001, n)
        st["bytes"] = byts
        # ~0.5% elephants: more packets than a hot row's 11 bits hold, so
        # the full-width spill lane carries rows in steady state too
        st["packets"] = np.where(rng.random(n) < 0.005,
                                 rng.integers(2048, 4096, n),
                                 rng.integers(1, 12, n))
        tcp = ev["key"]["proto"] == 6
        st["tcp_flags"] = np.where(tcp, rng.integers(0, 1 << 9, n), 0)
        st["dscp"] = rng.integers(0, 64, n)
        st["eth_protocol"] = np.where(self.v6[idx], 0x86DD, 0x0800)
        st["if_index_first"] = 2
        now = time.monotonic_ns()
        st["first_seen_ns"] = now
        st["last_seen_ns"] = now + rng.integers(0, 5_000_000_000, n)
        extra = np.zeros(n, binfmt.EXTRA_REC_DTYPE)
        extra["rtt_ns"] = np.where(rng.random(n) < 0.30,
                                   rng.integers(1, 5_000, n) * 1000, 0)
        dns = np.zeros(n, binfmt.DNS_REC_DTYPE)
        dns["latency_ns"] = np.where(rng.random(n) < 0.05,
                                     rng.integers(1, 2_000, n) * 1000, 0)
        drops = np.zeros(n, binfmt.DROPS_REC_DTYPE)
        dropped = rng.random(n) < 0.02
        drops["bytes"] = np.where(dropped, rng.integers(1, 1500, n), 0)
        drops["packets"] = np.where(dropped, rng.integers(1, 4, n), 0)
        drops["latest_cause"] = np.where(dropped, rng.integers(2, 80, n), 0)
        xlat = np.zeros(n, binfmt.XLAT_REC_DTYPE)
        nat = rng.random(n) < 0.03
        xlat["src_ip"][nat] = ev["key"]["src_ip"][nat]
        xlat["dst_ip"][nat] = ev["key"]["dst_ip"][nat]
        quic = np.zeros(n, binfmt.QUIC_REC_DTYPE)
        is_quic = (~tcp) & (rng.random(n) < 0.10)
        quic["version"] = np.where(is_quic, 1, 0)
        quic["seen_long_hdr"] = is_quic

        exact = np.bincount(idx, weights=byts, minlength=self.sizes.universe)
        return {
            "n": n, "events": ev,
            "feats": dict(extra=extra, dns=dns, drops=drops, xlat=xlat,
                          quic=quic),
            "exact_bytes": exact,
            # a pool address appears as a v4-mapped and as a v6 source
            "distinct_src": len(np.unique(
                self.src_of_key[idx] * 2 + self.v6[idx])),
            "nat": int(nat.sum()), "quic": int(is_quic.sum()),
        }

    def five_tuple(self, i: int) -> tuple:
        """Key `i` as the report renders it."""
        from netobserv_tpu.model.flow import ip_from_16

        k = self.keys[i]
        return (ip_from_16(k["src_ip"].tobytes()),
                ip_from_16(k["dst_ip"].tobytes()),
                int(k["src_port"]), int(k["dst_port"]), int(k["proto"]))


def reported_keys(entries: list[dict]) -> set:
    """Heavy-hitter entries (report or /query/topk) as 5-tuples."""
    return {(e["SrcAddr"], e["DstAddr"], e["SrcPort"], e["DstPort"],
             e["Proto"]) for e in entries}


def evictions_of(win: dict, size: int):
    from netobserv_tpu.datapath.fetcher import EvictedFlows

    for lo in range(0, win["n"], size):
        hi = min(lo + size, win["n"])
        yield EvictedFlows(win["events"][lo:hi],
                           **{k: v[lo:hi] for k, v in win["feats"].items()})


# --------------------------------------------------------------------------
# the agent under test and its normal outputs
# --------------------------------------------------------------------------

def http_json(port: int, path: str):
    try:
        with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                    timeout=30) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


def scrape(port: int, prefix: str) -> dict:
    """/metrics as {(name, (label values...)): value}, prefix stripped."""
    from prometheus_client.parser import text_string_to_metric_families

    with urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics",
                                timeout=30) as r:
        text = r.read().decode()
    out = {}
    for fam in text_string_to_metric_families(text):
        for s in fam.samples:
            if s.name.startswith(prefix):
                out[(s.name[len(prefix):],
                     tuple(v for _, v in sorted(s.labels.items())))] = s.value
    return out


def wait_for(pred, timeout_s: float, what: str, poll_s: float = 0.1):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        got = pred()
        if got:
            return got
        time.sleep(poll_s)
    raise Failed(f"timed out after {timeout_s:.0f}s waiting for {what}")


def base_env(sizes: Sizes) -> dict:
    """The agent's environment: EXPORT=tpu-sketch and nothing that touches
    geometry — every sketch knob stays at its config.py default."""
    return {
        "EXPORT": "tpu-sketch", "AGENT_IP": "127.0.0.1",
        "SKETCH_WINDOW": f"{sizes.window_s}s",
        # drain the injected evictions promptly (the default 5 s cadence
        # would spend most of a window waiting on the drain timer)
        "CACHE_ACTIVE_TIMEOUT": "100ms",
        "LOG_LEVEL": "warning",
    }


@contextlib.contextmanager
def environ(env: dict):
    """`env` over os.environ for the block (the agent is env-configured)."""
    saved = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        yield
    finally:
        for k, v in saved.items():
            if v is None:
                del os.environ[k]
            else:
                os.environ[k] = v


class AgentUnderTest:
    """A FlowsAgent built the way `python -m netobserv_tpu` builds it from the
    environment, with the fetcher substituted and the sink observed."""

    def __init__(self):
        from netobserv_tpu.agent.agent import FlowsAgent
        from netobserv_tpu.config import load_config
        from netobserv_tpu.datapath.fetcher import FakeFetcher
        from netobserv_tpu.exporter.tpu_sketch import TpuSketchExporter
        from netobserv_tpu.metrics.registry import Metrics, MetricsSettings
        from netobserv_tpu.metrics.server import start_metrics_server

        self.cfg = cfg = load_config()
        cfg.validate()
        self.reports: list[dict] = []
        self.metrics = Metrics(MetricsSettings(prefix=cfg.metrics_prefix,
                                               level=cfg.metrics_level))
        self.t0 = time.monotonic()
        self.exporter = TpuSketchExporter.from_config(
            cfg, metrics=self.metrics, sink=self.reports.append)
        self.fetcher = FakeFetcher()
        self.agent = FlowsAgent(cfg, self.fetcher, self.exporter,
                                metrics=self.metrics,
                                agent_ip=cfg.agent_ip or "127.0.0.1")
        self.srv = start_metrics_server(
            self.metrics.registry, "127.0.0.1", 0,
            health_source=self.agent.health_snapshot,
            query_routes=self.agent.query_routes)
        self.port = self.srv.server_address[1]
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self.agent.run,
                                        args=(self._stop,), daemon=True)
        self._thread.start()

    def counters(self) -> dict:
        return scrape(self.port, self.cfg.metrics_prefix)

    def folded(self) -> int:
        return int(self.counters().get(("sketch_records_total", ()), 0))

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=120)
        self.srv.shutdown()
        if self._thread.is_alive():
            raise Failed("agent did not stop within 120s")


def wait_ladder_warm(aut: AgentUnderTest, timeout_s: float) -> dict:
    def warm():
        _, st = http_json(aut.port, "/query/status")
        sb = st.get("superbatch")
        return sb if sb and sb["warm"] == sb["ladder"] else None
    return wait_for(warm, timeout_s, "every superbatch ladder entry to warm "
                    "(one that fails to compile logs an error by name)", 0.5)


def grade_window(c: Checks, traffic: Traffic, win: dict, report: dict,
                 port: int) -> dict:
    """One closed window's published answers against the exact ones."""
    w = report["Window"]
    c.check(report["Records"] == win["n"],
            f"window {w}: records published {report['Records']:.0f} == "
            f"injected {win['n']}")
    c.check(report["QuicRecords"] == win["quic"]
            and report["NatRecords"] == win["nat"],
            f"window {w}: QUIC/NAT marker counts "
            f"{report['QuicRecords']:.0f}/{report['NatRecords']:.0f} == "
            f"{win['quic']}/{win['nat']}")

    exact = win["exact_bytes"]
    order = np.argsort(-exact)
    code, top = http_json(port, "/query/topk?n=1024")
    c.check(code == 200 and top.get("window") == w,
            f"window {w}: /query/topk answers for this window")
    got = reported_keys(top.get("topk", []))
    want = [traffic.five_tuple(int(i)) for i in order[:100]]
    recall = sum(k in got for k in want) / 100
    c.check(recall >= 0.99, f"window {w}: recall@100 by bytes {recall:.2f} "
            ">= 0.99")
    sunk = report["HeavyHitters"]
    c.check(bool(sunk) and top.get("topk", [])[:len(sunk)] == sunk,
            f"window {w}: the sink's report ({len(sunk)} heavy hitters) and "
            "the head of /query/topk agree")

    code, card = http_json(port, "/query/cardinality")
    est = card.get("distinct_src_estimate", 0.0)
    hll_err = abs(est - win["distinct_src"]) / win["distinct_src"]
    c.check(code == 200 and card.get("records") == win["n"]
            and hll_err <= 0.03,
            f"window {w}: distinct sources {est:.0f} vs exact "
            f"{win['distinct_src']} (error {hll_err:.2%} <= 3%)")

    # 20 probe keys whose exact bytes the f32 planes hold exactly (< 2^24),
    # spread from the heaviest such key down to the tail
    present = order[(exact[order] > 0) & (exact[order] < 2 ** 24)]
    probes = present[np.unique(np.geomspace(
        1, len(present), 20).astype(int) - 1)]
    low = within = 0
    conf = 1.0
    for i in probes:
        src, dst, sp, dp, proto = traffic.five_tuple(int(i))
        code, f = http_json(
            port, f"/query/frequency?src={src}&dst={dst}&src_port={sp}"
                  f"&dst_port={dp}&proto={proto}")
        if code != 200 or f.get("window") != w:
            continue
        conf = f["confidence"]
        low += f["est_bytes"] >= exact[i]
        within += f["est_bytes"] <= exact[i] + f["overestimate_bound_bytes"]
    n = len(probes)
    c.check(low == n, f"window {w}: CM estimate >= exact bytes for "
            f"{low}/{n} probe keys")
    c.check(within >= math.floor(conf * n),
            f"window {w}: {within}/{n} probes inside the route's own error "
            f"bar (stated confidence {conf:.3f})")
    return {"window": w, "recall_at_100": recall, "hll_error": hll_err}


def mesh_partials(c: Checks, exporter, n_devices: int, w: int) -> None:
    """Before the roll merges them: every state leaf lives in shards on all
    devices, and every device has folded records of its own. The per-device
    partials exist nowhere but the exporter's live state, hence the reach."""
    import jax

    with exporter._lock:
        state = exporter._state
        leaves = jax.tree.leaves(state)
        spread = min(len({s.device for s in leaf.addressable_shards})
                     for leaf in leaves)
        partials = np.asarray(state.total_records).reshape(-1)
    c.check(spread == n_devices,
            f"graded window #{w}: every state leaf has shards on {n_devices} "
            f"distinct devices (least spread: {spread})")
    c.check(len(partials) == n_devices and (partials > 0).all(),
            f"graded window #{w}: per-device partial record counts "
            f"{partials.astype(int).tolist()} all > 0 before the roll")


COLLECTIVES = ("all-reduce", "all-gather", "collective-permute",
               "reduce-scatter", "all-to-all")


def mosaic_check(c: Checks, w, lowered) -> None:
    """Five Mosaic kernels in the executable `w` last compiled: countmin
    update_two, hll update, topk reduce x2 (SLOT_ROUNDS), signal update."""
    n = lowered.as_text().count("tpu_custom_call")
    c.check(n == 5, f"executable {w.name} ({w.calls} calls): {n} Mosaic "
            "custom calls == 5")


def executables_proof(c: Checks, n_devices: int) -> None:
    """What the dispatched executables hold, from the retrace registry's
    record of each one's last compile: five Mosaic kernels in every ingest
    entry; on a mesh, no collective in the ingest and some in the roll."""
    from netobserv_tpu.utils import retrace

    proven = []
    for w in retrace.watched():
        if not w.calls or not w.last_avals:
            continue
        ingest = "ingest" in w.name
        lowered = w.lower(*w.last_avals)
        if ingest:
            proven.append(w.name)
            mosaic_check(c, w, lowered)
        if n_devices > 1:
            hlo = lowered.compile().as_text()
            found = [k for k in COLLECTIVES if k in hlo]
            if ingest:
                c.check(not found, f"executable {w.name}: steady-state "
                        f"HLO holds no collective (found {found})")
            else:
                c.check(bool(found), f"executable {w.name}: the roll's "
                        f"HLO merges over the mesh ({found})")
    c.check(any(n.endswith("_x4") for n in proven)
            and any(n.endswith("_x1") for n in proven),
            f"the dispatched ladder entries were among them: {proven}")


def main_leg(c: Checks, seed: int, sizes: Sizes, n_devices: int,
             debug_port: int) -> dict:
    print(f"main leg: agent -> tpu-sketch, default geometry, "
          f"{sizes.windows} windows x {sizes.evictions_per_window} evictions "
          f"x {sizes.eviction} records", flush=True)
    traffic = Traffic(seed, sizes)
    aut = AgentUnderTest()
    exporter = aut.exporter
    path = ("single" if not exporter._distributed else
            "mesh " + json.dumps({k: int(v) for k, v
                                  in exporter._mesh.shape.items()}))
    print(f"path: {path}", flush=True)
    try:
        sb = wait_ladder_warm(aut, 900)
        ladder_warm_s = time.monotonic() - aut.t0
        c.check(sb["ladder"] == [1, 2, 4],
                f"ladder entries {sb['ladder']} all warm")

        # warm-up window: a sub-batch eviction folds at the window close, so
        # the x1 entry and the roll compile before the graded windows
        warm = traffic.window(100)
        injected = warm["n"]
        for e in evictions_of(warm, warm["n"]):
            aut.fetcher.inject_eviction(e)
        wait_for(lambda: any(r["Records"] for r in aut.reports),
                 600 + sizes.window_s, "the warm-up window's report", 0.2)
        setup_s = time.monotonic() - aut.t0
        print(f"set-up: {ladder_warm_s:.1f}s to a warm ladder, "
              f"{setup_s:.1f}s to the first published window", flush=True)
        _, ex0 = http_json(debug_port, "/debug/executables")
        retraces0 = ex0["retraces_total"]

        grades = []
        n_win = sizes.eviction * sizes.evictions_per_window
        win, closed = traffic.window(n_win), None
        seen = len(aut.reports)
        wait_for(lambda: len(aut.reports) > seen, sizes.window_s + 60,
                 "a window boundary", 0.02)
        for i in range(sizes.windows):
            # injected right at a boundary (the previous window's report):
            # the whole period is this window's to fold in
            seen, t_inject = len(aut.reports), time.monotonic()
            for e in evictions_of(win, sizes.eviction):
                aut.fetcher.inject_eviction(e)
            injected += win["n"]
            if closed is not None:
                # the window that just closed stays published until the
                # next roll: grade it while this one folds
                grades.append(grade_window(c, traffic, *closed, aut.port))
            nxt = traffic.window(n_win) if i + 1 < sizes.windows else None
            # all of it folded but the sub-batch tail the close will fold
            wait_for(lambda: aut.folded() > injected - aut.cfg.sketch_batch_size
                     or len(aut.reports) > seen,
                     sizes.window_s + 60, "the window's records to fold", 0.25)
            if len(aut.reports) > seen:
                raise Failed(
                    f"the {sizes.window_s:.0f}s window closed while its "
                    "records were still folding: membership is ambiguous "
                    "(host too slow for this window length)")
            print(f"graded window #{i + 1}: folded "
                  f"{time.monotonic() - t_inject:.1f}s into its "
                  f"{sizes.window_s:.0f}s period", flush=True)
            if n_devices > 1:
                mesh_partials(c, exporter, n_devices, i + 1)
            wait_for(lambda: len(aut.reports) > seen, sizes.window_s + 60,
                     "the window timer to close the window", 0.02)
            oracle = {k: v for k, v in win.items()
                      if k not in ("events", "feats")}
            closed, win = (oracle, aut.reports[seen]), nxt
        grades.append(grade_window(c, traffic, *closed, aut.port))

        counters = aut.counters()
        _, status = http_json(aut.port, "/query/status")
        _, ex = http_json(debug_port, "/debug/executables")
        _, health = http_json(aut.port, "/healthz")
    finally:
        aut.stop()

    published = sum(r["Records"] for r in aut.reports)
    c.check(published == injected,
            f"{len(grades)} graded windows; records published over all "
            f"{len(aut.reports)} windows {published:.0f} == injected "
            f"{injected}, exactly")

    def counter(name, *labels):
        return int(counters.get((name, labels), 0))
    errors = {k: v for k, v in counters.items()
              if k[0] == "errors_total" and v}
    c.check(counter("sketch_ingest_errors_total") == 0 and not errors
            and counter("sketch_reports_shed_total") == 0,
            f"0 ingest errors, 0 roll/publish errors, 0 reports shed "
            f"(errors_total: {errors or 'none'})")
    c.check(ex["retraces_total"] == retraces0 == 0,
            f"0 retraces after warm-up (total {ex['retraces_total']})")
    folds = status["superbatch"]["folds"]
    c.check(counter("sketch_superbatch_folds_total", "4") > 0,
            f"superbatch folds dispatched by k: {folds}")
    c.check(health.get("status") == "Started" and not health["degraded"],
            f"/healthz: {health.get('status')}, degraded="
            f"{health.get('degraded')}")
    print(f"feed: {counter('sketch_resident_continuations_total')} "
          f"continuation chunks, "
          f"{counter('sketch_resident_spill_rows_total')} spill rows, "
          f"{counter('sketch_resident_dict_epochs_total')} dictionary "
          f"epochs, {counter('sketch_direct_fold_rows_total')} rows folded "
          "from eviction views", flush=True)
    c.check(counter("sketch_resident_continuations_total") > 0
            and counter("sketch_resident_spill_rows_total") > 0,
            "misses drove the new-key lane: continuation chunks and spill "
            "rows both happened")
    executables_proof(c, n_devices)
    return {"path": path, "setup_s": setup_s, "ladder_warm_s": ladder_warm_s,
            "records": injected, "windows": len(aut.reports),
            "grades": grades}


# --------------------------------------------------------------------------
# what compiled: Pallas against scatter, on the chip
# --------------------------------------------------------------------------

def pallas_vs_scatter(c: Checks, seed: int) -> None:
    """The same seeded batches through make_ingest_fn(use_pallas=True) and
    (use_pallas=False): every leaf of the state must be EQUAL. Sums are
    integer-valued and kept below 2^24, where f32 addition does not depend
    on order — so equal means equal, and a reduced-precision pass in the MXU
    shows here."""
    import jax

    from netobserv_tpu.sketch import state as sk

    rng = np.random.default_rng(seed)
    b, n_keys = 8192, 50_000
    universe = rng.integers(0, 2 ** 32, (n_keys, sk.KEY_WORDS),
                            dtype=np.uint32)
    cfg = sk.SketchConfig()
    folds = {up: (sk.make_ingest_fn(donate=False, use_pallas=up),
                  sk.init_state(cfg)) for up in (True, False)}
    for _ in range(4):
        drop_b = np.where(rng.random(b) < 0.02,
                          rng.integers(1, 1500, b), 0).astype(np.int32)
        batch = {
            "keys": universe[np.minimum(rng.zipf(1.2, b) - 1, n_keys - 1)],
            "bytes": rng.integers(64, 1500, b).astype(np.float32),
            "packets": rng.integers(1, 12, b).astype(np.int32),
            "rtt_us": rng.integers(0, 5000, b).astype(np.int32),
            "dns_latency_us": rng.integers(0, 2000, b).astype(np.int32),
            "sampling": np.zeros(b, np.int32),
            "valid": rng.random(b) < 0.97,
            "tcp_flags": rng.integers(0, 1 << 9, b).astype(np.int32),
            "dscp": rng.integers(0, 64, b).astype(np.int32),
            "markers": rng.integers(0, 4, b).astype(np.int32),
            "drop_bytes": drop_b,
            "drop_packets": (drop_b > 0).astype(np.int32),
            "drop_cause": np.where(drop_b > 0, rng.integers(2, 80, b),
                                   0).astype(np.int32),
        }
        folds = {up: (fn, fn(s, batch)) for up, (fn, s) in folds.items()}
    got, want = (jax.tree.map(np.asarray, folds[up][1])
                 for up in (True, False))
    peak = max(float(x.max()) for x in jax.tree.leaves(want)
               if x.ndim and x.dtype == np.float32)
    c.check(peak < 2 ** 24, f"scatter reference sums stay below 2^24 "
            f"(peak {peak:.0f}): order cannot matter")
    unequal = [name for name in want._fields if any(
        not np.array_equal(g, w) for g, w in zip(
            jax.tree.leaves(getattr(got, name)),
            jax.tree.leaves(getattr(want, name))))]
    c.check(not unequal, "Pallas and scatter ingests leave EQUAL state on "
            f"the chip: CM planes, HLL registers, slot table, signal planes "
            f"(unequal: {unequal or 'none'})")


# --------------------------------------------------------------------------
# the compile cache, and the short legs beside the main one
# --------------------------------------------------------------------------

class CacheHits:
    """Persistent-compilation-cache traffic, from jax.monitoring."""

    def __init__(self):
        import jax.monitoring

        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **kwargs) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def second_construction(c: Checks, cache: CacheHits, first_s: float) -> None:
    """A second exporter in this process recompiles nothing the cache
    holds: the ladder warms from the directory the first one filled."""
    from netobserv_tpu.config import load_config
    from netobserv_tpu.exporter.tpu_sketch import TpuSketchExporter

    hits0 = cache.hits
    t0 = time.monotonic()
    exporter = TpuSketchExporter.from_config(load_config(),
                                             sink=lambda report: None)
    try:
        def warm():
            sb = exporter.query_status()["superbatch"]
            return sb["warm"] == sb["ladder"]
        wait_for(warm, 900, "the second exporter's ladder to warm", 0.2)
        dt = time.monotonic() - t0
    finally:
        exporter.close()
    hits = cache.hits - hits0
    c.check(hits > 0,
            f"second construction hit the compile cache {hits} times: warm "
            f"ladder in {dt:.1f}s against {first_s:.1f}s the first time "
            f"({'shorter' if dt < first_s else 'NOT shorter'})")


def extra_leg(c: Checks, name: str, env: dict, seed: int, sizes: Sizes,
              debug_port: int, expect: dict, entries: tuple = ()):
    """One short leg beside the main one: the exporter as `env` configures
    it, its ladder warmed, one eviction folded, one window rolled, the total
    right, zero errors, five Mosaic kernels in each ingest executable the
    leg itself built (`entries`: name suffixes that must be among them), and
    /debug/executables naming the fold form that ran (`expect`). Returns
    the leg's Metrics."""
    from netobserv_tpu.config import load_config
    from netobserv_tpu.exporter.tpu_sketch import TpuSketchExporter
    from netobserv_tpu.metrics.registry import Metrics
    from netobserv_tpu.utils import retrace

    print(f"{name} leg: {env}", flush=True)
    reports: list[dict] = []
    metrics = Metrics()
    before = {id(w) for w in retrace.watched()}
    # a long window: the leg's one roll is its flush
    with environ({**env, "SKETCH_WINDOW": "10m"}):
        cfg = load_config()
        cfg.validate()
        exporter = TpuSketchExporter.from_config(cfg, metrics=metrics,
                                                 sink=reports.append)
    try:
        def warm():
            sb = exporter.query_status().get("superbatch")
            return sb is None or sb["warm"] == sb["ladder"]
        # un-warmed entries are unselectable: fold only once all can serve
        wait_for(warm, 900, f"the {name} leg's ladder to warm", 0.2)
        traffic = Traffic(seed, dataclasses.replace(
            sizes, universe=min(sizes.universe, 1 << 16)))
        win = traffic.window(sizes.eviction)
        for e in evictions_of(win, win["n"]):
            exporter.export_evicted(e)
        exporter.flush()
        _, ex = http_json(debug_port, "/debug/executables")
        proven = []
        for w in retrace.watched():
            if id(w) not in before and w.calls and "ingest" in w.name:
                proven.append(w.name)
                mosaic_check(c, w, w.lower(*w.last_avals))
        c.check(all(any(n.endswith(sfx) for n in proven) for sfx in entries),
                f"{name}: ingest executables built and called: {proven}")
    finally:
        exporter.close()
    total = sum(r["Records"] for r in reports)
    c.check(total == win["n"],
            f"{name}: {len(reports)} report(s), records {total:.0f} == "
            f"folded {win['n']}")
    got = reported_keys([e for r in reports for e in r["HeavyHitters"]])
    top10 = [traffic.five_tuple(int(i))
             for i in np.argsort(-win["exact_bytes"])[:10]]
    c.check(all(k in got for k in top10),
            f"{name}: the exact top-10 keys by bytes are all reported")
    errs = int(metrics.sketch_ingest_errors_total._value.get())
    c.check(errs == 0, f"{name}: 0 ingest errors")
    if expect:
        rows = [e for e in ex["executables"] if e["calls"] and all(
            e.get(k) == v for k, v in expect.items())]
        c.check(bool(rows), f"{name}: /debug/executables shows "
                f"{[(e['fn'], e['calls']) for e in rows]} with {expect}")
    return metrics


# --------------------------------------------------------------------------

def legs(c: Checks, seed: int, sizes: Sizes, n_devices: int,
         debug_port: int) -> None:
    # the dictionary-reset path, on the wide default path: at the default
    # 2^18 slots a lane dictionary never fills under the main leg's load
    # (0 epochs there), so this leg shrinks the key table — and only that
    metrics = extra_leg(c, "dictionary-reset", {
        "SKETCH_RESIDENT_SLOTS": "256"}, seed + 2, sizes, debug_port, {},
        ("_x1", "_x2", "_x4"))
    epochs = int(metrics.sketch_resident_dict_epochs_total._value.get())
    c.check(epochs > 0, f"dictionary-reset: {epochs} dictionary epoch "
            "resets on the 256-slot table (the reset path ran on the chip)")
    if n_devices > 1:
        print(f"tiered / tenants legs: not run on {n_devices} devices "
              "(single-device planes; they run on the one-chip machine)",
              flush=True)
        return
    # what a user gets from each knob ALONE: default slots, default ladder
    extra_leg(c, "tiered", {"SKETCH_TIERED": "true"}, seed + 3, sizes,
              debug_port, {"tiered": "decode"}, ("_x1", "_x2", "_x4"))
    extra_leg(c, "tenants", {"SKETCH_TENANTS": "4"}, seed + 4, sizes,
              debug_port, {"tenants": 4, "fn": "tenant_ingest"},
              ("tenant_ingest",))


def run(seed: int, sizes: Sizes, n_devices: int) -> Checks:
    """Every phase, given devices that passed the platform check."""
    import jax

    from netobserv_tpu.datapath import flowpack
    from netobserv_tpu.server import start_debug_server
    from netobserv_tpu.utils.platform import enable_compile_cache

    c = Checks()
    cache_dir = enable_compile_cache()
    n_cached = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    print(f"compile cache: {cache_dir} ({n_cached} entries at start — "
          f"{'warm' if n_cached else 'cold'})", flush=True)
    cache = CacheHits()

    # built from the files git would commit: libflowpack.so is ignored, so
    # compile it from flowpack.cc before anything loads its symbols
    c.check(flowpack.build_native(force=True) and flowpack.native_available(),
            "libflowpack.so rebuilt from flowpack.cc with g++ and loaded")

    debug = start_debug_server("127.0.0.1:0")
    debug_port = debug.server_address[1]
    try:
        with environ(base_env(sizes)):
            main = main_leg(c, seed, sizes, n_devices, debug_port)
            second_construction(c, cache, main["ladder_warm_s"])
            legs(c, seed, sizes, n_devices, debug_port)
        print("pallas-vs-scatter leg", flush=True)
        pallas_vs_scatter(c, seed + 1)
    finally:
        debug.shutdown()

    g = main["grades"]
    print(f"summary: path {main['path']}; set-up {main['setup_s']:.1f}s "
          f"({'warm' if n_cached else 'cold'} cache, {cache.hits} hits / "
          f"{cache.misses} misses); {main['records']} records in "
          f"{main['windows']} windows; recall@100 "
          f"{[round(x['recall_at_100'], 2) for x in g]}; HLL error "
          f"{[round(x['hll_error'], 4) for x in g]}; jax {jax.__version__}",
          flush=True)
    return c


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=2026)
    ap.add_argument("--chips", type=int, default=0,
                    help="assert this many devices (0: whatever JAX finds)")
    args = ap.parse_args()

    # first act: find the chip. A CPU is never a stand-in
    import jax
    devices = jax.devices()
    dev = {"platform": devices[0].platform,
           "kind": devices[0].device_kind, "count": len(devices)}
    if dev["platform"] != "tpu":
        print(f"chip_smoke: no TPU found (jax platform {dev['platform']!r}, "
              f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}); this "
              "script stands for the system and runs only on the chip",
              file=sys.stderr)
        return 2
    if args.chips and dev["count"] != args.chips:
        print(f"chip_smoke: expected {args.chips} devices, JAX found "
              f"{dev['count']}", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    try:
        import netobserv_tpu  # noqa: F401
    except ImportError as exc:
        print(f"chip_smoke: the netobserv_tpu package is not beside this "
              f"script ({exc})", file=sys.stderr)
        return 2
    logging.basicConfig(
        level=logging.WARNING, stream=sys.stderr,
        format="%(asctime)s %(levelname)s %(name)s %(message)s")
    print(f"device: platform {dev['platform']}, kind {dev['kind']}, "
          f"count {dev['count']}", flush=True)
    try:
        checks = run(args.seed, Sizes(), dev["count"])
        failures = checks.failures
    except Failed as exc:
        failures = [str(exc)]
    ok = not failures
    for f in failures:
        print(f"FAILED: {f}", flush=True)
    print(json.dumps({"ok": ok, "device": dev}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
