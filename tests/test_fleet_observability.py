"""Fleet observability plane (ISSUE 18): cross-process trace contexts over
the delta wire, the /federation/fleet telemetry rollup, and the
per-executable device-accounting registry behind /debug/executables.

Pins, per plane:

- TraceContext keeps the tracing zero-cost bar: with TRACE_SAMPLE unset,
  context_of is one attribute check answering None (nothing serialized)
  and continue_trace is the shared NULL_TRACE — no allocation, no lock.
  Enabled, a continued trace ADOPTS the origin's id verbatim and the
  recorder correlates both sides by that one string.
- The aggregator continues a sampled frame's trace through ingest child
  spans and fans the roll/publish spans to every parked agent trace at
  window close; /federation/fleet renders only the seq-stamped snapshot
  the timer (or flush) publishes — whole-dict swaps, torn reads
  impossible, agent eviction drops the row at the next rebuild.
- The retrace watchdog's wrapper IS the accounting registry: dispatch
  count + wall seconds, compile seconds, last abstract-shape signature
  and donated-bytes estimate per watched jit — refreshed on every
  compile, zero new jitted entries, zero post-warmup retraces from the
  accounting itself.
"""

from __future__ import annotations

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import tests.conftest  # noqa: F401  (forces the CPU backend)

from netobserv_tpu.federation import delta as fdelta
from netobserv_tpu.federation.aggregator import FederationAggregator
from netobserv_tpu.metrics.registry import Metrics
from netobserv_tpu.sketch import state as sk
from netobserv_tpu.utils import retrace, tracing

CFG = sk.SketchConfig(cm_depth=2, cm_width=256, hll_precision=6,
                      perdst_buckets=16, perdst_precision=4,
                      persrc_buckets=16, persrc_precision=4,
                      topk=16, hist_buckets=16, ewma_buckets=16)
DIMS = {"cm_depth": 2, "cm_width": 256, "hll_precision": 6, "topk": 16,
        "ewma_buckets": 16}


@pytest.fixture(autouse=True)
def _reset_tracing():
    yield
    tracing.configure(sample=0.0)
    tracing.recorder.clear()
    tracing.set_metrics(None)


def _tables() -> dict:
    rng = np.random.default_rng(3)
    s = sk.init_state(CFG)
    n = 32
    drop_b = np.where(rng.random(n) < 0.3,
                      rng.integers(1, 500, n), 0).astype(np.int32)
    arrays = {
        "keys": rng.integers(0, 2**32, (n, 10), dtype=np.uint32),
        "bytes": rng.integers(1, 1000, n).astype(np.float32),
        "packets": rng.integers(1, 5, n).astype(np.int32),
        "rtt_us": rng.integers(1, 5000, n).astype(np.int32),
        "dns_latency_us": rng.integers(0, 100, n).astype(np.int32),
        "sampling": np.zeros(n, np.int32),
        "valid": np.ones(n, np.bool_),
        "tcp_flags": rng.integers(0, 1 << 9, n).astype(np.int32),
        "dscp": rng.integers(0, 64, n).astype(np.int32),
        "markers": rng.integers(0, 4, n).astype(np.int32),
        "drop_bytes": drop_b,
        "drop_packets": (drop_b > 0).astype(np.int32),
        "drop_cause": np.where(drop_b > 0, 2, 0).astype(np.int32),
    }
    s = sk.ingest(s, arrays)
    roll = sk.make_roll_fn(CFG, with_tables=True)
    _, _, tables = roll(s)
    return {k: np.asarray(v) for k, v in tables.items()}


def _frame(tables, agent="agent-0", window=0, seq=0, uuid="u0",
           trace_ctx=None, telemetry=None) -> bytes:
    return fdelta.encode_frame(
        tables, agent_id=agent, window=window, ts_ms=1234, dims=DIMS,
        window_seq=seq, frame_uuid=uuid, agent_epoch=7,
        trace_ctx=trace_ctx, telemetry=telemetry)


# --- TraceContext: the zero-cost + adoption contract -----------------------

class TestTraceContext:
    def test_disabled_context_of_null_trace_is_none(self):
        tracing.configure(sample=0.0)
        assert tracing.start_trace("window") is tracing.NULL_TRACE
        assert tracing.context_of(tracing.NULL_TRACE) is None

    def test_disabled_continue_trace_is_null(self):
        """A receiver with tracing off pays one check and records nothing,
        even for a sampled propagated context."""
        tracing.configure(sample=0.0)
        ctx = tracing.TraceContext("aabb0011", "window@a", True)
        assert tracing.continue_trace(ctx) is tracing.NULL_TRACE

    def test_absent_unsampled_or_idless_context_is_null(self):
        tracing.configure(sample=1.0)
        assert tracing.continue_trace(None) is tracing.NULL_TRACE
        assert tracing.continue_trace(
            tracing.TraceContext("aabb", "w", False)) is tracing.NULL_TRACE
        assert tracing.continue_trace(
            tracing.TraceContext("", "w", True)) is tracing.NULL_TRACE

    def test_continue_adopts_origin_id_and_correlates(self):
        tracing.configure(sample=1.0, capacity=8)
        t = tracing.start_trace("window")
        ctx = tracing.context_of(t, origin="window@agent-7")
        assert ctx is not None and ctx.sampled
        assert ctx.trace_id == t.trace_id
        cont = tracing.continue_trace(ctx, "federation_delta")
        assert cont.trace_id == t.trace_id
        assert cont.origin == "window@agent-7"
        with t.stage("delta_push"):
            pass
        with cont.stage("delta_validate"):
            pass
        t.finish()
        cont.finish()
        both = tracing.snapshot(trace_id=t.trace_id)
        assert sorted(e["kind"] for e in both) == ["federation_delta",
                                                  "window"]
        assert {e["trace_id"] for e in both} == {t.trace_id}

    def test_local_ids_are_salted_unique(self):
        """Two locally-born traces never share an id, and ids carry the
        process salt (cross-process correlation must not alias)."""
        tracing.configure(sample=1.0)
        a, b = tracing.start_trace("batch"), tracing.start_trace("batch")
        assert a.trace_id != b.trace_id
        assert len(a.trace_id) == 24  # 16 salt + 8 counter hex chars

    def test_group_collapses_and_fans_out(self):
        tracing.configure(sample=1.0, capacity=8)
        assert tracing.group() is tracing.NULL_TRACE
        assert tracing.group(tracing.NULL_TRACE) is tracing.NULL_TRACE
        t = tracing.start_trace("window")
        assert tracing.group(tracing.NULL_TRACE, t) is t
        u = tracing.start_trace("window")
        g = tracing.group(t, u)
        with g.stage("roll_dispatch"):
            pass
        g.finish()
        for member in (t, u):
            entry = tracing.snapshot(trace_id=member.trace_id)[0]
            assert entry["stages"][0]["stage"] == "roll_dispatch"

    def test_snapshot_limit_caps_after_filter(self):
        tracing.configure(sample=1.0, capacity=8)
        for _ in range(4):
            t = tracing.start_trace("batch")
            with t.stage("s"):
                pass
            t.finish()
        assert len(tracing.snapshot()) == 4
        assert len(tracing.snapshot(limit=2)) == 2
        assert tracing.snapshot(trace_id="nope") == []


# --- aggregator: continued traces + fleet rollup ---------------------------

class TestAggregatorFleet:
    def _agg(self, **kw):
        return FederationAggregator(sketch_cfg=CFG, window_s=3600,
                                    sink=lambda obj: None, **kw)

    def test_frame_trace_continued_through_publish(self):
        """A sampled frame's context is continued at ingest (validate/
        ledger/merge spans), parked, and the window close fans the roll/
        publish spans onto it — the recorder ends up with the full
        cross-process journey under the agent's id."""
        tracing.configure(sample=1.0, capacity=16)
        tables = _tables()
        agg = self._agg()
        try:
            ctx = tracing.TraceContext("f1ee7000aabbccdd00000001",
                                       "window@agent-0", True)
            ack = agg.ingest_frame(_frame(tables, trace_ctx=ctx))
            assert ack.accepted == 1, ack.reason
            agg.flush()
        finally:
            agg.close()
        entries = tracing.snapshot(trace_id=ctx.trace_id)
        assert len(entries) == 1
        stages = [s["stage"] for s in entries[0]["stages"]]
        for want in ("delta_validate", "delta_ledger",
                     "delta_merge_dispatch", "roll_dispatch",
                     "report_render", "report_sink"):
            assert want in stages, (want, stages)
        assert entries[0]["origin"] == "window@agent-0"

    def test_unstamped_frame_continues_nothing(self):
        tracing.configure(sample=1.0, capacity=16)
        agg = self._agg()
        try:
            ack = agg.ingest_frame(_frame(_tables()))
            assert ack.accepted == 1, ack.reason
            agg.flush()
        finally:
            agg.close()
        assert all(e["kind"] != "federation_delta"
                   for e in tracing.snapshot())

    def test_fleet_snapshot_rollup_and_counts(self):
        tables = _tables()
        agg = self._agg()
        try:
            tel0 = {"shed_factor": 1.0, "conditions": [],
                    "host_records_per_s": 100.0, "map_occupancy": 0.1,
                    "windows_published": 3}
            tel1 = {"shed_factor": 8.0,
                    "conditions": ["OVERLOADED", "ALERTING"],
                    "host_records_per_s": 900.5, "map_occupancy": 0.9,
                    "windows_published": 5}
            assert agg.fleet() is None  # nothing published yet
            agg.ingest_frame(_frame(tables, agent="a0", telemetry=tel0))
            agg.ingest_frame(_frame(tables, agent="a1", telemetry=tel1))
            agg.flush()
            fleet = agg.fleet()
            assert sorted(fleet["agents"]) == ["a0", "a1"]
            assert fleet["agents"]["a0"]["telemetry"] == tel0
            assert fleet["agents"]["a1"]["telemetry"] == tel1
            assert fleet["counts"] == {"agents": 2, "stale": 0,
                                       "overloaded": 1, "degraded": 0,
                                       "alerting": 1}
            seq = fleet["seq"]
            # latest-wins: a newer frame's block replaces the old one
            agg.ingest_frame(_frame(
                tables, agent="a1", window=1, seq=1, uuid="u1",
                telemetry={**tel1, "conditions": [],
                           "windows_published": 6}))
            agg.flush()
            fleet2 = agg.fleet()
            assert fleet2["seq"] > seq
            assert fleet2["agents"]["a1"]["telemetry"][
                "windows_published"] == 6
            assert fleet2["counts"]["overloaded"] == 0
            # the previously published dict is immutable history — the
            # swap replaced, never mutated, the reference a reader holds
            assert fleet["agents"]["a1"]["telemetry"][
                "windows_published"] == 5
        finally:
            agg.close()

    def test_fleet_poller_never_sees_torn_snapshot(self):
        """Concurrent fleet() readers against repeated rebuilds: every
        observed dict is internally consistent (counts match the agent
        rows it was built from) and seq never goes backwards."""
        tables = _tables()
        agg = self._agg()
        stop = threading.Event()
        torn: list[str] = []
        seqs: list[int] = []

        def poll():
            last = 0
            while not stop.is_set():
                f = agg.fleet()
                if f is None:
                    continue
                if f["counts"]["agents"] != len(f["agents"]):
                    torn.append("counts/agents mismatch")
                over = sum(1 for v in f["agents"].values()
                           if "OVERLOADED" in
                           ((v.get("telemetry") or {})
                            .get("conditions", ())))
                if over != f["counts"]["overloaded"]:
                    torn.append("overloaded count mismatch")
                if f["seq"] < last:
                    torn.append("seq went backwards")
                last = f["seq"]
                seqs.append(f["seq"])

        try:
            agg.ingest_frame(_frame(tables, agent="a0", telemetry={
                "shed_factor": 1.0, "conditions": [],
                "host_records_per_s": 0.0, "map_occupancy": 0.0,
                "windows_published": 1}))
            t = threading.Thread(target=poll, daemon=True)
            t.start()
            for i in range(30):
                cond = ["OVERLOADED"] if i % 2 else []
                agg.ingest_frame(_frame(
                    tables, agent="a0", window=i + 1, seq=i + 1,
                    uuid=f"u{i + 1}",
                    telemetry={"shed_factor": float(1 + i % 2),
                               "conditions": cond,
                               "host_records_per_s": 0.0,
                               "map_occupancy": 0.0,
                               "windows_published": i + 2}))
                agg._update_fleet()
            stop.set()
            t.join(timeout=5)
            final = agg.fleet()
        finally:
            stop.set()
            agg.close()
        assert not torn, torn[:3]
        assert seqs, "poller never observed a snapshot"
        assert final["seq"] >= 30

    def test_evicted_agent_row_removed_from_fleet(self):
        tables = _tables()
        agg = self._agg(agent_ttl_s=0.05)
        try:
            agg.ingest_frame(_frame(tables, agent="dark", telemetry={
                "shed_factor": 1.0, "conditions": [],
                "host_records_per_s": 0.0, "map_occupancy": 0.0,
                "windows_published": 1}))
            agg._update_fleet()
            assert "dark" in agg.fleet()["agents"]
            time.sleep(0.08)
            agg._evict_stale_agents()
            agg._update_fleet()
            fleet = agg.fleet()
            assert "dark" not in fleet["agents"]
            assert fleet["counts"]["agents"] == 0
        finally:
            agg.close()

    def test_fleet_route_and_metric(self):
        from netobserv_tpu.federation.query import start_query_server

        m = Metrics()
        tables = _tables()
        agg = self._agg(metrics=m)
        srv = start_query_server(agg, 0, address="127.0.0.1")
        port = srv.server_address[1]

        def get(path):
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}{path}", timeout=5) as r:
                return r.status, json.loads(r.read())

        try:
            with pytest.raises(urllib.error.HTTPError) as err:
                get("/federation/fleet")
            assert err.value.code == 503  # nothing published yet
            assert m.federation_fleet_requests_total.labels(
                "no_window")._value.get() == 1
            agg.ingest_frame(_frame(tables, agent="a0", telemetry={
                "shed_factor": 2.0, "conditions": ["OVERLOADED"],
                "host_records_per_s": 5.5, "map_occupancy": 0.4,
                "windows_published": 1}))
            agg.flush()
            status, fleet = get("/federation/fleet")
            assert status == 200
            assert fleet["agents"]["a0"]["telemetry"]["shed_factor"] == 2.0
            assert fleet["counts"]["overloaded"] == 1
            assert m.federation_fleet_requests_total.labels(
                "ok")._value.get() == 1
            # the aggregator tier mounts the debug views too
            status, body = get("/debug/executables")
            assert status == 200
            assert "executables" in body and "retraces_total" in body
            status, body = get("/debug/traces?limit=1")
            assert status == 200 and "traces" in body
            # the index advertises the new routes
            _, idx = get("/federation")
            assert "/federation/fleet" in idx["routes"]
            assert "/debug/traces" in idx["routes"]
            assert "/debug/executables" in idx["routes"]
        finally:
            srv.shutdown()
            agg.close()

    def test_propagation_counters(self):
        m = Metrics()
        tracing.configure(sample=1.0, capacity=8)
        tables = _tables()
        agg = self._agg(metrics=m)
        try:
            agg.ingest_frame(_frame(tables, trace_ctx=tracing.TraceContext(
                "cc00ffee00000000aabbccdd", "window@a", True)))
            agg.flush()
        finally:
            agg.close()
        assert m.trace_context_propagated_total.labels(
            "continued")._value.get() == 1


# --- the per-executable accounting registry --------------------------------

class TestExecutableRegistry:
    def test_accounting_under_warmup_and_forced_retrace(self):
        import jax
        import jax.numpy as jnp

        m = Metrics()
        retrace.set_metrics(m)
        try:
            fn = retrace.watch(jax.jit(lambda x: x + 1), "acct_probe",
                               warmup_calls=1)
            before_total = retrace.total_retraces()
            fn(jnp.zeros(4, jnp.float32))          # warmup compile
            assert fn.calls == 1 and fn.compiles == 1 and fn.retraces == 0
            assert fn.dispatch_seconds > 0.0
            assert fn.compile_seconds >= 0.0
            assert "float32[4]" in fn.last_signature
            assert fn.donated_bytes == 16
            d1 = fn.dispatch_seconds
            fn(jnp.ones(4, jnp.float32))           # cached executable
            assert fn.compiles == 1 and fn.calls == 2
            assert fn.dispatch_seconds > d1
            fn(jnp.zeros(8, jnp.float32))          # forced retrace
            assert fn.compiles == 2 and fn.retraces == 1
            assert retrace.total_retraces() == before_total + 1
            # signature/donation refresh on EVERY compile: the row
            # describes the executable now serving steady state
            assert "float32[8]" in fn.last_signature
            assert fn.donated_bytes == 32
            row = next(r for r in retrace.snapshot()
                       if r["fn"] == "acct_probe")
            assert row["calls"] == 3
            assert row["dispatch_seconds"] > 0.0
            assert row["donated_bytes_estimate"] == 32
            assert "float32[8]" in row["last_signature"]
            assert m.executable_dispatch_seconds_total.labels(
                "acct_probe")._value.get() == pytest.approx(
                fn.dispatch_seconds, rel=1e-6)
            assert m.sketch_retraces_total.labels(
                "acct_probe")._value.get() == 1
        finally:
            retrace.set_metrics(None)

    def test_snapshot_matches_debug_route(self):
        """`retrace.snapshot()` is the SAME registry view
        /debug/executables serves — one truth for the accounting."""
        from netobserv_tpu.server.debug import _executables_dump

        stamped = retrace.snapshot()
        served = json.loads(_executables_dump({}))
        assert [r["fn"] for r in served["executables"]] == \
            [r["fn"] for r in stamped]
        assert served["retraces_total"] == retrace.total_retraces()
