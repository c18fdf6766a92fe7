"""Flight recorder + retrace watchdog (utils/tracing.py, utils/retrace.py).

Pins the tentpole contracts:

- tracing disabled (TRACE_SAMPLE unset) records nothing in the flight
  recorder: start_trace returns the one shared null trace, whose stage()
  opens the profiler's annotation `netobserv:<stage>` (the profiler's own
  no-op while no session runs) and reads the stage clocks — no Trace, no
  recorder entry, no lock: two clock pairs and one add into the calling
  thread's own table;
- the stage clocks are always on, with jax loaded or not: two threads on
  one stage lose no call and no second, a thread's totals outlive it, and
  /metrics exposes them as stage_{wall,cpu}_seconds_total and
  stage_calls_total;
- under a jax.profiler session the same stages lie on the profiler's clock
  with the ids that chain an eviction to its fold chunks and a chunk to its
  dispatch (eviction / evictions / chunk / k / cont / fn / call);
- sampled traces capture per-stage durations and inter-stage queue-wait
  gaps, newest-first in the fixed-size ring;
- the batch journey (evict -> queue -> fold -> pack -> ingest dispatch) and
  the window journey (roll drain -> roll dispatch -> render -> sink) both
  land in the recorder end to end through the real exporter;
- /debug/traces and /debug/jax answer on the debug server and the index
  describes every route;
- the retrace watchdog: a post-warmup recompile of a watched jitted entry
  point increments sketch_retraces_total{fn=...}; the warmup window
  suppresses the expected first compile.
"""

from __future__ import annotations

import json
import urllib.error
import urllib.request

import numpy as np
import pytest
from prometheus_client import generate_latest

from netobserv_tpu.metrics.registry import Metrics
from netobserv_tpu.utils import retrace, tracing


@pytest.fixture(autouse=True)
def _reset_tracing():
    yield
    tracing.configure(sample=0.0)
    tracing.recorder.clear()
    tracing.set_metrics(None)
    retrace.set_metrics(None)


SMALL_CFG_KW = dict(cm_width=1 << 12, topk=256, hll_precision=8,
                    perdst_buckets=256, perdst_precision=4,
                    persrc_buckets=256, persrc_precision=4,
                    hist_buckets=256, ewma_buckets=256)


# --- null-path contract ----------------------------------------------------

def test_disabled_is_the_shared_null_trace_and_records_nothing(monkeypatch):
    tracing.configure(sample=0.0)
    t1 = tracing.start_trace("batch")
    t2 = tracing.start_trace("window")
    assert t1 is tracing.NULL_TRACE and t2 is tracing.NULL_TRACE
    assert not t1.sampled
    # this thread's clock table exists after its first stage
    with t1.stage("warm"):
        pass
    # without sampling a stage is the profiler's annotation and the stage
    # clocks, and nothing of the flight recorder's: no Trace and no span,
    # no recorder entry, no lock (each raises if touched)
    monkeypatch.setattr(tracing.threading, "Lock",
                        lambda: pytest.fail("a lock without sampling"))
    monkeypatch.setattr(tracing, "_tables_lock", None)
    monkeypatch.setattr(tracing.Trace, "_add",
                        lambda *a: pytest.fail("a span without sampling"))
    monkeypatch.setattr(tracing.FlightRecorder, "add",
                        lambda *a: pytest.fail("a recorder entry"))
    before = tracing._clock_local.table.get("evict", [0.0, 0.0, 0])[2]
    for handle in (t1, t1.bind(eviction=7)):
        s1 = handle.stage("evict")
        s2 = handle.stage("fold", eviction=7)
        for span in (s1, s2):
            assert not isinstance(span, tracing.Trace)
            assert span._traces == ()
            with span:
                pass
        handle.finish()
    assert tracing._clock_local.table["evict"][2] == before + 2
    assert len(tracing.recorder) == 0
    assert not tracing.enabled()


def test_a_process_without_jax_still_counts():
    """`utils.tracing` never imports jax: an agent with EXPORT=grpc has no
    profiler to annotate for, and importing the drain must not load one —
    yet its stages count on the stage clocks."""
    code = ("import sys, time\n"
            "import netobserv_tpu.flow.map_tracer\n"
            "from netobserv_tpu.utils import tracing\n"
            "span = tracing.NULL_TRACE.stage('evict', eviction=1)\n"
            "assert tracing.annotate('evict') is tracing.NULL_SPAN\n"
            "with span:\n"
            "    time.sleep(0.01)\n"
            "wall, cpu, calls = tracing.stage_clocks()['evict']\n"
            "assert calls == 1 and wall >= 0.01 and 0 <= cpu < wall, "
            "(wall, cpu)\n"
            "assert 'jax' not in sys.modules, 'jax was imported'\n")
    import subprocess
    import sys

    got = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120)
    assert got.returncode == 0, got.stderr


def test_two_threads_on_one_stage_lose_no_call_and_no_second(monkeypatch):
    """Each thread adds into its own table: whatever the interleaving, the
    sum is exact. A fake clock makes every stage exactly 1 wall second and
    0.5 CPU second, per thread."""
    import threading
    import types

    local = threading.local()

    def tick(attr, step):
        def read():
            v = getattr(local, attr, 0.0) + step
            setattr(local, attr, v)
            return v
        return read
    monkeypatch.setattr(tracing, "time", types.SimpleNamespace(
        perf_counter=tick("wall", 1.0), thread_time=tick("cpu", 0.5)))
    n, go = 20_000, threading.Barrier(2)

    def hammer():
        go.wait()
        for _ in range(n):
            with tracing.NULL_TRACE.stage("hammer", chunk=1):
                pass
    threads = [threading.Thread(target=hammer) for _ in range(2)]
    base = tracing.stage_clocks().get("hammer", (0.0, 0.0, 0))
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall, cpu, calls = tracing.stage_clocks()["hammer"]
    assert calls - base[2] == 2 * n
    assert wall - base[0] == 2 * n * 1.0
    assert cpu - base[1] == 2 * n * 0.5


def test_a_threads_totals_outlive_it():
    import threading

    def run():
        for _ in range(3):
            with tracing.NULL_TRACE.stage("ephemeral"):
                pass
    base = tracing.stage_clocks().get("ephemeral", (0.0, 0.0, 0))[2]
    t = threading.Thread(target=run)
    t.start()
    t.join()
    # the first scrape folds the exited thread's table into the retired
    # one; later scrapes still count it, once
    for _ in range(2):
        assert tracing.stage_clocks()["ephemeral"][2] == base + 3
    assert all(th is not t for th, _ in tracing._tables)


def test_stage_clocks_are_counters_on_metrics():
    from prometheus_client.parser import text_string_to_metric_families

    m = Metrics()
    with tracing.NULL_TRACE.stage("scraped"):
        pass
    got = {}
    for fam in text_string_to_metric_families(
            generate_latest(m.registry).decode()):
        for smp in fam.samples:
            if smp.labels.get("stage") == "scraped":
                got[smp.name] = smp.value
    wall, cpu, calls = tracing.stage_clocks()["scraped"]
    assert got["ebpf_agent_stage_calls_total"] == calls >= 1
    assert got["ebpf_agent_stage_wall_seconds_total"] == wall > 0
    assert got["ebpf_agent_stage_cpu_seconds_total"] == cpu >= 0


def test_null_trace_survives_every_pipeline_verb():
    """The null object must accept the full Trace surface (the pipeline
    never branches on sampled-ness except to attach to EvictedFlows)."""
    t = tracing.NULL_TRACE
    with t.stage("anything"):
        with t.stage("nested"):
            pass
    t.finish()
    t.finish()  # idempotent


# --- sampled traces --------------------------------------------------------

def test_sampled_trace_records_stages_gaps_and_order():
    tracing.configure(sample=1.0, capacity=8)
    t = tracing.start_trace("batch")
    assert t.sampled
    with t.stage("evict"):
        pass
    with t.stage("fold"):
        pass
    t.finish()
    snap = tracing.snapshot()
    assert len(snap) == 1
    got = snap[0]
    assert got["kind"] == "batch"
    names = [s["stage"] for s in got["stages"]]
    assert names == ["evict", "fold"]
    for s in got["stages"]:
        assert s["dur_ms"] >= 0.0
    # the second stage's gap is the wait between evict end and fold start
    assert got["stages"][0]["gap_ms"] == 0.0
    assert got["stages"][1]["gap_ms"] >= 0.0
    assert got["total_ms"] >= 0.0


def test_active_trace_binding():
    """The per-thread active trace (map_tracer binds it around the drain so
    the columnar eviction plane can attach decode/merge_percpu/align child
    spans without widening the FlowFetcher protocol): unbound -> the shared
    null trace; bound -> that trace; cleared -> null again. Bindings are
    thread-local."""
    import threading

    assert tracing.active_trace() is tracing.NULL_TRACE
    tracing.configure(sample=1.0, capacity=8)
    t = tracing.start_trace("batch")
    tracing.set_active(t)
    try:
        assert tracing.active_trace() is t
        seen = []
        th = threading.Thread(
            target=lambda: seen.append(tracing.active_trace()))
        th.start()
        th.join()
        assert seen == [tracing.NULL_TRACE]  # other threads stay unbound
    finally:
        tracing.clear_active()
    assert tracing.active_trace() is tracing.NULL_TRACE


def test_evict_child_spans_ride_the_batch_trace():
    """A fetcher reading tracing.active_trace() inside lookup_and_delete
    (the BpfmanFetcher eviction plane) lands its child spans on the SAME
    sampled trace map_tracer started, each with the drain's `eviction` id
    — and with sampling off, the drain's handle is the unsampled one."""
    import queue

    from netobserv_tpu.datapath.fetcher import FakeFetcher
    from netobserv_tpu.flow.map_tracer import MapTracer
    from netobserv_tpu.model import binfmt

    class SpanningFetcher(FakeFetcher):
        def lookup_and_delete(self):
            trace = tracing.active_trace()
            self.saw_unsampled = not trace.sampled
            with trace.stage("decode"):
                pass
            with trace.stage("merge_percpu"):
                pass
            with trace.stage("align"):
                pass
            return super().lookup_and_delete()

    def run_once():
        fetcher = SpanningFetcher()
        events = np.zeros(2, binfmt.FLOW_EVENT_DTYPE)
        events["key"]["src_port"] = [1, 2]
        fetcher.inject_events(events)
        out: queue.Queue = queue.Queue()
        tracer = MapTracer(fetcher, out, columnar=True)
        tracer._evict_once()
        return fetcher, out.get_nowait()

    tracing.configure(sample=1.0, capacity=8)
    f, evicted = run_once()
    assert not f.saw_unsampled
    # the columnar path leaves the open trace riding the EvictedFlows for
    # the exporter fold — the drain's child spans are already on it,
    # alongside map_tracer's own evict span
    stages = {s.stage for s in evicted.trace.spans}
    assert {"evict", "decode", "merge_percpu", "align"} <= stages
    assert evicted.eviction > 0
    assert {s.ids.get("eviction") for s in evicted.trace.spans} == {
        evicted.eviction}
    tracing.configure(sample=0.0)
    f2, evicted2 = run_once()
    assert f2.saw_unsampled  # unsampled drains never see a live trace
    assert not hasattr(evicted2, "trace")


def test_recorder_is_bounded_and_newest_first():
    tracing.configure(sample=1.0, capacity=4)
    for i in range(10):
        t = tracing.start_trace("batch")
        with t.stage("evict"):
            pass
        t.finish()
    snap = tracing.snapshot()
    assert len(snap) == 4
    ids = [s["id"] for s in snap]
    assert ids == sorted(ids, reverse=True)  # newest first


def test_sampling_period_is_deterministic():
    tracing.configure(sample=0.5, capacity=16)
    sampled = [tracing.start_trace().sampled for _ in range(8)]
    assert sampled == [False, True] * 4


def test_sampling_counters_are_per_kind():
    """The pipeline issues interleaved kinds in a fixed pattern (one batch
    + one fold per eviction, one window per roll); a SHARED counter would
    alias that pattern and starve a kind forever. Each kind must sample on
    its own period."""
    tracing.configure(sample=0.5, capacity=16)
    seen = {"batch": [], "window": []}
    for _ in range(4):  # strict alternation — the aliasing-prone pattern
        seen["batch"].append(tracing.start_trace("batch").sampled)
        seen["window"].append(tracing.start_trace("window").sampled)
    assert seen["batch"] == [False, True, False, True]
    assert seen["window"] == [False, True, False, True]


def test_finish_without_spans_records_nothing():
    tracing.configure(sample=1.0, capacity=4)
    t = tracing.start_trace("batch")
    t.finish()
    assert len(tracing.recorder) == 0


def test_spans_feed_stage_seconds_histogram():
    tracing.configure(sample=1.0, capacity=4)
    m = Metrics()
    tracing.set_metrics(m)
    t = tracing.start_trace("batch")
    with t.stage("fold"):
        pass
    t.finish()
    text = generate_latest(m.registry).decode()
    assert 'ebpf_agent_stage_seconds_count{stage="fold"} 1.0' in text


# --- end-to-end through the real exporter ---------------------------------

def _small_exporter(sink, window_s=60.0, batch_size=512):
    from netobserv_tpu.exporter.tpu_sketch import TpuSketchExporter
    from netobserv_tpu.sketch.state import SketchConfig

    return TpuSketchExporter(batch_size=batch_size, window_s=window_s,
                             sketch_cfg=SketchConfig(**SMALL_CFG_KW),
                             sink=sink)


def test_batch_and_window_traces_end_to_end():
    from netobserv_tpu.datapath.replay import SyntheticFetcher

    tracing.configure(sample=1.0, capacity=32)
    reports: list = []
    exp = _small_exporter(reports.append)
    try:
        fetcher = SyntheticFetcher(flows_per_eviction=512, n_distinct=200)
        for _ in range(3):
            ev = fetcher.lookup_and_delete()
            # what MapTracer does on the columnar path
            trace = tracing.start_trace("batch")
            with trace.stage("evict"):
                pass
            ev.trace = trace
            exp.export_evicted(ev)
        exp.flush()
    finally:
        exp.close()
    assert reports, "flush must publish a window report"
    snap = tracing.snapshot()
    kinds = {s["kind"] for s in snap}
    assert "batch" in kinds and "window" in kinds
    batch = next(s for s in snap if s["kind"] == "batch")
    names = [st["stage"] for st in batch["stages"]]
    assert names[0] == "evict"
    assert "fold" in names
    assert "resident_pack" in names or "pack" in names
    assert "ingest_dispatch" in names
    # the evict->fold gap is the export queue wait
    fold = next(st for st in batch["stages"] if st["stage"] == "fold")
    assert "gap_ms" in fold
    window = next(s for s in snap if s["kind"] == "window")
    wnames = [st["stage"] for st in window["stages"]]
    for expect in ("roll_drain", "roll_dispatch", "report_render",
                   "report_sink"):
        assert expect in wnames, (expect, wnames)


def test_map_tracer_attaches_trace_on_columnar_path():
    import queue

    from netobserv_tpu.datapath.fetcher import FakeFetcher
    from netobserv_tpu.flow import MapTracer

    from tests.test_pipeline import make_events

    tracing.configure(sample=1.0, capacity=8)
    fake = FakeFetcher()
    fake.inject_events(make_events(3))
    out: queue.Queue = queue.Queue()
    mt = MapTracer(fake, out, columnar=True)
    mt._evict_once()
    evicted = out.get_nowait()
    assert evicted.trace is not None and evicted.trace.sampled
    stages = [s.stage for s in evicted.trace.spans]
    assert stages == ["evict"]

    # disabled: no attribute rides the eviction at all
    tracing.configure(sample=0.0)
    fake.inject_events(make_events(2))
    mt._evict_once()
    evicted = out.get_nowait()
    assert getattr(evicted, "trace", None) is None


def test_exporter_disabled_tracing_records_nothing():
    from netobserv_tpu.datapath.replay import SyntheticFetcher

    tracing.configure(sample=0.0)
    exp = _small_exporter(lambda obj: None)
    try:
        fetcher = SyntheticFetcher(flows_per_eviction=512, n_distinct=100)
        exp.export_evicted(fetcher.lookup_and_delete())
        exp.flush()
    finally:
        exp.close()
    assert len(tracing.recorder) == 0


# --- debug server routes ---------------------------------------------------

def _get(srv, path):
    port = srv.server_address[1]
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                timeout=5) as resp:
        return resp.status, resp.headers.get("Content-Type", ""), resp.read()


def test_debug_traces_and_jax_routes():
    from netobserv_tpu.server import start_debug_server

    tracing.configure(sample=1.0, capacity=8)
    t = tracing.start_trace("batch")
    with t.stage("evict"):
        pass
    t.finish()
    srv = start_debug_server("127.0.0.1:0")
    try:
        status, ctype, body = _get(srv, "/debug/traces")
        assert status == 200 and ctype.startswith("application/json")
        obj = json.loads(body)
        assert obj["sampling_enabled"] is True
        assert obj["traces"][0]["stages"][0]["stage"] == "evict"

        status, ctype, body = _get(srv, "/debug/jax")
        assert status == 200 and ctype.startswith("application/json")
        obj = json.loads(body)
        assert obj["backend"] == "cpu"
        assert obj["device_count"] >= 1
        assert isinstance(obj["live_arrays"], int)
        assert "compilation_cache" in obj
        assert isinstance(obj["retrace_watchdog"], list)

        # the index lists every route with a one-line description
        status, _ctype, body = _get(srv, "/debug")
        text = body.decode()
        for route in ("/debug/threads", "/debug/tracemalloc", "/debug/gc",
                      "/debug/traces", "/debug/jax"):
            assert route in text
            line = next(ln for ln in text.splitlines()
                        if ln.startswith(route))
            assert len(line.split(None, 1)[1]) > 10, f"{route} undescribed"

        # unknown path still 404s
        with pytest.raises(urllib.error.HTTPError) as err:
            _get(srv, "/debug/nope")
        assert err.value.code == 404
    finally:
        srv.shutdown()


def test_debug_traces_params_and_executables_route():
    """?limit= caps the trace list, ?trace= is the single-id lookup (the
    cross-process correlation URL), a malformed limit is ignored, and
    /debug/executables serves the accounting registry with an index
    description."""
    from netobserv_tpu.server import start_debug_server

    tracing.configure(sample=1.0, capacity=8)
    tracing.recorder.clear()
    ids = []
    for _ in range(3):
        t = tracing.start_trace("batch")
        with t.stage("evict"):
            pass
        t.finish()
        ids.append(t.trace_id)
    srv = start_debug_server("127.0.0.1:0")
    try:
        _, _, body = _get(srv, "/debug/traces?limit=2")
        assert len(json.loads(body)["traces"]) == 2
        _, _, body = _get(srv, f"/debug/traces?trace={ids[0]}")
        got = json.loads(body)["traces"]
        assert [t["trace_id"] for t in got] == [ids[0]]
        _, _, body = _get(srv, "/debug/traces?trace=no-such-id")
        assert json.loads(body)["traces"] == []
        _, _, body = _get(srv, "/debug/traces?limit=bogus")
        assert len(json.loads(body)["traces"]) == 3  # param ignored

        status, ctype, body = _get(srv, "/debug/executables")
        assert status == 200 and ctype.startswith("application/json")
        obj = json.loads(body)
        assert isinstance(obj["executables"], list)
        assert obj["retraces_total"] == retrace.total_retraces()
        for row in obj["executables"]:
            assert {"fn", "calls", "compiles", "retraces",
                    "dispatch_seconds", "compile_seconds",
                    "donated_bytes_estimate"} <= row.keys()

        _, _, body = _get(srv, "/debug")
        line = next(ln for ln in body.decode().splitlines()
                    if ln.startswith("/debug/executables"))
        assert len(line.split(None, 1)[1]) > 10
    finally:
        srv.shutdown()


# --- retrace watchdog ------------------------------------------------------

def test_retrace_watchdog_counts_post_warmup_recompiles():
    import jax
    import jax.numpy as jnp

    m = Metrics()
    retrace.set_metrics(m)
    fn = retrace.watch(jax.jit(lambda x: x * 2 + 1), "test_entry",
                       warmup_calls=1)
    # warmup: the first call's compile is expected — no alarm
    fn(jnp.ones(8))
    assert fn.compiles == 1 and fn.retraces == 0
    # steady state at the same shape: silence
    for _ in range(3):
        fn(jnp.ones(8))
    assert fn.compiles == 1 and fn.retraces == 0
    # changed shape after warmup: the invariant is broken -> alarm
    fn(jnp.ones(16))
    assert fn.compiles == 2 and fn.retraces == 1
    assert "[16]" in fn.last_retrace
    text = generate_latest(m.registry).decode()
    assert ('ebpf_agent_sketch_retraces_total{fn="test_entry"} 1.0'
            in text)


def test_retrace_warmup_window_suppresses_false_positives():
    import jax
    import jax.numpy as jnp

    m = Metrics()
    retrace.set_metrics(m)
    # a 2-call warmup tolerates two distinct warmup shapes (e.g. an entry
    # point warmed on both its steady and its flush shape)
    fn = retrace.watch(jax.jit(lambda x: x + 1), "warmup_entry",
                       warmup_calls=2)
    fn(jnp.ones(4))
    fn(jnp.ones(8))
    assert fn.compiles == 2 and fn.retraces == 0
    text = generate_latest(m.registry).decode()
    # no RETRACE series for this entry (warmup suppressed the alarm);
    # the accounting registry's dispatch counter still reports it — that
    # is attribution, not an alarm
    assert 'sketch_retraces_total{fn="warmup_entry"}' not in text
    assert ('executable_dispatch_seconds_total{fn="warmup_entry"}'
            in text)


def test_first_call_failure_names_the_executable_at_error_level(caplog):
    """A first call that fails did not lower or compile — not transient.
    The callers swallow and count without knowing which executable it was,
    so the wrapper says it, by name, at ERROR; later failures stay theirs."""
    import jax
    import jax.numpy as jnp

    fn = retrace.watch(jax.jit(lambda x: x.reshape(7)), "refused_entry")
    with caplog.at_level("ERROR", logger="netobserv_tpu.retrace"):
        with pytest.raises(TypeError):
            fn(jnp.ones(3))
        assert ["refused_entry" in r.getMessage() and r.levelname == "ERROR"
                for r in caplog.records] == [True]
        with pytest.raises(TypeError):
            fn(jnp.ones(3))
        assert len(caplog.records) == 1


def test_last_avals_relower_the_dispatched_executable():
    """`last_avals` (shape, dtype, sharding of the last compile) is enough
    to re-lower what was dispatched, without holding its buffers."""
    import jax
    import jax.numpy as jnp

    fn = retrace.watch(jax.jit(lambda a, b: a @ b["w"]), "avals_entry")
    fn(jnp.ones((4, 8)), {"w": np.ones((8, 2), np.float32)})
    assert jax.tree.map(lambda x: x.shape, fn.last_avals) == \
        ((4, 8), {"w": (8, 2)})
    assert "dot_general" in fn.lower(*fn.last_avals).as_text()


def test_retrace_watchdog_on_real_ingest_changed_batch_shape():
    """The CI-speed force-retrace: a jitted dense ingest fed a CHANGED batch
    shape after warmup must fire sketch_retraces_total."""
    import jax

    from netobserv_tpu.sketch import state as sk

    m = Metrics()
    retrace.set_metrics(m)
    cfg = sk.SketchConfig(**SMALL_CFG_KW)
    state = sk.init_state(cfg)
    ingest = sk.make_ingest_dense_fn(donate=False, name="ingest_dense_test")
    rng = np.random.default_rng(3)

    def dense(n):
        # build via arrays_to_dense: keys + counters only
        arrays = {
            "keys": rng.integers(0, 2**32, (n, 10), dtype=np.uint32),
            "bytes": rng.integers(1, 1500, n).astype(np.float32),
            "packets": np.ones(n, np.int32),
            "rtt_us": np.zeros(n, np.int32),
            "dns_latency_us": np.zeros(n, np.int32),
            "sampling": np.zeros(n, np.int32),
            "valid": np.ones(n, np.bool_),
        }
        return sk.arrays_to_dense(arrays).reshape(-1)

    state = ingest(state, jax.device_put(dense(64)))
    jax.block_until_ready(state)
    assert ingest.retraces == 0
    # same shape again: still silent
    state = ingest(state, jax.device_put(dense(64)))
    assert ingest.retraces == 0
    # the forbidden event: a different batch shape post-warmup
    state = ingest(state, jax.device_put(dense(128)))
    jax.block_until_ready(state)
    assert ingest.retraces == 1
    text = generate_latest(m.registry).decode()
    assert 'fn="ingest_dense_test"' in text


def test_watch_delegates_jit_introspection():
    import jax
    import jax.numpy as jnp

    fn = retrace.watch(jax.jit(lambda x: x + 1), "lower_entry")
    lowered = fn.lower(jnp.ones(4))  # AOT path through the wrapper
    assert "add" in lowered.as_text()
    # double-watch returns the same wrapper
    assert retrace.watch(fn, "again") is fn


def test_exporter_full_cycle_stays_retrace_silent():
    """Acceptance pin: a full exporter cycle (folds incl. a padded partial
    batch + window roll + publish) performs ZERO post-warmup retraces."""
    from netobserv_tpu.datapath.replay import SyntheticFetcher

    before = retrace.total_retraces()
    exp = _small_exporter(lambda obj: None)
    try:
        fetcher = SyntheticFetcher(flows_per_eviction=300, n_distinct=100)
        for _ in range(6):  # 300-row evictions roll over the 512 batch
            exp.export_evicted(fetcher.lookup_and_delete())
        exp.flush()
        exp.flush()  # second window: roll is past ITS warmup call too
    finally:
        exp.close()
    assert retrace.total_retraces() == before


def test_default_one_device_exporter_registers_exactly_the_served_entries(
        monkeypatch):
    """What `/debug/executables` lists for the exporter `from_config` builds
    on ONE device at the default feed and ladder, after a warm ladder, an
    eviction and a roll: the dict-arrays `ingest`, one resident entry per
    ladder size, the top entry's wide lane family, and `roll` — no more (a
    second form of a served entry would be a second program to keep warm:
    the wide x4 is the one that earns it, PERF.md section 6, PR 35) and no
    fewer (a deleted factory cannot silently take a served entry with
    it)."""
    import jax

    from netobserv_tpu.config import load_config
    from netobserv_tpu.datapath.replay import SyntheticFetcher
    from netobserv_tpu.exporter.tpu_sketch import TpuSketchExporter
    from netobserv_tpu.server.debug import _executables_dump

    real_devices = jax.devices
    monkeypatch.setattr(jax, "devices",
                        lambda *a, **k: real_devices(*a, **k)[:1])
    before = {id(w) for w in retrace.watched()}
    cfg = load_config({
        "EXPORT": "tpu-sketch", "SKETCH_WINDOW": "1h",
        "SKETCH_BATCH_SIZE": "512", "SKETCH_CM_WIDTH": "1024",
        "SKETCH_TOPK": "64", "SKETCH_HLL_PRECISION": "8",
        "SKETCH_RESIDENT_SLOTS": "4096"})
    exp = TpuSketchExporter.from_config(cfg, sink=lambda obj: None)
    try:
        exp._warm_thread.join()
        exp.export_evicted(SyntheticFetcher(
            flows_per_eviction=700, n_distinct=100).lookup_and_delete())
        exp.flush()
        mine = {w.name: w for w in retrace.watched() if id(w) not in before}
        assert sorted(mine) == [
            "ingest", "ingest_resident_lanes_wide_x4",
            "ingest_resident_lanes_x1", "ingest_resident_lanes_x2",
            "ingest_resident_lanes_x4", "roll"]
        # the served ones ran; the dict-arrays entry is built, never called
        assert {n for n, w in mine.items() if w.calls} == set(mine) - {
            "ingest"}
        served = {r["fn"] for r in
                  json.loads(_executables_dump({}))["executables"]}
        assert set(mine) <= served
    finally:
        exp.close()


# --- the stages on the profiler's clock, with ids --------------------------

def _resident_exporter(metrics=None):
    from netobserv_tpu.exporter.tpu_sketch import TpuSketchExporter
    from netobserv_tpu.sketch.state import SketchConfig

    return TpuSketchExporter(batch_size=512, window_s=600.0,
                             sketch_cfg=SketchConfig(**SMALL_CFG_KW),
                             sink=lambda obj: None, metrics=metrics)


def _served_path():
    """(fetcher, queue, MapTracer) feeding 1,300-flow evictions: two full
    512-row batches fold as each arrives, the tail rides to the next."""
    import queue

    from netobserv_tpu.datapath.fetcher import FakeFetcher
    from netobserv_tpu.flow.map_tracer import MapTracer

    out: queue.Queue = queue.Queue()
    fetcher = FakeFetcher()
    return fetcher, out, MapTracer(fetcher, out, columnar=True)


def _drain_and_export(fetcher, tracer, out, exp, n: int) -> list:
    """`n` evictions MapTracer -> TpuSketchExporter; their sequence
    numbers."""
    from tests.test_pipeline import make_events

    seqs = []
    for i in range(n):
        fetcher.inject_events(make_events(1300, sport0=1000 + 37 * i))
        tracer._evict_once()
        evicted = out.get_nowait()
        seqs.append(evicted.eviction)
        exp.export_evicted(evicted)
    return seqs


@pytest.fixture(scope="module")
def capture(tmp_path_factory):
    """One jax.profiler session (CPU backend) round two 1,300-flow evictions
    through MapTracer -> TpuSketchExporter and the window's close: every
    `netobserv:` annotation on the host plane as (stage, ids, start, end)."""
    import glob

    import jax
    from jax.profiler import ProfileData

    tracing.configure(sample=0.0)
    exp = _resident_exporter()
    fetcher, out, tracer = _served_path()
    where = str(tmp_path_factory.mktemp("capture"))
    try:
        _drain_and_export(fetcher, tracer, out, exp, 1)     # compiles x1
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(where, profiler_options=opts)
        try:
            seqs = _drain_and_export(fetcher, tracer, out, exp, 2)
            exp.flush()
        finally:
            jax.profiler.stop_trace()
    finally:
        exp.close()
    path = glob.glob(where + "/plugins/profile/*/*.xplane.pb")[0]
    found = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(tracing.ANNOTATION_PREFIX):
                    found.append((e.name[len(tracing.ANNOTATION_PREFIX):],
                                  dict(e.stats), e.start_ns,
                                  e.start_ns + e.duration_ns))
    return {"seqs": seqs, "spans": sorted(found, key=lambda s: s[2]),
            "recorded": len(tracing.recorder)}


def _of(capture, stage):
    return [s for s in capture["spans"] if s[0] == stage]


def test_capture_holds_every_served_path_stage(capture):
    stages = {s[0] for s in capture["spans"]}
    assert {"evict", "export", "fold", "resident_pack", "put",
            "ingest_dispatch", "dispatch", "roll_drain", "roll_dispatch",
            "publish", "report_render", "query_snapshot",
            "report_sink"} <= stages
    # the profiler's spans need no sampling, and sample nothing
    assert capture["recorded"] == 0


def test_capture_eviction_ids_chain_evict_to_fold_to_chunks(capture):
    seqs = capture["seqs"]
    assert [s[1]["eviction"] for s in _of(capture, "evict")] == seqs
    folds = _of(capture, "fold")
    assert folds and {f[1]["eviction"] for f in folds} <= set(seqs)
    for fold in folds:
        # a fold starts after the evict of the eviction it names ended:
        # the difference is the export queue wait
        evict = next(e for e in _of(capture, "evict")
                     if e[1]["eviction"] == fold[1]["eviction"])
        assert fold[2] >= evict[3]
    held = set()
    for stage in ("resident_pack", "put", "ingest_dispatch"):
        for s in _of(capture, stage):
            first, last = (int(x) for x in s[1]["evictions"].split("-"))
            assert first <= last and last in seqs
            held.update(range(first, last + 1))
            assert s[1]["k"] == 1 and s[1]["cont"] in (0, 1)
    assert set(seqs) <= held    # every eviction's rows ride some chunk


def test_capture_export_carries_the_eviction_and_holds_its_fold(capture):
    """One `export` per eviction, on the seam's thread, after the eviction's
    `evict`: end of `evict` to start of `export` is the queue alone."""
    seqs, exports = capture["seqs"], _of(capture, "export")
    assert [e[1]["eviction"] for e in exports] == seqs
    evicts = {e[1]["eviction"]: e for e in _of(capture, "evict")}
    for e in exports:
        assert e[2] >= evicts[e[1]["eviction"]][3]
    # a steady fold and its chunks nest inside the export that triggered it
    inside = [f for f in _of(capture, "fold")
              if any(e[2] <= f[2] and f[3] <= e[3] for e in exports)]
    assert inside
    for f in inside:
        e = next(e for e in exports if e[2] <= f[2] and f[3] <= e[3])
        assert f[1]["eviction"] == e[1]["eviction"]


def test_capture_publish_carries_the_window_and_holds_render_to_sink(capture):
    (publish,) = _of(capture, "publish")
    render, sink = _of(capture, "report_render"), _of(capture, "report_sink")
    assert publish[1]["window"] == render[0][1]["window"]
    for child in render + sink + _of(capture, "query_snapshot"):
        assert publish[2] <= child[2] and child[3] <= publish[3]


def test_capture_chunk_ids_chain_pack_put_dispatch_to_the_jit_call(capture):
    packs, puts = _of(capture, "resident_pack"), _of(capture, "put")
    sends = _of(capture, "ingest_dispatch")
    chunks = [s[1]["chunk"] for s in packs]
    assert chunks == sorted(set(chunks)) and len(chunks) >= 2
    assert [s[1]["chunk"] for s in puts] == chunks
    assert [s[1]["chunk"] for s in sends] == chunks
    # conftest's 8 virtual devices make the exporter build its mesh: the
    # entry is sharded_ingest_resident_x1 there, ingest_resident_lanes_x1
    # on one device
    calls = [d for d in _of(capture, "dispatch") if "ingest" in d[1]["fn"]]
    assert {d[1]["fn"] for d in calls} <= {"sharded_ingest_resident_x1",
                                           "ingest_resident_lanes_x1"}
    assert len({d[1]["fn"] for d in calls}) == 1
    # one watched call inside each ingest_dispatch, numbered in order
    assert len(calls) == len(sends)
    for send, call in zip(sends, calls):
        assert send[2] <= call[2] and call[3] <= send[3]
    numbers = [c[1]["call"] for c in calls]
    assert numbers == list(range(numbers[0], numbers[0] + len(numbers)))
    for pack, put, send in zip(packs, puts, sends):
        assert pack[3] <= put[2] and put[3] <= send[2]


def test_capture_window_ids_ride_from_the_roll_to_the_sink(capture):
    windows = {s[0]: s[1]["window"] for s in capture["spans"]
               if "window" in s[1]}
    assert set(windows) >= {"roll_drain", "roll_dispatch", "publish",
                            "report_render", "query_snapshot", "report_sink"}
    assert len(set(windows.values())) == 1
    roll = [d for d in _of(capture, "dispatch")
            if d[1]["fn"] in ("roll", "sharded_merge")]
    assert len(roll) == 1


def test_no_session_no_sampling_leaves_no_trace_and_no_stage_seconds():
    from netobserv_tpu.server import start_debug_server

    tracing.configure(sample=0.0)
    m = Metrics()
    tracing.set_metrics(m)
    exp = _resident_exporter(metrics=m)
    fetcher, out, tracer = _served_path()
    try:
        _drain_and_export(fetcher, tracer, out, exp, 2)
        exp.flush()
        chunks = exp._ring.chunks
    finally:
        exp.close()
    srv = start_debug_server("127.0.0.1:0")
    try:
        _, _, body = _get(srv, "/debug/traces")
    finally:
        srv.shutdown()
    served = json.loads(body)
    assert served["traces"] == [] and not served["sampling_enabled"]
    text = generate_latest(m.registry).decode()
    assert "ebpf_agent_stage_seconds_count" not in text
    # the always-on pack timer, once per fold chunk
    assert chunks >= 2
    assert f"ebpf_agent_sketch_pack_seconds_count {float(chunks)}" in text


def test_sampled_spans_carry_the_ids_their_annotations_do():
    tracing.configure(sample=1.0, capacity=8)
    t = tracing.start_trace("batch")
    with t.bind(eviction=5).stage("evict"):
        pass
    with t.stage("fold", eviction=5):
        with t.bind(evictions="4-5").stage("put", chunk=9, k=2, cont=0):
            pass
    t.finish()
    stages = {s["stage"]: s.get("ids") for s in tracing.snapshot()[0]["stages"]}
    assert stages == {"evict": {"eviction": 5}, "fold": {"eviction": 5},
                      "put": {"evictions": "4-5", "chunk": 9, "k": 2,
                              "cont": 0}}
