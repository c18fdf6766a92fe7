"""Agent query plane (netobserv_tpu/query + the exporter snapshot publisher
+ the metrics server's /query/* routes).

Pins the subsystem's contracts:

- snapshot consistency: publishes swap WHOLE dicts with a monotonic seq —
  a poller hammering the surface during concurrent rolls never observes a
  torn mix of two windows;
- staleness: `query_snapshot_age_seconds` grows while the refresh is
  disabled and resets at every roll;
- the `sketch.query_snapshot` fault point: a failing snapshot publish
  never stalls `export_evicted` and never loses the window report (and the
  point is zero-cost when FAULT_POINTS is unset, like every other point);
- the mid-window refresh (SKETCH_QUERY_REFRESH) serves the LIVE window
  with zero post-warmup retraces and never perturbs the window's state;
  disabled (the default) there is no refresh machinery at all — the
  bit-identical exporter-path bar;
- route behavior: params, error codes, `query_requests_total` labels, and
  the HTTP wiring on the metrics server.
"""

from __future__ import annotations

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest
from prometheus_client import generate_latest

from netobserv_tpu.datapath.fetcher import EvictedFlows
from netobserv_tpu.exporter.tpu_sketch import TpuSketchExporter
from netobserv_tpu.metrics.registry import Metrics
from netobserv_tpu.metrics.server import start_metrics_server
from netobserv_tpu.query.routes import QueryRoutes
from netobserv_tpu.query.snapshot import SnapshotPublisher
from netobserv_tpu.sketch.state import SketchConfig
from netobserv_tpu.utils import faultinject, retrace

from tests.test_pipeline import make_events

SMALL_CFG = SketchConfig(cm_depth=2, cm_width=1 << 10, hll_precision=6,
                         perdst_buckets=32, perdst_precision=4,
                         persrc_buckets=32, persrc_precision=4,
                         topk=16, hist_buckets=64, ewma_buckets=32)


@pytest.fixture(autouse=True)
def _clean_faults():
    yield
    faultinject.clear()
    faultinject.hits.clear()


def make_exporter(metrics=None, sink=None, window_s=3600.0, **kw):
    return TpuSketchExporter(batch_size=64, window_s=window_s,
                             sketch_cfg=SMALL_CFG, metrics=metrics,
                             sink=sink or (lambda obj: None), **kw)


# --- snapshot publisher -------------------------------------------------

def test_publisher_seq_monotonic_and_age_resets():
    pub = SnapshotPublisher()
    assert pub.get() is None
    assert pub.stats()["published"] is False
    s1 = pub.publish({"window": 0, "ts_ms": 1, "report": {}})
    time.sleep(0.05)
    age_before = pub.age_s()
    s2 = pub.publish({"window": 1, "ts_ms": 2, "report": {}})
    assert (s1, s2) == (1, 2)
    assert pub.get()["seq"] == 2
    assert pub.age_s() < age_before  # publish reset the age clock
    st = pub.stats()
    assert st["published"] and st["window"] == 1
    assert st["snapshots_published"] == 2 and st["mid_window_refreshes"] == 0


def test_publisher_snapshot_is_immutable_reference_swap():
    """A reader holding a snapshot keeps ITS window's view even after
    later publishes (whole-dict swap, never in-place mutation)."""
    pub = SnapshotPublisher()
    pub.publish({"window": 7, "ts_ms": 1, "report": {"Records": 7.0}})
    held = pub.get()
    pub.publish({"window": 8, "ts_ms": 2, "report": {"Records": 8.0}})
    assert held["window"] == 7 and held["report"]["Records"] == 7.0
    assert pub.get()["window"] == 8


# --- routes (no exporter, synthetic snapshots) --------------------------

def _snap(window=3, records=10.0):
    report = {
        "Records": records, "Bytes": 1000.0, "DistinctSrcEstimate": 4.0,
        "HeavyHitters": [
            {"SrcAddr": "10.0.0.1", "DstAddr": "10.0.0.2", "SrcPort": 1,
             "DstPort": 443, "Proto": 6, "EstBytes": 900.0}],
        "DdosSuspectBuckets": [], "SynFloodSuspectBuckets": [],
        "PortScanSuspectBuckets": [], "DropAnomalyBuckets": [],
        "AsymmetricConversationBuckets": [],
        "FlowAscents": [{"SrcAddr": "10.0.5.9", "Ratio": 16.0, "Key": "k"}],
        "FlowDescents": [], "NewHeavyKeys": [], "EvictedKeys": [],
        "HeavyChurn": {"ascents": 1, "descents": 0, "new": 0,
                       "evictions": 2.0, "tracked": 1},
    }
    return {"window": window, "ts_ms": 123, "seq": 5, "report": report,
            "cm_bytes": np.ones((2, 1 << 10), np.float32),
            "cm_pkts": np.ones((2, 1 << 10), np.float32)}


def test_routes_dispatch_and_metrics_labels():
    m = Metrics()
    snap = _snap()
    qr = QueryRoutes(lambda: snap, lambda: {"published": True}, metrics=m)

    code, body = qr.handle("/query/topk", {"n": "1"})
    assert code == 200
    assert body["window"] == 3 and body["seq"] == 5
    assert body["topk"][0]["DstPort"] == 443

    # /query/topk carries the SAME CM error bars /query/frequency renders
    # (slot counts are CM point estimates; one bar-math helper in core)
    assert body["overestimate_bound_bytes"] == pytest.approx(np.e)
    assert 0 < body["confidence"] < 1

    code, body = qr.handle("/query/churn", {})
    assert code == 200 and body["window"] == 3
    assert body["ascents"] == [{"SrcAddr": "10.0.5.9", "Ratio": 16.0,
                                "Key": "k"}]
    assert body["summary"]["evictions"] == 2.0
    assert body["overestimate_bound_bytes"] == pytest.approx(np.e)

    code, body = qr.handle("/query/cardinality", {})
    assert code == 200 and body["distinct_src_estimate"] == 4.0

    code, body = qr.handle("/query/victims", {})
    assert code == 200 and body["syn_flood"] == []

    code, body = qr.handle("/query/status", {})
    assert code == 200 and body["published"] is True

    code, body = qr.handle("/query/frequency", {"src": "10.0.0.1"})
    assert code == 400  # dst missing

    code, body = qr.handle("/query/frequency",
                           {"src": "10.0.0.1", "dst": "10.0.0.2",
                            "dst_port": "443", "proto": "6"})
    assert code == 200
    # d=2/w=1024 all-ones planes: est = 1, bound = (e/w) * sum(row0)
    assert body["est_bytes"] == 1.0
    assert body["overestimate_bound_bytes"] == pytest.approx(np.e)
    assert 0 < body["confidence"] < 1

    code, body = qr.handle("/query/topk", {"n": "bogus"})
    assert code == 400  # malformed params are the caller's fault, not a 500

    code, body = qr.handle("/query/nope", {})
    assert code == 404 and "routes" in body

    code, body = qr.handle("/query", {})
    assert code == 200 and "/query/topk" in body["routes"]

    text = generate_latest(m.registry).decode()
    assert 'query_requests_total{result="ok",route="topk"} 1.0' in text
    assert 'query_requests_total{result="bad_request",route="frequency"}' \
        in text
    assert 'query_requests_total{result="not_found",route="nope"} 1.0' in text


def test_routes_no_snapshot_and_sharded_planes():
    qr = QueryRoutes(lambda: None, dict)
    for route in ("topk", "frequency", "cardinality", "victims"):
        code, body = qr.handle(f"/query/{route}", {"src": "1.1.1.1",
                                                   "dst": "2.2.2.2"})
        assert code == 503, route
    # a width-sharded mesh's snapshot holds [shards, depth, width / shards]
    # planes: frequency answers from the key's OWNER shard at the local
    # width, with the bar of that shard's own mass and width
    snap = _snap()
    planes = np.stack([np.full((2, 1 << 9), 1.0, np.float32),
                       np.full((2, 1 << 9), 3.0, np.float32)])
    snap["cm_bytes"] = snap["cm_pkts"] = planes
    qr = QueryRoutes(lambda: snap, dict)
    code, body = qr.handle("/query/topk", {})
    # the widest shard's bound covers every rendered heavy hitter
    assert code == 200
    assert body["overestimate_bound_bytes"] == pytest.approx(3 * np.e)
    code, body = qr.handle("/query/frequency",
                           {"src": "1.1.1.1", "dst": "2.2.2.2"})
    assert code == 200 and body["width"] == 1 << 9
    assert body["shard"] in (0, 1)
    mass = (1.0, 3.0)[body["shard"]]
    assert body["est_bytes"] == mass
    assert body["overestimate_bound_bytes"] == pytest.approx(mass * np.e)


def test_routes_survive_raising_status():
    """The query surface must keep answering: a raising status_fn is a 500
    JSON error, never an unhandled exception, and counted as error."""
    m = Metrics()

    def boom():
        raise RuntimeError("no status for you")

    qr = QueryRoutes(lambda: None, boom, metrics=m)
    code, body = qr.handle("/query/status", {})
    assert code == 500 and "no status for you" in body["error"]
    text = generate_latest(m.registry).decode()
    assert 'query_requests_total{result="error",route="status"} 1.0' in text


# --- exporter integration ----------------------------------------------

def test_roll_publishes_snapshot_with_tables():
    m = Metrics()
    exp = make_exporter(metrics=m)
    try:
        exp.export_evicted(EvictedFlows(make_events(32, nbytes=500)))
        exp.flush()
        snap = exp.query.get()
        assert snap is not None and not snap["mid_window"]
        assert snap["report"]["Records"] == 32.0
        assert snap["cm_bytes"].shape == (2, 1 << 10)
        # the snapshot is HOST-side numpy, not device arrays
        assert isinstance(snap["cm_bytes"], np.ndarray)
        # routed frequency answers over the same snapshot: 32 rows of one
        # src/dst pair, each 500B + per-flow overhead goes to one CM cell
        code, body = exp.query_routes.handle(
            "/query/frequency", {"src": "10.0.0.1", "dst": "10.0.0.2",
                                 "src_port": "1000", "dst_port": "443",
                                 "proto": "6"})
        assert code == 200
        assert body["est_bytes"] >= 500.0  # CM never underestimates
        st = exp.query_status()
        assert st["records"] == 32.0 and st["window_s"] == 3600.0
    finally:
        exp.close()


def test_sink_report_keeps_64_heavy_rows_while_the_snapshot_keeps_all():
    """/query/topk promises `?n=` up to the slot table, so the snapshot's
    report renders all of it; a sink's copy stays at REPORT_HEAVY rows."""
    from netobserv_tpu.exporter import tpu_sketch
    from netobserv_tpu.query.core import topk_payload

    snap = _snap()
    row = snap["report"]["HeavyHitters"][0]
    snap["report"]["HeavyHitters"] = [
        {**row, "EstBytes": 900.0 - i} for i in range(100)]
    sunk = tpu_sketch._for_sink(snap["report"])
    assert len(sunk["HeavyHitters"]) == tpu_sketch.REPORT_HEAVY == 64
    served = topk_payload(snap, n=1024)["topk"]
    assert len(served) == 100 and served[:64] == sunk["HeavyHitters"]
    small = _snap()["report"]
    assert tpu_sketch._for_sink(small) is small


def test_snapshot_age_grows_without_refresh_and_resets_at_roll():
    m = Metrics()
    exp = make_exporter(metrics=m)
    try:
        exp.export_evicted(EvictedFlows(make_events(4)))
        exp.flush()
        age0 = exp.query.age_s()
        time.sleep(0.25)
        # refresh disabled: nothing publishes between rolls — the gauge
        # (wired to age_s via set_function) grows
        grown = exp.query.age_s()
        assert grown >= age0 + 0.2
        # the gauge is function-wired to the publisher's clock
        line = [l for l in generate_latest(m.registry).decode().splitlines()
                if "query_snapshot_age_seconds " in l
                and not l.startswith("#")][0]
        assert float(line.split()[1]) == pytest.approx(exp.query.age_s(),
                                                       abs=0.2)
        exp.flush()  # roll -> publish -> age resets
        assert exp.query.age_s() < 0.2
    finally:
        exp.close()


def test_query_snapshot_fault_never_stalls_exports_or_loses_report():
    """An armed sketch.query_snapshot crash: the window report still
    reaches the sink, export_evicted keeps landing, the error is counted,
    and /query keeps serving the PREVIOUS snapshot."""
    m = Metrics()
    reports: list[dict] = []
    exp = make_exporter(metrics=m, sink=reports.append)
    try:
        exp.export_evicted(EvictedFlows(make_events(8)))
        exp.flush()
        assert len(reports) == 1 and exp.query.get() is not None
        seq_before = exp.query.get()["seq"]

        faultinject.arm("sketch.query_snapshot", "crash", times=1)
        exp.export_evicted(EvictedFlows(make_events(16)))
        exp.flush()
        # report published despite the snapshot crash
        assert len(reports) == 2 and reports[1]["Records"] == 16.0
        # /query still serves the previous window's snapshot
        snap = exp.query.get()
        assert snap["seq"] == seq_before
        assert snap["report"]["Records"] == 8.0
        text = generate_latest(m.registry).decode()
        assert ('errors_total{component="tpu-sketch-query",'
                'severity="error"} 1.0') in text

        # next window publishes normally again
        exp.export_evicted(EvictedFlows(make_events(4)))
        exp.flush()
        assert exp.query.get()["seq"] > seq_before
        assert len(reports) == 3
    finally:
        exp.close()


def test_query_snapshot_point_zero_cost_when_unset():
    """Like every stage-boundary point: unset FAULT_POINTS means the fire
    is a dict-miss no-op (the shared zero-cost bar)."""
    assert not faultinject.armed("sketch.query_snapshot")
    t0 = time.perf_counter()
    for _ in range(10_000):
        faultinject.fire("sketch.query_snapshot")
    assert time.perf_counter() - t0 < 0.5


# --- seq-field torn-read poller under concurrent rolls ------------------

def test_poller_never_sees_torn_snapshot_under_concurrent_rolls():
    """A reader hammering the snapshot while windows roll concurrently:
    every observed snapshot is internally consistent (its report IS its
    window's) and (window, seq) only moves forward."""
    exp = make_exporter(window_s=3600.0)
    stop = threading.Event()
    seen: list[tuple[int, int, float]] = []
    errors: list[str] = []

    def poll():
        last = (-1, -1)
        while not stop.is_set():
            snap = exp.query.get()
            if snap is None:
                continue
            key = (snap["window"], snap["seq"])
            # internal consistency: the stamped window is the report's
            if snap["window"] != snap["report"]["Window"]:
                errors.append(f"torn: {snap['window']} vs "
                              f"{snap['report']['Window']}")
            if key < last:
                errors.append(f"went backwards: {last} -> {key}")
            if key != last:
                seen.append((*key, snap["report"]["Records"]))
            last = key

    t = threading.Thread(target=poll, daemon=True)
    try:
        t.start()
        for i in range(12):
            exp.export_evicted(EvictedFlows(make_events(8 + i)))
            exp.flush()
    finally:
        stop.set()
        t.join(timeout=10)
        exp.close()
    assert not errors, errors[:5]
    assert len(seen) >= 10  # the poller actually observed the churn


# --- mid-window refresh -------------------------------------------------

def test_mid_window_refresh_serves_live_window_without_roll():
    """SKETCH_QUERY_REFRESH: the live (un-rolled) window becomes queryable
    (mid_window=True), the real roll later carries the SAME totals (the
    refresh never perturbs state), and no post-warmup retrace fires."""
    reports: list[dict] = []
    exp = make_exporter(sink=reports.append, window_s=3600.0,
                        query_refresh_s=0.2)
    try:
        exp.export_evicted(EvictedFlows(make_events(24, nbytes=100)))
        deadline = time.monotonic() + 20
        snap = None
        while time.monotonic() < deadline:
            snap = exp.query.get()
            if snap is not None and snap["report"]["Records"] == 24.0:
                break
            time.sleep(0.05)
        assert snap is not None and snap["mid_window"]
        assert snap["report"]["Records"] == 24.0
        assert not reports  # no window closed yet
        before = retrace.total_retraces()
        st = exp.query_status()
        assert st["mid_window_refreshes"] >= 1
        # the roll publishes the same window with the same totals
        exp.flush()
        assert reports and reports[0]["Records"] == 24.0
        final = exp.query.get()
        assert not final["mid_window"]
        assert retrace.total_retraces() == before
    finally:
        exp.close()


def test_refresh_disabled_is_structurally_absent():
    """The zero-cost bar for the disabled path: no refresh schedule exists
    (one is-None check on the timer), and nothing ever publishes between
    rolls."""
    exp = make_exporter()  # query_refresh_s defaults to 0
    try:
        assert exp._next_refresh is None
        exp.export_evicted(EvictedFlows(make_events(4)))
        time.sleep(0.5)  # several timer ticks
        assert exp.query.get() is None  # nothing published without a roll
    finally:
        exp.close()


# --- HTTP wiring on the metrics server ----------------------------------

def _http_get(srv, path):
    port = srv.server_address[1]
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=5) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as err:
        body = err.read()
        try:
            return err.code, json.loads(body)
        except json.JSONDecodeError:
            return err.code, {}


def test_metrics_server_serves_query_routes():
    m = Metrics()
    exp = make_exporter(metrics=m)
    srv = start_metrics_server(m.registry, "127.0.0.1", 0,
                               query_routes=exp.query_routes)
    try:
        code, body = _http_get(srv, "/query/topk")
        assert code == 503  # no window yet
        exp.export_evicted(EvictedFlows(make_events(16, nbytes=300)))
        exp.flush()
        code, body = _http_get(srv, "/query/topk?n=5")
        assert code == 200 and len(body["topk"]) >= 1
        code, body = _http_get(srv, "/query/status")
        assert code == 200 and body["records"] == 16.0
        code, body = _http_get(srv, "/query/frequency?src=10.0.0.1"
                                    "&dst=10.0.0.2&src_port=1000"
                                    "&dst_port=443&proto=6")
        assert code == 200 and body["est_bytes"] >= 300.0
        code, body = _http_get(srv, "/query")
        assert code == 200 and "/query/victims" in body["routes"]
    finally:
        srv.shutdown()
        exp.close()


def test_metrics_server_404_without_query_source():
    m = Metrics()
    srv = start_metrics_server(m.registry, "127.0.0.1", 0)
    try:
        code, _body = _http_get(srv, "/query/topk")
        assert code == 404
    finally:
        srv.shutdown()


# --- back-scroll ring (ISSUE 11): point-in-time reads of closed windows --

def test_publisher_history_keeps_closed_windows_only():
    pub = SnapshotPublisher(history=3)
    for w in (1, 2, 3, 4):
        pub.publish(_snap(window=w))
        # mid-window refreshes are the LIVE view, never history
        pub.publish(_snap(window=w + 1), mid_window=True)
    assert pub.windows() == [2, 3, 4]  # cap 3: window 1 evicted
    assert pub.get_window(1) is None
    assert pub.get_window(3)["window"] == 3
    assert pub.get_window(3)["mid_window"] is False
    st = pub.stats()
    assert st["history_cap"] == 3
    assert st["history_windows"] == [2, 3, 4]


def test_publisher_history_republish_keeps_final_roll():
    """A window id rolled twice (refresh-then-roll share ids too) keeps
    the LATEST roll snapshot and moves it to the newest ring slot."""
    pub = SnapshotPublisher(history=2)
    pub.publish(_snap(window=7, records=1.0))
    pub.publish(_snap(window=8, records=2.0))
    pub.publish(_snap(window=7, records=99.0))  # re-publish
    assert pub.windows() == [8, 7]
    assert pub.get_window(7)["report"]["Records"] == 99.0


def test_publisher_history_disabled_by_default():
    pub = SnapshotPublisher()
    pub.publish(_snap(window=1))
    assert pub.windows() == []
    assert pub.get_window(1) is None


def test_routes_window_param_serves_ring_and_404s_evicted():
    m = Metrics()
    pub = SnapshotPublisher(history=2)
    pub.publish(_snap(window=5, records=50.0))
    pub.publish(_snap(window=6, records=60.0))
    live = _snap(window=7, records=70.0)
    pub.publish(live)
    qr = QueryRoutes(pub.get, lambda: {"published": True}, metrics=m,
                     history_fn=pub.get_window, windows_fn=pub.windows)
    # no param: the live snapshot
    code, body = qr.handle("/query/cardinality", {})
    assert code == 200 and body["records"] == 70.0
    # point-in-time read of a past closed window
    code, body = qr.handle("/query/cardinality", {"window": "6"})
    assert code == 200 and body["records"] == 60.0 and body["window"] == 6
    code, body = qr.handle("/query/topk", {"window": "6", "n": "1"})
    assert code == 200 and body["window"] == 6
    code, body = qr.handle(
        "/query/frequency",
        {"window": "6", "src": "10.0.0.1", "dst": "10.0.0.2"})
    assert code == 200
    # evicted (cap 2 kept 6 and 7) and never-seen ids: 404 + discovery
    for wid in ("5", "99"):
        code, body = qr.handle("/query/victims", {"window": wid})
        assert code == 404
        assert body["windows"] == [6, 7]
    # malformed id is the caller's fault
    code, _ = qr.handle("/query/topk", {"window": "bogus"})
    assert code == 400
    text = generate_latest(m.registry).decode()
    assert 'query_requests_total{result="not_found",route="victims"} 2.0' \
        in text


def test_routes_window_param_without_ring_404s():
    qr = QueryRoutes(lambda: _snap(), lambda: {})
    code, body = qr.handle("/query/topk", {"window": "3"})
    assert code == 404 and body["windows"] == []


def test_exporter_back_scroll_end_to_end():
    """Three rolled windows through a real exporter: every id in the ring
    answers point-in-time with ITS window's data; /query/status lists the
    ring."""
    exp = make_exporter(query_history=4)
    try:
        seen = []
        for i in range(3):
            exp.export_evicted(
                EvictedFlows(make_events(32 * (i + 1), nbytes=100)))
            exp.flush()
            seen.append(exp.query.get()["window"])
        assert exp.query.windows() == seen  # oldest first, all retained
        for i, wid in enumerate(seen):
            code, body = exp.query_routes.handle(
                "/query/cardinality", {"window": str(wid)})
            assert code == 200
            assert body["records"] == 32.0 * (i + 1)
            assert body["window"] == wid
        st = exp.query_status()
        assert st["history_windows"] == seen
        # an id never rolled answers 404 with the discovery list
        code, body = exp.query_routes.handle(
            "/query/topk", {"window": str(max(seen) + 1000)})
        assert code == 404 and body["windows"] == seen
    finally:
        exp.close()
