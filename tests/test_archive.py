"""Sketch warehouse (netobserv_tpu/archive): segment store retention,
device-merged range queries vs the union-roll oracle, compaction accuracy,
exporter wiring, and the wedged-disk failure mode.

The load-bearing acceptance claims (ISSUE 15):

- a range query over any contiguous set of RAW archived windows is
  BIT-EXACT against the union roll of their flows (CM planes, histograms,
  rates, HLL registers, totals), with the slot table pinned against the
  table-merge replay oracle (the chaos-suite rule: a set-associative
  table under congestion is path-dependent, so its oracle is the merge
  replay, never the raw-flow union);
- compacted (super-window) ranges stay within the widened CM error bars
  (one-sided overestimate over the merged mass);
- ARCHIVE_DIR unset means NO archive object exists (the zero-cost bar);
- zero post-warmup retraces across the range-merge ladder
  (watchdog-verified);
- a wedged archive disk never stalls ingest and never loses a window
  report (the sketch.archive_write fault point).
"""

from __future__ import annotations

import json

import numpy as np
import pytest

import tests.conftest  # noqa: F401  (forces the CPU backend)

from netobserv_tpu.archive import (
    ArchiveStore, SketchArchive, maybe_archive,
)
from netobserv_tpu.archive.store import segment_filename
from netobserv_tpu.federation import delta as fdelta
from netobserv_tpu.metrics.registry import Metrics
from netobserv_tpu.sketch import state as sk
from netobserv_tpu.utils import faultinject, retrace
from tests.test_federation import CFG, make_arrays


@pytest.fixture(autouse=True)
def _clean_faults():
    yield
    faultinject.clear()
    faultinject.hits.clear()


def build_windows(n_windows, tmp_path, rng_seed=7, batches_per_window=2,
                  raw_windows=64, compact_group=8, max_levels=3,
                  ladder_max=16, metrics=None, n_keys=40):
    """Fold `n_windows` synthetic windows through a real roll, archiving
    each; returns (archive, per-window segment tables in window order,
    per-window batch lists) so tests can build both oracles."""
    rng = np.random.default_rng(rng_seed)
    # <= topk distinct keys: the top-K truncates nowhere, so every merge
    # order carries the same key set (the test_federation pattern)
    universe = rng.integers(0, 2**32, (n_keys, 10), dtype=np.uint32)
    roll = sk.make_roll_fn(CFG, with_tables=True)
    store = ArchiveStore(str(tmp_path), raw_windows=raw_windows,
                         compact_group=compact_group,
                         max_levels=max_levels, metrics=metrics)
    arch = SketchArchive(store, CFG, metrics=metrics, agent_id="t",
                         ladder_max=ladder_max)
    s = sk.init_state(CFG)
    window_tables, window_batches = [], []
    for w in range(n_windows):
        batches = [make_arrays(rng, universe)
                   for _ in range(batches_per_window)]
        for arrays in batches:
            s = sk.ingest(s, arrays)
        s, _, tables = roll(s)
        host = {k: np.asarray(v) for k, v in tables.items()}
        arch.write_window(host, window=w, ts_ms=1_000 + w)
        window_tables.append(host)
        window_batches.append(batches)
    return arch, window_tables, window_batches


def union_state(batch_lists):
    union = sk.init_state(CFG)
    for batches in batch_lists:
        for arrays in batches:
            union = sk.ingest(union, arrays)
    return union


def replay_tables(table_dicts):
    """The table-merge replay oracle: fold the window snapshots, in
    order, through the same statemerge primitive the ladder jits."""
    import jax.numpy as jnp

    from netobserv_tpu.federation import statemerge
    state = sk.init_state(CFG)
    for tabs in table_dicts:
        state = statemerge.merge_tables(
            state, {k: jnp.asarray(np.ascontiguousarray(v))
                    for k, v in tabs.items()})
    return state


def heavy_entries(heavy_arrays):
    words = np.asarray(heavy_arrays["words"])
    valid = np.asarray(heavy_arrays["valid"])
    counts = np.asarray(heavy_arrays["counts"])
    return {(words[i].tobytes(), float(counts[i]))
            for i in range(len(valid)) if valid[i]}


# --- store mechanics (host-side, no device) -----------------------------

def test_store_append_select_and_manifest(tmp_path):
    store = ArchiveStore(str(tmp_path), raw_windows=4, compact_group=2)
    for w in range(3):
        store.append(b"x" * (10 + w), 0, w, w)
    assert [s.window_from for s in store.select(1, 2)] == [1, 2]
    assert store.select(5, 9) == []
    assert store.total_bytes() == 10 + 11 + 12
    manifest = json.load(open(tmp_path / "MANIFEST.json"))
    assert len(manifest["segments"]) == 3
    # reopen: the scan rebuilds the same index
    store2 = ArchiveStore(str(tmp_path), raw_windows=4, compact_group=2)
    assert [s.name for s in store2.segments()] == \
        [s.name for s in store.segments()]


def test_store_torn_manifest_is_healed_by_scan(tmp_path):
    """The manifest is a cache, the directory scan is the truth: a torn
    MANIFEST.json (crash mid-write in a pre-atomicio world) must not lose
    the archive."""
    store = ArchiveStore(str(tmp_path), raw_windows=4, compact_group=2)
    store.append(b"payload", 0, 7, 7)
    (tmp_path / "MANIFEST.json").write_text('{"segments": [{"trunc')
    store2 = ArchiveStore(str(tmp_path), raw_windows=4, compact_group=2)
    assert [s.window_from for s in store2.segments()] == [7]
    json.load(open(tmp_path / "MANIFEST.json"))  # rewritten whole


def test_store_crash_mid_replace_heals_to_higher_level(tmp_path):
    """replace() lands the merged super-window BEFORE deleting its inputs;
    the open-time scan must heal the overlap by keeping the HIGHER level
    (the merged segment contains the shadowed windows)."""
    store = ArchiveStore(str(tmp_path), raw_windows=2, compact_group=2)
    for w in range(2):
        store.append(b"raw", 0, w, w)
    # simulate the crash: the compacted L1 segment landed, inputs survive
    (tmp_path / segment_filename(1, 0, 1)).write_bytes(b"merged")
    healed = ArchiveStore(str(tmp_path), raw_windows=2, compact_group=2)
    segs = healed.segments()
    assert [(s.level, s.window_from, s.window_to) for s in segs] == \
        [(1, 0, 1)]
    assert not (tmp_path / segment_filename(0, 0, 0)).exists()


def test_store_restarted_window_counter_newest_wins(tmp_path):
    """An agent whose window counter restarted at 0 (no checkpoint dir)
    re-appends old window ids: append's intersection sweep must retire
    the stale incarnation's history — one segment per window id, never a
    double-indexed range (a double entry would double-count every
    /query/range over it) and never a stale super-window shadowing the
    fresh raw segment at the next open-time heal."""
    store = ArchiveStore(str(tmp_path), raw_windows=4, compact_group=2)
    store.append(b"old-0", 0, 0, 0)
    store.append(b"old-1", 0, 1, 1)
    store.replace(store.segments(), b"old-merged", 1, 0, 1)
    assert [(s.level, s.window_from, s.window_to)
            for s in store.segments()] == [(1, 0, 1)]
    # the restarted incarnation writes window 0 again: the stale
    # super-window intersects it and is forfeit (newest write wins)
    store.append(b"new-0", 0, 0, 0)
    assert [(s.level, s.window_from, s.window_to)
            for s in store.segments()] == [(0, 0, 0)]
    assert store.read(store.segments()[0]) == b"new-0"
    # same-id rewrite: one index entry, the newer bytes
    store.append(b"new-0b", 0, 0, 0)
    assert len(store.segments()) == 1
    assert store.read(store.segments()[0]) == b"new-0b"
    # a reopen sees the same single-coverage view (no heal deletions)
    store2 = ArchiveStore(str(tmp_path), raw_windows=4, compact_group=2)
    assert [(s.level, s.window_from) for s in store2.segments()] == \
        [(0, 0)]


def test_store_pending_compaction_and_top_level_retention(tmp_path):
    store = ArchiveStore(str(tmp_path), raw_windows=2, compact_group=2,
                         max_levels=1)
    for w in range(4):
        store.append(b"s", 0, w, w)
        if store.pending_compaction() is not None:
            level, group = store.pending_compaction()
            assert level == 0 and len(group) == 2
            store.replace(group, b"m", 1, group[0].window_from,
                          group[-1].window_to)
    # level 1 IS max_levels: it never compacts, only ages out
    for w in range(4, 12):
        store.append(b"s", 0, w, w)
        while store.pending_compaction() is not None:
            level, group = store.pending_compaction()
            store.replace(group, b"m", level + 1, group[0].window_from,
                          group[-1].window_to)
        store.enforce_top_level_retention()
    top = [s for s in store.segments() if s.level == 1]
    assert len(top) <= 2  # the cap held
    assert len(store.segments()) <= 2 + 2 + 1  # bounded overall


# --- range queries vs the union-roll oracle (the acceptance claim) ------

def test_raw_range_bit_exact_vs_union_roll(tmp_path):
    arch, tables, batches = build_windows(4, tmp_path)
    snap = arch.engine.range_snapshot(0, 3)
    union = union_state(batches)
    np.testing.assert_array_equal(snap["cm_bytes"],
                                  np.asarray(union.cm_bytes.counts))
    np.testing.assert_array_equal(snap["cm_pkts"],
                                  np.asarray(union.cm_pkts.counts))
    rep = snap["report"]
    assert rep["Records"] == float(union.total_records)
    assert rep["Bytes"] == float(union.total_bytes)
    assert rep["DropBytes"] == float(union.total_drop_bytes)
    assert rep["QuicRecords"] == float(union.quic_records)
    # distinct-source estimate flows from bit-equal HLL registers
    import jax.numpy as jnp  # noqa: F401
    from netobserv_tpu.ops import hll
    assert rep["DistinctSrcEstimate"] == float(
        np.asarray(hll.estimate(union.hll_src.regs)))
    # slot table: the table-merge replay oracle, full-array bit-exact
    # (single dispatch merges in the replay's exact order)
    oracle = replay_tables(tables)
    ladder_fit = arch.engine._ladder_fit(4)
    assert ladder_fit == 4  # one dispatch, no chaining
    merged = arch.engine.range_snapshot(0, 3)  # re-run is deterministic
    report_entries = {(e["SrcAddr"], e["DstAddr"], e["SrcPort"],
                       e["DstPort"], e["Proto"], e["EstBytes"])
                      for e in merged["report"]["HeavyHitters"]}
    from netobserv_tpu.exporter.tpu_sketch import report_to_json
    _, oracle_report = sk.roll_window(oracle, CFG)
    oracle_entries = {(e["SrcAddr"], e["DstAddr"], e["SrcPort"],
                       e["DstPort"], e["Proto"], e["EstBytes"])
                      for e in report_to_json(
                          oracle_report)["HeavyHitters"]}
    assert report_entries == oracle_entries


def test_partial_range_pads_ladder_and_stays_exact(tmp_path):
    """3 segments pad to the 4-wide ladder entry with ZERO tables — the
    exact merge identity, so the padded dispatch equals the 3-window
    union bit-for-bit."""
    arch, tables, batches = build_windows(5, tmp_path)
    snap = arch.engine.range_snapshot(1, 3)
    assert snap["range"]["segments_merged"] == 3
    union = union_state(batches[1:4])
    np.testing.assert_array_equal(snap["cm_bytes"],
                                  np.asarray(union.cm_bytes.counts))
    assert snap["report"]["Records"] == float(union.total_records)
    # slot table vs the replay oracle of exactly those windows' tables
    oracle = replay_tables(tables[1:4])
    np.testing.assert_array_equal(
        np.asarray(oracle.cm_bytes.counts), snap["cm_bytes"])
    got = heavy_entries({"words": np.zeros((0, 10), np.uint32),
                         "valid": np.zeros(0, bool),
                         "counts": np.zeros(0)})
    assert got == set()  # helper sanity on the empty case
    want = heavy_entries({"words": oracle.heavy.words,
                          "valid": oracle.heavy.valid,
                          "counts": oracle.heavy.counts})
    have = {(e["SrcAddr"], e["DstAddr"], e["SrcPort"], e["DstPort"],
             e["Proto"]) for e in snap["report"]["HeavyHitters"]}
    assert len(want) == len(snap["report"]["HeavyHitters"]) == len(have)


def test_chained_range_beyond_ladder_max_stays_exact(tmp_path):
    """Ranges wider than the ladder CHAIN dispatches (merged tables
    re-enter as an input). Linear/max structures stay bit-exact against
    the union (integer-valued f32 sums are order-independent); the slot
    table keeps the oracle's key set and final CM-scored counts."""
    arch, tables, batches = build_windows(5, tmp_path, ladder_max=2)
    snap = arch.engine.range_snapshot(0, 4)
    assert snap["range"]["merge_dispatches"] > 1
    union = union_state(batches)
    np.testing.assert_array_equal(snap["cm_bytes"],
                                  np.asarray(union.cm_bytes.counts))
    assert snap["report"]["Records"] == float(union.total_records)
    oracle = replay_tables(tables)
    got = {(e["SrcAddr"], e["SrcPort"], e["EstBytes"])
           for e in snap["report"]["HeavyHitters"]}
    from netobserv_tpu.exporter.tpu_sketch import report_to_json
    _, oracle_report = sk.roll_window(oracle, CFG)
    want = {(e["SrcAddr"], e["SrcPort"], e["EstBytes"])
            for e in report_to_json(oracle_report)["HeavyHitters"]}
    assert got == want


def test_compacted_range_within_widened_cm_bars(tmp_path):
    """After compaction the range rides super-windows: every per-key CM
    estimate must stay one-sided within the widened bound — true count <=
    estimate <= true + (e/w) * merged mass (the additive-error-counter
    property the warehouse leans on)."""
    rng = np.random.default_rng(11)
    universe = rng.integers(0, 2**32, (40, 10), dtype=np.uint32)
    roll = sk.make_roll_fn(CFG, with_tables=True)
    store = ArchiveStore(str(tmp_path), raw_windows=2, compact_group=2,
                         max_levels=2)
    arch = SketchArchive(store, CFG, agent_id="t", ladder_max=4)
    s = sk.init_state(CFG)
    true_bytes: dict[bytes, float] = {}
    for w in range(9):
        for _ in range(2):
            arrays = make_arrays(rng, universe)
            s = sk.ingest(s, arrays)
            for i in range(len(arrays["bytes"])):
                key = arrays["keys"][i].tobytes()
                true_bytes[key] = true_bytes.get(key, 0.0) \
                    + float(arrays["bytes"][i])
        s, _, tables = roll(s)
        arch.write_window({k: np.asarray(v) for k, v in tables.items()},
                          window=w, ts_ms=1_000 + w)
    assert any(seg.level > 0 for seg in store.segments())
    snap = arch.engine.range_snapshot(0, 8)
    assert snap["range"]["compacted"]
    cm = snap["cm_bytes"]
    d, w_ = cm.shape
    bound = np.e / w_ * float(np.sum(cm[0]))
    from netobserv_tpu.ops.hashing import base_hashes_multi_np
    h = base_hashes_multi_np(universe)
    for j, key in enumerate(universe):
        with np.errstate(over="ignore"):
            idx = (h["h1"][j]
                   + np.arange(d, dtype=np.uint32) * h["h2"][j]) \
                & np.uint32(w_ - 1)
        est = float(np.min(cm[np.arange(d), idx]))
        true = true_bytes.get(key.tobytes(), 0.0)
        assert true <= est + 1e-3, (j, true, est)
        assert est <= true + bound + 1e-3, (j, true, est, bound)
    # totals stay exact through compaction (pure sums)
    assert snap["report"]["Records"] == 9 * 2 * 32


def test_merged_record_totals_pass_2_31_without_wrapping(tmp_path):
    """Range queries and compactions add up many windows' counts: past
    2^31 records the merged total stays f32 (approximate past 2^24, never
    wraps), through chained dispatches and through the compacted segment
    written back for the next level."""
    rng = np.random.default_rng(13)
    universe = rng.integers(0, 2**32, (40, 10), dtype=np.uint32)
    roll = sk.make_roll_fn(CFG, with_tables=True)
    store = ArchiveStore(str(tmp_path), raw_windows=2, compact_group=2,
                         max_levels=2)
    arch = SketchArchive(store, CFG, agent_id="t", ladder_max=2)
    s = sk.init_state(CFG)
    per_window = float(3 << 29)  # 1.61e9 records, exact in f32
    for w in range(6):
        s = sk.ingest(s, make_arrays(rng, universe))
        s, _, tables = roll(s)
        host = {k: np.array(v) for k, v in tables.items()}
        host["scalars"][0] = per_window
        arch.write_window(host, window=w, ts_ms=1_000 + w)
    assert any(seg.level > 0 for seg in store.segments())
    snap = arch.engine.range_snapshot(0, 5)
    assert snap["range"]["compacted"]
    assert snap["range"]["merge_dispatches"] > 1
    assert snap["report"]["Records"] == 6 * per_window


def test_zero_retraces_across_ladder_and_compaction(tmp_path):
    """Watchdog-verified: every ladder entry compiles exactly once (its
    warmup call), across range queries of every size AND compactions —
    padding keeps shapes fixed, so nothing ever retraces."""
    arch, _tables, _batches = build_windows(
        9, tmp_path, raw_windows=2, compact_group=2, max_levels=2,
        ladder_max=4)
    for rng in ((0, 0), (0, 2), (0, 5), (0, 8), (3, 8)):
        code, _ = arch.route_payload({"from": str(rng[0]),
                                      "to": str(rng[1])})
        assert code == 200
    arch.engine.warm()  # idempotent: everything is already compiled
    watched = {w["fn"]: w for w in retrace.snapshot()
               if w["fn"].startswith("archive_merge_x")}
    assert watched, "ladder entries were never watched"
    for fn, w in watched.items():
        assert w["retraces"] == 0, w
        assert w["compiles"] <= 1, w


# --- route surface -------------------------------------------------------

def test_route_payload_views_and_errors(tmp_path):
    metrics = Metrics()
    arch, _t, _b = build_windows(3, tmp_path, metrics=metrics)
    code, body = arch.route_payload({"from": "0", "to": "2"})
    assert code == 200 and body["range"]["windows_merged"] == 3
    assert "overestimate_bound_bytes" in body
    code, body = arch.route_payload({"from": "0", "to": "2"}, "topk")
    assert code == 200 and body["topk"]
    code, body = arch.route_payload(
        {"from": "0", "to": "1", "src": "10.0.0.1", "dst": "10.0.0.2"},
        "frequency")
    assert code == 200 and "est_bytes" in body
    code, body = arch.route_payload({"from": "0", "to": "2"}, "victims")
    assert code == 200
    # errors: missing params, empty range, unknown view, uncovered range
    assert arch.route_payload({})[0] == 400
    assert arch.route_payload({"from": "3", "to": "1"})[0] == 400
    assert arch.route_payload({"from": "0", "to": "1"},
                              "bogus")[0] == 404
    code, body = arch.route_payload({"from": "50", "to": "60"})
    assert code == 404 and body["coverage"]
    assert arch.route_payload({"from": "0", "to": "1", "src": "a"},
                              "frequency")[0] == 400
    counts = {}
    for metric in metrics.registry.collect():
        if metric.name == "ebpf_agent_archive_range_requests":
            for s in metric.samples:
                if s.name.endswith("_total"):
                    counts[s.labels["result"]] = s.value
    assert counts["ok"] == 4
    assert counts["bad_request"] == 3
    assert counts["not_found"] == 2


def test_query_routes_range_dispatch(tmp_path):
    from netobserv_tpu.query.routes import QueryRoutes
    arch, _t, _b = build_windows(2, tmp_path)
    routes = QueryRoutes(lambda: None, dict, archive=arch)
    code, body = routes.handle("/query/range",
                               {"from": "0", "to": "1"})
    assert code == 200 and body["range"]["covered"] == [0, 1]
    code, body = routes.handle("/query/range/topk",
                               {"from": "0", "to": "1"})
    assert code == 200 and "topk" in body
    # disabled surface: no archive object exists
    bare = QueryRoutes(lambda: None, dict)
    code, body = bare.handle("/query/range", {"from": "0", "to": "1"})
    assert code == 404 and "ARCHIVE_DIR" in body["error"]


def test_maybe_archive_unset_is_none():
    """The zero-cost bar: ARCHIVE_DIR unset builds NO archive object —
    the exporter publish path keeps one is-None check and nothing else."""
    from netobserv_tpu.config import load_config
    cfg = load_config({"EXPORT": "stdout"})
    assert maybe_archive(cfg, CFG) is None


# --- exporter integration ------------------------------------------------

def exporter_with_archive(tmp_path, metrics=None, sink=None):
    from netobserv_tpu.exporter.tpu_sketch import TpuSketchExporter
    store = ArchiveStore(str(tmp_path), raw_windows=4, compact_group=2)
    arch = SketchArchive(store, CFG, metrics=metrics, agent_id="t",
                         ladder_max=2)
    exp = TpuSketchExporter(batch_size=64, window_s=3600.0,
                            sketch_cfg=CFG, metrics=metrics,
                            sink=sink or (lambda obj: None),
                            archive=arch)
    return exp, arch


def test_exporter_archives_each_closed_window(tmp_path):
    reports = []
    exp, arch = exporter_with_archive(tmp_path, sink=reports.append)
    try:
        exp.flush()  # closes + publishes window 0 (idle windows roll too)
        exp.flush()
        assert len(reports) == 2
        segs = arch.engine._store.segments()
        assert [(s.level, s.window_from) for s in segs] == [(0, 0), (0, 1)]
        code, body = exp.query_routes.handle(
            "/query/range", {"from": "0", "to": "1"})
        assert code == 200 and body["range"]["windows_merged"] == 2
        assert "archive" in exp.query_status()
    finally:
        exp.close()


def test_wedged_archive_disk_never_loses_the_report(tmp_path):
    """The sketch.archive_write fault point: a crashing archive write must
    neither lose the window report (already at the sink) nor poison the
    publish path — counted, next window archives again."""
    metrics = Metrics()
    reports = []
    exp, arch = exporter_with_archive(tmp_path, metrics=metrics,
                                      sink=reports.append)
    try:
        faultinject.arm("sketch.archive_write", "crash", times=1)
        exp.flush()
        assert len(reports) == 1  # the report survived the dead disk
        assert faultinject.hits["sketch.archive_write"] >= 1
        assert not arch.engine._store.segments()  # window 0 not archived
        exp.flush()  # disk "recovered": window 1 archives normally
        assert len(reports) == 2
        assert [s.window_from for s in arch.engine._store.segments()] \
            == [1]
    finally:
        exp.close()


def test_archive_unset_exporter_has_no_archive_object(tmp_path):
    from netobserv_tpu.exporter.tpu_sketch import TpuSketchExporter
    exp = TpuSketchExporter(batch_size=64, window_s=3600.0,
                            sketch_cfg=CFG, sink=lambda obj: None)
    try:
        assert exp._archive is None
        code, body = exp.query_routes.handle("/query/range",
                                             {"from": "0", "to": "1"})
        assert code == 404
    finally:
        exp.close()


# --- federation surface --------------------------------------------------

def test_federation_range_thin_adapter(tmp_path):
    """/federation/range rides the SAME route_payload body builder the
    agent mounts (never forked) — drive it through the aggregator's
    archive attribute exactly as federation/query.py does.

    Deliberately NOT test_federation's CFG geometry: the aggregator jits
    the module-level `statemerge.merge_tables`, and jax's lowering cache
    is shared across jit instances of one function — pre-warming
    test_federation's exact signature from this (alphabetically earlier)
    file would turn its `compiles == 1` watchdog assertion into a stale
    cache hit."""
    from netobserv_tpu.federation.aggregator import FederationAggregator
    my_cfg = CFG._replace(topk=32)
    dims = {"cm_depth": my_cfg.cm_depth, "cm_width": my_cfg.cm_width,
            "hll_precision": my_cfg.hll_precision, "topk": my_cfg.topk,
            "ewma_buckets": my_cfg.ewma_buckets}
    rng = np.random.default_rng(9)
    universe = rng.integers(0, 2**32, (24, 10), dtype=np.uint32)
    roll = sk.make_roll_fn(my_cfg, with_tables=True)
    union = sk.init_state(my_cfg)
    frames = []
    for a in range(2):
        s = sk.init_state(my_cfg)
        arrays = make_arrays(rng, universe)
        s = sk.ingest(s, arrays)
        union = sk.ingest(union, arrays)
        _, _, tables = roll(s)
        frames.append(fdelta.encode_frame(
            {k: np.asarray(v) for k, v in tables.items()},
            agent_id=f"agent-{a}", window=0, ts_ms=1234, dims=dims))
    store = ArchiveStore(str(tmp_path), raw_windows=4, compact_group=2)
    arch = SketchArchive(store, my_cfg, agent_id="federation",
                         ladder_max=2)
    agg = FederationAggregator(sketch_cfg=my_cfg, window_s=3600.0,
                               archive=arch)
    try:
        for data in frames:
            assert agg.ingest_frame(data).accepted == 1
        agg.flush()
        segs = store.segments()
        assert len(segs) == 1 and segs[0].window_from == 0
        code, body = arch.route_payload({"from": "0", "to": "0"})
        assert code == 200
        assert body["records"] == float(union.total_records)
        snap = arch.engine.range_snapshot(0, 0)
        np.testing.assert_array_equal(
            snap["cm_bytes"], np.asarray(union.cm_bytes.counts))
        assert "archive" in agg.status()
    finally:
        agg.close()


# --- retention soak (slow tier) -----------------------------------------

@pytest.mark.slow
def test_retention_soak_bounded_disk_and_accurate_ranges(tmp_path):
    """Many windows through writer + compactor: segment count and disk
    bytes stay bounded by the retention math, compacted range answers
    stay within the widened CM bars, and the whole run keeps zero
    post-warmup retraces across the ladder."""
    raw_windows, group, max_levels = 4, 2, 2
    metrics = Metrics()
    arch, tables, batches = build_windows(
        40, tmp_path, raw_windows=raw_windows, compact_group=group,
        max_levels=max_levels, ladder_max=4, metrics=metrics,
        batches_per_window=1)
    store = arch.engine._store
    # disk bound: each level holds < cap + group segments
    per_level: dict[int, int] = {}
    for s in store.segments():
        per_level[s.level] = per_level.get(s.level, 0) + 1
    for level, n in per_level.items():
        assert n < raw_windows + group, (level, n, per_level)
    assert len(store.segments()) <= (max_levels + 1) \
        * (raw_windows + group - 1)
    seg_bytes = max(s.nbytes for s in store.segments())
    assert store.total_bytes() <= len(store.segments()) * seg_bytes
    # old history survives coarser: window 0 may be gone (top-level cap),
    # but SOME compacted super-window exists and answers
    assert any(s.level > 0 for s in store.segments())
    cov = store.coverage()
    lo = cov[0]["window_from"]
    code, body = arch.route_payload({"from": str(lo), "to": "39"})
    assert code == 200 and body["range"]["compacted"]
    # accuracy: totals of the covered windows are exact sums
    covered_from, covered_to = body["range"]["covered"]
    union = union_state(batches[covered_from:covered_to + 1])
    snap = arch.engine.range_snapshot(covered_from, covered_to)
    np.testing.assert_array_equal(snap["cm_bytes"],
                                  np.asarray(union.cm_bytes.counts))
    assert snap["report"]["Records"] == float(union.total_records)
    # zero post-warmup retraces across the whole soak
    for w in retrace.snapshot():
        if w["fn"].startswith("archive_merge_x"):
            assert w["retraces"] == 0, w
            assert w["compiles"] <= 1, w
    # the counters moved and satisfy the write/consume identity:
    # writes = live segments + compaction inputs consumed + drops >= 0
    collected = {m.name: m for m in metrics.registry.collect()}
    writes = collected["ebpf_agent_archive_segments"].samples[0].value
    compactions = \
        collected["ebpf_agent_archive_compactions"].samples[0].value
    assert compactions > 0
    drops = writes - compactions * store.compact_group \
        - len(store.segments())
    assert drops >= 0, (writes, compactions, len(store.segments()))
    assert collected["ebpf_agent_archive_bytes"].samples[0].value > 0
