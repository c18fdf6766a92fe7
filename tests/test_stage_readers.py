"""The benchmark's readers of the program's own stage clocks and stage
annotations (cellbench/readers: stage_union, stage_clock, idle_by_stage,
stage_gap) and the metric files that name them.

Synthetic captures pin the arithmetic; the recorded fixtures pin the
readers on real captures: `cell1_stages.xplane.pb`, cut with cellbench/
cut_capture.py from a traced run of collector-1chip.zipf-saturate on a TPU
v5 lite by a program with the `export` and `publish` stages, and the older
`cell1_cut.xplane.pb` and `cell2_capture.xplane.pb`, whose programs have no
`export` stage: there every reader of it answers None, never 0."""

from __future__ import annotations

import json
import os
import types

import pytest

from cellbench import capture, xtrace
from cellbench.readers import idle_by_stage, stage_clock, stage_gap, stage_union

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TESTDATA = os.path.join(ROOT, "cellbench", "testdata")
STAGES = os.path.join(TESTDATA, "cell1_stages.xplane.pb")
OLD = {"cell1_cut": os.path.join(TESTDATA, "cell1_cut.xplane.pb"),
       "cell2_capture": os.path.join(TESTDATA, "cell2_capture.xplane.pb")}
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
NEW = ["drain_busy_share", "export_busy_share", "decode_oncpu_share",
       "export_oncpu_share", "decode_stage_s_per_mrec",
       "idle_awaiting_drain_share", "idle_in_pack_share",
       "idle_in_export_glue_share", "publish_stage_s_p50",
       "export_queue_s_p50", "export_stage_s_p50", "evict_to_export_s_p50"]


def spec(name: str) -> dict:
    with open(os.path.join(ROOT, "cellbench", "metrics", name + ".json")) as f:
        return json.load(f)


def reader(name: str):
    import importlib

    return importlib.import_module(f"cellbench.readers.{spec(name)['reader']}")


def fake_cap(stages: dict, lo=0.0, hi=10.0):
    cap = types.SimpleNamespace(lo=lo, hi=hi, stages=stages, devices=[[]])
    cap.in_window = lambda st: [s for s in stages.get(st, ())
                                if s[0] >= lo and s[1] <= hi]
    return cap


def ctx_of(before=None, after=None, records=2e6):
    return types.SimpleNamespace(counters0=before or {}, counters1=after or {},
                                 records=records, notes=[], cpu0=None,
                                 cpu1=None, main_tid=0)


# --- synthetic -------------------------------------------------------------

def test_stage_union_is_the_clipped_union_over_the_window(monkeypatch):
    cap = fake_cap({"evict": [(-1.0, 0.5, {}), (0.25, 2.0, {}),
                              (3.0, 4.0, {}), (9.5, 11.0, {})]})
    monkeypatch.setattr(capture, "of_run", lambda: cap)
    got = stage_union.read(None, {"stage": "evict"})
    assert got == pytest.approx(100.0 * (2.0 + 1.0 + 0.5) / 10.0)
    assert stage_union.read(None, {"stage": "export"}) is None


def test_stage_clock_is_a_ratio_of_two_counter_deltas():
    def snap(wall, cpu, calls):
        out = {("records_total", ()): 1.0}
        for st, w, c, n in zip(("merge_percpu", "align", "export"),
                               wall, cpu, calls):
            out[("stage_wall_seconds_total", (st,))] = w
            out[("stage_cpu_seconds_total", (st,))] = c
            out[("stage_calls_total", (st,))] = n
        return out
    ctx = ctx_of(snap((1.0, 1.0, 5.0), (0.5, 0.5, 1.0), (10, 10, 10)),
                 snap((3.0, 2.0, 9.0), (1.5, 1.1, 3.0), (20, 20, 20)))
    decode = {"stages": ["merge_percpu", "align"], "num": "cpu",
              "den": "wall", "scale": 100.0}
    assert stage_clock.read(ctx, decode) == pytest.approx(100 * 1.6 / 3.0)
    per_mrec = dict(decode, num="wall", den="records", scale=1e6)
    assert stage_clock.read(ctx, per_mrec) == pytest.approx(3.0 / 2.0)
    assert ctx.notes and "merge_percpu" in ctx.notes[0]
    # a program without the clocks, or without the stage: nothing to read
    assert stage_clock.read(ctx_of({}, {("records_total", ()): 5.0}),
                            decode) is None
    assert stage_clock.read(ctx, dict(decode, stages=["publish"])) is None


def test_idle_share_splits_each_gap_by_the_stage_at_its_middle():
    gaps = [(0.0, 1.0), (2.0, 2.5), (4.0, 6.0), (8.0, 8.5)]
    stages = {"export": [(1.8, 3.0, {}), (3.5, 7.0, {})],
              "resident_pack": [(4.5, 5.5, {})],
              "put": [(2.0, 2.4, {})]}
    total = 4.0
    # gap middles: 0.5 (no export), 2.25 (export, inside put), 5.0 (export,
    # inside resident_pack), 8.25 (no export)
    assert idle_by_stage.share(gaps, stages, None, ["export"]) == \
        pytest.approx(100 * 1.5 / total)
    assert idle_by_stage.share(gaps, stages, ["resident_pack", "pack"],
                               []) == pytest.approx(100 * 2.0 / total)
    assert idle_by_stage.share(
        gaps, stages, ["export"],
        ["resident_pack", "pack", "put", "ingest_dispatch",
         "staging_wait"]) == 0.0
    assert idle_by_stage.share([], stages, None, []) is None


def test_idle_share_needs_the_stages_it_splits_by(monkeypatch):
    cap = fake_cap({"evict": [(0.0, 1.0, {})]})
    monkeypatch.setattr(capture, "of_run", lambda: cap)
    assert idle_by_stage.read(None, {"requires": ["export"],
                                     "outside": ["export"]}) is None


def test_stage_gap_joins_two_stages_on_the_eviction():
    cap = fake_cap({
        "evict": [(0.0, 0.1, {"eviction": 1}), (0.2, 0.3, {"eviction": 2}),
                  (0.4, 0.5, {"eviction": 3})],
        "export": [(0.15, 0.25, {"eviction": 1}),
                   (0.35, 0.45, {"eviction": 2}),
                   (0.6, 10.5, {"eviction": 3}),      # ends past the window
                   (0.7, 0.8, {"eviction": 9})]})     # no evict: no pair
    queue = stage_gap.pairs(cap, ["evict", "end"], ["export", "start"],
                            "eviction")
    assert queue == pytest.approx([0.05, 0.05])
    lag = stage_gap.pairs(cap, ["evict", "start"], ["export", "end"],
                          "eviction")
    assert lag == pytest.approx([0.25, 0.25])
    empty = fake_cap({"evict": cap.stages["evict"]})
    assert stage_gap.pairs(empty, ["evict", "end"], ["export", "start"],
                           "eviction") == []


# --- the metric files ------------------------------------------------------

@pytest.mark.parametrize("name", NEW)
def test_metric_file_agrees_with_its_benchmark_entry(name):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    got = spec(name)
    for key in ("unit", "better", "source", "layer", "moves", "workloads"):
        assert got[key] == entry[key], key
    cells = {w["name"] for w in BENCH["workloads"]}
    assert set(entry["workloads"]) <= cells
    ends = next(m for m in BENCH["end_to_end"] if m["name"] == entry["moves"])
    assert set(entry["workloads"]) <= set(ends["workloads"])
    assert hasattr(reader(name), "read")


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_stages_reads_none_never_zero(name,
                                                            monkeypatch):
    """On the recorded capture of a program that annotates nothing (and
    with no stage counters), every new metric answers None."""
    old = capture.Capture(OLD["cell1_cut"])
    monkeypatch.setattr(capture, "of_run", lambda: old)
    monkeypatch.setattr(xtrace, "newest", lambda _: OLD["cell1_cut"])
    ctx = ctx_of({("sketch_records_total", ()): 0.0},
                 {("sketch_records_total", ()): 9.0})
    assert reader(name).read(ctx, spec(name).get("args", {})) is None


# --- the recorded capture of a program with the stages ---------------------

with open(os.path.join(TESTDATA, "cell1_stages.json")) as f:
    WANT = json.load(f)


@pytest.fixture
def stages_capture(monkeypatch):
    cap = capture.Capture(STAGES)
    monkeypatch.setattr(capture, "of_run", lambda: cap)
    monkeypatch.setattr(xtrace, "newest", lambda _: STAGES)
    return cap


def test_the_capture_holds_one_export_per_evict(stages_capture):
    got = {st: len(v) for st, v in stages_capture.stages.items()}
    assert {k: got.get(k) for k in WANT["stages"]} == WANT["stages"]
    evicts = {s[2]["eviction"]: s for s in stages_capture.stages["evict"]}
    exports = stages_capture.stages["export"]
    evictions = [s[2]["eviction"] for s in exports]
    assert evictions == sorted(set(evictions))
    # the cut's first export may be of an eviction drained before the cut
    assert set(evictions[1:]) <= set(evicts)
    for s in exports:
        if s[2]["eviction"] in evicts:
            assert evicts[s[2]["eviction"]][1] <= s[0]


@pytest.mark.parametrize("name", [
    "drain_busy_share", "export_busy_share", "idle_awaiting_drain_share",
    "idle_in_pack_share", "idle_in_export_glue_share", "export_queue_s_p50",
    "evict_to_export_s_p50", "export_stage_s_p50"])
def test_reader_on_the_recorded_capture(name, stages_capture):
    got = reader(name).read(ctx_of(), spec(name)["args"])
    assert got == pytest.approx(WANT[name], rel=1e-9)


def test_the_idle_split_covers_the_idle_time_once(stages_capture):
    def read(name):
        return reader(name).read(ctx_of(), spec(name)["args"])
    awaiting = read("idle_awaiting_drain_share")
    inside_export = idle_by_stage.read(None, {"requires": ["export"],
                                              "inside": ["export"]})
    assert awaiting + inside_export == pytest.approx(100.0)
    assert read("idle_in_export_glue_share") <= inside_export
    assert 0 < read("export_queue_s_p50") < read("evict_to_export_s_p50")


@pytest.mark.parametrize("name", ["export_busy_share", "export_queue_s_p50",
                                  "idle_in_pack_share", "export_stage_s_p50"])
def test_a_program_that_annotates_evict_but_not_export(name, monkeypatch):
    """An older program (`cell2_capture`) has `evict` and the fold's stages
    but no `export`: whatever reads the seam answers None, while the drain's
    share reads a number."""
    old = capture.Capture(OLD["cell2_capture"])
    monkeypatch.setattr(capture, "of_run", lambda: old)
    monkeypatch.setattr(xtrace, "newest", lambda _: OLD["cell2_capture"])
    assert reader(name).read(ctx_of(), spec(name)["args"]) is None
    drain = reader("drain_busy_share").read(ctx_of(), {"stage": "evict"})
    assert 0 < drain < 100
