"""`fold_countmin_roofline` through its own files (cellbench/metrics, readers/
scope_roofline.py, scope_cost.py) on PR 25's recorded capture of a TPU v5
lite: the share is the needed work's least time over the `countmin` scope's
device time, weighted by each ladder entry's runs, and a program that names
nothing gives nothing. Runs on a CPU."""

import json
import os
import re
import types

import pytest

from cellbench import capture, roofline, scope_cost
from cellbench.readers import scope_roofline

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
with open(os.path.join(HERE, "..", "testdata", "cell2_capture.json")) as f:
    WANT = json.load(f)
with open(os.path.join(ROOT, "cellbench", "metrics",
                       "fold_countmin_roofline.json")) as f:
    SPEC = json.load(f)


def config(name: str) -> dict:
    with open(os.path.join(ROOT, "cellbench", "configs", f"{name}.json")) as f:
        return json.load(f)


def ctx_for(calls: dict, conf: str = "collector-1chip"):
    return types.SimpleNamespace(
        records=2e6, notes=[], config=config(conf), n_devices=1,
        device_kind="TPU v5 lite",
        calls_in_window=lambda exe: calls.get(exe, 0))


def test_countmin_cost_counts_touched_counters_and_the_rows_once():
    g = config("collector-1chip")["geometry"]
    cost = scope_cost.countmin(g, 4, 1)
    rows = 4 * 8192
    assert cost["ops"] == rows * 2 * 4
    assert cost["bytes"] == rows * 2 * 4 * 8 + rows * 17
    # the same work whatever the width: the wide geometry needs no more
    wide = config("collector-wide-1chip")["geometry"]
    assert scope_cost.countmin(wide, 4, 1) == cost
    # a shard of four folds a quarter of the rows
    assert scope_cost.countmin(g, 4, 4)["ops"] == cost["ops"] / 4


def test_share_is_least_time_over_the_scopes_device_time(monkeypatch):
    cap = capture.Capture(os.path.join(HERE, "..", "testdata",
                                       "cell2_capture.xplane.pb"))
    monkeypatch.setattr(capture, "of_run", lambda: cap)
    calls = {exe: 10 * got["runs"] for exe, got in WANT["runs"].items()}
    ctx = ctx_for(calls)
    got = scope_roofline.read(ctx, SPEC["args"])
    peaks = roofline.peaks_for("TPU v5 lite")
    least = took = 0.0
    for exe, run in WANT["runs"].items():
        k = int(re.search(r"_x(\d+)$", exe).group(1))
        t, _ = roofline.least_seconds(
            scope_cost.countmin(ctx.config["geometry"], k, 1), peaks)
        least += t * calls[exe]
        took += run.get("countmin", 0.0) / run["runs"] * calls[exe]
    assert got == pytest.approx(100.0 * least / took, rel=1e-9)
    assert 0 < got < 100
    assert any("roofline countmin of" in n for n in ctx.notes)
    # no run of the entries in the window: nothing to read
    assert scope_roofline.read(ctx_for({}), SPEC["args"]) is None


def test_a_program_that_names_nothing_gives_nothing(monkeypatch):
    cap = capture.Capture(os.path.join(HERE, "..", "testdata",
                                       "cell1_cut.xplane.pb"))
    monkeypatch.setattr(capture, "of_run", lambda: cap)
    assert scope_roofline.read(ctx_for({"fn": 10}), SPEC["args"]) is None
    monkeypatch.setattr(capture, "of_run", lambda: None)
    assert scope_roofline.read(ctx_for({"fn": 10}), SPEC["args"]) is None
