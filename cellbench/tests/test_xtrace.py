"""The trace reduction, on synthetic intervals and on a recorded trace: a
cut-down `.xplane.pb` of this benchmark's own traced run of
collector-1chip.zipf-saturate on a TPU v5 lite (PR 24), made with
cellbench/cut_trace.py."""

import json
import os

import pytest

from cellbench import xtrace

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(HERE, "..", "testdata", "cell1_cut.xplane.pb")
with open(os.path.join(HERE, "..", "testdata", "cell1_cut.json")) as f:
    WANT = json.load(f)


def test_union_and_gaps():
    busy, gaps = xtrace.union_s(
        [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.2, 3.4), (9.0, 11.0)], 0.5, 10)
    assert busy == pytest.approx(1.5 + 1.0 + 1.0)
    assert gaps == [(2.0, 3.0), (4.0, 9.0)]


def test_modules_are_named_by_run_id_order():
    calls = ["warm"] * 7 + ["x4", "x4", "x1", "x4", "roll", "x4", "x1"]
    # three programs the benchmark does not watch ran before the first call
    mods = [(float(i), i + 0.5, fp, 3 + 7 + i) for i, fp in enumerate(
        ["A", "A", "B", "A", "C", "A", "B"])]
    assert xtrace.name_modules(mods, calls) == {"A": "x4", "B": "x1",
                                                "C": "roll"}
    # no consistent naming: a fingerprint under two names at every offset
    assert xtrace.name_modules(mods, ["x4", "x1"] * 20) == {}


def test_op_label():
    assert xtrace.op_label(
        "%fusion.51 = f32[270336]{0:T(1024)} fusion(f32[1024]{0} %a), "
        "kind=kCustom") == ("fusion.51", "fusion:f32[270336]")
    assert xtrace.op_label(
        "%fn.5 = (f32[2,4,65536]{2,1,0:T(4,128)}, s32[8]{0}) custom-call("
        "f32[2] %x)") == ("fn.5", "custom-call:f32[2,4,65536]x2")


@pytest.fixture(scope="module")
def trace():
    return xtrace.load(FIXTURE)


def test_recorded_trace_busy_union(trace):
    lo, hi = xtrace.window_of(trace)
    assert hi - lo == pytest.approx(WANT["window_s"], abs=1e-6)
    busy, gaps = xtrace.union_s(trace.devices[0]["ops"], lo, hi)
    assert busy == pytest.approx(WANT["busy_s"], rel=1e-6)
    assert busy <= hi - lo and gaps


def test_recorded_trace_per_executable_time_and_op_names(trace):
    dev = trace.devices[0]
    names = xtrace.name_modules(dev["modules"], WANT["calls"])
    assert sorted(names.values()) == sorted(WANT["per_executable"])
    lo, hi = xtrace.window_of(trace)
    mods = xtrace.by_module(dev, names, lo, hi)
    for exe, (runs, op_s) in WANT["per_executable"].items():
        mine = [m["op_s"] for m in mods if m["exe"] == exe]
        assert len(mine) == runs
        assert sum(mine) == pytest.approx(op_s, rel=1e-6)
    ops = {}
    for m in mods:
        for op, label, s in m["ops"]:
            key = f"{m['exe']}/{op}:{label}"
            ops[key] = ops.get(key, 0.0) + s
    top = sorted(ops, key=ops.get, reverse=True)[:3]
    assert top == WANT["top_ops"]
    assert "custom-call:f32[2,4,65536]" in top[0]


def test_recorded_trace_idle_gaps_name_host_spans(trace):
    lo, hi = xtrace.window_of(trace)
    gaps = xtrace.idle_gaps(trace, lo, hi,
                            ["jit_call", "pack", "decode", "publish", "export"])
    assert gaps and all(s >= 0 for _, s in gaps)
    assert {n for n, _ in gaps} <= {"jit_call", "pack", "decode", "publish",
                                    "export", "none"}
