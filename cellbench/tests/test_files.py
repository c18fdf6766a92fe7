"""Every file BENCHMARK.json names loads, and the entries agree with each
other. Runs on a CPU: python3 -m pytest cellbench/tests -q"""

import importlib
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def load(rel):
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


BENCH = load("BENCHMARK.json")
CELLS = {w["name"]: w for w in BENCH["workloads"]}


def cells_of(metric):
    return metric.get("workloads", list(CELLS))


def test_paths_and_command():
    assert BENCH["paths"] == ["cellbench"]
    assert BENCH["command"] == ["python3", "cellbench/run.py"]
    assert os.path.isfile(os.path.join(ROOT, BENCH["command"][1]))
    assert 1 <= BENCH["run_seconds"] <= 51


@pytest.mark.parametrize("conf", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file(conf):
    body = load(conf["file"])
    assert conf["file"].startswith("cellbench/")
    assert body["name"] == conf["name"] and body["source"] == conf["source"]
    assert set(conf["reduced"]) == set(body["reduced"])
    for key in ("env", "assumed", "expect", "geometry", "guarantees"):
        assert key in body
    assert any(w["config"] == conf["name"] for w in BENCH["workloads"])


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_files(cell):
    assert NAME.match(cell["name"]) and len(cell["why"]) <= 200
    assert cell["chips"] in (1, 4)
    conf = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    assert load(conf["file"])["chips"] == cell["chips"]
    mix = load(f"cellbench/traffic/{cell['traffic']}.json")
    gen = importlib.import_module(f"cellbench.generators.{mix['generator']}")
    gen.Generator(mix)
    for key in ("universe", "zipf_a", "stream_records", "fill_records",
                "graded", "trace_seconds", "map_cpus"):
        assert key in mix
    mine = [m for m in BENCH["end_to_end"] if cell["name"] in cells_of(m)]
    assert "setup_s" in [m["name"] for m in mine] and len(mine) >= 2
    assert any(cell["name"] in cells_of(m) for m in BENCH["per_layer"])


@pytest.mark.parametrize("metric", BENCH["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric(metric):
    spec = load(f"cellbench/metrics/{metric['name']}.json")
    for key in ("unit", "layer", "moves", "better", "source"):
        assert spec[key] == metric[key], key
    reader = importlib.import_module(f"cellbench.readers.{spec['reader']}")
    assert callable(reader.read)
    assert re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", metric["unit"])
    moved = next(m for m in BENCH["end_to_end"] if m["name"] == metric["moves"])
    for cell in cells_of(metric):
        assert cell in CELLS
        assert cell in cells_of(moved), (
            f"{metric['name']} moves {moved['name']}, which {cell} does not "
            "report")


def test_end_to_end():
    names = [m["name"] for m in BENCH["end_to_end"]]
    assert "setup_s" in names and len(set(names)) == len(names)
    assert set(names) <= {"records_per_s", "evict_lag_s_p50",
                          "evict_lag_s_p95", "setup_s"}
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def test_four_chip_cells_are_a_minority():
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)


def test_peaks_table_refuses_an_unknown_device():
    from cellbench import roofline

    assert roofline.peaks_for("TPU v5 lite")["bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks_for("cpu")
