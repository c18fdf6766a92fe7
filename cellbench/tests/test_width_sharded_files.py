"""The files PR 34 added: the width-sharded configuration, the three scope
metrics and the two four-chip cells load, agree with BENCHMARK.json and keep
to what the issue set. Runs on a CPU: python3 -m pytest cellbench/tests -q"""

import importlib
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def load(rel):
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


BENCH = load("BENCHMARK.json")
CELLS = {w["name"]: w for w in BENCH["workloads"]}
METRICS = {m["name"]: m for m in BENCH["per_layer"]}
WIDE2X2 = "collector-wide-mesh2x2.widekeys-saturate"
NEWKEYS4 = "collector-mesh4.newkeys-saturate"


def test_the_width_sharded_configuration_is_the_wide_one_on_a_2x2_mesh():
    wide = load("cellbench/configs/collector-wide-1chip.json")
    conf = load("cellbench/configs/collector-wide-mesh2x2.json")
    entry = next(c for c in BENCH["configs"] if c["name"] == conf["name"])
    assert entry["file"] == "cellbench/configs/collector-wide-mesh2x2.json"
    assert entry["source"] == conf["source"] != wide["source"]
    assert conf["chips"] == 4
    assert conf["env"] == {**wide["env"], "SKETCH_MESH_SHAPE": "2x2"}
    assert conf["expect"] == {"distributed": True,
                              "mesh": {"data": 2, "sketch": 2}}
    # the whole-width sizes: scope_cost divides a fold's rows by the chips
    assert conf["geometry"] == wide["geometry"]
    assert set(conf["reduced"]) == set(wide["reduced"]) == set(
        entry["reduced"])
    assert conf["guarantees"][:len(wide["guarantees"])] == wide["guarantees"]
    assert any("/query/frequency" in g for g in conf["guarantees"][5:])
    assert "ARCHIVE_DIR" in conf["deployment"]


@pytest.mark.parametrize("name,scope,exe,cells", [
    ("fold_owner_mask_s_per_mrec", "owner_mask", "ingest", [WIDE2X2]),
    ("merge_tables_gather_s_per_mrec", "merge_tables_gather", "roll|merge",
     [WIDE2X2]),
    ("merge_topk_gather_s_per_mrec", "merge_topk_gather", "roll|merge",
     ["collector-mesh4.zipf-saturate", NEWKEYS4, WIDE2X2])])
def test_scope_metric_file(name, scope, exe, cells):
    spec = load(f"cellbench/metrics/{name}.json")
    assert spec["reader"] == "scope_time"
    assert spec["args"] == {"executable": exe, "scopes": [scope],
                            "per": "mrec"}
    assert callable(importlib.import_module(
        "cellbench.readers.scope_time").read)
    assert METRICS[name]["workloads"] == cells
    assert METRICS[name]["moves"] == "records_per_s"


def test_the_two_cells_report_what_moves_records_per_s():
    assert CELLS[NEWKEYS4]["chips"] == CELLS[WIDE2X2]["chips"] == 4
    assert CELLS[NEWKEYS4]["config"] == "collector-mesh4"
    assert CELLS[WIDE2X2]["traffic"] == "widekeys-saturate"
    rate = next(m for m in BENCH["end_to_end"]
                if m["name"] == "records_per_s")
    assert rate["workloads"][-2:] == [NEWKEYS4, WIDE2X2]
    for m in BENCH["per_layer"]:
        if m["moves"] != "records_per_s":
            continue
        if set(m["workloads"]) <= {"collector-mesh4.zipf-saturate", NEWKEYS4,
                                   WIDE2X2}:
            continue    # the scope metrics above
        assert NEWKEYS4 in m["workloads"], m["name"]
        # roofline.fold_cost reckons a full-width replica on every chip:
        # stale for an owner-sharded chip (PERF.md section 7)
        assert (WIDE2X2 in m["workloads"]) == (m["name"] != "fold_roofline")
