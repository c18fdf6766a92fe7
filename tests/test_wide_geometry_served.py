"""The served path at a small "wide" geometry against the exact reference.

Three child processes (tests/served_geometry_worker.py), each the agent as
`python -m netobserv_tpu` builds it with EXPORT=tpu-sketch — MapTracer ->
exporter, only the fetcher substituted, the benchmark's own rehearsal — on the
SAME seeded records, graded by `cellbench/oracle.py` against its exact numpy
aggregation:

- `default`: Count-Min width 65,536, 2^18-slot key tables (config.py's);
- `wide_small_tables`: width 2^20 (1,024 x top-K) with 256 slots a pack lane —
  below the distinct keys a lane meets, so its dictionaries roll epochs;
- `wide`: width 2^20 with 8,192 slots — above the 4,096-key universe, so none
  does. The shape of `collector-wide-1chip` (cellbench/configs), cut to what a
  CPU folds in seconds.

What the sketches answer exactly must not depend on the geometry, and what
they estimate must hold the oracle's gates at every one of them.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(ROOT, "tests", "served_geometry_worker.py")
SEED = 2147483659
#: name -> (SKETCH_CM_WIDTH, SKETCH_RESIDENT_SLOTS)
GEOMETRIES = {"default": (1 << 16, 1 << 18),
              "wide_small_tables": (1 << 20, 256),
              "wide": (1 << 20, 8192)}
#: fields of a window report that are the graded window's alone and exact:
#: nothing an earlier window reaches into (EWMA baselines, churn and
#: flow-trend fields, the decayed latency histograms — the runs pace their
#: earlier windows differently) and no f32 sum past 2^24, whose last bit
#: follows the order the rows were folded in (`Bytes`)
EXACT_FIELDS = ("Records", "DistinctSrcEstimate", "DropBytes", "DropPackets",
                "QuicRecords", "NatRecords", "DropCauses")


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """{geometry: the worker's JSON}, the three runs side by side."""
    out = tmp_path_factory.mktemp("served_geometry")
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("SKETCH_", "XLA_FLAGS"))}
    procs = {}
    for name, (width, slots) in GEOMETRIES.items():
        path = str(out / f"{name}.json")
        log = open(str(out / f"{name}.log"), "w")
        procs[name] = (subprocess.Popen(
            [sys.executable, WORKER, str(width), str(slots), str(SEED), path],
            stdout=log, stderr=subprocess.STDOUT, env=env, cwd=ROOT), path,
            log)
    got = {}
    for name, (proc, path, log) in procs.items():
        try:
            rc = proc.wait(timeout=900)
        finally:
            proc.kill()
            log.close()
        with open(log.name) as f:
            tail = f.read()[-2000:]
        assert rc == 0, f"{name}: exit {rc}\n{tail}"
        with open(path) as f:
            got[name] = json.load(f)
    return got


@pytest.mark.parametrize("name", GEOMETRIES)
def test_every_gate_of_the_exact_reference_holds(served, name):
    run = served[name]
    assert run["failed"] == 0
    assert all(run["gates"].values()), run["gates"]
    assert run["correct"]


@pytest.mark.parametrize("name,rolls", [("default", False),
                                        ("wide_small_tables", True),
                                        ("wide", False)])
def test_dictionary_epochs_roll_only_under_the_distinct_keys(served, name,
                                                             rolls):
    """`sketch_resident_dict_epochs_total` over the whole run: a lane whose
    table has fewer slots than it meets distinct keys rolls, one sized above
    the traffic's keys never does — with every answer still correct."""
    assert (served[name]["epochs"] > 0) == rolls, served[name]["epochs"]


@pytest.mark.parametrize("name", ["wide_small_tables", "wide"])
def test_exact_answers_equal_the_default_geometrys(served, name):
    want, got = served["default"]["graded"], served[name]["graded"]
    for field in EXACT_FIELDS:
        assert got[field] == want[field], field

    def head(report):
        return [(e["SrcAddr"], e["DstAddr"], e["SrcPort"], e["DstPort"],
                 e["Proto"], e["EstBytes"]) for e in report["HeavyHitters"]]
    # 6,000 records meet no collision at either width: the estimates ARE
    # the exact sums, so the heavy hitters agree entry for entry
    assert head(got)[:32] == head(want)[:32]


@pytest.mark.parametrize("name", GEOMETRIES)
def test_key_table_gauge_reads_the_bytes_as_allocated(served, name):
    """One table a pack region (max(ladder) x the host's pack lanes: 32 on
    an 8-core host), SKETCH_RESIDENT_SLOTS rows each, of 10 key words: one
    (regions * slots, 10) array."""
    rows, words = served[name]["table_shape"]
    regions, rest = divmod(rows, GEOMETRIES[name][1])
    assert (words, rest) == (10, 0) and regions and regions % 4 == 0
    assert served[name]["table_bytes"] == rows * words * 4


def test_state_gauge_grows_by_the_wider_planes_alone(served):
    planes = 2 * 4 * ((1 << 20) - (1 << 16)) * 4
    assert (served["wide"]["hbm_bytes"] - served["default"]["hbm_bytes"]
            == planes)
    assert (served["wide"]["hbm_bytes"]
            == served["wide_small_tables"]["hbm_bytes"])


@pytest.mark.parametrize("name", GEOMETRIES)
def test_every_dispatched_ingest_entry_names_its_countmin_form(served, name):
    """On the CPU the automatic rule folds with the scatter at every width;
    each ladder entry that ran says so in /debug/executables."""
    forms = served[name]["forms"]
    assert forms and set(forms.values()) == {"scatter"}, forms
