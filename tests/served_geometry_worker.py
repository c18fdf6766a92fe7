"""Child process of tests/test_wide_geometry_served.py (not a test file):
one rehearsal-sized run of the benchmark's served path — the agent as
`python -m netobserv_tpu` builds it, MapTracer -> exporter, only the fetcher
substituted — at the Count-Min width and key-table slots given, graded by
`cellbench/oracle.py` against its exact numpy aggregation of the same seeded
records. Writes what the test compares as JSON to the path given.

    python tests/served_geometry_worker.py <cm_width> <slots> <seed> <out.json>
"""

import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    os.environ["JAX_PLATFORMS"] = "cpu"
    # CPU executables stay out of the checkout's .jax_cache
    cache = tempfile.mkdtemp(prefix="served_geometry_cache_")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    try:
        return serve(*sys.argv[1:5])
    finally:
        shutil.rmtree(cache, ignore_errors=True)


def serve(width: str, slots: str, seed: str, out: str) -> int:
    sys.path.insert(0, ROOT)
    from cellbench import harness, rehearse, run

    held = []

    class Kept(harness.AgentUnderTest):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            held.append(self)
            self.final = {}

        def stop(self):
            self.final = self.counters()
            self.exe = self.executables()
            super().stop()

    harness.AgentUnderTest = Kept
    tiny = json.loads(json.dumps(rehearse.TINY))
    tiny["env"].update(SKETCH_CM_WIDTH=width, SKETCH_RESIDENT_SLOTS=slots)
    tiny["mix"]["graded"] = {"records": 6000, "eviction": 2000}
    result = run.run_cell("collector-1chip.zipf-saturate", int(seed), 4.0,
                          False, harness.process_age_s(), time.perf_counter(),
                          rehearsal=tiny)
    aut = held[0]

    def total(name):
        return sum(v for (n, _), v in aut.final.items() if n == name)
    # the graded window: the last report that carries its 6,000 records
    graded = next(r for _, r in reversed(aut.reports) if r["Records"] == 6000)
    with open(out, "w") as f:
        json.dump({
            "correct": result["correct"], "failed": result["failed"],
            "gates": result["rehearsal"]["gates"],
            "epochs": total("sketch_resident_dict_epochs_total"),
            "table_bytes": total("sketch_resident_table_bytes"),
            "hbm_bytes": total("sketch_resident_hbm_bytes"),
            "table_shape": list(aut.exporter._ring.key_tables.shape),
            "forms": {e["fn"]: e.get("countmin")
                      for e in aut.exe["executables"]
                      if e["fn"].startswith("ingest") and e["calls"]},
            "graded": graded}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
