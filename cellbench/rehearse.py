#!/usr/bin/env python3
"""cellbench/rehearse.py — the benchmark's control flow on the CPU, tiny.

    python3 cellbench/rehearse.py [--workload <cell>] [--seed N] [--throwaway]

JAX_PLATFORMS=cpu, tiny sizes, and four virtual devices for a four-chip cell.
It prints counts and correctness only: no rate, no time, nothing under a
metric's name. `--throwaway` proves the harness is driven by data: in a
temporary copy it adds a configuration, a mix, a per-layer metric and a cell
as new files plus one entry each, edits no file that is there, and rehearses
the new cell from the copy.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

TINY = {
    "mix": {"universe": 4096, "stream_records": 20000, "eviction": 2000,
            "max_eviction": 2000, "rate_per_s": 4000, "fill_records": 8000,
            "trace_seconds": 1},
    "env": {"SKETCH_WINDOW": "2s"},
}


def throwaway(seed: int) -> int:
    """A new cell from new files and one entry each, in a temporary copy."""
    tmp = tempfile.mkdtemp(prefix="cellbench_throwaway_")
    try:
        shutil.copytree(HERE, os.path.join(tmp, "cellbench"),
                        ignore=shutil.ignore_patterns("__pycache__"))
        os.symlink(os.path.join(ROOT, "netobserv_tpu"),
                   os.path.join(tmp, "netobserv_tpu"))
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            bench = json.load(f)
        before = {}
        for d, _, files in os.walk(os.path.join(tmp, "cellbench")):
            for name in files:
                with open(os.path.join(d, name), "rb") as f:
                    before[os.path.join(d, name)] = f.read()

        def derive(kind, old, new, **changes):
            with open(os.path.join(tmp, "cellbench", kind, old + ".json")) as f:
                obj = json.load(f)
            obj.update(changes)
            with open(os.path.join(tmp, "cellbench", kind, new + ".json"),
                      "w") as f:
                json.dump(obj, f)
        first = bench["workloads"][0]
        derive("configs", first["config"], "throwaway-config",
               name="throwaway-config")
        derive("traffic", first["traffic"], "throwaway-mix", zipf_a=1.1)
        derive("metrics", "records_per_fold", "throwaway_metric",
               name="throwaway_metric")
        conf = dict(next(c for c in bench["configs"]
                         if c["name"] == first["config"]),
                    name="throwaway-config",
                    file="cellbench/configs/throwaway-config.json")
        bench["configs"].append(conf)
        cell = dict(first, name="throwaway.cell", config="throwaway-config",
                    traffic="throwaway-mix")
        bench["workloads"].append(cell)
        for m in bench["end_to_end"]:
            if first["name"] in m.get("workloads", ()):
                m["workloads"].append(cell["name"])
        bench["per_layer"].append({
            "name": "throwaway_metric", "unit": "records/fold",
            "better": "higher", "source": "program_counter",
            "layer": "pending buffer + ladder",
            "moves": next(m["name"] for m in bench["end_to_end"]
                          if cell["name"] in m.get("workloads", ())),
            "workloads": [cell["name"]]})
        with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
            json.dump(bench, f)
        rc = subprocess.run(
            [sys.executable, os.path.join(tmp, "cellbench", "rehearse.py"),
             "--workload", cell["name"], "--seed", str(seed)]).returncode
        for path, data in before.items():
            with open(path, "rb") as f:
                if f.read() != data:
                    print(f"# throw-away cell EDITED {path}", flush=True)
                    rc = rc or 1
        print(f"# throw-away cell: {len(before)} files untouched, 3 files and "
              f"4 entries added, exit {rc}", flush=True)
        return rc
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default=None)
    ap.add_argument("--seed", type=int, default=2147483659)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--throwaway", action="store_true")
    args = ap.parse_args()
    if args.throwaway:
        return throwaway(args.seed)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cells = {w["name"]: w for w in json.load(f)["workloads"]}
    workload = args.workload or next(iter(cells))
    os.environ["JAX_PLATFORMS"] = "cpu"
    if cells[workload]["chips"] > 1:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count="
              f"{cells[workload]['chips']}")
    # CPU executables stay out of the checkout's .jax_cache
    cache = tempfile.mkdtemp(prefix="cellbench_rehearsal_cache_")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    sys.path.insert(0, ROOT)
    from cellbench import harness, run
    try:
        tiny = json.loads(json.dumps(TINY))
        with open(os.path.join(
                HERE, "traffic", cells[workload]["traffic"] + ".json")) as f:
            graded = dict(json.load(f)["graded"], records=6000, eviction=2000)
        tiny["mix"]["graded"] = graded
        result = run.run_cell(workload, args.seed, args.seconds,
                              bool(args.trace), harness.process_age_s(),
                              time.perf_counter(), rehearsal=tiny)
    except harness.Failed as exc:
        print(f"cellbench rehearsal: {exc}", file=sys.stderr)
        return 2
    finally:
        shutil.rmtree(cache, ignore_errors=True)
    print(json.dumps(result), flush=True)
    return 0 if result["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
