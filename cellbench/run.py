#!/usr/bin/env python3
"""cellbench/run.py — one cell, one run, one result line.

    python3 cellbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

In ONE process: finds exactly the cell's chips (else exits non-zero with
nothing on stdout: never a CPU stand-in), builds the agent the way
`python -m netobserv_tpu` does with EXPORT=tpu-sketch and only the fetcher
substituted, fills, warms, measures a whole number of sketch windows, drains,
grades one window against the exact aggregation, and prints the result as the
last line. Everything that belongs to one cell is data: see
cellbench/README.md.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import shutil
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from cellbench import harness  # noqa: E402
from cellbench.harness import Failed, note  # noqa: E402

TRACE_DIR = os.path.join(ROOT, ".cellbench_trace")
#: who gets an idle gap, first match first (see xtrace.idle_gaps)
GAP_ORDER = ["jit_call", "pack", "decode", "publish", "export"]


def load_json(rel: str) -> dict:
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


def load_cell(workload: str) -> dict:
    """The cell's entry of BENCHMARK.json with its files resolved."""
    bench = load_json("BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise Failed(f"BENCHMARK.json has no workload {workload!r} "
                     f"(it has {sorted(cells)})")
    cell = dict(cells[workload])
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cell["config_file"] = load_json(conf["file"])
    cell["mix"] = load_json(f"cellbench/traffic/{cell['traffic']}.json")

    def mine(m):
        return "workloads" not in m or workload in m["workloads"]
    cell["end_to_end"] = [m for m in bench["end_to_end"] if mine(m)]
    cell["per_layer"] = [m for m in bench["per_layer"] if mine(m)]
    return cell


def read_metric(name: str, ctx):
    """A metric through its own file: cellbench/metrics/<name>.json names a
    reader kind (cellbench/readers/<kind>.py) and its arguments."""
    spec = load_json(f"cellbench/metrics/{name}.json")
    reader = importlib.import_module(f"cellbench.readers.{spec['reader']}")
    value = reader.read(ctx, spec.get("args", {}))
    return None if value is None else {"value": float(value),
                                       "unit": spec["unit"]}


class Window:
    """What one measured window left behind, for the readers."""

    def __init__(self):
        self.t0 = self.t1 = 0.0         # the edges, perf_counter seconds
        self.records = 0.0              # published in the window's reports
        self.window_s = 0.0             # SKETCH_WINDOW
        self.counters0 = self.counters1 = None
        self.exe0 = self.exe1 = None
        self.cpu0 = self.cpu1 = None
        self.main_tid = threading.get_native_id()
        self.spans = self.offer = None
        self.samples: list = []         # offer.samples whose export ended inside
        self.all_reports: list = []
        self.config: dict = {}
        self.device_kind = ""
        self.n_devices = 1
        self.notes: list = []
        # the traced slice
        self.modules: list = []         # per device: xtrace.by_module
        self.busy_s = self.trace_window_s = 0.0

    def counter_delta(self, name: str) -> float:
        return counter_delta(self.counters0, self.counters1, name)

    def calls_in_window(self, exe: str) -> int:
        def calls(snap):
            return sum(e["calls"] for e in snap["executables"]
                       if e["fn"] == exe)
        return calls(self.exe1) - calls(self.exe0)


def counter_delta(before: dict, after: dict, name: str) -> float:
    """A counter's growth between two scrapes, every label set summed."""
    return sum(v - before.get(k, 0.0) for k, v in after.items()
               if k[0] == name)


def find_devices(chips: int, rehearsal: bool) -> dict:
    """First act: the chips. A CPU is never a stand-in."""
    import jax

    devices = jax.devices()
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    if not rehearsal and dev["platform"] != "tpu":
        raise Failed(f"no TPU found (jax platform {dev['platform']!r}, "
                     f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}): "
                     "a cell runs only on the chip")
    if dev["count"] != chips:
        raise Failed(f"the cell asks for {chips} chip(s), JAX found "
                     f"{dev['count']}")
    return dev


def build_native() -> None:
    """libflowpack.so is ignored by git: a checkout starts without it."""
    from netobserv_tpu.datapath import flowpack

    lib, src = flowpack._LIB_PATHS[0], os.path.join(
        flowpack._NATIVE_DIR, "flowpack.cc")
    stale = (not os.path.exists(lib)
             or os.path.getmtime(lib) < os.path.getmtime(src))
    if not (flowpack.build_native(force=stale)
            and flowpack.native_available()):
        raise Failed("libflowpack.so could not be built from flowpack.cc")


class Lowerings:
    """Every jaxpr->MLIR lowering in the process (one per trace of a jitted
    callable, whether or not the persistent cache then serves it)."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax.monitoring

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == self.EVENT:
            self.n += 1


def check_mesh(exporter, expect: dict) -> None:
    """The configuration pins the path the exporter must have built."""
    got = {"distributed": bool(exporter._distributed),
           "mesh": ({k: int(v) for k, v in exporter._mesh.shape.items()}
                    if exporter._distributed else None)}
    if got != expect:
        raise Failed(f"the exporter built {got}, the configuration pins "
                     f"{expect}")


def device_memory_peak() -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks))


def hand_and_wait(aut, dumps: list, what: str, timeout_s: float) -> int:
    """Queue `dumps` for the next drains and wait until the agent has exported
    them all; returns the number of reports seen before the first."""
    seen = aut.n_reports()
    target = aut.offer.handed + len(dumps)
    aut.fetcher.queued.extend(dumps)
    aut.fetcher.flush()
    harness.wait_for(lambda: aut.offer.exported >= target, timeout_s, what,
                     0.01)
    return seen


def reduce_trace(ctx: Window, calls: list) -> dict:
    """The traced slice -> ctx.modules, busy/window seconds, breakdown."""
    from cellbench import xtrace

    trace = xtrace.load(xtrace.newest(TRACE_DIR))
    if not trace.devices:
        raise Failed("the trace holds no device operations")
    lo, hi = xtrace.window_of(trace)
    ctx.trace_window_s = hi - lo
    busy, ops = [], {}
    for dev in trace.devices:
        names = xtrace.name_modules(dev["modules"], calls)
        if not names:
            ctx.notes.append("trace: modules could not be named by run_id "
                             "order; per-executable metrics are left out")
        mods = xtrace.by_module(dev, names, lo, hi)
        ctx.modules.append(mods)
        busy.append(xtrace.union_s(dev["ops"], lo, hi)[0])
        for m in mods:
            for op, label, s in m["ops"]:
                key = (m["exe"], op, label)
                ops[key] = ops.get(key, 0.0) + s / len(trace.devices)
    ctx.busy_s = sum(busy) / len(busy)
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[f"{exe}/{op}:{label}", s]
                           for (exe, op, label), s in top],
            "idle_gaps": xtrace.idle_gaps(trace, lo, hi, GAP_ORDER)}


def set_up(workload: str, seed: int, trace: bool, age0: float, m0: float,
           rehearsal: dict | None = None):
    """Everything before the offer: the chips, the agent, the traffic, a warm
    ladder, x1 and the roll. Returns the run's state."""
    import types

    import numpy as np

    cell = load_cell(workload)
    config, mix = cell["config_file"], dict(cell["mix"])
    env = dict(config["env"])
    if rehearsal:
        mix.update(rehearsal.get("mix", {}))
        env.update(rehearsal.get("env", {}))
    dev = find_devices(cell["chips"], bool(rehearsal))
    os.environ.update(env)

    from netobserv_tpu.utils.platform import enable_compile_cache
    cache_dir = enable_compile_cache()
    n_cached = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    note(f"cell {workload} seed {seed} trace {int(trace)}; device {dev}; "
         f"compile cache {cache_dir} ({n_cached} entries)")
    build_native()
    lowerings = Lowerings()

    from cellbench import traffic
    spans, offer = harness.Spans(annotate=trace), harness.Offer()
    if trace:
        harness.wrap_product(spans)     # before the first watched jit call
    aut = harness.AgentUnderTest(spans, offer, wrap_publish=trace)
    try:
        check_mesh(aut.exporter, config["expect"])
        window_s = float(aut.cfg.sketch_window)

        def at() -> float:
            return age0 + time.perf_counter() - m0

        # the traffic, while the ladder warms on the exporter's own thread
        rng = np.random.default_rng(seed)
        uni = traffic.Universe(rng, mix["universe"], mix["zipf_a"],
                               mix["v6_share"])
        stream = traffic.Stream(rng, uni, mix["stream_records"],
                                mix["map_cpus"], mix.get("new_key_share", 0))
        graded = traffic.Stream(rng, uni, mix["graded"]["records"],
                                mix["map_cpus"],
                                mix["graded"].get("new_key_share", 0))
        warm = traffic.Stream(rng, uni, 100, mix["map_cpus"])
        note(f"set-up: traffic drawn at {at():.1f}s")
        ladder = harness.wait_ladder_warm(aut, 900)
        note(f"set-up: ladder {ladder} warm at {at():.1f}s")

        # x1 and the roll compile at first use: a sub-batch eviction folds at
        # the window close, so both are warm before anything is measured
        seen = hand_and_wait(aut, [warm.take(100)], "the warm-up eviction", 600)
        while not any(r["Records"] for _, r in aut.reports[seen:]):
            aut.wait_report(aut.n_reports(), 600 + window_s,
                            "the warm-up window's report")
        note(f"set-up: x1 and roll warm at {at():.1f}s")
    except BaseException:
        aut.stop()
        raise
    aut.fetcher.stream = stream
    return types.SimpleNamespace(
        cell=cell, config=config, mix=mix, dev=dev, aut=aut, spans=spans,
        offer=offer, graded=graded, window_s=window_s, lowerings=lowerings)


def start_offer(run, mix: dict):
    """The mix's own loop, from now on."""
    gen = importlib.import_module(
        f"cellbench.generators.{mix['generator']}").Generator(mix)
    gen.start(time.perf_counter())
    run.aut.fetcher.generator = gen
    run.aut.fetcher.flush()
    return gen


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             age0: float, m0: float, rehearsal: dict | None = None) -> dict:
    import numpy as np

    from cellbench import oracle
    run = set_up(workload, seed, trace, age0, m0, rehearsal)
    cell, config, mix, dev = run.cell, run.config, run.mix, run.dev
    aut, spans, offer, graded = run.aut, run.spans, run.offer, run.graded
    window_s, lowerings = run.window_s, run.lowerings
    try:
        # the mix's own loop, from here to the end of the window
        gen = start_offer(run, mix)
        fill_to = offer.handed_records + mix["fill_records"]
        harness.wait_for(lambda: offer.handed_records >= fill_to, 600,
                         "the fill", 0.02)
        i0 = aut.wait_report(aut.n_reports(), window_s + 120, "the first edge")

        # ---- the measured window: from one report's arrival to another's
        ctx = Window()
        ctx.t0 = aut.reports[i0][0]
        setup_s = age0 + ctx.t0 - m0
        n_windows = max(1, int(seconds // window_s))
        ctx.counters0, ctx.exe0 = aut.counters(), aut.executables()
        ctx.cpu0, low0 = harness.thread_cpu_s(), lowerings.n
        note(f"window: first edge at {setup_s:.1f}s; {n_windows} sketch "
             f"windows of {window_s:.0f}s")
        levels = []     # traced run: is the window stationary, roll by roll?

        def watch_levels():
            for i in range(i0 + 1, i0 + n_windows + 1):
                aut.wait_report(i, window_s + 120, "a window's report")
                levels.append(aut.counters())
        if trace:
            import jax.profiler
            watcher = threading.Thread(target=watch_levels, daemon=True)
            watcher.start()
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
            with spans.span("traced"):
                time.sleep(min(mix["trace_seconds"], seconds))
            jax.profiler.stop_trace()
            with open(os.path.join(TRACE_DIR, "calls.json"), "w") as f:
                json.dump(spans.calls, f)   # for whoever cuts a fixture
        i1 = aut.wait_report(i0 + n_windows, n_windows * window_s + 120,
                             "the last edge")
        if trace:
            watcher.join(timeout=60)
        ctx.t1 = aut.reports[i1][0]
        ctx.counters1, ctx.exe1 = aut.counters(), aut.executables()
        ctx.cpu1, low1 = harness.thread_cpu_s(), lowerings.n
        ctx.records = float(sum(r["Records"]
                                for _, r in aut.reports[i0 + 1:i1 + 1]))
        with offer.lock:
            ctx.samples = [s for s in offer.samples
                           if ctx.t0 < s[2] <= ctx.t1]
        gen_s_max, asked_empty = offer.gen_s_max, offer.asked_empty

        # ---- stop the offer, drain, and grade one window alone
        aut.fetcher.generator = None
        size = mix["graded"]["eviction"]
        dumps = [graded.take(min(size, mix["graded"]["records"] - lo))
                 for lo in range(0, mix["graded"]["records"], size)]
        want = oracle.exact(np.concatenate([d.events() for d in dumps]))
        harness.wait_for(lambda: offer.exported >= offer.handed, 300,
                         "the hand-overs to drain", 0.01)
        # the graded window starts at a report's arrival, with nothing of
        # the offer left unpublished
        aut.wait_report(aut.n_reports(), window_s + 120, "a window's report")
        while sum(r["Records"] for _, r in aut.reports) < offer.handed_records:
            aut.wait_report(aut.n_reports(), window_s + 120,
                            "the drained window's report")
        seen = hand_and_wait(aut, dumps, "the graded evictions", 300)
        g = aut.wait_report(seen, window_s + 120, "the graded window's report")
        report = aut.reports[g][1]
        gates = oracle.grade(want, report, aut.query)
        counters, exe = aut.counters(), aut.executables()
        _, health = aut.query("/healthz")
    finally:
        aut.stop()

    published = sum(r["Records"] for _, r in aut.reports)
    attempted = offer.handed_records
    gates.append(("every_acknowledged_record_published_once",
                  published == attempted,
                  f"published {published:.0f} == handed {attempted} over "
                  f"{len(aut.reports)} reports"))

    def counter(name):
        return sum(v for k, v in counters.items() if k[0] == name)
    bad = {k: v for k, v in counters.items() if v and k[0] in (
        "errors_total", "export_errors_total", "dropped_flows_total",
        "sketch_ingest_errors_total", "sketch_reports_shed_total",
        "sketch_shed_rows_total")}
    gates.append(("no_errors_drops_or_shed_reports", not bad
                  and health.get("status") == "Started"
                  and not health.get("degraded"),
                  f"0 ingest, roll, publish and export errors, 0 drops, 0 "
                  f"reports shed, /healthz Started (found: {bad or 'none'})"))
    gates.append(("no_retrace", exe["retraces_total"] == 0,
                  f"retraces {exe['retraces_total']}"))
    gates.append(("no_compilation_in_window", low1 == low0,
                  f"lowerings inside the window {low1 - low0}"))
    if gen.wants_drain(0):
        gates.append(("generator_never_starved", asked_empty == 0,
                      f"gen_starved {asked_empty}"))
    gates = [(name, bool(ok), words) for name, ok, words in gates]
    for name, ok, words in gates:
        note(f"[{'ok' if ok else 'FAIL'}] {name}: {words}")
    lost = max(0, attempted - int(published)) + int(
        counter("dropped_flows_total"))

    ctx.spans, ctx.offer, ctx.window_s = spans, offer, window_s
    ctx.all_reports, ctx.config = aut.reports, config
    ctx.device_kind, ctx.n_devices = dev["kind"], dev["count"]
    wall = ctx.t1 - ctx.t0
    lags = sorted(s[2] - s[0] for s in ctx.samples)
    note(f"window: {ctx.records:.0f} records in {wall:.3f}s over "
         f"{n_windows} sketch windows; {len(lags)} evictions exported, "
         f"export_evicted busy "
         f"{sum(s[2] - s[1] for s in ctx.samples) / wall:.1%} of it; "
         f"gen_starved {asked_empty}; gen_late_s_max {gen_s_max:.6f}; "
         f"handed {attempted} in {offer.handed} evictions")

    result = {"correct": all(ok for _, ok, _ in gates),
              "attempted": int(attempted), "failed": int(lost),
              "metrics": {}, "device": dict(dev)}
    if rehearsal:
        # a CPU run: counts and correctness, nothing under a metric's name
        result["rehearsal"] = {
            "records_in_window": ctx.records, "sketch_windows": n_windows,
            "evictions_exported": len(lags),
            "folds": counter("sketch_superbatch_folds_total"),
            "spill_rows": counter("sketch_resident_spill_rows_total"),
            "continuations": counter("sketch_resident_continuations_total"),
            "gates": {name: ok for name, ok, _ in gates}}
        return result
    result["device"]["memory_peak_bytes"] = device_memory_peak()
    if not trace:
        from cellbench.readers import quantile
        have = {"setup_s": setup_s,
                "records_per_s": ctx.records / wall if wall else None,
                "evict_lag_s_p50": quantile(lags, 0.50),
                "evict_lag_s_p95": quantile(lags, 0.95)}
        for m in cell["end_to_end"]:
            if have.get(m["name"]) is None:
                raise Failed(f"end-to-end metric {m['name']} has no reading")
            result["metrics"][m["name"]] = {"value": float(have[m["name"]]),
                                            "unit": m["unit"]}
        return result
    result["breakdown"] = reduce_trace(ctx, spans.calls)
    result["device"].update(busy_s=ctx.busy_s, window_s=ctx.trace_window_s)
    for m in cell["per_layer"]:
        got = read_metric(m["name"], ctx)
        if got is None:
            note(f"per-layer metric {m['name']}: nothing to read, left out")
        else:
            result["metrics"][m["name"]] = got
    for line in ctx.notes:
        note(line)
    steps = [ctx.counters0] + levels
    for name in ("sketch_resident_spill_rows_total",
                 "sketch_resident_continuations_total"):
        per = [(counter_delta(a, b, name),
                counter_delta(a, b, "sketch_records_total"))
               for a, b in zip(steps, steps[1:])]
        note(f"{name} per million records, sketch window by sketch window: "
             + ", ".join(f"{1e6 * d / r:.0f}" if r else "-" for d, r in per))
    return result


def main(argv=None) -> int:
    age0, m0 = harness.process_age_s(), time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), age0, m0)
    except Failed as exc:
        print(f"cellbench: {exc}", file=sys.stderr)
        return 2
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
