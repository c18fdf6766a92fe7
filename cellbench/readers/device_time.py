"""Device seconds of the operations under the executables that match
`executable` (a regular expression over the watched jit names), from the
profiler trace, averaged over the chips.

per "mrec": sum over those executables of (mean op-seconds of one run, from
the traced slice) x (runs in the whole window, from /debug/executables) per
million records published — so the slice's edges do not matter.
per "run_p50": the median op-seconds of one run."""

import re

from cellbench.readers import quantile


def runs_of(ctx, pat):
    """{exe: [op-seconds of each traced run, averaged over the chips]}"""
    per_dev = []
    for mods in ctx.modules:
        got = {}
        for m in mods:
            if pat.search(m["exe"]):
                got.setdefault(m["exe"], []).append(m["op_s"])
        per_dev.append(got)
    out = {}
    for exe in per_dev[0] if per_dev else ():
        n = min(len(d.get(exe, ())) for d in per_dev)
        out[exe] = [sum(d[exe][i] for d in per_dev) / len(per_dev)
                    for i in range(n)]
    return out


def read(ctx, args):
    if not ctx.modules:
        return None
    runs = runs_of(ctx, re.compile(args["executable"]))
    if not runs:
        return None
    if args["per"] == "run_p50":
        return quantile([s for v in runs.values() for s in v], 0.5)
    if not ctx.records:
        return None
    total = sum(sum(v) / len(v) * ctx.calls_in_window(exe)
                for exe, v in runs.items() if v)
    return total / (ctx.records / 1e6)
