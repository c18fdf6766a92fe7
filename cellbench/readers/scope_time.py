"""Device op-seconds under named scopes of the program (`jax.named_scope` in
sketch/state.py: resident_decode, hash, countmin, topk, hll_src, hll_grids,
quantile, signals, totals), in the module runs of the executables that match
`executable`, from the capture's op metadata (cellbench/capture.py), averaged
over the chips. Attribution is by fusion root: XLA gives a fusion the metadata
of its root and a copy it inserts that of its user.

`scopes`: the scopes to sum; null with `known` (every scope the program
declares) sums what lies under none of them.
per "mrec": like device_time — sum over the executables of (mean seconds of
one traced run) x (runs in the whole window, /debug/executables) per million
records published; the parts of one executable add up to its device_time.
per "share": percent of those executables' op-seconds, same weights.
None where the capture names no such executable or carries no op metadata
(a program from before PR 25)."""

import re

from cellbench import capture


def _per_exe(cap, pat, wanted, known):
    """{exe: (mean seconds in `wanted`, mean op-seconds) of one run}, means
    over the traced runs and the chips."""
    sums = {}
    for mods in cap.inside:
        for m in mods:
            if m["program"] is None or not pat.search(m["exe"]):
                continue
            mine = 0.0
            for op, _label, s in m["ops"]:
                # an op the profiler gave no op_name lies under no scope
                scope = capture.scope_of(
                    cap.scopes.get((m["program"], op), ""), known)
                if (scope in wanted) if wanted is not None else scope is None:
                    mine += s
            got = sums.setdefault(m["exe"], [0.0, 0.0, 0])
            got[0] += mine
            got[1] += m["op_s"]
            got[2] += 1
    return {exe: (a / n, b / n) for exe, (a, b, n) in sums.items()}


def read(ctx, args):
    cap = capture.of_run()
    if cap is None or not cap.scopes:
        return None
    wanted = args.get("scopes")
    known = set(args.get("known") or wanted)
    per_exe = _per_exe(cap, re.compile(args["executable"]),
                       None if wanted is None else set(wanted), known)
    if not per_exe:
        return None
    part = sum(a * ctx.calls_in_window(exe) for exe, (a, _) in per_exe.items())
    if args["per"] == "share":
        whole = sum(b * ctx.calls_in_window(exe)
                    for exe, (_, b) in per_exe.items())
        return 100.0 * part / whole if whole else None
    return part / (ctx.records / 1e6) if ctx.records else None
