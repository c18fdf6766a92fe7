"""The share of chip 0's idle time, percent, by what the program was doing:
each gap of the chip's busy union in the traced window (as
`xtrace.idle_gaps` finds them) counts where its middle lies inside one of the
`inside` stages (null: anywhere) and inside none of the `outside` stages. The
stages are the program's own `netobserv:<stage>` annotations
(cellbench/capture.py), on the device trace's clock, whatever thread opened
them.

`requires`: stages the capture must hold for the split to mean anything (the
seam's `export`); None where it lacks one of them, or where the chip never
idled."""

import bisect
import functools

from cellbench import capture, xtrace


@functools.lru_cache(maxsize=1)
def _gaps(path: str) -> list:
    trace = xtrace.load(path)
    lo, hi = xtrace.window_of(trace)
    return xtrace.union_s(trace.devices[0]["ops"], lo, hi)[1]


def merged(spans: list) -> tuple:
    """(starts, ends) of the union of `spans` (by start), disjoint, sorted."""
    starts, ends = [], []
    for a, b, *_ in spans:
        if ends and a <= ends[-1]:
            ends[-1] = max(ends[-1], b)
        else:
            starts.append(a)
            ends.append(b)
    return starts, ends


def covers(union: tuple, t: float) -> bool:
    starts, ends = union
    i = bisect.bisect_right(starts, t) - 1
    return i >= 0 and t <= ends[i]


def share(gaps: list, stages: dict, inside, outside) -> float | None:
    """Percent of the gaps' seconds whose middle lies inside one of
    `inside` (None: anywhere) and none of `outside`."""
    def union(names):
        return merged(sorted(s for n in names for s in stages.get(n, ())))
    ins = None if inside is None else union(inside)
    outs = union(outside)
    total = mine = 0.0
    for a, b in gaps:
        total += b - a
        mid = (a + b) / 2
        if (ins is None or covers(ins, mid)) and not covers(outs, mid):
            mine += b - a
    return 100.0 * mine / total if total else None


def read(ctx, args):
    cap = capture.of_run()
    if cap is None or not cap.devices or not all(
            cap.stages.get(s) for s in args.get("requires", ())):
        return None
    from cellbench import run

    gaps = _gaps(xtrace.newest(run.TRACE_DIR))
    return share(gaps, cap.stages, args.get("inside"),
                 args.get("outside", ()))
