"""The share of the traced window, percent, that a thread spent inside one of
the program's stages: the union of the stage's `netobserv:<stage>` annotations
(cellbench/capture.py), clipped to the window, over the window's length.
`evict` is the drain thread (one drain a span), `export` the thread that runs
the exporter's columnar seam (one eviction a span: the queue thread, or
`sketch-fold` under SKETCH_OVERLAP).

`stage`: the stage's name. None where the capture holds no such annotation
(a program without the stage)."""

from cellbench import capture, xtrace


def read(ctx, args):
    cap = capture.of_run()
    if cap is None or not cap.stages.get(args["stage"]):
        return None
    busy, _ = xtrace.union_s(cap.stages[args["stage"]], cap.lo, cap.hi)
    return 100.0 * busy / (cap.hi - cap.lo)
