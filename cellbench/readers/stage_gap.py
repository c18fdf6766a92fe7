"""A statistic, over the evictions whose `to` stage lies wholly inside the
traced window, of the time from one edge of the eviction's `from` stage to
one edge of its `to` stage, the two joined on an id the program stamps on
both (`eviction`: utils/tracing.py, cellbench/capture.py).

`from`, `to`: [stage, "start" | "end"]; `on`: the id; `stat`: p50 | p95 |
max | mean. ["evict", "end"] -> ["export", "start"] is the export queue
alone; ["evict", "start"] -> ["export", "end"] the program's twin of the
judged eviction lag (hand-over to the seam's return). None where the
capture holds either stage without the id (a program without the stage)."""

from cellbench import capture
from cellbench.readers import stat

_EDGE = {"start": 0, "end": 1}


def pairs(cap, src: list, dst: list, on: str) -> list:
    """[to edge - from edge] of every `to` annotation in the window whose id
    some `from` annotation carries (the latest such, where several do)."""
    (a, a_edge), (b, b_edge) = src, dst
    first = {}
    for span in cap.stages.get(a, ()):
        if on in span[2]:
            first[span[2][on]] = span[_EDGE[a_edge]]
    return [span[_EDGE[b_edge]] - first[span[2][on]]
            for span in cap.in_window(b)
            if span[2].get(on) in first]


def read(ctx, args):
    cap = capture.of_run()
    if cap is None:
        return None
    values = pairs(cap, args["from"], args["to"], args["on"])
    return stat(values, args["stat"], 0) if values else None
