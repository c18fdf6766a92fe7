"""Median time from a sketch window's deadline to the arrival of its report at
the sink: the device has finished every fold of the window, the roll and the
transfer by then, so this is the device-true completion time. The deadline of
the window that roll j closes is roll j-1's dispatch plus SKETCH_WINDOW (the
exporter sets it there). Needs the traced run's `jit_call:<roll>` spans."""

import re

from cellbench.readers import stat


def read(ctx, args):
    pat = re.compile(args["roll"])
    rolls = sorted(s for name, got in ctx.spans.by_name.items()
                   if name.startswith("jit_call:") and pat.search(name[9:])
                   for s in got)
    if len(rolls) != len(ctx.all_reports) or len(rolls) < 2:
        return None
    lags = [arrival - (rolls[j - 1][0] + ctx.window_s)
            for j, (arrival, _) in enumerate(ctx.all_reports)
            if j >= 1 and ctx.t0 < arrival <= ctx.t1]
    return stat(lags, args.get("stat", "p50"), 0)
