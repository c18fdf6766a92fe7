"""A fold's share of its roofline: the least time the chip could take for the
bytes and operations the fold needs (cellbench/roofline.py, from the
configuration's shapes and the peaks table) over the device time it took,
weighted over the ladder entries by their traced runs. Percent."""

import re

from cellbench import roofline
from cellbench.readers.device_time import runs_of


def read(ctx, args):
    if not ctx.modules:
        return None
    runs = runs_of(ctx, re.compile(args["executable"]))
    peaks = roofline.peaks_for(ctx.device_kind)
    least = took = 0.0
    for exe, secs in runs.items():
        k = re.search(r"_x(\d+)$", exe)
        if not k or not secs:
            continue
        cost = roofline.fold_cost(ctx.config["geometry"], int(k.group(1)),
                                  ctx.n_devices)
        t, bound = roofline.least_seconds(cost, peaks)
        ctx.notes.append(f"roofline {exe}: least {t * 1e6:.1f}us by {bound}, "
                         f"took {sum(secs) / len(secs) * 1e3:.2f}ms a run")
        least += t * len(secs)
        took += sum(secs)
    return 100.0 * least / took if took else None
