"""A named scope's share of its roofline: the least time the chip could take
for the work the scope's ALGORITHM needs (cellbench/scope_cost.py, from the
configuration's shapes and the peaks table) over the device time the ops under
the scope took (as `scope_time` finds them), weighted over the ladder entries
by their runs in the whole window. Percent.

`cost` names the function in scope_cost.COSTS; `scopes` the scopes whose ops
are the time. None where the capture names no such executable or carries no op
metadata (a program from before PR 25), or where no run lies under the scope."""

import re

from cellbench import capture, roofline, scope_cost
from cellbench.readers.scope_time import _per_exe


def read(ctx, args):
    cap = capture.of_run()
    if cap is None or not cap.scopes:
        return None
    wanted = set(args["scopes"])
    per_exe = _per_exe(cap, re.compile(args["executable"]), wanted, wanted)
    peaks = roofline.peaks_for(ctx.device_kind)
    cost_of = scope_cost.COSTS[args["cost"]]
    least = took = 0.0
    for exe, (secs, _) in per_exe.items():
        k = re.search(r"_x(\d+)$", exe)
        calls = ctx.calls_in_window(exe)
        if not k or not secs or not calls:
            continue
        t, bound = roofline.least_seconds(
            cost_of(ctx.config["geometry"], int(k.group(1)), ctx.n_devices),
            peaks)
        ctx.notes.append(
            f"roofline {'+'.join(sorted(wanted))} of {exe}: least "
            f"{t * 1e6:.2f}us by {bound}, took {secs * 1e3:.3f}ms a run")
        least += t * calls
        took += secs * calls
    return 100.0 * least / took if took else None
