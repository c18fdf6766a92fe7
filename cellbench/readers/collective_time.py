"""Seconds of collective operations (all-reduce, all-gather, ...) in one run
of the executables that match `executable`, mean over the traced runs and the
chips. None on one chip, where there are none."""

import re

from cellbench.xtrace import COLLECTIVE


def read(ctx, args):
    pat = re.compile(args["executable"])
    per_run = [sum(s for _, label, s in m["ops"] if COLLECTIVE.search(label))
               for mods in ctx.modules for m in mods if pat.search(m["exe"])]
    if not per_run or not any(per_run):
        return None
    return sum(per_run) / len(per_run)
