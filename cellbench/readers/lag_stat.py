"""A statistic over the evictions whose export returned inside the window, of
the time between two of an eviction's stamps: `handover` (the fetcher's
lookup_and_delete was called: its newest record's creation time),
`export_in` and `export_out` (the exporter's export_evicted entered and
returned: the eviction is packed and dispatched, at most a ring's slots ahead
of the device)."""

from cellbench.readers import stat

_AT = {"handover": 0, "export_in": 1, "export_out": 2}


def read(ctx, args):
    a, b = _AT[args["from"]], _AT[args["to"]]
    values = [s[b] - s[a] for s in ctx.samples]
    return stat(values, args["stat"], ctx.records / 1e6)
