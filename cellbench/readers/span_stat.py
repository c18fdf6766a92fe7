"""A statistic of the benchmark's own spans of one name that ended inside the
window. `span`: the name (a prefix before ':' takes every span under it);
`stat`: p50 | p95 | max | mean | sum_per_mrec."""

from cellbench.readers import stat


def read(ctx, args):
    want = args["span"]
    values = [d for name in ctx.spans.by_name
              if name == want or name.startswith(want + ":")
              for d in ctx.spans.durations(name, ctx.t0, ctx.t1)]
    return stat(values, args["stat"], ctx.records / 1e6)
