"""scale * delta(num) / delta(den) over the window, from two scrapes of the
product's /metrics. `num`, `den`: counter names (prefix stripped); every label
set of a name is summed. den "records" is the window's published records."""


def _delta(ctx, name):
    if name == "records":
        return ctx.records
    return ctx.counter_delta(name)


def read(ctx, args):
    den = _delta(ctx, args["den"])
    if not den:
        return None
    return args.get("scale", 1.0) * _delta(ctx, args["num"]) / den
