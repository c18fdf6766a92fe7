"""The device's idle share of the traced window, percent: 1 - (union of the
device-op intervals) / window, averaged over the chips."""


def read(ctx, args):
    if not ctx.busy_s or not ctx.trace_window_s:
        return None
    return 100.0 * (1.0 - ctx.busy_s / ctx.trace_window_s)
