"""One module per reader kind: `read(ctx, args)` -> a number, or None where
there is nothing to read (the harness then leaves the metric out of the line).
`ctx` is `cellbench.run.Window`: what one measured window left behind."""

import math


def quantile(values: list, q: float):
    """The q-quantile by linear interpolation; None for no samples."""
    if not values:
        return None
    v = sorted(values)
    pos = q * (len(v) - 1)
    lo, hi = math.floor(pos), math.ceil(pos)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def stat(values: list, how: str, mrec: float):
    """`how`: p50 | p95 | max | mean | sum_per_mrec."""
    if not values:
        return None
    if how == "max":
        return max(values)
    if how == "mean":
        return sum(values) / len(values)
    if how == "sum_per_mrec":
        return sum(values) / mrec if mrec else None
    if how.startswith("p"):
        return quantile(values, int(how[1:]) / 100)
    raise ValueError(f"unknown stat {how!r}")
