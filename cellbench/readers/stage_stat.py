"""A statistic of the product's own stage annotations `netobserv:<stage>` in
the capture (utils/tracing.py opens one per stage boundary; cellbench/
capture.py reads them with their arguments), over the annotations that lie
inside the traced window.

`stage`: the stage's name; `where`: {argument: value} an annotation must carry
(e.g. {"cont": 1}, {"k": 4}); `stat`: p50 | p95 | max | mean |
sum_per_mrec. sum_per_mrec scales like device_time: (mean seconds of one
annotation) x (growth of the counter `count` over the whole window: how often
the stage ran) per million records published — `count` is
sketch_superbatch_folds_total for a per-chunk stage.
None where the capture holds no such annotation (a program before PR 25)."""

from cellbench import capture
from cellbench.readers import stat


def read(ctx, args):
    cap = capture.of_run()
    if cap is None:
        return None
    where = args.get("where", {})
    values = [end - start for start, end, got in cap.in_window(args["stage"])
              if all(got.get(k) == v for k, v in where.items())]
    if not values:
        return None
    if args["stat"] != "sum_per_mrec":
        return stat(values, args["stat"], 0)
    if not ctx.records:
        return None
    runs = ctx.counter_delta(args["count"])
    return sum(values) / len(values) * runs / (ctx.records / 1e6)
