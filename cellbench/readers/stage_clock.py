"""A ratio of the program's own stage clocks over the window, from two scrapes
of `/metrics`: `stage_wall_seconds_total{stage}`,
`stage_cpu_seconds_total{stage}` and `stage_calls_total{stage}`
(utils/tracing.py; every call of a stage, sampled or not, children
included).

`stages`: the stages summed; `num`, `den`: "wall" | "cpu" | "calls" of those
stages, or `den` "records" (the window's published records); `scale`
multiplies. `cpu` over `wall` is the share of the stage a thread ran on a
CPU: the rest it waited (the GIL, a lock, a device call). A ratio of two
counters, so the traced run's late closing scrape cannot skew it.
None where the program has none of these counters (a program without the
stage clocks) or nothing grew.

The first read of a run notes every stage's wall, CPU and calls over the
window, and the CPU of the top-level stages (`evict`, `export`, `publish`,
`roll_drain`, `roll_dispatch`: a window closed by the seam nests its `roll_*`
inside `export`, so this sum is an upper bound) against the /proc CPU of the
agent's threads."""

COUNTER = {"wall": "stage_wall_seconds_total",
           "cpu": "stage_cpu_seconds_total",
           "calls": "stage_calls_total"}
TOP = ("evict", "export", "publish", "roll_drain", "roll_dispatch")


def deltas(ctx, what: str) -> dict:
    """{stage: growth over the window} of one stage counter; {} where the
    program does not export it."""
    name = COUNTER[what]
    before, after = ctx.counters0 or {}, ctx.counters1 or {}
    return {k[1][0]: v - before.get(k, 0.0) for k, v in after.items()
            if k[0] == name and len(k[1]) == 1}


def _note(ctx) -> None:
    wall, cpu, calls = (deltas(ctx, w) for w in ("wall", "cpu", "calls"))
    ctx.notes.append("stage clocks over the window (wall s / CPU s / calls): "
                     + ", ".join(f"{s} {wall[s]:.3f}/{cpu.get(s, 0):.3f}/"
                                 f"{calls.get(s, 0):.0f}"
                                 for s in sorted(wall, key=lambda s: -wall[s])
                                 if calls.get(s)))
    if ctx.cpu0 is None or ctx.cpu1 is None:
        return
    proc = sum(c - ctx.cpu0.get(tid, (n, 0.0))[1]
               for tid, (n, c) in ctx.cpu1.items() if tid != ctx.main_tid)
    top = sum(cpu.get(s, 0.0) for s in TOP)
    ctx.notes.append(f"stage clocks: top-level stages' CPU {top:.3f} s of "
                     f"the agent threads' /proc CPU {proc:.3f} s "
                     f"({100 * top / proc if proc else 0:.1f}%)")


def read(ctx, args):
    num = deltas(ctx, args["num"])
    if not any(s in num for s in args["stages"]):
        return None
    if not getattr(ctx, "stage_clocks_noted", False):
        ctx.stage_clocks_noted = True
        _note(ctx)
    top = sum(num.get(s, 0.0) for s in args["stages"])
    if args["den"] == "records":
        bottom = ctx.records
    else:
        bottom = sum(deltas(ctx, args["den"]).get(s, 0.0)
                     for s in args["stages"])
    if not bottom:
        return None
    return args.get("scale", 1.0) * top / bottom
