"""CPU seconds of the process's threads over the window per million records,
from /proc/self/task/*/stat at both edges. The benchmark's own main thread is
left out; every other thread is the agent's or the runtime's."""


def read(ctx, args):
    if not ctx.records:
        return None
    by_name = {}
    for tid, (name, cpu) in ctx.cpu1.items():
        if tid != ctx.main_tid:
            by_name[name] = (by_name.get(name, 0.0) + cpu
                             - ctx.cpu0.get(tid, (name, 0.0))[1])
    busiest = sorted(by_name.items(), key=lambda kv: -kv[1])[:8]
    ctx.notes.append("threads' CPU seconds over the window: " + ", ".join(
        f"{n} {s:.2f}" for n, s in busiest))
    return sum(by_name.values()) / (ctx.records / 1e6)
