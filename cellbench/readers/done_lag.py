"""Per eviction, the time to the END ON THE DEVICE of the last fold chunk that
carries its rows — the device-true twin of the eviction lags, which end when
`export_evicted` returns (at dispatch). Everything is read from the capture
(cellbench/capture.py): the product's `netobserv:` annotations and the module
runs, on one clock.

The chain, by the ids the product stamps (utils/tracing.py):
  `evict` (eviction=n)  ->  the `ingest_dispatch` annotations whose
  `evictions=<a>-<b>` range holds n; the last of them is final once a later
  chunk's range starts beyond n  ->  the `dispatch` annotation inside it
  (fn=<watch name>, call=<c>)  ->  the run of module `jit_<fn>` that executed
  call c.
A chip runs programs in the order the host dispatched them, so the i-th run
(of the executables that were dispatched inside the capture) executed dispatch
`i - j`, where j counts the runs already in flight when the capture began. j
is the least offset under which every run carries its dispatch's name and
starts after its dispatch began — and that is only PROOF where some run
started on an idle chip right at its own dispatch (under a backlog the
condition also holds for offsets that are too small). Where no run does (a
saturated device), or more runs were in flight than `max_in_flight`, the join
is ambiguous: None and a note, never a guess. `call`, the per-executable
sequence number, is the cross-check: the calls of one executable must come
out consecutive.

`from`: "evict" (the start of the eviction's `evict` annotation: the drain
began) or "dispatch" (the end of the last chunk's `dispatch`: how far the
ring runs ahead of the chip). `stat`: p50 | p95 | max | mean."""

from cellbench import capture
from cellbench.readers import stat

#: a run that starts within SLACK_S of its dispatch's end, after an idle gap
#: of at least IDLE_S on its chip, was started by that dispatch; the device's
#: clock may lead the host's by up to SKEW_S (0.3-0.5 ms read on the v5e)
SLACK_S = 0.005
IDLE_S = 0.001
SKEW_S = 0.002


def _inside(outer: list, inner: list) -> dict:
    """{index into outer: the inner annotation it contains}; both by start."""
    out, j = {}, 0
    for i, (a, b, _) in enumerate(outer):
        while j < len(inner) and inner[j][0] < a:
            j += 1
        if j < len(inner) and inner[j][1] <= b:
            out[i] = inner[j]
    return out


def _run_ends(runs: list, dispatches: list, max_in_flight: int):
    """{(fn, call): end of its module run} on one chip, or None where no
    offset is proven. `runs`: the chip's module runs in start order;
    `dispatches`: every `dispatch` annotation, (start, end, args) by start."""
    fns = {d[2]["fn"] for d in dispatches}
    mine = [(r, runs[i - 1]["end"] if i else None)
            for i, r in enumerate(runs) if r["exe"] in fns]
    for j in range(max_in_flight + 1):
        pairs = list(zip(mine[j:], dispatches))
        if pairs and all(r["exe"] == d[2]["fn"]
                         and r["start"] >= d[0] - SKEW_S
                         for (r, _), d in pairs):
            break
    else:
        return None
    if not any(before is not None and r["start"] - before >= IDLE_S
               and r["start"] <= d[1] + SLACK_S
               for (r, before), d in pairs):
        return None
    ends, last = {}, {}
    for (r, _), d in pairs:
        fn, call = d[2]["fn"], d[2]["call"]
        if last.setdefault(fn, call - 1) != call - 1:
            return None         # a watched call the capture did not see
        last[fn] = call
        ends[(fn, call)] = r["end"]
    return ends


def lags(cap, max_in_flight: int = 8):
    """[(eviction, done - evict start, done - last dispatch end)] for every
    eviction drained inside the traced window whose last chunk is known, or
    (None, why) where the join is ambiguous."""
    sends = cap.stages.get("ingest_dispatch", [])
    calls = _inside(sends, cap.stages.get("dispatch", []))
    chunks = []         # (first, last, fn, call, dispatch end) by chunk order
    for i, (_, _, got) in enumerate(sends):
        if i not in calls or "evictions" not in got:
            continue
        first, last = (int(x) for x in str(got["evictions"]).split("-"))
        d = calls[i]
        chunks.append((first, last, d[2]["fn"], d[2]["call"], d[1]))
    if not chunks:
        return None, "no chunk annotation carries an evictions range"
    per_chip = [_run_ends(runs, cap.stages["dispatch"], max_in_flight)
                for runs in cap.devices]
    if not per_chip or any(e is None for e in per_chip):
        return None, ("no run starts on an idle chip at its own dispatch, or "
                      "the runs do not carry the dispatches' names in order: "
                      "which run executed which call is not proven")
    # on a mesh a chunk is done when its run has ended on every chip
    ends = {key: max(e[key] for e in per_chip)
            for key in set.intersection(*(set(e) for e in per_chip))}
    out = []
    for start, end, got in cap.in_window("evict"):
        n = got.get("eviction")
        holding = [c for c in chunks if c[0] <= n <= c[1]]
        if not holding or not any(c[0] > n for c in chunks):
            continue        # nothing folded yet, or its tail may still wait
        _, _, fn, call, sent = holding[-1]
        if (fn, call) in ends:
            done = ends[(fn, call)]
            out.append((n, done - start, done - sent))
    return out, ""


def read(ctx, args):
    cap = capture.of_run()
    if cap is None or "evict" not in cap.stages:
        return None
    got, why = lags(cap, args.get("max_in_flight", 8))
    if got is None:
        ctx.notes.append(f"done_lag: {why}")
        return None
    ctx.notes.append(f"done_lag from {args['from']}: {len(got)} evictions "
                     "joined to the end of their last fold on the chip")
    values = [g[1] if args["from"] == "evict" else g[2] for g in got]
    return stat(values, args["stat"], 0)
