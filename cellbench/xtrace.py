"""Reduction of a profiler trace (`.xplane.pb`) to what the readers need.

Read with `jax.profiler.ProfileData` and nothing else. Per device plane
(`/device:TPU:<n>`): the `XLA Modules` line gives one event per executed
program with its `run_id`, the `XLA Ops` line one event per operation. The
host plane carries the benchmark's own `cellbench:<name>` annotations, on the
same clock.

The product jits every entry point as `fn`, so the trace names all modules
`jit_fn(<fingerprint>)`. They are named here by the order of execution: the
device runs programs in the order the host dispatched them, `run_id` counts
them, and the benchmark logs the name of every watched jit call in order. The
offset between the two sequences is the one under which every fingerprint maps
to one name and every name to one fingerprint.
"""

from __future__ import annotations

import collections
import functools
import glob
import os
import re

COLLECTIVE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)")
_OP = re.compile(r"^%(\S+) = (.*?) ([a-z][a-z0-9\-]*)\(")
_LAYOUT = re.compile(r"\{[^{}]*\}")
ANNOTATION = "cellbench:"


class Trace:
    """devices: [{"modules": [(start, end, fingerprint, run_id)],
    "ops": [(start, end, name)]}], seconds on the trace's clock;
    host: {annotation: [(start, end)]}."""

    def __init__(self, devices: list, host: dict):
        self.devices, self.host = devices, host


def newest(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    devices, host = [], collections.defaultdict(list)
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            dev = {"modules": [], "ops": []}
            for line in plane.lines:
                if line.name == "XLA Modules":
                    for e in line.events:
                        run_id = dict(e.stats).get("run_id")
                        dev["modules"].append(
                            (e.start_ns / 1e9,
                             (e.start_ns + e.duration_ns) / 1e9,
                             e.name, run_id))
                elif line.name == "XLA Ops":
                    dev["ops"] = [(e.start_ns / 1e9,
                                   (e.start_ns + e.duration_ns) / 1e9, e.name)
                                  for e in line.events]
            dev["modules"].sort()
            dev["ops"].sort()
            if dev["ops"]:
                devices.append(dev)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(ANNOTATION):
                        host[e.name[len(ANNOTATION):]].append(
                            (e.start_ns / 1e9,
                             (e.start_ns + e.duration_ns) / 1e9))
    for spans in host.values():
        spans.sort()
    return Trace(devices, dict(host))


def union_s(intervals, lo: float, hi: float):
    """Total length of the union of `intervals` (sorted by start) clipped to
    [lo, hi], and the gaps between them inside it."""
    busy, gaps, edge = 0.0, [], lo
    for a, b, *_ in intervals:
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        if a > edge:
            gaps.append((edge, a))
        if b > edge:
            busy += b - max(a, edge)
            edge = b
    if hi > edge:
        gaps.append((edge, hi))
    return busy, gaps


def name_modules(modules: list, calls: list) -> dict:
    """fingerprint -> executable name, from the `run_id` order (see the
    module docstring). {} where no single consistent naming exists."""
    runs = [(rid, fp) for _, _, fp, rid in modules if rid is not None]
    if not runs or not calls:
        return {}
    found = []
    for off in range(min(r for r, _ in runs) + 1):
        fwd, back, ok = {}, {}, True
        for rid, fp in runs:
            i = rid - off
            if not 0 <= i < len(calls):
                ok = False
                break
            name = calls[i]
            if fwd.setdefault(fp, name) != name or \
                    back.setdefault(name, fp) != fp:
                ok = False
                break
        if ok and fwd not in found:
            found.append(fwd)
    return found[0] if len(found) == 1 else {}


@functools.lru_cache(maxsize=None)
def op_label(name: str) -> tuple:
    """('fusion.51', 'fusion:f32[270336]') from an XLA op event's name."""
    m = _OP.match(name)
    if not m:
        return name[:40], ""
    shape = _LAYOUT.sub("", m.group(2)).replace(" ", "")
    if shape.startswith("("):
        parts = shape.strip("()").split("],")
        shape = parts[0].rstrip("]") + "]" + (f"x{len(parts)}"
                                              if len(parts) > 1 else "")
    return m.group(1), f"{m.group(3)}:{shape}"


def by_module(dev: dict, names: dict, lo: float, hi: float) -> list:
    """Every module run that lies wholly inside [lo, hi]:
    {"exe", "start", "end", "ops": [(op, label, seconds)], "op_s"}."""
    out, ops, j = [], dev["ops"], 0
    for start, end, fp, _ in dev["modules"]:
        while j < len(ops) and ops[j][0] < start:
            j += 1
        k, mine = j, []
        while k < len(ops) and ops[k][0] < end:
            op, label = op_label(ops[k][2])
            mine.append((op, label, ops[k][1] - ops[k][0]))
            k += 1
        j = k
        if start >= lo and end <= hi:
            out.append({"exe": names.get(fp, fp), "start": start, "end": end,
                        "ops": mine, "op_s": sum(o[2] for o in mine)})
    return out


def window_of(trace: Trace) -> tuple:
    """The traced window: the benchmark's `traced` annotation."""
    spans = trace.host.get("traced")
    if not spans:
        raise ValueError("the trace holds no cellbench:traced annotation")
    return spans[0]


def idle_gaps(trace: Trace, lo: float, hi: float, order: list) -> list:
    """[[what the host was doing, idle seconds]], most first: every gap of
    the first device's busy union, given to the first annotation of `order`
    that covers the gap's middle, else to "none"."""
    _, gaps = union_s(trace.devices[0]["ops"], lo, hi)
    total = collections.Counter()
    spans = {n: sorted(x for name, got in trace.host.items()
                       if name == n or name.startswith(n + ":")
                       for x in got) for n in order}
    cursor = dict.fromkeys(order, 0)
    for a, b in gaps:
        mid, what = (a + b) / 2, "none"
        for n in order:
            s, i = spans[n], cursor[n]
            while i < len(s) and s[i][1] < mid:
                i += 1
            cursor[n] = i
            # annotations of one name may nest or overlap across threads:
            # look a few ahead
            if any(x[0] <= mid <= x[1] for x in s[i:i + 16]):
                what = n
                break
        total[what] += b - a
    return [[n, s] for n, s in total.most_common(10)]
