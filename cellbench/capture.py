"""What the PROGRAM wrote into a profiler capture, for the readers that need
more than `xtrace.py` keeps (PR 25; `xtrace.py` stays the yardstick for busy
time, per-executable time and idle gaps, and is used here for those).

Three things, all from the newest `.xplane.pb` of the traced run:

- **module runs by name.** The product names every jitted entry before it
  jits it (`utils/retrace.jit`), so the `XLA Modules` line reads
  `jit_<watch name>(<program id>)`: no inference from `run_id` order. A
  capture of a program that does not (every module `jit_fn(...)`) simply has
  no run under the names the metrics ask for, and their readers return None.
- **the named scope of every device op.** The profiler attaches the HLO
  `op_name` to an op's event METADATA (stat `tf_op`, e.g.
  `jit(ingest_resident_lanes_x4)/jit(main)/countmin/...`), which
  `jax.profiler.ProfileData` does not expose; the few fields needed are read
  from the protobuf wire format directly (`_fields`; no TensorFlow). XLA gives
  a fusion the metadata of its root and a copy it inserts that of its user, so
  attribution is by root.
- **the product's stage annotations** `netobserv:<stage>` on the host plane,
  with their arguments (`eviction`, `evictions`, `chunk`, `k`, `cont`,
  `window`; `fn`, `call` on `dispatch`), on the device trace's clock.
"""

from __future__ import annotations

import collections
import functools
import os
import re

from cellbench import xtrace

ANNOTATION = "netobserv:"
_MODULE = re.compile(r"^jit_(.+)\((\d+)\)$")
#: a path component of an op_name that is a transform, not a scope:
#: `jit(main)`, `vmap(...)`, and the bare `shard_map` of a mesh program
#: (`jit(sharded_ingest_resident_x4)/shard_map/countmin/...`)
_WRAPPER = re.compile(r"^(\w+\(.*\)|shard_map)$")


# --------------------------------------------------------------------------
# the protobuf wire format, as far as XSpace needs it
# --------------------------------------------------------------------------

def _varint(buf, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: an int for a varint, a
    memoryview for a length-delimited field; fixed-width fields are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield key >> 3, value
        elif wire == 2:
            size, i = _varint(buf, i)
            yield key >> 3, buf[i:i + size]
            i += size
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"wire type {wire} in an XSpace")


def _stat(buf) -> tuple:
    """XStat -> (metadata id, value); a ref_value stays an ('ref', id)."""
    key, value = 0, None
    for no, v in _fields(buf):
        if no == 1:
            key = v
        elif no in (3, 4):          # uint64_value, int64_value
            value = v
        elif no == 5:               # str_value
            value = bytes(v).decode("utf-8", "replace")
        elif no == 7:               # ref_value: a stat_metadata id
            value = ("ref", v)
    return key, value


def op_scopes(path: str) -> dict:
    """{(program id, op name): op_name metadata} over the device planes: the
    op's name as an `XLA Ops` event shows it (`fusion.51`), the program as
    the module's name carries it."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for no, plane in _fields(space):
        if no != 1:
            continue
        name, metas, stat_names = "", [], {}
        for pno, v in _fields(plane):
            if pno == 2:
                name = bytes(v).decode()
            elif pno == 4:          # map<int64, XEventMetadata>
                metas.extend(mv for mno, mv in _fields(v) if mno == 2)
            elif pno == 5:          # map<int64, XStatMetadata>
                for mno, mv in _fields(v):
                    if mno == 2:
                        sid, sname = 0, ""
                        for sno, sv in _fields(mv):
                            if sno == 1:
                                sid = sv
                            elif sno == 2:
                                sname = bytes(sv).decode()
                        stat_names[sid] = sname
        if not name.startswith("/device:TPU:"):
            continue
        for meta in metas:
            display, stats = "", {}
            for mno, mv in _fields(meta):
                if mno == 4:
                    display = bytes(mv).decode("utf-8", "replace")
                elif mno == 5:
                    sid, value = _stat(mv)
                    if isinstance(value, tuple):
                        value = stat_names.get(value[1], "")
                    stats[stat_names.get(sid, "")] = value
            if display and "tf_op" in stats and "program_id" in stats:
                out[(str(stats["program_id"]), display)] = stats["tf_op"]
    return out


def scope_of(op_name: str, known) -> str | None:
    """The first path component of an op_name that is one of `known`:
    'jit(f)/jit(main)/countmin/countmin_update_two/pallas_call:' -> countmin.
    Transform wrappers (`jit(main)`, `vmap(...)`, `shard_map`) are stepped
    over; anything else first means the op lies under no known scope."""
    for part in op_name.split("/")[:-1]:
        if part in known:
            return part
        if part and not _WRAPPER.match(part):
            return None
    return None


# --------------------------------------------------------------------------
# the capture
# --------------------------------------------------------------------------

def _module(event_name: str) -> tuple:
    """('ingest_resident_lanes_x4', '<program id>') from a module event's
    name `jit_<name>(<program id>)`; (None, None) for any other name. A
    program from before PR 25 reads `jit_fn(...)`: 'fn', which no metric's
    pattern asks for."""
    m = _MODULE.match(event_name)
    return (m.group(1), m.group(2)) if m else (None, None)


class Capture:
    """lo, hi: the traced window (`cellbench:traced`), seconds;
    devices: per chip, every module run as {"exe", "program", "start", "end"}
    in start order (`exe` None where the name is not `jit_<name>(<id>)`);
    inside: per chip, `xtrace.by_module` of the runs wholly inside the window,
    each with its "program";
    scopes: `op_scopes`; stages: {stage: [(start, end, args)]} by start."""

    def __init__(self, path: str):
        trace = xtrace.load(path)
        self.lo, self.hi = xtrace.window_of(trace)
        self.devices, self.inside = [], []
        for dev in trace.devices:
            runs = []
            for start, end, fp, _ in dev["modules"]:
                exe, program = _module(fp)
                runs.append({"exe": exe, "program": program,
                             "start": start, "end": end})
            self.devices.append(runs)
            mods = xtrace.by_module(dev, {}, self.lo, self.hi)
            for m in mods:      # unnamed by xtrace: "exe" is the event name
                exe, m["program"] = _module(m["exe"])
                m["exe"] = exe or m["exe"]
            self.inside.append(mods)
        self.scopes = op_scopes(path) if any(
            m["program"] for mods in self.inside for m in mods) else {}
        self.stages = _stages(path)

    def in_window(self, stage: str) -> list:
        """The annotations of one stage that lie wholly inside the window."""
        return [s for s in self.stages.get(stage, ())
                if s[0] >= self.lo and s[1] <= self.hi]


def _stages(path: str) -> dict:
    from jax.profiler import ProfileData

    out = collections.defaultdict(list)
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(ANNOTATION):
                    out[e.name[len(ANNOTATION):]].append(
                        (e.start_ns / 1e9, (e.start_ns + e.duration_ns) / 1e9,
                         dict(e.stats)))
    for spans in out.values():
        spans.sort(key=lambda s: s[0])
    return dict(out)


@functools.lru_cache(maxsize=2)
def load(path: str) -> Capture:
    return Capture(path)


def of_run() -> Capture | None:
    """The capture of the traced run in progress (the newest `.xplane.pb`
    under run.py's trace directory), parsed once for all its readers."""
    from cellbench import run

    try:
        return load(os.path.abspath(xtrace.newest(run.TRACE_DIR)))
    except FileNotFoundError:
        return None
