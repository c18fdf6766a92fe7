#!/usr/bin/env python3
"""cellbench/cut_capture.py — cut a recorded `.xplane.pb` down to a fixture
for the readers of cellbench/capture.py.

    python3 cellbench/cut_capture.py <in.xplane.pb> <out.xplane.pb> <from_s> <to_s>

Like cut_trace.py it keeps, of the device planes, the `XLA Modules` and
`XLA Ops` events that lie wholly inside [from_s, to_s] (seconds on the trace's
clock) and cuts `cellbench:traced` to the slice. Unlike it, it keeps what
PR 25's readers read: each op's `display_name` and the `tf_op` (the HLO
op_name, with its named scopes) and `program_id` stats of its metadata, and
the product's `netobserv:<stage>` annotations on the host plane with their
arguments. Every other metadata stat and the op events' own stats are
dropped, and an op's name (its whole HLO line) is cut to its first 240
characters. A tool for whoever records a
new fixture, not part of a run: it needs the XSpace protobuf classes, which
come with TensorFlow here (`tensorflow.tsl.profiler.protobuf.xplane_pb2`);
the tests read the fixture with jax and capture.py's own wire-format reader.
"""

import sys

PREFIXES = ("cellbench:", "netobserv:")
KEEP_STATS = ("tf_op", "program_id")
NAME_CHARS = 240


def main() -> int:
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    src, dst = sys.argv[1], sys.argv[2]
    lo, hi = float(sys.argv[3]), float(sys.argv[4])
    space = xplane_pb2.XSpace()
    with open(src, "rb") as f:
        space.ParseFromString(f.read())
    lo_ps, hi_ps = int(lo * 1e12), int(hi * 1e12)
    keep_planes = []
    for plane in space.planes:
        device = plane.name.startswith("/device:TPU:")
        if not device and plane.name != "/host:CPU":
            continue
        names = {i: m.name for i, m in plane.event_metadata.items()}
        stat_ids = {i: m.name for i, m in plane.stat_metadata.items()}
        lines = []
        for line in plane.lines:
            if device and line.name not in ("XLA Modules", "XLA Ops"):
                continue
            base = line.timestamp_ns * 1000
            events = []
            for e in line.events:
                a, b = base + e.offset_ps, base + e.offset_ps + e.duration_ps
                name = names.get(e.metadata_id, "")
                if not device and not name.startswith(PREFIXES):
                    continue
                if name == "cellbench:traced":
                    e.offset_ps, e.duration_ps = lo_ps - base, hi_ps - lo_ps
                elif a < lo_ps or b > hi_ps:
                    continue
                kept = xplane_pb2.XEvent.FromString(e.SerializeToString())
                if line.name == "XLA Ops":
                    del kept.stats[:]   # device offsets: nothing reads them
                events.append(kept)
            if events:
                del line.events[:]
                line.events.extend(events)
                lines.append(xplane_pb2.XLine.FromString(
                    line.SerializeToString()))
        del plane.lines[:]
        plane.lines.extend(lines)
        used_e = {e.metadata_id for ln in plane.lines for e in ln.events}
        for i in [i for i in plane.event_metadata if i not in used_e]:
            del plane.event_metadata[i]
        used_s = {s.metadata_id for ln in plane.lines for e in ln.events
                  for s in e.stats}
        used_s |= {s.ref_value for ln in plane.lines for e in ln.events
                   for s in e.stats if s.WhichOneof("value") == "ref_value"}
        for m in plane.event_metadata.values():
            kept = [xplane_pb2.XStat.FromString(s.SerializeToString())
                    for s in m.stats
                    if stat_ids.get(s.metadata_id) in KEEP_STATS]
            del m.stats[:]
            m.stats.extend(kept)
            for s in kept:
                used_s.add(s.metadata_id)
                if s.WhichOneof("value") == "ref_value":
                    used_s.add(s.ref_value)
            m.name = m.name[:NAME_CHARS]
        for i in [i for i in plane.stat_metadata if i not in used_s]:
            del plane.stat_metadata[i]
        del plane.stats[:]
        keep_planes.append(xplane_pb2.XPlane.FromString(
            plane.SerializeToString()))
    del space.planes[:]
    space.planes.extend(keep_planes)
    with open(dst, "wb") as f:
        f.write(space.SerializeToString())
    print(f"{dst}: {len(space.SerializeToString())} bytes, "
          f"{sum(len(ln.events) for p in space.planes for ln in p.lines)} events")
    return 0


if __name__ == "__main__":
    sys.exit(main())
