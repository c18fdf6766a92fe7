#!/usr/bin/env python3
"""cellbench/cut_trace.py — cut a recorded `.xplane.pb` down to a test fixture.

    python3 cellbench/cut_trace.py <in.xplane.pb> <out.xplane.pb> <from_s> <to_s>

Keeps, of the device planes, the `XLA Modules` and `XLA Ops` events that lie
wholly inside [from_s, to_s] (seconds on the trace's clock), and of the host
plane the benchmark's own `cellbench:` annotations; `cellbench:traced` is cut
to the slice itself. Metadata nothing refers to any more is dropped, and an
op's name (its whole HLO line) is cut to its first 240 characters. A tool for
whoever records a new fixture, not part of a run: it needs the XSpace protobuf
classes, which come with TensorFlow here
(`tensorflow.tsl.profiler.protobuf.xplane_pb2`); the tests read the fixture
with jax alone.
"""

import sys

PREFIX = "cellbench:"
#: an op's name is its whole HLO line; its head holds name, shape and opcode
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
        lines = []
        for line in plane.lines:
            if device and line.name not in ("XLA Modules", "XLA Ops"):
                continue
            base = line.timestamp_ns * 1000
            events = []
            for e in line.events:
                a, b = base + e.offset_ps, base + e.offset_ps + e.duration_ps
                name = names.get(e.metadata_id, "")
                if not device and not name.startswith(PREFIX):
                    continue
                if name == PREFIX + "traced":
                    e.offset_ps, e.duration_ps = lo_ps - base, hi_ps - lo_ps
                elif a < lo_ps or b > hi_ps:
                    continue
                events.append(e)
            if events:
                kept = [xplane_pb2.XEvent.FromString(e.SerializeToString())
                        for e in events]
                del line.events[:]
                line.events.extend(kept)
                lines.append(xplane_pb2.XLine.FromString(
                    line.SerializeToString()))
        del plane.lines[:]
        plane.lines.extend(lines)
        used_e = {e.metadata_id for ln in plane.lines for e in ln.events}
        used_s = {s.metadata_id for ln in plane.lines for e in ln.events
                  for s in e.stats}
        for i in [i for i in plane.event_metadata if i not in used_e]:
            del plane.event_metadata[i]
        for m in plane.event_metadata.values():
            del m.stats[:]
            m.ClearField("display_name")
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
