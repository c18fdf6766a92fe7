#!/usr/bin/env python3
"""Regenerate docs/config.md from the AgentConfig dataclass (make gen-docs)."""
import dataclasses
import sys

sys.path.insert(0, ".")
from netobserv_tpu.config import AgentConfig, _DURATION_FIELDS  # noqa: E402

out = []
out.append("# Configuration\n")
out.append("All configuration is environment-driven (no flags, no files), matching")
out.append("the reference agent's surface. Durations use Go syntax (`5s`, `300ms`, `1m30s`).\n")
out.append("| Env var | Default | Type | Field |")
out.append("|---|---|---|---|")
for f in dataclasses.fields(AgentConfig):
    env = f.metadata.get("env", "")
    if not env:
        continue
    default = f.metadata.get("default", "")
    typ = ("duration" if f.name in _DURATION_FIELDS
           else (f.type if isinstance(f.type, str) else f.type.__name__))
    out.append(f"| `{env}` | `{default}` | {typ} | {f.name} |")
out.append("")
out.append("## Notes")
out.append("- `EXPORT` selects the backend: `grpc`, `kafka`, `ipfix+udp`, `ipfix+tcp`,")
out.append("  `direct-flp`, `stdout`, or the TPU-native `tpu-sketch`.")
out.append("- `FLOW_FILTER_RULES` takes a JSON array of rule objects (see docs/flow_filtering.md).")
out.append("- `SKETCH_*` knobs configure the tpu-sketch backend (sizes must be powers of two where noted).")
out.append("- Sizing for a key count: `SKETCH_CM_WIDTH` W costs 2 planes x `SKETCH_CM_DEPTH` x W x 4 B on the device")
out.append("  (2 MB at 65,536; 134 MB at 4,194,304) — one counter a depth row for every 4 distinct keys of a window;")
out.append("  `SKETCH_RESIDENT_SLOTS` costs 32 pack regions x slots x 40 B (335 MB at 262,144; 1.34 GB at 1,048,576) and")
out.append("  must exceed the live distinct keys, or the lane dictionaries roll epochs")
out.append("  (docs/tpu_sketch.md \"Sizing the sketch for a key count\").")
out.append("- `DATAPATH` (this framework only): `kernel`, `synthetic`, `pcap:<path>`, or `grpc:<port>`.")
out.append("- `UDN_MAPPING_FILE` (this framework only): JSON {iface: udn} map for ENABLE_UDN_MAPPING.")
with open("docs/config.md", "w") as fh:
    fh.write("\n".join(out) + "\n")
print("docs/config.md regenerated")
