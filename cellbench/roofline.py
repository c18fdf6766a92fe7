"""What one fold has to do, from the configuration's shapes: the bytes and
operations the algorithm needs (not what the kernels happen to execute), and
the least time a chip could take for them by the peaks table.

Bytes: the packed rows shipped once, and every sketch table read and written
once a fold. Operations: one update per record per table row it touches. A
fold's one-hot matmuls, padding and copies are the implementation's, so they
count as time and not as needed work: that is what the share is for.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks_for(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "cellbench/peaks.json: add it with its source")
    return table[device_kind]


def table_bytes(geometry: dict) -> int:
    """Bytes of the sketch state one fold updates (f32/i32 cells)."""
    g = geometry
    cells = (g["cm_planes"] * g["cm_depth"] * g["cm_width"]
             + (1 << g["hll_precision"])
             + g["grids"] * g["grid_buckets"] * g["grid_registers"]
             + g["topk"] * g["topk_words"]
             + g["signal_planes"] * g["signal_buckets"])
    return 4 * cells


def fold_cost(geometry: dict, k: int, shards: int) -> dict:
    """Needed bytes and operations of one ladder-k fold ON ONE DEVICE of
    `shards`: it receives its share of the rows and updates its own full
    copy of the tables (the per-device partials the roll merges)."""
    rows = k * geometry["batch"] / shards
    ship = k * geometry["ship_bytes_per_batch"] / shards
    updates_per_row = (geometry["cm_planes"] * geometry["cm_depth"] + 1
                       + geometry["grids"] + geometry["signal_planes"] + 1)
    return {"bytes": ship + 2 * table_bytes(geometry),
            "ops": rows * updates_per_row}


def least_seconds(cost: dict, peaks: dict) -> tuple:
    by_bytes = cost["bytes"] / peaks["bytes_per_s"]
    by_ops = cost["ops"] / peaks["flops_per_s"]
    return max(by_bytes, by_ops), ("bytes" if by_bytes >= by_ops else "ops")
