"""What one named scope of a fold has to do, from the configuration's shapes:
the bytes and operations the ALGORITHM needs, whichever form the program runs
(`roofline.py` does the same for a whole fold). A scope's share of its
roofline reckoned from here cannot go stale when a kernel is replaced, and
cannot pass 100%: no form can touch less than this.

`countmin`: a record adds its value to `cm_planes` x `cm_depth` counters —
each a 4-byte read and a 4-byte write, one add — and the scope reads the
record's two base hashes, its two values and its valid flag once.
"""

from __future__ import annotations

#: h1, h2 (u32), bytes and packets (f32), valid: what the scope reads of a row
COUNTMIN_ROW_BYTES = 4 + 4 + 4 + 4 + 1


def countmin(geometry: dict, k: int, shards: int) -> dict:
    """Needed bytes and operations of the Count-Min update of one ladder-k
    fold ON ONE DEVICE of `shards` (it receives its share of the rows)."""
    rows = k * geometry["batch"] / shards
    updates = rows * geometry["cm_planes"] * geometry["cm_depth"]
    return {"bytes": 8 * updates + COUNTMIN_ROW_BYTES * rows, "ops": updates}


COSTS = {"countmin": countmin}
