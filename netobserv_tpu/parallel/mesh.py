"""Mesh construction helpers.

Axes:
- `data`   — batch dimension sharding; each device folds its shard of the flow
             stream into a local sketch replica (per-CPU-map analog).
- `sketch` — optional width sharding of the big linear sketches (Count-Min
             columns), for sketch sizes beyond one chip's comfortable HBM slice.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

DATA_AXIS = "data"
SKETCH_AXIS = "sketch"


@dataclass(frozen=True)
class MeshSpec:
    data: int
    sketch: int = 1

    @classmethod
    def parse(cls, text: str, n_devices: int) -> "MeshSpec":
        """Parse "4", "4x2", or "" (all devices on data axis)."""
        if not text:
            return cls(data=n_devices)
        parts = [int(p) for p in text.lower().split("x")]
        if len(parts) == 1:
            return cls(data=parts[0])
        if len(parts) == 2:
            return cls(data=parts[0], sketch=parts[1])
        raise ValueError(f"bad mesh shape {text!r} (want D or DxS)")


def make_mesh(spec: Optional[MeshSpec] = None,
              devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    devices = list(devices if devices is not None else jax.devices())
    spec = spec or MeshSpec(data=len(devices))
    n = spec.data * spec.sketch
    if n > len(devices):
        raise ValueError(
            f"mesh {spec} needs {n} devices, have {len(devices)}")
    grid = np.asarray(devices[:n]).reshape(spec.data, spec.sketch)
    return Mesh(grid, (DATA_AXIS, SKETCH_AXIS))
