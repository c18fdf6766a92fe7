"""Tiered counter planes: self-adjusting sketch memory (SKETCH_TIERED).

The SALSA/additive-error-counter direction from PAPERS.md, TPU-idiomatic:
every sketch table today burns a full-width element per counter, yet in
heavy-tailed traffic the overwhelming majority of counters never leave the
bottom few bits. Tiered mode keeps the RESIDENT form of the big counter
tables narrow and decodes to the canonical wide tables only transiently,
inside the fold/roll executables:

- **Count-Min planes** — a u8 base plane covering the full ``[d, w]``
  geometry (the bytes plane counts in ``bytes_unit``-byte units, ceil per
  fold — overestimate-preserving, the additive-error-counter tradeoff;
  the packets plane counts raw) plus two fixed-shape overflow tiers:
  a direct-mapped u16 MID tier (one cell per ``mid_group`` columns) and a
  u32 TOP tier (one cell per ``top_group`` columns). A counter that
  saturates its base cell is *promoted*: the overflow mass spills into its
  group's mid cell (and from a saturated mid cell into the top cell, which
  finally clamps — sat-add, like the 16-bit drop lanes). Promotion is a
  masked in-place update over fixed shapes — never a reshape, never a
  data-dependent shape, zero retraces. Decode attributes a shared overflow
  cell to every promoted member of its group, so estimates are
  OVERESTIMATES only — exactly the Count-Min error direction, and the min
  over depth rows bounds the aliasing like any other CM collision.
- **HLL banks** (global src + both per-bucket grids) — registers hold
  ranks <= 33 (6 bits); they pack LOSSLESSLY four-per-three-bytes
  (i32 -> 0.75 B/register, 5.33x) and unpack transiently in the fold.

Tiers are a steady-state representation only: the fold decodes to wide,
runs the EXISTING equivalence-pinned update forms (the scatter chain and
the fused Pallas batch walk — both unchanged, still bit-exact against each
other in tiered mode), and re-encodes the per-fold delta into the tiers.
Window roll, ``state_tables`` (the delta wire / query snapshot), and
checkpoints all see the canonical wide tables via the decode folded into
the same executables — no wire v4, no checkpoint format bump.

Semantics (pinned bit-exact against the numpy twin in
tests/test_tiered.py; per plane, per fold):

1. ``du = ceil(max(delta, 0) / unit)`` — the fold's per-counter delta in
   units (unit 1 for packets: exact).
2. ``s = base + du``; ``base' = min(s, 255)``; base overflow ``s - base'``
   group-sums into the mid tier; ``mid' = min(mid + spill, 65535)``; mid
   overflow group-sums into the top tier; ``top' = min(top + spill,
   TOP_MAX)`` — the top tier clamps (sat-add).
3. decode: ``units = base + [base==255] * (mid_g + [mid_g==65535] *
   top_G)``; value = ``units * unit``.

Promotion is lossless while a mid/top cell has a single promoted group
member (decode == wide exactly across every tier boundary); shared cells
alias — overestimate-only, like CM columns themselves.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from netobserv_tpu.ops import countmin, hll

#: base plane saturation point (u8)
BASE_MAX = 255
#: mid tier saturation point (u16)
MID_MAX = 65535
#: top tier clamp (u32 storage; kept at a power of two so the f32 clamp
#: arithmetic the twin pins is exact) — "sat-add" semantics: overflow past
#: this is dropped, the cell saturates
TOP_MAX = 1 << 30


class TierSpec(NamedTuple):
    """Static tier geometry (hashable — rides SketchConfig / jit cache
    keys). ``mid_group``/``top_group`` are COLUMNS per overflow cell;
    ``bytes_unit`` is the byte quantum of the bytes plane's units."""

    mid_group: int = 32
    top_group: int = 256
    bytes_unit: int = 256

    def check(self, cm_width: int) -> None:
        for name, v in (("mid_group", self.mid_group),
                        ("top_group", self.top_group)):
            if v < 2 or v & (v - 1):
                raise ValueError(
                    f"tier {name} must be a power of two >= 2 (got {v})")
        if self.bytes_unit < 1 or self.bytes_unit & (self.bytes_unit - 1):
            raise ValueError("tier bytes_unit must be a power of two >= 1 "
                             f"(got {self.bytes_unit})")
        if self.top_group <= self.mid_group:
            raise ValueError(
                f"tier top_group ({self.top_group}) must exceed mid_group "
                f"({self.mid_group}) — tiers must narrow as they widen")
        if cm_width % self.top_group:
            raise ValueError(
                f"tier top_group ({self.top_group}) must divide "
                f"SKETCH_CM_WIDTH ({cm_width})")


class TieredPlane(NamedTuple):
    """One Count-Min counter plane in tiered form (values in UNITS)."""

    base: jax.Array  # u8  [d, w]
    mid: jax.Array   # u16 [d, w // mid_group]
    top: jax.Array   # u32 [d, w // top_group]


class TieredTables(NamedTuple):
    """The resident narrow form of every tier-covered sketch table."""

    cm_bytes: TieredPlane
    cm_pkts: TieredPlane
    hll_src: jax.Array      # u8 [m//4*3] — 6-bit packed registers
    hll_per_dst: jax.Array  # u8 [D, m//4*3]
    hll_per_src: jax.Array  # u8 [S, m//4*3]


@jax.tree_util.register_pytree_node_class
class TieredState:
    """Sketch state with the big counter tables resident in tiered form.

    ``rest`` is an ordinary SketchState whose cm/hll fields hold ZERO-SIZE
    placeholders (they cost nothing and are never read — every consumer
    goes through :func:`decode_state` / the fold's transient wide view).
    ``spec`` is static pytree aux data, so each tier geometry is its own
    jit cache entry — same rule as batch shapes."""

    __slots__ = ("tables", "rest", "spec")

    def __init__(self, tables: TieredTables, rest, spec: TierSpec):
        self.tables = tables
        self.rest = rest
        self.spec = spec

    def tree_flatten(self):
        return (self.tables, self.rest), self.spec

    @classmethod
    def tree_unflatten(cls, spec, children):
        return cls(children[0], children[1], spec)

    # ergonomic pass-throughs for the fields that stay wide (exporters
    # reading the window counter)
    @property
    def heavy(self):
        return self.rest.heavy

    @property
    def window(self):
        return self.rest.window


# --------------------------------------------------------------------------
# plane encode / decode / fold-add (the promotion path)
# --------------------------------------------------------------------------

def _group_sum(x: jax.Array, g: int) -> jax.Array:
    d, n = x.shape
    return x.reshape(d, n // g, g).sum(axis=-1)


def _expand(x: jax.Array, g: int) -> jax.Array:
    d, n = x.shape
    return jnp.broadcast_to(x[:, :, None], (d, n, g)).reshape(d, n * g)


def _spill(over: jax.Array, mid_f: jax.Array, top_u: jax.Array,
           spec: TierSpec) -> tuple[jax.Array, jax.Array]:
    """Cascade base-level overflow (units, f32 [d, w]) through the mid and
    top tiers: group-sum, saturate, spill, clamp (sat-add at the top).

    The mid math stays f32 (cells cap at 65535 between folds and per-fold
    spills are far below 2^24 units, so every add is exact). The TOP cell
    accumulates in u32 INTEGER arithmetic: a top cell aggregates a whole
    top_group's overflow and crosses 2^24 units long before any single
    wide counter would — f32 accumulation there would silently round away
    small per-fold spills, an UNDERCOUNT (the one direction this module
    forbids). `top_u` is the resident u32 array."""
    s2 = mid_f + _group_sum(over, spec.mid_group)
    new_mid = jnp.minimum(s2, float(MID_MAX))
    spill = _group_sum(s2 - new_mid, spec.top_group // spec.mid_group)
    # per-fold spill is f32-exact (< 2^24 units per fold by construction);
    # clamp BEFORE the u32 cast, then saturate against the remaining room
    inc = jnp.minimum(spill, float(TOP_MAX)).astype(jnp.uint32)
    room = jnp.uint32(TOP_MAX) - top_u
    new_top = top_u + jnp.minimum(inc, room)
    return new_mid.astype(jnp.uint16), new_top


def init_plane(depth: int, width: int, spec: TierSpec) -> TieredPlane:
    return TieredPlane(
        base=jnp.zeros((depth, width), jnp.uint8),
        mid=jnp.zeros((depth, width // spec.mid_group), jnp.uint16),
        top=jnp.zeros((depth, width // spec.top_group), jnp.uint32))


def encode_plane(wide: jax.Array, spec: TierSpec, unit: int) -> TieredPlane:
    """From-scratch encode of a wide value table (init / window roll /
    decay / checkpoint restore). NOT the per-fold path — that is
    :func:`plane_add`, which preserves the tiers' overflow attribution."""
    # ALWAYS ceil, unit 1 included: fractional values (a decayed window)
    # must round UP into whole units — truncation would undercount, the
    # one error direction Count-Min forbids
    vu = jnp.ceil(wide.astype(jnp.float32) / unit)
    base = jnp.minimum(vu, float(BASE_MAX))
    d, w = wide.shape
    mid, top = _spill(vu - base,
                      jnp.zeros((d, w // spec.mid_group), jnp.float32),
                      jnp.zeros((d, w // spec.top_group), jnp.uint32), spec)
    return TieredPlane(base=base.astype(jnp.uint8), mid=mid, top=top)


def plane_add(plane: TieredPlane, delta: jax.Array, spec: TierSpec,
              unit: int) -> TieredPlane:
    """Fold one batch's per-counter delta (raw value domain, >= 0) into the
    tiered plane. Saturation promotion = the masked in-place spill below;
    every shape is fixed, so the jitted fold never retraces."""
    du = jnp.ceil(jnp.maximum(delta, 0.0) / unit)  # ceil: overestimate-only
    s = plane.base.astype(jnp.float32) + du
    new_base = jnp.minimum(s, float(BASE_MAX))
    mid, top = _spill(s - new_base, plane.mid.astype(jnp.float32),
                      plane.top, spec)
    return TieredPlane(base=new_base.astype(jnp.uint8), mid=mid, top=top)


def decay_plane(plane: TieredPlane, factor: float) -> TieredPlane:
    """Window decay at the REPRESENTATION level: scale each tier array
    elementwise (ceil — overestimate-only), keeping SATURATED base/mid
    cells saturated so their overflow attribution survives the decay.

    Deliberately NOT decode -> decay -> encode: decode attributes a shared
    overflow cell to every promoted group member, so a from-scratch
    re-encode would re-SUM those attributed values back into the cell and
    COMPOUND the aliasing every window (counts would grow under decay).
    Elementwise scaling never re-sums, so shared-cell overestimates decay
    like everything else. The floor this buys — a promoted counter never
    reads below BASE_MAX units — is a bounded overestimate, same class as
    the aliasing itself."""
    basef = jnp.ceil(plane.base.astype(jnp.float32) * factor)
    new_base = jnp.where(plane.base == BASE_MAX, plane.base,
                         basef.astype(jnp.uint8))
    midf = jnp.ceil(plane.mid.astype(jnp.float32) * factor)
    new_mid = jnp.where(plane.mid == MID_MAX, plane.mid,
                        midf.astype(jnp.uint16))
    new_top = jnp.ceil(plane.top.astype(jnp.float32) * factor).astype(
        jnp.uint32)
    return TieredPlane(base=new_base, mid=new_mid, top=new_top)


def decode_plane(plane: TieredPlane, spec: TierSpec, unit: int) -> jax.Array:
    """Wide f32 [d, w] view. A shared overflow cell is attributed to EVERY
    promoted member of its group — overestimate-only, the CM direction."""
    mid_f = plane.mid.astype(jnp.float32)
    top_per_mid = _expand(plane.top.astype(jnp.float32),
                          spec.top_group // spec.mid_group)
    mid_tot = mid_f + jnp.where(plane.mid == MID_MAX, top_per_mid, 0.0)
    per_col = _expand(mid_tot, spec.mid_group)
    units = plane.base.astype(jnp.float32) + jnp.where(
        plane.base == BASE_MAX, per_col, 0.0)
    return units * unit if unit > 1 else units


# --------------------------------------------------------------------------
# HLL register packing (6-bit, lossless — ranks are <= 33)
# --------------------------------------------------------------------------

def pack_hll(regs: jax.Array) -> jax.Array:
    """int32[..., m] registers -> u8[..., m//4*3] (4 regs per 3 bytes)."""
    *lead, m = regs.shape
    assert m % 4 == 0, f"HLL register count {m} must be a multiple of 4"
    r = regs.astype(jnp.uint32).reshape(*lead, m // 4, 4)
    v = r[..., 0] | (r[..., 1] << 6) | (r[..., 2] << 12) | (r[..., 3] << 18)
    b = jnp.stack([v & 0xFF, (v >> 8) & 0xFF, (v >> 16) & 0xFF], axis=-1)
    return b.astype(jnp.uint8).reshape(*lead, (m // 4) * 3)


def unpack_hll(packed: jax.Array) -> jax.Array:
    """Inverse of :func:`pack_hll` -> int32[..., m]."""
    *lead, n = packed.shape
    b = packed.astype(jnp.uint32).reshape(*lead, n // 3, 3)
    v = b[..., 0] | (b[..., 1] << 8) | (b[..., 2] << 16)
    r = jnp.stack([v & 63, (v >> 6) & 63, (v >> 12) & 63, (v >> 18) & 63],
                  axis=-1)
    return r.astype(jnp.int32).reshape(*lead, (n // 3) * 4)


# --------------------------------------------------------------------------
# state-level encode / decode (used by sketch/state.py's one-branch hooks)
# --------------------------------------------------------------------------

def _strip(wide) -> "object":
    """A SketchState with the tier-covered tables replaced by zero-size
    placeholders (shape info for re-widening lives in the tier arrays)."""
    return wide._replace(
        cm_bytes=countmin.CountMin(jnp.zeros((0, 0), jnp.float32)),
        cm_pkts=countmin.CountMin(jnp.zeros((0, 0), jnp.float32)),
        hll_src=hll.HLL(jnp.zeros((0,), jnp.int32)),
        hll_per_dst=hll.PerDstHLL(jnp.zeros((0, 0), jnp.int32)),
        hll_per_src=hll.PerDstHLL(jnp.zeros((0, 0), jnp.int32)))


def widen(ts: TieredState, cmb_wide: jax.Array, cmp_wide: jax.Array):
    """The transient wide SketchState a fold/roll operates on, given the
    two CM planes already decoded (so the fold can reuse them for the
    delta extraction without decoding twice)."""
    t = ts.tables
    return ts.rest._replace(
        cm_bytes=countmin.CountMin(cmb_wide),
        cm_pkts=countmin.CountMin(cmp_wide),
        hll_src=hll.HLL(unpack_hll(t.hll_src)),
        hll_per_dst=hll.PerDstHLL(unpack_hll(t.hll_per_dst)),
        hll_per_src=hll.PerDstHLL(unpack_hll(t.hll_per_src)))


def widen_interior(ts: TieredState, fuse_hll_src: bool):
    """The transient SketchState the TIER-INTERIOR fold operates on: the
    CM planes keep their zero-size placeholders (the interior kernel folds
    the tier arrays directly — no wide decode), and the global-src HLL
    bank stays packed too when the fused signal lane handles it
    (``fuse_hll_src``). Only the per-bucket HLL grids unpack — their fold
    is scatter-only by the measured gating verdict."""
    t = ts.tables
    rest = ts.rest._replace(
        hll_per_dst=hll.PerDstHLL(unpack_hll(t.hll_per_dst)),
        hll_per_src=hll.PerDstHLL(unpack_hll(t.hll_per_src)))
    if not fuse_hll_src:
        rest = rest._replace(hll_src=hll.HLL(unpack_hll(t.hll_src)))
    return rest


def interior_encode(ts: TieredState, cm_bytes: TieredPlane,
                    cm_pkts: TieredPlane, hll_src_packed,
                    new_work) -> TieredState:
    """Close one tier-interior fold: the CM planes arrive already promoted
    by the kernel, the global-src bank arrives packed when the fused lane
    folded it (else re-packs from the wide work state), the per-bucket
    grids re-pack losslessly, everything else rides ``new_work``."""
    tables = TieredTables(
        cm_bytes=cm_bytes,
        cm_pkts=cm_pkts,
        hll_src=(hll_src_packed if hll_src_packed is not None
                 else pack_hll(new_work.hll_src.regs)),
        hll_per_dst=pack_hll(new_work.hll_per_dst.regs),
        hll_per_src=pack_hll(new_work.hll_per_src.regs))
    return TieredState(tables, _strip(new_work), ts.spec)


def decode_state(ts: TieredState):
    """The canonical wide SketchState (what roll / state_tables /
    checkpoints see)."""
    spec = ts.spec
    return widen(ts,
                 decode_plane(ts.tables.cm_bytes, spec, spec.bytes_unit),
                 decode_plane(ts.tables.cm_pkts, spec, 1))


def decay_encode(ts: TieredState, wide_decayed,
                 factor: float) -> TieredState:
    """The decayed-window re-encode: CM tiers scale at the representation
    level (:func:`decay_plane` — shared-cell attribution is never
    re-summed, so aliasing cannot compound window over window), the HLL
    banks re-pack from the decayed wide (decay resets their registers),
    everything else rides the decayed wide ``rest``."""
    t = ts.tables
    tables = TieredTables(
        cm_bytes=decay_plane(t.cm_bytes, factor),
        cm_pkts=decay_plane(t.cm_pkts, factor),
        hll_src=pack_hll(wide_decayed.hll_src.regs),
        hll_per_dst=pack_hll(wide_decayed.hll_per_dst.regs),
        hll_per_src=pack_hll(wide_decayed.hll_per_src.regs))
    return TieredState(tables, _strip(wide_decayed), ts.spec)


def encode_state(wide, spec: TierSpec) -> TieredState:
    """From-scratch encode (init / reset-roll / checkpoint restore — paths
    whose wide tables are fresh zeros or a restore). NEVER the decay/keep
    roll path: re-encoding a table whose promoted counters share overflow
    cells re-SUMS the decode's per-member attribution back into the cell
    and compounds it every window — decay rolls go through
    :func:`decay_encode`, keep rolls keep the tier arrays verbatim. On a
    checkpoint restore a shared cell inflates ONCE (overestimate-only,
    bounded, restore-rate); the per-fold path (:func:`fold_encode`) never
    round-trips at all."""
    tables = TieredTables(
        cm_bytes=encode_plane(wide.cm_bytes.counts, spec, spec.bytes_unit),
        cm_pkts=encode_plane(wide.cm_pkts.counts.astype(jnp.float32),
                             spec, 1),
        hll_src=pack_hll(wide.hll_src.regs),
        hll_per_dst=pack_hll(wide.hll_per_dst.regs),
        hll_per_src=pack_hll(wide.hll_per_src.regs))
    return TieredState(tables, _strip(wide), spec)


def fold_encode(ts: TieredState, cmb_wide: jax.Array, cmp_wide: jax.Array,
                new_wide) -> TieredState:
    """Re-encode after one fold: the CM planes advance by the fold's exact
    per-counter delta (new - decoded, untouched counters contribute 0);
    the HLL banks re-pack losslessly; everything else rides ``rest``."""
    spec = ts.spec
    tables = TieredTables(
        cm_bytes=plane_add(ts.tables.cm_bytes,
                           new_wide.cm_bytes.counts - cmb_wide,
                           spec, spec.bytes_unit),
        cm_pkts=plane_add(ts.tables.cm_pkts,
                          new_wide.cm_pkts.counts - cmp_wide, spec, 1),
        hll_src=pack_hll(new_wide.hll_src.regs),
        hll_per_dst=pack_hll(new_wide.hll_per_dst.regs),
        hll_per_src=pack_hll(new_wide.hll_per_src.regs))
    return TieredState(tables, _strip(new_wide), spec)


# --------------------------------------------------------------------------
# accounting (the metrics surface — host-side, never on the fold path)
# --------------------------------------------------------------------------

#: the sketch tables the tiered representation covers — the byte-reduction
#: claim (tests/test_tiered.py) is computed over exactly these
COUNTER_TABLES = ("cm_bytes", "cm_pkts", "hll_src", "hll_per_dst",
                  "hll_per_src")


def array_bytes(tree) -> int:
    """Total bytes of a pytree's arrays (shape math — no transfer)."""
    return sum(math.prod(leaf.shape) * np.dtype(leaf.dtype).itemsize
               for leaf in jax.tree.leaves(tree))


def counter_table_bytes(state) -> dict[str, int]:
    """Per-table resident bytes of the tier-covered tables, for either
    representation (wide SketchState or TieredState)."""
    if isinstance(state, TieredState):
        t = state.tables
        return {name: array_bytes(getattr(t, name))
                for name in COUNTER_TABLES}
    return {"cm_bytes": array_bytes(state.cm_bytes),
            "cm_pkts": array_bytes(state.cm_pkts),
            "hll_src": array_bytes(state.hll_src),
            "hll_per_dst": array_bytes(state.hll_per_dst),
            "hll_per_src": array_bytes(state.hll_per_src)}

