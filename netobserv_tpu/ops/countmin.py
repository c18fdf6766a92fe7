"""Count-Min sketch: point-queryable frequency table in O(d*w) memory.

The TPU replacement for exact per-key hashmap aggregation (reference:
`pkg/flow/account.go` Accounter). Counters are a dense [depth, width] array;
updates are masked scatter-adds over a batch, queries are gather+min. Merging two
sketches (across chips over ICI) is elementwise `+` / `psum` — that linearity is
why this sketch family suits SPMD (SURVEY.md §2.3 item 1).

Error bound (Cormode & Muthukrishnan): with w = 2^k, depth d, a point query
overestimates by at most eps*N with probability 1-delta, eps = e/w, delta = e^-d.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import custom_batching

from netobserv_tpu.ops import hashing


class CountMin(NamedTuple):
    """Sketch state: counts[depth, width]. dtype float32 for byte volumes
    (exact below 2^24, ~1e-7 relative above — fine for heavy-hitter ranking),
    int32 for packet counts."""

    counts: jax.Array

    @property
    def depth(self) -> int:
        return self.counts.shape[0]

    @property
    def width(self) -> int:
        return self.counts.shape[1]


def init(depth: int = 4, width: int = 1 << 16, dtype=jnp.float32) -> CountMin:
    assert width & (width - 1) == 0, "width must be a power of two"
    return CountMin(counts=jnp.zeros((depth, width), dtype=dtype))


def update(cm: CountMin, h1: jax.Array, h2: jax.Array, values: jax.Array,
           valid: jax.Array) -> CountMin:
    """Fold one batch into the sketch.

    h1/h2: uint32[B] base hashes; values: [B]; valid: bool[B].
    Duplicate keys within a batch accumulate correctly (scatter-add semantics).
    """
    d, w = cm.counts.shape
    idx = hashing.row_indices(h1, h2, d, w)  # uint32[d, B]
    vals = jnp.where(valid, values, 0).astype(cm.counts.dtype)
    vals = jnp.broadcast_to(vals[None, :], idx.shape)
    rows = jnp.broadcast_to(jnp.arange(d, dtype=jnp.int32)[:, None], idx.shape)
    new = cm.counts.at[rows, idx.astype(jnp.int32)].add(
        vals, mode="drop", unique_indices=False)
    return CountMin(counts=new)


@custom_batching.custom_vmap
def _scatter_add_two(counts_a: jax.Array, counts_b: jax.Array,
                     idx: jax.Array, va: jax.Array,
                     vb: jax.Array) -> tuple[jax.Array, jax.Array]:
    """The scatter core of `update_two`: counts [d, w] f32, idx [d, B] i32,
    va/vb [B] f32 (already masked). Unbatched, this is exactly the historic
    one-scatter interleaved form. Under vmap (the tenant-stacked fold,
    sketch/tenancy.py) the custom rule below replaces XLA's batched-scatter
    lowering — which serializes pathologically on CPU — with a flat
    (T*d, w) scatter per plane at the same per-update cost as the unbatched
    form; bit-exact either way (same adds per cell in the same batch order;
    tests/test_tenancy.py pins it per tenant)."""
    d, w = counts_a.shape
    # one flat index per (depth row, record) into [d*w, 2]: the form XLA
    # rewrites a 2-coordinate scatter into anyway, written here so that the
    # scatter it runs is the one traced and keeps its op_name (a rewritten
    # one has none, and a device capture then finds it under no scope)
    stacked = jnp.stack([counts_a, counts_b], axis=-1).reshape(d * w, 2)
    vals = jnp.stack([va, vb], axis=-1)  # [B, 2]
    vals = jnp.broadcast_to(vals[None], (d,) + vals.shape)  # [d, B, 2]
    flat = jnp.arange(d, dtype=jnp.int32)[:, None] * w + idx  # [d, B]
    new = stacked.at[flat.reshape(-1)].add(
        vals.reshape(-1, 2), mode="drop", unique_indices=False)
    new = new.reshape(d, w, 2)
    return new[..., 0], new[..., 1]


@_scatter_add_two.def_vmap
def _scatter_add_two_batched(axis_size, in_batched, counts_a, counts_b,
                             idx, va, vb):
    t = axis_size

    def bcast(x, batched):
        return x if batched else jnp.broadcast_to(x[None], (t,) + x.shape)

    counts_a = bcast(counts_a, in_batched[0])
    counts_b = bcast(counts_b, in_batched[1])
    idx = bcast(idx, in_batched[2])
    va = bcast(va, in_batched[3])
    vb = bcast(vb, in_batched[4])
    d, w = counts_a.shape[1:]
    b = va.shape[-1]
    # flatten the tenant axis into the row axis: tenant t's depth-r row is
    # flat row t*d + r, so one plain 2-coordinate scatter covers all t*d*b
    # updates (reshape is a bitcast; the scatter stays in place under
    # donation). Two per-plane scatters rather than one interleaved — the
    # [t, d, w, 2] interleave would materialize a full copy of both planes.
    rows = jnp.broadcast_to(jnp.arange(t * d, dtype=jnp.int32)[:, None],
                            (t * d, b))
    fidx = idx.reshape(t * d, b)

    def one(counts, v):
        vv = jnp.broadcast_to(v[:, None, :], (t, d, b)).reshape(t * d, b)
        return counts.reshape(t * d, w).at[rows, fidx].add(
            vv, mode="drop", unique_indices=False).reshape(t, d, w)

    return (one(counts_a, va), one(counts_b, vb)), (True, True)


def update_two(cm_a: CountMin, cm_b: CountMin, h1: jax.Array, h2: jax.Array,
               vals_a: jax.Array, vals_b: jax.Array,
               valid: jax.Array) -> tuple[CountMin, CountMin]:
    """Fold one batch into two same-shape sketches with ONE scatter.

    The two counter planes (bytes, packets) share hash indices, so stacking
    them on a trailing axis halves the scatter count on the hot path.

    Both sketches must use inexact (float) counters: the fold accumulates in
    float32, which would silently round large int32 counters."""
    d, w = cm_a.counts.shape
    assert cm_b.counts.shape == (d, w)
    assert (jnp.issubdtype(cm_a.counts.dtype, jnp.inexact)
            and jnp.issubdtype(cm_b.counts.dtype, jnp.inexact)), \
        "update_two requires float sketches (use countmin.update for int)"
    idx = hashing.row_indices(h1, h2, d, w).astype(jnp.int32)  # [d, B]
    va = jnp.where(valid, vals_a, 0).astype(jnp.float32)
    vb = jnp.where(valid, vals_b, 0).astype(jnp.float32)
    new_a, new_b = _scatter_add_two(cm_a.counts.astype(jnp.float32),
                                    cm_b.counts.astype(jnp.float32), idx,
                                    va, vb)
    return (CountMin(counts=new_a.astype(cm_a.counts.dtype)),
            CountMin(counts=new_b.astype(cm_b.counts.dtype)))


def query(cm: CountMin, h1: jax.Array, h2: jax.Array) -> jax.Array:
    """Point-query estimated counts for keys given their base hashes."""
    d, w = cm.counts.shape
    idx = hashing.row_indices(h1, h2, d, w)  # [d, B]
    rows = jnp.broadcast_to(jnp.arange(d, dtype=jnp.int32)[:, None], idx.shape)
    ests = cm.counts[rows, idx.astype(jnp.int32)]  # [d, B]
    return jnp.min(ests, axis=0)


def merge(a: CountMin, b: CountMin) -> CountMin:
    """Linear merge — the ICI collective for this sketch is psum."""
    return CountMin(counts=a.counts + b.counts)


# ---------------------------------------------------------------------------
# Width sharding: the [d, W] counter array is split across the `sketch`
# mesh axis by KEY OWNERSHIP (model-parallel sketches — SURVEY.md §2.3
# mapping). An independent hash assigns every key to one shard; the owner
# folds the key's ENTIRE depth into its local [d, W/nsk] subtable, which is
# an ordinary width-W/nsk sketch of the rows it owns: the fold is `update_two`
# (or its kernel twin) with `valid & owned` for `valid`, the point query is
# `query` on the local plane (sketch/state.ingest; query/core on the host).
# Owner-locality is the point: a shard point-queries its own keys with NO
# collective — which is what lets the steady-state ingest run collective-free
# on 2D meshes. The psum query exists only for the window-roll merge. Per-key
# error matches an unsharded width-W sketch: each shard holds ~1/nsk of the
# keys in 1/nsk of the columns, so counter load (keys per column) is
# unchanged.
# ---------------------------------------------------------------------------

def owner_shard(h1: jax.Array, h2: jax.Array, n_shards: int) -> jax.Array:
    """Which sketch shard owns each key — an independent hash of the 64-bit
    key identity (decorrelated from the column hashes). Host twin:
    `hashing.owner_shard_np` (pinned equal, tests/test_width_sharded_served)."""
    return (hashing.fmix32(h1 ^ (h2 * jnp.uint32(0x9E3779B1)))
            % jnp.uint32(n_shards)).astype(jnp.int32)


def query_sharded(cm_local: CountMin, h1: jax.Array, h2: jax.Array,
                  axis_name: str, n_shards: int) -> jax.Array:
    """Exact point query against an owner-sharded sketch (one psum; used at
    window roll, never on the per-batch path)."""
    shard = jax.lax.axis_index(axis_name).astype(jnp.int32)
    mine = owner_shard(h1, h2, n_shards) == shard
    part = jnp.where(mine, query(cm_local, h1, h2), 0.0)
    return jax.lax.psum(part, axis_name)  # exactly one shard owns each key


def total(cm: CountMin) -> jax.Array:
    """Total inserted mass (any single row sums to N)."""
    return jnp.sum(cm.counts[0])
