"""Persistent-slot top-K maintenance as ONE Pallas batch walk a round.

The gather/scatter form of the slot plane (`ops.topk.slot_prepare` +
`_slot_reduce_scatter`) looks three table fields up per (row, way) — XLA
gathers that the TPU executes element by element, 7.6 ns each (PERF.md
section 6, PR 31) — and then pays three serialized scatter passes over the
batch. The whole slot table is K slots (K=1024 default — 4 KB a field), so
this kernel holds it in VMEM and classifies AND reduces the batch in one
walk: `mslot` and `target` never exist in HBM.

Layout: slots along the sublanes, a chunk of CHUNK_B batch rows along the
lanes. The per-row inputs arrive as lane-major rows and the table as
[K, CHUNK_B] lane-broadcast planes, so nothing is relaid out in the walk. A
row's SLOT_WAYS candidate slots are `(s1 + way * s2) mod K` with s2 odd, so
slot l is its candidate number `(l - s1) * s2^-1 mod K` when that is below
SLOT_WAYS, and no candidate otherwise: ONE multiply a cell gives the
candidate mask and each candidate's way, where a compare a way would take
eight. From there the walk is `slot_prepare` cell for cell — the match
restricted to the candidates (lowest way first), the weakest candidate by
(defense, way), the challenger test — followed by the three reductions,
kept a lane position apart ([K, CHUNK_B] accumulators, elementwise updates)
and reduced across the lanes once, after the walk.

Contract (the two-form invariant): the kernel returns exactly the three
reductions `ops.topk.slot_compose` consumes, bit-equal to `slot_prepare` +
`_slot_reduce_scatter` on any table and any rows (f32 max is
order-independent; both tie-breaks are integer mins), so the table after
every round is the same table — pinned by tests/test_pallas_topk.py.
`interpret` defaults to True off-TPU so the CPU mesh can execute it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from netobserv_tpu.ops import topk
from netobserv_tpu.ops.topk import NO_WINNER, SLOT_WAYS, SlotTable

#: batch rows per walk step: one lane tile, so every [K, CHUNK_B]
#: intermediate at the default K=1024 is 128 vector registers' worth
CHUNK_B = 128


def _walk_kernel(ids_ref, est_ref, th1_ref, th2_ref, tdef_ref,
                 match_out, chall_out, row_out, m_acc, c_acc, r_acc, *,
                 n_chunks: int, k: int):
    """ids [4, B] i32 (h1, h2, s1, s2^-1: `pack_rows`), est [1, B] f32; the
    table planes [K, CHUNK_B] (identity halves as i32, `slot_defense` f32:
    a slot is valid where it is >= 0); outputs [K, 1]."""
    slot = jax.lax.broadcasted_iota(jnp.int32, (k, CHUNK_B), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, CHUNK_B), 1)
    m_acc[...] = jnp.full((k, CHUNK_B), -1.0, jnp.float32)
    c_acc[...] = jnp.full((k, CHUNK_B), -1.0, jnp.float32)
    r_acc[...] = jnp.full((k, CHUNK_B), NO_WINNER, jnp.int32)

    def chunk_body(i, carry):
        sl = pl.ds(pl.multiple_of(i * CHUNK_B, CHUNK_B), CHUNK_B)
        h1 = ids_ref[pl.ds(0, 1), sl]                    # [1, C]
        h2 = ids_ref[pl.ds(1, 1), sl]
        s1 = ids_ref[pl.ds(2, 1), sl]
        s2_inv = ids_ref[pl.ds(3, 1), sl]
        est = est_ref[:, sl]
        live = est > 0.0
        # each slot's way among this row's candidates (>= WAYS: none)
        way = ((slot - s1) * s2_inv) & (k - 1)           # [K, C]
        cand = way < SLOT_WAYS
        # --- match: the candidate that holds the row's key, lowest way ---
        defense = tdef_ref[...]
        hit = (cand & (defense >= 0.0)
               & (th1_ref[...] == h1) & (th2_ref[...] == h2))
        hit_way = jnp.where(hit, way, SLOT_WAYS)
        m_way = jnp.min(hit_way, axis=0, keepdims=True)  # [1, C]
        matched = live & (m_way < SLOT_WAYS)
        m_est = jnp.where(hit_way == jnp.where(matched, m_way, -1), est, -1.0)
        m_acc[...] = jnp.maximum(m_acc[...], m_est)
        # --- target: the weakest candidate by (defense, way), if beaten ---
        c_def = jnp.where(cand, defense, jnp.inf)
        t_def = jnp.min(c_def, axis=0, keepdims=True)
        t_way = jnp.min(jnp.where(c_def == t_def, way, SLOT_WAYS), axis=0,
                        keepdims=True)
        challenger = live & ~matched & (est > t_def)
        t_est = jnp.where(way == jnp.where(challenger, t_way, -1), est, -1.0)
        # rows only grow along the walk, so at a lane position the first
        # row to reach a maximum stays its winner: (max, lowest row at max)
        better = t_est > c_acc[...]
        r_acc[...] = jnp.where(better, i * CHUNK_B + lane, r_acc[...])
        c_acc[...] = jnp.where(better, t_est, c_acc[...])
        return carry

    jax.lax.fori_loop(0, n_chunks, chunk_body, 0)
    match_out[...] = jnp.max(m_acc[...], axis=1, keepdims=True)
    c_max = jnp.max(c_acc[...], axis=1, keepdims=True)
    chall_out[...] = c_max
    # c_max > -1 keeps dead challengers out (_slot_reduce_scatter's gate;
    # their lane positions still hold NO_WINNER)
    row_out[...] = jnp.min(
        jnp.where((c_acc[...] == c_max) & (c_max > -1.0), r_acc[...],
                  NO_WINNER), axis=1, keepdims=True)


def eligible(k: int) -> bool:
    """Static shape gate: the slot count must be lane-aligned (it is the
    sublane extent of every plane, and a power of two by `init_slots`)."""
    return k % 128 == 0


def pack_rows(h1: jax.Array, h2: jax.Array, est: jax.Array, k: int
              ) -> tuple[jax.Array, jax.Array]:
    """The batch as the walk reads it, shared by a fold's rounds: ids
    i32[4, B'] = (h1, h2, s1 mod K, s2^-1 mod K) and est f32[1, B'], B padded
    to whole chunks with dead rows (est -1: neither match nor challenge).

    s2 is odd, so it has an inverse mod 2^32 (Newton: x <- x * (2 - s2 * x)
    doubles the correct low bits, and s2 itself is right to three), which
    is its inverse mod every power of two K as well."""
    s1, s2 = topk.slot_strides(h1, h2)
    inv = s2
    for _ in range(4):
        inv = inv * (jnp.uint32(2) - s2 * inv)
    mask = jnp.uint32(k - 1)
    ids = jax.lax.bitcast_convert_type(
        jnp.stack([h1, h2, s1 & mask, inv & mask]), jnp.int32)
    pad = (-h1.shape[0]) % CHUNK_B
    return (jnp.pad(ids, ((0, 0), (0, pad))),
            jnp.pad(est.astype(jnp.float32), (0, pad),
                    constant_values=-1.0).reshape(1, -1))


def walk(table: SlotTable, ids: jax.Array, est: jax.Array,
         interpret: bool | None = None
         ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One round over `pack_rows`' batch against `table`: (match_max[K] f32,
    chall_max[K] f32, win_row[K] i32 — NO_WINNER where no challenger), as
    `_slot_reduce_scatter` of `slot_prepare`'s classification."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    k = table.k
    assert eligible(k), f"slot count {k} is not lane-aligned"

    def plane(x):
        if x.dtype == jnp.uint32:
            x = jax.lax.bitcast_convert_type(x, jnp.int32)
        return jnp.broadcast_to(x[:, None], (k, CHUNK_B))

    kernel = functools.partial(_walk_kernel, n_chunks=ids.shape[1] // CHUNK_B,
                               k=k)
    match_max, chall_max, win_row = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((k, 1), jnp.float32),
                   jax.ShapeDtypeStruct((k, 1), jnp.float32),
                   jax.ShapeDtypeStruct((k, 1), jnp.int32)),
        scratch_shapes=[pltpu.VMEM((k, CHUNK_B), jnp.float32),
                        pltpu.VMEM((k, CHUNK_B), jnp.float32),
                        pltpu.VMEM((k, CHUNK_B), jnp.int32)],
        name="topk_slot_walk",
        interpret=interpret,
    )(ids, est, plane(table.h1), plane(table.h2),
      plane(topk.slot_defense(table)))
    return match_max[:, 0], chall_max[:, 0], win_row[:, 0]
