"""HLL register fold as a Pallas kernel: scatter-max -> tiled one-hot max.

Same reformulation as the Count-Min kernel, with max-reduce on the VPU instead
of an MXU contraction: for each 128-lane register tile, every batch chunk
contributes `where(idx == lane, rank, 0)` and the tile takes the running
elementwise max. Cost is B*m lane compares per batch (~2.7e8 at B=16k,
m=16384), trivially within VPU headroom — versus a serialized XLA scatter.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from netobserv_tpu.ops.hll import HLL, _rank

TILE_M = 512
CHUNK_B = 2048


def _fold_kernel(regs_ref, idx_ref, rank_ref, out_ref, *, n_chunks: int):
    j = pl.program_id(0)
    lanes = j * TILE_M + jax.lax.broadcasted_iota(jnp.int32, (1, TILE_M), 1)

    def chunk_body(i, acc):
        sl = pl.dslice(i * CHUNK_B, CHUNK_B)
        idx = idx_ref[0, sl].reshape(CHUNK_B, 1)
        rank = rank_ref[0, sl].reshape(CHUNK_B, 1)
        contrib = jnp.max(jnp.where(idx == lanes, rank, 0), axis=0)
        return jnp.maximum(acc, contrib)

    acc = regs_ref[0]
    acc = jax.lax.fori_loop(0, n_chunks, chunk_body, acc)
    out_ref[0] = acc


def _fold_flat(regs_flat: jax.Array, idx: jax.Array, rank: jax.Array,
               interpret: bool) -> jax.Array:
    """One-hot max fold over a FLAT register array of any TILE_M-aligned
    size. The per-dst/per-src grids stay on the XLA scatter: a one-hot fold
    pays D*m lane-compares per RECORD (4096x64 = 262K, 16x the global
    HLL's) where the scatter touches O(1) cells per record."""
    m = regs_flat.shape[0]
    assert m % TILE_M == 0, f"m={m} must be a multiple of {TILE_M}"
    pad = (-idx.shape[0]) % CHUNK_B
    if pad:
        idx = jnp.pad(idx, (0, pad))
        rank = jnp.pad(rank, (0, pad))
    n_chunks = idx.shape[0] // CHUNK_B

    kernel = functools.partial(_fold_kernel, n_chunks=n_chunks)
    new_regs = pl.pallas_call(
        kernel,
        grid=(m // TILE_M,),
        in_specs=[
            pl.BlockSpec((1, TILE_M), lambda j: (0, j)),
            # whole-batch blocks ride as [1, B] rows: a 1-D block does not
            # survive vmap (the tenant stack), whose batching rule leaves
            # (Squeezed, B) — a shape Mosaic refuses
            pl.BlockSpec((1, idx.shape[0]), lambda j: (0, 0)),
            pl.BlockSpec((1, idx.shape[0]), lambda j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, TILE_M), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, m), jnp.int32),
        input_output_aliases={0: 0},
        name="hll_update",
        interpret=interpret,
    )(regs_flat.reshape(1, m), idx.reshape(1, -1), rank.reshape(1, -1))
    return new_regs.reshape(m)


def update(hll: HLL, h1: jax.Array, h2: jax.Array, valid: jax.Array,
           interpret: bool | None = None) -> HLL:
    """Drop-in replacement for hll.update."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    m = hll.regs.shape[0]
    idx = (h1 & jnp.uint32(m - 1)).astype(jnp.int32)
    rank = jnp.where(valid, _rank(h2), 0)
    return HLL(regs=_fold_flat(hll.regs, idx, rank, interpret))

