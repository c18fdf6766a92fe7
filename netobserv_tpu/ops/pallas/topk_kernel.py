"""Persistent-slot top-K maintenance as ONE Pallas batch walk.

The un-fused form of the slot plane (`ops.topk._slot_reduce_scatter`) pays
three serialized XLA scatter passes over the batch — match refresh, challenge
max, winner-row min. The whole slot table is K lanes (K=1024 default — a few
KB), so the kernel keeps all three per-slot accumulators in VMEM and walks
the batch ONCE, the same single-pass formulation as the sibling megakernels
(`countmin_kernel.py`, `signal_kernel.py`; cf. the streaming top-K
accelerator line, PAPERS.md arxiv 2505.*/2005.13332: candidate tracking in
the update path, not a post-pass).

Contract (the two-form invariant): this kernel consumes exactly the
`(mslot, target, est)` row classification `ops.topk.slot_prepare` produces
and returns exactly the three reductions `ops.topk.slot_compose` consumes —
bit-exact against the scatter twin (f32 max is order-independent; the
winner tie-break is an integer min), pinned by tests/test_pallas_topk.py.
`interpret` defaults to True off-TPU so the CPU mesh can execute it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from netobserv_tpu.ops.topk import NO_WINNER

#: batch chunk per VMEM walk step — [CHUNK_B, K] intermediates at the
#: default K=1024 are 1 MiB, comfortably inside VMEM next to the three
#: K-lane accumulators
CHUNK_B = 256


def _reduce_kernel(mslot_ref, target_ref, est_ref, match_out, chall_out,
                   row_out, *, n_chunks: int, k: int):
    lanes = jax.lax.broadcasted_iota(jnp.int32, (1, k), 1)

    def chunk_body(i, acc):
        m_max, c_max, c_row = acc
        sl = pl.dslice(i * CHUNK_B, CHUNK_B)
        est = est_ref[0, sl].reshape(CHUNK_B, 1)
        # --- match refresh: max est among rows occupying each slot ---
        m_mask = mslot_ref[0, sl].reshape(CHUNK_B, 1) == lanes   # [C, K]
        m_est = jnp.where(m_mask, est, -1.0)
        m_max = jnp.maximum(m_max, jnp.max(m_est, axis=0, keepdims=True))
        # --- challenge: max est among each slot's challengers, and the
        # LOWEST row index achieving that max (the deterministic winner);
        # the (max, min-row-at-max) pair combines associatively across
        # chunks, so one walk matches the scatter form bit-exact ---
        t_mask = target_ref[0, sl].reshape(CHUNK_B, 1) == lanes  # [C, K]
        t_est = jnp.where(t_mask, est, -1.0)
        k_max = jnp.max(t_est, axis=0, keepdims=True)            # [1, K]
        rows = (i * CHUNK_B
                + jax.lax.broadcasted_iota(jnp.int32, (CHUNK_B, 1), 0))
        at_max = t_mask & (t_est == k_max) & (k_max > -1.0)
        k_row = jnp.min(jnp.where(at_max, rows, NO_WINNER), axis=0,
                        keepdims=True)
        better = k_max > c_max
        tied = k_max == c_max
        c_row = jnp.where(better, k_row,
                          jnp.where(tied, jnp.minimum(c_row, k_row), c_row))
        c_max = jnp.maximum(c_max, k_max)
        return m_max, c_max, c_row

    init = (jnp.full((1, k), -1.0, jnp.float32),
            jnp.full((1, k), -1.0, jnp.float32),
            jnp.full((1, k), NO_WINNER, jnp.int32))
    m_max, c_max, c_row = jax.lax.fori_loop(0, n_chunks, chunk_body, init)
    match_out[...] = m_max
    chall_out[...] = c_max
    row_out[...] = c_row


def eligible(k: int) -> bool:
    """Static shape gate: the slot count must be lane-aligned (the three
    accumulators live as [1, K] VMEM rows)."""
    return k % 128 == 0


def reduce(mslot: jax.Array, target: jax.Array, est: jax.Array, k: int,
           interpret: bool | None = None
           ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The three per-slot reductions of one batch in one walk.

    mslot/target: int32[B] slot ids (k = inactive row, per slot_prepare);
    est: f32[B] CM estimates (-1 dead). Returns (match_max[K] f32,
    chall_max[K] f32, win_row[K] i32 — NO_WINNER where no challenger)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    assert eligible(k), f"slot count {k} is not lane-aligned"
    b = mslot.shape[0]
    pad = (-b) % CHUNK_B
    if pad:
        # padded rows target slot k (inactive) with dead estimates — the
        # lane compares never match them, exactly like the scatter drop
        mslot = jnp.pad(mslot, (0, pad), constant_values=k)
        target = jnp.pad(target, (0, pad), constant_values=k)
        est = jnp.pad(est, (0, pad), constant_values=-1.0)
    n_chunks = mslot.shape[0] // CHUNK_B

    kernel = functools.partial(_reduce_kernel, n_chunks=n_chunks, k=k)
    match_max, chall_max, win_row = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((1, k), jnp.float32),
                   jax.ShapeDtypeStruct((1, k), jnp.float32),
                   jax.ShapeDtypeStruct((1, k), jnp.int32)),
        name="topk_reduce",
        interpret=interpret,
    )(mslot.astype(jnp.int32).reshape(1, -1),
      target.astype(jnp.int32).reshape(1, -1),
      est.astype(jnp.float32).reshape(1, -1))
    return match_max[0], chall_max[0], win_row[0]
