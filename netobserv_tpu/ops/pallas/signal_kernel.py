"""Fused signal-plane fold as ONE Pallas kernel: eight scatter chains -> one
batch walk.

The un-fused ingest pays a separate serialized XLA scatter/EWMA pass over the
batch for every small signal table (DDoS/SYN/drop EWMA rates, SYN-ACK
responses, conversation fwd/rev, DSCP bytes, drop causes). All of those
tables together are a few tens of KB — they fit VMEM simultaneously — so the
kernel walks the batch ONCE and updates them together, the TPU analog of the
single-pass sketch accelerators (arxiv 2504.16896, 2005.13332).

Formulation: the eight scatter-adds group into five INDEX FAMILIES (victim =
dst bucket, src bucket, conversation pair, DSCP code, drop cause). Per batch
chunk each family builds its one-hot membership matrix once and contracts it
with ALL of its value rows on the MXU:

  - dst family  -> ddos bytes, SYN half-open mass, dropped bytes   (3 rows)
  - src family  -> SYN-ACK responses                               (1 row)
  - pair family -> conversation fwd / rev bytes                    (2 rows)
  - dscp / cause -> one row each over a shared 256-lane aux table

so a record costs ~3m + 512 lane compares (m = EWMA bucket count, 12.8K at
the m=4096 default) plus MXU MACs, replacing eight dependent scatter passes.
The per-dst / per-src HLL GRIDS are deliberately NOT here: their one-hot
fold pays D*2^p (262K) compares per record versus a single scatter touch —
the measured verdict in docs/tpu_sketch.md ("Per-stage ingest attribution").

Same contract as the sibling kernels: `interpret` defaults to True off-TPU
(testable on the CPU mesh), counters donated via input_output_aliases, and
bit-exact equivalence with the scatter chain is pinned by
tests/test_pallas_signal.py (integer-valued f32 masses make the float sums
order-independent).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from netobserv_tpu.ops.pallas import tier_tiles
from netobserv_tpu.ops.pallas.countmin_kernel import EXACT

CHUNK_B = 1024
#: packed-HLL register-triple tile width of the tiered variant's grid
TILE_R = 512
#: shared width of the small-table aux plane (row 0 = DSCP, row 1 = drop
#: causes); both tables must fit (sketch.state N_DSCP=64, N_DROP_CAUSES=128)
AUX_W = 256

#: value-plane row order (main table rows 0..5 match vals rows 0..5)
#: [ddos, syn, drops | synack | conv_fwd, conv_rev] + aux [dscp, cause]
N_MAIN = 6
N_VALS = 8
#: index families: [dst, src, pair, dscp, cause]
N_IDX = 5


class SignalPlanes(NamedTuple):
    """The signal tables the fused kernel updates, as plain arrays."""

    ddos_rate: jax.Array   # f32[m]
    syn_rate: jax.Array    # f32[m]
    drops_rate: jax.Array  # f32[m]
    synack: jax.Array      # f32[m]
    conv_fwd: jax.Array    # f32[m]
    conv_rev: jax.Array    # f32[m]
    dscp_bytes: jax.Array  # f32[n_dscp]  (n_dscp <= AUX_W)
    drop_causes: jax.Array  # f32[n_causes] (n_causes <= AUX_W)


def _signal_fold_body(main_ref, aux_ref, idx_ref, vals_ref, main_out,
                      aux_out, *, n_chunks: int, m: int):
    """The five-family one-hot fold shared by :func:`_fold_kernel` and the
    tiered variant (one body — the two kernels cannot drift)."""
    lanes_m = jax.lax.broadcasted_iota(jnp.int32, (1, m), 1)
    lanes_a = jax.lax.broadcasted_iota(jnp.int32, (1, AUX_W), 1)

    def chunk_body(i, acc):
        acc_main, acc_aux = acc
        sl = pl.dslice(i * CHUNK_B, CHUNK_B)
        vals = vals_ref[:, sl]                                  # [8, C]

        def onehot(fam, lanes):
            idx = idx_ref[fam, sl].reshape(CHUNK_B, 1)
            return (idx == lanes).astype(jnp.float32)           # [C, W]

        # one one-hot build per index family, shared by its value rows
        c_dst = jnp.dot(vals[0:3], onehot(0, lanes_m), precision=EXACT,
                        preferred_element_type=jnp.float32)     # [3, m]
        c_src = jnp.dot(vals[3:4], onehot(1, lanes_m), precision=EXACT,
                        preferred_element_type=jnp.float32)     # [1, m]
        c_pair = jnp.dot(vals[4:6], onehot(2, lanes_m), precision=EXACT,
                         preferred_element_type=jnp.float32)    # [2, m]
        c_dscp = jnp.dot(vals[6:7], onehot(3, lanes_a), precision=EXACT,
                         preferred_element_type=jnp.float32)    # [1, AUX_W]
        c_cause = jnp.dot(vals[7:8], onehot(4, lanes_a), precision=EXACT,
                          preferred_element_type=jnp.float32)   # [1, AUX_W]
        new_main = acc_main + jnp.concatenate([c_dst, c_src, c_pair], axis=0)
        new_aux = acc_aux + jnp.concatenate([c_dscp, c_cause], axis=0)
        return new_main, new_aux

    acc = jax.lax.fori_loop(0, n_chunks, chunk_body,
                            (main_ref[...], aux_ref[...]))
    main_out[...] = acc[0]
    aux_out[...] = acc[1]


def _fold_kernel(main_ref, aux_ref, idx_ref, vals_ref, main_out, aux_out, *,
                 n_chunks: int, m: int):
    _signal_fold_body(main_ref, aux_ref, idx_ref, vals_ref, main_out,
                      aux_out, n_chunks=n_chunks, m=m)


def _fold_tiered_kernel(main_ref, aux_ref, pk3_ref, idx_ref, vals_ref,
                        hidx_ref, hrank_ref, main_out, aux_out, pk3_out, *,
                        n_chunks: int, m: int, tile_r: int):
    """Tiered megakernel: the signal fold plus the packed global-src HLL
    lane in one walk. The grid tiles the packed register triples; the
    signal tables ride constant-index blocks (revisited across grid steps,
    so their fold runs once, on the first step). The HLL registers stay
    6-bit packed in HBM — unpack/max/pack all happen on the VMEM tile."""
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _signal():
        _signal_fold_body(main_ref, aux_ref, idx_ref, vals_ref, main_out,
                          aux_out, n_chunks=n_chunks, m=m)

    # registers 4t + r for the packed triples t of this tile
    rows = tuple(tier_tiles.unpack_reg_rows(pk3_ref[...]))
    t_lanes = j * tile_r + jax.lax.broadcasted_iota(
        jnp.int32, (1, tile_r), 1)

    def hll_body(i, carry):
        sl = pl.dslice(i * CHUNK_B, CHUNK_B)
        hidx = hidx_ref[sl].reshape(CHUNK_B, 1)
        hrank = hrank_ref[sl].reshape(CHUNK_B, 1)
        new = []
        for r in range(4):  # static unroll over the 4 regs per triple
            hit = ((hidx >> 2) == t_lanes) & ((hidx & 3) == r)
            contrib = jnp.max(jnp.where(hit, hrank, 0), axis=0,
                              keepdims=True)
            new.append(jnp.maximum(carry[r], contrib))
        return tuple(new)

    rows = jax.lax.fori_loop(0, n_chunks, hll_body, rows)
    pk3_out[...] = tier_tiles.pack_reg_rows(list(rows))


def eligible(planes: SignalPlanes) -> bool:
    """Static shape gate: the six m-wide planes must share one power-of-two,
    lane-aligned width and the aux tables must fit the shared aux plane."""
    m = planes.ddos_rate.shape[0]
    return (all(p.shape == (m,) for p in
                (planes.syn_rate, planes.drops_rate, planes.synack,
                 planes.conv_fwd, planes.conv_rev))
            and m % 128 == 0
            and planes.dscp_bytes.shape[0] <= AUX_W
            and planes.drop_causes.shape[0] <= AUX_W)


def update(planes: SignalPlanes, idx: jax.Array, vals: jax.Array,
           interpret: bool | None = None) -> SignalPlanes:
    """Fold one batch into every signal plane in one pass.

    idx:  i32[5, B] — [dst_bucket, src_bucket, pair_bucket, dscp, cause],
          each already masked into its table's range.
    vals: f32[8, B] — [ddos, syn, drops, synack, conv_fwd, conv_rev, dscp,
          cause] masses, already validity/signal-masked (0 = no-op).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    assert eligible(planes), "signal planes ineligible for the fused kernel"
    m = planes.ddos_rate.shape[0]
    b = idx.shape[1]
    assert vals.shape == (N_VALS, b) and idx.shape == (N_IDX, b)
    pad = (-b) % CHUNK_B
    if pad:  # zero mass adds nothing — the padded tail is a no-op
        idx = jnp.pad(idx, ((0, 0), (0, pad)))
        vals = jnp.pad(vals, ((0, 0), (0, pad)))
    n_chunks = idx.shape[1] // CHUNK_B

    main = jnp.stack([planes.ddos_rate, planes.syn_rate, planes.drops_rate,
                      planes.synack, planes.conv_fwd, planes.conv_rev])
    n_dscp = planes.dscp_bytes.shape[0]
    n_causes = planes.drop_causes.shape[0]
    aux = jnp.zeros((2, AUX_W), jnp.float32)
    aux = aux.at[0, :n_dscp].set(planes.dscp_bytes)
    aux = aux.at[1, :n_causes].set(planes.drop_causes)

    kernel = functools.partial(_fold_kernel, n_chunks=n_chunks, m=m)
    new_main, new_aux = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((N_MAIN, m), jnp.float32),
                   jax.ShapeDtypeStruct((2, AUX_W), jnp.float32)),
        input_output_aliases={0: 0, 1: 1},
        name="signal_update",
        interpret=interpret,
    )(main, aux, idx.astype(jnp.int32), vals.astype(jnp.float32))
    return SignalPlanes(
        ddos_rate=new_main[0], syn_rate=new_main[1], drops_rate=new_main[2],
        synack=new_main[3], conv_fwd=new_main[4], conv_rev=new_main[5],
        dscp_bytes=new_aux[0, :n_dscp], drop_causes=new_aux[1, :n_causes])


def hll_fusible(m: int) -> bool:
    """Static gate for folding the packed global-src HLL bank into the
    tiered megakernel: the register-triple axis must tile evenly."""
    n3 = m // 4
    return m % 4 == 0 and n3 > 0 and (n3 <= TILE_R or n3 % TILE_R == 0)


def update_tiered(planes: SignalPlanes, packed: jax.Array, idx: jax.Array,
                  vals: jax.Array, hll_idx: jax.Array, hll_rank: jax.Array,
                  interpret: bool | None = None
                  ) -> tuple[SignalPlanes, jax.Array]:
    """Tiered twin of :func:`update`: the same signal fold PLUS the
    6-bit-packed global-src HLL bank folded in the same walk, without ever
    unpacking it to wide i32 registers in HBM.

    packed:   u8[m//4*3] — tiered.pack_hll layout.
    hll_idx:  i32[B] — register index per record (``h1 & (m-1)``).
    hll_rank: i32[B] — rank per record, 0 for invalid (max no-op).
    Returns (new planes, new packed bank) — the max fold is
    order-independent, so the lane is bit-exact vs unpack->update->pack.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    assert eligible(planes), "signal planes ineligible for the fused kernel"
    n = packed.shape[0]
    n3 = n // 3
    m_hll = n3 * 4
    assert n % 3 == 0 and hll_fusible(m_hll), \
        f"packed HLL bank of {n} bytes ineligible for the tiered kernel"
    m = planes.ddos_rate.shape[0]
    b = idx.shape[1]
    assert vals.shape == (N_VALS, b) and idx.shape == (N_IDX, b)
    assert hll_idx.shape == (b,) and hll_rank.shape == (b,)
    pad = (-b) % CHUNK_B
    if pad:  # zero mass / rank-0 tails are no-ops under add / max
        idx = jnp.pad(idx, ((0, 0), (0, pad)))
        vals = jnp.pad(vals, ((0, 0), (0, pad)))
        hll_idx = jnp.pad(hll_idx, (0, pad))
        hll_rank = jnp.pad(hll_rank, (0, pad))
    n_chunks = idx.shape[1] // CHUNK_B
    tile_r = min(TILE_R, n3)

    main = jnp.stack([planes.ddos_rate, planes.syn_rate, planes.drops_rate,
                      planes.synack, planes.conv_fwd, planes.conv_rev])
    n_dscp = planes.dscp_bytes.shape[0]
    n_causes = planes.drop_causes.shape[0]
    aux = jnp.zeros((2, AUX_W), jnp.float32)
    aux = aux.at[0, :n_dscp].set(planes.dscp_bytes)
    aux = aux.at[1, :n_causes].set(planes.drop_causes)
    # kernel-facing byte-row layout: byte j of triple t at [j, t] (the
    # reshape/transpose runs in XLA on the small u8 array, not in-kernel)
    pk3 = packed.reshape(n3, 3).T

    kernel = functools.partial(_fold_tiered_kernel, n_chunks=n_chunks, m=m,
                               tile_r=tile_r)
    new_main, new_aux, new_pk3 = pl.pallas_call(
        kernel,
        grid=(n3 // tile_r,),
        in_specs=[
            pl.BlockSpec((N_MAIN, m), lambda j: (0, 0)),
            pl.BlockSpec((2, AUX_W), lambda j: (0, 0)),
            pl.BlockSpec((3, tile_r), lambda j: (0, j)),
            pl.BlockSpec((N_IDX, idx.shape[1]), lambda j: (0, 0)),
            pl.BlockSpec((N_VALS, idx.shape[1]), lambda j: (0, 0)),
            pl.BlockSpec((idx.shape[1],), lambda j: (0,)),
            pl.BlockSpec((idx.shape[1],), lambda j: (0,)),
        ],
        out_specs=(
            pl.BlockSpec((N_MAIN, m), lambda j: (0, 0)),
            pl.BlockSpec((2, AUX_W), lambda j: (0, 0)),
            pl.BlockSpec((3, tile_r), lambda j: (0, j)),
        ),
        out_shape=(jax.ShapeDtypeStruct((N_MAIN, m), jnp.float32),
                   jax.ShapeDtypeStruct((2, AUX_W), jnp.float32),
                   jax.ShapeDtypeStruct((3, n3), jnp.uint8)),
        input_output_aliases={0: 0, 1: 1, 2: 2},
        name="signal_update_tiered",
        interpret=interpret,
    )(main, aux, pk3, idx.astype(jnp.int32), vals.astype(jnp.float32),
      hll_idx.astype(jnp.int32), hll_rank.astype(jnp.int32))
    return (SignalPlanes(
        ddos_rate=new_main[0], syn_rate=new_main[1], drops_rate=new_main[2],
        synack=new_main[3], conv_fwd=new_main[4], conv_rev=new_main[5],
        dscp_bytes=new_aux[0, :n_dscp], drop_causes=new_aux[1, :n_causes]),
        new_pk3.T.reshape(n))
