"""Count-Min fold as a Pallas kernel: scatter-add -> factored one-hot matmul.

A column index is written idx = hi * LO + lo (LO = 256 lanes, HI = W / LO)
and the counters are viewed as [planes, d, HI, LO], the row-major split of
[planes, d, W]. For one depth row and one chunk of C records the scatter's
sum is then ONE matmul on the MXU,

    counts[p, r] += A_p . Bm^T     A_p[h, b] = (hi_b == h) * val_p[b]  [HI, C]
                                   Bm[l, b]  = (lo_b == l)             [LO, C]

of height planes * HI (the planes stacked, sharing one Bm), contraction C,
width LO. Both operands are built with the record axis along the lanes, as
the index and value rows arrive, so nothing is relaid out.

Costs, as counts (d = 4, W = 65,536, an x4 fold's B = 33,792 rows, 34,816
after padding to CHUNK_B): the VPU builds d * B * (2*HI + LO) = 1.1e8
membership cells (one compare a cell of A and of Bm, one select a plane and
part); the MXU makes 2 * d * B * W = 1.8e10 multiply-accumulates in bf16
passes — three, because the value row is split by hand into three bf16-exact
parts (an f32 has 24 significant bits, a bf16 eight) and the 0/1 of a one-hot
is exact in bf16, so every product is exact and the f32 accumulation adds
whole values: integer sums below 2^24 equal the scatter twin's bit for bit
(`chip_smoke.py`'s pallas_vs_scatter leg); HBM moves the planes once each way.
A plain one-hot over the whole width would build d * B * W = 9.1e9 cells for
the same sums. The MACs grow with W, so at widths of 2^20 and more, where HI
is tiled over the grid and every tile walks the whole batch, the XLA scatter
is the cheaper form and `sketch.state.fold_forms` picks it (measured table in
docs/tpu_sketch.md "Count-Min form by width").

The counters are donated (input_output_aliases) so the fold is in-place in
HBM. Callers use `countmin.update` unless `state.fold_forms` picks this.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from netobserv_tpu.ops import hashing
from netobserv_tpu.ops.countmin import CountMin
from netobserv_tpu.ops.pallas import tier_tiles

#: the low digit of a column index: one counter row of the [HI, LO] view
LO = 256
LO_BITS = LO.bit_length() - 1
#: a counters block [planes, d, HI tile, LO] stays at or under this in VMEM
BLOCK_BYTES = 2 << 20
TILE_W = 512
CHUNK_B = 1024
#: contraction precision of the f32 one-hot matmuls in ops/pallas. The MXU's
#: default for f32 operands is ONE bf16 pass, which rounds each value to 8
#: mantissa bits before it is added (seen on the v5e, PR 21: byte sums off by
#: up to 159 per counter, in both directions — a Count-Min that can
#: UNDERestimate). HIGHEST keeps the f32 product exact, so integer-valued
#: sums below 2^24 equal the scatter twin's bit for bit, compiled as
#: interpreted.
EXACT = jax.lax.Precision.HIGHEST


def _bf16_parts(x: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    """f32 `x` as three f32 arrays, each exact in bf16, that sum to `x`."""
    def head(v):
        return v.astype(jnp.bfloat16).astype(jnp.float32)
    hi = head(x)
    mid = head(x - hi)
    return hi, mid, x - hi - mid


def _fold_kernel(counts_ref, idx_ref, vals_ref, out_ref, *, n_chunks: int):
    """The factored walk, for any number of planes: counts/out
    [P, d, HI tile, LO] f32, idx [d, B] i32, vals [P, B] f32. A grid step
    owns one HI tile and walks the whole batch; rows whose hi digit lies in
    another tile match no row of A and add nothing."""
    planes, depth, ht, _ = out_ref.shape
    hi_rows = (pl.program_id(0) * ht
               + jax.lax.broadcasted_iota(jnp.int32, (ht, CHUNK_B), 0))
    lo_rows = jax.lax.broadcasted_iota(jnp.int32, (LO, CHUNK_B), 0)
    out_ref[...] = counts_ref[...]

    def chunk_body(i, carry):
        sl = pl.ds(pl.multiple_of(i * CHUNK_B, CHUNK_B), CHUNK_B)
        parts = _bf16_parts(vals_ref[:, sl])             # 3 x [P, CHUNK_B]
        for r in range(depth):  # static unroll over sketch depth
            idx = idx_ref[pl.ds(r, 1), sl]               # [1, CHUNK_B]
            member = (idx >> LO_BITS) == hi_rows         # [HT, CHUNK_B]
            bm = ((idx & (LO - 1)) == lo_rows).astype(jnp.bfloat16)
            contrib = 0.0
            for part in parts:
                a = jnp.concatenate(
                    [jnp.where(member, part[p:p + 1], 0.0)
                     for p in range(planes)]).astype(jnp.bfloat16)
                contrib += jax.lax.dot_general(          # A . Bm^T
                    a, bm, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)  # [P*HT, LO]
            for p in range(planes):
                out_ref[p, r] += contrib[p * ht:(p + 1) * ht]
        return carry

    jax.lax.fori_loop(0, n_chunks, chunk_body, 0)


def _fold(planes: tuple[jax.Array, ...], h1: jax.Array, h2: jax.Array,
          values: tuple[jax.Array, ...], valid: jax.Array, name: str,
          interpret: bool | None) -> jax.Array:
    """`planes` ([d, W] each) stacked to [P, d, W] f32, plus the scatter-add
    of each plane's `values` row ([B], masked by `valid`) at
    hashing.row_indices(h1, h2)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n, (d, w) = len(planes), planes[0].shape
    assert w % TILE_W == 0, f"width {w} must be a multiple of {TILE_W}"
    pad = (-h1.shape[0]) % CHUNK_B
    if pad:  # padding rows carry h2 = 1 and value 0, like invalid ones
        h1 = jnp.pad(h1, (0, pad))
        h2 = jnp.pad(h2, (0, pad), constant_values=1)
        valid = jnp.pad(valid, (0, pad))
        values = tuple(jnp.pad(v, (0, pad)) for v in values)
    idx = hashing.row_indices(h1, h2, d, w).astype(jnp.int32)  # [d, B]
    vals = jnp.stack([jnp.where(valid, v, 0).astype(jnp.float32)
                      for v in values])                        # [P, B]
    bp = idx.shape[1]
    hi = w // LO
    fit = max(8, BLOCK_BYTES // (n * d * LO * 4))
    ht = min(hi, 1 << (fit.bit_length() - 1))  # a power of two, as hi is
    block = pl.BlockSpec((n, d, ht, LO), lambda j: (0, 0, j, 0))
    new_counts = pl.pallas_call(
        functools.partial(_fold_kernel, n_chunks=bp // CHUNK_B),
        grid=(hi // ht,),
        in_specs=[
            block,
            pl.BlockSpec((d, bp), lambda j: (0, 0)),   # all indices
            pl.BlockSpec((n, bp), lambda j: (0, 0)),   # all values
        ],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct((n, d, hi, LO), jnp.float32),
        input_output_aliases={0: 0},
        name=name,
        interpret=interpret,
    )(jnp.stack([c.astype(jnp.float32) for c in planes]).reshape(
        n, d, hi, LO), idx, vals)
    return new_counts.reshape(n, d, w)


def update_two(cm_a: CountMin, cm_b: CountMin, h1: jax.Array, h2: jax.Array,
               vals_a: jax.Array, vals_b: jax.Array, valid: jax.Array,
               interpret: bool | None = None) -> tuple[CountMin, CountMin]:
    """Fused drop-in for countmin.update_two: both planes (bytes, packets)
    fold in ONE kernel sharing hash indices and the lo one-hot."""
    assert cm_b.counts.shape == cm_a.counts.shape
    new_counts = _fold((cm_a.counts, cm_b.counts), h1, h2, (vals_a, vals_b),
                       valid, "countmin_update_two", interpret)
    return (CountMin(counts=new_counts[0].astype(cm_a.counts.dtype)),
            CountMin(counts=new_counts[1].astype(cm_b.counts.dtype)))


def _tier2_kernel(base_ref, mid_ref, top_ref, idx_ref, vals_ref,
                  base_out, mid_out, top_out, q_out, *, depth: int,
                  n_chunks: int, mid_group: int, top_group: int,
                  units: tuple[int, int]):
    """Tier-interior dual-plane fold: decode the narrow tier tiles to a
    wide f32 view IN VMEM, run a width-tiled one-hot chunk walk on it,
    then promote the per-fold delta back into the tiers — the wide array
    never exists in HBM. A second walk gathers the post-fold bytes-plane
    estimate per record (q_out accumulates across width tiles; each index
    hits exactly one tile, so the sum is an exact gather) so the heavy-
    hitter plane can query without a wide temporary either."""
    j = pl.program_id(0)
    base = j * TILE_W
    lanes = base + jax.lax.broadcasted_iota(jnp.int32, (1, TILE_W), 1)
    tm = TILE_W // mid_group
    em = tier_tiles.expand_matrix(TILE_W, mid_group)
    et = tier_tiles.expand_matrix(tm, top_group // mid_group)
    gm = tier_tiles.groupsum_matrix(TILE_W, mid_group)
    gt = tier_tiles.groupsum_matrix(tm, top_group // mid_group)

    base_i = base_ref[...].astype(jnp.int32)   # [2, d, T]
    mid_i = mid_ref[...].astype(jnp.int32)     # [2, d, T//mg]
    top_u = top_ref[...]                       # [2, d, T//tg] u32
    dec = jnp.stack([
        tier_tiles.decode_tile(base_i[p], mid_i[p], top_u[p], em, et,
                               units[p])
        for p in range(2)])                    # [2, d, T] f32 wide view

    def chunk_body(i, acc):  # acc seeded from dec
        sl = pl.dslice(i * CHUNK_B, CHUNK_B)
        vals = vals_ref[:, sl]                       # [2, CHUNK_B]
        new_rows = []
        for r in range(depth):  # static unroll over sketch depth
            idx = idx_ref[r, sl].reshape(CHUNK_B, 1)
            onehot = (idx == lanes).astype(jnp.float32)  # [CHUNK_B, TILE_W]
            contrib = jnp.dot(vals, onehot, precision=EXACT,
                              preferred_element_type=jnp.float32)  # [2, W]
            new_rows.append(acc[:, r] + contrib)
        return jnp.stack(new_rows, axis=1)           # [2, d, TILE_W]

    new = jax.lax.fori_loop(0, n_chunks, chunk_body, dec)
    for p in range(2):
        nb, nm, nt = tier_tiles.promote_tile(
            base_i[p], mid_i[p], top_u[p], dec[p], new[p], gm, gt, units[p])
        base_out[p] = nb
        mid_out[p] = nm
        top_out[p] = nt

    # bytes-plane query on the post-fold wide view (pre-promotion — the
    # same values countmin.query reads in the decode-wrapped form)
    @pl.when(j == 0)
    def _zero():
        q_out[...] = jnp.zeros_like(q_out[...])

    wide0 = new[0]

    def q_body(i, carry):
        sl = pl.dslice(i * CHUNK_B, CHUNK_B)
        for r in range(depth):
            idx = idx_ref[r, sl].reshape(CHUNK_B, 1)
            qc = jnp.sum(jnp.where(idx == lanes, wide0[r:r + 1, :], 0.0),
                         axis=1)
            q_out[r, sl] = q_out[r, sl] + qc
        return carry

    jax.lax.fori_loop(0, n_chunks, q_body, 0)


def tiered_eligible(width: int, spec, interpret: bool | None = None) -> bool:
    """Static gate for the tier-interior walk: whole tiles, whole top
    groups per tile (so promotion never crosses a tile boundary) — and only
    where the kernel INTERPRETS. Compiled, Mosaic refuses it twice over (v5e,
    PR 21): the tier blocks are `TILE_W // group` lanes wide — 16 and 2 at
    the default groups, neither a multiple of 128 nor the full dimension —
    and with lane-aligned groups (2, 4) the u32 top tier has no cast to f32.
    A TPU therefore folds tiers through the decode wrap, which compiles,
    and this walk is dead code on the device (debt D5: delete it or make it
    compile)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return (interpret and width % TILE_W == 0
            and TILE_W % spec.top_group == 0)


def update_two_tiered(plane_a, plane_b, h1: jax.Array, h2: jax.Array,
                      vals_a: jax.Array, vals_b: jax.Array, valid: jax.Array,
                      spec, interpret: bool | None = None):
    """Tier-native twin of :func:`update_two`: folds BOTH Count-Min planes
    straight into their (u8 base, u16 mid, u32 top) tier arrays and returns
    ``(new_plane_a, new_plane_b, est)`` where ``est[b]`` is
    ``countmin.query`` of the post-fold bytes plane's transient wide view
    (what the slot table queries). Semantics are ``tiered.fold_encode`` of
    the wide fold — pinned bit-exact by tests/test_tiered.py."""
    from netobserv_tpu.sketch.tiered import TieredPlane
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    d, w = plane_a.base.shape
    assert plane_b.base.shape == (d, w)
    assert tiered_eligible(w, spec, interpret), \
        f"width {w} / top_group {spec.top_group} ineligible for tier tiles"
    mg, tg = spec.mid_group, spec.top_group
    b = h1.shape[0]
    pad = (-b) % CHUNK_B
    if pad:
        h1 = jnp.pad(h1, (0, pad))
        h2 = jnp.pad(h2, (0, pad), constant_values=1)
        vals_a = jnp.pad(vals_a, (0, pad))
        vals_b = jnp.pad(vals_b, (0, pad))
        valid = jnp.pad(valid, (0, pad))
    idx = hashing.row_indices(h1, h2, d, w).astype(jnp.int32)  # [d, B]
    vals = jnp.stack([
        jnp.where(valid, vals_a, 0).astype(jnp.float32),
        jnp.where(valid, vals_b, 0).astype(jnp.float32)])      # [2, B]
    base_s = jnp.stack([plane_a.base, plane_b.base])   # [2, d, w] u8
    mid_s = jnp.stack([plane_a.mid, plane_b.mid])      # [2, d, w//mg] u16
    top_s = jnp.stack([plane_a.top, plane_b.top])      # [2, d, w//tg] u32
    n_chunks = idx.shape[1] // CHUNK_B

    kernel = functools.partial(
        _tier2_kernel, depth=d, n_chunks=n_chunks, mid_group=mg,
        top_group=tg, units=(spec.bytes_unit, 1))
    nb, nm, nt, q = pl.pallas_call(
        kernel,
        grid=(w // TILE_W,),
        in_specs=[
            pl.BlockSpec((2, d, TILE_W), lambda j: (0, 0, j)),
            pl.BlockSpec((2, d, TILE_W // mg), lambda j: (0, 0, j)),
            pl.BlockSpec((2, d, TILE_W // tg), lambda j: (0, 0, j)),
            pl.BlockSpec((d, idx.shape[1]), lambda j: (0, 0)),
            pl.BlockSpec((2, idx.shape[1]), lambda j: (0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((2, d, TILE_W), lambda j: (0, 0, j)),
            pl.BlockSpec((2, d, TILE_W // mg), lambda j: (0, 0, j)),
            pl.BlockSpec((2, d, TILE_W // tg), lambda j: (0, 0, j)),
            pl.BlockSpec((d, idx.shape[1]), lambda j: (0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((2, d, w), jnp.uint8),
            jax.ShapeDtypeStruct((2, d, w // mg), jnp.uint16),
            jax.ShapeDtypeStruct((2, d, w // tg), jnp.uint32),
            jax.ShapeDtypeStruct((d, idx.shape[1]), jnp.float32),
        ),
        input_output_aliases={0: 0, 1: 1, 2: 2},
        name="countmin_update_two_tiered",
        interpret=interpret,
    )(base_s, mid_s, top_s, idx, vals)
    est = jnp.min(q[:, :b], axis=0)
    return (TieredPlane(base=nb[0], mid=nm[0], top=nt[0]),
            TieredPlane(base=nb[1], mid=nm[1], top=nt[1]), est)


def update(cm: CountMin, h1: jax.Array, h2: jax.Array, values: jax.Array,
           valid: jax.Array, interpret: bool | None = None) -> CountMin:
    """Drop-in replacement for countmin.update (float32 sketches): the
    one-plane case of :func:`update_two`'s walk.

    `interpret` defaults to True off-TPU so the kernel is testable on the
    CPU mesh; on TPU it compiles through Mosaic."""
    return CountMin(counts=_fold((cm.counts,), h1, h2, (values,), valid,
                                 "countmin_update", interpret)[0])
