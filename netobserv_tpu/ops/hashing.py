"""Vectorized flow-key hashing in uint32 lanes.

Murmur3-style mixing (multiply/rotate/xor) over the packed KEY_WORDS uint32 words
of each flow key, fully unrolled (word count is static), batched over the leading
axis. Double hashing (Kirsch–Mitzenmacher) derives the d Count-Min row indices
from two base hashes, so each batch is hashed exactly twice regardless of depth.

Replaces the reference's per-record Go map hashing + FNV (implicit in Go's
runtime map, `pkg/flow/account.go:204-246`) with VPU-friendly lane math.
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np

try:  # pragma: no cover - exercised by the jax-less qemu CI tier
    import jax
    import jax.numpy as jnp
except ImportError:  # big-endian s390x: only the numpy twins are usable
    jax = None
    jnp = None

# numpy scalars, NOT jnp: module-level jnp constants would initialize the XLA
# backend at import time, which breaks jax.distributed.initialize() for any
# process that imports this package before multi-host bootstrap
_C1 = np.uint32(0xCC9E2D51)
_C2 = np.uint32(0x1B873593)
_M5 = np.uint32(5)
_N1 = np.uint32(0xE6546B64)
_F1 = np.uint32(0x85EBCA6B)
_F2 = np.uint32(0xC2B2AE35)


def _rotl32(x: jax.Array, r: int) -> jax.Array:
    return (x << r) | (x >> (32 - r))


def fmix32(h: jax.Array) -> jax.Array:
    """Murmur3 finalizer: full avalanche on a uint32 lane."""
    h = h ^ (h >> 16)
    h = h * _F1
    h = h ^ (h >> 13)
    h = h * _F2
    h = h ^ (h >> 16)
    return h


def hash_words(words: jax.Array, seed: int | jax.Array) -> jax.Array:
    """Hash packed key words -> uint32.

    words: uint32[..., W] (W static, typically KEY_WORDS=10)
    seed:  scalar (python int or uint32 array)
    returns uint32[...]
    """
    words = words.astype(jnp.uint32)
    w = words.shape[-1]
    h = jnp.broadcast_to(jnp.asarray(seed, dtype=jnp.uint32), words.shape[:-1])
    for i in range(w):  # static unroll
        k = words[..., i] * _C1
        k = _rotl32(k, 15) * _C2
        h = h ^ k
        h = _rotl32(h, 13) * _M5 + _N1
    h = h ^ jnp.uint32(w * 4)
    return fmix32(h)


#: seed of the VICTIM/destination bucket family — the per-dst HLL grid,
#: every EWMA victim bucket (ddos/syn/drops), the conversation pair hash,
#: and the exporter's host-side victim naming all key off it; one
#: definition so the device and host sides cannot drift
DST_BUCKET_SEED = 0x0D57
#: seed of the source-hash family (global/per-src HLL, fan-out grid)
SRC_BUCKET_SEED = 0x0517
#: seed of the (dst addr, dst port) fan-out family — the port-scan signal's
#: per-src HLL grid keys off it (was inlined in sketch/state.py)
DSTPORT_FANOUT_SEED = 0x5CA7
#: seed of the tenant-owner family (multi-tenant sketch planes): the host
#: router assigns every evicted flow to a tenant by this hash of the FULL
#: flow key, so a flow's tenant is stable across windows and agents. Both
#: sides (device `tenant_of`, host `tenant_of_np`) derive from this one
#: constant — never inline it
TENANT_SEED = 0x7E4A

#: base_hashes' two seed constants (h1 / h2 family); every derived family
#: xors its bucket seed into these
_H1_SEED = 0x9747B28C
_H2_SEED = 0x5BD1E995


def base_hashes(words: jax.Array, seed: int = 0) -> tuple[jax.Array, jax.Array]:
    """Two independent base hashes (h2 forced odd so strides generate Z_{2^k})."""
    h1 = hash_words(words, jnp.uint32(_H1_SEED) ^ jnp.uint32(seed))
    h2 = hash_words(words, jnp.uint32(_H2_SEED) ^ jnp.uint32(seed))
    return h1, h2 | jnp.uint32(1)


class MultiHashes(NamedTuple):
    """Every hash family the sketch ingest consumes, from ONE sweep over the
    key words (`base_hashes_multi`). Values are bit-identical to the separate
    `base_hashes` calls they replace — pinned by tests/test_hashing_multi.py."""

    h1: jax.Array       #: flow family h1 (all KEY_WORDS, seed 0)
    h2: jax.Array       #: flow family h2 (odd)
    src_h1: jax.Array   #: SRC_BUCKET_SEED over the src words (0:4)
    src_h2: jax.Array   #: … h2 (odd)
    dst_h1: jax.Array   #: DST_BUCKET_SEED over the dst words (4:8)
    dp_h1: jax.Array    #: DSTPORT_FANOUT_SEED over dst words + dst port
    dp_h2: jax.Array    #: … h2 (odd)
    src_sym: jax.Array  #: DST_BUCKET_SEED over the SRC words (victim-bucket
    #: hash of the source endpoint: conv pair + SYN-ACK bucketing)


#: word-index sets absorbed by each family (KEY_WORDS layout:
#: src ip words 0..3, dst ip words 4..7, ports word 8, proto word 9;
#: index 10 is the synthesized dst-port column)
_FLOW_IDXS = tuple(range(10))
_SRC_IDXS = (0, 1, 2, 3)
_DST_IDXS = (4, 5, 6, 7)
_DP_IDXS = (4, 5, 6, 7, 10)


def base_hashes_multi(words: jax.Array) -> MultiHashes:
    """All five hash families in ONE pass over the key words.

    The murmur3 per-word k-mix (multiply/rotate/multiply) is seed-independent,
    so it is computed once per word and shared by every family; only the
    cheap h-side accumulation runs per family — and the unused h2 halves of
    the dst-bucket and src-sym families are skipped entirely. Replaces five
    separate `base_hashes` sweeps in `sketch.state.ingest` (bit-identical;
    the numpy host twin `hash_words_np` and the seed constants above remain
    the single source of truth)."""
    words = words.astype(jnp.uint32)
    assert words.shape[-1] == 10, "base_hashes_multi expects KEY_WORDS=10"
    shape = words.shape[:-1]

    def k_mix(w):
        k = w * _C1
        return _rotl32(k, 15) * _C2

    ks = [k_mix(words[..., i]) for i in range(10)]
    # the dst-port column the fan-out family hashes (low half of word 8)
    ks.append(k_mix(words[..., 8] & jnp.uint32(0xFFFF)))

    def run(seed: int, idxs: tuple[int, ...]) -> jax.Array:
        h = jnp.broadcast_to(jnp.uint32(seed), shape)
        for i in idxs:
            h = _rotl32(h ^ ks[i], 13) * _M5 + _N1
        return fmix32(h ^ jnp.uint32(len(idxs) * 4))

    return MultiHashes(
        h1=run(_H1_SEED, _FLOW_IDXS),
        h2=run(_H2_SEED, _FLOW_IDXS) | jnp.uint32(1),
        src_h1=run(_H1_SEED ^ SRC_BUCKET_SEED, _SRC_IDXS),
        src_h2=run(_H2_SEED ^ SRC_BUCKET_SEED, _SRC_IDXS) | jnp.uint32(1),
        dst_h1=run(_H1_SEED ^ DST_BUCKET_SEED, _DST_IDXS),
        dp_h1=run(_H1_SEED ^ DSTPORT_FANOUT_SEED, _DP_IDXS),
        dp_h2=run(_H2_SEED ^ DSTPORT_FANOUT_SEED, _DP_IDXS) | jnp.uint32(1),
        src_sym=run(_H1_SEED ^ DST_BUCKET_SEED, _SRC_IDXS),
    )


def base_hashes_multi_np(words: np.ndarray) -> dict[str, np.ndarray]:
    """Pure-numpy twin of `base_hashes_multi` (same field names) — runs on
    jax-less hosts, including the big-endian qemu CI tier, where it pins the
    fused sweep against golden vectors so an endianness regression in the
    shared k-mix cannot drift silently (the multi-hash output feeds the
    host-side numpy twins via the shared seed constants)."""
    w = np.ascontiguousarray(words, dtype=np.uint32)
    assert w.shape[-1] == 10
    with np.errstate(over="ignore"):
        def k_mix(col):
            k = col * _C1
            return ((k << np.uint32(15)) | (k >> np.uint32(17))) * _C2

        ks = [k_mix(w[..., i]) for i in range(10)]
        ks.append(k_mix(w[..., 8] & np.uint32(0xFFFF)))

        def run(seed: int, idxs: tuple[int, ...]) -> np.ndarray:
            h = np.full(w.shape[:-1], np.uint32(seed), np.uint32)
            for i in idxs:
                h = h ^ ks[i]
                h = ((h << np.uint32(13)) | (h >> np.uint32(19))) * _M5 + _N1
            h = h ^ np.uint32(len(idxs) * 4)
            h = h ^ (h >> np.uint32(16))
            h = h * _F1
            h = h ^ (h >> np.uint32(13))
            h = h * _F2
            return h ^ (h >> np.uint32(16))

        return {
            "h1": run(_H1_SEED, _FLOW_IDXS),
            "h2": run(_H2_SEED, _FLOW_IDXS) | np.uint32(1),
            "src_h1": run(_H1_SEED ^ SRC_BUCKET_SEED, _SRC_IDXS),
            "src_h2": run(_H2_SEED ^ SRC_BUCKET_SEED, _SRC_IDXS)
            | np.uint32(1),
            "dst_h1": run(_H1_SEED ^ DST_BUCKET_SEED, _DST_IDXS),
            "dp_h1": run(_H1_SEED ^ DSTPORT_FANOUT_SEED, _DP_IDXS),
            "dp_h2": run(_H2_SEED ^ DSTPORT_FANOUT_SEED, _DP_IDXS)
            | np.uint32(1),
            "src_sym": run(_H1_SEED ^ DST_BUCKET_SEED, _SRC_IDXS),
        }


def hash_words_np(words: np.ndarray, seed: int = 0) -> np.ndarray:
    """Pure-numpy twin of `hash_words` under `base_hashes`' h1 seeding —
    for HOST-side bucket lookups (e.g. mapping report suspect buckets back
    to heavy-hitter keys) without dispatching a device op (a wedged
    accelerator link must never stall report rendering). Equivalence-tested
    against the jax path."""
    w = np.ascontiguousarray(words, dtype=np.uint32)
    nwords = w.shape[-1]
    with np.errstate(over="ignore"):
        h = np.full(w.shape[:-1], np.uint32(0x9747B28C) ^ np.uint32(seed),
                    np.uint32)
        for i in range(nwords):
            k = w[..., i] * _C1
            k = ((k << np.uint32(15)) | (k >> np.uint32(17))) * _C2
            h = h ^ k
            h = ((h << np.uint32(13)) | (h >> np.uint32(19))) * _M5 + _N1
        h = h ^ np.uint32(nwords * 4)
        h = h ^ (h >> np.uint32(16))
        h = h * _F1
        h = h ^ (h >> np.uint32(13))
        h = h * _F2
        h = h ^ (h >> np.uint32(16))
    return h


def owner_shard_np(h1: np.ndarray, h2: np.ndarray,
                   n_shards: int) -> np.ndarray:
    """Pure-numpy twin of `ops.countmin.owner_shard`: which sketch shard of a
    width-sharded mesh owns each key identity — the host query surface
    (query/core.frequency_payload) picks the plane to index with it."""
    with np.errstate(over="ignore"):
        h = (np.asarray(h1, np.uint32)
             ^ (np.asarray(h2, np.uint32) * np.uint32(0x9E3779B1)))
        for shift, mult in ((16, _F1), (13, _F2)):     # fmix32
            h = (h ^ (h >> np.uint32(shift))) * mult
        h = h ^ (h >> np.uint32(16))
    return (h % np.uint32(n_shards)).astype(np.int32)


def tenant_of(words: jax.Array, n_tenants: int) -> jax.Array:
    """Tenant owner of each flow key: int32[...] in [0, n_tenants).

    Hashes the FULL key words under TENANT_SEED (h1 family), mod the tenant
    count — decorrelated from every sketch family, so tenant routing never
    biases bucket occupancy. `n_tenants` need not be a power of two."""
    h = hash_words(words, jnp.uint32(_H1_SEED) ^ jnp.uint32(TENANT_SEED))
    return (h % jnp.uint32(n_tenants)).astype(jnp.int32)


def tenant_of_np(words: np.ndarray, n_tenants: int) -> np.ndarray:
    """Pure-numpy twin of `tenant_of` — the HOST router (sketch/tenancy.py)
    assigns evicted rows with this; equivalence + golden vectors pinned by
    tests/test_tenancy.py (goldens run on the big-endian qemu tier)."""
    h = hash_words_np(words, TENANT_SEED)
    return (h % np.uint32(n_tenants)).astype(np.int32)


def row_indices(h1: jax.Array, h2: jax.Array, depth: int, width: int) -> jax.Array:
    """Kirsch–Mitzenmacher: index for row i is (h1 + i*h2) mod width.

    width must be a power of two. Returns uint32[depth, ...].
    """
    assert width & (width - 1) == 0, "width must be a power of two"
    rows = jnp.arange(depth, dtype=jnp.uint32).reshape((depth,) + (1,) * h1.ndim)
    return (h1[None] + rows * h2[None]) & jnp.uint32(width - 1)
