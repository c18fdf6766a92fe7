"""The plain reference of a width-sharded (owner-sharded) Count-Min deployment
(not a test file): straightforward numpy, independent of `netobserv_tpu/ops/`
and `netobserv_tpu/sketch/` — its murmur3 is written out here from the
algorithm, in 64-bit integers masked to 32 bits, and only the published seed
constants are shared with the code under test.

From (key words, bytes, packets) it builds what such a deployment must hold
and answer:

- `planes`: the `shards` local-width Count-Min planes `[shards, depth,
  width / shards]` — every key folds its WHOLE depth into the plane of the
  one shard that owns it, at index `(h1 + row * h2) mod (width / shards)`;
- `estimate`: the point query of one key (the minimum over its owner
  plane's depth rows);
- `exact_sums` / `heavy_hitters`: the exact per-key sums and their head.

Sums are float64; the device's planes are float32, so the two are EQUAL only
while every counter stays an integer under 2^24 (the tests feed such bytes).
"""

from __future__ import annotations

import numpy as np

M32 = np.uint64(0xFFFFFFFF)
#: the flow family's two murmur3 seeds (h1 / h2) and the ownership hash's
#: multiplier: the constants `ops/hashing.py` and `ops/countmin.py` publish
H1_SEED, H2_SEED = 0x9747B28C, 0x5BD1E995
OWNER_MULT = 0x9E3779B1


def _rotl(x: np.ndarray, r: int) -> np.ndarray:
    return ((x << np.uint64(r)) | (x >> np.uint64(32 - r))) & M32


def _fmix(h: np.ndarray) -> np.ndarray:
    h = h ^ (h >> np.uint64(16))
    h = (h * np.uint64(0x85EBCA6B)) & M32
    h = h ^ (h >> np.uint64(13))
    h = (h * np.uint64(0xC2B2AE35)) & M32
    return h ^ (h >> np.uint64(16))


def murmur3_words(words: np.ndarray, seed: int) -> np.ndarray:
    """MurmurHash3 x86_32 of each row of u32 words (the body's 4-byte blocks
    are the words themselves; no tail), as uint64 holding 32 bits."""
    w = np.asarray(words, np.uint32).astype(np.uint64)
    h = np.full(w.shape[0], seed, np.uint64)
    for i in range(w.shape[1]):
        k = (w[:, i] * np.uint64(0xCC9E2D51)) & M32
        k = (_rotl(k, 15) * np.uint64(0x1B873593)) & M32
        h = _rotl(h ^ k, 13)
        h = (h * np.uint64(5) + np.uint64(0xE6546B64)) & M32
    return _fmix(h ^ np.uint64(4 * w.shape[1]))


def flow_hashes(words: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(h1, h2) of each key; h2 is forced odd (a stride that generates the
    power-of-two width)."""
    return (murmur3_words(words, H1_SEED),
            murmur3_words(words, H2_SEED) | np.uint64(1))


def owner(h1: np.ndarray, h2: np.ndarray, shards: int) -> np.ndarray:
    """The shard that owns each key identity."""
    return (_fmix(h1 ^ ((h2 * np.uint64(OWNER_MULT)) & M32))
            % np.uint64(shards)).astype(np.int64)


def columns(h1: np.ndarray, h2: np.ndarray, depth: int,
            local_width: int) -> np.ndarray:
    """[depth, n] column of each key in each depth row of its owner plane."""
    rows = np.arange(depth, dtype=np.uint64)[:, None]
    return (((h1[None] + rows * h2[None]) & M32)
            & np.uint64(local_width - 1)).astype(np.int64)


def planes(words: np.ndarray, values: np.ndarray, depth: int, width: int,
           shards: int) -> np.ndarray:
    """float64[shards, depth, width / shards] after folding every record."""
    h1, h2 = flow_hashes(words)
    local = width // shards
    out = np.zeros((shards, depth, local), np.float64)
    who, cols = owner(h1, h2, shards), columns(h1, h2, depth, local)
    for r in range(depth):
        np.add.at(out, (who, r, cols[r]), np.asarray(values, np.float64))
    return out


def estimate(sharded: np.ndarray, words: np.ndarray) -> np.ndarray:
    """The point query of each key against `planes`' result."""
    h1, h2 = flow_hashes(words)
    shards, depth, local = sharded.shape
    who, cols = owner(h1, h2, shards), columns(h1, h2, depth, local)
    return np.min(sharded[who[None], np.arange(depth)[:, None], cols], axis=0)


def exact_sums(words: np.ndarray, values: np.ndarray
               ) -> tuple[np.ndarray, np.ndarray]:
    """(distinct key words, their exact sums), heaviest first; ties keep the
    order of first appearance."""
    w = np.ascontiguousarray(words, np.uint32)
    as_rows = w.view((np.void, w.dtype.itemsize * w.shape[1])).reshape(-1)
    _, first, inv = np.unique(as_rows, return_index=True, return_inverse=True)
    sums = np.bincount(inv.reshape(-1),
                       weights=np.asarray(values, np.float64))
    by_arrival = np.argsort(first, kind="stable")
    order = by_arrival[np.argsort(-sums[by_arrival], kind="stable")]
    return w[first[order]], sums[order]


def heavy_hitters(words: np.ndarray, values: np.ndarray, n: int
                  ) -> tuple[np.ndarray, np.ndarray]:
    keys, sums = exact_sums(words, values)
    return keys[:n], sums[:n]
