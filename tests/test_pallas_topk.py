"""The persistent-slot two-form invariant: the fused Pallas reduction
(`ops/pallas/topk_kernel.py`) must be BIT-EXACT against the un-fused
scatter form for the slot-table maintenance — the same contract the
sibling kernels pin (tests/test_pallas_signal.py, countmin). The preamble
(`slot_prepare`) and tail (`slot_compose`) are literally shared code, so
the pin covers the three per-slot reductions and the whole-update
composition, across ragged batch sizes, duplicate keys, capacity
pressure, and multi-batch streams."""

from __future__ import annotations

import numpy as np
import pytest

import tests.conftest  # noqa: F401  (forces the CPU backend)

import jax
import jax.numpy as jnp

from netobserv_tpu.ops import countmin, hashing, topk
from netobserv_tpu.ops.pallas import topk_kernel

KW = 10


def _batch(rng, universe, n):
    ranks = rng.integers(0, len(universe), n)
    words = jnp.asarray(universe[ranks])
    vals = jnp.asarray(rng.integers(64, 9000, n).astype(np.float32))
    valid = jnp.asarray(rng.random(n) < 0.9)
    return words, vals, valid


def _assert_tables_equal(a: topk.SlotTable, b: topk.SlotTable):
    for name in topk.SlotTable._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(a, name)), np.asarray(getattr(b, name)),
            err_msg=name)


@pytest.mark.parametrize("k,n_keys,b", [
    # one geometry in tier-1 (the invariant stays pinned per PR); the
    # pressure/ragged variants ride the slow tier — tier-1 wall budget
    (128, 64, 512),       # no pressure, lots of duplicates
    pytest.param(128, 1000, 1000, marks=pytest.mark.slow),  # pressure
    pytest.param(256, 300, 777, marks=pytest.mark.slow),    # ragged
])
def test_fused_reductions_bit_exact_vs_scatter(k, n_keys, b):
    rng = np.random.default_rng(k + n_keys)
    universe = rng.integers(0, 2**32, (n_keys, KW), dtype=np.uint32)
    cm = countmin.init(4, 1 << 12)
    t_s = t_p = topk.init_slots(k, KW)
    for it in range(4):
        words, vals, valid = _batch(rng, universe, b)
        h1, h2 = hashing.base_hashes(words)
        cm = countmin.update(cm, h1, h2, vals, valid)
        t_s, ev_s = topk.slot_update(t_s, cm, words, h1, h2, valid,
                                     window=it, use_pallas=False)
        t_p, ev_p = topk.slot_update(t_p, cm, words, h1, h2, valid,
                                     window=it, use_pallas=True)
        _assert_tables_equal(t_s, t_p)
        assert float(ev_s) == float(ev_p)
        if it == 1:  # roll mid-stream: persistence is part of the pin
            t_s, t_p = topk.slot_roll(t_s, 0.0), topk.slot_roll(t_p, 0.0)


def test_raw_reductions_match_on_adversarial_rows():
    """Drive the reduction pair directly with hand-built (mslot, target,
    est) rows: duplicate challengers on one slot (max-then-min-row
    tie-break), dead rows, inactive rows, and a ragged length that forces
    kernel padding."""
    k = 128
    n = topk_kernel.CHUNK_B + 37       # ragged => padded tail
    rng = np.random.default_rng(5)
    mslot = rng.integers(0, k + 1, n).astype(np.int32)
    target = rng.integers(0, k + 1, n).astype(np.int32)
    est = rng.integers(0, 500, n).astype(np.float32)
    est[rng.random(n) < 0.2] = -1.0     # dead rows
    # force exact ties competing for one slot: min row index must win
    # (slot 7 first cleared of random challengers so the tie is the max)
    target[target == 7] = 8
    target[10] = target[40] = target[90] = 7
    est[10] = est[40] = est[90] = 333.0
    s = topk._slot_reduce_scatter(jnp.asarray(mslot), jnp.asarray(target),
                                  jnp.asarray(est), k)
    p = topk_kernel.reduce(jnp.asarray(mslot), jnp.asarray(target),
                           jnp.asarray(est), k)
    for name, a, b in zip(("match_max", "chall_max", "win_row"), s, p):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)
    # the tie itself: slot 7's winner is the LOWEST competing row
    assert int(np.asarray(p[2])[7]) == 10


def test_eligibility_gate():
    assert topk_kernel.eligible(128) and topk_kernel.eligible(1024)
    assert not topk_kernel.eligible(100)


def test_full_ingest_heavy_plane_bit_exact_fused_vs_unfused():
    """The production seam: `sketch.state.ingest` with use_pallas=True
    routes the slot maintenance through the kernel (plus the sibling CM/
    HLL/signal kernels) — its heavy table must be bit-exact against the
    all-scatter ingest. Geometry chosen kernel-eligible for every sibling
    (width % 512, lanes % 128)."""
    from netobserv_tpu.sketch import state as sk

    cfg = sk.SketchConfig(cm_width=1 << 12, topk=128, persrc_buckets=256,
                          perdst_buckets=256, ewma_buckets=512)
    rng = np.random.default_rng(11)
    universe = rng.integers(0, 2**32, (400, KW), dtype=np.uint32)
    s_f, s_u = sk.init_state(cfg), sk.init_state(cfg)
    for _ in range(3):
        n = 512
        arrays = {
            "keys": jnp.asarray(universe[rng.integers(0, 400, n)]),
            "bytes": jnp.asarray(
                rng.integers(1, 1000, n).astype(np.float32)),
            "packets": jnp.asarray(rng.integers(1, 5, n).astype(np.int32)),
            "rtt_us": jnp.zeros(n, jnp.int32),
            "dns_latency_us": jnp.zeros(n, jnp.int32),
            "sampling": jnp.zeros(n, jnp.int32),
            "valid": jnp.ones(n, jnp.bool_),
        }
        s_f = sk.ingest(s_f, arrays, use_pallas=True)
        s_u = sk.ingest(s_u, arrays, use_pallas=False)
    _assert_tables_equal(s_f.heavy, s_u.heavy)
    assert float(s_f.heavy_evictions) == float(s_u.heavy_evictions)


def test_zero_postwarmup_retraces_across_folds_and_rolls():
    """Slot maintenance lives inside the watched ingest/roll executables:
    a stream of folds, rolls and refresh-style re-rolls must compile each
    entry exactly once (the fixed-shape invariant — counted through the
    retrace.jit wrappers the factories return)."""
    from netobserv_tpu.sketch import state as sk

    cfg = sk.SketchConfig(cm_width=1 << 10, topk=64, persrc_buckets=64,
                          perdst_buckets=64, ewma_buckets=128)
    ing = sk.make_ingest_fn(donate=False, name="topk_t_ingest")
    roll = sk.make_roll_fn(cfg, with_tables=True, name="topk_t_roll")
    rng = np.random.default_rng(3)
    universe = rng.integers(0, 2**32, (100, KW), dtype=np.uint32)
    s = sk.init_state(cfg)
    for w in range(3):
        for _ in range(2):
            n = 256
            s = ing(s, {
                "keys": jnp.asarray(universe[rng.integers(0, 100, n)]),
                "bytes": jnp.asarray(
                    rng.integers(1, 1000, n).astype(np.float32)),
                "packets": jnp.ones(n, jnp.int32),
                "rtt_us": jnp.zeros(n, jnp.int32),
                "dns_latency_us": jnp.zeros(n, jnp.int32),
                "sampling": jnp.zeros(n, jnp.int32),
                "valid": jnp.ones(n, jnp.bool_),
            })
        s, _rep, _tables = roll(s)
    jax.block_until_ready(s.heavy.counts)
    assert ing.retraces == 0 and roll.retraces == 0
    assert ing.calls == 6 and roll.calls == 3
