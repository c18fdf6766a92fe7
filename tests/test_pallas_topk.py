"""The persistent-slot two-form invariant: the Pallas batch walk
(`ops/pallas/topk_kernel.py`: classification AND reductions inside the
kernel, the table in VMEM) must be BIT-EXACT against the gather/scatter
form (`slot_prepare` + `_slot_reduce_scatter`) — the same contract the
sibling kernels pin (tests/test_pallas_signal.py, countmin). Only the tail
(`slot_compose`) is shared code, so the pin covers the three per-slot
reductions of every round on hand-built tables (empty, rolled, a key
outside its candidates, one identity in two candidates, dead and padded
rows, two new keys on one slot) and the table after every round, across
ragged batch sizes, duplicate keys, capacity pressure, multi-batch
streams and `jax.vmap` (the tenant stack)."""

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


GEOMETRIES = [
    # one geometry in tier-1 per test; the pressure/ragged variants ride
    # the slow tier — tier-1 wall budget
    (128, 64, 512),
    pytest.param(128, 1000, 1000, marks=pytest.mark.slow),
    pytest.param(256, 300, 777, marks=pytest.mark.slow),
]


@pytest.mark.parametrize("k,n_keys,b", GEOMETRIES)
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


def _reductions_both_forms(table, h1, h2, est):
    """(gather/scatter reductions, walk reductions), asserted bit-equal."""
    k = table.k
    ref = topk._slot_reduce_scatter(*topk.slot_prepare(table, h1, h2, est),
                                    est, k)
    got = topk_kernel.walk(table, *topk_kernel.pack_rows(h1, h2, est, k))
    for name, a, b in zip(("match_max", "chall_max", "win_row"), ref, got):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)
    return ref


@pytest.mark.parametrize("k,n_keys,b", GEOMETRIES)
def test_walk_bit_exact_vs_gather_scatter_every_round(k, n_keys, b):
    """Round by round, as `slot_update` runs them: the walk's reductions
    equal the gather/scatter form's on the table the previous round left,
    and so does the table `slot_compose` makes of them."""
    rng = np.random.default_rng(k * 7 + n_keys)
    universe = rng.integers(0, 2**32, (n_keys, KW), dtype=np.uint32)
    cm = countmin.init(4, 1 << 12)
    table = topk.init_slots(k, KW)
    for it in range(3):
        words, vals, valid = _batch(rng, universe, b)
        h1, h2 = hashing.base_hashes(words)
        cm = countmin.update(cm, h1, h2, vals, valid)
        est = jnp.where(valid, countmin.query(cm, h1, h2), -1.0)
        for _ in range(topk.SLOT_ROUNDS):
            ref = _reductions_both_forms(table, h1, h2, est)
            table, _ = topk.slot_compose(table, *ref, words, h1, h2, it)
        if it == 0:
            table = topk.slot_roll(table, 0.0)
    assert int(np.asarray(table.valid).sum()) >= min(k, n_keys) // 2


def _keys(rng, n):
    words = jnp.asarray(rng.integers(0, 2**32, (n, KW), dtype=np.uint32))
    return (words, *hashing.base_hashes(words))


def _filled_table(rng, k, n_keys=300, b=600):
    """A table some folds old, with its Count-Min and key universe."""
    universe = rng.integers(0, 2**32, (n_keys, KW), dtype=np.uint32)
    cm = countmin.init(4, 1 << 12)
    table = topk.init_slots(k, KW)
    for it in range(2):
        words, vals, valid = _batch(rng, universe, b)
        h1, h2 = hashing.base_hashes(words)
        cm = countmin.update(cm, h1, h2, vals, valid)
        table, _ = topk.slot_update(table, cm, words, h1, h2, valid,
                                    window=it)
    return table, cm, universe


def _place(table, slot, h1, h2, count):
    """`table` with identity (h1, h2) resident in `slot`."""
    return table._replace(
        h1=table.h1.at[slot].set(h1), h2=table.h2.at[slot].set(h2),
        counts=table.counts.at[slot].set(count),
        valid=table.valid.at[slot].set(True))


def test_walk_empty_table_ties_go_to_the_lowest_way():
    """Every slot of an empty table defends with -1, so every live row's
    eight candidates tie: the target is way 0's slot, NOT the lowest slot
    number among the candidates."""
    k = 128
    rng = np.random.default_rng(21)
    _, h1, h2 = _keys(rng, 300)
    est = jnp.asarray(rng.integers(1, 500, 300).astype(np.float32))
    table = topk.init_slots(k, KW)
    _, chall_max, win_row = _reductions_both_forms(table, h1, h2, est)
    cands = np.asarray(topk.slot_candidates(h1, h2, k))
    assert (cands[:, 0] != cands.min(axis=1)).any()   # the two rules differ
    want = np.full(k, -1.0, np.float32)
    np.maximum.at(want, cands[:, 0], np.asarray(est))
    np.testing.assert_array_equal(np.asarray(chall_max), want)
    assert int((np.asarray(win_row) != topk.NO_WINNER).sum()) == int(
        (want > -1).sum())


def test_walk_right_after_a_roll():
    """`slot_roll(carry=0)`: counts 0, prev_counts > 0 — occupants defend
    with last window's mass, resident keys match and refresh, new keys
    must beat the previous window's counts."""
    k = 128
    rng = np.random.default_rng(22)
    table, cm, universe = _filled_table(rng, k)
    table = topk.slot_roll(table, 0.0)
    assert float(table.counts.max()) == 0.0 < float(table.prev_counts.max())
    words = jnp.asarray(np.concatenate([
        universe[rng.integers(0, len(universe), 200)],
        rng.integers(0, 2**32, (200, KW), dtype=np.uint32)]))
    h1, h2 = hashing.base_hashes(words)
    est = jnp.asarray(rng.integers(1, 60000, 400).astype(np.float32))
    match_max, chall_max, _ = _reductions_both_forms(table, h1, h2, est)
    assert (np.asarray(match_max) > 0).any()
    assert (np.asarray(chall_max) > 0).any()


def test_walk_key_outside_its_candidates_does_not_match():
    """`merge_slot_tables` places keys by rank, so a table can hold a key
    in a slot that is none of its candidates: both forms leave that slot
    alone and let the row challenge its weakest candidate."""
    k = 128
    rng = np.random.default_rng(23)
    _, h1, h2 = _keys(rng, 1)
    cands = np.asarray(topk.slot_candidates(h1, h2, k))[0]
    outside = next(s for s in range(k) if s not in cands)
    table = _place(topk.init_slots(k, KW), outside, h1[0], h2[0], 50.0)
    est = jnp.asarray([70.0], jnp.float32)
    match_max, chall_max, win_row = _reductions_both_forms(table, h1, h2,
                                                           est)
    assert float(match_max[outside]) == -1.0
    assert float(chall_max[cands[0]]) == 70.0 and int(win_row[cands[0]]) == 0
    # and the same identity INSIDE the candidates does match
    table = _place(topk.init_slots(k, KW), int(cands[3]), h1[0], h2[0], 50.0)
    match_max, chall_max, _ = _reductions_both_forms(table, h1, h2, est)
    assert float(match_max[cands[3]]) == 70.0
    assert float(chall_max.max()) == -1.0


def test_walk_identity_in_two_candidates_matches_the_lowest_way():
    """No fold produces it, but the contract is any table: one identity in
    two of a row's candidates refreshes the lower WAY only."""
    k = 128
    rng = np.random.default_rng(24)
    _, h1, h2 = _keys(rng, 1)
    cands = np.asarray(topk.slot_candidates(h1, h2, k))[0]
    table = topk.init_slots(k, KW)
    for way in (5, 2):
        table = _place(table, int(cands[way]), h1[0], h2[0], 10.0)
    match_max, _, _ = _reductions_both_forms(
        table, h1, h2, jnp.asarray([33.0], jnp.float32))
    assert float(match_max[cands[2]]) == 33.0
    assert float(match_max[cands[5]]) == -1.0


def test_walk_dead_and_padded_rows_and_a_ragged_batch():
    """b is no multiple of CHUNK_B (the kernel pads with dead rows); dead
    rows (est -1: invalid) and zero estimates neither match nor challenge,
    resident or not."""
    k = 128
    rng = np.random.default_rng(25)
    table, cm, universe = _filled_table(rng, k)
    n = 2 * topk_kernel.CHUNK_B + 37
    words = jnp.asarray(universe[rng.integers(0, len(universe), n)])
    h1, h2 = hashing.base_hashes(words)
    est = np.asarray(countmin.query(cm, h1, h2)).copy()
    dead = rng.random(n) < 0.3
    est[dead] = -1.0
    est[~dead & (rng.random(n) < 0.2)] = 0.0
    live_only = np.where(est > 0, est, -1.0)
    ref = _reductions_both_forms(table, h1, h2, jnp.asarray(est))
    ref_live = _reductions_both_forms(table, h1, h2, jnp.asarray(live_only))
    for a, b in zip(ref[:2], ref_live[:2]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert (np.asarray(ref[0]) > 0).any()


def test_walk_two_new_keys_on_one_slot_elect_max_then_lowest_row():
    """Several new keys whose weakest candidate is the same slot: the
    highest estimate wins, an exact tie goes to the LOWEST row."""
    k = 128
    rng = np.random.default_rng(26)
    _, h1, h2 = _keys(rng, 4 * topk_kernel.CHUNK_B)   # spans walk chunks
    first = np.asarray(topk.slot_candidates(h1, h2, k))[:, 0]
    slot = np.bincount(first).argmax()
    rows = np.flatnonzero(first == slot)
    assert len(rows) >= 3
    est = np.full(len(first), 5.0, np.float32)
    est[rows[1]] = est[rows[2]] = 900.0           # tie between two rows
    _, chall_max, win_row = _reductions_both_forms(
        topk.init_slots(k, KW), h1, h2, jnp.asarray(est))
    assert float(chall_max[slot]) == 900.0
    assert int(win_row[slot]) == rows[1]


def test_walk_under_vmap_matches_per_tenant_walks():
    """The tenant stack vmaps the whole ingest: the kernel must batch, and
    each tenant's reductions equal its own un-batched walk's."""
    k, n, tenants = 128, 300, 3
    rng = np.random.default_rng(27)
    tables, ids, ests, want = [], [], [], []
    for _ in range(tenants):
        table, cm, universe = _filled_table(rng, k)
        words = jnp.asarray(np.concatenate([
            universe[rng.integers(0, len(universe), n // 2)],
            rng.integers(0, 2**32, (n - n // 2, KW), dtype=np.uint32)]))
        h1, h2 = hashing.base_hashes(words)
        est = jnp.where(jnp.asarray(rng.random(n) < 0.9),
                        countmin.query(cm, h1, h2) + 1.0, -1.0)
        want.append(_reductions_both_forms(table, h1, h2, est))
        i, e = topk_kernel.pack_rows(h1, h2, est, k)
        tables.append(table), ids.append(i), ests.append(e)
    got = jax.vmap(topk_kernel.walk)(
        jax.tree.map(lambda *x: jnp.stack(x), *tables), jnp.stack(ids),
        jnp.stack(ests))
    for t in range(tenants):
        for a, b in zip(want[t], got):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b[t]))


def test_eligibility_gate():
    assert topk_kernel.eligible(128) and topk_kernel.eligible(1024)
    assert not topk_kernel.eligible(100)


@pytest.mark.parametrize("n", [512, 300])
def test_full_ingest_heavy_plane_bit_exact_fused_vs_unfused(n):
    """The production seam: `sketch.state.ingest` with use_pallas=True
    classifies and reduces the slot maintenance inside the walk kernel
    (plus the sibling CM/HLL/signal kernels) — its heavy table must be
    bit-exact against the all-scatter ingest after every fold, across a
    roll, at a batch that is (512) and is not (300) whole walk chunks.
    Geometry chosen kernel-eligible for every sibling (width % 512,
    lanes % 128)."""
    from netobserv_tpu.sketch import state as sk

    cfg = sk.SketchConfig(cm_width=1 << 12, topk=128, persrc_buckets=256,
                          perdst_buckets=256, ewma_buckets=512)
    rng = np.random.default_rng(11)
    universe = rng.integers(0, 2**32, (400, KW), dtype=np.uint32)
    s_f, s_u = sk.init_state(cfg), sk.init_state(cfg)
    roll = sk.make_roll_fn(cfg, name="topk_t_full_roll")
    for it in range(3):
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
        if it == 0:
            s_f, s_u = roll(s_f)[0], roll(s_u)[0]
    # the fused ingest holds the walk, one a round, and no per-(row, way)
    # lookup of a table field: the gather form's [n, WAYS] results are gone
    jaxpr = str(jax.make_jaxpr(
        lambda s, a: sk.ingest(s, a, use_pallas=True))(s_f, arrays))
    assert jaxpr.count("topk_slot_walk") == topk.SLOT_ROUNDS
    assert f"[{n},{topk.SLOT_WAYS}]" not in jaxpr


@pytest.mark.parametrize("slots,use_pallas", [(64, None), (128, True)],
                         ids=["gather-form", "walk-form"])
def test_zero_postwarmup_retraces_across_folds_and_rolls(slots, use_pallas):
    """Slot maintenance lives inside the watched ingest/roll executables:
    a stream of folds, rolls and refresh-style re-rolls must compile each
    entry exactly once (the fixed-shape invariant — counted through the
    retrace.jit wrappers the factories return), in the gather form (K not
    lane-aligned) and with the walk kernel inside the executable."""
    from netobserv_tpu.sketch import state as sk

    cfg = sk.SketchConfig(cm_width=1 << 10, topk=slots, persrc_buckets=64,
                          perdst_buckets=64, ewma_buckets=128)
    ing = sk.make_ingest_fn(donate=False, use_pallas=use_pallas,
                            name=f"topk_t_ingest_{slots}")
    roll = sk.make_roll_fn(cfg, with_tables=True,
                           name=f"topk_t_roll_{slots}")
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
