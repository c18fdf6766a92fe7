"""Sketch-kernel correctness vs exact CPU aggregation (the reference's
Accounter-style hashmap is the oracle — SURVEY.md §4 implication (b))."""

import numpy as np
import pytest

import tests.conftest  # noqa: F401 — force CPU platform before jax import
import jax
import jax.numpy as jnp

from netobserv_tpu.ops import countmin, ewma, hashing, hll, quantile, topk

KW = 10


def rand_keys(n, n_distinct, rng, zipf_a=0.0):
    """n key rows drawn from n_distinct distinct keys (optionally zipf-skewed).
    Returns (words[n, KW], ids[n])."""
    universe = rng.integers(0, 2**32, size=(n_distinct, KW), dtype=np.uint32)
    if zipf_a > 0:
        ranks = rng.zipf(zipf_a, size=n)
        ids = np.minimum(ranks - 1, n_distinct - 1).astype(np.int64)
    else:
        ids = rng.integers(0, n_distinct, size=n)
    return universe[ids], ids


class TestHashing:
    def test_deterministic_and_seeded(self):
        rng = np.random.default_rng(0)
        words = jnp.asarray(rng.integers(0, 2**32, (64, KW), dtype=np.uint32))
        a = hashing.hash_words(words, 7)
        b = hashing.hash_words(words, 7)
        c = hashing.hash_words(words, 8)
        assert np.array_equal(a, b)
        assert not np.array_equal(a, c)
        assert a.dtype == jnp.uint32

    def test_single_bit_avalanche(self):
        base = jnp.zeros((1, KW), dtype=jnp.uint32)
        flipped = base.at[0, 3].set(jnp.uint32(1))
        h0 = int(hashing.hash_words(base, 0)[0])
        h1 = int(hashing.hash_words(flipped, 0)[0])
        diff = bin(h0 ^ h1).count("1")
        assert 8 <= diff <= 24  # ~16 expected for a good mixer

    def test_uniformity(self):
        rng = np.random.default_rng(1)
        words = jnp.asarray(rng.integers(0, 2**32, (20000, KW), dtype=np.uint32))
        h = np.asarray(hashing.hash_words(words, 0))
        buckets = np.bincount(h % 64, minlength=64)
        # chi-square-ish sanity: all buckets within 25% of the mean
        assert buckets.min() > 20000 / 64 * 0.75
        assert buckets.max() < 20000 / 64 * 1.25

    def test_row_indices_distinct_rows(self):
        h1 = jnp.asarray([5], dtype=jnp.uint32)
        h2 = jnp.asarray([3], dtype=jnp.uint32)
        idx = hashing.row_indices(h1, h2, 4, 1 << 10)
        vals = [int(idx[i, 0]) for i in range(4)]
        assert vals == [(5 + i * 3) % 1024 for i in range(4)]


class TestCountMin:
    def test_never_underestimates_and_bounds(self):
        rng = np.random.default_rng(2)
        n, n_distinct = 4096, 300
        words, ids = rand_keys(n, n_distinct, rng)
        vals = rng.integers(1, 1000, size=n)
        exact = np.zeros(n_distinct)
        np.add.at(exact, ids, vals)

        cm = countmin.init(4, 1 << 12, jnp.float32)
        wj = jnp.asarray(words)
        h1, h2 = hashing.base_hashes(wj)
        cm = countmin.update(cm, h1, h2, jnp.asarray(vals, jnp.float32),
                             jnp.ones(n, jnp.bool_))
        # query each distinct key once
        uniq_words, uniq_idx = np.unique(ids, return_index=True)
        qw = jnp.asarray(words[uniq_idx])
        q1, q2 = hashing.base_hashes(qw)
        est = np.asarray(countmin.query(cm, q1, q2))
        truth = exact[uniq_words]
        assert np.all(est >= truth - 1e-3)  # CM never underestimates
        # error bound: eps = e/w with prob 1-e^-d; allow generous slack
        total = vals.sum()
        assert np.mean(est - truth) < 2.72 / (1 << 12) * total * 2

    def test_masked_rows_ignored(self):
        cm = countmin.init(2, 1 << 8, jnp.int32)
        words = jnp.asarray(np.arange(4 * KW, dtype=np.uint32).reshape(4, KW))
        h1, h2 = hashing.base_hashes(words)
        valid = jnp.asarray([True, False, True, False])
        cm = countmin.update(cm, h1, h2, jnp.full((4,), 10, jnp.int32), valid)
        assert int(countmin.total(cm)) == 20

    def test_merge_linear(self):
        rng = np.random.default_rng(3)
        words = jnp.asarray(rng.integers(0, 2**32, (16, KW), dtype=np.uint32))
        h1, h2 = hashing.base_hashes(words)
        v = jnp.ones((16,), jnp.float32)
        ok = jnp.ones((16,), jnp.bool_)
        a = countmin.update(countmin.init(2, 256), h1, h2, v, ok)
        b = countmin.update(countmin.init(2, 256), h1, h2, v * 2, ok)
        m = countmin.merge(a, b)
        est = countmin.query(m, h1, h2)
        assert np.all(np.asarray(est) >= 3.0)


class TestHLL:
    @pytest.mark.parametrize("true_card", [100, 5000, 200_000])
    def test_cardinality_error(self, true_card):
        rng = np.random.default_rng(4)
        words = rng.integers(0, 2**32, (true_card, 4), dtype=np.uint32)
        # feed each distinct key ~2x in shuffled order
        feed = np.concatenate([words, words[: true_card // 2]])
        rng.shuffle(feed)
        h = hll.init(precision=12)
        for start in range(0, len(feed), 65536):
            chunk = jnp.asarray(feed[start:start + 65536])
            h1, h2 = hashing.base_hashes(chunk)
            h = hll.update(h, h1, h2, jnp.ones(len(chunk), jnp.bool_))
        est = float(hll.estimate(h.regs))
        rel_err = abs(est - true_card) / true_card
        # theoretical std err = 1.04/sqrt(4096) ~ 1.6%; allow 4 sigma
        assert rel_err < 0.065, f"{est} vs {true_card}"

    def test_merge_max_equals_union(self):
        rng = np.random.default_rng(5)
        w1 = jnp.asarray(rng.integers(0, 2**32, (1000, 4), dtype=np.uint32))
        w2 = jnp.asarray(rng.integers(0, 2**32, (1000, 4), dtype=np.uint32))
        ones = jnp.ones(1000, jnp.bool_)
        a = hll.init(10)
        b = hll.init(10)
        h11, h12 = hashing.base_hashes(w1)
        h21, h22 = hashing.base_hashes(w2)
        a = hll.update(a, h11, h12, ones)
        b = hll.update(b, h21, h22, ones)
        both = hll.init(10)
        both = hll.update(both, h11, h12, ones)
        both = hll.update(both, h21, h22, ones)
        merged = hll.merge_regs(a.regs, b.regs)
        assert np.array_equal(np.asarray(merged), np.asarray(both.regs))

    def test_per_dst(self):
        rng = np.random.default_rng(6)
        n_dst = 8
        dsts = rng.integers(0, 2**32, (n_dst, 4), dtype=np.uint32)
        per_dst_srcs = [rng.integers(0, 2**32, (500 * (i + 1), 4), dtype=np.uint32)
                        for i in range(n_dst)]
        s = hll.init_per_dst(dst_buckets=256, precision=10)
        for i in range(n_dst):
            srcs = per_dst_srcs[i]
            drow = jnp.asarray(np.tile(dsts[i], (len(srcs), 1)))
            srow = jnp.asarray(srcs)
            dh, _ = hashing.base_hashes(drow, seed=1)
            sh1, sh2 = hashing.base_hashes(srow)
            s = hll.update_per_dst(s, dh, sh1, sh2,
                                   jnp.ones(len(srcs), jnp.bool_))
        ests = np.asarray(hll.estimate(s.regs))
        for i in range(n_dst):
            dh = int(hashing.base_hashes(jnp.asarray(dsts[i][None, :]), seed=1)[0][0])
            bucket = dh & 255
            true = 500 * (i + 1)
            assert abs(ests[bucket] - true) / true < 0.25  # small m -> coarse


class TestSlotTable:
    """The persistent-slot heavy-hitter plane (ISSUE 13): stable per-key
    identity across folds and rolls, churn metadata, and the roll-time
    merge graded against the exact-sort oracle."""

    def _stream(self, rng, n_keys, n, k=256, cm_width=1 << 14,
                zipf_a=1.3, batches=None):
        words_all, ids = rand_keys(n, n_keys, rng, zipf_a=zipf_a)
        vals = rng.integers(100, 1500, size=n)
        cm = countmin.init(4, cm_width, jnp.float32)
        table = topk.init_slots(k, KW)
        bs = 8192
        for s in range(0, n, bs):
            chunk = words_all[s:s + bs]
            pad = bs - len(chunk)
            wj = jnp.asarray(np.pad(chunk, ((0, pad), (0, 0))))
            vj = jnp.asarray(np.pad(vals[s:s + bs].astype(np.float32),
                                    (0, pad)))
            ok = jnp.asarray(np.pad(np.ones(len(chunk), bool), (0, pad)))
            h1, h2 = hashing.base_hashes(wj)
            cm = countmin.update(cm, h1, h2, vj, ok)
            table, _ = topk.slot_update(table, cm, wj, h1, h2, ok)
        exact = {}
        for i, v in zip(ids, vals):
            exact[i] = exact.get(i, 0) + int(v)
        return cm, table, words_all, ids, exact

    def test_recall_on_zipf(self):
        """Top-64 recall on a Zipf(1.3) stream of 5,000 keys holds the
        0.99 bar (BASELINE: recall loss < 1%)."""
        rng = np.random.default_rng(7)
        k = 64
        _cm, table, words, ids, exact = self._stream(rng, 5000, 50_000)
        true_top = set(sorted(exact, key=exact.get, reverse=True)[:k])
        counts = np.asarray(table.counts)
        tvalid = np.asarray(table.valid)
        order = np.argsort(-np.where(tvalid, counts, -1.0))[:k]
        got = {tuple(r) for r in np.asarray(table.words)[order]}
        true_words = {tuple(words[np.nonzero(ids == t)[0][0]])
                      for t in true_top}
        recall = len(got & true_words) / k
        assert recall >= 0.99, f"top-{k} recall {recall}"

    def test_dedup_within_batch(self):
        words = jnp.asarray(np.tile(
            np.arange(KW, dtype=np.uint32), (8, 1)))  # 8 copies of one key
        h1, h2 = hashing.base_hashes(words)
        cm = countmin.update(countmin.init(2, 256), h1, h2,
                             jnp.ones(8, jnp.float32), jnp.ones(8, jnp.bool_))
        t, evicted = topk.slot_update(topk.init_slots(4, KW), cm, words, h1,
                                      h2, jnp.ones(8, jnp.bool_))
        assert int(t.valid.sum()) == 1  # one key, one slot
        slot = int(np.argmax(np.asarray(t.valid)))
        assert float(t.counts[slot]) == pytest.approx(8.0)
        assert tuple(np.asarray(t.words[slot])) == tuple(range(KW))
        assert float(evicted) == 0.0

    def test_empty_batch_keeps_table_empty(self):
        cm = countmin.init(2, 256)
        words = jnp.zeros((4, KW), jnp.uint32)
        h1, h2 = hashing.base_hashes(words)
        t, _ = topk.slot_update(topk.init_slots(8, KW), cm, words, h1, h2,
                                jnp.zeros(4, jnp.bool_))
        assert int(t.valid.sum()) == 0
        assert int(t.epoch.sum()) == 0

    def test_identity_and_metadata_persist_across_rolls(self):
        """The tentpole property: a slot keeps its key, first_seen and
        epoch across a window roll; prev_counts snapshot the closed
        window; the incumbent defends with last window's mass."""
        rng = np.random.default_rng(9)
        cm, table, *_ = self._stream(rng, 100, 4000)
        pre_counts = np.asarray(table.counts).copy()
        rolled = topk.slot_roll(table, 0.0)
        np.testing.assert_array_equal(np.asarray(rolled.h1),
                                      np.asarray(table.h1))
        np.testing.assert_array_equal(np.asarray(rolled.words),
                                      np.asarray(table.words))
        np.testing.assert_array_equal(np.asarray(rolled.first_seen),
                                      np.asarray(table.first_seen))
        np.testing.assert_array_equal(np.asarray(rolled.epoch),
                                      np.asarray(table.epoch))
        np.testing.assert_array_equal(np.asarray(rolled.prev_counts),
                                      pre_counts)
        assert float(jnp.sum(rolled.counts)) == 0.0
        # keep/decay carries
        keep = topk.slot_roll(table, 1.0)
        np.testing.assert_array_equal(np.asarray(keep.counts), pre_counts)
        decay = topk.slot_roll(table, 0.5)
        np.testing.assert_allclose(np.asarray(decay.counts),
                                   pre_counts * 0.5)

    def test_new_key_needs_to_beat_the_defense(self):
        """A fresh window's challenger must out-mass the incumbent's
        counts + prev_counts — a persistent elephant is not evicted by
        the first mouse of the next window."""
        rng = np.random.default_rng(3)
        uni = rng.integers(0, 2**32, (2, KW), dtype=np.uint32)
        cm = countmin.init(2, 1 << 10)
        table = topk.init_slots(2, KW)  # K=2: maximal congestion
        elephant = jnp.asarray(uni[0][None])
        h1e, h2e = hashing.base_hashes(elephant)
        ok1 = jnp.ones(1, jnp.bool_)
        cm = countmin.update(cm, h1e, h2e,
                             jnp.full(1, 1000.0, jnp.float32), ok1)
        table, _ = topk.slot_update(table, cm, elephant, h1e, h2e, ok1)
        table = topk.slot_roll(table, 0.0)  # counts 0, prev 1000
        cm = countmin.init(2, 1 << 10)      # fresh window CM
        mouse = jnp.asarray(uni[1][None])
        h1m, h2m = hashing.base_hashes(mouse)
        cm = countmin.update(cm, h1m, h2m,
                             jnp.full(1, 10.0, jnp.float32), ok1)
        t2, ev = topk.slot_update(table, cm, mouse, h1m, h2m, ok1,
                                  window=1)
        # the elephant's slot survives: either the mouse found the other
        # slot (empty, defense -1) or lost the challenge — the elephant's
        # identity is still in the table with prev mass intact
        h1s = set(np.asarray(t2.h1)[np.asarray(t2.valid)].tolist())
        assert int(np.asarray(h1e)[0]) in h1s
        # and a true new elephant DOES take over a weak slot
        cm = countmin.update(cm, h1m, h2m,
                             jnp.full(1, 5000.0, jnp.float32), ok1)
        t3, _ = topk.slot_update(t2, cm, mouse, h1m, h2m, ok1, window=1)
        got = set(np.asarray(t3.h1)[np.asarray(t3.valid)].tolist())
        assert int(np.asarray(h1m)[0]) in got

    def test_merge_vs_exact_sort_within_cm_bounds(self):
        """Window-merge equivalence (ISSUE 13 satellite): merging two
        shards' slot tables against the merged CM recalls the exact-sort
        oracle's top hitters (CM estimates over-count within e/w * N, so
        the graded bar is recall of the true top set, not order), and the
        churn metadata merges per segment (prev SUM, first_seen MIN,
        epoch MAX)."""
        rng = np.random.default_rng(21)
        n, n_keys, k = 30_000, 2000, 128
        words_all, ids = rand_keys(n, n_keys, rng, zipf_a=1.3)
        vals = rng.integers(100, 1500, size=n)
        cms, tables = [], []
        for shard in range(2):
            cm = countmin.init(4, 1 << 14, jnp.float32)
            table = topk.init_slots(k, KW)
            sl = slice(shard * (n // 2), (shard + 1) * (n // 2))
            w, v = words_all[sl], vals[sl].astype(np.float32)
            bs = 8192
            for s in range(0, len(w), bs):
                pad = bs - len(w[s:s + bs])
                wj = jnp.asarray(np.pad(w[s:s + bs], ((0, pad), (0, 0))))
                vj = jnp.asarray(np.pad(v[s:s + bs], (0, pad)))
                ok = jnp.asarray(np.pad(np.ones(len(w[s:s + bs]), bool),
                                        (0, pad)))
                h1, h2 = hashing.base_hashes(wj)
                cm = countmin.update(cm, h1, h2, vj, ok)
                table, _ = topk.slot_update(table, cm, wj, h1, h2, ok)
            cms.append(cm)
            tables.append(topk.slot_roll(table, 1.0))  # prev = counts
        cm_merged = countmin.merge(*cms)
        stacked = jax.tree.map(lambda a, b: jnp.concatenate([a, b], 0),
                               tables[0], tables[1])
        merged = topk.merge_slot_tables(stacked, cm_merged, k)
        # recall vs the exact oracle, top-32
        exact = {}
        for i, v in zip(ids, vals):
            exact[i] = exact.get(i, 0) + int(v)
        top = 32
        true_top = set(sorted(exact, key=exact.get, reverse=True)[:top])
        counts = np.asarray(merged.counts)
        order = np.argsort(-np.where(np.asarray(merged.valid), counts,
                                     -1.0))[:top]
        got = {tuple(r) for r in np.asarray(merged.words)[order]}
        true_words = {tuple(words_all[np.nonzero(ids == t)[0][0]])
                      for t in true_top}
        assert len(got & true_words) / top >= 0.95
        # counts are re-scored against the merged CM: never below truth
        # for the true-top keys we recalled (CM never underestimates)
        lookup = {tuple(words_all[np.nonzero(ids == t)[0][0]]):
                  exact[t] for t in true_top}
        for i in order:
            key = tuple(np.asarray(merged.words)[i])
            if key in lookup:
                assert counts[i] >= lookup[key] * 0.999
        # metadata: duplicated identities sum their prev partials
        both = {}
        for t in tables:
            h1s = np.asarray(t.h1)
            pv = np.asarray(t.prev_counts)
            va = np.asarray(t.valid)
            for i in range(len(va)):
                if va[i]:
                    both[int(h1s[i])] = both.get(int(h1s[i]), 0.0) \
                        + float(pv[i])
        mh1 = np.asarray(merged.h1)
        mpv = np.asarray(merged.prev_counts)
        mva = np.asarray(merged.valid)
        for i in range(len(mva)):
            if mva[i]:
                assert mpv[i] == pytest.approx(both[int(mh1[i])])

    def test_eviction_counter_counts_replacements(self):
        rng = np.random.default_rng(17)
        uni = rng.integers(0, 2**32, (64, KW), dtype=np.uint32)
        cm = countmin.init(2, 1 << 10)
        table = topk.init_slots(4, KW)  # tiny table: constant pressure
        total = 0.0
        for it in range(4):
            wj = jnp.asarray(uni[rng.integers(0, 64, 128)])
            h1, h2 = hashing.base_hashes(wj)
            vj = jnp.asarray(
                rng.integers(100, 10_000, 128).astype(np.float32))
            ok = jnp.ones(128, jnp.bool_)
            cm = countmin.update(cm, h1, h2, vj, ok)
            table, ev = topk.slot_update(table, cm, wj, h1, h2, ok,
                                         window=it)
            total += float(ev)
        assert total > 0  # 64 keys through 4 slots MUST churn
        assert int(np.asarray(table.valid).sum()) == 4


class TestQuantile:
    def test_relative_error(self):
        rng = np.random.default_rng(8)
        samples = rng.lognormal(mean=8, sigma=1.5, size=40_000).astype(np.int32)
        h = quantile.init(1024)
        for s in range(0, len(samples), 8192):
            chunk = jnp.asarray(samples[s:s + 8192])
            h = quantile.update(h, chunk, jnp.ones(len(chunk), jnp.bool_))
        qs = np.array([0.5, 0.9, 0.99], dtype=np.float32)
        est = np.asarray(quantile.quantile(h, jnp.asarray(qs)))
        truth = np.quantile(samples, qs)
        rel = np.abs(est - truth) / truth
        assert np.all(rel < 0.06), f"{est} vs {truth}"

    def test_empty_histogram_quantiles_are_zero(self):
        h = quantile.init(128)
        est = np.asarray(quantile.quantile(h, jnp.asarray([0.5, 0.99])))
        assert np.all(est == 0.0)

    def test_small_bucket_count_still_covers_range(self):
        # gamma_for widens spacing so 5000us doesn't saturate 64 buckets
        g = quantile.gamma_for(64)
        h = quantile.init(64)
        h = quantile.update(h, jnp.full(100, 5000, jnp.int32),
                            jnp.ones(100, jnp.bool_), gamma=g)
        est = float(quantile.quantile(h, jnp.asarray([0.5]), gamma=g)[0])
        assert abs(est - 5000) / 5000 < 0.5  # coarse buckets, right ballpark

    def test_zero_bucket(self):
        h = quantile.init(64)
        h = quantile.update(h, jnp.zeros(10, jnp.int32), jnp.ones(10, jnp.bool_))
        assert int(h.counts[0]) == 10


class TestEWMA:
    def test_spike_detection(self):
        s = ewma.init(256)
        dsts = jnp.asarray(np.arange(16, dtype=np.uint32))
        ok = jnp.ones(16, jnp.bool_)
        # 5 calm windows of rate ~100
        rng = np.random.default_rng(9)
        for _ in range(5):
            vals = jnp.asarray(rng.normal(100, 5, 16).astype(np.float32))
            s = ewma.accumulate(s, dsts, vals, ok)
            s, z = ewma.roll(s, alpha=0.3)
            assert not bool(ewma.suspects(z).any())
        # attack window: dst 3 gets 100x
        vals = np.full(16, 100.0, np.float32)
        vals[3] = 10_000.0
        s = ewma.accumulate(s, dsts, jnp.asarray(vals), ok)
        s, z = ewma.roll(s, alpha=0.3)
        sus = np.asarray(ewma.suspects(z))
        bucket3 = int(np.asarray(dsts)[3]) & 255
        assert sus[bucket3]
        assert sus.sum() == 1


def test_port_scan_fanout_detection():
    """Per-source fan-out grid (beyond-reference analytics): a scanner
    touching thousands of distinct (dst, port) pairs must light up its
    source bucket's fan-out estimate and surface in the window report's
    PortScanSuspectBuckets; normal clients must not."""
    import numpy as np

    from netobserv_tpu.exporter.tpu_sketch import report_to_json
    from netobserv_tpu.model.columnar import pack_key_words
    from netobserv_tpu.sketch import state as sk

    rng = np.random.default_rng(5)
    cfg = sk.SketchConfig(cm_width=1 << 12, topk=64, persrc_buckets=256,
                          persrc_precision=6)
    state = sk.init_state(cfg)
    ingest = jax.jit(sk.ingest)

    def batch(keys):
        n = len(keys)
        return {
            "keys": keys, "bytes": np.full(n, 100.0, np.float32),
            "packets": np.ones(n, np.int32),
            "rtt_us": np.zeros(n, np.int32),
            "dns_latency_us": np.zeros(n, np.int32),
            "sampling": np.zeros(n, np.int32),
            "valid": np.ones(n, np.bool_),
        }

    import netobserv_tpu.model.binfmt as binfmt

    def keys_for(src_last, dsts_ports):
        arr = np.zeros(len(dsts_ports), dtype=binfmt.FLOW_KEY_DTYPE)
        for i, (dst_last, port) in enumerate(dsts_ports):
            arr[i]["src_ip"][10:12] = 0xFF
            arr[i]["src_ip"][12:] = [10, 0, 0, src_last]
            arr[i]["dst_ip"][10:12] = 0xFF
            arr[i]["dst_ip"][12:] = [10, 0, dst_last % 250 + 1, dst_last // 250]
            arr[i]["src_port"] = 40000
            arr[i]["dst_port"] = port
            arr[i]["proto"] = 6
        return pack_key_words(arr)

    # the scanner: one source sweeping 2000 distinct (dst, port) pairs
    scan_pairs = [(i % 500, 1 + i % 4096) for i in range(2000)]
    state = ingest(state, batch(keys_for(7, scan_pairs)))
    # normal clients: 50 sources, 4 (dst, port) pairs each
    for s in range(50):
        state = ingest(state, batch(keys_for(100 + s % 100,
                                             [(s, 443), (s, 80),
                                              (s + 1, 443), (s + 2, 53)])))
    _, report = sk.roll_window(state, cfg)
    fanout = np.asarray(report.per_src_fanout)
    top = float(np.max(fanout))
    assert top > 1000, f"scanner fan-out estimate too low: {top}"
    # only the scanner's bucket is anywhere near it
    assert np.sort(fanout)[-2] < top / 10
    obj = report_to_json(report)
    assert obj["PortScanSuspectBuckets"], "scanner not reported"
    assert obj["PortScanSuspectBuckets"][0]["distinct_dst_port_pairs"] > 1000


def test_fanout_counts_initiators_not_responders():
    """The fan-out grid's direction gate: initiator flows count whether the
    handshake completed or not (lone-SYN AND full-connect scans fire), but
    RESPONDER flows (the SYN_ACK composite) never do — a server answering
    one NAT'd client churning source ports must not look like a scanner
    (the nat_churn zoo scenario end-to-ends this)."""
    import numpy as np

    from netobserv_tpu.model.columnar import pack_key_words
    from netobserv_tpu.model.flow import TcpFlags, classify_tcp_flags
    from netobserv_tpu.sketch import state as sk
    import netobserv_tpu.model.binfmt as binfmt

    cfg = sk.SketchConfig(cm_width=1 << 10, topk=16, persrc_buckets=256,
                          persrc_precision=6, hll_precision=6,
                          perdst_buckets=32, perdst_precision=4,
                          hist_buckets=64, ewma_buckets=32)
    ingest = jax.jit(sk.ingest, static_argnames=())

    def keys(src_last, pairs):
        arr = np.zeros(len(pairs), dtype=binfmt.FLOW_KEY_DTYPE)
        for i, (dst_last, port) in enumerate(pairs):
            arr[i]["src_ip"][12:] = [10, 0, 0, src_last]
            arr[i]["dst_ip"][12:] = [10, 0, dst_last % 250 + 1, 1]
            arr[i]["src_port"], arr[i]["dst_port"] = 40000, port
            arr[i]["proto"] = 6
        return pack_key_words(arr)

    def batch(kw, flags_val):
        n = len(kw)
        return {"keys": kw, "bytes": np.full(n, 100.0, np.float32),
                "packets": np.ones(n, np.int32),
                "rtt_us": np.zeros(n, np.int32),
                "dns_latency_us": np.zeros(n, np.int32),
                "sampling": np.zeros(n, np.int32),
                "valid": np.ones(n, np.bool_),
                "tcp_flags": np.full(n, flags_val, np.int32)}

    pairs = [(i % 200, 1 + i) for i in range(1500)]
    # flags OR-accumulate across PER-PACKET classifications: a client sends
    # SYN (0x02) then ACK/PSH in separate packets — the SYN_ACK composite
    # never sets; the responder's single SYN+ACK packet sets it
    full_connect = int(TcpFlags.SYN | TcpFlags.ACK | TcpFlags.PSH)
    responder = classify_tcp_flags(int(TcpFlags.SYN | TcpFlags.ACK))
    # full-connect scanner: handshake completed — must still fire
    s1 = ingest(sk.init_state(cfg), batch(keys(7, pairs), full_connect))
    _, rep1 = sk.roll_window(s1, cfg)
    assert float(np.max(np.asarray(rep1.per_src_fanout))) > 1000
    # responder sweeping the same pair count (the NAT-churn server shape):
    # must stay dark
    s2 = ingest(sk.init_state(cfg), batch(keys(9, pairs), responder))
    _, rep2 = sk.roll_window(s2, cfg)
    assert float(np.max(np.asarray(rep2.per_src_fanout))) == 0.0


def test_ddos_z_threshold_configurable():
    """The DDoS suspect cut is the SKETCH_DDOS_Z knob, not a hardcoded 6.0
    (VERDICT r3 weak #4): the same report yields different suspect sets at
    different thresholds."""
    import numpy as np

    from netobserv_tpu.exporter.tpu_sketch import report_to_json
    from netobserv_tpu.ops import topk
    from netobserv_tpu.sketch.state import WindowReport

    z = np.array([0.0, 5.0, 7.0], np.float32)
    zero3 = np.zeros(3, np.float32)
    report = WindowReport(
        heavy=topk.init_slots(4), distinct_src=np.float32(0),
        per_dst_cardinality=np.zeros(4, np.float32),
        per_src_fanout=np.zeros(4, np.float32),
        rtt_quantiles_us=np.zeros(5, np.float32),
        dns_quantiles_us=np.zeros(5, np.float32), ddos_z=z,
        syn_z=zero3, syn_rate=zero3, synack_rate=zero3, drop_z=zero3,
        drop_causes=np.zeros(128, np.float32),
        dscp_bytes=np.zeros(64, np.float32),
        conv_fwd=zero3, conv_rev=zero3,
        total_records=np.float32(0), total_bytes=np.float32(0),
        total_drop_bytes=np.float32(0), total_drop_packets=np.float32(0),
        quic_records=np.float32(0), nat_records=np.float32(0),
        heavy_evictions=np.float32(0),
        window=np.int32(1))
    default = report_to_json(report)
    assert [s["bucket"] for s in default["DdosSuspectBuckets"]] == [2]
    low = report_to_json(report, ddos_z_threshold=4.5)
    # worst-z first (severity order survives the [:32] truncation)
    assert [s["bucket"] for s in low["DdosSuspectBuckets"]] == [2, 1]


def test_drop_cause_names_in_report(monkeypatch):
    """DropCauseNames maps kernel reason IDs through the LIVE kernel's
    tracepoint symbol table (the reference's static table mislabels on
    newer kernels — utils/drop_reasons.py), with the histogram's overflow
    bucket labeled explicitly."""
    import numpy as np

    from netobserv_tpu.utils import drop_reasons
    from netobserv_tpu.exporter.tpu_sketch import report_to_json
    from netobserv_tpu.ops import topk
    from netobserv_tpu.sketch.state import N_DROP_CAUSES, WindowReport

    monkeypatch.setattr(drop_reasons, "live_drop_reasons",
                        lambda: {6: "SKB_DROP_REASON_SOCKET_RCVBUFF"})

    causes = np.zeros(N_DROP_CAUSES, np.float32)
    causes[6] = 12.0                 # SKB_DROP_REASON_SOCKET_RCVBUFF
    causes[N_DROP_CAUSES - 1] = 3.0  # saturated subsystem reasons
    zero = np.zeros(4, np.float32)
    report = WindowReport(
        heavy=topk.init_slots(4), distinct_src=np.float32(0),
        per_dst_cardinality=zero, per_src_fanout=zero,
        rtt_quantiles_us=np.zeros(5, np.float32),
        dns_quantiles_us=np.zeros(5, np.float32),
        ddos_z=zero, syn_z=zero, syn_rate=zero, synack_rate=zero,
        drop_z=zero, drop_causes=causes,
        dscp_bytes=np.zeros(64, np.float32),
        conv_fwd=zero, conv_rev=zero,
        total_records=np.float32(0), total_bytes=np.float32(0),
        total_drop_bytes=np.float32(0), total_drop_packets=np.float32(0),
        quic_records=np.float32(0), nat_records=np.float32(0),
        heavy_evictions=np.float32(0),
        window=np.int32(0))
    obj = report_to_json(report)
    assert obj["DropCauseNames"]["SKB_DROP_REASON_SOCKET_RCVBUFF"] == 12.0
    assert obj["DropCauseNames"]["OTHER_OR_SUBSYSTEM"] == 3.0
    assert obj["DropCauses"] == {"6": 12.0, str(N_DROP_CAUSES - 1): 3.0}


def test_drop_reason_name_fallback_to_parity_table(monkeypatch):
    """Without tracefs (no root / locked down) the name lookup falls back
    to the reference-parity FLP table; unknown ids print numerically."""
    from netobserv_tpu.utils import drop_reasons

    monkeypatch.setattr(drop_reasons, "live_drop_reasons", lambda: {})
    assert drop_reasons.drop_reason_name(2) == "SKB_DROP_REASON_NOT_SPECIFIED"
    assert drop_reasons.drop_reason_name(64000) == "64000"


def test_dscp_class_names_in_report():
    """DscpClassBytes labels QoS codepoints with their RFC names (EF, CSx,
    AFxy); unnamed codepoints stay numeric."""
    import numpy as np

    from netobserv_tpu.exporter.tpu_sketch import report_to_json
    from netobserv_tpu.ops import topk
    from netobserv_tpu.sketch.state import N_DROP_CAUSES, WindowReport

    dscp = np.zeros(64, np.float32)
    dscp[46] = 10.0   # EF
    dscp[0] = 5.0     # CS0 (best effort)
    dscp[10] = 2.0    # AF11
    dscp[3] = 1.0     # unnamed
    zero = np.zeros(4, np.float32)
    report = WindowReport(
        heavy=topk.init_slots(4), distinct_src=np.float32(0),
        per_dst_cardinality=zero, per_src_fanout=zero,
        rtt_quantiles_us=np.zeros(5, np.float32),
        dns_quantiles_us=np.zeros(5, np.float32),
        ddos_z=zero, syn_z=zero, syn_rate=zero, synack_rate=zero,
        drop_z=zero, drop_causes=np.zeros(N_DROP_CAUSES, np.float32),
        dscp_bytes=dscp, conv_fwd=zero, conv_rev=zero,
        total_records=np.float32(0), total_bytes=np.float32(0),
        total_drop_bytes=np.float32(0), total_drop_packets=np.float32(0),
        quic_records=np.float32(0), nat_records=np.float32(0),
        heavy_evictions=np.float32(0),
        window=np.int32(0))
    obj = report_to_json(report)
    assert obj["DscpClassBytes"] == {
        "EF": 10.0, "CS0": 5.0, "AF11": 2.0, "3": 1.0}


def test_hash_words_np_twin_matches_jax():
    """The host-side numpy hash twin must equal base_hashes' h1 for every
    seed the report path uses (bucket mapping would silently misattribute
    victims otherwise)."""
    from netobserv_tpu.ops.hashing import base_hashes, hash_words_np

    rng = np.random.default_rng(12)
    w = rng.integers(0, 2**32, (256, 4), dtype=np.uint32)
    for seed in (0, 0x0517, 0x0D57, 0x5CA7):
        a = np.asarray(base_hashes(jnp.asarray(w), seed=seed)[0])
        np.testing.assert_array_equal(a, hash_words_np(w, seed=seed))


def test_ddos_suspects_carry_probable_victims():
    """A DDoS suspect bucket names the heavy-hitter destination(s) that hash
    into it — the operator's bridge from bucket ids to concrete victims."""
    import numpy as np

    from netobserv_tpu.exporter.tpu_sketch import report_to_json
    from netobserv_tpu.model.columnar import pack_key_words
    from netobserv_tpu.sketch import state as sk
    import netobserv_tpu.model.binfmt as binfmt
    from netobserv_tpu.ops.hashing import hash_words_np

    cfg = sk.SketchConfig(cm_width=1 << 12, topk=16, ewma_buckets=64)
    state = sk.init_state(cfg)
    n = 64
    arr = np.zeros(n, dtype=binfmt.FLOW_KEY_DTYPE)
    for i in range(n):
        arr[i]["src_ip"][10:12] = 0xFF
        arr[i]["src_ip"][12:] = [10, 0, 0, i % 250 + 1]
        arr[i]["dst_ip"][10:12] = 0xFF
        arr[i]["dst_ip"][12:] = [10, 9, 9, 9]   # one victim
        arr[i]["src_port"] = 30000 + i
        arr[i]["dst_port"] = 80
        arr[i]["proto"] = 6
    kw = pack_key_words(arr)
    arrays = {
        "keys": kw, "bytes": np.full(n, 1e6, np.float32),
        "packets": np.ones(n, np.int32), "rtt_us": np.zeros(n, np.int32),
        "dns_latency_us": np.zeros(n, np.int32),
        "sampling": np.zeros(n, np.int32), "valid": np.ones(n, np.bool_),
    }
    ingest = jax.jit(sk.ingest)
    # two calm baseline windows, then the surge window
    for scale in (1e-3, 1e-3, 1.0):
        scaled = dict(arrays, bytes=arrays["bytes"] * scale)
        state = ingest(state, scaled)
        state, report = sk.roll_window(state, cfg)
    obj = report_to_json(report)
    assert obj["DdosSuspectBuckets"], "surge not flagged"
    from netobserv_tpu.ops.hashing import DST_BUCKET_SEED
    vb = int(hash_words_np(kw[:1, 4:8], seed=DST_BUCKET_SEED)[0] & 63)
    hit = [s for s in obj["DdosSuspectBuckets"] if s["bucket"] == vb]
    assert hit and "10.9.9.9" in hit[0]["probable_victims"]


def test_keep_state_roll_resets_synack_with_its_ewma():
    """roll_window(reset_sketches=False) must zero synack alongside the syn
    EWMA rate — the flood ratio pairs a per-window numerator with a
    per-window denominator in EVERY roll mode."""
    import numpy as np

    from netobserv_tpu.sketch import state as sk

    cfg = sk.SketchConfig(cm_width=1 << 10, topk=16, ewma_buckets=32)
    n = 8
    arrays = {
        "keys": np.random.default_rng(1).integers(
            0, 2**32, (n, 10)).astype(np.uint32),
        "bytes": np.full(n, 10.0, np.float32),
        "packets": np.ones(n, np.int32),
        "rtt_us": np.zeros(n, np.int32),
        "dns_latency_us": np.zeros(n, np.int32),
        "sampling": np.zeros(n, np.int32),
        "valid": np.ones(n, np.bool_),
        "tcp_flags": np.full(n, 0x112, np.int32),  # SYN-ACK responses
        "dscp": np.zeros(n, np.int32),
        "drop_bytes": np.zeros(n, np.int32),
        "drop_packets": np.zeros(n, np.int32),
        "drop_cause": np.zeros(n, np.int32),
    }
    s = sk.ingest(sk.init_state(cfg), arrays)
    assert float(np.asarray(s.synack).sum()) == n
    for kwargs in ({"reset_sketches": True}, {"reset_sketches": False},
                   {"decay_factor": 0.5}):
        rolled, _ = sk.roll_window(s, cfg, **kwargs)
        assert float(np.asarray(rolled.synack).sum()) == 0.0, kwargs
        assert float(np.asarray(rolled.syn.rate).sum()) == 0.0, kwargs
