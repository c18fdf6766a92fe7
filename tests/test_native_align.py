"""The eviction's align half as one native call (flowpack.align_eviction,
flowpack.cc fp_align_eviction) against its numpy twin (loader._align_numpy:
`_join_keys` plus the numpy scatter), which stays the fallback and the
oracle. Every comparison is bit for bit: the events (orphan rows and their
first/last-seen times included), every aligned feature array, and the
orphan count (`fallback_rows`).

Also pinned here: the stale-library fallback, the
eviction_align_native_rows_total counter, and a time bound on the two
inputs a careless join walks quadratically (one key everywhere, every key
distinct).
"""

from __future__ import annotations

import queue
import time

import numpy as np
import pytest

from netobserv_tpu.datapath import flowpack, loader
from netobserv_tpu.datapath.fetcher import FakeFetcher
from netobserv_tpu.model import binfmt

pytestmark = pytest.mark.skipif(not flowpack.build_native(),
                                reason="native flowpack build unavailable")

FEATURES = {"extra": binfmt.EXTRA_REC_DTYPE, "dns": binfmt.DNS_REC_DTYPE,
            "drops": binfmt.DROPS_REC_DTYPE,
            "nevents": binfmt.NEVENTS_REC_DTYPE,
            "xlat": binfmt.XLAT_REC_DTYPE, "quic": binfmt.QUIC_REC_DTYPE}


def _keys(n: int, rng, pool: int = 0) -> np.ndarray:
    """(n, 40) u8 keys; with `pool` > 0 drawn from that many distinct keys
    (duplicates across drain chunks)."""
    if pool:
        uniq = rng.integers(0, 256, (pool, 40), dtype=np.uint8)
        return np.ascontiguousarray(uniq[rng.integers(0, pool, n)])
    return rng.integers(0, 256, (n, 40), dtype=np.uint8)


def _records(n: int, dtype, rng) -> np.ndarray:
    """Merged feature records of random bytes; a tenth of the first-seen
    times 0 (the orphan-time sentinel)."""
    recs = rng.integers(0, 256, (n, dtype.itemsize),
                        dtype=np.uint8).view(dtype).reshape(n)
    recs["first_seen_ns"][rng.random(n) < 0.1] = 0
    return recs


def _stats(n: int, rng) -> np.ndarray:
    return _records(n, binfmt.FLOW_STATS_DTYPE, rng)


def _both(agg_keys, agg_stats, feats: dict):
    """Run the native and the numpy align on one drain and require the
    same bytes; returns the native result."""
    drained = {a: (k, None) for a, (k, _r) in feats.items()}
    merged = {a: r for a, (_k, r) in feats.items()}
    nat = flowpack.align_eviction(
        agg_keys, agg_stats, [(k, r) for k, r in feats.values()])
    assert nat is not None, "native align refused a valid drain"
    events, outs, n_app, lex = nat
    ref_events, ref_feats, ref_app = loader._align_numpy(
        agg_keys, agg_stats.reshape(-1, 1), drained, merged)
    assert n_app == ref_app
    assert events.dtype == ref_events.dtype
    assert events.tobytes() == ref_events.tobytes()
    for attr, out in zip(feats, outs):
        ref = ref_feats[attr]
        assert (out is None) == (ref is None), attr
        if out is not None:
            assert out.dtype == ref.dtype and out.shape == ref.shape, attr
            assert out.tobytes() == ref.tobytes(), attr
    return events, dict(zip(feats, outs)), n_app, lex


class TestEquivalence:
    def test_empty_drain(self):
        rng = np.random.default_rng(1)
        ev, feats, n_app, _ = _both(
            np.empty((0, 40), np.uint8), _stats(0, rng),
            {"extra": (np.empty((0, 40), np.uint8),
                       _records(0, binfmt.EXTRA_REC_DTYPE, rng))})
        assert len(ev) == 0 and feats["extra"] is None and n_app == 0

    def test_aggregate_rows_only(self):
        rng = np.random.default_rng(2)
        keys = _keys(500, rng, pool=300)   # duplicates stay rows of their own
        ev, _feats, n_app, _ = _both(keys, _stats(500, rng), {})
        assert len(ev) == 500 and n_app == 0

    def test_feature_rows_only_all_orphans(self):
        rng = np.random.default_rng(3)
        fk = _keys(40, rng, pool=25)
        dk = np.ascontiguousarray(fk[::3])
        ev, feats, n_app, _ = _both(
            np.empty((0, 40), np.uint8), _stats(0, rng),
            {"extra": (fk, _records(40, binfmt.EXTRA_REC_DTYPE, rng)),
             "dns": (dk, _records(len(dk), binfmt.DNS_REC_DTYPE, rng))})
        n_uniq = len(np.unique(fk.view("V40")))
        assert n_app == n_uniq == len(ev)
        assert feats["extra"] is not None and feats["dns"] is not None

    def test_duplicate_aggregate_keys_last_wins(self):
        rng = np.random.default_rng(4)
        agg = _keys(8, rng)
        dup = np.ascontiguousarray(np.concatenate([agg[:1], agg, agg[3:4]]))
        fk = np.ascontiguousarray(np.concatenate([agg[:1], agg[3:4]]))
        recs = _records(2, binfmt.EXTRA_REC_DTYPE, rng)
        _ev, feats, n_app, _ = _both(dup, _stats(10, rng),
                                     {"extra": (fk, recs)})
        assert n_app == 0
        # key 0 at rows 0 and 1, key 3 at rows 4 and 9: the last row holds
        got = feats["extra"]
        assert got[1].tobytes() == recs[0].tobytes()
        assert got[9].tobytes() == recs[1].tobytes()
        assert not got[0].tobytes().strip(b"\0")
        assert not got[4].tobytes().strip(b"\0")

    def test_duplicate_feature_rows_last_record_wins(self):
        rng = np.random.default_rng(5)
        agg = _keys(6, rng)
        orphan = _keys(1, rng)
        fk = np.ascontiguousarray(
            np.concatenate([agg[2:3], agg[2:3], orphan, orphan]))
        recs = _records(4, binfmt.DROPS_REC_DTYPE, rng)
        ev, feats, n_app, _ = _both(agg, _stats(6, rng),
                                    {"drops": (fk, recs)})
        assert n_app == 1 and len(ev) == 7
        assert feats["drops"][2].tobytes() == recs[1].tobytes()
        assert feats["drops"][6].tobytes() == recs[3].tobytes()

    def test_every_feature_map_eight_cpus(self):
        """Every feature map present, merged from 8 per-CPU partials by the
        product's own merge, with orphans shared between maps."""
        rng = np.random.default_rng(6)
        n = 3000
        agg = _keys(n, rng, pool=2000)
        orphans = _keys(50, rng)
        pool = np.concatenate([agg, orphans])
        feats = {}
        for i, (attr, dtype) in enumerate(FEATURES.items()):
            m = 200 + 150 * i
            fk = np.ascontiguousarray(pool[rng.integers(0, len(pool), m)])
            parts = rng.integers(0, 256, (m, 8 * dtype.itemsize),
                                 dtype=np.uint8).view(dtype)
            feats[attr] = (fk, flowpack.merge_percpu_batch(attr, parts))
        _ev, out, n_app, _ = _both(agg, _stats(n, rng), feats)
        assert n_app > 0
        assert all(out[a] is not None for a in FEATURES)

    def test_forced_hash_collision_takes_the_lexicographic_order(self):
        """Two distinct keys on one 64-bit hash (solved in closed form:
        every hash round is invertible) trip the lexicographic fallback on
        both sides, and the orphan order follows it."""
        MASK = (1 << 64) - 1
        C, M = 0xC2B2AE3D27D4EB4F, 0x9E3779B97F4A7C15
        C_INV, M_INV = pow(C, -1, 1 << 64), pow(M, -1, 1 << 64)

        def rounds(words, upto):
            h = words[0]
            for i in range(1, upto):
                h = ((h ^ (words[i] * C & MASK)) * M) & MASK
                h ^= h >> 29
            return h

        def unshift29(y):
            x = y
            for _ in range(3):
                x = y ^ (x >> 29)
            return x

        rng = np.random.default_rng(7)
        a = [int(x) for x in rng.integers(0, 1 << 63, size=5)]
        prefix = [int(x) for x in rng.integers(0, 1 << 63, size=4)]
        h4 = unshift29(rounds(a, 5))
        b = prefix + [((((h4 * M_INV) & MASK) ^ rounds(prefix, 4))
                       * C_INV) & MASK]
        assert rounds(a, 5) == rounds(b, 5) and a != b
        key_a = np.frombuffer(np.array(a, "<u8").tobytes(), np.uint8)
        key_b = np.frombuffer(np.array(b, "<u8").tobytes(), np.uint8)
        agg = np.ascontiguousarray(np.vstack([_keys(20, rng), key_a]))
        # both colliding keys as feature rows: key_a matches, key_b orphans,
        # beside other orphans whose order the fallback decides
        fk = np.ascontiguousarray(np.vstack([key_b, _keys(5, rng), key_a,
                                             key_b]))
        _ev, _f, n_app, lex = _both(
            agg, _stats(21, rng),
            {"extra": (fk, _records(8, binfmt.EXTRA_REC_DTYPE, rng))})
        assert lex == 1 and n_app == 6

    @pytest.mark.parametrize("n_agg,n_feat,pool", [
        (0, 1, 0), (1, 0, 0), (1, 1, 1), (17, 40, 30), (1000, 700, 800),
        (50_000, 20_000, 30_000), (120_000, 80_000, 0),
        (150_000, 50_000, 40_000)])
    def test_fuzz_sizes(self, n_agg, n_feat, pool):
        """Sizes 0..200,000 rows: distinct keys, a Zipf-like pool, orphans,
        several maps, empty maps among them."""
        rng = np.random.default_rng(n_agg * 7 + n_feat)
        agg = _keys(n_agg, rng, pool=pool if pool else 0)
        extra_keys = _keys(max(n_feat // 10, 1), rng)
        src = np.concatenate([agg, extra_keys]) if n_agg else extra_keys
        feats = {}
        split = [n_feat // 2, n_feat // 4, 0, n_feat - n_feat // 2
                 - n_feat // 4]
        for (attr, dtype), m in zip(FEATURES.items(), split):
            fk = np.ascontiguousarray(src[rng.integers(0, len(src), m)])
            feats[attr] = (fk, _records(m, dtype, rng))
        ev, _f, _n, _lex = _both(agg, _stats(n_agg, rng), feats)
        assert len(ev) >= n_agg


class TestDecodeEvictionPath:
    def _drain(self, rng):
        agg = _keys(300, rng, pool=200)
        orphans = _keys(4, rng)
        fk = np.ascontiguousarray(np.concatenate([agg[:50], orphans]))
        parts = np.zeros((54, 8), binfmt.EXTRA_REC_DTYPE)
        parts["rtt_ns"] = rng.integers(1, 10**6, (54, 8))
        parts["first_seen_ns"] = rng.integers(1, 10**9, (54, 8))
        parts["last_seen_ns"] = rng.integers(10**9, 2 * 10**9, (54, 8))
        stats = _stats(300, rng).reshape(-1, 1)
        return agg, stats, {"extra": (fk, parts)}

    def test_stale_library_falls_back_to_numpy(self, tmp_path, monkeypatch):
        """An old-ABI library is refused at load; decode_eviction then runs
        the numpy align with the same bytes and the same orphan count, and
        counts no native rows."""
        rng = np.random.default_rng(8)
        agg, stats, drained = self._drain(rng)
        want = loader.decode_eviction(agg, stats, drained)
        assert want.decode_stats["align_native_rows"] == len(want)
        stale = str(tmp_path / "libflowpack_stale.so")
        assert flowpack.build_native(force=True, out=stale,
                                     abi=flowpack._ABI_VERSION - 1)
        monkeypatch.setattr(flowpack, "_LIB_PATHS", [stale])
        monkeypatch.setattr(flowpack, "_lib", None)
        monkeypatch.setattr(flowpack, "abi_fallbacks", 0)
        got = loader.decode_eviction(agg, stats, drained)
        assert not flowpack.native_available() and flowpack.abi_fallbacks
        assert got.decode_stats == {
            "fallback_rows": want.decode_stats["fallback_rows"],
            "align_native_rows": 0}
        assert got.decode_stats["fallback_rows"] == 4
        assert got.events.tobytes() == want.events.tobytes()
        assert got.extra.tobytes() == want.extra.tobytes()

    def test_native_rows_counter_counts_the_aligned_rows(self):
        """eviction_align_native_rows_total grows by exactly the event rows
        the native align produced, drain by drain; a numpy-aligned drain
        adds nothing."""
        from netobserv_tpu.flow.map_tracer import MapTracer
        from netobserv_tpu.metrics.registry import Metrics

        rng = np.random.default_rng(9)
        metrics = Metrics()
        fetcher = FakeFetcher()
        tracer = MapTracer(fetcher, queue.Queue(), metrics=metrics,
                           columnar=True)
        name = "ebpf_agent_eviction_align_native_rows_total"
        sizes = []
        for _ in range(3):
            ev = loader.decode_eviction(*self._drain(rng))
            sizes.append(len(ev))
            fetcher.inject_eviction(ev)
            tracer._evict_once()
        assert metrics.registry.get_sample_value(name) == sum(sizes)
        numpy_ev = loader.EvictedFlows(np.zeros(5, binfmt.FLOW_EVENT_DTYPE))
        numpy_ev.decode_stats = {"fallback_rows": 0, "align_native_rows": 0}
        fetcher.inject_eviction(numpy_ev)
        tracer._evict_once()
        assert metrics.registry.get_sample_value(name) == sum(sizes)


class TestTermination:
    """The native join is bounded by its input: the two shapes a careless
    walk or sort takes quadratically finish well inside a CPU bound."""

    @pytest.mark.parametrize("shape", ["one_key", "distinct"])
    def test_200k_rows_finish(self, shape):
        rng = np.random.default_rng(10)
        n_agg, n_feat = 120_000, 80_000
        if shape == "one_key":
            key = _keys(1, rng)
            agg = np.ascontiguousarray(np.repeat(key, n_agg, axis=0))
            fk = np.ascontiguousarray(np.repeat(key, n_feat, axis=0))
        else:
            allk = _keys(n_agg + n_feat, rng)
            agg, fk = allk[:n_agg], np.ascontiguousarray(allk[n_agg:])
        recs = _records(n_feat, binfmt.EXTRA_REC_DTYPE, rng)
        t0 = time.perf_counter()
        out = flowpack.align_eviction(agg, _stats(n_agg, rng), [(fk, recs)])
        elapsed = time.perf_counter() - t0
        assert out is not None
        events, (aligned,), n_app, _lex = out
        assert elapsed < 5.0, f"{shape}: {elapsed:.2f} s"
        if shape == "one_key":
            # every feature row on the last agg row, the last record there
            assert n_app == 0 and len(events) == n_agg
            assert aligned[n_agg - 1].tobytes() == recs[-1].tobytes()
        else:
            assert n_app == n_feat and len(events) == n_agg + n_feat
