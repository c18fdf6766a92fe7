"""Fused evict→fold host stream (ISSUE 11), drain-lane half: parallel
per-feature-map drain→merge lanes, the native FLOW_EVENT interleave, the
lane-sharded batch merge, and — most load-bearing — the PER-LANE zero-copy
view lifetime rule, all exercised WITHOUT bpffs (synthetic maps whose
drain buffers are reused exactly like BpfMap._batch_bufs). The live-kernel
twin of the aliasing pin lives in tests/test_bpfman.py.

What is pinned:

- a BpfmanFetcher draining through worker lanes produces BIT-IDENTICAL
  EvictedFlows to the sequential drain over the same map contents — the
  lanes change scheduling, never merge or alignment semantics;
- each lane's views alias only its OWN map's cached buffers, and every
  view is copied out before lookup_and_delete returns: redraining (or
  scribbling) every map afterwards never mutates an earlier EvictedFlows;
- a view held PAST its lane's next drain IS caught aliasing (the hazard
  the copy boundary exists for — the test proves the fake reproduces it);
- flowpack.events_from_keys_stats (native interleave) == the binfmt numpy
  twin, tail rows and empty drains included;
- merge_percpu_batch(threads=N, out=) row-sharded lanes == the one-call
  merge == the columnar numpy twin;
- EVICT_DRAIN_LANES resolution (0 = auto capped by cores/maps,
  1 = sequential, N capped by the feature-map count).
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from netobserv_tpu.datapath import flowpack, loader
from netobserv_tpu.model import binfmt
from netobserv_tpu.utils import tracing


@pytest.fixture(scope="module")
def native():
    if not flowpack.build_native():
        pytest.skip("no g++ toolchain for the native packer")
    assert flowpack.native_available()
    return True


def _keys_u8(n, rng, port_base=0):
    k = np.zeros(n, binfmt.FLOW_KEY_DTYPE)
    k["src_ip"] = rng.integers(0, 256, (n, 16))
    k["dst_ip"] = rng.integers(0, 256, (n, 16))
    k["src_port"] = (port_base + np.arange(n)) & 0xFFFF
    k["proto"] = 6
    return np.frombuffer(k.tobytes(), np.uint8).reshape(n, 40).copy()


class LaneMap:
    """Synthetic BpfMap twin with the REAL zero-copy drain contract: one
    persistent (key, value) buffer pair per map, `load()` rewrites it, and
    drain_batched_arrays returns VIEWS into it — exactly the
    `_batch_bufs` reuse that makes view lifetime a hazard."""

    def __init__(self, key_size: int, value_itemsize: int, n_cpus: int,
                 capacity: int = 4096):
        self.key_size = key_size
        self.n_cpus = n_cpus
        self._pad_vs = value_itemsize
        self._kbuf = np.zeros((capacity, key_size), np.uint8)
        self._vbuf = np.zeros((capacity, value_itemsize * n_cpus), np.uint8)
        self._n = 0
        self.drains = 0

    def load(self, keys_u8: np.ndarray, vals: np.ndarray) -> None:
        n = len(keys_u8)
        self._kbuf[:n] = keys_u8
        self._vbuf[:n] = np.ascontiguousarray(vals).view(np.uint8).reshape(
            n, -1)
        self._n = n

    def scribble(self) -> None:
        """Simulate the next drain rewriting the cached buffers."""
        self._kbuf[:] = 0xAA
        self._vbuf[:] = 0xBB

    def drain_batched_arrays(self):
        self.drains += 1
        n = self._n
        return self._kbuf[:n], self._vbuf[:n]

    def close(self):
        pass


def _synth_drain(rng, n_flows=300, n_cpus=4):
    """(agg_keys, agg_vals, features) with orphan feature keys and a
    live-traffic lane mix (extra everywhere, dns sparse, drops sparse)."""
    agg_keys = _keys_u8(n_flows, rng)
    agg_vals = np.zeros((n_flows, 1), binfmt.FLOW_STATS_DTYPE)
    s = agg_vals[:, 0]
    s["bytes"] = rng.integers(64, 10**6, n_flows)
    s["packets"] = rng.integers(1, 500, n_flows)
    s["first_seen_ns"] = rng.integers(1, 10**9, n_flows)
    s["last_seen_ns"] = s["first_seen_ns"] + rng.integers(1, 10**8, n_flows)

    def percpu(dtype, m, fill):
        v = np.zeros((m, n_cpus), dtype)
        fill(v)
        v["first_seen_ns"] = rng.integers(1, 10**9, (m, n_cpus))
        v["last_seen_ns"] = rng.integers(10**9, 2 * 10**9, (m, n_cpus))
        return v

    orph = _keys_u8(max(n_flows // 50, 1), rng, port_base=1 << 15)
    ex_keys = np.concatenate([agg_keys, orph])
    extra = percpu(binfmt.EXTRA_REC_DTYPE, len(ex_keys),
                   lambda v: v.__setitem__(
                       "rtt_ns", rng.integers(0, 10**7, v["rtt_ns"].shape)))
    n_dns = max(n_flows // 20, 1)
    dns = percpu(binfmt.DNS_REC_DTYPE, n_dns,
                 lambda v: v.__setitem__(
                     "latency_ns",
                     rng.integers(0, 10**7, v["latency_ns"].shape)))
    n_drop = max(n_flows // 30, 1)
    drops = percpu(binfmt.DROPS_REC_DTYPE, n_drop,
                   lambda v: (v.__setitem__(
                       "bytes", rng.integers(0, 1500, v["bytes"].shape)),
                       v.__setitem__(
                           "packets", rng.integers(0, 3,
                                                   v["packets"].shape))))
    return agg_keys, agg_vals, {
        "extra": (ex_keys, extra),
        "dns": (agg_keys[:n_dns].copy(), dns),
        "drops": (agg_keys[n_flows - n_drop:].copy(), drops),
    }


def make_fetcher(lanes: int, n_cpus=4) -> loader.BpfmanFetcher:
    """A BpfmanFetcher over LaneMaps (no bpffs), with `lanes` drain lanes
    (pool sized like _init_drain_lanes: at most one worker per map)."""
    f = loader.BpfmanFetcher.__new__(loader.BpfmanFetcher)
    f._n_cpus = n_cpus
    f._base = ""
    f._agg = LaneMap(40, binfmt.FLOW_STATS_DTYPE.itemsize, 1)
    f._features = {
        "extra": (LaneMap(40, binfmt.EXTRA_REC_DTYPE.itemsize, n_cpus),
                  binfmt.EXTRA_REC_DTYPE),
        "dns": (LaneMap(40, binfmt.DNS_REC_DTYPE.itemsize, n_cpus),
                binfmt.DNS_REC_DTYPE),
        "drops": (LaneMap(40, binfmt.DROPS_REC_DTYPE.itemsize, n_cpus),
                  binfmt.DROPS_REC_DTYPE),
    }
    f._drain_lanes = lanes
    f._drain_pool = (ThreadPoolExecutor(
        max_workers=min(lanes, len(f._features)),
        thread_name_prefix="evict-drain") if lanes > 1 else None)
    return f


def load_fetcher(f: loader.BpfmanFetcher, drain) -> None:
    agg_keys, agg_vals, features = drain
    f._agg.load(agg_keys, agg_vals)
    for attr, (fkeys, fvals) in features.items():
        f._features[attr][0].load(fkeys, fvals)


def evicted_payload(ev) -> dict:
    out = {"events": ev.events.tobytes()}
    for name in ("extra", "dns", "drops", "xlat", "nevents", "quic"):
        col = getattr(ev, name)
        out[name] = None if col is None else col.tobytes()
    return out


class TestParallelLanes:
    @pytest.mark.parametrize("lanes", [3, 8])
    def test_lanes_match_sequential_bit_exact(self, native, lanes):
        # lanes=8 over 3 maps: each lane merge row-shards with threads=2
        # (the big-map relief path) — still bit-exact
        rng = np.random.default_rng(31)
        drains = [_synth_drain(np.random.default_rng(31 + i))
                  for i in range(4)]
        seq, par = make_fetcher(1), make_fetcher(lanes)
        try:
            for drain in drains:  # fresh contents each round: races surface
                load_fetcher(seq, drain)
                load_fetcher(par, drain)
                a = seq.lookup_and_delete()
                clocks0 = tracing.stage_clocks()
                b = par.lookup_and_delete()
                clocks1 = tracing.stage_clocks()
                assert evicted_payload(a) == evicted_payload(b)
                assert a.decode_stats["drain_lanes"] == 1
                assert b.decode_stats["drain_lanes"] == lanes
                # the lanes merged in-lane: the drain is one `decode` and
                # one `align` on the stage clocks, and no `merge_percpu`
                for stage, calls in (("decode", 1), ("align", 1),
                                     ("merge_percpu", 0)):
                    assert (clocks1.get(stage, (0, 0, 0))[2]
                            - clocks0.get(stage, (0, 0, 0))[2]) == calls
                assert b.decode_stats["fallback_rows"] == \
                    a.decode_stats["fallback_rows"] > 0
        finally:
            par._drain_pool.shutdown(wait=True)

    def test_lane_views_copied_before_return(self, native):
        """The per-lane lifetime rule: after lookup_and_delete returns,
        scribbling EVERY map's cached drain buffers (what the next drain
        does) must not perturb the EvictedFlows — the one copy already
        happened at its construction."""
        par = make_fetcher(3)
        try:
            load_fetcher(par, _synth_drain(np.random.default_rng(5)))
            ev = par.lookup_and_delete()
            before = evicted_payload(ev)
            par._agg.scribble()
            for fmap, _dt in par._features.values():
                fmap.scribble()
            assert evicted_payload(ev) == before, \
                "EvictedFlows aliased a lane's drain buffer"
        finally:
            par._drain_pool.shutdown(wait=True)

    def test_raw_lane_views_do_alias(self):
        """Counter-proof that the fake reproduces the hazard: a RAW drain
        view held past its lane's next load IS mutated — the copy boundary
        above is load-bearing, not vacuous."""
        m = LaneMap(40, binfmt.EXTRA_REC_DTYPE.itemsize, 2)
        rng = np.random.default_rng(6)
        keys = _keys_u8(8, rng)
        vals = np.zeros((8, 2), binfmt.EXTRA_REC_DTYPE)
        vals["rtt_ns"] = rng.integers(1, 10**6, (8, 2))
        m.load(keys, vals)
        kview, vview = m.drain_batched_arrays()
        snap = vview.tobytes()
        m.scribble()
        assert vview.tobytes() != snap
        assert (kview == 0xAA).all()

    def test_pool_is_none_check_when_sequential(self):
        f = make_fetcher(1)
        assert f._drain_pool is None


class TestResolveDrainLanes:
    def test_sequential_and_no_maps(self):
        assert loader.resolve_drain_lanes(1, 6) == 1
        assert loader.resolve_drain_lanes(0, 0) == 1
        assert loader.resolve_drain_lanes(4, 0) == 1

    def test_auto_caps_by_cores_and_maps(self, monkeypatch):
        import os
        monkeypatch.setattr(os, "cpu_count", lambda: 2)
        assert loader.resolve_drain_lanes(0, 6) == 2
        monkeypatch.setattr(os, "cpu_count", lambda: 16)
        assert loader.resolve_drain_lanes(0, 6) == 6
        monkeypatch.setattr(os, "cpu_count", lambda: 1)
        assert loader.resolve_drain_lanes(0, 6) == 1

    def test_explicit_trusted_beyond_maps_with_sanity_cap(self, monkeypatch):
        import os
        monkeypatch.setattr(os, "cpu_count", lambda: 1)
        # explicit oversubscription is the operator's call (syscall-bound)
        assert loader.resolve_drain_lanes(4, 6) == 4
        # lanes beyond the map count become per-map merge row-shards; the
        # only bound is the sanity ceiling
        assert loader.resolve_drain_lanes(8, 3) == 8
        assert loader.resolve_drain_lanes(32, 6) == loader._MAX_DRAIN_LANES


class TestNativeInterleave:
    def test_matches_numpy_twin_with_tail(self, native):
        rng = np.random.default_rng(9)
        n = 257
        keys = _keys_u8(n, rng)
        stats = np.zeros(n, binfmt.FLOW_STATS_DTYPE)
        stats["bytes"] = rng.integers(0, 1 << 50, n)
        stats["tcp_flags"] = rng.integers(0, 1 << 16, n)
        stats["src_mac"] = rng.integers(0, 256, (n, 6))
        a = flowpack.events_from_keys_stats(keys, stats, n_total=n + 7)
        b = binfmt.events_from_keys_stats(
            keys.view(binfmt.FLOW_KEY_DTYPE).reshape(-1), stats,
            n_total=n + 7)
        assert a.tobytes() == b.tobytes()
        c = flowpack.events_from_keys_stats(keys, stats, n_total=n + 7,
                                            use_native=False)
        assert c.tobytes() == b.tobytes()

    def test_empty_and_structured_keys(self, native):
        empty = flowpack.events_from_keys_stats(
            np.empty((0, 40), np.uint8),
            np.empty(0, binfmt.FLOW_STATS_DTYPE), n_total=3)
        assert len(empty) == 3 and not empty.view(np.uint8).any()
        rng = np.random.default_rng(2)
        keys = _keys_u8(5, rng)
        stats = np.zeros(5, binfmt.FLOW_STATS_DTYPE)
        stats["packets"] = np.arange(5)
        via_struct = flowpack.events_from_keys_stats(
            keys.view(binfmt.FLOW_KEY_DTYPE).reshape(-1), stats)
        via_u8 = flowpack.events_from_keys_stats(keys, stats)
        assert via_struct.tobytes() == via_u8.tobytes()

    def test_length_mismatch_raises(self, native):
        with pytest.raises(ValueError):
            flowpack.events_from_keys_stats(
                np.zeros((3, 40), np.uint8),
                np.zeros(2, binfmt.FLOW_STATS_DTYPE))

    def test_short_n_total_refused_not_overrun(self, native):
        # the native memcpy loop would write past a short buffer; both
        # paths must refuse identically
        for un in (True, False):
            with pytest.raises(ValueError):
                flowpack.events_from_keys_stats(
                    np.zeros((3, 40), np.uint8),
                    np.zeros(3, binfmt.FLOW_STATS_DTYPE), n_total=2,
                    use_native=un)


class TestLaneShardedMerge:
    @pytest.mark.parametrize("kind,dtype", [
        ("extra", binfmt.EXTRA_REC_DTYPE),
        ("stats", binfmt.FLOW_STATS_DTYPE),
        ("drops", binfmt.DROPS_REC_DTYPE),
    ])
    def test_threads_and_out_equivalent(self, native, kind, dtype):
        rng = np.random.default_rng(11)
        n = flowpack._MERGE_LANE_MIN_ROWS + 37  # past the lane floor
        vals = np.zeros((n, 4), dtype)
        vals["first_seen_ns"] = rng.integers(1, 1 << 40, (n, 4))
        vals["last_seen_ns"] = rng.integers(1, 1 << 40, (n, 4))
        if kind == "extra":
            vals["rtt_ns"] = rng.integers(0, 1 << 30, (n, 4))
        if kind == "stats":
            vals["bytes"] = rng.integers(0, 1 << 50, (n, 4))
            vals["tcp_flags"] = rng.integers(0, 1 << 16, (n, 4))
        if kind == "drops":
            vals["bytes"] = rng.integers(0, 1 << 16, (n, 4))
        one = flowpack.merge_percpu_batch(kind, vals)
        sharded = flowpack.merge_percpu_batch(kind, vals, threads=3)
        out = np.zeros(n, dtype)
        ret = flowpack.merge_percpu_batch(kind, vals, out=out, threads=2)
        assert ret is out
        twin = flowpack.merge_percpu_batch(kind, vals, use_native=False)
        assert one.tobytes() == sharded.tobytes() == out.tobytes() \
            == twin.tobytes()

    def test_out_validation(self, native):
        vals = np.zeros((4, 2), binfmt.EXTRA_REC_DTYPE)
        with pytest.raises(ValueError):
            flowpack.merge_percpu_batch(
                "extra", vals, out=np.zeros(3, binfmt.EXTRA_REC_DTYPE))
        with pytest.raises(ValueError):
            flowpack.merge_percpu_batch(
                "extra", vals, out=np.zeros(4, binfmt.DNS_REC_DTYPE))

    def test_numpy_fallback_fills_out(self):
        rng = np.random.default_rng(3)
        vals = np.zeros((16, 2), binfmt.EXTRA_REC_DTYPE)
        vals["rtt_ns"] = rng.integers(0, 10**6, (16, 2))
        out = np.zeros(16, binfmt.EXTRA_REC_DTYPE)
        ret = flowpack.merge_percpu_batch("extra", vals, use_native=False,
                                          out=out)
        assert ret is out
        assert out.tobytes() == flowpack.merge_percpu_batch(
            "extra", vals, use_native=False).tobytes()
