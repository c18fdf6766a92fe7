"""Native flowpack vs numpy fallback equivalence (and the native build)."""

import numpy as np
import pytest

from netobserv_tpu.datapath import flowpack
from netobserv_tpu.model import binfmt
from tests.test_model import make_event


@pytest.fixture(scope="module")
def native():
    if not flowpack.build_native():
        pytest.skip("no g++ available to build libflowpack")
    assert flowpack.native_available()
    return True


def _events(n=17):
    events = np.zeros(n, dtype=binfmt.FLOW_EVENT_DTYPE)
    for i in range(n):
        events[i] = make_event(sport=1000 + i, nbytes=10 * i + 1, pkts=i + 1)
    events["stats"]["sampling"] = 50
    events["stats"]["dscp"] = 46
    return events


class TestPack:
    def test_native_matches_numpy(self, native):
        events = _events()
        a = flowpack.pack_events(events, batch_size=32, use_native=True)
        b = flowpack.pack_events(events, batch_size=32, use_native=False)
        for name, col in a.columns().items():
            np.testing.assert_array_equal(
                col, getattr(b, name), err_msg=f"column {name}")

    def test_pack_from_raw_bytes(self, native):
        events = _events(5)
        batch = flowpack.pack_events(events.tobytes(), use_native=True)
        assert batch.n_valid == 5
        assert batch.bytes[:5].tolist() == [1, 11, 21, 31, 41]

    def test_empty(self, native):
        batch = flowpack.pack_events(b"", batch_size=4)
        assert batch.n_valid == 0


class TestPackDense:
    def _extra_dns(self, n):
        extra = np.zeros(n, dtype=binfmt.EXTRA_REC_DTYPE)
        extra["rtt_ns"] = np.arange(n, dtype=np.uint64) * 123_000
        dns = np.zeros(n, dtype=binfmt.DNS_REC_DTYPE)
        dns["latency_ns"] = np.arange(n, dtype=np.uint64) * 77_000
        return extra, dns

    def test_native_matches_numpy(self, native):
        events = _events()
        extra, dns = self._extra_dns(len(events))
        a = flowpack.pack_dense(events, batch_size=32, extra=extra, dns=dns,
                                use_native=True)
        b = flowpack.pack_dense(events, batch_size=32, extra=extra, dns=dns,
                                use_native=False)
        np.testing.assert_array_equal(a, b)

    def test_matches_column_path(self, native):
        """The dense rows must carry exactly what batch_to_device exposes —
        the single shared definition the ingest consumes either way."""
        from netobserv_tpu.sketch import state as sk

        events = _events()
        extra, dns = self._extra_dns(len(events))
        dense = flowpack.pack_dense(events, batch_size=32, extra=extra,
                                    dns=dns)
        batch = flowpack.pack_events(events, batch_size=32, extra=extra,
                                     dns=dns)
        arrays = sk.batch_to_device(batch)
        np.testing.assert_array_equal(dense[:, :10], arrays["keys"])
        np.testing.assert_array_equal(dense[:, 10].view(np.float32),
                                      arrays["bytes"])
        np.testing.assert_array_equal(dense[:, 11].astype(np.int32),
                                      arrays["packets"])
        np.testing.assert_array_equal(dense[:, 12].astype(np.int32),
                                      arrays["rtt_us"])
        np.testing.assert_array_equal(dense[:, 13].astype(np.int32),
                                      arrays["dns_latency_us"])
        np.testing.assert_array_equal(dense[:, 14] != 0, arrays["valid"])
        np.testing.assert_array_equal(dense[:, 15].astype(np.int32),
                                      arrays["sampling"])

    def test_reused_out_buffer_zeroes_padding(self, native):
        """A preallocated out buffer is fully overwritten: stale rows from a
        bigger previous batch must never survive as phantom valid rows."""
        out = np.full((32, flowpack.DENSE_WORDS), 0xAB, np.uint32)
        flowpack.pack_dense(_events(20), batch_size=32, out=out)
        assert out[20:, 14].sum() == 0          # padding invalid
        assert (out[20:] == 0).all()
        dense2 = flowpack.pack_dense(_events(3), batch_size=32, out=out)
        assert dense2 is out
        assert (out[3:] == 0).all()

    def test_short_feature_arrays_padded(self, native):
        """extra/dns arrays shorter than the event count must not OOB-read
        (native) or broadcast-fail (numpy): missing tail rows read as 0."""
        events = _events(8)
        extra, dns = self._extra_dns(3)
        for un in (True, False):
            dense = flowpack.pack_dense(events, batch_size=8, extra=extra,
                                        dns=dns, use_native=un)
            assert (dense[3:, 12] == 0).all() and (dense[3:, 13] == 0).all()
            assert dense[2, 12] == 2 * 123 and dense[2, 13] == 2 * 77

    def test_empty(self, native):
        dense = flowpack.pack_dense(b"", batch_size=4)
        assert (dense == 0).all()

    def test_ingest_dense_equals_dict_ingest(self, native):
        """Folding the dense feed must produce bit-identical sketch state to
        the six-array dict path (same ingest, different transport)."""
        import jax

        from netobserv_tpu.sketch import state as sk

        events = _events(17)
        extra, dns = self._extra_dns(17)
        cfg = sk.SketchConfig(cm_width=1 << 10, topk=64)
        batch = flowpack.pack_events(events, batch_size=32, extra=extra,
                                     dns=dns)
        arrays = sk.batch_to_device(batch)
        s_dict = jax.jit(sk.ingest)(sk.init_state(cfg), arrays)
        dense = flowpack.pack_dense(events, batch_size=32, extra=extra,
                                    dns=dns)
        s_dense = sk.make_ingest_dense_fn(donate=False)(
            sk.init_state(cfg), dense)
        for name in sk.SketchState._fields:
            da, db = getattr(s_dict, name), getattr(s_dense, name)
            jax.tree.map(lambda a, b: np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b)), da, db)


class TestMergePercpu:
    @pytest.mark.parametrize(
        "kind", ["stats", "extra", "drops", "dns", "nevents", "xlat", "quic"])
    def test_native_matches_python(self, native, kind):
        rng = np.random.default_rng(3)
        dtype = flowpack._MERGE_FNS[kind][1]
        vals = np.zeros(4, dtype=dtype)
        # random-ish partials with valid fields
        for i in range(4):
            vals[i]["first_seen_ns"] = int(rng.integers(1, 10**9))
            vals[i]["last_seen_ns"] = int(rng.integers(10**9, 2 * 10**9))
            if kind == "stats":
                vals[i]["bytes"] = int(rng.integers(0, 10**6))
                vals[i]["packets"] = int(rng.integers(0, 1000))
                vals[i]["tcp_flags"] = int(rng.integers(0, 0xFFF))
                vals[i]["dscp"] = int(rng.integers(0, 64))
                vals[i]["ssl_version"] = int(
                    rng.choice([0, 0x0303, 0x0304]))
            elif kind == "extra":
                vals[i]["rtt_ns"] = int(rng.integers(0, 10**8))
                vals[i]["ipsec_ret"] = int(rng.integers(-2, 3))
                vals[i]["ipsec_encrypted"] = int(rng.integers(0, 2))
            elif kind == "drops":
                vals[i]["bytes"] = int(rng.integers(0, 0xFFFF))
                vals[i]["packets"] = int(rng.integers(0, 0xFFFF))
                vals[i]["latest_cause"] = int(rng.integers(0, 5))
                vals[i]["latest_flags"] = int(rng.integers(0, 0xFF))
            elif kind == "dns":
                vals[i]["latency_ns"] = int(rng.integers(0, 10**7))
                vals[i]["dns_id"] = int(rng.integers(0, 2**16))
                vals[i]["dns_flags"] = int(rng.integers(0, 2**16))
            elif kind == "nevents":
                n_ev = int(rng.integers(0, 5))
                for j in range(n_ev):
                    vals[i]["events"][j] = rng.integers(
                        1, 255, size=8, dtype=np.uint8)
                    vals[i]["bytes"][j] = int(rng.integers(1, 2000))
                    vals[i]["packets"][j] = int(rng.integers(1, 10))
                vals[i]["n_events"] = n_ev
            elif kind == "xlat":
                if rng.integers(0, 2):
                    vals[i]["src_ip"] = rng.integers(
                        1, 255, size=16, dtype=np.uint8)
                    vals[i]["dst_ip"] = rng.integers(
                        1, 255, size=16, dtype=np.uint8)
                    vals[i]["src_port"] = int(rng.integers(1, 2**16))
                    vals[i]["dst_port"] = int(rng.integers(1, 2**16))
                    vals[i]["zone_id"] = int(rng.integers(0, 2**16))
            elif kind == "quic":
                vals[i]["version"] = int(rng.integers(0, 3))
                vals[i]["seen_long_hdr"] = int(rng.integers(0, 2))
                vals[i]["seen_short_hdr"] = int(rng.integers(0, 2))
        a = flowpack.merge_percpu(kind, vals, use_native=True)
        b = flowpack.merge_percpu(kind, vals, use_native=False)
        assert a.tobytes() == b.tobytes(), kind

    def test_nevents_ring_wrap_equivalence(self, native):
        """Cursor wrap with duplicates: both implementations must agree."""
        cap = binfmt.NEVENTS_REC_DTYPE["events"].shape[0]
        vals = np.zeros(2, dtype=binfmt.NEVENTS_REC_DTYPE)
        for j in range(cap):
            vals[0]["events"][j] = [j + 1] * 8
            vals[0]["packets"][j] = 1
        vals[0]["n_events"] = 1  # wrapped cursor
        vals[1]["events"][0] = [1] * 8   # dup of slot 0
        vals[1]["events"][1] = [99] * 8  # fresh
        vals[1]["packets"][:2] = 1
        vals[1]["n_events"] = 2
        a = flowpack.merge_percpu("nevents", vals, use_native=True)
        b = flowpack.merge_percpu("nevents", vals, use_native=False)
        assert a.tobytes() == b.tobytes()

    def test_stats_saturating_and_dedup(self, native):
        vals = np.zeros(2, dtype=binfmt.FLOW_STATS_DTYPE)
        vals[0]["bytes"] = 2**64 - 10
        vals[1]["bytes"] = 100
        vals[0]["packets"] = 1
        vals[0]["n_observed_intf"] = 1
        vals[0]["observed_intf"][0] = 3
        vals[1]["n_observed_intf"] = 2
        vals[1]["observed_intf"][0] = 3
        vals[1]["observed_intf"][1] = 9
        out = flowpack.merge_percpu("stats", vals, use_native=True)
        assert int(out["bytes"]) == 2**64 - 1  # saturated
        assert int(out["n_observed_intf"]) == 2  # 3 deduped, 9 appended


class TestMergePercpuBatch:
    """merge_percpu_batch API surface (the full four-form fuzz lives in
    tests/test_evict_columnar.py): batch rows == per-key calls, native ==
    columnar fallback, and shape validation."""

    @pytest.mark.parametrize(
        "kind", ["stats", "extra", "drops", "dns", "nevents", "xlat", "quic"])
    def test_batch_rows_match_single_key(self, native, kind):
        rng = np.random.default_rng(21)
        dtype = flowpack._MERGE_FNS[kind][1]
        raw = rng.integers(0, 256, (5, 4 * dtype.itemsize),
                           dtype=np.int64).astype(np.uint8)
        vals = raw.copy().view(dtype)
        if kind == "dns":
            vals["name"] = b"\x03abc"  # keep both name rules equivalent
        if kind == "nevents":
            vals["n_events"] = vals["n_events"] % 8
        for un in (True, False):
            batch = flowpack.merge_percpu_batch(kind, vals, use_native=un)
            for i in range(len(vals)):
                one = flowpack.merge_percpu(kind, vals[i], use_native=un)
                assert one.tobytes() == batch[i].tobytes(), (kind, un, i)

    def test_rejects_non_2d(self, native):
        vals = np.zeros(4, dtype=binfmt.EXTRA_REC_DTYPE)
        with pytest.raises(ValueError):
            flowpack.merge_percpu_batch("extra", vals)

    def test_empty_batch(self, native):
        vals = np.zeros((0, 4), dtype=binfmt.EXTRA_REC_DTYPE)
        for un in (True, False):
            out = flowpack.merge_percpu_batch("extra", vals, use_native=un)
            assert out.shape == (0,) and out.dtype == binfmt.EXTRA_REC_DTYPE


class TestStagingRing:
    def test_ring_matches_sequential_ingest(self, native):
        """Folding batches through the 4-slot staging ring (buffer reuse +
        async dispatch) must produce the same state as sequential dict-path
        ingest — slot reuse must never let a later batch overwrite rows an
        in-flight ingest still needs."""
        import jax

        from netobserv_tpu.sketch import state as sk
        from netobserv_tpu.sketch.staging import DenseStagingRing

        cfg = sk.SketchConfig(cm_width=1 << 10, topk=64)
        batches = []
        for s in range(11):
            ev = _events(32)
            ev["key"]["src_port"] = 2000 + 37 * s + np.arange(32)
            batches.append(ev)

        ring = DenseStagingRing(
            32, sk.make_ingest_dense_fn(donate=False, with_token=True))
        s_ring = sk.init_state(cfg)
        for ev in batches:
            s_ring = ring.fold(s_ring, ev)
        ring.drain()

        ingest = jax.jit(sk.ingest)
        s_ref = sk.init_state(cfg)
        for ev in batches:
            arrays = sk.batch_to_device(
                flowpack.pack_events(ev, batch_size=32))
            s_ref = ingest(s_ref, arrays)

        for name in sk.SketchState._fields:
            jax.tree.map(lambda a, b: np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b)),
                getattr(s_ring, name), getattr(s_ref, name))


class TestSamplingDebias:
    def test_sampled_volume_scaled(self):
        """A 1-in-N sampled flow must fold as N flows' worth of bytes/packets
        (reference semantics: the Sampling field scales collector-side
        estimates); unsampled (0) and 1:1 fold unscaled."""
        import jax
        import jax.numpy as jnp

        from netobserv_tpu.sketch import state as sk

        cfg = sk.SketchConfig(cm_width=1 << 10, topk=16)
        base = {
            "keys": np.arange(80, dtype=np.uint32).reshape(8, 10),
            "bytes": np.full(8, 100.0, np.float32),
            "packets": np.full(8, 3, np.int32),
            "rtt_us": np.zeros(8, np.int32),
            "dns_latency_us": np.zeros(8, np.int32),
            "valid": np.ones(8, np.bool_),
        }
        ingest = jax.jit(sk.ingest)
        s0 = ingest(sk.init_state(cfg),
                    {**base, "sampling": np.zeros(8, np.int32)})
        s1 = ingest(sk.init_state(cfg),
                    {**base, "sampling": np.full(8, 4, np.int32)})
        assert float(s1.total_bytes) == 4 * float(s0.total_bytes)
        assert float(s1.total_records) == float(s0.total_records)  # observed
        np.testing.assert_array_equal(np.asarray(s1.cm_bytes.counts),
                                      4 * np.asarray(s0.cm_bytes.counts))
        np.testing.assert_array_equal(np.asarray(s1.cm_pkts.counts),
                                      4 * np.asarray(s0.cm_pkts.counts))


def _mixed_events(n=24, n_v6=5):
    """Events with v4-mapped keys, the last n_v6 rows genuine v6."""
    events = _events(n)
    for i in range(n - n_v6, n):
        events[i]["key"]["src_ip"] = np.arange(16, dtype=np.uint8) + i
        events[i]["key"]["dst_ip"] = np.arange(16, dtype=np.uint8) * 2 + i
    return events


class TestPackCompact:
    def test_native_matches_numpy(self, native):
        events = _mixed_events()
        extra = np.zeros(len(events), dtype=binfmt.EXTRA_REC_DTYPE)
        extra["rtt_ns"] = np.arange(len(events), dtype=np.uint64) * 9_000
        a = flowpack.pack_compact(events, batch_size=32, spill_cap=8,
                                  extra=extra, use_native=True)
        b = flowpack.pack_compact(events, batch_size=32, spill_cap=8,
                                  extra=extra, use_native=False)
        np.testing.assert_array_equal(a, b)

    def test_overflow_returns_none(self, native):
        events = _mixed_events(24, n_v6=10)
        for un in (True, False):
            assert flowpack.pack_compact(events, batch_size=32, spill_cap=4,
                                         use_native=un) is None

    def test_ingest_compact_equals_dense(self, native):
        """The compact transport must fold to bit-identical sketch state as
        the dense transport — v4 key reconstruction included."""
        import jax

        from netobserv_tpu.sketch import state as sk

        events = _mixed_events()
        cfg = sk.SketchConfig(cm_width=1 << 10, topk=64)
        dense = flowpack.pack_dense(events, batch_size=37)
        s_dense = sk.make_ingest_dense_fn(donate=False)(
            sk.init_state(cfg), dense)
        comp = flowpack.pack_compact(events, batch_size=37, spill_cap=5)
        s_comp = sk.make_ingest_compact_fn(37, 5, donate=False)(
            sk.init_state(cfg), comp)
        # the lanes permute row order, so compare order-insensitive state:
        # every sketch is row-order invariant (sums/maxes over the batch)
        for name in ("cm_bytes", "cm_pkts", "hll_src", "hll_per_dst",
                     "hist_rtt", "hist_dns", "ddos", "total_records",
                     "total_bytes"):
            jax.tree.map(
                lambda a, b: np.testing.assert_allclose(
                    np.asarray(a), np.asarray(b), rtol=1e-6),
                getattr(s_dense, name), getattr(s_comp, name))

    def test_ring_compact_with_fallback(self, native):
        """The compact staging ring (with overflow batches taking the dense
        fallback) must agree with sequential dense ingest on the linear
        (row-order-invariant) sketches."""
        import jax

        from netobserv_tpu.sketch import state as sk
        from netobserv_tpu.sketch.staging import DenseStagingRing

        cfg = sk.SketchConfig(cm_width=1 << 10, topk=64)
        batches = []
        for i in range(9):
            # batch 4 overflows the spill lane -> dense fallback
            ev = _mixed_events(24, n_v6=10 if i == 4 else 3)
            ev["key"]["src_port"] = 3000 + 41 * i + np.arange(24)
            batches.append(ev)
        spill = 4
        ring = DenseStagingRing(
            32, sk.make_ingest_compact_fn(32, spill, donate=False,
                                          with_token=True),
            spill_cap=spill,
            ingest_fallback=sk.make_ingest_dense_fn(donate=False,
                                                    with_token=True))
        s_ring = sk.init_state(cfg)
        for ev in batches:
            s_ring = ring.fold(s_ring, ev)
        ring.drain()

        ingest = jax.jit(sk.ingest)
        s_ref = sk.init_state(cfg)
        for ev in batches:
            s_ref = ingest(s_ref, sk.batch_to_device(
                flowpack.pack_events(ev, batch_size=32)))
        for name in ("cm_bytes", "cm_pkts", "hll_src", "hll_per_dst",
                     "total_records", "total_bytes"):
            jax.tree.map(
                lambda a, b: np.testing.assert_allclose(
                    np.asarray(a), np.asarray(b), rtol=1e-6),
                getattr(s_ring, name), getattr(s_ref, name))


class TestStagingStallCounter:
    def test_stall_counted_when_slot_busy(self, native):
        """A fold that finds its slot's previous ingest still in flight must
        count a stall (ring.stalls + metrics.sketch_staging_stalls_total) —
        the operator's signal that the device, not the packer, is the
        bottleneck; ready slots must not count."""
        from prometheus_client import CollectorRegistry

        from netobserv_tpu.metrics.registry import Metrics, MetricsSettings
        from netobserv_tpu.sketch import state as sk
        from netobserv_tpu.sketch.staging import DenseStagingRing

        m = Metrics(MetricsSettings(), registry=CollectorRegistry())
        cfg = sk.SketchConfig(cm_width=1 << 10, topk=64)
        ring = DenseStagingRing(
            32, sk.make_ingest_dense_fn(donate=False, with_token=True),
            metrics=m)
        state = sk.init_state(cfg)
        # a DRAINED ring never stalls: every token is ready by construction
        for _ in range(6):
            state = ring.fold(state, _events(8))
            ring.drain()
        before = ring.stalls
        ring.fold(state, _events(8))
        assert ring.stalls == before  # drained slots are ready slots

        class _BusyToken:
            def __init__(self):
                self.blocked = False

            def is_ready(self):
                return False

            def block_until_ready(self):
                self.blocked = True

        tok = _BusyToken()
        ring._tokens[ring._slot] = tok
        ring.fold(state, _events(8))
        assert ring.stalls == before + 1
        assert m.sketch_staging_stalls_total._value.get() == before + 1.0
        assert tok.blocked  # correctness guard still waited on the slot


class TestShardedPack:
    def test_sharded_pack_equivalence(self, native):
        """Row-sharded parallel pack must be byte-identical to the
        single-pass pack, including the zero-padded tail and every feature
        lane, at thread counts that do and don't divide the row count."""
        rng = np.random.default_rng(11)
        n, bs = 1000, 1024
        ev = _events(n)
        extra = np.zeros(n, binfmt.EXTRA_REC_DTYPE)
        extra["rtt_ns"] = rng.integers(0, 10**7, n)
        drops = np.zeros(n, binfmt.DROPS_REC_DTYPE)
        drops["bytes"] = rng.integers(0, 500, n)
        drops["packets"] = (drops["bytes"] > 0).astype(np.uint16)
        drops["latest_cause"] = rng.integers(0, 1 << 17, n)  # subsys bits
        ref = flowpack.pack_dense(ev, batch_size=bs, extra=extra,
                                  drops=drops)
        for threads in (2, 3, 7):
            got = flowpack.pack_dense_sharded(
                ev, batch_size=bs, threads=threads, extra=extra, drops=drops)
            np.testing.assert_array_equal(got, ref)

    def test_sharded_pack_short_feature_arrays(self, native):
        """Feature arrays shorter than the event count zero-extend the same
        way in the sharded and single-pass packs."""
        ev = _events(64)
        dns = np.zeros(20, binfmt.DNS_REC_DTYPE)
        dns["latency_ns"] = 5_000_000
        ref = flowpack.pack_dense(ev, batch_size=64, dns=dns)
        got = flowpack.pack_dense_sharded(ev, batch_size=64, threads=4,
                                          dns=dns)
        np.testing.assert_array_equal(got, ref)


class TestCompactDropSpill:
    def test_drop_rows_spill_and_signals_match_dense(self, native):
        """Drop-carrying rows must ride the spill lane (the compact lane
        zeros drop columns by construction), and the compact transport must
        agree with the dense transport on EVERY signal plane the feature
        lane feeds — drops EWMA, cause histogram, totals, SYN, markers."""
        import jax

        from netobserv_tpu.sketch import state as sk

        events = _mixed_events(24, n_v6=3)
        events["stats"]["tcp_flags"] = 0x02  # half-open SYNs
        n = len(events)
        drops = np.zeros(n, binfmt.DROPS_REC_DTYPE)
        drops["bytes"][::5] = 700          # v4 rows with drops must spill
        drops["packets"][::5] = 2
        drops["latest_cause"][::5] = 6
        quic = np.zeros(n, binfmt.QUIC_REC_DTYPE)
        quic["version"][1] = 1
        xlat = np.zeros(n, binfmt.XLAT_REC_DTYPE)
        xlat["src_ip"][2] = 9
        xlat["dst_ip"][2] = 9

        # native and numpy compact packs agree with features present
        a = flowpack.pack_compact(events, batch_size=32, spill_cap=12,
                                  drops=drops, quic=quic, xlat=xlat,
                                  use_native=True)
        b = flowpack.pack_compact(events, batch_size=32, spill_cap=12,
                                  drops=drops, quic=quic, xlat=xlat,
                                  use_native=False)
        np.testing.assert_array_equal(a, b)

        cfg = sk.SketchConfig(cm_width=1 << 10, topk=64)
        dense = flowpack.pack_dense(events, batch_size=32, drops=drops,
                                    quic=quic, xlat=xlat)
        s_dense = sk.make_ingest_dense_fn(donate=False)(
            sk.init_state(cfg), dense)
        s_comp = sk.make_ingest_compact_fn(32, 12, donate=False)(
            sk.init_state(cfg), a)
        for name in ("drops_ewma", "drop_causes", "total_drop_bytes",
                     "total_drop_packets", "syn", "synack", "dscp_bytes",
                     "quic_records", "nat_records"):
            jax.tree.map(
                lambda x, y: np.testing.assert_allclose(
                    np.asarray(x), np.asarray(y), rtol=1e-6, err_msg=name),
                getattr(s_dense, name), getattr(s_comp, name))
        # _events stamps sampling=50: the sketches fold the de-biased
        # estimate (x50), same as fast-path volume counters
        assert float(s_comp.total_drop_bytes) == 700.0 * 50 * len(drops[::5])
        assert float(s_comp.quic_records) == 1.0
        assert float(s_comp.nat_records) == 1.0


@pytest.mark.parametrize("batch", [256, 1024, 8192])
def test_wide_resident_caps_differ_from_the_narrow_in_nk_alone(batch):
    """The wide lane family of the resident feed: a new-key lane three
    eighths of the region's rows, every other lane the narrow family's (a
    spill row is a row of the fold; a new-key row 44 bytes of transfer)."""
    narrow = flowpack.default_resident_caps(batch)
    wide = flowpack.wide_resident_caps(batch)
    assert (wide.dns, wide.drop, wide.spill) == (
        narrow.dns, narrow.drop, narrow.spill)
    assert wide.nk == max(batch * 3 // 8, narrow.nk) >= narrow.nk
    assert (flowpack.resident_buf_len(batch, wide)
            - flowpack.resident_buf_len(batch, narrow)
            == (wide.nk - narrow.nk) * flowpack.NK_WORDS)
