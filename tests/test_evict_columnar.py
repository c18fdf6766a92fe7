"""Columnar eviction plane: the four-form merge-semantics equivalence and
the vectorized alignment join.

The per-CPU merge contract exists in four userspace forms — per-record
python (`accumulate.accumulate_*`), per-key native (`fp_merge_*`), columnar
python (`accumulate.COLUMNAR_MERGES`), and batch native
(`fp_merge_*_batch`) — and they must agree BIT-EXACTLY for every feature
kind (CLAUDE.md merge invariant). This suite fuzzes all four against each
other across shapes (n_cpus=1 fast path included), pins the named edge
cases (u16/u32/u64 saturation, MAC fill, interface-dedup cap clamp incl.
the transiently-over-cap counter, nevents ring wrap), and carries
endian-independent golden vectors that REALLY execute on the big-endian
qemu CI tier (ci.yml layout-multiarch), like the hashing twins.

The alignment half (`loader.decode_eviction` / `loader._join_keys`) is
jax-free too: dict-idiom parity (last duplicate wins), ringbuf-orphan
standalone events, duplicate keys across drain chunks, empty drains, and
the forced hash-collision lexsort fallback.
"""

from __future__ import annotations

import numpy as np
import pytest

from netobserv_tpu.datapath import flowpack, loader
from netobserv_tpu.model import accumulate as acc
from netobserv_tpu.model import binfmt
from netobserv_tpu.utils import tracing

KINDS = ["stats", "extra", "drops", "dns", "nevents", "xlat", "quic"]


@pytest.fixture(scope="module")
def native():
    if not flowpack.build_native():
        pytest.skip("no g++ available to build libflowpack")
    assert flowpack.native_available()
    return True


def _rand_partials(kind: str, n_keys: int, n_cpus: int,
                   rng: np.random.Generator) -> np.ndarray:
    """Random-but-plausible per-CPU partials for one feature kind. Raw
    random bytes with two sanitizations: the nevents cursor stays byte-range
    sane, and DNS names start non-NUL when non-empty (wire qnames always do;
    a leading-NUL name is the one latent divergence between the python
    any-nonzero rule and the native name[0] rule, predating this suite)."""
    dtype = flowpack._MERGE_FNS[kind][1]
    raw = rng.integers(0, 256, (n_keys, n_cpus, dtype.itemsize),
                       dtype=np.int64).astype(np.uint8)
    vals = raw.reshape(n_keys, n_cpus * dtype.itemsize).copy().view(dtype)
    if kind == "dns" and n_keys:
        name = vals["name"]
        # clear names with a NUL first byte entirely (realistic absent name)
        first = np.frombuffer(name.tobytes(), np.uint8).reshape(
            n_keys, n_cpus, 32)[:, :, 0]
        vals["name"] = np.where(first == 0, np.bytes_(b""), name)
    return vals


def _perrecord_reference(kind: str, vals: np.ndarray) -> np.ndarray:
    dtype, py_fn = flowpack._MERGE_FNS[kind][1], flowpack._MERGE_FNS[kind][2]
    out = np.zeros(len(vals), dtype)
    for i in range(len(vals)):
        out[i] = acc.merge_percpu(vals[i], py_fn)
    return out


class TestFourFormEquivalence:
    @pytest.mark.parametrize("kind", KINDS)
    @pytest.mark.parametrize("shape", [(0, 4), (7, 1), (23, 4), (31, 8)])
    def test_columnar_matches_per_record(self, kind, shape):
        rng = np.random.default_rng(hash((kind, shape)) & 0xFFFF)
        vals = _rand_partials(kind, *shape, rng)
        ref = _perrecord_reference(kind, vals)
        got = acc.COLUMNAR_MERGES[kind](vals)
        assert got.tobytes() == ref.tobytes(), kind

    @pytest.mark.parametrize("kind", KINDS)
    @pytest.mark.parametrize("shape", [(0, 4), (7, 1), (23, 4), (31, 8)])
    def test_native_batch_matches_per_record(self, native, kind, shape):
        rng = np.random.default_rng(hash((kind, shape)) & 0xFFFF)
        vals = _rand_partials(kind, *shape, rng)
        ref = _perrecord_reference(kind, vals)
        got = flowpack.merge_percpu_batch(kind, vals, use_native=True)
        assert got.tobytes() == ref.tobytes(), kind

    @pytest.mark.parametrize("kind", KINDS)
    def test_single_key_native_matches_batch(self, native, kind):
        """The per-key native entry (the accounter path) and the batch entry
        must agree row for row."""
        rng = np.random.default_rng(99)
        vals = _rand_partials(kind, 9, 4, rng)
        batch = flowpack.merge_percpu_batch(kind, vals, use_native=True)
        for i in range(len(vals)):
            one = flowpack.merge_percpu(kind, vals[i], use_native=True)
            assert one.tobytes() == batch[i].tobytes(), (kind, i)


class TestMergeEdgeCases:
    """The named saturation/dedup/fill behaviors, asserted on FIELD VALUES
    (endian-independent — these are the golden vectors the big-endian qemu
    tier executes) and cross-checked against every form."""

    def _all_forms(self, kind, vals):
        forms = {
            "per_record": _perrecord_reference(kind, vals),
            "columnar": acc.COLUMNAR_MERGES[kind](vals),
        }
        if flowpack.native_available():
            forms["native_batch"] = flowpack.merge_percpu_batch(
                kind, vals, use_native=True)
        ref = forms["per_record"]
        for name, got in forms.items():
            assert got.tobytes() == ref.tobytes(), name
        return ref

    def test_u64_u32_saturation_and_flag_or(self):
        vals = np.zeros((2, 3), binfmt.FLOW_STATS_DTYPE)
        vals[0, 0]["bytes"] = 2**64 - 10
        vals[0, 1]["bytes"] = 100
        vals[0, 2]["bytes"] = 5
        vals[0, 0]["packets"] = 2**32 - 3
        vals[0, 1]["packets"] = 7
        vals[1, 0]["bytes"] = 11
        vals[1, 1]["bytes"] = 31
        vals[1, 0]["tcp_flags"] = 0x02
        vals[1, 2]["tcp_flags"] = 0x10
        out = self._all_forms("stats", vals)
        assert int(out[0]["bytes"]) == 2**64 - 1      # saturated, not wrapped
        assert int(out[0]["packets"]) == 2**32 - 1
        assert int(out[1]["bytes"]) == 42
        assert int(out[1]["tcp_flags"]) == 0x12

    def test_u16_drop_saturation(self):
        vals = np.zeros((1, 3), binfmt.DROPS_REC_DTYPE)
        vals[0]["bytes"] = [0xFFF0, 0x0100, 1]
        vals[0]["packets"] = [2, 3, 4]
        vals[0, 1]["latest_cause"] = 77
        out = self._all_forms("drops", vals)
        assert int(out[0]["bytes"]) == 0xFFFF
        assert int(out[0]["packets"]) == 9
        assert int(out[0]["latest_cause"]) == 77

    def test_mac_fill_if_unset(self):
        vals = np.zeros((2, 3), binfmt.FLOW_STATS_DTYPE)
        vals[0, 1]["src_mac"] = [1, 2, 3, 4, 5, 6]   # first non-zero wins
        vals[0, 2]["src_mac"] = [9, 9, 9, 9, 9, 9]
        vals[1, 0]["dst_mac"] = [7, 7, 7, 7, 7, 7]   # cpu0 already set: kept
        vals[1, 1]["dst_mac"] = [8, 8, 8, 8, 8, 8]
        out = self._all_forms("stats", vals)
        assert out[0]["src_mac"].tolist() == [1, 2, 3, 4, 5, 6]
        assert out[1]["dst_mac"].tolist() == [7, 7, 7, 7, 7, 7]

    def test_interface_dedup_cap_clamp_and_overcap_counter(self):
        cap = binfmt.FLOW_STATS_DTYPE["observed_intf"].shape[0]
        vals = np.zeros((2, 2), binfmt.FLOW_STATS_DTYPE)
        # key 0: the datapath's lock-free reservation left the counter
        # TRANSIENTLY above capacity — must clamp before indexing
        vals[0, 0]["n_observed_intf"] = cap + 3
        vals[0, 0]["observed_intf"][:] = np.arange(cap) + 1
        vals[0, 1]["n_observed_intf"] = 2
        vals[0, 1]["observed_intf"][:2] = [1, 99]    # 1 dups, 99 over cap
        vals[0, 1]["observed_direction"][:2] = [0, 1]
        # key 1: dedup on (intf, direction) PAIRS, append until cap
        vals[1, 0]["n_observed_intf"] = 1
        vals[1, 0]["observed_intf"][0] = 3
        vals[1, 1]["n_observed_intf"] = 2
        vals[1, 1]["observed_intf"][:2] = [3, 3]
        vals[1, 1]["observed_direction"][:2] = [0, 1]  # same intf, other dir
        out = self._all_forms("stats", vals)
        assert int(out[0]["n_observed_intf"]) == cap   # clamped, full
        assert int(out[1]["n_observed_intf"]) == 2
        assert out[1]["observed_intf"][:2].tolist() == [3, 3]
        assert out[1]["observed_direction"][:2].tolist() == [0, 1]

    def test_nevents_ring_wrap(self):
        cap = binfmt.NEVENTS_REC_DTYPE["events"].shape[0]
        vals = np.zeros((1, 2), binfmt.NEVENTS_REC_DTYPE)
        for j in range(cap):
            vals[0, 0]["events"][j] = [j + 1] * 8
            vals[0, 0]["packets"][j] = 1
        vals[0, 0]["n_events"] = 1                   # wrapped cursor
        vals[0, 1]["events"][0] = [1] * 8            # dup of slot 0
        vals[0, 1]["events"][1] = [99] * 8           # fresh -> overwrites
        vals[0, 1]["packets"][:2] = 1
        vals[0, 1]["n_events"] = 2
        out = self._all_forms("nevents", vals)
        assert out[0]["events"][1].tolist() == [99] * 8
        assert int(out[0]["n_events"]) == 2

    def test_times_zero_means_unset(self):
        vals = np.zeros((1, 3), binfmt.EXTRA_REC_DTYPE)
        vals[0]["first_seen_ns"] = [0, 500, 100]
        vals[0]["last_seen_ns"] = [0, 7, 9]
        vals[0]["rtt_ns"] = [3, 1, 2]
        out = self._all_forms("extra", vals)
        assert int(out[0]["first_seen_ns"]) == 100   # zero never wins min
        assert int(out[0]["last_seen_ns"]) == 9
        assert int(out[0]["rtt_ns"]) == 3

    def test_ssl_version_first_wins_mismatch_flag(self):
        vals = np.zeros((2, 3), binfmt.FLOW_STATS_DTYPE)
        vals[0]["ssl_version"] = [0, 0x0303, 0x0304]  # conflict -> flag
        vals[1]["ssl_version"] = [0x0304, 0, 0x0304]  # agreement -> no flag
        out = self._all_forms("stats", vals)
        assert int(out[0]["ssl_version"]) == 0x0303
        assert int(out[0]["misc_flags"]) & acc.MISC_SSL_MISMATCH
        assert int(out[1]["ssl_version"]) == 0x0304
        assert not int(out[1]["misc_flags"]) & acc.MISC_SSL_MISMATCH


# ---------------------------------------------------------------------------
# alignment join (loader.decode_eviction / loader._join_keys)
# ---------------------------------------------------------------------------

def _keys_u8(n, rng, port_base=0):
    k = np.zeros(n, binfmt.FLOW_KEY_DTYPE)
    k["src_ip"] = rng.integers(0, 256, (n, 16))
    k["dst_ip"] = rng.integers(0, 256, (n, 16))
    k["src_port"] = (port_base + np.arange(n)) & 0xFFFF
    k["proto"] = 6
    return np.frombuffer(k.tobytes(), np.uint8).reshape(n, 40).copy()


class TestDecodeEviction:
    def test_alignment_and_orphans(self):
        rng = np.random.default_rng(8)
        n, c = 64, 4
        agg = _keys_u8(n, rng)
        stats = np.zeros((n, 1), binfmt.FLOW_STATS_DTYPE)
        stats["bytes"][:, 0] = np.arange(n) + 1
        sel = rng.permutation(n)[:40]
        orph = _keys_u8(3, rng, port_base=50_000)
        ex_k = np.concatenate([agg[sel], orph])
        ex_v = np.zeros((43, c), binfmt.EXTRA_REC_DTYPE)
        ex_v["rtt_ns"] = rng.integers(1, 10**7, (43, c))
        ex_v["first_seen_ns"] = rng.integers(1, 10**9, (43, c))
        ex_v["last_seen_ns"] = rng.integers(10**9, 2 * 10**9, (43, c))
        # a second feature shares orphan key 0 -> SAME appended row
        dn_k = orph[:1].copy()
        dn_v = np.zeros((1, c), binfmt.DNS_REC_DTYPE)
        dn_v["latency_ns"][0] = [5, 9, 2, 1]
        clocks0 = tracing.stage_clocks()
        ev = loader.decode_eviction(
            agg, stats, {"extra": (ex_k, ex_v), "dns": (dn_k, dn_v)})
        clocks1 = tracing.stage_clocks()
        assert len(ev) == n + 3
        assert np.array_equal(ev.events["stats"][:n], stats[:, 0])
        for j, si in enumerate(sel):
            assert int(ev.extra[si]["rtt_ns"]) == int(ex_v["rtt_ns"][j].max())
        app = {ev.events["key"][n + i].tobytes(): n + i for i in range(3)}
        assert set(app) == {orph[i].tobytes() for i in range(3)}
        shared = app[orph[0].tobytes()]
        assert int(ev.dns[shared]["latency_ns"]) == 9
        assert int(ev.extra[shared]["rtt_ns"]) == int(ex_v["rtt_ns"][40].max())
        # appended standalone stats carry the merged rec's seen times
        mex = flowpack.merge_percpu("extra", ex_v[40])
        assert int(ev.events["stats"][shared]["first_seen_ns"]) == \
            int(mex["first_seen_ns"])
        assert int(ev.events["stats"][shared]["last_seen_ns"]) == \
            int(mex["last_seen_ns"])
        # the merge and the join are one call each of their stage clocks
        # (stage_{wall,cpu}_seconds_total); fallback_rows and the rows the
        # native align carried ride the EvictedFlows for map_tracer's
        # counters
        for stage in ("merge_percpu", "align"):
            w0, c0, n0 = clocks0.get(stage, (0.0, 0.0, 0))
            w1, c1, n1 = clocks1[stage]
            assert n1 == n0 + 1 and w1 >= w0 and c1 >= c0
        assert ev.decode_stats == {
            "fallback_rows": 3,
            "align_native_rows": n + 3 if flowpack.native_available() else 0}

    def test_duplicate_keys_last_wins(self):
        """Duplicate agg keys across drain chunks: feature rows land on the
        LAST duplicate (python-dict idiom parity); duplicate feature keys:
        the last record wins the scatter."""
        rng = np.random.default_rng(9)
        agg = _keys_u8(8, rng)
        dup = np.concatenate([agg[:1], agg])          # key 0 at rows 0 and 1
        stats = np.zeros((9, 1), binfmt.FLOW_STATS_DTYPE)
        fk = np.concatenate([agg[:1], agg[:1]])       # duplicate feature key
        fv = np.zeros((2, 2), binfmt.EXTRA_REC_DTYPE)
        fv["rtt_ns"][0] = 111
        fv["rtt_ns"][1] = 222
        ev = loader.decode_eviction(dup, stats, {"extra": (fk, fv)})
        assert len(ev) == 9
        nz = np.nonzero(ev.extra["rtt_ns"])[0].tolist()
        assert nz == [1]                              # last duplicate agg row
        assert int(ev.extra[1]["rtt_ns"]) == 222      # last feature rec wins

    def test_empty_drains(self):
        ev = loader.decode_eviction(
            np.empty((0, 40), np.uint8),
            np.empty((0, 1), binfmt.FLOW_STATS_DTYPE), {})
        assert len(ev) == 0 and ev.extra is None
        rng = np.random.default_rng(10)
        agg = _keys_u8(4, rng)
        ev2 = loader.decode_eviction(
            agg, np.zeros((4, 1), binfmt.FLOW_STATS_DTYPE),
            {"dns": (np.empty((0, 40), np.uint8),
                     np.empty((0, 2), binfmt.DNS_REC_DTYPE))})
        assert len(ev2) == 4 and ev2.dns is None      # drained empty -> None

    def test_orphan_only_drain(self):
        """Feature rows with NO aggregation drain at all (ringbuf-fallback
        flood) still become standalone events."""
        rng = np.random.default_rng(11)
        fk = _keys_u8(5, rng)
        fv = np.zeros((5, 2), binfmt.EXTRA_REC_DTYPE)
        fv["rtt_ns"][:, 0] = np.arange(5) + 1
        ev = loader.decode_eviction(
            np.empty((0, 40), np.uint8),
            np.empty((0, 1), binfmt.FLOW_STATS_DTYPE), {"extra": (fk, fv)})
        assert len(ev) == 5
        got = {ev.events["key"][i].tobytes(): int(ev.extra[i]["rtt_ns"])
               for i in range(5)}
        want = {fk[i].tobytes(): i + 1 for i in range(5)}
        assert got == want

    def test_hash_collision_falls_back_to_exact_sort(self, monkeypatch):
        """Force every key onto one hash value: the numpy join must detect
        the distinct-keys-per-hash-group condition and produce the same
        result through the lexsort fallback. The hash is the numpy twin's,
        so the forced run takes the numpy align (the native align's own
        collision test is tests/test_native_align.py)."""
        rng = np.random.default_rng(12)
        agg = _keys_u8(16, rng)
        stats = np.zeros((16, 1), binfmt.FLOW_STATS_DTYPE)
        sel = np.arange(0, 16, 2)
        fk = agg[sel].copy()
        fv = np.zeros((8, 2), binfmt.EXTRA_REC_DTYPE)
        fv["rtt_ns"][:, 0] = np.arange(8) + 1
        ref = loader.decode_eviction(agg, stats, {"extra": (fk, fv)})
        monkeypatch.setattr(
            loader, "_hash_keys_u64",
            lambda ku8: np.zeros(len(ku8), np.uint64))
        monkeypatch.setattr(flowpack, "align_eviction", lambda *a: None)
        got = loader.decode_eviction(agg, stats, {"extra": (fk, fv)})
        assert got.decode_stats["align_native_rows"] == 0
        assert got.events.tobytes() == ref.events.tobytes()
        assert got.extra.tobytes() == ref.extra.tobytes()


class TestDrainArraysFallback:
    """The per-key drain fallback of loader._drain_map_arrays (batch-less
    kernels) must decode identically to the zero-copy path's layout."""

    class _FakeMap:
        key_size = 40
        n_cpus = 2
        _pad_vs = binfmt.EXTRA_REC_DTYPE.itemsize

        def __init__(self, pairs, batched):
            self._pairs = pairs
            self._batched = batched

        def drain_batched_arrays(self):
            if not self._batched:
                return None
            n = len(self._pairs)
            k = np.frombuffer(b"".join(p[0] for p in self._pairs),
                              np.uint8).reshape(n, 40)
            v = np.frombuffer(b"".join(p[1] for p in self._pairs),
                              np.uint8).reshape(n, self._pad_vs * self.n_cpus)
            return k, v

        def drain(self):
            return list(self._pairs)

    def test_paths_agree(self):
        rng = np.random.default_rng(13)
        keys = _keys_u8(6, rng)
        vals = np.zeros((6, 2), binfmt.EXTRA_REC_DTYPE)
        vals["rtt_ns"] = rng.integers(0, 10**6, (6, 2))
        pairs = [(keys[i].tobytes(), vals[i].tobytes()) for i in range(6)]
        k1, v1 = loader._drain_map_arrays(
            self._FakeMap(pairs, batched=True), binfmt.EXTRA_REC_DTYPE)
        k2, v2 = loader._drain_map_arrays(
            self._FakeMap(pairs, batched=False), binfmt.EXTRA_REC_DTYPE)
        assert np.array_equal(k1, k2)
        assert v1.tobytes() == v2.tobytes()
        assert v1.shape == (6, 2) and v1.dtype == binfmt.EXTRA_REC_DTYPE


class TestColumnarGcSkip:
    """FORCE_GARBAGE_COLLECTION fires only on the record-materializing path:
    the columnar fast path births no per-record objects, so the collect
    there is pure stall and must be skipped."""

    def _run(self, columnar: bool) -> int:
        import gc
        import queue

        from netobserv_tpu.datapath.fetcher import FakeFetcher
        from netobserv_tpu.flow.map_tracer import MapTracer

        events = np.zeros(3, binfmt.FLOW_EVENT_DTYPE)
        events["key"]["src_port"] = [1, 2, 3]
        fetcher = FakeFetcher()
        fetcher.inject_events(events.copy())
        out: queue.Queue = queue.Queue()
        tracer = MapTracer(fetcher, out, columnar=columnar, force_gc=True)
        calls = 0
        real = gc.collect

        def counting():
            nonlocal calls
            calls += 1
            return real()

        gc.collect = counting
        try:
            tracer._evict_once()
        finally:
            gc.collect = real
        assert out.get_nowait() is not None
        return calls

    def test_record_path_collects(self):
        assert self._run(columnar=False) == 1

    def test_columnar_path_skips(self):
        assert self._run(columnar=True) == 0
