"""Federation plane: delta codec, aggregator merge correctness, transport.

The load-bearing test is federated-vs-union equivalence: N synthetic
agents' per-window deltas merged centrally must equal the single-state
fold of the union stream — bit-exact for the linear/max structures (CM,
histograms, rates, HLL registers) and the top-K set, with ZERO post-warmup
retraces on the aggregator's jitted entries (the fixed-shape invariant,
watchdog-verified directly on the wrappers).
"""

from __future__ import annotations

import json
import urllib.request

import numpy as np
import pytest

import tests.conftest  # noqa: F401  (forces the CPU backend)

from netobserv_tpu.federation import delta as fdelta
from netobserv_tpu.federation.aggregator import FederationAggregator
from netobserv_tpu.sketch import state as sk

CFG = sk.SketchConfig(cm_depth=3, cm_width=1024, hll_precision=8,
                      perdst_buckets=64, perdst_precision=5,
                      persrc_buckets=64, persrc_precision=5,
                      topk=64, hist_buckets=128, ewma_buckets=64)
DIMS = {"cm_depth": 3, "cm_width": 1024, "hll_precision": 8, "topk": 64,
        "ewma_buckets": 64}
N_AGENTS = 4
N_DISTINCT = 48  # <= topk so federated and union top-K truncate nowhere


def make_arrays(rng, universe, n=32):
    """One batch over a SHARED key universe, feature columns included (so
    the signal planes carry mass through the delta too). Integer-valued
    floats keep every float32 sum exact — the bit-exact claims below rely
    on it."""
    ranks = rng.integers(0, len(universe), n)
    drop_b = np.where(rng.random(n) < 0.3,
                      rng.integers(1, 500, n), 0).astype(np.int32)
    return {
        "keys": universe[ranks],
        "bytes": rng.integers(1, 1000, n).astype(np.float32),
        "packets": rng.integers(1, 5, n).astype(np.int32),
        "rtt_us": rng.integers(1, 5000, n).astype(np.int32),
        "dns_latency_us": rng.integers(0, 100, n).astype(np.int32),
        "sampling": np.zeros(n, np.int32),
        "valid": np.ones(n, np.bool_),
        "tcp_flags": rng.integers(0, 1 << 9, n).astype(np.int32),
        "dscp": rng.integers(0, 64, n).astype(np.int32),
        "markers": rng.integers(0, 4, n).astype(np.int32),
        "drop_bytes": drop_b,
        "drop_packets": (drop_b > 0).astype(np.int32),
        "drop_cause": np.where(drop_b > 0, 2, 0).astype(np.int32),
    }


def agent_frames_and_union(seed=7, n_batches=2):
    """Fold per-agent streams AND the union stream; return (frames,
    union_state)."""
    rng = np.random.default_rng(seed)
    universe = rng.integers(0, 2**32, (N_DISTINCT, 10), dtype=np.uint32)
    roll = sk.make_roll_fn(CFG, with_tables=True)
    frames = []
    union = sk.init_state(CFG)
    for a in range(N_AGENTS):
        s = sk.init_state(CFG)
        for _ in range(n_batches):
            arrays = make_arrays(rng, universe)
            s = sk.ingest(s, arrays)
            union = sk.ingest(union, arrays)
        _, _, tables = roll(s)
        frames.append(fdelta.encode_frame(
            {k: np.asarray(v) for k, v in tables.items()},
            agent_id=f"agent-{a}", window=0, ts_ms=1234, dims=DIMS))
    return frames, union


# --- codec ---------------------------------------------------------------

class TestDeltaCodec:
    def test_roundtrip_zlib_and_raw(self):
        s = sk.init_state(CFG)
        arrays = make_arrays(np.random.default_rng(0),
                             np.random.default_rng(1).integers(
                                 0, 2**32, (8, 10), dtype=np.uint32))
        s = sk.ingest(s, arrays)
        tables = {k: np.asarray(v) for k, v in sk.state_tables(s).items()}
        for codec in (fdelta.CODEC_ZLIB, fdelta.CODEC_RAW):
            data = fdelta.encode_frame(tables, agent_id="a", window=3,
                                       ts_ms=99, dims=DIMS, codec=codec)
            frame = fdelta.decode_frame(data)
            assert frame.agent_id == "a"
            assert frame.window == 3
            assert frame.dims == DIMS
            for name, dt in fdelta.TABLE_SPEC:
                np.testing.assert_array_equal(
                    frame.tables[name],
                    tables[name].astype(dt),
                    err_msg=name)

    def test_zlib_compresses_sparse_tables(self):
        tables = {k: np.asarray(v)
                  for k, v in sk.state_tables(sk.init_state(CFG)).items()}
        raw = fdelta.encode_frame(tables, agent_id="a", window=0, ts_ms=0,
                                  dims=DIMS, codec=fdelta.CODEC_RAW)
        packed = fdelta.encode_frame(tables, agent_id="a", window=0,
                                     ts_ms=0, dims=DIMS)
        assert len(packed) < len(raw) / 10  # zeros deflate hard

    def test_version_mismatch_rejected(self):
        from netobserv_tpu.pb import sketch_delta_pb2 as pb
        tables = {k: np.asarray(v)
                  for k, v in sk.state_tables(sk.init_state(CFG)).items()}
        data = fdelta.encode_frame(tables, agent_id="a", window=0, ts_ms=0,
                                   dims=DIMS)
        msg = pb.SketchDelta.FromString(data)
        msg.version = fdelta.DELTA_FORMAT_VERSION + 1
        with pytest.raises(fdelta.DeltaVersionError):
            fdelta.decode_frame(msg.SerializeToString())

    def test_missing_tensor_rejected(self):
        from netobserv_tpu.pb import sketch_delta_pb2 as pb
        tables = {k: np.asarray(v)
                  for k, v in sk.state_tables(sk.init_state(CFG)).items()}
        data = fdelta.encode_frame(tables, agent_id="a", window=0, ts_ms=0,
                                   dims=DIMS)
        msg = pb.SketchDelta.FromString(data)
        del msg.tensors[0]
        with pytest.raises(fdelta.DeltaFrameError):
            fdelta.decode_frame(msg.SerializeToString())

    def test_garbage_rejected(self):
        with pytest.raises(fdelta.DeltaFrameError):
            fdelta.decode_frame(b"\xff" * 64)

    def _valid_frame_msg(self):
        from netobserv_tpu.pb import sketch_delta_pb2 as pb
        tables = {k: np.asarray(v)
                  for k, v in sk.state_tables(sk.init_state(CFG)).items()}
        data = fdelta.encode_frame(tables, agent_id="a", window=0, ts_ms=0,
                                   dims=DIMS)
        return pb.SketchDelta.FromString(data)

    def test_foreign_dtype_rejected(self):
        """A same-shape foreign dtype must never reach the jitted merge
        (it would change the abstract signature and force a retrace)."""
        msg = self._valid_frame_msg()
        assert msg.tensors[0].name == "cm_bytes"
        msg.tensors[0].dtype = 2  # <i4 where the spec says <f4
        with pytest.raises(fdelta.DeltaFrameError, match="dtype"):
            fdelta.decode_frame(msg.SerializeToString())

    def test_unknown_tensor_rejected(self):
        msg = self._valid_frame_msg()
        msg.tensors[0].name = "evil_extra"
        with pytest.raises(fdelta.DeltaFrameError):
            fdelta.decode_frame(msg.SerializeToString())

    def test_zlib_bomb_rejected_bounded(self):
        """A tensor whose zlib stream inflates past its declared shape is
        rejected WITHOUT allocating the inflated size (bounded inflate)."""
        import zlib
        msg = self._valid_frame_msg()
        t = msg.tensors[0]  # declared shape stays (depth, width)
        t.codec = fdelta.CODEC_ZLIB
        t.data = zlib.compress(b"\x00" * (64 << 20), 1)  # 64 MiB of zeros
        with pytest.raises(fdelta.DeltaFrameError, match="inflates"):
            fdelta.decode_frame(msg.SerializeToString())

    def test_declared_oversize_shape_rejected(self):
        msg = self._valid_frame_msg()
        t = msg.tensors[0]
        del t.shape[:]
        t.shape.extend([1 << 16, 1 << 16])  # 16 GiB declared
        with pytest.raises(fdelta.DeltaFrameError, match="cap"):
            fdelta.decode_frame(msg.SerializeToString())


# --- the acceptance test: federated == union -----------------------------

class TestFederatedEqualsUnion:
    @pytest.fixture(scope="class")
    def merged(self):
        frames, union = agent_frames_and_union()
        reports: list[dict] = []
        agg = FederationAggregator(sketch_cfg=CFG, window_s=3600,
                                   sink=reports.append)
        for f in frames:
            ack = agg.ingest_frame(f)
            assert ack.accepted == 1, ack.reason
        # grab the aggregate BEFORE the roll resets it (same window the
        # union state is still in)
        agg_state = agg._state
        with agg._lock:
            agg._close_window_locked()
        agg._publish_queued()
        yield agg, agg_state, union, reports, frames
        agg.close()

    def test_linear_and_max_structures_bit_exact(self, merged):
        agg, agg_state, union, _, _ = merged
        np.testing.assert_array_equal(np.asarray(agg_state.cm_bytes.counts),
                                      np.asarray(union.cm_bytes.counts))
        np.testing.assert_array_equal(np.asarray(agg_state.cm_pkts.counts),
                                      np.asarray(union.cm_pkts.counts))
        for name in ("hll_src", "hll_per_dst", "hll_per_src"):
            np.testing.assert_array_equal(
                np.asarray(getattr(agg_state, name).regs),
                np.asarray(getattr(union, name).regs), err_msg=name)
        for name in ("synack", "drop_causes", "dscp_bytes", "conv_fwd",
                     "conv_rev"):
            np.testing.assert_array_equal(
                np.asarray(getattr(agg_state, name)),
                np.asarray(getattr(union, name)), err_msg=name)
        np.testing.assert_array_equal(np.asarray(agg_state.ddos.rate),
                                      np.asarray(union.ddos.rate))
        np.testing.assert_array_equal(np.asarray(agg_state.syn.rate),
                                      np.asarray(union.syn.rate))
        np.testing.assert_array_equal(np.asarray(agg_state.hist_rtt.counts),
                                      np.asarray(union.hist_rtt.counts))
        assert float(agg_state.total_records) == float(union.total_records)
        assert float(agg_state.total_bytes) == float(union.total_bytes)

    def test_topk_table_bit_exact_vs_table_union(self, merged):
        """The persistent-slot analog of the old set equality: the
        aggregate's slot table must BIT-EXACT equal the sequential
        statemerge fold of the same frames into a fresh state — every
        field, including the churn metadata (prev_counts sum, first_seen
        min, epoch max). The raw-flow union's table is NOT the oracle any
        more: a set-associative table under congestion is path-dependent
        (an agent-local stream and the union stream legitimately keep
        slightly different marginal keys; the heavy ones agree — pinned
        by recall below)."""
        import jax.numpy as jnp

        from netobserv_tpu.federation import statemerge
        _, agg_state, union, _, frames = merged
        oracle = sk.init_state(CFG)
        for data in frames:
            frame = fdelta.decode_frame(data)
            # same churn re-basing the aggregator applies (localize_churn;
            # cluster window 0 — no roll happened before the capture)
            host = fdelta.localize_churn(fdelta.upgrade_tables(frame), 0)
            tabs = {k: jnp.asarray(np.ascontiguousarray(v))
                    for k, v in host.items()}
            oracle = statemerge.merge_tables(oracle, tabs)
        for name in ("words", "h1", "h2", "counts", "prev_counts",
                     "first_seen", "epoch", "valid"):
            np.testing.assert_array_equal(
                np.asarray(getattr(agg_state.heavy, name)),
                np.asarray(getattr(oracle.heavy, name)), err_msg=name)

    def test_topk_heavy_recall_vs_union(self, merged):
        """The quality claim the set equality used to carry: the TOP
        hitters by merged-CM mass chart in BOTH the federated table and
        the union stream's table (marginal tail keys may differ — the
        documented set-associative path dependence)."""
        _, agg_state, union, _, _ = merged

        def top_words(state, n):
            counts = np.asarray(state.heavy.counts)
            valid = np.asarray(state.heavy.valid)
            words = np.asarray(state.heavy.words)
            order = np.argsort(-np.where(valid, counts, -1.0))[:n]
            return {words[i].tobytes() for i in order if valid[i]}

        n = 16
        fed, un = top_words(agg_state, n), top_words(union, n)
        assert len(fed & un) / n >= 0.9

    def test_hll_cardinality_within_bound(self, merged):
        _, agg_state, union, reports, _ = merged
        # registers are bit-exact (above), so estimates agree; also sanity-
        # check the estimate against the true distinct count within the
        # standard HLL error bound (~1.04/sqrt(m), take 5 sigma)
        est = reports[0]["DistinctSrcEstimate"]
        m = 1 << CFG.hll_precision
        assert abs(est - N_DISTINCT) <= max(5 * 1.04 / np.sqrt(m)
                                            * N_DISTINCT, 3)

    def test_cluster_report_matches_union_roll(self, merged):
        _, _, union, reports, _ = merged
        rep = reports[0]
        _, union_rep = sk.make_roll_fn(CFG)(union)
        assert rep["Records"] == float(union_rep.total_records)
        assert rep["Bytes"] == float(union_rep.total_bytes)
        assert rep["DistinctSrcEstimate"] == float(union_rep.distinct_src)
        np.testing.assert_array_equal(
            np.asarray([rep["RttQuantilesUs"][q]
                        for q in ("0.5", "0.9", "0.99")]),
            np.asarray(union_rep.rtt_quantiles_us)[[0, 1, 3]])
        assert rep["Type"] == "federation_window_report"
        assert rep["Agents"] == [f"agent-{a}" for a in range(N_AGENTS)]

    def test_zero_postwarmup_retraces(self, merged):
        agg, _, _, _, _ = merged
        # the watchdog wrappers themselves: N_AGENTS merges through ONE
        # compile, the roll through one compile — any retrace means a
        # frame changed shape past validation
        assert agg._fold.calls >= N_AGENTS
        assert agg._fold.compiles == 1
        assert agg._fold.retraces == 0
        assert agg._roll.retraces == 0


# --- rejection / robustness ---------------------------------------------

class TestAggregatorRejection:
    @pytest.fixture()
    def agg(self):
        a = FederationAggregator(sketch_cfg=CFG, window_s=3600,
                                 sink=lambda obj: None)
        yield a
        a.close()

    def test_shape_mismatch_rejected_not_fatal(self, agg):
        other_cfg = sk.SketchConfig(cm_depth=2, cm_width=512,
                                    hll_precision=6, perdst_buckets=32,
                                    perdst_precision=4, persrc_buckets=32,
                                    persrc_precision=4, topk=32,
                                    hist_buckets=64, ewma_buckets=32)
        _, _, tables = sk.make_roll_fn(other_cfg, with_tables=True)(
            sk.init_state(other_cfg))
        frame = fdelta.encode_frame(
            {k: np.asarray(v) for k, v in tables.items()},
            agent_id="skewed", window=0, ts_ms=0,
            dims={"cm_depth": 2, "cm_width": 512, "hll_precision": 6,
                  "topk": 32, "ewma_buckets": 32})
        ack = agg.ingest_frame(frame)
        assert ack.accepted == 0
        assert "shape" in ack.reason or "geometry" in ack.reason
        # the plane survives: a good frame still merges
        good, _ = agent_frames_and_union(seed=1, n_batches=1)
        assert agg.ingest_frame(good[0]).accepted == 1

    def test_garbage_and_version_rejected(self, agg):
        assert agg.ingest_frame(b"not a frame").accepted == 0
        from netobserv_tpu.pb import sketch_delta_pb2 as pb
        frames, _ = agent_frames_and_union(seed=2, n_batches=1)
        msg = pb.SketchDelta.FromString(frames[0])
        msg.version = 999
        ack = agg.ingest_frame(msg.SerializeToString())
        assert ack.accepted == 0 and "version" in ack.reason

    def test_rejections_counted(self):
        from netobserv_tpu.metrics.registry import Metrics
        m = Metrics()
        agg = FederationAggregator(sketch_cfg=CFG, window_s=3600,
                                   metrics=m, sink=lambda obj: None)
        try:
            agg.ingest_frame(b"junk")
            frames, _ = agent_frames_and_union(seed=3, n_batches=1)
            agg.ingest_frame(frames[0])
        finally:
            agg.close()
        get = m.registry.get_sample_value
        assert get("ebpf_agent_federation_deltas_total",
                   {"result": "decode_error"}) == 1
        assert get("ebpf_agent_federation_deltas_total",
                   {"result": "ok"}) == 1
        assert get("ebpf_agent_federation_delta_bytes_total") > 0


# --- transport: gRPC push + retry sink -----------------------------------

class TestTransport:
    def test_grpc_push_end_to_end(self):
        from netobserv_tpu.exporter.federation import FederationDeltaSink
        from netobserv_tpu.grpc.federation import (
            start_federation_collector,
        )
        agg = FederationAggregator(sketch_cfg=CFG, window_s=3600,
                                   sink=lambda obj: None)
        server, port, _ = start_federation_collector(
            port=0, handler=agg.ingest_frame)
        try:
            sink = FederationDeltaSink("127.0.0.1", port)
            frames, _ = agent_frames_and_union(seed=4, n_batches=1)
            assert sink(frames[0]) is True
            assert agg.status()["frames_total"] == 1
            sink.close()
        finally:
            server.stop(grace=None)
            agg.close()

    def test_sink_swallows_dead_aggregator(self):
        from netobserv_tpu.exporter.federation import FederationDeltaSink
        from netobserv_tpu.metrics.registry import Metrics
        m = Metrics()
        sink = FederationDeltaSink("127.0.0.1", 1, retries=2,
                                   backoff_initial_s=0.01, timeout_s=0.2,
                                   metrics=m)
        assert sink(b"frame") is False  # swallowed, never raises
        assert m.registry.get_sample_value(
            "ebpf_agent_federation_deltas_sent_total",
            {"result": "error"}) == 1
        sink.close()

    def test_bad_frame_acked_not_crash(self):
        """A malformed frame over the wire gets accepted=0, and the server
        keeps serving (exporters/servers never crash the pipeline)."""
        from netobserv_tpu.grpc.federation import (
            FederationClient, start_federation_collector,
        )
        agg = FederationAggregator(sketch_cfg=CFG, window_s=3600,
                                   sink=lambda obj: None)
        server, port, _ = start_federation_collector(
            port=0, handler=agg.ingest_frame)
        try:
            client = FederationClient("127.0.0.1", port)
            ack = client.send(b"\x00garbage")
            assert ack.accepted == 0
            frames, _ = agent_frames_and_union(seed=5, n_batches=1)
            assert client.send(frames[0]).accepted == 1
            client.close()
        finally:
            server.stop(grace=None)
            agg.close()


# --- agent-side exporter seam --------------------------------------------

class TestExporterDeltaSeam:
    def test_roll_publishes_delta_frame(self):
        from netobserv_tpu.exporter.tpu_sketch import TpuSketchExporter
        from tests.test_exporters import make_record
        frames: list[bytes] = []
        reports: list[dict] = []
        exp = TpuSketchExporter(batch_size=16, window_s=3600,
                                sketch_cfg=CFG, sink=reports.append,
                                delta_sink=frames.append,
                                agent_id="test-agent")
        exp.export_batch([make_record(sport=1000 + i) for i in range(16)])
        exp.flush()
        exp.close()
        assert reports and frames
        frame = fdelta.decode_frame(frames[0])
        assert frame.agent_id == "test-agent"
        assert frame.dims == DIMS
        assert float(frame.tables["scalars"][0]) == 16.0  # records

    def test_delta_sink_failure_keeps_report(self):
        from netobserv_tpu.exporter.tpu_sketch import TpuSketchExporter
        from tests.test_exporters import make_record

        def boom(frame):
            raise RuntimeError("aggregator exploded")
        reports: list[dict] = []
        exp = TpuSketchExporter(batch_size=16, window_s=3600,
                                sketch_cfg=CFG, sink=reports.append,
                                delta_sink=boom)
        exp.export_batch([make_record() for _ in range(16)])
        exp.flush()
        exp.close()
        assert reports, "delta failure must not lose the local report"

    def test_decay_mode_disables_delta(self):
        from netobserv_tpu.exporter.tpu_sketch import TpuSketchExporter
        exp = TpuSketchExporter(batch_size=16, window_s=3600,
                                sketch_cfg=CFG, sink=lambda obj: None,
                                delta_sink=lambda f: True,
                                decay_factor=0.5)
        try:
            assert exp._delta_sink is None
        finally:
            exp.close()

    def test_delta_export_fault_point(self):
        """The sketch.delta_export fault point fires per window at the
        serialize boundary; a crash there loses the frame, not the
        report."""
        from netobserv_tpu.exporter.tpu_sketch import TpuSketchExporter
        from netobserv_tpu.utils import faultinject
        from tests.test_exporters import make_record
        frames: list[bytes] = []
        reports: list[dict] = []
        exp = TpuSketchExporter(batch_size=16, window_s=3600,
                                sketch_cfg=CFG, sink=reports.append,
                                delta_sink=frames.append)
        faultinject.arm("sketch.delta_export", "crash", times=1)
        try:
            exp.export_batch([make_record() for _ in range(16)])
            exp.flush()
            # the armed window: frame lost, report still published
            assert faultinject.hits.get("sketch.delta_export") == 1
            assert reports and not frames
        finally:
            faultinject.clear()
            exp.close()
        # disarmed close-time window publishes its (empty-window) frame —
        # empty frames are deliberate, they keep agent staleness fresh
        assert frames


# --- query surface --------------------------------------------------------

class TestQuerySurface:
    @pytest.fixture()
    def served(self):
        from netobserv_tpu.federation.query import start_query_server
        agg = FederationAggregator(sketch_cfg=CFG, window_s=3600,
                                   sink=lambda obj: None)
        srv = start_query_server(
            agg, port=0,
            health_source=lambda: {"status": "Started", "degraded": False,
                                   "stages": {}})
        port = srv.server_address[1]

        def get(path, expect=200):
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}{path}", timeout=10) as r:
                    return r.status, json.loads(r.read())
            except urllib.error.HTTPError as e:
                return e.code, json.loads(e.read())
        yield agg, get
        srv.shutdown()
        agg.close()

    def test_routes(self, served):
        agg, get = served
        code, _ = get("/federation/topk")
        assert code == 503  # no window published yet
        frames, _ = agent_frames_and_union(seed=6, n_batches=1)
        for f in frames:
            assert agg.ingest_frame(f).accepted == 1
        agg.flush()
        code, topk = get("/federation/topk?n=5")
        assert code == 200 and len(topk["topk"]) == 5
        code, card = get("/federation/cardinality")
        assert code == 200 and card["records"] > 0
        code, victims = get("/federation/victims")
        assert code == 200 and "ddos" in victims
        code, status = get("/federation/status")
        assert code == 200
        assert sorted(status["agents"]) == [f"agent-{a}"
                                            for a in range(N_AGENTS)]
        code, health = get("/healthz")
        assert code == 200 and health["status"] == "Started"
        code, freq = get("/federation/frequency?src=10.0.0.1&dst=10.0.0.2")
        assert code == 200 and "est_bytes" in freq
        code, err = get("/federation/frequency")  # missing params
        assert code == 400


# --- mesh fold (slow tier: 8-virtual-device compile-heavy) ----------------

@pytest.mark.slow
class TestMeshAggregator:
    def test_mesh_fold_matches_single_device(self):
        frames, union = agent_frames_and_union(seed=8)
        reports: list[dict] = []
        agg = FederationAggregator(sketch_cfg=CFG, window_s=3600,
                                   mesh_shape="4x1", sink=reports.append)
        try:
            for f in frames:
                assert agg.ingest_frame(f).accepted == 1, "mesh merge"
            agg.flush()
        finally:
            agg.close()
        assert reports
        rep = reports[0]
        _, union_rep = sk.make_roll_fn(CFG)(union)
        assert rep["Records"] == float(union_rep.total_records)
        assert rep["Bytes"] == float(union_rep.total_bytes)
        assert rep["DistinctSrcEstimate"] == float(union_rep.distinct_src)
        fed = {(h["SrcAddr"], h["DstAddr"], h["SrcPort"], h["DstPort"],
                h["EstBytes"]) for h in rep["HeavyHitters"]}
        from netobserv_tpu.exporter.tpu_sketch import report_to_json
        un = {(h["SrcAddr"], h["DstAddr"], h["SrcPort"], h["DstPort"],
               h["EstBytes"])
              for h in report_to_json(union_rep,
                                      max_heavy=64)["HeavyHitters"]}
        assert fed == un

    def test_width_sharded_mesh_refused(self):
        from netobserv_tpu.parallel import MeshSpec, make_mesh
        from netobserv_tpu.parallel import merge as pmerge
        mesh = make_mesh(MeshSpec(data=2, sketch=2))
        # the FOLD of whole-width delta tables has no owner-sharded form;
        # the roll's table snapshot does (PR 34: per-owner-shard planes,
        # tests/test_width_sharded_served.py)
        with pytest.raises(ValueError):
            pmerge.make_fold_delta_fn(mesh, CFG)


# --- service wiring (ephemeral ports, in-process) -------------------------

class TestAggregatorService:
    def test_service_end_to_end(self):
        from netobserv_tpu.config import AgentConfig
        from netobserv_tpu.exporter.federation import FederationDeltaSink
        from netobserv_tpu.federation.service import (
            FederationAggregatorService,
        )
        cfg = AgentConfig()
        cfg.sketch_cm_depth, cfg.sketch_cm_width = CFG.cm_depth, CFG.cm_width
        cfg.sketch_hll_precision, cfg.sketch_topk = (CFG.hll_precision,
                                                     CFG.topk)
        cfg.federation_listen_port = 0
        cfg.federation_query_port = 0
        cfg.federation_window = 3600.0
        reports: list[dict] = []
        svc = FederationAggregatorService(cfg, sink=reports.append)
        svc.start()
        try:
            # NOTE: the service's SketchConfig comes from from_agent_config
            # (production dims for the per-* grids), so build frames with
            # the SAME config instead of the test CFG
            from netobserv_tpu.sketch.state import SketchConfig
            svc_cfg = SketchConfig.from_agent_config(cfg)
            roll = sk.make_roll_fn(svc_cfg, with_tables=True)
            s = sk.ingest(sk.init_state(svc_cfg), make_arrays(
                np.random.default_rng(0),
                np.random.default_rng(1).integers(0, 2**32, (16, 10),
                                                  dtype=np.uint32)))
            _, _, tables = roll(s)
            frame = fdelta.encode_frame(
                {k: np.asarray(v) for k, v in tables.items()},
                agent_id="svc-agent", window=0, ts_ms=0,
                dims={"cm_depth": svc_cfg.cm_depth,
                      "cm_width": svc_cfg.cm_width,
                      "hll_precision": svc_cfg.hll_precision,
                      "topk": svc_cfg.topk,
                      "ewma_buckets": svc_cfg.ewma_buckets})
            sink = FederationDeltaSink("127.0.0.1", svc.grpc_port)
            assert sink(frame) is True
            sink.close()
            svc.aggregator.flush()
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{svc.query_port}/federation/status",
                    timeout=10) as r:
                status = json.loads(r.read())
            assert "svc-agent" in status["agents"]
            assert svc.health_snapshot()["status"] == "Started"
        finally:
            svc.shutdown()
        assert reports
