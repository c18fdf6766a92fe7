"""Fake-driven end-to-end pipeline tests (reference analog:
`pkg/agent/agent_test.go` — full in-process pipeline over injected data)."""

import io
import json
import queue
import threading
import time

import numpy as np
import pytest

from netobserv_tpu.agent import FlowsAgent, Status
from netobserv_tpu.config import load_config
from netobserv_tpu.datapath.fetcher import EvictedFlows, FakeFetcher
from netobserv_tpu.exporter.base import Exporter
from netobserv_tpu.exporter.stdout_json import StdoutJSONExporter
from netobserv_tpu.model import binfmt
from netobserv_tpu.model.flow import GlobalCounter, ip_to_16


def make_events(n, sport0=1000, nbytes=100):
    events = np.zeros(n, dtype=binfmt.FLOW_EVENT_DTYPE)
    now = time.clock_gettime_ns(time.CLOCK_MONOTONIC)
    for i in range(n):
        events[i]["key"]["src_ip"] = np.frombuffer(ip_to_16("10.0.0.1"), np.uint8)
        events[i]["key"]["dst_ip"] = np.frombuffer(ip_to_16("10.0.0.2"), np.uint8)
        events[i]["key"]["src_port"] = sport0 + i
        events[i]["key"]["dst_port"] = 443
        events[i]["key"]["proto"] = 6
        events[i]["stats"]["bytes"] = nbytes
        events[i]["stats"]["packets"] = 2
        events[i]["stats"]["first_seen_ns"] = now - 10**9
        events[i]["stats"]["last_seen_ns"] = now
        events[i]["stats"]["eth_protocol"] = 0x0800
        events[i]["stats"]["if_index_first"] = 1
    return events


class CollectExporter(Exporter):
    name = "collect"

    def __init__(self):
        self.batches: "queue.Queue[list]" = queue.Queue()

    def export_batch(self, records):
        self.batches.put(records)


def make_agent(fake, exporter, **env):
    cfg = load_config(environ={
        "EXPORT": "stdout", "CACHE_ACTIVE_TIMEOUT": "100ms",
        "BUFFERS_LENGTH": "10", **env})
    return FlowsAgent(cfg, fake, exporter)


class TestAgentPipeline:
    def test_end_to_end_map_path(self):
        fake = FakeFetcher()
        out = CollectExporter()
        agent = make_agent(fake, out)
        stop = threading.Event()
        t = threading.Thread(target=agent.run, args=(stop,), daemon=True)
        t.start()
        try:
            fake.bump_counter(GlobalCounter.FILTER_ACCEPT, 5)
            fake.inject_events(make_events(3))
            batch = out.batches.get(timeout=3)
            assert len(batch) == 3
            assert batch[0].key.src == "10.0.0.1"
            assert batch[0].bytes_ == 100
            assert agent.status == Status.STARTED
        finally:
            stop.set()
            t.join(timeout=5)
        assert agent.status == Status.STOPPED
        assert fake.closed

    def test_ringbuf_fallback_path(self):
        fake = FakeFetcher()
        out = CollectExporter()
        # a 2s accounter window: both pre-queued singles are always accounted
        # long before the first eviction, even under heavy host load
        agent = make_agent(fake, out, ENABLE_FLOWS_RINGBUF_FALLBACK="true",
                           CACHE_ACTIVE_TIMEOUT="2s")
        # two ringbuf singles for the same flow must be re-aggregated; queue
        # them BEFORE the agent starts so they land in one accounter window
        ev = make_events(1, nbytes=40)
        fake.inject_ringbuf(ev)
        fake.inject_ringbuf(ev)
        stop = threading.Event()
        t = threading.Thread(target=agent.run, args=(stop,), daemon=True)
        t.start()
        try:
            deadline = time.monotonic() + 8
            merged = None
            while time.monotonic() < deadline:
                try:
                    batch = out.batches.get(timeout=0.5)
                except queue.Empty:
                    continue
                for r in batch:
                    if r.packets:
                        merged = r
                if merged and merged.bytes_ == 80:
                    break
            assert merged is not None
            assert merged.bytes_ == 80  # accumulated, not duplicated
            assert merged.packets == 4
        finally:
            stop.set()
            t.join(timeout=5)

    def test_final_eviction_on_shutdown(self):
        fake = FakeFetcher()
        out = CollectExporter()
        agent = make_agent(fake, out, CACHE_ACTIVE_TIMEOUT="30s")
        stop = threading.Event()
        t = threading.Thread(target=agent.run, args=(stop,), daemon=True)
        t.start()
        time.sleep(0.2)
        # injected after start; ticker (30s) won't fire — shutdown must drain
        fake.inject_events(make_events(2))
        stop.set()
        t.join(timeout=5)
        batch = out.batches.get(timeout=1)
        assert len(batch) == 2


class TestStdoutExporter:
    def test_json_lines(self):
        from netobserv_tpu.model.record import records_from_events
        buf = io.StringIO()
        exp = StdoutJSONExporter(stream=buf)
        recs = records_from_events(make_events(2))
        exp.export_batch(recs)
        lines = [json.loads(l) for l in buf.getvalue().splitlines()]
        assert len(lines) == 2
        assert lines[0]["SrcAddr"] == "10.0.0.1"
        assert lines[0]["DstPort"] == 443

    def test_flp_map_format(self):
        from netobserv_tpu.exporter.flp_map import record_to_map
        from netobserv_tpu.model.record import records_from_events
        recs = records_from_events(make_events(1))
        m = record_to_map(recs[0])
        assert m["SrcAddr"] == "10.0.0.1"
        assert m["Proto"] == 6
        assert m["SrcMac"] == "00:00:00:00:00:00"
        assert "TimeFlowStartMs" in m and "AgentIP" in m


class TestTpuSketchExporter:
    def test_reports_heavy_hitters(self):
        from netobserv_tpu.exporter.tpu_sketch import TpuSketchExporter
        from netobserv_tpu.model.record import records_from_events
        from netobserv_tpu.sketch.state import SketchConfig

        reports = []
        exp = TpuSketchExporter(
            batch_size=64, window_s=3600,  # manual window close
            sketch_cfg=SketchConfig(cm_depth=2, cm_width=1 << 10,
                                    hll_precision=6, perdst_buckets=32,
                                    perdst_precision=4, topk=16,
                                    hist_buckets=64, ewma_buckets=32),
            mesh_shape="", sink=reports.append)
        # one elephant flow + background
        elephant = make_events(1, sport0=7777, nbytes=1_000_000)
        exp.export_batch(records_from_events(elephant))
        exp.export_batch(records_from_events(make_events(30, nbytes=10)))
        exp.flush()
        assert len(reports) == 1
        rep = reports[0]
        assert rep["Type"] == "sketch_window_report"
        assert rep["Records"] == 31
        top = rep["HeavyHitters"][0]
        assert top["SrcPort"] == 7777
        assert top["EstBytes"] >= 1_000_000
        assert rep["DistinctSrcEstimate"] > 0

    def test_columnar_fast_path(self):
        from netobserv_tpu.datapath.fetcher import EvictedFlows
        from netobserv_tpu.exporter.tpu_sketch import TpuSketchExporter
        from netobserv_tpu.sketch.state import SketchConfig

        reports = []
        exp = TpuSketchExporter(
            batch_size=8192,  # larger than the injected evictions: the
            # window drain must still fold the partial batch
            window_s=3600,
            sketch_cfg=SketchConfig(cm_depth=2, cm_width=1 << 10,
                                    hll_precision=6, perdst_buckets=32,
                                    perdst_precision=4, topk=16,
                                    hist_buckets=64, ewma_buckets=32),
            sink=reports.append)
        assert exp.supports_columnar
        import numpy as np

        from netobserv_tpu.model import binfmt
        extra = np.zeros(3, dtype=binfmt.EXTRA_REC_DTYPE)
        extra["rtt_ns"] = [5_000_000, 1_000_000, 9_000_000]
        exp.export_evicted(EvictedFlows(make_events(3), extra=extra))
        exp.export_evicted(EvictedFlows(make_events(2, sport0=9000)))
        exp.flush()
        assert len(reports) == 1
        rep = reports[0]
        assert rep["Records"] == 5
        # rtt feature column reached the histogram (values in ms range)
        assert rep["RttQuantilesUs"]["0.99"] > 1000

    def test_window_rolls_and_resets(self):
        from netobserv_tpu.exporter.tpu_sketch import TpuSketchExporter
        from netobserv_tpu.model.record import records_from_events
        from netobserv_tpu.sketch.state import SketchConfig

        reports = []
        exp = TpuSketchExporter(
            batch_size=8, window_s=3600,
            sketch_cfg=SketchConfig(cm_depth=2, cm_width=256, hll_precision=6,
                                    perdst_buckets=32, perdst_precision=4,
                                    topk=8, hist_buckets=64, ewma_buckets=32),
            sink=reports.append)
        exp.export_batch(records_from_events(make_events(5)))
        exp.flush()
        exp.export_batch(records_from_events(make_events(7)))
        exp.flush()
        assert [r["Window"] for r in reports] == [0, 1]
        assert reports[0]["Records"] == 5
        assert reports[1]["Records"] == 7  # reset between windows


class TestDecayWindows:
    def test_decay_keeps_half_the_mass(self):
        from netobserv_tpu.exporter.tpu_sketch import TpuSketchExporter
        from netobserv_tpu.model.record import records_from_events
        from netobserv_tpu.sketch.state import SketchConfig

        reports = []
        exp = TpuSketchExporter(
            batch_size=8, window_s=3600, decay_factor=0.5,
            sketch_cfg=SketchConfig(cm_depth=2, cm_width=256, hll_precision=6,
                                    perdst_buckets=32, perdst_precision=4,
                                    topk=8, hist_buckets=64, ewma_buckets=32),
            sink=reports.append)
        exp.export_batch(records_from_events(make_events(4, nbytes=1000)))
        exp.flush()
        exp.flush()  # no new traffic: the decayed mass remains visible
        assert reports[0]["Bytes"] == 4000
        assert reports[1]["Bytes"] == 2000  # decayed by 0.5, not reset to 0
        # heavy-hitter table survives decay AND its counts decay consistently
        assert len(reports[1]["HeavyHitters"]) > 0
        assert reports[1]["HeavyHitters"][0]["EstBytes"] == 500.0
        total_hh = sum(h["EstBytes"] for h in reports[1]["HeavyHitters"])
        assert total_hh <= reports[1]["Bytes"] + 1e-6


def test_port_scan_surfaces_in_exporter_window_report():
    """Agent-level scan detection: a scanning source fed through the FULL
    TpuSketchExporter pipeline (records -> batches -> device fold -> window
    roll -> JSON sink) must surface in PortScanSuspectBuckets."""
    from netobserv_tpu.exporter.tpu_sketch import TpuSketchExporter
    from netobserv_tpu.model.flow import FlowKey
    from netobserv_tpu.model.record import Record
    from netobserv_tpu.sketch.state import SketchConfig

    def rec(src, dst, dport):
        return Record(
            key=FlowKey.make(src, dst, 40000, dport, 6), bytes_=60,
            packets=1, eth_protocol=0x0800, tcp_flags=0x02, direction=1,
            src_mac=b"\x02" * 6, dst_mac=b"\x04" * 6, if_index=3,
            interface="eth0", dscp=0, sampling=0,
            agent_ip="192.0.2.1")

    reports = []
    exp = TpuSketchExporter(
        batch_size=128, window_s=3600,
        sketch_cfg=SketchConfig(cm_depth=2, cm_width=1 << 10,
                                hll_precision=6, perdst_buckets=32,
                                perdst_precision=4, topk=16, hist_buckets=64,
                                ewma_buckets=32, persrc_buckets=64,
                                persrc_precision=6),
        mesh_shape="", sink=reports.append,
        scan_fanout_threshold=200)
    # the scanner: one source sweeping 1024 distinct (dst, port) pairs
    scan = [rec("10.9.9.9", f"10.0.{i % 250}.{i // 250 + 1}", 1 + i % 1024)
            for i in range(1024)]
    # normal client
    normal = [rec("10.1.1.1", "10.2.2.2", 443) for _ in range(32)]
    exp.export_batch(scan)
    exp.export_batch(normal)
    exp.flush()
    assert reports, "no window report emitted"
    suspects = reports[-1]["PortScanSuspectBuckets"]
    assert suspects, "scanner not reported through the exporter pipeline"
    assert suspects[0]["distinct_dst_port_pairs"] > 500
    exp.close()


def test_syn_flood_surfaces_in_exporter_window_report():
    """Agent-level SYN-flood detection: a spoofed flood (many half-open SYN
    records to one victim, few SYN-ACK responses) through the FULL
    TpuSketchExporter pipeline must surface in SynFloodSuspectBuckets;
    a busy-but-healthy service (every SYN answered) must not."""
    from netobserv_tpu.exporter.tpu_sketch import TpuSketchExporter
    from netobserv_tpu.model.flow import FlowKey
    from netobserv_tpu.model.record import Record
    from netobserv_tpu.sketch.state import SketchConfig

    def rec(src, dst, sport, dport, flags):
        return Record(
            key=FlowKey.make(src, dst, sport, dport, 6), bytes_=60,
            packets=1, eth_protocol=0x0800, tcp_flags=flags, direction=1,
            src_mac=b"\x02" * 6, dst_mac=b"\x04" * 6, if_index=3,
            interface="eth0", dscp=0, sampling=0, agent_ip="192.0.2.1")

    reports = []
    exp = TpuSketchExporter(
        batch_size=128, window_s=3600,
        sketch_cfg=SketchConfig(cm_depth=2, cm_width=1 << 10,
                                hll_precision=6, perdst_buckets=32,
                                perdst_precision=4, topk=16, hist_buckets=64,
                                ewma_buckets=64),
        sink=reports.append, synflood_min=64, synflood_ratio=8.0)
    victim = "10.0.0.5"
    # the flood: 512 spoofed sources, SYN never ACKed (half-open), and the
    # victim manages only a handful of SYN-ACK responses
    flood = [rec(f"172.16.{i % 200}.{i % 250 + 1}", victim,
                 1024 + i, 80, 0x02) for i in range(512)]
    flood += [rec(victim, f"172.16.0.{i + 1}", 80, 2000 + i, 0x112)
              for i in range(4)]
    # a busy healthy service: 200 clients, every handshake completes (client
    # flows carry SYN|ACK, server responses carry SYN-ACK)
    healthy = [rec(f"10.7.0.{i % 250 + 1}", "10.0.0.9", 3000 + i, 443, 0x12)
               for i in range(200)]
    healthy += [rec("10.0.0.9", f"10.7.0.{i % 250 + 1}", 443, 3000 + i, 0x112)
                for i in range(200)]
    exp.export_batch(flood)
    exp.export_batch(healthy)
    exp.flush()  # close() below rolls one more (empty) window
    assert reports, "no window report emitted"
    suspects = reports[0]["SynFloodSuspectBuckets"]
    assert suspects, "flood not reported through the exporter pipeline"
    assert suspects[0]["syn"] >= 500
    assert suspects[0]["synack"] <= 8
    # exactly the victim's bucket: the healthy service bucket stays quiet
    assert len(suspects) == 1
    exp.close()


def test_drop_storm_surfaces_in_exporter_window_report():
    """Agent-level drop-anomaly detection over the COLUMNAR fast path: two
    calm windows seed the EWMA baseline, then a drop storm (kernel drops
    record array riding the eviction) must push the victim bucket's
    dropped-bytes z-score over the threshold and surface cause totals."""
    from netobserv_tpu.exporter.tpu_sketch import TpuSketchExporter
    from netobserv_tpu.sketch.state import SketchConfig

    reports = []
    exp = TpuSketchExporter(
        batch_size=64, window_s=3600,
        sketch_cfg=SketchConfig(cm_depth=2, cm_width=1 << 10,
                                hll_precision=6, perdst_buckets=32,
                                perdst_precision=4, topk=16, hist_buckets=64,
                                ewma_buckets=64),
        sink=reports.append, drop_z_threshold=6.0)

    def evict(drop_bytes, cause=2):
        ev = make_events(64)
        drops = np.zeros(64, dtype=binfmt.DROPS_REC_DTYPE)
        if drop_bytes:
            drops["bytes"] = drop_bytes
            drops["packets"] = 3
            drops["latest_cause"] = cause
        return EvictedFlows(ev, drops=drops if drop_bytes else None)

    for _ in range(2):  # calm baseline windows (EWMA warmup)
        exp.export_evicted(evict(0))
        exp.flush()
    exp.export_evicted(evict(1400, cause=5))
    exp.flush()  # close() below rolls one more (empty) window
    storm = reports[2]
    assert storm["DropBytes"] == 1400.0 * 64
    assert storm["DropPackets"] == 3.0 * 64
    assert storm["DropCauses"] == {"5": 3.0 * 64}
    assert storm["DropAnomalyBuckets"], "drop storm not reported"
    calm = reports[1]
    assert calm["DropBytes"] == 0.0 and not calm["DropAnomalyBuckets"]
    exp.close()


def test_decay_preserves_signal_planes():
    """Decay-mode window rolls must treat the feature-lane planes
    consistently: linear histograms (drop causes, DSCP bytes) decay like
    the latency hists; the SYN-ACK window accumulator resets with its
    paired EWMA rate; totals decay."""
    import numpy as np

    from netobserv_tpu.sketch import state as sk

    cfg = sk.SketchConfig(cm_width=1 << 10, topk=16, ewma_buckets=32)
    n = 16
    arrays = {
        "keys": np.random.default_rng(0).integers(
            0, 2**32, (n, 10)).astype(np.uint32),
        "bytes": np.full(n, 100.0, np.float32),
        "packets": np.ones(n, np.int32),
        "rtt_us": np.zeros(n, np.int32),
        "dns_latency_us": np.zeros(n, np.int32),
        "sampling": np.zeros(n, np.int32),
        "valid": np.ones(n, np.bool_),
        "tcp_flags": np.full(n, 0x102, np.int32),  # SYN-ACK responses
        "dscp": np.full(n, 46, np.int32),
        "markers": np.full(n, 3, np.int32),        # quic + nat
        "drop_bytes": np.full(n, 10, np.int32),
        "drop_packets": np.ones(n, np.int32),
        "drop_cause": np.full(n, 4, np.int32),
    }
    s = sk.ingest(sk.init_state(cfg), arrays)
    assert float(s.synack.sum()) == n
    s2 = sk.decay_state(s, 0.5)
    assert float(s2.drop_causes.sum()) == n / 2        # linear: decays
    assert float(s2.dscp_bytes.sum()) == 100.0 * n / 2
    assert float(s2.total_drop_bytes) == 10 * n / 2
    assert float(s2.quic_records) == n / 2
    assert float(s2.nat_records) == n / 2
    assert float(s2.synack.sum()) == 0.0               # paired w/ EWMA rate


def test_window_analytics_gauges():
    """Window rolls publish last-window analytics to Prometheus (records,
    drop bytes, suspect counts per signal) so operators can alert off the
    metrics endpoint, not only the JSON stream."""
    from prometheus_client import CollectorRegistry

    from netobserv_tpu.exporter.tpu_sketch import TpuSketchExporter
    from netobserv_tpu.metrics.registry import Metrics, MetricsSettings
    from netobserv_tpu.sketch.state import SketchConfig

    m = Metrics(MetricsSettings(), registry=CollectorRegistry())
    exp = TpuSketchExporter(
        batch_size=64, window_s=3600,
        sketch_cfg=SketchConfig(cm_depth=2, cm_width=1 << 10,
                                hll_precision=6, perdst_buckets=32,
                                perdst_precision=4, topk=16, hist_buckets=64,
                                ewma_buckets=64),
        sink=lambda rep: None, metrics=m)
    ev = make_events(40)
    drops = np.zeros(40, dtype=binfmt.DROPS_REC_DTYPE)
    drops["bytes"] = 100
    drops["packets"] = 1
    exp.export_evicted(EvictedFlows(ev, drops=drops))
    exp.flush()  # close() rolls one more (empty) window afterwards
    assert m.sketch_window_records._value.get() == 40.0
    assert m.sketch_window_drop_bytes._value.get() == 100.0 * 40
    for sig in ("ddos", "port_scan", "syn_flood", "drop_storm"):
        assert m.sketch_window_suspects.labels(sig)._value.get() == 0.0
    exp.close()
    assert m.sketch_window_records._value.get() == 0.0  # last window wins


def test_ingest_never_retraces_across_windows():
    """CLAUDE.md invariant pinned: folding evictions of VARYING live counts
    (padding), rolling windows, and folding again must all hit ONE compiled
    ingest executable — a retrace would silently tank steady-state rate."""
    from netobserv_tpu.exporter.tpu_sketch import TpuSketchExporter
    from netobserv_tpu.sketch.state import SketchConfig

    exp = TpuSketchExporter(
        batch_size=64, window_s=3600,
        sketch_cfg=SketchConfig(cm_depth=2, cm_width=1 << 10,
                                hll_precision=6, perdst_buckets=32,
                                perdst_precision=4, topk=16, hist_buckets=64,
                                ewma_buckets=32),
        sink=lambda rep: None)
    # warm: first fold compiles; a donated-state layout respecialization
    # may add ONE more executable on call 2 — steady state starts here
    for n in (64, 17):
        exp.export_evicted(EvictedFlows(make_events(n)))
        exp.flush()
    ingest_jit = exp._ring._ingests[1]  # batch-sized evictions: x1 only
    warm = ingest_jit._cache_size()
    assert warm <= 2, f"ingest compiled {warm} variants during warmup"
    for n in (64, 3, 64, 17, 5):
        exp.export_evicted(EvictedFlows(make_events(n)))
        exp.flush()  # windows roll between batches too
    assert ingest_jit._cache_size() == warm, "steady-state ingest retraced"
    fallback = getattr(exp._ring, "_ingest_fallback", None)
    if fallback is not None:
        assert fallback._cache_size() == 0, "dense fallback ran unexpectedly"
    exp.close()


def test_one_way_conversation_surfaces_in_exporter_window_report():
    """Conversation-asymmetry detection through the FULL exporter pipeline:
    a one-way elephant transfer (A->B only) must surface in
    AsymmetricConversationBuckets; a balanced conversation (both directions)
    must not — regardless of flow direction order."""
    from netobserv_tpu.exporter.tpu_sketch import TpuSketchExporter
    from netobserv_tpu.model.flow import FlowKey
    from netobserv_tpu.model.record import Record
    from netobserv_tpu.sketch.state import SketchConfig

    def rec(src, dst, sport, dport, nbytes):
        return Record(
            key=FlowKey.make(src, dst, sport, dport, 17), bytes_=nbytes,
            packets=max(1, nbytes // 1400), eth_protocol=0x0800, tcp_flags=0,
            direction=1, src_mac=b"\x02" * 6, dst_mac=b"\x04" * 6,
            if_index=3, interface="eth0", dscp=0, sampling=0,
            agent_ip="192.0.2.1")

    reports = []
    exp = TpuSketchExporter(
        batch_size=16, window_s=3600,
        sketch_cfg=SketchConfig(cm_depth=2, cm_width=1 << 10,
                                hll_precision=6, perdst_buckets=32,
                                perdst_precision=4, topk=16, hist_buckets=64,
                                ewma_buckets=64),
        sink=reports.append, asym_min_bytes=1 << 20, asym_ratio=0.95)
    # one-way elephant: 4MB A->B, nothing back
    flows = [rec("10.5.0.1", "10.5.0.2", 5001, 5002, 1 << 20)
             for _ in range(4)]
    # balanced conversation, larger than the floor in BOTH directions
    flows += [rec("10.6.0.1", "10.6.0.2", 6001, 6002, 1 << 20),
              rec("10.6.0.2", "10.6.0.1", 6002, 6001, (1 << 20) - 4096)]
    exp.export_batch(flows)
    exp.flush()
    asym = reports[0]["AsymmetricConversationBuckets"]
    assert len(asym) == 1, f"expected exactly the one-way pair: {asym}"
    assert asym[0]["bytes"] == float(4 << 20)
    assert asym[0]["one_way_share"] == 1.0
    exp.close()


def test_hairpin_conversations_excluded_from_asymmetry():
    """src == dst traffic (hairpin NAT / loopback capture) has no
    meaningful direction — it must not fire a one-way alert."""
    from netobserv_tpu.exporter.tpu_sketch import TpuSketchExporter
    from netobserv_tpu.model.flow import FlowKey
    from netobserv_tpu.model.record import Record
    from netobserv_tpu.sketch.state import SketchConfig

    reports = []
    exp = TpuSketchExporter(
        batch_size=8, window_s=3600,
        sketch_cfg=SketchConfig(cm_depth=2, cm_width=1 << 10,
                                hll_precision=6, perdst_buckets=32,
                                perdst_precision=4, topk=16, hist_buckets=64,
                                ewma_buckets=64),
        sink=reports.append, asym_min_bytes=1 << 20)
    hair = [Record(key=FlowKey.make("10.9.9.9", "10.9.9.9", 4000 + d, 4001, 17),
                   bytes_=2 << 20, packets=9, eth_protocol=0x0800,
                   tcp_flags=0, direction=d % 2, src_mac=b"\x02" * 6,
                   dst_mac=b"\x04" * 6, if_index=3, interface="lo", dscp=0,
                   sampling=0, agent_ip="192.0.2.1") for d in range(4)]
    exp.export_batch(hair)
    exp.flush()
    assert reports[0]["AsymmetricConversationBuckets"] == []
    exp.close()


def test_feed_formats_agree_on_window_totals():
    """SKETCH_FEED=resident|compact|dense are three transports for the SAME
    math: identical evictions must produce identical window totals and
    heavy-hitter sets through the production exporter."""
    import numpy as np

    from netobserv_tpu.datapath.fetcher import EvictedFlows
    from netobserv_tpu.exporter.tpu_sketch import TpuSketchExporter
    from netobserv_tpu.model import binfmt
    from netobserv_tpu.sketch.state import SketchConfig

    cfg = SketchConfig(cm_depth=2, cm_width=1 << 10, hll_precision=6,
                       perdst_buckets=32, perdst_precision=4, topk=16,
                       hist_buckets=64, ewma_buckets=32)
    reports = {}
    for feed in ("resident", "compact", "dense"):
        out = []
        exp = TpuSketchExporter(batch_size=64, window_s=3600,
                                sketch_cfg=cfg, sink=out.append, feed=feed)
        extra = np.zeros(8, dtype=binfmt.EXTRA_REC_DTYPE)
        extra["rtt_ns"] = 2_000_000
        exp.export_evicted(EvictedFlows(make_events(8), extra=extra))
        exp.export_evicted(EvictedFlows(make_events(5, sport0=9000,
                                                    nbytes=50_000)))
        exp.flush()
        assert len(out) == 1, feed
        reports[feed] = out[0]
    base = reports["dense"]
    for feed in ("resident", "compact"):
        rep = reports[feed]
        assert rep["Records"] == base["Records"] == 13, feed
        assert rep["Bytes"] == base["Bytes"], feed
        hh = lambda r: {(h["SrcAddr"], h["SrcPort"], h["EstBytes"])
                        for h in r["HeavyHitters"]}
        assert hh(rep) == hh(base), feed
