"""tpu-sketch exporter: offloads flow aggregation/analytics to JAX/TPU.

The north-star backend (BASELINE.json): record batches arriving at the exporter
seam are packed into fixed-shape columnar tensors, folded on-device into
streaming sketches (Count-Min, HLL, top-K, latency histograms, EWMA), and every
SKETCH_WINDOW seconds a cluster-wide WindowReport is emitted (top-K heavy
hitters with exact keys, cardinalities, latency quantiles, DDoS z-scores).

Multi-chip: when more than one device is visible (or SKETCH_MESH_SHAPE is set)
the state is partitioned over a Mesh and merged over ICI at window roll
(`netobserv_tpu.parallel`). Reports go to a pluggable sink (JSON lines by
default — feed it to Kafka/gRPC by passing a different sink).
"""

from __future__ import annotations

import collections
import functools
import json
import logging
import sys
import threading
import time
from typing import Callable, Optional

import numpy as np

from netobserv_tpu.alerts.rules import SIGNAL_FIELDS
from netobserv_tpu.config import (
    DEFAULT_ASYM_MIN_BYTES, DEFAULT_ASYM_RATIO, DEFAULT_CHURN_ASCENT,
    DEFAULT_CHURN_MIN_BYTES, DEFAULT_DDOS_Z, DEFAULT_DROP_Z,
    DEFAULT_SCAN_FANOUT, DEFAULT_SYNFLOOD_MIN, DEFAULT_SYNFLOOD_RATIO,
)
from netobserv_tpu.datapath import flowpack
from netobserv_tpu.exporter.base import Exporter
from netobserv_tpu.sketch import staging
from netobserv_tpu.model.columnar import FlowBatch, unpack_key_words
from netobserv_tpu.model.flow import ip_from_16
from netobserv_tpu.model.record import Record
from netobserv_tpu.utils import faultinject, retrace, tracing

log = logging.getLogger("netobserv_tpu.exporter.tpu_sketch")

#: once-per-process dedup of the multi-device SKETCH_TIERED degrade warning
#: (chaos/restart loops rebuild exporters; the queryable truth is the
#: tiered_degraded supervisor condition, not the log line)
_TIERED_DEGRADE_WARNED = False

ReportSink = Callable[[dict], None]


def _default_sink(report: dict) -> None:
    sys.stdout.write(json.dumps(report, separators=(",", ":")) + "\n")
    sys.stdout.flush()


class KafkaReportSink:
    """Publishes window reports as JSON Kafka messages; closeable."""

    def __init__(self, cfg):
        from netobserv_tpu.kafka.producer import (
            KafkaProducer, SASLSettings, TLSSettings,
        )
        sasl = SASLSettings(enable=cfg.kafka_enable_sasl,
                            mechanism=cfg.kafka_sasl_type)
        if sasl.enable:
            from netobserv_tpu.exporter.kafka import _read_secret
            sasl.username = _read_secret(cfg.kafka_sasl_client_id_path)
            sasl.password = _read_secret(cfg.kafka_sasl_client_secret_path)
        self._producer = KafkaProducer(
            brokers=cfg.kafka_brokers, topic=cfg.kafka_topic,
            acks=0 if cfg.kafka_async else 1,
            tls=TLSSettings(
                enable=cfg.kafka_enable_tls,
                insecure_skip_verify=cfg.kafka_tls_insecure_skip_verify,
                ca_path=cfg.kafka_tls_ca_cert_path,
                cert_path=cfg.kafka_tls_user_cert_path,
                key_path=cfg.kafka_tls_user_key_path),
            sasl=sasl, compression=cfg.kafka_compression)

    def __call__(self, report: dict) -> None:
        self._producer.send_batch([
            (b"sketch_report",
             json.dumps(report, separators=(",", ":")).encode())])

    def close(self) -> None:
        self._producer.close()


def make_report_sink(cfg) -> ReportSink:
    """SKETCH_REPORT_SINK switch: stdout JSON lines (default) or Kafka
    (BASELINE config 5: anomaly scores over the Kafka export path)."""
    if cfg.sketch_report_sink == "kafka":
        return KafkaReportSink(cfg)
    if cfg.sketch_report_sink not in ("", "stdout"):
        raise ValueError(
            f"SKETCH_REPORT_SINK={cfg.sketch_report_sink!r} (want stdout|kafka)")
    return _default_sink


def _slot_key_entries(words: np.ndarray, rows) -> list[dict]:
    """Render slot-table rows' packed key words into addr/port dicts, with
    a stable `Key` fingerprint string (the churn alert rules' dedup id)."""
    rows = np.asarray(rows, dtype=np.int64)
    out: list[dict] = []
    if not len(rows):
        return out
    keys = unpack_key_words(words[rows])
    for k in keys:
        src = ip_from_16(k["src_ip"].tobytes())
        dst = ip_from_16(k["dst_ip"].tobytes())
        sp, dp, proto = int(k["src_port"]), int(k["dst_port"]), \
            int(k["proto"])
        out.append({
            "SrcAddr": src, "DstAddr": dst, "SrcPort": sp, "DstPort": dp,
            "Proto": proto,
            "Key": f"{src}:{sp}->{dst}:{dp}/{proto}",
        })
    return out


def heavy_identity_index(report) -> dict:
    """(h1, h2) identity -> rendered key entry of every VALID slot — the
    previous-roll index `report_to_json` diffs against to name EVICTED
    keys (identities that left the table since the last closed window).
    Host-side numpy only; the exporter/aggregator stash one per ROLL."""
    valid = np.asarray(report.heavy.valid)
    rows = np.nonzero(valid)[0]
    h1 = np.asarray(report.heavy.h1)
    h2 = np.asarray(report.heavy.h2)
    counts = np.asarray(report.heavy.counts)
    entries = _slot_key_entries(np.asarray(report.heavy.words), rows)
    out = {}
    for j, i in enumerate(rows):
        e = dict(entries[j])
        e["EstBytes"] = float(counts[i])
        out[(int(h1[i]), int(h2[i]))] = e
    return out


#: heavy-hitter rows in the window report a sink receives (and the rows
#: victim names are drawn from). /query/topk serves `?n=` up to the whole
#: slot table from the query snapshot, which renders more
REPORT_HEAVY = 64


def _for_sink(obj: dict) -> dict:
    """The rendered report as a sink receives it: the snapshot's copy may
    carry the whole slot table, a sink's stays at REPORT_HEAVY rows."""
    heavy = obj["HeavyHitters"]
    if len(heavy) <= REPORT_HEAVY:
        return obj
    return {**obj, "HeavyHitters": heavy[:REPORT_HEAVY]}


def report_to_json(report, max_heavy: int = REPORT_HEAVY,
                   scan_fanout_threshold: float = DEFAULT_SCAN_FANOUT,
                   ddos_z_threshold: float = DEFAULT_DDOS_Z,
                   synflood_min: float = DEFAULT_SYNFLOOD_MIN,
                   synflood_ratio: float = DEFAULT_SYNFLOOD_RATIO,
                   drop_z_threshold: float = DEFAULT_DROP_Z,
                   asym_min_bytes: float = DEFAULT_ASYM_MIN_BYTES,
                   asym_ratio: float = DEFAULT_ASYM_RATIO,
                   churn_ascent: float = DEFAULT_CHURN_ASCENT,
                   churn_min_bytes: float = DEFAULT_CHURN_MIN_BYTES,
                   prev_heavy_index: Optional[dict] = None,
                   partial_window: bool = False) -> dict:
    """Render a device WindowReport into a host JSON object.

    The persistent-slot table makes this a per-KEY churn renderer too:
    FlowAscents / FlowDescents / NewHeavyKeys derive from each slot's
    (counts, prev_counts, first_seen) under the `churn_ascent` /
    `churn_min_bytes` gates — the ONE threshold truth the zoo runner and
    the default flow_ascent/new_heavy_key alert rules share (the
    alerts/rules.py one-truth note). `prev_heavy_index` (the previous
    ROLL's `heavy_identity_index`) names EvictedKeys by diffing identity
    sets; without it the list renders empty (first window, refresh-only
    consumers)."""
    words = np.asarray(report.heavy.words)
    valid = np.asarray(report.heavy.valid)
    counts = np.asarray(report.heavy.counts)
    prevs = np.asarray(report.heavy.prev_counts)
    first_seen = np.asarray(report.heavy.first_seen)
    window = int(report.window)
    order = np.argsort(-np.where(valid, counts, -np.inf))[:max_heavy]
    heavy = []
    sel = [i for i in order if valid[i]]
    if sel:
        keys = unpack_key_words(words[sel])
        for j, i in enumerate(sel):
            k = keys[j]
            heavy.append({
                "SrcAddr": ip_from_16(k["src_ip"].tobytes()),
                "DstAddr": ip_from_16(k["dst_ip"].tobytes()),
                "SrcPort": int(k["src_port"]),
                "DstPort": int(k["dst_port"]),
                "Proto": int(k["proto"]),
                "EstBytes": float(counts[i]),
                "PrevEstBytes": float(prevs[i]),
                "FirstSeenWindow": int(first_seen[i]),
            })
    # --- per-key churn (the device-resident heavy-hitter plane) ---
    # ascent: window-over-window growth >= churn_ascent with real current
    # mass; descent: the reciprocal collapse of a previously-heavy key;
    # new: first_seen == this window (gated to window > 0 — in the
    # table's very first window EVERYTHING is new, which is noise, and
    # prev_counts are all zero so ascents are structurally quiet too)
    asc_all = np.nonzero(valid & (prevs > 0)
                         & (counts >= churn_ascent * prevs)
                         & (counts >= churn_min_bytes))[0]
    asc_rows = asc_all[np.argsort(-counts[asc_all])][:32]
    # descents render only for CLOSED windows: a mid-window refresh
    # compares a partial window against a full previous one, so right
    # after a roll EVERY steady incumbent would read as collapsed
    # (ascents have no such problem — a partial count exceeding the full
    # previous window is real growth, and it is what makes detection
    # sub-window)
    desc_all = np.nonzero(valid & (prevs >= churn_min_bytes)
                          & (counts <= prevs / churn_ascent))[0] \
        if not partial_window else np.zeros(0, np.int64)
    desc_rows = desc_all[np.argsort(-prevs[desc_all])][:32]
    new_all = np.nonzero(valid & (first_seen == window)
                         & (counts >= churn_min_bytes))[0] \
        if window > 0 else np.zeros(0, np.int64)
    new_rows = new_all[np.argsort(-counts[new_all])][:32]

    def churn_entries(rows) -> list[dict]:
        out = _slot_key_entries(words, rows)
        for j, i in enumerate(rows):
            out[j].update({
                "EstBytes": float(counts[i]),
                "PrevEstBytes": float(prevs[i]),
                "Ratio": round(float(counts[i] / max(prevs[i], 1.0)), 3),
                "FirstSeenWindow": int(first_seen[i]),
            })
        return out

    evicted_keys: list[dict] = []
    if prev_heavy_index:
        h1a = np.asarray(report.heavy.h1)
        h2a = np.asarray(report.heavy.h2)
        cur_ids = {(int(h1a[i]), int(h2a[i]))
                   for i in np.nonzero(valid)[0]}
        gone = [e for ident, e in prev_heavy_index.items()
                if ident not in cur_ids]
        gone.sort(key=lambda e: -e.get("EstBytes", 0.0))
        evicted_keys = gone[:32]
    # best-effort victim names via the shared query core (the ONE
    # implementation — numpy hash twin under DST_BUCKET_SEED; report
    # rendering must never dispatch a device op)
    from netobserv_tpu.query.core import victim_bucket_names
    n_buckets = np.asarray(report.ddos_z).shape[0]
    named = sel[:REPORT_HEAVY]
    dst_bucket_names = victim_bucket_names(
        words[np.asarray(named, dtype=np.int64)] if named
        else words[:0], heavy[:REPORT_HEAVY], n_buckets)

    def victims(bucket: int) -> list:
        return dst_bucket_names.get(int(bucket), [])

    z = np.asarray(report.ddos_z)
    suspects = np.nonzero(z > ddos_z_threshold)[0]
    suspects = suspects[np.argsort(-z[suspects])]  # worst first before [:32]
    # port-scan suspects: source buckets whose distinct-(dst addr, dst
    # port) PAIR fan-out this window exceeds the threshold (a scanner
    # touches hundreds+; a normal client a handful)
    fanout = np.asarray(report.per_src_fanout)
    scan = np.argsort(fanout)[::-1]
    scan = scan[fanout[scan] >= scan_fanout_threshold]
    # SYN-flood suspects: victim buckets offered >= synflood_min half-open
    # attempts this window while accepting (SYN-ACKing) at most 1/ratio of
    # them — the offered:accepted asymmetry IS the flood signature
    syn = np.asarray(report.syn_rate)
    synack = np.asarray(report.synack_rate)
    syn_z = np.asarray(report.syn_z)
    flood = np.nonzero((syn >= synflood_min)
                       & (syn >= synflood_ratio * (synack + 1.0)))[0]
    flood = flood[np.argsort(-syn[flood])]
    drop_z = np.asarray(report.drop_z)
    drop_anom = np.nonzero(drop_z > drop_z_threshold)[0]
    drop_anom = drop_anom[np.argsort(-drop_z[drop_anom])]  # worst first
    causes = np.asarray(report.drop_causes)
    cause_idx = np.nonzero(causes > 0)[0]
    cause_idx = cause_idx[np.argsort(-causes[cause_idx])][:16]
    from netobserv_tpu.utils.drop_reasons import drop_reason_name

    def cause_name(c: int) -> str:
        # live-kernel mapping first (the static reference table mislabels
        # on newer kernels — utils/drop_reasons.py); the histogram's last
        # bucket catches saturated/subsystem reasons (state.py N_DROP_CAUSES)
        if c == causes.shape[0] - 1:
            return "OTHER_OR_SUBSYSTEM"
        return drop_reason_name(int(c))
    # one-way conversations: pair buckets over the volume floor whose
    # byte share in one direction exceeds the ratio (exfil / UDP-flood
    # shape; a healthy TCP transfer still carries ~3-5% ACK backflow)
    fwd = np.asarray(report.conv_fwd)
    rev = np.asarray(report.conv_rev)
    conv_total = fwd + rev
    one_way_share = np.maximum(fwd, rev) / np.maximum(conv_total, 1.0)
    asym = np.nonzero((conv_total >= asym_min_bytes)
                      & (one_way_share >= asym_ratio))[0]
    asym = asym[np.argsort(-conv_total[asym])]
    dscp = np.asarray(report.dscp_bytes)
    dscp_idx = np.nonzero(dscp > 0)[0]

    def dscp_name(c: int) -> str:
        # RFC 2474/2597/3246 codepoints (stable, unlike the kernel enums);
        # unnamed codepoints print numerically
        if c == 46:
            return "EF"
        if c == 44:
            return "VOICE-ADMIT"
        if c % 8 == 0:
            return f"CS{c // 8}"
        afc, afd = c // 8, (c % 8) // 2
        if 1 <= afc <= 4 and 1 <= afd <= 3 and c % 2 == 0:
            return f"AF{afc}{afd}"
        return str(c)
    qs = [0.5, 0.9, 0.95, 0.99, 0.999]
    return {
        "Type": "sketch_window_report",
        "Window": int(report.window),
        "Records": float(report.total_records),
        "Bytes": float(report.total_bytes),
        "DistinctSrcEstimate": float(report.distinct_src),
        "DropBytes": float(report.total_drop_bytes),
        "DropPackets": float(report.total_drop_packets),
        "QuicRecords": float(report.quic_records),
        "NatRecords": float(report.nat_records),
        "HeavyHitters": heavy,
        "RttQuantilesUs": {str(q): float(v) for q, v in zip(
            qs, np.asarray(report.rtt_quantiles_us))},
        "DnsLatencyQuantilesUs": {str(q): float(v) for q, v in zip(
            qs, np.asarray(report.dns_quantiles_us))},
        "DdosSuspectBuckets": [
            {"bucket": int(b), "z": float(z[b]),
             "probable_victims": victims(b)} for b in suspects[:32]],
        "PortScanSuspectBuckets": [
            {"bucket": int(b), "distinct_dst_port_pairs": float(fanout[b])}
            for b in scan[:32]],
        "SynFloodSuspectBuckets": [
            {"bucket": int(b), "syn": float(syn[b]),
             "synack": float(synack[b]), "z": float(syn_z[b]),
             "probable_victims": victims(b)}
            for b in flood[:32]],
        "DropAnomalyBuckets": [
            {"bucket": int(b), "z": float(drop_z[b]),
             "probable_victims": victims(b)}
            for b in drop_anom[:32]],
        "AsymmetricConversationBuckets": [
            {"bucket": int(b), "bytes": float(conv_total[b]),
             "one_way_share": round(float(one_way_share[b]), 4)}
            for b in asym[:32]],
        "DropCauses": {str(int(c)): float(causes[c]) for c in cause_idx},
        "DropCauseNames": {cause_name(int(c)): float(causes[c])
                           for c in cause_idx},
        "DscpBytes": {str(int(d)): float(dscp[d]) for d in dscp_idx},
        "DscpClassBytes": {dscp_name(int(d)): float(dscp[d])
                           for d in dscp_idx},
        "FlowAscents": churn_entries(asc_rows),
        "FlowDescents": churn_entries(desc_rows),
        "NewHeavyKeys": churn_entries(new_rows),
        "EvictedKeys": evicted_keys,
        "HeavyChurn": {
            "ascents": int(len(asc_all)),
            "descents": int(len(desc_all)),
            "new": int(len(new_all)),
            "evictions": float(report.heavy_evictions),
            "tracked": int(valid.sum()),
        },
    }


def _tiered_decode(state):
    """`tiered.decode_state` under the watch name of its jitted entry (one
    function object per process: exporters share jax's trace cache)."""
    from netobserv_tpu.sketch.tiered import decode_state

    return decode_state(state)


class TpuSketchExporter(Exporter):
    name = "tpu-sketch"
    supports_columnar = True

    def __init__(self, batch_size: int = 8192, window_s: float = 60.0,
                 sketch_cfg=None, mesh_shape: str = "",
                 sink: Optional[ReportSink] = None, metrics=None,
                 checkpoint_dir: str = "", checkpoint_every: int = 0,
                 decay_factor: Optional[float] = None,
                 scan_fanout_threshold: float = DEFAULT_SCAN_FANOUT,
                 ddos_z_threshold: float = DEFAULT_DDOS_Z,
                 synflood_min: float = DEFAULT_SYNFLOOD_MIN,
                 synflood_ratio: float = DEFAULT_SYNFLOOD_RATIO,
                 drop_z_threshold: float = DEFAULT_DROP_Z,
                 pack_threads: int = 1,
                 pack_threads_explicit: bool = True,
                 asym_min_bytes: float = DEFAULT_ASYM_MIN_BYTES,
                 asym_ratio: float = DEFAULT_ASYM_RATIO,
                 feed: str = "resident",
                 resident_slots: int = 1 << 18,
                 superbatch: tuple = (1,),
                 warm_ladder: bool = False,
                 delta_sink=None,
                 agent_id: str = "",
                 shed_watermark: float = 0.0,
                 shed_max: int = 64,
                 shed_slot_budget_s: float = 30.0,
                 shed_seed: int = 2026,
                 query_refresh_s: float = 0.0,
                 overlap_depth: int = 0,
                 query_history: int = 0,
                 alerts=None,
                 archive=None,
                 churn_ascent: float = DEFAULT_CHURN_ASCENT,
                 churn_min_bytes: float = DEFAULT_CHURN_MIN_BYTES,
                 tenants: int = 0):
        # superbatch defaults to NO ladder for direct construction: the
        # ladder costs superbatch_max-sized ring buffers, dictionaries and
        # key-table rows up front, and only pays off once warmed — the
        # production entry (`from_config`) passes the SKETCH_SUPERBATCH
        # ladder AND warms it; embedders opting in should do the same
        # jax-importing modules are pulled in lazily so the host agent can run
        # exporter-free on machines without accelerators
        from netobserv_tpu.sketch import state as sk

        self._sk = sk
        self._batch_size = batch_size
        self._window_s = window_s
        self._cfg = sketch_cfg or sk.SketchConfig()
        self._sink = sink or _default_sink
        self._scan_fanout = scan_fanout_threshold
        self._ddos_z = ddos_z_threshold
        self._synflood_min = synflood_min
        self._synflood_ratio = synflood_ratio
        self._drop_z = drop_z_threshold
        self._asym_min_bytes = asym_min_bytes
        self._asym_ratio = asym_ratio
        self._churn_ascent = churn_ascent
        self._churn_min_bytes = churn_min_bytes
        # previous ROLL's heavy identity index (EvictedKeys diff source):
        # updated only at closed-window renders — a mid-window refresh
        # diffs against the same last-closed window, never against itself
        self._prev_heavy_index: Optional[dict] = None
        #: tenant-mode twin of _prev_heavy_index: one slot per tenant (the
        #: EvictedKeys diff is per tenant plane — cross-tenant diffs would
        #: read every routed key as churned)
        self._tenant_prev_heavy: dict[int, Optional[dict]] = {}
        self._metrics = metrics
        # federation delta export (federation/delta.py): snapshot the
        # mergeable tables at roll, frame + push them on the timer thread
        self._delta_sink = delta_sink
        if agent_id:
            self._agent_id = agent_id
        else:
            import socket
            self._agent_id = socket.gethostname()
        # idempotent-delivery identity (wire v2): the epoch marks THIS
        # process incarnation (monotonic across restarts), so a restarted
        # agent's reset window counter re-registers as a fresh epoch at
        # the aggregator instead of reading as a flood of stale frames
        self._agent_epoch = time.time_ns()
        # fleet-telemetry block (frames' optional AgentTelemetry): every
        # value here is already computed elsewhere — the block is assembled
        # once per PUBLISH on the timer thread, never on the fold path.
        # _map_occupancy is a single float store per DRAIN
        # (note_map_occupancy, wired through MapTracer's occupancy sink).
        self._windows_published = 0
        self._host_rate_ewma = 0.0
        self._last_publish_mono: Optional[float] = None
        self._map_occupancy = 0.0
        if self._delta_sink is not None and decay_factor is not None:
            # decayed tables are CUMULATIVE (sliding window): pushing them
            # per window would double-count every prior window's mass at
            # the aggregator, whose merge assumes per-window deltas
            log.warning("federation delta export requires "
                        "SKETCH_WINDOW_MODE=reset (decay frames are "
                        "cumulative); disabling delta export")
            self._drop_delta_sink()
        if metrics is not None:
            # retrace alarms and span histograms land in THIS agent's
            # registry (module-level binding: one facade per process in
            # production; tests rebind freely)
            retrace.set_metrics(metrics)
            tracing.set_metrics(metrics)
        #: batch trace (flight recorder) riding the pending buffer: the
        #: first sampled eviction's trace is finished by the fold that
        #: consumes its rows
        self._pending_trace = None
        #: windows closed by this exporter (`window=<n>` on their stages)
        self._windows_closed = 0
        # resident pack LANES cost per-lane device key tables and only pay
        # off where parallel dictionary probes actually scale: engage them
        # for an EXPLICIT SKETCH_PACK_THREADS (the operator chose), but an
        # auto-resolved count only on hosts with enough cores (a 2-vCPU
        # box measures ~30% SLOWER with 2 lanes — docs/tpu_sketch.md)
        import os as _os
        self._lane_threads = pack_threads if (
            pack_threads_explicit or (_os.cpu_count() or 1) >= 4) else 1
        #: superbatch fold ladder (SKETCH_SUPERBATCH): queued evictions
        #: coalesce into the largest fitting k*batch superbatch and fold as
        #: ONE fixed-shape dispatch from a per-k pre-built jit
        #: (sketch/staging.py ladder; docs/tpu_sketch.md)
        self._superbatch = tuple(sorted({int(k) for k in (superbatch
                                                          or (1,))}))
        if self._superbatch[0] != 1:
            raise ValueError("superbatch ladder must include 1")
        self._lock = threading.Lock()
        # serializes CALLS into the roll executable (dispatch only — the
        # device work stays async): the window close and the mid-window
        # refresh run on different threads (with SKETCH_OVERLAP the fold
        # worker closes windows too), and two threads first-tracing the
        # same jit double-compile — a spurious post-warmup retrace alarm,
        # found live. After the first compile this is an uncontended
        # microsecond hold around a cache hit.
        self._roll_mutex = threading.Lock()
        # created BEFORE anything that spawns a background thread: the
        # ladder-warm thread polls _closed between compiles, and a warm
        # kicked off mid-__init__ must never race the attribute into
        # existence (observed live as an AttributeError killing the warm)
        self._closed = threading.Event()
        self._pending: list[Record] = []
        # rolled-but-unpublished device-side WindowReports, queued under
        # self._lock, rendered+delivered by the window-timer thread OUTSIDE
        # it — folds never wait on report_to_json or a sink. Bounded: a
        # sink that wedges forever must not pin an ever-growing set of
        # device reports (drops are counted in _roll_locked). State is
        # deliberately NOT queued with the report — later folds donate it.
        self._reports: collections.deque = collections.deque()
        self._max_queued_reports = 8
        self._publish_lock = threading.Lock()
        self._window_deadline = time.monotonic() + window_s
        self._n_windows_saved = 0
        # distributed init MUST precede anything that touches the JAX backend
        # (including orbax CheckpointManager construction)
        from netobserv_tpu.parallel.distributed import (
            maybe_initialize_distributed,
        )
        maybe_initialize_distributed()

        self._ckpt = None
        self._ckpt_every = checkpoint_every
        if checkpoint_dir:
            from netobserv_tpu.sketch.checkpoint import SketchCheckpointer
            self._ckpt = SketchCheckpointer(checkpoint_dir)

        import jax
        devs = jax.devices()
        self._distributed = len(devs) > 1 or ("x" in mesh_shape)
        #: multi-tenant sketch stack (SKETCH_TENANTS, sketch/tenancy.py):
        #: N tenant states on a leading axis, ONE vmapped dispatch folds
        #: every tenant's evictions. None (unset) keeps every path
        #: bit-identical — no stack object, one is-None check.
        self._tenancy = None
        if tenants and self._distributed:
            # no mesh-sharded stacked form yet (config.validate blocks the
            # env combination; direct construction degrades gracefully —
            # the SKETCH_TIERED pattern)
            log.warning("SKETCH_TENANTS has no mesh-sharded form; running "
                        "the mesh exporter single-tenant")
            tenants = 0
        #: True when SKETCH_TIERED was requested but degraded away (the
        #: mesh has no sharded tier form) — surfaced as a supervisor
        #: CONDITION so /healthz shows WHY resident memory is wide
        self._tiered_degraded = False
        if self._distributed and self._cfg.tiered is not None:
            # no owner-sharded tier form yet (config.validate blocks the
            # env combination; direct construction degrades gracefully —
            # exporters never crash the pipeline). The warning dedupes to
            # once per PROCESS (exporters are rebuilt on restart/chaos
            # loops; the log line is informational, the health condition
            # below is the queryable truth)
            global _TIERED_DEGRADE_WARNED
            if not _TIERED_DEGRADE_WARNED:
                _TIERED_DEGRADE_WARNED = True
                log.warning("SKETCH_TIERED has no sharded form; running the "
                            "mesh exporter with wide-resident tables")
            self._tiered_degraded = True
            self._cfg = self._cfg._replace(tiered=None)
        #: which tiered fold form this backend engages ("interior" |
        #: "decode" | None) — the /debug/executables attribution
        #: for every watched ingest/roll entry (one program each, never
        #: hidden variants). Rolls always ride the wide decode.
        self._tier_form = sk.tiered_fold_form(self._cfg)
        self._tier_roll_form = "decode" if self._cfg.tiered else None
        #: previous closed-window promoted-counter masks, per CM table —
        #: the tier-promotions counter increments by NEW promotions only
        #: (host bools, timer thread; see _publish_tier_metrics). Masks
        #: are only kept when promotions PERSIST across windows (decay
        #: mode); reset mode starts every window from fresh planes, so
        #: there occupancy IS the window's new-promotion count.
        self._tier_prev_promoted: dict = {}
        self._tier_sticky_promotions = decay_factor is not None
        #: jitted decode-to-wide for checkpoint saves (tiered mode only —
        #: checkpoints keep the canonical wide SketchState layout, so the
        #: format/version stamp never moves with the resident
        #: representation). Built lazily, retrace-watched like every
        #: jitted entry the exporter constructs.
        self._tiered_decode = None
        if self._distributed:
            from netobserv_tpu.parallel import (
                MeshSpec, make_mesh, merge as pmerge)
            spec = MeshSpec.parse(mesh_shape, len(devs))
            self._mesh = make_mesh(spec)
            self._ndata = spec.data
            # fixed batch shape must split evenly over the data axis
            self._batch_size = -(-self._batch_size // spec.data) * spec.data
            self._pm = pmerge
            self._state = pmerge.init_dist_state(self._cfg, self._mesh)
            self._ingest = pmerge.make_sharded_ingest_fn(self._mesh, self._cfg)
            ingest_dense = pmerge.make_sharded_ingest_fn(
                self._mesh, self._cfg, dense=True, with_token=True)
            dense_put = lambda buf: pmerge.shard_dense(  # noqa: E731
                self._mesh, buf)
            if self._delta_sink is not None and spec.sketch > 1:
                # width-sharded CM planes are independent local-width
                # sketches — there is no whole-width snapshot to frame
                log.warning("federation delta export needs a data-axis-only "
                            "mesh; disabling it on this %dx%d exporter",
                            spec.data, spec.sketch)
                self._drop_delta_sink()
            # the merged table snapshot is one extra output of the roll on
            # every mesh: whole-width planes on a data-axis-only mesh, the
            # per-owner-shard local-width planes [S, depth, width / S] on a
            # width-sharded one (/query/frequency answers from either)
            self._roll = pmerge.make_merge_fn(
                self._mesh, self._cfg, decay_factor=decay_factor,
                with_tables=True)
            if feed == "resident":
                # resident feed over the mesh: per-data-shard dictionaries
                # + device key tables (~15B/record instead of dense's 80;
                # lookups stay shard-local — no collectives added). When
                # pack threads outnumber the data shards, each shard's rows
                # additionally split into pack LANES so every thread gets
                # its own dictionary+region (host-pack parallelism beyond
                # the mesh width)
                bps = self._batch_size // spec.data
                lanes = staging.pick_lanes(
                    bps, max(1, self._lane_threads // spec.data))
                bpl = bps // lanes
                caps = flowpack.default_resident_caps(bpl)
                wide_caps = flowpack.wide_resident_caps(bpl)
                ladder = self._superbatch

                def sharded_entry(k, caps, family=""):
                    return pmerge.make_sharded_ingest_resident_fn(
                        self._mesh, self._cfg, bpl, caps, resident_slots,
                        lanes=k * lanes,
                        watch_name=f"sharded_ingest_resident{family}_x{k}")
                self._ring = staging.ShardedResidentStagingRing(
                    self._batch_size, spec.data,
                    {k: sharded_entry(k, caps) for k in ladder},
                    key_tables=functools.partial(
                        pmerge.init_resident_tables, self._mesh,
                        resident_slots, lanes=max(ladder) * lanes),
                    put=dense_put,
                    caps=caps, slot_cap=resident_slots, metrics=metrics,
                    pack_threads=pack_threads, lanes=lanes, ladder=ladder,
                    lazy_ladder=True,
                    # a key flood saturates, so its chunks are the top
                    # entry's: one wide program, not one a ladder size
                    wide_ingest={max(ladder): sharded_entry(
                        max(ladder), wide_caps, "_wide")},
                    wide_caps=wide_caps)
            else:
                if feed == "compact":
                    log.info("SKETCH_FEED=compact has no sharded form "
                             "(spill compaction breaks the row split); "
                             "using dense")
                elif feed != "dense":
                    log.warning("unknown SKETCH_FEED %r; using dense", feed)
                # dense: full-width rows, row-sharded over the data axis
                self._ring = staging.DenseStagingRing(
                    self._batch_size, ingest_dense, put=dense_put,
                    metrics=metrics, pack_threads=pack_threads)
        elif tenants:
            from netobserv_tpu.sketch import tenancy
            self._ndata = 1
            self._tenancy = tenancy.TenantStack(
                tenants, self._cfg, self._batch_size, metrics=metrics,
                decay_factor=decay_factor)
            self._state = tenancy.init_stacked_state(self._cfg, tenants)
            # the Record path routes through the stack's fold_rows; there
            # is no separate unstacked ingest entry to dispatch
            self._ingest = None
            # ONE stacked roll closes every tenant's window; _roll_locked
            # drives it through the same (state, report, tables) contract
            self._roll = self._tenancy.roll
            self._ring = self._tenancy
            if feed != "dense":
                log.info("tenant mode ships the dense stacked feed; "
                         "SKETCH_FEED=%r does not apply", feed)
        else:
            self._ndata = 1
            self._state = sk.init_state(self._cfg)
            # retrace watchdog: every jitted entry point the exporter can
            # dispatch is watched — its first compile is warmup, any later
            # compile alarms (sketch_retraces_total{fn=...})
            self._ingest = sk.make_ingest_fn(
                use_pallas=self._cfg.use_pallas, name="ingest",
                tiered=self._tier_form)
            # with_tables unconditionally: the pre-roll table snapshot is
            # one extra output of the same roll executable, and it feeds
            # BOTH the federation delta export and the query plane's
            # per-roll snapshot (/query/frequency needs the CM planes)
            self._roll = sk.make_roll_fn(
                self._cfg, decay_factor=decay_factor, with_tables=True,
                name="roll", tiered=self._tier_roll_form)
            self._ring = self._make_single_device_ring(
                feed, resident_slots, pack_threads, metrics)
        if self._tenancy is not None and self._ckpt is not None:
            # no stacked-tenant checkpoint layout yet: a wide-era restore
            # into the (N, ...) stack (or vice versa) would tear — refuse
            # with a warning rather than save state a future single-tenant
            # agent restores corrupt (the SKETCH_TIERED degradation rule)
            log.warning("sketch checkpointing has no stacked-tenant form; "
                        "disabling it while SKETCH_TENANTS is set")
            self._ckpt.close()
            self._ckpt = None
        # zero-concat eviction accumulator (columnar fast path): rows copy
        # once into a preallocated rolling buffer instead of per-fold
        # np.concatenate over events + five feature lanes. Sized for the
        # ring's superbatch ladder: queued evictions coalesce up to
        # superbatch_max batches and fold as ONE ladder dispatch (window
        # close always flushes, so nothing waits past the window)
        self._pending_buf = staging.PendingEventBuffer(
            self._batch_size, getattr(self._ring, "superbatch_max", 1),
            metrics=metrics)
        #: the resident ladder ring can hand back the rows a chunk's regions
        #: could not take; the pending buffer keeps them for the next chunk
        #: (the other rings consume every row they are offered)
        self._carry_ring = isinstance(
            self._ring, staging.ShardedResidentStagingRing)
        # overload control plane (sketch/overload.py): admission control at
        # the export_evicted seam. Disabled (the default), _overload is None
        # and the shed path is one is-None check — bit-identical to the
        # unshedded exporter (no RNG, no copies). Enabled, the ring's slot
        # wait is also bounded so a wedged device drops batches (counted)
        # instead of wedging the eviction feed.
        from netobserv_tpu.sketch import overload
        self._overload = overload.maybe_controller(
            self._batch_size, shed_watermark, shed_max, metrics=metrics,
            seed=shed_seed)
        if self._overload is not None:
            self._ring.slot_wait_budget_s = shed_slot_budget_s
        # fold-duty tracking for the controller's busy weight (the depth
        # term of the pressure score only counts when the seam actually
        # spends its wall clock folding — sketch/overload.py docstring);
        # touched only when the controller exists
        self._busy_fold_s = 0.0
        self._busy_last_t: Optional[float] = None
        self._busy_ewma = 0.0
        # query plane (netobserv_tpu/query): the roll's table snapshot +
        # rendered report publish as this agent's queryable view at every
        # window close; /query/* on the metrics server reads ONLY this
        # (off the hot path, the /debug/traces rules). The optional
        # mid-window refresh (SKETCH_QUERY_REFRESH) re-runs the existing
        # roll executable on the timer thread WITHOUT adopting its state —
        # no new jitted entry, so the refresh can never retrace.
        from netobserv_tpu.query import QueryRoutes, SnapshotPublisher
        self.query = SnapshotPublisher(history=query_history)
        #: tenant-mode query plane: one publisher per tenant — every data
        #: route resolves ?tenant= to its publisher (query/routes.py); the
        #: shared `self.query` slot stays unused so no route can serve one
        #: tenant's estimates as another's
        self._tenant_query = (
            [SnapshotPublisher(history=query_history)
             for _ in range(tenants)] if self._tenancy is not None else None)
        # continuous detection plane (netobserv_tpu/alerts): the engine
        # rides EVERY snapshot publish (roll + mid-window refresh) on the
        # timer thread — host-only, no new jit, nothing on the fold path.
        # None (ALERT_RULES unset) keeps the publish path bit-identical:
        # one is-None check, no engine object (the zero-cost bar).
        self._alerts = alerts
        # sketch warehouse (netobserv_tpu/archive): each closed window's
        # table snapshot lands as an on-disk segment at publish time
        # (timer thread, own try, sketch.archive_write fault point) and
        # /query/range merges archived segments on demand. None
        # (ARCHIVE_DIR unset) keeps the publish path bit-identical: no
        # store, no engine, one is-None check (the zero-cost bar).
        if archive is not None and self._mesh_shards()["sketch"] > 1:
            # width-sharded meshes have no whole-width table snapshot to
            # archive (the same contract that disables the delta export)
            log.warning("sketch archive needs a data-axis-only mesh; "
                        "disabling it on this exporter")
            archive = None
        if self._tenancy is not None and archive is not None and \
                not hasattr(archive, "write_tenant_window"):
            # tenant segments must land in per-tenant stores (mixing them
            # would merge tenants at range-query time); from_config builds
            # the set — a direct single-store archive degrades off
            log.warning("tenant mode needs a per-tenant archive set "
                        "(archive.tenant_archives); disabling the archive "
                        "on this exporter")
            archive = None
        self._archive = archive
        self.query_routes = QueryRoutes(self.query.get, self.query_status,
                                        metrics=metrics,
                                        history_fn=self.query.get_window,
                                        windows_fn=self.query.windows,
                                        alerts=alerts,
                                        archive=archive,
                                        tenant_publishers=self._tenant_query)
        if metrics is not None:
            if self._tenant_query is not None:
                # freshness = the most recent tenant publish (all tenants
                # publish together at roll; a refresh updates all of them)
                pubs = self._tenant_query
                metrics.query_snapshot_age_seconds.set_function(
                    lambda: min(p.age_s() for p in pubs))
            else:
                metrics.query_snapshot_age_seconds.set_function(
                    self.query.age_s)
        self._query_refresh_s = query_refresh_s
        if query_refresh_s and jax.process_count() > 1:
            # each process's timer would dispatch the roll's collectives on
            # its own schedule — divergent collective order across
            # processes is a hang, not a feature
            log.warning("SKETCH_QUERY_REFRESH disabled on multi-process "
                        "meshes (refresh rolls would run collectives on "
                        "unsynchronized timers)")
            self._query_refresh_s = 0.0
        self._next_refresh = (time.monotonic() + self._query_refresh_s
                              if self._query_refresh_s else None)
        if metrics is not None:
            # resident sketch-state footprint (shape math, no transfer):
            # the capacity story SKETCH_TIERED buys — several windows/
            # tenants resident per HBM — made visible per agent
            from netobserv_tpu.sketch.tiered import array_bytes
            metrics.sketch_resident_hbm_bytes.set(array_bytes(self._state))
            for axis, n in self._mesh_shards().items():
                metrics.sketch_mesh_shards.labels(axis=axis).set(n)
            # the key tables live in the staging ring, not in the state:
            # at SKETCH_RESIDENT_SLOTS=2^20 they are 1.34 GB (2.15 GB as
            # a TPU lays 10 words out on 16 sublanes) beside a state of
            # 140 MB, so they get a gauge of their own — one table a
            # region dictionary, counted without making them
            ring = self._ring
            metrics.sketch_resident_table_bytes.set(
                len(ring.kdicts) * ring.slot_cap * self._sk.KEY_WORDS * 4
                if isinstance(ring, staging.ShardedResidentStagingRing)
                else 0)
        if warm_ladder:
            self.warm_superbatch_ladder()
        # the staging ring packs the next batch while the previous
        # transfers/ingests are in flight; its slot-reuse tokens also bound
        # the async dispatch queue to the ring depth, so sustained overload
        # backpressures the eviction loop (see sketch/staging.py)
        # restore prior sketch state if a checkpoint exists; an
        # incompatible checkpoint (layout change across an upgrade, e.g.
        # the owner-sharded top-K gaining a sketch-axis dim) must degrade
        # to a fresh window, not kill the agent (exporters never crash the
        # pipeline — CLAUDE.md invariant)
        if self._ckpt is not None and self._ckpt.latest_step() is not None:
            try:
                if self._cfg.tiered is not None:
                    # checkpoints are WIDE (steady-state tiers never reach
                    # disk): restore into the wide layout, then encode —
                    # a wide-era checkpoint restores into a tiered agent
                    # and vice versa, no format bump
                    from netobserv_tpu.sketch import tiered as sk_tiered
                    wide = self._ckpt.restore(self._sk.init_state(
                        self._cfg._replace(tiered=None)))
                    self._state = sk_tiered.encode_state(
                        wide, self._cfg.tiered)
                else:
                    self._state = self._ckpt.restore(self._state)
                log.info("restored sketch state from checkpoint step %s",
                         self._ckpt.latest_step())
            except Exception as exc:
                log.warning(
                    "sketch checkpoint at step %s is incompatible with this "
                    "version (%s); starting from a fresh window",
                    self._ckpt.latest_step(), exc)
        # idle-window timer: reports keep flowing even when no batches arrive
        #: supervision hook for the window timer (agent/supervisor.py)
        self.heartbeat = lambda: None
        self._timer: Optional[threading.Thread] = None
        # overlapped eviction dispatch (SKETCH_OVERLAP): with a depth, the
        # admit/buffer/fold work moves to a dedicated supervised fold
        # thread behind a bounded handoff, so the eviction feed's next
        # drain overlaps this batch's pack/dispatch (classic double buffer
        # at depth 1). A full handoff BLOCKS export_evicted — the same
        # feed backpressure as the synchronous seam, one batch deeper.
        # Disabled (depth 0, the default): no queue, no thread, one
        # is-None check — export_evicted is bit-identical to the
        # synchronous exporter.
        self._handoff = None
        self._inflight_rows = 0  # rows put but not yet picked up
        self._inflight_lock = threading.Lock()
        # fused-pipeline pack surface (EVICT_NATIVE_PIPELINE): built on
        # demand by resident_pack_surface(); None keeps every fold path
        # bit-identical (one is-None check)
        self._pack_surface: Optional[staging.ResidentPackSurface] = None
        self.fold_heartbeat = lambda: None
        self._fold_thread: Optional[threading.Thread] = None
        if overlap_depth > 0:
            import queue as _queue
            self._handoff = _queue.Queue(maxsize=overlap_depth)
            self._start_fold_worker()
        self.start_window_timer()

    def warm_superbatch_ladder(self, block: bool = False) -> None:
        """Compile every superbatch ladder entry ahead of traffic, against
        THROWAWAY zero state/tables of identical shapes (the compile cache
        keys on shapes, so the first real superbatch hits a warm
        executable instead of stalling mid-traffic on a multi-second
        compile). Runs on a background thread by default — agent startup
        isn't serialized behind the ladder — and counts as each watched
        entry's warmup call, so the no-retrace alarm stays armed.

        The exporter's ring is built `lazy_ladder`: entries beyond 1x only
        become SELECTABLE here, as each compile lands (`ring.mark_warm`) —
        an unwarmed exporter folds 1x forever rather than ever paying a
        ladder compile inside a live `export_evicted`. The wide lane
        family's entries (`ring.programs()`) compile after the narrow
        ladder through the same spare state and tables, and a flood folds
        narrow until they have.

        MULTI-PROCESS meshes warm synchronously regardless of `block`:
        every process must select the same ladder k for the same fold (the
        sharded ingest is one SPMD program — divergent k means divergent
        global computations and a collective hang), so availability must
        flip deterministically: all entries warmed, in ladder order, on
        every process, before any process serves traffic."""
        ring = self._ring
        if not isinstance(ring, staging.ShardedResidentStagingRing):
            return  # dense/compact feeds have no ladder (docs/tpu_sketch.md)
        import jax
        multiprocess = jax.process_count() > 1
        if multiprocess:
            block = True

        def _warm() -> None:
            import jax
            # ONE spare state and table array for every entry: a call
            # donates them and the next entry takes what it returned (zero
            # regions define no key and hold no row) — an array an entry
            # would be 2.15 GB each at 2^20 slots, beside the ring's own
            state = tables = None
            for k, wide in ring.programs():
                if self._closed.is_set():
                    return  # shutting down: stop compiling, exit promptly
                if ring.is_warm(k, wide):
                    # already selectable (k=1, or a prior warm): live folds
                    # may be tracing it RIGHT NOW — a concurrent duplicate
                    # first-trace here would fire a spurious post-warmup
                    # retrace alarm, for zero benefit
                    continue
                ingest, _, region_words = ring.program(k, wide)
                try:
                    if tables is None:
                        state = (
                            self._pm.init_dist_state(self._cfg, self._mesh)
                            if self._distributed
                            else self._sk.init_state(self._cfg))
                        tables = ring.make_tables()
                    nr = ring.n_shards * k * ring.lanes
                    flat = np.zeros(nr * region_words, np.uint32)
                    state, tables, token = ingest(
                        state, tables, ring._put(flat))
                    jax.block_until_ready(token)
                    ring.mark_warm(k, wide=wide)
                except Exception as exc:
                    state = tables = None  # donated to the call that failed
                    if multiprocess:
                        # divergent availability across processes means
                        # divergent SPMD programs later — fail the startup
                        # loudly instead of hanging a collective mid-run
                        raise
                    # single process: never fatal — the entry stays
                    # unselectable and folds ride the smaller ones. But a
                    # warm fails by not lowering or compiling, which no
                    # retry repairs: say so at error level, by name
                    log.error("superbatch ladder entry %r failed to warm "
                              "and stays disabled: %s",
                              getattr(ingest, "name", k), exc)

        if block:
            _warm()
        else:
            self._warm_thread = threading.Thread(
                target=_warm, name="sketch-ladder-warm", daemon=True)
            self._warm_thread.start()

    def _drop_delta_sink(self) -> None:
        """Disable delta export, CLOSING the sink (from_config already
        opened its gRPC channel — dropping the reference would leak it)."""
        sink_close = getattr(self._delta_sink, "close", None)
        if sink_close is not None:
            sink_close()
        self._delta_sink = None

    @property
    def _window_poll_s(self) -> float:
        """Window timer wakeup period — the ONE definition; the heartbeat
        deadline in register_supervised rides on top of it."""
        return min(1.0, self._window_s / 10)

    def start_window_timer(self) -> None:
        """(Re)start the idle-window timer thread; the supervisor uses this
        as the sketch-window stage's restart callable."""
        self._timer = threading.Thread(
            target=self._window_loop, name="sketch-window", daemon=True)
        self._timer.start()

    def register_supervised(self, supervisor, heartbeat_timeout_s=None,
                            **kwargs) -> None:
        """Register the window timer with the agent's supervisor. The
        heartbeat deadline rides on top of the timer's own poll period."""
        beat = supervisor.register(
            "sketch-window", restart=self.start_window_timer,
            thread_getter=lambda: self._timer,
            heartbeat_timeout_s=(heartbeat_timeout_s or 10.0)
            + self._window_poll_s,
            **kwargs)
        self.heartbeat = beat
        # the OVERLOADED condition rides the supervisor's condition
        # registry so /healthz + /readyz surface it next to (and distinct
        # from) DEGRADED — shedding is deliberate graceful degradation,
        # not a dead stage
        # getattr: timer-only harnesses (tests) build the exporter via
        # __new__ and register just the window timer
        ctl = getattr(self, "_overload", None)
        if ctl is not None and hasattr(supervisor, "register_condition"):
            supervisor.register_condition(
                "overloaded",
                lambda: {"active": ctl.overloaded, **ctl.snapshot()})
        # the ALERTING condition is OVERLOADED's sibling: a raised alert
        # is the detection plane doing its job, not a failing stage —
        # /readyz stays 200 (conditions never gate readiness)
        eng = getattr(self, "_alerts", None)
        if eng is not None and hasattr(supervisor, "register_condition"):
            supervisor.register_condition("alerting", eng.condition)
        # tiered_degraded: SKETCH_TIERED was requested but the mesh has no
        # sharded tier form — /healthz shows WHY resident memory is wide.
        # A condition, never DEGRADED: the exporter made a deliberate,
        # documented fallback; readiness is untouched.
        if (getattr(self, "_tiered_degraded", False)
                and hasattr(supervisor, "register_condition")):
            supervisor.register_condition(
                "tiered_degraded",
                lambda: {"active": True,
                         "reason": "SKETCH_TIERED has no sharded form; "
                                   "resident tables are wide"})
        # the overlap fold worker is a pipeline stage like any other: a
        # crash/hang restarts it (the handoff queue survives the restart,
        # so queued evictions still fold)
        if getattr(self, "_handoff", None) is not None:
            self.fold_heartbeat = supervisor.register(
                "sketch-fold", restart=self._start_fold_worker,
                thread_getter=lambda: self._fold_thread,
                heartbeat_timeout_s=(heartbeat_timeout_s or 10.0) + 0.2,
                **kwargs)

    @classmethod
    def from_config(cls, cfg, metrics=None, sink=None):
        from netobserv_tpu.alerts import maybe_engine
        from netobserv_tpu.archive import maybe_archive
        from netobserv_tpu.sketch.state import SketchConfig
        if sink is None:
            sink = make_report_sink(cfg)
        delta_sink = None
        if cfg.federation_target:
            from netobserv_tpu.exporter.federation import FederationDeltaSink
            host, _, port = cfg.federation_target.rpartition(":")
            delta_sink = FederationDeltaSink(host or "127.0.0.1", int(port),
                                             metrics=metrics)
        sketch_cfg = SketchConfig.from_agent_config(cfg)
        archive = None
        if cfg.archive_dir:
            # width-sharded meshes ("DxS", S > 1) have no whole-width
            # table snapshot to archive — decide from the SHAPE STRING
            # alone (touching jax.devices() here would race the
            # distributed init the constructor performs) and skip the
            # store construction entirely: opening a store scans, heals
            # and rewrites the manifest, side effects a discarded
            # feature must not have
            from netobserv_tpu.parallel import MeshSpec
            try:
                width_sharded = MeshSpec.parse(
                    cfg.sketch_mesh_shape, 1).sketch > 1
            except ValueError:
                width_sharded = False  # the ctor raises the real error
            if width_sharded:
                log.warning("ARCHIVE_DIR set on a width-sharded mesh "
                            "(SKETCH_MESH_SHAPE=%s): no whole-width "
                            "table snapshot exists — archive disabled",
                            cfg.sketch_mesh_shape)
            elif cfg.sketch_tenants > 0:
                # per-tenant stores under ARCHIVE_DIR/tenant-<t>: range
                # queries stay tenant-scoped (archive.tenant_archives)
                from netobserv_tpu.archive import tenant_archives
                archive = tenant_archives(cfg, sketch_cfg,
                                          cfg.sketch_tenants,
                                          metrics=metrics)
            else:
                archive = maybe_archive(cfg, sketch_cfg, metrics=metrics)
        return cls(delta_sink=delta_sink, agent_id=cfg.federation_agent_id,
                   batch_size=cfg.sketch_batch_size, window_s=cfg.sketch_window,
                   sketch_cfg=sketch_cfg,
                   mesh_shape=cfg.sketch_mesh_shape, metrics=metrics, sink=sink,
                   checkpoint_dir=cfg.sketch_checkpoint_dir,
                   checkpoint_every=cfg.sketch_checkpoint_every,
                   scan_fanout_threshold=cfg.sketch_scan_fanout,
                   ddos_z_threshold=cfg.sketch_ddos_z,
                   synflood_min=cfg.sketch_synflood_min,
                   synflood_ratio=cfg.sketch_synflood_ratio,
                   drop_z_threshold=cfg.sketch_drop_z,
                   pack_threads=cfg.resolved_pack_threads(),
                   pack_threads_explicit=cfg.sketch_pack_threads > 0,
                   asym_min_bytes=cfg.sketch_asym_min_bytes,
                   asym_ratio=cfg.sketch_asym_ratio,
                   feed=cfg.sketch_feed,
                   resident_slots=cfg.sketch_resident_slots,
                   superbatch=cfg.parsed_superbatch_ladder(),
                   shed_watermark=cfg.sketch_shed_watermark,
                   shed_max=cfg.sketch_shed_max,
                   shed_slot_budget_s=cfg.sketch_shed_slot_budget,
                   query_refresh_s=cfg.sketch_query_refresh,
                   overlap_depth=cfg.sketch_overlap,
                   query_history=cfg.sketch_query_history,
                   alerts=maybe_engine(cfg, metrics),
                   archive=archive,
                   churn_ascent=cfg.sketch_churn_ascent,
                   churn_min_bytes=cfg.sketch_churn_min_bytes,
                   tenants=cfg.sketch_tenants,
                   warm_ladder=True,
                   decay_factor=(cfg.sketch_decay_factor
                                 if cfg.sketch_window_mode == "decay" else None))

    @property
    def overloaded(self) -> bool:
        """True while the overload controller is shedding load (the
        /healthz OVERLOADED condition; always False when disabled)."""
        return self._overload is not None and self._overload.overloaded

    def overload_snapshot(self) -> Optional[dict]:
        """Controller state for the health surface (None when disabled)."""
        return None if self._overload is None else self._overload.snapshot()

    def note_map_occupancy(self, ratio: float) -> None:
        """Record the last kernel-map drain's occupancy for the fleet
        telemetry block (MapTracer's occupancy sink; one float store per
        drain — float assignment is atomic under the GIL, no lock)."""
        self._map_occupancy = float(ratio)

    def _telemetry_block(self, records: int) -> dict:
        """Per-agent health block stamped into the delta frame. Assembled
        once per window PUBLISH on the timer thread from values the
        exporter already holds — no device op, no new clock on the fold
        path. The rec/s EWMA smooths window-records / window-elapsed over
        publishes (alpha 0.3; the first window seeds it)."""
        now = time.monotonic()
        if self._last_publish_mono is not None:
            elapsed = max(now - self._last_publish_mono, 1e-6)
            rate = records / elapsed
            self._host_rate_ewma = (rate if self._host_rate_ewma == 0.0
                                    else 0.3 * rate
                                    + 0.7 * self._host_rate_ewma)
        self._last_publish_mono = now
        conditions = []
        if self.overloaded:
            conditions.append("OVERLOADED")
        eng = self._alerts
        if eng is not None:
            try:
                if eng.condition().get("active"):
                    conditions.append("ALERTING")
            except Exception:  # telemetry must never lose the frame
                pass
        ctl = self._overload
        return {
            "shed_factor": (float(ctl.shed) if ctl is not None else 1.0),
            "conditions": conditions,
            "host_records_per_s": round(self._host_rate_ewma, 3),
            "map_occupancy": round(self._map_occupancy, 6),
            "windows_published": self._windows_published,
        }

    def resident_pack_surface(self) -> Optional[staging.ResidentPackSurface]:
        """The pack surface for the fused native drain pipeline
        (EVICT_NATIVE_PIPELINE): lets `fp_drain_to_resident` pack resident
        regions at drain time with THIS ring's dictionaries. None when the
        feed can't accept pre-packed regions — non-resident/single-lane
        feeds, no native library, or admission control enabled (the
        controller thins rows AFTER drain; a pre-packed arena can't be
        thinned, so fused drains would bypass shedding)."""
        if self._pack_surface is not None:
            return self._pack_surface
        ring = self._ring
        if not isinstance(ring, staging.ShardedResidentStagingRing):
            return None
        if self._overload is not None:
            return None
        if not flowpack.native_available():
            return None
        self._pack_surface = staging.ResidentPackSurface(ring)
        return self._pack_surface

    def _fold_packed_locked(self, packed, trace, seq: int) -> bool:
        """Ship a fused-pipeline arena of eviction `seq` (caller holds the
        exporter lock).
        True = shipped (the eviction's raw rows are represented; don't
        buffer them). False = discarded (stale epoch / no surface): the
        caller folds the raw arrays instead — an EvictedFlows ALWAYS
        carries them regardless of packing."""
        surface = self._pack_surface
        if surface is None or self._overload is not None:
            packed.free()
            return False
        with surface.lock:
            if packed.epoch != surface.epoch:
                # an invalidation already re-zeroed `outstanding` and reset
                # the dictionaries; this arena's slot references are stale
                packed.free()
                return False
            surface.outstanding -= 1
        t0 = time.perf_counter()
        n = packed.segs  # row count rides the raw arrays; segs for logs
        owned = trace is None
        if owned:
            trace = tracing.start_trace("fold")
        try:
            with trace.stage("fold", eviction=seq):
                faultinject.fire("sketch.ingest")
                self._state = self._ring.fold_packed(
                    self._state, packed,
                    trace=trace.bind(evictions=f"{seq}-{seq}"))
        except staging.StagingWedged as exc:
            # same adoption rule as _fold_events — dispatched segments
            # donated the state; and the surface must invalidate (this
            # arena's remaining slot definitions are dropping)
            if exc.state is not None:
                self._state = exc.state
            surface.invalidate()
            log.error("staging slot-wait budget exceeded mid packed fold "
                      "(%d segments): %s", n, exc)
            if self._metrics is not None:
                self._metrics.sketch_ingest_errors_total.inc()
                self._metrics.count_error("tpu-sketch-ingest")
            packed.free()
            return True  # rows up to the wedge shipped; never double-fold
        except Exception as exc:
            self._count_ingest_error(n, exc)  # rolls the surface epoch too
            packed.free()
            return True
        finally:
            if owned:
                trace.finish()
            if self._overload is not None:
                self._busy_fold_s += time.perf_counter() - t0
        packed.free()
        if self._metrics is not None:
            self._metrics.sketch_batches_total.inc()
            if self._tier_form == "interior":
                self._metrics.sketch_tiered_interior_folds_total.inc()
            self._metrics.sketch_ingest_seconds.observe(
                time.perf_counter() - t0)
        return True

    # --- Exporter interface ---
    def export_batch(self, records: list[Record]) -> None:
        with self._lock:
            self._pending.extend(records)
            while len(self._pending) >= self._batch_size:
                chunk, self._pending = (self._pending[:self._batch_size],
                                        self._pending[self._batch_size:])
                self._fold(chunk)
            if time.monotonic() >= self._window_deadline:
                self._close_window_locked()

    def export_evicted(self, evicted) -> None:
        """Columnar fast path: fold raw evictions without building Records.
        Full batches fold as the rolling buffer fills (zero concatenation);
        a due window only dispatches the roll here — rendering and sink I/O
        happen on the timer thread, so this never waits on a sink.

        With SKETCH_OVERLAP the eviction lands in the bounded handoff and
        this returns immediately (blocking only when the handoff is full) —
        the supervised fold thread runs the admit/buffer/fold below, so the
        caller's next drain overlaps this batch's pack/dispatch."""
        if self._handoff is not None:
            with self._inflight_lock:
                self._inflight_rows += len(evicted)
            self._handoff.put(evicted)
            return
        self._export_evicted_now(evicted)

    def _queued_overlap_rows(self) -> int:
        """Rows sitting in the overlap handoff (0 on the synchronous
        path) — part of the TRUE pending depth the overload controller
        must see. The in-hand eviction is decremented before its own
        `ctl.update` so it is never counted twice."""
        if self._handoff is None:
            return 0
        with self._inflight_lock:
            return self._inflight_rows

    def _export_evicted_now(self, evicted) -> None:
        """The admit/buffer/fold half of the columnar seam (synchronous
        callers run it inline; the overlap fold thread runs it per handoff
        item).

        Admission control (overload controller, when enabled): the
        pending-fold depth at arrival — buffered rows + this eviction +
        anything still queued in the overlap handoff — plus the ring's
        slot-wait p95 drive the AIMD shed factor, and the batch is thinned
        BEFORE buffering — surviving rows carry the factor in their
        `sampling` field, so the device de-bias keeps every estimate
        unbiased."""
        trace = getattr(evicted, "trace", None)
        seq = getattr(evicted, "eviction", 0)
        with self._lock:
            packed = getattr(evicted, "packed", None)
            if packed is not None:
                # fused-pipeline arena riding the eviction: ship it in
                # place of the raw arrays (the same rows, packed by
                # flowpack.cc's own continuation schedule —
                # tests/test_native_pipeline.py); a stale epoch falls
                # through to the raw path below
                evicted.packed = None
                if self._fold_packed_locked(packed, trace, seq):
                    if trace is not None:
                        trace.finish()
                    if self._metrics is not None:
                        self._metrics.sketch_records_total.inc(len(evicted))
                    if time.monotonic() >= self._window_deadline:
                        self._close_window_locked()
                    return
            ctl = self._overload
            if ctl is not None:
                # busy = fold seconds per wall second since the previous
                # arrival (EWMA): a healthy device that folds instantly
                # zeroes the depth term no matter how large arrivals are
                now = time.perf_counter()
                last, self._busy_last_t = self._busy_last_t, now
                if last is not None:
                    inst = min(1.0, self._busy_fold_s
                               / max(now - last, 1e-6))
                    self._busy_ewma = 0.5 * self._busy_ewma + 0.5 * inst
                self._busy_fold_s = 0.0
                ctl.update(self._pending_buf.n + len(evicted)
                           + self._queued_overlap_rows(),
                           self._ring.slot_wait_p95(),
                           busy=self._busy_ewma)
                evicted = ctl.admit(evicted)
            if trace is not None:
                if self._pending_trace is None:
                    self._pending_trace = trace  # the next fold finishes it
                else:
                    trace.finish()  # rare: two sampled evictions in one fold
            self._pending_buf.append(evicted, self._fold_events)
            if time.monotonic() >= self._window_deadline:
                self._close_window_locked()

    def _start_fold_worker(self) -> None:
        """(Re)start the overlap fold thread; the supervisor uses this as
        the sketch-fold stage's restart callable."""
        self._fold_thread = threading.Thread(
            target=self._fold_loop, name="sketch-fold", daemon=True)
        self._fold_thread.start()

    def _fold_loop(self) -> None:
        import queue as _queue
        while not self._closed.is_set():
            self.fold_heartbeat()
            try:
                evicted = self._handoff.get(timeout=0.2)
            except _queue.Empty:
                continue
            try:
                with self._inflight_lock:
                    self._inflight_rows -= len(evicted)
                self._export_evicted_now(evicted)
            except Exception as exc:
                # a fold-path bug loses THIS batch (counted), never the
                # worker — the same contract as the QueueExporter loop
                log.error("overlap fold failed (batch of %d dropped): %s",
                          len(evicted), exc)
                if self._metrics is not None:
                    self._metrics.count_error("tpu-sketch")
            finally:
                self._handoff.task_done()

    def _drain_handoff(self, timeout_s: float = 30.0) -> None:
        """Wait until every queued eviction has been admitted and folded
        (flush/shutdown path). Bounded: a dead fold worker must not hang
        flush forever — leftovers are drained synchronously by close()."""
        if self._handoff is None:
            return
        deadline = time.monotonic() + timeout_s
        while self._handoff.unfinished_tasks and \
                time.monotonic() < deadline:
            if (self._fold_thread is None
                    or not self._fold_thread.is_alive()):
                return  # close() (or the supervisor) owns the leftovers
            time.sleep(0.005)

    def _fold_events(self, events, feats, finish: bool = False):
        """The pending buffer's fold callback. On the resident ladder ring
        a steady fold dispatches each chunk once and returns the row ranges
        its regions left, which the buffer keeps for the next fold
        (`ShardedResidentStagingRing.fold(carry=True)`); with `finish` —
        whatever must end with an empty buffer: roll, flush, close — every
        row is consumed on return. Rows are counted where they are
        consumed."""
        t0 = time.perf_counter()
        n = len(events)
        carry = self._carry_ring and not finish
        left = None
        # batch trace continuity: the sampled eviction trace riding the
        # pending buffer (or a fold-local sample when none) — the gap from
        # its evict span to this fold span IS the export queue wait
        trace = self._pending_trace
        self._pending_trace = None
        if trace is None:
            trace = tracing.start_trace("fold")
        # the fold chunks name the evictions whose rows they carry: the
        # buffer knows them by row (an older eviction's left rows and tail
        # ride at the front of a later eviction's fold)
        first, last = self._pending_buf.evictions
        try:
            with trace.stage("fold", eviction=last):
                faultinject.fire("sketch.ingest")
                if self._pack_surface is not None:
                    # ship order must equal dict-mutation order: this raw
                    # fold's pack mutates the dictionaries NOW, so any
                    # fused arena still outstanding (packed earlier, not
                    # yet shipped) must not ship afterwards — no-op when
                    # none are outstanding (staging.ResidentPackSurface)
                    self._pack_surface.invalidate_for_raw_fold()
                chunks = trace.bind(evictions=f"{first}-{last}")
                if carry:
                    self._state, left = self._ring.fold(
                        self._state, events, trace=chunks, carry=True,
                        **feats)
                    n -= sum(hi - lo for lo, hi in left)
                else:
                    self._state = self._ring.fold(
                        self._state, events, trace=chunks, **feats)
        except staging.StagingWedged as exc:
            # the slot-wait budget tripped at a chunk boundary: the rows
            # not yet packed drop (no dictionary slot was committed for
            # them, so no epoch roll) — a wedged device costs at most one
            # batch per fold while the eviction feed keeps its cadence.
            # ADOPT the exception's state: earlier chunks of this fold may
            # have dispatched, and their ingests DONATED the state we
            # passed in — keeping self._state would keep deleted buffers
            # (exc.state is self._state when nothing dispatched)
            if exc.state is not None:
                self._state = exc.state
            log.error("staging slot-wait budget exceeded "
                      "(up to %d rows dropped): %s", n, exc)
            if self._metrics is not None:
                self._metrics.sketch_ingest_errors_total.inc()
                self._metrics.count_error("tpu-sketch-ingest")
            return None
        except Exception as exc:
            # graceful degradation: a device error loses THIS batch (counted)
            # instead of poisoning the exporter thread / window timer
            self._count_ingest_error(n, exc)
            return None
        finally:
            trace.finish()
            if self._overload is not None:
                self._busy_fold_s += time.perf_counter() - t0
        if self._metrics is not None:
            self._metrics.sketch_batches_total.inc()
            if self._tier_form == "interior":
                self._metrics.sketch_tiered_interior_folds_total.inc()
            self._metrics.sketch_records_total.inc(n)
            self._metrics.sketch_ingest_seconds.observe(
                time.perf_counter() - t0)
        return left

    def _count_ingest_error(self, n: int, exc: Exception) -> None:
        log.error("sketch ingest failed (batch of %d dropped): %s", n, exc)
        if self._metrics is not None:
            self._metrics.sketch_ingest_errors_total.inc()
            self._metrics.count_error("tpu-sketch-ingest")
        # resident feed: the host dictionary may have committed slot
        # definitions the device table never received (the dropped buffer
        # carried them). Roll the epoch so every live slot is redefined
        # through the new-key lane before any hot row references it —
        # otherwise later hot rows would score against stale device keys
        # (the resident-feed contract, CLAUDE.md)
        kdicts = getattr(self._ring, "kdicts", None)
        if kdicts is None:
            kd = getattr(self._ring, "kdict", None)
            kdicts = [kd] if kd is not None else []
        for kd in kdicts:
            kd.reset()
        if kdicts:
            self._ring.dict_resets += len(kdicts)
            if self._metrics is not None:
                self._metrics.sketch_resident_dict_epochs_total.inc(
                    len(kdicts))
        surface = getattr(self, "_pack_surface", None)
        if surface is not None:
            # the reset above IS an epoch roll — outstanding fused arenas
            # were packed against the pre-reset dictionaries
            surface.note_external_reset()

    def _drain_pending_locked(self) -> None:
        if self._pending:
            self._fold(self._pending)
            self._pending = []
        self._pending_buf.flush_to(
            functools.partial(self._fold_events, finish=True))
        if self._tenancy is not None:
            # ship any partially-filled tenant buffers as one last stacked
            # fold — a roll (or refresh) must never strand routed rows
            try:
                self._state = self._tenancy.flush(self._state)
            except staging.StagingWedged as exc:
                if exc.state is not None:
                    self._state = exc.state
                log.error("tenant flush hit the slot-wait budget "
                          "(buffered rows dropped): %s", exc)
                if self._metrics is not None:
                    self._metrics.sketch_ingest_errors_total.inc()
                    self._metrics.count_error("tpu-sketch-ingest")

    def _close_window_locked(self) -> None:
        """Drain pending rows and dispatch the roll, under ONE window trace
        (roll_drain + roll_dispatch spans; the render/sink spans attach when
        the queued report publishes on the timer thread)."""
        # `window=<n>` (this exporter's count of closed windows) rides the
        # handle from here to the sink, so a capture ties a report's render
        # and delivery to the roll that closed its window
        self._windows_closed += 1
        wtrace = tracing.start_trace("window").bind(
            window=self._windows_closed)
        try:
            with wtrace.stage("roll_drain"):
                self._drain_pending_locked()
            self._roll_locked(wtrace)
        except BaseException:
            # a failed roll never reaches the report queue, so nothing else
            # will seal the trace — a failing window's spans are exactly the
            # evidence the recorder exists for
            wtrace.finish()
            raise

    def flush(self) -> None:
        """Fold pending records, close the current window now, and publish
        the report synchronously (shutdown/tests path). With the overlap
        seam, queued evictions fold first — a flush observes everything
        exported before it."""
        self._drain_handoff()
        with self._lock:
            self._close_window_locked()
        self._publish_queued()

    def close(self) -> None:
        self._closed.set()
        # overlap fold worker first: it holds evictions the flush below
        # must observe; after the join any leftovers (worker died, or
        # raced the _closed flag) drain synchronously on this thread
        if self._fold_thread is not None:
            self._drain_handoff()
            self._fold_thread.join(timeout=10.0)
            import queue as _queue
            while True:
                try:
                    evicted = self._handoff.get_nowait()
                except _queue.Empty:
                    break
                with self._inflight_lock:
                    self._inflight_rows -= len(evicted)
                try:
                    # same per-batch containment as the fold worker: the
                    # leftover drain exists for the worker-died case, and
                    # the batch that killed it would otherwise re-raise
                    # here and abort the remaining teardown joins
                    self._export_evicted_now(evicted)
                except Exception as exc:
                    log.error("close-path fold failed (batch of %d "
                              "dropped): %s", len(evicted), exc)
                    if self._metrics is not None:
                        self._metrics.count_error("tpu-sketch")
                finally:
                    self._handoff.task_done()
        # a mid-flight query refresh (roll dispatch + table transfer on the
        # timer thread) must finish before the interpreter starts tearing
        # down, or its in-flight device work on a daemon thread aborts the
        # C++ runtime at exit ("terminate called without an active
        # exception") — give the join a refresh-sized budget; without the
        # refresh the timer only ever waits on its poll tick
        self._timer.join(timeout=10.0 if self._query_refresh_s else 2.0)
        # same exit hazard for the background ladder warm: an agent
        # SIGTERMed during its first ~minute can still be compiling ladder
        # entries here — _warm skips remaining entries once _closed is
        # set, so this join only ever waits out the ONE in-flight compile
        # (bounded: a wedged backend must not wedge shutdown forever)
        warm = getattr(self, "_warm_thread", None)
        if warm is not None and warm.is_alive():
            warm.join(timeout=30.0)
        self.flush()
        if self._tenancy is not None:
            self._tenancy.close()  # per-tenant series label hygiene
        if self._ckpt is not None:
            self._ckpt.close()
        sink_close = getattr(self._sink, "close", None)
        if sink_close is not None:
            sink_close()
        if self._delta_sink is not None:
            delta_close = getattr(self._delta_sink, "close", None)
            if delta_close is not None:
                delta_close()

    def _window_loop(self) -> None:
        while not self._closed.wait(timeout=self._window_poll_s):
            self.heartbeat()
            # outside the try: a bug in the timer stage itself — the
            # supervisor's job (restart), not the swallow-and-retry path
            faultinject.fire("sketch.window_timer")
            try:
                faultinject.fire("sketch.window_roll")
                with self._lock:
                    if time.monotonic() >= self._window_deadline:
                        self._close_window_locked()
            except Exception as exc:
                # a roll failure must not kill the timer — the next window
                # retries
                log.error("window roll failed (will retry next window): %s",
                          exc)
                if self._metrics is not None:
                    self._metrics.count_error("tpu-sketch")
            # publish OUTSIDE the exporter lock: folds proceed while the
            # report transfers/renders and the sink (possibly blocking
            # Kafka I/O) delivers. A crash here is a timer-stage bug — the
            # supervisor restarts the thread and the still-queued report
            # publishes exactly once after the restart (no double-emit:
            # the deadline already advanced at roll time).
            if self._reports:
                faultinject.fire("sketch.window_publish")
            self._publish_queued()
            self._maybe_refresh_query()

    def _maybe_refresh_query(self) -> None:
        """SKETCH_QUERY_REFRESH tick (timer thread). Disabled (the
        default), this is one is-None check — the zero-cost bar. A refresh
        failure is swallowed+counted; the next tick retries."""
        nxt = getattr(self, "_next_refresh", None)
        if nxt is None or self._closed.is_set() or time.monotonic() < nxt:
            return
        self._next_refresh = time.monotonic() + self._query_refresh_s
        try:
            self._refresh_query_snapshot()
        except Exception as exc:
            log.error("mid-window query refresh failed (will retry): %s",
                      exc)
            if self._metrics is not None:
                self._metrics.count_error("tpu-sketch-query")

    # --- internals ---
    def _make_single_device_ring(self, feed: str, resident_slots: int,
                                 pack_threads: int, metrics):
        """Single-device staging ring by feed format (SKETCH_FEED):
        "resident" (default) ships ~15B/record slot-id hot rows against a
        device key table (byte budget in docs/tpu_sketch.md; lane
        overflows continue into the next chunk, a full dictionary rolls
        its epoch) — SKETCH_PACK_THREADS > 1 splits the batch into that
        many pack LANES, each with its own dictionary + device key table,
        packed in true parallel (the host-pack ceiling scales with
        threads); "compact" ships 40B v4-compact rows with a dense
        fallback; "dense" ships 80B full-width rows (the debugging
        baseline — also what sharded meshes use)."""
        import jax

        sk = self._sk
        kw = dict(use_pallas=self._cfg.use_pallas, with_token=True,
                  tiered=self._tier_form)
        if feed == "resident":
            lanes = staging.pick_lanes(self._batch_size, self._lane_threads)
            ladder = self._superbatch
            bpl = self._batch_size // lanes
            caps = flowpack.default_resident_caps(bpl)
            wide_caps = flowpack.wide_resident_caps(bpl)

            # one fixed-shape jitted entry PER ladder size, every one under
            # its own name and retrace watch — a post-warmup compile of any
            # ladder shape is a live alarm (sketch_retraces_total{fn=..._xk})
            # and a device capture reads jit_ingest_resident_lanes_x<k>
            def entry(k, caps, family=""):
                return sk.make_ingest_resident_lanes_fn(
                    bpl, caps, k * lanes, resident_slots,
                    use_pallas=self._cfg.use_pallas,
                    name=f"ingest_resident_lanes{family}_x{k}",
                    tiered=self._tier_form)
            return staging.ShardedResidentStagingRing(
                self._batch_size, 1, {k: entry(k, caps) for k in ladder},
                key_tables=functools.partial(
                    sk.init_key_tables, max(ladder) * lanes, resident_slots),
                put=jax.device_put, caps=caps, slot_cap=resident_slots,
                metrics=metrics, pack_threads=pack_threads, lanes=lanes,
                ladder=ladder, lazy_ladder=True,
                # the wide lane family of the TOP entry (a key flood
                # saturates, so its chunks are top-entry chunks)
                wide_ingest={max(ladder): entry(max(ladder), wide_caps,
                                                "_wide")},
                wide_caps=wide_caps)
        if feed == "compact":
            spill_cap = staging.default_spill_cap(self._batch_size)
            return staging.DenseStagingRing(
                self._batch_size,
                sk.make_ingest_compact_fn(self._batch_size, spill_cap,
                                          name="ingest_compact", **kw),
                spill_cap=spill_cap,
                ingest_fallback=sk.make_ingest_dense_fn(
                    name="ingest_dense", **kw),
                metrics=metrics, pack_threads=pack_threads)
        if feed != "dense":
            log.warning("unknown SKETCH_FEED %r; using dense", feed)
        return staging.DenseStagingRing(
            self._batch_size,
            sk.make_ingest_dense_fn(name="ingest_dense", **kw),
            metrics=metrics, pack_threads=pack_threads)

    def _fold(self, records: list[Record]) -> None:
        t0 = time.perf_counter()
        trace = tracing.start_trace("fold")
        try:
            # always pad to the fixed batch size: a single static shape
            # means the jitted ingest compiles exactly once (no per-window
            # retraces). A from_records failure still propagates to the
            # caller (an export error, not an ingest error) — only the
            # trace seal is widened over it.
            with trace.stage("pack"):
                batch = FlowBatch.from_records(records,
                                               batch_size=self._batch_size)
            try:
                faultinject.fire("sketch.ingest")
                if self._tenancy is not None:
                    # Record path in tenant mode: pack through the columnar
                    # twin (arrays_to_dense IS the pinned dense layout) and
                    # route the valid rows — padding must not spend tenant
                    # fill-buffer slots
                    arrays = self._sk.batch_to_device(batch)
                    rows = self._sk.arrays_to_dense(arrays).reshape(
                        -1, self._sk.DENSE_WORDS)
                    self._state = self._tenancy.fold_rows(
                        self._state, rows[arrays["valid"]], trace=trace)
                else:
                    with trace.stage("ingest_dispatch"):
                        arrays = self._sk.batch_to_device(batch)
                        if self._distributed:
                            arrays = self._pm.shard_batch(self._mesh,
                                                          arrays)
                        self._state = self._ingest(self._state, arrays)
            except staging.StagingWedged as exc:
                # tenant path only: adopt the wedge's state (dispatched
                # stacked folds donated the reference we passed in)
                if exc.state is not None:
                    self._state = exc.state
                log.error("staging slot-wait budget exceeded (up to %d "
                          "rows dropped): %s", len(records), exc)
                if self._metrics is not None:
                    self._metrics.sketch_ingest_errors_total.inc()
                    self._metrics.count_error("tpu-sketch-ingest")
                return
            except Exception as exc:
                self._count_ingest_error(len(records), exc)
                return
        finally:
            trace.finish()
        if self._metrics is not None:
            self._metrics.sketch_batches_total.inc()
            if self._tier_form == "interior":
                self._metrics.sketch_tiered_interior_folds_total.inc()
            self._metrics.sketch_records_total.inc(len(records))
            self._metrics.sketch_ingest_seconds.observe(
                time.perf_counter() - t0)

    def _roll_locked(self, wtrace=tracing.NULL_TRACE) -> None:
        """Close the window UNDER self._lock: advance the deadline, dispatch
        the (async) device roll, swap in the fresh-window state, and queue
        the still-on-device report. No host transfer, JSON rendering, or
        sink I/O happens here — that is `_publish_queued`'s job on the
        window-timer thread, so `export_batch`/`export_evicted` callers
        blocked on this lock never wait behind a sink."""
        self._window_deadline = time.monotonic() + self._window_s
        if self._overload is not None:
            # bounded recovery: a pressure-free window snaps the shed
            # factor back to 1 even if the feed went idle (no updates)
            self._overload.window_roll()
        with wtrace.stage("roll_dispatch"):
            with self._roll_mutex:  # vs a concurrent refresh roll
                self._state, report, tables = self._roll(self._state)
        # the window trace rides the queued report; render/sink spans attach
        # at publish time on the timer thread (the gap in between is the
        # report's queue wait)
        self._reports.append((report, tables, wtrace))
        while len(self._reports) > self._max_queued_reports:
            # a wedged sink has the timer blocked mid-publish: shed the
            # OLDEST unpublished window instead of accumulating device
            # reports without bound (counted, like any lost report)
            try:
                _shed, _shed_tables, shed_trace = self._reports.popleft()
            except IndexError:
                break  # the publisher drained it between len() and pop
            shed_trace.finish()
            log.error("window report queue full (sink stalled?); "
                      "dropping the oldest unpublished report")
            if self._metrics is not None:
                # dedicated series (not the generic error counter): a
                # wedged sink shedding whole windows of reports deserves
                # its own alert line
                self._metrics.sketch_reports_shed_total.inc()
        # checkpointing stays at roll time: later folds DONATE self._state
        # into the jitted ingest, so a deferred save could read a deleted
        # buffer. orbax copies to host before save() returns; the int()
        # waits only for the roll itself, and only on checkpoint windows.
        if self._ckpt is not None and self._ckpt_every:
            self._n_windows_saved += 1
            if self._n_windows_saved % self._ckpt_every == 0:
                self._ckpt.save(int(report.window),
                                self._ckpt_state_view(self._state))

    def _publish_queued(self) -> None:
        """Render and deliver every queued window report (timer thread, or
        flush() at shutdown). A sink/render failure loses THAT report —
        counted, logged — because its window already rolled; the next
        window's report still flows."""
        with self._publish_lock:
            while self._reports:
                try:
                    report, tables, wtrace = self._reports.popleft()
                except IndexError:
                    return  # _roll_locked's shed loop emptied it first
                try:
                    self._publish_report(report, wtrace, tables=tables)
                except Exception as exc:
                    log.error("window report publish failed "
                              "(report lost): %s", exc)
                    if self._metrics is not None:
                        self._metrics.count_error("tpu-sketch")
                finally:
                    wtrace.finish()

    def _render_report(self, report, roll: bool = False,
                       tenant: Optional[int] = None) -> dict:
        """Render a device WindowReport with THIS exporter's thresholds.
        `roll=True` (closed-window publishes) additionally rotates the
        previous-roll heavy index the EvictedKeys diff reads — refreshes
        keep diffing against the last CLOSED window. `tenant` (tenant-mode
        fan-out) renders one tenant's slice of the stacked report against
        that tenant's OWN previous-roll index and stamps the id into the
        report object."""
        prev = (self._prev_heavy_index if tenant is None
                else self._tenant_prev_heavy.get(tenant))
        obj = report_to_json(
            # the whole slot table, heaviest first: /query/topk serves
            # `?n=` up to the table size from the snapshot's copy; a sink
            # gets the first REPORT_HEAVY (_for_sink)
            report, max_heavy=self._cfg.topk,
            scan_fanout_threshold=self._scan_fanout,
            ddos_z_threshold=self._ddos_z,
            synflood_min=self._synflood_min,
            synflood_ratio=self._synflood_ratio,
            drop_z_threshold=self._drop_z,
            asym_min_bytes=self._asym_min_bytes,
            asym_ratio=self._asym_ratio,
            churn_ascent=self._churn_ascent,
            churn_min_bytes=self._churn_min_bytes,
            prev_heavy_index=prev,
            partial_window=not roll)
        if roll:
            idx = heavy_identity_index(report)
            if tenant is None:
                self._prev_heavy_index = idx
            else:
                self._tenant_prev_heavy[tenant] = idx
        if tenant is not None:
            obj["Tenant"] = int(tenant)
        return obj

    def _publish_query_snapshot(self, obj: dict, tables,
                                mid_window: bool = False,
                                tenant: Optional[int] = None) -> None:
        """Swap in a fresh query snapshot (query/snapshot.py seq-stamps it).
        The np.asarray touch is the device->host transfer of the CM planes
        — per window (or per refresh), on the timer thread, never under
        the exporter lock. `tenant` routes the snapshot to that tenant's
        publisher (tenant-mode fan-out) and rides in the snap dict — the
        alert engine's fingerprints and /query responses carry it."""
        snap = {
            "window": obj["Window"],
            "ts_ms": obj["TimestampMs"],
            "report": obj,
            "cm_bytes": np.asarray(tables["cm_bytes"]),
            "cm_pkts": np.asarray(tables["cm_pkts"]),
        }
        if tenant is not None:
            snap["tenant"] = int(tenant)
            self._tenant_query[tenant].publish(snap, mid_window=mid_window)
        else:
            self.query.publish(snap, mid_window=mid_window)
        # alert evaluation rides the publish it just observed (timer
        # thread); safe_evaluate swallows+counts — a failing evaluation
        # can never lose the snapshot (already swapped in) or the report
        # (the caller's own try covers that separately). The
        # ``alerts.evaluate`` fault point fires inside evaluate().
        if self._alerts is not None:
            self._alerts.safe_evaluate(snap, mid_window=mid_window)

    def _mesh_shards(self) -> dict:
        """{"data": D, "sketch": S} of the device mesh (1 x 1 off a mesh)."""
        if not self._distributed:
            return {"data": 1, "sketch": 1}
        return {k: int(v) for k, v in self._mesh.shape.items()}

    def query_status(self) -> dict:
        """/query/status payload: snapshot freshness + plane counters.
        Reads the publisher ONCE and derives seq/window/mid_window from
        that same snapshot — stats() and a racing publish between two
        reads would otherwise mix two snapshots' fields in one response
        (the torn-read guarantee covers this route too)."""
        snap = self.query.get()
        st = self.query.stats()
        st.update({"agent_id": self._agent_id,
                   "window_s": self._window_s,
                   "refresh_s": self._query_refresh_s,
                   "overloaded": self.overloaded})
        # the device mesh, and the width of the Count-Min planes ONE chip
        # folds into and /query/frequency indexes (cm_width / sketch shards)
        shards = self._mesh_shards()
        st["mesh"] = shards
        st["cm_local_width"] = self._cfg.cm_width // shards["sketch"]
        ring = self._ring
        if isinstance(ring, staging.ShardedResidentStagingRing):
            # which superbatch entries are compiled and selectable (the
            # construction warm enables them one by one; one that failed to
            # compile never appears) and what has been dispatched so far
            st["superbatch"] = {
                "ladder": list(ring.ladder),
                "warm": ring.warm_entries(),
                "folds": {str(k): n for k, n
                          in sorted(ring.superbatch_folds.items())},
                # of those folds, the chunks that took the wide lane family
                "wide_folds": ring.wide_folds}
        if getattr(self, "_tiered_degraded", False):
            # mirror of the tiered_degraded supervisor condition: why
            # resident memory is wide despite SKETCH_TIERED being set
            st["tiered_degraded"] = True
        if self._alerts is not None:
            # one view read (the read-once rule): active count and last
            # transition seq come from the SAME published alert view, so a
            # poller never needs a second racy /query/alerts round-trip
            st["alerts"] = self._alerts.summary()
        if self._archive is not None:
            # warehouse discovery: segment counts/levels/disk bytes so a
            # poller can range-query without probing for 404s
            st["archive"] = self._archive.stats()
        if self._tenant_query is not None:
            # tenant discovery: which planes have published, and each one's
            # current window — read each publisher ONCE (same torn-read
            # rule as the top-level snapshot)
            snaps_t = [p.get() for p in self._tenant_query]
            st["tenants"] = {
                "n": len(self._tenant_query),
                "published": sum(1 for s in snaps_t if s is not None),
                "stacked_folds": self._tenancy.folds,
                "routed_rows": self._tenancy.routed_rows,
                "windows": {str(t): (None if s is None else s["window"])
                            for t, s in enumerate(snaps_t)},
            }
        if snap is not None:
            st.update({"published": True, "seq": snap["seq"],
                       "window": snap["window"],
                       "mid_window": snap["mid_window"]})
            rep = snap["report"]
            st.update({
                "records": rep["Records"], "bytes": rep["Bytes"],
                "distinct_src_estimate": rep["DistinctSrcEstimate"],
                "drop_bytes": rep["DropBytes"],
                "quic_records": rep["QuicRecords"],
                "nat_records": rep["NatRecords"],
                "rtt_quantiles_us": rep["RttQuantilesUs"],
                "dns_latency_quantiles_us": rep["DnsLatencyQuantilesUs"],
                "suspects": {sig: len(rep[key]) for sig, key
                             in SIGNAL_FIELDS.items()},
            })
        return st

    def _refresh_query_snapshot(self) -> None:
        """Mid-window refresh (SKETCH_QUERY_REFRESH): re-run the EXISTING
        roll executable against a STAGED device-side copy of the live
        state and publish its report + tables WITHOUT adopting the rolled
        state — the live window keeps accumulating untouched. The copy is
        load-bearing, not defensive, on EVERY deployment: the mesh roll
        donates its input, and the single-device resident INGEST donates
        the state buffers — either way a concurrent fold deletes the live
        reference under this off-lock roll (the federation checkpoint
        staging pattern, aggregator.py). Only the copy happens
        under the exporter lock; the roll dispatch, render, transfer and
        publish all run OFF the lock on the timer thread. No new jitted
        entry exists to retrace. The buffered sub-batch tail IS drained
        first (the same padded fold the window close would dispatch —
        additive merge semantics make the early fold invisible in the
        window's final totals), so the refresh reflects every exported
        row; the drain only ever runs with the refresh enabled, so the
        disabled path keeps its exact fold sequence."""
        import jax
        import jax.numpy as jnp
        with self._lock:
            self._drain_pending_locked()
            # the copy is donation protection on EVERY deployment: the
            # mesh roll donates its input, and on a single device the
            # resident INGEST donates the state buffers — a fold racing
            # this refresh off the lock would delete the captured live
            # reference mid-roll (observed live as "Array has been
            # deleted" + a spurious roll retrace). The copy is enqueued
            # under the lock, so device program order reads the buffers
            # before any later fold's donation overwrites them (the
            # federation checkpoint staging pattern).
            staged = jax.tree.map(jnp.copy, self._state)
        with self._roll_mutex:  # vs a concurrent window-close roll
            _discard, report, tables = self._roll(staged)
        ts_ms = time.time_ns() // 1_000_000
        if self._tenancy is not None:
            # stacked refresh: one staged roll already closed every
            # tenant's view — fan the slices out to the per-tenant
            # publishers (mid-window publishes never enter history rings)
            from netobserv_tpu.sketch import tenancy
            nt = self._tenancy.n_tenants
            reps = tenancy.split_tenants(report, nt)
            tabs = (tenancy.split_tenants(tables, nt)
                    if tables is not None else [None] * nt)
            faultinject.fire("sketch.query_snapshot")
            for t, (rep, tab) in enumerate(zip(reps, tabs)):
                obj = self._render_report(rep, tenant=t)
                obj["TimestampMs"] = ts_ms
                self._publish_query_snapshot(obj, tab, mid_window=True,
                                             tenant=t)
            return
        obj = self._render_report(report)
        obj["TimestampMs"] = ts_ms
        faultinject.fire("sketch.query_snapshot")
        self._publish_query_snapshot(obj, tables, mid_window=True)

    def _ckpt_state_view(self, state):
        """What a checkpoint saves: the state itself, or — tiered mode —
        its canonical wide decode (checkpoints never see the resident tier
        layout; format stamp unchanged). The decode is a retrace-watched
        jitted entry dispatched only on checkpoint windows."""
        if self._cfg.tiered is None:
            return state
        if self._tiered_decode is None:
            self._tiered_decode = retrace.jit(_tiered_decode, "tiered_decode",
                                              tiered="decode")
        return self._tiered_decode(state)

    def _publish_tier_metrics(self, tables, tenant=None) -> None:
        """Per-window tier telemetry from the published WIDE tables (the
        host copy the snapshot already paid for). The counter counts NEW
        promotions only: counters at/past base saturation this window that
        were NOT saturated at the previous closed-window publish — in
        decay/keep roll modes a steady heavy hitter stays promoted across
        windows and must not re-count every publish (the per-window-
        counter rule heavy_evictions pins). Reset mode clears the mask
        with the window, so there the delta equals occupancy. Timer
        thread, per window — never the fold path."""
        from netobserv_tpu.sketch.tiered import BASE_MAX
        spec = self._cfg.tiered
        for table, span in (("cm_bytes", BASE_MAX * spec.bytes_unit),
                            ("cm_pkts", BASE_MAX)):
            promoted = np.asarray(tables[table]) >= span
            fresh = promoted
            if self._tier_sticky_promotions:
                prev = self._tier_prev_promoted.get((table, tenant))
                if prev is not None:
                    fresh = promoted & ~prev
                self._tier_prev_promoted[(table, tenant)] = promoted
            self._metrics.sketch_tier_promotions_total.labels(
                table=table).inc(int(fresh.sum()))

    def _publish_report(self, report, wtrace=tracing.NULL_TRACE,
                        tables=None) -> None:
        if self._tenancy is not None:
            # stacked roll output: fan every tenant's slice out through the
            # same publish discipline (delta -> render -> snapshot -> sink
            # -> archive, each failure domain its own try)
            self._publish_report_tenants(report, wtrace, tables)
            return
        self._windows_published += 1  # telemetry: counts THIS window
        if self._delta_sink is not None and tables is not None:
            # federation delta FIRST, in its own try: a dead aggregator (or
            # a serialize bug) loses the frame — counted by the sink — but
            # never the local JSON report below. Per window, never per
            # record, like every fault point / span.
            try:
                with wtrace.stage("report_serialize"):
                    faultinject.fire("sketch.delta_export")
                    from netobserv_tpu.federation import delta as fdelta
                    # cross-process trace context: ONE check — an unsampled
                    # window answers None and the frame stays byte-identical
                    # to the context-less wire. Encoded once, here: the
                    # sink's retries resend these bytes, never a re-derived
                    # context.
                    ctx = tracing.context_of(
                        wtrace, origin=f"window@{self._agent_id}")
                    if ctx is not None and self._metrics is not None:
                        self._metrics.trace_context_propagated_total.labels(
                            "stamped").inc()
                    host_tables = {k: np.asarray(v)
                                   for k, v in tables.items()}
                    # window_seq rides the window counter (one frame per
                    # closed window); frame_uuid is drawn ONCE here — the
                    # sink's retry ladder resends these same bytes, so an
                    # ambiguous-deadline redelivery dedups at the ledger
                    frame = fdelta.encode_frame(
                        host_tables,
                        agent_id=self._agent_id,
                        window=int(np.asarray(report.window)),
                        ts_ms=time.time_ns() // 1_000_000,
                        agent_epoch=self._agent_epoch,
                        trace_ctx=ctx,
                        telemetry=self._telemetry_block(
                            int(float(host_tables["scalars"][0]))),
                        dims={"cm_depth": self._cfg.cm_depth,
                              "cm_width": self._cfg.cm_width,
                              "hll_precision": self._cfg.hll_precision,
                              "topk": self._cfg.topk,
                              "ewma_buckets": self._cfg.ewma_buckets})
                with wtrace.stage("delta_push"):
                    self._delta_sink(frame)  # sink swallows+counts inside
            except Exception as exc:
                log.error("delta frame serialize/push failed "
                          "(frame lost, report still publishes): %s", exc)
                if self._metrics is not None:
                    self._metrics.count_error("federation")
        with wtrace.stage("report_render"):
            # includes the device->host transfer of the report arrays (the
            # first np.asarray touch) — deliberately not split out, so the
            # un-traced path never adds a blocking device sync
            obj = self._render_report(report, roll=True)
        obj["TimestampMs"] = time.time_ns() // 1_000_000
        if self._metrics is not None:
            self._metrics.sketch_heavy_evictions_total.inc(
                obj["HeavyChurn"]["evictions"])
        # query-snapshot publish in its OWN try, BEFORE the sink: a failing
        # publish (the sketch.query_snapshot fault point's job to prove)
        # must never lose the window report, and a blocked sink must never
        # delay query freshness. Per window, never per record.
        try:
            with wtrace.stage("query_snapshot"):
                faultinject.fire("sketch.query_snapshot")
                self._publish_query_snapshot(obj, tables)
        except Exception as exc:
            log.error("query snapshot publish failed (window report still "
                      "publishes; /query serves the previous snapshot): %s",
                      exc)
            if self._metrics is not None:
                self._metrics.count_error("tpu-sketch-query")
        with wtrace.stage("report_sink"):
            self._sink(_for_sink(obj))
        # sketch-warehouse write LAST, in its own try: the report already
        # reached the sink and the query snapshot already swapped in, so a
        # failing (or wedged) archive disk loses only durability of THIS
        # window's segment — counted, never the report. A hung write
        # stalls only this supervised timer thread (heartbeat stops, the
        # supervisor flips DEGRADED); ingest folds never wait here. The
        # host copies below are the staged snapshot — the roll's table
        # OUTPUTS, never the live donated state (the federation
        # checkpoint staging rule).
        if self._archive is not None and tables is not None:
            try:
                with wtrace.stage("archive_write"):
                    faultinject.fire("sketch.archive_write")
                    self._archive.write_window(
                        {k: np.asarray(v) for k, v in tables.items()},
                        window=int(obj["Window"]),
                        ts_ms=int(obj["TimestampMs"]))
            except Exception as exc:
                log.error("archive segment write failed (window %s not "
                          "archived; report already published): %s",
                          obj["Window"], exc)
                if self._metrics is not None:
                    self._metrics.count_error("tpu-sketch-archive")
        if self._metrics is not None:
            if self._cfg.tiered is not None and tables is not None:
                try:
                    self._publish_tier_metrics(tables)
                except Exception as exc:  # telemetry never loses a report
                    log.warning("tier metrics publish failed: %s", exc)
            self._metrics.sketch_window_reports_total.inc()
            self._metrics.sketch_window_records.set(obj["Records"])
            self._metrics.sketch_window_drop_bytes.set(obj["DropBytes"])
            for sig, key in SIGNAL_FIELDS.items():
                self._metrics.sketch_window_suspects.labels(sig).set(
                    len(obj[key]))

    def _publish_report_tenants(self, report, wtrace=tracing.NULL_TRACE,
                                tables=None) -> None:
        """Tenant-mode publish: split the stacked roll outputs ONCE (one
        device pull for the whole stack, then zero-copy per-tenant views)
        and run every tenant's slice through the same publish seams as the
        single-tenant path — delta frames first (per-tenant TenantInfo on
        the wire), render with per-tenant heavy-identity rotation, per-
        tenant snapshot publishes + alert evaluations, the sink, and
        per-tenant archive segments. Each failure domain keeps its own try
        and its single-tenant semantics: a dead aggregator loses frames,
        never the reports; a failing snapshot publish loses one tenant's
        freshness, never the window."""
        from netobserv_tpu.sketch import tenancy
        n = self._tenancy.n_tenants
        self._windows_published += 1  # telemetry: counts THIS window
        with wtrace.stage("report_render"):
            reps = tenancy.split_tenants(report, n)
            tabs = (tenancy.split_tenants(tables, n)
                    if tables is not None else [None] * n)
            objs = [self._render_report(rep, roll=True, tenant=t)
                    for t, rep in enumerate(reps)]
        ts_ms = time.time_ns() // 1_000_000
        for obj in objs:
            obj["TimestampMs"] = ts_ms
        if self._delta_sink is not None and tables is not None:
            try:
                with wtrace.stage("report_serialize"):
                    faultinject.fire("sketch.delta_export")
                    from netobserv_tpu.federation import delta as fdelta
                    ctx = tracing.context_of(
                        wtrace, origin=f"window@{self._agent_id}")
                    if ctx is not None and self._metrics is not None:
                        self._metrics.trace_context_propagated_total.labels(
                            "stamped").inc()
                    # ONE telemetry block per window (the publish-rate EWMA
                    # must see one publish, not N), stamped into every
                    # tenant's frame; window_seq rides the shared window
                    # counter — the aggregator's ledger keys per
                    # (agent, tenant) source (federation.delta.source_key)
                    total = sum(int(float(tab["scalars"][0]))
                                for tab in tabs)
                    tel = self._telemetry_block(total)
                    dims = {"cm_depth": self._cfg.cm_depth,
                            "cm_width": self._cfg.cm_width,
                            "hll_precision": self._cfg.hll_precision,
                            "topk": self._cfg.topk,
                            "ewma_buckets": self._cfg.ewma_buckets}
                    window = int(reps[0].window)
                    frames = [fdelta.encode_frame(
                        {k: np.asarray(v) for k, v in tab.items()},
                        agent_id=self._agent_id, window=window,
                        ts_ms=ts_ms, agent_epoch=self._agent_epoch,
                        trace_ctx=ctx, telemetry=tel, tenant=(t, n),
                        dims=dims) for t, tab in enumerate(tabs)]
                with wtrace.stage("delta_push"):
                    for frame in frames:
                        self._delta_sink(frame)  # sink swallows+counts
            except Exception as exc:
                log.error("tenant delta frame serialize/push failed "
                          "(frames lost, reports still publish): %s", exc)
                if self._metrics is not None:
                    self._metrics.count_error("federation")
        with wtrace.stage("query_snapshot"):
            for t, (obj, tab) in enumerate(zip(objs, tabs)):
                try:
                    faultinject.fire("sketch.query_snapshot")
                    self._publish_query_snapshot(obj, tab, tenant=t)
                except Exception as exc:
                    log.error("tenant %d query snapshot publish failed "
                              "(window report still publishes): %s", t, exc)
                    if self._metrics is not None:
                        self._metrics.count_error("tpu-sketch-query")
        with wtrace.stage("report_sink"):
            for obj in objs:
                self._sink(_for_sink(obj))
        if self._archive is not None and tables is not None:
            try:
                with wtrace.stage("archive_write"):
                    faultinject.fire("sketch.archive_write")
                    for t, (obj, tab) in enumerate(zip(objs, tabs)):
                        self._archive.write_tenant_window(
                            {k: np.asarray(v) for k, v in tab.items()},
                            window=int(obj["Window"]), ts_ms=ts_ms,
                            tenant=t)
            except Exception as exc:
                log.error("tenant archive segment write failed (window %s "
                          "not fully archived; reports already "
                          "published): %s", objs[0]["Window"], exc)
                if self._metrics is not None:
                    self._metrics.count_error("tpu-sketch-archive")
        if self._metrics is not None:
            m = self._metrics
            m.sketch_heavy_evictions_total.inc(
                sum(o["HeavyChurn"]["evictions"] for o in objs))
            if self._cfg.tiered is not None and tables is not None:
                try:
                    for t, tab in enumerate(tabs):
                        self._publish_tier_metrics(tab, tenant=t)
                except Exception as exc:  # telemetry never loses a report
                    log.warning("tier metrics publish failed: %s", exc)
            m.sketch_window_reports_total.inc()
            # agent-level gauges aggregate across tenants; the per-tenant
            # series carries each plane's own window totals
            m.sketch_window_records.set(sum(o["Records"] for o in objs))
            m.sketch_window_drop_bytes.set(
                sum(o["DropBytes"] for o in objs))
            for t, obj in enumerate(objs):
                m.sketch_tenant_window_records.labels(str(t)).set(
                    obj["Records"])
            for sig, key in SIGNAL_FIELDS.items():
                m.sketch_window_suspects.labels(sig).set(
                    sum(len(o[key]) for o in objs))
