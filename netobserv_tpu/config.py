"""Agent configuration: a single env-var-driven settings object.

Capability parity with the reference's env-tag struct (`pkg/config/config.go:83-308`):
same variable names, same defaults, zero flags / zero files. TPU-specific knobs are
added under the ``SKETCH_*`` prefix (the `tpu-sketch` exporter backend is new).
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
import re
from dataclasses import dataclass, field
from typing import Any, Optional

_DURATION_RE = re.compile(r"(\d+(?:\.\d+)?)(ns|us|µs|ms|s|m|h)")
_DURATION_UNITS = {
    "ns": 1e-9, "us": 1e-6, "µs": 1e-6, "ms": 1e-3, "s": 1.0, "m": 60.0, "h": 3600.0,
}


def parse_duration(text: str) -> float:
    """Parse a Go-style duration string ("5s", "300ms", "1m30s") into seconds."""
    text = text.strip()
    if not text:
        return 0.0
    try:
        return float(text)  # plain number = seconds
    except ValueError:
        pass
    total = 0.0
    pos = 0
    for m in _DURATION_RE.finditer(text):
        if m.start() != pos:
            raise ValueError(f"invalid duration: {text!r}")
        total += float(m.group(1)) * _DURATION_UNITS[m.group(2)]
        pos = m.end()
    if pos != len(text):
        raise ValueError(f"invalid duration: {text!r}")
    return total


def _parse_bool(text: str) -> bool:
    return text.strip().lower() in ("1", "true", "yes", "on")


def _env(name: str, default: str = "") -> dict:
    return {"metadata": {"env": name, "default": default}}


# Exporter backend names (reference: `pkg/agent/agent.go:246-261` switch).
EXPORT_GRPC = "grpc"
EXPORT_KAFKA = "kafka"
EXPORT_IPFIX_UDP = "ipfix+udp"
EXPORT_IPFIX_TCP = "ipfix+tcp"
EXPORT_DIRECT_FLP = "direct-flp"
# New in this framework: offload aggregation/analytics to TPU sketches.
EXPORT_TPU_SKETCH = "tpu-sketch"
# Debug-friendly terminal exporter (stdout JSON lines).
EXPORT_STDOUT = "stdout"

#: port-scan fan-out threshold default — the ONE definition; the
#: sketch_scan_fanout field and the tpu-sketch exporter both use it
DEFAULT_SCAN_FANOUT = 512

#: DDoS z-score threshold default — same single-definition treatment as
#: DEFAULT_SCAN_FANOUT (the two anomaly signals share an operational shape)
DEFAULT_DDOS_Z = 6.0

#: SYN-flood: minimum half-open attempts per victim bucket per window, and
#: the offered:accepted (SYN : SYN-ACK) ratio both required to report
DEFAULT_SYNFLOOD_MIN = 128
DEFAULT_SYNFLOOD_RATIO = 8.0

#: drop-anomaly z-score threshold (EWMA surge of dropped bytes per bucket)
DEFAULT_DROP_Z = 6.0

#: conversation asymmetry: minimum window bytes in a pair bucket and the
#: one-way share (max(dir)/total) at which it is reported
DEFAULT_ASYM_MIN_BYTES = 1 << 20
DEFAULT_ASYM_RATIO = 0.95

#: heavy-hitter churn (persistent-slot top-K plane): a slot whose window
#: count reaches ASCENT x its previous-window count (with at least
#: MIN_BYTES of current mass) renders as a flow ascent; the reciprocal
#: direction (prev >= MIN_BYTES, count <= prev/ASCENT) as a descent; a
#: slot first seen this window with >= MIN_BYTES as a new heavy key.
#: Single definitions — the renderer, the zoo runner, and the default
#: flow_ascent/new_heavy_key alert rules all read these
DEFAULT_CHURN_ASCENT = 8.0
DEFAULT_CHURN_MIN_BYTES = 1 << 20

VALID_EXPORTERS = (
    EXPORT_GRPC, EXPORT_KAFKA, EXPORT_IPFIX_UDP, EXPORT_IPFIX_TCP,
    EXPORT_DIRECT_FLP, EXPORT_TPU_SKETCH, EXPORT_STDOUT,
)


@dataclass
class FlowFilterRule:
    """One flow-filter rule (reference schema: `pkg/config/config.go:27-81`)."""

    ip_cidr: str = "0.0.0.0/0"
    action: str = "Accept"  # Accept | Reject
    direction: str = ""  # Ingress | Egress | ""
    protocol: str = ""  # TCP | UDP | SCTP | ICMP | ICMPv6
    source_port: int = 0
    source_port_range: str = ""
    source_ports: str = ""
    destination_port: int = 0
    destination_port_range: str = ""
    destination_ports: str = ""
    port: int = 0
    port_range: str = ""
    ports: str = ""
    icmp_type: int = 0
    icmp_code: int = 0
    peer_ip: str = ""
    peer_cidr: str = ""
    tcp_flags: str = ""  # e.g. "SYN", "SYN-ACK"
    drops: bool = False
    sample: int = 0  # per-rule sampling override

    @classmethod
    def from_json_obj(cls, obj: dict) -> "FlowFilterRule":
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in obj.items() if k in names})


def parse_filter_rules(text: str) -> list[FlowFilterRule]:
    """Parse the JSON-in-env FLOW_FILTER_RULES list (reference: `agent.go:445-474`)."""
    if not text.strip():
        return []
    data = json.loads(text)
    if not isinstance(data, list):
        raise ValueError("FLOW_FILTER_RULES must be a JSON array")
    return [FlowFilterRule.from_json_obj(o) for o in data]


@dataclass
class AgentConfig:  # noqa: PLR0902 - deliberately wide, mirrors reference
    """All agent knobs. Field metadata carries the env var name and default.

    Reference: `pkg/config/config.go:83-308` (same env names/defaults unless noted).
    """

    # --- identity / export target ---
    agent_ip: str = field(default="", **_env("AGENT_IP"))
    agent_ip_iface: str = field(default="external", **_env("AGENT_IP_IFACE", "external"))
    agent_ip_type: str = field(default="any", **_env("AGENT_IP_TYPE", "any"))
    export: str = field(default="grpc", **_env("EXPORT", "grpc"))
    target_host: str = field(default="", **_env("TARGET_HOST"))
    target_port: int = field(default=0, **_env("TARGET_PORT", "0"))
    target_tls_ca_cert_path: str = field(default="", **_env("TARGET_TLS_CA_CERT_PATH"))
    target_tls_user_cert_path: str = field(default="", **_env("TARGET_TLS_USER_CERT_PATH"))
    target_tls_user_key_path: str = field(default="", **_env("TARGET_TLS_USER_KEY_PATH"))
    grpc_message_max_flows: int = field(default=10000, **_env("GRPC_MESSAGE_MAX_FLOWS", "10000"))
    grpc_reconnect_timer: float = field(default=0.0, **_env("GRPC_RECONNECT_TIMER"))
    grpc_reconnect_timer_randomization: float = field(
        default=0.0, **_env("GRPC_RECONNECT_TIMER_RANDOMIZATION"))

    # --- interface selection ---
    interfaces: list[str] = field(default_factory=list, **_env("INTERFACES"))
    exclude_interfaces: list[str] = field(
        default_factory=lambda: ["lo"], **_env("EXCLUDE_INTERFACES", "lo"))
    interface_ips: list[str] = field(default_factory=list, **_env("INTERFACE_IPS"))
    listen_interfaces: str = field(default="watch", **_env("LISTEN_INTERFACES", "watch"))
    listen_poll_period: float = field(default=10.0, **_env("LISTEN_POLL_PERIOD", "10s"))
    preferred_interface_for_mac_prefix: str = field(
        default="", **_env("PREFERRED_INTERFACE_FOR_MAC_PREFIX"))

    # --- pipeline sizing ---
    buffers_length: int = field(default=50, **_env("BUFFERS_LENGTH", "50"))
    exporter_buffer_length: int = field(default=0, **_env("EXPORTER_BUFFER_LENGTH", "0"))
    cache_max_flows: int = field(default=5000, **_env("CACHE_MAX_FLOWS", "5000"))
    cache_active_timeout: float = field(default=5.0, **_env("CACHE_ACTIVE_TIMEOUT", "5s"))
    #: eviction drain worker lanes: each lane drains one per-CPU feature
    #: map (batched bpf(2) syscalls + native per-CPU merge, both
    #: GIL-releasing) while the calling thread drains the aggregation map;
    #: key alignment stays one vectorized join. 0 = auto (one lane per
    #: feature map, bounded by cores; 1-core hosts stay sequential),
    #: 1 = sequential drain (the pre-lane behavior, bit-identical output);
    #: an explicit N beyond the feature-map count turns the surplus into
    #: per-map merge row-shards (big-map relief)
    evict_drain_lanes: int = field(default=0, **_env("EVICT_DRAIN_LANES", "0"))
    #: fuse the whole per-drain host chain — batched bpf(2) drain, per-CPU
    #: merge, key-alignment join — into ONE GIL-releasing native call
    #: (flowpack fp_drain_to_resident) so drain lanes scale with cores
    #: instead of re-entering the interpreter between native islands.
    #: SCHEDULING ONLY: unset is bit-identical to the island chain (one
    #: is-None check); enabled output is equivalence-pinned against it
    #: (tests/test_native_pipeline.py). Requires the native library at the
    #: current ABI and kernel batch-op support — both probed on the first
    #: drain (which always runs the python chain), degrading silently to
    #: the island chain when either is missing
    evict_native_pipeline: bool = field(
        default=False, **_env("EVICT_NATIVE_PIPELINE", "false"))
    direction: str = field(default="both", **_env("DIRECTION", "both"))
    sampling: int = field(default=0, **_env("SAMPLING", "0"))
    enable_flows_ringbuf_fallback: bool = field(
        default=False, **_env("ENABLE_FLOWS_RINGBUF_FALLBACK", "false"))
    force_garbage_collection: bool = field(
        default=True, **_env("FORCE_GARBAGE_COLLECTION", "true"))
    stale_entries_evict_timeout: float = field(
        default=5.0, **_env("STALE_ENTRIES_EVICT_TIMEOUT", "5s"))

    # --- attach behavior ---
    tc_attach_mode: str = field(default="tcx", **_env("TC_ATTACH_MODE", "tcx"))
    tc_attach_retries: int = field(default=4, **_env("TC_ATTACH_RETRIES", "4"))
    tcx_attach_anchor_ingress: str = field(
        default="none", **_env("TCX_ATTACH_ANCHOR_INGRESS", "none"))
    tcx_attach_anchor_egress: str = field(
        default="none", **_env("TCX_ATTACH_ANCHOR_EGRESS", "none"))

    # --- kafka ---
    kafka_brokers: list[str] = field(default_factory=list, **_env("KAFKA_BROKERS"))
    kafka_topic: str = field(default="network-flows", **_env("KAFKA_TOPIC", "network-flows"))
    kafka_batch_messages: int = field(default=1000, **_env("KAFKA_BATCH_MESSAGES", "1000"))
    kafka_batch_size: int = field(default=1048576, **_env("KAFKA_BATCH_SIZE", "1048576"))
    kafka_async: bool = field(default=True, **_env("KAFKA_ASYNC", "true"))
    kafka_compression: str = field(default="none", **_env("KAFKA_COMPRESSION", "none"))
    kafka_enable_tls: bool = field(default=False, **_env("KAFKA_ENABLE_TLS", "false"))
    kafka_tls_insecure_skip_verify: bool = field(
        default=False, **_env("KAFKA_TLS_INSECURE_SKIP_VERIFY", "false"))
    kafka_tls_ca_cert_path: str = field(default="", **_env("KAFKA_TLS_CA_CERT_PATH"))
    kafka_tls_user_cert_path: str = field(default="", **_env("KAFKA_TLS_USER_CERT_PATH"))
    kafka_tls_user_key_path: str = field(default="", **_env("KAFKA_TLS_USER_KEY_PATH"))
    kafka_enable_sasl: bool = field(default=False, **_env("KAFKA_ENABLE_SASL", "false"))
    kafka_sasl_type: str = field(default="plain", **_env("KAFKA_SASL_TYPE", "plain"))
    kafka_sasl_client_id_path: str = field(default="", **_env("KAFKA_SASL_CLIENT_ID_PATH"))
    kafka_sasl_client_secret_path: str = field(
        default="", **_env("KAFKA_SASL_CLIENT_SECRET_PATH"))

    # --- observability ---
    log_level: str = field(default="info", **_env("LOG_LEVEL", "info"))
    pprof_addr: str = field(default="", **_env("PPROF_ADDR"))
    metrics_enable: bool = field(default=False, **_env("METRICS_ENABLE", "false"))
    metrics_level: str = field(default="info", **_env("METRICS_LEVEL", "info"))
    metrics_server_address: str = field(default="", **_env("METRICS_SERVER_ADDRESS"))
    metrics_server_port: int = field(default=9090, **_env("METRICS_SERVER_PORT", "9090"))
    metrics_tls_cert_path: str = field(default="", **_env("METRICS_TLS_CERT_PATH"))
    metrics_tls_key_path: str = field(default="", **_env("METRICS_TLS_KEY_PATH"))
    metrics_prefix: str = field(default="ebpf_agent_", **_env("METRICS_PREFIX", "ebpf_agent_"))

    # --- pipeline supervision (agent/supervisor.py; new) ---
    #: master switch for the stage supervisor (crash/hang detection,
    #: bounded restarts, DEGRADED transitions, /healthz detail)
    supervisor_enable: bool = field(
        default=True, **_env("SUPERVISOR_ENABLE", "true"))
    supervisor_check_period: float = field(
        default=0.25, **_env("SUPERVISOR_CHECK_PERIOD", "250ms"))
    #: consecutive failures a stage may accrue before it is DEGRADED
    supervisor_max_restarts: int = field(
        default=5, **_env("SUPERVISOR_MAX_RESTARTS", "5"))
    supervisor_backoff_initial: float = field(
        default=0.2, **_env("SUPERVISOR_BACKOFF_INITIAL", "200ms"))
    supervisor_backoff_max: float = field(
        default=30.0, **_env("SUPERVISOR_BACKOFF_MAX", "30s"))
    #: a stage healthy this long after a restart earns its budget back
    supervisor_healthy_reset: float = field(
        default=30.0, **_env("SUPERVISOR_HEALTHY_RESET", "30s"))
    #: hang deadline for fast-poll stages; timer-paced stages (map tracer,
    #: sketch window) get this ON TOP of their own period. The default must
    #: sit ABOVE the worst legitimate stall in a stage loop — the sketch
    #: ingest's first on-chip compile can block the exporter thread for
    #: minutes (see .claude/skills/verify) and must not be "detected"
    supervisor_heartbeat_timeout: float = field(
        default=300.0, **_env("SUPERVISOR_HEARTBEAT_TIMEOUT", "5m"))

    # --- feature enables (propagated to the datapath as compile-time consts) ---
    enable_rtt: bool = field(default=False, **_env("ENABLE_RTT", "false"))
    enable_pkt_drops: bool = field(default=False, **_env("ENABLE_PKT_DROPS", "false"))
    enable_dns_tracking: bool = field(default=False, **_env("ENABLE_DNS_TRACKING", "false"))
    dns_tracking_port: int = field(default=53, **_env("DNS_TRACKING_PORT", "53"))
    enable_network_events_monitoring: bool = field(
        default=False, **_env("ENABLE_NETWORK_EVENTS_MONITORING", "false"))
    network_events_monitoring_group_id: int = field(
        default=10, **_env("NETWORK_EVENTS_MONITORING_GROUP_ID", "10"))
    enable_pkt_translation: bool = field(
        default=False, **_env("ENABLE_PKT_TRANSLATION", "false"))
    enable_ipsec_tracking: bool = field(
        default=False, **_env("ENABLE_IPSEC_TRACKING", "false"))
    enable_openssl_tracking: bool = field(
        default=False, **_env("ENABLE_OPENSSL_TRACKING", "false"))
    openssl_path: str = field(default="/usr/bin/openssl", **_env("OPENSSL_PATH", "/usr/bin/openssl"))
    enable_tls_tracking: bool = field(default=False, **_env("ENABLE_TLS_TRACKING", "false"))
    quic_tracking_mode: int = field(default=0, **_env("QUIC_TRACKING_MODE", "0"))
    enable_udn_mapping: bool = field(default=False, **_env("ENABLE_UDN_MAPPING", "false"))

    # --- filtering ---
    flow_filter_rules: str = field(default="", **_env("FLOW_FILTER_RULES"))

    # --- program-manager (bpfman) mode ---
    ebpf_program_manager_mode: bool = field(
        default=False, **_env("EBPF_PROGRAM_MANAGER_MODE", "false"))
    bpfman_bpf_fs_path: str = field(
        default="/run/netobserv/maps", **_env("BPFMAN_BPF_FS_PATH", "/run/netobserv/maps"))

    # --- PCA (packet capture) mode ---
    enable_pca: bool = field(default=False, **_env("ENABLE_PCA", "false"))
    pca_server_port: int = field(default=0, **_env("PCA_SERVER_PORT", "0"))

    # --- direct-FLP ---
    flp_config: str = field(default="", **_env("FLP_CONFIG"))
    #: JSON file mapping IP -> Kubernetes metadata for add_kubernetes rules
    #: (the file-backed KubeDataSource; a live informer can be injected)
    flp_kube_map: str = field(default="", **_env("FLP_KUBE_MAP"))
    #: ip2location-layout range CSV for add_location rules
    flp_location_db: str = field(default="", **_env("FLP_LOCATION_DB"))

    # --- deprecated aliases (reference: `config.go:298-323`) ---
    flows_target_host: str = field(default="", **_env("FLOWS_TARGET_HOST"))
    flows_target_port: int = field(default=0, **_env("FLOWS_TARGET_PORT", "0"))

    # --- TPU sketch backend (new; no reference equivalent) ---
    sketch_batch_size: int = field(default=8192, **_env("SKETCH_BATCH_SIZE", "8192"))
    sketch_cm_depth: int = field(default=4, **_env("SKETCH_CM_DEPTH", "4"))
    #: Count-Min width W (power of two): a point query overestimates by at
    #: most e/W of the window's total with probability 1 - e^-depth, so size
    #: W for the DISTINCT keys a window holds — one counter a depth row for
    #: every 4 live keys keeps the tail's answers inside BASELINE.json's
    #: < 1% recall loss (docs/tpu_sketch.md "Sizing the sketch for a key
    #: count"): 65,536 for a node's few hundred thousand flows, 2^20-2^22
    #: for a cluster collector's millions. On the device: 2 planes x depth x
    #: W x 4 B (2 MB at the default, 134 MB at 2^22). The fold picks the
    #: Count-Min form from W by itself (sketch/state.fold_forms).
    sketch_cm_width: int = field(default=65536, **_env("SKETCH_CM_WIDTH", "65536"))
    sketch_hll_precision: int = field(default=14, **_env("SKETCH_HLL_PRECISION", "14"))
    sketch_topk: int = field(default=1024, **_env("SKETCH_TOPK", "1024"))
    sketch_window: float = field(default=60.0, **_env("SKETCH_WINDOW", "60s"))
    sketch_ewma_alpha: float = field(default=0.3, **_env("SKETCH_EWMA_ALPHA", "0.3"))
    sketch_checkpoint_dir: str = field(default="", **_env("SKETCH_CHECKPOINT_DIR"))
    sketch_checkpoint_every: int = field(default=0, **_env("SKETCH_CHECKPOINT_EVERY", "0"))
    sketch_mesh_shape: str = field(default="", **_env("SKETCH_MESH_SHAPE"))  # e.g. "2x4"
    #: auto (default) = the Pallas kernels on TPU at Count-Min widths >= 16K,
    #: the Count-Min form following the width (sketch/state.fold_forms), XLA
    #: scatter elsewhere; true/false (any bool spelling) force one path
    sketch_use_pallas: str = field(default="auto",
                                   **_env("SKETCH_USE_PALLAS", "auto"))
    # window handling: "reset" zeroes sketches each window; "decay" multiplies
    # linear sketches by SKETCH_DECAY_FACTOR instead (sliding-window flavor)
    sketch_window_mode: str = field(default="reset", **_env("SKETCH_WINDOW_MODE", "reset"))
    #: per-window distinct-(dst addr, dst port) pair fan-out at which a
    #: source bucket is reported as a port-scan suspect
    sketch_scan_fanout: int = field(
        default=DEFAULT_SCAN_FANOUT,
        **_env("SKETCH_SCAN_FANOUT", str(DEFAULT_SCAN_FANOUT)))
    #: EWMA z-score above which a destination bucket is reported as a DDoS
    #: suspect (per-window; see exporter/tpu_sketch.py report_to_json)
    sketch_ddos_z: float = field(default=DEFAULT_DDOS_Z,
                                 **_env("SKETCH_DDOS_Z", str(DEFAULT_DDOS_Z)))
    #: SYN-flood report gates: a victim bucket is reported when its window
    #: half-open count >= MIN and >= RATIO x its SYN-ACK responses
    sketch_synflood_min: int = field(
        default=DEFAULT_SYNFLOOD_MIN,
        **_env("SKETCH_SYNFLOOD_MIN", str(DEFAULT_SYNFLOOD_MIN)))
    sketch_synflood_ratio: float = field(
        default=DEFAULT_SYNFLOOD_RATIO,
        **_env("SKETCH_SYNFLOOD_RATIO", str(DEFAULT_SYNFLOOD_RATIO)))
    #: drop-anomaly z-score threshold (EWMA surge of dropped bytes)
    sketch_drop_z: float = field(default=DEFAULT_DROP_Z,
                                 **_env("SKETCH_DROP_Z", str(DEFAULT_DROP_Z)))
    #: conversation-asymmetry report gates: bucket volume floor and the
    #: one-way byte share (max direction / total) that flags it
    sketch_asym_min_bytes: int = field(
        default=DEFAULT_ASYM_MIN_BYTES,
        **_env("SKETCH_ASYM_MIN_BYTES", str(DEFAULT_ASYM_MIN_BYTES)))
    sketch_asym_ratio: float = field(
        default=DEFAULT_ASYM_RATIO,
        **_env("SKETCH_ASYM_RATIO", str(DEFAULT_ASYM_RATIO)))
    #: heavy-hitter churn render gates (persistent-slot top-K plane): the
    #: count:prev_count growth factor that renders a slot as a flow
    #: ascent/descent, and the current-mass floor for ascent + new-heavy
    #: listings (see exporter/tpu_sketch.py report_to_json)
    sketch_churn_ascent: float = field(
        default=DEFAULT_CHURN_ASCENT,
        **_env("SKETCH_CHURN_ASCENT", str(DEFAULT_CHURN_ASCENT)))
    sketch_churn_min_bytes: int = field(
        default=DEFAULT_CHURN_MIN_BYTES,
        **_env("SKETCH_CHURN_MIN_BYTES", str(DEFAULT_CHURN_MIN_BYTES)))
    #: native packer threads (0 = auto: cpu count, max 8). Dense feed:
    #: row-sharded single-pass packs. RESIDENT feed (the default): the
    #: batch splits into this many pack LANES, each with its own
    #: dictionary + device key table, packed in true parallel — the
    #: host-pack ceiling scales with threads (docs/tpu_sketch.md
    #: "host-path ceiling"). The single-chip compact pack stays a single
    #: pass (its data-dependent spill compaction doesn't row-shard; at
    #: ~80M rec/s it sits above any realistic link anyway)
    sketch_pack_threads: int = field(default=0,
                                     **_env("SKETCH_PACK_THREADS", "0"))
    #: tiered counter planes (sketch/tiered.py): keep the RESIDENT form of
    #: the CM planes + HLL banks narrow (u8 base + u16/u32 overflow tiers
    #: with in-executable saturation promotion; 6-bit packed HLL
    #: registers) — ~4x less HBM per resident sketch window at equal
    #: geometry (docs/tpu_sketch.md "Tiered counter planes"). Folds decode
    #: to the canonical wide tables transiently inside the same executable
    #: (`tiered=decode` in /debug/executables) — on a TPU always: the
    #: tier-interior Pallas walk is dead code on the device (Mosaic refuses
    #: it; it runs only interpreted, in the CPU suites). Single-device
    #: only; unset is bit-identical to the wide-resident path.
    sketch_tiered: bool = field(default=False, **_env("SKETCH_TIERED", "false"))
    #: CM columns sharing one u16 MID overflow cell (power of two)
    sketch_tier_mid_group: int = field(
        default=32, **_env("SKETCH_TIER_MID_GROUP", "32"))
    #: CM columns sharing one u32 TOP overflow cell (power of two,
    #: > mid_group, divides SKETCH_CM_WIDTH)
    sketch_tier_top_group: int = field(
        default=256, **_env("SKETCH_TIER_TOP_GROUP", "256"))
    #: byte quantum of the bytes plane's tiered units (power of two; folds
    #: CEIL to it — overestimate-preserving). The u8 base then spans
    #: 255*unit bytes per counter per window before promotion.
    sketch_tier_bytes_unit: int = field(
        default=256, **_env("SKETCH_TIER_BYTES_UNIT", "256"))
    sketch_decay_factor: float = field(default=0.5, **_env("SKETCH_DECAY_FACTOR", "0.5"))
    #: multi-tenant sketch planes (sketch/tenancy.py): > 0 stacks that many
    #: independent tenant states on a leading axis — ONE vmapped dispatch
    #: folds every tenant's evictions (rows route by a key-derived
    #: `ops/hashing.tenant_of` owner) and ONE roll closes every tenant's
    #: window; /query/*?tenant=, alerts, archive segments and delta frames
    #: fan out per tenant. 0 (default) is bit-identical to the
    #: single-tenant path (no stack object, one is-None check).
    #: Single-device only (config.validate rejects SKETCH_MESH_SHAPE).
    sketch_tenants: int = field(default=0, **_env("SKETCH_TENANTS", "0"))
    #: host->device feed format: "resident" (default, ~15B/record
    #: slot-id rows against a device key table; sharded meshes use one
    #: dictionary+table per data shard), "compact" (40B v4-compact rows,
    #: single-device only) or "dense" (80B full-width rows).
    sketch_feed: str = field(default="resident", **_env("SKETCH_FEED", "resident"))
    #: resident-feed key-table capacity (slots; power of two <= 2^20).
    #: A full dictionary rolls its epoch — size it above the flow-cache
    #: working set (CACHE_MAX_FLOWS): rows reach the pack regions by
    #: position, so EACH region's dictionary converges on every distinct
    #: key of the traffic, and a collector whose live keys pass the slot
    #: count rolls epochs (sketch_resident_dict_epochs_total). On the
    #: device: regions (max(SKETCH_SUPERBATCH) x pack lanes, 32 at the
    #: defaults) x slots x 40 B — 335 MB at 2^18, 1.34 GB at 2^20
    #: (sketch_resident_table_bytes) — and every fold relays the whole
    #: table, so a slot costs device time as well as memory
    sketch_resident_slots: int = field(
        default=1 << 18, **_env("SKETCH_RESIDENT_SLOTS", str(1 << 18)))
    # where window reports go: "stdout" (JSON lines) or "kafka" (uses the
    # KAFKA_* settings; one message per report, key = "sketch_report")
    sketch_report_sink: str = field(default="stdout", **_env("SKETCH_REPORT_SINK", "stdout"))
    #: superbatch fold ladder: comma-separated batch multiples (must
    #: include 1). Queued evictions coalesce into the largest fitting
    #: ladder shape and fold as ONE device dispatch; "1" disables
    #: coalescing (docs/tpu_sketch.md "superbatch fold coalescing")
    sketch_superbatch: str = field(default="1,2,4",
                                   **_env("SKETCH_SUPERBATCH", "1,2,4"))
    #: mid-window query-snapshot refresh period for the agent's /query/*
    #: surface (e.g. "5s"): the supervised timer thread re-runs the
    #: existing roll executable against the live state and publishes its
    #: report + tables WITHOUT closing the window. 0 (default) disables the
    #: refresh entirely — /query serves the last ROLL's snapshot and the
    #: exporter path is bit-identical to pre-query-plane behavior
    sketch_query_refresh: float = field(
        default=0.0, **_env("SKETCH_QUERY_REFRESH", "0"))
    #: closed-window snapshot ring for /query/* back-scroll: the publisher
    #: keeps the last N ROLL snapshots (mid-window refreshes never enter
    #: the ring) and `?window=<id>` serves point-in-time reads; evicted or
    #: never-seen ids answer 404. Still snapshot-only — no device op, no
    #: exporter lock. 0 disables the ring (?window= always 404s)
    sketch_query_history: int = field(
        default=8, **_env("SKETCH_QUERY_HISTORY", "8"))
    #: overlapped eviction dispatch: > 0 runs admit/buffer/fold on a
    #: dedicated supervised fold thread behind a bounded handoff of this
    #: depth, so the eviction feed's drain N+1 overlaps pack/dispatch N
    #: (1 = classic double buffer). A full handoff blocks the feed — the
    #: same backpressure as the synchronous seam, one batch deeper. 0
    #: (default) keeps the synchronous export_evicted path, bit-identical
    #: to the pre-overlap exporter
    sketch_overlap: int = field(default=0, **_env("SKETCH_OVERLAP", "0"))

    # --- overload control plane (sketch/overload.py; new) ---
    #: high watermark (in BATCHES: pending-fold depth weighted by the
    #: seam's fold-duty fraction, plus slot-wait pressure —
    #: docs/architecture.md "Overload & backpressure") above which the
    #: exporter sheds load by unbiased 1-in-N row sampling.
    #: 0 (default) disables shedding entirely: the export path is
    #: bit-identical to the unshedded agent (no RNG, no controller).
    sketch_shed_watermark: float = field(
        default=0.0, **_env("SKETCH_SHED_WATERMARK", "0"))
    #: ceiling on the AIMD shed factor N (at most 1-in-N rows admitted
    #: under sustained overload; the factor multiplies into each surviving
    #: row's `sampling` field so estimates stay unbiased)
    sketch_shed_max: int = field(default=64, **_env("SKETCH_SHED_MAX", "64"))
    #: bound on how long ONE fold may wait for a staging-ring slot when
    #: shedding is enabled — a wedged device then drops batches (counted)
    #: instead of wedging the eviction feed. Generous by default: the
    #: first on-chip compile legitimately stalls for minutes on cold
    #: caches, and the ladder warm runs in the background.
    sketch_shed_slot_budget: float = field(
        default=30.0, **_env("SKETCH_SHED_SLOT_BUDGET", "30s"))
    #: kernel aggregation-map occupancy fraction (of CACHE_MAX_FLOWS) at
    #: which the map tracer starts early evictions (at most 2x the
    #: configured cadence) to shrink the ringbuf-fallback window.
    #: 0 (default) disables pressure relief.
    map_pressure_watermark: float = field(
        default=0.0, **_env("MAP_PRESSURE_WATERMARK", "0"))

    # --- continuous detection & alerting plane (alerts/; new) ---
    #: declarative alert rule set over published query snapshots
    #: ("default" = one rule per anomaly signal; comma list picks a
    #: subset; cardinality_surge:<n> / topk_share:<f> add scalar rules —
    #: alerts/rules.py). Unset (the default) means NO engine exists: the
    #: exporter path is bit-identical to the alert-less agent (one
    #: is-None check — the tracing/fault-point zero-cost bar)
    alert_rules: str = field(default="", **_env("ALERT_RULES"))
    #: hysteresis: consecutive firing evaluations to RAISE an alert
    alert_raise_evals: int = field(default=2, **_env("ALERT_RAISE_EVALS", "2"))
    #: hysteresis: consecutive quiet CLOSED-WINDOW (roll) evaluations to
    #: CLEAR an active alert — mid-window refreshes hold state instead of
    #: counting (the signal plane resets each roll, so a sustained
    #: anomaly looks quiet while a fresh window re-accumulates)
    alert_clear_evals: int = field(default=2, **_env("ALERT_CLEAR_EVALS", "2"))
    #: transition fan-out sinks ("log,metrics" default; "webhook" POSTs
    #: JSON to ALERT_WEBHOOK_URL with per-sink rate limiting + bounded
    #: retry — alerts/sinks.py)
    alert_sinks: str = field(default="log,metrics",
                             **_env("ALERT_SINKS", "log,metrics"))
    alert_webhook_url: str = field(default="", **_env("ALERT_WEBHOOK_URL"))
    #: per-alert flap-suppression window for the webhook: a CLEAR landing
    #: within this interval of the alert's last delivery is HELD (the
    #: receiver keeps the alert visible through a flap) and reconciles
    #: once the interval expires — per-fingerprint delivery rate is
    #: bounded to ~2 per interval, distinct alerts are never throttled
    #: (alerts/sinks.py delivery discipline)
    alert_webhook_interval: float = field(
        default=1.0, **_env("ALERT_WEBHOOK_INTERVAL", "1s"))
    #: recent-transitions ring capacity (the /query/alerts "recent" list)
    alert_ring: int = field(default=256, **_env("ALERT_RING", "256"))

    # --- sketch warehouse (archive/; new) ---
    #: on-disk window archive directory ("" = no archive — the publish
    #: path is bit-identical to the pre-archive exporter). Set on a
    #: tpu-sketch agent (per-agent history) or on the federation
    #: aggregator (cluster-wide history); both mount /…/range over it
    archive_dir: str = field(default="", **_env("ARCHIVE_DIR"))
    #: RAW (per-window) segments kept per retention level before the
    #: oldest ARCHIVE_COMPACT_GROUP of them compact one level up
    archive_raw_windows: int = field(
        default=64, **_env("ARCHIVE_RAW_WINDOWS", "64"))
    #: segments merged per compaction (the RRD coarsening factor G):
    #: level-N super-windows each cover G^N raw windows
    archive_compact_group: int = field(
        default=8, **_env("ARCHIVE_COMPACT_GROUP", "8"))
    #: retention levels above raw; the top level deletes its oldest
    #: beyond the cap, bounding disk at
    #: (levels+1) * (ARCHIVE_RAW_WINDOWS + G - 1) segments
    archive_max_levels: int = field(
        default=3, **_env("ARCHIVE_MAX_LEVELS", "3"))
    #: largest single-dispatch merge size of the range-query ladder
    #: (power of two; one pre-built jit per power of two up to it —
    #: wider ranges chain dispatches)
    archive_merge_ladder_max: int = field(
        default=16, **_env("ARCHIVE_MERGE_LADDER_MAX", "16"))

    # --- sketch federation plane (federation/; new) ---
    #: "host:port" of the central aggregator's Federation gRPC endpoint;
    #: set on per-host agents to stream one delta frame per closed window
    #: (requires SKETCH_WINDOW_MODE=reset — decay frames are cumulative)
    federation_target: str = field(default="", **_env("FEDERATION_TARGET"))
    #: stable agent identity stamped into delta frames (default: hostname)
    federation_agent_id: str = field(default="",
                                     **_env("FEDERATION_AGENT_ID"))
    #: FEDERATION_MODE=aggregator turns `python -m netobserv_tpu` into the
    #: central aggregator tier instead of a flow agent
    federation_mode: str = field(default="", **_env("FEDERATION_MODE"))
    #: aggregator: Federation gRPC listen port (delta ingest)
    federation_listen_port: int = field(
        default=9999, **_env("FEDERATION_LISTEN_PORT", "9999"))
    #: aggregator: cluster-wide query surface HTTP port (0 = ephemeral,
    #: for tests; -1 disables the surface)
    federation_query_port: int = field(
        default=9998, **_env("FEDERATION_QUERY_PORT", "9998"))
    #: aggregator window period (cluster report + EWMA baseline roll)
    federation_window: float = field(default=60.0,
                                     **_env("FEDERATION_WINDOW", "60s"))
    #: aggregator device mesh ("" = single device; "4x1" shards agent
    #: ownership over the data axis and merges over ICI at window roll)
    federation_mesh_shape: str = field(default="",
                                       **_env("FEDERATION_MESH_SHAPE"))
    #: seconds without a delta before an agent counts as dark in /readyz
    #: detail and the staleness gauge commentary (2 windows by default)
    federation_stale_after: float = field(
        default=120.0, **_env("FEDERATION_STALE_AFTER", "120s"))
    #: seconds without a delta before the aggregator EVICTS an agent: it
    #: leaves the ownership view, its staleness gauge series is deleted
    #: (label cardinality stays bounded by the live fleet), and its
    #: delivery-ledger entry is forgotten. 0 disables eviction. A
    #: returning agent re-registers cleanly (fresh epoch after a restart).
    federation_agent_ttl: float = field(
        default=600.0, **_env("FEDERATION_AGENT_TTL", "600s"))
    #: aggregator checkpoint directory ("" = no checkpointing): the
    #: aggregate SketchState + per-agent delivery ledger are saved at each
    #: window roll and restored on startup — a restart loses at most the
    #: uncheckpointed partial window, never a closed one, and redelivered
    #: pre-crash frames still dedup against the restored ledger
    federation_checkpoint_dir: str = field(
        default="", **_env("FEDERATION_CHECKPOINT_DIR"))
    #: checkpoint every Nth aggregator window roll (1 = every window)
    federation_checkpoint_every: int = field(
        default=1, **_env("FEDERATION_CHECKPOINT_EVERY", "1"))

    def resolved_pack_threads(self) -> int:
        """SKETCH_PACK_THREADS with 0 = auto (cpu count, capped at 8)."""
        if self.sketch_pack_threads > 0:
            return self.sketch_pack_threads
        return min(os.cpu_count() or 1, 8)

    def parsed_superbatch_ladder(self) -> tuple:
        """SKETCH_SUPERBATCH as a sorted, deduplicated int tuple — the ONE
        parse of the ladder spec (validate() and the exporter both use it)."""
        try:
            ladder = tuple(sorted({int(tok) for tok in
                                   self.sketch_superbatch.split(",") if tok}))
        except ValueError as exc:
            raise ValueError(
                f"SKETCH_SUPERBATCH={self.sketch_superbatch!r}: "
                "want comma-separated ints, e.g. 1,2,4") from exc
        if not ladder or ladder[0] != 1 or any(k < 1 for k in ladder):
            raise ValueError(
                f"SKETCH_SUPERBATCH={self.sketch_superbatch!r}: the ladder "
                "must include 1 and be positive")
        if ladder[-1] > 64:
            # fail fast on a typo: every entry costs a jitted executable,
            # ring buffers and key-table rows sized k*batch — a stray
            # '400' would OOM at startup instead of erroring here
            raise ValueError(
                f"SKETCH_SUPERBATCH={self.sketch_superbatch!r}: ladder "
                "entries above 64 are almost certainly a typo (each costs "
                "k*batch-sized buffers and key-table rows)")
        return ladder

    def parsed_filter_rules(self) -> list[FlowFilterRule]:
        return parse_filter_rules(self.flow_filter_rules)

    def manage_deprecated(self) -> None:
        """Apply deprecated-key shims (reference: `config.go:310-323`)."""
        if self.flows_target_host and not self.target_host:
            self.target_host = self.flows_target_host
        if self.flows_target_port and not self.target_port:
            self.target_port = self.flows_target_port
        if self.enable_pca and self.pca_server_port and not self.target_port:
            self.target_port = self.pca_server_port

    def validate(self) -> None:
        if self.export not in VALID_EXPORTERS:
            raise ValueError(
                f"EXPORT={self.export!r} is not one of {', '.join(VALID_EXPORTERS)}")
        if self.export in (EXPORT_GRPC, EXPORT_IPFIX_UDP, EXPORT_IPFIX_TCP):
            if not self.target_host or not self.target_port:
                raise ValueError(
                    f"EXPORT={self.export}: TARGET_HOST and TARGET_PORT are required")
        if self.export == EXPORT_KAFKA and not self.kafka_brokers:
            raise ValueError("EXPORT=kafka: KAFKA_BROKERS is required")
        if self.sketch_cm_width < 2 or self.sketch_cm_width & (self.sketch_cm_width - 1):
            raise ValueError("SKETCH_CM_WIDTH must be a power of two >= 2")
        if self.sketch_tiered:
            for env_name, v, floor in (
                    ("SKETCH_TIER_MID_GROUP", self.sketch_tier_mid_group, 2),
                    ("SKETCH_TIER_TOP_GROUP", self.sketch_tier_top_group, 2),
                    ("SKETCH_TIER_BYTES_UNIT", self.sketch_tier_bytes_unit,
                     1)):
                if v < floor or v & (v - 1):
                    raise ValueError(
                        f"{env_name} must be a power of two >= {floor} "
                        f"(got {v}) — tier geometry must stay power-of-two-"
                        "compatible with SKETCH_CM_WIDTH")
            if self.sketch_tier_top_group <= self.sketch_tier_mid_group:
                raise ValueError(
                    f"SKETCH_TIER_TOP_GROUP ({self.sketch_tier_top_group}) "
                    f"must exceed SKETCH_TIER_MID_GROUP "
                    f"({self.sketch_tier_mid_group}): tiers must narrow as "
                    "counters widen")
            if self.sketch_cm_width % self.sketch_tier_top_group:
                raise ValueError(
                    f"SKETCH_TIER_TOP_GROUP ({self.sketch_tier_top_group}) "
                    f"must divide SKETCH_CM_WIDTH ({self.sketch_cm_width})")
            if self.sketch_mesh_shape:
                raise ValueError(
                    "SKETCH_TIERED has no owner-sharded form yet (tiered "
                    "counter planes are single-device); unset "
                    "SKETCH_MESH_SHAPE or SKETCH_TIERED")
        if self.sketch_tenants < 0:
            raise ValueError("SKETCH_TENANTS must be >= 0")
        if self.sketch_tenants and self.sketch_mesh_shape:
            raise ValueError(
                "SKETCH_TENANTS has no mesh-sharded form yet (the tenant "
                "stack is single-device, like SKETCH_TIERED); unset "
                "SKETCH_MESH_SHAPE or SKETCH_TENANTS")
        if not (4 <= self.sketch_hll_precision <= 18):
            raise ValueError("SKETCH_HLL_PRECISION must be in [4, 18]")
        if self.sketch_window_mode not in ("reset", "decay"):
            raise ValueError(
                f"SKETCH_WINDOW_MODE={self.sketch_window_mode!r} "
                "(want reset|decay)")
        if self.sketch_window_mode == "decay" and not (
                0.0 < self.sketch_decay_factor < 1.0):
            raise ValueError("SKETCH_DECAY_FACTOR must be in (0, 1)")
        if self.sketch_report_sink not in ("", "stdout", "kafka"):
            raise ValueError(
                f"SKETCH_REPORT_SINK={self.sketch_report_sink!r} "
                "(want stdout|kafka)")
        self.parsed_superbatch_ladder()  # raises on a malformed ladder spec
        if self.sketch_query_refresh < 0:
            raise ValueError(
                "SKETCH_QUERY_REFRESH must be >= 0 (0 disables the "
                "mid-window refresh)")
        if self.sketch_shed_watermark < 0:
            raise ValueError("SKETCH_SHED_WATERMARK must be >= 0 (0 disables)")
        if self.sketch_query_history < 0:
            raise ValueError("SKETCH_QUERY_HISTORY must be >= 0 "
                             "(0 disables the back-scroll ring)")
        if self.sketch_overlap < 0:
            raise ValueError("SKETCH_OVERLAP must be >= 0 (0 keeps the "
                             "synchronous export seam)")
        if self.evict_drain_lanes < 0:
            raise ValueError("EVICT_DRAIN_LANES must be >= 0 (0 = auto, "
                             "1 = sequential)")
        if self.sketch_shed_max < 2:
            raise ValueError("SKETCH_SHED_MAX must be >= 2 (it bounds the "
                             "1-in-N shed factor)")
        if not (0.0 <= self.map_pressure_watermark < 1.0):
            raise ValueError("MAP_PRESSURE_WATERMARK must be in [0, 1) "
                             "(a fraction of CACHE_MAX_FLOWS; 0 disables)")
        if self.alert_raise_evals < 1 or self.alert_clear_evals < 1:
            raise ValueError("ALERT_RAISE_EVALS and ALERT_CLEAR_EVALS "
                             "must be >= 1")
        if self.sketch_churn_ascent <= 1.0:
            raise ValueError("SKETCH_CHURN_ASCENT must be > 1 (it is a "
                             "window-over-window growth factor)")
        if self.sketch_churn_min_bytes < 0:
            raise ValueError("SKETCH_CHURN_MIN_BYTES must be >= 0")
        if self.alert_ring < 1:
            raise ValueError("ALERT_RING must be >= 1")
        if self.alert_webhook_interval < 0:
            raise ValueError("ALERT_WEBHOOK_INTERVAL must be >= 0")
        if self.alert_rules:
            # fail fast on a malformed rule spec or sink set (the engine
            # would only parse them at exporter construction otherwise);
            # the webhook-URL requirement is validated by the ONE sink
            # builder via a throwaway registry-less construction
            from netobserv_tpu.alerts.rules import parse_rules
            from netobserv_tpu.alerts.sinks import build_sinks
            parse_rules(self.alert_rules)
            build_sinks(self)
        if self.archive_compact_group < 2:
            raise ValueError("ARCHIVE_COMPACT_GROUP must be >= 2 (it is "
                             "the RRD coarsening factor)")
        if self.archive_raw_windows < self.archive_compact_group:
            raise ValueError(
                f"ARCHIVE_RAW_WINDOWS ({self.archive_raw_windows}) must "
                f"be >= ARCHIVE_COMPACT_GROUP "
                f"({self.archive_compact_group})")
        if self.archive_max_levels < 1:
            raise ValueError("ARCHIVE_MAX_LEVELS must be >= 1")
        v = self.archive_merge_ladder_max
        if v < 1 or v & (v - 1) or v > 64:
            raise ValueError(
                f"ARCHIVE_MERGE_LADDER_MAX must be a power of two in "
                f"[1, 64] (got {v}) — every power of two up to it costs "
                "a pre-built merge executable")
        if self.federation_mode not in ("", "aggregator"):
            raise ValueError(
                f"FEDERATION_MODE={self.federation_mode!r} "
                "(want empty|aggregator)")
        if self.federation_target and ":" not in self.federation_target:
            raise ValueError(
                f"FEDERATION_TARGET={self.federation_target!r} "
                "(want host:port)")
        if self.federation_target and self.sketch_window_mode == "decay":
            logging.getLogger("netobserv_tpu.config").warning(
                "FEDERATION_TARGET with SKETCH_WINDOW_MODE=decay: delta "
                "export is disabled (decayed tables are cumulative, the "
                "aggregator merges per-window deltas)")
        if self.sketch_cm_width < 16 * self.sketch_topk:
            # measured F1 cliff (docs/accuracy.md): top-K precision degrades
            # once Count-Min columns are shared by too many tracked keys —
            # warn, don't refuse (small-memory deployments may accept it)
            logging.getLogger("netobserv_tpu.config").warning(
                "SKETCH_CM_WIDTH=%d is below 16*SKETCH_TOPK=%d: heavy-hitter "
                "precision degrades measurably at this ratio (docs/"
                "accuracy.md); widen the sketch or shrink the top-K",
                self.sketch_cm_width, 16 * self.sketch_topk)


_DURATION_FIELDS = {
    "cache_active_timeout", "listen_poll_period", "stale_entries_evict_timeout",
    "grpc_reconnect_timer", "grpc_reconnect_timer_randomization", "sketch_window",
    "supervisor_check_period", "supervisor_backoff_initial",
    "supervisor_backoff_max", "supervisor_healthy_reset",
    "supervisor_heartbeat_timeout", "federation_window",
    "federation_stale_after", "federation_agent_ttl",
    "sketch_shed_slot_budget", "sketch_query_refresh",
    "alert_webhook_interval",
}


def _coerce(f: dataclasses.Field, raw: str) -> Any:
    if f.name in _DURATION_FIELDS:
        return parse_duration(raw)
    if f.type in ("bool", bool):
        return _parse_bool(raw)
    if f.type in ("int", int):
        return int(raw)
    if f.type in ("float", float):
        return float(raw)
    if f.type in ("list[str]",):
        return [s.strip() for s in raw.split(",") if s.strip()]
    return raw


def load_config(environ: Optional[dict] = None) -> AgentConfig:
    """Build an AgentConfig from environment variables (reference: env.Parse)."""
    environ = os.environ if environ is None else environ
    kwargs: dict[str, Any] = {}
    for f in dataclasses.fields(AgentConfig):
        env_name = f.metadata.get("env")
        if not env_name:
            continue
        raw = environ.get(env_name)
        if raw is None:
            continue
        if raw == "":
            # set-but-empty clears string/list fields (e.g. EXCLUDE_INTERFACES="")
            # but cannot express a numeric/bool value — treat as unset for those.
            if f.type in ("str", str):
                kwargs[f.name] = ""
            elif f.type in ("list[str]",):
                kwargs[f.name] = []
            continue
        kwargs[f.name] = _coerce(f, raw)
    cfg = AgentConfig(**kwargs)
    cfg.manage_deprecated()
    return cfg
