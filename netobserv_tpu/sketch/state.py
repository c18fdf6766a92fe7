"""Combined sketch state and the jittable ingest step — the framework's
"flagship model".

One `ingest` call folds a fixed-shape columnar flow batch into:
- Count-Min (bytes) + Count-Min (packets) over the 5-tuple (both f32),
- a top-K heavy-hitter table scored by CM byte estimates,
- a global distinct-source HyperLogLog, a per-destination HLL grid, and a
  per-source (dst, port) fan-out HLL grid (port-scan signal),
- RTT and DNS-latency log-histograms,
- EWMA accumulators per victim bucket: DDoS volume, half-open SYN attempts
  (+ the window's SYN-ACK responses for the offered:accepted ratio), and
  kernel-dropped bytes,
- drop-cause and DSCP histograms, QUIC/NAT marker totals,
- per-direction bytes of each unordered endpoint pair (conversation
  asymmetry — one-way/exfil shape).

The flag/drop/marker inputs ride the dense feed's feature lane (words
16..19, flowpack.cc layout); feeds without those columns simply skip the
corresponding signals (trace-time optional). The streaming-chunk design is
the long-context answer for this domain (SURVEY.md §5.7): state is
constant-size in stream length; batches are the "sequence chunks"; time is
windowed by `roll_window`.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from netobserv_tpu.model.columnar import KEY_WORDS, FlowBatch
from netobserv_tpu.model.flow import TcpFlags
from netobserv_tpu.ops import countmin, ewma, hashing, hll, quantile, topk
from netobserv_tpu.sketch import tiered
from netobserv_tpu.utils import retrace


class SketchConfig(NamedTuple):
    cm_depth: int = 4
    cm_width: int = 1 << 16
    hll_precision: int = 14
    perdst_buckets: int = 4096
    perdst_precision: int = 6
    # per-SOURCE fan-out grid (port-scan detection): distinct (dst, dport)
    # per source bucket
    persrc_buckets: int = 4096
    persrc_precision: int = 6
    topk: int = 1024
    hist_buckets: int = 1024
    ewma_buckets: int = 4096
    ewma_alpha: float = 0.3
    #: None = auto: the forms `fold_forms` picks from the platform and the
    #: Count-Min width (measured on the chip, docs/tpu_sketch.md); the
    #: scatter everywhere off-TPU, incl. CPU where the kernel interprets
    use_pallas: bool | None = None
    #: tiered counter planes (SKETCH_TIERED, sketch/tiered.py): the
    #: resident form of the CM planes + HLL banks goes narrow (u8 base +
    #: u16/u32 overflow tiers; 6-bit packed HLL registers), decoded to the
    #: canonical wide tables transiently inside the fold/roll executables.
    #: None (the default) keeps today's wide-resident path bit-identical.
    tiered: "tiered.TierSpec | None" = None

    @classmethod
    def from_agent_config(cls, cfg) -> "SketchConfig":
        raw = str(cfg.sketch_use_pallas).strip().lower()
        if raw in ("auto", ""):
            pallas = None
        else:
            # accept every spelling the old bool field accepted, so an
            # explicit opt-out like SKETCH_USE_PALLAS=0/off stays an opt-out
            pallas = raw in ("1", "true", "yes", "on")
        tiers = None
        if getattr(cfg, "sketch_tiered", False):
            tiers = tiered.TierSpec(
                mid_group=cfg.sketch_tier_mid_group,
                top_group=cfg.sketch_tier_top_group,
                bytes_unit=cfg.sketch_tier_bytes_unit)
        return cls(cm_depth=cfg.sketch_cm_depth, cm_width=cfg.sketch_cm_width,
                   hll_precision=cfg.sketch_hll_precision, topk=cfg.sketch_topk,
                   ewma_alpha=cfg.sketch_ewma_alpha,
                   use_pallas=pallas, tiered=tiers)


class SketchState(NamedTuple):
    cm_bytes: countmin.CountMin
    cm_pkts: countmin.CountMin
    # persistent-slot heavy-hitter table (ops/topk.SlotTable): rows keep
    # stable per-key identity across folds AND window rolls, so the roll
    # ships a ready top-K with per-key churn (counts vs prev_counts,
    # first_seen, epoch) — candidate maintenance lives in the batch walk
    heavy: topk.SlotTable
    hll_src: hll.HLL
    hll_per_dst: hll.PerDstHLL
    hll_per_src: hll.PerDstHLL  # fan-out grid: distinct (dst,port) per src
    hist_rtt: quantile.LogHist
    hist_dns: quantile.LogHist
    ddos: ewma.EWMA
    # SYN-flood signal: EWMA of half-open SYN attempts per victim bucket,
    # plus this window's SYN-ACK responses in the SAME buckets (the ratio
    # denominator; a flooded service accepts far fewer than it is offered)
    syn: ewma.EWMA
    synack: jax.Array         # f32[m] — current-window SYN-ACK responses
    # drop-anomaly signal: EWMA of dropped bytes per victim bucket
    drops_ewma: ewma.EWMA
    drop_causes: jax.Array    # f32[N_DROP_CAUSES] — window drop pkts by cause
    dscp_bytes: jax.Array     # f32[N_DSCP] — window bytes by DSCP class
    # conversation-asymmetry signal: bytes per DIRECTION of each unordered
    # endpoint pair (one-way elephants = exfiltration / UDP-flood shape).
    # The bucket hash is direction-invariant (sum of the two endpoint
    # hashes under one seed); "fwd" is the canonical lower-hash endpoint
    conv_fwd: jax.Array       # f32[m]
    conv_rev: jax.Array       # f32[m]
    # i32[] — records folded this window, an exact count: an f32 sum
    # stops counting odd additions past 2^24 (16.8M records a window).
    # f32 in a state whose count spans windows or agents (widen_count)
    total_records: jax.Array
    total_bytes: jax.Array    # f32[] — window totals
    total_drop_bytes: jax.Array    # f32[]
    total_drop_packets: jax.Array  # f32[]
    quic_records: jax.Array   # f32[] — window records with QUIC marker
    nat_records: jax.Array    # f32[] — window records with a NAT translation
    # valid slot-table occupants evicted by heavier challengers this window
    # (the churn record's eviction pressure scalar)
    heavy_evictions: jax.Array  # f32[]
    window: jax.Array         # i32[]


class WindowReport(NamedTuple):
    """Snapshot emitted at each window roll (still on device until pulled)."""

    heavy: topk.SlotTable
    distinct_src: jax.Array        # f32[] global cardinality estimate
    per_dst_cardinality: jax.Array  # f32[D]
    per_src_fanout: jax.Array       # f32[S] distinct (dst,port) per src bucket
    rtt_quantiles_us: jax.Array    # f32[5] for q = .5 .9 .95 .99 .999
    dns_quantiles_us: jax.Array    # f32[5]
    ddos_z: jax.Array              # f32[m] z-score per dst bucket
    syn_z: jax.Array               # f32[m] half-open SYN surge z per bucket
    syn_rate: jax.Array            # f32[m] this window's half-open attempts
    synack_rate: jax.Array         # f32[m] this window's SYN-ACK responses
    drop_z: jax.Array              # f32[m] dropped-bytes surge z per bucket
    drop_causes: jax.Array         # f32[N_DROP_CAUSES] drop pkts by cause
    dscp_bytes: jax.Array          # f32[N_DSCP] bytes by DSCP class
    conv_fwd: jax.Array            # f32[m] bytes toward the canonical dir
    conv_rev: jax.Array            # f32[m] bytes the other way
    total_records: jax.Array
    total_bytes: jax.Array
    total_drop_bytes: jax.Array
    total_drop_packets: jax.Array
    quic_records: jax.Array
    nat_records: jax.Array
    heavy_evictions: jax.Array
    window: jax.Array


QS = np.array([0.5, 0.9, 0.95, 0.99, 0.999], dtype=np.float32)

#: drop-cause histogram size — kernel SKB_DROP_REASON values clamp to the
#: last bucket (the enum tops out well below this; cf. reference
#: pkg/decode drop-cause table)
N_DROP_CAUSES = 128
#: DSCP class histogram size (6-bit code space)
N_DSCP = 64


def init_state(cfg: SketchConfig = SketchConfig()):
    if cfg.tiered is not None:
        # tiered counter planes (SKETCH_TIERED): encode a fresh wide state
        # — from zeros, the encode is exact. Everything downstream
        # branches on the state's TYPE, so this is the ONE entry gate.
        cfg.tiered.check(cfg.cm_width)
        return tiered.encode_state(init_state(cfg._replace(tiered=None)),
                                   cfg.tiered)
    return SketchState(
        # both counter planes are float32: packet counts stay exact below
        # 2^24 per window, and a single dtype lets the Pallas fold serve both
        cm_bytes=countmin.init(cfg.cm_depth, cfg.cm_width, jnp.float32),
        cm_pkts=countmin.init(cfg.cm_depth, cfg.cm_width, jnp.float32),
        heavy=topk.init_slots(cfg.topk, KEY_WORDS),
        hll_src=hll.init(cfg.hll_precision),
        hll_per_dst=hll.init_per_dst(cfg.perdst_buckets, cfg.perdst_precision),
        hll_per_src=hll.init_per_dst(cfg.persrc_buckets,
                                     cfg.persrc_precision),
        hist_rtt=quantile.init(cfg.hist_buckets),
        hist_dns=quantile.init(cfg.hist_buckets),
        ddos=ewma.init(cfg.ewma_buckets),
        syn=ewma.init(cfg.ewma_buckets),
        synack=jnp.zeros((cfg.ewma_buckets,), jnp.float32),
        drops_ewma=ewma.init(cfg.ewma_buckets),
        drop_causes=jnp.zeros((N_DROP_CAUSES,), jnp.float32),
        dscp_bytes=jnp.zeros((N_DSCP,), jnp.float32),
        conv_fwd=jnp.zeros((cfg.ewma_buckets,), jnp.float32),
        conv_rev=jnp.zeros((cfg.ewma_buckets,), jnp.float32),
        total_records=jnp.zeros((), jnp.int32),
        total_bytes=jnp.zeros((), jnp.float32),
        total_drop_bytes=jnp.zeros((), jnp.float32),
        total_drop_packets=jnp.zeros((), jnp.float32),
        quic_records=jnp.zeros((), jnp.float32),
        nat_records=jnp.zeros((), jnp.float32),
        heavy_evictions=jnp.zeros((), jnp.float32),
        window=jnp.zeros((), jnp.int32),
    )


def batch_to_device(batch: FlowBatch) -> dict[str, np.ndarray]:
    """Convert a host FlowBatch into the dtype-stable array dict the jitted
    ingest expects (bytes to float32 — u64 is unavailable without x64; sketch
    counters are float anyway)."""
    return {
        "keys": batch.keys.astype(np.uint32),
        "bytes": batch.bytes.astype(np.float32),
        "packets": batch.packets.astype(np.int32),
        "rtt_us": batch.rtt_us.astype(np.int32),
        "dns_latency_us": batch.dns_latency_us.astype(np.int32),
        "valid": batch.valid.astype(np.bool_),
        "sampling": batch.sampling.astype(np.int32),
        "tcp_flags": batch.tcp_flags.astype(np.int32),
        "dscp": batch.dscp.astype(np.int32),
        "drop_bytes": batch.drop_bytes.astype(np.int32),
        "drop_packets": batch.drop_packets.astype(np.int32),
    }


DENSE_WORDS = 20  # row width; must equal flowpack.DENSE_WORDS (layout twin)


def dense_to_arrays(dense: jax.Array) -> dict[str, jax.Array]:
    """Device-side unpack of the flowpack dense feed — one host->device
    transfer per batch instead of many. Accepts the batch either as (B, 20)
    rows or FLAT (B*20,) — flat is how the staging ring ships it: a 1-D
    transfer avoids the device tiling pad a 20-wide minor dimension
    suffers, and the reshape here fuses into the ingest executable. Row
    layout is pinned in flowpack.cc fp_pack_dense."""
    if dense.ndim == 1:
        dense = dense.reshape(-1, DENSE_WORDS)
    return {
        "keys": dense[:, :KEY_WORDS],
        "bytes": jax.lax.bitcast_convert_type(dense[:, 10], jnp.float32),
        "packets": dense[:, 11].astype(jnp.int32),
        "rtt_us": dense[:, 12].astype(jnp.int32),
        "dns_latency_us": dense[:, 13].astype(jnp.int32),
        "valid": dense[:, 14] != 0,
        "sampling": dense[:, 15].astype(jnp.int32),
        "tcp_flags": (dense[:, 16] & jnp.uint32(0xFFFF)).astype(jnp.int32),
        "dscp": ((dense[:, 16] >> 16) & jnp.uint32(0xFF)).astype(jnp.int32),
        "markers": (dense[:, 16] >> 24).astype(jnp.int32),
        "drop_bytes": (dense[:, 17] & jnp.uint32(0xFFFF)).astype(jnp.int32),
        "drop_packets": (dense[:, 17] >> 16).astype(jnp.int32),
        "drop_cause": (dense[:, 18] & jnp.uint32(0xFFFF)).astype(jnp.int32),
    }


def arrays_to_dense(arrays: dict[str, np.ndarray]) -> np.ndarray:
    """Host-side inverse of dense_to_arrays: pack an array dict into the
    flat flowpack dense feed — the one Python twin of the row layout pinned
    in flowpack.cc fp_pack_dense (tests and the dryrun build synthetic
    batches through here so a layout change has a single site). The feature
    columns (tcp_flags/dscp/markers/drop_*) are optional — absent keys pack
    as zero, matching a datapath with those trackers disabled."""
    n = len(arrays["valid"])
    zeros = np.zeros(n, np.uint32)

    def col(name):
        return np.asarray(arrays.get(name, zeros), np.uint32)

    dense = np.zeros((n, DENSE_WORDS), np.uint32)
    dense[:, :KEY_WORDS] = arrays["keys"]
    dense[:, 10] = np.asarray(arrays["bytes"], np.float32).view(np.uint32)
    dense[:, 11] = arrays["packets"]
    dense[:, 12] = arrays["rtt_us"]
    dense[:, 13] = arrays["dns_latency_us"]
    dense[:, 14] = np.asarray(arrays["valid"], np.uint32)
    dense[:, 15] = col("sampling")
    dense[:, 16] = ((col("tcp_flags") & 0xFFFF) | (col("dscp") << 16)
                    | (col("markers") << 24))
    # saturate the 16-bit drop lanes like flowpack.cc fill_feature_words
    # (the C side's inputs are u16 by dtype; this twin takes arbitrary
    # ints and must not bleed bits into the adjacent lane)
    dense[:, 17] = (np.minimum(col("drop_bytes"), 0xFFFF)
                    | (np.minimum(col("drop_packets"), 0xFFFF) << 16))
    dense[:, 18] = np.minimum(col("drop_cause"), 0xFFFF)
    return dense.reshape(-1)


class _TierHook:
    """Trace-time mailbox of one tier-interior fold: carries the resident
    TieredState into the body's branch points (so the CM walk folds the
    tier arrays directly and the fused signal walk folds the packed
    global-src bank) and collects the kernels' tier outputs for
    :func:`tiered.interior_encode`. Plain-Python mutation is safe here —
    tracing is linear and the hook never crosses a jit boundary."""

    __slots__ = ("state", "fuse_hll", "out")

    def __init__(self, state, fuse_hll: bool):
        self.state = state
        self.fuse_hll = fuse_hll
        self.out: dict = {}


def _tier_interior_ok(state) -> bool:
    """Static eligibility of the tier-interior Pallas walk (trace-time)."""
    from netobserv_tpu.ops.pallas import countmin_kernel
    width = state.tables.cm_bytes.base.shape[1]
    return countmin_kernel.tiered_eligible(width, state.spec)


#: Count-Min widths (inclusive) at which the factored one-hot kernel
#: (`ops/pallas/countmin_kernel._fold`) is the cheaper form on a TPU; outside
#: them the XLA scatter is. See :func:`fold_forms`.
CM_FACTORED_WIDTHS = (1 << 14, 1 << 19)


def fold_forms(width: int, use_pallas: bool | None = None,
               platform: str | None = None) -> tuple[bool, str]:
    """The ONE selection of a fold's forms from what the code observes:
    ``(run the Pallas kernels, Count-Min form)``, the form ``"factored"``
    (the one-hot contraction on the MXU) or ``"scatter"`` (XLA's scatter-add).
    `ingest`, every ladder factory and the mesh's per-shard fold resolve
    through here at trace time; nothing else compares a width.

    `use_pallas` None is the automatic rule (SKETCH_USE_PALLAS=auto), on a
    TPU only (`platform` defaults to `jax.default_backend()`):

    - the kernels (HLL, signals, the slot top-K walk and a Count-Min kernel)
      run from width `CM_FACTORED_WIDTHS[0]` up. That bound is the whole
      kernel set's and is kept where it was: the Count-Min call alone
      favours the kernel down to 2^13, the lowest width timed, and below it
      a sketch is a test's (no deployment runs under 2^16);
    - the Count-Min form follows the width, because the factored kernel's
      MACs are d x W a record and the scatter's touches are d: factored up to
      `CM_FACTORED_WIDTHS[1]`, the scatter above. Measured on a v5e (PR 32,
      the call alone, table in docs/tpu_sketch.md "Count-Min form by width").

    An explicit True (SKETCH_USE_PALLAS=on, the tests' kernel twins) forces
    every kernel, the factored one wherever the width tiles; False forces the
    scatter forms. The `est` the slot top-K reads back is `countmin.query` of
    the table whichever form folded it."""
    auto = use_pallas is None
    if auto:
        use_pallas = ((platform or jax.default_backend()) == "tpu"
                      and width >= CM_FACTORED_WIDTHS[0])
    factored = (use_pallas and width % 512 == 0
                and not (auto and width > CM_FACTORED_WIDTHS[1]))
    return bool(use_pallas), "factored" if factored else "scatter"


def tiered_fold_form(cfg: SketchConfig) -> str | None:
    """Which fold form a tiered pipeline under ``cfg`` engages on THIS
    backend: ``"interior"`` (tier-native Pallas walk), ``"decode"``
    (decode-to-wide wrap), or None when tiers are off. Mirrors the
    trace-time gate in :func:`ingest` — accounting/attribution only."""
    if cfg.tiered is None:
        return None
    if fold_forms(cfg.cm_width, cfg.use_pallas)[0]:
        from netobserv_tpu.ops.pallas import countmin_kernel
        if countmin_kernel.tiered_eligible(cfg.cm_width, cfg.tiered):
            return "interior"
    return "decode"


def ingest(state: SketchState, arrays: dict[str, jax.Array],
           sketch_axis: str | None = None, sketch_shards: int = 1,
           use_pallas: bool | None = None,
           tier_interior: bool | None = None,
           _tier: "_TierHook | None" = None) -> SketchState:
    """Fold one batch into all sketches. Pure; jit with donate_argnums=0.

    When `sketch_axis` is set (inside shard_map over a 2D mesh), the Count-Min
    planes and the slot table are width-sharded across that axis by KEY
    OWNERSHIP (`countmin.owner_shard`): `state.cm_*` are the LOCAL-width
    planes of this shard, and ownership is a row mask (named scope
    `owner_mask`) — the planes fold the rows this shard owns through the
    same form as a whole-width replica (`fold_forms` at the local width),
    the slot walk sees every other row dead, and the HLL and signal kernels
    fold the replicated planes as on one chip. Steady state performs NO
    collectives at all: a shard folds and point-queries its own keys
    entirely locally. The one psum-backed exact query (`query_sharded`)
    runs only inside the window-roll merge, which gathers per-shard tables
    and re-scores against the globally merged sketch
    (`parallel.merge.merge_states`).
    """
    if isinstance(state, tiered.TieredState):
        # tiered counter planes: decode the resident tiers to the canonical
        # wide tables TRANSIENTLY (inside this same executable), run the
        # exact same fold below — both equivalence-pinned forms (scatter
        # chain and Pallas walk) unchanged — then fold the per-counter
        # delta back through the saturation-promotion path. Static branch:
        # resolved at trace time, the wide path is untouched when disabled.
        if sketch_axis is not None:
            raise NotImplementedError(
                "SKETCH_TIERED has no owner-sharded form yet — tiered "
                "counter planes are single-device (config.validate blocks "
                "SKETCH_MESH_SHAPE with SKETCH_TIERED)")
        spec = state.spec
        # the same rule as the wide path, at the tiers' width
        up = fold_forms(state.tables.cm_bytes.base.shape[1], use_pallas)[0]
        if up and tier_interior is not False and _tier_interior_ok(state):
            # TIER-INTERIOR fold: the Pallas walks read/promote the narrow
            # tier arrays directly in VMEM — no wide CM temporary in HBM.
            # The decode-wrapped path below stays verbatim as the scatter
            # twin / equivalence oracle (tests/test_tiered.py pins
            # interior vs decode-wrapped-scatter bit-exact).
            from netobserv_tpu.ops.pallas import signal_kernel
            r = state.rest
            probe = signal_kernel.SignalPlanes(
                ddos_rate=r.ddos.rate, syn_rate=r.syn.rate,
                drops_rate=r.drops_ewma.rate, synack=r.synack,
                conv_fwd=r.conv_fwd, conv_rev=r.conv_rev,
                dscp_bytes=r.dscp_bytes, drop_causes=r.drop_causes)
            m_hll = state.tables.hll_src.shape[0] // 3 * 4
            fuse = (signal_kernel.eligible(probe)
                    and signal_kernel.hll_fusible(m_hll))
            hook = _TierHook(state, fuse)
            work = tiered.widen_interior(state, fuse)
            new_work = ingest(work, arrays, use_pallas=True, _tier=hook)
            return tiered.interior_encode(
                state, hook.out["cm_bytes"], hook.out["cm_pkts"],
                hook.out.get("hll_src"), new_work)
        cmb_wide = tiered.decode_plane(state.tables.cm_bytes, spec,
                                       spec.bytes_unit)
        cmp_wide = tiered.decode_plane(state.tables.cm_pkts, spec, 1)
        new_wide = ingest(tiered.widen(state, cmb_wide, cmp_wide), arrays,
                          use_pallas=use_pallas)
        return tiered.fold_encode(state, cmb_wide, cmp_wide, new_wide)
    use_pallas, cm_form = fold_forms(state.cm_bytes.width, use_pallas)
    words = arrays["keys"]
    valid = arrays["valid"]
    bytes_f = arrays["bytes"]
    pkts = arrays["packets"]
    with jax.named_scope("hash"):
        samp = arrays.get("sampling")
        if samp is not None:
            # de-bias sampled traffic: a 1-in-N sampled flow record stands for N
            # flows' worth of volume (reference scales at the collector via the
            # exported Sampling field; sketches must fold the scaled estimate or
            # heavy-hitter/volume numbers undercount). 0 = unsampled. The
            # overload controller (sketch/overload.py) leans on exactly this
            # lane: host-side shedding multiplies its 1-in-N factor into each
            # surviving row's sampling, so kernel sampling and overload shed
            # compose multiplicatively and both de-bias HERE — any change to
            # this factor changes the shed-unbiasedness contract pinned by
            # tests/test_overload.py.
            factor = jnp.maximum(samp, 1)
            bytes_f = bytes_f * factor.astype(jnp.float32)
            pkts = pkts * factor

        # ONE sweep computes every hash family (flow h1/h2, src bucket, dst
        # bucket, dst-port fan-out, src-sym): the murmur k-mix per key word is
        # shared across families instead of five independent base_hashes passes
        mhash = hashing.base_hashes_multi(words)
        h1, h2 = mhash.h1, mhash.h2
        src_h1, src_h2 = mhash.src_h1, mhash.src_h2
        dst_h1 = mhash.dst_h1

    # a width-sharded mesh changes WHICH rows a chip folds into its Count-Min
    # planes and its slot table, not how: ownership is a row mask (`mine`
    # stands where `valid` does) and the planes are the local width
    owned = None
    mine = valid
    if sketch_axis is not None:
        with jax.named_scope("owner_mask"):
            owned = countmin.owner_shard(h1, h2, sketch_shards) == \
                jax.lax.axis_index(sketch_axis).astype(jnp.int32)
            mine = valid & owned

    # each branch folds the two Count-Min planes and says how the slot
    # top-K scores against them (`topk_kw`); the walk itself is one call
    with jax.named_scope("countmin"):
        if _tier is not None:
            # tier-interior: the CM fields here are zero-size placeholders
            # (whose width trivially tiles) — the walk reads and promotes
            # the resident tier arrays directly
            from netobserv_tpu.ops.pallas import countmin_kernel
            t = _tier.state.tables
            new_cmb, new_cmp, est = countmin_kernel.update_two_tiered(
                t.cm_bytes, t.cm_pkts, h1, h2, bytes_f,
                pkts.astype(jnp.float32), valid, _tier.state.spec)
            _tier.out["cm_bytes"] = new_cmb
            _tier.out["cm_pkts"] = new_cmp
            cm_b, cm_p = state.cm_bytes, state.cm_pkts  # stay placeholders
            # the kernel already gathered the post-fold bytes estimate
            # from its transient wide view — exactly countmin.query of the
            # decode-wrapped form's cm_b
            topk_kw = dict(query_fn=lambda a, b: est,
                           use_pallas=state.heavy.k % 128 == 0)
        else:
            # the form `fold_forms` chose for this width (the LOCAL width on
            # a width-sharded mesh), named in the ops' metadata
            # (countmin/factored | countmin/scatter) and, with that width,
            # on the /debug/executables row of the entry being traced
            retrace.label("countmin", cm_form)
            retrace.label("countmin_width", str(state.cm_bytes.width))
            with jax.named_scope(cm_form):
                if cm_form == "factored":
                    from netobserv_tpu.ops.pallas import countmin_kernel
                    # fused: both planes share hash indices + one-hot build
                    cm_b, cm_p = countmin_kernel.update_two(
                        state.cm_bytes, state.cm_pkts, h1, h2, bytes_f,
                        pkts.astype(jnp.float32), mine)
                else:
                    cm_b, cm_p = countmin.update_two(
                        state.cm_bytes, state.cm_pkts, h1, h2, bytes_f, pkts,
                        mine)
            # persistent-slot maintenance in the batch walk: the fused
            # Pallas reduction twin engages with the other kernels
            # (lane-aligned K); the scatter form everywhere else —
            # bit-exact either way (tests/test_pallas_topk.py pins it)
            topk_kw = dict(
                use_pallas=use_pallas and state.heavy.k % 128 == 0)
            if owned is not None:
                # collective-free scoring: this shard fully owns its keys'
                # counters, so its table tracks exactly the keys it owns
                # and every other row is dead to it (the merge gathers the
                # tables across the sketch axis and re-scores globally)
                topk_kw["query_fn"] = lambda a, b: jnp.where(
                    owned, countmin.query(cm_b, a, b), -1.0)
    with jax.named_scope("topk"):
        heavy, evicted = topk.slot_update(
            state.heavy, cm_b, words, h1, h2, valid, window=state.window,
            **topk_kw)
    with jax.named_scope("hll_src"):
        if _tier is not None and _tier.fuse_hll:
            # the global-src bank stays 6-bit packed; the fused signal walk
            # below folds it and stashes the new packed bank in the hook
            hll_src = state.hll_src  # zero-size placeholder
        elif use_pallas and state.hll_src.regs.shape[0] % 512 == 0:
            from netobserv_tpu.ops.pallas import hll_kernel
            hll_src = hll_kernel.update(state.hll_src, src_h1, src_h2, valid)
        else:
            hll_src = hll.update(state.hll_src, src_h1, src_h2, valid)
    with jax.named_scope("hll_grids"):
        per_dst = hll.update_per_dst(state.hll_per_dst, dst_h1, src_h1,
                                     src_h2, valid)
        flags = arrays.get("tcp_flags")
        # port-scan signal: distinct (dst addr, dst port) fan-out per SOURCE
        # bucket — a scanner touches many; a normal client few. The (dst,
        # port) hashes come from the shared multi-hash sweep above (seed:
        # hashing.DSTPORT_FANOUT_SEED). Only INITIATOR-side flows count:
        # a flow that sent SYN+ACK together (the TcpFlags.SYN_ACK
        # composite) is a RESPONDER — without the gate a server answering
        # one NAT'd client churning through hundreds of source ports
        # sweeps hundreds of distinct (addr, port) pairs and lights the
        # grid (the nat_churn scenario). Initiators count whether the
        # handshake completed or not (SYN with or without a later ACK),
        # so both lone-SYN and full-connect scans fire; flows with no
        # SYN-side evidence at all (non-TCP rows, mid-capture sessions:
        # flags without SYN) keep the pre-gate behavior only when they
        # are not responders.
        fanout_valid = valid
        if flags is not None:
            f32 = flags.astype(jnp.int32)
            fanout_valid = valid & ((f32 & TcpFlags.SYN_ACK) == 0)
        per_src = hll.update_per_dst(state.hll_per_src, src_h1, mhash.dp_h1,
                                     mhash.dp_h2, fanout_valid)
    with jax.named_scope("quantile"):
        rtt = arrays["rtt_us"]
        dns = arrays["dns_latency_us"]
        gamma = quantile.gamma_for(state.hist_rtt.n_buckets)
        hist_rtt = quantile.update(state.hist_rtt, rtt, valid & (rtt > 0),
                                   gamma)
        hist_dns = quantile.update(state.hist_dns, dns, valid & (dns > 0),
                                   gamma)
    with jax.named_scope("signals"):
        # --- signal planes (trace-time optional feature columns: a feed
        # without a column — e.g. the legacy six-array dict — simply skips the
        # corresponding signal; the fused kernel receives a zero value row
        # instead, which is bit-identical to skipping) ---
        # conversation asymmetry hashes BOTH endpoints under one seed so the
        # pair bucket is direction-invariant (A->B and B->A land together);
        # the lower endpoint hash defines the canonical "fwd" direction.
        # src_sym hashes the src words under the dst seed — also exactly the
        # victim-bucket hash the SYN-ACK side needs.
        src_sym = mhash.src_sym
        mass = factor.astype(jnp.float32) if samp is not None else 1.0
        if flags is not None:  # read above, at the fan-out gate
            # SYN-flood: half-open attempts (SYN seen, never ACKed — a spoofed
            # flood leaves one such record per probe) bucket by victim = dst;
            # SYN-ACK response flows bucket by victim = src (the responder),
            # using the SAME hash seed so both land in one bucket per victim.
            # Flag bits ride the dense feed from the datapath's OR-accumulated
            # tcp_flags (reference exports them per flow, proto/flow.proto:30).
            f = flags.astype(jnp.int32)
            half_open = valid & ((f & TcpFlags.SYN) != 0) & \
                ((f & TcpFlags.ACK) == 0)
            is_synack = valid & ((f & TcpFlags.SYN_ACK) != 0)
        dscp = arrays.get("dscp")
        db = arrays.get("drop_bytes")
        cause = arrays.get("drop_cause") if db is not None else None
        tdb, tdp = state.total_drop_bytes, state.total_drop_packets
        if db is not None:
            dbf = db.astype(jnp.float32) * mass
            dpf = arrays["drop_packets"].astype(jnp.float32) * mass
            tdb = tdb + jnp.sum(jnp.where(valid, dbf, 0.0))
            tdp = tdp + jnp.sum(jnp.where(valid, dpf, 0.0))
        pair_idx = ((src_sym + dst_h1)
                    & jnp.uint32(state.conv_fwd.shape[0] - 1)
                    ).astype(jnp.int32)
        is_fwd = src_sym < dst_h1
        # self-pairs (src == dst: hairpin NAT, loopback capture) have no
        # meaningful direction — both ways would land "fwd" and fire a
        # false one-way alert every window; exclude them from the signal
        conv_ok = valid & (src_sym != dst_h1)

        use_signal_kernel = use_pallas
        if use_signal_kernel:
            from netobserv_tpu.ops.pallas import signal_kernel
            planes = signal_kernel.SignalPlanes(
                ddos_rate=state.ddos.rate, syn_rate=state.syn.rate,
                drops_rate=state.drops_ewma.rate, synack=state.synack,
                conv_fwd=state.conv_fwd, conv_rev=state.conv_rev,
                dscp_bytes=state.dscp_bytes, drop_causes=state.drop_causes)
            use_signal_kernel = signal_kernel.eligible(planes)
        if use_signal_kernel:
            # fused signal-plane fold: all eight scatter targets update in ONE
            # Pallas batch walk (ops/pallas/signal_kernel.py); absent feature
            # columns contribute zero-mass rows — bit-identical to skipping
            m_sig = state.conv_fwd.shape[0]
            zeros_b = jnp.zeros_like(bytes_f)
            izeros_b = jnp.zeros(bytes_f.shape, jnp.int32)
            dst_idx = (dst_h1 & jnp.uint32(m_sig - 1)).astype(jnp.int32)
            src_idx = (src_sym & jnp.uint32(m_sig - 1)).astype(jnp.int32)
            v_ddos = jnp.where(valid, bytes_f, 0.0)
            if flags is not None:
                v_syn = jnp.where(half_open, mass, 0.0)
                v_synack = jnp.where(is_synack, mass, 0.0)
            else:
                v_syn = v_synack = zeros_b
            if db is not None:
                v_drops = jnp.where(valid, dbf, 0.0)
            else:
                v_drops = zeros_b
            if cause is not None:
                cause_idx = jnp.minimum(cause.astype(jnp.int32),
                                        N_DROP_CAUSES - 1)
                v_cause = jnp.where(valid & (dpf > 0), dpf, 0.0)
            else:
                cause_idx, v_cause = izeros_b, zeros_b
            v_fwd = jnp.where(conv_ok & is_fwd, bytes_f, 0.0)
            v_rev = jnp.where(conv_ok & ~is_fwd, bytes_f, 0.0)
            if dscp is not None:
                dscp_idx = dscp.astype(jnp.int32) & (N_DSCP - 1)
                v_dscp = jnp.where(valid, bytes_f, 0.0)
            else:
                dscp_idx, v_dscp = izeros_b, zeros_b
            sig_idx = jnp.stack([dst_idx, src_idx, pair_idx, dscp_idx,
                                 cause_idx])
            sig_vals = jnp.stack([v_ddos, v_syn, v_drops, v_synack, v_fwd,
                                  v_rev, v_dscp, v_cause])
            if _tier is not None and _tier.fuse_hll:
                # tiered megakernel: the same signal fold plus the packed
                # global-src HLL lane in one walk (idx/rank mirror
                # hll_kernel.update exactly — max fold, bit-exact)
                packed = _tier.state.tables.hll_src
                m_hll = packed.shape[0] // 3 * 4
                hll_idx = (src_h1 & jnp.uint32(m_hll - 1)).astype(jnp.int32)
                hll_rank = jnp.where(valid, hll._rank(src_h2), 0)
                out, new_packed = signal_kernel.update_tiered(
                    planes, packed, sig_idx, sig_vals, hll_idx, hll_rank)
                _tier.out["hll_src"] = new_packed
            else:
                out = signal_kernel.update(planes, sig_idx, sig_vals)
            ddos = state.ddos._replace(rate=out.ddos_rate)
            syn_state = state.syn._replace(rate=out.syn_rate)
            drops_state = state.drops_ewma._replace(rate=out.drops_rate)
            synack_arr = out.synack
            conv_fwd, conv_rev = out.conv_fwd, out.conv_rev
            dscp_bytes, drop_causes = out.dscp_bytes, out.drop_causes
        else:
            # un-fused scatter chain (CPU / ineligible shapes)
            # — the fused kernel above is equivalence-pinned against exactly
            # this path (tests/test_pallas_signal.py)
            ddos = ewma.accumulate(state.ddos, dst_h1, bytes_f, valid)
            conv_fwd = state.conv_fwd.at[pair_idx].add(
                jnp.where(conv_ok & is_fwd, bytes_f, 0.0), mode="drop")
            conv_rev = state.conv_rev.at[pair_idx].add(
                jnp.where(conv_ok & ~is_fwd, bytes_f, 0.0), mode="drop")
            syn_state, synack_arr = state.syn, state.synack
            if flags is not None:
                syn_state = ewma.accumulate(state.syn, dst_h1,
                                            jnp.where(half_open, mass, 0.0),
                                            valid)
                sa_idx = (src_sym & jnp.uint32(state.synack.shape[0] - 1)
                          ).astype(jnp.int32)
                synack_arr = state.synack.at[sa_idx].add(
                    jnp.where(is_synack, mass, 0.0), mode="drop")
            dscp_bytes = state.dscp_bytes
            if dscp is not None:
                dscp_bytes = dscp_bytes.at[
                    dscp.astype(jnp.int32) & (N_DSCP - 1)].add(
                    jnp.where(valid, bytes_f, 0.0), mode="drop")
            drops_state, drop_causes = state.drops_ewma, state.drop_causes
            if db is not None:
                drops_state = ewma.accumulate(state.drops_ewma, dst_h1, dbf,
                                              valid)
            if cause is not None:
                ci = jnp.minimum(cause.astype(jnp.int32), N_DROP_CAUSES - 1)
                drop_causes = drop_causes.at[ci].add(
                    jnp.where(valid & (dpf > 0), dpf, 0.0), mode="drop")
    with jax.named_scope("totals"):
        mk = arrays.get("markers")
        quic_rec, nat_rec = state.quic_records, state.nat_records
        if mk is not None:
            mki = mk.astype(jnp.int32)
            quic_rec = quic_rec + jnp.sum(
                (valid & ((mki & 1) != 0)).astype(jnp.float32))
            nat_rec = nat_rec + jnp.sum(
                (valid & ((mki & 2) != 0)).astype(jnp.float32))

        return SketchState(
            cm_bytes=cm_b, cm_pkts=cm_p, heavy=heavy, hll_src=hll_src,
            hll_per_dst=per_dst, hll_per_src=per_src, hist_rtt=hist_rtt,
            hist_dns=hist_dns, ddos=ddos,
            syn=syn_state, synack=synack_arr, drops_ewma=drops_state,
            drop_causes=drop_causes, dscp_bytes=dscp_bytes,
            conv_fwd=conv_fwd, conv_rev=conv_rev,
            total_records=state.total_records + jnp.sum(
                valid.astype(jnp.int32)),
            total_bytes=state.total_bytes + jnp.sum(
                jnp.where(valid, bytes_f, 0.0)),
            total_drop_bytes=tdb, total_drop_packets=tdp,
            quic_records=quic_rec, nat_records=nat_rec,
            heavy_evictions=state.heavy_evictions + evicted,
            window=state.window,
        )


def make_ingest_fn(donate: bool = True,
                   use_pallas: bool | None = None,
                   tier_interior: bool | None = None,
                   name: str = "ingest", tiered: str | None = None):
    """Jitted ingest; donates the state buffers so updates are in-place on
    HBM. Like every factory here it goes through `retrace.jit`: `name` is
    the watch name AND the XLA module's (`jit_<name>`); `tiered` is the
    registry's fold-form attribution (`retrace.watch`)."""
    fn = lambda s, a: ingest(s, a, use_pallas=use_pallas,  # noqa: E731
                             tier_interior=tier_interior)
    return retrace.jit(fn, name, tiered=tiered,
                       donate_argnums=(0,) if donate else ())


COMPACT_WORDS = 10  # must equal flowpack.COMPACT_WORDS (layout twin)
_V4_PREFIX_WORD2 = 0xFFFF0000  # bytes 8..11 of a v4-in-v6 mapped address


def compact_to_arrays(flat: jax.Array, batch_size: int,
                      spill_cap: int) -> dict[str, jax.Array]:
    """Device-side unpack of the flowpack COMPACT feed (flat
    `[batch_size*10 v4 rows | spill_cap*20 dense rows]`, layout pinned in
    flowpack.cc fp_pack_compact). Reconstructs full 10-word v4-mapped keys
    from the 4-word compact form and concatenates the spill lane, yielding
    one (batch_size + spill_cap)-row array dict for the ordinary ingest —
    the row widening happens in HBM where bandwidth is ~free; the transfer
    link only ever saw ~half of the dense feed's bytes. Drop columns are
    zero on the compact lane by construction: drop-carrying rows always
    ride the spill lane (fp_pack_compact routes them there)."""
    c = flat[:batch_size * COMPACT_WORDS].reshape(batch_size, COMPACT_WORDS)
    spill = dense_to_arrays(
        flat[batch_size * COMPACT_WORDS:].reshape(spill_cap, DENSE_WORDS))
    zeros = jnp.zeros((batch_size,), jnp.uint32)
    prefix = jnp.full((batch_size,), _V4_PREFIX_WORD2, jnp.uint32)
    keys = jnp.stack(
        [zeros, zeros, prefix, c[:, 0],
         zeros, zeros, prefix, c[:, 1],
         c[:, 2], c[:, 3] & jnp.uint32(0x00FFFFFF)], axis=1)
    izeros = zeros.astype(jnp.int32)
    comp = {
        "keys": keys,
        "bytes": jax.lax.bitcast_convert_type(c[:, 4], jnp.float32),
        "packets": c[:, 5].astype(jnp.int32),
        "rtt_us": c[:, 6].astype(jnp.int32),
        "dns_latency_us": c[:, 7].astype(jnp.int32),
        "valid": (c[:, 3] & jnp.uint32(0x80000000)) != 0,
        "sampling": c[:, 8].astype(jnp.int32),
        "tcp_flags": (c[:, 9] & jnp.uint32(0xFFFF)).astype(jnp.int32),
        "dscp": ((c[:, 9] >> 16) & jnp.uint32(0xFF)).astype(jnp.int32),
        "markers": (c[:, 9] >> 24).astype(jnp.int32),
        "drop_bytes": izeros,
        "drop_packets": izeros,
        "drop_cause": izeros,
    }
    return {k: jnp.concatenate([comp[k], spill[k]], axis=0) for k in comp}


def make_ingest_compact_fn(batch_size: int, spill_cap: int,
                           donate: bool = True,
                           use_pallas: bool | None = None,
                           with_token: bool = False,
                           name: str = "ingest_compact",
                           tiered: str | None = None):
    """Jitted `(state, flat compact feed) -> state` (see compact_to_arrays /
    flowpack.pack_compact). `with_token` as in make_ingest_dense_fn."""
    def fn(s, flat):
        arrays = compact_to_arrays(flat, batch_size, spill_cap)
        s = ingest(s, arrays, use_pallas=use_pallas)
        return (s, flat[:1]) if with_token else s
    return retrace.jit(fn, name, tiered=tiered,
                       donate_argnums=(0,) if donate else ())


RESIDENT_HDR = 4   # layout twins of flowpack.cc fp_pack_resident
HOT_WORDS = 3
NK_WORDS = 11


def _region_nk(flat: jax.Array, batch_size: int, caps, base: int,
               past_end: int) -> tuple[jax.Array, jax.Array]:
    """A region's new-key lane as (table rows, key words): a defined row
    lands at `base + slot` (`base` = lane * slot_cap); an undefined one
    indexes `past_end`, the row count of the WHOLE table array, so a
    mode=\"drop\" scatter discards it — `base + slot_cap` would be slot 0
    of the next lane."""
    nk_off = (RESIDENT_HDR + batch_size * HOT_WORDS + caps.dns
              + caps.drop * 2)
    nk = flat[nk_off:nk_off + caps.nk * NK_WORDS].reshape(caps.nk, NK_WORDS)
    nk_def = (nk[:, 0] >> 31) != 0
    nk_row = jnp.where(
        nk_def, (nk[:, 0] & jnp.uint32(0xFFFFF)).astype(jnp.int32) + base,
        past_end)
    return nk_row, nk[:, 1:]


def _resident_region_arrays(flat: jax.Array, key_tables: jax.Array,
                            batch_size: int, caps, lane: int,
                            slot_cap: int) -> dict:
    """One resident region's rows as an array dict (layout pinned in
    flowpack.cc fp_pack_resident; host packer flowpack.pack_resident):
    gathers full 10-word keys by slot id from lane `lane`'s rows of the
    SHARED (L * slot_cap, KW) key tables, decodes the range-coded rtt/dns
    codes, scatters the sparse dns/drop lanes onto their rows, and
    concatenates the full-width spill lane. The region's new-key lane is
    NOT read here: the caller has already applied every region's in one
    combined scatter (`resident_lane_arrays`), which XLA updates in place
    under donation (a per-region scatter/gather CHAIN was measured to copy
    the full shared table once per region on the ladder path)."""
    hot_off = RESIDENT_HDR
    dns_off = hot_off + batch_size * HOT_WORDS
    drop_off = dns_off + caps.dns
    nk_off = drop_off + caps.drop * 2
    spill_off = nk_off + caps.nk * NK_WORDS
    hdr = flat[:RESIDENT_HDR]
    hot = flat[hot_off:dns_off].reshape(batch_size, HOT_WORDS)
    dnsl = flat[dns_off:drop_off]
    dropl = flat[drop_off:nk_off].reshape(caps.drop, 2)
    spill = dense_to_arrays(flat[spill_off:].reshape(caps.spill, DENSE_WORDS))

    w0 = hot[:, 0]
    valid = (w0 >> 31) != 0
    # the 20-bit slot field means something only where `valid`: hold every
    # row's index inside this lane (invalid rows are masked downstream)
    slots = jnp.minimum((w0 & jnp.uint32(0xFFFFF)).astype(jnp.int32),
                        slot_cap - 1)
    keys = key_tables[lane * slot_cap + slots]
    rtt = (((w0 >> 20) & jnp.uint32(0xFF))
           << (2 * ((w0 >> 28) & jnp.uint32(0x7)))).astype(jnp.int32)
    w2 = hot[:, 2]
    # sparse dns lane: unused entries are all-zero -> add 0 to row 0
    d_idx = (dnsl >> 16).astype(jnp.int32)
    d_val = ((dnsl & jnp.uint32(0xFFF))
             << ((dnsl >> 12) & jnp.uint32(0xF))).astype(jnp.int32)
    dns_arr = jnp.zeros((batch_size,), jnp.int32).at[d_idx].add(
        d_val, mode="drop")
    # sparse drop lane: bytes/packets scatter-add; cause scatter-max (a
    # value, not a count — zero rows are no-ops under max as well)
    r_idx = (dropl[:, 0] >> 16).astype(jnp.int32)
    zeros_b = jnp.zeros((batch_size,), jnp.int32)
    drop_bytes = zeros_b.at[r_idx].add(
        (dropl[:, 1] & jnp.uint32(0xFFFF)).astype(jnp.int32), mode="drop")
    drop_pkts = zeros_b.at[r_idx].add(
        (dropl[:, 1] >> 16).astype(jnp.int32), mode="drop")
    drop_cause = zeros_b.at[r_idx].max(
        (dropl[:, 0] & jnp.uint32(0xFFFF)).astype(jnp.int32), mode="drop")
    comp = {
        "keys": keys,
        "bytes": jax.lax.bitcast_convert_type(hot[:, 1], jnp.float32),
        "packets": (w2 & jnp.uint32(0x7FF)).astype(jnp.int32),
        "rtt_us": rtt,
        "dns_latency_us": dns_arr,
        "valid": valid,
        "sampling": jnp.broadcast_to(hdr[0].astype(jnp.int32), (batch_size,)),
        "tcp_flags": ((w2 >> 11) & jnp.uint32(0x7FF)).astype(jnp.int32),
        "dscp": ((w2 >> 22) & jnp.uint32(0x3F)).astype(jnp.int32),
        "markers": (w2 >> 28).astype(jnp.int32),
        "drop_bytes": drop_bytes,
        "drop_packets": drop_pkts,
        "drop_cause": drop_cause,
    }
    return {k: jnp.concatenate([comp[k], spill[k]], axis=0) for k in comp}


def init_key_tables(n_lanes: int, slot_cap: int) -> jax.Array:
    """Per-LANE device key tables for the lane-sharded resident feed on a
    single device: ONE (n_lanes * slot_cap, KEY_WORDS) u32 array, lane
    `l`'s slot `s` in row `l * slot_cap + s` — one independent table per
    host-side packer lane (`sketch.staging` lane-sharded ring), the
    single-device twin of `parallel.merge.init_resident_tables`. A TPU
    stores a 10-wide minor dimension words-major by itself (`{0,1:T(8,128)}`:
    words on the sublanes, rows on the 128 lanes), which is the form its
    scatter and gathers run in, so a fold updates the donated array in
    place and holds no second copy of it; the 3-D (lanes, slots, words)
    form tiled (8 lanes x 128 slots) and was relaid five times a fold
    (PERF.md section 6, PR 33). The 10 words fill 16 sublanes: the array
    holds 16/10 of the bytes `sketch_resident_table_bytes` reports (335 MB
    at 32 lanes x 2^18 slots is 537 MB of HBM, 1.34 GB at 2^20 slots 2.15
    GB). On the CPU the same array is row-major and the scatter is in place
    as well — a (KEY_WORDS, rows) array is not: XLA's CPU scatter wants the
    scattered dimension first and transposes the whole table to get it."""
    return jnp.zeros((n_lanes * slot_cap, KEY_WORDS), jnp.uint32)


def _resident_region_words(batch_size: int, caps) -> int:
    """Flat word count of one resident region — the layout twin of
    `flowpack.resident_buf_len` (state.py keeps its own constants so the
    device unpack has no host-package import)."""
    return (RESIDENT_HDR + batch_size * HOT_WORDS + caps.dns + caps.drop * 2
            + caps.nk * NK_WORDS + caps.spill * DENSE_WORDS)


def resident_lane_arrays(flat: jax.Array, key_tables: jax.Array,
                         batch_per_lane: int, caps, n_lanes: int,
                         slot_cap: int) -> tuple[dict, jax.Array]:
    """Unpack `n_lanes` concatenated resident regions against per-lane key
    tables into ONE array dict for the ordinary ingest — the device end of
    the three-place wire contract (flowpack.cc fp_pack_resident <->
    flowpack.pack_resident <-> here), which holds PER REGION: regions are
    looped and their fixed-shape columns concatenated, so the jitted caller
    never retraces. All the row widening happens in HBM; the transfer link
    only ever saw ~15 bytes/record (byte budget in docs/tpu_sketch.md).
    Returns (arrays, new_key_tables).

    `key_tables` is the (total_lanes * slot_cap, KEY_WORDS) array of
    `init_key_tables`: lane `l`'s slot `s` is row `l * slot_cap + s`, the
    2-D form the scatter and the gathers take as it stands, so the donated
    array is updated in place (no table-sized op but the scatter:
    tests/test_tpu_lowering.py pins it for the TPU). `slot_cap` is static
    and comes from the caller (the ring's `slot_cap`): the array may carry
    MORE lanes than `n_lanes` (the superbatch fold ladder: every ladder
    entry shares ONE array sized for the largest superbatch; a smaller
    entry scatters only into its leading lanes' rows), so its row count
    does not give it. EVERY region's new-key lane applies as one combined
    scatter on the shared donated array before any hot-row gather — XLA
    keeps that single scatter in place, where a per-region scatter/gather
    chain was measured to copy the full table array once per region; the
    within-region "new keys land before hot rows reference them" ordering
    is preserved because all scatters precede all gathers and lanes are
    row-disjoint."""
    with jax.named_scope("resident_decode"):
        return _resident_lane_arrays(flat, key_tables, batch_per_lane, caps,
                                     n_lanes, slot_cap)


def _resident_lane_arrays(flat, key_tables, batch_per_lane, caps, n_lanes,
                          slot_cap):
    total, kw = key_tables.shape
    if kw != KEY_WORDS or total % slot_cap or n_lanes * slot_cap > total:
        raise ValueError(
            f"key tables {key_tables.shape} do not hold {n_lanes} lanes of "
            f"{slot_cap} slots as (lanes * slot_cap, {KEY_WORDS})")
    words = _resident_region_words(batch_per_lane, caps)
    regions = [flat[i * words:(i + 1) * words] for i in range(n_lanes)]
    nk_parts = [_region_nk(r, batch_per_lane, caps, i * slot_cap, total)
                for i, r in enumerate(regions)]
    key_tables = key_tables.at[
        jnp.concatenate([r for r, _ in nk_parts])].set(
        jnp.concatenate([w for _, w in nk_parts]), mode="drop")
    lanes = [_resident_region_arrays(r, key_tables, batch_per_lane, caps, i,
                                     slot_cap)
             for i, r in enumerate(regions)]
    if n_lanes == 1:
        return lanes[0], key_tables
    out = {k: jnp.concatenate([a[k] for a in lanes], axis=0)
           for k in lanes[0]}
    return out, key_tables


def make_ingest_resident_lanes_fn(batch_per_lane: int, caps, n_lanes: int,
                                  slot_cap: int, donate: bool = True,
                                  use_pallas: bool | None = None,
                                  name: str = "ingest_resident_lanes",
                                  tiered: str | None = None):
    """Jitted `(state, key_tables, flat) -> (state, key_tables, token)` for
    the LANE-SHARDED resident feed on one device: `flat` concatenates
    `n_lanes` independent resident regions, each packed by its own host
    KeyDict (`sketch.staging.ShardedResidentStagingRing` with one shard and
    L lanes — the native pack releases the GIL, so lanes pack in true
    parallel), and `key_tables` is `init_key_tables(L, slot_cap)` with
    L >= n_lanes (`resident_lane_arrays`); `slot_cap` is the ring's.
    Always returns the slot-reuse token (the ring requires it)."""
    def fn(s, tables, flat):
        arrays, tables = resident_lane_arrays(flat, tables, batch_per_lane,
                                              caps, n_lanes, slot_cap)
        s = ingest(s, arrays, use_pallas=use_pallas)
        return s, tables, flat[:1]
    return retrace.jit(fn, name, tiered=tiered,
                       donate_argnums=(0, 1) if donate else ())


def make_ingest_dense_fn(donate: bool = True,
                         use_pallas: bool | None = None,
                         with_token: bool = False,
                         name: str = "ingest_dense",
                         tiered: str | None = None):
    """Jitted `(state, dense (B,20)u32) -> state` — the single-transfer host
    feed path (see dense_to_arrays / flowpack.pack_dense).

    `with_token=True` returns `(state, token)` where token is a tiny slice of
    the dense input: it becomes ready only once the whole ingest executable
    has finished reading the (possibly host-aliased) input buffer — the
    slot-reuse guard for `sketch.staging.DenseStagingRing`."""
    if with_token:
        def fn(s, d):
            return ingest(s, dense_to_arrays(d),
                          use_pallas=use_pallas), d.reshape(-1)[:1]
    else:
        fn = lambda s, d: ingest(s, dense_to_arrays(d),  # noqa: E731
                                 use_pallas=use_pallas)
    return retrace.jit(fn, name, tiered=tiered,
                       donate_argnums=(0,) if donate else ())


def widen_count(state: SketchState) -> SketchState:
    """`state` with its record count in f32, for a state whose count adds
    up many windows or agents (the federation aggregate, an archive merge,
    a state rolled in keep mode): an i32 wraps past 2^31 records, an f32
    sum is approximate past 2^24 and never wraps. A live window keeps the
    exact i32 count of `init_state`."""
    return state._replace(
        total_records=state.total_records.astype(jnp.float32))


def decay_state(state: SketchState, factor: float) -> SketchState:
    """Sliding-window flavor: scale the linear sketches by `factor` instead of
    zeroing them (Count-Min and histograms are linear, so decay is exact for
    them; HLL registers cannot decay and are reset). Slot-table counts are CM
    estimates, so they decay by the same factor to stay consistent with the
    window totals; `slot_roll` additionally snapshots this window's final
    counts into `prev_counts` (the churn baseline) while identity, first_seen
    and epoch persist."""
    if isinstance(state, tiered.TieredState):
        # decay the REST in the wide domain; the CM tiers scale at the
        # representation level (decay_plane) — a decode->re-encode here
        # would re-sum shared-cell attribution and compound the aliasing
        # every decay (counts would GROW under decay; pinned)
        wide_decayed = decay_state(tiered.decode_state(state), factor)
        return tiered.decay_encode(state, wide_decayed, factor)
    return state._replace(
        heavy=topk.slot_roll(state.heavy, factor),
        cm_bytes=countmin.CountMin(state.cm_bytes.counts * factor),
        cm_pkts=countmin.CountMin(
            (state.cm_pkts.counts.astype(jnp.float32) * factor
             ).astype(state.cm_pkts.counts.dtype)),
        hll_src=hll.HLL(jnp.zeros_like(state.hll_src.regs)),
        hll_per_dst=hll.PerDstHLL(jnp.zeros_like(state.hll_per_dst.regs)),
        hll_per_src=hll.PerDstHLL(jnp.zeros_like(state.hll_per_src.regs)),
        hist_rtt=quantile.LogHist(state.hist_rtt.counts * factor),
        hist_dns=quantile.LogHist(state.hist_dns.counts * factor),
        # window accumulators paired with an EWMA rate (synack) reset with
        # it; pure per-window histograms decay like the latency hists
        synack=jnp.zeros_like(state.synack),
        drop_causes=state.drop_causes * factor,
        dscp_bytes=state.dscp_bytes * factor,
        conv_fwd=state.conv_fwd * factor,
        conv_rev=state.conv_rev * factor,
        total_records=(state.total_records.astype(jnp.float32) * factor
                       ).astype(state.total_records.dtype),
        total_bytes=state.total_bytes * factor,
        total_drop_bytes=state.total_drop_bytes * factor,
        total_drop_packets=state.total_drop_packets * factor,
        quic_records=state.quic_records * factor,
        nat_records=state.nat_records * factor,
        # eviction EVENTS are per-window in every mode (decaying an event
        # count would re-report prior windows' fractional evictions
        # forever, and the publish-time counter inc assumes a window delta)
        heavy_evictions=jnp.zeros_like(state.heavy_evictions),
    )


def roll_window(state: SketchState, cfg: SketchConfig,
                reset_sketches: bool = True,
                decay_factor: float | None = None
                ) -> tuple[SketchState, WindowReport]:
    """Close the current window: emit a report, roll EWMA baselines, and
    reset (or decay) the windowed sketch state while keeping the baselines."""
    with jax.named_scope("roll"):
        return _roll_window(state, cfg, reset_sketches, decay_factor)


def _roll_window(state, cfg, reset_sketches, decay_factor):
    if isinstance(state, tiered.TieredState):
        # the decode-to-wide step folded into the existing roll executable:
        # the report and (via state_tables) the delta wire / query snapshot
        # see only canonical wide tables — no wire v4, no checkpoint bump.
        # The FRESH state re-tiers per roll mode WITHOUT a decode->encode
        # round trip (which would re-sum shared-overflow attribution and
        # compound it every window): reset encodes fresh zeros (exact),
        # decay scales the tier arrays elementwise, keep leaves them
        # verbatim.
        new_wide, report = _roll_window(tiered.decode_state(state), cfg,
                                        reset_sketches, decay_factor)
        if decay_factor is not None:
            new_state = tiered.decay_encode(state, new_wide, decay_factor)
        elif reset_sketches:
            new_state = tiered.encode_state(new_wide, state.spec)
        else:
            # keep mode leaves the CM planes and HLL banks untouched —
            # the resident tier arrays ARE that, bit for bit
            new_state = tiered.TieredState(
                state.tables, tiered._strip(new_wide), state.spec)
        return new_state, report
    ddos_state, z = ewma.roll(state.ddos, cfg.ewma_alpha)
    syn_state, syn_z = ewma.roll(state.syn, cfg.ewma_alpha)
    drops_state, drop_z = ewma.roll(state.drops_ewma, cfg.ewma_alpha)
    gamma = quantile.gamma_for(state.hist_rtt.n_buckets)
    report = WindowReport(
        heavy=state.heavy,
        distinct_src=hll.estimate(state.hll_src.regs),
        per_dst_cardinality=hll.estimate(state.hll_per_dst.regs),
        per_src_fanout=hll.estimate(state.hll_per_src.regs),
        rtt_quantiles_us=quantile.quantile(state.hist_rtt, jnp.asarray(QS), gamma),
        dns_quantiles_us=quantile.quantile(state.hist_dns, jnp.asarray(QS), gamma),
        ddos_z=z,
        syn_z=syn_z,
        syn_rate=state.syn.rate,
        synack_rate=state.synack,
        drop_z=drop_z,
        drop_causes=state.drop_causes,
        dscp_bytes=state.dscp_bytes,
        conv_fwd=state.conv_fwd,
        conv_rev=state.conv_rev,
        total_records=state.total_records,
        total_bytes=state.total_bytes,
        total_drop_bytes=state.total_drop_bytes,
        total_drop_packets=state.total_drop_packets,
        quic_records=state.quic_records,
        nat_records=state.nat_records,
        heavy_evictions=state.heavy_evictions,
        window=state.window,
    )
    if decay_factor is not None:
        new_state = decay_state(state, decay_factor)._replace(
            ddos=ddos_state, syn=syn_state, drops_ewma=drops_state,
            window=state.window + 1)
    elif reset_sketches:
        fresh = init_state(SketchConfig(
            cm_depth=state.cm_bytes.depth, cm_width=state.cm_bytes.width,
            hll_precision=state.hll_src.precision,
            perdst_buckets=state.hll_per_dst.regs.shape[0],
            perdst_precision=int(state.hll_per_dst.regs.shape[1]).bit_length() - 1,
            persrc_buckets=state.hll_per_src.regs.shape[0],
            persrc_precision=int(state.hll_per_src.regs.shape[1]).bit_length() - 1,
            topk=state.heavy.k, hist_buckets=state.hist_rtt.n_buckets,
            ewma_buckets=state.ddos.rate.shape[0], ewma_alpha=cfg.ewma_alpha))
        # the slot table PERSISTS across the roll (identity, first_seen,
        # epoch); only its windowed counts roll: prev_counts <- counts,
        # counts <- 0 — next window's estimates rebuild from the fresh CM
        # while incumbents defend with last window's mass
        # the count keeps its dtype (f32 in a widen_count state)
        new_state = fresh._replace(ddos=ddos_state, syn=syn_state,
                                   drops_ewma=drops_state,
                                   heavy=topk.slot_roll(state.heavy, 0.0),
                                   total_records=jnp.zeros_like(
                                       state.total_records),
                                   window=state.window + 1)
    else:
        # synack pairs with the syn EWMA's per-window rate (which roll just
        # zeroed) — it must reset with it even when sketches are kept, or
        # the flood ratio divides a window numerator by a cumulative
        # denominator and detection decays every window
        new_state = state._replace(ddos=ddos_state, syn=syn_state,
                                   drops_ewma=drops_state,
                                   synack=jnp.zeros_like(state.synack),
                                   # cumulative mode: counts keep growing
                                   # with the kept CM; churn = counts -
                                   # prev_counts per window. Eviction
                                   # EVENTS stay per-window like synack
                                   # (total_records grows too: widen_count
                                   # a state that may pass 2^31 records)
                                   heavy=topk.slot_roll(state.heavy, 1.0),
                                   heavy_evictions=jnp.zeros_like(
                                       state.heavy_evictions),
                                   window=state.window + 1)
    return new_state, report


def state_tables(state: SketchState) -> dict[str, jax.Array]:
    """The MERGEABLE table snapshot of a (pre-roll) state — the device twin
    of the federation delta-frame layout (`federation.delta.TABLE_SPEC`; the
    encoder itself is jax-free). Every entry merges exactly: CM planes and
    histograms add, HLL registers max, top-K candidates concat + re-score,
    signal-plane window rates add. EWMA baselines (mean/var) are absent by
    design — the aggregator keeps its own cluster-level baselines."""
    if isinstance(state, tiered.TieredState):
        # the delta wire and checkpoints keep seeing wide tables (tiers are
        # a steady-state representation only)
        return state_tables(tiered.decode_state(state))
    return {
        "cm_bytes": state.cm_bytes.counts,
        "cm_pkts": state.cm_pkts.counts,
        "heavy_words": state.heavy.words,
        "heavy_h1": state.heavy.h1,
        "heavy_h2": state.heavy.h2,
        "heavy_counts": state.heavy.counts,
        "heavy_valid": state.heavy.valid,
        # persistent-slot churn metadata (delta wire v3): prev_counts merge
        # by SUM (per-shard partials of one key add), first_seen MIN,
        # epoch MAX — federation.delta.TABLE_SPEC carries all three
        "heavy_prev_counts": state.heavy.prev_counts,
        "heavy_first_seen": state.heavy.first_seen,
        "heavy_epoch": state.heavy.epoch,
        "hll_src": state.hll_src.regs,
        "hll_per_dst": state.hll_per_dst.regs,
        "hll_per_src": state.hll_per_src.regs,
        "hist_rtt": state.hist_rtt.counts,
        "hist_dns": state.hist_dns.counts,
        "ddos_rate": state.ddos.rate,
        "syn_rate": state.syn.rate,
        "synack": state.synack,
        "drops_rate": state.drops_ewma.rate,
        "drop_causes": state.drop_causes,
        "dscp_bytes": state.dscp_bytes,
        "conv_fwd": state.conv_fwd,
        "conv_rev": state.conv_rev,
        # federation.delta.SCALAR_FIELDS order
        "scalars": jnp.stack([
            state.total_records.astype(jnp.float32), state.total_bytes,
            state.total_drop_bytes, state.total_drop_packets,
            state.quic_records, state.nat_records,
            state.heavy_evictions]),
    }


def make_roll_fn(cfg: SketchConfig, reset_sketches: bool = True,
                 decay_factor: float | None = None,
                 with_tables: bool = False,
                 name: str = "roll", tiered: str | None = None):
    """Jitted window roll. `with_tables=True` additionally returns the
    PRE-roll mergeable table snapshot (`state_tables`) for the federation
    delta export — one extra output of the same executable, so a due window
    still dispatches exactly one device program."""
    def fn(s):
        new_state, report = roll_window(s, cfg, reset_sketches, decay_factor)
        if with_tables:
            return new_state, report, state_tables(s)
        return new_state, report
    return retrace.jit(fn, name, tiered=tiered)
