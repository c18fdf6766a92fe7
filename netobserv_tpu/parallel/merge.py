"""Sharded sketch ingest + ICI window merge (shard_map over the device mesh).

Layout of the distributed state (`DistState` = SketchState pytree with a leading
`data`-axis dimension on every array):

- every leaf:               [n_data, ...]  sharded P("data") — per-device partials
- Count-Min counts:         [n_data, depth, width] sharded P("data", None, "sketch")
                            — width additionally split across the `sketch` axis
- EWMA mean/var:            identical across the data axis (baselines are global;
                            only `rate` is a true partial)

Steady state does **zero collectives**: each device folds its batch shard into
its partial (the per-CPU-map analog, SURVEY.md §2.3 item 1), on a sketch axis
> 1 the rows it OWNS into its local-width planes, through the same fold forms
as a whole-width replica (`_local_ingest`). All communication happens at
window roll: psum for linear sketches, max for HLL registers, all_gather +
re-select for the top-K table — the ICI merge the north star asks for
(BASELINE.json config 3) — and, for the table snapshot of a width-sharded
mesh, one all_gather of the merged planes over the sketch axis.
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from netobserv_tpu.ops import countmin, ewma, hll, quantile, topk
from netobserv_tpu.parallel.mesh import DATA_AXIS, SKETCH_AXIS
from netobserv_tpu.sketch import state as sk
from netobserv_tpu.utils import retrace

# ---------------------------------------------------------------------------
# sharding specs
# ---------------------------------------------------------------------------


def _state_specs(state: sk.SketchState) -> sk.SketchState:
    """PartitionSpec tree for the distributed state (leading data axis added;
    Count-Min width additionally split over the sketch axis; the top-K table
    carries a SECOND leading sketch-axis dim — owner-sharded scoring makes
    each sketch shard's table a distinct key set, not a replica)."""
    d = P(DATA_AXIS)
    h = P(DATA_AXIS, SKETCH_AXIS)
    return sk.SketchState(
        cm_bytes=countmin.CountMin(counts=P(DATA_AXIS, None, SKETCH_AXIS)),
        cm_pkts=countmin.CountMin(counts=P(DATA_AXIS, None, SKETCH_AXIS)),
        heavy=topk.SlotTable(words=h, h1=h, h2=h, counts=h, prev_counts=h,
                             first_seen=h, epoch=h, valid=h),
        hll_src=hll.HLL(regs=d),
        hll_per_dst=hll.PerDstHLL(regs=d),
        hll_per_src=hll.PerDstHLL(regs=d),
        hist_rtt=quantile.LogHist(counts=d),
        hist_dns=quantile.LogHist(counts=d),
        ddos=ewma.EWMA(mean=d, var=d, rate=d, windows=d),
        syn=ewma.EWMA(mean=d, var=d, rate=d, windows=d),
        synack=d,
        drops_ewma=ewma.EWMA(mean=d, var=d, rate=d, windows=d),
        drop_causes=d, dscp_bytes=d,
        conv_fwd=d, conv_rev=d,
        total_records=d, total_bytes=d,
        total_drop_bytes=d, total_drop_packets=d,
        quic_records=d, nat_records=d, heavy_evictions=d, window=d,
    )


def _drop_lead(pstate: sk.SketchState) -> sk.SketchState:
    """Local (inside-shard_map) view: drop the data-axis dim everywhere and
    the extra sketch-axis dim on the top-K table."""
    s = jax.tree.map(lambda x: x[0], pstate)
    return s._replace(heavy=jax.tree.map(lambda x: x[0], s.heavy))


def _add_lead(s: sk.SketchState) -> sk.SketchState:
    """Inverse of _drop_lead."""
    out = jax.tree.map(lambda x: x[None], s)
    return out._replace(heavy=jax.tree.map(lambda x: x[None], out.heavy))


def _put_global(arr: np.ndarray, mesh: Mesh, spec: P) -> jax.Array:
    """device_put a host-global array with the given sharding. On a
    multi-process mesh each addressable shard is placed explicitly: every
    process holds the SAME global array (the existing shard_batch/
    shard_dense contract), and some jax releases route the one-put form
    through a cross-host equality collective that CPU backends cannot
    execute (the 2-process gloo dryrun would die in device_put)."""
    sharding = NamedSharding(mesh, spec)
    if jax.process_count() == 1:
        return jax.device_put(arr, sharding)
    shards = [
        jax.device_put(arr[idx], d)
        for d, idx in sharding.addressable_devices_indices_map(
            arr.shape).items()
    ]
    return jax.make_array_from_single_device_arrays(
        arr.shape, sharding, shards)


def put_replicated(mesh: Mesh, arr: np.ndarray) -> jax.Array:
    """device_put a host array fully replicated over the mesh (multi-process
    safe — same explicit per-shard placement as `_put_global`). The
    federation fold's delta tables ride this."""
    return _put_global(np.asarray(arr), mesh, P())


def init_dist_state(cfg: sk.SketchConfig, mesh: Mesh) -> sk.SketchState:
    """Per-device partial sketch state, zeros, laid out across the mesh."""
    ndata = mesh.shape[DATA_AXIS]
    nsk = mesh.shape[SKETCH_AXIS]
    template = sk.init_state(cfg)
    specs = _state_specs(template)

    def place(leaf, spec):
        # top-K leaves (spec P(data, sketch)) carry a SECOND lead dim: one
        # distinct owner-sharded table per (data, sketch) device
        lead = (ndata, nsk) if (len(spec) >= 2 and spec[1] == SKETCH_AXIS) \
            else (ndata,)
        arr = np.zeros(lead + leaf.shape, dtype=leaf.dtype)
        return _put_global(arr, mesh, spec)

    return jax.tree.map(place, template, specs)


def shard_batch(mesh: Mesh, arrays: dict[str, np.ndarray]) -> dict[str, jax.Array]:
    """Place a global columnar batch (leading dim divisible by n_data) onto the
    mesh, split along the data axis and replicated along the sketch axis."""
    out = {}
    for k, v in arrays.items():
        out[k] = _put_global(np.asarray(v), mesh, P(DATA_AXIS))
    return out


# ---------------------------------------------------------------------------
# sharded ingest (no collectives)
# ---------------------------------------------------------------------------


def _local_ingest(s: sk.SketchState, arrays: dict, mesh: Mesh,
                  cfg: sk.SketchConfig) -> sk.SketchState:
    """One device's fold inside the shard_map. With a sketch axis the
    Count-Min planes and the slot table are owner-sharded: the same fold
    with ownership as a row mask, in the forms `sk.fold_forms` picks at the
    LOCAL width. The mesh rides the entry's /debug/executables row."""
    ndata, nsk = mesh.shape[DATA_AXIS], mesh.shape[SKETCH_AXIS]
    retrace.label("mesh", f"{ndata}x{nsk}")
    return sk.ingest(s, arrays,
                     sketch_axis=SKETCH_AXIS if nsk > 1 else None,
                     sketch_shards=nsk, use_pallas=cfg.use_pallas)


def make_sharded_ingest_fn(mesh: Mesh, cfg: sk.SketchConfig,
                           donate: bool = True,
                           dense: bool = False,
                           with_token: bool = False) -> Callable:
    """Jitted `(dist_state, batch) -> dist_state` over the mesh.

    `dense=False`: batch is the six-array dict. `dense=True`: batch is one
    (B, 16) u32 flowpack dense array (row-sharded over the data axis, ONE
    transfer per batch); each shard unpacks its rows locally — the unpack is
    elementwise, so sharding it adds no collectives.

    `with_token=True` (dense only) returns `(dist_state, token)`, the
    slot-reuse guard for `sketch.staging.DenseStagingRing` (see
    `sketch.state.make_ingest_dense_fn`)."""
    if with_token and not dense:
        raise ValueError("with_token requires dense=True")
    template = sk.init_state(cfg)
    specs = _state_specs(template)

    def local_step(pstate: sk.SketchState, batch):
        s = _drop_lead(pstate)
        arrays = sk.dense_to_arrays(batch) if dense else batch
        s = _local_ingest(s, arrays, mesh, cfg)
        out = _add_lead(s)
        if with_token:
            return out, (batch[:1] if batch.ndim == 1 else batch[:1, 0])
        return out

    # one spec as a pytree PREFIX covers the whole batch: every column is
    # row-sharded over the data axis, whatever feature columns it carries
    batch_specs = P(DATA_AXIS)
    shmapped = jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(specs, batch_specs),
        out_specs=(specs, P(DATA_AXIS)) if with_token else specs,
        check_vma=False,
    )
    # named, jitted and watched in one call (utils/retrace.jit): the
    # wrapper delegates .lower / ._cache_size, so the HLO no-collectives
    # checks still introspect it
    return retrace.jit(
        shmapped, "sharded_ingest_dense" if dense else "sharded_ingest",
        donate_argnums=(0,) if donate else ())


def init_resident_tables(mesh: Mesh, slot_cap: int,
                         lanes: int = 1) -> jax.Array:
    """Per-DATA-shard device key tables for the sharded resident feed:
    (n_data * lanes * slot_cap, KEY_WORDS) u32, rows sharded P(data) — each
    data shard holds one `sketch.state.init_key_tables(lanes, slot_cap)`
    array (lane `l`'s slot `s` in its row `l * slot_cap + s`; on the chip
    16/10 of its logical bytes, and no second copy inside a fold; a leading
    unit axis per shard would cost a copy in and a copy out): `lanes`
    independent tables, one per host-side packer lane
    (lanes > 1 lets the host pack a shard's rows across several threads;
    `sketch.staging.ShardedResidentStagingRing`), and the sketch-axis
    replicas stay consistent because every sketch column of a data row
    applies the same new-key lanes. Lookups are pure local gathers, so the
    steady-state no-collectives invariant is untouched."""
    ndata = mesh.shape[DATA_AXIS]
    arr = np.zeros((ndata * lanes * slot_cap, sk.KEY_WORDS), np.uint32)
    return _put_global(arr, mesh, P(DATA_AXIS))


def make_sharded_ingest_resident_fn(mesh: Mesh, cfg: sk.SketchConfig,
                                    batch_per_lane: int, caps,
                                    slot_cap: int, donate: bool = True,
                                    lanes: int = 1,
                                    watch_name: str =
                                    "sharded_ingest_resident") -> Callable:
    """Jitted `(dist_state, key_tables, flat) -> (dist_state, key_tables,
    token)` — the RESIDENT feed over the mesh (~15B/record instead of the
    dense feed's 80). `flat` concatenates `lanes` resident regions per data
    shard (`flowpack.resident_buf_len(batch_per_lane, caps)` words each,
    packed by that region's own KeyDict —
    `sketch.staging.ShardedResidentStagingRing`); the contiguous split over
    the data axis lands exactly on per-shard region-group boundaries. Each
    shard scatters its new-key lanes into ITS table slices and gathers
    hot-row keys locally — no collectives.

    `key_tables` is `init_resident_tables(mesh, slot_cap, lanes=L)` with
    L >= `lanes` lanes per shard (the superbatch fold ladder shares one
    table array across ladder entries, so `slot_cap` is passed, not read
    off the array — `sketch.state.resident_lane_arrays`, which the
    per-shard step runs on its own rows); `watch_name` distinguishes
    ladder entries in the retrace watchdog accounting."""
    template = sk.init_state(cfg)
    specs = _state_specs(template)

    def local_step(pstate: sk.SketchState, table, flat):
        s = _drop_lead(pstate)
        arrays, tbl = sk.resident_lane_arrays(flat, table, batch_per_lane,
                                              caps, lanes, slot_cap)
        s = _local_ingest(s, arrays, mesh, cfg)
        return _add_lead(s), tbl, flat[:1]

    shmapped = jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(specs, P(DATA_AXIS), P(DATA_AXIS)),
        out_specs=(specs, P(DATA_AXIS), P(DATA_AXIS)),
        check_vma=False,
    )
    return retrace.jit(shmapped, watch_name,
                       donate_argnums=(0, 1) if donate else ())


def shard_dense(mesh: Mesh, dense: np.ndarray) -> jax.Array:
    """Place a flowpack dense batch onto the mesh, rows split over the data
    axis, replicated over the sketch axis. Accepts (B, 20) rows or the flat
    (B*20,) form the staging ring ships (a contiguous flat split lands on
    row boundaries because B divides evenly over the data axis)."""
    return _put_global(np.asarray(dense), mesh, P(DATA_AXIS))


def shard_dense_per_device(mesh: Mesh, flat: np.ndarray) -> jax.Array:
    """shard_dense via EXPLICIT per-device placement: slice the flat host
    buffer along the data axis and issue one single-device `device_put` per
    LOCAL device, then assemble the global array. Semantically identical to
    `shard_dense`; the difference is the transfer shape — N independent
    host->device DMAs this host can run in parallel, instead of one sharded
    put whose slicing strategy is the runtime's.

    Multi-process meshes: each process places only the slices of ITS OWN
    devices (`make_array_from_single_device_arrays` takes addressable
    shards only), so `flat` must hold this host's rows at their GLOBAL
    positions — in practice every host packs the full batch layout and
    transfers just its slices (the per-host feed shape the multi-chip
    budget calls for, docs/tpu_sketch.md); `__graft_entry__` measures both
    strategies and the dryrun reports the split."""
    assert flat.ndim == 1
    ndata = mesh.shape[DATA_AXIS]
    per = len(flat) // ndata
    assert per * ndata == len(flat)
    sharding = NamedSharding(mesh, P(DATA_AXIS))
    shards = []
    me = jax.process_index()
    # Mesh.devices is an (data, sketch) ndarray; P(DATA_AXIS) replicates
    # each data-slice across the sketch columns
    for i in range(ndata):
        row = None
        for dev in np.asarray(mesh.devices)[i]:
            if dev.process_index != me:
                continue  # another host feeds that device
            if row is None:
                row = flat[i * per:(i + 1) * per]
            shards.append(jax.device_put(row, dev))
    return jax.make_array_from_single_device_arrays(
        flat.shape, sharding, shards)


# ---------------------------------------------------------------------------
# window roll: merge partials over ICI, emit a replicated report, reset
# ---------------------------------------------------------------------------


def merge_states(s: sk.SketchState, nsk: int) -> sk.SketchState:
    """Merge per-device partials into a replicated view (call inside shard_map;
    arrays here are local slices without the data-axis dim). Two named
    scopes tell the roll's collectives apart in a device capture:
    `merge_allreduce` (psum / pmax of every additive and max-merged plane)
    and `merge_topk_gather` (the slot tables' all-gather and re-score)."""
    with jax.named_scope("merge_allreduce"):
        cm_b = countmin.CountMin(jax.lax.psum(s.cm_bytes.counts, DATA_AXIS))
        cm_p = countmin.CountMin(jax.lax.psum(s.cm_pkts.counts, DATA_AXIS))

    def gather(x):
        # owner-sharded tables hold DISJOINT key sets per sketch shard, so
        # the candidate pool must be gathered over BOTH mesh axes
        x = jax.lax.all_gather(x, DATA_AXIS, axis=0, tiled=True)
        if nsk > 1:
            x = jax.lax.all_gather(x, SKETCH_AXIS, axis=0, tiled=True)
        return x

    with jax.named_scope("merge_topk_gather"):
        stacked = jax.tree.map(gather, s.heavy)
        if nsk > 1:
            qfn = lambda a, b: countmin.query_sharded(  # noqa: E731
                cm_b, a, b, SKETCH_AXIS, nsk)
        else:
            qfn = None
        # roll-time reconciliation of the persistent slot tables: duplicate
        # identities across shards collapse with segmented metadata merges
        # (prev_counts sum, first_seen min, epoch max) and counts re-score
        # against the globally merged CM — the one place cross-shard top-K
        # work happens (steady state stays collective-free)
        heavy = topk.merge_slot_tables(stacked, cm_b, s.heavy.k,
                                       query_fn=qfn)
    with jax.named_scope("merge_allreduce"):
        return sk.SketchState(
            cm_bytes=cm_b, cm_pkts=cm_p, heavy=heavy,
            hll_src=hll.HLL(jax.lax.pmax(s.hll_src.regs, DATA_AXIS)),
            hll_per_dst=hll.PerDstHLL(
                jax.lax.pmax(s.hll_per_dst.regs, DATA_AXIS)),
            hll_per_src=hll.PerDstHLL(
                jax.lax.pmax(s.hll_per_src.regs, DATA_AXIS)),
            hist_rtt=quantile.LogHist(
                jax.lax.psum(s.hist_rtt.counts, DATA_AXIS)),
            hist_dns=quantile.LogHist(
                jax.lax.psum(s.hist_dns.counts, DATA_AXIS)),
            ddos=ewma.EWMA(mean=s.ddos.mean, var=s.ddos.var,
                           rate=jax.lax.psum(s.ddos.rate, DATA_AXIS),
                           windows=s.ddos.windows),
            # the EWMA baselines (mean/var) are replicated and rolled identically
            # on every device; only the window rates are true partials
            syn=ewma.EWMA(mean=s.syn.mean, var=s.syn.var,
                          rate=jax.lax.psum(s.syn.rate, DATA_AXIS),
                          windows=s.syn.windows),
            synack=jax.lax.psum(s.synack, DATA_AXIS),
            drops_ewma=ewma.EWMA(mean=s.drops_ewma.mean, var=s.drops_ewma.var,
                                 rate=jax.lax.psum(s.drops_ewma.rate, DATA_AXIS),
                                 windows=s.drops_ewma.windows),
            drop_causes=jax.lax.psum(s.drop_causes, DATA_AXIS),
            dscp_bytes=jax.lax.psum(s.dscp_bytes, DATA_AXIS),
            conv_fwd=jax.lax.psum(s.conv_fwd, DATA_AXIS),
            conv_rev=jax.lax.psum(s.conv_rev, DATA_AXIS),
            total_records=jax.lax.psum(s.total_records, DATA_AXIS),
            total_bytes=jax.lax.psum(s.total_bytes, DATA_AXIS),
            total_drop_bytes=jax.lax.psum(s.total_drop_bytes, DATA_AXIS),
            total_drop_packets=jax.lax.psum(s.total_drop_packets, DATA_AXIS),
            quic_records=jax.lax.psum(s.quic_records, DATA_AXIS),
            nat_records=jax.lax.psum(s.nat_records, DATA_AXIS),
            heavy_evictions=jax.lax.psum(s.heavy_evictions, DATA_AXIS),
            window=s.window,
        )


def make_fold_delta_fn(mesh: Mesh, cfg: sk.SketchConfig,
                       donate: bool = True) -> Callable:
    """Jitted `(dist_state, tables, owner) -> dist_state` — the FEDERATION
    aggregator's mesh fold: merge ONE agent's delta-frame tables
    (`federation.delta.TABLE_SPEC` device arrays, replicated over the mesh)
    into the data shard that OWNS that agent (`owner`: i32[1], a stable
    hash of the agent id — deltas from one agent always land in one
    shard's partial, the per-CPU-map analog one level up). Steady state
    adds no collectives: every shard computes the masked merge locally;
    all cross-shard reconciliation stays at window roll
    (`make_merge_fn`'s two-axis gather), exactly like the flow ingest.

    The federation mesh shards AGENT ownership over the data axis only:
    a width-sharded (sketch axis > 1) mesh cannot accept deltas, because
    an owner-sharded CM shard is an INDEPENDENT width-w/nsk sketch (keys
    re-hash into the local width) — a whole-width delta table has no
    decomposition into it. Width sharding stays an agent-side feature;
    use an Nx1 federation mesh."""
    from netobserv_tpu.federation import statemerge

    nsk = mesh.shape[SKETCH_AXIS]
    if nsk > 1:
        raise ValueError(
            "federation fold requires a data-axis-only mesh (Nx1): "
            "owner-sharded CM shards re-hash keys into their local width, "
            f"so a whole-width delta table cannot merge into a {nsk}-way "
            "width-sharded aggregate")
    template = sk.init_state(cfg)
    specs = _state_specs(template)

    def local_fold(pstate: sk.SketchState, t: dict, owner: jax.Array):
        s = _drop_lead(pstate)
        mine = jax.lax.axis_index(DATA_AXIS) == owner[0]
        merged = statemerge.merge_tables(s, t)
        new = jax.tree.map(lambda a, b: jnp.where(mine, a, b), merged, s)
        return _add_lead(new)

    shmapped = jax.shard_map(
        local_fold, mesh=mesh,
        # tables + owner are replicated to every device; the fold masks
        in_specs=(specs, P(), P()),
        out_specs=specs, check_vma=False,
    )
    return retrace.jit(shmapped, "federation_fold_delta",
                       donate_argnums=(0,) if donate else ())


def make_merge_fn(mesh: Mesh, cfg: sk.SketchConfig,
                  reset_sketches: bool = True,
                  decay_factor: float | None = None,
                  with_tables: bool = False) -> Callable:
    """Jitted `(dist_state) -> (dist_state, WindowReport)`.

    The report is fully replicated (every device computes the cluster-wide
    merge); the returned state is reset for the next window with EWMA baselines
    rolled on the merged rates.

    `with_tables=True` additionally returns the REPLICATED merged table
    snapshot (`sketch.state.state_tables` of the merged pre-roll state) —
    the query surface's source on mesh deployments. On a width-sharded mesh
    the two Count-Min planes of that snapshot are `[nsk, depth, width / nsk]`:
    shard `s` is the independent local-width sketch of the keys
    `countmin.owner_shard` gives to `s` (psum over `data`, then one
    all_gather over `sketch`, named scope `merge_tables_gather`); every
    other table is as on a data-axis-only mesh. There is no whole-width
    form of such planes, so the federation fold and the archive stay
    data-axis-only (`make_fold_delta_fn`).
    """
    nsk = mesh.shape[SKETCH_AXIS]
    template = sk.init_state(cfg)
    specs = _state_specs(template)

    report_specs = sk.WindowReport(
        heavy=topk.SlotTable(words=P(), h1=P(), h2=P(), counts=P(),
                             prev_counts=P(), first_seen=P(), epoch=P(),
                             valid=P()),
        distinct_src=P(), per_dst_cardinality=P(), per_src_fanout=P(),
        rtt_quantiles_us=P(),
        dns_quantiles_us=P(), ddos_z=P(), syn_z=P(), syn_rate=P(),
        synack_rate=P(), drop_z=P(), drop_causes=P(), dscp_bytes=P(),
        conv_fwd=P(), conv_rev=P(),
        total_records=P(), total_bytes=P(),
        total_drop_bytes=P(), total_drop_packets=P(),
        quic_records=P(), nat_records=P(), heavy_evictions=P(),
        window=P(),
    )

    def local_roll(pstate: sk.SketchState):
        s = _drop_lead(pstate)
        merged = merge_states(s, nsk)
        tables = None
        if with_tables:
            tables = sk.state_tables(merged)
            if nsk > 1:
                with jax.named_scope("merge_tables_gather"):
                    for name in ("cm_bytes", "cm_pkts"):
                        tables[name] = jax.lax.all_gather(
                            tables[name], SKETCH_AXIS, axis=0)
        ddos_state, z = ewma.roll(merged.ddos, cfg.ewma_alpha)
        syn_state, syn_z = ewma.roll(merged.syn, cfg.ewma_alpha)
        drops_state, drop_z = ewma.roll(merged.drops_ewma, cfg.ewma_alpha)
        gamma = quantile.gamma_for(merged.hist_rtt.n_buckets)
        report = sk.WindowReport(
            heavy=merged.heavy,
            distinct_src=hll.estimate(merged.hll_src.regs),
            per_dst_cardinality=hll.estimate(merged.hll_per_dst.regs),
            per_src_fanout=hll.estimate(merged.hll_per_src.regs),
            rtt_quantiles_us=quantile.quantile(merged.hist_rtt,
                                               jnp.asarray(sk.QS), gamma),
            dns_quantiles_us=quantile.quantile(merged.hist_dns,
                                               jnp.asarray(sk.QS), gamma),
            ddos_z=z,
            syn_z=syn_z,
            syn_rate=merged.syn.rate,
            synack_rate=merged.synack,
            drop_z=drop_z,
            drop_causes=merged.drop_causes,
            dscp_bytes=merged.dscp_bytes,
            conv_fwd=merged.conv_fwd,
            conv_rev=merged.conv_rev,
            total_records=merged.total_records,
            total_bytes=merged.total_bytes,
            total_drop_bytes=merged.total_drop_bytes,
            total_drop_packets=merged.total_drop_packets,
            quic_records=merged.quic_records,
            nat_records=merged.nat_records,
            heavy_evictions=merged.heavy_evictions,
            window=merged.window,
        )
        ewma_rolled = dict(
            ddos=ddos_state._replace(rate=jnp.zeros_like(s.ddos.rate)),
            syn=syn_state._replace(rate=jnp.zeros_like(s.syn.rate)),
            drops_ewma=drops_state._replace(
                rate=jnp.zeros_like(s.drops_ewma.rate)),
        )
        if decay_factor is not None:
            # decay the local PARTIAL (linearity makes per-shard decay exact)
            new = sk.decay_state(s, decay_factor)._replace(
                window=s.window + 1, **ewma_rolled,
            )
        elif reset_sketches:
            fresh = jax.tree.map(jnp.zeros_like, s)
            # each device's slot table PERSISTS through the roll (identity,
            # first_seen, epoch stay local — no collectives): prev_counts
            # take this window's final per-device estimates, counts reset
            new = fresh._replace(
                heavy=topk.slot_roll(s.heavy, 0.0),
                window=s.window + 1, **ewma_rolled,
            )
        else:
            # synack resets with its paired EWMA rate (see state.roll_window)
            new = s._replace(ddos=ddos_state, syn=syn_state,
                             drops_ewma=drops_state,
                             synack=jnp.zeros_like(s.synack),
                             heavy=topk.slot_roll(s.heavy, 1.0),
                             heavy_evictions=jnp.zeros_like(
                                 s.heavy_evictions),
                             window=s.window + 1)
        if with_tables:
            return _add_lead(new), report, tables
        return _add_lead(new), report

    if with_tables:
        table_specs = {name: P() for name in
                       sk.state_tables(sk.init_state(cfg))}
        out_specs = (specs, report_specs, table_specs)
    else:
        out_specs = (specs, report_specs)
    shmapped = jax.shard_map(
        local_roll, mesh=mesh, in_specs=(specs,),
        out_specs=out_specs, check_vma=False,
    )
    return retrace.jit(shmapped, "sharded_merge", donate_argnums=(0,))
