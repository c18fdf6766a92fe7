"""Central TPU aggregator: the fleet's one sketch-merge plane.

Hundreds of per-host agents each stream one delta frame per closed window
(`federation.delta`); this tier decodes, validates, and hierarchically
merges them ON DEVICE:

- single device: one jitted `statemerge.merge_tables` entry (donated
  aggregate, fixed frame shapes — compiled once, watched for retraces);
- in-pod mesh (`FEDERATION_MESH_SHAPE`): agents are hash-assigned to data
  shards and folded into per-shard partials with NO collectives
  (`parallel.merge.make_fold_delta_fn`); the two-axis ICI gather at window
  roll (`parallel.merge.make_merge_fn`) reconciles — the same steady-state/
  roll split as the flow ingest, one level up;
- cross-pod: `parallel.distributed.maybe_initialize_distributed` wires the
  spanning mesh (FEDERATION_* or SKETCH_* coordinator envs), and the same
  shard_map programs run across hosts over DCN.

The aggregate IS a `SketchState` fed by deltas instead of records, so the
existing window roll and report renderer serve the cluster-wide report
unchanged. Everything query-facing is published as a HOST-side snapshot at
window roll on the timer thread — the HTTP query surface (`federation.
query`) never dispatches a device op (same off-hot-path rules as
/debug/traces).
"""

from __future__ import annotations

import collections
import logging
import threading
import time
import zlib
from typing import Callable, Optional

import numpy as np

from netobserv_tpu.federation import delta as fdelta
from netobserv_tpu.pb import sketch_delta_pb2
from netobserv_tpu.utils import faultinject, retrace, tracing

log = logging.getLogger("netobserv_tpu.federation.aggregator")


def agent_owner_shard(agent_id: str, n_shards: int) -> int:
    """Stable agent -> data-shard assignment (mesh mode): one agent's
    deltas always fold into the same shard's partial."""
    return zlib.crc32(agent_id.encode()) % max(1, n_shards)


def _federation_merge(state, tables):
    """The single-device fold under its own name: ONE function object for
    every aggregator of the process, so jax's trace cache serves the second
    one (a per-instance lambda would re-trace inside its first frame's
    deadline)."""
    from netobserv_tpu.federation import statemerge

    return statemerge.merge_tables(state, tables)


class FederationAggregator:
    """Delta ingest + on-device merge + windowed cluster reports.

    Exporter-grade failure semantics: a bad frame is acked `accepted=0`
    and counted, a merge failure loses that frame (counted), a roll
    failure retries next window — nothing here ever tears down the gRPC
    stream every other agent is pushing on.
    """

    def __init__(self, sketch_cfg=None, window_s: float = 60.0,
                 mesh_shape: str = "", metrics=None,
                 sink: Optional[Callable[[dict], None]] = None,
                 stale_after_s: float = 120.0,
                 report_kwargs: Optional[dict] = None,
                 checkpoint_dir: str = "", checkpoint_every: int = 1,
                 agent_ttl_s: float = 0.0, alerts=None, archive=None):
        from netobserv_tpu.parallel.distributed import (
            maybe_initialize_distributed,
        )
        # the aggregator tier's spanning mesh wires under its own env
        # prefix (FEDERATION_*), falling back to the shared SKETCH_* one
        maybe_initialize_distributed(prefixes=("FEDERATION_", "SKETCH_"))
        import jax

        from netobserv_tpu.sketch import state as sk

        self._sk = sk
        self._cfg = sketch_cfg or sk.SketchConfig()
        self._window_s = window_s
        self._metrics = metrics
        self._sink = sink
        self._stale_after_s = stale_after_s
        self._report_kwargs = report_kwargs or {}
        #: previous merged-window heavy identity index (EvictedKeys diff)
        self._prev_heavy_index: Optional[dict] = None
        if metrics is not None:
            retrace.set_metrics(metrics)
            tracing.set_metrics(metrics)
        # frame contract: expected tensor shapes + geometry, derived from
        # THIS aggregator's config (a foreign shape must never reach the
        # fixed-shape jitted merge)
        template = sk.state_tables(sk.init_state(self._cfg))
        self._expected_shapes = fdelta.expected_shapes(template)
        self._dims = {"cm_depth": self._cfg.cm_depth,
                      "cm_width": self._cfg.cm_width,
                      "hll_precision": self._cfg.hll_precision,
                      "topk": self._cfg.topk,
                      "ewma_buckets": self._cfg.ewma_buckets}

        self._distributed = bool(mesh_shape)
        if self._distributed:
            from netobserv_tpu.parallel import (
                MeshSpec, make_mesh, merge as pmerge)
            spec = MeshSpec.parse(mesh_shape, len(jax.devices()))
            self._mesh = make_mesh(spec)
            self._ndata = spec.data
            self._pm = pmerge
            # merged counts are f32 from the first fold on: the jitted
            # fold and roll then keep one dtype (sk.widen_count)
            self._state = pmerge.init_dist_state(
                self._cfg, self._mesh,
                template=sk.widen_count(sk.init_state(self._cfg)))
            self._fold = pmerge.make_fold_delta_fn(self._mesh, self._cfg)
            self._roll = pmerge.make_merge_fn(self._mesh, self._cfg,
                                              with_tables=True)
        else:
            self._ndata = 1
            self._state = sk.widen_count(sk.init_state(self._cfg))
            self._fold = retrace.jit(_federation_merge, "federation_merge",
                                     donate_argnums=(0,))
            self._roll = sk.make_roll_fn(self._cfg, with_tables=True,
                                         name="federation_roll")

        self._lock = threading.Lock()          # aggregate state + counters
        self._publish_lock = threading.Lock()
        self._reports: collections.deque = collections.deque()
        self._max_queued_reports = 4
        self._window_deadline = time.monotonic() + window_s
        #: agent id -> {"last_ms", "window", "frames"} (monotonic last too)
        self._agents: dict[str, dict] = {}
        #: idempotent-delivery ledger: agent id -> {"epoch", "window_seq",
        #: "frame_uuid"} of the LAST APPLIED v2 frame. Checkpointed next to
        #: the aggregate state (same step) so redelivery across an
        #: aggregator restart still dedups; bounded by agent-TTL eviction.
        self._ledger: dict[str, dict] = {}
        self._window_agents: set[str] = set()
        self._frames_total = 0
        #: staleness-based agent eviction (FEDERATION_AGENT_TTL; 0 = off):
        #: past the TTL an agent leaves the ownership view AND its
        #: staleness gauge series is deleted (label cardinality must not
        #: grow forever with departed agents)
        self._agent_ttl_s = agent_ttl_s
        #: host mirror of the aggregate's window counter (updated at roll/
        #: restore): delta churn tensors re-base into the CLUSTER window
        #: domain before merging (fdelta.localize_churn) and reading the
        #: device scalar per frame would be a sync on the ingest path
        self._window_host = 0
        self._snapshot: Optional[dict] = None
        self._snap_lock = threading.Lock()
        self._snap_seq = 0
        #: continued agent traces parked for the current window (sampled
        #: frames only); adopted by the window trace at roll so the
        #: roll/publish spans complete each agent's cross-process journey
        self._window_traces: list = []
        self._max_window_traces = 32
        #: published fleet snapshot (/federation/fleet): whole-dict
        #: seq-stamped swaps, rebuilt on the timer thread — the route only
        #: ever reads the published reference (torn reads impossible by
        #: construction, merge lock never taken on the request path)
        self._fleet: Optional[dict] = None
        self._fleet_lock = threading.Lock()
        self._fleet_seq = 0
        self._closed = threading.Event()
        # cluster-wide continuous detection (netobserv_tpu/alerts): the
        # SAME engine core the agents mount, driven here by the merged-
        # window snapshot each roll publishes (thin-adapter pattern, like
        # federation/query.py over query/core). None = disabled, one
        # is-None check on the publish path.
        self.alerts = alerts
        # cluster-wide sketch warehouse (netobserv_tpu/archive): the SAME
        # archive plane the agents mount, fed here by each MERGED window's
        # tables at publish — /federation/range is a thin adapter over its
        # route_payload (the federation/query.py never-fork rule). None =
        # disabled, one is-None check on the publish path.
        self.archive = archive

        # checkpoint/restore: aggregate SketchState + delivery ledger saved
        # at window roll (post-roll state, so a restore can never re-publish
        # a closed window); restart loses at most the uncheckpointed
        # partial window
        self._ckpt = None
        self._ckpt_dir = checkpoint_dir
        self._ckpt_every = max(1, int(checkpoint_every))
        self._n_rolls = 0
        self._pending_ckpt: Optional[tuple] = None
        if checkpoint_dir:
            from netobserv_tpu.sketch.checkpoint import SketchCheckpointer
            self._ckpt = SketchCheckpointer(checkpoint_dir)
            self._maybe_restore()

        self.heartbeat = lambda: None
        self._timer: Optional[threading.Thread] = None
        self.start_window_timer()

    # --- checkpoint/restore ---------------------------------------------
    def _maybe_restore(self) -> None:
        """Restore the aggregate state + delivery ledger from the latest
        checkpoint. A restore failure starts a fresh window (logged) — the
        aggregator tier must come up in any case. The restored pytree has
        the SAME shapes/dtypes as the init template, so the jitted
        fold/roll entries never retrace across a restart."""
        try:
            step = self._ckpt.latest_step()
            if step is not None:
                self._state = self._ckpt.restore(self._state)
                self._apply_restored_meta(
                    self._ckpt.read_metadata(step) or {})
            # publish-commit marker: with checkpoint_every > 1 (or before
            # the first tensor save) windows PUBLISHED after the newest
            # tensor checkpoint must neither re-use their window id nor
            # re-merge their redelivered frames — fast-forward the window
            # counter past the last published id and overlay the ledger
            # those publishes committed (the skipped windows' tensor
            # contribution is the documented every-N durability loss)
            pub = self._ckpt.read_publish_marker()
            restored_w = int(np.asarray(self._state.window))
            if pub is not None and pub["window"] >= restored_w:
                self._apply_restored_meta(pub["meta"])
                self._state = self._state._replace(
                    window=self._state.window
                    + np.int32(pub["window"] + 1 - restored_w))
            elif step is None:
                return
            self._window_host = int(np.asarray(self._state.window))
            log.info("restored federation aggregate (checkpoint step %s, "
                     "next window %d, %d agents in the ledger)", step,
                     self._window_host, len(self._ledger))
        except Exception as exc:
            log.error("aggregator checkpoint restore failed "
                      "(starting a fresh window): %s", exc)
            if self._metrics is not None:
                self._metrics.count_error("federation")
            self._quarantine_checkpoints()

    def _quarantine_checkpoints(self) -> None:
        """An unrestorable checkpoint directory must not stay live: the
        fresh process restarts its window counter at 0, so orbax retention
        (highest steps win) would garbage-collect every NEW checkpoint
        while latest_step() kept answering the corrupt high step — the
        next restart would retry the same broken restore forever. Move the
        directory aside (kept for forensics) and checkpoint into a clean
        one; if even the rename fails, disable checkpointing rather than
        write into a poisoned dir."""
        import os
        try:
            self._ckpt.close()
        except Exception:
            pass
        dest = f"{self._ckpt_dir}.corrupt-{os.getpid()}-{time.time_ns()}"
        try:
            os.rename(self._ckpt_dir, dest)
            from netobserv_tpu.sketch.checkpoint import SketchCheckpointer
            self._ckpt = SketchCheckpointer(self._ckpt_dir)
            log.warning("quarantined unrestorable checkpoint dir to %s; "
                        "checkpointing continues into a fresh %s",
                        dest, self._ckpt_dir)
        except Exception as exc:
            self._ckpt = None
            log.error("could not quarantine checkpoint dir %s (%s) — "
                      "checkpointing DISABLED for this run",
                      self._ckpt_dir, exc)

    def _apply_restored_meta(self, meta: dict) -> None:
        """Re-seat the delivery ledger + agent view from checkpointed
        metadata (the roll-time sidecar, or the newer publish marker)."""
        self._ledger = {a: dict(v)
                        for a, v in (meta.get("ledger") or {}).items()}
        # re-seat agent liveness from wall-clock last_ms: monotonic
        # deadlines do not survive a process, so staleness restarts
        # from the checkpointed wall gap (clamped at 0)
        now_ms, now_mono = time.time() * 1e3, time.monotonic()
        self._agents.clear()
        for a, info in (meta.get("agents") or {}).items():
            gap_s = max(0.0, (now_ms - float(info.get("last_ms", 0.0)))
                        / 1e3)
            self._agents[a] = {
                "frames": int(info.get("frames", 0)),
                "window": int(info.get("window", 0)),
                "last_ms": float(info.get("last_ms", 0.0)),
                "last_mono": now_mono - gap_s}

    def _delivery_meta_locked(self) -> dict:
        """JSON-able ledger + agent view (caller holds self._lock)."""
        return {"ledger": {a: dict(v) for a, v in self._ledger.items()},
                "agents": {a: {"frames": v["frames"], "window": v["window"],
                               "last_ms": v["last_ms"]}
                           for a, v in self._agents.items()}}

    def _stage_checkpoint_locked(self, report) -> None:
        """Stage this roll's checkpoint UNDER self._lock: later folds
        DONATE self._state into the jitted merge, so the save must work
        from a private device-side copy taken before any post-roll fold
        can run. The disk I/O itself happens OFF the lock
        (_run_pending_checkpoint, timer thread) — a HUNG checkpoint
        filesystem stalls only the supervised timer thread (heartbeat
        stops, supervisor flips DEGRADED), never delta ingest, which
        would otherwise deadlock fleet-wide behind this lock."""
        import jax
        import jax.numpy as jnp

        snap = jax.tree.map(jnp.copy, self._state)
        jax.block_until_ready(snap)  # the copy must land before unlock
        self._pending_ckpt = (int(np.asarray(report.window)),
                              self._delivery_meta_locked(), snap)

    def _run_pending_checkpoint(self) -> None:
        """Persist the staged (ledger sidecar, then state) pair, OFF
        self._lock, before any queued publish (durable checkpoint, then
        publish — exactly-once across a restart). A checkpoint failure is
        swallowed + counted: a wedged disk loses durability, never the
        live plane."""
        with self._lock:
            payload, self._pending_ckpt = self._pending_ckpt, None
        if payload is None or self._ckpt is None:
            return
        step, meta, snap = payload
        m = self._metrics
        try:
            faultinject.fire("federation.checkpoint")
            self._ckpt.save_metadata(step, meta)
            # wait=True: the checkpoint is DURABLE before this window
            # publishes — a kill any time after restores this boundary
            self._ckpt.save(step, snap, wait=True)
            if m is not None:
                m.federation_checkpoints_total.labels("ok").inc()
        except Exception as exc:
            log.error("federation checkpoint failed (window keeps "
                      "rolling without durability): %s", exc)
            if m is not None:
                m.federation_checkpoints_total.labels("error").inc()
                m.count_error("federation")

    # --- delta ingest (gRPC handler) ------------------------------------
    def ingest_frame(self, data: bytes) -> sketch_delta_pb2.DeltaAck:
        """Decode + validate + ledger-check + merge one frame; always
        returns an ack. Idempotent: a redelivered v2 frame (same agent /
        epoch / window_seq / frame_uuid) acks accepted+duplicate without
        merging, and an out-of-order stale window acks-and-discards — a
        sender retrying after an ambiguous DEADLINE_EXCEEDED can never
        double-count a window."""
        t0 = time.perf_counter()
        trace = tracing.start_trace("delta")
        # the continued CROSS-PROCESS trace (the frame's optional
        # trace_ctx): resolved right after decode; NULL_TRACE until then
        # and on every unsampled/context-less frame — one is-None-shaped
        # check per frame, the zero-cost bar
        cont = tracing.NULL_TRACE
        parked = False
        try:
            data = faultinject.fire("federation.delta_ingest", data)
            try:
                with trace.stage("delta_decode"):
                    frame = fdelta.decode_frame(data)
                    # legacy (v1/v2) frames normalize to the current table
                    # layout HERE — zero-filled churn tensors, padded
                    # scalars — so the fixed-signature jitted merge sees
                    # one layout for every supported version (no retrace)
                    frame = frame._replace(
                        tables=fdelta.upgrade_tables(frame))
            except fdelta.DeltaVersionError as exc:
                return self._reject("version_mismatch", str(exc))
            except fdelta.DeltaFrameError as exc:
                return self._reject("decode_error", str(exc))
            cont = tracing.continue_trace(frame.trace_ctx,
                                          "federation_delta")
            if cont.sampled and self._metrics is not None:
                self._metrics.trace_context_propagated_total.labels(
                    "continued").inc()
            # validate/ledger/merge spans land on BOTH the local delta
            # trace and the continued agent trace (group collapses to one
            # object — the shared NULL_TRACE — when neither is sampled)
            tr = tracing.group(trace, cont)
            try:
                with tr.stage("delta_validate"):
                    fdelta.validate_shapes(frame, self._expected_shapes)
                    if frame.dims != self._dims:
                        raise fdelta.DeltaFrameError(
                            f"frame geometry {frame.dims} != aggregator's "
                            f"{self._dims} (agent {frame.agent_id!r})")
            except fdelta.DeltaFrameError as exc:
                return self._reject("shape_mismatch", str(exc))
            try:
                with tr.stage("delta_merge_dispatch"):
                    result = self._merge_frame(frame, tr)
            except Exception as exc:
                log.error("delta merge failed (frame from %r dropped): %s",
                          frame.agent_id, exc)
                return self._reject("merge_error", str(exc))
            # a MERGED frame's continued trace parks until this window
            # closes: the roll/publish spans attach there, completing the
            # agent->cluster journey under one trace id
            if cont.sampled and result in ("ok", "legacy"):
                parked = self._park_window_trace(cont)
        finally:
            trace.finish()
            if cont.sampled and not parked:
                cont.finish()
        m = self._metrics
        if m is not None:
            m.federation_deltas_total.labels(result).inc()
            m.federation_delta_bytes_total.inc(len(data))
            if result in ("ok", "legacy"):
                # only real merges feed the histogram: discarded frames
                # are near-no-ops and would bury the step change the docs
                # say to watch for (retraces)
                m.federation_merge_seconds.observe(time.perf_counter() - t0)
        return sketch_delta_pb2.DeltaAck(
            accepted=1, version=fdelta.DELTA_FORMAT_VERSION,
            duplicate=1 if result in ("duplicate", "stale") else 0,
            reason=(fdelta.ACK_REASON_DUPLICATE if result == "duplicate"
                    else fdelta.ACK_REASON_STALE if result == "stale"
                    else ""))

    def _reject(self, result: str,
                reason: str) -> sketch_delta_pb2.DeltaAck:
        log.warning("delta frame rejected (%s): %s", result, reason)
        if self._metrics is not None:
            self._metrics.federation_deltas_total.labels(result).inc()
        return sketch_delta_pb2.DeltaAck(
            accepted=0, version=fdelta.DELTA_FORMAT_VERSION, reason=reason)

    def _ledger_verdict_locked(self, frame: fdelta.DeltaFrame) -> str:
        """Classify a frame against the last-applied ledger (caller holds
        self._lock). Returns one of:

        - ``legacy``    v1 frame — no delivery header; merge unconditionally
        - ``ok``        first delivery of a new window (or a new epoch —
                        a returning agent re-registers cleanly)
        - ``duplicate`` same (epoch, window_seq, frame_uuid) already
                        applied — redelivery after an ambiguous deadline
        - ``stale``     window_seq at-or-behind the last applied one (or a
                        dead epoch's straggler) — out-of-order delivery;
                        ack-and-discard, never merge
        """
        if frame.version < 2:
            return "legacy"
        # tenant planes ledger independently (fdelta.source_key): a
        # multi-tenant agent's N frames per window share agent_id, epoch
        # and window_seq — keyed by bare agent_id, tenants 1..N-1 would
        # read as stale deliveries of tenant 0's frame and be discarded
        last = self._ledger.get(fdelta.source_key(frame))
        if last is None or frame.agent_epoch > last["epoch"]:
            return "ok"
        if frame.agent_epoch < last["epoch"]:
            return "stale"
        if frame.window_seq > last["window_seq"]:
            return "ok"
        if (frame.window_seq == last["window_seq"]
                and frame.frame_uuid == last["frame_uuid"]):
            return "duplicate"
        return "stale"

    def _note_discard_locked(self, frame: fdelta.DeltaFrame,
                             verdict: str) -> None:
        """Bookkeeping for a discarded frame (caller holds self._lock).
        A DUPLICATE refreshes liveness — the agent is alive, its window
        just doesn't contribute twice. A STALE frame deliberately does
        NOT: if an agent's epoch ever regresses (a wall-clock step-back
        across a restart), every frame it sends reads stale, and the only
        self-healing path is the TTL eviction forgetting the poisoned
        ledger entry so the agent can re-register — stale frames keeping
        it 'alive' would block that forever."""
        src = fdelta.source_key(frame)
        last = self._ledger.get(src)
        if last is not None and frame.agent_epoch < last["epoch"]:
            log.warning(
                "agent %r sent epoch %d below its ledger epoch %d (clock "
                "step-back across a restart?) — frames discarded as stale "
                "until the FEDERATION_AGENT_TTL eviction re-admits it",
                src, frame.agent_epoch, last["epoch"])
        if verdict == "duplicate" and src in self._agents:
            info = self._agents[src]
            info["last_ms"] = time.time() * 1e3
            info["last_mono"] = time.monotonic()

    def _park_window_trace(self, cont) -> bool:
        """Hold a continued (sampled, merged) agent trace until the window
        it contributed to closes — the roll/publish spans attach there.
        Bounded: past the cap the oldest parked trace seals early (its
        ingest spans are already evidence) so a hot window cannot grow the
        list without bound. Returns True when parked (the caller must not
        finish it)."""
        with self._lock:
            self._window_traces.append(cont)
            shed = (self._window_traces.pop(0)
                    if len(self._window_traces) > self._max_window_traces
                    else None)
        if shed is not None:
            shed.finish()
        return True

    def _merge_frame(self, frame: fdelta.DeltaFrame,
                     tr=tracing.NULL_TRACE) -> str:
        import jax

        # advisory pre-check: a redelivered/stale frame must not pay the
        # host->device transfer of the whole table set just to be
        # discarded under the lock (a retry flood would otherwise steal
        # transfer bandwidth from real merges)
        with tr.stage("delta_ledger"):
            with self._lock:
                early = self._ledger_verdict_locked(frame)
                if early in ("duplicate", "stale"):
                    self._note_discard_locked(frame, early)
                    return early
        # churn tensors re-base into the CLUSTER window domain: the
        # aggregate's own slot_roll maintains the cluster prev baseline
        # (summing agents' agent-window prevs would double-count every
        # persistent key), and first_seen stamps the cluster window a key
        # first reached this table (fdelta.localize_churn)
        host_tables = fdelta.localize_churn(frame.tables, self._window_host)
        if self._distributed:
            tables = {name: self._pm.put_replicated(
                self._mesh, np.ascontiguousarray(arr))
                for name, arr in host_tables.items()}
            owner = self._pm.put_replicated(self._mesh, np.asarray(
                [agent_owner_shard(fdelta.source_key(frame),
                                   self._ndata)], np.int32))
        else:
            tables = {name: jax.device_put(arr)
                      for name, arr in host_tables.items()}
        with self._lock:
            # authoritative verdict + fold + ledger update are ONE critical
            # section: two racing copies of the same frame serialize here,
            # the second sees the first's ledger entry and discards
            verdict = self._ledger_verdict_locked(frame)
            if verdict not in ("ok", "legacy"):
                self._note_discard_locked(frame, verdict)
                return verdict
            if self._distributed:
                self._state = self._fold(self._state, tables, owner)
            else:
                self._state = self._fold(self._state, tables)
            src = fdelta.source_key(frame)
            if verdict == "ok":
                self._ledger[src] = {
                    "epoch": frame.agent_epoch,
                    "window_seq": frame.window_seq,
                    "frame_uuid": frame.frame_uuid}
            self._frames_total += 1
            self._window_agents.add(src)
            info = self._agents.setdefault(
                src, {"frames": 0, "window": 0, "last_ms": 0.0,
                      "last_mono": 0.0})
            info["frames"] += 1
            info["window"] = frame.window
            info["last_ms"] = time.time() * 1e3
            info["last_mono"] = time.monotonic()
            if frame.telemetry is not None:
                # latest-wins per-agent health block (the fleet table's
                # row); frames without one leave the previous block in
                # place (mixed-fleet rollouts keep their last report)
                info["telemetry"] = frame.telemetry
            if time.monotonic() >= self._window_deadline:
                self._close_window_locked()
        return verdict

    # --- window roll ----------------------------------------------------
    def start_window_timer(self) -> None:
        self._timer = threading.Thread(
            target=self._window_loop, name="federation-window", daemon=True)
        self._timer.start()

    @property
    def _window_poll_s(self) -> float:
        return min(1.0, self._window_s / 10)

    def register_supervised(self, supervisor, heartbeat_timeout_s=None,
                            **kwargs) -> None:
        beat = supervisor.register(
            "federation-window", restart=self.start_window_timer,
            thread_getter=lambda: self._timer,
            heartbeat_timeout_s=(heartbeat_timeout_s or 10.0)
            + self._window_poll_s,
            **kwargs)
        self.heartbeat = beat

    def _window_loop(self) -> None:
        while not self._closed.wait(timeout=self._window_poll_s):
            self.heartbeat()
            faultinject.fire("federation.window_timer")
            try:
                faultinject.fire("federation.window_roll")
                with self._lock:
                    if time.monotonic() >= self._window_deadline:
                        self._close_window_locked()
            except Exception as exc:
                log.error("federation window roll failed (will retry): %s",
                          exc)
                if self._metrics is not None:
                    self._metrics.count_error("federation")
            self._evict_stale_agents()
            self._update_staleness()
            self._update_fleet()
            self._publish_queued()

    def _close_window_locked(self) -> None:
        """Dispatch the roll UNDER self._lock; render/publish happen on the
        timer thread outside it (delta merges never wait on a sink)."""
        # the window trace is a GROUP: the aggregator's own trace plus
        # every continued agent trace parked this window — one roll/publish
        # serves them all, so its spans land on each (group() collapses to
        # the shared NULL_TRACE when nothing is sampled)
        conts, self._window_traces = self._window_traces, []
        wtrace = tracing.group(
            tracing.start_trace("federation_window"), *conts)
        self._window_deadline = time.monotonic() + self._window_s
        try:
            with wtrace.stage("roll_dispatch"):
                self._state, report, tables = self._roll(self._state)
        except BaseException:
            wtrace.finish()
            raise
        self._window_host += 1  # keep the host mirror on the roll counter
        agents = sorted(self._window_agents)
        self._window_agents = set()
        # checkpoint the POST-roll state + the ledger at this step: a
        # restore resumes the fresh window (never re-rolls, never
        # re-publishes a closed one) and redelivered pre-crash frames
        # still dedup against the restored ledger
        if self._ckpt is not None:
            self._n_rolls += 1
            if self._n_rolls % self._ckpt_every == 0:
                self._stage_checkpoint_locked(report)
        self._reports.append((report, tables, agents, wtrace))
        while len(self._reports) > self._max_queued_reports:
            try:
                _r, _t, _a, shed = self._reports.popleft()
            except IndexError:
                break
            shed.finish()
            log.error("federation report queue full; dropping the oldest "
                      "unpublished window")
            if self._metrics is not None:
                self._metrics.count_error("federation")

    def _publish_queued(self, timeout_s: Optional[float] = None) -> None:
        # a bounded acquire (close()/shutdown path) must not deadlock
        # behind a timer thread wedged inside a hung checkpoint save —
        # the save holds this lock for the duration of its disk I/O
        if not self._publish_lock.acquire(
                timeout=-1 if timeout_s is None else timeout_s):
            log.error("publish lock busy past %.1fs (hung checkpoint "
                      "disk?) — skipping publish on this path", timeout_s)
            if self._metrics is not None:
                self._metrics.count_error("federation")
            return
        try:
            self._run_pending_checkpoint()
            while self._reports:
                try:
                    report, tables, agents, wtrace = self._reports.popleft()
                except IndexError:
                    return
                try:
                    self._publish(report, tables, agents, wtrace)
                except Exception as exc:
                    log.error("federation report publish failed "
                              "(report lost): %s", exc)
                    if self._metrics is not None:
                        self._metrics.count_error("federation")
                finally:
                    wtrace.finish()
        finally:
            self._publish_lock.release()

    def _publish(self, report, tables, agents: list, wtrace) -> None:
        from netobserv_tpu.exporter.tpu_sketch import (
            heavy_identity_index, report_to_json,
        )

        with wtrace.stage("report_render"):
            obj = report_to_json(report,
                                 prev_heavy_index=self._prev_heavy_index,
                                 **self._report_kwargs)
            # cluster-tier EvictedKeys diff against the previous MERGED
            # window (same rotate-at-roll contract as the exporter)
            self._prev_heavy_index = heavy_identity_index(report)
            obj["Type"] = "federation_window_report"
            obj["Agents"] = agents
            obj["TimestampMs"] = time.time_ns() // 1_000_000
            # host copies of the merged tables the query surface reads
            # (the np.asarray touch includes the device->host transfer)
            cm_bytes = np.asarray(tables["cm_bytes"])
            cm_pkts = np.asarray(tables["cm_pkts"])
            heavy = {k: np.asarray(tables["heavy_" + k])
                     for k in ("words", "h1", "h2", "counts", "valid",
                               "prev_counts", "first_seen", "epoch")}
        with self._snap_lock:
            self._snap_seq += 1
            seq = self._snap_seq
        snap = {
            "window": obj["Window"],
            "ts_ms": obj["TimestampMs"],
            "seq": seq,
            "report": obj,
            "agents": {a: dict(v) for a, v in self._agents_view().items()},
            "cm_bytes": cm_bytes,
            "cm_pkts": cm_pkts,
            "heavy": heavy,
            "total_records": obj["Records"],
            "total_bytes": obj["Bytes"],
        }
        with self._snap_lock:
            self._snapshot = snap
        # cluster-wide alert evaluation rides the snapshot it just
        # published (timer thread; safe_evaluate swallows+counts — a
        # failing evaluation never loses the publish or the sink
        # delivery below)
        if self.alerts is not None:
            self.alerts.safe_evaluate(snap)
        m = self._metrics
        if m is not None:
            m.federation_active_agents.set(len(agents))
            m.sketch_window_reports_total.inc()
        if self._ckpt is not None:
            # publish-commit marker, written BEFORE the sink (at-most-once
            # like the rest of the publish path): a restore from an older
            # tensor checkpoint (checkpoint_every > 1) fast-forwards past
            # this window id and keeps the ledger it committed
            try:
                with self._lock:
                    meta = self._delivery_meta_locked()
                self._ckpt.save_publish_marker(obj["Window"], meta)
            except Exception as exc:
                log.error("publish marker write failed (a restart may "
                          "re-publish window %s): %s", obj["Window"], exc)
                if m is not None:
                    m.count_error("federation")
        if self._sink is not None:
            with wtrace.stage("report_sink"):
                self._sink(obj)
        # cluster-wide warehouse write LAST, own try (the agent-side
        # ordering rule): the snapshot and sink already committed, so a
        # wedged archive disk loses only this merged window's durability —
        # counted — and stalls only this supervised timer thread, never
        # delta ingest. The tables here are the roll's outputs (staged by
        # construction), and the np.asarray copies above already landed.
        if self.archive is not None:
            try:
                faultinject.fire("sketch.archive_write")
                host_tables = {name: np.asarray(tables[name])
                               for name, _ in fdelta.TABLE_SPEC}
                self.archive.write_window(host_tables,
                                          window=int(obj["Window"]),
                                          ts_ms=int(obj["TimestampMs"]))
            except Exception as exc:
                log.error("cluster archive write failed (window %s not "
                          "archived; report already published): %s",
                          obj["Window"], exc)
                if m is not None:
                    m.count_error("federation-archive")

    def _agents_view(self) -> dict:
        now = time.monotonic()
        with self._lock:
            return {a: {"frames": v["frames"], "window": v["window"],
                        "last_ms": v["last_ms"],
                        "staleness_s": round(now - v["last_mono"], 3),
                        "stale": (now - v["last_mono"])
                        > self._stale_after_s,
                        "epoch": self._ledger.get(a, {}).get("epoch", 0),
                        "window_seq": self._ledger.get(a, {})
                        .get("window_seq", 0),
                        "telemetry": v.get("telemetry")}
                    for a, v in self._agents.items()}

    def _update_fleet(self) -> None:
        """Rebuild + swap the published fleet snapshot (timer thread; also
        run by flush() so tests/shutdown see a current table). The build
        reads the agent view under the merge lock BRIEFLY here — the
        /federation/fleet route never does: it reads only the reference
        this whole-dict seq-stamped swap publishes."""
        agents = self._agents_view()
        counts = {"agents": len(agents),
                  "stale": sum(1 for v in agents.values() if v["stale"]),
                  "overloaded": 0, "degraded": 0, "alerting": 0}
        for v in agents.values():
            tel = v.get("telemetry")
            conditions = (tel or {}).get("conditions", ())
            if "OVERLOADED" in conditions:
                counts["overloaded"] += 1
            if "DEGRADED" in conditions:
                counts["degraded"] += 1
            if "ALERTING" in conditions:
                counts["alerting"] += 1
        with self._fleet_lock:
            self._fleet_seq += 1
            self._fleet = {"seq": self._fleet_seq,
                           "ts_ms": time.time_ns() // 1_000_000,
                           "window_s": self._window_s,
                           "stale_after_s": self._stale_after_s,
                           "counts": counts,
                           "agents": agents}

    def fleet(self) -> Optional[dict]:
        """The published fleet snapshot (None before the first timer tick
        sees any state). Host-side dict only — never a device op, never
        the merge lock; an evicted agent drops out at the next rebuild."""
        with self._fleet_lock:
            return self._fleet

    def _update_staleness(self) -> None:
        m = self._metrics
        if m is None:
            return
        for agent, info in self._agents_view().items():
            m.federation_agent_staleness_seconds.labels(agent).set(
                info["staleness_s"])

    def _evict_stale_agents(self) -> None:
        """Agent lifecycle (FEDERATION_AGENT_TTL): drop agents silent past
        the TTL from the ownership view, DELETE their per-agent gauge
        series (departed agents must not pin label cardinality forever),
        and forget their ledger entry — a returning agent re-registers
        cleanly (same epoch + higher seq, or a fresh epoch after a
        restart). Counted in federation_agent_evictions_total."""
        ttl = self._agent_ttl_s
        if not ttl:
            return
        now = time.monotonic()
        with self._lock:
            dead = [a for a, v in self._agents.items()
                    if now - v["last_mono"] > ttl]
            for a in dead:
                del self._agents[a]
                self._ledger.pop(a, None)
                self._window_agents.discard(a)
        m = self._metrics
        for a in dead:
            log.warning("evicting dark agent %r (no delta for > %.0fs)",
                        a, ttl)
            if m is not None:
                m.remove_labeled(m.federation_agent_staleness_seconds, a)
                m.federation_agent_evictions_total.inc()

    # --- query surface (host-side, never a device op) -------------------
    def snapshot(self) -> Optional[dict]:
        """The last closed window's published snapshot (None before the
        first roll publishes)."""
        with self._snap_lock:
            return self._snapshot

    def status(self) -> dict:
        with self._lock:
            frames = self._frames_total
            window_agents = sorted(self._window_agents)
        snap = self.snapshot()
        out = {
            "frames_total": frames,
            "agents": self._agents_view(),
            "current_window_agents": window_agents,
            "last_published_window": None if snap is None
            else snap["window"],
            "window_s": self._window_s,
            "mesh": self._distributed,
            "format_version": fdelta.DELTA_FORMAT_VERSION,
            "supported_versions": list(fdelta.SUPPORTED_VERSIONS),
            "agent_ttl_s": self._agent_ttl_s,
            "checkpointing": self._ckpt is not None,
        }
        if self.alerts is not None:
            # one engine-view read, same read-once rule as /query/status
            out["alerts"] = self.alerts.summary()
        if self.archive is not None:
            out["archive"] = self.archive.stats()
        return out

    def query_frequency(self, src: str, dst: str, src_port: int = 0,
                        dst_port: int = 0, proto: int = 0) -> Optional[dict]:
        """CM point query with error bars against the last closed window's
        MERGED tables — delegated to the shared query core (pure host
        numpy through the hashing twins, non-blocking)."""
        snap = self.snapshot()
        if snap is None:
            return None
        from netobserv_tpu.query import core as qcore
        return qcore.frequency_payload(snap, src, dst, src_port, dst_port,
                                       proto)

    # --- lifecycle ------------------------------------------------------
    def flush(self, timeout_s: Optional[float] = None) -> None:
        """Close the current window now and publish synchronously.
        `timeout_s` bounds the wait for the publish lock (shutdown path:
        a timer thread wedged inside a hung checkpoint save holds it —
        close() must still return)."""
        with self._lock:
            self._close_window_locked()
        self._update_fleet()
        self._publish_queued(timeout_s)

    def close(self) -> None:
        self._closed.set()
        if self._timer is not None:
            self._timer.join(timeout=2.0)
        # bounded: a hung checkpoint disk must wedge the timer thread at
        # worst, never turn shutdown into a deadlock on the publish lock
        self.flush(timeout_s=10.0)
        if self._ckpt is not None:
            try:
                self._ckpt.close()
            except Exception as exc:
                log.error("checkpointer close failed: %s", exc)

    def kill(self) -> None:
        """Chaos-harness crash: stop the timer WITHOUT the final flush,
        publish, or checkpoint — everything since the last roll-time
        checkpoint is lost, exactly like a SIGKILL. Tests use this to pin
        the restore semantics; production shutdown is close()."""
        self._closed.set()
        if self._timer is not None:
            self._timer.join(timeout=2.0)
