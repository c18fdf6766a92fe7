"""Host->device staging ring for the dense flow feed.

A small ring of preallocated host buffers lets eviction batch i+1 be packed
(`flowpack.pack_dense`, single C++ pass) while batch i's host->device
transfer and ingest are still in flight — the host-path pipelining that
closes the seam the reference names as its own hot spot
(`pkg/model/record_bench_test.go:10-14`).

Slot-reuse safety: a slot is repacked only after the *ingest* that consumed
it has finished, guarded by a token output of the jitted ingest (a tiny
slice of the dense input; it becomes ready only when the whole executable
has run). Blocking on the `device_put` result instead is NOT sufficient: on
backends that zero-copy aligned host arrays (the CPU backend), the put
result is "ready" immediately while the async-dispatched ingest may still be
reading the aliased host memory.

Depth: 4 slots (2 leave the packer waiting on every in-flight ingest; the
depth has not been measured on this machine's link).
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Callable, Optional

import numpy as np

from netobserv_tpu.datapath import flowpack
from netobserv_tpu.model import binfmt
from netobserv_tpu.utils import faultinject, tracing


class StagingWedged(RuntimeError):
    """A fold exceeded the ring's slot-wait budget: the device (or its
    transfer link) is wedged. Raised only when `slot_wait_budget_s` is set
    (the overload controller arms it); the exporter catches it like any
    ingest failure — the unfolded remainder drops, counted, and the
    eviction feed keeps its cadence instead of inheriting the wedge.

    `state` carries the LAST VALID sketch state at the moment the wait
    tripped. This is load-bearing: a multi-chunk fold may have already
    dispatched earlier chunks, and every ingest jit DONATES its input
    state — the caller's pre-fold reference is a deleted buffer by then.
    The catcher must adopt `state` (identical to what it passed in when
    nothing had dispatched yet), or every later fold reads freed memory."""

    state = None


def default_spill_cap(batch_size: int) -> int:
    """Production spill-lane sizing for the compact feed: 1/8 of the batch
    (v6-heavy batches beyond it fall back to the dense feed). Bench and the
    exporter share this so the measured configuration is the shipped one."""
    return max(batch_size // 8, 64)


def pick_lanes(per_unit: int, want: int) -> int:
    """Largest lane count <= `want` that divides `per_unit` evenly (lane
    regions need equal fixed shapes for the retrace-free jitted unpack)."""
    lanes = max(1, min(want, per_unit))
    while per_unit % lanes:
        lanes -= 1
    return lanes


def _raw(a: np.ndarray) -> np.ndarray:
    """`a`'s rows as opaque bytes. numpy assigns a structured array field
    by field — a 144-byte event row costs 12 times a memmove, an
    overlapping slide 50 times — and a void view of the same rows as one
    block."""
    return a.view(f"V{a.dtype.itemsize}")


def _copy_records(dst: np.ndarray, src: np.ndarray) -> None:
    """dst[:] = src, as one block copy where the two share a dtype."""
    if src.dtype == dst.dtype:
        _raw(dst)[:] = _raw(src)
    else:
        dst[:] = src


class PendingEventBuffer:
    """Preallocated rolling accumulator for queued evictions — the
    zero-concat fold path. The exporter used to `np.concatenate` every
    queued eviction's events AND five feature lanes per fold (materializing
    zero arrays for absent lanes); this copies each incoming row exactly
    once into a fixed buffer and hands the fold zero-copy prefix views.

    `superbatch_max > 1` sizes the buffer for that many batches and
    coalesces rows that ARRIVE together: a large eviction (or several
    queued ones delivered back-to-back) folds as ONE k-batch superbatch
    the ladder ring dispatches as a single fixed-shape call instead of k
    per-batch dispatches (`ShardedResidentStagingRing` ladder). Small
    evictions keep the old cadence — fold as soon as a full batch is
    buffered — so the exporter-seam latency of a light stream is
    unchanged; coalescing only ever batches work that was already queued
    in one `append` (deferring folds to a fill deadline instead was
    measured to CONCENTRATE slot waits into multi-second export stalls on
    a device slower than the feed — tests/test_roll_nonblocking.py).

    Between folds the buffer holds, in arrival order, the rows that
    earlier folds LEFT ahead of the newest eviction's sub-batch tail. A
    fold callback may return the row ranges it did not consume (the
    resident ring's `fold(carry=True)`: each region's suffix behind a full
    side lane); those rows keep their feature-lane rows and ride the next
    fold that is dispatched anyway. `append` folds again while what is
    held makes a batch, so less than one batch waits for the next
    eviction, left rows included. A callback that returns None consumed
    everything, so `flush_to` with such a callback (the exporter's roll /
    flush / close) leaves the buffer empty.

    Feature-lane semantics match the old `_concat_feature`: a lane is
    passed to the fold iff ANY eviction in the current batch carried it,
    with zeroed rows standing in for evictions that lacked it (`_live`
    tracks per-lane liveness so untouched lanes cost nothing).

    DIRECT-TO-LANE fast path: when the buffer is empty and an arriving
    eviction's feature lanes are row-aligned with its events (the columnar
    eviction plane always builds them that way — `decode_eviction`), its
    batch-aligned PREFIX folds straight from zero-copy VIEWS of the
    eviction's own arrays — the resident pack lanes read the drain-decode
    output directly, skipping this buffer's copy entirely; only the rows
    those folds left and the sub-batch tail are copied in. Fold semantics
    are identical (the gate guarantees the zero-pad contract is moot for
    aligned lanes), pinned by tests/test_staging_direct.py. `direct_rows`
    counts the bypassing rows (`sketch_direct_fold_rows_total`)."""

    LANES = (("extra", binfmt.EXTRA_REC_DTYPE),
             ("dns", binfmt.DNS_REC_DTYPE),
             ("drops", binfmt.DROPS_REC_DTYPE),
             ("xlat", binfmt.XLAT_REC_DTYPE),
             ("quic", binfmt.QUIC_REC_DTYPE))

    def __init__(self, batch_size: int, superbatch_max: int = 1,
                 metrics=None):
        self.batch_size = batch_size
        self.capacity = batch_size * max(1, superbatch_max)
        self.n = 0
        self.events = np.zeros(self.capacity, binfmt.FLOW_EVENT_DTYPE)
        self._lanes = {name: np.zeros(self.capacity, dt)
                       for name, dt in self.LANES}
        self._live = {name: False for name, _ in self.LANES}
        #: per buffered row, the sequence number of the eviction it came
        #: with (`EvictedFlows.eviction`) — nondecreasing, the rows being
        #: in arrival order
        self._seq = np.zeros(self.capacity, np.int64)
        #: (oldest, newest) eviction among the rows of the fold on offer:
        #: set before every fold callback, for the ids on its stages
        self.evictions = (0, 0)
        self._metrics = metrics
        #: rows folded directly from eviction views (no buffer copy)
        self.direct_rows = 0

    def __len__(self) -> int:
        return self.n

    def _lanes_aligned(self, evicted, n: int) -> bool:
        """True when every present feature lane covers all `n` event rows —
        the gate for folding views of the eviction's own arrays (a short
        lane needs the buffer's zero-pad; fall back to the copy path)."""
        for name, _dt in self.LANES:
            col = getattr(evicted, name, None)
            if col is not None and len(col) and len(col) != n:
                return False
        return True

    def append(self, evicted, fold: Callable) -> None:
        """Copy `evicted` (an EvictedFlows) into the buffer, then fire
        `fold(events, feats)` with views into it for every full batch
        buffered — as one coalesced batch-aligned prefix (the ladder ring
        dispatches it as a single superbatch), keeping any sub-batch tail
        buffered for the next eviction. The fold must consume its views
        before returning (both ring pack paths copy synchronously); it
        returns None, or the ascending `(lo, hi)` row ranges of `events`
        that it left, which stay buffered ahead of the tail.

        An eviction meeting the direct-to-lane gate (empty buffer,
        batch-aligned prefix, aligned lanes) folds that prefix zero-copy
        from its own arrays — in capacity-sized chunks, so a fold is
        never LARGER than the copy path could have produced (the dense/
        compact rings do not chunk internally; only the resident ladder
        ring does) — and the rows those folds left and the sub-batch tail
        take the copy path below."""
        ev = evicted.events
        seq = getattr(evicted, "eviction", 0)
        off = 0
        if self.n == 0 and len(ev) >= self.batch_size \
                and self._lanes_aligned(evicted, len(ev)):
            while len(ev) - off >= self.batch_size:
                take = min(len(ev) - off, self.capacity)
                take -= take % self.batch_size
                feats = {}
                for name, _dt in self.LANES:
                    col = getattr(evicted, name, None)
                    feats[name] = (col[off:off + take]
                                   if col is not None and len(col) else None)
                self.evictions = (seq, seq)
                try:
                    left = fold(ev[off:off + take], feats) or ()
                except BaseException:
                    # a raising fold drops ITS chunk (counted upstream)
                    # like _fold_prefix — the rest still buffers, and the
                    # dropped rows never count as routed-direct
                    self._copy_in(evicted, off + take, fold)
                    raise
                direct = take
                for lo, hi in left:
                    self._copy_rows(evicted, off + lo, off + hi, fold)
                    direct -= hi - lo
                off += take
                self.direct_rows += direct
                if self._metrics is not None:
                    self._metrics.sketch_direct_fold_rows_total.inc(direct)
        self._copy_in(evicted, off, fold)

    def _copy_in(self, evicted, off: int, fold: Callable) -> None:
        """The copy path: buffer `evicted`'s rows from `off` on, folding
        full batches as they fill — again while what the folds left still
        makes a batch, so that less than one batch waits for the next
        eviction, as before rows could be left."""
        self._copy_rows(evicted, off, len(evicted.events), fold)
        while self.n >= self.batch_size:
            self._fold_prefix(fold, self.n - self.n % self.batch_size)

    def _copy_rows(self, evicted, off: int, end: int, fold: Callable) -> None:
        """Buffer `evicted`'s rows [off, end) behind what is held, folding
        the whole buffer whenever it fills."""
        ev = evicted.events
        seq = getattr(evicted, "eviction", 0)
        while off < end:
            take = min(end - off, self.capacity - self.n)
            lo, hi = self.n, self.n + take
            _copy_records(self.events[lo:hi], ev[off:off + take])
            self._seq[lo:hi] = seq
            for name, _ in self.LANES:
                col = getattr(evicted, name, None)
                lane = self._lanes[name]
                if col is not None and len(col):
                    if not self._live[name]:
                        lane[:lo] = 0  # earlier evictions lacked this lane
                        self._live[name] = True
                    c = col[off:off + take]
                    _copy_records(lane[lo:lo + len(c)], c)
                    lane[lo + len(c):hi] = 0  # short lane: zero-pad its tail
                elif self._live[name]:
                    lane[lo:hi] = 0
            self.n += take
            off += take
            if self.n == self.capacity:
                self.flush_to(fold)

    def flush_to(self, fold: Callable) -> None:
        """Fold whatever is buffered (a partial batch pads downstream);
        no-op when empty. What the fold leaves stays buffered; a fold that
        raises must not leave its rows queued for a re-fold (the exporter
        counts the batch as dropped)."""
        if self.n:
            self._fold_prefix(fold, self.n)

    def _fold_prefix(self, fold: Callable, rows: int) -> None:
        """Fold the `rows` prefix and slide what it left, then the tail
        behind it, to the front. The fold consumes its views synchronously,
        so the rows move after it returns; a RAISING fold still drops the
        prefix (counted upstream) and keeps the tail."""
        n = self.n
        feats = {name: (self._lanes[name][:rows] if self._live[name]
                        else None) for name, _ in self.LANES}
        self.evictions = (int(self._seq[0]), int(self._seq[rows - 1]))
        left = None
        try:
            left = fold(self.events[:rows], feats)
            if left and sum(hi - lo for lo, hi in left) >= rows:
                # the callers above fold until the rows fit: a fold that
                # takes none would spin the export thread for ever
                left = None
                raise RuntimeError("fold consumed none of its rows")
        finally:
            arrays = [_raw(self.events), self._seq] + [
                _raw(self._lanes[name]) for name, _ in self.LANES
                if self._live[name]]
            kept = 0
            for lo, hi in (*(left or ()), (rows, n)):
                if lo != kept:
                    for a in arrays:
                        a[kept:kept + hi - lo] = a[lo:hi]
                kept += hi - lo
            if not kept:
                for name, _ in self.LANES:
                    self._live[name] = False
            self.n = kept


class _SlotRing:
    """Shared slot/token protocol of every staging ring — ONE definition of
    the slot-reuse guard described in the module docstring (the token must
    be a slice of the jitted ingest's input; blocking on the put result is
    not sufficient on zero-copy backends)."""

    #: recent slot-wait samples kept for the p95 the overload controller
    #: reads (fixed window: one float store per fold, no allocation)
    WAIT_WINDOW = 64

    def _init_slots(self, bufs: list, metrics) -> None:
        self._bufs = bufs
        self._tokens: list = [None] * len(bufs)
        self._slot = 0
        self._metrics = metrics
        self.stalls = 0
        #: optional bound on one fold's slot wait (seconds); None = wait
        #: forever (the historical behavior). The tpu-sketch exporter sets
        #: it when overload shedding is enabled so a wedged device drops
        #: batches instead of wedging the eviction feed (StagingWedged).
        self.slot_wait_budget_s: Optional[float] = None
        self._waits = np.zeros(self.WAIT_WINDOW, np.float64)
        self._wait_i = 0
        self._wait_n = 0
        #: fold chunks begun by this ring (`chunk=<n>` on their stages)
        self.chunks = 0

    def _chunk(self, trace, k: int = 1, cont: bool = False,
               wide: bool = False):
        """The stage handle of the fold chunk about to begin: `trace` with
        `chunk=<n>` (this ring's sequence number), `k=<ladder entry>`,
        `cont=<0|1>` (a continuation of the rows the chunk before could not
        take) and `wide=<0|1>` (the resident ring's lane family) bound
        beside whatever the caller bound (`evictions=<a>-<b>`) — the ids
        its staging_wait / pack / put / ingest_dispatch stages carry in a
        profiler capture."""
        self.chunks += 1
        return trace.bind(chunk=self.chunks, k=k, cont=int(cont),
                          wide=int(wide))

    @contextlib.contextmanager
    def _pack_stage(self, chunk, stage: str = "resident_pack"):
        """One chunk's pack stage, timed into `sketch_pack_seconds` — the
        wall the folding thread waits for the pack, all lanes together: the
        always-on twin of the slot wait, for the operator with no
        profiler."""
        t0 = time.perf_counter()
        with chunk.stage(stage):
            yield
        if self._metrics is not None:
            self._metrics.sketch_pack_seconds.observe(
                time.perf_counter() - t0)

    def _record_wait(self, seconds: float) -> None:
        self._waits[self._wait_i] = seconds
        self._wait_i = (self._wait_i + 1) % self.WAIT_WINDOW
        if self._wait_n < self.WAIT_WINDOW:
            self._wait_n += 1

    def slot_wait_p95(self) -> float:
        """p95 of the last WAIT_WINDOW folds' slot waits (0.0 until any
        fold has run) — the device-backpressure half of the overload
        controller's pressure score."""
        if not self._wait_n:
            return 0.0
        return float(np.percentile(self._waits[:self._wait_n], 95))

    def _fold_trace(self, trace):
        """Resolve a fold's trace context: the caller's (batch trace riding
        the eviction, or the exporter's NULL), else sample one here — a
        directly-driven ring still exercises the span layer. Returns
        (trace, owned): the ring finishes only traces it created."""
        if trace is not None:
            return trace, False
        return tracing.start_trace("fold"), True

    def _wait_slot(self, trace=tracing.NULL_TRACE) -> int:
        """Return the next slot index, blocking until its previous consumer
        (the ingest that read the slot's buffer) has finished."""
        import jax

        # chaos seam: a hang here models a wedged device/transfer stalling
        # the staging feed — the thread folding (the exporter stage) stops
        # beating and the supervisor's hang detection takes over
        faultinject.fire("sketch.staging_wait")
        slot = self._slot
        tok = self._tokens[slot]
        wait_s = 0.0
        if tok is not None:
            if not tok.is_ready():
                self.stalls += 1
                if self._metrics is not None:
                    self._metrics.sketch_staging_stalls_total.inc()
                t0 = time.perf_counter()
                with trace.stage("staging_wait"):
                    budget = self.slot_wait_budget_s
                    if budget is None:
                        jax.block_until_ready(tok)
                    else:
                        # bounded wait: poll readiness up to the budget; a
                        # still-busy slot past it means the device wedged —
                        # raise instead of inheriting the wedge (the token
                        # stays in place; a later fold re-waits on it)
                        deadline = t0 + budget
                        while not tok.is_ready():
                            if time.perf_counter() >= deadline:
                                self._record_wait(time.perf_counter() - t0)
                                raise StagingWedged(
                                    f"staging slot busy past the "
                                    f"{budget:.1f}s slot-wait budget "
                                    "(device/transfer wedged)")
                            time.sleep(0.002)
                        jax.block_until_ready(tok)
                wait_s = time.perf_counter() - t0
                if self._metrics is not None:
                    self._metrics.sketch_slot_wait_seconds.observe(wait_s)
            else:
                jax.block_until_ready(tok)
        self._record_wait(wait_s)
        return slot

    def _advance(self, slot: int, token) -> None:
        self._tokens[slot] = token
        self._slot = (slot + 1) % len(self._bufs)

    def drain(self) -> None:
        """Block until every in-flight batch has been fully ingested (host
        buffers are then free; used before checkpoint/window close)."""
        import jax

        for tok in self._tokens:
            if tok is not None:
                jax.block_until_ready(tok)


class DenseStagingRing(_SlotRing):
    """Reusable host buffers + in-flight tokens for the dense ingest path.

    `ingest` must be a token-returning jitted fn — built with
    `sketch.state.make_ingest_dense_fn(with_token=True)` or
    `parallel.merge.make_sharded_ingest_fn(dense=True, with_token=True)` —
    i.e. `(state, dense) -> (state, token)`. `put` places a packed host
    buffer on device(s); defaults to `jax.device_put` (single device).

    Compact mode (`spill_cap` set, single-device only): slots hold the flat
    v4-compact feed (`flowpack.pack_compact`, ~40% of the dense bytes —
    the transfer link is the host path's bottleneck) and `ingest` must be a
    `make_ingest_compact_fn(with_token=True)` jit. Batches whose non-v4
    flows overflow the spill lane fall back to the dense feed through
    `ingest_fallback` (a `make_ingest_dense_fn(with_token=True)` jit) —
    same math, bigger transfer, synchronously drained (rare path).
    """

    def __init__(self, batch_size: int, ingest: Callable,
                 put: Optional[Callable] = None, n_slots: int = 4,
                 spill_cap: Optional[int] = None,
                 ingest_fallback: Optional[Callable] = None,
                 metrics=None, pack_threads: int = 1):
        import jax

        self.batch_size = batch_size
        #: >1 shards each dense pack across this many native packer threads
        #: (flowpack.pack_dense_sharded) — matters on hosts where the pack,
        #: not the transfer link, bounds the feed
        self.pack_threads = pack_threads
        self.spill_cap = spill_cap
        self._ingest = ingest
        self._ingest_fallback = ingest_fallback
        self._put = put or jax.device_put
        if spill_cap is not None:
            shape: tuple = (flowpack.compact_buf_len(batch_size, spill_cap),)
            if ingest_fallback is None:
                raise ValueError("compact mode needs ingest_fallback")
        else:
            shape = (batch_size, flowpack.DENSE_WORDS)
        self._init_slots([np.empty(shape, np.uint32)
                          for _ in range(n_slots)], metrics)
        self._dense_buf: Optional[np.ndarray] = None  # lazy fallback buffer
        self.dense_fallbacks = 0  # spill-overflow batches shipped full-width

    def fold(self, state, events, extra=None, dns=None, drops=None,
             xlat=None, quic=None, trace=None):
        """Pack `events` into the next free slot, ship it, ingest it; returns
        the new sketch state (async — not blocked on)."""
        trace, owned = self._fold_trace(trace)
        try:
            chunk = self._chunk(trace)
            try:
                slot = self._wait_slot(chunk)
            except StagingWedged as exc:
                exc.state = state  # nothing dispatched: caller's own state
                raise
            feats = dict(extra=extra, dns=dns, drops=drops, xlat=xlat,
                         quic=quic)
            if self.spill_cap is not None:
                with self._pack_stage(chunk, "pack"):
                    buf = flowpack.pack_compact(
                        events, batch_size=self.batch_size,
                        spill_cap=self.spill_cap,
                        out=self._bufs[slot], **feats)
                if buf is None:
                    return self._fold_dense_fallback(state, events, feats)
            else:
                with self._pack_stage(chunk, "pack"):
                    buf = flowpack.pack_dense_sharded(
                        events, batch_size=self.batch_size,
                        threads=self.pack_threads, out=self._bufs[slot],
                        **feats)
                # ship FLAT: a (B*20,) transfer dodges device-layout padding
                # of the 20-wide minor dim (the ingest jit reshapes back,
                # fused, free)
                buf = buf.reshape(-1)
            # host-to-device transfer and jit enqueue are different costs:
            # one stage each
            with chunk.stage("put"):
                dev = self._put(buf)
            with chunk.stage("ingest_dispatch"):
                state, token = self._ingest(state, dev)
            self._advance(slot, token)
            return state
        finally:
            if owned:
                trace.finish()

    def _fold_dense_fallback(self, state, events, feats):
        """Non-v4 (or spill-overflow) flows exceeded the spill lane: ship
        this batch full-width. Synchronous (the shared dense buffer has no
        slot ring), and rare — only v6-dominant traffic or a drop storm
        takes it repeatedly, at dense-path speed; the counter makes that
        degradation observable (sketch_dense_fallback_total)."""
        import jax

        self.dense_fallbacks += 1
        if self._metrics is not None:
            self._metrics.sketch_dense_fallback_total.inc()
        if self._dense_buf is None:
            self._dense_buf = np.empty(
                (self.batch_size, flowpack.DENSE_WORDS), np.uint32)
        buf = flowpack.pack_dense_sharded(
            events, batch_size=self.batch_size, threads=self.pack_threads,
            out=self._dense_buf, **feats)
        state, tok = self._ingest_fallback(state, self._put(buf.reshape(-1)))
        jax.block_until_ready(tok)
        return state


class ShardedResidentStagingRing(_SlotRing):
    """Resident feed split into independent pack REGIONS — `n_shards` data
    shards x `lanes` lanes per shard. The batch splits into
    `n_shards * lanes` contiguous row blocks, each packed by its OWN
    KeyDict into its own resident buffer region; the concatenated flat
    buffer ships with one put whose contiguous data-axis split lands
    exactly on per-shard region-group boundaries.

    Two deployments share this ring:

    - mesh (`n_shards` > 1): device twin
      `parallel.merge.make_sharded_ingest_resident_fn` +
      `init_resident_tables` (independent key tables per (shard, lane) —
      lookups stay local, the steady-state no-collectives invariant holds);
      `put` is `parallel.merge.shard_dense` bound to the mesh.
    - single device (`n_shards` == 1, `lanes` > 1): device twin
      `sketch.state.make_ingest_resident_lanes_fn` + `init_key_tables`;
      `put` is a plain `device_put`. This is how SKETCH_PACK_THREADS
      engages the resident feed — the per-lane packs run on the pool in
      true parallel (native pack releases the GIL), raising the host-pack
      ceiling that a single `pack_resident` pass tops out at.

    Multi-process note: every process must fold the SAME global batches
    (the existing `shard_batch`/`shard_dense` assumption) — dictionary
    evolution is deterministic in row order, so all processes assign
    identical slots.

    Superbatch LADDER (`ladder=(1, 2, 4)`): when a fold receives k queued
    batches' worth of rows (the exporter's `PendingEventBuffer` coalesces
    evictions up to `superbatch_max` batches), the whole superbatch packs
    into `n_shards * k * lanes` regions and ships as ONE put + ONE jitted
    ingest dispatch of the k-entry instead of k per-batch dispatches —
    amortizing the per-dispatch python/jit/transfer overhead. Every ladder
    entry is its own fixed-shape jitted fn (no retraces); they all share
    ONE key-table array sized for the largest entry (a smaller entry
    updates only its leading lanes' rows, `state.resident_lane_arrays`)
    and per-(shard, ladder-position, lane) dictionaries, so a region's
    dictionary <-> device-table pairing is stable across ladder sizes.

    Rows a chunk cannot take: a region stops packing where one of its side
    lanes (new keys, spill, DNS, drops) fills. `fold()` ships what the
    regions took and then either packs the regions' remainders into
    CONTINUATION chunks of the same shape until none is left (the default:
    every row consumed on return), or — `carry=True`, the exporter's steady
    path — returns the remainders to the caller, whose `PendingEventBuffer`
    offers them again at the front of the next fold. Either way every row
    reaches a region's dictionary in stream order, so the schedule is a
    pure function of the row stream (the multi-process rule above holds).

    Lane FAMILIES (`wide_ingest`): a ladder entry may have a second
    program whose regions differ only in the capacity of the new-key lane
    (`flowpack.wide_resident_caps`). The two share the dictionaries and the
    key tables — a slot defined through either lane is the same slot — and
    the ring picks the family of the next such chunk from what the packer
    saw in the last one (`_next_family`): wide once the regions whose
    new-key lane filled left a quarter of the rows on offer, narrow again
    once a wide chunk's new keys come no faster than the narrow lanes hold
    them. That too is a pure function of the row stream. A key flood (a
    fifth of the records on never-seen keys) then folds in one offer a
    record where the narrow lane takes three; stationary traffic never
    leaves narrow, whose table scatter is a sixth the size.

    `ingest`: `{k: (dist_state, key_tables, flat) -> (dist_state,
    key_tables, token)}` for every ladder entry (a bare callable means
    `{1: fn}`); `wide_ingest`: the same for the entries that have a wide
    program, built with `wide_caps`. `key_tables` must carry
    `superbatch_max * lanes` lanes of `slot_cap` rows per shard
    (`state.init_key_tables`; the ring only hands the array on), and every
    entry must have been built with this ring's `slot_cap`. A zero-argument
    callable in its place makes the array at the first dispatch (and a
    spare on request, `make_tables`): the exporter's ladder warm-up folds
    through a spare, and at 2^20 slots a table is 2.15 GB of a 16 GB chip —
    the two need not be alive together.
    `pack_threads > 1` packs the regions concurrently."""

    def __init__(self, batch_size: int, n_shards: int, ingest,
                 key_tables, put: Callable,
                 caps=None, slot_cap: int = 1 << 18, n_slots: int = 4,
                 metrics=None, pack_threads: int = 1, lanes: int = 1,
                 ladder: tuple = (1,), lazy_ladder: bool = False,
                 wide_ingest: Optional[dict] = None, wide_caps=None):
        self.ladder = tuple(sorted({int(k) for k in ladder}))
        if not self.ladder or self.ladder[0] != 1:
            raise ValueError("superbatch ladder must include 1")
        self.superbatch_max = self.ladder[-1]
        # lazy_ladder: entries > 1 become SELECTABLE only once mark_warm
        # says their jit is compiled (the exporter's construction warm) —
        # a cold ladder entry must never compile inside a live fold, which
        # would stall export_evicted for seconds (test_roll_nonblocking).
        # Eager (default) trusts the caller to warm by folding (offline
        # tools, tests).
        self._available = {1} if lazy_ladder else set(self.ladder)
        self._wide_ingests = dict(wide_ingest or {})
        if set(self._wide_ingests) - set(self.ladder):
            raise ValueError("a wide entry needs its narrow ladder entry")
        #: wide entries a fold may select (compiled: `mark_warm(wide=True)`)
        self._wide_available = (set() if lazy_ladder
                                else set(self._wide_ingests))
        #: the family the next chunk of an entry with a wide program takes
        self._wide_next = False
        n_regions = n_shards * lanes
        if batch_size % n_regions:
            raise ValueError(
                "batch_size must divide evenly over shards x lanes")
        self.batch_size = batch_size
        self.n_shards = n_shards
        self.lanes = lanes
        #: regions of ONE 1x batch (a k-superbatch packs k*n_regions)
        self.n_regions = n_regions
        self.batch_per_region = batch_size // n_regions
        self.caps = caps or flowpack.default_resident_caps(
            self.batch_per_region)
        self.slot_cap = slot_cap
        self.pack_threads = pack_threads
        self.kdicts = [flowpack.KeyDict(slot_cap)
                       for _ in range(n_regions * self.superbatch_max)]
        #: a new zeroed table array (None where the ring was handed one)
        self.make_tables = key_tables if callable(key_tables) else None
        self._key_tables = None if self.make_tables else key_tables
        self._ingests = ingest if not callable(ingest) else {1: ingest}
        missing = set(self.ladder) - set(self._ingests)
        if missing:
            raise ValueError(f"no ingest fn for ladder entries {missing}")
        self._put = put
        self.continuations = 0
        #: rows a carry fold handed back to ride a later chunk (mirrors
        #: sketch_resident_carried_rows_total)
        self.carried_rows = 0
        self.dict_resets = 0
        self.spill_rows = 0
        #: dispatch counts by superbatch size (mirrors
        #: sketch_superbatch_folds_total{k})
        self.superbatch_folds: dict[int, int] = {}
        #: chunks dispatched through a wide entry (mirrors
        #: sketch_resident_wide_folds_total)
        self.wide_folds = 0
        self._region_words = flowpack.resident_buf_len(self.batch_per_region,
                                                       self.caps)
        self.wide_caps = wide_caps or flowpack.wide_resident_caps(
            self.batch_per_region)
        self._wide_region_words = flowpack.resident_buf_len(
            self.batch_per_region, self.wide_caps)
        slot_words = max(
            [self.superbatch_max * self._region_words]
            + [k * self._wide_region_words for k in self._wide_ingests])
        self._init_slots(
            [np.empty(n_regions * slot_words, np.uint32)
             for _ in range(n_slots)], metrics)

    @property
    def key_tables(self):
        """The device key tables (made here, once, where the ring was
        given their factory)."""
        if self._key_tables is None:
            self._key_tables = self.make_tables()
        return self._key_tables

    @key_tables.setter
    def key_tables(self, tables) -> None:
        self._key_tables = tables

    def programs(self) -> list[tuple[int, bool]]:
        """Every compiled program this ring can dispatch, as `(k, wide)` in
        warm-up order: the narrow ladder, then the wide entries."""
        return ([(k, False) for k in self.ladder]
                + [(k, True) for k in sorted(self._wide_ingests)])

    def program(self, k: int, wide: bool = False):
        """`(ingest fn, caps, words of one region)` of ladder entry `k` in
        the narrow or the wide lane family."""
        if wide:
            return (self._wide_ingests[k], self.wide_caps,
                    self._wide_region_words)
        return self._ingests[k], self.caps, self._region_words

    def is_warm(self, k: int, wide: bool = False) -> bool:
        return k in (self._wide_available if wide else self._available)

    def mark_warm(self, *ks: int, wide: bool = False) -> None:
        """Make ladder entries selectable (call after compiling them — the
        exporter's `warm_superbatch_ladder`)."""
        (self._wide_available if wide else self._available).update(
            int(k) for k in ks)

    def warm_entries(self) -> list[int]:
        """The ladder entries whose every program is compiled (the narrow
        one and, where the entry has one, the wide): `== list(ladder)` says
        no fold can meet a compile any more. A narrow entry is selectable
        from its own compile on, whatever its wide twin does."""
        return sorted(k for k in self._available
                      if k not in self._wide_ingests
                      or k in self._wide_available)

    def _next_family(self, wide: bool, offered: int, left_on_nk: int,
                     new_keys: int) -> bool:
        """The lane family of the NEXT chunk, from what the packer saw in
        this one: of the `offered` rows, `left_on_nk` were left by regions
        whose new-key lane filled (a region that stopped on its spill lane
        alone gains nothing from a wider new-key lane), and the regions
        defined `new_keys` keys between them. Narrow -> wide where a wider
        lane would have taken a quarter of the chunk more; wide -> narrow
        where new keys come no faster than the narrow lanes hold them (a
        sixteenth of the rows at 1,024 rows a region). A narrow region
        takes all its rows up to a miss rate of a tenth and leaves a
        quarter of them from an eighth, so between the two the family
        stays, and stationary traffic (one row in a hundred left, one in
        ten while the dictionaries learn a wide universe) never flips it."""
        if wide:
            return new_keys * self.batch_per_region > self.caps.nk * offered
        return 4 * left_on_nk >= offered > 0

    def fold(self, state, events, extra=None, dns=None, drops=None,
             xlat=None, quic=None, trace=None, carry: bool = False):
        """Pack `events` (split over the regions, possibly in several
        chunks) into free ring slots, ship and ingest each; returns the new
        dist state (async — not blocked on). Row counts beyond one batch
        dispatch as the largest fitting superbatch ladder entries.

        A region stops packing where one of its side lanes fills. By
        default the rows behind that point fold in continuation chunks, so
        every row is consumed on return. With `carry` each chunk is
        dispatched ONCE and the return is `(state, left)`: the ascending
        `(lo, hi)` row ranges of `events` that no region took, for a
        caller that offers them again at the front of its next fold
        (`PendingEventBuffer`) — a continuation chunk costs a full-shape
        run for the few regions that stopped."""
        n = len(events)
        if n == 0:
            return (state, []) if carry else state
        trace, owned = self._fold_trace(trace)
        try:
            feats = dict(extra=extra, dns=dns, drops=drops, xlat=xlat,
                         quic=quic)
            start = 0
            left = []
            while start < n:
                remaining = n - start
                k = max((x for x in self.ladder
                         if x in self._available
                         and x * self.batch_size <= remaining), default=1)
                take = min(remaining, k * self.batch_size)
                chunk_feats = {
                    name: (v[start:start + take]
                           if v is not None and len(v) else None)
                    for name, v in feats.items()}
                state, chunk_left = self._fold_chunk(
                    state, events[start:start + take], chunk_feats, k, trace,
                    carry)
                left += [(start + lo, start + hi) for lo, hi in chunk_left]
                start += take
            return (state, left) if carry else state
        finally:
            if owned:
                trace.finish()

    def _fold_chunk(self, state, events, feats, k: int, trace, carry: bool):
        """Pack and dispatch ONE k-superbatch chunk (<= k * batch_size rows)
        through the k ladder entry, again for what its regions could not
        take until none is left — or, with `carry`, once. Each dispatch
        takes the lane family the one before it called for (`_next_family`)
        where entry k has a compiled wide program. Returns the state and
        the row ranges left (empty without `carry`)."""
        n = len(events)
        nr = self.n_shards * k * self.lanes
        kl = k * self.lanes
        kmax_l = self.superbatch_max * self.lanes
        bounds = [n * i // nr for i in range(nr + 1)]
        shard_ev = [events[bounds[i]:bounds[i + 1]] for i in range(nr)]
        shard_feats = [
            {name: (v[bounds[i]:bounds[i + 1]] if v is not None and len(v)
                    else None) for name, v in feats.items()}
            for i in range(nr)]
        starts = [0] * nr
        first = True
        while any(starts[i] < len(shard_ev[i]) for i in range(nr)):
            adaptive = k in self._wide_available
            wide = adaptive and self._wide_next
            ingest, caps, region_words = self.program(k, wide)
            ship_words = nr * region_words
            chunk = self._chunk(trace, k, not first, wide)
            try:
                slot = self._wait_slot(chunk)
            except StagingWedged as exc:
                # earlier chunks may have dispatched (donating the caller's
                # state buffers); hand the last valid state to the catcher
                exc.state = state
                raise
            buf = self._bufs[slot]
            offered = sum(len(shard_ev[i]) - starts[i] for i in range(nr))

            def pack_shard(i):
                # touches only region-local state (its dict, its buffer
                # region, starts[i]); returns the diagnostic counters so
                # threaded packs don't race on shared attributes
                region = buf[i * region_words:(i + 1) * region_words]
                if starts[i] >= len(shard_ev[i]):
                    # exhausted region in a continuation chunk: mask it
                    # empty (validity words only — 1/3 of a full memset),
                    # and don't roll its dictionary epoch for rows it
                    # isn't packing
                    flowpack.zero_resident_region(
                        region, self.batch_per_region, caps)
                    return 0, 0, 0
                # region i of a k-chunk is (shard, ladder-position j) —
                # dict j of that shard, whatever k and lane family the
                # chunk uses, so the dictionary always matches device table
                # row j
                kd = self.kdicts[(i // kl) * kmax_l + (i % kl)]
                resets = 0
                if kd.count() >= self.slot_cap:
                    kd.reset()  # per-region epoch roll
                    resets = 1
                _, consumed = flowpack.pack_resident(
                    shard_ev[i], batch_size=self.batch_per_region,
                    kdict=kd, caps=caps, start=starts[i],
                    out=region, **shard_feats[i])
                if consumed == 0 and starts[i] < len(shard_ev[i]):
                    raise RuntimeError("resident pack made no progress")
                starts[i] += consumed
                return int(region[2]), resets, int(region[1])

            with self._pack_stage(chunk):
                if self.pack_threads > 1 and nr > 1:
                    # per-region dictionaries are independent; the native
                    # pack releases the GIL, so regions pack in true parallel
                    outs = [f.result() for f in flowpack._pack_submit(
                        min(self.pack_threads, nr),
                        [lambda i=i: pack_shard(i) for i in range(nr)])]
                else:
                    outs = [pack_shard(i) for i in range(nr)]
            chunk_spills = sum(o[0] for o in outs)
            chunk_resets = sum(o[1] for o in outs)
            if adaptive:
                self._wide_next = self._next_family(
                    wide, offered,
                    sum(len(shard_ev[i]) - starts[i] for i in range(nr)
                        if outs[i][2] >= caps.nk),
                    sum(o[2] for o in outs))
            self.spill_rows += chunk_spills
            self.dict_resets += chunk_resets
            self.superbatch_folds[k] = self.superbatch_folds.get(k, 0) + 1
            self.wide_folds += wide
            if self._metrics is not None:
                if chunk_spills:
                    self._metrics.sketch_resident_spill_rows_total.inc(
                        chunk_spills)
                if chunk_resets:
                    self._metrics.sketch_resident_dict_epochs_total.inc(
                        chunk_resets)
                if not first:
                    self._metrics.sketch_resident_continuations_total.inc()
                self._metrics.sketch_superbatch_folds_total.labels(
                    str(k)).inc()
                if wide:
                    self._metrics.sketch_resident_wide_folds_total.inc()
            if not first:
                self.continuations += 1
            first = False
            # host-to-device transfer and jit enqueue are different costs:
            # one stage each
            with chunk.stage("put"):
                dev = self._put(buf[:ship_words])
            with chunk.stage("ingest_dispatch"):
                state, self.key_tables, token = ingest(
                    state, self.key_tables, dev)
            self._advance(slot, token)
            if carry:
                break
        left = [(bounds[i] + starts[i], bounds[i + 1]) for i in range(nr)
                if starts[i] < len(shard_ev[i])]
        if left:
            rows = sum(hi - lo for lo, hi in left)
            self.carried_rows += rows
            if self._metrics is not None:
                self._metrics.sketch_resident_carried_rows_total.inc(rows)
        return state, left

    def fold_packed(self, state, packed, trace=None):
        """Ship PRE-PACKED resident regions (the fused native pipeline's
        arena — loader's fp_drain_to_resident ran the pack stage at drain
        time with this ring's own dictionaries). SCHEDULING ONLY: the arena
        holds the segments of `flowpack.cc`'s own schedule — every chunk
        finished by continuation segments, as `fold()` without `carry`
        packs the same rows (tests/test_native_pipeline.py pins it against
        a replica of that schedule) — so this path only replaces the
        per-region python pack loop with one memcpy per segment and counts
        its segments as that loop counts its chunks. It is NOT the
        exporter's steady schedule: there a chunk is dispatched once and
        the rows it left ride the next (`fold(carry=True)`), so the two
        consume the same rows in a different order. The caller (exporter)
        holds the ResidentPackSurface lock and has already checked the
        pack epoch."""
        trace, owned = self._fold_trace(trace)
        try:
            rw = self._region_words
            for ch in packed.chunks:
                nr = self.n_shards * ch.k * self.lanes
                seg_words = nr * rw
                for s in range(ch.n_segs):
                    chunk = self._chunk(trace, ch.k, bool(s))
                    try:
                        slot = self._wait_slot(chunk)
                    except StagingWedged as exc:
                        # chunks already dispatched donated the caller's
                        # state buffers (the _fold_chunk rule) — hand the
                        # last valid state over; the surface invalidates
                        # (pre-packed slot definitions are dropping)
                        exc.state = state
                        raise
                    buf = self._bufs[slot]
                    off = ch.arena_off + s * seg_words
                    with self._pack_stage(chunk):
                        np.copyto(buf[:seg_words],
                                  packed.arena[off:off + seg_words])
                    self.superbatch_folds[ch.k] = (
                        self.superbatch_folds.get(ch.k, 0) + 1)
                    if s:
                        self.continuations += 1
                    if self._metrics is not None:
                        if s:
                            (self._metrics
                             .sketch_resident_continuations_total.inc())
                        self._metrics.sketch_superbatch_folds_total.labels(
                            str(ch.k)).inc()
                    with chunk.stage("put"):
                        dev = self._put(buf[:seg_words])
                    with chunk.stage("ingest_dispatch"):
                        state, self.key_tables, token = self._ingests[ch.k](
                            state, self.key_tables, dev)
                    self._advance(slot, token)
                # per-chunk counters the native pack already aggregated
                self.spill_rows += ch.spills
                self.dict_resets += ch.resets
                if self._metrics is not None:
                    if ch.spills:
                        self._metrics.sketch_resident_spill_rows_total.inc(
                            ch.spills)
                    if ch.resets:
                        self._metrics.sketch_resident_dict_epochs_total.inc(
                            ch.resets)
            return state
        finally:
            if owned:
                trace.finish()


class ResidentPackSurface:
    """Coordination point between the drain-side fused pack
    (loader.NativeEvictPipeline / fp_drain_to_resident) and the ring that
    owns the dictionaries the pack mutates.

    The load-bearing invariant is SHIP ORDER = DICT-MUTATION ORDER: a
    shipped resident buffer must contain (or follow) every slot definition
    its hot rows reference. Fused packs mutate the dictionaries at DRAIN
    time but ship at FOLD time; a raw fold (python pack) mutates at ship
    time. So whenever a raw fold would run while fused-packed arenas are
    still outstanding (packed, not yet shipped), those arenas' slot
    definitions would ship AFTER rows referencing them — `invalidate()`
    resolves it by bumping the epoch (outstanding arenas are discarded at
    their fold; their raw rows refold) and resetting every ring dictionary
    (the safe epoch-roll: each live slot is redefined through the new-key
    lane before any hot row references it). With no outstanding arena a
    raw fold needs no invalidation — mixed steady state stays cheap.

    Lock order: the exporter lock may be held when taking `lock`; `lock`
    holders never take the exporter lock (the drain thread holds `lock`
    across the whole fused native call)."""

    def __init__(self, ring: "ShardedResidentStagingRing"):
        self.ring = ring
        self.lock = threading.Lock()
        self.epoch = 0
        #: fused-packed arenas produced but not yet shipped or discarded
        self.outstanding = 0

    def pack_spec(self) -> dict:
        """The ring's current pack geometry for NativePipe.drain(pack=...).
        Call under `lock` (the available-ladder set and the dictionary
        handles must not move between spec and pack)."""
        ring = self.ring
        ks = sorted(k for k in ring.ladder if k in ring._available)
        kmax_l = ring.superbatch_max * ring.lanes
        ladder = []
        for k in ks:
            kl = k * ring.lanes
            nr = ring.n_shards * k * ring.lanes
            ladder.append((k, [
                ring.kdicts[(i // kl) * kmax_l + (i % kl)]._live_handle()
                for i in range(nr)]))
        return {"batch_size": ring.batch_size,
                "batch_per_region": ring.batch_per_region,
                "slot_cap": ring.slot_cap, "caps": ring.caps,
                "ladder": ladder}

    def invalidate_for_raw_fold(self) -> None:
        """Call BEFORE any raw (non-packed) fold while this surface is
        bound. No-op when no fused arena is outstanding."""
        with self.lock:
            if self.outstanding:
                self._invalidate_locked()

    def invalidate(self) -> None:
        with self.lock:
            self._invalidate_locked()

    def note_external_reset(self) -> None:
        """The caller already reset the ring dictionaries itself (the
        ingest-error epoch roll) — record the epoch move so outstanding
        fused arenas (packed against the pre-reset dictionaries) discard
        at their fold instead of shipping stale slot references."""
        with self.lock:
            self.epoch += 1
            self.outstanding = 0

    def _invalidate_locked(self) -> None:
        self.epoch += 1
        self.outstanding = 0
        ring = self.ring
        for kd in ring.kdicts:
            kd.reset()
        ring.dict_resets += len(ring.kdicts)
        if ring._metrics is not None:
            ring._metrics.sketch_resident_dict_epochs_total.inc(
                len(ring.kdicts))

