"""Flight recorder: sampled end-to-end batch tracing with stage spans.

A *trace* follows one unit of work through the pipeline — a "batch" trace is
born at map eviction and rides the EvictedFlows object through the queues to
the exporter fold; a "window" trace is born at window roll and rides the
queued device report through render and sink delivery. Each pipeline stage
wraps its work in a *span* (``with trace.stage("resident_pack"): ...``);
completed traces land in a fixed-size ring buffer (the flight recorder,
``/debug/traces`` on the debug server) and every span duration feeds the
``stage_seconds{stage=...}`` histogram family when a Metrics facade is bound
(:func:`set_metrics`, done by ``FlowsAgent.__init__``).

The inter-span *gaps* are as load-bearing as the spans: the time between the
``evict`` span's end and the ``fold`` span's start is exactly the
evicted/export queue wait — the first thing to grow when the exporter falls
behind.

Three recorders share every stage boundary:

- **the stage clocks** — always on, sampled or not, with jax loaded or not:
  when a stage exits, its wall seconds (``time.perf_counter``), its
  thread-CPU seconds (``time.thread_time``: a thread blocked on the GIL or
  on a lock accrues none) and one call are added to a table owned by the
  calling thread. No add can be lost between threads, and the hot path
  takes no lock and calls no Prometheus code. :func:`stage_clocks` sums
  the tables, a thread's table kept after the thread exits; the metrics
  registry exposes the sums as ``stage_wall_seconds_total{stage}``,
  ``stage_cpu_seconds_total{stage}`` and ``stage_calls_total{stage}``.
  Totals include child stages: a stage's self time is its total less its
  children's totals (the nesting is docs/observability.md "Stages").
- **the profiler's** — every ``stage()`` opens a
  ``jax.profiler.TraceAnnotation`` named ``netobserv:<stage>``, sampled or
  not, carrying the stage's ids as arguments (``eviction``, ``evictions``,
  ``chunk``, ``k``, ``cont``, ``window``; ``fn``/``call`` on the
  ``dispatch`` annotation ``utils.retrace`` opens through
  :func:`annotate`). While no ``jax.profiler`` session runs the annotation
  is the profiler's own no-op (one flag test in C++; about a microsecond
  of Python per stage, measured on the v5e host in PR 25); in a capture the
  spans lie on the device trace's clock, and the ids tie an eviction to the
  fold chunks that carry its rows and a dispatch to its module run. There
  is no switch and no environment variable. ``jax`` is never imported from
  here: a process that has not loaded it (an agent with ``EXPORT=grpc``)
  gets the shared :data:`NULL_SPAN` instead.
- **the flight recorder** — sampled by ``TRACE_SAMPLE``, below. Sampled
  spans carry the same ids (``/debug/traces`` renders them per stage).

Sampling and the zero-cost contract:

- ``TRACE_SAMPLE`` (env, float in [0, 1], default 0/unset = disabled) is the
  per-trace sampling rate, applied deterministically PER TRACE KIND (every
  round(1/rate)-th :func:`start_trace` call of that kind samples, so
  ``TRACE_SAMPLE=1`` traces everything, tests are reproducible, and the
  pipeline's periodic call pattern cannot alias one kind out of the
  sample).
- Disabled (the default), :func:`start_trace` is one module-bool check
  returning the shared :data:`NULL_TRACE`, whose ``stage()`` opens the
  profiler annotation and reads the stage clocks, and nothing else — no
  ``Trace``, no recorder entry, no lock: two clock pairs and one add into
  the thread's own table (a thread's first stage registers its table
  once, under a lock; pinned by tests/test_tracing.py).
- Unsampled calls while enabled cost one int increment + one modulo.

``TRACE_RING`` (env, default 64) bounds how many completed traces the
recorder keeps; snapshots are newest-first.

Cross-process propagation (the federation seam): a sampled trace exposes a
serializable :class:`TraceContext` via :func:`context_of` (fleet-unique hex
trace id + origin span + sample bit). The agent stamps it into the delta
frame; the aggregator calls :func:`continue_trace` to keep recording child
spans under the SAME trace id, so ``/debug/traces?trace=<id>`` on either
process shows one window's journey end to end. Both helpers keep the
zero-cost bar: ``context_of(NULL_TRACE)`` is one attribute check returning
``None`` (nothing serialized, the frame stays byte-identical), and
``continue_trace`` with tracing disabled — or a ``None``/unsampled context —
returns the shared :data:`NULL_TRACE`. The sampling decision is made ONCE at
the origin: a receiver with tracing enabled always honors a propagated
sampled context (its own period applies only to traces it originates).
"""

from __future__ import annotations

import itertools
import os
import sys
import threading
import time
from collections import deque
from typing import NamedTuple, Optional

__all__ = [
    "NULL_SPAN", "NULL_TRACE", "Trace", "FlightRecorder", "TraceContext",
    "start_trace", "configure", "set_metrics", "snapshot", "enabled",
    "set_active", "clear_active", "active_trace",
    "context_of", "continue_trace", "group", "annotate", "stage_clocks",
]


class _NullSpan:
    """Shared no-op context manager: what a stage is in a process that has
    not loaded jax (no profiler to annotate for)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NULL_SPAN = _NullSpan()

#: prefix of every annotation this module opens in a profiler capture
ANNOTATION_PREFIX = "netobserv:"
_annotation = None  # jax.profiler.TraceAnnotation, once jax is loaded


def annotate(stage: str, **ids):
    """The profiler's span for one stage boundary: a context manager that
    is ``jax.profiler.TraceAnnotation("netobserv:<stage>", **ids)`` — the
    profiler's own no-op while no session runs — or :data:`NULL_SPAN` in a
    process that has not imported jax (this module never imports it).
    Per drain, per fold chunk, per window, per dispatch: never per record."""
    global _annotation
    cls = _annotation
    if cls is None:
        if "jax" not in sys.modules:
            return NULL_SPAN
        from jax.profiler import TraceAnnotation as cls
        _annotation = cls
    return cls(ANNOTATION_PREFIX + stage, **ids)


class _Bound:
    """A trace handle whose every stage carries `ids` besides its own (the
    drain thread's ``eviction=<n>``, a fold's ``evictions=<a>-<b>``, a
    window's ``window=<n>``): what ``trace.bind(**ids)`` returns, for
    handing to callees that open stages without knowing the ids. Everything
    else (``trace_id``, ``kind``, ...) reads through to the trace."""

    __slots__ = ("_trace", "_ids")

    def __init__(self, trace, ids: dict):
        self._trace = trace
        self._ids = ids

    def stage(self, name: str, **ids):
        return self._trace.stage(name, **{**self._ids, **ids})

    def bind(self, **ids):
        return _Bound(self._trace, {**self._ids, **ids})

    def __getattr__(self, item: str):     # sampled, finish, trace_id, ...
        return getattr(self._trace, item)


class _NullTrace:
    """Shared do-nothing trace: every un-sampled batch carries this. Its
    stages are profiler annotations and stage clocks only."""

    __slots__ = ()
    sampled = False

    def stage(self, name: str, **ids):
        return _SpanCtx((), name, ids)

    def bind(self, **ids):
        return _Bound(self, ids)

    def finish(self) -> None:
        pass


NULL_TRACE = _NullTrace()


class TraceContext(NamedTuple):
    """Serializable identity of a sampled trace, for crossing a process
    boundary (the delta frame's optional ``trace_ctx`` field). ``trace_id``
    is the fleet-unique hex id (process salt + local counter), ``origin``
    names the span/process that exported it, ``sampled`` is the origin's
    sampling verdict — carried explicitly so an unsampled context decoded
    off a hand-built frame still resolves to NULL_TRACE."""

    trace_id: str
    origin: str = ""
    sampled: bool = True


class _Span:
    __slots__ = ("stage", "t0", "t1", "thread", "ids")

    def __init__(self, stage: str, t0: float, t1: float, thread: str,
                 ids: dict):
        self.stage = stage
        self.t0 = t0
        self.t1 = t1
        self.thread = thread
        self.ids = ids


class _SpanCtx:
    """One stage boundary: ONE profiler annotation, the calling thread's
    stage clocks, and a span on each sampled trace — none for the null
    trace, several for a :class:`TraceGroup`. Clocks and spans record on
    exit even when the stage raised — a failed stage's duration is
    evidence, not noise."""

    __slots__ = ("_traces", "_stage", "_ids", "_t0", "_c0", "_ann")

    def __init__(self, traces: tuple, stage: str, ids: dict):
        self._traces = traces
        self._stage = stage
        self._ids = ids
        self._t0 = self._c0 = 0.0
        self._ann = NULL_SPAN

    def __enter__(self):
        self._ann = annotate(self._stage, **self._ids)
        self._ann.__enter__()
        self._c0 = time.thread_time()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, et, ev, tb):
        t1 = time.perf_counter()
        cpu = time.thread_time() - self._c0
        # the stage clocks, inline: the hot path's one add
        try:
            acc = _clock_local.table[self._stage]
        except (AttributeError, KeyError):
            acc = _clock_acc(self._stage)
        acc[0] += t1 - self._t0
        acc[1] += cpu
        acc[2] += 1
        self._ann.__exit__(et, ev, tb)
        for t in self._traces:
            t._add(self._stage, self._t0, t1, self._ids)
        return False


# --- stage clocks ----------------------------------------------------------
# Each thread adds into its own {stage: [wall_s, cpu_s, calls]}: the owner is
# the table's only writer, so no add is lost and the hot path takes no lock.
# A scrape (stage_clocks) reads every table under _tables_lock, which a
# thread also takes once, to register its table at its first stage.
_clock_local = threading.local()
_tables: list = []      # (thread, table) of every live thread that clocked
_retired: dict = {}     # the tables of exited threads, summed
_tables_lock = threading.Lock()


def _clock_acc(stage: str) -> list:
    """The calling thread's accumulator of `stage`, made at its first
    exit (and the thread's table at its first stage)."""
    try:
        table = _clock_local.table
    except AttributeError:
        table = _clock_local.table = {}
        with _tables_lock:
            _tables.append((threading.current_thread(), table))
    return table.setdefault(stage, [0.0, 0.0, 0])


def _sum_into(out: dict, table: dict) -> None:
    for stage, (wall, cpu, calls) in table.items():
        acc = out.setdefault(stage, [0.0, 0.0, 0])
        acc[0] += wall
        acc[1] += cpu
        acc[2] += calls


def stage_clocks() -> dict:
    """``{stage: (wall_s, cpu_s, calls)}`` of every stage that has exited in
    this process, summed over its threads, those that have exited included
    (their tables fold into one here). Monotonic: the counters
    ``stage_{wall,cpu}_seconds_total`` / ``stage_calls_total`` read it."""
    with _tables_lock:
        live = []
        for thread, table in _tables:
            if thread.is_alive():
                live.append((thread, table))
            else:
                _sum_into(_retired, table)
        _tables[:] = live
        out = {stage: list(acc) for stage, acc in _retired.items()}
        for _, table in live:
            # copy() is one C call under the GIL: the owner may be adding a
            # stage meanwhile
            _sum_into(out, table.copy())
    return {stage: tuple(acc) for stage, acc in out.items()}


class Trace:
    """One sampled unit of work. Spans may be appended from several threads
    (evict on the map-tracer thread, fold on the exporter thread, publish on
    the window timer), so appends take a per-trace lock — sampled traces are
    rare by construction, the lock never sits on the un-sampled path."""

    __slots__ = ("kind", "id", "trace_id", "origin", "unix_t0", "t0",
                 "spans", "_lock", "_done")
    sampled = True

    def __init__(self, kind: str, local_id: int,
                 trace_id: Optional[str] = None, origin: str = ""):
        self.kind = kind
        self.id = local_id
        # fleet-unique hex id: process salt + local counter for traces born
        # here; a continued trace ADOPTS the origin's id verbatim so the
        # recorder entries on both sides correlate by one string
        self.trace_id = (trace_id if trace_id is not None
                         else f"{_salt}{local_id:08x}")
        self.origin = origin
        self.unix_t0 = time.time()
        self.t0 = time.perf_counter()
        self.spans: list[_Span] = []
        self._lock = threading.Lock()
        self._done = False

    def stage(self, name: str, **ids) -> _SpanCtx:
        return _SpanCtx((self,), name, ids)

    def bind(self, **ids) -> _Bound:
        return _Bound(self, ids)

    def _add(self, stage: str, t0: float, t1: float, ids: dict) -> None:
        with self._lock:
            if not self._done:
                self.spans.append(_Span(
                    stage, t0, t1, threading.current_thread().name, ids))

    def finish(self) -> None:
        """Seal the trace and hand it to the flight recorder (idempotent —
        a batch trace that merged into an already-traced fold is finished
        by whoever holds it last)."""
        with self._lock:
            if self._done:
                return
            self._done = True
            spans = list(self.spans)
        m = _metrics
        if m is not None:
            for s in spans:
                m.observe_stage(s.stage, s.t1 - s.t0)
        if spans:
            _recorder.add(self)

    def render(self) -> dict:
        """JSON-ready view: spans sorted by start, durations and the
        queue-wait gap to the previous stage in milliseconds."""
        with self._lock:
            spans = sorted(self.spans, key=lambda s: s.t0)
        stages = []
        prev_t1: Optional[float] = None
        for s in spans:
            stages.append({
                "stage": s.stage,
                "thread": s.thread,
                "offset_ms": round((s.t0 - self.t0) * 1e3, 3),
                "dur_ms": round((s.t1 - s.t0) * 1e3, 3),
                # inter-stage gap = queue wait (negative means the spans
                # overlapped across threads; reported raw, not clipped)
                "gap_ms": (round((s.t0 - prev_t1) * 1e3, 3)
                           if prev_t1 is not None else 0.0),
                # what caused the span: eviction / evictions / chunk / k /
                # cont / window — the same arguments its profiler
                # annotation carries
                **({"ids": dict(s.ids)} if s.ids else {}),
            })
            prev_t1 = s.t1
        total = (spans[-1].t1 - spans[0].t0) if spans else 0.0
        out = {
            "id": self.id,
            "trace_id": self.trace_id,
            "kind": self.kind,
            "start_unix_ms": int(self.unix_t0 * 1e3),
            "total_ms": round(total * 1e3, 3),
            "stages": stages,
        }
        if self.origin:
            out["origin"] = self.origin
        return out


class TraceGroup:
    """Several sampled traces sharing the same spans — the aggregator's
    window close, where one roll/publish serves every agent trace continued
    into that window plus the aggregator's own window trace. stage() fans
    out to each member; finish() seals them all (Trace.finish is
    idempotent, so a member finished elsewhere is harmless)."""

    __slots__ = ("traces",)
    sampled = True

    def __init__(self, traces: list):
        self.traces = traces

    def stage(self, name: str, **ids) -> _SpanCtx:
        return _SpanCtx(tuple(self.traces), name, ids)

    def bind(self, **ids) -> _Bound:
        return _Bound(self, ids)

    def finish(self) -> None:
        for t in self.traces:
            t.finish()


def group(*traces):
    """Combine traces for shared spans: drops unsampled members, collapses
    to the single member or the shared NULL_TRACE when possible (so the
    common nothing-sampled case allocates nothing)."""
    live = [t for t in traces if t.sampled]
    if not live:
        return NULL_TRACE
    if len(live) == 1:
        return live[0]
    return TraceGroup(live)


class FlightRecorder:
    """Fixed-size ring of completed traces."""

    def __init__(self, capacity: int = 64):
        self._dq: deque = deque(maxlen=max(1, capacity))
        self._lock = threading.Lock()

    def add(self, trace: Trace) -> None:
        with self._lock:
            self._dq.append(trace)

    def snapshot(self, limit: Optional[int] = None,
                 trace_id: Optional[str] = None) -> list[dict]:
        """Newest-first JSON-ready dump (the /debug/traces body).
        ``trace_id`` keeps only traces with that exact hex id (the
        cross-process correlation lookup); ``limit`` caps the result
        AFTER filtering."""
        with self._lock:
            traces = list(self._dq)
        out = [t.render() for t in reversed(traces)]
        if trace_id is not None:
            out = [t for t in out if t.get("trace_id") == trace_id]
        if limit is not None and limit >= 0:
            out = out[:limit]
        return out

    def clear(self) -> None:
        with self._lock:
            self._dq.clear()

    def __len__(self) -> int:
        with self._lock:
            return len(self._dq)


# --- module state ----------------------------------------------------------

_enabled = False
# sample every _period-th start_trace() call PER KIND: a single shared
# counter would alias with the pipeline's periodic call pattern (each
# eviction issues one "batch" and one "fold" call, so at rate 0.5 one kind
# would land on the sampled residue every time and the other never; the
# once-per-window "window" call would pin to one residue at low rates).
# Kept >= 1 at ALL times so a concurrent configure(0) can never expose a
# modulo-by-zero to a hot-path thread that already saw _enabled=True.
_period = 1
# itertools.count: atomic under the GIL — start_trace is called from the
# map-tracer, exporter, and timer threads concurrently, and a plain `+= 1`
# would lose increments (skewing the deterministic period) and hand out
# duplicate trace ids
_counters: dict = {}
_counters_lock = threading.Lock()
_next_id = itertools.count(1)
# process-scoped salt prefixing every locally-born trace id: two agents (or
# an agent and the aggregator) must never mint the same hex id, or the
# cross-process correlation at /debug/traces?trace= aliases unrelated work
_salt = f"{os.getpid() & 0xffffffff:08x}{int.from_bytes(os.urandom(4), 'big'):08x}"
_metrics = None  # Metrics facade (set_metrics); observe_stage sink
_recorder = FlightRecorder(int(os.environ.get("TRACE_RING", "64") or 64))

recorder = _recorder  # public alias (server/debug.py, tests)


def configure(sample: Optional[float] = None,
              capacity: Optional[int] = None) -> None:
    """(Re)configure sampling; ``None`` re-reads the TRACE_SAMPLE env var.
    Rates in (0, 1] sample every round(1/rate)-th trace; 0 disables."""
    global _enabled, _period, _counters, _recorder, recorder
    if sample is None:
        sample = float(os.environ.get("TRACE_SAMPLE", "0") or 0)
    if not 0.0 <= sample <= 1.0:
        raise ValueError(f"TRACE_SAMPLE={sample!r} must be in [0, 1]")
    if capacity is not None:
        _recorder = recorder = FlightRecorder(capacity)
    _counters = {}
    if sample <= 0.0:
        _enabled = False  # _period stays >= 1 (hot-path race safety above)
    else:
        _period = max(1, round(1.0 / sample))
        _enabled = True


def enabled() -> bool:
    return _enabled


def start_trace(kind: str = "batch"):
    """The hot-path entry: returns a live :class:`Trace` for sampled calls,
    the shared :data:`NULL_TRACE` otherwise. Disabled = one bool check.
    Sampling is deterministic PER KIND (see _period above)."""
    if not _enabled:
        return NULL_TRACE
    c = _counters.get(kind)
    if c is None:
        with _counters_lock:
            c = _counters.setdefault(kind, itertools.count(1))
    if next(c) % _period:
        return NULL_TRACE
    return Trace(kind, next(_next_id))


def context_of(trace, origin: str = "") -> Optional[TraceContext]:
    """Serializable context of a sampled trace, or ``None``. The zero-cost
    gate for the wire: NULL_TRACE (tracing off or this window unsampled)
    answers None in one attribute check, and the caller stamps nothing —
    the frame stays byte-identical to the context-less encoding."""
    if not trace.sampled:
        return None
    return TraceContext(trace.trace_id, origin or trace.kind, True)


def continue_trace(ctx, kind: str = "batch"):
    """Continue a propagated trace in THIS process: a live :class:`Trace`
    adopting the context's trace id, or the shared NULL_TRACE when tracing
    is disabled here or the context is absent/unsampled. The origin's
    sampling verdict is honored as-is — the local period applies only to
    locally-born traces."""
    if not _enabled or ctx is None or not ctx.sampled or not ctx.trace_id:
        return NULL_TRACE
    return Trace(kind, next(_next_id), trace_id=ctx.trace_id,
                 origin=ctx.origin)


# Per-thread active trace: lets a deep callee (the kernel drain inside
# BpfmanFetcher.lookup_and_delete) attach child spans to the trace born in
# map_tracer WITHOUT widening the FlowFetcher protocol. map_tracer binds the
# drain's handle (the batch trace or the shared null one, with the drain's
# eviction=<n>) so the callee's stages carry the id in a capture whether or
# not the drain is sampled: one thread-local write and one getattr PER
# DRAIN, never per record.
_active = threading.local()


def set_active(trace) -> None:
    """Bind `trace` as the calling thread's active trace."""
    _active.trace = trace


def clear_active() -> None:
    _active.trace = None


def active_trace():
    """The calling thread's bound trace, or the shared NULL_TRACE."""
    t = getattr(_active, "trace", None)
    return NULL_TRACE if t is None else t


def set_metrics(metrics) -> None:
    """Bind the Metrics facade whose ``observe_stage`` receives every span
    of every finished trace (stage_seconds{stage=...})."""
    global _metrics
    _metrics = metrics


def snapshot(limit: Optional[int] = None,
             trace_id: Optional[str] = None) -> list[dict]:
    """Newest-first completed traces (the /debug/traces payload); see
    :meth:`FlightRecorder.snapshot` for the filter params."""
    return _recorder.snapshot(limit=limit, trace_id=trace_id)


# arm from the environment at import; unset -> disabled, start_trace stays
# on the one-branch path
if os.environ.get("TRACE_SAMPLE"):
    configure()
