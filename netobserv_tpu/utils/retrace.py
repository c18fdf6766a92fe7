"""Jit retrace watchdog: the "ingest must never retrace" invariant, live.

The CLAUDE.md invariant — fixed batch shapes, padding, masks, no
data-dependent shapes under jit — is enforced by tests but was never
*watched* in production, where a retrace is a multi-second ingest stall and
an unbounded compile-cache leak. This module turns it into an alarm:

- every jitted entry point the pipeline constructs is made by :func:`jit`,
  the ONE seam that names, jits and watches (the factories of
  ``sketch/state.py``, ``parallel/merge.py``, ``sketch/tenancy.py``): the
  watch name is the function's ``__name__``, so the XLA module is
  ``jit_<watch name>`` and a device capture names every program the way
  ``/debug/executables`` does;
- every call of a watched entry opens the profiler annotation
  ``netobserv:dispatch`` with ``fn=<watch name>`` and ``call=<n>`` (its
  per-executable sequence number, ``Watched.calls``): the k-th run of
  module ``jit_<fn>`` in a capture is the run of dispatch ``call0 + k``
  (``utils.tracing.annotate`` — the profiler's no-op while no session
  runs);
- a process-wide ``jax.monitoring`` listener counts XLA *lowerings*
  (``/jax/core/compile/jaxpr_to_mlir_module_duration``) and attributes each
  to the watched entry point currently executing on that thread (jit traces
  and lowers synchronously in the calling thread; lowering fires on every
  retrace even when the persistent compilation cache serves the executable,
  which ``backend_compile`` events would miss);
- each entry point's first ``warmup_calls`` calls (default 1,
  ``RETRACE_WARMUP_CALLS``) may compile freely — that is the expected
  warmup window. A compile on any later call is a retrace: it increments
  ``sketch_retraces_total{fn=...}`` (when a Metrics facade is bound via
  :func:`set_metrics`) and logs the offending abstract shapes.

``RETRACE_WATCHDOG=0`` disables wrapping entirely (``watch`` returns the
function untouched). The wrapper itself costs two thread-local attribute
writes plus one monotonic-clock pair per call — per *batch*, never per
record.

Beyond the alarm, the wrapper IS the per-executable accounting registry
(``/debug/executables`` on agent and aggregator): per watched jit it tracks dispatch count, cumulative dispatch
wall seconds (fed to ``executable_dispatch_seconds_total{fn=...}`` when a
Metrics facade is bound), cumulative compile seconds (the lowering
listener's duration, warmup included), the last abstract-shape signature
seen at a compile, and a donated-bytes estimate (sum of array-arg nbytes at
the last compile — the HBM the executable's donation reuses per dispatch).
This is the attribution surface the proof-of-performance round reads: where
wall/compile/HBM went, per executable, not per lumped stage.

Wrapped functions delegate attribute access to the underlying jit function,
so AOT introspection (``fn.lower(...)``, ``fn._cache_size()``) keeps working
(tests/test_parallel.py lowers the sharded ingest to assert the
no-collectives invariant — through the wrapper).
"""

from __future__ import annotations

import logging
import os
import threading
import time
import weakref
from typing import Any, Callable, Optional

from netobserv_tpu.utils import tracing

log = logging.getLogger("netobserv_tpu.retrace")

#: fires once per jaxpr->MLIR lowering, i.e. once per (re)trace of a jitted
#: callable, regardless of persistent-compilation-cache hits
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

_enabled = os.environ.get("RETRACE_WATCHDOG", "1").strip().lower() not in (
    "0", "false", "no", "off")
_default_warmup = int(os.environ.get("RETRACE_WARMUP_CALLS", "1") or 1)
_metrics = None
_installed = False
_install_lock = threading.Lock()
_tls = threading.local()
#: every live Watched wrapper, for /debug/jax and tests. Weak references:
#: the registry must not pin dead exporters' jit functions (and their
#: compile caches) for process lifetime — a torn-down wrapper just drops
#: out of the accounting
_registry: list["weakref.ref[Watched]"] = []
#: process-lifetime alarm history — survives wrapper GC (the registry is
#: weak, the verdict is not)
_retraces_total = 0


def _describe(args: tuple, limit: int = 600) -> str:
    """Abstract shapes of a call's arguments (dtype[shape] per leaf)."""
    try:
        import jax

        desc = str(jax.tree.map(
            lambda x: f"{getattr(x, 'dtype', type(x).__name__)}"
                      f"{list(getattr(x, 'shape', []))}", args))
    except Exception as exc:  # never let diagnostics break the caller
        desc = f"<unrenderable args: {exc}>"
    return desc if len(desc) <= limit else desc[:limit] + "...(truncated)"


def _avals(args: tuple) -> tuple:
    """`args` as ShapeDtypeStructs (sharding kept) — what `.lower` needs to
    rebuild the same executable without holding the buffers."""
    try:
        import jax

        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=getattr(x, "sharding", None)),
            args)
    except Exception:  # never let diagnostics break the caller
        return ()


def _donated_bytes(args: tuple) -> int:
    """Sum of array-argument bytes at compile time: the donated-buffer HBM
    estimate for one dispatch of this signature (the state arrays the fold
    ladder donates dominate; scalars contribute 0)."""
    total = 0
    try:
        import jax

        for leaf in jax.tree.leaves(args):
            nbytes = getattr(leaf, "nbytes", None)
            if nbytes is not None:
                total += int(nbytes)
    except Exception:  # never let accounting break the caller
        return 0
    return total


class Watched:
    """Callable wrapper counting compilations of one jitted entry point,
    and the per-executable accounting row behind /debug/executables."""

    __slots__ = ("_fn", "name", "warmup_calls", "calls", "compiles",
                 "retraces", "last_retrace", "dispatch_seconds",
                 "compile_seconds", "last_signature", "last_avals",
                 "donated_bytes", "tenants", "tiered", "labels",
                 "__weakref__")

    def __init__(self, fn: Callable, name: str, warmup_calls: int,
                 tenants: Optional[int] = None,
                 tiered: Optional[str] = None):
        self._fn = fn
        self.name = name
        self.warmup_calls = warmup_calls
        self.calls = 0
        self.compiles = 0
        self.retraces = 0
        self.last_retrace: str = ""
        self.dispatch_seconds = 0.0
        self.compile_seconds = 0.0
        self.last_signature: str = ""
        #: abstract arguments (shape, dtype, sharding) of the last compile:
        #: `w.lower(*w.last_avals)` re-lowers the executable that serves
        #: steady state, for HLO checks of what was really dispatched
        self.last_avals: tuple = ()
        self.donated_bytes = 0
        #: tenant count of a tenant-stacked (vmapped) executable — the
        #: /debug/executables registry reports the stacked fold as ONE fn
        #: with its tenant axis named, never N anonymous entries
        self.tenants = tenants
        #: tiered fold form of a SKETCH_TIERED executable ("interior" |
        #: "decode") — same one-program rule: the registry attributes
        #: which walk the entry compiled to, never a hidden variant
        self.tiered = tiered
        #: what the traced function said of the forms it compiled to
        #: (:func:`label`, e.g. countmin=factored|scatter): chosen at trace
        #: time from what the code observes, so only the trace can name it
        self.labels: dict[str, str] = {}

    def __call__(self, *args, **kwargs):
        self.calls += 1
        prev = getattr(_tls, "active", None)
        _tls.active = self
        _tls.args = args
        t0 = time.perf_counter()
        try:
            with tracing.annotate("dispatch", fn=self.name, call=self.calls):
                return self._fn(*args, **kwargs)
        except Exception as exc:
            if self.calls <= self.warmup_calls:
                # a first call that fails is a lowering or compile refusal
                # (or a backend that will not start): not transient, and
                # the callers' swallow-and-count handlers do not know which
                # executable it was
                log.error("first call of jitted entry %r failed — the "
                          "executable did not lower/compile: %s: %s",
                          self.name, type(exc).__name__, exc)
            raise
        finally:
            # one monotonic-clock pair per DISPATCH (per batch, never per
            # record) — the wall attribution the accounting registry exists
            # for. Async dispatch means this is enqueue cost on TPU and
            # full execution on CPU; either way it is the wall the pipeline
            # thread actually spent inside this executable's call.
            dt = time.perf_counter() - t0
            self.dispatch_seconds += dt
            m = _metrics
            if m is not None:
                m.observe_dispatch(self.name, dt)
            _tls.active = prev
            _tls.args = None

    def __getattr__(self, item: str) -> Any:
        # delegate .lower / ._cache_size / __wrapped__-style access
        return getattr(object.__getattribute__(self, "_fn"), item)

    def _note_compile(self, duration: float = 0.0) -> None:
        global _retraces_total
        self.compiles += 1
        self.compile_seconds += duration
        args = getattr(_tls, "args", None) or ()
        # signature/donation refresh on EVERY compile, warmup included —
        # the registry row must describe the executable that actually
        # serves steady state, which is the last one compiled
        sig = _describe(args)
        if self.tenants is not None:
            # tenant-stacked entries prefix the axis size so the lowered
            # signature reads as one executable folding N tenants (the
            # leading dim of every stacked arg IS this count)
            sig = f"tenants={self.tenants} {sig}"
        if self.tiered is not None:
            # tiered entries prefix the fold form so the signature reads
            # as the tier-interior walk or the decode-to-wide wrap
            sig = f"tiered={self.tiered} {sig}"
        self.last_signature = sig
        self.last_avals = _avals(args)
        self.donated_bytes = _donated_bytes(args)
        if self.calls <= self.warmup_calls:
            return  # expected warmup compile
        self.retraces += 1
        _retraces_total += 1
        self.last_retrace = self.last_signature
        log.error(
            "post-warmup XLA retrace of jitted entry %r (call %d, compile "
            "%d): the fixed-shape ingest invariant is broken; offending "
            "abstract shapes: %s",
            self.name, self.calls, self.compiles, self.last_retrace)
        m = _metrics
        if m is not None:
            m.count_retrace(self.name)

    def stats(self) -> dict:
        return {"fn": self.name, "calls": self.calls,
                "compiles": self.compiles, "retraces": self.retraces,
                "warmup_calls": self.warmup_calls,
                "dispatch_seconds": round(self.dispatch_seconds, 6),
                "compile_seconds": round(self.compile_seconds, 6),
                "donated_bytes_estimate": self.donated_bytes,
                **({"tenants": self.tenants}
                   if self.tenants is not None else {}),
                **({"tiered": self.tiered}
                   if self.tiered is not None else {}),
                **self.labels,
                **({"last_signature": self.last_signature}
                   if self.last_signature else {}),
                **({"last_retrace": self.last_retrace}
                   if self.last_retrace else {})}


def label(key: str, value: str) -> None:
    """Called while a watched entry is being TRACED: put `key: value` on its
    /debug/executables row (a form the function chose from the shapes it
    saw). Outside a watched call it does nothing."""
    w = getattr(_tls, "active", None)
    if w is not None:
        w.labels[key] = value


def _listener(event: str, duration: float, **kwargs) -> None:
    if event != _LOWER_EVENT:
        return
    w = getattr(_tls, "active", None)
    if w is not None:
        w._note_compile(duration)


def _ensure_installed() -> None:
    global _installed
    with _install_lock:
        if _installed:
            return
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(_listener)
        _installed = True


def watch(fn: Callable, name: str,
          warmup_calls: Optional[int] = None,
          tenants: Optional[int] = None,
          tiered: Optional[str] = None) -> Callable:
    """Wrap a jitted entry point for retrace accounting. Returns `fn`
    unchanged when the watchdog is disabled; never double-wraps.
    `tenants` marks a tenant-stacked (vmapped) executable: the registry
    reports it as one fn with the tenant count in its signature string.
    `tiered` ("interior" | "decode") marks a SKETCH_TIERED executable with
    the fold form it compiled to — one program either way, attributed."""
    if not _enabled or isinstance(fn, Watched):
        return fn
    _ensure_installed()
    w = Watched(fn, name, _default_warmup if warmup_calls is None
                else warmup_calls, tenants=tenants, tiered=tiered)
    with _install_lock:
        _registry.append(weakref.ref(w))
        if len(_registry) % 64 == 0:  # amortized sweep of dead wrappers
            _registry[:] = [r for r in _registry if r() is not None]
    return w


def jit(fn: Callable, name: str, *, tenants: Optional[int] = None,
        tiered: Optional[str] = None, **jit_kwargs) -> Callable:
    """Name, jit and watch one entry point — in that order, so the name the
    registry reports (``/debug/executables``, ``sketch_retraces_total{fn}``)
    is the name XLA gives the module (``jit_<name>``) and a device capture
    needs no inference to tell the programs apart. `jit_kwargs` go to
    ``jax.jit``; the rest to :func:`watch`."""
    import jax

    fn.__name__ = fn.__qualname__ = name
    return watch(jax.jit(fn, **jit_kwargs), name, tenants=tenants,
                 tiered=tiered)


def watched() -> list[Watched]:
    """Every live wrapper (the registry rows' owners — `lower` one with its
    `last_avals` to inspect the executable it dispatched)."""
    return [w for w in (r() for r in _registry) if w is not None]


def set_metrics(metrics) -> None:
    """Bind the Metrics facade whose ``count_retrace`` receives post-warmup
    retraces (sketch_retraces_total{fn=...})."""
    global _metrics
    _metrics = metrics


def snapshot() -> list[dict]:
    """Per-entry-point compile accounting (live wrappers), for /debug/jax."""
    return [w.stats() for w in watched()]


def total_retraces() -> int:
    """Process-lifetime post-warmup retrace count (monotonic; includes
    wrappers that have since been garbage-collected)."""
    return _retraces_total
