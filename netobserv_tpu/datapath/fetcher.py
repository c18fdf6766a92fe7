"""Fetcher protocol + in-memory fake.

`FlowFetcher` is the seam between the kernel datapath and the userspace
pipeline (reference: `pkg/tracer/tracer.go:52-76` FlowFetcher; fake analog:
`pkg/test/tracer_fake.go`). The real libbpf-backed implementation lives in
`netobserv_tpu.datapath.loader`; everything above this seam is kernel-free and
fully testable.
"""

from __future__ import annotations

import queue
import threading
from typing import Optional, Protocol

import numpy as np

from netobserv_tpu.model import binfmt
from netobserv_tpu.model.flow import GlobalCounter


class EvictedFlows:
    """One map eviction: base flow events + per-feature parallel arrays.

    `events` is a FLOW_EVENT structured array (per-CPU partials already
    merged); feature arrays are aligned with `events` rows (or None when the
    feature is disabled).

    Ownership contract: every array is OWNED by this object — the columnar
    drain decode reads zero-copy views of the kernel batch buffers, and
    construction here is the single copy boundary (a later drain must never
    mutate an earlier EvictedFlows; pinned by the aliasing regression in
    tests/test_bpfman.py). `decode_stats` carries what the columnar
    eviction plane says of the drain that built it — `seconds` (the whole
    drain's decode; map_tracer feeds it to `eviction_decode_seconds`),
    `fallback_rows`, `drain_lanes`, and on the fused native path
    `native_path` and the `native` per-stage split. The seconds of each
    stage inside it are the stage clocks (`utils/tracing.stage_clocks`)."""

    def __init__(self, events: np.ndarray,
                 dns: Optional[np.ndarray] = None,
                 drops: Optional[np.ndarray] = None,
                 extra: Optional[np.ndarray] = None,
                 xlat: Optional[np.ndarray] = None,
                 nevents: Optional[np.ndarray] = None,
                 quic: Optional[np.ndarray] = None):
        self.events = events
        self.dns = dns
        self.drops = drops
        self.extra = extra
        self.xlat = xlat
        self.nevents = nevents
        self.quic = quic
        self.decode_stats: Optional[dict] = None
        #: process-wide sequence number MapTracer stamps at the drain (0 =
        #: not drained by a MapTracer): the id its stages carry in a
        #: profiler capture, from `evict` to the fold chunks
        self.eviction = 0
        #: fused-pipeline extra (loader.PackedEviction): resident regions
        #: pre-packed at drain time. The raw arrays above are ALWAYS the
        #: full eviction regardless — a consumer that can't ship the packed
        #: arena (epoch moved, no surface) frees it and folds these.
        self.packed = None

    def __len__(self) -> int:
        return len(self.events)


class FlowFetcher(Protocol):
    """What the pipeline needs from the datapath."""

    def lookup_and_delete(self) -> EvictedFlows:
        """Drain the kernel aggregation map (one eviction)."""
        ...

    def read_ringbuf(self, timeout_s: float) -> Optional[bytes]:
        """Block up to timeout_s for one raw flow event (map-full fallback).
        Returns None on timeout."""
        ...

    def read_ssl(self, timeout_s: float) -> Optional[bytes]:
        """Block up to timeout_s for one raw SSL plaintext event (OpenSSL
        uprobe ring buffer). Returns None on timeout."""
        ...

    def read_global_counters(self) -> dict[GlobalCounter, int]:
        """Scrape-and-reset the datapath's global counters."""
        ...

    def purge_stale(self, older_than_s: float) -> int:
        """Drop auxiliary-map entries (e.g. unanswered DNS correlations) older
        than the deadline; returns how many were purged. (Reference analog:
        DeleteMapsStaleEntries, `pkg/tracer/tracer.go:1188-1216`.)"""
        ...

    def attach(self, if_index: int, if_name: str, direction: str,
               netns: str = "") -> None: ...

    def detach(self, if_index: int, if_name: str,
               netns: str = "") -> None: ...

    def close(self) -> None: ...


class FakeFetcher:
    """Injectable fetcher for tests and pcap/synthetic replay.

    Push map dumps with `inject_eviction`, ringbuf events with
    `inject_ringbuf` (reference analog: `pkg/test/tracer_fake.go:17-84`)."""

    def __init__(self):
        self._evictions: queue.Queue[EvictedFlows] = queue.Queue()
        self._ringbuf: queue.Queue[bytes] = queue.Queue()
        self._ssl: queue.Queue[bytes] = queue.Queue()
        self._counters: dict[GlobalCounter, int] = {}
        self._lock = threading.Lock()
        self.attached: dict[int, str] = {}
        self.closed = False

    # --- injection side ---
    def inject_eviction(self, evicted: EvictedFlows) -> None:
        self._evictions.put(evicted)

    def inject_events(self, events: np.ndarray, **features) -> None:
        self.inject_eviction(EvictedFlows(events, **features))

    def inject_ringbuf(self, event: np.ndarray | bytes) -> None:
        if isinstance(event, np.ndarray):
            event = np.ascontiguousarray(
                event, dtype=binfmt.FLOW_EVENT_DTYPE).tobytes()
        self._ringbuf.put(event)

    def inject_ssl(self, event: bytes) -> None:
        self._ssl.put(event)

    def bump_counter(self, key: GlobalCounter, n: int = 1) -> None:
        with self._lock:
            self._counters[key] = self._counters.get(key, 0) + n

    # --- FlowFetcher side ---
    def lookup_and_delete(self) -> EvictedFlows:
        try:
            return self._evictions.get_nowait()
        except queue.Empty:
            return EvictedFlows(np.zeros(0, dtype=binfmt.FLOW_EVENT_DTYPE))

    def read_ringbuf(self, timeout_s: float) -> Optional[bytes]:
        try:
            return self._ringbuf.get(timeout=timeout_s)
        except queue.Empty:
            return None

    def read_ssl(self, timeout_s: float) -> Optional[bytes]:
        try:
            return self._ssl.get(timeout=timeout_s)
        except queue.Empty:
            return None

    def read_global_counters(self) -> dict[GlobalCounter, int]:
        with self._lock:
            out, self._counters = self._counters, {}
        return out

    def purge_stale(self, older_than_s: float) -> int:
        self.purged_calls = getattr(self, "purged_calls", 0) + 1
        return 0

    def attach(self, if_index: int, if_name: str, direction: str,
               netns: str = "") -> None:
        # keyed like the real fetchers: ifindex values repeat across netns
        self.attached[(netns, if_index) if netns else if_index] = if_name

    def detach(self, if_index: int, if_name: str,
               netns: str = "") -> None:
        self.attached.pop((netns, if_index) if netns else if_index, None)

    def close(self) -> None:
        self.closed = True
