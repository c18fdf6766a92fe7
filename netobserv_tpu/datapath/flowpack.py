"""ctypes binding for the native flowpack library, with numpy fallback.

The native path packs raw flow-event buffers into columnar arrays and merges
per-CPU partials without Python-level per-record loops. When the shared
library isn't built, a vectorized numpy implementation provides identical
results (tests assert equivalence).
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
from typing import Optional

import numpy as np

from netobserv_tpu.model import accumulate, binfmt
from netobserv_tpu.model.columnar import (
    KEY_WORDS, FlowBatch, overlay_features, pack_key_words,
)

log = logging.getLogger("netobserv_tpu.datapath.flowpack")

_NATIVE_DIR = os.path.join(os.path.dirname(__file__), "native")
_LIB_PATHS = [
    os.path.join(_NATIVE_DIR, "build", "libflowpack.so"),
    os.path.join(_NATIVE_DIR, "libflowpack.so"),
]


class _Columns(ctypes.Structure):
    _fields_ = [
        ("keys", ctypes.c_void_p), ("bytes", ctypes.c_void_p),
        ("packets", ctypes.c_void_p), ("tcp_flags", ctypes.c_void_p),
        ("eth_protocol", ctypes.c_void_p), ("direction", ctypes.c_void_p),
        ("if_index", ctypes.c_void_p), ("dscp", ctypes.c_void_p),
        ("sampling", ctypes.c_void_p), ("first_seen_ns", ctypes.c_void_p),
        ("last_seen_ns", ctypes.c_void_p),
    ]


_lib: Optional[ctypes.CDLL] = None


_ABI_VERSION = 10

#: count of library loads rejected for ABI/symbol mismatch (stale `make
#: native` build) — the agent degrades to the numpy/python twin chain
#: instead of dying at import; MapTracer syncs this into the registry's
#: flowpack_abi_fallback_total once per process.
abi_fallbacks = 0

#: dense TPU-feed row width (words); layout documented in flowpack.cc
DENSE_WORDS = 20
#: compact (v4) TPU-feed row width; layout documented in flowpack.cc
COMPACT_WORDS = 10
#: resident feed constants; layout documented in flowpack.cc fp_pack_resident
RESIDENT_HDR = 4
HOT_WORDS = 3
NK_WORDS = 11
#: hot-row rtt code ceiling (µs); larger samples spill full-width
RTT_MAX_US = 0xFF << 14
#: bytes 8..11 of a v4-in-v6 mapped address as a LE u32
_V4_PREFIX_WORD2 = 0xFFFF0000


def compact_buf_len(batch_size: int, spill_cap: int) -> int:
    """Flat word count of a compact feed buffer: compact lane + spill lane."""
    return batch_size * COMPACT_WORDS + spill_cap * DENSE_WORDS


class ResidentCaps:
    """Static side-lane capacities of the resident feed (fixed shapes keep
    the jitted unpack retrace-free; overflows fall back to the dense feed)."""

    __slots__ = ("dns", "drop", "nk", "spill")

    def __init__(self, dns: int, drop: int, nk: int, spill: int):
        self.dns, self.drop, self.nk, self.spill = dns, drop, nk, spill

    def __iter__(self):
        return iter((self.dns, self.drop, self.nk, self.spill))

    def __eq__(self, other):
        return tuple(self) == tuple(other)

    def __repr__(self):
        return (f"ResidentCaps(dns={self.dns}, drop={self.drop}, "
                f"nk={self.nk}, spill={self.spill})")


def default_resident_caps(batch_size: int) -> ResidentCaps:
    """The NARROW lane family — what every ladder entry ships with unless
    the ring has seen a key flood (byte budget in docs/tpu_sketch.md):
    DNS-latency and drop rows are minorities of live traffic; new keys are
    a trickle on a warm flow table under STATIONARY traffic (Zipf: one row
    in a hundred rides a second chunk); the spill lane only carries rows
    the hot row cannot represent exactly. A region stops packing at `nk`
    new keys + `spill` rows, so under a spoofed-source flood, a drifting hot
    set or a dictionary epoch roll (miss rate near 0.3) it takes 300 of its
    1,024 rows and every record is offered three times: that traffic
    packs through `wide_resident_caps` instead (the ring chooses per
    chunk, `sketch.staging.ShardedResidentStagingRing`)."""
    return ResidentCaps(dns=max(batch_size // 16, 64),
                        drop=max(batch_size // 16, 64),
                        nk=max(batch_size // 32, 64),
                        spill=max(batch_size // 64, 32))


def wide_resident_caps(batch_size: int) -> ResidentCaps:
    """The WIDE lane family: the narrow caps with a new-key lane three
    eighths of the region's hot rows, so a region at a flood's miss rate
    (0.3, up to 0.4) takes all its rows in one offer. Only `nk` differs: a
    new-key row costs its 44 bytes of transfer and its share of the one
    combined table scatter, whereas a spill row is a ROW OF THE FOLD (every
    kernel walks `batch + spill` rows a region), so the spill lane stays as
    it is. Settled on the chip (PERF.md section 6, PR 35): a quarter leaves
    a tenth of a flood chunk's rows to sub-batch narrow chunks, for the
    same rate end to end and more of the export thread's time."""
    narrow = default_resident_caps(batch_size)
    return ResidentCaps(dns=narrow.dns, drop=narrow.drop,
                        nk=max(batch_size * 3 // 8, narrow.nk),
                        spill=narrow.spill)


def resident_buf_len(batch_size: int, caps: ResidentCaps) -> int:
    """Flat word count of a resident feed buffer (header + all lanes)."""
    return (RESIDENT_HDR + batch_size * HOT_WORDS + caps.dns + caps.drop * 2
            + caps.nk * NK_WORDS + caps.spill * DENSE_WORDS)


def zero_resident_region(out: np.ndarray, batch_size: int,
                         caps: ResidentCaps) -> None:
    """Mask a resident region as EMPTY by zeroing only the words the device
    unpack (`sketch.state.resident_lane_arrays`) reads as validity gates:
    hot-row word 0 (valid bit + slot + rtt code), the sparse dns/drop lanes
    (their entries scatter by embedded row index), new-key word 0 (defined
    bit) and spill word 14 (valid). Every other word of an invalid row is
    masked on device, so stale content there is unreadable — this writes
    ~1/3 of a full `region[:] = 0` memset, which is what the exhausted-shard
    continuation path used to pay per chunk."""
    hot_off = RESIDENT_HDR
    dns_off = hot_off + batch_size * HOT_WORDS
    nk_off = dns_off + caps.dns + caps.drop * 2
    spill_off = nk_off + caps.nk * NK_WORDS
    out[:RESIDENT_HDR] = 0
    out[hot_off:dns_off:HOT_WORDS] = 0   # hot valid|rtt|slot words
    out[dns_off:nk_off] = 0              # dns + drop lanes (row-idx entries)
    out[nk_off:spill_off:NK_WORDS] = 0   # new-key defined bits
    out[spill_off + 14::DENSE_WORDS] = 0  # spill valid words


class KeyDict:
    """Host key->slot dictionary backing the resident feed — native
    (flowpack.cc fp_dict) when the library is built, pure-python twin
    otherwise (tests pin their equivalence). Slots are assigned sequentially
    in first-seen order; reset() empties the dictionary (the device key
    table needs no matching reset: every live slot is redefined through the
    new-key lane before a hot row references it)."""

    def __init__(self, slot_cap: int = 1 << 18,
                 use_native: Optional[bool] = None):
        if slot_cap <= 0 or slot_cap > (1 << 20):
            raise ValueError("slot_cap must be in 1..2^20 (20-bit slot ids)")
        self.slot_cap = slot_cap
        if use_native is None:
            use_native = native_available()
        self.native = bool(use_native and native_available())
        if self.native:
            _lib.fp_dict_new.restype = ctypes.c_void_p
            self._handle = _lib.fp_dict_new(ctypes.c_uint32(slot_cap))
            if not self._handle:
                raise MemoryError("fp_dict_new failed")
            self._py = None
        else:
            self._handle = None
            self._py: Optional[dict] = {}

    def _live_handle(self) -> int:
        if not self._handle:
            raise ValueError("KeyDict is closed")
        return self._handle

    def count(self) -> int:
        if self.native:
            _lib.fp_dict_count.restype = ctypes.c_uint32
            return int(_lib.fp_dict_count(ctypes.c_void_p(
                self._live_handle())))
        return len(self._py)

    def reset(self) -> None:
        if self.native:
            _lib.fp_dict_reset(ctypes.c_void_p(self._live_handle()))
        else:
            self._py.clear()

    def close(self) -> None:
        if self.native and self._handle:
            _lib.fp_dict_free(ctypes.c_void_p(self._handle))
            self._handle = None

    def __del__(self):  # best-effort; close() is the real API
        try:
            self.close()
        except Exception:
            pass


def _find_lib() -> Optional[ctypes.CDLL]:
    global abi_fallbacks
    for path in _LIB_PATHS:
        if os.path.exists(path):
            # a stale .so (wrong ABI, or so old it predates fp_abi_version)
            # must degrade to the python twin chain, never raise at import
            try:
                lib = ctypes.CDLL(path)
                ver = int(lib.fp_abi_version())
            except (OSError, AttributeError) as exc:
                abi_fallbacks += 1
                log.warning("flowpack library unusable at %s (%s) — falling "
                            "back to the python chain; rebuild with "
                            "`make native`", path, exc)
                continue
            if ver == _ABI_VERSION:
                lib.fp_crc32c.restype = ctypes.c_uint32
                return lib
            abi_fallbacks += 1
            log.warning("flowpack ABI mismatch at %s (built %d, need %d) — "
                        "falling back to the python chain; rebuild with "
                        "`make native`", path, ver, _ABI_VERSION)
    return None


def crc32c(data: bytes) -> Optional[int]:
    """Native crc32c, or None when the library isn't built."""
    if not native_available():
        return None
    return int(_lib.fp_crc32c(data, ctypes.c_size_t(len(data))))


def build_native(force: bool = False, out: Optional[str] = None,
                 abi: Optional[int] = None) -> bool:
    """Compile libflowpack.so with g++ (no cmake configure round trip).
    The ABI version is stamped into the .so at compile time
    (-DFP_ABI_VERSION) so the loader's mismatch fallback is a build
    property, not a source edit; `abi`/`out` let tests build a deliberately
    stale library somewhere harmless."""
    want_abi = _ABI_VERSION if abi is None else abi
    out = _LIB_PATHS[0] if out is None else out
    os.makedirs(os.path.dirname(out), exist_ok=True)
    if os.path.exists(out) and not force:
        # a stale build from another ABI must be rebuilt, not kept
        try:
            if ctypes.CDLL(out).fp_abi_version() == want_abi:
                return True
        except (OSError, AttributeError):
            pass
    src = os.path.join(_NATIVE_DIR, "flowpack.cc")
    try:
        subprocess.run(
            ["g++", "-O3", "-fno-exceptions", "-Wall", "-Werror", "-pthread",
             f"-DFP_ABI_VERSION={want_abi}", "-shared", "-fPIC",
             src, "-o", out],
            check=True, capture_output=True, text=True)
        return True
    except (OSError, subprocess.CalledProcessError) as exc:
        log.warning("flowpack native build failed: %s", exc)
        return False


def native_available() -> bool:
    global _lib
    if _lib is None:
        _lib = _find_lib()
    return _lib is not None


def _ptr(a: np.ndarray) -> ctypes.c_void_p:
    return ctypes.c_void_p(a.ctypes.data)


def pack_events(events_raw: bytes | np.ndarray,
                batch_size: Optional[int] = None,
                extra: Optional[np.ndarray] = None,
                dns: Optional[np.ndarray] = None,
                drops: Optional[np.ndarray] = None,
                use_native: Optional[bool] = None) -> FlowBatch:
    """Raw flow-event buffer (+ optional feature arrays) -> columnar FlowBatch."""
    if isinstance(events_raw, np.ndarray):
        events = np.ascontiguousarray(events_raw, dtype=binfmt.FLOW_EVENT_DTYPE)
    else:
        events = binfmt.decode_flow_events(events_raw)
    if use_native is None:
        use_native = native_available()
    if not (use_native and native_available()):
        # the pure-python path IS FlowBatch.from_events — one definition
        return FlowBatch.from_events(events, batch_size=batch_size,
                                     extra=extra, dns=dns, drops=drops)
    n = len(events)
    batch_size = batch_size or max(n, 1)
    if n > batch_size:
        raise ValueError(f"{n} events exceed batch size {batch_size}")
    b = FlowBatch.empty(batch_size)
    if n == 0:
        return b
    cols = _Columns(
        keys=_ptr(b.keys), bytes=_ptr(b.bytes), packets=_ptr(b.packets),
        tcp_flags=_ptr(b.tcp_flags), eth_protocol=_ptr(b.eth_protocol),
        direction=_ptr(b.direction), if_index=_ptr(b.if_index),
        dscp=_ptr(b.dscp), sampling=_ptr(b.sampling),
        first_seen_ns=_ptr(b.first_seen_ns),
        last_seen_ns=_ptr(b.last_seen_ns))
    raw = events.tobytes()
    _lib.fp_pack(raw, ctypes.c_size_t(n), ctypes.byref(cols))
    overlay_features(b, n, extra=extra, dns=dns, drops=drops)
    b.valid[:n] = True
    return b


def _fit_rows(arr, n, dtype):
    """Contiguous, exactly n rows (zero-padded) — the native pack loops index
    row i for every i < n, so a short array must never reach them."""
    if arr is None or not len(arr):
        return None
    a = np.ascontiguousarray(arr[:n], dtype=dtype)
    if len(a) < n:
        a = np.concatenate([a, np.zeros(n - len(a), dtype)])
    return np.ascontiguousarray(a)


def _feature_words(stats, ex, xl, qc, dr) -> np.ndarray:
    """(n, 4) u32 feature words 16..19 of the dense row — the numpy twin of
    flowpack.cc fill_feature_words (w16 = tcp_flags|dscp<<16|markers<<24,
    w17 = drop bytes|packets<<16, w18 = drop cause|state<<16, w19 = 0)."""
    n = len(stats)
    w = np.zeros((n, 4), np.uint32)
    markers = np.zeros(n, np.uint32)
    if qc is not None:
        markers |= ((qc["version"] != 0) | (qc["seen_long_hdr"] != 0)
                    | (qc["seen_short_hdr"] != 0)).astype(np.uint32)
    if xl is not None:
        # complete translation = both endpoints observed (fp_merge_xlat rule)
        both = xl["src_ip"].any(axis=1) & xl["dst_ip"].any(axis=1)
        markers |= both.astype(np.uint32) << 1
    if ex is not None:
        markers |= (ex["ipsec_encrypted"] != 0).astype(np.uint32) << 2
        markers |= (ex["ipsec_ret"] != 0).astype(np.uint32) << 3
    w[:, 0] = (stats["tcp_flags"].astype(np.uint32)
               | (stats["dscp"].astype(np.uint32) << 16)
               | (markers << 24))
    if dr is not None:
        w[:, 1] = (dr["bytes"].astype(np.uint32)
                   | (dr["packets"].astype(np.uint32) << 16))
        # saturate, don't mask: subsystem drop reasons (kernel >= 6.0) carry
        # the subsystem in bits 16+ — masking would alias them onto core
        # reasons; saturation lands them in the histogram overflow bucket
        w[:, 2] = (np.minimum(dr["latest_cause"], np.uint32(0xFFFF))
                   | (dr["latest_state"].astype(np.uint32) << 16))
    return w


def pack_dense(events_raw: bytes | np.ndarray,
               batch_size: Optional[int] = None,
               extra: Optional[np.ndarray] = None,
               dns: Optional[np.ndarray] = None,
               drops: Optional[np.ndarray] = None,
               xlat: Optional[np.ndarray] = None,
               quic: Optional[np.ndarray] = None,
               out: Optional[np.ndarray] = None,
               use_native: Optional[bool] = None) -> np.ndarray:
    """Raw flow-event buffer -> one (batch_size, DENSE_WORDS) u32 array, the
    single-transfer TPU feed (row layout documented in flowpack.cc; unpacked
    on-device by sketch.state.dense_to_arrays). Pass a preallocated `out` to
    skip the per-batch allocation — the tail rows are zeroed either way, so a
    reused buffer never leaks stale rows into the padding."""
    if isinstance(events_raw, np.ndarray):
        events = np.ascontiguousarray(events_raw, dtype=binfmt.FLOW_EVENT_DTYPE)
    else:
        events = binfmt.decode_flow_events(events_raw)
    n = len(events)
    batch_size = batch_size or max(n, 1)
    if n > batch_size:
        raise ValueError(f"{n} events exceed batch size {batch_size}")
    if out is None:
        out = np.empty((batch_size, DENSE_WORDS), dtype=np.uint32)
    elif (out.shape != (batch_size, DENSE_WORDS)
          or out.dtype != np.uint32 or not out.flags.c_contiguous):
        raise ValueError(
            f"out must be C-contiguous (batch_size, {DENSE_WORDS}) uint32")
    ex = _fit_rows(extra, n, binfmt.EXTRA_REC_DTYPE)
    dn = _fit_rows(dns, n, binfmt.DNS_REC_DTYPE)
    dr = _fit_rows(drops, n, binfmt.DROPS_REC_DTYPE)
    xl = _fit_rows(xlat, n, binfmt.XLAT_REC_DTYPE)
    qc = _fit_rows(quic, n, binfmt.QUIC_REC_DTYPE)
    if use_native is None:
        use_native = native_available()
    if use_native and native_available():
        _lib.fp_pack_dense(
            ctypes.c_void_p(events.ctypes.data), ctypes.c_size_t(n),
            ctypes.c_void_p(ex.ctypes.data if ex is not None else None),
            ctypes.c_void_p(dn.ctypes.data if dn is not None else None),
            ctypes.c_void_p(dr.ctypes.data if dr is not None else None),
            ctypes.c_void_p(xl.ctypes.data if xl is not None else None),
            ctypes.c_void_p(qc.ctypes.data if qc is not None else None),
            ctypes.c_void_p(out.ctypes.data), ctypes.c_size_t(batch_size))
        return out
    out[n:] = 0
    if n:
        stats = events["stats"]
        out[:n, :10] = pack_key_words(events["key"])
        out[:n, 10] = stats["bytes"].astype(np.float32).view(np.uint32)
        out[:n, 11] = stats["packets"]
        out[:n, 12] = ex["rtt_ns"] // 1000 if ex is not None else 0
        out[:n, 13] = dn["latency_ns"] // 1000 if dn is not None else 0
        out[:n, 14] = 1
        out[:n, 15] = stats["sampling"]
        out[:n, 16:] = _feature_words(stats, ex, xl, qc, dr)
    return out


_PACK_POOL = None
_PACK_POOL_SIZE = 0
_PACK_POOL_LOCK = __import__("threading").Lock()


def _pack_submit(threads: int, fns):
    """Submit shard jobs under the pool lock: creation, growth (with
    retirement of the old pool's workers) and submission are one atomic
    step, so a concurrent grower can never shut a pool down between another
    caller obtaining it and submitting to it. shutdown(wait=False) lets
    already-submitted futures run to completion."""
    global _PACK_POOL, _PACK_POOL_SIZE
    with _PACK_POOL_LOCK:
        if _PACK_POOL is None or _PACK_POOL_SIZE < threads:
            from concurrent.futures import ThreadPoolExecutor
            if _PACK_POOL is not None:
                _PACK_POOL.shutdown(wait=False)
            _PACK_POOL = ThreadPoolExecutor(max_workers=threads,
                                            thread_name_prefix="flowpack")
            _PACK_POOL_SIZE = threads
        return [_PACK_POOL.submit(fn) for fn in fns]


def pack_dense_sharded(events_raw: bytes | np.ndarray,
                       batch_size: int,
                       threads: int,
                       extra: Optional[np.ndarray] = None,
                       dns: Optional[np.ndarray] = None,
                       drops: Optional[np.ndarray] = None,
                       xlat: Optional[np.ndarray] = None,
                       quic: Optional[np.ndarray] = None,
                       out: Optional[np.ndarray] = None) -> np.ndarray:
    """pack_dense with the rows sharded across `threads` packer threads —
    each thread runs the native single-pass pack on a disjoint row range of
    the SAME output buffer (ctypes releases the GIL, so the passes execute
    in true parallel). Identical output to pack_dense (equivalence-tested);
    the eviction-buffer sharding the host path needs once the transfer link
    stops being the bottleneck (PCIe-attached chips — docs/tpu_sketch.md)."""
    if isinstance(events_raw, np.ndarray):
        events = np.ascontiguousarray(events_raw, dtype=binfmt.FLOW_EVENT_DTYPE)
    else:
        events = binfmt.decode_flow_events(events_raw)
    n = len(events)
    if n > batch_size:
        raise ValueError(f"{n} events exceed batch size {batch_size}")
    if threads <= 1 or n < 2 * threads or not native_available():
        return pack_dense(events, batch_size=batch_size, extra=extra,
                          dns=dns, drops=drops, xlat=xlat, quic=quic, out=out)
    if out is None:
        out = np.empty((batch_size, DENSE_WORDS), dtype=np.uint32)
    feats = {"extra": extra, "dns": dns, "drops": drops, "xlat": xlat,
             "quic": quic}
    bounds = [n * i // threads for i in range(threads + 1)]

    def shard(i):
        lo, hi = bounds[i], bounds[i + 1]
        # the LAST shard also zero-pads the buffer tail (rows n..batch_size)
        bs = (batch_size - lo) if i == threads - 1 else (hi - lo)
        pack_dense(events[lo:hi], batch_size=bs, out=out[lo:lo + bs],
                   **{k: (v[lo:hi] if v is not None and len(v) else None)
                      for k, v in feats.items()})

    for f in _pack_submit(threads, [lambda i=i: shard(i)
                                    for i in range(threads)]):
        f.result()
    return out


def pack_compact(events_raw: bytes | np.ndarray,
                 batch_size: int,
                 spill_cap: int,
                 extra: Optional[np.ndarray] = None,
                 dns: Optional[np.ndarray] = None,
                 drops: Optional[np.ndarray] = None,
                 xlat: Optional[np.ndarray] = None,
                 quic: Optional[np.ndarray] = None,
                 out: Optional[np.ndarray] = None,
                 use_native: Optional[bool] = None) -> Optional[np.ndarray]:
    """Raw flow-event buffer -> ONE flat u32 buffer
    `[batch_size*10 compact v4 rows | spill_cap*20 dense rows]` — the
    low-bytes-per-record TPU feed for v4-dominant traffic (the transfer
    link, not compute, bounds the host path; a v4 key needs 4 words, not
    10). Non-v4 flows — and rows carrying drop data, rare outside drop
    storms — go to the full-width spill lane; returns None when they exceed
    `spill_cap` (caller falls back to pack_dense for that batch). Layout is
    pinned in flowpack.cc fp_pack_compact; device unpack is
    sketch.state.compact_to_arrays."""
    if isinstance(events_raw, np.ndarray):
        events = np.ascontiguousarray(events_raw, dtype=binfmt.FLOW_EVENT_DTYPE)
    else:
        events = binfmt.decode_flow_events(events_raw)
    n = len(events)
    if n > batch_size:
        raise ValueError(f"{n} events exceed batch size {batch_size}")
    total = compact_buf_len(batch_size, spill_cap)
    if out is None:
        out = np.empty(total, dtype=np.uint32)
    elif (out.shape != (total,) or out.dtype != np.uint32
          or not out.flags.c_contiguous):
        raise ValueError(f"out must be C-contiguous ({total},) uint32")

    ex = _fit_rows(extra, n, binfmt.EXTRA_REC_DTYPE)
    dn = _fit_rows(dns, n, binfmt.DNS_REC_DTYPE)
    dr = _fit_rows(drops, n, binfmt.DROPS_REC_DTYPE)
    xl = _fit_rows(xlat, n, binfmt.XLAT_REC_DTYPE)
    qc = _fit_rows(quic, n, binfmt.QUIC_REC_DTYPE)
    if use_native is None:
        use_native = native_available()
    if use_native and native_available():
        _lib.fp_pack_compact.restype = ctypes.c_int
        ns = _lib.fp_pack_compact(
            ctypes.c_void_p(events.ctypes.data), ctypes.c_size_t(n),
            ctypes.c_void_p(ex.ctypes.data if ex is not None else None),
            ctypes.c_void_p(dn.ctypes.data if dn is not None else None),
            ctypes.c_void_p(dr.ctypes.data if dr is not None else None),
            ctypes.c_void_p(xl.ctypes.data if xl is not None else None),
            ctypes.c_void_p(qc.ctypes.data if qc is not None else None),
            ctypes.c_void_p(out.ctypes.data), ctypes.c_size_t(batch_size),
            ctypes.c_size_t(spill_cap))
        return None if ns < 0 else out
    # numpy twin (layout oracle for the native path)
    comp = out[:batch_size * COMPACT_WORDS].reshape(batch_size, COMPACT_WORDS)
    spill = out[batch_size * COMPACT_WORDS:].reshape(spill_cap, DENSE_WORDS)
    comp[:] = 0
    spill[:] = 0
    if not n:
        return out
    kw = pack_key_words(events["key"])
    stats = events["stats"]
    fw = _feature_words(stats, ex, xl, qc, dr)
    has_drops = (fw[:, 1] != 0) if dr is not None else np.zeros(n, np.bool_)
    is4 = ((kw[:, 0] == 0) & (kw[:, 1] == 0)
           & (kw[:, 2] == _V4_PREFIX_WORD2)
           & (kw[:, 4] == 0) & (kw[:, 5] == 0)
           & (kw[:, 6] == _V4_PREFIX_WORD2)
           & ~has_drops)
    n_sp = int((~is4).sum())
    if n_sp > spill_cap:
        return None
    rtt = (ex["rtt_ns"] // 1000).astype(np.uint32) if ex is not None \
        else np.zeros(n, np.uint32)
    dlat = (dn["latency_ns"] // 1000).astype(np.uint32) if dn is not None \
        else np.zeros(n, np.uint32)
    c = comp[:int(is4.sum())]
    c[:, 0] = kw[is4, 3]
    c[:, 1] = kw[is4, 7]
    c[:, 2] = kw[is4, 8]
    c[:, 3] = kw[is4, 9] | np.uint32(0x80000000)
    c[:, 4] = stats["bytes"][is4].astype(np.float32).view(np.uint32)
    c[:, 5] = stats["packets"][is4]
    c[:, 6] = rtt[is4]
    c[:, 7] = dlat[is4]
    c[:, 8] = stats["sampling"][is4]
    c[:, 9] = fw[is4, 0]
    if n_sp:
        s = spill[:n_sp]
        s[:, :10] = kw[~is4]
        s[:, 10] = stats["bytes"][~is4].astype(np.float32).view(np.uint32)
        s[:, 11] = stats["packets"][~is4]
        s[:, 12] = rtt[~is4]
        s[:, 13] = dlat[~is4]
        s[:, 14] = 1
        s[:, 15] = stats["sampling"][~is4]
        s[:, 16:] = fw[~is4]
    return out


def _rtt_code11(rtt_us: int) -> int:
    e = 0
    while (rtt_us >> (2 * e)) > 0xFF:
        e += 1
    return ((rtt_us >> (2 * e)) & 0xFF) | (e << 8)


def _lat_code16(us: int) -> int:
    e = 0
    while (us >> e) > 0xFFF and e < 15:
        e += 1
    return min(us >> e, 0xFFF) | (e << 12)


def pack_resident(events_raw: bytes | np.ndarray,
                  batch_size: int,
                  kdict: KeyDict,
                  caps: ResidentCaps,
                  start: int = 0,
                  extra: Optional[np.ndarray] = None,
                  dns: Optional[np.ndarray] = None,
                  drops: Optional[np.ndarray] = None,
                  xlat: Optional[np.ndarray] = None,
                  quic: Optional[np.ndarray] = None,
                  out: Optional[np.ndarray] = None
                  ) -> tuple[np.ndarray, int]:
    """Raw flow-event buffer -> the resident feed (layout pinned in
    flowpack.cc fp_pack_resident; device unpack is
    sketch.state.resident_lane_arrays). Packs events[start:] until the hot or
    spill lane fills; returns (buffer, rows_consumed) — partial packing
    with continuation (the caller ships the prefix and calls again with the
    next start), so the dictionary and the device key table learn
    monotonically even under cold-start key floods. Whether the native or
    the python path runs follows the dictionary's own nativeness — the two
    sides share per-row state and cannot be mixed."""
    if isinstance(events_raw, np.ndarray):
        events = np.ascontiguousarray(events_raw, dtype=binfmt.FLOW_EVENT_DTYPE)
    else:
        events = binfmt.decode_flow_events(events_raw)
    n = len(events)
    if batch_size > 0xFFFF:
        raise ValueError("resident feed row indices are 16-bit")
    if min(caps.spill, caps.nk) < 1:
        raise ValueError("resident caps must be >= 1 (progress guarantee)")
    if not 0 <= start <= n:
        raise ValueError(f"start {start} out of range 0..{n}")
    total = resident_buf_len(batch_size, caps)
    if out is None:
        out = np.empty(total, dtype=np.uint32)
    elif (out.shape != (total,) or out.dtype != np.uint32
          or not out.flags.c_contiguous):
        raise ValueError(f"out must be C-contiguous ({total},) uint32")
    ex = _fit_rows(extra, n, binfmt.EXTRA_REC_DTYPE)
    dn = _fit_rows(dns, n, binfmt.DNS_REC_DTYPE)
    dr = _fit_rows(drops, n, binfmt.DROPS_REC_DTYPE)
    xl = _fit_rows(xlat, n, binfmt.XLAT_REC_DTYPE)
    qc = _fit_rows(quic, n, binfmt.QUIC_REC_DTYPE)
    if kdict.native:
        _lib.fp_pack_resident.restype = ctypes.c_int64
        consumed = _lib.fp_pack_resident(
            ctypes.c_void_p(events.ctypes.data), ctypes.c_size_t(start),
            ctypes.c_size_t(n),
            ctypes.c_void_p(ex.ctypes.data if ex is not None else None),
            ctypes.c_void_p(dn.ctypes.data if dn is not None else None),
            ctypes.c_void_p(dr.ctypes.data if dr is not None else None),
            ctypes.c_void_p(xl.ctypes.data if xl is not None else None),
            ctypes.c_void_p(qc.ctypes.data if qc is not None else None),
            ctypes.c_void_p(kdict._live_handle()),
            ctypes.c_void_p(out.ctypes.data),
            ctypes.c_size_t(batch_size), ctypes.c_size_t(caps.dns),
            ctypes.c_size_t(caps.drop), ctypes.c_size_t(caps.nk),
            ctypes.c_size_t(caps.spill))
        return out, int(consumed)
    # ---- python twin (the layout oracle; per-row because the dictionary
    # state evolves first-seen-sequentially, exactly like the native side)
    hot_off = RESIDENT_HDR
    dns_off = hot_off + batch_size * HOT_WORDS
    drop_off = dns_off + caps.dns
    nk_off = drop_off + caps.drop * 2
    spill_off = nk_off + caps.nk * NK_WORDS
    out[:] = 0
    def_sampling = int(events["stats"]["sampling"][start]) if start < n else 0
    out[0] = def_sampling
    if start >= n:
        return out, 0
    # derived arrays over the REMAINDER only — a batch split into many
    # continuation chunks must not recompute the full batch per chunk
    sl = slice(start, n)
    kw_rel = pack_key_words(events["key"][sl])
    fw_rel = _feature_words(events["stats"][sl],
                            ex[sl] if ex is not None else None,
                            xl[sl] if xl is not None else None,
                            qc[sl] if qc is not None else None,
                            dr[sl] if dr is not None else None)
    stats = events["stats"]
    # u32 wrap matches the native cast (and the dense path's u32 column)
    rtt_rel = ((ex["rtt_ns"][sl] // 1000).astype(np.uint32)
               if ex is not None else np.zeros(n - start, np.uint32))
    dlat_rel = ((dn["latency_ns"][sl] // 1000).astype(np.uint64)
                if dn is not None else np.zeros(n - start, np.uint64))
    py = kdict._py
    nh = nd = nr = nk = ns = 0
    i = start
    while i < n and nh < batch_size:
        j = i - start
        kb = kw_rel[j].tobytes()
        slot = py.get(kb)
        if slot is None and nk < caps.nk and len(py) < kdict.slot_cap:
            slot = len(py)
            py[kb] = slot
            row = nk_off + nk * NK_WORDS
            out[row] = 0x80000000 | slot
            out[row + 1:row + 11] = kw_rel[j]
            nk += 1
        rtt = int(rtt_rel[j])
        dlat = int(dlat_rel[j])
        has_drops = dr is not None and bool(dr["bytes"][i] or dr["packets"][i])
        pk, fl = int(stats["packets"][i]), int(stats["tcp_flags"][i])
        hot_ok = (slot is not None and pk < 0x800 and fl < 0x800
                  and int(stats["dscp"][i]) < 0x40
                  and int(stats["sampling"][i]) == def_sampling
                  and rtt <= RTT_MAX_US
                  and (not dlat or nd < caps.dns)
                  and (not has_drops or nr < caps.drop))
        if hot_ok:
            row = hot_off + nh * HOT_WORDS
            out[row] = 0x80000000 | (_rtt_code11(rtt) << 20) | slot
            out[row + 1] = np.float32(stats["bytes"][i]).view(np.uint32)
            out[row + 2] = (pk | (fl << 11)
                            | (int(stats["dscp"][i]) << 22)
                            | ((int(fw_rel[j, 0]) >> 24) << 28))
            if dlat:
                out[dns_off + nd] = (nh << 16) | _lat_code16(dlat)
                nd += 1
            if has_drops:
                cause = min(int(dr["latest_cause"][i]), 0xFFFF)
                out[drop_off + nr * 2] = (nh << 16) | cause
                out[drop_off + nr * 2 + 1] = ((int(dr["packets"][i]) << 16)
                                              | int(dr["bytes"][i]))
                nr += 1
            nh += 1
        else:
            if ns >= caps.spill:
                break  # chunk full: caller continues from row i
            row = spill_off + ns * DENSE_WORDS
            out[row:row + 10] = kw_rel[j]
            out[row + 10] = np.float32(stats["bytes"][i]).view(np.uint32)
            out[row + 11] = pk
            out[row + 12] = rtt
            # explicit u32 wrap: the native packer casts (uint32_t)dlat, and
            # np.uint32(x) raises OverflowError for x >= 2^32 (a DNS latency
            # over ~71 minutes in µs) instead of wrapping like the C++ side
            out[row + 13] = np.uint32(dlat & 0xFFFFFFFF)
            out[row + 14] = 1
            out[row + 15] = stats["sampling"][i]
            out[row + 16:row + 20] = fw_rel[j]
            ns += 1
        i += 1
    out[1], out[2], out[3] = nk, ns, nd | (nr << 16)
    return out, i - start


_MERGE_FNS = {
    "stats": ("fp_merge_stats", binfmt.FLOW_STATS_DTYPE,
              accumulate.accumulate_base),
    "extra": ("fp_merge_extra", binfmt.EXTRA_REC_DTYPE,
              accumulate.accumulate_extra),
    "drops": ("fp_merge_drops", binfmt.DROPS_REC_DTYPE,
              accumulate.accumulate_drops),
    "dns": ("fp_merge_dns", binfmt.DNS_REC_DTYPE, accumulate.accumulate_dns),
    "nevents": ("fp_merge_nevents", binfmt.NEVENTS_REC_DTYPE,
                accumulate.accumulate_network_events),
    "xlat": ("fp_merge_xlat", binfmt.XLAT_REC_DTYPE,
             accumulate.accumulate_xlat),
    "quic": ("fp_merge_quic", binfmt.QUIC_REC_DTYPE,
             accumulate.accumulate_quic),
}


def merge_percpu(kind: str, values: np.ndarray,
                 use_native: Optional[bool] = None) -> np.ndarray:
    """Merge per-CPU partial records (shape (n_cpu,) structured) into one.
    Single-key API (the accounter path); drains use merge_percpu_batch."""
    fn_name, dtype, py_fn = _MERGE_FNS[kind]
    values = np.ascontiguousarray(values, dtype=dtype)
    if use_native is None:
        use_native = native_available()
    if use_native and native_available():
        out = np.zeros(1, dtype=dtype)
        # pass the already-contiguous array pointer — materializing a bytes
        # object per call doubled the per-flow cost of the old drain loop
        getattr(_lib, fn_name)(
            _ptr(values), ctypes.c_size_t(len(values)), _ptr(out))
        return out[0]
    return accumulate.merge_percpu(values, py_fn)


#: row floor below which lane-sharding a batch merge costs more than the
#: pool round trip saves (one fp_merge_*_batch call is already ~ns/row)
_MERGE_LANE_MIN_ROWS = 4096


def merge_percpu_batch(kind: str, values: np.ndarray,
                       use_native: Optional[bool] = None,
                       out: Optional[np.ndarray] = None,
                       threads: int = 1) -> np.ndarray:
    """Merge per-CPU partials for a WHOLE drained map: values shaped
    (n_keys, n_cpus) structured -> (n_keys,) merged records. Native path is
    one fp_merge_*_batch call over a single pointer (no per-key ctypes round
    trips); fallback is the columnar numpy twin in model/accumulate.py.
    Both are equivalence-pinned against the per-record accumulate_* loop
    (tests/test_evict_columnar.py).

    `out` writes into a caller buffer (must be (n_keys,) of the record
    dtype). `threads > 1` row-shards ONE map's merge across that many pack
    lanes — each lane is its own fp_merge_*_batch call over a disjoint
    contiguous row range of the same buffers (the native call releases the
    GIL, so lanes merge in true parallel; per-key semantics make row
    sharding trivially equivalent). Engages only for native merges past
    `_MERGE_LANE_MIN_ROWS` rows — the eviction plane's big-map (flows_extra)
    relief when one map dominates the drain."""
    fn_name, dtype, _py_fn = _MERGE_FNS[kind]
    values = np.ascontiguousarray(values, dtype=dtype)
    if values.ndim != 2:
        raise ValueError(f"values must be (n_keys, n_cpus), got "
                         f"{values.shape}")
    n_keys, n_cpus = values.shape
    if out is not None and (out.dtype != dtype or len(out) != n_keys
                            or not out.flags.c_contiguous):
        raise ValueError("out must be a contiguous (n_keys,) array of the "
                         "record dtype")
    if use_native is None:
        use_native = native_available()
    if use_native and native_available() and n_keys:
        if out is None:
            out = np.zeros(n_keys, dtype=dtype)
        fn = getattr(_lib, fn_name + "_batch")

        def run(lo: int, hi: int) -> None:
            fn(_ptr(values[lo:hi]), ctypes.c_size_t(hi - lo),
               ctypes.c_size_t(n_cpus), _ptr(out[lo:hi]))

        if threads > 1 and n_keys >= max(_MERGE_LANE_MIN_ROWS, 2 * threads):
            bounds = [n_keys * i // threads for i in range(threads + 1)]
            for f in _pack_submit(threads,
                                  [lambda i=i: run(bounds[i], bounds[i + 1])
                                   for i in range(threads)]):
                f.result()
        else:
            run(0, n_keys)
        return out
    merged = accumulate.COLUMNAR_MERGES[kind](values)
    if out is not None:
        out[:] = merged
        return out
    return merged


def events_from_keys_stats(keys: np.ndarray, stats: np.ndarray,
                           n_total: Optional[int] = None,
                           use_native: Optional[bool] = None) -> np.ndarray:
    """Compose FLOW_EVENT rows from the two columns a batched drain yields —
    the columnar eviction plane's single copy boundary, done as ONE native
    interleave pass (fp_events_from_keys_stats) instead of two strided numpy
    field assignments. `keys` is (n, 40) u8 or (n,) FLOW_KEY; `stats` is
    (n,) FLOW_STATS. The numpy twin is binfmt.events_from_keys_stats
    (equivalence pinned in tests/test_evict_parallel.py); semantics are
    identical, including the zeroed `n_total` tail the loader appends
    ringbuf-orphan events into."""
    if keys.dtype != np.uint8:
        keys = np.ascontiguousarray(keys).view(np.uint8).reshape(
            -1, binfmt.FLOW_KEY_DTYPE.itemsize)
    n = len(keys)
    if len(stats) != n:
        raise ValueError(f"keys/stats length mismatch: {n} vs {len(stats)}")
    if n_total is not None and n_total < n:
        # the numpy twin raises on broadcast; the native memcpy loop would
        # silently write past the short buffer instead — refuse first
        raise ValueError(f"n_total {n_total} < {n} rows")
    if use_native is None:
        use_native = native_available()
    if not (use_native and native_available()):
        return binfmt.events_from_keys_stats(
            keys.view(binfmt.FLOW_KEY_DTYPE).reshape(-1) if n
            else np.empty(0, binfmt.FLOW_KEY_DTYPE),
            stats, n_total=n_total)
    keys = np.ascontiguousarray(keys)
    stats = np.ascontiguousarray(stats, dtype=binfmt.FLOW_STATS_DTYPE)
    out = np.zeros(n_total if n_total is not None else n,
                   dtype=binfmt.FLOW_EVENT_DTYPE)
    if n:
        _lib.fp_events_from_keys_stats(
            _ptr(keys), _ptr(stats), ctypes.c_size_t(n), _ptr(out))
    return out


# ---------------------------------------------------------------------------
# Fused one-call eviction pipeline (flowpack.cc fp_drain_to_resident).
# SCHEDULING ONLY: the native call chains the very same batched drain,
# fp_merge_*_batch, _join_keys-twin join and fp_pack_resident the Python
# chain orchestrates — never a fifth merge form, never a fourth resident
# layout. The Python chain stays the equivalence oracle
# (tests/test_native_pipeline.py pins the fused output bit-exact).
# ---------------------------------------------------------------------------

#: map kind ids of the fused pipeline (flowpack.cc FPK_*); map 0 of a pipe
#: must be "stats" (the aggregation map, rows used verbatim)
PIPE_KINDS = {"stats": 0, "extra": 1, "dns": 2, "drops": 3,
              "nevents": 4, "xlat": 5, "quic": 6}

#: record dtype per pipe kind (the aligned-feature view dtypes)
PIPE_DTYPES = {
    "stats": binfmt.FLOW_STATS_DTYPE, "extra": binfmt.EXTRA_REC_DTYPE,
    "dns": binfmt.DNS_REC_DTYPE, "drops": binfmt.DROPS_REC_DTYPE,
    "nevents": binfmt.NEVENTS_REC_DTYPE, "xlat": binfmt.XLAT_REC_DTYPE,
    "quic": binfmt.QUIC_REC_DTYPE,
}

_PIPE_MAX_MAPS = 8
_PIPE_MAX_LADDER = 8


class _PipeMapCfg(ctypes.Structure):
    _fields_ = [("fd", ctypes.c_int32), ("kind", ctypes.c_uint32),
                ("value_size", ctypes.c_uint32), ("n_cpus", ctypes.c_uint32),
                ("max_entries", ctypes.c_uint32)]


class _PipeLadder(ctypes.Structure):
    _fields_ = [("k", ctypes.c_uint32), ("nr", ctypes.c_uint32),
                ("dicts", ctypes.POINTER(ctypes.c_uint64))]


class _PipePackCfg(ctypes.Structure):
    _fields_ = [("n_ladder", ctypes.c_uint32), ("batch_size", ctypes.c_uint32),
                ("batch_per_region", ctypes.c_uint32),
                ("slot_cap", ctypes.c_uint32), ("dns_cap", ctypes.c_uint32),
                ("drop_cap", ctypes.c_uint32), ("nk_cap", ctypes.c_uint32),
                ("spill_cap", ctypes.c_uint32),
                ("ladder", _PipeLadder * _PIPE_MAX_LADDER)]


class _PipeChunk(ctypes.Structure):
    _fields_ = [("row_start", ctypes.c_uint64), ("rows", ctypes.c_uint64),
                ("arena_off", ctypes.c_uint64), ("k", ctypes.c_uint32),
                ("n_segs", ctypes.c_uint32), ("spills", ctypes.c_uint32),
                ("resets", ctypes.c_uint32)]


class _PipeResult(ctypes.Structure):
    _fields_ = [("n_events", ctypes.c_uint64), ("n_agg", ctypes.c_uint64),
                ("n_orphans", ctypes.c_uint64),
                ("packed_rows", ctypes.c_uint64),
                ("drain_ns", ctypes.c_uint64), ("merge_ns", ctypes.c_uint64),
                ("join_ns", ctypes.c_uint64), ("pack_ns", ctypes.c_uint64),
                ("syscalls", ctypes.c_uint64),
                ("lex_fallback", ctypes.c_uint64),
                ("batch_err_mask", ctypes.c_uint64),
                ("n_chunks", ctypes.c_uint64),
                ("arena_words", ctypes.c_uint64),
                ("spill_rows", ctypes.c_uint64),
                ("dict_resets", ctypes.c_uint64), ("segs", ctypes.c_uint64),
                ("events", ctypes.c_void_p), ("arena", ctypes.c_void_p),
                ("chunks", ctypes.c_void_p),
                ("aligned", ctypes.c_void_p * _PIPE_MAX_MAPS),
                ("map_rows", ctypes.c_uint64 * _PIPE_MAX_MAPS)]


def _pipe_view(addr: Optional[int], nbytes: int, dtype) -> Optional[np.ndarray]:
    if not addr or nbytes == 0:
        return None
    buf = (ctypes.c_uint8 * nbytes).from_address(addr)
    return np.frombuffer(buf, dtype=dtype)


class PipeChunk:
    """One pack chunk of a fused drain — mirrors one outer iteration of
    ShardedResidentStagingRing._fold_chunk (k-ladder selection, continuation
    segments). The caller ships arena[arena_off : arena_off + n_segs *
    (nr(k) * region_words)] as n_segs ring-slot images."""

    __slots__ = ("row_start", "rows", "arena_off", "k", "n_segs", "spills",
                 "resets")

    def __init__(self, c: "_PipeChunk"):
        self.row_start = int(c.row_start)
        self.rows = int(c.rows)
        self.arena_off = int(c.arena_off)
        self.k = int(c.k)
        self.n_segs = int(c.n_segs)
        self.spills = int(c.spills)
        self.resets = int(c.resets)


class PipeResult:
    """Outputs of one fused drain. `events`/`aligned[kind]` are zero-copy
    VIEWS of pipe-handle scratch — valid only until the pipe's next drain
    (the drain_batched_arrays cached-buffer rule; the one copy happens at
    the EvictedFlows boundary). The packed `arena` is owned by THIS object:
    call free() (or let __del__ catch it) after the regions are shipped."""

    __slots__ = ("n_events", "n_agg", "n_orphans", "packed_rows", "drain_s",
                 "merge_s", "join_s", "pack_s", "syscalls", "lex_fallback",
                 "batch_err_mask", "map_rows", "events", "aligned", "arena",
                 "chunks", "spill_rows", "dict_resets", "segs", "_arena_ptr")

    def __init__(self, res: _PipeResult, kinds: list):
        self.n_events = int(res.n_events)
        self.n_agg = int(res.n_agg)
        self.n_orphans = int(res.n_orphans)
        self.packed_rows = int(res.packed_rows)
        self.drain_s = res.drain_ns * 1e-9
        self.merge_s = res.merge_ns * 1e-9
        self.join_s = res.join_ns * 1e-9
        self.pack_s = res.pack_ns * 1e-9
        self.syscalls = int(res.syscalls)
        self.lex_fallback = int(res.lex_fallback)
        self.batch_err_mask = int(res.batch_err_mask)
        self.spill_rows = int(res.spill_rows)
        self.dict_resets = int(res.dict_resets)
        self.segs = int(res.segs)
        self.map_rows = [int(res.map_rows[i]) for i in range(len(kinds))]
        self.events = _pipe_view(
            res.events, self.n_events * binfmt.FLOW_EVENT_DTYPE.itemsize,
            binfmt.FLOW_EVENT_DTYPE)
        self.aligned = {}
        for i, kind in enumerate(kinds):
            if i == 0:
                continue  # the stats map composes into events, not aligned
            dt = PIPE_DTYPES[kind]
            self.aligned[kind] = _pipe_view(
                res.aligned[i], self.n_events * dt.itemsize, dt)
        self._arena_ptr = res.arena or 0
        self.arena = _pipe_view(self._arena_ptr,
                                int(res.arena_words) * 4, np.uint32)
        self.chunks = []
        if res.n_chunks and res.chunks:
            carr = (_PipeChunk * int(res.n_chunks)).from_address(res.chunks)
            self.chunks = [PipeChunk(c) for c in carr]

    def free(self) -> None:
        if self._arena_ptr:
            _lib.fp_buf_free(ctypes.c_void_p(self._arena_ptr))
            self._arena_ptr = 0
            self.arena = None

    def __del__(self):  # best-effort; free() is the real API
        try:
            self.free()
        except Exception:
            pass


class NativePipe:
    """Handle on one fp_drain_to_resident pipeline over a fixed set of maps.
    `maps` is [(fd, kind, value_size, n_cpus, max_entries)] with map 0 the
    aggregation map (kind "stats", n_cpus 1); fd < 0 makes a map injected
    (set_drained) for tests. `lanes` fans the per-map drain+merge
    over that many native worker threads (GIL released for the whole call)."""

    def __init__(self, maps: list, lanes: int = 1):
        if not native_available():
            raise RuntimeError("native flowpack library unavailable")
        if not maps or len(maps) > _PIPE_MAX_MAPS:
            raise ValueError(f"1..{_PIPE_MAX_MAPS} maps required")
        self.kinds = [m[1] for m in maps]
        cfgs = (_PipeMapCfg * len(maps))()
        for i, (fd, kind, value_size, n_cpus, max_entries) in enumerate(maps):
            cfgs[i] = _PipeMapCfg(fd=fd, kind=PIPE_KINDS[kind],
                                  value_size=value_size, n_cpus=n_cpus,
                                  max_entries=max_entries)
        _lib.fp_pipe_new.restype = ctypes.c_void_p
        _lib.fp_drain_to_resident.restype = ctypes.c_int64
        _lib.fp_pipe_set_drained.restype = ctypes.c_int
        self._handle = _lib.fp_pipe_new(cfgs, ctypes.c_uint32(len(maps)),
                                        ctypes.c_uint32(max(lanes, 1)))
        if not self._handle:
            raise ValueError("fp_pipe_new rejected the map configuration")

    def set_drained(self, idx: int, keys: np.ndarray,
                    vals: np.ndarray) -> None:
        """Inject one drain's (keys, vals) for an fd<0 map: keys (n, 40) u8,
        vals the kernel layout (n rows x n_cpus images, contiguous)."""
        keys = np.ascontiguousarray(keys)
        vals = np.ascontiguousarray(vals)
        n = len(keys)
        rc = _lib.fp_pipe_set_drained(
            ctypes.c_void_p(self._handle), ctypes.c_uint32(idx),
            _ptr(keys), _ptr(vals), ctypes.c_uint32(n))
        if rc != 0:
            raise ValueError(f"fp_pipe_set_drained({idx}) failed")

    def drain(self, pack: Optional[dict] = None) -> PipeResult:
        """Run the fused chain. `pack` (None = drain/merge/join only) is
        {"batch_size", "batch_per_region", "slot_cap", "caps": ResidentCaps,
        "ladder": [(k, [dict handles])]} with ladder ks ascending, k=1
        first, handles from KeyDict._live_handle() in the ring's per-region
        dictionary order."""
        res = _PipeResult()
        keepalive = []
        pk_ref = None
        if pack is not None:
            caps = pack["caps"]
            ladder = pack["ladder"]
            if len(ladder) > _PIPE_MAX_LADDER:
                raise ValueError("ladder too deep")
            pk = _PipePackCfg(
                n_ladder=len(ladder), batch_size=pack["batch_size"],
                batch_per_region=pack["batch_per_region"],
                slot_cap=pack["slot_cap"], dns_cap=caps.dns,
                drop_cap=caps.drop, nk_cap=caps.nk, spill_cap=caps.spill)
            for li, (k, handles) in enumerate(ladder):
                arr = (ctypes.c_uint64 * len(handles))(*handles)
                keepalive.append(arr)
                pk.ladder[li] = _PipeLadder(
                    k=k, nr=len(handles),
                    dicts=ctypes.cast(arr, ctypes.POINTER(ctypes.c_uint64)))
            pk_ref = ctypes.byref(pk)
            keepalive.append(pk)
        rc = int(_lib.fp_drain_to_resident(
            ctypes.c_void_p(self._handle), pk_ref, ctypes.byref(res)))
        del keepalive
        if rc < 0:
            raise RuntimeError(f"fp_drain_to_resident failed (rc={rc})")
        return PipeResult(res, self.kinds)

    def close(self) -> None:
        if self._handle:
            _lib.fp_pipe_free(ctypes.c_void_p(self._handle))
            self._handle = None

    def __del__(self):  # best-effort; close() is the real API
        try:
            self.close()
        except Exception:
            pass
