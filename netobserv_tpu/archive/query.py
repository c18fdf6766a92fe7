"""Device-merged time-range queries over the archive, and the compactor
that shares their executables.

The range plane answers ``/query/range?from=&to=`` (and the
``topk | frequency | cardinality | victims`` views) by selecting the
covering segments and merging their K table snapshots in ONE fixed-shape
device dispatch: a warmed LADDER of merge sizes (powers of two up to
`ladder_max` — the `SKETCH_SUPERBATCH` pattern), one pre-built jit per
ladder k, every entry made by `retrace.jit`. K segments pad UP to the next
ladder size with ZERO tables (the exact merge identity: CM/hist/rates add
zeros, HLL maxes zeros, an all-invalid slot table contributes no
candidates), so shapes never depend on the request — zero post-warmup
retraces. Ranges wider than `ladder_max` CHAIN: each dispatch's merged
tables re-enter the next dispatch as one more input (the merged snapshot
has exactly the TABLE_SPEC shapes, by construction).

Merge semantics are the equivalence-pinned `federation.statemerge.
merge_tables` — CM planes/histograms/rates add, HLL max, slot tables
through `ops/topk.merge_slot_tables` — so a range answer over raw
segments is bit-exact against the union roll (tests/test_archive.py pins
it; the slot table against the table-merge replay oracle, per the chaos
suite rule). The rendered report flows through the ONE query core
(`query/core.py`): the CM error bars on a merged plane are computed from
the MERGED row sum, which IS the widened bound — the Count-Min
overestimate stays one-sided under merging (`(e/w) * N_total` over the
merged mass, confidence unchanged), the additive-error-counter result the
warehouse leans on (PAPERS.md).

Deviation from the live query plane's snapshot-only rule, by design: a
range request DOES dispatch a device op (the merge). It still never takes
the exporter lock and never touches live donated state — every input
comes off disk — and dispatches serialize under the engine's own lock
(two threads first-tracing one ladder entry would double-compile, the
spurious-retrace hazard `_roll_mutex` documents).

The COMPACTOR is the same machinery pointed at retention: a pending group
merges through the same ladder executables and the merged snapshot is
re-encoded one level up — compaction and range answers can never disagree
about what a merge means.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Optional

import numpy as np

from netobserv_tpu.archive import segment as aseg
from netobserv_tpu.archive.store import ArchiveStore, SegInfo
from netobserv_tpu.federation import delta as fdelta
from netobserv_tpu.utils import retrace

log = logging.getLogger("netobserv_tpu.archive.query")

#: range views and their query-core payload builders ("" = summary)
VIEWS = ("", "summary", "topk", "frequency", "cardinality", "victims")


class ArchiveQueryEngine:
    """Warmed merge ladder + range rendering over one ArchiveStore."""

    def __init__(self, store: ArchiveStore, sketch_cfg, metrics=None,
                 ladder_max: int = 16,
                 report_kwargs: Optional[dict] = None):
        from netobserv_tpu.sketch import state as sk
        if ladder_max < 1 or ladder_max & (ladder_max - 1):
            raise ValueError("ladder_max must be a power of two >= 1")
        self._store = store
        self._sk = sk
        # the ladder merges decode to the canonical WIDE layout; a tiered
        # exporter archives wide snapshots (state_tables decodes), so the
        # engine always runs the wide config
        self._cfg = sketch_cfg._replace(tiered=None) \
            if getattr(sketch_cfg, "tiered", None) is not None \
            else sketch_cfg
        self._metrics = metrics
        self._report_kwargs = report_kwargs or {}
        self.ladder = tuple(1 << i
                            for i in range(ladder_max.bit_length()))
        #: one serialization point for ladder compiles, dispatches AND
        #: store mutations: queries read segment files the compactor may
        #: replace, and two threads first-tracing one ladder entry would
        #: double-compile (a spurious post-warmup retrace alarm)
        self.lock = threading.RLock()
        self._merge_fns: dict[int, object] = {}
        self._zero_tables: Optional[dict] = None
        self._expected_shapes: Optional[dict] = None
        self.dims = {"cm_depth": self._cfg.cm_depth,
                     "cm_width": self._cfg.cm_width,
                     "hll_precision": self._cfg.hll_precision,
                     "topk": self._cfg.topk,
                     "ewma_buckets": self._cfg.ewma_buckets}

    # --- ladder ----------------------------------------------------------
    def _zero_template(self) -> dict:
        """Host zero tables in spec dtypes — the pad identity."""
        if self._zero_tables is None:
            tables = self._sk.state_tables(self._sk.init_state(self._cfg))
            self._zero_tables = {
                name: np.zeros(np.asarray(tables[name]).shape, dt)
                for name, dt in fdelta.TABLE_SPEC}
            self._expected_shapes = {n: a.shape for n, a
                                     in self._zero_tables.items()}
        return self._zero_tables

    def _merge_fn(self, k: int):
        """The ladder-k executable: merge k stacked table snapshots into a
        fresh state, return (device WindowReport, merged state_tables).
        Built lazily under the engine lock; the first call per k is the
        watchdog's warmup compile, anything later alarms."""
        fn = self._merge_fns.get(k)
        if fn is not None:
            return fn
        import jax

        from netobserv_tpu.federation import statemerge
        sk, cfg = self._sk, self._cfg
        names = [n for n, _ in fdelta.TABLE_SPEC]

        def merge_k(stacked):
            state = sk.init_state(cfg)
            for i in range(k):  # fixed k: unrolls into one program
                state = statemerge.merge_tables(
                    state, {n: stacked[n][i] for n in names})
            tables = sk.state_tables(state)
            _new, report = sk.roll_window(state, cfg)
            return report, tables

        fn = retrace.jit(merge_k, f"archive_merge_x{k}")
        self._merge_fns[k] = fn
        return fn

    def warm(self) -> None:
        """Compile every ladder entry against zero stacks — the
        production entry (`archive.maybe_archive`) runs this on a
        background thread at construction, so the first real range query
        or compaction hits warm executables instead of stalling the HTTP
        or timer thread on a multi-second compile. The lock is taken PER
        entry: a window publish slips in between compiles instead of
        queueing behind the whole ladder. Idempotent; entries a live
        query raced to first are skipped (their first use was their
        watchdog warmup call)."""
        import jax
        for k in self.ladder:
            with self.lock:
                if k in self._merge_fns:
                    continue
                zero = self._zero_template()
                stacked = {n: np.broadcast_to(
                    z, (k,) + z.shape).copy() for n, z in zero.items()}
                report, _tables = self._merge_fn(k)(stacked)
                jax.block_until_ready(report.window)

    def _ladder_fit(self, n: int) -> int:
        for k in self.ladder:
            if k >= n:
                return k
        return self.ladder[-1]

    def _dispatch(self, table_dicts: list[dict]) -> tuple:
        """Merge up to ladder_max snapshots in one dispatch (padding with
        the zero identity). Returns (device report, device tables)."""
        k = self._ladder_fit(len(table_dicts))
        zero = self._zero_template()
        pads = [zero] * (k - len(table_dicts))
        stacked = {n: np.stack([np.asarray(t[n], dt)
                                for t in table_dicts + pads])
                   for n, dt in fdelta.TABLE_SPEC}
        return self._merge_fn(k)(stacked)

    def merge_tables_host(
            self, table_dicts: list[dict]) -> tuple[object, dict, int]:
        """Merge an arbitrary number of table snapshots, chaining
        dispatches past ladder_max. Returns (device report of the final
        merge, HOST copies of the merged tables, dispatch count). Caller
        holds the engine lock."""
        if not table_dicts:
            raise ValueError("nothing to merge")
        n_merges = 0
        cap = self.ladder[-1]
        pending = list(table_dicts)
        while True:
            chunk, pending = pending[:cap], pending[cap:]
            report, tables = self._dispatch(chunk)
            n_merges += 1
            host = {n: np.asarray(tables[n]) for n, _
                    in fdelta.TABLE_SPEC}
            if not pending:
                return report, host, n_merges
            # the merged snapshot re-enters as one more input (same
            # TABLE_SPEC shapes by construction)
            pending = [host] + pending

    # --- segment plumbing -------------------------------------------------
    def _decode_checked(self, seg: SegInfo) -> aseg.Segment:
        decoded = aseg.decode_segment(self._store.read(seg))
        self._zero_template()  # ensures _expected_shapes
        for name, arr in decoded.tables.items():
            want = self._expected_shapes[name]
            if tuple(arr.shape) != tuple(want):
                raise aseg.ArchiveSegmentError(
                    f"segment {seg.name}: tensor {name!r} shape "
                    f"{tuple(arr.shape)} != this config's {tuple(want)} "
                    "(the archive was written by a different "
                    "SketchConfig)")
        return decoded

    def compact_once(self) -> bool:
        """Merge one pending retention group into a super-window one level
        up (store.replace lands it before the inputs die). Returns True
        when a compaction ran."""
        with self.lock:
            pending = self._store.pending_compaction()
            if pending is None:
                return False
            level, group = pending
            decoded = [self._decode_checked(s) for s in group]
            _report, merged, _n = self.merge_tables_host(
                [d.tables for d in decoded])
            seg_bytes = aseg.encode_segment(
                merged, agent_id=decoded[-1].agent_id, level=level + 1,
                window_from=group[0].window_from,
                window_to=group[-1].window_to,
                n_windows=sum(d.n_windows for d in decoded),
                ts_ms=max(d.ts_ms for d in decoded), dims=self.dims)
            self._store.replace(group, seg_bytes, level + 1,
                                group[0].window_from,
                                group[-1].window_to)
        if self._metrics is not None:
            self._metrics.archive_compactions_total.inc()
        log.info("archive compaction: L%d windows [%d, %d] -> L%d",
                 level, group[0].window_from, group[-1].window_to,
                 level + 1)
        return True

    # --- range answers ----------------------------------------------------
    def range_snapshot(self, window_from: int,
                       window_to: int) -> Optional[dict]:
        """Merge the covering segments into one snapshot dict shaped like
        the live query plane's (`query/core.py` contract: window / ts_ms /
        seq / report / cm planes) plus the range metadata. None when no
        archived window intersects the range."""
        t0 = time.perf_counter()
        with self.lock:
            segs = self._store.select(window_from, window_to)
            if not segs:
                return None
            decoded = [self._decode_checked(s) for s in segs]
            report, merged, n_merges = self.merge_tables_host(
                [d.tables for d in decoded])
            from netobserv_tpu.exporter.tpu_sketch import report_to_json
            obj = report_to_json(report, **self._report_kwargs)
        covered = (segs[0].window_from, segs[-1].window_to)
        obj["Type"] = "sketch_range_report"
        obj["Window"] = covered[1]
        obj["WindowFrom"], obj["WindowTo"] = covered
        obj["TimestampMs"] = max(d.ts_ms for d in decoded)
        snap = {
            "window": covered[1],
            "ts_ms": obj["TimestampMs"],
            "seq": 0,  # range answers are derived, not published — no seq
            "report": obj,
            "cm_bytes": merged["cm_bytes"],
            "cm_pkts": merged["cm_pkts"],
            "range": {
                "requested": [int(window_from), int(window_to)],
                "covered": [covered[0], covered[1]],
                "windows_merged": sum(d.n_windows for d in decoded),
                "segments_merged": len(segs),
                "merge_dispatches": n_merges,
                "compacted": any(s.level > 0 for s in segs),
                "merge_seconds": round(time.perf_counter() - t0, 6),
            },
        }
        return snap

    def route_payload(self, params: dict,
                      view: Optional[str] = None) -> tuple[int, dict]:
        """The `/query/range` (and `/federation/range`) body builder —
        agent and federation surfaces are thin adapters over exactly this
        (the federation/query.py never-fork rule). Returns (status,
        JSON-able body); every request is counted in
        ``archive_range_requests_total{result}``."""
        code, body = self._route(params, view)
        if self._metrics is not None:
            result = ("ok" if code == 200 else
                      "bad_request" if code == 400 else
                      "not_found" if code == 404 else "error")
            self._metrics.archive_range_requests_total.labels(result).inc()
        return code, body

    def _route(self, params: dict,
               view: Optional[str]) -> tuple[int, dict]:
        view = (view or params.get("view") or "").strip()
        if view not in VIEWS:
            return 404, {"error": f"unknown range view {view!r}",
                         "views": [v for v in VIEWS if v]}
        try:
            window_from = int(params["from"])
            window_to = int(params["to"])
        except (KeyError, TypeError, ValueError):
            return 400, {"error": "from and to window ids are required "
                                  "(?from=<id>&to=<id>)"}
        if window_to < window_from:
            return 400, {"error": f"empty range [{window_from}, "
                                  f"{window_to}]"}
        try:
            snap = self.range_snapshot(window_from, window_to)
        except Exception as exc:
            log.error("range query [%d, %d] failed: %s", window_from,
                      window_to, exc)
            return 500, {"error": str(exc)}
        if snap is None:
            return 404, {"error": f"no archived windows in "
                                  f"[{window_from}, {window_to}]",
                         "coverage": self._store.coverage()}
        from netobserv_tpu.query import core as qcore
        rng = snap["range"]
        if view in ("", "summary"):
            body = qcore.cardinality_payload(snap)
            body.update(qcore.cm_error_bars(snap))
        elif view == "topk":
            body = qcore.topk_payload(snap, params.get("n", 100))
        elif view == "cardinality":
            body = qcore.cardinality_payload(snap)
        elif view == "victims":
            body = qcore.victims_payload(snap)
        else:  # frequency
            if not params.get("src") or not params.get("dst"):
                return 400, {"error": "src and dst are required"}
            body = qcore.frequency_payload(
                snap, params["src"], params["dst"],
                int(params.get("src_port", 0)),
                int(params.get("dst_port", 0)),
                int(params.get("proto", 0)))
        body["range"] = rng
        return 200, body

    def stats(self) -> dict:
        with self.lock:
            out = self._store.stats()
        out["ladder"] = list(self.ladder)
        out["warmed"] = sorted(self._merge_fns)
        return out
