"""Multi-tenant sketch planes: one dispatch folds every tenant.

Many *independent* observation domains (namespaces, customers, VPCs) per
chip is the ROADMAP's "millions of users" shape — and a full exporter per
tenant would pay N jit dispatches, N staging rings and N roll timers for
work whose per-dispatch overhead, not compute, bounds the host seam
(SALSA's thesis, PAPERS.md). `TenantStack` amortizes it: N tenant
`SketchState`s stack along a leading axis (SketchState is a pytree), ONE
vmapped+donated ingest executable folds every tenant's evictions and ONE
vmapped roll closes every tenant's window.

Routing happens in the columnar host path: evicted rows pack once to dense
rows (`flowpack.pack_dense`), each row's tenant owner is a key-derived hash
(`ops/hashing.tenant_of_np`, the numpy twin of the device `tenant_of` —
decorrelated from every sketch family), and rows accumulate into per-tenant
fixed-shape (B, 20) buffers. When any tenant's buffer fills, ALL buffers
ship as one zero-padded (N, B*20) stacked fold — invalid (all-zero) rows
are the fold's no-op identity, so padding costs nothing but transfer bytes.
Fixed shapes everywhere: zero data-dependent shapes, zero retraces across
the tenant-count ladder (each N is its own watched executable, the
`tenants=` attribution in utils/retrace).

Per-tenant bit-exactness is the contract that makes this a pure perf
change: tenant t's lane of the stacked fold receives exactly the (B, 20)
array a single-tenant exporter fed the routed slice would ingest, and the
vmapped scatter core (`ops/countmin._scatter_add_two`'s custom_vmap rule)
applies the same adds per cell in the same order — tests/test_tenancy.py
pins stacked-vs-routed-slice equality for every table.

Scheduling notes:
- the slot/token protocol is inherited from `sketch.staging._SlotRing`
  verbatim (the CPU backend zero-copies aligned host arrays, so blocking
  on the put result is NOT sufficient — the token is a slice of the
  ingest's input and becomes ready only when the executable finished).
- `TenantStack` duck-types the staging rings' `fold`/`slot_wait_p95`
  surface, so the exporter's eviction path, overload coupling and
  PendingEventBuffer compose unchanged.
- mesh composition is refused-with-warning (the SKETCH_TIERED pattern;
  config.validate names SKETCH_TENANTS + SKETCH_MESH_SHAPE).
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from netobserv_tpu.datapath import flowpack
from netobserv_tpu.model.columnar import KEY_WORDS
from netobserv_tpu.ops import hashing
from netobserv_tpu.sketch import state as sk
from netobserv_tpu.sketch.staging import StagingWedged, _SlotRing
from netobserv_tpu.utils import retrace, tracing

DENSE_WORDS = sk.DENSE_WORDS


def init_stacked_state(cfg: sk.SketchConfig, n_tenants: int):
    """N independent fresh tenant states stacked on a leading axis — every
    leaf of the SketchState pytree (tiered included) gains dim 0 = N."""
    import jax
    import jax.numpy as jnp

    base = sk.init_state(cfg)
    return jax.tree.map(lambda x: jnp.stack([x] * n_tenants), base)


def split_tenants(tree, n_tenants: int) -> list:
    """Slice a stacked pytree (roll report / table dict) into N per-tenant
    host trees. One np.asarray per leaf (one device pull for the whole
    stack), then zero-copy views per tenant."""
    import jax

    host = jax.tree.map(np.asarray, tree)
    return [jax.tree.map(lambda x: x[t], host) for t in range(n_tenants)]


class TenantStack(_SlotRing):
    """The stacked multi-tenant sketch plane: host router + per-tenant
    fill buffers + ONE vmapped ingest/roll pair.

    Duck-types the staging-ring fold surface the exporter drives:
    ``fold(state, events, extra=, dns=, drops=, xlat=, quic=, trace=)`` and
    ``slot_wait_p95()``. `flush()` ships any partially-filled tenant
    buffers (window close calls it before the stacked roll).
    """

    def __init__(self, n_tenants: int, cfg: sk.SketchConfig,
                 batch_size: int, metrics=None, n_slots: int = 4,
                 reset_sketches: bool = True,
                 decay_factor: Optional[float] = None):
        import jax

        if n_tenants < 1:
            raise ValueError("TenantStack needs n_tenants >= 1")
        self.n_tenants = n_tenants
        self.batch_size = batch_size
        self.cfg = cfg
        self.folds = 0          #: stacked ingest dispatches
        self.routed_rows = 0    #: rows routed to tenant buffers
        self._put = jax.device_put
        # per-tenant fill buffers (host, reused): rows accumulate here in
        # arrival order until any tenant's buffer fills
        self._fillbuf = np.zeros((n_tenants, batch_size, DENSE_WORDS),
                                 np.uint32)
        self._fill = [0] * n_tenants
        self._init_slots(
            [np.empty((n_tenants, batch_size * DENSE_WORDS), np.uint32)
             for _ in range(n_slots)], metrics)

        def one(s, flat):
            return sk.ingest(s, sk.dense_to_arrays(flat),
                             use_pallas=cfg.use_pallas)

        def ingest_fn(s, dense):
            # dense: (N, B*20) u32 — flat per tenant lane (the same
            # device-layout-padding dodge the dense ring ships). Token =
            # a slice of the input (the _SlotRing slot-reuse guard).
            s = jax.vmap(one)(s, dense)
            return s, dense.reshape(-1)[:1]

        # donation is load-bearing: the stacked state is N x the resident
        # footprint, and an undonated vmapped fold copies all of it per
        # dispatch (measured 10x+ slower at N=64)
        self._ingest = retrace.jit(ingest_fn, "tenant_ingest",
                                   tenants=n_tenants, donate_argnums=(0,))

        def roll_one(s):
            # mirrors make_roll_fn(with_tables=True): the report and the
            # mergeable tables are of the PRE-roll state, one executable
            new_state, report = sk.roll_window(s, cfg, reset_sketches,
                                               decay_factor)
            return new_state, report, sk.state_tables(s)

        self._roll = retrace.jit(jax.vmap(roll_one), "tenant_roll",
                                 tenants=n_tenants)
        if metrics is not None:
            metrics.sketch_tenants_active.set(n_tenants)

    # -- host router ------------------------------------------------------
    def route(self, events, extra=None, dns=None, drops=None, xlat=None,
              quic=None) -> tuple[np.ndarray, np.ndarray]:
        """Pack `events` once to dense rows and derive each row's tenant
        owner. Returns (rows (M, 20) u32, owners int32[M]). Split out so
        tests reuse the exact production routing."""
        rows = flowpack.pack_dense(events, batch_size=max(len(events), 1),
                                   extra=extra, dns=dns, drops=drops,
                                   xlat=xlat, quic=quic)
        owners = hashing.tenant_of_np(rows[:, :KEY_WORDS], self.n_tenants)
        return rows, owners

    def fold(self, state, events, extra=None, dns=None, drops=None,
             xlat=None, quic=None, trace=None):
        """Route `events` to tenant buffers; every time a tenant's buffer
        fills, ship ONE stacked fold of all tenants' pending rows (async —
        not blocked on). Returns the new stacked state."""
        if len(events) == 0:
            return state
        trace, owned = self._fold_trace(trace)
        try:
            with trace.stage("tenant_route"):
                rows, owners = self.route(events, extra=extra, dns=dns,
                                          drops=drops, xlat=xlat, quic=quic)
            return self._fold_routed(state, rows, owners, trace)
        finally:
            if owned:
                trace.finish()

    def fold_rows(self, state, rows: np.ndarray, trace=None):
        """Fold pre-packed dense rows ((M, 20) u32 — the Record/batch path,
        which already packed through the columnar twin). Same routing and
        dispatch as `fold`."""
        if len(rows) == 0:
            return state
        trace, owned = self._fold_trace(trace)
        try:
            owners = hashing.tenant_of_np(rows[:, :KEY_WORDS],
                                          self.n_tenants)
            return self._fold_routed(state, rows, owners, trace)
        finally:
            if owned:
                trace.finish()

    def _fold_routed(self, state, rows, owners, trace):
        self.routed_rows += len(rows)
        try:
            for t in range(self.n_tenants):
                sel = rows[owners == t]
                off = 0
                while off < len(sel):
                    take = min(len(sel) - off,
                               self.batch_size - self._fill[t])
                    lo = self._fill[t]
                    self._fillbuf[t, lo:lo + take] = sel[off:off + take]
                    self._fill[t] += take
                    off += take
                    if self._fill[t] == self.batch_size:
                        state = self._dispatch(state, trace)
        except StagingWedged as exc:
            # earlier dispatches of this fold DONATED the state they were
            # handed — the caller's pre-fold reference is deleted by then.
            # `state` here is the last valid reference (identical to the
            # caller's when nothing dispatched): the catcher must adopt it
            # (the staging-ring wedge contract).
            exc.state = state
            raise
        return state

    def flush(self, state, trace=None):
        """Ship any partially-filled tenant buffers as one stacked fold
        (no-op when all buffers are empty) — window close calls this so a
        roll never strands buffered rows."""
        if not any(self._fill):
            return state
        try:
            return self._dispatch(state, trace or tracing.NULL_TRACE)
        except StagingWedged as exc:
            exc.state = state  # nothing dispatched: caller's own state
            raise

    def _dispatch(self, state, trace):
        """One stacked fold: copy every tenant's fill prefix into a ship
        slot (zero-padding the tail — invalid rows are the fold identity),
        device_put, dispatch the vmapped ingest, advance the token ring."""
        chunk = self._chunk(trace)
        slot = self._wait_slot(chunk)
        buf = self._bufs[slot]
        for t in range(self.n_tenants):
            f = self._fill[t] * DENSE_WORDS
            if f:
                buf[t, :f] = self._fillbuf[t].reshape(-1)[:f]
            buf[t, f:] = 0
            self._fill[t] = 0
        with chunk.stage("put"):
            dev = self._put(buf)
        with chunk.stage("ingest_dispatch"):
            state, token = self._ingest(state, dev)
        self._advance(slot, token)
        self.folds += 1
        if self._metrics is not None:
            self._metrics.sketch_tenant_folds_total.inc()
        return state

    # -- roll / teardown --------------------------------------------------
    def roll(self, state):
        """ONE stacked roll closing every tenant's window: returns
        (new stacked state, stacked report, stacked pre-roll tables)."""
        return self._roll(state)

    def close(self) -> None:
        """Tenant-series label hygiene (the federation agent-eviction
        pattern): drained/removed tenants must not leave their labelled
        series behind — evict every per-tenant series and zero the
        active-tenants gauge."""
        m = self._metrics
        if m is None:
            return
        for t in range(self.n_tenants):
            m.remove_labeled(m.sketch_tenant_window_records, str(t))
        m.sketch_tenants_active.set(0)
