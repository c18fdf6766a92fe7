"""Superbatch fold coalescing at the seams (ISSUE-4 tentpole 3).

k queued batches folded as ONE ladder superbatch must produce the same
sketch state as k sequential per-batch folds — including sampling de-bias,
feature-lane liveness through `PendingEventBuffer`, and the padded-tail
mask — and NO ladder shape may ever retrace post-warmup.

State comparison: every leaf is pinned bit-exact except the top-K table,
which is compared as a SET of (key, count) — a superbatch scores all its
candidates against the fully-updated Count-Min in one `topk.update` while
the sequential path re-scores incrementally, so slot ORDER (top_k tie
ranks) may differ while the surviving keys and their final CM estimates
are identical."""

import numpy as np
import pytest

import tests.conftest  # noqa: F401
import jax

from netobserv_tpu.datapath import flowpack
from netobserv_tpu.datapath.fetcher import EvictedFlows
from netobserv_tpu.model import binfmt
from netobserv_tpu.sketch import staging, state as sk
from netobserv_tpu.utils import retrace

pytestmark = pytest.mark.skipif(
    not flowpack.build_native(), reason="native flowpack build unavailable")

B = 256
CFG = sk.SketchConfig(cm_width=1 << 12, topk=512, hll_precision=8,
                      perdst_buckets=64, perdst_precision=4,
                      persrc_buckets=64, persrc_precision=4,
                      hist_buckets=128, ewma_buckets=256)


def make_events(n, seed=0, sampling=0):
    rng = np.random.default_rng(seed)
    ev = np.zeros(n, binfmt.FLOW_EVENT_DTYPE)
    # small distinct-key universe: the top-K table (512) holds every key,
    # so both fold orders converge to the same key set deterministically
    keys = rng.integers(0, 40, n)
    ev["key"]["src_ip"][:, 10] = 0xFF
    ev["key"]["src_ip"][:, 11] = 0xFF
    ev["key"]["src_ip"][:, 12] = 10
    ev["key"]["src_ip"][:, 15] = keys
    ev["key"]["dst_ip"][:] = ev["key"]["src_ip"]
    ev["key"]["dst_ip"][:, 12] = 20
    ev["key"]["src_port"] = 1000 + keys
    ev["key"]["dst_port"] = 443
    ev["key"]["proto"] = 6
    ev["stats"]["bytes"] = rng.integers(64, 1500, n)
    ev["stats"]["packets"] = rng.integers(1, 4, n)
    ev["stats"]["eth_protocol"] = 0x0800
    ev["stats"]["if_index_first"] = 1
    ev["stats"]["sampling"] = sampling
    ev["stats"]["tcp_flags"] = rng.integers(0, 1 << 9, n)
    ev["stats"]["dscp"] = rng.integers(0, 64, n)
    return ev


def make_feats(n, seed=1):
    rng = np.random.default_rng(seed)
    ex = np.zeros(n, binfmt.EXTRA_REC_DTYPE)
    ex["rtt_ns"] = rng.integers(0, 5_000_000, n)
    dn = np.zeros(n, binfmt.DNS_REC_DTYPE)
    dn["latency_ns"][rng.random(n) < 0.2] = 1_000_000
    dr = np.zeros(n, binfmt.DROPS_REC_DTYPE)
    hit = rng.random(n) < 0.1
    dr["bytes"] = np.where(hit, 900, 0)
    dr["packets"] = hit
    dr["latest_cause"] = np.where(hit, 5, 0)
    return {"extra": ex, "dns": dn, "drops": dr}


def _make_ring(ladder=(1, 2, 4), lanes=1, slot_cap=1 << 12):
    caps = flowpack.default_resident_caps(B // lanes)
    ingests = {k: sk.make_ingest_resident_lanes_fn(
        B // lanes, caps, k * lanes, slot_cap, donate=True) for k in ladder}
    return staging.ShardedResidentStagingRing(
        B, 1, ingests,
        key_tables=jax.device_put(
            sk.init_key_tables(max(ladder) * lanes, slot_cap)),
        put=jax.device_put, caps=caps, slot_cap=slot_cap, lanes=lanes,
        ladder=ladder)


def assert_states_equal(a: sk.SketchState, b: sk.SketchState):
    """Bit-exact on every leaf; top-K compared as a (key words, count)
    set (see module docstring)."""
    for field in sk.SketchState._fields:
        if field == "heavy":
            continue
        la, lb = getattr(a, field), getattr(b, field)
        leaves_a, leaves_b = jax.tree.leaves(la), jax.tree.leaves(lb)
        for xa, xb in zip(leaves_a, leaves_b):
            np.testing.assert_array_equal(np.asarray(xa), np.asarray(xb),
                                          err_msg=field)
    def heavy_set(s):
        # dist states carry (data, sketch) lead dims on the top-K table —
        # flatten to rows before the set compare
        words = np.asarray(s.heavy.words).reshape(-1, sk.KEY_WORDS)
        counts = np.asarray(s.heavy.counts).reshape(-1)
        valid = np.asarray(s.heavy.valid).reshape(-1)
        return {(tuple(w), float(c)) for w, c, v in
                zip(words, counts, valid) if v}

    assert heavy_set(a) == heavy_set(b)


def test_superbatch_equals_sequential_folds():
    """4 batches as ONE 4x superbatch == 4 sequential 1x folds, features
    included, bit-exact outside the top-K slot order."""
    n = 4 * B
    ev, feats = make_events(n, seed=3), make_feats(n, seed=4)
    ring_sb = _make_ring(ladder=(1, 2, 4))
    ring_seq = _make_ring(ladder=(1,))
    s_sb = ring_sb.fold(sk.init_state(CFG), ev, **feats)
    ring_sb.drain()
    s_seq = sk.init_state(CFG)
    for i in range(4):
        s_seq = ring_seq.fold(
            s_seq, ev[i * B:(i + 1) * B],
            **{k: v[i * B:(i + 1) * B] for k, v in feats.items()})
    ring_seq.drain()
    assert ring_sb.superbatch_folds.get(4, 0) >= 1
    assert ring_seq.superbatch_folds.get(1, 0) >= 4
    assert_states_equal(s_sb, s_seq)


def test_superbatch_equals_sequential_with_lanes():
    """Same equivalence with 2 pack lanes per batch (region layout k*lanes)."""
    n = 2 * B
    ev, feats = make_events(n, seed=5), make_feats(n, seed=6)
    ring_sb = _make_ring(ladder=(1, 2), lanes=2)
    ring_seq = _make_ring(ladder=(1,), lanes=2)
    s_sb = ring_sb.fold(sk.init_state(CFG), ev, **feats)
    ring_sb.drain()
    s_seq = sk.init_state(CFG)
    for i in range(2):
        s_seq = ring_seq.fold(
            s_seq, ev[i * B:(i + 1) * B],
            **{k: v[i * B:(i + 1) * B] for k, v in feats.items()})
    ring_seq.drain()
    assert ring_sb.superbatch_folds.get(2, 0) >= 1
    assert_states_equal(s_sb, s_seq)


def test_superbatch_padded_tail_and_mixed_sampling():
    """A non-multiple row count (padded-tail mask) with MIXED per-row
    sampling factors (de-bias must ride the spill lane for rows whose
    sampling differs from the region default) folds identically."""
    n = 2 * B + 57
    ev = make_events(n, seed=7)
    rng = np.random.default_rng(8)
    ev["stats"]["sampling"] = np.where(rng.random(n) < 0.3, 10, 0)
    ring_sb = _make_ring(ladder=(1, 2, 4))
    ring_seq = _make_ring(ladder=(1,))
    s_sb = ring_sb.fold(sk.init_state(CFG), ev)
    ring_sb.drain()
    s_seq = sk.init_state(CFG)
    for lo in range(0, n, B):
        s_seq = ring_seq.fold(s_seq, ev[lo:lo + B])
    ring_seq.drain()
    assert_states_equal(s_sb, s_seq)
    # de-bias really happened: sampled rows count x10
    plain = make_events(n, seed=7)
    ring_p = _make_ring(ladder=(1,))
    s_plain = ring_p.fold(sk.init_state(CFG), plain)
    ring_p.drain()
    assert float(s_sb.total_bytes) > float(s_plain.total_bytes) * 2


def test_pending_buffer_coalesces_and_preserves_lane_liveness():
    """Exporter-level seam: the SAME eviction stream — mixed lane-carrying
    and lane-less evictions, ragged sizes — through a coalescing exporter
    (ladder 1,2,4) and a non-coalescing one (ladder 1) ends in the same
    state; the coalescing one dispatched superbatches."""
    from netobserv_tpu.exporter.tpu_sketch import TpuSketchExporter

    def evictions():
        out = []
        for i in range(11):
            # 700 rows in one eviction -> multi-batch arrivals that the
            # coalescing exporter folds as ladder superbatches
            n = (97, 700, 301)[i % 3]
            ev = make_events(n, seed=20 + i, sampling=(0, 4)[i % 2])
            feats = make_feats(n, seed=40 + i)
            if i % 3 == 0:
                out.append(EvictedFlows(ev, **feats))  # all lanes live
            elif i % 3 == 1:
                out.append(EvictedFlows(ev, drops=feats["drops"]))
            else:
                out.append(EvictedFlows(ev))           # lane-less
        return out

    # per-device PARTIALS legitimately differ between the two paths (rows
    # land on data shards by position, and a 4x superbatch splits them
    # differently than four 1x folds — these tests run on the 8-virtual-
    # device mesh), so equivalence is pinned on the MERGED window report:
    # every signal it carries (totals, CM-scored heavy hitters,
    # cardinalities, quantiles, z-scores, conv/dscp/cause planes) must be
    # identical — masses are integers, so even float sums are exact
    reports = {}
    folds = {}
    for name, ladder in (("sb", (1, 2, 4)), ("seq", (1,))):
        got = []
        exp = TpuSketchExporter(batch_size=B, window_s=3600, sketch_cfg=CFG,
                                sink=got.append, superbatch=ladder)
        # the exporter's ladder is lazy: entries > 1 engage only once
        # warmed (a cold entry must never compile inside a live fold)
        exp.warm_superbatch_ladder(block=True)
        for ev in evictions():
            exp.export_evicted(ev)
        exp.flush()
        folds[name] = dict(exp._ring.superbatch_folds)
        exp.close()
        rep = got[0]
        rep.pop("TimestampMs")
        rep["HeavyHitters"] = sorted(
            rep["HeavyHitters"], key=lambda h: sorted(h.items()))
        reports[name] = rep
    assert any(k > 1 for k in folds["sb"]), folds["sb"]
    assert set(folds["seq"]) == {1}
    assert reports["sb"] == reports["seq"]


def test_zero_retraces_across_ladder():
    """Watchdog-verified: folding every ladder size (plus ragged tails and
    continuation chunks) compiles each ladder entry exactly once — zero
    post-warmup retraces across the whole ladder, and the warm path
    pre-compiles every shape so real traffic never compiles at all."""
    from netobserv_tpu.exporter.tpu_sketch import TpuSketchExporter

    exp = TpuSketchExporter(batch_size=B, window_s=3600, sketch_cfg=CFG,
                            sink=lambda rep: None, superbatch=(1, 2, 4))
    # /query/status says which entries a fold may select: x1 from the
    # start, the rest only once their warm compile landed
    assert exp.query_status()["superbatch"] == {
        "ladder": [1, 2, 4], "warm": [1], "folds": {}, "wide_folds": 0}
    exp.warm_superbatch_ladder(block=True)
    assert exp.query_status()["superbatch"]["warm"] == [1, 2, 4]
    # single-device names ingest_resident_lanes_x{k}; the 8-virtual-device
    # mesh (tests/conftest.py) names sharded_ingest_resident_x{k}; the top
    # entry's wide lane family has a program of its own in either
    prefixes = ("ingest_resident_lanes_x", "sharded_ingest_resident_x",
                "ingest_resident_lanes_wide_x",
                "sharded_ingest_resident_wide_x")

    def ladder_watched():
        return {w["fn"]: w for w in retrace.snapshot()
                if w["fn"].startswith(prefixes)}

    watched = ladder_watched()
    assert {fn[-2:] for fn in watched} >= {"x1", "x2", "x4"}, set(watched)
    assert sum("_wide_x4" in fn for fn in watched) == 1, set(watched)
    for fn, w in watched.items():
        # x1 is always selectable, so warm deliberately SKIPS it (a live
        # fold could be tracing it concurrently); it compiles at first use
        if not fn.endswith("x1"):
            assert w["calls"] >= 1, w  # the warm call
    # sizes chosen so capacity fills fire 4x folds and the final drain
    # holds ~600 rows — a 2x chunk plus a padded 1x tail
    for size in (4 * B, B, 2 * B, 4 * B, 2 * B + 31, 4 * B, 313):
        exp.export_evicted(EvictedFlows(make_events(size, seed=size)))
        # 40 distinct keys never call for the wide family: make the next
        # x4 chunk take it, so both of the entry's programs are dispatched
        exp._ring._wide_next = True
    with exp._lock:
        exp._drain_pending_locked()
    exp._ring.drain()
    assert {k for k in exp._ring.superbatch_folds} >= {1, 2, 4}
    assert 0 < exp._ring.wide_folds < exp._ring.superbatch_folds[4]
    assert exp.query_status()["superbatch"]["wide_folds"] == (
        exp._ring.wide_folds)
    for w in ladder_watched().values():
        assert w["retraces"] == 0, w
        # ONE compile per fixed shape, ever — the warm call's
        assert w["compiles"] <= 1, w
    exp.close()


def test_pending_buffer_coalesces_arrivals_keeps_tails():
    """Rows that arrive together fold as ONE batch-aligned superbatch
    prefix; the sub-batch tail stays buffered; a capacity fill flushes."""
    got = []
    buf = staging.PendingEventBuffer(64, superbatch_max=4)
    assert buf.capacity == 256
    ev = make_events(200, seed=1)
    buf.append(EvictedFlows(ev), lambda e, f: got.append(len(e)))
    # 200 rows arrived together -> one 192-row (3-batch) superbatch fold,
    # 8-row tail kept for the next eviction
    assert got == [192] and len(buf) == 8
    buf.append(EvictedFlows(ev), lambda e, f: got.append(len(e)))
    assert got == [192, 192] and len(buf) == 16
    buf.append(EvictedFlows(make_events(30, seed=2)),
               lambda e, f: got.append(len(e)))
    assert got == [192, 192] and len(buf) == 46  # below a batch: deferred
    buf.flush_to(lambda e, f: got.append(len(e)))
    assert got == [192, 192, 46] and len(buf) == 0
    # a single eviction larger than capacity flushes at the fill mark
    got.clear()
    buf.append(EvictedFlows(make_events(300, seed=3)),
               lambda e, f: got.append(len(e)))
    assert got == [256] and len(buf) == 44


# --- rows a chunk cannot take ride the next chunk ---------------------------

#: side lanes small enough that nearly every region stops early: new keys
#: beyond 4 and rows needing the spill lane beyond 2 a region are left
TIGHT_CAPS = flowpack.ResidentCaps(dns=8, drop=8, nk=4, spill=2)

RING_FORMS = {"one_shard_eight_lanes": (1, 8), "four_shards_two_lanes": (4, 2)}


@pytest.fixture(params=sorted(RING_FORMS))
def tight_exporter(request, monkeypatch):
    """A factory of exporters whose resident ring has TIGHT_CAPS, in the
    two forms the served path runs: one device with 8 pack lanes, and a
    4-shard mesh with 2 lanes a shard (32 regions an x4 chunk either way).
    `carry=False` gives the finish-everything form: every fold consumes
    all it is offered, through continuation chunks."""
    from netobserv_tpu.exporter.tpu_sketch import TpuSketchExporter

    shards, lanes = RING_FORMS[request.param]
    monkeypatch.setattr(flowpack, "default_resident_caps",
                        lambda batch: TIGHT_CAPS)
    if shards == 1:
        real_devices = jax.devices
        monkeypatch.setattr(jax, "devices",
                            lambda *a, **k: real_devices(*a, **k)[:1])
    made = []

    def make(carry=True, **kw):
        exp = TpuSketchExporter(
            batch_size=B, window_s=3600, sketch_cfg=CFG, pack_threads=8,
            superbatch=(1, 2, 4), mesh_shape="" if shards == 1 else "4",
            **kw)
        made.append(exp)
        exp.warm_superbatch_ladder(block=True)
        ring = exp._ring
        assert (ring.n_shards, ring.lanes, ring.caps) == (
            shards, lanes, TIGHT_CAPS)
        exp._carry_ring = carry
        return exp

    yield make
    for exp in made:
        exp.close()


def mixed_evictions():
    """Ragged evictions, some with every feature lane, some with one, some
    with none — so a lane goes live while older left rows wait."""
    out = []
    for i in range(9):
        n = (700, 97, 1100, 301)[i % 4]
        ev = make_events(n, seed=60 + i)
        feats = make_feats(n, seed=80 + i)
        if i % 3 == 0:
            out.append(EvictedFlows(ev))
        elif i % 3 == 1:
            out.append(EvictedFlows(ev, **feats))
        else:
            out.append(EvictedFlows(ev, drops=feats["drops"]))
        out[-1].eviction = i + 1
    return out


def sorted_report(rep):
    rep = dict(rep)
    rep.pop("TimestampMs")
    rep["HeavyHitters"] = sorted(rep["HeavyHitters"],
                                 key=lambda h: sorted(h.items()))
    return rep


def test_carried_rows_fold_once_and_match_the_finishing_form(tight_exporter):
    """The same stream through the carrying exporter and through the
    finish-everything form ends, after flush(), in the same merged window
    report; every row handed is folded exactly once; the steady folds
    shipped no continuation chunk."""
    from netobserv_tpu.metrics.registry import Metrics, MetricsSettings

    handed = sum(len(e) for e in mixed_evictions())
    reports, rings = {}, {}
    for carry in (True, False):
        got = []
        metrics = Metrics(MetricsSettings())
        exp = tight_exporter(carry=carry, sink=got.append, metrics=metrics)
        for ev in mixed_evictions():
            exp.export_evicted(ev)
            # less than a batch waits for the next eviction, left rows
            # included (chip_smoke.py counts on it to tell a window's end)
            assert len(exp._pending_buf) < B
        steady_continuations = exp._ring.continuations
        assert (len(exp._pending_buf) > 0) or not carry
        exp.flush()
        assert len(exp._pending_buf) == 0
        assert metrics.sketch_records_total._value.get() == handed
        assert (metrics.sketch_resident_carried_rows_total._value.get()
                == exp._ring.carried_rows)
        assert got[0]["Records"] == handed
        reports[carry] = sorted_report(got[0])
        rings[carry] = (exp._ring, steady_continuations)
    assert reports[True] == reports[False]
    ring, steady = rings[True]
    assert ring.carried_rows > 0 and steady == 0
    plain, _ = rings[False]
    assert plain.carried_rows == 0 and plain.continuations > 0
    # each dispatch of the carrying form loads every region again: fewer
    # dispatches for the same rows
    assert (sum(ring.superbatch_folds.values())
            < sum(plain.superbatch_folds.values()))


def test_ring_makes_its_key_tables_at_the_first_fold(tight_exporter):
    """The ladder warm-up folds through ONE spare table array of its own
    and drops it; the ring's array is not alive beside it — it is made by
    the first dispatch, (lanes * slot_cap, 10) a shard, and the gauge
    counted its bytes from the geometry alone."""
    from netobserv_tpu.metrics.registry import Metrics, MetricsSettings

    metrics = Metrics(MetricsSettings())
    exp = tight_exporter(metrics=metrics)
    ring = exp._ring
    assert ring.warm_entries() == [1, 2, 4]
    assert ring._key_tables is None
    exp.export_evicted(EvictedFlows(make_events(B, seed=4)))
    tables = ring.key_tables
    rows = ring.n_shards * ring.superbatch_max * ring.lanes * ring.slot_cap
    assert tables.shape == (rows, sk.KEY_WORDS)
    assert ring.key_tables is tables
    assert (metrics.sketch_resident_table_bytes._value.get()
            == rows * sk.KEY_WORDS * 4)


def test_roll_flush_and_close_leave_nothing_carried(tight_exporter):
    got = []
    exp = tight_exporter(sink=got.append)
    evs = mixed_evictions()
    for ev in evs[:3]:
        exp.export_evicted(ev)
    assert len(exp._pending_buf) > 0
    with exp._lock:
        exp._close_window_locked()              # a roll
    assert len(exp._pending_buf) == 0
    assert not any(exp._pending_buf._live.values())
    for ev in evs[3:6]:
        exp.export_evicted(ev)
    assert len(exp._pending_buf) > 0
    exp.flush()
    assert len(exp._pending_buf) == 0
    # newest first: in arrival order the wide family (the top entry's, which
    # this stream calls for once) happens to leave exactly nothing buffered
    for ev in reversed(evs[6:]):
        exp.export_evicted(ev)
    assert len(exp._pending_buf) > 0
    exp.close()
    assert len(exp._pending_buf) == 0
    # every window published exactly the rows handed while it was open
    assert [r["Records"] for r in got] == [
        sum(len(e) for e in evs[lo:lo + 3]) for lo in (0, 3, 6)]


def test_wedged_slot_mid_carry_drops_one_fold_and_adopts_the_state(
        tight_exporter):
    """The slot-wait budget trips on the SECOND chunk of a fold that holds
    carried rows: the fold's rows drop (at most what the finishing form
    drops: the fold on offer), the exporter adopts the state the first
    chunk left (its own was donated), nothing stale stays buffered, and
    the feed goes on."""
    from netobserv_tpu.metrics.registry import Metrics, MetricsSettings

    class NeverReady:
        def is_ready(self):
            return False

    metrics = Metrics(MetricsSettings())
    exp = tight_exporter(metrics=metrics, shed_watermark=1e9,
                         shed_slot_budget_s=0.1)
    ring = exp._ring
    exp.export_evicted(EvictedFlows(make_events(B + 40, seed=1)))
    held = len(exp._pending_buf)
    assert held > 40                            # carried rows and the tail
    folded = metrics.sketch_records_total._value.get()
    assert folded == B + 40 - held
    pre = exp._state
    # 3 batches arrive: with what is held, a 3-batch prefix folds as an x2
    # chunk and an x1 chunk; the x1 chunk's slot never frees
    wedged = (ring._slot + 1) % len(ring._tokens)
    real = ring._tokens[wedged]
    ring._tokens[wedged] = NeverReady()
    try:
        exp.export_evicted(EvictedFlows(make_events(3 * B, seed=2)))
    finally:
        ring._tokens[wedged] = real
    assert exp._state is not pre
    assert metrics.sketch_ingest_errors_total._value.get() == 1
    assert metrics.sketch_records_total._value.get() == folded
    assert len(exp._pending_buf) == held        # the new tail alone
    got = []
    exp._sink = got.append
    exp.export_evicted(EvictedFlows(make_events(B, seed=3)))
    exp.flush()
    # the report counts what the device folded: everything handed but the
    # 3 batches the wedged fold was offered, of which the x2 chunk's
    # consumed rows did reach the device
    offered = 3 * B
    lost = (B + 40 + 3 * B + B) - got[0]["Records"]
    assert 0 < lost <= offered


# --- the wide lane family ----------------------------------------------------

def keyed_events(keys, seed=0):
    """One event a key: the 5-tuple is the 64-bit `keys[i]` written into
    the source address and port."""
    keys = np.asarray(keys, np.uint64)
    n = len(keys)
    rng = np.random.default_rng(seed)
    ev = np.zeros(n, binfmt.FLOW_EVENT_DTYPE)
    ev["key"]["src_ip"][:, 10:12] = 0xFF
    for j in range(6):      # bytes 0..3 of the key in 12..15, 4..5 in 4..5
        ev["key"]["src_ip"][:, (12, 13, 14, 15, 4, 5)[j]] = (
            keys >> np.uint64(8 * j)) & np.uint64(0xFF)
    ev["key"]["dst_ip"][:, 10:12] = 0xFF
    ev["key"]["dst_ip"][:, 12] = 20
    ev["key"]["src_port"] = (keys >> np.uint64(48)).astype(np.uint16)
    ev["key"]["dst_port"] = 443
    ev["key"]["proto"] = 6
    ev["stats"]["bytes"] = rng.integers(64, 1500, n)
    ev["stats"]["packets"] = rng.integers(1, 4, n)
    ev["stats"]["eth_protocol"] = 0x0800
    ev["stats"]["if_index_first"] = 1
    return ev


class KeyStream:
    """Zipf(1.2) over `universe` keys from a fixed popularity order; with
    `new_share`, that share of the rows carries a key never seen before (a
    counter above the universe): the spoofed-source flood of the cell
    `collector-1chip.newkeys-saturate`."""

    def __init__(self, seed, universe=1 << 16, new_share=0.0):
        self.rng = np.random.default_rng(seed)
        self.universe, self.new_share = universe, new_share
        self.fresh = 1 << 32

    def take(self, n):
        keys = np.minimum(self.rng.zipf(1.2, n), self.universe).astype(
            np.uint64)
        new = np.flatnonzero(self.rng.random(n) < self.new_share)
        keys[new] = self.fresh + np.arange(len(new), dtype=np.uint64)
        self.fresh += len(new)
        return keyed_events(keys, seed=self.fresh)


def family_ring(n_shards=1, lanes=8, batch=8192, log=None):
    """The served ring's geometry (32 regions of 1,024 rows an x4 chunk,
    the default narrow and wide caps) over programs that only say who was
    called: the schedule is the host's alone."""
    token = jax.numpy.zeros(1)

    def program(name):
        def ingest(state, tables, flat):
            if log is not None:
                log.append(name)
            return state, tables, token
        return ingest
    ring = staging.ShardedResidentStagingRing(
        batch, n_shards, {k: program(f"x{k}") for k in (1, 2, 4)},
        key_tables=object(), put=lambda buf: buf, lanes=lanes,
        pack_threads=4, ladder=(1, 2, 4), lazy_ladder=True,
        wide_ingest={4: program("wide_x4")})
    assert ring.batch_per_region == 1024
    assert ring.caps == flowpack.default_resident_caps(1024)
    assert ring.wide_caps == flowpack.wide_resident_caps(1024)
    return ring


def carry_through(ring, stream, chunks, rows=4 * 8192):
    """Offer `chunks` top-entry chunks of `stream`, the rows each left
    riding the front of the next, as the pending buffer offers them."""
    held = np.zeros(0, binfmt.FLOW_EVENT_DTYPE)
    for _ in range(chunks):
        events = np.concatenate([held, stream.take(rows - len(held))])
        _, left = ring.fold(None, events, carry=True)
        held = np.concatenate([events[lo:hi] for lo, hi in left]
                              or [events[:0]])


FLOOD = dict(universe=1 << 12, new_share=0.2)
RING_SHAPES = {"one_shard_eight_lanes": (1, 8),
               "four_shards_two_lanes": (4, 2),
               "two_shards_four_lanes": (2, 4)}


@pytest.mark.parametrize("shape", sorted(RING_SHAPES))
def test_a_key_flood_picks_the_wide_family_and_leaves_few_rows(shape):
    """A fifth of the rows on never-seen keys: the narrow lanes (64 new keys
    + 32 spill rows a region of 1,024) leave two rows in three, the ring
    answers with the wide entry from the second chunk on and, once the 32
    dictionaries hold the popular keys, leaves under a tenth; held narrow
    it goes on offering every row three times."""
    rings = {}
    for held_narrow in (False, True):
        ring = family_ring(*RING_SHAPES[shape])
        ring.mark_warm(2, 4)
        if not held_narrow:
            ring.mark_warm(4, wide=True)
        stream = KeyStream(7, **FLOOD)
        carry_through(ring, stream, chunks=12)
        learning = ring.carried_rows
        carry_through(ring, stream, chunks=12)
        rings[held_narrow] = (ring, ring.carried_rows - learning)
    (wide, wide_left), (narrow, narrow_left) = rings[False], rings[True]
    offered = 12 * 4 * 8192
    assert narrow.wide_folds == 0 and narrow_left > offered // 2
    assert wide.superbatch_folds == narrow.superbatch_folds == {4: 24}
    assert wide.wide_folds == 23        # the first chunk is how it learns
    assert wide_left < offered // 10


def test_the_family_sequence_is_a_pure_function_of_the_row_stream():
    """Two rings fed the same rows dispatch the same programs in the same
    order (every process of a spanning mesh must), through a flood that
    starts and ends inside the stream."""
    logs = []
    for _ in range(2):
        log = []
        ring = family_ring(log=log)
        ring.mark_warm(2, 4)
        ring.mark_warm(4, wide=True)
        for seed, share, chunks in ((3, 0.0, 6), (4, 0.2, 6), (3, 0.0, 30)):
            carry_through(ring, KeyStream(seed, 1 << 12, share), chunks)
        logs.append(log)
    assert logs[0] == logs[1]
    assert {"x4", "wide_x4"} <= set(logs[0])
    assert logs[0][-1] == "x4"          # and back once the flood is over


def test_a_zipf_stream_on_learned_dictionaries_never_picks_wide():
    """Stationary traffic: once the 32 dictionaries hold the head of the
    popularity order (cold, a Zipf stream IS a key flood, and the ring may
    answer it as one), no chunk calls for the wide family, chunk after
    chunk."""
    ring = family_ring()
    ring.mark_warm(2, 4)
    ring.mark_warm(4, wide=True)
    stream = KeyStream(11, universe=1 << 12)
    carry_through(ring, stream, chunks=30)
    learned, left = ring.wide_folds, ring.carried_rows
    carry_through(ring, stream, chunks=30)
    assert ring.wide_folds == learned < 30
    assert ring.carried_rows - left < 30 * 4 * 8192 // 100
    assert not ring._wide_next


def test_status_warm_waits_for_the_wide_entry():
    """`superbatch.warm` equals `superbatch.ladder` only when EVERY program
    is compiled: a benchmark's set-up waits on that equality, and a wide
    entry that compiled inside the window would be a lowering there. The
    narrow x4 entry is selectable from its own compile on."""
    ring = family_ring()
    assert ring.programs() == [(1, False), (2, False), (4, False),
                               (4, True)]
    assert ring.warm_entries() == [1]
    ring.mark_warm(2, 4)
    assert ring.warm_entries() == [1, 2] and ring.is_warm(4)
    carry_through(ring, KeyStream(7, **FLOOD), chunks=3)
    assert ring.superbatch_folds == {4: 3} and ring.wide_folds == 0
    ring.mark_warm(4, wide=True)
    assert ring.warm_entries() == [1, 2, 4] == list(ring.ladder)
    carry_through(ring, KeyStream(8, **FLOOD), chunks=3)
    assert ring.wide_folds == 2


def test_wide_and_narrow_fold_a_flood_to_the_same_window(tight_exporter):
    """The same flood through an exporter free to pick the wide family and
    through one held narrow: the same window totals, the same heavy hitters
    at the sink and the same head of /query/topk — a slot defined through
    either lane is the same slot — in fewer dispatches."""
    from netobserv_tpu.metrics.registry import Metrics, MetricsSettings

    def evictions():
        stream = KeyStream(21, universe=40, new_share=0.2)
        out = []
        for i, n in enumerate((4 * B, 4 * B + 70, 97, 8 * B, 4 * B, 301)):
            out.append(EvictedFlows(stream.take(n)))
            out[-1].eviction = i + 1
        return out

    handed = sum(len(e.events) for e in evictions())
    seen = {}
    for held_narrow in (False, True):
        got = []
        metrics = Metrics(MetricsSettings())
        exp = tight_exporter(sink=got.append, metrics=metrics)
        ring = exp._ring
        assert ring.wide_caps.nk == 3 * ring.caps.nk
        if held_narrow:
            ring._wide_available.clear()
        for ev in evictions():
            exp.export_evicted(ev)
        exp.flush()
        assert got[0]["Records"] == handed
        assert (metrics.sketch_resident_wide_folds_total._value.get()
                == ring.wide_folds)
        code, body = exp.query_routes.handle("/query/topk", {"n": "20"})
        assert code == 200
        seen[held_narrow] = (sorted_report(got[0]), body["topk"],
                             ring.wide_folds,
                             sum(ring.superbatch_folds.values()))
    (rep_w, topk_w, wide_w, folds_w), (rep_n, topk_n, wide_n, folds_n) = (
        seen[False], seen[True])
    assert wide_w > 0 == wide_n
    # the slot table's churn counts follow the fold order (module docstring)
    rep_w.pop("HeavyChurn"), rep_n.pop("HeavyChurn")
    assert rep_w == rep_n
    assert topk_w == topk_n
    assert folds_w < folds_n
