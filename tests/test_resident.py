"""Resident-key feed: packer twins, device unpack, ring fallbacks.

The resident feed is the lowest-bytes-per-record host->device path
(~15B/record at production batch size; byte budget in docs/tpu_sketch.md):
hot rows carry a 20-bit slot id into a device-resident key table instead of
the 10 key words (flowpack.cc fp_pack_resident <-> flowpack.pack_resident
<-> sketch.state.resident_lane_arrays). These tests pin:
- native C++ packer == pure-python twin, byte for byte, dict state included
- folding through the resident ring == folding the same batches dense, for
  every exact-path signal (CM planes, top-K, totals, drops, flags); the
  range-coded rtt/dns land within one log-histogram bucket
- partial packing with continuation: a full lane stops the chunk, the
  shipped prefix is self-consistent, and the remainder packs next — the
  dictionary and device table learn monotonically under cold-start floods
- full dictionary -> epoch reset at the next fold, results still exact
"""
from __future__ import annotations

import importlib.util

import numpy as np
import pytest

from netobserv_tpu.datapath import flowpack
from netobserv_tpu.datapath.replay import SyntheticFetcher
from netobserv_tpu.model import binfmt

pytestmark = pytest.mark.skipif(
    not flowpack.build_native(), reason="native flowpack build unavailable")

#: the PACKER tests below run on the jax-free big-endian qemu CI tier too
#: (native/python twin equality is byte-order-sensitive); only the device
#: ingest tests need jax
needs_jax = pytest.mark.skipif(importlib.util.find_spec("jax") is None,
                               reason="jax unavailable (qemu tier)")

B = 512


def make_feed(n_batches=4, n_distinct=200, seed=5, v6_every=0,
              flows_per_eviction=B):
    """Synthetic eviction batches with dns/drops/rtt feature rows."""
    fetcher = SyntheticFetcher(flows_per_eviction=flows_per_eviction,
                               n_distinct=n_distinct, seed=seed)
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n_batches):
        ev = fetcher.lookup_and_delete()
        events, extra = ev.events[:B].copy(), ev.extra[:B].copy()
        n = len(events)
        if v6_every:
            # de-map some keys to real v6 (resident feed carries ANY key)
            events["key"]["src_ip"][::v6_every, 0] = 0x20
        dn = np.zeros(n, binfmt.DNS_REC_DTYPE)
        dn["latency_ns"][rng.random(n) < 0.05] = rng.integers(1, 3_000_000)
        dr = np.zeros(n, binfmt.DROPS_REC_DTYPE)
        hit = rng.random(n) < 0.02
        dr["bytes"][hit] = rng.integers(1, 3000)
        dr["packets"][hit] = 1
        dr["latest_cause"][hit] = 2
        out.append((events, dict(extra=extra, dns=dn, drops=dr)))
    return out


#: the two lane families a ring packs with (`wide` differs in `nk` alone),
#: at B rows a region
CAPS_FAMILIES = {"narrow": flowpack.default_resident_caps,
                 "wide": flowpack.wide_resident_caps}


def flood_feed(n_batches=4, seed=9, new_keys=120):
    """`make_feed` with `new_keys` keys a batch that no batch before it
    held (one 5-tuple stamped with a counter), on its leading rows: more
    than a narrow region takes in one offer (64 new keys + 32 spill rows),
    fewer than the wide lane holds (192)."""
    feed = make_feed(n_batches=n_batches, n_distinct=4000, seed=seed)
    for i, (events, _) in enumerate(feed):
        assert len(events) >= new_keys
        events["key"] = events["key"][0]
        events["key"]["src_port"] = (1000 * i
                                     + np.arange(len(events)) % new_keys)
    return feed


@pytest.mark.parametrize("family", sorted(CAPS_FAMILIES))
@pytest.mark.parametrize("feed", ["steady", "flood"])
def test_native_matches_python_twin(family, feed):
    caps = CAPS_FAMILIES[family](B)
    kd_n = flowpack.KeyDict(1 << 12, use_native=True)
    kd_p = flowpack.KeyDict(1 << 12, use_native=False)
    assert kd_n.native and not kd_p.native
    chunks = 0
    batches = (make_feed(n_batches=5, v6_every=17) if feed == "steady"
               else flood_feed())
    for events, feats in batches:
        start = 0
        while start < len(events):
            bn, cn = flowpack.pack_resident(events, B, kd_n, caps,
                                            start=start, **feats)
            bp, cp = flowpack.pack_resident(events, B, kd_p, caps,
                                            start=start, **feats)
            assert cn == cp and cn > 0
            assert np.array_equal(bn, bp)
            assert kd_n.count() == kd_p.count()
            start += cn
            chunks += 1
    if feed == "flood":
        # the narrow region stops at 64 new keys + 32 spill rows and needs
        # a second offer, the wide one takes the batch
        assert (chunks > len(batches)) == (family == "narrow")
    kd_n.close()


def test_native_matches_python_twin_on_overflowing_latency():
    """A DNS latency >= 2^32 µs must WRAP identically on both packers in the
    spill lane ((uint32_t) cast in flowpack.cc; np.uint32(dlat) used to raise
    OverflowError in the python twin instead)."""
    caps = flowpack.default_resident_caps(B)
    kd_n = flowpack.KeyDict(1 << 12, use_native=True)
    kd_p = flowpack.KeyDict(1 << 12, use_native=False)
    (events, feats), = make_feed(n_batches=1)
    # dlat_us = latency_ns // 1000 = 2^32 + 7 -> wraps to 7 in the u32 column
    feats["dns"]["latency_ns"][:4] = ((1 << 32) + 7) * 1000
    # force those rows OFF the hot lane (packets over the 11-bit packed
    # budget) so they take the full-width spill row where the cast lives
    events["stats"]["packets"][:4] = 0x900
    start = 0
    n_spilled = 0
    while start < len(events):
        bn, cn = flowpack.pack_resident(events, B, kd_n, caps,
                                        start=start, **feats)
        bp, cp = flowpack.pack_resident(events, B, kd_p, caps,
                                        start=start, **feats)
        assert cn == cp and cn > 0
        assert np.array_equal(bn, bp)
        n_spilled += int(bn[2])
        start += cn
    assert n_spilled >= 4  # the overflowing rows actually rode the spill lane
    kd_n.close()


def test_rtt_code_roundtrip_error_bound():
    # 11-bit code: m << (2e); relative error < 2^-8 within the code range
    for v in [0, 1, 255, 256, 1000, 4095, 65535, 1 << 20, flowpack.RTT_MAX_US]:
        c = flowpack._rtt_code11(v)
        dec = (c & 0xFF) << (2 * (c >> 8))
        assert dec <= v and (v == 0 or (v - dec) / v < 1 / 256)


def test_lat_code_roundtrip_error_bound():
    for v in [0, 1, 4095, 4096, 100_000, 2_000_000, (0xFFF << 15)]:
        c = flowpack._lat_code16(v)
        dec = (c & 0xFFF) << (c >> 12)
        assert dec <= v and (v == 0 or (v - dec) / v < 1 / 4096)
    # beyond range: saturates, never overflows the 16-bit field
    assert flowpack._lat_code16((0xFFF << 15) * 10) <= 0xFFFF


def _fold_both_ways(feed, slot_cap=1 << 12, caps=None):
    import jax

    from netobserv_tpu.sketch import state as sk

    cfg = sk.SketchConfig()
    # one shard, one lane, ladder (1,): the ring the exporter serves
    ring = _lane_ring(1, caps=caps, slot_cap=slot_cap)
    dense_fn = sk.make_ingest_dense_fn(with_token=True)
    s_r, s_d = sk.init_state(cfg), sk.init_state(cfg)
    for events, feats in feed:
        s_r = ring.fold(s_r, events, **feats)
        db = flowpack.pack_dense(events, batch_size=B, **feats)
        s_d, _ = dense_fn(s_d, jax.device_put(db.reshape(-1)))
    ring.drain()
    jax.block_until_ready(s_d)
    return s_r, s_d, ring


def _assert_exact_signals_match(s_r, s_d):
    for f in ("total_records", "total_bytes", "total_drop_bytes",
              "total_drop_packets", "quic_records", "nat_records"):
        assert float(getattr(s_r, f)) == pytest.approx(
            float(getattr(s_d, f))), f
    np.testing.assert_allclose(np.asarray(s_r.cm_bytes.counts),
                               np.asarray(s_d.cm_bytes.counts))
    np.testing.assert_allclose(np.asarray(s_r.cm_pkts.counts),
                               np.asarray(s_d.cm_pkts.counts))
    np.testing.assert_allclose(np.asarray(s_r.drop_causes),
                               np.asarray(s_d.drop_causes))
    np.testing.assert_allclose(np.asarray(s_r.dscp_bytes),
                               np.asarray(s_d.dscp_bytes))
    np.testing.assert_allclose(np.asarray(s_r.syn.rate),
                               np.asarray(s_d.syn.rate))
    np.testing.assert_allclose(np.asarray(s_r.synack),
                               np.asarray(s_d.synack))
    got_r = {tuple(w) for w, v in zip(np.asarray(s_r.heavy.words),
                                      np.asarray(s_r.heavy.valid)) if v}
    got_d = {tuple(w) for w, v in zip(np.asarray(s_d.heavy.words),
                                      np.asarray(s_d.heavy.valid)) if v}
    assert got_r == got_d


@needs_jax
@pytest.mark.parametrize("family", sorted(CAPS_FAMILIES))
@pytest.mark.parametrize("feed", ["steady", "flood"])
def test_resident_ring_matches_dense_ingest(family, feed):
    """The device unpack of either lane family against the dense reference,
    on steady traffic and on a flood (continuation chunks in the narrow
    family, none in the wide)."""
    s_r, s_d, ring = _fold_both_ways(
        make_feed(n_batches=6, v6_every=29) if feed == "steady"
        else flood_feed(), caps=CAPS_FAMILIES[family](B))
    assert ring.dict_resets == 0
    if feed == "flood":
        assert (ring.continuations > 0) == (family == "narrow")
    _assert_exact_signals_match(s_r, s_d)
    # rtt/dns ride range codes: total mass identical, values shift at most
    # one log bucket (code error 1/256 < the ~1.6% bucket width)
    for hist in ("hist_rtt", "hist_dns"):
        hr = np.asarray(getattr(s_r, hist).counts)
        hd = np.asarray(getattr(s_d, hist).counts)
        assert hr.sum() == pytest.approx(hd.sum())
        # mass moved = half the L1 distance; each moved record shifts <= 1
        # bucket, so cumulative sums differ by at most the moved mass at
        # any prefix — and the moved mass is bounded by total mass
        cum = np.abs(np.cumsum(hr) - np.cumsum(hd))
        assert cum.max() <= hd.sum()


def test_second_epoch_is_mostly_hot():
    feed = make_feed(n_batches=10, n_distinct=64)
    caps = flowpack.default_resident_caps(B)
    kd = flowpack.KeyDict(1 << 12)
    per_batch = []
    for events, feats in feed:
        buf, consumed = flowpack.pack_resident(events, B, kd, caps, **feats)
        assert consumed == len(events)
        per_batch.append((int(buf[1]) + int(buf[2])) / len(events))
    # warmup batches insert the key universe; once the dictionary is warm,
    # repeats dominate and the newkey+spill lanes go quiet (the Zipf tail
    # still surfaces the odd first-seen rank — that's the workload)
    assert max(per_batch[6:]) < 0.05, per_batch
    kd.close()


def test_continuation_covers_every_row():
    # tiny lanes force multi-chunk packing; every row must be consumed
    # exactly once across chunks and the dictionary learns monotonically
    caps = flowpack.ResidentCaps(dns=8, drop=8, nk=8, spill=4)
    kd = flowpack.KeyDict(1 << 12)
    feed = make_feed(n_batches=1, n_distinct=400)
    events, feats = feed[0]
    start, chunks = 0, 0
    counts = []
    while start < len(events):
        buf, consumed = flowpack.pack_resident(events, B, kd, caps,
                                               start=start, **feats)
        assert consumed > 0
        start += consumed
        chunks += 1
        counts.append(kd.count())
    assert chunks > 1                      # the lanes really did fill
    assert counts == sorted(counts)        # no rollback, ever
    assert kd.count() == counts[-1] > 8    # learned past one chunk's nk cap
    kd.close()


@needs_jax
def test_continuation_ring_stays_correct():
    caps = flowpack.ResidentCaps(dns=8, drop=8, nk=8, spill=4)
    s_r, s_d, ring = _fold_both_ways(make_feed(n_batches=4, n_distinct=300),
                                     caps=caps)
    assert ring.continuations > 0
    _assert_exact_signals_match(s_r, s_d)


@needs_jax
def test_dict_full_resets_and_stays_correct():
    # slot_cap smaller than the key universe: the ring must roll the
    # dictionary epoch and keep folding correctly
    feed = make_feed(n_batches=6, n_distinct=500, seed=11)
    s_r, s_d, ring = _fold_both_ways(feed, slot_cap=128)
    assert ring.dict_resets > 0
    _assert_exact_signals_match(s_r, s_d)


def test_same_key_twice_in_one_batch_single_slot():
    caps = flowpack.default_resident_caps(B)
    kd = flowpack.KeyDict(1 << 12)
    feed = make_feed(n_batches=1, n_distinct=4, flows_per_eviction=64)
    events, feats = feed[0]
    # duplicate the whole batch back to back: every key repeats
    ev2 = np.concatenate([events, events])
    buf, consumed = flowpack.pack_resident(ev2, B, kd, caps)
    assert consumed == len(ev2)
    assert kd.count() <= 4 + 1  # one slot per distinct key
    kd.close()


def test_slot_cap_bounds():
    with pytest.raises(ValueError):
        flowpack.KeyDict(1 << 21)  # 20-bit slot ids
    with pytest.raises(ValueError):
        flowpack.KeyDict(0)


def test_buf_len_matches_layout():
    caps = flowpack.ResidentCaps(dns=16, drop=8, nk=4, spill=2)
    assert flowpack.resident_buf_len(32, caps) == (
        4 + 32 * 3 + 16 + 8 * 2 + 4 * 11 + 2 * 20)


# --- lane-sharded resident feed (single device, SKETCH_PACK_THREADS) ---


def _fold_lanes(feed, lanes, slot_cap=1 << 12, caps=None):
    """Fold `feed` through the LANE-SHARDED resident ring on one device
    (n_shards=1, L lanes — the SKETCH_PACK_THREADS path)."""
    import jax

    from netobserv_tpu.sketch import state as sk
    from netobserv_tpu.sketch.staging import ShardedResidentStagingRing

    bpl = B // lanes
    caps = caps or flowpack.default_resident_caps(bpl)
    cfg = sk.SketchConfig()
    ring = ShardedResidentStagingRing(
        B, 1, sk.make_ingest_resident_lanes_fn(bpl, caps, lanes, slot_cap),
        key_tables=jax.device_put(sk.init_key_tables(lanes, slot_cap)),
        put=jax.device_put, caps=caps, slot_cap=slot_cap,
        pack_threads=lanes, lanes=lanes)
    s = sk.init_state(cfg)
    for events, feats in feed:
        s = ring.fold(s, events, **feats)
    ring.drain()
    jax.block_until_ready(s)
    return s, ring


@needs_jax
def test_lane_sharded_matches_unsharded_resident():
    """Single-device lane-sharded resident ingest == the unsharded resident
    ingest on the same stream: order-independent sketches (CM planes, HLL
    registers, totals) are bit-identical, heavy-hitter recall matches, and
    each lane's device key table matches the keys its dictionary assigned."""
    import jax

    from netobserv_tpu.ops import hll

    feed = make_feed(n_batches=6, n_distinct=250, v6_every=23)
    s_single, _, ring_single = _fold_both_ways(feed)
    s_lanes, ring = _fold_lanes(feed, lanes=4)
    assert ring.continuations == 0  # default caps hold the whole stream

    for f in ("total_records", "total_bytes", "total_drop_bytes",
              "total_drop_packets", "quic_records", "nat_records"):
        assert float(getattr(s_lanes, f)) == pytest.approx(
            float(getattr(s_single, f))), f
    np.testing.assert_allclose(np.asarray(s_lanes.cm_bytes.counts),
                               np.asarray(s_single.cm_bytes.counts))
    np.testing.assert_allclose(np.asarray(s_lanes.cm_pkts.counts),
                               np.asarray(s_single.cm_pkts.counts))
    np.testing.assert_array_equal(np.asarray(s_lanes.hll_src.regs),
                                  np.asarray(s_single.hll_src.regs))
    assert float(hll.estimate(s_lanes.hll_src.regs)) == pytest.approx(
        float(hll.estimate(s_single.hll_src.regs)))
    # 250 distinct keys << topk slots: BOTH tables hold every key (recall 1)
    got_l = {tuple(w) for w, v in zip(np.asarray(s_lanes.heavy.words),
                                      np.asarray(s_lanes.heavy.valid)) if v}
    got_s = {tuple(w) for w, v in zip(np.asarray(s_single.heavy.words),
                                      np.asarray(s_single.heavy.valid)) if v}
    assert got_l == got_s

    # key-table contract per lane: slot i of lane L's device table holds the
    # i-th DISTINCT key first seen in lane L's row slice, in stream order
    # (the dictionary assigns slots sequentially; the new-key lane defines
    # them on device before any hot row references them)
    from netobserv_tpu.model.columnar import pack_key_words
    tables = np.asarray(ring.key_tables)  # (lanes * slot_cap, 10)
    assert tables.shape == (ring.n_regions * ring.slot_cap, 10)
    for lane in range(ring.n_regions):
        expected: dict[bytes, int] = {}
        for events, _feats in feed:
            n = len(events)
            lo = n * lane // ring.n_regions
            hi = n * (lane + 1) // ring.n_regions
            for kw in pack_key_words(events["key"][lo:hi]):
                expected.setdefault(kw.tobytes(), len(expected))
        assert ring.kdicts[lane].count() == len(expected)
        for kb, slot in expected.items():
            assert tables[lane * ring.slot_cap + slot].tobytes() == kb


@needs_jax
def test_lane_ring_exhausted_region_masks_stale_buffer():
    """Continuation chunks with UNEVEN lane progress: the exhausted lane's
    region keeps the previous chunk's bytes and is masked empty via the
    strided validity zeroing (flowpack.zero_resident_region) — results must
    still match the dense ingest exactly (a stale row leaking through the
    mask would break every total)."""
    caps = flowpack.ResidentCaps(dns=8, drop=8, nk=64, spill=2)
    feed = make_feed(n_batches=3, n_distinct=100)
    for events, _ in feed:
        # second half of every batch: packets over the 11-bit hot budget
        # force the spill lane (cap 2) -> lane 1 needs many continuation
        # chunks while lane 0 finishes in one -> exhausted-region path
        events["stats"]["packets"][len(events) // 2:] = 0x900
    import jax

    from netobserv_tpu.sketch import state as sk

    s_lanes, ring = _fold_lanes(feed, lanes=2, caps=caps)
    assert ring.continuations > 0

    dense_fn = sk.make_ingest_dense_fn(with_token=True)
    s_d = sk.init_state(sk.SketchConfig())
    for events, feats in feed:
        db = flowpack.pack_dense(events, batch_size=B, **feats)
        s_d, _ = dense_fn(s_d, jax.device_put(db.reshape(-1)))
    jax.block_until_ready(s_d)
    _assert_exact_signals_match(s_lanes, s_d)


@needs_jax
def test_zero_resident_region_masks_garbage_exactly():
    """flowpack.zero_resident_region on an all-0xFF buffer must make the
    device unpack + ingest behave exactly like a fully zeroed region (the
    pin for replacing the full memset with strided validity writes)."""
    import jax

    from netobserv_tpu.sketch import state as sk

    bs = 32
    caps = flowpack.ResidentCaps(dns=4, drop=4, nk=4, spill=2)
    total = flowpack.resident_buf_len(bs, caps)
    garbage = np.full(total, 0xFFFFFFFF, np.uint32)
    flowpack.zero_resident_region(garbage, bs, caps)
    zeros = np.zeros(total, np.uint32)
    cfg = sk.SketchConfig(cm_width=1 << 10, topk=16, ewma_buckets=32,
                          hll_precision=6, perdst_buckets=32,
                          perdst_precision=4, persrc_buckets=32,
                          persrc_precision=4, hist_buckets=64)
    fn = sk.make_ingest_resident_lanes_fn(bs, caps, 1, 64, donate=False)
    table = jax.device_put(sk.init_key_tables(1, 64))
    s_g, t_g, _ = fn(sk.init_state(cfg), table, jax.device_put(garbage))
    s_z, t_z, _ = fn(sk.init_state(cfg), table, jax.device_put(zeros))
    np.testing.assert_array_equal(np.asarray(t_g), np.asarray(t_z))
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), s_g, s_z)


def _lane_ring(lanes, caps=None, slot_cap=1 << 12):
    import jax

    from netobserv_tpu.sketch import state as sk
    from netobserv_tpu.sketch.staging import ShardedResidentStagingRing

    bpl = B // lanes
    caps = caps or flowpack.default_resident_caps(bpl)
    ring = ShardedResidentStagingRing(
        B, 1, sk.make_ingest_resident_lanes_fn(bpl, caps, lanes, slot_cap),
        key_tables=jax.device_put(sk.init_key_tables(lanes, slot_cap)),
        put=jax.device_put, caps=caps, slot_cap=slot_cap,
        pack_threads=lanes, lanes=lanes)
    for buf in ring._bufs:
        buf[:] = 0      # np.empty: make the slot images comparable
    return ring


@needs_jax
def test_carry_fold_leaves_region_suffixes_and_loses_no_row():
    """`fold(carry=True)` dispatches a chunk ONCE and hands back each
    region's suffix behind its full lane; offered again (as the pending
    buffer offers them, in arrival order ahead of newer rows) every row is
    folded exactly once: the state equals the dense ingest's, and no
    continuation chunk was shipped."""
    import jax

    from netobserv_tpu.sketch import state as sk

    caps = flowpack.ResidentCaps(dns=8, drop=8, nk=64, spill=2)
    feed = make_feed(n_batches=3, n_distinct=100)
    for events, _ in feed:
        events["stats"]["packets"][len(events) // 2:] = 0x900  # spill rows
    ring = _lane_ring(2, caps=caps)
    s = sk.init_state(sk.SketchConfig())
    # the rows waiting for a fold, and their lane rows: what an earlier
    # fold left, then the next eviction behind it
    held_ev = np.zeros(0, binfmt.FLOW_EVENT_DTYPE)
    held = {k: v[:0] for k, v in feed[0][1].items()}
    arrivals = list(feed)
    folds = 0
    while arrivals or len(held_ev):
        if arrivals:
            events, feats = arrivals.pop(0)
            held_ev = np.concatenate([held_ev, events])
            held = {k: np.concatenate([held[k], feats[k]]) for k in held}
        n = min(len(held_ev), B)
        s, left = ring.fold(s, held_ev[:n], carry=True,
                            **{k: v[:n] for k, v in held.items()})
        folds += 1
        assert folds < 400, "a carry fold consumes at least a row"
        assert left == sorted(left)
        assert all(0 <= lo < hi <= n for lo, hi in left)
        rows = np.concatenate([np.arange(lo, hi) for lo, hi in left]
                              + [np.arange(n, len(held_ev))]).astype(int)
        held_ev = held_ev[rows]
        held = {k: v[rows] for k, v in held.items()}
    ring.drain()
    assert ring.continuations == 0 and ring.carried_rows > 0
    assert sum(ring.superbatch_folds.values()) == folds == ring.chunks

    dense_fn = sk.make_ingest_dense_fn(with_token=True)
    s_d = sk.init_state(sk.SketchConfig())
    for events, feats in feed:
        db = flowpack.pack_dense(events, batch_size=B, **feats)
        s_d, _ = dense_fn(s_d, jax.device_put(db.reshape(-1)))
    jax.block_until_ready(s_d)
    _assert_exact_signals_match(s, s_d)
    assert float(s.total_records) == sum(len(e) for e, _ in feed)


@needs_jax
def test_carry_fold_with_nothing_left_is_the_plain_fold():
    """A chunk whose regions take all their rows: `carry` changes nothing —
    the same slot images, the same dispatches, the same state and tables."""
    import jax

    from netobserv_tpu.sketch import state as sk

    feed = make_feed(n_batches=5, n_distinct=250, v6_every=23)
    rings, states = [], []
    for carry in (False, True):
        ring = _lane_ring(4)
        s = sk.init_state(sk.SketchConfig())
        for events, feats in feed:
            if carry:
                s, left = ring.fold(s, events, carry=True, **feats)
                assert left == []
            else:
                s = ring.fold(s, events, **feats)
        ring.drain()
        rings.append(ring)
        states.append(jax.block_until_ready(s))
    plain, carried = rings
    assert carried.carried_rows == 0 == carried.continuations
    assert carried.superbatch_folds == plain.superbatch_folds
    assert carried.chunks == plain.chunks == len(feed)
    for a, b in zip(plain._bufs, carried._bufs):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(np.asarray(plain.key_tables),
                                  np.asarray(carried.key_tables))
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), states[0], states[1])


# --- the stored layout of the key tables (rows = lane * slot_cap + slot) ---

#: (lanes an entry folds, lanes the shared table array holds): one lane, the
#: x1 entry's 8 of 8, and the ladder case — an x1 entry's 8 leading lanes of
#: the 32 the x4 entry needs
LAYOUT_CASES = {"one_lane": (1, 1), "eight_lanes": (8, 8),
                "eight_of_32_lanes": (8, 32)}


def _np_decode_regions(flat, table, bpl, caps, n_lanes, slot_cap):
    """NumPy reference of `sketch.state.resident_lane_arrays` on a table
    stored (lanes * slot_cap, 10): every region's defined new-key rows land
    at row lane * slot_cap + slot FIRST, then each region's hot rows gather
    from their own lane (a row's slot field held inside the lane), the
    sparse dns / drop lanes scatter onto their rows, and the full-width
    spill rows follow. Returns (arrays, table)."""
    words = flowpack.resident_buf_len(bpl, caps)
    hot_off = flowpack.RESIDENT_HDR
    dns_off = hot_off + bpl * flowpack.HOT_WORDS
    drop_off = dns_off + caps.dns
    nk_off = drop_off + caps.drop * 2
    spill_off = nk_off + caps.nk * flowpack.NK_WORDS
    table = table.copy()
    regions = [flat[i * words:(i + 1) * words] for i in range(n_lanes)]
    for lane, r in enumerate(regions):
        nk = r[nk_off:spill_off].reshape(caps.nk, flowpack.NK_WORDS)
        for row in nk[(nk[:, 0] >> 31) != 0]:
            table[lane * slot_cap + int(row[0] & 0xFFFFF)] = row[1:]
    cols = {}
    for lane, r in enumerate(regions):
        hot = r[hot_off:dns_off].reshape(bpl, flowpack.HOT_WORDS)
        w0, w2 = hot[:, 0], hot[:, 2]
        slots = np.minimum(w0 & 0xFFFFF, slot_cap - 1).astype(np.int64)
        dns = np.zeros(bpl, np.int32)
        for e in r[dns_off:drop_off]:
            if (e >> 16) < bpl:
                dns[e >> 16] += np.int32((e & 0xFFF) << ((e >> 12) & 0xF))
        d_bytes, d_pkts, d_cause = (np.zeros(bpl, np.int32) for _ in "123")
        for a, b in r[drop_off:nk_off].reshape(caps.drop, 2):
            if (a >> 16) < bpl:
                d_bytes[a >> 16] += np.int32(b & 0xFFFF)
                d_pkts[a >> 16] += np.int32(b >> 16)
                d_cause[a >> 16] = max(d_cause[a >> 16], np.int32(a & 0xFFFF))
        sp = r[spill_off:].reshape(caps.spill, flowpack.DENSE_WORDS)
        comp = {
            "keys": (table[lane * slot_cap + slots], sp[:, :10]),
            "bytes": (hot[:, 1].view(np.float32), sp[:, 10].view(np.float32)),
            "packets": (w2 & 0x7FF, sp[:, 11]),
            "rtt_us": (((w0 >> 20) & 0xFF) << (2 * ((w0 >> 28) & 0x7)),
                       sp[:, 12]),
            "dns_latency_us": (dns, sp[:, 13]),
            "valid": ((w0 >> 31) != 0, sp[:, 14] != 0),
            "sampling": (np.full(bpl, r[0]), sp[:, 15]),
            "tcp_flags": ((w2 >> 11) & 0x7FF, sp[:, 16] & 0xFFFF),
            "dscp": ((w2 >> 22) & 0x3F, (sp[:, 16] >> 16) & 0xFF),
            "markers": (w2 >> 28, sp[:, 16] >> 24),
            "drop_bytes": (d_bytes, sp[:, 17] & 0xFFFF),
            "drop_packets": (d_pkts, sp[:, 17] >> 16),
            "drop_cause": (d_cause, sp[:, 18] & 0xFFFF),
        }
        for k, parts in comp.items():
            cols.setdefault(k, []).extend(parts)
    kinds = {"keys": np.uint32, "bytes": np.float32, "valid": np.bool_}
    return ({k: np.concatenate(v).astype(kinds.get(k, np.int32))
             for k, v in cols.items()}, table)


@needs_jax
@pytest.mark.parametrize("case", sorted(LAYOUT_CASES))
def test_key_table_layout_contract(case):
    """The key tables are ONE (lanes * slot_cap, 10) array, lane `l`'s slot
    `s` in row `l * slot_cap + s`. The trap this pins: an UNDEFINED new-key
    row must index past the whole array — `lane * slot_cap + slot_cap` is
    slot 0 of the next lane, occupied here — and a hot row without the
    valid bit, whose slot field is garbage, must still read its own lane.
    Arrays and table equal the NumPy reference bit for bit."""
    import jax

    from netobserv_tpu.sketch import state as sk

    n_lanes, table_lanes = LAYOUT_CASES[case]
    bpl, slot_cap = 32, 64
    caps = flowpack.ResidentCaps(dns=8, drop=8, nk=8, spill=4)
    words = flowpack.resident_buf_len(bpl, caps)
    rng = np.random.default_rng(33)
    # every slot of every lane occupied: a stray write shows anywhere
    table0 = rng.integers(1, 1 << 32, (table_lanes * slot_cap, 10),
                          dtype=np.uint32)
    flat = np.empty(n_lanes * words, np.uint32)
    n_defined = []
    for lane in range(n_lanes):
        # 2..6 distinct keys a lane, under caps.nk: the rest of the new-key
        # lane stays UNDEFINED, in the last lane and in every lane before it
        (events, feats), = make_feed(n_batches=1, seed=40 + lane, v6_every=5)
        events["key"] = events["key"][np.arange(len(events)) % (2 + lane % 5)]
        kd = flowpack.KeyDict(slot_cap)
        region = flat[lane * words:(lane + 1) * words]
        # an earlier fold took slots 0..2: this fold defines none of them,
        # so slot 0 of the NEXT lane is occupied and nothing rewrites it
        (older, _), = make_feed(n_batches=1, seed=90 + lane)
        flowpack.pack_resident(older[:3], bpl, kd, caps, out=region)
        assert kd.count() == 3
        _, took = flowpack.pack_resident(events[:bpl - 3], bpl, kd, caps,
                                         out=region, **{
                                             k: v[:bpl - 3]
                                             for k, v in feats.items()})
        assert took == bpl - 3
        n_defined.append(kd.count() - 3)
        kd.close()
        # a hot row past the packed ones: no valid bit, slot field all ones
        region[flowpack.RESIDENT_HDR + (bpl - 1) * flowpack.HOT_WORDS] = (
            0x000FFFFF)
    assert all(0 < n < caps.nk for n in n_defined)

    want, want_table = _np_decode_regions(flat, table0, bpl, caps, n_lanes,
                                          slot_cap)
    got, got_table = jax.jit(
        lambda f, t: sk.resident_lane_arrays(f, t, bpl, caps, n_lanes,
                                             slot_cap))(flat, table0)
    got_table = np.asarray(got_table)
    assert got_table.shape == (table_lanes * slot_cap, sk.KEY_WORDS)
    np.testing.assert_array_equal(got_table, want_table)
    # the writes are the defined new keys alone: slots 3..3+n-1 of each lane
    changed = np.flatnonzero((got_table != table0).any(axis=1))
    assert changed.tolist() == [lane * slot_cap + 3 + s
                                for lane, n in enumerate(n_defined)
                                for s in range(n)]
    assert set(got) == set(want)
    for k in want:
        assert np.asarray(got[k]).dtype == want[k].dtype, k
        np.testing.assert_array_equal(np.asarray(got[k]), want[k], err_msg=k)
    assert int(want["valid"].sum()) == n_lanes * (bpl - 3)
