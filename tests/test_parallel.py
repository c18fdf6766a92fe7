"""Multi-device sharded ingest + ICI merge must agree with single-device ingest
of the same stream (the distributed path is exact, not approximate — the same
guarantee the reference gets from per-CPU map merging, `pkg/tracer/tracer.go`
eviction merge)."""

import numpy as np
import pytest

import tests.conftest  # noqa: F401
import jax
import jax.numpy as jnp

# 8-virtual-device mesh compile-and-EXECUTE tests dominate tier-1 wall
# time (VERDICT weak #4): slow tier, with `make dryrun` covering multichip
# sharding in the default gate. The two *_has_no_collectives HLO-text
# checks stay UN-marked: they only lower (no device execution) and they
# pin the CLAUDE.md steady-state no-collectives invariant — that guard
# must stay inside the tier-1 keep-it-green loop. So does ONE small (2, 2)
# case of every width-sharded answer comparison below (PR 34: four of the
# eight devices, seconds each); their larger meshes stay in the slow tier.
slow = pytest.mark.slow


from netobserv_tpu.parallel import make_mesh, MeshSpec, merge as pmerge
from netobserv_tpu.sketch import state as sk

KW = 10
CFG = sk.SketchConfig(cm_depth=3, cm_width=1 << 10, hll_precision=8,
                      perdst_buckets=64, perdst_precision=5, topk=32,
                      hist_buckets=128, ewma_buckets=64)


def make_arrays(n, rng, n_distinct=200):
    universe = rng.integers(0, 2**32, (n_distinct, KW), dtype=np.uint32)
    ids = rng.integers(0, n_distinct, n)
    return {
        "keys": universe[ids],
        "bytes": rng.integers(1, 10_000, n).astype(np.float32),
        "packets": rng.integers(1, 10, n).astype(np.int32),
        "rtt_us": rng.integers(0, 5_000, n).astype(np.int32),
        "dns_latency_us": rng.integers(0, 100, n).astype(np.int32),
        "sampling": np.zeros(n, np.int32),
        "valid": np.ones(n, np.bool_),
        # feature lane (flags/dscp/markers/drops) — nonzero so the dict and
        # dense transports must agree on the new signal planes too
        "tcp_flags": rng.integers(0, 1 << 9, n).astype(np.int32),
        "dscp": rng.integers(0, 64, n).astype(np.int32),
        "markers": rng.integers(0, 16, n).astype(np.int32),
        "drop_bytes": rng.integers(0, 100, n).astype(np.int32),
        "drop_packets": rng.integers(0, 3, n).astype(np.int32),
        "drop_cause": rng.integers(0, 80, n).astype(np.int32),
    }


def single_device_report(arrays, cfg=CFG):
    s = sk.init_state(cfg)
    s = sk.ingest(s, {k: jnp.asarray(v) for k, v in arrays.items()})
    _, report = sk.roll_window(s, cfg)
    return report


@pytest.mark.parametrize("mesh_shape",
                         [pytest.param((8, 1), marks=slow),
                          pytest.param((4, 2), marks=slow),
                          pytest.param((2, 4), marks=slow), (2, 2)])
def test_sharded_matches_single_device(mesh_shape):
    """Exactness: with a key universe that fits every local table, the merged
    distributed report equals the single-device report bit-for-bit. (With more
    keys than table slots, distributed top-K is a union-of-local-top-K
    candidate heuristic — covered by test_topk_recall_skewed below.)"""
    ndata, nsk = mesh_shape
    if ndata * nsk > len(jax.devices()):
        pytest.skip("not enough devices")
    rng = np.random.default_rng(42)
    arrays = make_arrays(ndata * 128, rng, n_distinct=24)
    # "fits every table": a key has 8 candidate slots, so 24 keys need more
    # than CFG's 32 slots for EVERY one of them to find a slot on one device
    cfg = CFG._replace(topk=128)

    ref = single_device_report(arrays, cfg)

    mesh = make_mesh(MeshSpec(data=ndata, sketch=nsk))
    dist = pmerge.init_dist_state(cfg, mesh)
    ingest_fn = pmerge.make_sharded_ingest_fn(mesh, cfg)
    merge_fn = pmerge.make_merge_fn(mesh, cfg)
    dist = ingest_fn(dist, pmerge.shard_batch(mesh, arrays))
    dist, report = merge_fn(dist)

    assert float(report.total_records) == float(ref.total_records)
    assert float(report.total_bytes) == pytest.approx(
        float(ref.total_bytes), rel=1e-6)
    assert float(report.distinct_src) == pytest.approx(
        float(ref.distinct_src), rel=1e-6)
    np.testing.assert_allclose(np.asarray(report.rtt_quantiles_us),
                               np.asarray(ref.rtt_quantiles_us), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(report.dns_quantiles_us),
                               np.asarray(ref.dns_quantiles_us), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(report.per_dst_cardinality),
                               np.asarray(ref.per_dst_cardinality), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(report.per_src_fanout),
                               np.asarray(ref.per_src_fanout), rtol=1e-6)
    # feature-lane signals cross the ICI merge exactly too
    for field in ("syn_rate", "synack_rate", "drop_causes", "dscp_bytes"):
        np.testing.assert_allclose(np.asarray(getattr(report, field)),
                                   np.asarray(getattr(ref, field)),
                                   rtol=1e-6, err_msg=field)
    for field in ("total_drop_bytes", "total_drop_packets", "quic_records",
                  "nat_records"):
        assert float(getattr(report, field)) == pytest.approx(
            float(getattr(ref, field)), rel=1e-6), field
    # top-K: same key set, same estimates
    ref_set = {tuple(w) for w, v in zip(np.asarray(ref.heavy.words),
                                        np.asarray(ref.heavy.valid)) if v}
    got_set = {tuple(w) for w, v in zip(np.asarray(report.heavy.words),
                                        np.asarray(report.heavy.valid)) if v}
    assert ref_set == got_set
    ref_counts = {tuple(w): float(c) for w, c, v in zip(
        np.asarray(ref.heavy.words), np.asarray(ref.heavy.counts),
        np.asarray(ref.heavy.valid)) if v}
    got_counts = {tuple(w): float(c) for w, c, v in zip(
        np.asarray(report.heavy.words), np.asarray(report.heavy.counts),
        np.asarray(report.heavy.valid)) if v}
    for k in ref_counts:
        assert got_counts[k] == pytest.approx(ref_counts[k], rel=1e-5)


# inverse transport: the shared single-site packer (layout twin of
# flowpack.cc fp_pack_dense)
arrays_to_dense = sk.arrays_to_dense


@pytest.mark.parametrize("mesh_shape",
                         [pytest.param((8, 1), marks=slow),
                          pytest.param((4, 2), marks=slow), (2, 2)])
def test_sharded_dense_matches_dict_transport(mesh_shape):
    """The dense (single-transfer) sharded ingest must produce the same
    distributed state as the six-array dict transport — same ingest math,
    different wire format."""
    ndata, nsk = mesh_shape
    if ndata * nsk > len(jax.devices()):
        pytest.skip("not enough devices")
    rng = np.random.default_rng(7)
    arrays = make_arrays(ndata * 128, rng, n_distinct=24)

    mesh = make_mesh(MeshSpec(data=ndata, sketch=nsk))
    ingest_dict = pmerge.make_sharded_ingest_fn(mesh, CFG, donate=False)
    ingest_dense = pmerge.make_sharded_ingest_fn(mesh, CFG, donate=False,
                                                 dense=True)
    d1 = ingest_dict(pmerge.init_dist_state(CFG, mesh),
                     pmerge.shard_batch(mesh, arrays))
    d2 = ingest_dense(pmerge.init_dist_state(CFG, mesh),
                      pmerge.shard_dense(mesh, arrays_to_dense(arrays)))
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), d1, d2)


@slow
def test_topk_recall_skewed():
    """On zipf-skewed traffic (the realistic heavy-hitter regime) the merged
    distributed table recalls the true global top keys."""
    ndata, nsk = 4, 2
    rng = np.random.default_rng(7)
    n, n_distinct = ndata * 2048, 1000
    universe = rng.integers(0, 2**32, (n_distinct, KW), dtype=np.uint32)
    ranks = np.minimum(rng.zipf(1.4, n) - 1, n_distinct - 1)
    arrays = {
        "keys": universe[ranks],
        "bytes": rng.integers(100, 1500, n).astype(np.float32),
        "packets": np.ones(n, np.int32),
        "rtt_us": np.zeros(n, np.int32),
        "dns_latency_us": np.zeros(n, np.int32),
        "sampling": np.zeros(n, np.int32),
        "valid": np.ones(n, np.bool_),
    }
    exact: dict[int, float] = {}
    for r, b in zip(ranks, arrays["bytes"]):
        exact[r] = exact.get(r, 0.0) + float(b)
    check_k = 16
    true_top = sorted(exact, key=exact.get, reverse=True)[:check_k]

    mesh = make_mesh(MeshSpec(data=ndata, sketch=nsk))
    dist = pmerge.init_dist_state(CFG, mesh)
    ingest_fn = pmerge.make_sharded_ingest_fn(mesh, CFG)
    merge_fn = pmerge.make_merge_fn(mesh, CFG)
    dist = ingest_fn(dist, pmerge.shard_batch(mesh, arrays))
    dist, report = merge_fn(dist)

    got = {tuple(w) for w, v in zip(np.asarray(report.heavy.words),
                                    np.asarray(report.heavy.valid)) if v}
    hits = sum(tuple(universe[t]) in got for t in true_top)
    assert hits / check_k >= 0.95, f"recall {hits}/{check_k}"


@slow
def test_multiple_windows_and_state_reset():
    mesh = make_mesh(MeshSpec(data=4, sketch=2))
    rng = np.random.default_rng(1)
    dist = pmerge.init_dist_state(CFG, mesh)
    ingest_fn = pmerge.make_sharded_ingest_fn(mesh, CFG)
    merge_fn = pmerge.make_merge_fn(mesh, CFG)
    for w in range(3):
        arrays = make_arrays(4 * 64, rng)
        dist = ingest_fn(dist, pmerge.shard_batch(mesh, arrays))
        dist, report = merge_fn(dist)
        assert int(report.window) == w
        assert float(report.total_records) == 4 * 64
    # after reset, partial counters are zero again
    assert float(jnp.sum(dist.cm_bytes.counts)) == 0.0
    assert float(jnp.sum(dist.total_records)) == 0.0


@slow
def test_ddos_alarm_travels_through_merge():
    mesh = make_mesh(MeshSpec(data=8, sketch=1))
    rng = np.random.default_rng(2)
    dist = pmerge.init_dist_state(CFG, mesh)
    ingest_fn = pmerge.make_sharded_ingest_fn(mesh, CFG)
    merge_fn = pmerge.make_merge_fn(mesh, CFG)
    calm = make_arrays(8 * 64, rng)
    for _ in range(4):
        dist = ingest_fn(dist, pmerge.shard_batch(mesh, calm))
        dist, report = merge_fn(dist)
        assert not bool((report.ddos_z > 6.0).any())
    # attack: all traffic to one destination, 100x volume
    attack = make_arrays(8 * 64, rng, n_distinct=1)
    attack["bytes"] = np.full(8 * 64, 1e6, np.float32)
    dist = ingest_fn(dist, pmerge.shard_batch(mesh, attack))
    dist, report = merge_fn(dist)
    assert bool((report.ddos_z > 6.0).any())


@pytest.mark.parametrize("mesh_shape",
                         [pytest.param((8, 1), marks=slow),
                          pytest.param((4, 2), marks=slow), (2, 2)])
def test_staging_ring_sharded_dense_token(mesh_shape):
    """The production distributed exporter combination — DenseStagingRing +
    sharded dense ingest with reuse tokens + shard_dense placement — must
    match the dict-transport sharded ingest across multiple folds (slot reuse
    under async dispatch included)."""
    from netobserv_tpu.datapath import flowpack
    from netobserv_tpu.model import binfmt
    from netobserv_tpu.sketch.staging import DenseStagingRing

    ndata, nsk = mesh_shape
    if ndata * nsk > len(jax.devices()):
        pytest.skip("not enough devices")
    rng = np.random.default_rng(11)
    bs = ndata * 64

    def random_batch(n):
        ev = np.zeros(n, dtype=binfmt.FLOW_EVENT_DTYPE)
        ev["key"]["src_ip"] = rng.integers(0, 256, (n, 16), dtype=np.uint8)
        ev["key"]["dst_ip"] = rng.integers(0, 256, (n, 16), dtype=np.uint8)
        ev["key"]["src_port"] = rng.integers(1, 1 << 16, n)
        ev["key"]["dst_port"] = rng.integers(1, 1 << 16, n)
        ev["key"]["proto"] = rng.integers(0, 256, n)
        ev["stats"]["bytes"] = rng.integers(1, 10_000, n)
        ev["stats"]["packets"] = rng.integers(1, 10, n)
        extra = np.zeros(n, dtype=binfmt.EXTRA_REC_DTYPE)
        extra["rtt_ns"] = rng.integers(0, 5_000, n, dtype=np.uint64) * 1000
        dns = np.zeros(n, dtype=binfmt.DNS_REC_DTYPE)
        dns["latency_ns"] = rng.integers(0, 100, n, dtype=np.uint64) * 1000
        return ev, extra, dns

    batches = [random_batch(bs) for _ in range(9)]

    mesh = make_mesh(MeshSpec(data=ndata, sketch=nsk))
    ingest_tok = pmerge.make_sharded_ingest_fn(mesh, CFG, donate=False,
                                               dense=True, with_token=True)
    ring = DenseStagingRing(bs, ingest_tok,
                            put=lambda buf: pmerge.shard_dense(mesh, buf))
    s_ring = pmerge.init_dist_state(CFG, mesh)
    for ev, extra, dns in batches:
        s_ring = ring.fold(s_ring, ev, extra=extra, dns=dns)
    ring.drain()

    ingest_dict = pmerge.make_sharded_ingest_fn(mesh, CFG, donate=False)
    s_ref = pmerge.init_dist_state(CFG, mesh)
    for ev, extra, dns in batches:
        batch = flowpack.pack_events(ev, batch_size=bs, extra=extra, dns=dns)
        arrays = sk.batch_to_device(batch)
        s_ref = ingest_dict(s_ref, pmerge.shard_batch(mesh, arrays))
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), s_ring, s_ref)


@pytest.mark.parametrize("mesh_shape", [(4, 2), (2, 4)])
def test_steady_state_ingest_has_no_collectives(mesh_shape):
    """CLAUDE.md invariant, strengthened in round 3: the per-batch sharded
    ingest performs NO collectives on EITHER mesh axis — the owner-sharded
    Count-Min scores its own keys locally, and cross-shard reconciliation
    happens only at window roll. Checked against the compiled HLO."""
    ndata, nsk = mesh_shape
    if ndata * nsk > len(jax.devices()):
        pytest.skip("not enough devices")
    mesh = make_mesh(MeshSpec(data=ndata, sketch=nsk))
    ingest_fn = pmerge.make_sharded_ingest_fn(mesh, CFG, donate=False)
    rng = np.random.default_rng(3)
    arrays = pmerge.shard_batch(mesh, make_arrays(ndata * 64, rng))
    dist = pmerge.init_dist_state(CFG, mesh)
    hlo = ingest_fn.lower(dist, arrays).compile().as_text()
    for coll in ("all-reduce", "all-gather", "collective-permute",
                 "reduce-scatter", "all-to-all"):
        assert coll not in hlo, f"steady-state ingest contains {coll}"
    # the window roll DOES reconcile (sanity check the detector works)
    merge_fn = pmerge.make_merge_fn(mesh, CFG)
    hlo_roll = merge_fn.lower(dist).compile().as_text()
    assert any(c in hlo_roll for c in ("all-reduce", "all-gather"))


@pytest.mark.parametrize("mesh_shape",
                         [pytest.param((8, 1), marks=slow),
                          pytest.param((4, 2), marks=slow), (2, 2)])
def test_shard_dense_per_device_equivalent(mesh_shape):
    """Explicit per-device placement (N independent DMAs — the multi-chip
    feed shape) must produce the same global sharded array as the one-put
    shard_dense, and feed the sharded ingest identically."""
    ndata, nsk = mesh_shape
    if ndata * nsk > len(jax.devices()):
        pytest.skip("not enough devices")
    rng = np.random.default_rng(9)
    arrays = make_arrays(ndata * 64, rng, n_distinct=32)
    flat = arrays_to_dense(arrays)
    mesh = make_mesh(MeshSpec(data=ndata, sketch=nsk))
    a = pmerge.shard_dense(mesh, flat)
    b = pmerge.shard_dense_per_device(mesh, flat)
    assert a.sharding.is_equivalent_to(b.sharding, a.ndim)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    ing = pmerge.make_sharded_ingest_fn(mesh, CFG, donate=False, dense=True)
    d1 = ing(pmerge.init_dist_state(CFG, mesh), a)
    d2 = ing(pmerge.init_dist_state(CFG, mesh), b)
    jax.tree.map(lambda x, y: np.testing.assert_array_equal(
        np.asarray(x), np.asarray(y)), d1, d2)


@pytest.mark.parametrize("mesh_shape,lanes",
                         [pytest.param((8, 1), 1, marks=slow),
                          pytest.param((4, 2), 1, marks=slow),
                          pytest.param((4, 2), 2, marks=slow),
                          ((2, 2), 2)])
def test_sharded_resident_feed_matches_dense(mesh_shape, lanes):
    """The sharded RESIDENT feed (per-data-shard dictionaries + device key
    tables, ~15B/record) is a transport for the same math as the dense
    feed: identical global batches must produce identical merged reports —
    with pack lanes per shard too (SKETCH_PACK_THREADS on a mesh)."""
    from netobserv_tpu.datapath import flowpack
    from netobserv_tpu.model import binfmt
    from netobserv_tpu.sketch.staging import ShardedResidentStagingRing

    ndata, nsk = mesh_shape
    if ndata * nsk > len(jax.devices()):
        pytest.skip("not enough devices")
    mesh = make_mesh(MeshSpec(data=ndata, sketch=nsk))
    B = ndata * 128
    bpl = B // ndata // lanes
    caps = flowpack.default_resident_caps(bpl)

    # synthetic evictions with features (rtt + sparse dns/drops)
    from netobserv_tpu.datapath.replay import SyntheticFetcher
    fetcher = SyntheticFetcher(flows_per_eviction=B, n_distinct=300, seed=9)
    rng = np.random.default_rng(9)
    feeds = []
    for _ in range(5):
        ev = fetcher.lookup_and_delete()
        events, extra = ev.events[:B], ev.extra[:B]
        dn = np.zeros(len(events), binfmt.DNS_REC_DTYPE)
        dn["latency_ns"][rng.random(len(events)) < 0.05] = 700_000
        dr = np.zeros(len(events), binfmt.DROPS_REC_DTYPE)
        hit = rng.random(len(events)) < 0.02
        dr["bytes"][hit] = 500
        dr["packets"][hit] = 1
        feeds.append((events, dict(extra=extra, dns=dn, drops=dr)))

    # resident path
    ring = ShardedResidentStagingRing(
        B, ndata,
        pmerge.make_sharded_ingest_resident_fn(mesh, CFG, bpl, caps, 1 << 12,
                                               lanes=lanes),
        key_tables=pmerge.init_resident_tables(mesh, 1 << 12, lanes=lanes),
        put=lambda buf: pmerge.shard_dense(mesh, buf),
        caps=caps, slot_cap=1 << 12, lanes=lanes)
    dist_r = pmerge.init_dist_state(CFG, mesh)
    for events, feats in feeds:
        dist_r = ring.fold(dist_r, events, **feats)
    ring.drain()
    merge_fn = pmerge.make_merge_fn(mesh, CFG)
    dist_r, rep_r = merge_fn(dist_r)

    # dense path over the same batches
    ingest_dense = pmerge.make_sharded_ingest_fn(mesh, CFG, dense=True,
                                                 with_token=True)
    dist_d = pmerge.init_dist_state(CFG, mesh)
    for events, feats in feeds:
        db = flowpack.pack_dense(events, batch_size=B, **feats)
        dist_d, _tok = ingest_dense(dist_d, pmerge.shard_dense(
            mesh, db.reshape(-1)))
        jax.block_until_ready(dist_d)
    dist_d, rep_d = merge_fn(dist_d)
    jax.block_until_ready((rep_r, rep_d))

    assert float(rep_r.total_records) == float(rep_d.total_records)
    # totals accumulate in f32 and the two transports group/order the same
    # rows differently (continuation chunks, hot/spill lanes) — compare at
    # f32 resolution, like tests/test_resident.py does
    assert float(rep_r.total_bytes) == pytest.approx(
        float(rep_d.total_bytes))
    assert float(rep_r.total_drop_bytes) == pytest.approx(
        float(rep_d.total_drop_bytes))
    got_r = {tuple(w) for w, v in zip(np.asarray(rep_r.heavy.words),
                                      np.asarray(rep_r.heavy.valid)) if v}
    got_d = {tuple(w) for w, v in zip(np.asarray(rep_d.heavy.words),
                                      np.asarray(rep_d.heavy.valid)) if v}
    assert got_r == got_d


#: a geometry at which every kernel's static gate holds at the LOCAL width
#: of a two-way sketch axis (2^10): with use_pallas=True the owner-sharded
#: fold runs the factored Count-Min, the slot walk, the HLL and the signal
#: kernels (interpreted on the CPU)
KERNEL_CFG = sk.SketchConfig(cm_width=1 << 11, hll_precision=9, topk=128,
                             perdst_buckets=128, persrc_buckets=128,
                             hist_buckets=64, ewma_buckets=128,
                             use_pallas=True)


@pytest.mark.parametrize("mesh_shape,lanes",
                         [((8, 1), 1), ((4, 2), 1), ((4, 2), 2), ((2, 2), 4)])
def test_sharded_resident_ingest_has_no_collectives(mesh_shape, lanes):
    """The resident transport must not weaken the steady-state invariant:
    table scatter/gather are shard-local, so the compiled sharded resident
    ingest contains NO collectives on either mesh axis — including with
    pack LANES per shard (the per-lane unpack loop + table stack must stay
    purely local), and on the (2, 2) mesh with the KERNELS on (the forms an
    owner-sharded fold takes on a TPU)."""
    from netobserv_tpu.datapath import flowpack

    ndata, nsk = mesh_shape
    if ndata * nsk > len(jax.devices()):
        pytest.skip("not enough devices")
    mesh = make_mesh(MeshSpec(data=ndata, sketch=nsk))
    bpl = 64 // lanes
    caps = flowpack.default_resident_caps(bpl)
    cfg = KERNEL_CFG if mesh_shape == (2, 2) else CFG
    fn = pmerge.make_sharded_ingest_resident_fn(mesh, cfg, bpl, caps, 1 << 12,
                                                donate=False, lanes=lanes)
    dist = pmerge.init_dist_state(cfg, mesh)
    tables = pmerge.init_resident_tables(mesh, 1 << 12, lanes=lanes)
    flat = pmerge.shard_dense(mesh, np.zeros(
        ndata * lanes * flowpack.resident_buf_len(bpl, caps), np.uint32))
    hlo = fn.lower(dist, tables, flat).compile().as_text()
    for coll in ("all-reduce", "all-gather", "collective-permute",
                 "reduce-scatter", "all-to-all"):
        assert coll not in hlo, f"sharded resident ingest contains {coll}"
