"""Tiered counter planes (SKETCH_TIERED, sketch/tiered.py).

Pins the ISSUE-14 contracts:

- tiered-vs-wide DECODE EQUIVALENCE: bit-exact against the numpy twin of
  the tier spec under fuzz (promotion at every tier boundary, sat-add
  clamp at the top tier), and EXACT equality with the wide path wherever
  promotion is lossless (no saturation; sole-overflower groups);
- the two-form invariant: tiered ingest through the fused Pallas walk and
  the un-fused scatter chain stays bit-exact (the tiers wrap BOTH forms
  with one shared decode/encode);
- zero post-warmup retraces over the tiered ingest (fixed shapes — the
  promotion path is a masked in-place update, never a reshape);
- the disabled path: SKETCH_TIERED unset means no tier arrays anywhere and
  the untouched wide-resident pytree (the zero-cost bar);
- roll/state_tables/checkpoints see only canonical WIDE tables (no wire
  v4, no checkpoint format bump);
- the memory claim: >= 4x fewer resident bytes over the tier-covered
  counter tables at the production geometry.
"""

import numpy as np
import pytest

import tests.conftest  # noqa: F401
import jax
import jax.numpy as jnp

from netobserv_tpu.sketch import state as sk, tiered
from netobserv_tpu.sketch.tiered import (
    BASE_MAX, MID_MAX, TOP_MAX, TierSpec,
)

KW = 10

SMALL_TIERS = TierSpec(mid_group=8, top_group=32, bytes_unit=1)
SMALL_CFG = sk.SketchConfig(cm_depth=2, cm_width=1 << 10, hll_precision=6,
                            perdst_buckets=32, perdst_precision=4,
                            persrc_buckets=32, persrc_precision=4,
                            topk=16, hist_buckets=64, ewma_buckets=32)


def _batch(n, seed=0, max_bytes=100, keys=None):
    rng = np.random.default_rng(seed)
    return {
        "keys": (keys if keys is not None
                 else rng.integers(0, 2**32, (n, KW), dtype=np.uint32)),
        "bytes": rng.integers(1, max_bytes, n).astype(np.float32),
        "packets": rng.integers(1, 4, n).astype(np.int32),
        "rtt_us": rng.integers(0, 5000, n).astype(np.int32),
        "dns_latency_us": rng.integers(0, 2000, n).astype(np.int32),
        "sampling": np.zeros(n, np.int32),
        "valid": np.ones(n, np.bool_),
    }


def _dev(arrays):
    return {k: jnp.asarray(v) for k, v in arrays.items()}


# --------------------------------------------------------------------------
# the numpy TWIN of the tier spec (the decode-equivalence oracle) lives in
# tests/test_tiered_twin.py — a jax-free module so the big-endian qemu CI
# tier executes it (with golden digests) on real big-endian byte order
# --------------------------------------------------------------------------

from tests.test_tiered_twin import (  # noqa: E402
    GOLDEN, digest, fuzz_deltas, twin_decode, twin_plane_add,
)


def test_twin_constants_match_device_modules():
    """One value truth across the three homes of the tier constants: the
    numpy twin module, sketch/tiered.py, and the Pallas tile helpers."""
    import tests.test_tiered_twin as twin
    from netobserv_tpu.ops.pallas import tier_tiles

    for mod in (twin, tier_tiles):
        assert mod.BASE_MAX == BASE_MAX
        assert mod.MID_MAX == MID_MAX
        assert mod.TOP_MAX == TOP_MAX


def test_device_plane_matches_twin_golden_schedule():
    """The device plane over the twin module's deterministic fuzz schedule
    reproduces the PINNED golden digests: device == twin == golden, so the
    qemu tier's big-endian run pins the same counts this jax run does."""
    for (spec, unit), want in GOLDEN.items():
        dspec = TierSpec(spec.mid_group, spec.top_group, spec.bytes_unit)
        plane = tiered.init_plane(2, 256, dspec)
        for fold in range(6):
            plane = tiered.plane_add(
                plane, jnp.asarray(fuzz_deltas(fold, 2, 256, unit)),
                dspec, unit)
        host = tuple(np.asarray(x) for x in plane)
        assert digest(host, np.asarray(
            tiered.decode_plane(plane, dspec, unit))) == want


@pytest.mark.parametrize("spec,unit", [
    (TierSpec(mid_group=4, top_group=16, bytes_unit=1), 1),
    (TierSpec(mid_group=8, top_group=64, bytes_unit=64), 64),
])
def test_plane_fuzz_matches_twin_bit_exact(spec, unit):
    """Promotion at every tier boundary: per-fold deltas biased to cross
    the u8 base (255) and u16 mid (65535) saturation points, several
    folds deep — device arrays and decode match the twin bit-exactly."""
    rng = np.random.default_rng(3)
    d, w = 2, 256
    plane = tiered.init_plane(d, w, spec)
    twin = (np.zeros((d, w), np.uint8),
            np.zeros((d, w // spec.mid_group), np.uint16),
            np.zeros((d, w // spec.top_group), np.uint32))
    for fold in range(6):
        # integer unit masses, boundary-biased: most tiny, some straddling
        # base saturation, a few mid-tier sized (sums stay < 2^24 so f32
        # adds are order-independent -> the pin can be EXACT)
        delta = rng.integers(0, 40, (d, w)).astype(np.float32)
        hot = rng.random((d, w)) < 0.1
        delta += hot * rng.integers(200, 300, (d, w)).astype(np.float32)
        heavy = rng.random((d, w)) < 0.02
        delta += heavy * rng.integers(30_000, 80_000, (d, w)).astype(
            np.float32)
        delta *= unit
        plane = tiered.plane_add(plane, jnp.asarray(delta), spec, unit)
        twin = twin_plane_add(twin, delta, spec, unit)
        for got, want, name in zip(plane, twin, ("base", "mid", "top")):
            np.testing.assert_array_equal(
                np.asarray(got), want, err_msg=f"fold {fold} {name}")
    np.testing.assert_array_equal(
        np.asarray(tiered.decode_plane(plane, spec, unit)),
        twin_decode(twin, spec, unit))


def test_promotion_is_lossless_for_sole_overflowers():
    """decode == exact running total across EVERY tier boundary while a
    group has a single promoted member (unit 1): crossing 255, then
    65535+255, stays exact; only the top-tier clamp (sat-add) caps it."""
    spec = TierSpec(mid_group=4, top_group=16, bytes_unit=1)
    plane = tiered.init_plane(1, 32, spec)
    col, total = 5, np.float32(0)
    for step in (254.0, 1.0, 1.0, 250.0, 65_300.0, 1000.0):
        delta = np.zeros((1, 32), np.float32)
        delta[0, col] = step
        plane = tiered.plane_add(plane, jnp.asarray(delta), spec, 1)
        total = total + np.float32(step)
        assert float(tiered.decode_plane(plane, spec, 1)[0, col]) == total
    # sat-add at the top tier: one enormous fold clamps, decode caps at
    # base + mid + TOP_MAX (computed in f32, like the device path)
    delta = np.zeros((1, 32), np.float32)
    delta[0, col] = 2.0**31
    plane = tiered.plane_add(plane, jnp.asarray(delta), spec, 1)
    want = np.float32(BASE_MAX) + np.float32(MID_MAX) + np.float32(TOP_MAX)
    assert float(tiered.decode_plane(plane, spec, 1)[0, col]) == want
    # and it STAYS clamped — sat-add, not wraparound
    plane = tiered.plane_add(plane, jnp.asarray(delta), spec, 1)
    assert float(tiered.decode_plane(plane, spec, 1)[0, col]) == want


def test_top_tier_is_exact_past_f32_precision():
    """A top cell aggregates a whole top_group's overflow, so it crosses
    2^24 units long before any single wide counter — its accumulation is
    u32 integer sat-add, exact to the clamp: small per-fold spills onto a
    huge top cell must never be rounded away (an undercount, the one
    direction the module forbids; found by review)."""
    spec = TierSpec(mid_group=4, top_group=16, bytes_unit=1)
    plane = tiered.init_plane(1, 32, spec)
    big = np.zeros((1, 32), np.float32)
    big[0, 5] = float(1 << 25)  # park the top cell far past f32 precision
    plane = tiered.plane_add(plane, jnp.asarray(big), spec, 1)
    top_before = int(np.asarray(plane.top)[0, 0])
    assert top_before > (1 << 24)
    one = np.zeros((1, 32), np.float32)
    one[0, 5] = 1.0
    for _ in range(100):  # 100 consecutive +1-unit spills
        plane = tiered.plane_add(plane, jnp.asarray(one), spec, 1)
    assert int(np.asarray(plane.top)[0, 0]) == top_before + 100


def test_decay_does_not_compound_shared_cell_aliasing():
    """Two promoted counters sharing one mid cell, decayed repeatedly:
    decoded estimates must be NON-INCREASING window over window. The
    broken shape (decode -> decay -> from-scratch re-encode) re-sums the
    per-member attribution back into the shared cell and GROWS it ~1.5x
    per window (found by review; decay now scales the tier arrays
    elementwise instead)."""
    spec = TierSpec(mid_group=4, top_group=16, bytes_unit=1)
    plane = tiered.init_plane(1, 32, spec)
    delta = np.zeros((1, 32), np.float32)
    delta[0, 0] = delta[0, 1] = 5255.0  # same mid group, both promote
    plane = tiered.plane_add(plane, jnp.asarray(delta), spec, 1)
    prev = float(tiered.decode_plane(plane, spec, 1)[0, 0])
    for _ in range(6):
        plane = tiered.decay_plane(plane, 0.5)
        cur = float(tiered.decode_plane(plane, spec, 1)[0, 0])
        assert cur <= prev, f"decayed estimate grew: {prev} -> {cur}"
        prev = cur
    # and the state-level decay roll shows decayed totals shrinking too
    cfg = SMALL_CFG._replace(tiered=SMALL_TIERS)
    ts = sk.init_state(cfg)
    ts = jax.jit(sk.ingest)(ts, _dev(_batch(128, max_bytes=9000)))
    total = float(jnp.sum(tiered.decode_state(ts).cm_bytes.counts))
    roll = sk.make_roll_fn(cfg, decay_factor=0.5)
    for _ in range(4):
        ts, _report = roll(ts)
        cur = float(jnp.sum(tiered.decode_state(ts).cm_bytes.counts))
        assert cur <= total, f"decay roll grew CM mass: {total} -> {cur}"
        total = cur


def test_hll_pack_roundtrip_lossless():
    rng = np.random.default_rng(7)
    for shape in ((64,), (16, 64), (4, 256)):
        regs = rng.integers(0, 34, shape).astype(np.int32)  # ranks <= 33
        back = np.asarray(tiered.unpack_hll(tiered.pack_hll(
            jnp.asarray(regs))))
        np.testing.assert_array_equal(back, regs)


# --------------------------------------------------------------------------
# state-level equivalence
# --------------------------------------------------------------------------

def test_tiered_ingest_matches_wide_bit_exact_below_saturation():
    """No counter crosses the base span -> promotion never engages ->
    tiered decode equals the wide path EXACTLY, table for table (the HLL
    banks are lossless at any load)."""
    ts = sk.init_state(SMALL_CFG._replace(tiered=SMALL_TIERS))
    ws = sk.init_state(SMALL_CFG)
    ing = jax.jit(sk.ingest)
    for i in range(4):
        b = _dev(_batch(128, seed=i, max_bytes=40))
        ts, ws = ing(ts, b), ing(ws, b)
    dec = tiered.decode_state(ts)
    for name in ws._fields:
        got = jax.tree.leaves(getattr(dec, name))
        want = jax.tree.leaves(getattr(ws, name))
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                          err_msg=name)


def test_tiered_ingest_exact_across_boundaries_single_key():
    """State-level 'promotion at every tier boundary': ONE key hammered
    past the base and mid saturation points is a sole overflower in every
    CM group it hashes to -> tiered decode still equals wide EXACTLY."""
    cfg = SMALL_CFG._replace(tiered=SMALL_TIERS)
    key = np.full((1, KW), 7, np.uint32)
    ts, ws = sk.init_state(cfg), sk.init_state(SMALL_CFG)
    ing = jax.jit(sk.ingest)
    for step in (200.0, 100.0, 60_000.0, 9_000.0):  # crosses 255 and 65790
        b = _batch(1, max_bytes=2, keys=key)
        b["bytes"][:] = step
        b = _dev(b)
        ts, ws = ing(ts, b), ing(ws, b)
    dec = tiered.decode_state(ts)
    np.testing.assert_array_equal(np.asarray(dec.cm_bytes.counts),
                                  np.asarray(ws.cm_bytes.counts))
    np.testing.assert_array_equal(np.asarray(dec.cm_pkts.counts),
                                  np.asarray(ws.cm_pkts.counts))


def test_tiered_pallas_and_scatter_forms_bit_exact():
    """The two-form invariant holds THROUGH the tiers: one shared
    decode/encode wraps both fold forms, so tiered ingest with the fused
    kernels (interpret mode on CPU) matches the scatter chain bit-exactly
    — the tests/test_pallas_signal.py pin, tiered edition."""
    cfg = sk.SketchConfig(cm_depth=2, cm_width=512, hll_precision=6,
                          perdst_buckets=32, perdst_precision=4,
                          persrc_buckets=32, persrc_precision=4,
                          topk=16, hist_buckets=64, ewma_buckets=32,
                          tiered=TierSpec(mid_group=8, top_group=64,
                                          bytes_unit=64))
    b = _dev(_batch(96, seed=11, max_bytes=9000))
    out = {}
    for pallas in (False, True):
        s = sk.init_state(cfg)
        s = sk.ingest(s, b, use_pallas=pallas)
        out[pallas] = tiered.decode_state(s)
    for name in out[False]._fields:
        for g, w in zip(jax.tree.leaves(getattr(out[True], name)),
                        jax.tree.leaves(getattr(out[False], name))):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                          err_msg=name)


def test_zero_post_warmup_retraces():
    """Fixed shapes everywhere: promotion changes values, never shapes —
    the jitted tiered ingest compiles once and never again."""
    fn = sk.make_ingest_fn(donate=False, name="tiered_ingest_t")
    s = sk.init_state(SMALL_CFG._replace(tiered=SMALL_TIERS))
    for i in range(4):
        s = fn(s, _dev(_batch(128, seed=i, max_bytes=90_000)))
    jax.block_until_ready(jax.tree.leaves(s))
    assert fn.compiles == 1 and fn.retraces == 0


# --------------------------------------------------------------------------
# roll / tables / checkpoint surfaces stay WIDE
# --------------------------------------------------------------------------

def test_roll_decodes_to_wide_and_resets_tiers():
    cfg = SMALL_CFG._replace(tiered=SMALL_TIERS)
    ts = sk.init_state(cfg)
    ing = jax.jit(sk.ingest)
    for i in range(3):
        ts = ing(ts, _dev(_batch(128, seed=i, max_bytes=9000)))
    pre_wide = tiered.decode_state(ts)
    roll = sk.make_roll_fn(cfg, with_tables=True)
    new_state, report, tables = roll(ts)
    # the delta-wire/query table snapshot is the canonical wide decode
    np.testing.assert_array_equal(np.asarray(tables["cm_bytes"]),
                                  np.asarray(pre_wide.cm_bytes.counts))
    np.testing.assert_array_equal(np.asarray(tables["hll_src"]),
                                  np.asarray(pre_wide.hll_src.regs))
    assert tables["cm_bytes"].dtype == jnp.float32  # wide, not u8
    # the fresh window is tiered again, zeroed planes, window advanced
    assert isinstance(new_state, tiered.TieredState)
    assert int(new_state.window) == 1
    assert not np.asarray(new_state.tables.cm_bytes.base).any()
    # the report's heavy table survives the roll (persistent slots)
    assert np.asarray(report.heavy.counts).shape[0] == SMALL_CFG.topk
    # keep mode (reset_sketches=False) keeps the tier arrays VERBATIM —
    # never a decode->re-encode round trip (which would compound
    # shared-cell attribution every window)
    kept, _rep = sk.make_roll_fn(cfg, reset_sketches=False)(ts)
    for got, want in zip(jax.tree.leaves(kept.tables),
                         jax.tree.leaves(ts.tables)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_decay_roll_mode_stays_tiered():
    cfg = SMALL_CFG._replace(tiered=SMALL_TIERS)
    ts = sk.init_state(cfg)
    # below saturation: the decayed wide table re-encodes exactly, up to
    # the ceil quantization (+<= 1 unit per nonzero counter)
    ts = jax.jit(sk.ingest)(ts, _dev(_batch(128, max_bytes=40)))
    wide = tiered.decode_state(ts).cm_bytes.counts
    before = float(jnp.sum(wide))
    nonzero = int(jnp.sum(wide > 0))
    new_state, _report = sk.make_roll_fn(cfg, decay_factor=0.5)(ts)
    assert isinstance(new_state, tiered.TieredState)
    after = float(jnp.sum(tiered.decode_state(new_state).cm_bytes.counts))
    assert 0.5 * before <= after <= 0.5 * before + nonzero


def test_checkpoint_roundtrip_stays_wide_format(tmp_path):
    """Checkpoints save the DECODED wide state (no format bump): a tiered
    agent's save restores into the plain wide template, and re-encoding
    reproduces the state exactly below saturation."""
    pytest.importorskip("orbax.checkpoint")
    from netobserv_tpu.sketch.checkpoint import SketchCheckpointer

    cfg = SMALL_CFG._replace(tiered=SMALL_TIERS)
    ts = sk.init_state(cfg)
    ts = jax.jit(sk.ingest)(ts, _dev(_batch(128, max_bytes=40)))
    ckpt = SketchCheckpointer(str(tmp_path / "ck"))
    ckpt.save(0, tiered.decode_state(ts), wait=True)
    restored_wide = ckpt.restore(sk.init_state(SMALL_CFG))  # WIDE template
    back = tiered.encode_state(restored_wide, SMALL_TIERS)
    for got, want in zip(jax.tree.leaves(back), jax.tree.leaves(ts)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    ckpt.close()


# --------------------------------------------------------------------------
# disabled path + memory claim
# --------------------------------------------------------------------------

def test_disabled_path_has_no_tier_arrays():
    """SKETCH_TIERED unset = the untouched wide-resident path: plain
    SketchState pytree, identical dtypes, no narrow arrays anywhere, and
    ingest/roll return the same types as before the tier plane existed."""
    from netobserv_tpu.config import AgentConfig

    assert sk.SketchConfig().tiered is None
    assert sk.SketchConfig.from_agent_config(AgentConfig()).tiered is None
    s = sk.init_state(SMALL_CFG)
    assert isinstance(s, sk.SketchState)
    assert not any(l.dtype in (jnp.uint8, jnp.uint16)
                   for l in jax.tree.leaves(s))
    s = sk.ingest(s, _dev(_batch(64)))
    assert isinstance(s, sk.SketchState)
    new_state, _r = sk.roll_window(s, SMALL_CFG)
    assert isinstance(new_state, sk.SketchState)


def test_resident_bytes_reduction_at_production_geometry():
    """The ISSUE-14 acceptance bar: >= 4x fewer resident bytes over the
    tier-covered counter tables at equal (default) geometry."""
    wide = sk.init_state(sk.SketchConfig())
    narrow = sk.init_state(sk.SketchConfig(tiered=TierSpec()))
    wb = tiered.counter_table_bytes(wide)
    tb = tiered.counter_table_bytes(narrow)
    ratio = sum(wb.values()) / sum(tb.values())
    assert ratio >= 4.0, f"counter-table reduction {ratio:.2f}x < 4x"
    # whole-state footprint shrinks too (heavy table/EWMAs stay wide)
    assert tiered.array_bytes(narrow) < tiered.array_bytes(wide) / 3


# --------------------------------------------------------------------------
# exporter integration (fold -> roll -> publish -> metrics)
# --------------------------------------------------------------------------

def test_exporter_end_to_end_tiered(monkeypatch):
    from netobserv_tpu.datapath.replay import SyntheticFetcher
    from netobserv_tpu.exporter.tpu_sketch import TpuSketchExporter
    from netobserv_tpu.metrics.registry import Metrics

    # tiered planes are single-device; the conftest's 8-virtual-device CPU
    # mesh would route the exporter down the sharded path (where tiering
    # deliberately degrades to wide) — pin the exporter to one device
    real_devices = jax.devices
    monkeypatch.setattr(jax, "devices",
                        lambda *a, **k: real_devices(*a, **k)[:1])
    metrics = Metrics()
    reports = []
    cfg = SMALL_CFG._replace(tiered=SMALL_TIERS)
    exp = TpuSketchExporter(batch_size=64, window_s=3600.0, sketch_cfg=cfg,
                            metrics=metrics, sink=reports.append)
    try:
        assert isinstance(exp._state, tiered.TieredState)
        fetcher = SyntheticFetcher(flows_per_eviction=64, n_distinct=500)
        for _ in range(4):
            exp.export_evicted(fetcher.lookup_and_delete())
        exp.flush()
        assert reports and reports[0]["Records"] > 0
        # the query snapshot serves the WIDE CM planes
        snap = exp.query.get()
        assert snap is not None and snap["cm_bytes"].dtype == np.float32
        # the tier satellite metrics moved: promotions counted (tiny
        # geometry saturates), the resident-bytes gauge is set
        gauge = metrics.sketch_resident_hbm_bytes._value.get()
        assert gauge == tiered.array_bytes(exp._state)
        # the tiny unit-1 geometry saturates under synthetic traffic, so
        # the first closed window MUST report new promotions (> 0 — the
        # publish path, label wiring and span math are all load-bearing)
        prom = metrics.sketch_tier_promotions_total.labels(
            table="cm_bytes")._value.get()
        assert prom > 0
    finally:
        exp.close()
    # and the fresh window still folds (post-roll state is tiered)
    assert isinstance(exp._state, tiered.TieredState)


# --------------------------------------------------------------------------
# tier-native Pallas walks (ISSUE 20): fold on the packed u8/u16/u32 tiles,
# no wide decode temporary — the decode wrap stays the equivalence oracle
# --------------------------------------------------------------------------

INTERIOR_SPECS = [
    pytest.param(SMALL_TIERS, id="u1"),
    pytest.param(TierSpec(mid_group=8, top_group=64, bytes_unit=64),
                 id="u64"),
]


def _boundary_batches(spec, folds=4):
    """Boundary-crossing fold schedule INSIDE the f32-exact regime: every
    accumulated f32 value (wide CM cells, heavy slot counts) stays below
    2^24, where scatter order vs matmul tree-sum order cannot differ — the
    module's documented standing assumption, and the only regime where a
    bit-exact pin is even well-defined. The u64 spec needs concentrated
    mass (a 16-key universe) to drive whole mid GROUPS past 65535 units
    without any single cell leaving the regime."""
    if spec.bytes_unit == 1:
        return [_dev(_batch(96, seed=i, max_bytes=60_000))
                for i in range(folds)]
    rng = np.random.default_rng(5)
    universe = rng.integers(0, 2**32, (16, KW), dtype=np.uint32)
    out = []
    for i in range(6):
        b = _batch(96, seed=i, max_bytes=400_000,
                   keys=universe[rng.integers(0, 16, 96)])
        out.append(_dev(b))
    return out


def _interior_cfg(spec, **kw):
    """512-wide CM (tile-aligned: TILE_W | width, top_group | TILE_W) so
    the interior gate passes on the small test geometry."""
    base = dict(cm_depth=2, cm_width=512, hll_precision=6,
                perdst_buckets=32, perdst_precision=4,
                persrc_buckets=32, persrc_precision=4,
                topk=16, hist_buckets=64, ewma_buckets=32, tiered=spec)
    base.update(kw)
    return sk.SketchConfig(**base)


def _assert_tiered_states_equal(got, want):
    for g, w in zip(jax.tree.leaves(got.tables),
                    jax.tree.leaves(want.tables)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    dg, dw = tiered.decode_state(got), tiered.decode_state(want)
    for name in dw._fields:
        for g, w in zip(jax.tree.leaves(getattr(dg, name)),
                        jax.tree.leaves(getattr(dw, name))):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                          err_msg=name)


@pytest.mark.parametrize("spec", INTERIOR_SPECS)
def test_interior_walk_three_forms_bit_exact(spec):
    """Saturation-boundary fuzz, three fold forms: the tier-interior walk
    and the decode-wrapped Pallas walk both match the decode-wrapped
    scatter chain bit-exactly — tier arrays AND full wide decode (heavy
    table included). Deltas stay in the f32-exact regime (per-fold group
    sums < 2^24 units) while still crossing base -> mid -> top."""
    cfg = _interior_cfg(spec)
    assert sk.tiered_fold_form(cfg._replace(use_pallas=True)) == "interior"
    batches = _boundary_batches(spec)
    out = {}
    for name, kw in (("interior", dict(use_pallas=True)),
                     ("decode_pallas",
                      dict(use_pallas=True, tier_interior=False)),
                     ("scatter", dict(use_pallas=False))):
        s = sk.init_state(cfg)
        for b in batches:
            s = sk.ingest(s, b, **kw)
        out[name] = s
    # the schedule really promoted at every boundary
    t = out["interior"].tables.cm_bytes
    assert (np.asarray(t.base) == BASE_MAX).any()
    assert (np.asarray(t.mid) == MID_MAX).any()
    assert (np.asarray(t.top) > 0).any()
    _assert_tiered_states_equal(out["interior"], out["scatter"])
    _assert_tiered_states_equal(out["decode_pallas"], out["scatter"])


def test_interior_fused_hll_lane_and_fallback(monkeypatch):
    """ewma_buckets=128 makes the signal fold eligible, so the interior
    walk fuses the packed global-src HLL bank into the signal megakernel
    (spied via update_tiered); ewma_buckets=32 declines and the bank folds
    through the unfused unpack->scatter->pack seam. Both stay bit-exact
    vs the decode-wrapped scatter chain, packed bank included."""
    from netobserv_tpu.ops.pallas import signal_kernel

    spec = TierSpec(mid_group=8, top_group=64, bytes_unit=64)
    orig = signal_kernel.update_tiered
    for ewma, expect_fused in ((128, True), (32, False)):
        cfg = _interior_cfg(spec, ewma_buckets=ewma)
        calls = []
        monkeypatch.setattr(
            signal_kernel, "update_tiered",
            lambda *a, **k: (calls.append(1), orig(*a, **k))[1])
        si, ss = sk.init_state(cfg), sk.init_state(cfg)
        for i in range(2):
            b = _dev(_batch(96, seed=i, max_bytes=2_000_000))
            si = sk.ingest(si, b, use_pallas=True)
            ss = sk.ingest(ss, b, use_pallas=False)
        assert bool(calls) == expect_fused, ewma
        np.testing.assert_array_equal(
            np.asarray(si.tables.hll_src), np.asarray(ss.tables.hll_src))
        _assert_tiered_states_equal(si, ss)


@pytest.mark.parametrize("spec", INTERIOR_SPECS)
def test_interior_zero_retraces_across_superbatch_ladder(spec):
    """The superbatch ladder rule under the interior walk: one fixed-shape
    jit PER ladder size, each compiling exactly once (promotion changes
    values, never shapes) — and each watched entry carries the
    tiered=interior attribution /debug/executables reads."""
    cfg = _interior_cfg(spec)
    assert sk.tiered_fold_form(cfg._replace(use_pallas=True)) == "interior"
    s = sk.init_state(cfg)
    for k in (1, 2, 4):
        fn = sk.make_ingest_fn(donate=False, use_pallas=True,
                               name=f"tiered_interior_x{k}",
                               tiered="interior")
        for i in range(3):
            s = fn(s, _dev(_batch(64 * k, seed=i, max_bytes=9000)))
        jax.block_until_ready(jax.tree.leaves(s))
        assert fn.compiles == 1 and fn.retraces == 0, k
        assert fn.stats()["tiered"] == "interior"
        assert "tiered=interior" in fn.last_signature


def test_tiered_fold_form_gate():
    """The accounting twin of the trace-time gate: interior only when
    Pallas is on AND the geometry tiles (width % TILE_W == 0, top_group
    divides the tile); every decline lands on the decode wrap, tiers off
    is None. tier_interior=False (the bench A/B opt-out) is covered by
    the three-forms test above."""
    cfg = _interior_cfg(SMALL_TIERS)
    assert sk.tiered_fold_form(sk.SketchConfig()) is None
    assert sk.tiered_fold_form(cfg._replace(use_pallas=True)) == "interior"
    assert sk.tiered_fold_form(cfg._replace(use_pallas=False)) == "decode"
    assert sk.tiered_fold_form(
        cfg._replace(use_pallas=True, cm_width=256)) == "decode"
    wide_top = TierSpec(mid_group=8, top_group=1024, bytes_unit=1)
    assert sk.tiered_fold_form(
        cfg._replace(use_pallas=True, tiered=wide_top)) == "decode"


def test_mesh_degrade_warns_once_and_registers_condition(caplog):
    """Multi-device SKETCH_TIERED degrades to wide: the warning dedupes to
    once per PROCESS (chaos/restart loops rebuild exporters; the log line
    is informational), and the queryable truth is the tiered_degraded
    supervisor condition — a condition, never DEGRADED."""
    import netobserv_tpu.exporter.tpu_sketch as tsx
    from netobserv_tpu.exporter.tpu_sketch import TpuSketchExporter

    class _Sup:
        def __init__(self):
            self.conditions = {}

        def register(self, *a, **k):
            return lambda: None

        def register_condition(self, name, probe):
            self.conditions[name] = probe

    tsx._TIERED_DEGRADE_WARNED = False
    cfg = SMALL_CFG._replace(tiered=SMALL_TIERS)
    exps = []
    try:
        with caplog.at_level(
                "WARNING", logger="netobserv_tpu.exporter.tpu_sketch"):
            for _ in range(2):  # a restart loop rebuilds the exporter
                exps.append(TpuSketchExporter(
                    batch_size=64, window_s=3600.0, sketch_cfg=cfg,
                    sink=lambda obj: None))
        hits = [r for r in caplog.records
                if "SKETCH_TIERED has no sharded form" in r.getMessage()]
        assert len(hits) == 1
        for exp in exps:
            assert exp._tiered_degraded
            assert exp._cfg.tiered is None and exp._tier_form is None
            sup = _Sup()
            exp.register_supervised(sup)
            cond = sup.conditions["tiered_degraded"]()
            assert cond["active"] and "sharded" in cond["reason"]
            # and the /query/status mirror of the same condition
            assert exp.query_status().get("tiered_degraded") is True
    finally:
        for exp in exps:
            exp.close()
