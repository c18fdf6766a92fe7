"""Pallas kernel equivalence vs the XLA scatter implementation (interpret mode
on the CPU mesh; the same kernel compiles through Mosaic on TPU)."""

from unittest import mock

import numpy as np
import pytest

import tests.conftest  # noqa: F401
import jax
import jax.numpy as jnp

from netobserv_tpu.ops import countmin, hashing
from netobserv_tpu.ops.pallas import countmin_kernel

KW = 10


def test_pallas_countmin_matches_xla_scatter():
    rng = np.random.default_rng(11)
    b = 2048
    words = jnp.asarray(rng.integers(0, 2**32, (b, KW), dtype=np.uint32))
    vals = jnp.asarray(rng.integers(1, 1000, b).astype(np.float32))
    valid = jnp.asarray(rng.random(b) < 0.9)
    h1, h2 = hashing.base_hashes(words)

    ref = countmin.update(countmin.init(3, 1 << 11), h1, h2, vals, valid)
    got = countmin_kernel.update(countmin.init(3, 1 << 11), h1, h2, vals,
                                 valid, interpret=True)
    np.testing.assert_allclose(np.asarray(got.counts), np.asarray(ref.counts),
                               rtol=1e-6)


def test_pallas_countmin_accumulates_across_calls():
    rng = np.random.default_rng(12)
    words = jnp.asarray(rng.integers(0, 2**32, (1024, KW), dtype=np.uint32))
    vals = jnp.ones(1024, jnp.float32)
    valid = jnp.ones(1024, jnp.bool_)
    h1, h2 = hashing.base_hashes(words)
    cm = countmin.init(2, 1 << 10)
    for _ in range(3):
        cm = countmin_kernel.update(cm, h1, h2, vals, valid, interpret=True)
    est = countmin.query(cm, h1, h2)
    assert float(jnp.min(est)) >= 3.0


def _cm_inputs(seed, b, *, vmax=1500, valid_share=1.0, one_column=False):
    """Hashes, two integer-valued value rows and a validity mask for `b`
    records; `one_column` gives every record the same key, so every row of a
    chunk lands on one counter per depth row."""
    rng = np.random.default_rng(seed)
    words = rng.integers(0, 2**32, (1 if one_column else b, KW),
                         dtype=np.uint32)
    words = jnp.asarray(np.broadcast_to(words, (b, KW)))
    h1, h2 = hashing.base_hashes(words)
    va = jnp.asarray(rng.integers(0, vmax + 1, b).astype(np.float32))
    vb = jnp.asarray(rng.integers(0, 12, b).astype(np.float32))
    valid = jnp.asarray(rng.random(b) < valid_share)
    return h1, h2, va, vb, valid


def _cm_folds(planes, width, inputs, *, calls=1, depth=4):
    """(kernel counts, scatter counts), each a list of `planes` arrays, after
    `calls` successive folds of `inputs`; the kernel folds are jitted with
    the planes donated, as the ingest executables hold them."""
    h1, h2, va, vb, valid = inputs
    if planes == 2:
        def kern(a, b):
            return countmin_kernel.update_two(a, b, h1, h2, va, vb, valid,
                                              interpret=True)

        def scat(a, b):
            return countmin.update_two(a, b, h1, h2, va, vb, valid)
    else:
        def kern(a):
            return (countmin_kernel.update(a, h1, h2, va, valid,
                                           interpret=True),)

        def scat(a):
            return (countmin.update(a, h1, h2, va, valid),)
    kern = jax.jit(kern, donate_argnums=tuple(range(planes)))
    got, want = (tuple(countmin.init(depth, width) for _ in range(planes))
                 for _ in range(2))
    for _ in range(calls):
        got, want = kern(*got), scat(*want)
    return ([np.asarray(c.counts) for c in got],
            [np.asarray(c.counts) for c in want])


#: width, records, _cm_inputs' keywords, _cm_folds' keywords
CM_CASES = {
    "w16k-x4-batch": (16384, 33792, {}, {}),
    "w64k-x4-batch-invalid-rows": (65536, 33792, dict(valid_share=0.9), {}),
    "w64k-ragged-batch": (65536, 3000, dict(valid_share=0.97), {}),
    "w64k-values-to-2^20": (65536, 2048, dict(vmax=1 << 20), {}),
    "w16k-every-row-one-column": (16384, 2048,
                                  dict(vmax=4000, one_column=True), {}),
    "w256k-several-hi-tiles": (1 << 18, 1500, dict(valid_share=0.9), {}),
    "w16k-two-calls-donated": (16384, 3000, {}, dict(calls=2)),
    "w64k-depth-3": (65536, 1024, {}, dict(depth=3)),
}


@pytest.mark.parametrize("planes", [2, 1], ids=["update_two", "update"])
@pytest.mark.parametrize("case", CM_CASES)
def test_pallas_countmin_equals_scatter(case, planes):
    """The factored contraction adds whole values: integer sums below 2^24
    come out EQUAL to the scatter twin's, whatever the width (one HI tile or
    several), the batch (whole chunks or ragged), the duplicates in a chunk
    or the size of a value."""
    width, b, input_kw, fold_kw = CM_CASES[case]
    got, want = _cm_folds(planes, width, _cm_inputs(31, b, **input_kw),
                          **fold_kw)
    assert max(float(w.max()) for w in want) < 2 ** 24
    assert any(w.any() for w in want)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


def test_pallas_countmin_tiles_hi_when_the_planes_outgrow_a_block():
    """What `several-hi-tiles` rests on: past BLOCK_BYTES the grid has more
    than one step, each a whole power-of-two share of HI."""
    seen = []
    real = countmin_kernel.pl.pallas_call

    def spy(kernel, *, grid, **kw):
        seen.append((grid, kw["in_specs"][0].block_shape))
        return real(kernel, grid=grid, **kw)

    h1, h2, va, vb, valid = _cm_inputs(32, 1024)
    with mock.patch.object(countmin_kernel.pl, "pallas_call", spy):
        for width in (65536, 1 << 18):
            countmin_kernel.update_two(
                countmin.init(4, width), countmin.init(4, width), h1, h2,
                va, vb, valid, interpret=True)
        countmin_kernel.update(countmin.init(4, 1 << 18), h1, h2, va, valid,
                               interpret=True)
    lo = countmin_kernel.LO
    assert seen == [((1,), (2, 4, 256, lo)), ((4,), (2, 4, 256, lo)),
                    ((2,), (1, 4, 512, lo))]


def test_pallas_hll_matches_xla_scatter():
    from netobserv_tpu.ops import hll
    from netobserv_tpu.ops.pallas import hll_kernel
    rng = np.random.default_rng(21)
    b = 3000  # ragged (not a CHUNK_B multiple)
    words = jnp.asarray(rng.integers(0, 2**32, (b, 4), dtype=np.uint32))
    valid = jnp.asarray(rng.random(b) < 0.9)
    h1, h2 = hashing.base_hashes(words)
    ref = hll.update(hll.init(12), h1, h2, valid)  # 4096 regs
    got = hll_kernel.update(hll.init(12), h1, h2, valid, interpret=True)
    np.testing.assert_array_equal(np.asarray(got.regs), np.asarray(ref.regs))


def test_full_ingest_pallas_matches_default():
    from netobserv_tpu.sketch import state as sk
    rng = np.random.default_rng(22)
    cfg = sk.SketchConfig(cm_width=1024, topk=16, hll_precision=10,
                          perdst_buckets=32, perdst_precision=4,
                          hist_buckets=64, ewma_buckets=32)
    arrays = {
        "keys": jnp.asarray(rng.integers(0, 2**32, (512, KW), dtype=np.uint32)),
        "bytes": jnp.asarray(rng.integers(1, 100, 512).astype(np.float32)),
        "packets": jnp.ones(512, jnp.int32),
        "rtt_us": jnp.zeros(512, jnp.int32),
        "dns_latency_us": jnp.zeros(512, jnp.int32),
        "sampling": jnp.zeros(512, jnp.int32),
        "valid": jnp.ones(512, jnp.bool_),
    }
    import jax
    s_ref = jax.jit(lambda s, a: __import__("netobserv_tpu.sketch.state",
                                            fromlist=["ingest"]).ingest(s, a))(
        sk.init_state(cfg), arrays)
    s_pal = sk.make_ingest_fn(donate=False, use_pallas=True)(
        sk.init_state(cfg), arrays)
    np.testing.assert_allclose(np.asarray(s_pal.cm_bytes.counts),
                               np.asarray(s_ref.cm_bytes.counts), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(s_pal.hll_src.regs),
                                  np.asarray(s_ref.hll_src.regs))
    assert float(s_pal.total_records) == float(s_ref.total_records)


def test_pallas_countmin_pads_ragged_batch():
    rng = np.random.default_rng(13)
    b = 777  # not a multiple of CHUNK_B
    words = jnp.asarray(rng.integers(0, 2**32, (b, KW), dtype=np.uint32))
    vals = jnp.asarray(rng.integers(1, 10, b).astype(np.float32))
    valid = jnp.ones(b, jnp.bool_)
    h1, h2 = hashing.base_hashes(words)
    ref = countmin.update(countmin.init(2, 1 << 10), h1, h2, vals, valid)
    got = countmin_kernel.update(countmin.init(2, 1 << 10), h1, h2, vals,
                                 valid, interpret=True)
    np.testing.assert_allclose(np.asarray(got.counts), np.asarray(ref.counts),
                               rtol=1e-6)


def test_use_pallas_auto_policy():
    """auto = TPU AND width >= the measured crossover; every bool spelling
    the old field accepted still forces its path (an operator's explicit
    SKETCH_USE_PALLAS=0 opt-out must never flip into Pallas-on)."""
    from netobserv_tpu.config import load_config
    from netobserv_tpu.sketch.state import SketchConfig

    for spelling, want in (("auto", None), ("", None),
                           ("0", False), ("off", False), ("no", False),
                           ("false", False),
                           ("1", True), ("on", True), ("true", True)):
        cfg = load_config({"SKETCH_USE_PALLAS": spelling})
        assert SketchConfig.from_agent_config(cfg).use_pallas is want, \
            spelling

