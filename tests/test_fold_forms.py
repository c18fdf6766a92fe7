"""The fold's forms follow the Count-Min width through ONE function
(`sketch/state.fold_forms`): which side of each bound picks which form (the
platform handed in, never faked globally), that `ingest` runs the form the
function names, the label `/debug/executables` shows, and that the two
Count-Min forms — and `countmin.query` after them — agree bit for bit at a
width on each side of the upper bound (the kernel in interpret mode)."""

import re

import numpy as np
import pytest

import tests.conftest  # noqa: F401
import jax
import jax.numpy as jnp

from netobserv_tpu.ops import countmin, hashing
from netobserv_tpu.ops.pallas import countmin_kernel
from netobserv_tpu.sketch import state as sk

LOW, HIGH = sk.CM_FACTORED_WIDTHS
KW = 10


@pytest.mark.parametrize("width,use_pallas,platform,want", [
    # auto on a TPU: below the lower bound nothing runs as a kernel
    (LOW // 2, None, "tpu", (False, "scatter")),
    # inside the bounds the factored kernel folds, both bounds included
    (LOW, None, "tpu", (True, "factored")),
    (1 << 16, None, "tpu", (True, "factored")),
    (HIGH, None, "tpu", (True, "factored")),
    # above the upper bound the other kernels stay and the scatter folds
    (HIGH * 2, None, "tpu", (True, "scatter")),
    (1 << 22, None, "tpu", (True, "scatter")),
    # auto anywhere else: the scatter forms, whatever the width
    (1 << 16, None, "cpu", (False, "scatter")),
    (1 << 22, None, "gpu", (False, "scatter")),
    # forced on: every kernel wherever the width tiles, off the TPU too
    (1 << 22, True, "cpu", (True, "factored")),
    (LOW // 2, True, "tpu", (True, "factored")),
    (1000, True, "tpu", (True, "scatter")),
    # forced off
    (1 << 16, False, "tpu", (False, "scatter")),
])
def test_fold_forms_picks_each_form_on_each_side_of_the_bounds(
        width, use_pallas, platform, want):
    assert sk.fold_forms(width, use_pallas, platform) == want


def test_bounds_are_powers_of_two_and_ordered():
    assert LOW < HIGH and LOW & (LOW - 1) == 0 and HIGH & (HIGH - 1) == 0
    # the default geometry lies inside, so cells 1-4 keep the kernel
    assert LOW <= sk.SketchConfig().cm_width <= HIGH


def test_no_other_width_gate_in_the_fold():
    """The three copies of `>= 16384` became one function: nothing else in
    sketch/state.py compares a Count-Min width with a literal."""
    import inspect

    src = inspect.getsource(sk)
    assert not re.search(r"width\s*[<>]=?\s*(16384|1\s*<<)", src.replace(
        inspect.getsource(sk.fold_forms), ""))
    assert "16384" not in src


def _batch(rows: int, seed: int) -> dict:
    rng = np.random.default_rng(seed)
    dense = np.zeros((rows, sk.DENSE_WORDS), np.uint32)
    dense[:, :KW] = rng.integers(0, 2 ** 32, (rows, KW), dtype=np.uint32)
    arrays = dict(sk.dense_to_arrays(jnp.asarray(dense)))
    arrays["bytes"] = jnp.asarray(
        rng.integers(64, 9001, rows).astype(np.float32))
    arrays["packets"] = jnp.asarray(rng.integers(1, 12, rows).astype(np.int32))
    arrays["valid"] = jnp.asarray(rng.random(rows) < 0.95)
    return arrays


SMALL = sk.SketchConfig(cm_width=1 << 11, hll_precision=9, topk=128,
                        perdst_buckets=128, persrc_buckets=128,
                        hist_buckets=64, ewma_buckets=128)


@pytest.mark.parametrize("form", ["factored", "scatter"])
def test_ingest_runs_the_form_fold_forms_names(form, monkeypatch):
    """With the kernels on, the Count-Min kernel is in the traced fold
    exactly when `fold_forms` says "factored"; the other kernels stay."""
    monkeypatch.setattr(sk, "fold_forms", lambda *a, **k: (True, form))
    jaxpr = str(jax.make_jaxpr(lambda s, a: sk.ingest(s, a))(
        sk.init_state(SMALL), _batch(1024, 1)))
    assert ("countmin_update_two" in jaxpr) == (form == "factored")
    assert "signal_update" in jaxpr


@pytest.mark.parametrize("use_pallas,form", [(None, "scatter"),
                                              (True, "factored")])
def test_the_ingest_entry_is_labelled_with_its_countmin_form(use_pallas, form):
    """`/debug/executables` names the form the trace chose: on the CPU the
    automatic rule takes the scatter, forced on it takes the kernel."""
    fn = sk.make_ingest_fn(use_pallas=use_pallas, donate=False,
                           name=f"ingest_label_{form}")
    fn(sk.init_state(SMALL), _batch(1024, 2))
    assert fn.stats()["countmin"] == form


def _rows(rows: int, seed: int):
    rng = np.random.default_rng(seed)
    words = jnp.asarray(rng.integers(0, 2 ** 32, (rows, KW), dtype=np.uint32))
    h1, h2 = hashing.base_hashes(words)
    # a few keys repeated, so counters take more than one row of a chunk
    h1 = h1.at[rows // 2:].set(h1[:rows - rows // 2])
    h2 = h2.at[rows // 2:].set(h2[:rows - rows // 2])
    va = jnp.asarray(rng.integers(64, 9001, rows).astype(np.float32))
    vb = jnp.asarray(rng.integers(1, 4096, rows).astype(np.float32))
    valid = jnp.asarray(rng.random(rows) < 0.95)
    return h1, h2, va, vb, valid


@pytest.mark.parametrize("width", [HIGH, HIGH * 2],
                         ids=["at_the_bound", "above_the_bound"])
def test_the_two_countmin_forms_and_the_query_after_them_are_bit_equal(width):
    """Seeded integer-valued rows, two folds, a width on each side of the
    gate: the factored kernel (interpreted) and the scatter leave the same
    bits in both planes, so the `est` the slot top-K reads back is the same
    whichever form `fold_forms` chose."""
    forms = {
        "factored": jax.jit(lambda a, b, *r: countmin_kernel.update_two(
            a, b, *r, interpret=True)),
        "scatter": jax.jit(lambda a, b, *r: countmin.update_two(a, b, *r)),
    }
    got = {}
    for name, fold in forms.items():
        a, b = countmin.init(4, width), countmin.init(4, width)
        for seed in (7, 8):
            rows = _rows(1024, seed)
            a, b = fold(a, b, *rows)
        est = countmin.query(a, rows[0], rows[1])
        got[name] = [np.asarray(x) for x in (a.counts, b.counts, est)]
    for x, y in zip(got["factored"], got["scatter"]):
        np.testing.assert_array_equal(x, y)
    assert got["scatter"][0].sum() > 0
