"""What a TPU would be handed, checked without one: every ingest executable
the exporter can build at DEFAULT geometry is cross-lowered for the TPU
platform (`jax.default_backend` patched, so the auto gates take their TPU
branch) and must hold the Mosaic calls its gate promised. Lowering builds the
Mosaic module only — block-shape refusals surface here, in seconds, instead
of on the chip budget; VMEM, layouts and values are `chip_smoke.py`'s.
"""

import glob
import os
import subprocess
import sys
from unittest import mock

import pytest

import tests.conftest  # noqa: F401
import jax
import jax.numpy as jnp

from netobserv_tpu.datapath import flowpack
from netobserv_tpu.parallel import MeshSpec, make_mesh, merge as pmerge
from netobserv_tpu.sketch import state as sk, tenancy, tiered
from netobserv_tpu.utils import platform

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: countmin.update_two, hll.update, topk.reduce x SLOT_ROUNDS, signal.update
MOSAIC_CALLS = 5
CFG = sk.SketchConfig()
BATCH = 8192


def mosaic_calls(fn, *args) -> int:
    """Cross-lower `fn(*args)` for the TPU and count its Mosaic kernels."""
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        text = fn.trace(*args).lower(lowering_platforms=("tpu",)).as_text()
    return text.count("tpu_custom_call")


def state_shapes(cfg=CFG):
    return jax.eval_shape(lambda: sk.init_state(cfg))


def batch_shapes(rows: int) -> dict:
    """The array dict `ingest` takes, every feature column present."""
    return jax.eval_shape(
        sk.dense_to_arrays,
        jax.ShapeDtypeStruct((rows, sk.DENSE_WORDS), jnp.uint32))


def test_single_chip_ingest_lowers_with_five_mosaic_calls():
    assert mosaic_calls(sk.make_ingest_fn(), state_shapes(),
                        batch_shapes(2 * BATCH)) == MOSAIC_CALLS


@pytest.mark.parametrize("cfg", [CFG, CFG._replace(tiered=tiered.TierSpec())],
                         ids=["wide", "tiered"])
@pytest.mark.parametrize("k", [1, 2, 4])
def test_resident_ladder_entry_lowers_with_five_mosaic_calls(k, cfg):
    """What SKETCH_TIERED=true alone dispatches is this ladder over a tiered
    state, in the decode form: the same five kernels."""
    lanes = 8  # what an 8+-core host resolves (config.resolved_pack_threads)
    bpl = BATCH // lanes
    caps = flowpack.default_resident_caps(bpl)
    fn = sk.make_ingest_resident_lanes_fn(bpl, caps, k * lanes)
    tables = jax.ShapeDtypeStruct((4 * lanes, 1 << 18, sk.KEY_WORDS),
                                  jnp.uint32)
    flat = jax.ShapeDtypeStruct(
        (k * lanes * flowpack.resident_buf_len(bpl, caps),), jnp.uint32)
    assert mosaic_calls(fn, state_shapes(cfg), tables, flat) == MOSAIC_CALLS


def test_sharded_dense_ingest_lowers_with_five_mosaic_calls():
    mesh = make_mesh(MeshSpec(data=4), devices=jax.devices()[:4])
    fn = pmerge.make_sharded_ingest_fn(mesh, CFG, dense=True,
                                       with_token=True)
    dist = jax.eval_shape(lambda: pmerge.init_dist_state(CFG, mesh))
    dense = jax.ShapeDtypeStruct((BATCH * sk.DENSE_WORDS,), jnp.uint32)
    assert mosaic_calls(fn, dist, dense) == MOSAIC_CALLS


def test_tiered_ingest_takes_the_decode_form_on_a_tpu():
    """The tier-interior walk does not compile (countmin_kernel.
    tiered_eligible): on a TPU the gate must say so, and the decode form it
    falls to must lower with the five wide-path kernels."""
    cfg = CFG._replace(tiered=tiered.TierSpec())
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        assert sk.tiered_fold_form(cfg) == "decode"
    assert sk.tiered_fold_form(cfg._replace(use_pallas=True)) == "interior"
    assert mosaic_calls(sk.make_ingest_fn(), state_shapes(cfg),
                        batch_shapes(BATCH)) == MOSAIC_CALLS


def test_tenant_stack_ingest_lowers_with_five_mosaic_calls():
    """vmap turns every kernel's blocks into (Squeezed, ...) — a 1-D block
    does not survive it (hll_kernel's whole-batch rows are 2-D for this)."""
    n = 4
    stack = tenancy.TenantStack(n, CFG, BATCH)
    state = jax.eval_shape(lambda: tenancy.init_stacked_state(CFG, n))
    dense = jax.ShapeDtypeStruct((n, BATCH * sk.DENSE_WORDS), jnp.uint32)
    assert mosaic_calls(stack._ingest, state, dense) == MOSAIC_CALLS


# --- compile cache placement (utils/platform.enable_compile_cache) ---------

def test_compile_cache_env_wins_and_sets_nothing_in_code(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    with mock.patch.object(jax.config, "update") as update:
        assert platform.enable_compile_cache() == "/some/dir"
    update.assert_not_called()


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    with mock.patch.object(jax.config, "update") as update:
        got = platform.enable_compile_cache()
    assert got == os.path.join(ROOT, ".jax_cache")
    update.assert_called_once_with("jax_compilation_cache_dir", got)


# --- a run that finds no chip fails ----------------------------------------

#: a TPU's device nodes: where one exists, a child with JAX_PLATFORMS unset
#: would take the chip and run the whole bench
TPU_VISIBLE = bool(glob.glob("/dev/accel*") or glob.glob("/dev/vfio/[0-9]*"))


@pytest.mark.parametrize("script,env", [
    # chip_smoke never honours a CPU request
    ("chip_smoke.py", {"JAX_PLATFORMS": "cpu"}),
    # bench.py runs on the CPU only when asked to, by name
    pytest.param("bench.py", {"JAX_PLATFORMS": ""}, marks=pytest.mark.skipif(
        TPU_VISIBLE, reason="this host has a TPU: bench.py would find it")),
])
def test_no_tpu_means_nonzero_exit_and_no_result(script, env):
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, script)], cwd=ROOT,
        env={**os.environ, **env}, capture_output=True, text=True,
        timeout=120)
    assert r.returncode != 0, r.stdout[-2000:]
    assert "no TPU" in r.stderr, r.stderr[-2000:]
    assert '"metric"' not in r.stdout and '"ok"' not in r.stdout
