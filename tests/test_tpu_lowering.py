"""What a TPU would be handed, checked without one: every ingest executable
the exporter can build at DEFAULT geometry is cross-lowered for the TPU
platform (`jax.default_backend` patched, so the auto gates take their TPU
branch) and must hold the Mosaic calls its gate promised. Lowering builds the
Mosaic module only — block-shape refusals surface here, in seconds, instead
of on the chip budget; VMEM, layouts and values are `chip_smoke.py`'s.

The same lowerings pin what a device capture reads (utils/retrace.jit,
sketch/state.py scopes): every watched entry lowers to the module
`jit_<watch name>`, the fold's ops carry one named scope per sketch update in
their metadata, and each Pallas call its kernel's name.
"""

import os
import re
import subprocess
import sys
from unittest import mock

import numpy as np
import pytest

import tests.conftest  # noqa: F401
import jax
import jax.numpy as jnp

from netobserv_tpu.datapath import flowpack
from netobserv_tpu.parallel import MeshSpec, make_mesh, merge as pmerge
from netobserv_tpu.sketch import state as sk, tenancy, tiered
from netobserv_tpu.utils import platform

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: countmin.update_two, hll.update, topk.walk x SLOT_ROUNDS, signal.update
MOSAIC_CALLS = 5
CFG = sk.SketchConfig()
BATCH = 8192


def lowered(fn, *args, debug_info: bool = False) -> str:
    """`fn(*args)` cross-lowered for the TPU, as StableHLO text."""
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        return fn.trace(*args).lower(lowering_platforms=("tpu",)).as_text(
            debug_info=debug_info)


def mosaic_calls(fn, *args) -> int:
    """Cross-lower `fn(*args)` for the TPU and count its Mosaic kernels;
    the module must carry the entry's watch name."""
    text = lowered(fn, *args)
    assert f"module @jit_{fn.name} " in text, (fn.name, text[:200])
    return text.count("tpu_custom_call")


def scopes_of(text: str) -> set:
    """The named scopes in the op metadata of a debug-info lowering: of each
    `loc("<a>/<b>/<primitive>")`, the first component that is no transform
    wrapper such as `jit(main)` — how a device capture attributes an op."""
    found = set()
    for m in re.finditer(r'loc\("([^"]+)"', text):
        for part in m.group(1).split("/")[:-1]:
            if part and not re.fullmatch(r"\w+\(.*\)", part):
                found.add(part)
                break
    return found


#: sketch/state.py's one-level scopes inside every ingest executable
FOLD_SCOPES = {"hash", "countmin", "topk", "hll_src", "hll_grids",
               "quantile", "signals", "totals"}


def family_caps(bpl: int, wide: bool):
    """(caps, the family's part of an entry's name) as the exporter builds
    its narrow ladder and the top entry's wide twin."""
    if wide:
        return flowpack.wide_resident_caps(bpl), "_wide"
    return flowpack.default_resident_caps(bpl), ""


def resident_ladder_entry(k: int, cfg=CFG, lanes: int = 8,
                          slots: int = 1 << 18, wide: bool = False):
    """(fn, args) of the exporter's single-device ladder entry x<k>."""
    bpl = BATCH // lanes
    caps, family = family_caps(bpl, wide)
    fn = sk.make_ingest_resident_lanes_fn(
        bpl, caps, k * lanes, slots,
        name=f"ingest_resident_lanes{family}_x{k}")
    tables = jax.ShapeDtypeStruct((4 * lanes * slots, sk.KEY_WORDS),
                                  jnp.uint32)
    flat = jax.ShapeDtypeStruct(
        (k * lanes * flowpack.resident_buf_len(bpl, caps),), jnp.uint32)
    return fn, (state_shapes(cfg), tables, flat)


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
    # 8 lanes: what an 8+-core host resolves (config.resolved_pack_threads)
    fn, args = resident_ladder_entry(k, cfg)
    assert fn.name == f"ingest_resident_lanes_x{k}"
    assert mosaic_calls(fn, *args) == MOSAIC_CALLS


#: collector-wide-1chip (cellbench/configs): Count-Min 4 x 2^22, 2^20 slots
WIDE = CFG._replace(cm_width=1 << 22)


@pytest.mark.parametrize("k", [1, 2, 4])
def test_wide_ladder_entry_folds_countmin_with_the_scatter(k):
    """Above `sk.CM_FACTORED_WIDTHS` the automatic rule keeps the other four
    kernels and hands the Count-Min fold to XLA's scatter, under a scope
    that says so; at the default width the same entry keeps the kernel."""
    fn, args = resident_ladder_entry(k, WIDE, slots=1 << 20)
    text = lowered(fn, *args, debug_info=True)
    kernels = set(re.findall(r'kernel_name = "([^"]+)"', text))
    assert kernels == {"hll_update", "topk_slot_walk", "signal_update"}
    assert text.count("tpu_custom_call") == MOSAIC_CALLS - 1
    assert "/countmin/scatter/" in text
    assert "/countmin/factored/" not in text
    fn, args = resident_ladder_entry(k)
    default = lowered(fn, *args, debug_info=True)
    assert "/countmin/factored/" in default
    assert "/countmin/scatter/" not in default


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


# --- what a device capture reads: module names, scopes, kernel names --------

def test_fold_ops_carry_one_scope_per_sketch_update_and_kernels_their_names():
    fn, args = resident_ladder_entry(1)
    text = lowered(fn, *args, debug_info=True)
    assert "module @jit_ingest_resident_lanes_x1 " in text
    assert scopes_of(text) >= FOLD_SCOPES | {"resident_decode"}
    # nowhere deeper than one level: no scope nests in another
    for a in FOLD_SCOPES | {"resident_decode"}:
        for b in FOLD_SCOPES | {"resident_decode"}:
            assert f"/{a}/{b}/" not in text, (a, b)
    kernels = set(re.findall(r'kernel_name = "([^"]+)"', text))
    assert kernels == {"countmin_update_two", "hll_update", "topk_slot_walk",
                       "signal_update"}


@pytest.mark.parametrize("with_tables", [False, True])
def test_roll_lowers_named_and_scoped(with_tables):
    fn = sk.make_roll_fn(CFG, with_tables=with_tables)
    text = lowered(fn, state_shapes(), debug_info=True)
    assert "module @jit_roll " in text
    assert "roll" in scopes_of(text)


@pytest.mark.parametrize("k", [1, 2, 4])
def test_sharded_resident_ladder_entry_lowers_named_and_scoped(k):
    """The mesh cell's ingests: `jit_sharded_ingest_resident_x<k>`, the
    same scopes inside the shard_map body."""
    mesh = make_mesh(MeshSpec(data=4), devices=jax.devices()[:4])
    lanes = 2
    bpl = BATCH // (4 * lanes)
    caps = flowpack.default_resident_caps(bpl)
    name = f"sharded_ingest_resident_x{k}"
    fn = pmerge.make_sharded_ingest_resident_fn(
        mesh, CFG, bpl, caps, 1 << 18, lanes=k * lanes, watch_name=name)
    dist = jax.eval_shape(lambda: pmerge.init_dist_state(CFG, mesh))
    tables = jax.ShapeDtypeStruct((4 * 4 * lanes << 18, sk.KEY_WORDS),
                                  jnp.uint32)
    flat = jax.ShapeDtypeStruct(
        (4 * k * lanes * flowpack.resident_buf_len(bpl, caps),), jnp.uint32)
    text = lowered(fn, dist, tables, flat, debug_info=True)
    assert f"module @jit_{name} " in text
    assert scopes_of(text) >= FOLD_SCOPES | {"resident_decode"}
    assert text.count("tpu_custom_call") == MOSAIC_CALLS


@pytest.mark.parametrize("cfg,kernels,form", [
    (CFG, {"countmin_update_two", "hll_update", "topk_slot_walk",
           "signal_update"}, "factored"),
    (WIDE, {"hll_update", "topk_slot_walk", "signal_update"}, "scatter")],
    ids=["default", "wide"])
def test_width_sharded_ladder_entry_lowers_with_the_kernels(cfg, kernels,
                                                            form):
    """Mesh data=2 x sketch=2 (collector-wide-mesh2x2's): the owner-sharded
    fold is the whole-width fold with ownership as a row mask — the same
    kernels, the Count-Min form `fold_forms` picks at the LOCAL width (2^15:
    factored; 2^21: the scatter), `owner_mask` a scope of its own."""
    mesh = make_mesh(MeshSpec(data=2, sketch=2), devices=jax.devices()[:4])
    lanes, k = 4, 4
    bpl = BATCH // (2 * lanes)
    caps = flowpack.default_resident_caps(bpl)
    name = f"sharded_ingest_resident_x{k}"
    fn = pmerge.make_sharded_ingest_resident_fn(
        mesh, cfg, bpl, caps, 1 << 18, lanes=k * lanes, watch_name=name)
    dist = jax.eval_shape(lambda: pmerge.init_dist_state(cfg, mesh))
    tables = jax.ShapeDtypeStruct((2 * 4 * lanes << 18, sk.KEY_WORDS),
                                  jnp.uint32)
    flat = jax.ShapeDtypeStruct(
        (2 * k * lanes * flowpack.resident_buf_len(bpl, caps),), jnp.uint32)
    text = lowered(fn, dist, tables, flat, debug_info=True)
    assert f"module @jit_{name} " in text
    assert scopes_of(text) >= FOLD_SCOPES | {"resident_decode", "owner_mask"}
    assert set(re.findall(r'kernel_name = "([^"]+)"', text)) == kernels
    # inside the shard_map body an op's name starts at the scope
    assert f'loc("countmin/{form}/' in text
    assert "stablehlo.all_" not in text and "collective_permute" not in text


def test_width_sharded_merge_gathers_the_planes_under_their_scope():
    """The table snapshot of a width-sharded roll: each merged Count-Min
    plane leaves as [sketch, depth, width / sketch], gathered over the
    sketch axis under `merge_tables_gather`."""
    mesh = make_mesh(MeshSpec(data=2, sketch=2), devices=jax.devices()[:4])
    fn = pmerge.make_merge_fn(mesh, CFG, with_tables=True)
    dist = jax.eval_shape(lambda: pmerge.init_dist_state(CFG, mesh))
    text = lowered(fn, dist, debug_info=True)
    assert {"merge_allreduce", "merge_topk_gather",
            "merge_tables_gather"} <= scopes_of(text)
    _, _, tables = jax.eval_shape(fn, dist)
    assert tables["cm_bytes"].shape == (2, CFG.cm_depth, CFG.cm_width // 2)
    assert tables["cm_pkts"].shape == tables["cm_bytes"].shape


def test_sharded_merge_lowers_named_with_its_collectives_scoped():
    mesh = make_mesh(MeshSpec(data=4), devices=jax.devices()[:4])
    fn = pmerge.make_merge_fn(mesh, CFG, with_tables=True)
    dist = jax.eval_shape(lambda: pmerge.init_dist_state(CFG, mesh))
    text = lowered(fn, dist, debug_info=True)
    assert "module @jit_sharded_merge " in text
    assert {"merge_allreduce", "merge_topk_gather"} <= scopes_of(text)
    # every collective of the roll lies under one of the two scopes
    locs = dict(re.findall(r'(#loc\d+) = loc\("([^"]+)"', text))
    seen = 0
    for line in text.splitlines():
        m = re.search(r'stablehlo\.(all_reduce|all_gather).*loc\((#loc\d+)\)$',
                      line)
        if m:
            seen += 1
            assert re.search(r"(^|/)merge_(allreduce|topk_gather)/",
                             locs[m.group(2)]), (m.group(1), locs[m.group(2)])
    assert seen


def test_tenant_roll_and_fold_delta_lower_named():
    n = 4
    stack = tenancy.TenantStack(n, CFG, BATCH)
    state = jax.eval_shape(lambda: tenancy.init_stacked_state(CFG, n))
    assert "module @jit_tenant_roll " in lowered(stack._roll, state)
    dense = jax.ShapeDtypeStruct((n, BATCH * sk.DENSE_WORDS), jnp.uint32)
    assert "module @jit_tenant_ingest " in lowered(stack._ingest, state,
                                                   dense)


# --- compiled for a described v5e: the key tables are never relaid ----------

@pytest.fixture(scope="module")
def v5e():
    """A v5e 2x2 host described to the TPU compiler (no chip attached), with
    the persistent compile cache off while this module's tests run: such a
    compile can be written to it but not read back without a chip. Only
    this file describes a topology (one process may hold libtpu)."""
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield topo
    jax.config.update("jax_enable_compilation_cache", cache_was)


def entry_of(text: str) -> tuple:
    """(header line, [instruction lines]) of the entry computation of a
    compiled module's HLO text."""
    entry = text[text.index("\nENTRY "):]
    return (text.splitlines()[0],
            entry[:entry.index("\n}")].splitlines()[2:])


def compiled_entry(fn, *args) -> tuple:
    """`entry_of` `fn(*args)` compiled for the TPU the arguments' shardings
    describe."""
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        return entry_of(fn.trace(*args).lower(
            lowering_platforms=("tpu",)).compile().as_text())


def assert_only_the_scatter_is_table_sized(header, entry, n_elements):
    """In the entry computation, the instructions whose result holds
    `n_elements` or more: the table's parameter, ONE fusion — the new-key
    scatter, reading that parameter, its output aliased onto it — and the
    root tuple. A pad, copy, slice or transpose of the table is a relayout
    of hundreds of MB a fold (PERF.md section 6, PR 33)."""
    big = {}
    for line in entry:
        m = re.match(r"\s*(ROOT )?%(\S+) = (\(.*?\)|\S+) ([\w-]+)\(", line)
        assert m, line[:200]
        shapes = re.findall(r"\b(?:pred|bf16|[usf]\d+)\[([\d,]*)\]",
                            m.group(3))
        if any(s and np.prod([int(d) for d in s.split(",")]) >= n_elements
               for s in shapes):
            big[m.group(2)] = (m.group(4), bool(m.group(1)), line)
    ops = sorted(op for op, _, _ in big.values())
    assert ops == ["fusion", "parameter", "tuple"], [
        line[:160] for _, _, line in big.values()]
    (param, (_, _, pline)), = [kv for kv in big.items()
                               if kv[1][0] == "parameter"]
    (_, _, fline), = [v for v in big.values() if v[0] == "fusion"]
    assert "resident_decode/scatter" in fline and f"fusion(%{param}," in fline
    assert next(r for op, r, _ in big.values() if op == "tuple")
    number = re.search(r"parameter\((\d+)\)", pline).group(1)
    assert re.search(rf"\({number}, {{}}, may-alias\)", header), header[:300]


@pytest.mark.parametrize("k,wide", [(1, False), (4, True)],
                         ids=["x1", "wide_x4"])
def test_x1_entry_compiles_with_no_table_sized_op_but_the_scatter(v5e, k,
                                                                  wide):
    """`ingest_resident_lanes_x1` at default geometry: 8 of the 32 lanes of
    the array the x4 entry needs, 2^18 slots each. And the top entry's wide
    lane family (`ingest_resident_lanes_wide_x4`: 32 new-key lanes of 384
    rows in the one combined scatter): a second program on the same table,
    held to the same."""
    from jax.sharding import SingleDeviceSharding
    one = SingleDeviceSharding(v5e.devices[0])
    fn, args = resident_ladder_entry(k, wide=wide)
    args = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=one), args)
    header, entry = compiled_entry(fn, *args)
    assert_only_the_scatter_is_table_sized(
        header, entry, sk.KEY_WORDS * 32 * (1 << 18))


def compiled_per_shard_x1(v5e, ndata: int, nsk: int, lanes: int,
                          slots: int = 1 << 18, k: int = 1,
                          wide: bool = False) -> str:
    """`sharded_ingest_resident_x1` (or `_x<k>`, or the wide family's) of
    mesh data=`ndata` x sketch=`nsk` at the default geometry, `lanes` pack
    lanes a shard, compiled for the described chips: the HLO text of one
    chip's program."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(v5e.devices).reshape(ndata, nsk),
                ("data", "sketch"))
    bpl = BATCH // (ndata * lanes)
    caps, family = family_caps(bpl, wide)
    fn = pmerge.make_sharded_ingest_resident_fn(
        mesh, CFG, bpl, caps, slots, lanes=k * lanes,
        watch_name=f"sharded_ingest_resident{family}_x{k}")
    cpu_mesh = make_mesh(MeshSpec(data=ndata, sketch=nsk),
                         devices=jax.devices()[:4])
    shapes = jax.eval_shape(lambda: (
        pmerge.init_dist_state(CFG, cpu_mesh),
        pmerge.init_resident_tables(cpu_mesh, slots, lanes=4 * lanes)))
    flat = jax.ShapeDtypeStruct(
        (ndata * k * lanes * flowpack.resident_buf_len(bpl, caps),),
        jnp.uint32)
    dist, tables, flat = jax.tree.map(
        lambda x, spec: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=NamedSharding(mesh, spec)),
        (*shapes, flat),
        (pmerge._state_specs(sk.init_state(CFG)), P("data"), P("data")))
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        return fn.trace(dist, tables, flat).lower(
            lowering_platforms=("tpu",)).compile().as_text()


@pytest.mark.parametrize("k,wide", [(1, False), (4, True)],
                         ids=["x1", "wide_x4"])
def test_per_shard_entry_compiles_with_no_table_sized_op_but_the_scatter(
        v5e, k, wide):
    """`sharded_ingest_resident_x1` on mesh data=4: each chip's program
    takes its rows of the sharded table as the same 2-D array — and so does
    `sharded_ingest_resident_wide_x4`, the top entry's wide lane family."""
    lanes, slots = 2, 1 << 18
    text = compiled_per_shard_x1(v5e, 4, 1, lanes, slots, k, wide)
    assert_only_the_scatter_is_table_sized(
        *entry_of(text), sk.KEY_WORDS * 4 * lanes * slots)


def test_width_sharded_entry_compiles_with_kernels_and_no_collective(v5e):
    """The same entry on mesh data=2 x sketch=2 (4 lanes a shard): the
    chip's compiler takes the five kernels inside the shard_map, puts in no
    collective, and relays no table. (At collector-wide-mesh2x2's sizes —
    2^21 a chip, 2^20 slots — it compiles with four and XLA's scatter: by
    hand, PERF.md section 6, PR 34; 25 s, too long for this tier.)"""
    lanes, slots = 4, 1 << 18
    text = compiled_per_shard_x1(v5e, 2, 2, lanes, slots)
    assert text.count('custom_call_target="tpu_custom_call"') == MOSAIC_CALLS
    for coll in ("all-reduce", "all-gather", "collective-permute",
                 "reduce-scatter", "all-to-all"):
        assert coll not in text, coll
    assert_only_the_scatter_is_table_sized(
        *entry_of(text), sk.KEY_WORDS * 4 * lanes * slots)


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


def test_no_tpu_means_nonzero_exit_and_no_result():
    # chip_smoke never honours a CPU request
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")], cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True,
        text=True, timeout=120)
    assert r.returncode != 0, r.stdout[-2000:]
    assert "no TPU" in r.stderr, r.stderr[-2000:]
    assert '"metric"' not in r.stdout and '"ok"' not in r.stdout
