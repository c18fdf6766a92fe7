"""Fused signal-plane Pallas kernel equivalence (interpret mode on the CPU
mesh, like the sibling Count-Min/HLL kernel suites; the same kernel compiles
through Mosaic on TPU).

The kernel replaces the serialized per-table scatter chain with ONE batch
walk over all eight signal tables (ops/pallas/signal_kernel.py). Masses are
integer-valued f32 well under 2^24, so float sums are order-independent and
the equivalence pins are BIT-exact, not approximate."""

import numpy as np

import tests.conftest  # noqa: F401
import jax
import jax.numpy as jnp

from netobserv_tpu.ops.pallas import signal_kernel
from netobserv_tpu.sketch import state as sk

KW = 10
M = 256


def _planes(m: int = M, n_dscp: int = 64, n_causes: int = 128):
    return signal_kernel.SignalPlanes(
        ddos_rate=jnp.zeros((m,), jnp.float32),
        syn_rate=jnp.zeros((m,), jnp.float32),
        drops_rate=jnp.zeros((m,), jnp.float32),
        synack=jnp.zeros((m,), jnp.float32),
        conv_fwd=jnp.zeros((m,), jnp.float32),
        conv_rev=jnp.zeros((m,), jnp.float32),
        dscp_bytes=jnp.zeros((n_dscp,), jnp.float32),
        drop_causes=jnp.zeros((n_causes,), jnp.float32))


def _scatter_reference(planes, idx, vals):
    """The un-fused chain: one scatter-add per (family row, table)."""
    out = []
    fam = (0, 0, 0, 1, 2, 2)  # main rows -> index families dst/src/pair
    tables = list(planes[:6])
    for row, table in enumerate(tables):
        out.append(np.asarray(
            table.at[idx[fam[row]]].add(vals[row], mode="drop")))
    dscp = planes.dscp_bytes.at[idx[3]].add(vals[6], mode="drop")
    causes = planes.drop_causes.at[idx[4]].add(vals[7], mode="drop")
    return out + [np.asarray(dscp), np.asarray(causes)]


def _random_batch(b: int, m: int = M, seed: int = 1):
    rng = np.random.default_rng(seed)
    idx = np.stack([
        rng.integers(0, m, b), rng.integers(0, m, b), rng.integers(0, m, b),
        rng.integers(0, 64, b), rng.integers(0, 128, b),
    ]).astype(np.int32)
    # integer-valued f32 masses -> order-independent sums -> exact pins
    vals = rng.integers(0, 2000, (8, b)).astype(np.float32)
    vals *= rng.random((8, b)) < 0.8  # zero rows model masked records
    return jnp.asarray(idx), jnp.asarray(vals)


def test_signal_kernel_matches_scatter_chain_bit_exact():
    idx, vals = _random_batch(2048)
    planes = _planes()
    got = signal_kernel.update(planes, idx, vals, interpret=True)
    want = _scatter_reference(planes, idx, vals)
    for g, w, name in zip(got, want, signal_kernel.SignalPlanes._fields):
        np.testing.assert_array_equal(np.asarray(g), w, err_msg=name)


def test_signal_kernel_accumulates_and_pads_ragged():
    idx, vals = _random_batch(777, seed=4)  # not a CHUNK_B multiple
    planes = _planes()
    for _ in range(3):
        planes = signal_kernel.update(planes, idx, vals, interpret=True)
    want = _planes()
    for _ in range(3):
        want = signal_kernel.SignalPlanes(*(
            jnp.asarray(a) for a in _scatter_reference(want, idx, vals)))
    for g, w, name in zip(planes, want, signal_kernel.SignalPlanes._fields):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=name)


def test_eligibility_gate():
    assert signal_kernel.eligible(_planes(256))
    assert not signal_kernel.eligible(_planes(96))  # not lane-aligned
    bad = _planes()._replace(synack=jnp.zeros((128,), jnp.float32))
    assert not signal_kernel.eligible(bad)  # mismatched widths
    assert not signal_kernel.eligible(
        _planes(n_causes=signal_kernel.AUX_W + 1))


def _arrays(b: int, seed: int, features: bool = True):
    rng = np.random.default_rng(seed)
    out = {
        "keys": jnp.asarray(rng.integers(0, 2**32, (b, KW),
                                         dtype=np.uint32)),
        "bytes": jnp.asarray(rng.integers(1, 2000, b).astype(np.float32)),
        "packets": jnp.asarray(rng.integers(1, 8, b).astype(np.int32)),
        "rtt_us": jnp.asarray(rng.integers(0, 900, b).astype(np.int32)),
        "dns_latency_us": jnp.zeros(b, jnp.int32),
        "sampling": jnp.asarray(rng.integers(0, 4, b).astype(np.int32)),
        "valid": jnp.asarray(rng.random(b) < 0.9),
    }
    if features:
        out.update({
            "tcp_flags": jnp.asarray(
                rng.integers(0, 1 << 9, b).astype(np.int32)),
            "dscp": jnp.asarray(rng.integers(0, 64, b).astype(np.int32)),
            "markers": jnp.asarray(rng.integers(0, 4, b).astype(np.int32)),
            "drop_bytes": jnp.asarray(
                rng.integers(0, 200, b).astype(np.int32)),
            "drop_packets": jnp.asarray(
                rng.integers(0, 3, b).astype(np.int32)),
            "drop_cause": jnp.asarray(
                rng.integers(0, 300, b).astype(np.int32)),
        })
    return out


def test_full_ingest_signal_planes_bit_exact_vs_unfused():
    """The WHOLE ingest with use_pallas=True (signal kernel + CM + HLL
    kernels, all interpret mode on CPU) against the scatter path: every
    signal plane must match bit-for-bit, feature lanes included."""
    cfg = sk.SketchConfig(cm_width=1024, topk=16, hll_precision=10,
                          perdst_buckets=32, perdst_precision=4,
                          persrc_buckets=32, persrc_precision=4,
                          hist_buckets=64, ewma_buckets=M)
    for features in (True, False):
        arrays = _arrays(700, seed=2, features=features)
        ref = jax.jit(lambda s, a: sk.ingest(s, a, use_pallas=False))(
            sk.init_state(cfg), arrays)
        pal = jax.jit(lambda s, a: sk.ingest(s, a, use_pallas=True))(
            sk.init_state(cfg), arrays)
        for f in ("synack", "conv_fwd", "conv_rev", "dscp_bytes",
                  "drop_causes"):
            np.testing.assert_array_equal(
                np.asarray(getattr(ref, f)), np.asarray(getattr(pal, f)),
                err_msg=f"{f} features={features}")
        for f in ("ddos", "syn", "drops_ewma"):
            np.testing.assert_array_equal(
                np.asarray(getattr(ref, f).rate),
                np.asarray(getattr(pal, f).rate),
                err_msg=f"{f}.rate features={features}")
        assert float(ref.total_records) == float(pal.total_records)
        np.testing.assert_allclose(np.asarray(ref.cm_bytes.counts),
                                   np.asarray(pal.cm_bytes.counts),
                                   rtol=1e-6)
