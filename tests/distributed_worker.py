"""Worker for the 2-process jax.distributed CPU test (launched by
tests/test_distributed.py). Exercises parallel/distributed.py's bootstrap and
then runs the REAL sharded ingest + ICI/DCN merge over a mesh spanning both
processes, asserting the merged report."""

import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("JAX_CPU_COLLECTIVES_IMPLEMENTATION", "gloo")
xla = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in xla:
    os.environ["XLA_FLAGS"] = xla + " --xla_force_host_platform_device_count=2"

import jax  # noqa: E402

# distributed init MUST precede anything that might touch the XLA backend —
# including importing modules that build jnp constants at import time
from netobserv_tpu.parallel.distributed import (  # noqa: E402
    maybe_initialize_distributed,
)

_initialized = maybe_initialize_distributed()

import numpy as np  # noqa: E402

from netobserv_tpu.parallel import MeshSpec, make_mesh  # noqa: E402
from netobserv_tpu.parallel import merge as pmerge  # noqa: E402
from netobserv_tpu.sketch import state as sk  # noqa: E402


def main() -> None:
    assert _initialized, "distributed init did not trigger"
    assert jax.process_count() == 2, jax.process_count()
    assert len(jax.devices()) == 4, len(jax.devices())  # 2 per process

    cfg = sk.SketchConfig(cm_depth=2, cm_width=1024, hll_precision=8,
                          perdst_buckets=32, perdst_precision=4, topk=32,
                          hist_buckets=64, ewma_buckets=32)
    mesh = make_mesh(MeshSpec(data=2, sketch=2))  # spans both processes
    dist = pmerge.init_dist_state(cfg, mesh)
    ingest_fn = pmerge.make_sharded_ingest_fn(mesh, cfg)
    merge_fn = pmerge.make_merge_fn(mesh, cfg)

    # every process provides the SAME global batch; device_put scatters it
    # across the cross-process sharding
    rng = np.random.default_rng(7)
    n = 2 * 256
    arrays = {
        "keys": rng.integers(0, 2**32, (n, 10), dtype=np.uint32),
        "bytes": rng.integers(1, 10_000, n).astype(np.float32),
        "packets": rng.integers(1, 10, n).astype(np.int32),
        "rtt_us": rng.integers(0, 5_000, n).astype(np.int32),
        "dns_latency_us": rng.integers(0, 100, n).astype(np.int32),
        "sampling": np.zeros(n, np.int32),
        "valid": np.ones(n, np.bool_),
    }
    dist = ingest_fn(dist, pmerge.shard_batch(mesh, arrays))
    dist, report = merge_fn(dist)
    jax.block_until_ready(report)
    # the merge emits a fully-replicated report (out_specs P()), so every
    # process can read it directly
    assert report.total_records.is_fully_replicated
    total = float(report.total_records)
    assert total == n, (total, n)
    print(f"DIST_OK records={total:.0f} procs={jax.process_count()} "
          f"mesh={dict(mesh.shape)}", flush=True)

    # optional volume leg (__graft_entry__._spanning_mesh_check): push a
    # zipf stream through the spanning mesh and assert recall vs the exact
    # oracle — every process computes the same oracle from the same seed
    n_volume = int(os.environ.get("NETOBSERV_WORKER_RECORDS", "0"))
    if n_volume <= 0:
        return
    batch = 2048
    n_distinct = 4000
    vrng = np.random.default_rng(99)
    universe = vrng.integers(0, 2**32, (n_distinct, 10), dtype=np.uint32)
    exact = np.zeros(n_distinct, np.float64)
    steps = max(1, n_volume // batch)
    dist = pmerge.init_dist_state(cfg, mesh)
    vingest = pmerge.make_sharded_ingest_fn(mesh, cfg)
    for _ in range(steps):
        ranks = np.minimum(vrng.zipf(1.2, batch) - 1, n_distinct - 1)
        byts = vrng.integers(64, 9000, batch).astype(np.float32)
        np.add.at(exact, ranks, byts.astype(np.float64))
        varrays = {
            "keys": universe[ranks],
            "bytes": byts,
            "packets": vrng.integers(1, 10, batch).astype(np.int32),
            "rtt_us": np.zeros(batch, np.int32),
            "dns_latency_us": np.zeros(batch, np.int32),
            "sampling": np.zeros(batch, np.int32),
            "valid": np.ones(batch, np.bool_),
        }
        dist = vingest(dist, pmerge.shard_batch(mesh, varrays))
        jax.block_until_ready(dist)
    dist, vreport = merge_fn(dist)
    jax.block_until_ready((dist, vreport))
    vtotal = float(vreport.total_records)
    assert vtotal == steps * batch, (vtotal, steps * batch)
    k = 20
    true_top = np.argsort(exact)[::-1][:k]
    got = {tuple(w) for w, v in zip(np.asarray(vreport.heavy.words),
                                    np.asarray(vreport.heavy.valid)) if v}
    recall = sum(tuple(universe[t]) in got for t in true_top) / k
    assert recall >= 0.85, f"spanning-mesh recall@{k} {recall:.2f}"
    print(f"DIST_VOLUME_OK records={vtotal:.0f} recall@{k}={recall:.3f} "
          f"procs={jax.process_count()} mesh={dict(mesh.shape)}", flush=True)


if __name__ == "__main__":
    sys.exit(main())
