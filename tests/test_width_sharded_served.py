"""A width-sharded collector (SKETCH_MESH_SHAPE=2x2: rows over data=2, the
Count-Min width and the slot top-K owner-sharded over sketch=2) on the served
path, against the plain reference of tests/owner_sharded_reference.py.

The exporter is built as `TpuSketchExporter.from_config` builds it on four
of conftest's eight host devices, fed evictions through `export_evicted`,
and asked through its own query routes: what the snapshot's planes hold must
EQUAL the reference's (integer bytes, every counter under 2^24), and what
`/query/frequency` and `/query/topk` answer must be the reference's answers.
The fold itself is pinned form against form (kernels in interpret mode
against the scatter twins), and the host's ownership hash against the
device's.
"""

import numpy as np
import pytest

import tests.conftest  # noqa: F401
import jax
import jax.numpy as jnp
from prometheus_client import generate_latest

from netobserv_tpu.datapath.fetcher import EvictedFlows
from netobserv_tpu.exporter.tpu_sketch import TpuSketchExporter
from netobserv_tpu.metrics.registry import Metrics
from netobserv_tpu.model import binfmt
from netobserv_tpu.model.columnar import pack_key_words
from netobserv_tpu.model.flow import ip_to_16
from netobserv_tpu.ops import countmin, hashing
from netobserv_tpu.parallel import make_mesh, MeshSpec, merge as pmerge
from netobserv_tpu.sketch import state as sk
from netobserv_tpu.utils import retrace

from tests import owner_sharded_reference as ref
from tests.test_parallel import KERNEL_CFG, make_arrays

SHARDS = 2
#: whole width 2^12: each sketch shard's planes are 4 x 2^11
CFG = sk.SketchConfig(cm_depth=4, cm_width=1 << 12, hll_precision=9,
                      perdst_buckets=128, perdst_precision=5,
                      persrc_buckets=128, persrc_precision=5, topk=128,
                      hist_buckets=64, ewma_buckets=128)
LOCAL = CFG.cm_width // SHARDS
N_KEYS, N_RECORDS, BATCH = 300, 3000, 256


def five_tuple(i: int) -> tuple:
    return (f"10.{i // 250}.{i % 250}.7", f"172.16.{i % 7}.9", 1024 + i,
            443 if i % 3 else 53, 6 if i % 2 else 17)


def make_records():
    """(events, key words, bytes, packets): N_RECORDS records over N_KEYS
    five-tuples, skewed so that a head exists; integer bytes whose per-key
    sums stay far under 2^24."""
    rng = np.random.default_rng(20261004)
    ids = np.minimum(rng.zipf(1.3, N_RECORDS) - 1, N_KEYS - 1)
    ev = np.zeros(N_RECORDS, dtype=binfmt.FLOW_EVENT_DTYPE)
    for row, i in enumerate(ids):
        src, dst, sport, dport, proto = five_tuple(int(i))
        ev[row]["key"]["src_ip"] = np.frombuffer(ip_to_16(src), np.uint8)
        ev[row]["key"]["dst_ip"] = np.frombuffer(ip_to_16(dst), np.uint8)
        ev[row]["key"]["src_port"], ev[row]["key"]["dst_port"] = sport, dport
        ev[row]["key"]["proto"] = proto
    ev["stats"]["bytes"] = rng.integers(40, 1500, N_RECORDS)
    ev["stats"]["packets"] = rng.integers(1, 9, N_RECORDS)
    return (ev, pack_key_words(ev["key"]),
            ev["stats"]["bytes"].astype(np.float64),
            ev["stats"]["packets"].astype(np.float64))


@pytest.fixture(scope="module")
def served():
    """One closed window of a 2x2 exporter over `make_records`."""
    events, words, byts, pkts = make_records()
    metrics, reports = Metrics(), []
    exp = TpuSketchExporter(batch_size=BATCH, window_s=3600.0, sketch_cfg=CFG,
                            mesh_shape="2x2", metrics=metrics,
                            sink=reports.append, resident_slots=1 << 10)
    try:
        for lo in range(0, N_RECORDS, 700):
            exp.export_evicted(EvictedFlows(events[lo:lo + 700].copy()))
        exp.flush()
        yield {"exp": exp, "snap": exp.query.get(), "reports": reports,
               "metrics": metrics, "words": words, "bytes": byts,
               "packets": pkts, "status": exp.query_status(),
               # what the reference says the deployment must hold
               "want": {"cm_bytes": ref.planes(words, byts, CFG.cm_depth,
                                               CFG.cm_width, SHARDS),
                        "cm_pkts": ref.planes(words, pkts, CFG.cm_depth,
                                              CFG.cm_width, SHARDS)},
               "rows": {r["fn"]: r for r in retrace.snapshot()}}
    finally:
        exp.close()


def ask(served, i: int) -> dict:
    src, dst, sport, dport, proto = five_tuple(i)
    code, body = served["exp"].query_routes.handle(
        "/query/frequency", {"src": src, "dst": dst, "src_port": str(sport),
                             "dst_port": str(dport), "proto": str(proto)})
    assert code == 200, body
    return body


def words_of(i: int) -> np.ndarray:
    src, dst, sport, dport, proto = five_tuple(i)
    key = np.zeros(1, binfmt.FLOW_KEY_DTYPE)
    key["src_ip"][0] = np.frombuffer(ip_to_16(src), np.uint8)
    key["dst_ip"][0] = np.frombuffer(ip_to_16(dst), np.uint8)
    key["src_port"], key["dst_port"], key["proto"] = sport, dport, proto
    return pack_key_words(key)


def test_the_snapshots_planes_equal_the_references(served):
    snap = served["snap"]
    assert snap["report"]["Records"] == N_RECORDS
    for table, values in (("cm_bytes", served["bytes"]),
                          ("cm_pkts", served["packets"])):
        want, got = served["want"][table], snap[table]
        assert isinstance(got, np.ndarray)
        assert got.shape == (SHARDS, CFG.cm_depth, LOCAL)
        np.testing.assert_array_equal(got, want, err_msg=table)
        # each shard holds its owned keys' mass alone, whole in every row
        assert got[:, 0].sum() == values.sum()


@pytest.mark.parametrize("i", [0, 1, 2, 5, 40, 150, N_KEYS - 1, N_KEYS + 77],
                         ids=lambda i: f"key{i}")
def test_frequency_answers_from_the_owner_shard(served, i):
    """Head keys, tail keys, the catch-all last key and one never sent."""
    body, words = ask(served, i), words_of(i)
    h1, h2 = ref.flow_hashes(words)
    want_b, want_p = served["want"]["cm_bytes"], served["want"]["cm_pkts"]
    shard = int(ref.owner(h1, h2, SHARDS)[0])
    assert (body["shard"], body["width"]) == (shard, LOCAL)
    assert body["est_bytes"] == ref.estimate(want_b, words)[0]
    assert body["est_packets"] == ref.estimate(want_p, words)[0]
    keys, sums = ref.exact_sums(served["words"], served["bytes"])
    sent = np.nonzero((keys == words[0]).all(axis=1))[0]
    exact = sums[sent[0]] if len(sent) else 0.0
    # the one-chip guarantee: never under, and inside the route's own bar,
    # which is the OWNER shard's mass over the LOCAL width
    assert body["est_bytes"] >= exact
    assert body["overestimate_bound_bytes"] == pytest.approx(
        np.e / LOCAL * want_b[shard, 0].sum())
    assert body["est_bytes"] <= exact + body["overestimate_bound_bytes"]
    assert body["confidence"] == pytest.approx(1 - np.exp(-CFG.cm_depth))


def test_topk_and_the_sinks_heavy_hitters_are_the_exact_head(served):
    code, top = served["exp"].query_routes.handle("/query/topk", {"n": "128"})
    assert code == 200
    keys, sums = ref.heavy_hitters(served["words"], served["bytes"], 32)
    got = top["topk"][:32]
    by_tuple = {five_tuple(i): i for i in range(N_KEYS)}
    for entry, key, total in zip(got, keys, sums):
        i = by_tuple[(entry["SrcAddr"], entry["DstAddr"], entry["SrcPort"],
                      entry["DstPort"], entry["Proto"])]
        # no collision at this load: the estimate IS the exact sum
        assert (words_of(i)[0] == key).all() and entry["EstBytes"] == total
    sunk = served["reports"][-1]["HeavyHitters"]
    assert sunk and top["topk"][:len(sunk)] == sunk
    # the bound /query/topk states covers every shard's plane
    assert top["overestimate_bound_bytes"] == pytest.approx(
        np.e / LOCAL * served["want"]["cm_bytes"][:, 0].sum(axis=1).max())


def test_status_gauge_and_executable_rows_name_the_mesh(served):
    st = served["status"]
    assert st["mesh"] == {"data": 2, "sketch": 2}
    assert st["cm_local_width"] == LOCAL
    text = generate_latest(served["metrics"].registry).decode()
    assert 'sketch_mesh_shards{axis="data"} 2.0' in text
    assert 'sketch_mesh_shards{axis="sketch"} 2.0' in text
    rows = [r for fn, r in served["rows"].items()
            if fn.startswith("sharded_ingest_resident") and r["calls"]]
    assert rows
    for row in rows:
        # on the CPU the automatic rule folds with the scatter, chosen at
        # the LOCAL width
        assert (row["mesh"], row["countmin"], row["countmin_width"]) == (
            "2x2", "scatter", str(LOCAL)), row


def test_the_hosts_ownership_hash_is_the_devices():
    rng = np.random.default_rng(7)
    h1 = rng.integers(0, 2**32, 4096, dtype=np.uint32)
    h2 = rng.integers(0, 2**32, 4096, dtype=np.uint32) | np.uint32(1)
    for shards in (2, 3, 4, 8):
        dev = np.asarray(countmin.owner_shard(jnp.asarray(h1),
                                              jnp.asarray(h2), shards))
        host = hashing.owner_shard_np(h1, h2, shards)
        np.testing.assert_array_equal(host, dev)
        np.testing.assert_array_equal(
            host, ref.owner(h1.astype(np.uint64), h2.astype(np.uint64),
                            shards))
        assert len(np.unique(host)) == shards


def test_the_owner_sharded_fold_with_kernels_is_its_scatter_twin():
    """use_pallas=True (the kernels, interpreted on the CPU) against False
    (every scatter form) on a 2x2 mesh: the same distributed state bit for
    bit after two folds, and the same merged report and table snapshot."""
    mesh = make_mesh(MeshSpec(data=2, sketch=2), devices=jax.devices()[:4])
    rng = np.random.default_rng(5)
    batches = [make_arrays(2 * 64, rng, n_distinct=96) for _ in range(2)]
    for b in batches:   # integer sums: f32 adds in any order agree
        b["bytes"] = np.floor(b["bytes"])
    out = {}
    for use_pallas in (True, False):
        cfg = KERNEL_CFG._replace(use_pallas=use_pallas)
        assert sk.fold_forms(cfg.cm_width // 2, use_pallas) == (
            use_pallas, "factored" if use_pallas else "scatter")
        fold = pmerge.make_sharded_ingest_fn(mesh, cfg, donate=False)
        dist = pmerge.init_dist_state(cfg, mesh)
        for b in batches:
            dist = fold(dist, pmerge.shard_batch(mesh, b))
        rolled = pmerge.make_merge_fn(mesh, cfg, with_tables=True)(
            jax.tree.map(jnp.copy, dist))
        out[use_pallas] = jax.tree.map(np.asarray, (dist, rolled[1:]))
        assert fold.stats()["countmin"] == (
            "factored" if use_pallas else "scatter")
    jax.tree.map(np.testing.assert_array_equal, out[True], out[False])
    # and the planes are the reference's
    _, (_report, tables) = out[True]
    words = np.concatenate([b["keys"] for b in batches])
    byts = np.concatenate([b["bytes"] for b in batches])
    np.testing.assert_array_equal(
        tables["cm_bytes"], ref.planes(words, byts, KERNEL_CFG.cm_depth,
                                       KERNEL_CFG.cm_width, 2))
