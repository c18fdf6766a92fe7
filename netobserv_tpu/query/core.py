"""The ONE implementation of sketch query math, shared by the agent and
federation query surfaces (jax-free: numpy + the `ops/hashing` numpy twins
only, so it runs on accelerator-less hosts and never blocks on a device).

All functions operate on an immutable host-side **snapshot dict** published
at a window boundary:

- ``window``   int — the closed (or live, for a mid-window refresh) window id
- ``ts_ms``    int — publish wall time
- ``seq``      int — monotonically increasing publish sequence (the
                torn-read guard: snapshots swap as WHOLE dicts, so any
                reader holding one sees a single window's consistent view;
                pollers order responses by ``(window, seq)``)
- ``report``   dict — the rendered window report (`report_to_json` shape)
- ``cm_bytes``/``cm_pkts`` — the merged Count-Min planes: f32[depth, width],
                or on a width-sharded mesh f32[shards, depth, width / shards]
                (shard ``s`` is the independent local-width sketch of the
                keys `ops.countmin.owner_shard` gives to ``s``)

The CM error-bar math (Cormode–Muthukrishnan) and the victim-bucket naming
(DST_BUCKET_SEED via `ops/hashing`, never inlined) live ONLY here.
"""

from __future__ import annotations

import numpy as np


def victim_bucket_names(heavy_words: np.ndarray, heavy: list[dict],
                        n_buckets: int) -> dict[int, list]:
    """Best-effort victim names: heavy-hitter addresses hashed into the same
    EWMA victim buckets the anomaly signals use (numpy hash twin — naming
    must never dispatch a device op). BOTH directions name a victim: its
    inbound traffic buckets via the dst words, its outbound (e.g. a flooded
    server still serving) via the src words — the device folds both into one
    bucket family (state.py src_sym/dst_h1 share DST_BUCKET_SEED). Spoofed
    floods' own flows rarely make the heavy table, but the victim's
    legitimate traffic does.

    `heavy_words` are the (n, KEY_WORDS) packed key words of exactly the
    rows rendered into `heavy` (same order)."""
    from netobserv_tpu.ops.hashing import DST_BUCKET_SEED, hash_words_np

    names: dict[int, list] = {}
    if not len(heavy):
        return names
    for cols, field in ((heavy_words[:, 4:8], "DstAddr"),
                        (heavy_words[:, 0:4], "SrcAddr")):
        buckets = hash_words_np(cols, seed=DST_BUCKET_SEED) & (n_buckets - 1)
        for j, b in enumerate(buckets):
            lst = names.setdefault(int(b), [])
            if len(lst) < 3 and heavy[j][field] not in lst:
                lst.append(heavy[j][field])
    return names


def _stamp(snap: dict, payload: dict) -> dict:
    """Prefix every snapshot-backed payload with the (window, ts_ms, seq)
    triple pollers order by."""
    return {"window": snap["window"], "ts_ms": snap["ts_ms"],
            "seq": snap.get("seq", 0), **payload}


def _bound(plane: np.ndarray) -> float:
    """Cormode–Muthukrishnan: a point query of `plane` [depth, width]
    overestimates by at most (e / width) x N, N the mass folded into it
    (any one depth row sums to N)."""
    return np.e / plane.shape[1] * float(np.sum(plane[0]))


def cm_error_bars(snap: dict) -> dict:
    """The Cormode–Muthukrishnan overestimate bound of the snapshot's CM
    planes — THE error-bar math (shared by /query/frequency and
    /query/topk; the slot-table counts ARE CM point estimates, so the
    same bound applies to every rendered heavy hitter). On a width-sharded
    mesh every heavy hitter was scored by its owner shard's local-width
    plane: the bound stated is the widest of the shards' own."""
    cm = snap["cm_bytes"]
    planes = cm if cm.ndim == 3 else cm[None]
    return {
        "overestimate_bound_bytes": max(_bound(p) for p in planes),
        "confidence": 1.0 - float(np.exp(-planes.shape[1])),
    }


def topk_payload(snap: dict, n: int = 100) -> dict:
    n = max(1, min(int(n), 1024))
    # every EstBytes (and churn count) is a CM point estimate: true
    # count <= estimate <= true + bound with the stated confidence —
    # the same bars /query/frequency renders, from the ONE helper
    return _stamp(snap, {"topk": snap["report"]["HeavyHitters"][:n],
                         **cm_error_bars(snap)})


def churn_payload(snap: dict) -> dict:
    """Per-key heavy-hitter churn of the snapshot's window: ascents,
    descents, new-heavy entries, evicted keys and the table's eviction
    pressure, as rendered by the exporter under its configured
    SKETCH_CHURN_* gates (the one threshold truth). Counts carry the same
    CM error bars as /query/topk."""
    report = snap["report"]
    payload = {
        "ascents": report.get("FlowAscents", []),
        "descents": report.get("FlowDescents", []),
        "new_heavy": report.get("NewHeavyKeys", []),
        "evicted": report.get("EvictedKeys", []),
        "summary": report.get("HeavyChurn", {}),
        **cm_error_bars(snap),
    }
    return _stamp(snap, payload)


def cardinality_payload(snap: dict) -> dict:
    report = snap["report"]
    return _stamp(snap, {
        "distinct_src_estimate": report["DistinctSrcEstimate"],
        "records": report["Records"],
        "bytes": report["Bytes"]})


def victims_payload(snap: dict) -> dict:
    # the signal -> report-key map is the alerting plane's SIGNAL_FIELDS
    # (one truth: /query/victims, the zoo's SIGNALS tuple and the default
    # alert rules can never disagree about what a signal is called)
    from netobserv_tpu.alerts.rules import SIGNAL_FIELDS
    report = snap["report"]
    return _stamp(snap, {sig: report[key]
                         for sig, key in SIGNAL_FIELDS.items()})


def frequency_payload(snap: dict, src: str, dst: str, src_port: int = 0,
                      dst_port: int = 0, proto: int = 0) -> dict:
    """CM point query with error bars against the snapshot's merged planes —
    pure host numpy through the hashing twins. On a width-sharded mesh the
    key's OWNER shard answers (`hashing.owner_shard_np`): its plane is
    indexed at the local width and the bar comes from that shard's own mass
    and width, and the payload names `shard` and that local `width`."""
    cm = snap["cm_bytes"]
    cm_pkts = snap["cm_pkts"]
    from netobserv_tpu.model import binfmt
    from netobserv_tpu.model.columnar import pack_key_words
    from netobserv_tpu.model.flow import FlowKey
    from netobserv_tpu.ops.hashing import (
        base_hashes_multi_np, owner_shard_np)

    fk = FlowKey.make(src, dst, src_port, dst_port, proto)
    karr = np.zeros(1, binfmt.FLOW_KEY_DTYPE)
    karr["src_ip"][0] = np.frombuffer(fk.src_ip, np.uint8)
    karr["dst_ip"][0] = np.frombuffer(fk.dst_ip, np.uint8)
    karr["src_port"] = src_port
    karr["dst_port"] = dst_port
    karr["proto"] = proto
    words = pack_key_words(karr)
    h = base_hashes_multi_np(words)
    where = {}
    if cm.ndim == 3:
        shard = int(owner_shard_np(h["h1"], h["h2"], cm.shape[0])[0])
        cm, cm_pkts = cm[shard], cm_pkts[shard]
        where = {"shard": shard, "width": int(cm.shape[1])}
    d, w = cm.shape
    with np.errstate(over="ignore"):
        idx = (h["h1"][0] + np.arange(d, dtype=np.uint32) * h["h2"][0]) \
            & np.uint32(w - 1)
    return _stamp(snap, {
        "est_bytes": float(np.min(cm[np.arange(d), idx])),
        "est_packets": float(np.min(cm_pkts[np.arange(d), idx])),
        "overestimate_bound_bytes": _bound(cm),
        "overestimate_bound_packets": _bound(cm_pkts),
        "confidence": 1.0 - float(np.exp(-d)),
        **where,
    })
