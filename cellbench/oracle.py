"""The plain reference: an exact numpy aggregation of the records themselves,
and the comparison that decides `correct`.

Independent of the code under test: it groups the flow events by their key
bytes (no sketch, no dictionary, no universe index), so it holds for any mix —
keys from the universe or stamped fresh. The gates are `PERF.md` section 2's,
as `chip_smoke.py`'s `grade_window` ran them on the chip (PR 21); no later PR
may weaken them.
"""

from __future__ import annotations

import math
import socket

import numpy as np

RECALL_AT_100 = 0.99        # BASELINE.json: < 1% heavy-hitter recall loss
DISTINCT_SRC_ERR = 0.03     # ~3.7 sigma of HLL p=14's 0.81% standard error
CM_PROBES = 20

_V4_MAPPED = b"\x00" * 10 + b"\xff\xff"


def render_ip(raw: bytes) -> str:
    if raw[:12] == _V4_MAPPED:
        return socket.inet_ntop(socket.AF_INET, raw[12:16])
    return socket.inet_ntop(socket.AF_INET6, raw)


def five_tuple(key) -> tuple:
    """One FLOW_KEY record as the report and the query routes render it."""
    return (render_ip(key["src_ip"].tobytes()),
            render_ip(key["dst_ip"].tobytes()),
            int(key["src_port"]), int(key["dst_port"]), int(key["proto"]))


def exact(events: np.ndarray) -> dict:
    """The exact answers over `events` (FLOW_EVENT records): bytes per
    distinct key, heaviest first, and the number of distinct sources."""
    keys = np.ascontiguousarray(events["key"])
    as_bytes = keys.view((np.void, keys.dtype.itemsize)).reshape(-1)
    uniq, first, inv = np.unique(as_bytes, return_index=True,
                                 return_inverse=True)
    byts = np.bincount(inv.reshape(-1),
                       weights=events["stats"]["bytes"].astype(np.float64),
                       minlength=len(uniq))
    order = np.argsort(-byts, kind="stable")
    src = np.ascontiguousarray(keys["src_ip"])
    return {"n": len(events), "keys": keys[first[order]],
            "bytes": byts[order],
            "distinct_src": len(np.unique(src.view((np.void, 16))))}


def reported_keys(entries: list) -> set:
    return {(e["SrcAddr"], e["DstAddr"], e["SrcPort"], e["DstPort"],
             e["Proto"]) for e in entries}


def grade(want: dict, report: dict, query) -> list:
    """One closed window's published answers against the exact ones.
    `query(path)` -> (status, json) on the agent's metrics server. Returns
    [(gate, ok, words)], every gate always present."""
    w = report["Window"]
    out = [("records", report["Records"] == want["n"],
            f"published {report['Records']:.0f} == fed {want['n']}")]

    code, top = query("/query/topk?n=1024")
    got = reported_keys(top.get("topk", []))
    head = [five_tuple(k) for k in want["keys"][:100]]
    recall = sum(k in got for k in head) / max(len(head), 1)
    out.append(("recall_at_100", code == 200 and top.get("window") == w
                and recall >= RECALL_AT_100,
                f"recall@100 by bytes {recall:.2f} >= {RECALL_AT_100}"))
    sunk = report["HeavyHitters"]
    out.append(("sink_equals_query",
                bool(sunk) and top.get("topk", [])[:len(sunk)] == sunk,
                "the sink's heavy hitters are the head of /query/topk"))

    code, card = query("/query/cardinality")
    est = card.get("distinct_src_estimate", 0.0)
    err = abs(est - want["distinct_src"]) / max(want["distinct_src"], 1)
    out.append(("distinct_sources", code == 200
                and card.get("records") == want["n"]
                and err <= DISTINCT_SRC_ERR,
                f"distinct sources {est:.0f} vs exact {want['distinct_src']} "
                f"(error {err:.2%} <= {DISTINCT_SRC_ERR:.0%})"))

    # probe keys whose exact bytes the f32 planes hold exactly (< 2^24),
    # spread from the heaviest such key down to the tail
    ok = np.nonzero(want["bytes"] < 2 ** 24)[0]
    probes = ok[np.unique(np.geomspace(1, len(ok), CM_PROBES).astype(int) - 1)]
    low = within = answered = 0
    conf = 1.0
    for i in probes:
        src, dst, sp, dp, proto = five_tuple(want["keys"][i])
        code, f = query(f"/query/frequency?src={src}&dst={dst}&src_port={sp}"
                        f"&dst_port={dp}&proto={proto}")
        if code != 200 or f.get("window") != w:
            continue
        answered += 1
        conf = f["confidence"]
        low += f["est_bytes"] >= want["bytes"][i]
        within += (f["est_bytes"]
                   <= want["bytes"][i] + f["overestimate_bound_bytes"])
    n = len(probes)
    out.append(("countmin_never_under", answered == n and low == n,
                f"CM estimate >= exact bytes for {low}/{n} probe keys"))
    out.append(("countmin_inside_bar",
                answered == n and within >= math.floor(conf * n),
                f"{within}/{n} probes inside the route's error bar "
                f"(confidence {conf:.3f})"))
    return out
