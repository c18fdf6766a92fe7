"""Device-side merge of a decoded delta frame into a SketchState.

The aggregator's aggregate IS a SketchState fed by table deltas instead of
flow records: every structure merges by its native operator (CM/histograms/
rates add, HLL max, top-K concat + re-score against the merged CM), so the
existing window roll (`sketch.state.roll_window`) and report renderer
(`exporter.tpu_sketch.report_to_json`) serve the cluster-wide plane
unchanged. Pure function — `federation.aggregator` jits it (single device)
and `parallel.merge.make_fold_delta_fn` calls it inside shard_map (mesh).
"""

from __future__ import annotations

import jax.numpy as jnp

from netobserv_tpu.ops import countmin, ewma, hll, quantile, topk
from netobserv_tpu.sketch import state as sk


def merge_tables(state: sk.SketchState, t: dict,
                 query_fn=None, candidate_valid=None) -> sk.SketchState:
    """Merge one agent's delta tables `t` (federation.delta.TABLE_SPEC
    names, device arrays; `heavy_valid` may be uint32) into `state`.

    `query_fn(h1, h2) -> est` overrides the plain CM point query for the
    top-K re-score (owner-sharded meshes); `candidate_valid` additionally
    masks which delta candidates this shard may adopt (key ownership).
    EWMA baselines (mean/var) are untouched — the aggregator rolls its own
    cluster-level baselines over the merged per-window rates.
    """
    cm_b = countmin.CountMin(state.cm_bytes.counts + t["cm_bytes"])
    cm_p = countmin.CountMin(state.cm_pkts.counts + t["cm_pkts"])
    d_valid = t["heavy_valid"] != 0
    if candidate_valid is not None:
        d_valid = d_valid & candidate_valid
    # persistent-slot merge: aggregate table + delta table concat, duplicate
    # identities collapse with segmented metadata merges (prev_counts SUM —
    # per-agent partials of one key add; first_seen MIN is best-effort at
    # this tier, agents count windows independently; epoch MAX), counts
    # re-score against the merged CM (ops/topk.merge_slot_tables — the one
    # roll-time reconciliation primitive, shared with parallel/merge.py).
    # v1/v2 frames reach here with zeroed churn tensors
    # (federation.delta.upgrade_tables), which merge as "no history".
    stacked = topk.SlotTable(
        words=jnp.concatenate([state.heavy.words,
                               t["heavy_words"].astype(jnp.uint32)], axis=0),
        h1=jnp.concatenate([state.heavy.h1, t["heavy_h1"]]),
        h2=jnp.concatenate([state.heavy.h2, t["heavy_h2"]]),
        counts=jnp.concatenate([state.heavy.counts, t["heavy_counts"]]),
        prev_counts=jnp.concatenate([state.heavy.prev_counts,
                                     t["heavy_prev_counts"]]),
        first_seen=jnp.concatenate([state.heavy.first_seen,
                                    t["heavy_first_seen"]]),
        epoch=jnp.concatenate([state.heavy.epoch, t["heavy_epoch"]]),
        valid=jnp.concatenate([state.heavy.valid, d_valid]),
    )
    heavy = topk.merge_slot_tables(stacked, cm_b, state.heavy.k,
                                   query_fn=query_fn)
    scalars = t["scalars"]
    return state._replace(
        cm_bytes=cm_b, cm_pkts=cm_p, heavy=heavy,
        hll_src=hll.HLL(jnp.maximum(state.hll_src.regs, t["hll_src"])),
        hll_per_dst=hll.PerDstHLL(
            jnp.maximum(state.hll_per_dst.regs, t["hll_per_dst"])),
        hll_per_src=hll.PerDstHLL(
            jnp.maximum(state.hll_per_src.regs, t["hll_per_src"])),
        hist_rtt=quantile.LogHist(state.hist_rtt.counts + t["hist_rtt"]),
        hist_dns=quantile.LogHist(state.hist_dns.counts + t["hist_dns"]),
        ddos=ewma.EWMA(mean=state.ddos.mean, var=state.ddos.var,
                       rate=state.ddos.rate + t["ddos_rate"],
                       windows=state.ddos.windows),
        syn=ewma.EWMA(mean=state.syn.mean, var=state.syn.var,
                      rate=state.syn.rate + t["syn_rate"],
                      windows=state.syn.windows),
        synack=state.synack + t["synack"],
        drops_ewma=ewma.EWMA(mean=state.drops_ewma.mean,
                             var=state.drops_ewma.var,
                             rate=state.drops_ewma.rate + t["drops_rate"],
                             windows=state.drops_ewma.windows),
        drop_causes=state.drop_causes + t["drop_causes"],
        dscp_bytes=state.dscp_bytes + t["dscp_bytes"],
        conv_fwd=state.conv_fwd + t["conv_fwd"],
        conv_rev=state.conv_rev + t["conv_rev"],
        # a merged count spans windows or agents: it stays f32 (see
        # sketch.state.widen_count), never the live window's i32
        total_records=state.total_records.astype(jnp.float32) + scalars[0],
        total_bytes=state.total_bytes + scalars[1],
        total_drop_bytes=state.total_drop_bytes + scalars[2],
        total_drop_packets=state.total_drop_packets + scalars[3],
        quic_records=state.quic_records + scalars[4],
        nat_records=state.nat_records + scalars[5],
        heavy_evictions=state.heavy_evictions + scalars[6],
    )
