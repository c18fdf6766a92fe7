"""Host-path stage profile: where do the records/s go?

Times each stage of the evict->pack->transfer->ingest seam in isolation on
the default device (the real TPU chip under the driver):

  pack    — flowpack.pack_dense into a reused buffer (C++ single pass)
  put     — pack + jax.device_put (transfer link)
  ring    — the full DenseStagingRing fold (production path)
  ingest  — on-device ingest alone (device ceiling, dense feed)

Prints one JSON line with all four rates so the bottleneck is explicit.
"""
from __future__ import annotations

import json
import sys
import time

import numpy as np

sys.path.insert(0, ".")

BATCH = 16384
SECONDS = 3.0


def main() -> None:
    from netobserv_tpu.utils.platform import enable_compile_cache
    enable_compile_cache()
    import jax

    from netobserv_tpu.datapath import flowpack
    from netobserv_tpu.datapath.replay import SyntheticFetcher
    from netobserv_tpu.sketch import state as sk
    from netobserv_tpu.sketch.staging import DenseStagingRing

    flowpack.build_native()
    fetcher = SyntheticFetcher(flows_per_eviction=BATCH, n_distinct=50_000)
    raw = np.concatenate(
        [fetcher.lookup_and_delete().events for _ in range(40)])
    full = [np.ascontiguousarray(raw[i:i + BATCH])
            for i in range(0, len(raw) - BATCH, BATCH)]
    out = np.empty((BATCH, flowpack.DENSE_WORDS), np.uint32)

    def rate(fn, warm=2):
        for i in range(warm):
            fn(i)
        n = 0
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < SECONDS:
            fn(n)
            n += 1
        return n * BATCH / (time.perf_counter() - t0)

    results = {}

    # 1. pack only (reused buffer)
    results["pack"] = rate(
        lambda i: flowpack.pack_dense(full[i % len(full)], batch_size=BATCH,
                                      out=out))

    # 2. pack + put (block on each transfer — isolates the link)
    def pack_put(i):
        dense = flowpack.pack_dense(full[i % len(full)], batch_size=BATCH,
                                    out=out)
        jax.device_put(dense).block_until_ready()
    results["pack_put"] = rate(pack_put)

    # 2b. put only, async pipelined (link ceiling with overlap)
    devs = [None] * 4
    def put_async(i):
        s = i % 4
        if devs[s] is not None:
            devs[s].block_until_ready()
        devs[s] = jax.device_put(out)
    results["put_async"] = rate(put_async)

    # 3. full production ring
    cfg = sk.SketchConfig()
    state = sk.init_state(cfg)
    ring = DenseStagingRing(
        BATCH, sk.make_ingest_dense_fn(donate=True, with_token=True))
    state = ring.fold(state, full[0])
    jax.block_until_ready(state)
    holder = [state]
    def ring_fold(i):
        holder[0] = ring.fold(holder[0], full[i % len(full)])
    results["ring"] = rate(ring_fold)
    jax.block_until_ready(holder[0])

    # 4. device ingest ceiling (dense already on device)
    ingest = sk.make_ingest_dense_fn(donate=True)
    state2 = sk.init_state(cfg)
    dev_batches = [jax.device_put(
        flowpack.pack_dense(f, batch_size=BATCH)) for f in full[:8]]
    st = [state2]
    def dev_only(i):
        st[0] = ingest(st[0], dev_batches[i % len(dev_batches)])
    results["ingest_device"] = rate(dev_only)
    jax.block_until_ready(st[0])

    # 5. compact production ring + batch-size sweep of the compact put
    #    (bigger batches amortize any per-transfer overhead of the link)
    from netobserv_tpu.sketch.staging import default_spill_cap
    for bs in (BATCH, BATCH * 4):
        # at least 2 slices of bs rows, whatever the pool size
        big = np.concatenate([raw] * (2 * bs // len(raw) + 1)) \
            if len(raw) < 3 * bs else raw
        fulls = [np.ascontiguousarray(big[i:i + bs])
                 for i in range(0, len(big) - bs, bs)][:6]
        assert fulls, (len(big), bs)
        spill = default_spill_cap(bs)
        cring = DenseStagingRing(
            bs, sk.make_ingest_compact_fn(bs, spill, donate=True,
                                          with_token=True),
            spill_cap=spill,
            ingest_fallback=sk.make_ingest_dense_fn(donate=True,
                                                    with_token=True))
        cstate = sk.init_state(cfg)
        cstate = cring.fold(cstate, fulls[0])
        jax.block_until_ready(cstate)
        ch = [cstate]

        def cfold(i):
            ch[0] = cring.fold(ch[0], fulls[i % len(fulls)])
        n = 0
        for _ in range(2):
            cfold(n); n += 1
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < SECONDS:
            cfold(n); n += 1
        jax.block_until_ready(ch[0])
        results[f"ring_compact_{bs}"] = (n - 2) * bs / (
            time.perf_counter() - t0)

        cbuf = np.empty(flowpack.compact_buf_len(bs, spill), np.uint32)
        flowpack.pack_compact(fulls[0], batch_size=bs, spill_cap=spill,
                              out=cbuf)
        def cput(i):
            jax.device_put(cbuf).block_until_ready()
        results[f"put_compact_{bs}"] = rate(cput) * (bs / BATCH)

    # 6. resident production ring (the shipped default) + its put ceiling
    from netobserv_tpu.sketch.staging import ResidentStagingRing
    caps = flowpack.default_resident_caps(BATCH)
    rring = ResidentStagingRing(
        BATCH, sk.make_ingest_resident_fn(BATCH, caps, donate=True,
                                          with_token=True), caps=caps)
    rstate = sk.init_state(cfg)
    for f in full:  # warm dict + compile
        rstate = rring.fold(rstate, f)
    jax.block_until_ready(rstate)
    rh = [rstate]
    def rfold(i):
        rh[0] = rring.fold(rh[0], full[i % len(full)])
    results["ring_resident"] = rate(rfold)
    jax.block_until_ready(rh[0])
    rbuf = np.empty(flowpack.resident_buf_len(BATCH, caps), np.uint32)
    flowpack.pack_resident(full[0], BATCH, rring.kdict, caps, out=rbuf)
    results["put_resident"] = rate(
        lambda i: jax.device_put(rbuf).block_until_ready())
    results["pack_resident"] = rate(
        lambda i: flowpack.pack_resident(full[i % len(full)], BATCH,
                                         rring.kdict, caps, out=rbuf))

    results = {k: round(v) for k, v in results.items()}
    results["device"] = jax.devices()[0].platform
    print(json.dumps(results))


if __name__ == "__main__":
    main()
