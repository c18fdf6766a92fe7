#!/usr/bin/env python3
"""cellbench/sweep.py — find the knee of a paced cell, once.

    python3 cellbench/sweep.py --workload <paced cell> [--rates a,b,...] [--seconds 8]

One set-up shared, then each rate offered for `--seconds` by the cell's own
open loop, the hand-overs drained between rates. One line per rate: the
eviction lag (hand-over to export_evicted's return) as a median over the first
and the second half of the step, the worst, and the most evictions handed and
not yet exported. Below the knee both halves agree; above it the second half
is worse than the first, because the queue grows all through the step. The
cell's `rate_per_s` is four fifths of the highest rate whose halves agree,
rounded down to a multiple of 25,000, written into the mix file as a number.
Run it again when nearly every eviction of the paced cell meets its lag: the
knee has moved (a later `benchmark` issue's job, never a perf PR's).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from cellbench import harness, run  # noqa: E402
from cellbench.readers import quantile  # noqa: E402

RATES = "200000,250000,300000,350000,400000,425000,450000,475000,500000"


def main() -> int:
    age0, m0 = harness.process_age_s(), time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=2147483659)
    ap.add_argument("--rates", default=RATES)
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args()
    try:
        state = run.set_up(args.workload, args.seed, False, age0, m0)
    except harness.Failed as exc:
        print(f"cellbench sweep: {exc}", file=sys.stderr)
        return 2
    aut, offer, mix = state.aut, state.offer, state.mix
    try:
        # fill at the first rate, as the cell does
        first = True
        for rate in (float(r) for r in args.rates.split(",")):
            step = dict(mix, rate_per_s=rate)
            run.start_offer(state, step)
            if first:
                fill_to = offer.handed_records + mix["fill_records"]
                harness.wait_for(lambda: offer.handed_records >= fill_to,
                                 600, "the fill", 0.05)
                first = False
            t0, h0 = time.perf_counter(), offer.handed_records
            unacked_max = 0
            while time.perf_counter() - t0 < args.seconds:
                unacked_max = max(unacked_max, offer.unacked)
                time.sleep(0.005)
            t1 = time.perf_counter()
            aut.fetcher.generator = None
            harness.wait_for(lambda: offer.exported >= offer.handed, 300,
                             "the hand-overs to drain", 0.01)
            with offer.lock:
                got = [s for s in offer.samples if t0 <= s[0] < t1]
            mid = (t0 + t1) / 2
            print(json.dumps({
                "offered_per_s": rate,
                "handed": offer.handed_records - h0, "evictions": len(got),
                "unacked_max": unacked_max,
                "lag_s_p50_first_half": quantile(
                    [s[2] - s[0] for s in got if s[0] < mid], 0.5),
                "lag_s_p50_second_half": quantile(
                    [s[2] - s[0] for s in got if s[0] >= mid], 0.5),
                "lag_s_p95": quantile([s[2] - s[0] for s in got], 0.95),
                "lag_s_max": max((s[2] - s[0] for s in got), default=None),
                "device": state.dev["kind"]}), flush=True)
    finally:
        aut.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
