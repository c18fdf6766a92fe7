"""The benchmark's oracle against chip_smoke.py's, on one seed."""

import numpy as np

import chip_smoke
from cellbench import oracle, traffic


def test_oracle_agrees_with_chip_smoke():
    sizes = chip_smoke.Sizes(universe=1 << 14)
    t = chip_smoke.Traffic(20260928, sizes)
    win = t.window(60_000)
    want = oracle.exact(win["events"])
    assert want["n"] == win["n"]
    assert want["distinct_src"] == win["distinct_src"]
    order = np.argsort(-win["exact_bytes"], kind="stable")
    present = order[win["exact_bytes"][order] > 0]
    assert len(want["bytes"]) == len(present)
    assert np.array_equal(want["bytes"], win["exact_bytes"][present])
    assert ([oracle.five_tuple(k) for k in want["keys"][:100]]
            == [t.five_tuple(int(i)) for i in present[:100]])


def test_stream_is_the_universe_draw_in_map_form():
    rng = np.random.default_rng(7)
    uni = traffic.Universe(rng, 1 << 12, 1.2)
    stream = traffic.Stream(rng, uni, 5000, map_cpus=4, new_key_share=0.2)
    a, b = stream.take(3000), stream.take(3000)      # b wraps to the start
    assert a.n == b.n == 3000
    fresh = a.agg_keys[:, 0] == 0xFD
    assert 0.15 < fresh.mean() < 0.25
    # a stamped key never repeats, within a dump or across dumps
    both = np.concatenate([a.agg_keys[fresh], b.agg_keys[b.agg_keys[:, 0] == 0xFD]])
    assert len(np.unique(both.view((np.void, 40)))) == len(both)
    # every feature row's key is a key of the dump (no orphan for the join)
    have = set(map(bytes, a.agg_keys))
    for keys, partials in a.drained.values():
        assert len(keys) == len(partials)
        assert all(bytes(k) in have for k in keys)
    want = oracle.exact(a.events())
    assert want["n"] == 3000 and want["bytes"].sum() == a.agg_vals["bytes"].sum()
