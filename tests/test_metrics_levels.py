"""METRICS_LEVEL gating: exposition output must differ by level, and
trace-level per-interface series must self-expire (reference parity:
`pkg/metrics/metrics.go:337-368` newInterfaceEventsCounter)."""

import time

import pytest
from prometheus_client import CollectorRegistry, generate_latest

from netobserv_tpu.metrics.registry import Metrics, MetricsSettings


def _expo(m: Metrics) -> str:
    return generate_latest(m.registry).decode()


def _count(m: Metrics, **kw) -> None:
    m.count_interface_event("added", ifname="eth0", ifindex=3,
                            netns="testns", mac="aa:bb:cc:dd:ee:ff",
                            retries=2, **kw)


def test_info_level_type_only():
    m = Metrics(MetricsSettings(level="info"),
                registry=CollectorRegistry())
    _count(m)
    out = _expo(m)
    assert 'type="added"' in out
    assert 'ifname="eth0"' not in out
    assert 'retries="2"' not in out


def test_debug_level_adds_retries():
    m = Metrics(MetricsSettings(level="debug"),
                registry=CollectorRegistry())
    _count(m)
    out = _expo(m)
    assert 'type="added"' in out and 'retries="2"' in out
    assert 'ifname="eth0"' not in out


def test_trace_level_full_cardinality_and_expiry():
    m = Metrics(MetricsSettings(level="trace", trace_ttl_s=0.2),
                registry=CollectorRegistry())
    _count(m)
    out = _expo(m)
    assert ('ifname="eth0"' in out and 'ifindex="3"' in out
            and 'netns="testns"' in out and 'mac="aa:bb:cc:dd:ee:ff"' in out
            and 'retries="2"' in out)
    # the janitor removes the series after the TTL (unbounded cardinality
    # must be self-limiting, the reference's 5-minute expiry goroutine)
    deadline = time.monotonic() + 3.0
    while 'ifname="eth0"' in _expo(m):
        assert time.monotonic() < deadline, "trace series never expired"
        time.sleep(0.05)


def test_trace_reincrement_refreshes_ttl():
    """An increment REFRESHES a live series' deadline — the janitor must
    never delete (and reset) a series that incremented within the TTL."""
    m = Metrics(MetricsSettings(level="trace", trace_ttl_s=0.6),
                registry=CollectorRegistry())
    _count(m)
    t0 = time.monotonic()
    # keep refreshing past the original deadline
    while time.monotonic() - t0 < 1.0:
        _count(m)
        assert 'ifname="eth0"' in _expo(m), "live series was expired"
        time.sleep(0.1)
    # stop incrementing: now it must expire
    deadline = time.monotonic() + 3.0
    while 'ifname="eth0"' in _expo(m):
        assert time.monotonic() < deadline, "series never expired after idle"
        time.sleep(0.05)


def test_trace_bang_spelling_accepted():
    # the reference spells it "trace!" to flag unbounded cardinality
    m = Metrics(MetricsSettings(level="trace!"),
                registry=CollectorRegistry())
    assert m.level == "trace"


def test_invalid_level_rejected():
    with pytest.raises(ValueError, match="METRICS_LEVEL"):
        Metrics(MetricsSettings(level="verbose"),
                registry=CollectorRegistry())


def test_listener_passes_interface_identity():
    """The interfaces listener feeds full identity so trace level actually
    has per-interface series to show."""
    from netobserv_tpu.agent.interfaces_listener import InterfaceListener  # noqa: F401  (import works)

    m = Metrics(MetricsSettings(level="trace", trace_ttl_s=60),
                registry=CollectorRegistry())
    # simulate the listener's call shape
    m.count_interface_event("attach", ifname="veth1", ifindex=7,
                            netns="", mac="02:00:00:00:00:01", retries=1)
    assert 'ifname="veth1"' in _expo(m)


def test_resident_staging_metrics_surface():
    """The resident ring's operational counters (continuation chunks, dict
    epochs, spill rows) reach the prometheus registry the agent scrapes."""
    from netobserv_tpu.datapath import flowpack
    from netobserv_tpu.datapath.replay import SyntheticFetcher
    from prometheus_client import CollectorRegistry

    from netobserv_tpu.metrics.registry import Metrics, MetricsSettings
    from netobserv_tpu.sketch import state as sk
    from netobserv_tpu.sketch.staging import ShardedResidentStagingRing

    if not flowpack.build_native():
        pytest.skip("native flowpack unavailable")
    m = Metrics(MetricsSettings(level="info"), registry=CollectorRegistry())
    B = 256
    caps = flowpack.ResidentCaps(dns=8, drop=8, nk=8, spill=4)  # tiny lanes
    import jax
    ring = ShardedResidentStagingRing(
        B, 1, sk.make_ingest_resident_lanes_fn(B, caps, 1, 64),
        key_tables=jax.device_put(sk.init_key_tables(1, 64)),
        put=jax.device_put, caps=caps, slot_cap=64, metrics=m)
    state = sk.init_state(sk.SketchConfig(
        cm_depth=2, cm_width=1 << 10, hll_precision=6, perdst_buckets=32,
        perdst_precision=4, topk=16, hist_buckets=64, ewma_buckets=32))
    fetcher = SyntheticFetcher(flows_per_eviction=B, n_distinct=400, seed=3)
    for _ in range(4):
        state = ring.fold(state, fetcher.lookup_and_delete().events[:B])
    ring.drain()
    g = m.registry.get_sample_value
    assert g("ebpf_agent_sketch_resident_continuations_total") >= 1
    assert g("ebpf_agent_sketch_resident_dict_epochs_total") >= 1
    assert g("ebpf_agent_sketch_resident_spill_rows_total") >= 1
