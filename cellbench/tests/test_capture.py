"""The readers of what the program wrote into a capture (cellbench/capture.py,
readers scope_time / stage_stat / done_lag), on synthetic input and on two
recorded fixtures: `cell2_capture.xplane.pb`, cut with cellbench/
cut_capture.py from PR 25's own traced run of collector-1chip.zipf-paced on a
TPU v5 lite (0.9 s of it: named modules, op metadata, `netobserv:`
annotations), and PR 24's `cell1_cut.xplane.pb`, which stands for a program
that names nothing (every module `jit_fn`, no metadata, no annotation):
there every reader finds nothing to read and says so with None."""

import copy
import json
import os
import types

import pytest

from cellbench import capture
from cellbench.readers import done_lag, scope_time, stage_stat

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(HERE, "..", "testdata", "cell2_capture.xplane.pb")
UNNAMED = os.path.join(HERE, "..", "testdata", "cell1_cut.xplane.pb")
with open(os.path.join(HERE, "..", "testdata", "cell2_capture.json")) as f:
    WANT = json.load(f)
SCOPES = ["resident_decode", "hash", "countmin", "topk", "hll_src",
          "hll_grids", "quantile", "signals", "totals"]


def varint(n: int) -> bytes:
    out = b""
    while True:
        out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def field(no: int, value) -> bytes:
    if isinstance(value, int):
        return varint(no << 3) + varint(value)
    return varint(no << 3 | 2) + varint(len(value)) + value


def test_wire_format_reader_walks_varints_bytes_and_skips_fixed_width():
    msg = (field(1, 300) + field(2, b"name") + varint(3 << 3 | 1) + b"8bytes!!"
           + varint(4 << 3 | 5) + b"4byt" + field(5, field(1, 7)))
    got = [(no, v if isinstance(v, int) else bytes(v))
           for no, v in capture._fields(memoryview(msg))]
    assert got == [(1, 300), (2, b"name"), (5, field(1, 7))]


def test_op_scopes_reads_tf_op_and_program_from_event_metadata(tmp_path):
    stat_md = (field(5, field(1, 1) + field(2, field(1, 1) + field(2, b"tf_op")))
               + field(5, field(1, 2) + field(2, field(1, 2)
                                              + field(2, b"program_id"))))
    meta = (field(1, 9) + field(2, b"%fusion.7 = f32[8] fusion()")
            + field(4, b"fusion.7")
            + field(5, field(1, 1) + field(5, b"jit(f)/jit(main)/topk/add:"))
            + field(5, field(1, 2) + field(4, 42)))
    plane = (field(2, b"/device:TPU:0") + stat_md
             + field(4, field(1, 9) + field(2, meta)))
    host = field(2, b"/host:CPU") + field(4, field(1, 9) + field(2, meta))
    path = tmp_path / "tiny.xplane.pb"
    path.write_bytes(field(1, plane) + field(1, host))
    assert capture.op_scopes(str(path)) == {
        ("42", "fusion.7"): "jit(f)/jit(main)/topk/add:"}


@pytest.mark.parametrize("op_name,scope", [
    ("jit(ingest_resident_lanes_x4)/jit(main)/countmin/countmin_update_two/"
     "pallas_call:", "countmin"),
    ("jit(sharded_ingest_resident_x2)/shard_map/resident_decode/scatter:",
     "resident_decode"),     # as the mesh cell's capture reads (PR 25)
    ("jit(tenant_ingest)/vmap(jit(main))/signals/add:", "signals"),
    ("jit(ingest_resident_lanes_x1)/jit(main)/reduce_sum:", None),
    ("jit(f)/jit(main)/while/body/topk/add:", None),    # by the FIRST scope
    ("", None),
])
def test_scope_of(op_name, scope):
    assert capture.scope_of(op_name, set(SCOPES)) == scope


@pytest.fixture(scope="module")
def cap():
    return capture.Capture(FIXTURE)


@pytest.fixture(scope="module")
def unnamed():
    return capture.Capture(UNNAMED)


def ctx_for(calls: dict, records: float = 2e6, counters: dict = None):
    return types.SimpleNamespace(
        records=records, notes=[],
        calls_in_window=lambda exe: calls.get(exe, 0),
        counter_delta=lambda name: (counters or {}).get(name, 0.0))


def test_modules_name_themselves(cap):
    exes = {r["exe"] for r in cap.devices[0]}
    assert exes == set(WANT["runs"])
    assert all(r["program"] and r["program"].isdigit()
               for r in cap.devices[0])
    assert {m["exe"] for m in cap.inside[0]} <= exes


def test_scope_time_splits_an_executable_and_the_parts_add_up(cap,
                                                              monkeypatch):
    monkeypatch.setattr(capture, "of_run", lambda: cap)
    calls = {exe: 10 * got["runs"] for exe, got in WANT["runs"].items()}
    ctx = ctx_for(calls)

    def read(scopes, per="mrec", known=None):
        args = {"executable": "ingest", "scopes": scopes, "per": per}
        if known:
            args["known"] = known
        return scope_time.read(ctx, args)
    parts = {s: read([s]) for s in SCOPES}
    for exe_scope in ("countmin", "topk", "resident_decode"):
        want = sum(got.get(exe_scope, 0.0) / got["runs"] * calls[exe]
                   for exe, got in WANT["runs"].items()) / 2.0
        assert parts[exe_scope] == pytest.approx(want, rel=1e-9)
    whole = sum(got["op_s"] / got["runs"] * calls[exe]
                for exe, got in WANT["runs"].items()) / 2.0
    none_share = read(None, "share", SCOPES)
    assert sum(parts.values()) + none_share / 100 * whole == pytest.approx(
        whole, rel=1e-9)
    assert 0 < none_share < 25
    # two scopes in one metric are the sum of the two
    assert read(["hll_src", "hll_grids"]) == pytest.approx(
        parts["hll_src"] + parts["hll_grids"], rel=1e-9)
    # an executable pattern that matches nothing: nothing to read
    assert scope_time.read(ctx, {"executable": "sharded", "scopes": ["topk"],
                                 "per": "mrec"}) is None


def test_stage_stat_reads_the_products_annotations(cap, monkeypatch):
    monkeypatch.setattr(capture, "of_run", lambda: cap)
    ctx = ctx_for({}, counters={"sketch_superbatch_folds_total": 600.0})
    for stage, n in WANT["stages"].items():
        assert len(cap.in_window(stage)) == n
    assert stage_stat.read(ctx, {"stage": "put", "stat": "p50"}) == \
        pytest.approx(WANT["put_p50"], rel=1e-9)
    k2 = [b - a for a, b, got in cap.in_window("put") if got["k"] == 2]
    assert len(k2) == WANT["put_k2_runs"]
    assert stage_stat.read(ctx, {"stage": "put", "stat": "max",
                                 "where": {"k": 2}}) == max(k2)
    per_mrec = stage_stat.read(ctx, {
        "stage": "put", "stat": "sum_per_mrec",
        "count": "sketch_superbatch_folds_total"})
    puts = [b - a for a, b, _ in cap.in_window("put")]
    assert per_mrec == pytest.approx(sum(puts) / len(puts) * 600.0 / 2.0)
    assert stage_stat.read(ctx, {"stage": "no_such", "stat": "p50"}) is None


def test_done_lag_joins_an_eviction_to_its_last_fold_on_the_chip(cap,
                                                                 monkeypatch):
    got, why = done_lag.lags(cap)
    assert why == "" and [g[0] for g in got] == WANT["done_lag"]["evictions"]
    for _, from_evict, from_dispatch in got:
        assert from_evict > from_dispatch > 0
    monkeypatch.setattr(capture, "of_run", lambda: cap)
    ctx = ctx_for({})
    assert done_lag.read(ctx, {"from": "evict", "stat": "p50"}) == \
        pytest.approx(WANT["done_lag"]["from_evict_p50"], rel=1e-9)
    assert done_lag.read(ctx, {"from": "dispatch", "stat": "p50"}) == \
        pytest.approx(WANT["done_lag"]["from_dispatch_p50"], rel=1e-9)
    assert "7 evictions joined" in ctx.notes[0]


def shifted(cap, stages=None, devices=None):
    other = copy.copy(cap)
    other.stages = stages if stages is not None else cap.stages
    other.devices = devices if devices is not None else cap.devices
    return other


def test_done_lag_refuses_an_ambiguous_join(cap, monkeypatch):
    # (1) a watched call the capture did not see: the calls of one
    # executable are no longer consecutive, so the order proves nothing
    d = cap.stages["dispatch"]
    holed = dict(cap.stages, dispatch=d[:5] + d[6:])
    assert done_lag.lags(shifted(cap, stages=holed))[0] is None
    # (2) a chip that never idles: every run starts where the run before
    # ended, so no run is tied to its own dispatch
    packed, edge = [], cap.devices[0][0]["start"]
    for r in cap.devices[0]:
        packed.append(dict(r, start=edge, end=edge + r["end"] - r["start"]))
        edge = packed[-1]["end"]
    got, why = done_lag.lags(shifted(cap, devices=[packed]))
    assert got is None and "not proven" in why
    # (3) more runs in flight at the capture's start than allowed
    late = [dict(r) for r in cap.devices[0]]
    extra = [dict(late[0], start=late[0]["start"] - 1 - i,
                  end=late[0]["start"] - 0.99 - i) for i in range(3)]
    assert done_lag.lags(shifted(cap, devices=[extra[::-1] + late]),
                         max_in_flight=2)[0] is None
    assert done_lag.lags(shifted(cap, devices=[extra[::-1] + late]),
                         max_in_flight=3)[0] is not None
    # the reader turns a refusal into None and a note
    monkeypatch.setattr(capture, "of_run",
                        lambda: shifted(cap, devices=[packed]))
    ctx = ctx_for({})
    assert done_lag.read(ctx, {"from": "evict", "stat": "p50"}) is None
    assert ctx.notes and "done_lag" in ctx.notes[0]


def test_a_program_that_names_nothing_gives_every_reader_nothing(unnamed,
                                                                 monkeypatch):
    """PR 24's fixture: modules `jit_fn(<fingerprint>)`, no op metadata, no
    `netobserv:` annotation — what the parent of PR 25 leaves in a capture."""
    monkeypatch.setattr(capture, "of_run", lambda: unnamed)
    assert not unnamed.stages and not unnamed.scopes
    assert {r["exe"] for r in unnamed.devices[0]} == {"fn"}
    ctx = ctx_for({"fn": 100})
    assert scope_time.read(ctx, {"executable": "ingest", "scopes": ["topk"],
                                 "per": "mrec"}) is None
    assert scope_time.read(ctx, {"executable": "ingest", "scopes": None,
                                 "known": SCOPES, "per": "share"}) is None
    assert stage_stat.read(ctx, {"stage": "put", "stat": "p50"}) is None
    assert done_lag.read(ctx, {"from": "evict", "stat": "p50"}) is None
    monkeypatch.setattr(capture, "of_run", lambda: None)    # no capture
    assert scope_time.read(ctx, {"executable": "ingest", "scopes": ["topk"],
                                 "per": "mrec"}) is None
    assert stage_stat.read(ctx, {"stage": "put", "stat": "p50"}) is None
    assert done_lag.read(ctx, {"from": "evict", "stat": "p50"}) is None
