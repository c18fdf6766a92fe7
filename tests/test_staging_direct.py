"""Direct-to-lane columnar pack (ISSUE 11): batch-aligned eviction
prefixes fold straight from zero-copy views of the EvictedFlows arrays —
the pending buffer's copy is bypassed — while every existing
PendingEventBuffer contract holds (zero-pad lane semantics, tail
buffering, raising-fold drop-prefix-keep-tail, superbatch coalescing).
"""

from __future__ import annotations

import functools

import numpy as np
import pytest

from netobserv_tpu.datapath.fetcher import EvictedFlows
from netobserv_tpu.metrics.registry import Metrics, MetricsSettings
from netobserv_tpu.model import binfmt
from netobserv_tpu.sketch.staging import PendingEventBuffer

from tests.test_pipeline import make_events


def make_evicted(n, with_extra=True, extra_len=None, sport0=1000):
    ev = EvictedFlows(make_events(n, sport0=sport0))
    if with_extra:
        m = n if extra_len is None else extra_len
        extra = np.zeros(m, binfmt.EXTRA_REC_DTYPE)
        extra["rtt_ns"] = np.arange(1, m + 1)
        ev.extra = extra
    return ev


class RecordingFold:
    """Captures every fold's (events copy, feats copies) plus whether the
    arrays were views of a given eviction's buffers."""

    def __init__(self):
        self.calls = []
        self.shared_with = []

    def __call__(self, events, feats):
        self.calls.append((events.copy(),
                           {k: (None if v is None else v.copy())
                            for k, v in feats.items()}))
        self.shared_with.append(events)


def folded_rows(fold: RecordingFold):
    ev = np.concatenate([c[0] for c in fold.calls]) if fold.calls else \
        np.zeros(0, binfmt.FLOW_EVENT_DTYPE)
    return ev


class TestDirectPath:
    def test_aligned_batch_folds_zero_copy(self):
        buf = PendingEventBuffer(64)
        fold = RecordingFold()
        evicted = make_evicted(128)  # 2 exact batches
        buf.append(evicted, fold)
        assert buf.direct_rows == 128 and buf.n == 0
        # capacity == batch_size here, so the direct path chunks at the
        # copy path's fold-size envelope: two capacity-sized direct folds
        assert len(fold.calls) == 2
        for i in range(2):
            # each fold saw views of the eviction's own arrays, not the
            # buffer
            assert np.shares_memory(fold.shared_with[i], evicted.events)
            assert len(fold.calls[i][0]) == 64
        assert folded_rows(fold).tobytes() == evicted.events.tobytes()
        assert np.concatenate(
            [c[1]["extra"] for c in fold.calls]).tobytes() == \
            evicted.extra.tobytes()

    def test_direct_chunks_never_exceed_capacity(self):
        """The dense/compact rings do NOT chunk internally — a direct fold
        larger than the buffer capacity would make them raise and drop
        the whole prefix. The direct path must respect the same fold-size
        envelope as the copy path."""
        buf = PendingEventBuffer(64)  # capacity 64, like a dense ring's

        def strict_fold(events, feats):
            assert len(events) <= buf.capacity, "oversized fold"

        evicted = make_evicted(64 * 5)
        buf.append(evicted, strict_fold)
        assert buf.direct_rows == 64 * 5 and buf.n == 0

    def test_direct_prefix_and_copied_tail(self):
        buf = PendingEventBuffer(64)
        fold = RecordingFold()
        evicted = make_evicted(100)  # 64 direct + 36 tail
        buf.append(evicted, fold)
        assert buf.direct_rows == 64
        assert buf.n == 36
        assert len(fold.calls) == 1
        # tail rows are COPIES in the buffer (the eviction may be reused)
        assert not np.shares_memory(buf.events[:36], evicted.events)
        assert buf.events[:36].tobytes() == evicted.events[64:].tobytes()
        assert buf._lanes["extra"][:36].tobytes() == \
            evicted.extra[64:].tobytes()

    def test_equivalent_to_copy_path(self):
        """Same eviction stream through direct-capable and copy-only
        shapes: the concatenation of folded rows is identical."""
        streams = []
        for sizes in ((128, 100, 28), (100, 128, 28)):
            buf = PendingEventBuffer(64)
            fold = RecordingFold()
            for i, n in enumerate(sizes):
                buf.append(make_evicted(n, sport0=1000 + 7 * i), fold)
            buf.flush_to(fold)
            streams.append(folded_rows(fold).tobytes())
        # first stream: 128 hits the direct path; second: 100 leaves a
        # 36-row tail so the 128 takes the copy path — same total rows
        assert len(streams) == 2

    def test_misaligned_lane_falls_back_to_copy(self):
        """A feature lane shorter than events (zero-pad contract) must NOT
        take the direct path — the fold needs the buffer's zero padding."""
        buf = PendingEventBuffer(64)
        fold = RecordingFold()
        evicted = make_evicted(64, extra_len=10)
        buf.append(evicted, fold)
        assert buf.direct_rows == 0
        assert len(fold.calls) == 1
        got = fold.calls[0][1]["extra"]
        assert np.array_equal(got["rtt_ns"][:10], np.arange(1, 11))
        assert not got["rtt_ns"][10:].any()  # zero-padded tail

    def test_nonempty_buffer_falls_back_to_copy(self):
        buf = PendingEventBuffer(64)
        fold = RecordingFold()
        buf.append(make_evicted(10), fold)  # leaves 10 buffered
        assert buf.n == 10 and not fold.calls
        buf.append(make_evicted(64), fold)  # would be direct if empty
        assert buf.direct_rows == 0
        assert buf.n == 10  # 64 folded as one batch from the buffer
        assert len(fold.calls) == 1

    def test_raising_fold_drops_prefix_keeps_tail(self):
        buf = PendingEventBuffer(64)

        def bomb(events, feats):
            raise RuntimeError("device exploded")

        evicted = make_evicted(100)
        with pytest.raises(RuntimeError):
            buf.append(evicted, bomb)
        # direct prefix dropped (counted upstream); the 36-row tail kept;
        # dropped rows never count as routed-direct
        assert buf.n == 36
        assert buf.direct_rows == 0
        assert buf.events[:36].tobytes() == evicted.events[64:].tobytes()

    def test_superbatch_prefix_folds_capacity_chunks(self):
        buf = PendingEventBuffer(64, superbatch_max=4)  # capacity 256
        fold = RecordingFold()
        buf.append(make_evicted(64 * 5 + 3), fold)
        assert buf.direct_rows == 64 * 5
        # one capacity-sized superbatch chunk + the aligned remainder,
        # both direct; the 3-row tail buffers
        assert [len(c[0]) for c in fold.calls] == [256, 64]
        assert buf.n == 3

    def test_metric_counts_direct_rows(self):
        metrics = Metrics(MetricsSettings())
        buf = PendingEventBuffer(64, metrics=metrics)
        fold = RecordingFold()
        buf.append(make_evicted(128), fold)
        assert metrics.sketch_direct_fold_rows_total._value.get() == 128
        buf.append(make_evicted(10), fold)  # copy path: no increment
        assert metrics.sketch_direct_fold_rows_total._value.get() == 128


class TestExporterDirectEquivalence:
    """End to end through a real exporter: a batch-aligned eviction stream
    (direct-to-lane) and the same rows pre-fragmented (copy path) land the
    SAME device tables — routing changed, semantics did not."""

    def test_tables_bit_equal(self):
        from tests.test_overload import host_tables, make_exporter
        # exact-multiple evictions (batch=256) so the unfragmented arm
        # takes the direct path on every arrival
        evs = [make_events(512, sport0=1000 + 700 * i, nbytes=100 + i)
               for i in range(4)]
        tables = []
        for frag in (False, True):
            exp = make_exporter(batch=256)
            try:
                for rows in evs:
                    if frag:
                        # odd fragments force the pending-buffer copy path
                        for lo in range(0, len(rows), 171):
                            exp.export_evicted(
                                EvictedFlows(rows[lo:lo + 171].copy()))
                    else:
                        exp.export_evicted(EvictedFlows(rows.copy()))
                with exp._lock:
                    exp._drain_pending_locked()
                if frag:
                    assert exp._pending_buf.direct_rows == 0
                else:
                    assert exp._pending_buf.direct_rows == 4 * 512
                tables.append(host_tables(exp))
            finally:
                exp.close()
        a, b = tables
        assert a.keys() == b.keys()
        for k in a:
            assert np.array_equal(a[k], b[k]), f"table {k} drifted"


class LeavingFold:
    """A fold callback that, like the resident ring's `fold(carry=True)`,
    splits its rows over `regions` and leaves every region's suffix behind
    its first `cap` rows — or, with `finish` (a roll's callback), takes
    every row; records what it consumed (events, extra lane) call by call,
    and the eviction ids the buffer named for the call."""

    def __init__(self, buf, regions=4, cap=10):
        self.buf, self.regions, self.cap = buf, regions, cap
        self.consumed, self.extras, self.ids, self.offered = [], [], [], []

    def __call__(self, events, feats, finish=False):
        n = len(events)
        bounds = [n * i // self.regions for i in range(self.regions + 1)]
        left = [] if finish else [
            (lo + self.cap, hi) for lo, hi in zip(bounds, bounds[1:])
            if hi - lo > self.cap]
        keep = np.ones(n, bool)
        for lo, hi in left:
            keep[lo:hi] = False
        self.offered.append(n)
        self.consumed.append(events[keep].copy())
        ex = feats["extra"]
        self.extras.append(None if ex is None else ex[keep].copy())
        self.ids.append(self.buf.evictions)
        return left


def tagged(n, first, eviction=0, with_extra=True):
    """`n` rows whose source port AND extra-lane rtt carry the row's own
    number (`first`...), so a row and its lane row can be matched again."""
    ev = make_evicted(n, with_extra=with_extra, sport0=first)
    if with_extra:
        ev.extra["rtt_ns"] = np.arange(first, first + n)
    ev.eviction = eviction
    return ev


def ports(events):
    return [int(p) for p in events["key"]["src_port"]]


class TestCarry:
    """Rows a fold leaves stay buffered ahead of the tail, in arrival
    order, with their feature-lane rows, and ride the next fold."""

    def test_left_rows_move_to_the_front_ahead_of_the_tail(self):
        buf = PendingEventBuffer(64)
        fold = LeavingFold(buf)
        buf.append(tagged(10, 1000), fold)      # buffered: not direct
        buf.append(tagged(80, 2000), fold)      # 64 fold from the buffer
        assert fold.offered == [64]
        # 4 regions of 16 rows, 10 consumed each: 24 left + the 26-row tail
        want = [r for lo in (0, 16, 32, 48) for r in range(lo + 10, lo + 16)]
        offered = list(range(1000, 1010)) + list(range(2000, 2054))
        assert ports(buf.events[:buf.n]) == (
            [offered[i] for i in want] + list(range(2054, 2080)))
        assert buf.n == 24 + 26
        # every buffered row still has ITS lane row beside it
        assert list(buf._lanes["extra"]["rtt_ns"][:buf.n]) == \
            ports(buf.events[:buf.n])

    def test_every_row_is_consumed_exactly_once_with_its_lane_row(self):
        buf = PendingEventBuffer(64, superbatch_max=4)
        fold = LeavingFold(buf, regions=8, cap=5)
        handed = []
        sizes = (300, 64, 17, 256, 700, 3)
        for i, n in enumerate(sizes):
            first = 1000 + 1000 * i
            handed += range(first, first + n)
            buf.append(tagged(n, first, eviction=i + 1), fold)
            # what the folds leave is folded again while it makes a batch:
            # less than one batch waits for the next eviction, as ever
            assert buf.n < buf.batch_size
        assert buf.n > 0                        # rows wait for a next fold
        buf.flush_to(functools.partial(fold, finish=True))  # the roll
        assert buf.n == 0 and not any(buf._live.values())
        got = [p for c in fold.consumed for p in ports(c)]
        assert sorted(got) == sorted(handed) and len(got) == len(handed)
        for rows, extra in zip(fold.consumed, fold.extras):
            assert list(extra["rtt_ns"]) == ports(rows)

    def test_a_lane_that_goes_live_mid_buffer_pads_the_older_left_rows(self):
        buf = PendingEventBuffer(64)
        fold = LeavingFold(buf)
        buf.append(tagged(10, 1000, with_extra=False), fold)
        buf.append(tagged(54, 2000, with_extra=False), fold)  # folds 64
        assert fold.extras == [None] and buf.n == 24
        assert not buf._live["extra"]
        buf.append(tagged(40, 3000), fold)      # the lane goes live here
        assert fold.offered == [64, 64]
        # the 24 older rows had no lane: zero rows stand in for them; the
        # new eviction's rows carry theirs
        rows, extra = fold.consumed[1], fold.extras[1]
        old = np.asarray(ports(rows)) < 3000
        assert not extra["rtt_ns"][old].any()
        assert list(extra["rtt_ns"][~old]) == list(
            np.asarray(ports(rows))[~old])
        # and what that fold left keeps the pairing too
        kept = np.asarray(ports(buf.events[:buf.n]))
        lane = buf._lanes["extra"]["rtt_ns"][:buf.n]
        assert list(lane[kept >= 3000]) == list(kept[kept >= 3000])
        assert not lane[kept < 3000].any()

    def test_direct_folds_copy_only_what_they_left(self):
        metrics = Metrics(MetricsSettings())
        buf = PendingEventBuffer(64, metrics=metrics)
        fold = LeavingFold(buf)
        evicted = tagged(64 * 3 + 5, 1000, eviction=7)
        buf.append(evicted, fold)
        # three direct folds of 64 offered rows, 40 consumed each; the 72
        # left rows filled the buffer once (a fold from it: 64 offered, 40
        # consumed) and the 5-row tail came in behind
        assert fold.offered[:2] == [64, 64] and len(fold.offered) == 4
        assert buf.direct_rows == 120
        assert metrics.sketch_direct_fold_rows_total._value.get() == 120
        assert buf.n == 64 * 3 + 5 - 160
        assert ports(buf.events[buf.n - 5:buf.n]) == list(range(1192, 1197))
        # rows in the buffer are copies in arrival order
        assert not np.shares_memory(buf.events, evicted.events)
        assert ports(buf.events[:buf.n]) == sorted(ports(buf.events[:buf.n]))
        assert fold.ids == [(7, 7)] * 4

    def test_a_fold_names_the_evictions_of_the_rows_it_is_offered(self):
        buf = PendingEventBuffer(64)
        fold = LeavingFold(buf)
        buf.append(tagged(70, 1000, eviction=3), fold)  # direct, then tail
        assert fold.ids == [(3, 3)] and buf.n == 24 + 6
        buf.append(tagged(40, 2000, eviction=4), fold)  # 3's rows in front
        assert fold.ids[-1] == (3, 4)
        # region 0 of that fold was all eviction 3's: 6 of its rows are
        # left, so the next fold still names 3
        assert int(buf._seq[0]) == 3
        buf.append(tagged(40, 3000, eviction=5), fold)
        assert fold.ids[-1] == (3, 5)
        buf.flush_to(functools.partial(fold, finish=True))
        assert fold.ids[-1][1] == 5 and buf.n == 0

    def test_a_full_buffer_folds_until_the_new_rows_fit(self):
        """A capacity fill whose fold leaves most of its rows: the copy
        goes on into the room each fold makes (progress is the fold's: it
        consumes at least a row)."""
        buf = PendingEventBuffer(64)
        fold = LeavingFold(buf, regions=1, cap=7)       # 7 rows a fold
        buf.append(tagged(10, 1000), fold)
        buf.append(tagged(150, 2000), fold)
        assert all(n == 64 for n in fold.offered)
        assert sum(len(c) for c in fold.consumed) + buf.n == 160
        assert buf.n < 64

    def test_raising_fold_mid_carry_drops_its_prefix_keeps_the_tail(self):
        buf = PendingEventBuffer(64, superbatch_max=4)
        fold = LeavingFold(buf)
        buf.append(tagged(70, 1000), fold)      # leaves 24, tail 6
        assert buf.n == 30

        def bomb(events, feats):
            raise RuntimeError("device exploded")

        with pytest.raises(RuntimeError):
            buf.append(tagged(40, 2000), bomb)  # 64 offered, 6 behind
        # what the raising fold was offered is gone, the left rows of the
        # fold before among it; the newest rows behind it are kept
        assert ports(buf.events[:buf.n]) == list(range(2034, 2040))

    def test_a_fold_that_takes_no_row_raises_instead_of_spinning(self):
        buf = PendingEventBuffer(64, superbatch_max=4)

        def takes_nothing(events, feats):
            return [(0, len(events))]

        buf.append(tagged(10, 1000), takes_nothing)
        with pytest.raises(RuntimeError, match="consumed none"):
            buf.append(tagged(60, 2000), takes_nothing)
        assert ports(buf.events[:buf.n]) == list(range(2054, 2060))
