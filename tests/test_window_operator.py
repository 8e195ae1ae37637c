"""Window operator harness tests — the WindowOperatorTest analogue.

ref: flink-streaming-java/src/test/java/.../streaming/runtime/operators/
windowing/WindowOperatorTest.java — assigner × trigger × lateness × purge
matrix, driven through a single-operator harness with explicit elements
and watermarks, golden-checked against a pure-Python reference model.

Semantics note: firing is batch-granular here (late elements re-fire
their windows at the next watermark call, not per element) — the
documented microbatching tradeoff; the golden model implements the same
granularity so contents must match exactly.
"""
import collections
import dataclasses

import numpy as np
import pytest

from flink_tpu.api.windowing import (
    SlidingEventTimeWindows,
    TumblingEventTimeWindows,
)
from flink_tpu.ops.aggregates import avg_of, count, max_of, min_of, multi, sum_of
from flink_tpu.ops.window import WindowOperator
from flink_tpu.time.watermarks import LONG_MIN


# ---------------------------------------------------------------------------
# Golden reference model (scalar, dict-based — reference semantics).
# ---------------------------------------------------------------------------

class GoldenWindows:
    def __init__(self, assigner, lateness=0):
        self.assigner = assigner
        self.lateness = lateness
        self.contents = collections.defaultdict(lambda: collections.defaultdict(list))
        self.wm = LONG_MIN
        self.pending_refire = set()
        self.attempted_max_end = None
        self.dropped = 0

    def add_batch(self, recs):
        """recs: list of (key, ts, value)"""
        for key, ts, v in recs:
            windows = self.assigner.assign_windows(ts)
            live = [w for w in windows if not (w.end - 1 + self.lateness <= self.wm)]
            if not live:
                self.dropped += 1
                continue
            for w in live:
                self.contents[w][key].append(v)
                already_passed = self.wm >= w.end - 1
                if already_passed:
                    self.pending_refire.add(w)

    def advance(self, wm):
        """Returns list of (key, window_start, window_end, values_list)."""
        if wm < self.wm:
            return []
        prev, self.wm = self.wm, wm
        fire = set(self.pending_refire)
        self.pending_refire.clear()
        for w in list(self.contents):
            if prev < w.end - 1 <= wm:
                fire.add(w)
        out = []
        for w in sorted(fire):
            for key, vals in sorted(self.contents.get(w, {}).items()):
                if vals:
                    out.append((key, w.start, w.end, list(vals)))
        # purge
        for w in list(self.contents):
            if w.end - 1 + self.lateness <= wm:
                del self.contents[w]
        return out


def run_pair(assigner, agg, events, watermarks, lateness=0, ooo=0, golden_agg=None):
    """Drive operator and golden model through interleaved batches and
    watermark advances; return (ours, golden) emission lists."""
    op = WindowOperator(assigner, agg, num_shards=8, slots_per_shard=64,
                        allowed_lateness_ms=lateness, max_out_of_orderness_ms=ooo)
    gold = GoldenWindows(assigner, lateness)
    ours, golden = [], []
    for batch, wm in zip(events, watermarks):
        if batch:
            keys = np.array([k for k, _, _ in batch], dtype=np.int64)
            ts = np.array([t for _, t, _ in batch], dtype=np.int64)
            vals = np.array([v for _, _, v in batch], dtype=np.float64)
            op.process_batch(keys, ts, {"v": vals})
            gold.add_batch(batch)
        if wm is not None:
            fired = op.advance_watermark(wm)
            for i in range(len(fired["key"])):
                row = {f: fired[f][i] for f in fired}
                ours.append(row)
            for key, ws, we, vals in gold.advance(wm):
                golden.append((key, ws, we, vals, golden_agg(vals) if golden_agg else len(vals)))
    return op, ours, golden


def assert_match(ours, golden, result_field, approx=False):
    ours_set = sorted(
        (int(r["key"]), int(r["window_start"]), int(r["window_end"]),
         round(float(r[result_field]), 4))
        for r in ours)
    gold_set = sorted(
        (int(k), int(ws), int(we), round(float(res), 4))
        for k, ws, we, vals, res in golden)
    assert ours_set == gold_set, f"\nours:   {ours_set}\ngolden: {gold_set}"


# ---------------------------------------------------------------------------


class TestTumblingCount:
    def test_basic_single_key(self):
        a = TumblingEventTimeWindows.of(1000)
        events = [[(1, 100, 1.0), (1, 200, 1.0), (1, 1100, 1.0)]]
        op, ours, golden = run_pair(a, count(), events, [2000])
        assert_match(ours, golden, "count")
        assert len(ours) == 2  # two windows fired

    def test_multiple_keys(self):
        a = TumblingEventTimeWindows.of(1000)
        events = [[(k, t, 1.0) for k in range(5) for t in (10, 500, 990)]]
        op, ours, golden = run_pair(a, count(), events, [999])
        assert_match(ours, golden, "count")
        assert len(ours) == 5
        assert all(int(r["count"]) == 3 for r in ours)

    def test_watermark_exactly_at_max_timestamp(self):
        # fire iff wm >= end - 1 (ref: EventTimeTrigger.onEventTime)
        a = TumblingEventTimeWindows.of(1000)
        op, ours, golden = run_pair(a, count(), [[(1, 0, 1.0)], []], [998, 999])
        assert_match(ours, golden, "count")
        assert len(ours) == 1

    def test_empty_windows_not_emitted(self):
        a = TumblingEventTimeWindows.of(1000)
        op, ours, golden = run_pair(a, count(), [[(1, 100, 1.0)]], [10_000])
        assert len(ours) == 1

    def test_no_regression_on_old_watermark(self):
        a = TumblingEventTimeWindows.of(1000)
        op = WindowOperator(a, count(), num_shards=4, slots_per_shard=16)
        op.process_batch(np.array([1]), np.array([100]), {})
        op.advance_watermark(2000)
        fired = op.advance_watermark(1000)
        assert len(fired["key"]) == 0


class TestAggregates:
    def test_sum_max_min_avg(self):
        a = TumblingEventTimeWindows.of(1000)
        agg = multi(count(), sum_of("v"), max_of("v"), min_of("v"), avg_of("v"))
        events = [[(1, 100, 3.0), (1, 200, 5.0), (1, 800, 1.0), (2, 300, 10.0)]]
        op, ours, golden = run_pair(a, agg, events, [1500])
        by_key = {int(r["key"]): r for r in ours}
        assert by_key[1]["count"] == 3
        assert by_key[1]["sum_v"] == 9.0
        assert by_key[1]["max_v"] == 5.0
        assert by_key[1]["min_v"] == 1.0
        assert abs(by_key[1]["avg_v"] - 3.0) < 1e-6
        assert by_key[2]["max_v"] == 10.0

    def test_sum_golden(self):
        a = TumblingEventTimeWindows.of(500)
        events = [[(k, t, float(k * t % 7)) for k in range(3) for t in (10, 400, 600, 900)]]
        op, ours, golden = run_pair(a, sum_of("v"), events, [2000], golden_agg=sum)
        assert_match(ours, golden, "sum_v")


class TestSlidingWindows:
    def test_q5_shape_sliding_count(self):
        # 10s window / 1s slide — the Nexmark Q5 configuration
        a = SlidingEventTimeWindows.of(10_000, 1_000)
        events = [[(1, 500, 1.0), (1, 5500, 1.0), (2, 9_999, 1.0)]]
        op, ours, golden = run_pair(a, count(), events, [30_000])
        assert_match(ours, golden, "count")
        # element at 500 belongs to 10 windows (ends 1000..10000)
        k1 = [r for r in ours if r["key"] == 1]
        assert sum(int(r["count"]) for r in k1) == 10 + 10

    def test_sliding_incremental_watermarks(self):
        a = SlidingEventTimeWindows.of(3000, 1000)
        events = [[(1, 500, 1.0)], [(1, 1500, 1.0)], [(1, 2500, 1.0)], []]
        op, ours, golden = run_pair(a, count(), events, [999, 1999, 2999, 10_000])
        assert_match(ours, golden, "count")


class TestLateness:
    def test_late_beyond_lateness_dropped(self):
        a = TumblingEventTimeWindows.of(1000)
        op = WindowOperator(a, count(), num_shards=4, slots_per_shard=16,
                            allowed_lateness_ms=0, max_out_of_orderness_ms=5000)
        op.process_batch(np.array([1]), np.array([100]), {})
        op.advance_watermark(2000)
        op.process_batch(np.array([1]), np.array([500]), {})  # window [0,1000) dead
        assert op.late_records == 1
        fired = op.advance_watermark(3000)
        assert len(fired["key"]) == 0

    def test_allowed_lateness_refires(self):
        a = TumblingEventTimeWindows.of(1000)
        events = [[(1, 100, 1.0)], [(1, 500, 1.0)], []]
        # wm 1500: window [0,1000) fired with count 1; late element at 500
        # arrives within lateness 1000 → refire with count 2
        op, ours, golden = run_pair(a, count(), events, [1500, 1500, 1600],
                                    lateness=1000, ooo=2000)
        assert_match(ours, golden, "count")
        counts = sorted(int(r["count"]) for r in ours)
        assert counts == [1, 2]

    def test_lateness_cleanup_boundary(self):
        # window [0,1000): dead exactly when wm >= end - 1 + lateness = 1499
        a = TumblingEventTimeWindows.of(1000)
        op = WindowOperator(a, count(), num_shards=4, slots_per_shard=16,
                            allowed_lateness_ms=500, max_out_of_orderness_ms=5000)
        op.process_batch(np.array([1]), np.array([100]), {})
        op.advance_watermark(1498)  # not yet dead
        op.process_batch(np.array([1]), np.array([200]), {})
        assert op.late_records == 0
        fired = op.advance_watermark(1498)
        assert [int(c) for c in fired["count"]] == [2]  # refire with update
        op.advance_watermark(1499)  # now dead
        op.process_batch(np.array([1]), np.array([300]), {})
        assert op.late_records == 1


class TestPurge:
    def test_state_cleared_after_lateness(self):
        a = TumblingEventTimeWindows.of(1000)
        op = WindowOperator(a, count(), num_shards=4, slots_per_shard=16,
                            max_out_of_orderness_ms=2000)
        op.process_batch(np.array([1]), np.array([100]), {})
        op.advance_watermark(5000)
        # all counts back to zero after purge
        assert int(np.asarray(op.state.counts).sum()) == 0


class TestSnapshotRestore:
    def test_snapshot_restore_mid_window(self):
        # ref pattern: WindowOperatorTest snapshot→restore→continue
        a = SlidingEventTimeWindows.of(3000, 1000)
        op1 = WindowOperator(a, count(), num_shards=4, slots_per_shard=16)
        op1.process_batch(np.array([1, 2]), np.array([500, 700]), {})
        op1.advance_watermark(999)
        snap = op1.snapshot_state()

        op2 = WindowOperator(a, count(), num_shards=4, slots_per_shard=16)
        op2.restore_state(snap)
        op2.process_batch(np.array([1]), np.array([1500]), {})
        fired = op2.advance_watermark(10_000)

        # golden: same events, no restore
        op3 = WindowOperator(a, count(), num_shards=4, slots_per_shard=16)
        op3.process_batch(np.array([1, 2]), np.array([500, 700]), {})
        op3.advance_watermark(999)
        op3.process_batch(np.array([1]), np.array([1500]), {})
        expected = op3.advance_watermark(10_000)

        got = sorted(zip(fired["key"], fired["window_end"], fired["count"]))
        want = sorted(zip(expected["key"], expected["window_end"], expected["count"]))
        assert [tuple(map(int, g)) for g in got] == [tuple(map(int, w)) for w in want]


class TestSnapshotPendingRefire:
    def test_refire_survives_restore(self):
        # checkpoint between a late element and its re-firing must not
        # lose the emission (exactly-once recovery)
        a = TumblingEventTimeWindows.of(1000)
        kw = dict(num_shards=4, slots_per_shard=16,
                  allowed_lateness_ms=1000, max_out_of_orderness_ms=2000)
        op1 = WindowOperator(a, count(), **kw)
        op1.process_batch(np.array([1]), np.array([100]), {})
        op1.advance_watermark(1500)                      # fires count=1
        op1.process_batch(np.array([1]), np.array([500]), {})  # pending refire
        snap = op1.snapshot_state()
        op2 = WindowOperator(a, count(), **kw)
        op2.restore_state(snap)
        fired = op2.advance_watermark(1600)
        assert [int(c) for c in fired["count"]] == [2]


class TestNonDivisibleSlide:
    def test_size_not_multiple_of_slide(self):
        # windows START at slide multiples; ends are offset by size
        a = SlidingEventTimeWindows.of(5000, 2000)
        events = [[(1, 100, 1.0)], []]
        op, ours, golden = run_pair(a, count(), events, [None, 60_000])
        assert_match(ours, golden, "count")
        ends = sorted(int(r["window_end"]) for r in ours)
        assert ends == [1000, 3000, 5000]

    def test_degenerate_pane_rejected(self):
        from flink_tpu.ops.window import WindowPlan
        with pytest.raises(ValueError, match="degenerate"):
            WindowPlan.plan(SlidingEventTimeWindows.of(3600_000, 7))


class TestFuzzVsGolden:
    @pytest.mark.parametrize("seed", [0, 1, 2])
    @pytest.mark.parametrize("size,slide,lateness", [
        (1000, 1000, 0),
        (5000, 1000, 1500),
        (4000, 2000, 0),
        (5000, 2000, 0),     # size NOT a multiple of slide
        (5000, 2000, 1500),
    ])
    def test_randomized(self, seed, size, slide, lateness):
        rng = np.random.default_rng(seed)
        a = SlidingEventTimeWindows.of(size, slide) if slide != size \
            else TumblingEventTimeWindows.of(size)
        ooo = 3000
        n_batches, batch = 12, 40
        events, wms = [], []
        max_ts = 0
        for i in range(n_batches):
            ts = rng.integers(max(0, max_ts - ooo), max_ts + 2000, batch)
            max_ts = max(max_ts, int(ts.max()))
            keys = rng.integers(0, 10, batch)
            b = [(int(k), int(t), 1.0) for k, t in zip(keys, ts)]
            events.append(b)
            wms.append(max_ts - ooo - 1)
        events.append([])
        wms.append(max_ts + size + lateness + 10_000)
        op, ours, golden = run_pair(a, count(), events, wms,
                                    lateness=lateness, ooo=ooo)
        assert_match(ours, golden, "count")


class TestLateAfterIdleGap:
    def test_late_window_after_idle_gap_fires(self):
        """Regression: a record in a window the watermark passed during an
        idle gap (within allowed lateness) must fire that window late
        (ref: EventTimeTrigger.onElement fires immediately when
        window.maxTimestamp() <= currentWatermark)."""
        from flink_tpu.api.windowing import TumblingEventTimeWindows
        from flink_tpu.ops import aggregates

        op = WindowOperator(
            TumblingEventTimeWindows.of(1000), aggregates.count(),
            num_shards=8, slots_per_shard=16, allowed_lateness_ms=10_000,
            max_out_of_orderness_ms=10_000)
        op.process_batch(np.array([1]), np.array([500]), {})
        fired = op.advance_watermark(50_000)  # idle gap: only [0,1000) fires
        assert {(int(k), int(e)) for k, e in zip(fired["key"], fired["window_end"])} == {(1, 1000)}
        # late but within lateness: 45999 + 10000 > 50000
        op.process_batch(np.array([2]), np.array([45_500]), {})
        fired = op.advance_watermark(50_001)
        assert {(int(k), int(e)) for k, e in zip(fired["key"], fired["window_end"])} == {(2, 46_000)}
        assert op.late_records == 0


class TestRingAutoGrow:
    def test_oversized_batch_grows_ring_exact_results(self):
        """A microbatch spanning more event time than the pane ring holds
        must grow the ring and remap live columns — not crash, not lose
        data (the backpressure answer is memory, then correctness)."""
        op = WindowOperator(
            TumblingEventTimeWindows.of(1000), count(),
            num_shards=8, slots_per_shard=16)
        ring0 = op.plan.ring
        # one batch covering 40 windows: far beyond the initial ring
        keys = np.arange(200) % 5
        ts = np.linspace(0, 40_000, 200).astype(np.int64)
        op.process_batch(keys, ts, {})
        assert op.plan.ring > ring0
        fired = op.advance_watermark(50_000).materialize()
        # golden: exact per-(key, window) counts
        expect = collections.Counter(
            (int(k), (int(t) // 1000) * 1000 + 1000) for k, t in zip(keys, ts))
        got = {(int(k), int(e)): int(c) for k, e, c in
               zip(fired["key"], fired["window_end"], fired["count"])}
        assert got == dict(expect)

    def test_grow_preserves_live_panes_mid_stream(self):
        """Grow while earlier panes hold data: pre-grow contents must
        survive the column remap."""
        op = WindowOperator(
            SlidingEventTimeWindows.of(4000, 2000), sum_of("v"),
            num_shards=8, slots_per_shard=16)
        op.process_batch(np.array([1, 2]), np.array([500, 1500]),
                         {"v": np.array([10.0, 20.0], np.float32)})
        # second batch leaps 60 windows ahead → forces growth
        op.process_batch(np.array([1]), np.array([120_000]),
                         {"v": np.array([7.0], np.float32)})
        fired = op.advance_watermark(200_000).materialize()
        rows = {(int(k), int(e)): float(s) for k, e, s in
                zip(fired["key"], fired["window_end"], fired["sum_v"])}
        # EXACT equality: the remap must not duplicate pre-grow panes
        # into phantom windows beyond the applied range
        assert rows == {
            (1, 2000): 10.0, (1, 4000): 10.0,
            (2, 2000): 20.0, (2, 4000): 20.0,
            (1, 122_000): 7.0, (1, 124_000): 7.0,
        }
        assert len(fired["key"]) == 6

    def test_grow_after_forward_leap_no_phantom_windows(self):
        """Advisor r2 repro: 2-record batch then a forward leap; the grow
        remap must not duplicate pre-grow sums into windows beyond the
        applied pane range (exact full-output equality)."""
        op = WindowOperator(
            TumblingEventTimeWindows.of(1000), sum_of("v"),
            num_shards=8, slots_per_shard=16)
        op.process_batch(np.array([1, 2]), np.array([100, 900]),
                         {"v": np.array([3.0, 4.0], np.float32)})
        # leap far ahead in the SAME operator — forces ring growth with
        # the new max pane way beyond anything applied to state
        op.process_batch(np.array([1]), np.array([116_000]),
                         {"v": np.array([5.0], np.float32)})
        fired = op.advance_watermark(200_000).materialize()
        rows = {(int(k), int(e)): float(s) for k, e, s in
                zip(fired["key"], fired["window_end"], fired["sum_v"])}
        assert rows == {(1, 1000): 3.0, (2, 1000): 4.0, (1, 117_000): 5.0}

    def test_snapshot_restore_across_grown_ring(self):
        op = WindowOperator(
            TumblingEventTimeWindows.of(1000), count(),
            num_shards=8, slots_per_shard=16)
        op.process_batch(np.arange(50) % 3,
                         np.linspace(0, 30_000, 50).astype(np.int64), {})
        snap = op.snapshot_state()
        op2 = WindowOperator(
            TumblingEventTimeWindows.of(1000), count(),
            num_shards=8, slots_per_shard=16)
        op2.restore_state(snap)
        a = op.advance_watermark(40_000).materialize()
        b = op2.advance_watermark(40_000).materialize()
        assert sorted(zip(a["key"], a["window_end"], a["count"])) == \
               sorted(zip(b["key"], b["window_end"], b["count"]))


class TestTopN:
    """Device-fused per-window top-n (the Q5 hot-items shape) — ref:
    Nexmark Q5 RANK() <= n semantics, ties at the n-th value kept."""

    def _op(self, n, by="count", **kw):
        return WindowOperator(
            TumblingEventTimeWindows.of(1000), count(),
            num_shards=8, slots_per_shard=64, top_n=(by, n), **kw)

    def test_fewer_candidates_than_n_emits_all(self):
        """Advisor r2 high: a window with fewer than n candidate keys
        must emit ALL of them (top_k pads with -inf ⇒ thresh=-inf ⇒
        every real candidate selects)."""
        op = self._op(5)
        op.process_batch(np.array([1, 2, 3]), np.array([100, 200, 300]), {})
        fired = op.advance_watermark(2000).materialize()
        got = {(int(k), int(c)) for k, c in zip(fired["key"], fired["count"])}
        assert got == {(1, 1), (2, 1), (3, 1)}

    def test_top1_picks_max_with_ties(self):
        op = self._op(1)
        # key 1: 3 bids, key 2: 3 bids, key 3: 1 bid → top(1) keeps ties
        keys = np.array([1, 1, 1, 2, 2, 2, 3])
        ts = np.full(7, 100)
        op.process_batch(keys, ts, {})
        fired = op.advance_watermark(2000).materialize()
        got = {(int(k), int(c)) for k, c in zip(fired["key"], fired["count"])}
        assert got == {(1, 3), (2, 3)}

    def test_top2_across_windows(self):
        op = self._op(2)
        keys = np.array([1, 1, 1, 2, 2, 3,   4, 5, 5])
        ts = np.array([0, 1, 2, 3, 4, 5,     1500, 1501, 1502])
        op.process_batch(keys, ts, {})
        fired = op.advance_watermark(3000).materialize()
        got = {(int(k), int(e), int(c)) for k, e, c in
               zip(fired["key"], fired["window_end"], fired["count"])}
        # window 1: counts 3,2,1 → top2 = {1:3, 2:2}; window 2: 1,2 → both
        assert got == {(1, 1000, 3), (2, 1000, 2), (4, 2000, 1), (5, 2000, 2)}

    def test_tie_explosion_raises_loudly(self):
        """More tied winners than the selection capacity must RAISE at
        drain (advisor r2 medium: no silent truncation)."""
        op = self._op(1)
        cap = op._topn_cap(1)
        nk = cap + 40
        assert nk <= 8 * 64
        keys = np.arange(nk)
        ts = np.full(nk, 100)
        op.process_batch(keys, ts, {})  # every key count=1 → all tie
        with pytest.raises(RuntimeError, match="truncation|tie"):
            op.advance_watermark(2000).materialize()


class TestLateLowPaneGrowth:
    def test_low_pane_batch_below_live_range_triggers_growth(self):
        """A batch arriving BELOW the live range (watermark not yet
        advanced, so not late) whose span vs the live max exceeds the
        ring must grow it — the batch max alone understates the span,
        and without growth the low pane's column write aliases the live
        max pane's column."""
        op = WindowOperator(
            TumblingEventTimeWindows.of(1000), sum_of("v"),
            num_shards=8, slots_per_shard=16)
        ring0 = op.plan.ring            # 6: 1 pane + 1 + 4 headroom
        hi_pane = ring0 + 4
        lo_pane = 4                     # collides: hi_pane % ring0 == 4
        assert hi_pane % ring0 == lo_pane % ring0
        op.process_batch(np.array([1]), np.array([hi_pane * 1000 + 499]),
                         {"v": np.array([2.0], np.float32)})
        op.process_batch(np.array([2]), np.array([lo_pane * 1000 + 500]),
                         {"v": np.array([9.0], np.float32)})
        assert op.plan.ring > ring0
        fired = op.advance_watermark(10_000_000).materialize()
        rows = {(int(k), int(e)): float(s) for k, e, s in
                zip(fired["key"], fired["window_end"], fired["sum_v"])}
        assert rows == {(1, (hi_pane + 1) * 1000): 2.0,
                        (2, (lo_pane + 1) * 1000): 9.0}


class TestSplitUpload:
    """The 3-byte/record (uint16 slot + uint8 column) upload encoding
    must be byte-identical to the packed-int32 path (apply_kernel vs
    apply_kernel_split), and layouts too large for it must fall back."""

    def _drive(self, op):
        rng = np.random.default_rng(7)
        out = []
        for i in range(4):
            n = 257
            keys = rng.integers(0, 50, n)
            ts = rng.integers(i * 2000, i * 2000 + 4000, n)
            vals = rng.random(n).astype(np.float32)
            op.process_batch(keys, ts, {"v": vals})
            fired = op.advance_watermark(i * 2000)
            for j in range(len(fired["key"])):
                out.append(tuple(
                    round(float(fired[f][j]), 4) if f.startswith("sum")
                    else int(fired[f][j])
                    for f in ("key", "window_start", "window_end", "sum_v")))
        fired = op.advance_watermark(10_000_000)
        for j in range(len(fired["key"])):
            out.append(tuple(
                round(float(fired[f][j]), 4) if f.startswith("sum")
                else int(fired[f][j])
                for f in ("key", "window_start", "window_end", "sum_v")))
        return sorted(out)

    def test_split_matches_packed(self):
        mk = lambda: WindowOperator(
            SlidingEventTimeWindows.of(3000, 1000), sum_of("v"),
            num_shards=8, slots_per_shard=16)
        op_split = mk()
        assert op_split._split_upload
        op_packed = mk()
        op_packed._split_upload = False
        assert self._drive(op_split) == self._drive(op_packed)

    def test_oversized_layout_falls_back(self):
        op = WindowOperator(
            TumblingEventTimeWindows.of(1000), count(),
            num_shards=16, slots_per_shard=8192)   # 131072 rows > u16
        assert not op._split_upload
        op.process_batch(np.array([1, 2]), np.array([100, 200]), {})
        fired = op.advance_watermark(5000)
        assert sorted(int(c) for c in fired["count"]) == [1, 1]


class TestHostPreaggregation:
    """The host combiner path (LaneAggregate.sum_fields): batches big
    enough to pass the decisive-win gate must produce results identical
    to the per-record upload path."""

    def _run(self, agg, golden_agg, field_vals, result_field,
             expect_preagg=True):
        assigner = SlidingEventTimeWindows.of(10_000, 1_000)
        rng = np.random.default_rng(3)
        B = 4096
        events, wms = [], []
        t = 0
        for i in range(4):
            keys = rng.integers(0, 40, B)
            ts = t + rng.integers(0, 3000, B)
            vals = field_vals(rng, B)
            events.append(list(zip(keys.tolist(), ts.tolist(), vals.tolist())))
            t += 3000
            wms.append(t - 1000)
        wms[-1] = t + 20_000
        op, ours, golden = run_pair(
            assigner, agg, events, wms, golden_agg=golden_agg)
        took_preagg = op.prof.get("preagg_batches", 0) > 0
        assert took_preagg == expect_preagg
        # f32 lane accumulation order differs between the paths; compare
        # with an f32-level tolerance, not digit-exact
        gold = {(int(k), int(ws), int(we)): res
                for k, ws, we, vals, res in golden}
        assert len(ours) == len(gold)
        for r in ours:
            key = (int(r["key"]), int(r["window_start"]), int(r["window_end"]))
            assert abs(float(r[result_field]) - gold[key]) < 1e-3 * max(
                1.0, abs(gold[key]))

    def test_count_preagg_matches_golden(self):
        self._run(count(), len, lambda rng, b: np.ones(b), "count")

    def test_sum_lane_preagg_matches_golden(self):
        self._run(sum_of("v"), sum,
                  lambda rng, b: rng.integers(0, 100, b).astype(np.float64),
                  "sum_v")

    def test_avg_lane_preagg_matches_golden(self):
        self._run(avg_of("v"), lambda vs: sum(vs) / len(vs),
                  lambda rng, b: rng.integers(0, 100, b).astype(np.float64),
                  "avg_v")

    def test_max_lane_falls_through(self):
        # max lanes are not host-combinable: sum_fields is None, the
        # operator must keep the per-record path and stay correct
        self._run(max_of("v"), max,
                  lambda rng, b: rng.integers(0, 100, b).astype(np.float64),
                  "max_v", expect_preagg=False)
