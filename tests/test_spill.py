"""Host spill store: state beyond HBM capacity degrades to slower, never
wrong — the RocksDBKeyedStateBackend role (ref: runtime/state/
RocksDBKeyedStateBackend, SURVEY §3.4, §3.10 item 1). The golden
contract: a run with tiny slot capacity + state.backend='spill' must
produce byte-identical results to a run with ample capacity, at key
cardinality ~100x the resident capacity (round-2 mandate #5)."""
import numpy as np
import pytest

from flink_tpu.api.environment import StreamExecutionEnvironment
from flink_tpu.api.sinks import CollectSink
from flink_tpu.api.sources import GeneratorSource
from flink_tpu.api.windowing import SlidingEventTimeWindows, TumblingEventTimeWindows
from flink_tpu.config import Configuration
from flink_tpu.ops import aggregates
from flink_tpu.ops.window import WindowOperator
from flink_tpu.state.spill import HostSpillStore
from flink_tpu.time.watermarks import WatermarkStrategy


def make_env(slots, backend="hbm", extra=None):
    conf = {
        "state.num-key-shards": 4,
        "state.slots-per-shard": slots,
        "state.backend": backend,
        "pipeline.microbatch-size": 256,
    }
    conf.update(extra or {})
    return StreamExecutionEnvironment(Configuration(conf))


def rows_of(sink):
    out = []
    for row in sink.rows:
        out.append(tuple(
            (k, int(v) if np.issubdtype(np.asarray(v).dtype, np.integer)
             else round(float(v), 3))
            for k, v in sorted(row.items())))
    return sorted(out)


def churn_source(n_batches=6, n_keys=1600, b=256):
    """~100x the 16-slot resident capacity (4 shards x 4 slots)."""
    def gen(split, i):
        if i >= n_batches:
            return None
        rng = np.random.default_rng(42 + i)
        return ({"k": rng.integers(0, n_keys, b).astype(np.int64),
                 "v": rng.integers(1, 100, b).astype(np.int64)},
                np.sort(rng.integers(i * 700, i * 700 + 1400, b)).astype(np.int64))
    return gen


def run_pipeline(env, agg_builder, window, src=None):
    sink = CollectSink()
    s = (env.from_source(GeneratorSource(src or churn_source()),
                         WatermarkStrategy.for_bounded_out_of_orderness(800))
         .key_by("k")
         .window(window))
    agg_builder(s).add_sink(sink)
    res = env.execute("spill-job")
    return sink, res


class TestSpillGolden:
    def test_count_100x_capacity_exact(self):
        """16 resident slots, 1600 distinct keys: spill run == roomy run."""
        roomy, _ = run_pipeline(make_env(2048),
                                lambda s: s.count(),
                                TumblingEventTimeWindows.of(1_000))
        tiny, res = run_pipeline(make_env(4, backend="spill"),
                                 lambda s: s.count(),
                                 TumblingEventTimeWindows.of(1_000))
        assert rows_of(roomy) == rows_of(tiny)
        assert res.metrics["records_spilled"] > 0
        assert res.metrics.get("records_dropped_full", 0) == 0

    def test_multi_lane_sum_max_avg_exact(self):
        agg = aggregates.multi(
            aggregates.sum_of("v"), aggregates.max_of("v"),
            aggregates.avg_of("v"))
        roomy, _ = run_pipeline(make_env(2048),
                                lambda s: s.aggregate(agg),
                                SlidingEventTimeWindows.of(2_000, 1_000))
        tiny, res = run_pipeline(make_env(4, backend="spill"),
                                 lambda s: s.aggregate(agg),
                                 SlidingEventTimeWindows.of(2_000, 1_000))
        assert rows_of(roomy) == rows_of(tiny)
        assert res.metrics["records_spilled"] > 0

    def test_hbm_backend_default_refuses_to_drop(self):
        """Default-safe policy: the 'hbm' backend at tiny capacity FAILS
        the job (the reference degrades, never drops — SURVEY §3.4)
        unless drops are explicitly allowed."""
        import pytest as _pytest

        with _pytest.raises(RuntimeError, match="key directory shard full"):
            run_pipeline(make_env(4),
                         lambda s: s.count(),
                         TumblingEventTimeWindows.of(1_000))

    def test_hbm_backend_drops_with_accounting_when_allowed(self):
        """state.allow-drops=true restores counted degradation — loud
        (records_dropped_full gauge), never silent."""
        env = make_env(4, extra={"state.allow-drops": True})
        _, res = run_pipeline(env,
                              lambda s: s.count(),
                              TumblingEventTimeWindows.of(1_000))
        assert res.metrics["records_dropped_full"] > 0
        assert res.metrics.get("records_spilled", 0) == 0

    def test_late_within_lateness_refires_spilled_key(self):
        """A late record for a HOST-resident key must re-fire its window
        with the updated result, mirroring the device path's
        late-within-lateness semantics."""
        def gen(split, i):
            if i == 0:  # 20 keys fill the 4x1 slots; most spill
                return ({"k": np.arange(20, dtype=np.int64)},
                        np.full(20, 500, np.int64))
            if i == 1:  # watermark passes window [0,1000) -> fires
                return ({"k": np.array([100], np.int64)},
                        np.array([1800], np.int64))
            if i == 2:  # late-but-allowed record for spilled key 19
                return ({"k": np.array([19], np.int64)},
                        np.array([600], np.int64))
            return None

        env = make_env(1, backend="spill",
                       extra={"pipeline.microbatch-size": 32})
        sink = CollectSink()
        (env.from_source(GeneratorSource(gen),
                         WatermarkStrategy.for_bounded_out_of_orderness(200))
         .key_by("k")
         .window(TumblingEventTimeWindows.of(1_000))
         .allowed_lateness(5_000)
         .count()
         .add_sink(sink))
        env.execute("late-spill")
        k19 = [(int(r["count"])) for r in sink.rows
               if int(r["key"]) == 19 and int(r["window_end"]) == 1000]
        # initial fire (count 1) then the late re-fire (count 2)
        assert k19 == [1, 2]

    def test_topn_union_rerank_exact(self):
        """Top-n winners must come from the UNION of device-resident and
        host-spilled keys — the hot key living on the host must not
        vanish from the leaderboard."""
        def gen(split, i):
            if i >= 4:
                return None
            rng = np.random.default_rng(9 + i)
            b = 200
            keys = rng.integers(0, 300, b).astype(np.int64)
            return ({"k": keys, "v": np.ones(b, np.int64)},
                    np.sort(rng.integers(i * 600, i * 600 + 1200, b)).astype(np.int64))

        def build(s):
            return s.count().top(3, "count")

        roomy, _ = run_pipeline(make_env(2048), build,
                                SlidingEventTimeWindows.of(2_000, 1_000),
                                src=gen)
        tiny, res = run_pipeline(make_env(4, backend="spill"), build,
                                 SlidingEventTimeWindows.of(2_000, 1_000),
                                 src=gen)
        assert res.metrics["records_spilled"] > 0
        assert rows_of(roomy) == rows_of(tiny)


class TestCoalescedDrainTopN:
    def test_union_rerank_survives_marker_coalescing(self):
        """The drain thread coalescing two fire markers into one ring
        poll must still re-rank each window's device winners against its
        host-spill rows — per-fire attribution rides the operator-level
        extras queue, not the markers (regression: a coalesced drain
        used to emit the displaced resident key alongside the spilled
        winner)."""
        from flink_tpu.ops.window import FiredWindows

        op = WindowOperator(
            TumblingEventTimeWindows.of(1_000), aggregates.count(),
            num_shards=1, slots_per_shard=1, max_out_of_orderness_ms=0,
            spill=True, top_n=("count", 1))
        # W1 [0,1000): resident key 7 (count 2) beats spilled key 50 (1)
        op.process_batch(np.array([7, 7, 50], np.int64),
                         np.array([100, 200, 300], np.int64), {})
        f1 = op.advance_watermark(1_500)
        # W2 [1000,2000): spilled key 50 (count 5) beats resident 7 (1)
        op.process_batch(np.array([7, 50, 50, 50, 50, 50], np.int64),
                         np.array([1100, 1200, 1200, 1300, 1300, 1400],
                                  np.int64), {})
        f2 = op.advance_watermark(2_500)
        FiredWindows.materialize_many([f1, f2])  # ONE coalesced poll
        rows = {}
        for f in (f1, f2):
            d = dict(f)
            for k, w, c in zip(d["key"], d["window_end"], d["count"]):
                rows.setdefault(int(w), []).append((int(k), int(c)))
        assert rows[1000] == [(7, 2)]
        assert rows[2000] == [(50, 5)]


    def test_refire_nonmonotone_rank_field_exact(self):
        """A late record can LOWER a key's avg, so the refire's winner
        set differs in a non-monotone way; the sync per-fire drain must
        deliver each fire's exact union leaderboard (regression: the
        coalesced dedup kept a stale device row that out-ranked the
        refire's true winner)."""
        from flink_tpu.ops.window import FiredWindows

        op = WindowOperator(
            TumblingEventTimeWindows.of(1_000), aggregates.avg_of("v"),
            num_shards=1, slots_per_shard=2, max_out_of_orderness_ms=0,
            allowed_lateness_ms=5_000, spill=True, top_n=("avg_v", 1))
        # resident A=1 (avg 900), B=2 (avg 600); spilled C=3 (avg 100)
        op.process_batch(
            np.array([1, 2, 3], np.int64),
            np.array([100, 200, 300], np.int64),
            {"v": np.array([900, 600, 100], np.int64)})
        f1 = op.advance_watermark(1_500)
        # late-within-lateness: A drops to avg 500 -> refire winner is B
        op.process_batch(np.array([1], np.int64),
                         np.array([400], np.int64),
                         {"v": np.array([100], np.int64)})
        f2 = op.advance_watermark(1_500)
        FiredWindows.materialize_many([f1, f2])
        w1 = [(int(k), float(v)) for k, v in zip(f1["key"], f1["avg_v"])]
        w2 = [(int(k), float(v)) for k, v in zip(f2["key"], f2["avg_v"])]
        assert w1 == [(1, 900.0)]
        assert w2 == [(2, 600.0)]

    def test_misrouted_records_not_absorbed(self):
        """slot == -1 (key outside this operator's shard range) is a
        routing error — the spill store must NOT aggregate it (the key
        would live on two workers at once); it drops with accounting."""
        from flink_tpu.records import hash_keys_numpy

        ks = np.arange(200, dtype=np.int64)
        shards = hash_keys_numpy(ks) % 4
        inside = ks[shards < 2][0]
        outside = ks[shards >= 2][0]
        op = WindowOperator(
            TumblingEventTimeWindows.of(1_000), aggregates.count(),
            num_shards=4, slots_per_shard=8, max_out_of_orderness_ms=0,
            shard_range=(0, 2), spill=True)
        op.allow_drops = True  # this test asserts the counted-drop path
        op.process_batch(np.array([inside, outside], np.int64),
                         np.array([100, 100], np.int64), {})
        assert op.records_dropped_full == 1
        assert op.records_spilled == 0
        fired = dict(op.advance_watermark(2_000))
        assert [int(k) for k in fired["key"]] == [int(inside)]


class TestSpillCheckpoint:
    def test_snapshot_restore_roundtrip(self, tmp_path):
        """Operator-level: snapshot mid-stream with host-resident state,
        restore into a fresh operator, continue — results match an
        uninterrupted twin."""
        def mk():
            return WindowOperator(
                TumblingEventTimeWindows.of(1_000), aggregates.count(),
                num_shards=4, slots_per_shard=2,
                max_out_of_orderness_ms=500, spill=True)

        keys1 = np.arange(40, dtype=np.int64)
        ts1 = np.full(40, 300, np.int64)
        keys2 = np.arange(40, dtype=np.int64)
        ts2 = np.full(40, 700, np.int64)

        straight = mk()
        straight.process_batch(keys1, ts1, {})
        straight.process_batch(keys2, ts2, {})
        want = dict(straight.advance_watermark(2_000))

        a = mk()
        a.process_batch(keys1, ts1, {})
        snap = a.snapshot_state()
        b = mk()
        b.restore_state(snap)
        b.process_batch(keys2, ts2, {})
        got = dict(b.advance_watermark(2_000))

        for f in want:
            w = np.asarray(want[f])
            g = np.asarray(got[f])
            ow = np.lexsort((np.asarray(want["key"]), np.asarray(want["window_end"])))
            og = np.lexsort((np.asarray(got["key"]), np.asarray(got["window_end"])))
            np.testing.assert_array_equal(w[ow], g[og], err_msg=f)


    def test_restore_into_hbm_backend_refuses_spill_state(self):
        """Switching state.backend to 'hbm' before a restore must not
        silently discard host-resident aggregates."""
        a = WindowOperator(
            TumblingEventTimeWindows.of(1_000), aggregates.count(),
            num_shards=1, slots_per_shard=1, max_out_of_orderness_ms=0,
            spill=True)
        a.process_batch(np.arange(10, dtype=np.int64),
                        np.full(10, 100, np.int64), {})
        snap = a.snapshot_state()
        b = WindowOperator(
            TumblingEventTimeWindows.of(1_000), aggregates.count(),
            num_shards=1, slots_per_shard=1, max_out_of_orderness_ms=0,
            spill=False)
        with pytest.raises(ValueError, match="spill"):
            b.restore_state(snap)


class TestSpillStoreUnit:
    def test_absorb_fire_purge(self):
        st = HostSpillStore(aggregates.multi(
            aggregates.sum_of("v"), aggregates.max_of("v")))
        keys = np.array([5, 5, 9, 5], np.int64)
        panes = np.array([0, 0, 0, 1], np.int64)
        v = np.array([10, 20, 7, 3], np.int64)
        st.absorb(keys, panes, {"v": v})
        # window = panes [0, 2) with ppw=2
        rows = st.fire([2], panes_per_window=2, pane_ms=1000,
                       offset_ms=0, size_ms=2000)
        got = {int(k): (s, m, c) for k, s, m, c in zip(
            rows["key"], rows["sum_v"], rows["max_v"], rows["count"])}
        assert got[5] == (33.0, 20.0, 3)
        assert got[9] == (7.0, 7.0, 1)
        st.purge_below(2)
        assert st.fire([2], 2, 1000, 0, 2000) is None
        assert st.records_spilled == 4


class TestHostPinnedLaneMath:
    """Spilled keys (and session segments) do their lane math on JAX's
    CPU backend; when the platform list hides it (JAX_PLATFORMS=tpu)
    both sites fail the same loud way instead of one raising and the
    other silently moving the work to the accelerator."""

    def test_hidden_cpu_backend_raises_naming_the_setting(self, monkeypatch):
        import jax

        from flink_tpu.ops.session import SessionOperator

        def no_cpu(backend=None, **_kw):
            raise RuntimeError(f"Unknown backend {backend}")

        monkeypatch.setattr(jax, "local_devices", no_cpu)
        monkeypatch.setenv("JAX_PLATFORMS", "tpu")
        store = HostSpillStore(aggregates.sum_of("v"))
        with pytest.raises(RuntimeError, match="JAX_PLATFORMS='tpu'"):
            store.absorb(np.arange(4), np.zeros(4, np.int64),
                         {"v": np.ones(4, np.float32)})
        sess = SessionOperator(1000, aggregates.sum_of("v"))
        with pytest.raises(RuntimeError, match="tpu,cpu"):
            sess.process_batch(np.arange(4), np.arange(4, dtype=np.int64),
                               {"v": np.ones(4, np.float32)})
