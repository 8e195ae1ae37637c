"""Async I/O operator (ref: AsyncWaitOperator / AsyncDataStream ITCases:
ordered vs unordered retrieval, capacity backpressure, watermark
hold-back, enrichment correctness)."""
import threading
import time

import numpy as np
import pytest

from flink_tpu.api.environment import StreamExecutionEnvironment
from flink_tpu.api.functions import KeyedProcessFunction
from flink_tpu.api.sinks import CollectSink
from flink_tpu.api.sources import GeneratorSource
from flink_tpu.api.windowing import TumblingEventTimeWindows
from flink_tpu.config import Configuration
from flink_tpu.ops.async_io import AsyncIOOperator
from flink_tpu.time.watermarks import WatermarkStrategy


def make_env():
    return StreamExecutionEnvironment(Configuration(
        {"pipeline.microbatch-size": 64,
         "state.num-key-shards": 4, "state.slots-per-shard": 32}))


def source(n_batches=6, b=64):
    def gen(split, i):
        if i >= n_batches:
            return None
        rng = np.random.default_rng(i)
        return ({"k": rng.integers(0, 10, b).astype(np.int64),
                 "x": np.full(b, i, np.int64)},
                np.sort(rng.integers(i * 500, i * 500 + 900, b)).astype(np.int64))
    return gen


class TestOperatorDirect:
    def test_ordered_release(self):
        order = []

        def slow_first(data, ts):
            # batch 0 is the slowest: ordered mode must still release 0,1,2
            time.sleep(0.3 if data["i"][0] == 0 else 0.01)
            order.append(int(data["i"][0]))
            return dict(data)

        op = AsyncIOOperator(slow_first, capacity=4, ordered=True)
        for i in range(3):
            op.submit(({"i": np.array([i])}, np.array([i]), np.ones(1, bool)), i)
        out = op.poll(drain=True)
        assert [int(b[0]["i"][0]) for b in out] == [0, 1, 2]
        op.close()

    def test_unordered_release_as_completed(self):
        ev = threading.Event()

        def blocky(data, ts):
            if data["i"][0] == 0:
                ev.wait(5)
            return dict(data)

        op = AsyncIOOperator(blocky, capacity=4, ordered=False)
        for i in range(3):
            op.submit(({"i": np.array([i])}, np.array([i]), np.ones(1, bool)), i)
        deadline = time.time() + 5
        got = []
        while len(got) < 2 and time.time() < deadline:
            got += op.poll()
            time.sleep(0.01)
        assert sorted(int(b[0]["i"][0]) for b in got) == [1, 2]
        # watermark held at the oldest pending submit (batch 0, wm 0)
        assert op.watermark <= 0
        ev.set()
        got += op.poll(drain=True)
        assert sorted(int(b[0]["i"][0]) for b in got) == [0, 1, 2]
        op.close()

    def test_capacity_backpressure_via_throttle(self):
        """submit() never blocks (push-lock discipline); throttle() —
        the outside-the-lock hook the ingest loop calls — blocks while
        more than ``capacity`` batches are still running."""
        release = threading.Event()

        def gate(data, ts):
            release.wait(10)
            return dict(data)

        op = AsyncIOOperator(gate, capacity=2, ordered=True, workers=4)
        t0 = time.monotonic()
        for i in range(3):
            op.submit(({"i": np.array([i])}, np.array([i]),
                       np.ones(1, bool)), i)
        # submits are non-blocking: all three returned over capacity
        # while the gate was still shut (one that waited for a free
        # slot would sit out the gate's 10 s); no deadline a loaded
        # host can miss
        assert not release.is_set()
        assert time.monotonic() - t0 < 5.0

        def delayed_release():
            time.sleep(0.25)
            release.set()

        threading.Thread(target=delayed_release, daemon=True).start()
        op.throttle()  # 3 running > capacity 2: blocks until release
        assert release.is_set()
        assert time.monotonic() - t0 >= 0.25
        op.poll(drain=True)
        op.close()

    def test_length_change_rejected(self):
        op = AsyncIOOperator(lambda d, ts: {"x": np.zeros(3)}, capacity=2)
        op.submit(({"x": np.zeros(2)}, np.zeros(2, np.int64),
                   np.ones(2, bool)), 0)
        with pytest.raises(ValueError, match="1:1"):
            op.poll(drain=True)
        op.close()

    def test_user_exception_propagates(self):
        def boom(data, ts):
            raise RuntimeError("lookup failed")

        op = AsyncIOOperator(boom, capacity=2)
        op.submit(({"x": np.zeros(1)}, np.zeros(1, np.int64),
                   np.ones(1, bool)), 0)
        with pytest.raises(RuntimeError, match="lookup failed"):
            op.poll(drain=True)
        op.close()


class TestAsyncE2E:
    def test_enrichment_into_window(self):
        """Enriched field feeds a downstream window; results must match
        the synchronous equivalent exactly (watermark hold-back keeps
        late-drops at zero despite slow lookups)."""
        def enrich(data, ts):
            time.sleep(0.02)  # slow external lookup
            out = dict(data)
            out["v"] = data["x"] * 10 + 1
            return out

        def build(env, sink, use_async):
            s = env.from_source(
                GeneratorSource(source()),
                WatermarkStrategy.for_bounded_out_of_orderness(500))
            if use_async:
                s = s.async_io(enrich, capacity=3)
            else:
                s = s.map(lambda d: {**d, "v": d["x"] * 10 + 1})
            (s.key_by("k").window(TumblingEventTimeWindows.of(1_000))
             .sum("v").add_sink(sink))

        env1, s1 = make_env(), CollectSink()
        build(env1, s1, use_async=False)
        env1.execute("sync")
        env2, s2 = make_env(), CollectSink()
        build(env2, s2, use_async=True)
        r = env2.execute("async")
        rows = lambda s: sorted((int(x["key"]), int(x["window_end"]),
                                 float(x["sum_v"])) for x in s.rows)
        assert rows(s1) == rows(s2)
        assert r.metrics.get("late_records", 0) == 0

    def test_checkpointing_with_async_io(self, tmp_path):
        """Interval checkpoints must coexist with async_io: the barrier
        drains in-flight batches first, the (stateless) operator rides
        the snapshot seam, and the job completes exactly-once
        (regression: snapshot_state used to be missing entirely)."""
        def enrich(data, ts):
            time.sleep(0.005)
            return {**dict(data), "v": data["x"] + 1}

        env = StreamExecutionEnvironment(Configuration(
            {"pipeline.microbatch-size": 64,
             "state.num-key-shards": 4, "state.slots-per-shard": 32,
             "execution.checkpointing.dir": str(tmp_path),
             "execution.checkpointing.interval": 1}))
        sink = CollectSink()
        (env.from_source(GeneratorSource(source()),
                         WatermarkStrategy.for_bounded_out_of_orderness(500))
         .async_io(enrich, capacity=3)
         .key_by("k").window(TumblingEventTimeWindows.of(1_000))
         .sum("v").add_sink(sink))
        env.execute("ckpt-async")
        assert len(sink.rows) > 0

    def test_unordered_same_results(self):
        def enrich(data, ts):
            time.sleep(0.001 * int(data["x"][0] % 3))
            return {**dict(data), "v": data["x"] + 1}

        def build(env, sink, ordered):
            (env.from_source(GeneratorSource(source()),
                             WatermarkStrategy.for_bounded_out_of_orderness(500))
             .async_io(enrich, capacity=4, ordered=ordered)
             .key_by("k").window(TumblingEventTimeWindows.of(1_000))
             .sum("v").add_sink(sink))

        outs = []
        for ordered in (True, False):
            env, sink = make_env(), CollectSink()
            build(env, sink, ordered)
            env.execute(f"o-{ordered}")
            outs.append(sorted((int(x["key"]), int(x["window_end"]),
                                float(x["sum_v"])) for x in sink.rows))
        assert outs[0] == outs[1]
