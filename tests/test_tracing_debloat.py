"""Tracing spans (checkpoint/restore/recovery), thread sampling, and
the adaptive microbatch debloater (ref: SURVEY §6.1 Span/TraceReporter,
flame graphs; §3.6 BufferDebloater)."""
import numpy as np
import pytest

from flink_tpu.config import Configuration
from flink_tpu.obs.metrics import Histogram
from flink_tpu.obs.tracing import Tracer, sample_threads, tracer


class TestTracer:
    def test_span_lifecycle_and_reporter(self):
        """The lifecycle half (the reporter seam, which nothing
        registered on, went in PR 38; the name stays so that the count of
        tests does): a span is in the ring once its block has ended."""
        t = Tracer()
        with t.span("checkpoint.freeze", checkpoint_id=7) as sp:
            assert t.spans() == []      # open: not in the ring yet
            sp.set("bytes", 123)
        spans = t.spans("checkpoint")
        assert len(spans) == 1
        s = spans[0]
        assert s["name"] == "checkpoint.freeze"
        assert s["attributes"] == {"checkpoint_id": 7, "bytes": 123}
        assert s["duration_ms"] is not None and s["duration_ms"] >= 0
        assert t.spans("restore") == []

    def test_span_records_error(self):
        t = Tracer()
        with pytest.raises(ValueError):
            with t.span("x"):
                raise ValueError("boom")
        assert "ValueError" in t.spans()[0]["attributes"]["error"]

    def test_ring_bounded(self):
        t = Tracer(capacity=8)
        for i in range(20):
            with t.span(f"s{i}"):
                pass
        assert len(t.spans()) == 8

    def test_checkpoint_emits_spans_end_to_end(self, tmp_path):
        from flink_tpu.api.environment import StreamExecutionEnvironment
        from flink_tpu.api.sinks import CollectSink
        from flink_tpu.api.windowing import TumblingEventTimeWindows

        tracer.clear()
        rng = np.random.default_rng(0)
        ts = np.sort(rng.integers(0, 4000, 1000)).astype(np.int64)
        env = StreamExecutionEnvironment(Configuration({
            "state.num-key-shards": 4, "state.slots-per-shard": 16,
            "pipeline.microbatch-size": 250,
            "execution.checkpointing.dir": str(tmp_path),
            "execution.checkpointing.interval": 1,
        }))
        sink = CollectSink()
        (env.from_collection({"k": rng.integers(0, 5, 1000).astype(np.int64)},
                             ts, batch_size=250)
         .key_by("k").window(TumblingEventTimeWindows.of(1000)).count()
         .add_sink(sink))
        env.execute("traced")
        freezes = tracer.spans("checkpoint.freeze")
        persists = tracer.spans("checkpoint.persist")
        assert freezes and persists
        assert all(s["duration_ms"] is not None for s in freezes + persists)

    def test_sample_threads_collapsed_stacks(self):
        import threading, time

        stop = threading.Event()

        def busy():
            while not stop.is_set():
                time.sleep(0.001)

        th = threading.Thread(target=busy, daemon=True)
        th.start()
        try:
            out = sample_threads(seconds=0.2, hz=50)
            assert out["samples"] > 0
            assert any("busy@" in stack for stack in out["stacks"])
        finally:
            stop.set()


class TestHistogramRecent:
    def test_quantile_recent_window(self):
        h = Histogram(size=64)
        for _ in range(50):
            h.update(1000.0)
        for _ in range(16):
            h.update(10.0)
        assert h.quantile_recent(0.99, window=16) == pytest.approx(10.0)
        assert h.quantile(0.5) == pytest.approx(1000.0)


class TestDebloater:
    def _run(self, conf_extra):
        from flink_tpu.api.environment import StreamExecutionEnvironment
        from flink_tpu.api.sinks import CollectSink
        from flink_tpu.api.windowing import TumblingEventTimeWindows

        rng = np.random.default_rng(1)
        n = 120_000
        ts = np.sort(rng.integers(0, 60_000, n)).astype(np.int64)
        env = StreamExecutionEnvironment(Configuration({
            "state.num-key-shards": 4, "state.slots-per-shard": 16,
            "pipeline.microbatch-size": 20_000, **conf_extra}))
        sink = CollectSink()
        (env.from_collection({"k": rng.integers(0, 5, n).astype(np.int64)},
                             ts, batch_size=20_000)
         .key_by("k").window(TumblingEventTimeWindows.of(1000)).count()
         .add_sink(sink))
        res = env.execute("debloat")
        return res, sink

    def test_off_by_default_single_batches(self):
        res, sink = self._run({})
        assert res.metrics["batches"] == 6  # source batches pass whole

    def test_target_rechunk_exact_results(self):
        res_a, sink_a = self._run({})
        # an absurdly low target drives the chunk down — results must
        # stay exactly equal regardless of how ingest re-chunks
        res_b, sink_b = self._run({"pipeline.target-latency": 1})
        key = lambda rows: sorted(
            (int(r["key"]), int(r["window_end"]), int(r["count"]))
            for r in rows)
        assert key(sink_a.rows) == key(sink_b.rows)

    def test_control_loop_halves_and_regrows(self):
        """Deterministic unit drive of the BufferDebloater control law:
        overshoot halves the chunk (floored), undershoot regrows it."""
        from flink_tpu.api.environment import StreamExecutionEnvironment
        from flink_tpu.graph.compiler import compile_job
        from flink_tpu.runtime.driver import Driver
        from flink_tpu.api.windowing import TumblingEventTimeWindows
        from flink_tpu.api.sinks import CollectSink

        env = StreamExecutionEnvironment(Configuration({
            "pipeline.target-latency": 100}))
        ts = np.arange(100, dtype=np.int64)
        (env.from_collection({"k": np.zeros(100, np.int64)}, ts)
         .key_by("k").window(TumblingEventTimeWindows.of(10)).count()
         .add_sink(CollectSink()))
        d = Driver(compile_job(env._transforms, env.config,
                               env._watermark_strategy), env.config)
        d._debloat_min = 4
        data = {"k": np.arange(32, dtype=np.int64)}
        ts32 = np.arange(32, dtype=np.int64)

        # first batch seeds the chunk at the source batch size
        out = list(d._debloat_split(data, ts32))
        assert len(out) == 1 and d._debloat_chunk == 32

        # overshoot: p99 of recent samples above target -> halve
        for _ in range(4):
            d._lat_hist.update(500.0)
        d._debloat_adjust()
        assert d._debloat_chunk == 16
        out = list(d._debloat_split(data, ts32))
        assert [len(t) for _, t in out] == [16, 16]
        # records preserved in order across chunks
        assert np.array_equal(
            np.concatenate([t for _, t in out]), ts32)

        # keep overshooting: floors at the minimum
        for _ in range(8):
            d._lat_hist.update(500.0)
            d._debloat_adjust()
        assert d._debloat_chunk == 4

        # deep undershoot: regrows 2x per step
        for _ in range(16):
            d._lat_hist.update(1.0)
        d._debloat_adjust()
        assert d._debloat_chunk == 8
