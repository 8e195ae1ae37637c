"""Checkpoint / resume / exactly-once tests — the RescalingITCase /
UnalignedCheckpointITCase analogues (ref: flink-tests/.../test/
checkpointing/*.java), driven on the local driver with simulated failure
(re-running the job from the latest checkpoint with replayable sources).
"""
import os

import numpy as np
import pytest

from flink_tpu import faults
from flink_tpu.api.environment import StreamExecutionEnvironment
from flink_tpu.api.sinks import TransactionalCollectSink
from flink_tpu.api.sources import GeneratorSource
from flink_tpu.api.windowing import SlidingEventTimeWindows, TumblingEventTimeWindows
from flink_tpu.checkpoint.storage import FsCheckpointStorage
from flink_tpu.config import Configuration
from flink_tpu.nexmark.generator import NexmarkConfig, bid_stream
from flink_tpu.nexmark.queries import q5_hot_items
from flink_tpu.ops import aggregates
from flink_tpu.ops.window import WindowOperator
from flink_tpu.runtime.supervisor import run_with_recovery
from flink_tpu.time.watermarks import WatermarkStrategy

from test_chaos import replayable


def make_conf(tmp_path, extra=None):
    c = {
        "state.num-key-shards": 8,
        "state.slots-per-shard": 64,
        "pipeline.microbatch-size": 128,
        "execution.checkpointing.dir": str(tmp_path),
        "execution.checkpointing.interval": 1,  # every loop pass (1ms wall)
    }
    c.update(extra or {})
    return Configuration(c)


def failing_source(n_batches, fail_after=None):
    """Deterministic generator; optionally raises mid-stream to simulate
    a task failure (ref: the throwing-mapper pattern in ITCases)."""

    def gen(split, i):
        if i >= n_batches:
            return None
        if fail_after is not None and i == fail_after:
            raise RuntimeError("injected failure")
        rng = np.random.default_rng(1000 * int(split) + i)
        keys = rng.integers(0, 10, 64).astype(np.int64)
        ts = np.sort(rng.integers(i * 500, i * 500 + 1000, 64)).astype(np.int64)
        return {"k": keys}, ts

    return gen


def golden_counts(n_batches, n_splits=1):
    expect = {}
    for split in range(n_splits):
        for i in range(n_batches):
            rng = np.random.default_rng(1000 * split + i)
            keys = rng.integers(0, 10, 64).astype(np.int64)
            ts = np.sort(rng.integers(i * 500, i * 500 + 1000, 64)).astype(np.int64)
            for k, t in zip(keys, ts):
                kk = (int(k), (int(t) // 1000) * 1000)
                expect[kk] = expect.get(kk, 0) + 1
    return expect


class TestCheckpointStorage:
    def test_save_load_latest_retention(self, tmp_path):
        st = FsCheckpointStorage(str(tmp_path), "job1", retained=2)
        for i in range(1, 5):
            st.save(i, {"x": np.arange(i), "checkpoint_id": i})
        hs = st.list_complete()
        assert [h.checkpoint_id for h in hs] == [3, 4]
        latest = st.latest()
        assert latest.checkpoint_id == 4
        payload = FsCheckpointStorage.load(latest)
        assert list(payload["x"]) == [0, 1, 2, 3]

    def test_savepoints_never_retired(self, tmp_path):
        st = FsCheckpointStorage(str(tmp_path), "job1", retained=1)
        st.save(1, {"a": 1}, savepoint=True)
        for i in range(2, 5):
            st.save(i, {"a": i})
        hs = st.list_complete()
        assert [(h.checkpoint_id, h.is_savepoint) for h in hs] == [
            (1, True), (4, False)]

    def test_incomplete_checkpoint_ignored(self, tmp_path):
        st = FsCheckpointStorage(str(tmp_path), "job1")
        st.save(1, {"a": 1})
        os.makedirs(os.path.join(str(tmp_path), "job1", "chk-2"))
        # chk-2 has no manifest → ignored
        assert st.latest().checkpoint_id == 1


class TestExactlyOnceResume:
    def test_fail_resume_exactly_once(self, tmp_path):
        """Run → crash mid-stream → resume from latest checkpoint →
        committed rows must equal the golden exactly (no loss, no dupes).
        """
        n_batches = 12
        sink = TransactionalCollectSink()

        def build(env, source):
            return (env.from_source(
                        source,
                        WatermarkStrategy.for_bounded_out_of_orderness(1000))
                    .key_by("k")
                    .window(TumblingEventTimeWindows.of(1000))
                    .count()
                    .add_sink(sink))

        env = StreamExecutionEnvironment(make_conf(tmp_path))
        build(env, GeneratorSource(failing_source(n_batches, fail_after=7)))
        with pytest.raises(RuntimeError, match="injected failure"):
            env.execute("eo-job")

        committed_before = len(sink.committed)
        # resume: same job name, restore=latest; sources replay from
        # recorded positions; uncommitted epochs discarded
        env2 = StreamExecutionEnvironment(make_conf(
            tmp_path, {"execution.checkpointing.restore": "latest"}))
        build(env2, GeneratorSource(failing_source(n_batches)))
        env2.execute("eo-job")

        got = {}
        for r in sink.committed:
            kk = (int(r["key"]), int(r["window_start"]))
            assert kk not in got, f"duplicate emission for {kk}"
            got[kk] = int(r["count"])
        assert got == golden_counts(n_batches)
        assert committed_before < len(sink.committed)

    def test_resume_without_failure_is_noop_restart(self, tmp_path):
        """Restoring from the final checkpoint of a completed job and
        re-running yields no duplicate commits (positions at end)."""
        n_batches = 4
        sink = TransactionalCollectSink()
        env = StreamExecutionEnvironment(make_conf(tmp_path))
        (env.from_source(GeneratorSource(failing_source(n_batches)),
                         WatermarkStrategy.for_bounded_out_of_orderness(1000))
         .key_by("k").window(TumblingEventTimeWindows.of(1000)).count()
         .add_sink(sink))
        env.execute("noop-job")
        n1 = len(sink.committed)
        assert {(int(r["key"]), int(r["window_start"])): int(r["count"])
                for r in sink.committed} == golden_counts(n_batches)

        env2 = StreamExecutionEnvironment(make_conf(
            tmp_path, {"execution.checkpointing.restore": "latest"}))
        (env2.from_source(GeneratorSource(failing_source(n_batches)),
                          WatermarkStrategy.for_bounded_out_of_orderness(1000))
         .key_by("k").window(TumblingEventTimeWindows.of(1000)).count()
         .add_sink(sink))
        env2.execute("noop-job")
        assert len(sink.committed) == n1  # nothing new: already at end


@pytest.mark.shard_map
class TestReshard:
    def test_restore_local_snapshot_into_mesh(self):
        """Rescale 1 → 8 devices: snapshot from a local operator restores
        into a sharded one (key-shard space fixed, rows re-blocked)."""
        import jax

        from flink_tpu.parallel.mesh import make_mesh_plan

        rng = np.random.default_rng(9)
        keys = rng.integers(0, 200, 500).astype(np.int64)
        ts = rng.integers(0, 3000, 500).astype(np.int64)

        op1 = WindowOperator(SlidingEventTimeWindows.of(2000, 1000),
                             aggregates.count(), num_shards=8,
                             slots_per_shard=64)
        op1.process_batch(keys, ts, {})
        snap = op1.snapshot_state()

        mp = make_mesh_plan(8, 64, jax.devices()[:8])
        op8 = WindowOperator(SlidingEventTimeWindows.of(2000, 1000),
                             aggregates.count(), mesh_plan=mp)
        op8.restore_state(snap)

        f1 = op1.advance_watermark(5000).materialize()
        f8 = op8.advance_watermark(5000).materialize()
        a = sorted(zip(f1["key"], f1["window_end"], f1["count"]))
        b = sorted(zip(f8["key"], f8["window_end"], f8["count"]))
        assert a == b and len(a) > 0

    def test_restore_mesh_snapshot_into_local(self):
        """Rescale 8 → 1 device."""
        import jax

        from flink_tpu.parallel.mesh import make_mesh_plan

        rng = np.random.default_rng(10)
        keys = rng.integers(0, 100, 400).astype(np.int64)
        ts = rng.integers(0, 2000, 400).astype(np.int64)

        mp = make_mesh_plan(8, 32, jax.devices()[:8])
        op8 = WindowOperator(TumblingEventTimeWindows.of(1000),
                             aggregates.count(), mesh_plan=mp)
        op8.process_batch(keys, ts, {})
        snap = op8.snapshot_state()

        op1 = WindowOperator(TumblingEventTimeWindows.of(1000),
                             aggregates.count(), num_shards=8,
                             slots_per_shard=32)
        op1.restore_state(snap)

        f8 = op8.advance_watermark(3000).materialize()
        f1 = op1.advance_watermark(3000).materialize()
        a = sorted(zip(f8["key"], f8["window_end"], f8["count"]))
        b = sorted(zip(f1["key"], f1["window_end"], f1["count"]))
        assert a == b and len(a) > 0


class _CrashOnCommitSink(TransactionalCollectSink):
    """Crashes between the checkpoint manifest write and the 2PC commit
    round — the exact window the staged-epoch persistence covers."""

    def __init__(self, crash_at_cid):
        super().__init__()
        self._crash_at = crash_at_cid
        self._crashed = False

    def notify_checkpoint_complete(self, checkpoint_id):
        if checkpoint_id == self._crash_at and not self._crashed:
            self._crashed = True
            raise RuntimeError("injected crash before commit")
        super().notify_checkpoint_complete(checkpoint_id)


class TestTwoPhaseCommitRecovery:
    def test_crash_between_save_and_commit_recommits_epoch(self, tmp_path):
        """Checkpoint N is saved but the process dies before the sink
        commit round. On restore the staged epoch persisted INSIDE
        checkpoint N must be re-committed, not aborted — otherwise that
        epoch's output is lost forever (sources replay only post-N).
        ref: TwoPhaseCommitSinkFunction pending-transaction state."""
        n_batches = 12
        sink = _CrashOnCommitSink(crash_at_cid=3)

        def build(env, source):
            return (env.from_source(
                        source,
                        WatermarkStrategy.for_bounded_out_of_orderness(1000))
                    .key_by("k")
                    .window(TumblingEventTimeWindows.of(1000))
                    .count()
                    .add_sink(sink))

        env = StreamExecutionEnvironment(make_conf(tmp_path))
        build(env, GeneratorSource(failing_source(n_batches)))
        with pytest.raises(RuntimeError, match="injected crash before commit"):
            env.execute("cp-crash-job")

        env2 = StreamExecutionEnvironment(make_conf(
            tmp_path, {"execution.checkpointing.restore": "latest"}))
        build(env2, GeneratorSource(failing_source(n_batches)))
        env2.execute("cp-crash-job")

        got = {}
        for r in sink.committed:
            kk = (int(r["key"]), int(r["window_start"]))
            assert kk not in got, f"duplicate emission for {kk}"
            got[kk] = int(r["count"])
        assert got == golden_counts(n_batches)

    def test_restore_with_no_checkpoint_aborts_reused_sink(self, tmp_path):
        """Failure BEFORE the first checkpoint: restore finds nothing, yet
        a sink instance reused across attempts must still drop the
        crashed attempt's pending rows or the full replay duplicates
        them."""
        n_batches = 6
        sink = TransactionalCollectSink()
        conf = {"execution.checkpointing.interval": 10_000_000}  # never mid-run

        def build(env, source):
            return (env.from_source(
                        source,
                        WatermarkStrategy.for_bounded_out_of_orderness(1000))
                    .key_by("k")
                    .window(TumblingEventTimeWindows.of(1000))
                    .count()
                    .add_sink(sink))

        env = StreamExecutionEnvironment(make_conf(tmp_path, conf))
        build(env, GeneratorSource(failing_source(n_batches, fail_after=4)))
        with pytest.raises(RuntimeError, match="injected failure"):
            env.execute("early-crash-job")

        conf2 = dict(conf, **{"execution.checkpointing.restore": "latest"})
        env2 = StreamExecutionEnvironment(make_conf(tmp_path, conf2))
        build(env2, GeneratorSource(failing_source(n_batches)))
        env2.execute("early-crash-job")

        got = {}
        for r in sink.committed:
            kk = (int(r["key"]), int(r["window_start"]))
            assert kk not in got, f"duplicate emission for {kk}"
            got[kk] = int(r["count"])
        assert got == golden_counts(n_batches)

    def test_crashed_attempt_drain_never_pollutes_next_attempt(self, tmp_path):
        """A crashing run must take its emit-drain thread down WITH it.
        The drain holds fired-but-undelivered windows; left running (it
        is a daemon), it would deliver them into the sink instance the
        NEXT attempt reuses — duplicates after recovery. A large
        emit-defer forces fires to still be queued at crash time, making
        the race deterministic (ref: StreamTask.cleanUpInternal cancels
        the output flusher before failover)."""
        n_batches = 6
        sink = TransactionalCollectSink()
        conf = {
            "execution.checkpointing.interval": 10_000_000,
            "pipeline.emit-defer": "500ms",  # fires sit queued at crash
        }

        def build(env, source):
            return (env.from_source(
                        source,
                        WatermarkStrategy.for_bounded_out_of_orderness(1000))
                    .key_by("k")
                    .window(TumblingEventTimeWindows.of(1000))
                    .count()
                    .add_sink(sink))

        env = StreamExecutionEnvironment(make_conf(tmp_path, conf))
        build(env, GeneratorSource(failing_source(n_batches, fail_after=4)))
        with pytest.raises(RuntimeError, match="injected failure"):
            env.execute("drain-leak-job")

        conf2 = dict(conf, **{"execution.checkpointing.restore": "latest",
                              "pipeline.emit-defer": "0ms"})
        env2 = StreamExecutionEnvironment(make_conf(tmp_path, conf2))
        build(env2, GeneratorSource(failing_source(n_batches)))
        env2.execute("drain-leak-job")

        # outlive attempt 1's deferral window: a leaked drain thread
        # would deliver its held fires into the reused sink about now
        import time as _time
        _time.sleep(0.8)
        assert sink._pending == [], (
            "crashed attempt's drain thread delivered into the reused sink")
        got = {}
        for r in sink.committed:
            kk = (int(r["key"]), int(r["window_start"]))
            assert kk not in got, f"duplicate emission for {kk}"
            got[kk] = int(r["count"])
        assert got == golden_counts(n_batches)

Q5_CFG = dict(batch_size=4096, n_batches=6, events_per_ms=100,
              num_active_auctions=500, hot_ratio=4)


class TestHostFedQ5Checkpoint:
    """Host-fed Q5 (``bid_stream``) under checkpointing: recovery from
    a checkpoint continues exactly once, source positions count batches
    (never more than the source has), and a checkpoint whose
    ``sub_factors`` field records a factor other than 1 (written by the
    removed device-chained source, whose positions counted slices of a
    batch) is refused by name. The field is input from outside the
    program: an old checkpoint may carry it, empty or holding 1s."""

    N_BATCHES = Q5_CFG["n_batches"]

    def _build(self, sink):
        def build_env(conf):
            env = StreamExecutionEnvironment(conf)
            q5_hot_items(env, bid_stream(NexmarkConfig(**Q5_CFG)),
                         sink, window_ms=2000, slide_ms=500,
                         out_of_orderness_ms=100)
            return env
        return build_env

    @staticmethod
    def _view(sink):
        return [tuple(sorted(r.items())) for r in sink.committed]

    def _conf(self, tmp_path, name, extra=None):
        c = {
            "state.num-key-shards": 16, "state.slots-per-shard": 64,
            "pipeline.microbatch-size": Q5_CFG["batch_size"],
            "execution.checkpointing.dir": str(tmp_path / name),
            "execution.checkpointing.interval": 1,
            "restart-strategy.type": "fixed-delay",
            "restart-strategy.fixed-delay.attempts": 20,
            "restart-strategy.fixed-delay.delay": 1,
        }
        c.update(extra or {})
        return Configuration(c)

    @pytest.mark.parametrize("factors", [None, 1], ids=["empty", "ones"])
    def test_restore_continues_identically(self, tmp_path, factors,
                                           monkeypatch):
        """The checkpoints are in the format PR 27's tree wrote on a
        host-fed job: a ``sub_factors`` field, empty (what it wrote) or
        holding an explicit 1 per source."""
        from flink_tpu.runtime.driver import Driver

        snapshot = Driver._snapshot

        def snapshot_as_the_parent_wrote_it(driver, *args, **kwargs):
            payload = snapshot(driver, *args, **kwargs)
            assert "sub_factors" not in payload
            payload["sub_factors"] = (
                {} if factors is None
                else {sid: factors for sid in payload["sources"]})
            return payload

        monkeypatch.setattr(Driver, "_snapshot",
                            snapshot_as_the_parent_wrote_it)

        golden_sink = TransactionalCollectSink()
        self._build(golden_sink)(
            self._conf(tmp_path, "golden-ckpt")).execute("q5-golden")
        golden = self._view(golden_sink)
        assert golden

        # the SECOND checkpoint write fails, however many the run gets
        # round to: a checkpoint begins only once the one before it is
        # durable and the run ends with one, so there are always two,
        # and the recovery restores the first
        sink = TransactionalCollectSink()
        plan = (faults.FaultPlan(seed=77)
                .rule("checkpoint.storage.write", "raise", count=1,
                      after=1))
        with plan.activate(), replayable(plan):
            run_with_recovery(
                self._build(sink), self._conf(tmp_path, "chaos-ckpt"),
                job_name="q5-chaos")
        assert self._view(sink) == golden
        assert len(plan.log) == 1, "the checkpoint fault never fired"

        # positions count batches (never more than the source has), and
        # a completed checkpoint cut the stream before its end: the
        # recovery resumed mid-stream
        seen, mid = 0, 0
        for root, job in (("golden-ckpt", "q5-golden"),
                          ("chaos-ckpt", "q5-chaos")):
            storage = FsCheckpointStorage(
                str(tmp_path / root), job_id=job)
            for h in storage.list_complete():
                seen += 1
                payload = FsCheckpointStorage.load(h)
                assert len(payload["sub_factors"]) == (
                    0 if factors is None else len(payload["sources"]))
                for pos in payload["sources"].values():
                    assert all(0 <= int(p) <= self.N_BATCHES
                               for p in pos.values()), pos
                    mid += sum(1 for p in pos.values()
                               if 0 < int(p) < self.N_BATCHES)
        assert seen > 0, "no completed checkpoints"
        assert mid > 0, "no checkpoint cut the stream mid-way"

    def test_restore_refuses_sub_batch_positions(self, tmp_path):
        """A checkpoint that records a factor other than 1 for a source
        holds positions counted in slices of a batch: restoring it
        names the field instead of reading them as batch positions."""
        conf = self._conf(tmp_path, "ckpt")
        self._build(TransactionalCollectSink())(conf).execute("q5-old")
        storage = FsCheckpointStorage(str(tmp_path / "ckpt"),
                                      job_id="q5-old")
        latest = storage.latest()
        payload = FsCheckpointStorage.load(latest)
        for added_by_load in ("op_file_versions", "op_file_compression",
                              "op_files", "op_aux_paths"):
            payload.pop(added_by_load, None)
        payload["sub_factors"] = {sid: 4 for sid in payload["sources"]}
        old = storage.save(latest.checkpoint_id + 1, payload,
                           savepoint=True)

        env = self._build(TransactionalCollectSink())(self._conf(
            tmp_path, "ckpt2",
            extra={"execution.checkpointing.restore": old.path}))
        with pytest.raises(ValueError, match="'sub_factors'"):
            env.execute("q5-restore-old")
