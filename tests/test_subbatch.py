"""Sub-batch fire/emit decoupling (``pipeline.sub-batches``, ISSUE 6).

The contract under test, exactly as shipped:

- K = 1 takes none of it (every sub-batch branch of the driver guards
  on K > 1), so the whole existing suite is its regression gate.
- Pipelines (wordcount, sessions) commit the **identical
  row set with per-key order preserved**; the global interleave across
  keys follows the fire cadence (a K=1 advance packs many window ends
  into one fire batch; K=4 fires the same ends in ascending groups).
  Runs with late-beyond-watermark records may additionally emit
  corrective late REFIRES earlier than K=1 would — the allowed-
  lateness semantics of a finer watermark cadence, not a defect — so
  the parity goldens here are refire-free by construction.
- Source positions count LOGICAL batches at every K: a host-fed Q5
  job recovers from a checkpoint exactly once at K = 1 and K = 4, and
  a checkpoint that records a sub-batch factor other than 1 (written
  by the removed device-chained source, whose positions counted
  sub-batches) is refused by name.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from flink_tpu import faults
from flink_tpu.api.environment import StreamExecutionEnvironment
from flink_tpu.api.sinks import FnSink, TransactionalCollectSink
from flink_tpu.api.sources import GeneratorSource
from flink_tpu.api.windowing import (
    EventTimeSessionWindows,
    TumblingEventTimeWindows,
)
from flink_tpu.config import Configuration
from flink_tpu.nexmark.generator import NexmarkConfig, bid_stream
from flink_tpu.nexmark.queries import q5_hot_items
from flink_tpu.runtime.supervisor import run_with_recovery
from flink_tpu.time.watermarks import WatermarkStrategy

from test_chaos import replayable

pytestmark = pytest.mark.subbatch

Q5_CFG = dict(batch_size=4096, n_batches=6, events_per_ms=100,
              num_active_auctions=500, hot_ratio=4)


def _capture_sink():
    rows = []

    def cap(b):
        if len(b.get("window_end", ())):
            rows.append({k: np.asarray(v).copy() for k, v in b.items()})

    def cat():
        return {k: np.concatenate([r[k] for r in rows]) for k in rows[0]}

    return cat, FnSink(cap)


def _sorted_view(rows):
    keys = sorted(rows)
    return sorted(zip(*(rows[k].tolist() for k in keys)))


def _per_key_seq(rows):
    out = {}
    fields = [f for f in sorted(rows) if f != "key"]
    for i, k in enumerate(rows["key"].tolist()):
        out.setdefault(k, []).append(
            tuple(rows[f][i].item() for f in fields))
    return out


class TestHostPlaneParity:
    """Host-fed pipelines: identical committed row SET, per-key order
    preserved, at every K (goldens are refire-free: the watermark's
    out-of-orderness bound covers the generator's disorder)."""

    @staticmethod
    def _wc_gen(split, i):
        if i >= 6:
            return None
        rng = np.random.default_rng(i)
        w = (rng.random(512) ** 2 * 50).astype(np.int64)
        ts = (i * 512 + np.arange(512, dtype=np.int64)) * 4
        return {"word": w}, ts

    def _run_wordcount(self, k):
        cat, sink = _capture_sink()
        env = StreamExecutionEnvironment(Configuration({
            "state.num-key-shards": 8, "state.slots-per-shard": 64,
            "pipeline.microbatch-size": 512,
            "pipeline.sub-batches": k}))
        (env.from_source(
            GeneratorSource(self._wc_gen),
            WatermarkStrategy.for_bounded_out_of_orderness(0))
            .key_by("word")
            .window(TumblingEventTimeWindows.of(500))
            .count().add_sink(sink))
        env.execute(f"wc-sub{k}")
        return cat()

    @staticmethod
    def _sess_gen(split, i):
        if i >= 6:
            return None
        rng = np.random.default_rng(500 + i)
        u = rng.integers(0, 30, 256).astype(np.int64)
        ts = (i * 400 + rng.integers(0, 600, 256)).astype(np.int64)
        return {"u": u}, ts

    def _run_sessions(self, k):
        cat, sink = _capture_sink()
        env = StreamExecutionEnvironment(Configuration({
            "state.num-key-shards": 8, "state.slots-per-shard": 64,
            "pipeline.microbatch-size": 256,
            "pipeline.sub-batches": k}))
        (env.from_source(
            GeneratorSource(self._sess_gen),
            # 600 covers the generator's intra-batch disorder exactly:
            # no record is ever late, so the fire SET is cadence-free
            WatermarkStrategy.for_bounded_out_of_orderness(600))
            .key_by("u")
            .window(EventTimeSessionWindows.with_gap(150))
            .allowed_lateness(1000)
            .count().add_sink(sink))
        env.execute(f"sess-sub{k}")
        return cat()

    @pytest.mark.parametrize("runner", ["wordcount", "sessions"])
    def test_rows_and_per_key_order_identical(self, runner):
        run = (self._run_wordcount if runner == "wordcount"
               else self._run_sessions)
        golden = run(1)
        assert len(golden["window_end"]) > 0
        for k in (2, 4):
            got = run(k)
            assert _sorted_view(got) == _sorted_view(golden), (runner, k)
            assert _per_key_seq(got) == _per_key_seq(golden), (runner, k)


class TestHostFedQ5Checkpoint:
    """Host-fed Q5 (``bid_stream``) under checkpointing: recovery from
    a checkpoint continues exactly once at K = 1 and K = 4, positions
    count logical batches, and a checkpoint whose positions counted
    sub-batches is refused."""

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

    def _conf(self, tmp_path, name, k, extra=None):
        c = {
            "state.num-key-shards": 16, "state.slots-per-shard": 64,
            "pipeline.microbatch-size": Q5_CFG["batch_size"],
            "pipeline.sub-batches": k,
            "execution.checkpointing.dir": str(tmp_path / name),
            "execution.checkpointing.interval": 1,
            "restart-strategy.type": "fixed-delay",
            "restart-strategy.fixed-delay.attempts": 20,
            "restart-strategy.fixed-delay.delay": 1,
        }
        c.update(extra or {})
        return Configuration(c)

    @pytest.mark.parametrize("k, factors", [(1, None), (4, 1)],
                             ids=["k1-empty", "k4-ones"])
    def test_restore_continues_identically(self, tmp_path, k, factors,
                                           monkeypatch):
        """The checkpoints are in the format PR 27's tree wrote on a
        host-fed job: a ``sub_factors`` field, empty (what it wrote) or
        holding an explicit 1 per source."""
        from flink_tpu.checkpoint.storage import FsCheckpointStorage
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
            self._conf(tmp_path, "golden-ckpt", k)).execute("q5-golden")
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
                self._build(sink), self._conf(tmp_path, "chaos-ckpt", k),
                job_name="q5-chaos")
        assert self._view(sink) == golden
        assert len(plan.log) == 1, "the checkpoint fault never fired"

        # positions count LOGICAL batches at every K (never more than
        # the source has), and a completed checkpoint cut the stream
        # before its end: the recovery resumed mid-stream
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
                               for p in pos.values()), (k, pos)
                    mid += sum(1 for p in pos.values()
                               if 0 < int(p) < self.N_BATCHES)
        assert seen > 0, "no completed checkpoints"
        assert mid > 0, "no checkpoint cut the stream mid-way"

    def test_restore_refuses_sub_batch_positions(self, tmp_path):
        """A checkpoint that records a sub-batch factor other than 1
        for a source holds positions counted in sub-batches: restoring
        it names the field instead of reading them as logical."""
        from flink_tpu.checkpoint.storage import FsCheckpointStorage

        conf = self._conf(tmp_path, "ckpt", 4)
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
            tmp_path, "ckpt2", 4,
            extra={"execution.checkpointing.restore": old.path}))
        with pytest.raises(ValueError, match="'sub_factors'"):
            env.execute("q5-restore-old")


class TestSubbatchChaosK4:
    """The K=4 chaos gate: the sessions pipeline recovers exactly-once
    with ``host.pool.task`` + checkpoint-storage faults armed while
    sub-batching is on (golden = fault-free at the SAME K: replay from
    sub-batch positions reproduces the same advance cadence, so even
    late-refire rows are deterministic under recovery)."""

    pytestmark = [pytest.mark.subbatch, pytest.mark.chaos]

    SUB_CONF = {"pipeline.sub-batches": 4, "host.parallelism": 4}

    def test_sessions_chaos_exactly_once_at_k4(self, tmp_path):
        from test_chaos import TestHostPoolChaos

        t = TestHostPoolChaos()
        golden = t._golden(t._sessions_builder, t._session_view,
                           tmp_path, extra={"pipeline.sub-batches": 4})
        plan = (faults.FaultPlan(seed=4321)
                .rule("host.pool.task", "raise", count=1, after=6)
                .rule("checkpoint.storage.write", "raise", count=1,
                      after=1))
        got, recoveries, fault_spans = t._chaos(
            t._sessions_builder, t._session_view, tmp_path, plan,
            extra=self.SUB_CONF)
        with replayable(plan):
            assert got == golden
            assert len(fault_spans) == len(plan.log) == 2
            assert 1 <= len(recoveries) <= 2


class TestValidation:
    def test_driver_rejects_below_one(self):
        env = StreamExecutionEnvironment(Configuration({
            "pipeline.sub-batches": 0}))
        (env.from_source(GeneratorSource(TestHostPlaneParity._wc_gen),
                         WatermarkStrategy.for_monotonous_timestamps())
            .key_by("word").window(TumblingEventTimeWindows.of(500))
            .count().collect())
        with pytest.raises(ValueError, match="sub-batches"):
            env.execute("bad-sub")

    def test_driver_rejects_indivisible_microbatch(self):
        env = StreamExecutionEnvironment(Configuration({
            "pipeline.microbatch-size": 512,
            "pipeline.sub-batches": 3,
            "analysis.fail-on": "off"}))  # reach the driver's own guard
        (env.from_source(GeneratorSource(TestHostPlaneParity._wc_gen),
                         WatermarkStrategy.for_monotonous_timestamps())
            .key_by("word").window(TumblingEventTimeWindows.of(500))
            .count().collect())
        with pytest.raises(ValueError, match="must divide"):
            env.execute("bad-sub-div")

    def test_analyzer_emit_defer_floor(self):
        from flink_tpu.analysis import analyze_config

        findings = analyze_config(Configuration({
            "pipeline.microbatch-size": 4096,
            "pipeline.sub-batches": 4,
            "pipeline.emit-defer": 200}))
        assert any(f.rule == "SUBBATCH_INVALID"
                   and "emit-defer" in f.message for f in findings)
        # K=1 with the same deferral is fine (no sub-batch cadence to
        # defeat), as is K=4 with the deferral on auto
        assert not analyze_config(Configuration({
            "pipeline.microbatch-size": 4096,
            "pipeline.emit-defer": 200}))
        assert not analyze_config(Configuration({
            "pipeline.microbatch-size": 4096,
            "pipeline.sub-batches": 4}))


class TestCliSmoke:
    def test_wordcount_sub_batches_via_cli(self, tmp_path):
        """Tier-1 smoke (ISSUE 6 satellite): bounded WordCount runs
        end-to-end with ``pipeline.sub-batches=4`` through ``python -m
        flink_tpu run --local`` and commits the same totals the K=1
        golden computes."""
        import runner_job_wordcount as job
        from flink_tpu.formats_columnar import ColumnarFormat

        sink_dir = str(tmp_path / "sink")
        n_batches = 6
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   PYTHONPATH=os.pathsep.join(
                       [os.path.dirname(__file__),
                        os.path.join(os.path.dirname(__file__), ".."),
                        os.environ.get("PYTHONPATH", "")]))
        proc = subprocess.run(
            [sys.executable, "-m", "flink_tpu", "run", "--local",
             "--entry", "runner_job_wordcount:build",
             "--job-id", "cli-sub-wc",
             "--conf", f"test.n-batches={n_batches}",
             "--conf", f"test.sink-dir={sink_dir}",
             "--conf", "pipeline.sub-batches=4",
             "--conf", "state.num-key-shards=4",
             "--conf", "state.slots-per-shard=32"],
            capture_output=True, text=True, timeout=240, env=env,
            cwd=os.path.dirname(__file__))
        assert proc.returncode == 0, proc.stderr[-2000:]
        out = json.loads(proc.stdout.strip().splitlines()[-1])
        assert out["state"] == "FINISHED"
        assert out["records_in"] == n_batches * job.BATCH

        fmt = ColumnarFormat(job.OUT_SCHEMA)
        total = 0
        committed = os.path.join(sink_dir, "committed")
        for name in sorted(os.listdir(committed)):
            with open(os.path.join(committed, name), "rb") as f:
                cols = fmt.deserialize(f.read())
            total += int(np.sum(cols["count"]))
        assert total == job.golden_total(n_batches)
