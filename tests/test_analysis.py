"""Plan-analyzer suite (flink_tpu/analysis/): one seeded-violation
pipeline per registered rule asserting the exact rule id + node fires,
clean-pipeline negatives, the driver's submit-time ``analysis.fail-on``
thresholds, the `flink_tpu analyze` CLI surface, and the DOGFOOD GATE —
the shipped tree and the golden pipelines must report zero findings,
so registry/config drift can never land silently (tier-1)."""
import json
import subprocess
import sys

import numpy as np
import pytest

from flink_tpu.analysis import AnalysisError, analyze_config
from flink_tpu.analysis.core import blocking, rule_catalog
from flink_tpu.api.datastream import DataStream
from flink_tpu.api.environment import StreamExecutionEnvironment
from flink_tpu.api.sources import GeneratorSource
from flink_tpu.api.windowing import GlobalWindows, TumblingEventTimeWindows
from flink_tpu.config import Configuration
from flink_tpu.graph.transformations import WindowAggregateTransformation
from flink_tpu.ops.aggregates import count
from flink_tpu.time.watermarks import WatermarkStrategy

pytestmark = pytest.mark.analysis

WM = WatermarkStrategy.for_monotonous_timestamps


def gen(split, i):
    if i >= 2:
        return None
    return ({"word": np.arange(8, dtype=np.int64)},
            (np.arange(8, dtype=np.int64) + i * 8) * 100)


def make_env(extra=None):
    conf = {"state.num-key-shards": 8, "state.slots-per-shard": 64,
            "pipeline.microbatch-size": 256}
    conf.update(extra or {})
    return StreamExecutionEnvironment(Configuration(conf))


def clean_pipeline(extra=None):
    """The golden shape: watermarked bounded source, keyBy, bounded
    window, collect — nothing for any rule to say."""
    env = make_env(extra)
    (env.from_source(GeneratorSource(gen), WM())
        .key_by("word")
        .window(TumblingEventTimeWindows.of(1000))
        .count()
        .collect())
    return env


# -- seeded violations: one builder per rule --------------------------------
# The coverage test parametrizes over rule_catalog(), so a rule added
# to the engine without a seeded-violation case here FAILS the suite.

SEEDS = {}


def seed(rule_id, node_name=None):
    def deco(fn):
        SEEDS[rule_id] = (fn, node_name)
        return fn
    return deco


@seed("EVENT_TIME_NO_WATERMARK", node_name="window_agg")
def _no_watermark(tmp_path):
    env = make_env()
    (env.from_source(GeneratorSource(gen))  # no WatermarkStrategy
        .key_by("word")
        .window(TumblingEventTimeWindows.of(1000))
        .count()
        .collect())
    return env.analyze()


@seed("NON_TRANSACTIONAL_SINK", node_name="collect")
def _write_through_sink(tmp_path):
    env = clean_pipeline({"execution.checkpointing.interval": 500})
    return env.analyze()


@seed("UNBOUNDED_SOURCE_IN_BATCH", node_name="source")
def _unbounded_batch(tmp_path):
    # strict compilation rejects this plan outright — the analyzer's
    # non-strict lowering must still surface it as a structured finding
    env = make_env({"execution.runtime-mode": "batch"})
    (env.from_source(GeneratorSource(gen, is_bounded=False), WM())
        .key_by("word")
        .window(TumblingEventTimeWindows.of(1000))
        .count()
        .collect())
    return env.analyze()


@seed("KEYED_OP_WITHOUT_KEYBY", node_name="rogue_window")
def _keyed_without_keyby(tmp_path):
    # the fluent API always inserts the keyBy exchange; build the
    # malformed graph the way a buggy planner/raw-transformation user
    # would — window fed directly by the source
    env = make_env()
    ds = env.from_source(GeneratorSource(gen), WM())
    t = WindowAggregateTransformation(
        "rogue_window", (ds.transform,),
        assigner=TumblingEventTimeWindows.of(1000), aggregate=count(),
        key_field="word")
    env._register(t)
    DataStream(env, t).collect()
    return env.analyze()


@seed("WINDOW_WITHOUT_FIRE_BOUND", node_name="window_agg")
def _global_window_no_trigger(tmp_path):
    env = make_env()
    (env.from_source(GeneratorSource(gen), WM())
        .key_by("word")
        .window(GlobalWindows.create())  # no .trigger(...)
        .count()
        .collect())
    return env.analyze()


@seed("LOG_TOPIC_MULTI_WRITER")
def _two_writers_one_topic(tmp_path):
    from flink_tpu.log.connectors import LogSink

    topic = str(tmp_path / "topic")
    env = make_env()
    ds = env.from_source(GeneratorSource(gen), WM())
    ds.add_sink(LogSink(topic), name="writer_a")
    ds.add_sink(LogSink(topic), name="writer_b")
    return env.analyze()


@seed("LOG_RETENTION_UNSAFE")
def _retention_below_checkpoint_interval(tmp_path):
    return analyze_config(Configuration({
        "execution.checkpointing.interval": 5000,
        "log.retention.ms": 100}))


@seed("CLEANER_DISABLED_WITH_RETENTION")
def _retention_with_no_executor(tmp_path):
    # a producing topic with a retention POLICY but no EXECUTOR: the
    # background cleaner is off and nothing else in the runtime
    # applies log.retention.* — the topic grows without bound while
    # its owner believes retention is active. Clean negatives in
    # TestCleanerDisabledWithRetention below.
    from flink_tpu.log.connectors import LogSink

    topic = str(tmp_path / "topic")
    env = make_env({"log.retention.ms": 60_000})
    ds = env.from_source(GeneratorSource(gen), WM())
    ds.add_sink(LogSink(topic), name="writer")
    return env.analyze()


@seed("LOG_PREFETCH_INVALID")
def _log_prefetch_invalid(tmp_path):
    return analyze_config(Configuration({
        "log.prefetch-segments": -1}))


@seed("FAULT_POINT_UNKNOWN")
def _fault_point_unknown(tmp_path):
    env = clean_pipeline({"faults.inject": "bogus.point=raise @1.0"})
    return env.analyze()


@seed("CONFIG_KEY_UNKNOWN")
def _config_key_typo(tmp_path):
    env = clean_pipeline({"execution.checkpointng.interval": 500})
    return env.analyze()


@seed("SESSION_QUOTA_INVALID")
def _session_quota_invalid(tmp_path):
    # a per-job slot quota above one runner's capacity: no fleet of any
    # size could place the job — the dispatcher rejects the submission
    # and the analyzer flags the conf before it is ever submitted
    env = clean_pipeline({"session.slots-per-job": 3,
                          "session.runner-slots": 2})
    return env.analyze()


@seed("SESSION_HA_UNSAFE")
def _session_checkpointing_without_ha(tmp_path):
    # a session cluster running checkpointing jobs with no
    # high-availability.dir: one dispatcher SIGKILL strands every
    # tenant even though their checkpoints would survive it. Clean
    # negatives: no session intent (plain checkpointing config) and a
    # session conf WITH an HA dir — both below.
    return analyze_config(Configuration({
        "session.max-jobs": 4,
        "execution.checkpointing.interval": 500}))


@seed("STORAGE_LOCAL_LOCKS_ON_REMOTE")
def _local_locks_on_remote_scheme(tmp_path):
    # lease dirs / HA dir / log topics on a non-file scheme: the
    # O_EXCL + rename-first lock discipline is local-fs-only (PR 9/11
    # honest residue) — acquisition degrades to read-check-write.
    # Clean negatives in TestStorageLocalLocksOnRemote below.
    return analyze_config(Configuration({
        "high-availability.dir": "s3://bucket/ha",
        "log.dir": "hdfs://nn/flink-log"}))


@seed("HOST_PARALLELISM_INVALID")
def _host_parallelism_invalid(tmp_path):
    # below 1: the driver rejects it at build; the analyzer must flag
    # it at submit (oversubscription past os.cpu_count() warns too,
    # but is machine-dependent — the < 1 case seeds deterministically)
    env = clean_pipeline({"host.parallelism": 0})
    return env.analyze()


@seed("DCN_OVERLAP_UNSAFE")
def _dcn_overlap_without_drain(tmp_path):
    # the loss-tolerant perf trade made silently: overlapped cross-host
    # exchange + checkpointing with the barrier drain off — a restore
    # would skip the one in-flight step's records. Clean negatives in
    # TestDcnOverlapUnsafeNegatives below.
    return analyze_config(Configuration({
        "cluster.num-processes": 2,
        "execution.checkpointing.interval": 500,
        "cluster.dcn-overlap-drain": False}))


@seed("CHECKPOINT_IN_BATCH")
def _checkpoint_in_batch(tmp_path):
    # config-only rule: no pipeline needed
    return analyze_config(Configuration({
        "execution.runtime-mode": "batch",
        "execution.checkpointing.interval": 500}))


@seed("RESCALE_INVALID")
def _reactive_rescale_without_checkpointing(tmp_path):
    # reactive mode with no checkpoint interval: every controller-armed
    # rescale's stop-with-savepoint would be rejected — arm/disarm loop
    return analyze_config(Configuration({"rescale.mode": "reactive"}))


@seed("RESCALE_COOLDOWN_THRASH")
def _rescale_cooldown_below_checkpoint_interval(tmp_path):
    return analyze_config(Configuration({
        "rescale.mode": "reactive",
        "execution.checkpointing.interval": "30s",
        "rescale.cooldown": "5s"}))


@seed("STATE_BUDGET_INVALID")
def _lsm_budget_below_run_floor(tmp_path):
    # budget below the run floor: every absorb seals a degenerate run
    return analyze_config(Configuration({
        "state.backend": "lsm",
        "state.memory-budget-bytes": 4096}))


@seed("STATE_BUDGET_IGNORED")
def _budget_set_on_resident_backend(tmp_path):
    # hbm/spill ignore the budget key — the bound does not exist
    return analyze_config(Configuration({
        "state.backend": "spill",
        "state.memory-budget-bytes": 1 << 20}))


# -- dataflow-plane seeds (the propagated lattices; full coverage and
# clean negatives live in tests/test_dataflow.py) ---------------------------

@seed("FIELD_NOT_IN_SCHEMA", node_name="window_agg")
def _keyby_on_dropped_field(tmp_path):
    # schema lattice: the map renames the key column away; the keyBy's
    # field reference is checked against the PROPAGATED schema
    env = make_env()
    (env.from_source(GeneratorSource(gen, schema={"word": "int64"}), WM())
        .map(lambda d: {"renamed": d["word"]}, name="drop_word")
        .key_by("word")
        .window(TumblingEventTimeWindows.of(1000))
        .count()
        .collect())
    return env.analyze()


@seed("SCHEMA_MISMATCH_UNION", node_name="union")
def _union_of_different_schemas(tmp_path):
    env = make_env()
    a = env.from_collection({"k": np.array([1], np.int64)},
                            np.array([100], np.int64))
    b = env.from_collection({"other": np.array([2], np.int64)},
                            np.array([200], np.int64))
    a.union(b).collect()
    return env.analyze()


@seed("UNBOUNDED_STATE_GROWTH", node_name="window_agg")
def _global_window_nonpurging_trigger(tmp_path):
    # state lattice: GlobalWindows element buffer + non-purging
    # CountTrigger + no evictor, fed by an UNBOUNDED source
    from flink_tpu.api.windowing import CountTrigger

    env = make_env()
    (env.from_source(GeneratorSource(gen, is_bounded=False), WM())
        .key_by("word")
        .window(GlobalWindows.create())
        .trigger(CountTrigger.of(3))
        .count()
        .collect())
    return env.analyze()


@seed("STALLED_WATERMARK_LEG", node_name="window_agg")
def _event_time_window_fed_by_count_window(tmp_path):
    # watermark lattice: count-window fires carry no event time; the
    # downstream event-time window's panes can never be crossed
    env = make_env()
    (env.from_source(GeneratorSource(gen, schema={"word": "int64"}), WM())
        .key_by("word")
        .count_window(3)
        .count()
        .key_by("key")
        .window(TumblingEventTimeWindows.of(1000))
        .count()
        .collect())
    return env.analyze()


@seed("NON_TXN_SINK_IN_CHAIN", node_name="collect")
def _log_chain_into_write_through_sink(tmp_path):
    # exactly-once taint through log topics: LogSource → CollectSink
    # under checkpointing escalates the generic sink warning to error
    from flink_tpu.log.connectors import LogSource

    env = make_env({"execution.checkpointing.interval": 500})
    (env.from_source(LogSource(str(tmp_path / "topic")), WM())
        .collect())
    return env.analyze()


@seed("STATE_BYTES_EXCEEDED", node_name="window_agg")
def _state_bytes_over_budget(tmp_path):
    # the --explain estimate as an admission check: a tiny per-key
    # budget trips on the clean pipeline's window geometry
    env = clean_pipeline({"analysis.max-state-bytes-per-key": 4})
    return env.analyze()


@seed("CHANGELOG_SINK_MISMATCH", node_name="collect")
def _changelog_into_write_through_sink(tmp_path):
    # op-typed retract rows (-U/+U) into a blind-append sink: every
    # retraction materializes as a duplicate record instead of a
    # deletion — the changelog contract needs an op-aware sink
    env = make_env()
    (env.from_source(GeneratorSource(gen), WM())
        .key_by("word")
        .running_aggregate(count(), retract=True)
        .collect())
    return env.analyze()


class TestChangelogSinkMismatchNegatives:
    """CHANGELOG_SINK_MISMATCH fires ONLY on op-typed rows meeting a
    changelog-blind sink: each changelog-capable sink, and the
    insert-only (non-retract) aggregate, keep it quiet (seeded
    violation in SEEDS above)."""

    def _hits(self, sink=None, retract=True):
        env = make_env()
        stream = (env.from_source(GeneratorSource(gen), WM())
                  .key_by("word")
                  .running_aggregate(count(), retract=retract))
        if sink is None:
            stream.collect()
        else:
            stream.add_sink(sink)
        return [f for f in env.analyze()
                if f.rule == "CHANGELOG_SINK_MISMATCH"]

    def test_retract_sink_is_clean(self):
        from flink_tpu.api.sinks import RetractSink

        assert self._hits(RetractSink(key_fields=("key",))) == []

    def test_upsert_sink_is_clean(self):
        from flink_tpu.api.sinks import UpsertSink

        assert self._hits(UpsertSink(key_fields=("key",))) == []

    def test_insert_only_aggregate_into_collect_is_clean(self):
        # upsert-shaped rows without the op lane: CollectSink sees
        # plain rows, nothing to mismatch
        assert self._hits(sink=None, retract=False) == []


class TestSessionHaUnsafeNegatives:
    """SESSION_HA_UNSAFE fires ONLY on the stranding shape: session
    intent + checkpointing + no HA dir. Each leg missing keeps it
    quiet (seeded violation in SEEDS above)."""

    def _hits(self, conf):
        return [f for f in analyze_config(Configuration(conf))
                if f.rule == "SESSION_HA_UNSAFE"]

    def test_checkpointing_without_session_intent_is_clean(self):
        assert self._hits(
            {"execution.checkpointing.interval": 500}) == []

    def test_session_without_checkpointing_is_clean(self):
        # nothing durable to strand: re-submission IS recovery
        assert self._hits({"session.max-jobs": 4}) == []

    def test_session_with_ha_dir_is_clean(self, tmp_path):
        assert self._hits({
            "session.max-jobs": 4,
            "execution.checkpointing.interval": 500,
            "high-availability.dir": str(tmp_path)}) == []


class TestDcnOverlapUnsafeNegatives:
    """DCN_OVERLAP_UNSAFE fires ONLY on the losing shape: cross-host +
    checkpointing + overlap on + drain off. Each leg missing keeps it
    quiet (seeded violation in SEEDS above)."""

    def _hits(self, conf):
        return [f for f in analyze_config(Configuration(conf))
                if f.rule == "DCN_OVERLAP_UNSAFE"]

    def test_default_drain_is_clean(self):
        assert self._hits({
            "cluster.num-processes": 2,
            "execution.checkpointing.interval": 500}) == []

    def test_single_process_is_clean(self):
        assert self._hits({
            "execution.checkpointing.interval": 500,
            "cluster.dcn-overlap-drain": False}) == []

    def test_no_checkpointing_is_clean(self):
        assert self._hits({
            "cluster.num-processes": 2,
            "cluster.dcn-overlap-drain": False}) == []

    def test_lockstep_loop_is_clean(self):
        assert self._hits({
            "cluster.num-processes": 2,
            "execution.checkpointing.interval": 500,
            "cluster.dcn-overlap": False,
            "cluster.dcn-overlap-drain": False}) == []


class TestRuleCatalog:
    def test_catalog_has_at_least_eight_rules(self):
        assert len(rule_catalog()) >= 8

    def test_dataflow_plane_has_at_least_six_rules(self):
        from flink_tpu.analysis.core import rule_catalog_full

        planes = [r.plane for r in rule_catalog_full()]
        assert planes.count("dataflow") >= 6
        for r in rule_catalog_full():
            assert r.description, f"{r.rule_id} has no description"
            assert r.fix, f"{r.rule_id} has no catalog fix hint"

    def test_finding_sort_puts_config_findings_after_node_zero(self):
        # regression: the old key `f.node or 0` conflated node 0 with
        # config-level findings (node=None) — None must sort LAST
        from flink_tpu.analysis.core import Finding, finding_sort_key

        at_node0 = Finding(rule="R", severity="warn", message="n0",
                           node=0)
        at_config = Finding(rule="R", severity="warn", message="conf")
        ordered = sorted([at_config, at_node0], key=finding_sort_key)
        assert ordered == [at_node0, at_config]

    @pytest.mark.parametrize("rule_id,severity",
                             rule_catalog(),
                             ids=[r for r, _ in rule_catalog()])
    def test_every_rule_fires_on_its_seeded_violation(
            self, rule_id, severity, tmp_path):
        assert rule_id in SEEDS, (
            f"rule {rule_id} has no seeded-violation case — every rule "
            "in the catalog must prove it fires")
        builder, node_name = SEEDS[rule_id]
        findings = builder(tmp_path)
        hits = [f for f in findings if f.rule == rule_id]
        assert hits, (f"{rule_id} did not fire; findings: "
                      f"{[f.rule for f in findings]}")
        for f in hits:
            assert f.severity == severity
            assert f.fix, f"{rule_id} finding has no fix hint"
        if node_name is not None:
            assert any(f.node_name == node_name for f in hits), (
                f"{rule_id} did not locate node {node_name!r}: "
                f"{[(f.node, f.node_name) for f in hits]}")

    def test_clean_pipeline_zero_findings(self):
        assert clean_pipeline().analyze() == []

    def test_clean_batch_pipeline_zero_findings(self):
        assert clean_pipeline(
            {"execution.runtime-mode": "batch"}).analyze() == []


class TestLeaseAwareMultiWriter:
    """ISSUE 9: LOG_TOPIC_MULTI_WRITER is lease-aware — two LogSinks
    on one topic with DISJOINT leased partitions are legal; the same
    partition without (or with an overlapping) lease still errors."""

    def _two_sinks(self, tmp_path, owned_a, owned_b):
        from flink_tpu.log.connectors import LogSink

        topic = str(tmp_path / "topic")
        env = make_env()
        ds = env.from_source(GeneratorSource(gen), WM())
        ds.add_sink(LogSink(topic, key_field="word", partitions=2,
                            owned_partitions=owned_a,
                            producer_id="prod-a"), name="writer_a")
        ds.add_sink(LogSink(topic, key_field="word", partitions=2,
                            owned_partitions=owned_b,
                            producer_id="prod-b"), name="writer_b")
        return [f for f in env.analyze()
                if f.rule == "LOG_TOPIC_MULTI_WRITER"]

    def test_disjoint_leased_partitions_are_legal(self, tmp_path):
        assert self._two_sinks(tmp_path, [0], [1]) == []

    def test_overlapping_leases_error_at_analyze(self, tmp_path):
        # leases acquire LAZILY (first use), so building the plan does
        # not raise — the analyzer flags the overlap BEFORE the runtime
        # fence would depose one of the writers mid-run
        hits = self._two_sinks(tmp_path, [0, 1], [0])
        assert len(hits) == 2
        assert "overlap" in hits[0].message

    def test_overlap_on_disk_is_flagged(self, tmp_path):
        # build the overlapping plan the way a deposed/raced pair would
        # look: construct the sinks against separate lease state, then
        # overlap their owned sets in one plan
        from flink_tpu.log.connectors import LogSink

        topic = str(tmp_path / "topic")
        env = make_env()
        ds = env.from_source(GeneratorSource(gen), WM())
        a = LogSink(topic, key_field="word", partitions=2,
                    owned_partitions=[0], producer_id="prod-a")
        b = LogSink(topic, key_field="word", partitions=2,
                    owned_partitions=[1], producer_id="prod-b")
        b._appender.owned = [0, 1]  # the raced/overlapped shape
        ds.add_sink(a, name="writer_a")
        ds.add_sink(b, name="writer_b")
        hits = [f for f in env.analyze()
                if f.rule == "LOG_TOPIC_MULTI_WRITER"]
        assert len(hits) == 2
        assert "overlap" in hits[0].message


class TestSubmitTimeAnalysis:
    """The driver runs the same rules at submit; ``analysis.fail-on``
    picks the blocking severity."""

    def test_error_finding_blocks_submit(self):
        env = clean_pipeline({"faults.inject": "bogus.point=raise"})
        with pytest.raises(AnalysisError) as ei:
            env.execute("blocked")
        assert any(f.rule == "FAULT_POINT_UNKNOWN"
                   for f in ei.value.findings)
        assert "analysis.fail-on" in str(ei.value)

    def test_fail_on_off_skips_analysis(self):
        env = clean_pipeline({"faults.inject": "bogus.point=raise",
                              "analysis.fail-on": "off"})
        r = env.execute("unblocked")
        assert r.metrics.get("records_in") == 16

    def test_warn_threshold_blocks_warn_findings(self):
        env = clean_pipeline({"no.such.key": 1,
                              "analysis.fail-on": "warn"})
        with pytest.raises(AnalysisError) as ei:
            env.execute("blocked")
        assert any(f.rule == "CONFIG_KEY_UNKNOWN"
                   for f in ei.value.findings)

    def test_warn_findings_pass_default_threshold_but_stay_visible(self):
        env = clean_pipeline({"no.such.key": 1})
        r = env.execute("warned")
        assert r.metrics.get("records_in") == 16
        assert any(f.rule == "CONFIG_KEY_UNKNOWN"
                   for f in env._driver.analysis_findings)

    def test_bad_fail_on_value_rejected(self):
        with pytest.raises(ValueError, match="fail-on"):
            blocking([], "sometimes")


class TestAnalyzeCli:
    def test_conf_file_violations_exit_1_with_json_findings(
            self, tmp_path, capsys):
        from flink_tpu.cli import main

        conf = tmp_path / "job.conf"
        conf.write_text("faults.inject: bogus.point=raise\n"
                        "execution.checkpointng.interval: 500\n")
        rc = main(["analyze", str(conf), "--json"])
        assert rc == 1
        rules = {json.loads(line)["rule"]
                 for line in capsys.readouterr().out.splitlines()}
        assert rules == {"FAULT_POINT_UNKNOWN", "CONFIG_KEY_UNKNOWN"}

    def test_clean_conf_exits_0(self, tmp_path, capsys):
        from flink_tpu.cli import main

        conf = tmp_path / "job.conf"
        conf.write_text("execution.checkpointing.interval: 500\n")
        assert main(["analyze", str(conf)]) == 0
        assert "no findings" in capsys.readouterr().out

    def test_fail_on_flag_overrides_conf(self, tmp_path, capsys):
        from flink_tpu.cli import main

        conf = tmp_path / "job.conf"
        conf.write_text("some.typo.key: 1\n")
        assert main(["analyze", str(conf)]) == 0  # warn < error
        assert main(["analyze", str(conf), "--fail-on", "warn"]) == 1
        capsys.readouterr()

    def test_golden_wordcount_entry_zero_findings(self, tmp_path, capsys):
        """Dogfood: the shipped golden pipeline (the batch-mode CLI
        smoke entry point) analyzes clean, plan rules included."""
        from flink_tpu.cli import main

        rc = main(["analyze", "--entry", "runner_job_wordcount:build",
                   "--conf", f"test.sink-dir={tmp_path / 'out'}"])
        assert rc == 0
        assert "no findings" in capsys.readouterr().out


class TestDogfoodGate:
    """Zero findings on the shipped tree — registry/config drift can
    never land silently again."""

    def test_repo_lints_zero_findings(self):
        from flink_tpu.analysis.pylints import lint_paths

        findings = lint_paths()
        assert findings == [], "\n".join(f.render() for f in findings)

    def test_lint_cli_smoke(self):
        """`python -m flink_tpu lint` from a cold process — the tier-1
        wrapper's drift gate, exit status included."""
        proc = subprocess.run(
            [sys.executable, "-m", "flink_tpu", "lint"],
            capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "no findings" in proc.stdout

    def test_full_pass_fits_the_wallclock_budget(self):
        """PR 19 perf gate: the WHOLE default lint pass — call-graph
        index plus every interprocedural plane (taint, pool writes,
        lock order, fences, unfired registry) — stays under 3 s of CPU, so
        the dogfood gate remains cheap enough to run on every commit.
        The call-graph architecture this budget bought: one flattened
        ast.walk per module at index time, type-bucketed call/with
        views, and per-module prefilters on the lock-order walk."""
        import time

        from flink_tpu.analysis.pylints import lint_paths

        # the budget is on the pass's own CPU time (it runs on the
        # calling thread), best of three: a wall clock beside other
        # busy processes reads the host's load, not the pass
        took = []
        for _ in range(3):
            t0 = time.thread_time()
            lint_paths()
            took.append(time.thread_time() - t0)
            if took[-1] < 3.0:
                break
        assert min(took) < 3.0, (
            f"full lint pass took {min(took):.2f}s of CPU at best "
            f"(budget 3.0s, attempts {took}) — the interprocedural "
            "planes must stay commit-hook cheap")

    def test_rules_md_is_current(self):
        """RULES.md staleness gate: the committed catalog doc must be
        byte-identical to what the registrations render — a new rule
        (analysis plane OR pylint plane) cannot ship undocumented; run
        `python tools/gen_rules.py` after editing rules."""
        import os

        from flink_tpu.analysis.docs import render_rules_md
        from flink_tpu.analysis.pylints import repo_root

        path = os.path.join(repo_root(), "RULES.md")
        with open(path, "r", encoding="utf-8") as f:
            committed = f.read()
        assert committed == render_rules_md(), (
            "RULES.md is stale — regenerate with "
            "`python tools/gen_rules.py`")


class TestStorageLocalLocksOnRemote:
    """PR-14 satellite: STORAGE_LOCAL_LOCKS_ON_REMOTE clean negatives
    (the seeded violation lives in SEEDS)."""

    def _rules(self, conf):
        return [f.rule for f in analyze_config(Configuration(conf))]

    def test_local_paths_are_quiet(self, tmp_path):
        assert "STORAGE_LOCAL_LOCKS_ON_REMOTE" not in self._rules({
            "high-availability.dir": str(tmp_path / "ha"),
            "log.dir": str(tmp_path / "log")})

    def test_explicit_file_scheme_is_quiet(self, tmp_path):
        assert "STORAGE_LOCAL_LOCKS_ON_REMOTE" not in self._rules({
            "high-availability.dir": f"file://{tmp_path}/ha",
            "log.dir": f"file://{tmp_path}/log"})

    def test_unset_dirs_are_quiet(self):
        assert "STORAGE_LOCAL_LOCKS_ON_REMOTE" not in self._rules({})

    def test_each_key_flags_independently(self, tmp_path):
        findings = [f for f in analyze_config(Configuration({
            "high-availability.dir": "s3://bucket/ha",
            "log.dir": str(tmp_path / "log")}))
            if f.rule == "STORAGE_LOCAL_LOCKS_ON_REMOTE"]
        assert len(findings) == 1
        assert "high-availability.dir" in findings[0].message

    def test_conditional_put_scheme_is_quiet(self):
        """PR-18 driver-awareness: a scheme whose registered driver
        advertises conditional_put (the objstore CAS driver) ports
        every lock-dependent path onto compare-and-swap — the race
        the rule warns about is PREVENTED there, not bounded."""
        assert "STORAGE_LOCAL_LOCKS_ON_REMOTE" not in self._rules({
            "high-availability.dir": "objstore://ha",
            "log.dir": "objstore://flink-log"})

    def test_non_cas_remote_still_flags(self):
        rules = self._rules({"log.dir": "hdfs://nn/flink-log"})
        assert "STORAGE_LOCAL_LOCKS_ON_REMOTE" in rules


class TestCleanerDisabledWithRetention:
    """PR-18 satellite: CLEANER_DISABLED_WITH_RETENTION clean
    negatives (the seeded violation lives in SEEDS)."""

    def _analyze(self, conf, with_sink=True):
        env = make_env(conf)
        ds = env.from_source(GeneratorSource(gen), WM())
        if with_sink:
            from flink_tpu.log.connectors import LogSink

            ds.add_sink(LogSink(str(env.config.get_raw(
                "test.topic", "/tmp/_t"))), name="writer")
        else:
            ds.collect()
        return [f.rule for f in env.analyze()]

    def test_cleaner_enabled_is_quiet(self, tmp_path):
        assert "CLEANER_DISABLED_WITH_RETENTION" not in self._analyze({
            "test.topic": str(tmp_path / "t"),
            "log.retention.ms": 60_000,
            "log.cleaner.enabled": True})

    def test_no_retention_is_quiet(self, tmp_path):
        assert "CLEANER_DISABLED_WITH_RETENTION" not in self._analyze({
            "test.topic": str(tmp_path / "t")})

    def test_consume_only_plan_is_quiet(self):
        """No LogSink in the plan: the consumer inherits the
        producer's maintenance regime — nothing to warn."""
        assert "CLEANER_DISABLED_WITH_RETENTION" not in self._analyze(
            {"log.retention.ms": 60_000}, with_sink=False)

    def test_bytes_retention_alone_fires(self, tmp_path):
        rules = self._analyze({"test.topic": str(tmp_path / "t"),
                               "log.retention.bytes": 1_000_000})
        assert "CLEANER_DISABLED_WITH_RETENTION" in rules


class TestRescaleRule:
    """ISSUE 16: RESCALE_INVALID / RESCALE_COOLDOWN_THRASH — the
    rescale.* grammar's unsatisfiable shapes error at submit, the
    thrash-but-legal shapes warn, and legal configs stay silent."""

    def _rules(self, conf):
        return [(f.rule, f.severity) for f in analyze_config(
            Configuration(conf))
            if f.rule.startswith("RESCALE")]

    def test_reactive_without_checkpointing_errors(self):
        assert ("RESCALE_INVALID", "error") in self._rules(
            {"rescale.mode": "reactive"})

    def test_unknown_mode_errors(self):
        assert ("RESCALE_INVALID", "error") in self._rules(
            {"rescale.mode": "adaptive"})

    def test_inverted_pressure_band_errors(self):
        assert ("RESCALE_INVALID", "error") in self._rules(
            {"rescale.mode": "reactive",
             "execution.checkpointing.interval": "1s",
             "rescale.target-pressure-high": 30,
             "rescale.target-pressure-low": 40})

    def test_bounds_violating_key_group_discipline_error(self):
        # 8 shards / 1 process = share 8; min-devices 3 divides nothing
        assert ("RESCALE_INVALID", "error") in self._rules(
            {"rescale.mode": "reactive",
             "execution.checkpointing.interval": "1s",
             "state.num-key-shards": "8",
             "rescale.min-devices": 3})

    def test_empty_width_range_errors(self):
        assert ("RESCALE_INVALID", "error") in self._rules(
            {"rescale.mode": "reactive",
             "execution.checkpointing.interval": "1s",
             "rescale.min-devices": 4,
             "rescale.max-devices": 2})

    def test_cooldown_below_checkpoint_interval_warns(self):
        rules = self._rules({
            "rescale.mode": "reactive",
            "execution.checkpointing.interval": "30s",
            "rescale.cooldown": "5s"})
        assert ("RESCALE_COOLDOWN_THRASH", "warn") in rules
        assert ("RESCALE_INVALID", "error") not in rules

    def test_legal_reactive_config_is_silent(self):
        assert self._rules({
            "rescale.mode": "reactive",
            "execution.checkpointing.interval": "30s",
            "rescale.cooldown": "120s",
            "state.num-key-shards": "128",
            "rescale.min-devices": 2,
            "rescale.max-devices": 8}) == []

    def test_mode_off_never_fires_regardless_of_knobs(self):
        # manual-only mode: the controller never reads the band/bounds,
        # so even a nonsense band must not block a manual-rescale user
        assert self._rules({
            "rescale.target-pressure-high": 10,
            "rescale.target-pressure-low": 90,
            "rescale.cooldown": "0ms"}) == []


class TestStateBudgetRule:
    """ISSUE 17: STATE_BUDGET_INVALID / STATE_BUDGET_IGNORED — the
    state.* backend grammar's can-never-work shapes error at submit,
    the does-nothing shape warns, and legal configs stay silent."""

    def _rules(self, conf):
        return [(f.rule, f.severity) for f in analyze_config(
            Configuration(conf))
            if f.rule.startswith("STATE_BUDGET")]

    def test_unknown_backend_errors(self):
        assert ("STATE_BUDGET_INVALID", "error") in self._rules(
            {"state.backend": "rocksdb"})

    def test_lsm_budget_below_run_floor_errors(self):
        # default floor is 64 KiB; a 4 KiB budget seals per batch
        assert ("STATE_BUDGET_INVALID", "error") in self._rules(
            {"state.backend": "lsm",
             "state.memory-budget-bytes": 4096})

    def test_unparseable_budget_errors(self):
        assert ("STATE_BUDGET_INVALID", "error") in self._rules(
            {"state.backend": "lsm",
             "state.memory-budget-bytes": "lots"})

    def test_compact_min_runs_below_two_errors(self):
        assert ("STATE_BUDGET_INVALID", "error") in self._rules(
            {"state.backend": "lsm",
             "state.lsm.compact-min-runs": 1})

    def test_budget_on_resident_backend_warns_not_errors(self):
        rules = self._rules({"state.backend": "spill",
                             "state.memory-budget-bytes": 1 << 20})
        assert ("STATE_BUDGET_IGNORED", "warn") in rules
        assert ("STATE_BUDGET_INVALID", "error") not in rules

    def test_lowered_floor_makes_tiny_budget_legal(self):
        # the crash-test shape: tiny runs on purpose, floor lowered to
        # match — self-consistent, must stay silent
        assert self._rules({
            "state.backend": "lsm",
            "state.memory-budget-bytes": 4096,
            "state.lsm.run-floor-bytes": 4096}) == []

    def test_legal_lsm_config_is_silent(self):
        assert self._rules({
            "state.backend": "lsm",
            "state.memory-budget-bytes": 64 << 20,
            "state.lsm.compact-min-runs": 4}) == []

    def test_default_config_is_silent(self):
        assert self._rules({}) == []

    def test_budget_unset_on_resident_backend_is_silent(self):
        # hbm with no budget key: nothing to warn about
        assert self._rules({"state.backend": "hbm"}) == []
