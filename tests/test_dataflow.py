"""Dataflow-plane suite (flink_tpu/analysis/dataflow.py): the three
propagated lattices — record schema, state-growth bound, watermark
capability — each with seeded violations AND clean negatives (the
rule-coverage parametrization itself lives in tests/test_analysis.py,
keyed off rule_catalog() so an unregistered-in-tests rule fails the
suite), the `analyze --explain` surface over the GOLDEN Q5 plan, the
zero-false-positive gates over the shipped golden pipelines (batch
wordcount, the log-chained two-job pair, every committed bench conf),
and the submit-wall-time budget (< 200ms — the analyzer runs at every
submit)."""
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from flink_tpu.analysis import dataflow
from flink_tpu.analysis.dataflow import explain_plan, propagate
from flink_tpu.api.environment import StreamExecutionEnvironment
from flink_tpu.api.sources import GeneratorSource
from flink_tpu.api.windowing import (
    EventTimeSessionWindows,
    SlidingEventTimeWindows,
    TumblingEventTimeWindows,
    TumblingProcessingTimeWindows,
)
from flink_tpu.config import Configuration
from flink_tpu.time.watermarks import WatermarkStrategy

pytestmark = pytest.mark.analysis

WM = WatermarkStrategy.for_monotonous_timestamps
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


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


def facts_of(env):
    plan = env.compile_plan(strict=False)
    return plan, propagate(plan, env.config)


def node_named(plan, name):
    return next(n for n in plan.nodes.values() if n.name == name)


# -- schema lattice ---------------------------------------------------------

class TestSchemaLattice:
    def test_source_declaration_seeds_and_chain_eval_steps(self):
        env = make_env()
        (env.from_source(GeneratorSource(gen, schema={"word": "int64"}),
                         WM())
            .map(lambda d: {"w2": d["word"] * 2}, name="double")
            .collect())
        plan, facts = facts_of(env)
        src = node_named(plan, "source")
        assert facts.nodes[src.id].schema == {"word": "int64"}
        chain = node_named(plan, "double")
        assert facts.nodes[chain.id].schema == {"w2": "int64"}

    def test_key_fn_keyby_injects_key_column(self):
        env = make_env()
        (env.from_source(GeneratorSource(gen, schema={"word": "int64"}),
                         WM())
            .key_by(lambda d: d["word"] % 4)
            .window(TumblingEventTimeWindows.of(1000))
            .count()
            .collect())
        assert env.analyze() == []  # the derived __key_N__ column exists

    def test_opaque_chain_degrades_to_unknown_not_finding(self):
        def boom(data):
            raise ValueError("opaque to abstract eval")

        env = make_env()
        (env.from_source(GeneratorSource(gen, schema={"word": "int64"}),
                         WM())
            .map(boom, name="opaque")
            .key_by("anything")  # unknown schema: no field check
            .window(TumblingEventTimeWindows.of(1000))
            .count()
            .collect())
        assert [f.rule for f in env.analyze()] == []
        plan, facts = facts_of(env)
        assert facts.nodes[node_named(plan, "opaque").id].schema is None

    def test_keyerror_on_unrelated_dict_is_opaque_not_finding(self):
        """Review regression: a fn KeyError whose key IS in the input
        schema came from some OTHER dict (a runtime-populated lookup
        table) — it must degrade to unknown, never claim the
        self-contradictory 'word not in [word]' schema error."""
        lookup = {}  # populated at runtime, empty at analysis

        def enrich(data):
            return {"tag": lookup["word"], **data}

        env = make_env()
        (env.from_source(GeneratorSource(gen, schema={"word": "int64"}),
                         WM())
            .map(enrich, name="enrich")
            .key_by("word")
            .window(TumblingEventTimeWindows.of(1000))
            .count()
            .collect())
        assert [f.rule for f in env.analyze()
                if f.rule == "FIELD_NOT_IN_SCHEMA"] == []
        plan, facts = facts_of(env)
        assert facts.nodes[node_named(plan, "enrich").id].schema is None

    def test_aggregate_over_missing_field_is_flagged(self):
        env = make_env()
        (env.from_source(GeneratorSource(gen, schema={"word": "int64"}),
                         WM())
            .key_by("word")
            .window(TumblingEventTimeWindows.of(1000))
            .sum("nope")
            .collect())
        fs = [f for f in env.analyze() if f.rule == "FIELD_NOT_IN_SCHEMA"]
        assert fs and "nope" in fs[0].message

    def test_join_key_against_leg_schema(self):
        env = make_env()
        left = env.from_source(
            GeneratorSource(gen, schema={"word": "int64"}), WM())
        right = env.from_source(
            GeneratorSource(gen, schema={"word": "int64"}), WM())
        (left.join(right).where("word").equal_to("wrod")
             .window(TumblingEventTimeWindows.of(1000))
             .apply()
             .collect())
        fs = [f for f in env.analyze() if f.rule == "FIELD_NOT_IN_SCHEMA"]
        assert fs and "wrod" in fs[0].message

    def test_union_of_equal_schemas_is_clean(self):
        env = make_env()
        a = env.from_collection({"k": np.array([1], np.int64)},
                                np.array([100], np.int64))
        b = env.from_collection({"k": np.array([2], np.int64)},
                                np.array([200], np.int64))
        a.union(b).key_by("k").window(
            TumblingEventTimeWindows.of(1000)).count().collect()
        # (EVENT_TIME_NO_WATERMARK legitimately warns here — the
        # collection source has no strategy; the SCHEMA plane is clean)
        assert [f for f in env.analyze()
                if f.rule in ("SCHEMA_MISMATCH_UNION",
                              "FIELD_NOT_IN_SCHEMA")] == []

    def test_submit_pass_never_calls_user_chain_fns(self):
        """The driver's automatic analysis runs with chain evaluation
        OFF: a side-effecting map must observe exactly the real batches
        — never a phantom empty batch from abstract eval."""
        calls = []

        def observed(data):
            calls.append(len(next(iter(data.values()))))
            return data

        env = make_env()
        (env.from_source(GeneratorSource(gen, schema={"word": "int64"}),
                         WM())
            .map(observed, name="observed")
            .key_by("word")
            .window(TumblingEventTimeWindows.of(1000))
            .count()
            .collect())
        env.execute("no-phantom-batches")
        assert calls == [8, 8]  # the two real batches, nothing else
        # the explicit surface DOES evaluate (0-row batch) — that is
        # the documented contract, not an accident
        env.analyze()
        assert calls == [8, 8, 0]


# -- state lattice ----------------------------------------------------------

class TestStateLattice:
    def test_sliding_window_geometry_estimate(self):
        env = make_env()
        (env.from_source(GeneratorSource(gen, schema={"word": "int64"}),
                         WM())
            .key_by("word")
            .window(SlidingEventTimeWindows.of(10_000, 1_000))
            .count()
            .collect())
        plan, facts = facts_of(env)
        nf = facts.nodes[node_named(plan, "window_agg").id]
        assert nf.state == "bounded"
        # count(): 0 lanes + i64 count = 8 B/cell; 10s window / 1s pane
        # + 1 = 11 live panes
        assert nf.state_bytes_per_key == 88
        assert "live panes" in nf.state_detail

    def test_session_and_global_agg_bounds(self):
        env = make_env()
        (env.from_source(GeneratorSource(gen, schema={"word": "int64"}),
                         WM())
            .key_by("word")
            .window(EventTimeSessionWindows.with_gap(500))
            .count()
            .collect())
        plan, facts = facts_of(env)
        nf = facts.nodes[node_named(plan, "session_agg").id]
        assert nf.state == "bounded" and "gap 500ms" in nf.state_detail

        env2 = make_env()
        from flink_tpu.ops.aggregates import count as count_agg

        (env2.from_source(GeneratorSource(gen, schema={"word": "int64"}),
                          WM())
            .key_by("word")
            .running_aggregate(count_agg())
            .collect())
        plan2, facts2 = facts_of(env2)
        nf2 = facts2.nodes[node_named(plan2, "running_agg").id]
        assert nf2.state == "bounded"
        assert "key cardinality" in nf2.state_detail

    def test_bounded_source_silences_unbounded_growth(self):
        """The same GlobalWindows shape over a BOUNDED source is capped
        at end-of-input — the rule needs an unbounded feed to fire."""
        from flink_tpu.api.windowing import CountTrigger, GlobalWindows

        env = make_env()
        (env.from_source(GeneratorSource(gen), WM())  # bounded default
            .key_by("word")
            .window(GlobalWindows.create())
            .trigger(CountTrigger.of(3))
            .count()
            .collect())
        assert [f.rule for f in env.analyze()
                if f.rule == "UNBOUNDED_STATE_GROWTH"] == []

    def test_count_window_purges_and_stays_clean(self):
        env = make_env()
        (env.from_source(GeneratorSource(gen, is_bounded=False), WM())
            .key_by("word")
            .count_window(4)
            .count()
            .collect())
        fs = [f.rule for f in env.analyze()]
        assert "UNBOUNDED_STATE_GROWTH" not in fs


# -- watermark lattice ------------------------------------------------------

class TestWatermarkLattice:
    def test_processing_time_window_axis(self):
        env = make_env()
        (env.from_source(GeneratorSource(gen, schema={"word": "int64"}),
                         WM())
            .key_by("word")
            .window(TumblingProcessingTimeWindows.of(1000))
            .count()
            .collect())
        plan, facts = facts_of(env)
        nf = facts.nodes[node_named(plan, "window_agg").id]
        assert nf.wm == "processing"
        # proc-time windows into a SINK are fine — no stalled finding
        assert [f.rule for f in env.analyze()
                if f.rule == "STALLED_WATERMARK_LEG"] == []

    def test_event_time_window_after_proc_time_window_stalls(self):
        env = make_env()
        (env.from_source(GeneratorSource(gen, schema={"word": "int64"}),
                         WM())
            .key_by("word")
            .window(TumblingProcessingTimeWindows.of(1000))
            .count()
            .key_by("key")
            .window(TumblingEventTimeWindows.of(1000))
            .count()
            .collect())
        fs = [f for f in env.analyze()
              if f.rule == "STALLED_WATERMARK_LEG"]
        assert fs and fs[0].severity == "error"

    def test_source_idleness_is_reported_in_facts(self):
        env = make_env()
        (env.from_source(
            GeneratorSource(gen),
            WatermarkStrategy.for_bounded_out_of_orderness(
                50).with_idleness(2000))
            .collect())
        plan, facts = facts_of(env)
        src = node_named(plan, "source")
        assert "idle after 2000ms" in facts.nodes[src.id].wm_note


# -- explain: the golden Q5 plan --------------------------------------------

class TestExplain:
    def test_golden_q5_every_node_has_nontrivial_facts(self, capsys):
        from flink_tpu.cli import main

        rc = main(["analyze", "--entry", "runner_job_q5:build",
                   "--explain",
                   "--conf", "state.num-key-shards=8",
                   "--conf", "state.slots-per-shard=64",
                   "--conf", "pipeline.microbatch-size=8192"])
        assert rc == 0
        out = capsys.readouterr().out
        assert "no findings" in out
        # every node of the lowered Q5 plan prints all three lattices,
        # and none of them is the trivial bottom
        blocks = out.split("\nnode ")[1:]
        assert len(blocks) == 4  # source, window, rename chain, sink
        for block in blocks:
            assert "schema" in block and "watermark" in block \
                and "state" in block
            assert "unknown" not in block.split("watermark")[0], block
        assert "B/key" in out            # the state-bytes estimate
        assert "auction:int64" in out    # declared bid schema
        assert "bid_count:int64" in out  # inferred through q5_rename

    def test_explain_requires_entry(self, capsys):
        from flink_tpu.cli import main

        assert main(["analyze", "--explain"]) == 2


# -- zero-false-positive gates over the shipped golden pipelines ------------

class TestGoldenNegatives:
    def test_batch_mode_golden_plan_zero_findings(self, tmp_path):
        """The full analyzer (old + new planes) over the batch-mode
        golden wordcount — the CLI smoke's exact entry point."""
        import runner_job_wordcount

        env = make_env({"execution.runtime-mode": "batch",
                        "test.sink-dir": str(tmp_path / "out")})
        runner_job_wordcount.build(env)
        assert env.analyze() == []

    def test_log_chained_two_job_plan_zero_findings(self, tmp_path):
        """Both halves of the log-chained pair (producer → topic →
        consumer): LogSink 2PC + FileSink 2PC keep every taint rule
        silent."""
        import runner_job_log_chain

        conf = {"log.dir": str(tmp_path / "log"),
                "test.sink-dir": str(tmp_path / "out"),
                "state.num-key-shards": 8,
                "state.slots-per-shard": 64,
                "pipeline.microbatch-size": 256,
                "execution.checkpointing.interval": 500,
                "execution.checkpointing.dir": str(tmp_path / "chk")}
        penv = StreamExecutionEnvironment(Configuration(dict(conf)))
        runner_job_log_chain.produce(penv)
        assert penv.analyze() == []
        cenv = StreamExecutionEnvironment(Configuration(dict(conf)))
        runner_job_log_chain.consume(cenv)
        assert cenv.analyze() == []

    def test_bench_headline_conf_and_pipeline_zero_findings(self):
        """The benchmark's Q5 pipeline under its committed conf
        (confs/bench_q5_host_fed.conf, read as the benchmark and
        chip_smoke.py read it) analyzes clean — host-fed source,
        declared schema."""
        from flink_tpu.nexmark.generator import NexmarkConfig, bid_stream
        from flink_tpu.nexmark.queries import q5_hot_items
        from flink_tpu.api.sinks import FnSink

        conf = Configuration.from_file(
            os.path.join(REPO, "confs", "bench_q5_host_fed.conf"))
        env = StreamExecutionEnvironment(conf)
        cfg = NexmarkConfig(batch_size=1 << 20, n_batches=2,
                            events_per_ms=100,
                            num_active_auctions=10_000, hot_ratio=4)
        q5_hot_items(env, bid_stream(cfg), FnSink(lambda b: None),
                     out_of_orderness_ms=1_000)
        assert env.analyze() == []


# -- committed confs: who reads them + cold-subprocess analyze --------------

class TestBenchConfGate:
    def test_every_committed_conf_has_a_reader(self):
        """confs/ holds what the benchmark's configurations
        (benchmark/configs/*.json: "conf", a probe's "conf") and
        chip_smoke.py read by name, and nothing else: a file nobody
        reads cannot pile up there again, and a name somebody reads
        is there."""
        import re

        cfg_dir = os.path.join(REPO, "benchmark", "configs")
        read = set()
        for f in sorted(os.listdir(cfg_dir)):
            if f.endswith(".json"):
                with open(os.path.join(cfg_dir, f), encoding="utf-8") as fh:
                    read |= set(re.findall(r'"conf"\s*:\s*"([^"]+)"',
                                           fh.read()))
        assert read, "no benchmark configuration names a conf"
        with open(os.path.join(REPO, "chip_smoke.py"),
                  encoding="utf-8") as fh:
            smoke = fh.read()
        on_disk = sorted(os.listdir(os.path.join(REPO, "confs")))
        assert on_disk, "confs/ is empty"
        for f in on_disk:
            assert f in read or f'"{f}"' in smoke, (
                f"confs/{f} is read by no benchmark/configs/*.json and "
                "not by chip_smoke.py — delete it or name its reader")
        assert read <= set(on_disk), sorted(read - set(on_disk))

    def test_the_benchmark_conf_holds_its_four_settings(self):
        """Every cell of the benchmark builds its job from this file:
        its settings are the cell's, to the letter (PR 46 took the
        header and the line of a removed option out, nothing else)."""
        conf = Configuration.from_file(
            os.path.join(REPO, "confs", "bench_q5_host_fed.conf"))
        assert conf.to_dict() == {"analysis.fail-on": "off",
                                  "pipeline.microbatch-size": "1048576",
                                  "state.num-key-shards": "128",
                                  "state.slots-per-shard": "256"}

    def test_every_committed_conf_cold_analyzes_clean(self):
        """Tier-1 dogfood: `python -m flink_tpu analyze <conf>` from a
        COLD subprocess over every committed bench conf, exit status
        checked at the strictest threshold (--fail-on warn overrides
        the conf's own analysis.fail-on: off)."""
        conf_dir = os.path.join(REPO, "confs")
        files = sorted(f for f in os.listdir(conf_dir)
                       if f.endswith(".conf"))
        assert files
        for f in files:
            proc = subprocess.run(
                [sys.executable, "-m", "flink_tpu", "analyze",
                 os.path.join(conf_dir, f), "--fail-on", "warn"],
                capture_output=True, text=True, timeout=300,
                cwd=REPO)
            assert proc.returncode == 0, (
                f"{f}: rc={proc.returncode}\n{proc.stdout}{proc.stderr}")
            assert "no findings" in proc.stdout, f"{f}: {proc.stdout}"


# -- submit wall-time budget ------------------------------------------------

class TestAnalyzerWallTime:
    def test_full_analyzer_under_200ms_on_golden_q5(self):
        """The analyzer runs at EVERY submit; on the largest golden
        plan (headline Q5) a fresh end-to-end pass — memo cleared, all
        17+ rules, chain eval on — must stay under 200ms (best of 3;
        first pass warms imports/jax outside the clock)."""
        from flink_tpu.analysis import analyze

        env = make_env({"pipeline.microbatch-size": 8192})
        import runner_job_q5

        runner_job_q5.build(env)
        plan = env.compile_plan(strict=False)
        analyze(plan, env.config)  # warm imports, jax, registries
        best = float("inf")
        for _ in range(3):
            dataflow.clear_memo()  # a fresh submit never has the memo
            t0 = time.perf_counter()
            analyze(plan, env.config)
            best = min(best, time.perf_counter() - t0)
        assert best < 0.200, f"analyzer took {best * 1e3:.1f}ms"
