"""Golden Q5 entry point for the analyzer surfaces — ``python -m
flink_tpu analyze --entry runner_job_q5:build --explain`` walks the
same pipeline shape the benchmark's Q5 cells measure (nexmark bid stream →
keyBy(auction) → 10s/1s sliding COUNT → device top-1 → rename → sink),
so the --explain facts in tests/test_dataflow.py are facts about THE
golden plan, not a toy."""
from flink_tpu.api.sinks import CollectSink
from flink_tpu.nexmark.generator import NexmarkConfig, bid_stream
from flink_tpu.nexmark.queries import q5_hot_items


def build(env):
    cfg = NexmarkConfig(
        batch_size=int(env.config.get_raw("test.batch-size", 8192)),
        n_batches=int(env.config.get_raw("test.n-batches", 2)))
    q5_hot_items(env, bid_stream(cfg), CollectSink(),
                 out_of_orderness_ms=1_000)
