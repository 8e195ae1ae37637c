"""Test harness environment.

Tests run on CPU jax with a virtual 8-device mesh — the MiniCluster
analogue (ref: flink-runtime/.../runtime/minicluster/MiniCluster.java runs
a whole cluster in one JVM; here XLA's forced host platform device count
gives N "chips" in one process, so keyBy all_to_all, sharded state, and
checkpoint/reshard are all testable without TPUs). SURVEY.md §5 mapping.

Must run before jax initializes a backend, hence top of conftest.
"""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"  # tests never take the chip
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
# tests (and the subprocesses they spawn) leave no persistent compile
# cache in the checkout; the cache tests place their own
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def pytest_configure(config):
    # tier-1 runs `-m 'not slow'`: the deterministic chaos slice (fixed
    # seeds, <60s) stays in; the long randomized soaks are `slow`
    config.addinivalue_line(
        "markers", "slow: long-running soak/benchmark tests, excluded "
        "from the tier-1 `-m 'not slow'` run")
    config.addinivalue_line(
        "markers", "chaos: fault-injection chaos tests (flink_tpu.faults)"
        " — every failure report prints the fault seed for replay")
    config.addinivalue_line(
        "markers", "batch: bounded-execution (execution.runtime-mode="
        "batch) tests — blocking shuffle, columnar exchange, final-only "
        "fires")
    config.addinivalue_line(
        "markers", "log: durable-log exchange tests (flink_tpu/log/) — "
        "embedded replayable topics, 2PC commit markers, exactly-once "
        "job chaining")
    config.addinivalue_line(
        "markers", "shard_map: device-mesh execution (jax.shard_map over "
        "the virtual 8-device CPU mesh)")
    config.addinivalue_line(
        "markers", "analysis: static-analysis suite (flink_tpu/analysis"
        "/) — plan-analyzer rules, repo AST lints, and the dogfood gate "
        "that keeps the shipped tree at zero findings (tier-1)")
    config.addinivalue_line(
        "markers", "hostpool: shared host worker-pool plane (flink_tpu/"
        "parallel/hostpool.py) — pool unit tests and the serial-vs-"
        "parallel byte-identical parity gates on the sessions, "
        "windowAll, and spill golden pipelines (tier-1)")
    config.addinivalue_line(
        "markers", "session: session-cluster runtime mode (flink_tpu/"
        "runtime/session.py) — slot quotas, FIFO admission queue, fair "
        "drain scheduling, autoscaler, per-job isolation, multi-tenant "
        "chaos, and the `session` CLI smoke (tier-1)")
    config.addinivalue_line(
        "markers", "changelog: changelog/retraction plane (records."
        "OP_FIELD) — op-typed retract streams, signed window lanes, "
        "session -U/+U refires, RetractSink exactly-once under chaos, "
        "and the lifted SQL shapes (agg-over-join, HAVING) (tier-1)")
    config.addinivalue_line(
        "markers", "firegate: the fused step's fire gate and the "
        "announced step tokens — the host-fed late-refire gate "
        "predicate against a numpy golden, the coalesced ring "
        "readback, and the unknown-key treatment of the two removed "
        "options pipeline.fire-gate / pipeline.readiness (tier-1)")
