"""NEXmark Q5 key-sharded over a mesh, through ``env.execute()``, on the
benchmark suite's own records (``benchmark/configs/nexmark_q5`` at its
rehearsal's cut): the deployment of the configuration
``nexmark_q5_mesh4``.

- the mesh job's rows equal the plain reference's and the one-chip job's;
- the exchange's counters reach ``JobResult.metrics`` and add up, and
  ``exchange_devices_idle`` is 0 only when every mesh device held state
  and received records;
- ``window.exchange_split`` is a leaf of the flat phase partition;
- a second job of the same shape compiles nothing.
"""
import jax
import jax.monitoring
import numpy as np
import pytest

import flink_tpu  # noqa: F401 — x64 before other jax users
from benchmark.configs import nexmark_q5
from benchmark.loadgen import BenchSource, RecordingSink
from benchmark.traffic_kinds.constant_rate import Schedule
from flink_tpu.api.environment import StreamExecutionEnvironment
from flink_tpu.config import Configuration
from flink_tpu.graph.compiler import compile_job
from flink_tpu.runtime.driver import PHASE_LEAVES, Driver

pytestmark = [
    pytest.mark.shard_map,
    pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 devices"),
]

BATCH = 8192
N_BATCHES = 12
SEED = 2**31 + 11
# nexmark_q5.json's params at its rehearsal's cut
PARAMS = {
    "window_ms": 10000, "slide_ms": 2000, "out_of_orderness_ms": 4000,
    "person_proportion": 1, "auction_proportion": 3, "bid_proportion": 46,
    "num_in_flight_auctions": 100, "hot_auction_ratio": 2,
    "num_active_people": 1000, "hot_bidders_ratio": 4,
    "auction_id_wrap": 400, "pool_batches": 4}
CONF = {"pipeline.microbatch-size": BATCH, "state.num-key-shards": 8,
        "state.slots-per-shard": 64, "analysis.fail-on": "off"}


def suite_job(mesh=None, pool=None, n_batches=N_BATCHES, make_driver=None,
              **conf):
    """Q5 over ``n_batches`` of the suite's bids at 2 events/ms.
    -> (JobResult, RecordingSink, BenchSource)."""
    settings = dict(CONF, **conf)
    if mesh:
        settings["cluster.mesh-devices"] = mesh
    env = StreamExecutionEnvironment(Configuration(settings))
    source = BenchSource(pool or nexmark_q5.make_pool(SEED, BATCH, PARAMS),
                         Schedule({"events_per_ms": 2}), BATCH,
                         schema=nexmark_q5.SCHEMA, max_batches=n_batches)
    sink = RecordingSink()
    nexmark_q5.build(env, source, sink.sink, PARAMS)
    if make_driver is None:
        return env.execute("q5-suite"), sink, source
    plan = compile_job(env._transforms, env.config, env._watermark_strategy)
    return make_driver(plan, env.config).run("q5-suite"), sink, source


def against_reference(sink, source, pool=None):
    pool = pool or nexmark_q5.make_pool(SEED, BATCH, PARAMS)
    sched = Schedule({"events_per_ms": 2})
    stream = ((pool[i % len(pool)], sched.batch_ts(i, BATCH))
              for i in range(source.batches))
    return nexmark_q5.check(stream, source.max_ts, sink.batches, PARAMS)


@pytest.fixture(scope="module")
def mesh_job():
    return suite_job(mesh=4)


class TestSuiteRecordsOnTheMesh:
    def test_rows_equal_the_plain_reference(self, mesh_job):
        res, sink, source = mesh_job
        cmp_ = against_reference(sink, source)
        assert cmp_["rows_expected"] == cmp_["rows_got"] > 0
        assert (cmp_["rows_missing"], cmp_["rows_not_in_reference"],
                cmp_["rows_duplicated"]) == (0, 0, 0), cmp_
        assert res.metrics["records_in"] == N_BATCHES * BATCH
        for zero in nexmark_q5.zero_counters(PARAMS):
            assert res.metrics.get(zero, 0) == 0, zero

    def test_rows_equal_the_one_chip_jobs(self, mesh_job):
        _res, one_sink, _src = suite_job()
        assert nexmark_q5.collect(one_sink.batches, PARAMS)[0].size > 0
        for a, b in zip(nexmark_q5.collect(one_sink.batches, PARAMS),
                        nexmark_q5.collect(mesh_job[1].batches, PARAMS)):
            np.testing.assert_array_equal(a, b)


class TestExchangeCounters:
    def test_records_per_device_add_up_to_the_valid_records(self, mesh_job):
        m = mesh_job[0].metrics
        per_dev = [m[f"exchange_records.{d}"] for d in range(4)]
        assert "exchange_records.4" not in m
        assert sum(per_dev) == m["records_in"] - m.get("late_records", 0) \
            == N_BATCHES * BATCH
        assert all(n > 0 for n in per_dev)
        assert m["exchange_records_max"] == max(per_dev)
        assert m["exchange_records_mean"] == pytest.approx(
            sum(per_dev) / 4)
        assert m["exchange_shard_skew"] == pytest.approx(
            max(per_dev) / (sum(per_dev) / 4))
        assert 1.0 <= m["exchange_shard_skew"] < 4.0

    def test_chunks_and_upload_bytes(self, mesh_job):
        m = mesh_job[0].metrics
        # no exchange capacity: a batch is one chunk, 3 bytes a record
        assert m["exchange_chunks"] == m["batches"] == N_BATCHES
        assert m["exchange_upload_bytes"] == 3 * N_BATCHES * BATCH
        assert m["exchange_overflow"] == 0

    def test_a_capacity_cuts_batches_into_more_chunks_and_loses_nothing(self):
        res, sink, source = suite_job(
            mesh=4, **{"pipeline.exchange-capacity": 600})
        m = res.metrics
        assert m["exchange_chunks"] > m["batches"] == N_BATCHES
        assert sum(m[f"exchange_records.{d}"] for d in range(4)) \
            == N_BATCHES * BATCH
        assert m["exchange_overflow"] == 0
        cmp_ = against_reference(sink, source)
        assert (cmp_["rows_missing"], cmp_["rows_not_in_reference"]) == (0, 0)

    def test_devices_idle_is_zero_on_the_sound_run(self, mesh_job):
        assert mesh_job[0].metrics["exchange_devices_idle"] == 0

    def test_devices_idle_counts_devices_that_received_nothing(self):
        """The control: every key falls in one device's shard block, so
        three devices of the four receive no record."""
        from flink_tpu.state.keyed import KeyDirectory

        ids = np.arange(nexmark_q5.FIRST_AUCTION_ID,
                        nexmark_q5.key_domain(PARAMS), dtype=np.int64)
        shard = KeyDirectory(8, 64).shard_of(ids)
        mine = ids[shard // 2 == 0]          # 2 shards a device
        assert 0 < len(mine) < 128
        pool = nexmark_q5.make_pool(SEED, BATCH, PARAMS)
        for b in pool:
            b["auction"] = mine[b["auction"] % len(mine)]
        res, sink, source = suite_job(mesh=4, pool=pool)
        m = res.metrics
        assert m["exchange_devices_idle"] == 3
        assert [m[f"exchange_records.{d}"] for d in range(4)] == [
            N_BATCHES * BATCH, 0, 0, 0]
        assert m["exchange_shard_skew"] == pytest.approx(4.0)
        # the answer is still the reference's: idle devices lose nothing
        cmp_ = against_reference(sink, source, pool)
        assert (cmp_["rows_missing"], cmp_["rows_not_in_reference"]) == (0, 0)

    @pytest.mark.parametrize("job", [
        dict(),
        # a driver built by hand without the mesh plan its conf asks for
        # (env.execute() always hands it over)
        dict(mesh=4, make_driver=lambda plan, conf: Driver(
            plan, conf, mesh_plan=None))], ids=["one-chip", "no-mesh-plan"])
    def test_only_operators_on_a_mesh_publish_exchange_counters(self, job):
        """No operator ran on a mesh, so no exchange counter is made up."""
        res, _sink, _src = suite_job(**job)
        assert not [k for k in res.metrics if k.startswith("exchange_")
                    and k != "exchange_overflow"]


class TestExchangeSplitLeaf:
    def test_the_partition_stays_flat_with_the_new_leaf(self, mesh_job):
        m = mesh_job[0].metrics
        pre = "profile.phase."
        leaves = {k[len(pre):]: v for k, v in m.items()
                  if k.startswith(pre) and not k.endswith(".n")
                  and k[len(pre):].startswith(("ingest.", "window.", "wm."))}
        assert leaves["window.exchange_split"] > 0
        assert m["profile.phase.window.exchange_split.n"] >= N_BATCHES
        wall = m["profile.phase.loop_wall_s"]
        assert abs(sum(leaves.values()) - wall) <= 0.03 * wall, leaves
        assert "window.exchange_split" in PHASE_LEAVES["dispatch"]
        assert m["profile.phase.dispatch"] == pytest.approx(
            sum(leaves.get(n, 0.0) for n in PHASE_LEAVES["dispatch"]),
            abs=1e-5)

    def test_the_one_chip_lane_never_opens_it(self):
        res, _sink, _src = suite_job()
        assert "profile.phase.window.exchange_split" not in res.metrics


def test_a_second_identical_mesh_job_compiles_nothing(mesh_job):
    compiles = []

    def on_duration(event, secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(secs)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        res, sink, source = suite_job(mesh=4)
    finally:
        jax.monitoring.unregister_event_duration_listener(
            on_duration)
    assert compiles == []
    assert res.metrics["exchange_devices_idle"] == 0
    assert against_reference(sink, source)["rows_missing"] == 0


def test_float_sums_cross_the_exchange_in_float32_and_stay_near_float64():
    """What the benchmark's float-sum probes hold the two deployments to
    (PERF.md section 2): one device pre-adds a batch's prices in float64
    on the host; under a mesh each record's price is added to its pane
    in float32 after the exchange, in arrival order — bit for bit what
    ``float_sum_mesh.mesh_lane_sums`` does in numpy — so a sum is off
    the float64 reference by float32 accumulation, parts in 10^6, not
    the 10^3 of a rounded dot. Every count and row is exact."""
    from benchmark.probes import float_sum, float_sum_mesh

    batch = float_sum.records(nexmark_q5, SEED, BATCH, PARAMS)
    sched = Schedule({"events_per_ms": 1})
    ts = sched.batch_ts(0, BATCH)
    errs = {}
    for mesh in (None, 4):
        settings = dict(CONF)
        if mesh:
            settings["cluster.mesh-devices"] = mesh
        env = StreamExecutionEnvironment(Configuration(settings))
        sink = RecordingSink()
        float_sum.build(env, BenchSource([batch], sched, BATCH,
                                         schema=float_sum.SCHEMA,
                                         max_batches=1), sink.sink, PARAMS)
        res = env.execute("float-sum")
        out = (float_sum_mesh if mesh else float_sum).check_rows(
            sink.batches, batch, ts, PARAMS)
        assert out["holds"] and out["sum_dtype"] == "float32", out
        errs[mesh] = out["sum_max_rel_err"]
        assert res.metrics.get("exchange_devices_idle", 0) == 0
    ppw = PARAMS["window_ms"] // PARAMS["slide_ms"]
    ref = float_sum.sliding(float_sum.pane_sums(batch, ts, PARAMS)[0], ppw)
    lane = float_sum_mesh.mesh_lane_sums(batch, ts, PARAMS)
    assert errs[4] == float_sum.gap(float_sum.window_sums_f32(lane, ppw), ref)
    assert errs[None] <= float_sum.SUM_RTOL < float_sum_mesh.SUM_RTOL
