"""NEXmark Q5 key-sharded over a mesh, through ``env.execute()``, on the
benchmark suite's own records (``benchmark/configs/nexmark_q5`` at its
rehearsal's cut): the deployment of the configuration
``nexmark_q5_mesh4``.

- the mesh job's rows equal the plain reference's and the one-chip job's;
- what crosses the exchange is a batch's pre-aggregated (slot, ring
  column) pairs where the batch allows it (local-global aggregation),
  and its records where it does not: the batch chooses, a job may mix
  both, and the answer is the same;
- the exchange's counters reach ``JobResult.metrics`` and add up in
  RECORDS on either lane, and ``exchange_devices_idle`` is 0 only when
  every mesh device held state and received records;
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
from flink_tpu.api.windowing import SlidingEventTimeWindows
from flink_tpu.config import Configuration
from flink_tpu.graph.compiler import compile_job
from flink_tpu.ops.aggregates import count, multi, sum_of
from flink_tpu.ops.window import WindowOperator
from flink_tpu.parallel.mesh import make_mesh_plan
from flink_tpu.runtime.driver import PHASE_LEAVES, Driver

pytestmark = [
    pytest.mark.shard_map,
    pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 devices"),
]

BATCH = 8192
N_BATCHES = 12
# a batch this small can hold as many (auction, pane) pairs as records,
# so its pair bound fails the gates of both pair-making lanes and its
# records cross the exchange one entry each
RECORD_LANE_BATCH = 256
RECORD_LANE_BATCHES = 48
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
              batch=BATCH, **conf):
    """Q5 over ``n_batches`` of the suite's bids at 2 events/ms.
    -> (JobResult, RecordingSink, BenchSource)."""
    settings = dict(CONF, **conf)
    settings["pipeline.microbatch-size"] = batch
    if mesh:
        settings["cluster.mesh-devices"] = mesh
    env = StreamExecutionEnvironment(Configuration(settings))
    source = BenchSource(pool or nexmark_q5.make_pool(SEED, batch, PARAMS),
                         Schedule({"events_per_ms": 2}), batch,
                         schema=nexmark_q5.SCHEMA, max_batches=n_batches)
    sink = RecordingSink()
    nexmark_q5.build(env, source, sink.sink, PARAMS)
    if make_driver is None:
        return env.execute("q5-suite"), sink, source
    plan = compile_job(env._transforms, env.config, env._watermark_strategy)
    return make_driver(plan, env.config).run("q5-suite"), sink, source


def offered(source, pool=None, batch=BATCH):
    pool = pool or nexmark_q5.make_pool(SEED, batch, PARAMS)
    sched = Schedule({"events_per_ms": 2})
    return [(pool[i % len(pool)], sched.batch_ts(i, batch))
            for i in range(source.batches)]


def against_reference(sink, source, pool=None, batch=BATCH):
    return nexmark_q5.check(iter(offered(source, pool, batch)),
                            source.max_ts, sink.batches, PARAMS)


def n_pairs(keys, ts, slide_ms=PARAMS["slide_ms"]):
    """Distinct (key, pane) pairs of a batch: what the host's
    pre-aggregate of that batch holds."""
    return np.unique(np.stack([keys, ts // slide_ms]), axis=1).shape[1]


def pairs_per_batch(source, batch=BATCH):
    return [n_pairs(bids["auction"], ts)
            for bids, ts in offered(source, batch=batch)]


def preagg_batches(metrics):
    return sum(v for k, v in metrics.items()
               if k.startswith("profile.op") and k.endswith(".preagg_batches"))


@pytest.fixture(scope="module")
def mesh_job():
    return suite_job(mesh=4)


@pytest.fixture(scope="module")
def record_lane_job():
    return suite_job(mesh=4, batch=RECORD_LANE_BATCH,
                     n_batches=RECORD_LANE_BATCHES)


@pytest.fixture(params=["pairs", "records"])
def lane_job(request, mesh_job, record_lane_job):
    """(job, batch size, batches): the suite's bids on the mesh, once in
    batches that cross the exchange as pairs and once as records."""
    return ((mesh_job, BATCH, N_BATCHES) if request.param == "pairs"
            else (record_lane_job, RECORD_LANE_BATCH, RECORD_LANE_BATCHES))


class TestSuiteRecordsOnTheMesh:
    def test_rows_equal_the_plain_reference(self, lane_job):
        (res, sink, source), batch, n_batches = lane_job
        cmp_ = against_reference(sink, source, batch=batch)
        assert cmp_["rows_expected"] == cmp_["rows_got"] > 0
        assert (cmp_["rows_missing"], cmp_["rows_not_in_reference"],
                cmp_["rows_duplicated"]) == (0, 0, 0), cmp_
        assert res.metrics["records_in"] == n_batches * batch
        for zero in nexmark_q5.zero_counters(PARAMS):
            assert res.metrics.get(zero, 0) == 0, zero

    def test_the_batch_chooses_the_lane(self, lane_job):
        """Pairs against records in the batch, the one-chip lane's own
        rule: no option names the lane."""
        (res, _sink, source), batch, n_batches = lane_job
        m = res.metrics
        assert m["batches"] == n_batches
        if batch == BATCH:
            assert preagg_batches(m) == n_batches
            assert m["exchange_entries"] == sum(pairs_per_batch(source))
            assert m["exchange_entries"] < n_batches * batch // 8
        else:
            assert preagg_batches(m) == 0
            assert m["exchange_entries"] == n_batches * batch

    def test_rows_equal_the_one_chip_jobs(self, mesh_job):
        _res, one_sink, _src = suite_job()
        assert nexmark_q5.collect(one_sink.batches, PARAMS)[0].size > 0
        for a, b in zip(nexmark_q5.collect(one_sink.batches, PARAMS),
                        nexmark_q5.collect(mesh_job[1].batches, PARAMS)):
            np.testing.assert_array_equal(a, b)


class TestExchangeCounters:
    def test_records_per_device_add_up_to_the_valid_records(self, lane_job):
        """In RECORDS on the pair lane too: a device reports the sum of
        the counts of the pairs it received."""
        (res, _sink, _source), batch, n_batches = lane_job
        m = res.metrics
        per_dev = [m[f"exchange_records.{d}"] for d in range(4)]
        assert "exchange_records.4" not in m
        assert sum(per_dev) == m["records_in"] - m.get("late_records", 0) \
            == n_batches * batch
        assert all(n > 0 for n in per_dev)
        assert m["exchange_records_max"] == max(per_dev)
        assert m["exchange_records_mean"] == pytest.approx(
            sum(per_dev) / 4)
        assert m["exchange_shard_skew"] == pytest.approx(
            max(per_dev) / (sum(per_dev) / 4))
        assert 1.0 <= m["exchange_shard_skew"] < 4.0

    def test_chunks_and_upload_bytes(self, lane_job):
        (res, _sink, source), batch, n_batches = lane_job
        m = res.metrics
        # no exchange capacity: a batch is one chunk
        assert m["exchange_chunks"] == m["batches"] == n_batches
        assert m["exchange_overflow"] == 0
        if batch == RECORD_LANE_BATCH:
            assert m["exchange_upload_bytes"] == 3 * n_batches * batch
            return
        # the pair buffers: (pair, count) int32 rows, padded to a pow2
        # of at least 256 rows: far under 3 bytes a record
        pairs = pairs_per_batch(source)
        assert m["exchange_entries"] == sum(pairs) < n_batches * batch
        assert m["exchange_upload_bytes"] == sum(
            8 * WindowOperator._pow2_target(max(n, 256), 4) for n in pairs)
        # a byte a record at this cut (400 keys in a batch of 8,192)
        assert m["exchange_upload_bytes"] < 3 * n_batches * batch // 2

    def test_a_capacity_cuts_batches_into_more_chunks_and_loses_nothing(self):
        """A capacity small enough to cut PAIR chunks: a batch's ~800
        pairs, padded to 1,024 in four arrival blocks, put ~64 in a
        (block, owner) bucket."""
        res, sink, source = suite_job(
            mesh=4, **{"pipeline.exchange-capacity": 40})
        m = res.metrics
        assert preagg_batches(m) == m["batches"] == N_BATCHES
        assert m["exchange_chunks"] > m["batches"]
        assert m["exchange_entries"] == sum(pairs_per_batch(source))
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
    def test_the_partition_stays_flat_with_the_new_leaf(self, lane_job):
        (res, _sink, _source), _batch, n_batches = lane_job
        m = res.metrics
        pre = "profile.phase."
        leaves = {k[len(pre):]: v for k, v in m.items()
                  if k.startswith(pre) and not k.endswith(".n")
                  and k[len(pre):].startswith(
                      ("ingest.", "window.", "wm.", "state."))}
        assert leaves["window.exchange_split"] > 0
        assert m["profile.phase.window.exchange_split.n"] >= n_batches
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
    # the pair program included: every batch of the job ran it
    assert preagg_batches(res.metrics) == N_BATCHES
    assert res.metrics["exchange_devices_idle"] == 0
    assert against_reference(sink, source)["rows_missing"] == 0


def float_sum_job(mesh, events_per_ms):
    """``float_sum``'s job on one seeded batch of the suite's bids.
    -> (JobResult, the probe's verdict on its rows, batch, timestamps)."""
    from benchmark.probes import float_sum, float_sum_mesh

    batch = float_sum.records(nexmark_q5, SEED, BATCH, PARAMS)
    sched = Schedule({"events_per_ms": events_per_ms})
    ts = sched.batch_ts(0, BATCH)
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
    assert res.metrics.get("exchange_devices_idle", 0) == 0
    return res, out, batch, ts


def test_float_sums_cross_the_exchange_pre_added_and_stay_near_float64():
    """What the benchmark's float-sum probes hold the two deployments to
    (PERF.md section 2). Where a batch's pairs cross the exchange (the
    whole batch in one pane: 400 pairs against 8,192 records), the host
    pre-adds its prices per pair in float64 on the mesh as on one
    device, so the mesh's sums meet ``float_sum``'s own limit and equal
    the one-chip job's. Every count and row is exact."""
    from benchmark.probes import float_sum

    errs = {}
    for mesh in (None, 4):
        res, out, _batch, _ts = float_sum_job(mesh, events_per_ms=100)
        assert preagg_batches(res.metrics) == 1
        errs[mesh] = out["sum_max_rel_err"]
    assert errs[4] == errs[None] <= float_sum.SUM_RTOL


def test_float_sums_of_a_high_cardinality_batch_cross_as_records():
    """The per-record lane is still there and chosen by the batch: over
    five panes the batch's pair bound (2,000 of 12 bytes) fails the
    gate, every record crosses the exchange as one entry and its price
    is added to its pane in float32 on the device that owns it — by a
    segmented add over the records sorted by cell, no worse than the
    arrival-order float32 add ``float_sum_mesh.mesh_lane_sums`` makes
    in numpy — so a sum is off the float64 reference by float32
    accumulation, parts in 10^6, not the 10^3 of a rounded dot."""
    from benchmark.probes import float_sum, float_sum_mesh

    res, out, batch, ts = float_sum_job(4, events_per_ms=1)
    assert preagg_batches(res.metrics) == 0
    assert res.metrics["exchange_entries"] == BATCH
    ppw = PARAMS["window_ms"] // PARAMS["slide_ms"]
    ref = float_sum.sliding(float_sum.pane_sums(batch, ts, PARAMS)[0], ppw)
    lane = float_sum_mesh.mesh_lane_sums(batch, ts, PARAMS)
    in_arrival_order = float_sum.gap(
        float_sum.window_sums_f32(lane, ppw), ref)
    assert 0 < out["sum_max_rel_err"] <= in_arrival_order \
        < float_sum_mesh.SUM_RTOL
    assert float_sum.SUM_RTOL < float_sum_mesh.SUM_RTOL


# -- the operator alone: lanes mixed in one job, growth, new keys ----------

WINDOWS = dict(max_out_of_orderness_ms=4000)
BIG, SMALL = 16384, 100


def mesh_operator(agg, **kw):
    return WindowOperator(
        SlidingEventTimeWindows.of(10_000, 2_000), agg,
        mesh_plan=make_mesh_plan(8, 64, jax.devices()[:4]), **WINDOWS, **kw)


def one_chip_operator(agg, **kw):
    return WindowOperator(SlidingEventTimeWindows.of(10_000, 2_000), agg,
                          num_shards=8, slots_per_shard=64, **WINDOWS, **kw)


def fired_rows(op, batches, final_wm):
    for keys, ts, data in batches:
        op.process_batch(keys, ts, data)
    fired = op.advance_watermark(final_wm)
    fields = sorted(fired)
    rows = sorted(zip(*(fired[f].tolist() for f in fields)))
    op.run_pending_release()    # the rows in hand, as the driver has it
    return rows, fields


def mixed_batches(rng):
    """Big batches over a few keys in one pane (pairs) alternating with
    small ones of keys never seen before (as many pairs as records)."""
    out, t, fresh = [], 0, 1000
    for i in range(6):
        if i % 2 == 0:
            keys = rng.integers(0, 30, BIG)
            ts = np.sort(rng.integers(t, t + 1000, BIG))
        else:
            keys = np.arange(fresh, fresh + SMALL)
            fresh += SMALL
            ts = np.sort(rng.integers(t, t + 1000, SMALL))
        out.append((keys.astype(np.int64), ts.astype(np.int64),
                    {"v": (rng.random(len(keys)) * 10).astype(np.float32)}))
        t += 1000
    return out


@pytest.mark.parametrize("agg", [count(), multi(count(), sum_of("v"))],
                         ids=["count", "count+sum"])
def test_a_job_whose_batches_alternate_between_the_lanes(agg):
    """count: the fused scan makes the pairs; count+sum: the general
    lane's combine does. Either way the small batches fall through to
    the per-record exchange, and the rows are the one-chip job's and
    the plain count's."""
    batches = mixed_batches(np.random.default_rng(5))
    mesh_op, one_op = mesh_operator(agg), one_chip_operator(agg)
    got, fields = fired_rows(mesh_op, batches, 60_000)
    want, _ = fired_rows(one_op, batches, 60_000)
    assert got == want and len(got) > 0
    # the plain reference of the counts: per (key, pane), then windows
    ref = {}
    for keys, ts, _data in batches:
        for k, p in zip(keys.tolist(), (ts // 2000).tolist()):
            for end in range(p + 1, p + 6):
                ref[(k, end * 2000)] = ref.get((k, end * 2000), 0) + 1
    ik, ie, ic = (fields.index(f) for f in ("key", "window_end", "count"))
    assert {(r[ik], r[ie]): r[ic] for r in got} == ref
    # only the big batches took pairs, on the mesh as on one chip
    assert mesh_op.prof["preagg_batches"] == one_op.prof["preagg_batches"] == 3
    pairs = sum(n_pairs(k, t) for k, t, _ in batches[::2])
    assert mesh_op.exchange_stats()["entries"] == pairs + 3 * SMALL
    assert mesh_op.exchange_stats()["records"].sum() == 3 * (BIG + SMALL)
    assert (agg.sum_width == 0) == (mesh_op.prof["scan_pane_moves"] > 0)
    assert mesh_op.exchange_overflow == 0


def test_ring_growth_and_new_keys_inside_a_mesh_batch_of_the_scan():
    """The fused scan under a mesh: a batch that brings keys the
    directory has not seen (``register_misses``, then the scan goes on
    over the misses) and spans more panes than the ring holds
    (``_grow_ring``, then the scan is done again, over the GLOBAL pair
    domain of the new ring)."""
    rng = np.random.default_rng(9)
    first = (rng.integers(0, 100, BIG).astype(np.int64),
             np.sort(rng.integers(0, 2000, BIG)).astype(np.int64), {})
    second = (rng.integers(0, 120, BIG).astype(np.int64),
              np.sort(rng.integers(2000, 34_000, BIG)).astype(np.int64), {})
    mesh_op, one_op = mesh_operator(count()), one_chip_operator(count())
    ring0 = mesh_op.plan.ring
    got, _ = fired_rows(mesh_op, [first, second], 100_000)
    want, _ = fired_rows(one_op, [first, second], 100_000)
    assert got == want and len(got) > 0
    assert mesh_op.plan.ring == one_op.plan.ring > ring0
    # 120 keys were registered; the last watermark purged every pane,
    # so every one of them has been released since
    assert mesh_op.directory.slots_allocated == 120
    assert mesh_op.directory.num_keys() == 0
    assert mesh_op.prof["preagg_batches"] == 2
    assert mesh_op.prof["scan_pane_moves"] > 0      # the scan ran
    assert mesh_op._preagg_ws.domain == 4 * mesh_op.layout.slots * \
        mesh_op.plan.ring
    assert mesh_op.exchange_stats()["records"].sum() == 2 * BIG


def test_pairs_of_a_process_that_owns_part_of_the_shard_space():
    """Cross-host: the directory hands out LOCAL slot ids over this
    process's shard range, which line up with the local mesh's row
    blocks, so the pair lane holds there as it does on one host."""
    from flink_tpu.state.keyed import KeyDirectory

    ids = np.arange(4000, dtype=np.int64)
    mine = ids[KeyDirectory(16, 64).shard_of(ids) >= 8][:60]
    rng = np.random.default_rng(13)
    batches = [(mine[rng.integers(0, len(mine), BIG)],
                np.sort(rng.integers(t, t + 2000, BIG)).astype(np.int64), {})
               for t in (0, 2000, 4000)]
    kw = dict(num_shards=16, shard_range=(8, 16))
    mesh_op = mesh_operator(count(), **kw)
    one_op = WindowOperator(SlidingEventTimeWindows.of(10_000, 2_000),
                            count(), slots_per_shard=64, **WINDOWS, **kw)
    got, _ = fired_rows(mesh_op, batches, 60_000)
    want, _ = fired_rows(one_op, batches, 60_000)
    assert got == want and len(got) > 0
    assert mesh_op.prof["preagg_batches"] == 3
    assert mesh_op.exchange_stats()["records"].sum() == 3 * BIG
