"""Mesh execution through the PUBLIC API (SURVEY §3.7, §4.A): with
``cluster.mesh-devices`` set, ``env.execute()`` runs the sharded step
over the virtual 8-device CPU mesh — and the results must be
byte-identical to single-device local execution. This is the
parallelism-rescaling correctness contract (ref: AbstractOperatorRestore
/ RescalingITCase compare-parallelism pattern).
"""
import numpy as np
import pytest
import jax

from flink_tpu.api.environment import StreamExecutionEnvironment
from flink_tpu.api.sinks import CollectSink
from flink_tpu.api.sources import GeneratorSource
from flink_tpu.api.windowing import SlidingEventTimeWindows, TumblingEventTimeWindows
from flink_tpu.config import Configuration
from flink_tpu.time.watermarks import WatermarkStrategy

pytestmark = pytest.mark.shard_map  # device-mesh suite


def make_env(mesh=None, extra=None):
    conf = {
        "state.num-key-shards": 32,
        "state.slots-per-shard": 16,
        "pipeline.microbatch-size": 256,
    }
    if mesh:
        conf["cluster.mesh-devices"] = mesh
    conf.update(extra or {})
    return StreamExecutionEnvironment(Configuration(conf))


def rows_of(sink):
    out = []
    for row in sink.rows:
        out.append(tuple(
            (k, int(v) if np.issubdtype(np.asarray(v).dtype, np.integer)
             else round(float(v), 4))
            for k, v in sorted(row.items())))
    return sorted(out)


def source(n_batches=8, n_keys=100, seed=0):
    def gen(split, i):
        if i >= n_batches:
            return None
        rng = np.random.default_rng(seed * 1000 + i)
        b = 192
        return ({"k": rng.integers(0, n_keys, b).astype(np.int64),
                 "v": rng.integers(1, 50, b).astype(np.int64)},
                np.sort(rng.integers(i * 700, i * 700 + 1400, b)).astype(np.int64))
    return gen


def build_q5_shape(env, sink, topn=None, n_batches=8, n_keys=100):
    """The Q5 pipeline shape: keyed sliding-window count (+ device
    top-n when ``topn``)."""
    s = (env.from_source(
            GeneratorSource(source(n_batches, n_keys)),
            WatermarkStrategy.for_bounded_out_of_orderness(500))
         .key_by("k")
         .window(SlidingEventTimeWindows.of(4_000, 1_000))
         .count())
    if topn:
        s = s.top(topn, by="count")
    s.add_sink(sink)
    return s


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8-device mesh")
class TestMeshExecute:
    def test_q5_sharded_via_public_api_matches_local(self):
        env_local = make_env()
        local_sink = CollectSink()
        build_q5_shape(env_local, local_sink)
        env_local.execute("q5-local")

        env_mesh = make_env(mesh="all")
        mesh_sink = CollectSink()
        build_q5_shape(env_mesh, mesh_sink)
        env_mesh.execute("q5-mesh")

        assert rows_of(local_sink) == rows_of(mesh_sink)
        assert len(rows_of(local_sink)) > 0

    def test_q5_topn_sharded_matches_local(self):
        env_local = make_env()
        local_sink = CollectSink()
        build_q5_shape(env_local, local_sink, topn=3)
        env_local.execute("q5top-local")

        env_mesh = make_env(mesh="all")
        mesh_sink = CollectSink()
        build_q5_shape(env_mesh, mesh_sink, topn=3)
        env_mesh.execute("q5top-mesh")

        assert rows_of(local_sink) == rows_of(mesh_sink)
        assert len(rows_of(local_sink)) > 0

    def test_topn_cross_device_ties_kept(self):
        """Keys engineered so the n-th count TIES across device
        boundaries: the distributed RANK()<=n (all_gather threshold)
        must keep every tying key, exactly like the local path."""
        def gen(split, i):
            if i >= 1:
                return None
            # 12 keys spread over all shards; counts: four keys tie at 5
            # (the n=2 threshold), others below
            keys, counts = [], {}
            rng = np.random.default_rng(42)
            tie_keys = [3, 40, 77, 90]     # hash to different shards
            low_keys = [5, 21, 55, 68]
            rows = []
            for k in tie_keys:
                rows += [k] * 5
            for k in low_keys:
                rows += [k] * 2
            rows = np.asarray(rows, np.int64)
            ts = np.full(len(rows), 500, np.int64)
            return ({"k": rows}, ts)

        def build(env, sink):
            (env.from_source(GeneratorSource(gen),
                             WatermarkStrategy.for_bounded_out_of_orderness(0))
             .key_by("k")
             .window(TumblingEventTimeWindows.of(1_000))
             .count()
             .top(2, by="count")
             .add_sink(sink))

        env_local, local_sink = make_env(), CollectSink()
        build(env_local, local_sink)
        env_local.execute("ties-local")

        env_mesh, mesh_sink = make_env(mesh="all"), CollectSink()
        build(env_mesh, mesh_sink)
        env_mesh.execute("ties-mesh")

        local_rows = rows_of(local_sink)
        assert local_rows == rows_of(mesh_sink)
        # all four tying keys survive the distributed threshold
        keys_out = {dict(r)["key"] for r in local_rows}
        assert keys_out == {3, 40, 77, 90}

    def test_sum_aggregate_sharded_matches_local(self):
        def build(env, sink):
            (env.from_source(GeneratorSource(source(6, 64, seed=9)),
                             WatermarkStrategy.for_bounded_out_of_orderness(500))
             .key_by("k")
             .window(TumblingEventTimeWindows.of(2_000))
             .sum("v")
             .add_sink(sink))

        env_local, local_sink = make_env(), CollectSink()
        build(env_local, local_sink)
        env_local.execute("sum-local")

        env_mesh, mesh_sink = make_env(mesh="all"), CollectSink()
        build(env_mesh, mesh_sink)
        env_mesh.execute("sum-mesh")

        assert rows_of(local_sink) == rows_of(mesh_sink)

    def test_mesh_devices_n_selects_subset(self):
        env = make_env(mesh="4")
        mp = env.build_mesh_plan()
        assert mp.n_devices == 4
        assert make_env(mesh="1").build_mesh_plan() is None
        assert make_env().build_mesh_plan() is None


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8-device mesh")
class TestExchangeNoLoss:
    def test_skewed_keys_tiny_capacity_exact_results(self):
        """Worst-case skew: ONE key (every record routes to one shard on
        one device) with exchange capacity 8. The host-side batch split
        must deliver every record — exact counts, zero overflow — where
        the counted-drop design silently lost data (round-2 weakness)."""
        def gen(split, i):
            if i >= 4:
                return None
            rng = np.random.default_rng(i)
            b = 192
            return ({"k": np.zeros(b, np.int64)},
                    np.sort(rng.integers(i * 700, i * 700 + 1400, b)).astype(np.int64))

        def build(env, sink):
            (env.from_source(GeneratorSource(gen),
                             WatermarkStrategy.for_bounded_out_of_orderness(500))
             .key_by("k")
             .window(TumblingEventTimeWindows.of(1_000))
             .count()
             .add_sink(sink))

        env_local, local_sink = make_env(), CollectSink()
        build(env_local, local_sink)
        env_local.execute("skew-local")

        env_mesh, mesh_sink = make_env(
            mesh="all", extra={"pipeline.exchange-capacity": 8}), CollectSink()
        build(env_mesh, mesh_sink)
        res = env_mesh.execute("skew-mesh")

        assert rows_of(local_sink) == rows_of(mesh_sink)
        assert sum(int(r["count"]) for r in mesh_sink.rows) == 4 * 192
        assert res.metrics.get("exchange_overflow", 0) == 0

    def test_mixed_skew_capacity_split_matches_local(self):
        """Hot key + long tail under a small capacity: split batches
        must still aggregate identically to the local path."""
        def gen(split, i):
            if i >= 5:
                return None
            rng = np.random.default_rng(100 + i)
            b = 256
            hot = rng.random(b) < 0.7
            keys = np.where(hot, 7, rng.integers(0, 50, b)).astype(np.int64)
            return ({"k": keys},
                    np.sort(rng.integers(i * 700, i * 700 + 1400, b)).astype(np.int64))

        def build(env, sink):
            (env.from_source(GeneratorSource(gen),
                             WatermarkStrategy.for_bounded_out_of_orderness(500))
             .key_by("k")
             .window(TumblingEventTimeWindows.of(2_000))
             .count()
             .add_sink(sink))

        env_local, local_sink = make_env(), CollectSink()
        build(env_local, local_sink)
        env_local.execute("mix-local")

        env_mesh, mesh_sink = make_env(
            mesh="all", extra={"pipeline.exchange-capacity": 16}), CollectSink()
        build(env_mesh, mesh_sink)
        env_mesh.execute("mix-mesh")

        assert rows_of(local_sink) == rows_of(mesh_sink)

    def test_split_invariant_padded_layout(self):
        """Property check on the splitter itself: every accepted chunk,
        re-bucketed with the PADDED dispatch layout (block length
        target // n_dev — what the device-side arrival split uses),
        stays within capacity. Guards the check-vs-dispatch layout
        mismatch class of bug directly."""
        from flink_tpu.ops.aggregates import count
        from flink_tpu.ops.window import WindowOperator
        from flink_tpu.parallel.mesh import make_mesh_plan

        mp = make_mesh_plan(num_shards=32, slots_per_shard=16)
        op = WindowOperator(TumblingEventTimeWindows.of(1_000), count(),
                            num_shards=32, slots_per_shard=16,
                            max_out_of_orderness_ms=500,
                            mesh_plan=mp, exchange_capacity=4)
        rng = np.random.default_rng(7)
        ring, spd, n_dev = op.plan.ring, mp.slots_per_device, mp.n_devices
        for trial in range(6):
            b = int(rng.integers(3, 400))
            # heavy skew: most records pack into few slots
            slots = np.where(rng.random(b) < 0.8, 0,
                             rng.integers(0, 32 * 16, b))
            pk = (slots * ring + rng.integers(0, ring, b)).astype(np.int64)
            chunks = op._split_for_exchange(pk, {"v": np.ones(b)}, n_dev)
            got = np.concatenate([c[0] for c in chunks])
            assert np.array_equal(np.sort(got), np.sort(pk))  # no loss
            for cpk, _, target in chunks:
                assert target % n_dev == 0 and target >= len(cpk)
                L = target // n_dev
                dest = (cpk // ring) // spd
                block = np.arange(len(cpk)) // L
                flat = block * n_dev + dest
                counts = np.bincount(flat, minlength=n_dev * n_dev)
                assert counts.max(initial=0) <= 4 or len(cpk) == 1

    def test_negative_exchange_capacity_rejected(self):
        env = make_env(mesh="all",
                       extra={"pipeline.exchange-capacity": -1})
        sink = CollectSink()
        build_q5_shape(env, sink, n_batches=1)
        with pytest.raises(ValueError, match="exchange-capacity"):
            env.execute("bad-cap")
