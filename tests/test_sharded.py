"""Sharded-path tests on a virtual 8-device CPU mesh — the MiniCluster
analogue (SURVEY §5 tier 3/4): keyBy all_to_all, sharded pane state,
parity with the single-device operator, snapshot/restore.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from flink_tpu.api.windowing import SlidingEventTimeWindows, TumblingEventTimeWindows
from flink_tpu.exchange.keyby import bucket_by_destination, keyby_exchange
from flink_tpu.ops.aggregates import count, max_of, multi, sum_of
from flink_tpu.ops.window import WindowOperator
from flink_tpu.parallel.mesh import AXIS, make_mesh_plan
from flink_tpu.utils.jaxcompat import shard_map


pytestmark = pytest.mark.shard_map  # device-mesh suite


@pytest.fixture(scope="module")
def mesh_plan():
    assert len(jax.devices()) == 8, "conftest must force 8 CPU devices"
    return make_mesh_plan(num_shards=32, slots_per_shard=64)


class TestBucketing:
    def test_bucket_by_destination(self):
        dest = jnp.array([2, 0, 2, 1, 0], dtype=jnp.int32)
        valid = jnp.array([True, True, True, False, True])
        payload = {"x": jnp.array([10, 11, 12, 13, 14], dtype=jnp.int64)}
        buckets, bv, overflow = bucket_by_destination(
            dest, valid, payload, n_dest=3, capacity=4)
        assert buckets["x"].shape == (3, 4)
        # dest 0 gets 11, 14; dest 1 nothing (record invalid); dest 2 gets 10, 12
        got0 = sorted(np.asarray(buckets["x"][0])[np.asarray(bv[0])].tolist())
        got1 = np.asarray(bv[1]).sum()
        got2 = sorted(np.asarray(buckets["x"][2])[np.asarray(bv[2])].tolist())
        assert got0 == [11, 14]
        assert got1 == 0
        assert got2 == [10, 12]
        assert np.asarray(overflow).tolist() == [0, 0, 0]

    def test_overflow_counted_not_silent(self):
        dest = jnp.zeros(6, dtype=jnp.int32)
        valid = jnp.ones(6, dtype=bool)
        payload = {"x": jnp.arange(6, dtype=jnp.int64)}
        buckets, bv, overflow = bucket_by_destination(
            dest, valid, payload, n_dest=2, capacity=4)
        assert int(np.asarray(bv[0]).sum()) == 4
        assert np.asarray(overflow).tolist() == [2, 0]


class TestAllToAll:
    def test_exchange_routes_every_record_to_owner(self, mesh_plan):
        n = mesh_plan.n_devices
        b_per_dev = 16

        def step(slot, valid):
            dest = (slot // mesh_plan.slots_per_device).astype(jnp.int32)
            recv, rv, overflow = keyby_exchange(
                dest, valid, {"slot": slot},
                n_devices=n, capacity=b_per_dev)
            my = jax.lax.axis_index(AXIS).astype(jnp.int64)
            ok = (recv["slot"] // mesh_plan.slots_per_device) == my
            misrouted = jnp.sum(jnp.where(rv, ~ok, False))
            return jnp.sum(rv)[None], misrouted[None]

        fn = jax.jit(shard_map(
            step, mesh=mesh_plan.mesh,
            in_specs=(P(AXIS), P(AXIS)),
            out_specs=(P(AXIS), P(AXIS))))

        rng = np.random.default_rng(0)
        slots = rng.integers(0, mesh_plan.total_slots, n * b_per_dev)
        valid = rng.random(n * b_per_dev) < 0.9
        received, misrouted = fn(jnp.asarray(slots), jnp.asarray(valid))
        assert int(np.asarray(received).sum()) == int(valid.sum())
        assert int(np.asarray(misrouted).sum()) == 0


class TestShardedWindowParity:
    """The sharded operator must produce byte-identical emissions to the
    single-device operator for identical input."""

    def _run(self, op, batches, wms):
        out = []
        for (keys, ts, data), wm in zip(batches, wms):
            if keys is not None:
                op.process_batch(keys, ts, data)
            fired = op.advance_watermark(wm)
            for i in range(len(fired["key"])):
                out.append(tuple(
                    (k, float(fired[k][i])) for k in sorted(fired)))
        return sorted(out)

    @pytest.mark.parametrize("case", ["tumbling", "sliding"])
    def test_parity(self, mesh_plan, case):
        if case == "tumbling":
            assigner = TumblingEventTimeWindows.of(1000)
            agg = multi(count(), sum_of("v"), max_of("v"))
        else:
            assigner = SlidingEventTimeWindows.of(5000, 1000)
            agg = count()
        kw = dict(allowed_lateness_ms=1000, max_out_of_orderness_ms=2000)
        local = WindowOperator(assigner, agg,
                               num_shards=mesh_plan.num_shards,
                               slots_per_shard=mesh_plan.slots_per_shard, **kw)
        sharded = WindowOperator(assigner, agg, mesh_plan=mesh_plan, **kw)

        rng = np.random.default_rng(3)
        batches, wms = [], []
        t = 0
        for _ in range(6):
            n = 100
            ts = rng.integers(max(0, t - 2000), t + 1200, n)
            t = max(t, int(ts.max()))
            keys = rng.integers(0, 50, n)
            vals = rng.random(n).astype(np.float32) * 10
            batches.append((keys, ts, {"v": vals}))
            wms.append(t - 2001)
        batches.append((None, None, None))
        wms.append(t + 20_000)

        got_local = self._run(local, batches, wms)
        got_sharded = self._run(sharded, batches, wms)
        assert got_local == got_sharded
        assert sharded.exchange_overflow == 0

    def test_sharded_snapshot_restore(self, mesh_plan):
        assigner = TumblingEventTimeWindows.of(1000)
        op1 = WindowOperator(assigner, count(), mesh_plan=mesh_plan,
                             max_out_of_orderness_ms=2000)
        op1.process_batch(np.array([1, 2, 3]), np.array([500, 600, 700]), {})
        snap = op1.snapshot_state()

        op2 = WindowOperator(assigner, count(), mesh_plan=mesh_plan,
                             max_out_of_orderness_ms=2000)
        op2.restore_state(snap)
        fired = op2.advance_watermark(5000)
        assert sorted(fired["key"].tolist()) == [1, 2, 3]
        assert all(int(c) == 1 for c in fired["count"])
