"""Unit tests for checkpoint/repartition.py — the key-group state
repartition plane (ref: StateAssignmentOperation round-trip coverage).

Scheme: EQUIVALENCE BY ROUTING. A reference operator fed every record
must behave identically to a fleet of N per-process operators fed
hash-routed shares whose savepoints were fused by ``merge_payloads`` —
both when merging down (2 -> 1: the merged state continues the
reference timeline) and when splitting up (1 -> 2: the union of the new
processes' emissions equals the reference and nothing fires twice).
"""
import numpy as np
import pytest

from flink_tpu.api.functions import KeyedProcessFunction
from flink_tpu.api.windowing import TumblingEventTimeWindows
from flink_tpu.checkpoint.repartition import RescaleError, merge_payloads
from flink_tpu.exchange.partitioners import hash_shards
from flink_tpu.ops.aggregates import count, sum_of
from flink_tpu.ops.count_window import CountWindowOperator
from flink_tpu.ops.global_agg import GlobalAggregateOperator
from flink_tpu.ops.process import KeyedProcessOperator
from flink_tpu.ops.session import SessionOperator
from flink_tpu.ops.window import WindowOperator
from flink_tpu.state.api import ValueStateDescriptor

NS, SPS = 8, 16           # num_shards, slots_per_shard
R = NS * SPS


# ---------------------------------------------------------------------------
# harness helpers
# ---------------------------------------------------------------------------

def _route(keys, ts, data, n_old):
    """Split one batch into per-old-process shares along shard spans —
    exactly what hybrid_route does across the DCN exchange."""
    owner = hash_shards(np.asarray(keys, np.int64), NS) // (NS // n_old)
    out = []
    for o in range(n_old):
        m = owner == o
        out.append((keys[m], ts[m], {f: v[m] for f, v in data.items()}))
    return out


def _norm(v):
    if isinstance(v, (float, np.floating)):
        return round(float(v), 6)
    return int(v)


def _rows(fired):
    """FiredWindows/dict -> sorted list of value tuples (field order
    fixed by sorted name) for order-insensitive comparison."""
    if fired is None:
        return []
    names = sorted(k for k in fired if not k.startswith("__"))
    if not names:
        return []
    n = len(fired[names[0]])
    return sorted(tuple(_norm(np.asarray(fired[f])[i]) for f in names)
                  for i in range(n))


def _batch(seed, t0, n=64, n_keys=24):
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, n_keys, n).astype(np.int64)
    ts = rng.integers(t0, t0 + 1000, n).astype(np.int64)
    return keys, ts, {"v": rng.random(n)}


def _payload(ops, pid, nproc, ckpt=3):
    """A driver-shaped savepoint payload wrapping real operator snaps."""
    spp = NS // nproc
    return {
        "sources": {"src": {i: 100 * pid + i for i in range(2)}},
        "sub_factors": {"src": 1},
        "wm_gens": {"src": [("gen", pid, i) for i in range(2)]},
        "max_ts": {"src": 1000 + pid},
        "out_wm": {"src": 900 - pid},
        "operators": ops,
        "op_versions": {"w": 1},
        "partitioners": {"rr": 7},
        "sinks": {},
        "metrics": {"records": 10 * (pid + 1), "name": f"p{pid}"},
        "checkpoint_id": ckpt + pid,
        "rescale": {"nproc": nproc, "pid": pid, "num_shards": NS,
                    "shard_range": [pid * spp, (pid + 1) * spp]},
    }


def _merge(payloads, new_pid, new_nproc, kinds):
    return merge_payloads(payloads, new_pid=new_pid, new_nproc=new_nproc,
                          num_shards=NS, slots_per_shard=SPS,
                          op_kinds=kinds)


# ---------------------------------------------------------------------------
# device window operator (factory kind "window")
# ---------------------------------------------------------------------------

class TestWindowRescale:
    def _mk(self, shard_range=None):
        return WindowOperator(TumblingEventTimeWindows.of(1000),
                              sum_of("v"), num_shards=NS,
                              slots_per_shard=SPS, shard_range=shard_range)

    def test_merge_down_continues_reference_timeline(self):
        """2 ranged processes -> 1 full process: pre-cut fires match and
        the merged state finishes the open windows exactly like the
        never-rescaled reference."""
        ref = self._mk()
        olds = [self._mk((0, 4)), self._mk((4, 8))]
        got_ref, got_old = [], []
        for seed, t0, wm in [(1, 0, None), (2, 1000, 1500)]:
            keys, ts, data = _batch(seed, t0)
            ref.process_batch(keys, ts, data)
            for op, (k, t, d) in zip(olds, _route(keys, ts, data, 2)):
                op.process_batch(k, t, d)
            if wm is not None:
                got_ref += _rows(ref.advance_watermark(wm))
                for op in olds:
                    got_old += _rows(op.advance_watermark(wm))
        assert sorted(got_old) == sorted(got_ref)  # pre-cut equivalence

        payloads = [_payload({"w": op.snapshot_state()}, pid, 2)
                    for pid, op in enumerate(olds)]
        merged = _merge(payloads, 0, 1, {"w": "window"})
        new = self._mk()
        new.restore_state(merged["operators"]["w"])

        keys, ts, data = _batch(3, 2000)
        ref.process_batch(keys, ts, data)
        new.process_batch(keys, ts, data)
        assert (_rows(new.advance_watermark(5000))
                == _rows(ref.advance_watermark(5000)))

    def test_split_up_no_window_fires_twice(self):
        """1 full process -> 2 ranged: every open window fires on exactly
        one new process and the union equals the reference."""
        ref = self._mk()
        keys, ts, data = _batch(4, 0)
        ref.process_batch(keys, ts, data)
        payload = _payload({"w": ref.snapshot_state()}, 0, 1)

        news = []
        for pid in (0, 1):
            merged = _merge([payload], pid, 2, {"w": "window"})
            op = self._mk((pid * 4, (pid + 1) * 4))
            op.restore_state(merged["operators"]["w"])
            news.append(op)

        keys, ts, data = _batch(5, 1000)
        ref.process_batch(keys, ts, data)
        for op, (k, t, d) in zip(news, _route(keys, ts, data, 2)):
            op.process_batch(k, t, d)
        got = []
        for op in news:
            got += _rows(op.advance_watermark(2500))
        exp = _rows(ref.advance_watermark(2500))
        assert sorted(got) == exp  # equality <=> union complete, no dupes

    def test_spilled_state_refuses_to_repartition(self):
        olds = [self._mk((0, 4)), self._mk((4, 8))]
        snaps = [op.snapshot_state() for op in olds]
        snaps[1]["spill"] = {"panes": [("pane", 0)]}
        payloads = [_payload({"w": s}, pid, 2)
                    for pid, s in enumerate(snaps)]
        with pytest.raises(RescaleError, match="spill"):
            _merge(payloads, 0, 1, {"w": "window"})

    def test_lsm_spilled_state_repartitions(self, tmp_path):
        """ISSUE 17: the DISK tier's snapshot repartitions where the
        RAM tier refuses — run rows carry their key-group shard, so
        merge-down (2 -> 1) continues the reference timeline with
        host-spilled aggregates intact."""
        def mk(name, shard_range=None):
            from flink_tpu.state.lsm import LsmSpillStore

            store = LsmSpillStore(
                sum_of("v"), store_dir=str(tmp_path / name),
                memory_budget_bytes=0, num_shards=NS)
            return WindowOperator(
                TumblingEventTimeWindows.of(1000), sum_of("v"),
                num_shards=NS, slots_per_shard=SPS,
                shard_range=shard_range, spill_store=store)

        ref = mk("ref")
        olds = [mk("old0", (0, 4)), mk("old1", (4, 8))]
        for seed, t0 in [(1, 0), (2, 1000)]:
            # ~5x the resident capacity: most keys spill to the tier
            keys, ts, data = _batch(seed, t0, n=512, n_keys=600)
            ref.process_batch(keys, ts, data)
            for op, (k, t, d) in zip(olds, _route(keys, ts, data, 2)):
                op.process_batch(k, t, d)

        payloads = [_payload({"w": op.snapshot_state()}, pid, 2)
                    for pid, op in enumerate(olds)]
        assert any(p["operators"]["w"]["spill"]["runs"]
                   for p in payloads), "nothing sealed — vacuous"
        merged = _merge(payloads, 0, 1, {"w": "window"})
        new = mk("new")
        new.restore_state(merged["operators"]["w"])

        keys, ts, data = _batch(3, 2000, n=512, n_keys=600)
        ref.process_batch(keys, ts, data)
        new.process_batch(keys, ts, data)
        assert (_rows(new.advance_watermark(5000))
                == _rows(ref.advance_watermark(5000)))

    def test_lsm_num_shards_mismatch_refuses(self, tmp_path):
        from flink_tpu.state.lsm import LsmSpillStore

        olds = [self._mk((0, 4)), self._mk((4, 8))]
        snaps = [op.snapshot_state() for op in olds]
        store = LsmSpillStore(sum_of("v"),
                              store_dir=str(tmp_path / "s"),
                              memory_budget_bytes=1 << 30,
                              num_shards=NS * 2)  # different key space
        snaps[1]["spill"] = store.snapshot()
        payloads = [_payload({"w": s}, pid, 2)
                    for pid, s in enumerate(snaps)]
        with pytest.raises(RescaleError, match="num_shards"):
            _merge(payloads, 0, 1, {"w": "window"})

    def test_diverged_pane_rings_refuse_to_splice(self):
        olds = [self._mk((0, 4)), self._mk((4, 8))]
        snaps = [op.snapshot_state() for op in olds]
        snaps[1]["ring"] = snaps[1]["ring"] + 8  # process-local auto-grow
        payloads = [_payload({"w": s}, pid, 2)
                    for pid, s in enumerate(snaps)]
        with pytest.raises(RescaleError, match="ring"):
            _merge(payloads, 0, 1, {"w": "window"})


# ---------------------------------------------------------------------------
# KeyedProcessOperator: named state + user timers
# ---------------------------------------------------------------------------

class _RunningSum(KeyedProcessFunction):
    def process_batch(self, ctx):
        vs = ctx.value_state(ValueStateDescriptor("sum", 0.0))
        order = np.argsort(ctx.slots, kind="stable")
        sl, v = ctx.slots[order], ctx.data["v"][order]
        uniq, starts = np.unique(sl, return_index=True)
        totals = np.add.reduceat(v.astype(np.float64), starts)
        vs[uniq] = vs[uniq] + totals
        ctx.emit({"key": ctx.keys[order][starts], "total": vs[uniq]},
                 ts=ctx.timestamps[order][starts])


class _IdleTimeout(KeyedProcessFunction):
    def __init__(self, gap):
        self.gap = gap

    def process_batch(self, ctx):
        last = ctx.value_state(ValueStateDescriptor("last_ts", -1.0))
        order = np.argsort(ctx.slots, kind="stable")
        sl, ts = ctx.slots[order], ctx.timestamps[order]
        uniq, starts = np.unique(sl, return_index=True)
        mx = np.maximum.reduceat(ts, starts)
        newer = mx > last[uniq]
        last[uniq[newer]] = mx[newer].astype(np.float64)
        ctx.register_event_time_timers(mx[newer] + self.gap,
                                       slots=uniq[newer])

    def on_timer(self, ctx):
        last = ctx.value_state(ValueStateDescriptor("last_ts", -1.0))
        live = last[ctx.slots] + self.gap == ctx.timestamps
        ctx.emit({"key": ctx.keys[live],
                  "idle_since": last[ctx.slots[live]].astype(np.int64)},
                 ts=ctx.timestamps[live])


class TestProcessRescale:
    def test_merge_down_carries_value_state(self):
        ref = KeyedProcessOperator(_RunningSum(), num_shards=NS,
                                   slots_per_shard=SPS)
        olds = [KeyedProcessOperator(_RunningSum(), num_shards=NS,
                                     slots_per_shard=SPS) for _ in range(2)]
        for seed in (10, 11):
            keys, ts, data = _batch(seed, 1000 * seed)
            ref.process_batch(keys, ts, data)
            got = []
            for op, (k, t, d) in zip(olds, _route(keys, ts, data, 2)):
                op.process_batch(k, t, d)
                got += _rows(dict(op.take_fired()))
            assert sorted(got) == _rows(dict(ref.take_fired()))

        payloads = [_payload({"p": op.snapshot_state()}, pid, 2)
                    for pid, op in enumerate(olds)]
        merged = _merge(payloads, 0, 1, {"p": "process"})
        new = KeyedProcessOperator(_RunningSum(), num_shards=NS,
                                   slots_per_shard=SPS)
        new.restore_state(merged["operators"]["p"])

        keys, ts, data = _batch(12, 12000)
        ref.process_batch(keys, ts, data)
        new.process_batch(keys, ts, data)
        # totals continue from the merged per-key sums
        assert _rows(dict(new.take_fired())) == _rows(dict(ref.take_fired()))

    def test_split_up_each_timer_fires_exactly_once(self):
        ref = KeyedProcessOperator(_IdleTimeout(1000), num_shards=NS,
                                   slots_per_shard=SPS)
        keys = np.arange(20, dtype=np.int64)
        ts = (100 + 17 * keys).astype(np.int64)
        ref.process_batch(keys, ts, {})  # arms one timer per key
        payload = _payload({"p": ref.snapshot_state()}, 0, 1)

        news = []
        for pid in (0, 1):
            merged = _merge([payload], pid, 2, {"p": "process"})
            op = KeyedProcessOperator(_IdleTimeout(1000), num_shards=NS,
                                      slots_per_shard=SPS)
            op.restore_state(merged["operators"]["p"])
            news.append(op)

        exp = _rows(dict(ref.advance_watermark(5000)))
        got = []
        for op in news:
            got += _rows(dict(op.advance_watermark(5000)))
        assert len(exp) == len(keys)
        assert sorted(got) == exp  # every key once, on one process only


# ---------------------------------------------------------------------------
# count windows, global aggregate, session windows
# ---------------------------------------------------------------------------

class TestCountWindowRescale:
    def test_merge_down_completes_partial_windows(self):
        def mk():
            return CountWindowOperator(sum_of("v"), 3, num_shards=NS,
                                       slots_per_shard=SPS)

        ref, olds = mk(), [mk(), mk()]
        keys = np.tile(np.arange(16, dtype=np.int64), 2)  # 2 of 3 per key
        ts = np.arange(len(keys), dtype=np.int64)
        data = {"v": np.arange(len(keys), dtype=np.float64)}
        ref.process_batch(keys, ts, data)
        assert _rows(ref.take_fired()) == []  # 2 of 3: nothing fires yet
        for op, (k, t, d) in zip(olds, _route(keys, ts, data, 2)):
            op.process_batch(k, t, d)
            assert _rows(op.take_fired()) == []

        payloads = [_payload({"c": op.snapshot_state()}, pid, 2)
                    for pid, op in enumerate(olds)]
        merged = _merge(payloads, 0, 1, {"c": "count_window"})
        new = mk()
        new.restore_state(merged["operators"]["c"])

        # the 3rd record per key completes windows whose first two
        # records pre-date the rescale cut
        keys2 = np.arange(16, dtype=np.int64)
        ts2 = np.full(16, 99, np.int64)
        data2 = {"v": np.full(16, 0.5)}
        ref.process_batch(keys2, ts2, data2)
        new.process_batch(keys2, ts2, data2)
        assert _rows(new.take_fired()) == _rows(ref.take_fired())


class TestGlobalAggRescale:
    def test_merge_down_upserts_running_totals(self):
        def mk():
            return GlobalAggregateOperator(sum_of("v"), num_shards=NS,
                                           slots_per_shard=SPS)

        ref, olds = mk(), [mk(), mk()]
        keys, ts, data = _batch(20, 0, n_keys=16)
        ref.process_batch(keys, ts, data)
        ref.take_fired()
        for op, (k, t, d) in zip(olds, _route(keys, ts, data, 2)):
            op.process_batch(k, t, d)
            op.take_fired()

        payloads = [_payload({"g": op.snapshot_state()}, pid, 2)
                    for pid, op in enumerate(olds)]
        merged = _merge(payloads, 0, 1, {"g": "global_agg"})
        new = mk()
        new.restore_state(merged["operators"]["g"])

        keys2, ts2, data2 = _batch(21, 1000, n_keys=16)
        ref.process_batch(keys2, ts2, data2)
        new.process_batch(keys2, ts2, data2)
        assert _rows(new.take_fired()) == _rows(ref.take_fired())


    @pytest.mark.parametrize("lane", ["host", "device"])
    def test_merge_down_integer_lanes_and_their_event_times(self, lane):
        """Integer lanes are int64 in the snapshot and an event-time
        lane is a timestamp there, so two processes whose first
        timestamps differ (each holds offsets from its own) merge into
        one state, which either lane of the operator restores."""
        from flink_tpu.ops import aggregates as A
        from flink_tpu.ops.groupagg_device import DeviceGroupAggOperator

        agg = A.multi(A.count(), A.int_sum_of("v", avg_field="avg_v"),
                      A.int_max_of("v"), A.latest_event_time("last"))

        def mk(cls=GlobalAggregateOperator):
            return cls(agg, num_shards=NS, slots_per_shard=SPS)

        def batch(seed, t0):
            keys, ts, _ = _batch(seed, t0, n_keys=16)
            v = np.random.default_rng(seed).integers(0, 2**31 - 1, len(keys))
            return keys, ts + 10**12, {"v": v.astype(np.int64)}

        ref, olds = mk(), [mk(), mk()]
        keys, ts, data = batch(22, 0)
        ref.process_batch(keys, ts, data)
        ref.take_fired()
        for op, (k, t, d) in zip(olds, _route(keys, ts, data, 2)):
            op.process_batch(k, t, d)
            op.take_fired()
        assert olds[0]._base != olds[1]._base
        payloads = [_payload({"g": op.snapshot_state()}, pid, 2)
                    for pid, op in enumerate(olds)]
        merged = _merge(payloads, 0, 1, {"g": "global_agg"})
        snap = merged["operators"]["g"]
        assert snap["sums"].dtype == np.int64
        assert snap["time_base"] == min(op._base for op in olds)
        new = mk(DeviceGroupAggOperator if lane == "device"
                 else GlobalAggregateOperator)
        new.restore_state(snap)
        keys2, ts2, data2 = batch(23, 1000)
        ref.process_batch(keys2, ts2, data2)
        new.process_batch(keys2, ts2, data2)
        got = _rows(new.take_fired())
        assert got == _rows(ref.take_fired())
        assert max(r[-1] for r in got) > 2**31     # a sum past one word


class TestSessionRescale:
    def test_merge_down_closes_open_sessions(self):
        def mk():
            return SessionOperator(1000, count())

        ref, olds = mk(), [mk(), mk()]
        keys, ts, data = _batch(30, 0, n_keys=16)
        ref.process_batch(keys, ts, data)
        for op, (k, t, d) in zip(olds, _route(keys, ts, data, 2)):
            op.process_batch(k, t, d)
        # keep sessions open across the cut
        ref.advance_watermark(500)
        for op in olds:
            op.advance_watermark(500)

        payloads = [_payload({"s": op.snapshot_state()}, pid, 2)
                    for pid, op in enumerate(olds)]
        merged = _merge(payloads, 0, 1, {"s": "session"})
        new = mk()
        new.restore_state(merged["operators"]["s"])

        # extend some sessions post-cut, then close everything
        keys2, ts2, data2 = _batch(31, 800, n_keys=16)
        ref.process_batch(keys2, ts2, data2)
        new.process_batch(keys2, ts2, data2)
        assert (_rows(new.advance_watermark(10_000))
                == _rows(ref.advance_watermark(10_000)))


# ---------------------------------------------------------------------------
# driver plane + savepoint-set validation
# ---------------------------------------------------------------------------

class TestDriverPlaneMerge:
    def _payloads(self):
        ops = []
        for pid in range(2):
            op = KeyedProcessOperator(_RunningSum(), num_shards=NS,
                                      slots_per_shard=SPS)
            ops.append(op)
        keys, ts, data = _batch(40, 0)
        for op, (k, t, d) in zip(ops, _route(keys, ts, data, 2)):
            op.process_batch(k, t, d)
            op.take_fired()
        return [_payload({"p": op.snapshot_state()}, pid, 2)
                for pid, op in enumerate(ops)]

    def test_driver_state_merges_by_rule(self):
        merged = _merge(self._payloads(), 0, 1, {"p": "process"})
        # split position from its old OWNER (owner of split s = s % 2)
        assert merged["sources"]["src"] == {0: 0, 1: 101}
        assert merged["wm_gens"]["src"] == [("gen", 0, 0), ("gen", 1, 1)]
        assert merged["max_ts"]["src"] == 1001    # max
        assert merged["out_wm"]["src"] == 899     # min
        assert merged["metrics"]["records"] == 30  # numeric sum
        assert merged["metrics"]["name"] == "p0"   # first non-numeric
        assert merged["checkpoint_id"] == 4        # max
        assert merged["partitioners"] == {}        # reset on rescale
        assert merged["sinks"] == {}               # committed by savepoint
        assert merged["rescale"] == {"nproc": 1, "pid": 0,
                                     "num_shards": NS,
                                     "shard_range": [0, NS]}

    @pytest.mark.parametrize("factors", [None, {"src": 1}, {"src": 4}],
                             ids=["absent", "ones", "a-factor-of-4"])
    def test_sub_factors_ride_through_to_the_restore(self, factors):
        """The field is an old checkpoint's word on how its source
        positions count; the merge hands it on as it found it, so the
        driver's restore can still refuse a factor other than 1."""
        payloads = self._payloads()
        for p in payloads:
            if factors is None:
                del p["sub_factors"]
            else:
                p["sub_factors"] = dict(factors)
        merged = _merge(payloads, 0, 1, {"p": "process"})
        assert merged["sub_factors"] == (factors or {})

    def test_empty_set_rejected(self):
        with pytest.raises(RescaleError, match="empty"):
            _merge([], 0, 1, {})

    def test_divisibility_enforced(self):
        with pytest.raises(RescaleError, match="divide"):
            _merge(self._payloads(), 0, 3, {"p": "process"})

    def test_foreign_fleet_size_rejected(self):
        payloads = self._payloads()
        payloads[0]["rescale"]["nproc"] = 4
        with pytest.raises(RescaleError, match="4-process"):
            _merge(payloads, 0, 1, {"p": "process"})

    def test_out_of_order_set_rejected(self):
        payloads = self._payloads()
        with pytest.raises(RescaleError, match="out of order"):
            _merge(payloads[::-1], 0, 1, {"p": "process"})

    def test_operator_missing_from_part_of_set_rejected(self):
        payloads = self._payloads()
        del payloads[1]["operators"]["p"]
        with pytest.raises(RescaleError, match="missing"):
            _merge(payloads, 0, 1, {"p": "process"})

    def test_unknown_keyed_kind_rejected(self):
        payloads = self._payloads()
        with pytest.raises(RescaleError, match="no repartition rule"):
            _merge(payloads, 0, 1, {"p": "quantum_window"})

    def test_keyless_kind_taken_verbatim(self):
        payloads = self._payloads()
        for pid, p in enumerate(payloads):
            p["operators"]["a"] = {"marker": pid}
        merged = _merge(payloads, 0, 1,
                        {"p": "process", "a": "window_all"})
        assert merged["operators"]["a"] == {"marker": 0}
