"""Fire-gated dispatch + piggybacked completion (ISSUE 15).

The contract under test, exactly as shipped:

- ``pipeline.fire-gate`` wraps the fused/devgen step programs' fire/
  top-n/ring-append subgraph (and the pane purge) in a device-side
  ``lax.cond`` keyed on the dispatch header's window-end list. The
  gate only ever skips provably-no-op work, so COMMITTED OUTPUT IS
  BYTE-IDENTICAL — including row order on the devgen path — with the
  gate on vs off at every sub-batch count (the tier-1 identity bar).
- The allowed-lateness REFIRE path must gate correctly: a late-within-
  lateness record re-fires its already-fired window, and that refire
  rides the header's end list exactly like a first fire — gating must
  never suppress it.
- ``pipeline.readiness`` flips HOW the throttle learns a step is done
  (piggybacked announced-token consume vs legacy is_ready spin) and
  nothing else: committed rows are identical across modes.
- Coalesced readback: a landed token carries the emit ring's head
  counters, so an opportunistic drain poll that provably has nothing
  to fetch skips the device round trip (prof["drain_skips"]) — and a
  later row-carrying fire re-arms the fetch.
- FIRE_GATE_INVALID (warn) flags gating forced off under sub-batching;
  READINESS_INVALID (error) flags unknown readiness values, which the
  driver also rejects at build.
"""
import numpy as np
import pytest

from flink_tpu.api.environment import StreamExecutionEnvironment
from flink_tpu.api.sinks import FnSink
from flink_tpu.api.sources import GeneratorSource
from flink_tpu.api.windowing import TumblingEventTimeWindows
from flink_tpu.config import Configuration
from flink_tpu.nexmark.generator import NexmarkConfig, bid_stream_device
from flink_tpu.nexmark.queries import q5_hot_items
from flink_tpu.time.watermarks import WatermarkStrategy

pytestmark = pytest.mark.firegate

Q5_CFG = dict(batch_size=4096, n_batches=6, events_per_ms=100,
              num_active_auctions=500, hot_ratio=4)


def _capture_sink():
    rows = []

    def cap(b):
        if len(b.get("window_end", ())):
            rows.append({k: np.asarray(v).copy() for k, v in b.items()})

    def cat():
        if not rows:
            return {}
        return {k: np.concatenate([r[k] for r in rows]) for k in rows[0]}

    return cat, FnSink(cap)


def _control_conf(k, fire_gate, readiness, extra=None):
    conf = {
        "analysis.fail-on": "off",
        "pipeline.microbatch-size": Q5_CFG["batch_size"],
        "state.num-key-shards": 128,
        "state.slots-per-shard": 64,
        "pipeline.sub-batches": k,
        "pipeline.fire-gate": fire_gate,
        "pipeline.readiness": readiness,
    }
    conf.update(extra or {})
    return conf


def _run_devgen_q5(k, fire_gate=True, readiness="piggyback"):
    cat, sink = _capture_sink()
    env = StreamExecutionEnvironment(Configuration(
        _control_conf(k, fire_gate, readiness)))
    q5_hot_items(env, bid_stream_device(NexmarkConfig(**Q5_CFG)), sink,
                 window_ms=10_000, slide_ms=1_000,
                 out_of_orderness_ms=1_000)
    res = env.execute(f"q5-gate-{fire_gate}-{readiness}-k{k}")
    return cat(), res.metrics


def _assert_identical_in_order(golden, got, ctx):
    assert set(got) == set(golden), ctx
    assert len(golden["window_end"]) > 0, ctx
    for f in sorted(golden):
        assert np.array_equal(np.asarray(golden[f]), np.asarray(got[f])), \
            (ctx, f)


class TestDevgenGateIdentity:
    """Devgen Q5 (the headline path): committed rows byte-identical
    INCLUDING ROW ORDER with fire-gating on vs off at K ∈ {1, 2, 4} —
    the gate skips work only on steps where the fire subgraph is a
    provable no-op."""

    def test_gate_on_off_byte_identical_k_1_2_4(self):
        for k in (1, 2, 4):
            golden, _ = _run_devgen_q5(k, fire_gate=False,
                                       readiness="probe")
            gated, m = _run_devgen_q5(k, fire_gate=True,
                                      readiness="piggyback")
            _assert_identical_in_order(golden, gated, f"K={k}")

    def test_gate_alone_identical_same_readiness(self):
        # isolate the gate axis: same readiness on both sides
        golden, _ = _run_devgen_q5(4, fire_gate=False,
                                   readiness="piggyback")
        gated, _ = _run_devgen_q5(4, fire_gate=True,
                                  readiness="piggyback")
        _assert_identical_in_order(golden, gated, "gate-axis")


class TestReadinessParity:
    """pipeline.readiness changes how the throttle waits, nothing
    else: committed rows identical across modes (gate held constant)."""

    def test_piggyback_vs_probe_identical(self):
        golden, _ = _run_devgen_q5(4, fire_gate=True, readiness="probe")
        got, _ = _run_devgen_q5(4, fire_gate=True, readiness="piggyback")
        _assert_identical_in_order(golden, got, "readiness-axis")


class TestHostFedLateRefire:
    """The allowed-lateness refire path on the HOST-FED fused plane: a
    late-within-lateness record re-fires its already-fired window with
    corrected contents, and the gate predicate must include that refire
    in the header's end list — identical output gated vs ungated."""

    N_KEYS = 16

    @staticmethod
    def _gen(split, i):
        # batch 0: window [0, 1000); batch 1: ts ~2500 advances the
        # watermark past the window end (it fires); batch 2: a LATE
        # record at ts 500 (within lateness) → the fired window must
        # RE-fire with count corrected
        if i >= 3:
            return None
        n = 256
        rng = np.random.default_rng(42 + i)
        keys = rng.integers(0, TestHostFedLateRefire.N_KEYS, n)
        if i == 0:
            ts = rng.integers(0, 1_000, n)
        elif i == 1:
            ts = rng.integers(2_400, 2_600, n)
        else:
            keys = keys[:8]
            ts = np.full(8, 500, np.int64)
        return {"auction": keys.astype(np.int64),
                "price": np.ones(len(keys), np.int64)}, ts.astype(np.int64)

    def _run(self, k, fire_gate, readiness="piggyback"):
        cat, sink = _capture_sink()
        env = StreamExecutionEnvironment(Configuration(_control_conf(
            k, fire_gate, readiness,
            extra={"pipeline.microbatch-size": 256})))
        stream = env.from_source(
            GeneratorSource(self._gen),
            WatermarkStrategy.for_bounded_out_of_orderness(0))
        top = (stream.key_by("auction")
               .window(TumblingEventTimeWindows.of(1_000))
               .allowed_lateness(10_000)
               .count()
               .top(4, by="count"))
        top.add_sink(sink)
        env.execute(f"late-refire-{fire_gate}-k{k}")
        return cat()

    def test_refire_survives_gating(self):
        for k in (1, 2):
            golden = self._run(k, fire_gate=False, readiness="probe")
            gated = self._run(k, fire_gate=True)
            # the late batch must actually have produced a refire (two
            # emissions of window_end=1000), or this test is vacuous
            we = np.asarray(golden["window_end"])
            assert (we == 1_000).sum() >= 2, "no refire in the golden"
            _assert_identical_in_order(golden, gated, f"refire K={k}")


class TestCoalescedReadback:
    """The piggybacked ring head: a landed token lets an opportunistic
    drain poll skip a provably-empty fetch; a row-carrying fire re-arms
    the fetch (no stale-skip row loss possible)."""

    def _op(self):
        from flink_tpu.api.windowing import SlidingEventTimeWindows
        from flink_tpu.ops import aggregates
        from flink_tpu.ops.window import WindowOperator

        return WindowOperator(
            SlidingEventTimeWindows.of(10_000, 1_000),
            aggregates.count(), num_shards=16, slots_per_shard=32,
            top_n=("count", 2), fire_gate=True, readiness="piggyback")

    def test_skip_then_rearm(self):
        op = self._op()
        rng = np.random.default_rng(5)

        def feed_and_fire(i):
            keys = rng.integers(0, 100, 2048)
            ts = rng.integers(i * 2_000, i * 2_000 + 2_000, 2048)
            op.process_batch(keys, ts, {})
            return op.advance_watermark(i * 2_000 + 1_999)

        feed_and_fire(5)  # first fire appends rows to the ring
        op.quiesce()      # retires every step → tokens consumed
        first = op.drain_ring(min_no=0)
        assert len(first["window_end"]) > 0
        skips0 = op.prof.get("drain_skips", 0.0)
        # nothing appended since: the poll must skip the fetch
        empty = op.drain_ring(min_no=0)
        assert len(empty["window_end"]) == 0
        assert op.prof.get("drain_skips", 0.0) == skips0 + 1
        # a new row-carrying fire re-arms the fetch — the head fact
        # goes stale at the fire and is only re-trusted once the
        # fire-covering token lands, so the poll can never stale-skip
        # rows. (Whether THIS opportunistic poll sees the rows depends
        # on the announce cadence, exactly as before the gate; the
        # barrier drain proves they are there.)
        feed_and_fire(6)
        op.quiesce()
        nxt = op.drain_ring(min_no=op._ring_version_no)
        assert len(nxt["window_end"]) > 0

    def test_barrier_drain_never_skips(self):
        op = self._op()
        rng = np.random.default_rng(6)
        keys = rng.integers(0, 100, 2048)
        op.process_batch(keys, rng.integers(0, 2_000, 2048), {})
        op.advance_watermark(1_999)
        op.quiesce()
        op.drain_ring(min_no=0)
        skips = op.prof.get("drain_skips", 0.0)
        # a barrier drain pins a version: it must fetch, not skip
        op.drain_ring(min_no=op._ring_version_no)
        assert op.prof.get("drain_skips", 0.0) == skips


class TestValidation:
    def test_driver_rejects_unknown_readiness(self):
        cat, sink = _capture_sink()
        env = StreamExecutionEnvironment(Configuration(_control_conf(
            1, True, "telepathy")))
        q5_hot_items(env, bid_stream_device(NexmarkConfig(**Q5_CFG)),
                     sink, window_ms=10_000, slide_ms=1_000)
        with pytest.raises(ValueError, match="pipeline.readiness"):
            env.execute("bad-readiness")

    def test_operator_rejects_unknown_readiness(self):
        from flink_tpu.api.windowing import TumblingEventTimeWindows as T
        from flink_tpu.ops import aggregates
        from flink_tpu.ops.window import WindowOperator

        with pytest.raises(ValueError, match="pipeline.readiness"):
            WindowOperator(T.of(1_000), aggregates.count(),
                           readiness="bogus")

    def test_analyzer_unknown_readiness_is_error(self):
        from flink_tpu.analysis import analyze_config

        fs = analyze_config(Configuration({
            "pipeline.readiness": "telepathy"}))
        (f,) = [f for f in fs if f.rule == "READINESS_INVALID"]
        # build-rejected config blocks at submit under the default gate
        assert f.severity == "error" and "readiness" in f.message

    def test_analyzer_gate_off_under_subbatching_arm(self):
        from flink_tpu.analysis import analyze_config

        fs = analyze_config(Configuration({
            "pipeline.fire-gate": False,
            "pipeline.sub-batches": 4}))
        assert any(f.rule == "FIRE_GATE_INVALID"
                   and "fire-gate" in f.message for f in fs)

    def test_analyzer_clean_negatives(self):
        from flink_tpu.analysis import analyze_config

        # defaults are clean; gate off at K=1 is a legal A/B axis
        for conf in ({}, {"pipeline.fire-gate": False},
                     {"pipeline.readiness": "probe",
                      "pipeline.sub-batches": 4}):
            fs = analyze_config(Configuration(conf))
            assert not [f for f in fs if f.rule == "FIRE_GATE_INVALID"], \
                conf
