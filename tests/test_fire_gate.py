"""The fused step's fire gate and the announced step tokens.

The contract under test:

- The fused step program runs its fire/top-n/ring-append subgraph (and
  the pane purge) under a device-side ``lax.cond`` keyed on the
  dispatch header's window-end list. The allowed-lateness REFIRE path
  must gate correctly: a late-within-lateness record re-fires its
  already-fired window, and that refire rides the header's end list
  exactly like a first fire — the gate must never suppress it. The
  golden is the window semantics in plain numpy.
- Coalesced readback: every step announces a tiny token; a landed
  fused-step token carries the emit ring's head counters, so an
  opportunistic drain poll that provably has nothing to fetch skips
  the device round trip (prof["drain_skips"]) — and a later
  row-carrying fire re-arms the fetch.
- ``pipeline.fire-gate``, ``pipeline.readiness`` and (PR 46)
  ``pipeline.sub-batches`` are gone: a conf that still sets them is
  told so as for any unknown key
  (CONFIG_KEY_UNKNOWN, warn), by the analyzer, the CLI and the submit
  gate, and the job runs as it does without them.
"""
import numpy as np
import pytest

from flink_tpu.api.environment import StreamExecutionEnvironment
from flink_tpu.api.sinks import FnSink
from flink_tpu.api.sources import GeneratorSource
from flink_tpu.api.windowing import TumblingEventTimeWindows
from flink_tpu.config import Configuration
from flink_tpu.time.watermarks import WatermarkStrategy

pytestmark = pytest.mark.firegate


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


def _conf(extra=None):
    conf = {
        "analysis.fail-on": "off",
        "pipeline.microbatch-size": 1024,
        "state.num-key-shards": 128,
        "state.slots-per-shard": 64,
    }
    conf.update(extra or {})
    return conf


def _assert_identical_in_order(golden, got, ctx):
    assert set(got) == set(golden), ctx
    assert len(golden["window_end"]) > 0, ctx
    for f in sorted(golden):
        assert np.array_equal(np.asarray(golden[f]), np.asarray(got[f])), \
            (ctx, f)


class TestHostFedLateRefire:
    """The allowed-lateness refire path on the HOST-FED fused plane: a
    late-within-lateness record re-fires its already-fired window with
    corrected contents, and the gate predicate must include that refire
    in the header's end list — the rows are the numpy golden's."""

    N_KEYS = 16
    TOP = 4
    N = 1024   # a batch the fused scan takes

    @staticmethod
    def _gen(split, i):
        # batch 0: window [0, 1000); batch 1: ts ~2500 advances the
        # watermark past the window end (it fires); batch 2: LATE
        # records at ts 500 (within lateness) → the fired window must
        # RE-fire with counts corrected; batch 3 moves the watermark on
        # (no new window end), so the refire rides that advance's step
        if i >= 4:
            return None
        n = TestHostFedLateRefire.N
        rng = np.random.default_rng(42 + i)
        keys = rng.integers(0, TestHostFedLateRefire.N_KEYS, n)
        ts = (rng.integers(0, 1_000, n), rng.integers(2_400, 2_600, n),
              np.full(n, 500), rng.integers(2_600, 2_800, n))[i]
        return {"auction": keys.astype(np.int64),
                "price": np.ones(n, np.int64)}, ts.astype(np.int64)

    def _run(self, extra=None):
        cat, sink = _capture_sink()
        env = StreamExecutionEnvironment(Configuration(_conf(extra)))
        stream = env.from_source(
            GeneratorSource(self._gen),
            WatermarkStrategy.for_bounded_out_of_orderness(0))
        top = (stream.key_by("auction")
               .window(TumblingEventTimeWindows.of(1_000))
               .allowed_lateness(10_000)
               .count()
               .top(self.TOP, by="count"))
        top.add_sink(sink)
        env.execute("late-refire")
        return cat(), env

    def _golden_emissions(self):
        """The emissions the window semantics prescribe, in plain
        numpy, as sorted (window_end, key, count) rows per emission:
        window 1000 fires once batch 1 has moved the watermark past
        it, RE-fires with the late batch's records added once batch 3
        moves the watermark again, and window 3000 fires at the end of
        input; each emission keeps the keys whose count reaches the
        4th largest (ties kept)."""
        batches = [self._gen("0", i) for i in range(4)]

        def top(window_end, upto):
            cnt = np.zeros(self.N_KEYS, np.int64)
            for data, ts in batches[:upto]:
                m = (ts // 1_000 + 1) * 1_000 == window_end
                cnt += np.bincount(data["auction"][m],
                                   minlength=self.N_KEYS)
            thresh = np.sort(cnt)[-self.TOP]
            return sorted((window_end, int(key), int(cnt[key]))
                          for key in np.flatnonzero(
                              (cnt >= thresh) & (cnt > 0)))

        return [top(1_000, 1), top(1_000, 3), top(3_000, 4)]

    def test_refire_survives_gating(self, monkeypatch):
        from flink_tpu.ops.window import WindowOperator

        fused = []   # (window ends handed over, the fused step took them)
        advance_fused = WindowOperator._advance_fused

        def spy(op, wm, ends):
            out = advance_fused(op, wm, ends)
            fused.append((len(ends), out is not None))
            return out

        monkeypatch.setattr(WindowOperator, "_advance_fused", spy)
        got, _ = self._run()
        rows = list(zip(got["window_end"].tolist(), got["key"].tolist(),
                        got["count"].tolist()))
        first, refire, last = self._golden_emissions()
        # the late batch must change the window's answer, or the
        # refire is not observable and this test is vacuous
        assert first != refire
        n1, n2 = len(first), len(first) + len(refire)
        assert sorted(rows[:n1]) == first
        assert sorted(rows[n1:n2]) == refire
        assert sorted(rows[n2:]) == last
        # and both firings of window 1000 rode the gated fused step:
        # the first beside the empty window 2000, the refire alone
        assert [n for n, took in fused if took and n] == [2, 1], fused


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
            top_n=("count", 2))

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
        # rows. A fire that carries rows announces its own version
        # (PR 42), whatever the announce cadence: once it has landed an
        # opportunistic poll reads it.
        op.emit_ring.announce_interval_s = float("inf")
        feed_and_fire(6)
        op.quiesce()
        nxt = op.drain_ring(min_no=0)
        assert len(nxt["window_end"]) > 0

    def test_every_step_announces_a_token_the_throttle_consumes(self):
        """Every dispatched step — the ingest applies as much as the
        fused advance — leaves one announced token on the in-flight
        deque; retiring it is a consume of that copy, and only the
        fused step's token carries ring-head words."""
        op = self._op()
        op.max_inflight_steps = 10**6    # nothing retires by itself
        rng = np.random.default_rng(7)
        for i in range(3):
            op.process_batch(rng.integers(0, 100, 2048),
                             rng.integers(i * 2_000, i * 2_000 + 2_000,
                                          2048), {})
            op.advance_watermark(i * 2_000 + 1_999)
        steps = list(op._inflight)
        assert len(steps) == op._token_seq >= 3
        assert [seq for _, _, seq in steps] == list(
            range(1, len(steps) + 1))
        heads = [head for _, head, _ in steps]
        assert set(heads) <= {None, (0, 1)} and (0, 1) in heads
        for token, _, _ in steps:
            assert np.asarray(token).size >= 1   # a landed host copy
        op.quiesce()
        assert not op._inflight

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
        op.drain_ring(min_no=op.emit_ring.version_no)
        assert op.prof.get("drain_skips", 0.0) == skips


class TestRemovedOptions:
    """``pipeline.fire-gate``, ``pipeline.readiness`` and
    ``pipeline.sub-batches`` no longer exist: a conf that still sets
    them gets the treatment of any key outside the option grammar."""

    OLD = {"pipeline.fire-gate": False, "pipeline.readiness": "probe",
           "pipeline.sub-batches": 4}

    @pytest.mark.parametrize("key", sorted(OLD))
    def test_analyzer_reports_an_unknown_key(self, key):
        from flink_tpu.analysis import analyze_config

        fs = analyze_config(Configuration(
            {key: self.OLD[key], "pipeline.microbatch-size": 1024}))
        (f,) = fs
        assert (f.rule, f.severity) == ("CONFIG_KEY_UNKNOWN", "warn")
        assert repr(key) in f.message

    def test_analyze_cli_warns_and_exits_zero(self, tmp_path):
        import os
        import subprocess
        import sys

        conf = tmp_path / "old.conf"
        conf.write_text("pipeline.fire-gate: false\n"
                        "pipeline.readiness: probe\n"
                        "pipeline.sub-batches: 4\n"
                        "pipeline.microbatch-size: 1024\n")
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        root = os.path.join(os.path.dirname(__file__), "..")

        def run(*flags):
            return subprocess.run(
                [sys.executable, "-m", "flink_tpu", "analyze", str(conf),
                 *flags], capture_output=True, text=True, timeout=240,
                env=env, cwd=root)

        proc = run()
        assert proc.returncode == 0, proc.stderr[-2000:]
        for key in self.OLD:
            assert f"CONFIG_KEY_UNKNOWN at config: config key '{key}'" \
                in proc.stdout
        assert run("--fail-on", "warn").returncode == 1

    def test_driver_runs_the_job_and_records_the_findings(self):
        # through the submit gate (analysis.fail-on at its default):
        # the job is admitted, runs as it does without the three keys
        # (one step a batch: nothing slices it), and the findings land
        # on the driver
        t = TestHostFedLateRefire()
        golden, plain = t._run({"analysis.fail-on": "error"})
        got, env = t._run({**self.OLD, "analysis.fail-on": "error"})
        _assert_identical_in_order(golden, got, "removed options")
        assert (env._driver.metrics["batches"]
                == plain._driver.metrics["batches"] == 4)
        unknown = [f for f in env._driver.analysis_findings
                   if f.rule == "CONFIG_KEY_UNKNOWN"]
        assert len(unknown) == len(self.OLD) == 3 and all(
            f.severity == "warn" for f in unknown)

    @pytest.mark.parametrize("extra, credit", [
        ({"pipeline.max-inflight-steps": 6}, 6),
        ({"pipeline.max-inflight-steps": 6,
          "session.concurrent-jobs": 3}, 2),
        ({"pipeline.max-inflight-steps": 6,
          "pipeline.sub-batches": 4}, 6),
    ], ids=["configured", "a-session-share", "the-dead-key-set"])
    def test_the_in_flight_credit_is_the_configured_one(self, extra,
                                                        credit):
        # pipeline.max-inflight-steps, divided by the session's share
        # where one is set, and nothing multiplied in (the slices of a
        # batch used to multiply it)
        from flink_tpu.ops.window import WindowOperator

        _, env = TestHostFedLateRefire()._run(extra)
        (op,) = [op for op in env._driver._ops.values()
                 if isinstance(op, WindowOperator)]
        assert op.max_inflight_steps == credit
