"""The data path's tracing (obs/tracing.py PhaseClock, Driver, WindowOperator):

- the ingest loop's phases are a flat partition of its wall time, the six
  ``phase_breakdown()`` phases are sums of named leaves, and the longest
  single interval is kept per leaf and per run;
- the phases (and ``Tracer.span``) are host events of a ``jax.profiler``
  trace, never nested on the loop thread, none open while the drain waits;
- ``trace.fires``: one record per committed window end, stamped in order;
- the clock itself: switch, count, longest, threads, exceptions.
"""
import glob
import threading
import time

import numpy as np
import pytest

import flink_tpu  # noqa: F401 — x64 before other jax users
from flink_tpu.api.environment import StreamExecutionEnvironment
from flink_tpu.api.sinks import CollectSink
from flink_tpu.api.sources import GeneratorSource
from flink_tpu.config import Configuration
from flink_tpu.nexmark.queries import q5_hot_items
from flink_tpu.obs.tracing import PhaseClock, tracer
from flink_tpu.runtime.driver import FIRE_RECORDS, PHASE_LEAVES

BATCH = 4096
PHASE_PREFIXES = ("ingest.", "window.", "wm.", "drain.", "state.")


def q5_job(n_batches=40, sleep_s=0.0, **conf):
    """A small host-fed Q5 (hot items, 10 s windows sliding by 2 s, 500 ms
    of event time a batch). Every fifth batch repeats its predecessor's
    timestamps, so the watermark stands still and the stashed upload goes
    out as a step of its own (``window.step_dispatch``) instead of riding
    the next fire. Returns (JobResult, committed rows, the driver)."""

    def gen(split, i):
        if i >= n_batches:
            return None
        if sleep_s:
            time.sleep(sleep_s)
        rng = np.random.default_rng(i)
        at = i - 1 if i % 5 == 4 else i
        ts = np.sort(at * 500 + rng.integers(0, 500, BATCH)).astype(np.int64)
        return {"auction": rng.integers(0, 50, BATCH).astype(np.int64)}, ts

    env = StreamExecutionEnvironment(Configuration({
        "pipeline.microbatch-size": BATCH, "state.num-key-shards": 8,
        "state.slots-per-shard": 64, "analysis.fail-on": "off", **conf}))
    sink = CollectSink()
    q5_hot_items(env, GeneratorSource(gen), sink, window_ms=10_000,
                 slide_ms=2_000, out_of_orderness_ms=1_000)
    res = env.execute("q5-phases")
    return res, sink.rows, env._driver


def leaves_of(metrics):
    """leaf -> seconds, from ``profile.phase.<leaf>`` (a leaf's name has a
    dot; the six sums and the run's own keys have none)."""
    pre = "profile.phase."
    return {k[len(pre):]: v for k, v in metrics.items()
            if k.startswith(pre) and k[len(pre):].startswith(PHASE_PREFIXES)
            and not k.endswith(".n")}


@pytest.fixture(scope="module")
def job():
    q5_job(n_batches=12)     # compiles; the measured run is warm
    return q5_job()


class TestJobPhases:
    def test_loop_leaves_sum_to_the_loops_wall_time(self, job):
        m = job[0].metrics
        leaves = leaves_of(m)
        loop = sum(v for k, v in leaves.items() if not k.startswith("drain."))
        wall = m["profile.phase.loop_wall_s"]
        assert wall > 0
        assert abs(loop - wall) <= 0.03 * wall, (loop, wall, leaves)
        for name in ("ingest.source_wait", "ingest.link_wait", "ingest.route",
                     "ingest.throttle", "ingest.bookkeeping", "wm.advance",
                     "window.key_scan", "window.pack", "window.h2d",
                     "window.step_dispatch", "window.fire_dispatch",
                     "drain.fetch", "drain.deliver"):
            assert leaves.get(name, 0.0) > 0.0, name
            assert m[f"profile.phase.{name}.n"] >= 1

    def test_the_six_phases_are_the_sums_of_their_leaves(self, job):
        res, _rows, driver = job
        leaves = leaves_of(res.metrics)
        assert set(driver.phase_breakdown()) == set(PHASE_LEAVES) == {
            "source", "dispatch", "throttle", "drain", "advance", "fire"}
        for phase, names in PHASE_LEAVES.items():
            want = sum(leaves.get(n, 0.0) for n in names)
            assert res.metrics[f"profile.phase.{phase}"] == pytest.approx(
                want, abs=1e-5), phase
            assert driver.phase_breakdown()[phase] == pytest.approx(
                want, abs=1e-5), phase

    def test_longest_interval_per_leaf_and_of_the_run(self, job):
        m = job[0].metrics
        pre = "profile.phase.longest_ms."
        per_leaf = {k[len(pre):]: v for k, v in m.items()
                    if k.startswith(pre)}
        assert set(per_leaf) == set(leaves_of(m))
        assert m["profile.phase.longest_ms"] == max(per_leaf.values()) > 0
        assert 0 <= m["profile.phase.longest_at_s"] <= 60
        for leaf, ms in per_leaf.items():
            # no single interval is longer than the leaf's total
            assert ms <= 1e3 * m[f"profile.phase.{leaf}"] + 1e-3, leaf
        # bench.py's _phase_summary calls float() on every such value
        for k, v in m.items():
            if k.startswith("profile.phase."):
                float(v)

    def test_old_accumulators_are_gone(self, job):
        res, _rows, driver = job
        assert not [k for k in res.metrics if k.startswith("profile.driver.")]
        assert not hasattr(driver, "prof")
        op_keys = {k.split(".", 2)[2] for k in res.metrics
                   if k.startswith("profile.op")}
        assert op_keys <= {"drain_fetch", "drain_fetches", "drain_skips",
                           "preagg_batches", "scan_pane_moves",
                           "scan_ranges", "assign_records",
                           "assign_memo_hits"}
        fetch = sum(v for k, v in res.metrics.items()
                    if k.startswith("profile.op")
                    and k.endswith(".drain_fetch"))
        assert fetch == pytest.approx(
            res.metrics["profile.phase.drain.fetch"], abs=1e-5)

    def test_scan_pane_moves_beside_the_key_scan(self, job):
        """The scan's own counter: an in-order stream costs a division or
        two a batch (one more in a call over newly registered keys), not
        one a record; and it rides along under ``profile.phase.``."""
        m = job[0].metrics
        per_op = {k: v for k, v in m.items()
                  if k.startswith("profile.op")
                  and k.endswith(".scan_pane_moves")}
        assert len(per_op) == 1
        (key, moves), = per_op.items()
        batches = m[key.replace("scan_pane_moves", "preagg_batches")]
        assert batches > 0
        assert batches <= moves <= 4 * batches < BATCH
        assert m["profile.phase.scan_pane_moves"] == moves


class TestFireRecords:
    def test_one_record_per_committed_window_end_in_stamp_order(self, job):
        res, rows, _driver = job
        fires = res.metrics["trace.fires"]
        committed = {int(r["window_end"]) for r in rows}
        assert committed
        ends = [f["window_end"] for f in fires]
        assert len(ends) == len(set(ends))      # in order: nothing refires
        assert committed <= set(ends)
        for f in fires:
            assert f["op"] is not None
            assert (f["t_input"] <= f["t_fire"] <= f["t_fetch0"]
                    <= f["t_fetch1"] <= f["t_sink"]), f

    def test_emit_latency_samples_are_t_sink_minus_t_fire(self, job):
        res, _rows, driver = job
        cohorts = {(f["t_fire"], f["t_sink"])
                   for f in res.metrics["trace.fires"]}
        want = sorted(1e3 * (t_sink - t_fire) for t_fire, t_sink in cohorts)
        got = sorted(driver._lat_hist._samples().tolist())
        assert got == pytest.approx(want)
        assert res.metrics["driver.emit_latency_ms.count"] == len(want)

    def test_the_records_are_bounded(self, job):
        driver = job[2]
        assert driver._fires.maxlen == FIRE_RECORDS == 4096
        kept = list(driver._fires)
        try:
            for i in range(3000):    # two window ends each: 6,000 records
                driver._fires.append({"window_ends": [2 * i, 2 * i + 1],
                                      "op": 0, "t_fire": float(i)})
            records = driver.fire_records()
            assert len(records) == 4096
            assert records[-1]["window_end"] == 5999
            assert records[0]["window_end"] == 5999 - 4095
            assert records[0]["t_sink"] is None
        finally:
            driver._fires.clear()
            driver._fires.extend(kept)


def read_host_lines(trace_dir):
    """The host plane's lines (one a thread), each a list of
    ``(event, start_ns, end_ns, stats)``."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    return [[(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
             for e in ln.events]
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:CPU") for ln in plane.lines]


def test_phases_are_host_events_of_a_profiler_trace(tmp_path):
    """Under ``jax.profiler`` the program's spans are in the trace's host
    plane: flat on the loop thread, work only on the drain thread, and the
    tracer's checkpoint spans beside them. The job runs on a thread of its
    own so that the test has a time limit of its own."""
    import jax

    q5_job(n_batches=12)     # compiles outside the trace
    out = {}

    def traced():
        out["job"] = q5_job(sleep_s=0.004, **{
            "execution.checkpointing.interval": 40,
            "execution.checkpointing.dir": str(tmp_path / "chk")})

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    tracer.clear()
    jax.profiler.start_trace(str(tmp_path / "trace"), profiler_options=opts)
    try:
        worker = threading.Thread(target=traced, daemon=True)
        worker.start()
        worker.join(timeout=120)
        assert not worker.is_alive(), "the traced job did not end in 120 s"
    finally:
        jax.profiler.stop_trace()
    assert "job" in out
    lines = read_host_lines(str(tmp_path / "trace"))

    def program(events):
        return sorted((s, e, n) for n, s, e, _st in events
                      if n.startswith(PHASE_PREFIXES))

    names = {n for evs in lines for n, *_ in evs}
    for want in ("ingest.route", "window.key_scan", "window.step_dispatch",
                 "window.fire_dispatch", "drain.fetch", "drain.deliver"):
        assert want in names, (want, sorted(
            n for n in names if n.startswith(PHASE_PREFIXES)))

    # the loop thread: the one that routes. Its program spans tile its
    # time: each begins where the one before ended, none inside another
    (loop,) = [evs for evs in lines
               if any(n == "ingest.route" for n, *_ in evs)]
    spans = program(loop)
    assert len(spans) > 100
    for (s0, e0, n0), (s1, e1, n1) in zip(spans, spans[1:]):
        assert s1 >= e0, (n0, s0, e0, n1, s1, e1)
    covered = sum(e - s for s, e, _n in spans)
    assert covered >= 0.9 * (spans[-1][1] - spans[0][0])

    # the drain thread spans work only: the source sleeps 4 ms a batch, the
    # drain waits for fires meanwhile, and no span of its covers that
    # (a checkpoint's freeze drains the ring on the loop thread as well)
    (drain,) = [evs for evs in lines if evs is not loop
                and any(n == "drain.deliver" for n, *_ in evs)]
    dspans = program(drain)
    assert {n for _s, _e, n in dspans} == {"drain.fetch", "drain.deliver"}
    for (_s0, e0, _n0), (s1, _e1, _n1) in zip(dspans, dspans[1:]):
        assert s1 >= e0
    busy = sum(e - s for s, e, _n in dspans)
    assert busy < 0.5 * (dspans[-1][1] - dspans[0][0])
    ring = [st for n, _s, _e, st in drain if n == "drain.fetch"]
    assert ring and all("ring" in st for st in ring)

    # Tracer.span joins in: the checkpoint's spans with their attributes,
    # and /traces' durations on the monotonic clock
    freezes = [st for evs in lines for n, _s, _e, st in evs
               if n == "checkpoint.freeze"]
    assert freezes and all("checkpoint_id" in st for st in freezes)
    assert "checkpoint.persist" in names
    recorded = tracer.spans("checkpoint.freeze")
    assert recorded and all(
        s["duration_ms"] is not None and 0 <= s["duration_ms"] < 60_000
        and s["start"] > 1e9 for s in recorded)


class TestPhaseClock:
    def test_switch_count_and_longest(self):
        """Everything is held against the clock readings ``phase()`` and
        ``stop()`` RETURNED, never against how long a sleep came out: a
        loaded host stretches a 2 ms sleep past a 12 ms one."""
        c = PhaseClock()
        t0 = c.phase("a")
        time.sleep(0.002)
        t1 = c.phase("b")
        assert c.open_phase() == "b"
        time.sleep(0.012)
        t2 = c.phase("a")
        assert c.phase("a") >= t2     # already open: still one interval
        # a's second interval is its longest by construction: it stays
        # open until it has outlasted the first, however long that was
        while time.perf_counter() - t2 <= t1 - t0:
            time.sleep(0.004)
        t3 = c.stop()
        assert c.open_phase() is None
        assert c.t_start <= t0 <= t1 <= t2 <= t3
        assert t3 - t2 > t1 - t0
        snap = c.snapshot()
        a, b = snap["a"], snap["b"]
        assert a["count"] == 2 and b["count"] == 1
        assert b["seconds"] == pytest.approx(t2 - t1, abs=1e-9)
        assert a["seconds"] == pytest.approx(
            (t1 - t0) + (t3 - t2), abs=1e-9)
        assert a["seconds"] + b["seconds"] == pytest.approx(
            t3 - t0, abs=1e-9)
        # a's longest is its second interval, which began after b's only
        # one; the first is in a's seconds and not in its longest
        assert a["longest_ms"] == pytest.approx(1e3 * (t3 - t2), abs=1e-6)
        assert a["longest_ms"] < 1e3 * a["seconds"]
        assert a["longest_at_s"] == pytest.approx(
            t2 - c.t_start, abs=1e-9)
        assert b["longest_ms"] == pytest.approx(1e3 * (t2 - t1), abs=1e-6)
        assert b["longest_at_s"] == pytest.approx(
            t1 - c.t_start, abs=1e-9)
        assert a["longest_at_s"] >= b["longest_at_s"] > 0

    def test_a_span_gives_the_previous_phase_back(self):
        c = PhaseClock()
        c.phase("outer")
        with c.span("inner", ring=3) as sp:
            assert c.open_phase() == "inner"
            c.phase("inner.later")      # a block may switch on, flat
        assert c.open_phase() == "outer"
        c.stop()
        snap = c.snapshot()
        assert snap["outer"]["count"] == 2      # split in two, not nested
        assert snap["inner"]["count"] == snap["inner.later"]["count"] == 1
        assert sp.seconds == pytest.approx(
            snap["inner"]["seconds"] + snap["inner.later"]["seconds"])
        with c.span("alone"):
            pass
        assert c.open_phase() is None

    def test_an_exception_leaves_no_phase_open(self):
        c = PhaseClock()
        with pytest.raises(ValueError):
            with c.span("work"):
                raise ValueError("boom")
        assert c.open_phase() is None
        assert c.snapshot()["work"]["count"] == 1

    def test_a_failed_run_leaves_no_phase_open(self):
        def gen(split, i):
            if i == 3:
                raise RuntimeError("source broke")
            ts = np.arange(i * 100, i * 100 + 64, dtype=np.int64)
            return {"auction": ts % 7}, ts

        env = StreamExecutionEnvironment(Configuration({
            "pipeline.microbatch-size": 64, "state.num-key-shards": 8,
            "state.slots-per-shard": 64, "analysis.fail-on": "off",
            "pipeline.source-prefetch": 0}))
        q5_hot_items(env, GeneratorSource(gen), CollectSink(),
                     window_ms=1_000, slide_ms=500)
        with pytest.raises(RuntimeError, match="source broke"):
            env.execute("q5-fails")
        assert env._driver.phases.open_phase() is None
        assert env._driver.phases.snapshot()["ingest.source_wait"]["count"] >= 3

    def test_two_threads_are_independent(self):
        c = PhaseClock()
        ready, go = threading.Barrier(2), threading.Event()

        def other():
            c.phase("other.work")
            ready.wait(timeout=10)
            go.wait(timeout=10)
            c.stop()

        t = threading.Thread(target=other, daemon=True)
        c.phase("main.work")
        t.start()
        ready.wait(timeout=10)
        # the other thread's open phase is not this thread's
        assert c.open_phase() == "main.work"
        c.phase("main.more")
        go.set()
        t.join(timeout=10)
        assert not t.is_alive()
        assert c.open_phase() == "main.more"
        c.stop()
        snap = c.snapshot()
        assert {k: v["count"] for k, v in snap.items()} == {
            "main.work": 1, "main.more": 1, "other.work": 1}
