"""The data path's tracing (obs/tracing.py PhaseClock, Driver, WindowOperator):

- the ingest loop's phases are a flat partition of its wall time, the six
  ``phase_breakdown()`` phases are sums of named leaves, and the longest
  single interval is kept per leaf and per run;
- the phases (and ``Tracer.span``) are host events of a ``jax.profiler``
  trace, never nested on the loop thread, none open while the drain waits;
- ``trace.fires``: one record per committed window end, its eight stamps
  in order;
- the clock itself: switch, count, longest, threads, exceptions;
- one level below a leaf (``PhaseClock.detail``): a child of the open leaf
  that leaves the partition alone, a host event nested in the leaf's, a
  counter only where no leaf is open; the general lane's six, once a batch.
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
from flink_tpu.runtime.driver import FIRE_RECORDS, FIRE_STAMPS, PHASE_LEAVES

BATCH = 4096
PHASE_PREFIXES = ("ingest.", "window.", "wm.", "drain.", "state.")


def q5_job(n_batches=40, sleep_s=0.0, auctions=50, **conf):
    """A small host-fed Q5 (hot items, 10 s windows sliding by 2 s, 500 ms
    of event time a batch). Every fifth batch repeats its predecessor's
    timestamps, so the watermark stands still and the stashed upload goes
    out as a step of its own (``window.step_dispatch``) instead of riding
    the next fire. ``auctions``: the ids a batch draws from; with about as
    many as records the fused scan's gate says no and every batch goes the
    general lane. Returns (JobResult, committed rows, the driver)."""

    def gen(split, i):
        if i >= n_batches:
            return None
        if sleep_s:
            time.sleep(sleep_s)
        rng = np.random.default_rng(i)
        at = i - 1 if i % 5 == 4 else i
        ts = np.sort(at * 500 + rng.integers(0, 500, BATCH)).astype(np.int64)
        return {"auction": rng.integers(0, auctions, BATCH).astype(np.int64)
                }, ts

    env = StreamExecutionEnvironment(Configuration({
        "pipeline.microbatch-size": BATCH, "state.num-key-shards": 8,
        "state.slots-per-shard": max(64, auctions // 4),
        "analysis.fail-on": "off", **conf}))
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
        # every such value is a number
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
                           "drain_landed", "drain_waited", "preagg_batches", "scan_pane_moves",
                           "scan_ranges", "assign_records",
                           "assign_memo_hits", "fires", "fires_direct"}
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
        assert FIRE_STAMPS == (
            "t_input", "t_fire", "t_queued", "t_fetch0", "t_ready",
            "t_fetch1", "t_push0", "t_sink")
        queued = 0
        for f in fires:
            assert f["op"] is not None
            assert set(f) == {"op", "window_end", *FIRE_STAMPS}
            # t_queued alone may be missing: where an earlier poll's fetch
            # took the rows before the cohort was handed to the drain
            assert all(f[k] is not None for k in FIRE_STAMPS
                       if k != "t_queued"), f
            at = [f[k] for k in FIRE_STAMPS if f[k] is not None]
            assert at == sorted(at), f
            queued += f["t_queued"] is not None
        assert queued >= len(fires) // 2

    def test_a_led_advance_fires_ahead_of_its_batchs_key_scan(
            self, monkeypatch):
        """PR 45: on the general lane the watermark pass a batch implies
        goes AHEAD of the batch's push. Its stamps keep their order
        (``t_input <= t_fire <= t_queued``), and ``t_fire`` lies before
        the batch's ``window.key_scan`` opens, where the old order's lies
        behind it."""
        scans = []
        switch = PhaseClock._switch

        def spy(clock, name, attrs):
            out = switch(clock, name, attrs)
            if name == "window.key_scan":
                scans.append(out[2])
            return out

        monkeypatch.setattr(PhaseClock, "_switch", spy)
        # 8 x 16,384 slots x 11 ring columns: no batch rides the fused step
        conf = {"state.slots-per-shard": 16384}
        q5_job(n_batches=8, auctions=3000, **conf)      # compiles
        scans.clear()
        res, rows, _driver = q5_job(n_batches=40, auctions=3000, **conf)
        m = res.metrics
        assert rows and 1 <= m["wm.advances_led"] < m["wm.advances"]
        led = set()
        for f in m["trace.fires"]:
            assert f["t_input"] <= f["t_fire"], f
            if f["t_queued"] is not None:
                assert f["t_fire"] <= f["t_queued"], f
            # the batch handed over at t_input is keyed in the first
            # key scan that opens after it (the flush has none)
            later = [t for t in scans if t > f["t_input"]]
            if later and f["t_fire"] < later[0]:
                led.add(f["t_fire"])
        assert 1 <= len(led) <= m["wm.advances_led"]

    def test_a_led_fires_delivery_does_not_wait_out_the_next_key_scan(
            self, monkeypatch):
        """PR 48: the loop holds ``_push_lock`` through no batch's push,
        so ``t_push0 - t_fetch1`` of a led advance's rows does not hold
        the ``window.key_scan`` of the batch behind it. The scan is held
        open here (its ``assign`` waits, once, until the sink has the
        rows or a bound has passed): the delivery's ``t_sink`` precedes
        that leaf's end, and the drain's wait for the lock is a fraction
        of the leaf."""
        from flink_tpu.api.sinks import CollectSink as Sink
        from flink_tpu.runtime.driver import Driver
        from flink_tpu.state.keyed import KeyDirectory

        scans, sunk, waited = [], threading.Event(), []
        switch, assign, write = (PhaseClock._switch, KeyDirectory.assign,
                                 Sink.write)
        loop = threading.get_ident()

        def spy(clock, name, attrs):
            prev, t_open, now = out = switch(clock, name, attrs)
            if (prev == "window.key_scan" != name
                    and threading.get_ident() == loop):
                scans.append((t_open, now))
            return out

        def held_assign(directory, keys, *a, **k):
            # the first scan behind a fire that has left for the drain
            if leds and not waited:
                t0 = time.perf_counter()
                waited.append((t0, sunk.wait(10.0)))
            return assign(directory, keys, *a, **k)

        def sink_write(sink, batch):
            write(sink, batch)
            if leds:
                sunk.set()

        conf = {"state.slots-per-shard": 16384}     # no batch is stashed
        q5_job(n_batches=8, auctions=3000, **conf)          # compiles
        leds = []
        emit = Driver._emit_fired

        def emit_fired(driver, nid, fired):
            if getattr(fired, "cohort", None) is not None:
                leds.append(fired.cohort)
            return emit(driver, nid, fired)

        monkeypatch.setattr(PhaseClock, "_switch", spy)
        monkeypatch.setattr(KeyDirectory, "assign", held_assign)
        monkeypatch.setattr(Sink, "write", sink_write)
        monkeypatch.setattr(Driver, "_emit_fired", emit_fired)
        res, rows, _driver = q5_job(n_batches=40, auctions=3000, **conf)
        m = res.metrics
        assert rows and m["wm.advances_led"] >= 1
        (t_wait, in_time), = waited
        assert in_time              # the rows came while the scan stood open
        scan, = [(a, b) for a, b in scans if a <= t_wait <= b]
        first = leds[0]
        # led: it fired ahead of the scan of the batch that completed it
        assert first["t_input"] <= first["t_fire"] < scan[0]
        assert not [a for a, _ in scans if first["t_input"] < a < scan[0]]
        assert scan[0] < first["t_sink"] < scan[1]
        assert first["t_push0"] - first["t_fetch1"] < (scan[1] - scan[0]) / 2
        assert m["push.loop_lock_takes"] == 0

    def test_the_fused_lane_leads_no_advance(self, job):
        m = job[0].metrics
        assert m["wm.advances_led"] == 0 < m["wm.advances"]
        assert m["profile.phase.wm_advances_led"] == 0
        assert m["profile.phase.wm_advances"] == m["wm.advances"]

    def test_t_queued_is_left_out_where_the_fetch_began_before_it(self, job):
        driver = job[2]
        kept = list(driver._fires)
        stamps = dict(zip(FIRE_STAMPS, map(float, range(8))))
        try:
            driver._fires.clear()
            driver._fires.append({"window_ends": [7], "op": 0, **stamps})
            # the drain's poll for the cohort before it read a newer ring
            # version: fetched at 2.5, handed over (t_queued) at 9
            driver._fires.append({"window_ends": [8], "op": 0, **stamps,
                                  "t_fetch0": 2.5, "t_queued": 9.0})
            first, second = driver.fire_records()
            assert first["t_queued"] == 2.0
            assert second["t_queued"] is None and second["t_fetch0"] == 2.5
        finally:
            driver._fires.clear()
            driver._fires.extend(kept)

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


def details_of(metrics):
    """``<leaf>/<name>`` -> (seconds, count, longest ms)."""
    pre = "profile.detail."
    return {k[len(pre):]: (v, metrics["profile.detail_n." + k[len(pre):]],
                           metrics["profile.detail_longest_ms."
                                   + k[len(pre):]])
            for k, v in metrics.items() if k.startswith(pre)}


GENERAL_LANE = {"window.key_scan/" + n for n in (
    "prepare", "panes", "assign", "slot_mask", "note_panes", "preagg_gate")}
DRAIN_WAITS = {"drain/defer", "drain/hold", "drain/landing_wait",
               "drain/link_wait", "drain/push_wait"}


class TestJobDetails:
    def test_the_general_lanes_six_count_once_a_batch(self):
        """4,096 records over 3,000 auctions: the fused scan's gate says no
        to every batch once the keys are registered, so the numpy lane
        runs, named below its leaf."""
        q5_job(n_batches=8, auctions=3000)      # compiles
        res, rows, _driver = q5_job(n_batches=30, auctions=3000)
        m = res.metrics
        assert rows
        details = details_of(m)
        assert GENERAL_LANE <= set(details)
        assert set(details) <= GENERAL_LANE | DRAIN_WAITS
        (assigned,) = [v for k, v in m.items()
                       if k.startswith("profile.op")
                       and k.endswith(".assign_records")]
        general = assigned // BATCH         # batches the directory keyed
        assert general >= 29
        for key in GENERAL_LANE:
            secs, n, longest_ms = details[key]
            assert n == general, key
            assert 0 < longest_ms <= 1e3 * secs + 1e-3, key
        # children of the leaf: together no more than the leaf, and the
        # leaf is still every batch's, whatever ran below it
        inside = sum(details[k][0] for k in GENERAL_LANE)
        assert 0 < inside <= m["profile.phase.window.key_scan"] + 1e-5
        assert m["profile.phase.window.key_scan.n"] >= 30
        # the partition is what it was: the loop's leaves sum to its wall
        leaves = leaves_of(m)
        loop = sum(v for k, v in leaves.items() if not k.startswith("drain."))
        wall = m["profile.phase.loop_wall_s"]
        assert abs(loop - wall) <= 0.03 * wall, (loop, wall)
        assert not [k for k in leaves if "/" in k]

    def test_the_fused_lane_has_none_after_its_first_batch(self, job):
        m = job[0].metrics
        details = details_of(m)
        assert m["profile.phase.window.key_scan.n"] >= 40
        for key in set(details) & GENERAL_LANE:
            assert details[key][1] <= 1, key
        assert set(details) <= GENERAL_LANE | DRAIN_WAITS

    def test_the_drains_waits_are_counters(self, job):
        """``pipeline.emit-defer`` on auto is no age at all, whatever the
        backend, so ``drain/defer`` is absent; ``drain/landing_wait``
        counts the polls whose rows had not landed when the drain came
        for them (``drain_waited``; the others are ``drain_landed``);
        the two locks are taken once a poll."""
        m = job[0].metrics
        details = details_of(m)
        assert "drain/defer" not in details
        landed, waited = (
            sum(v for k, v in m.items() if k.startswith("profile.op")
                and k.endswith("." + name))
            for name in ("drain_landed", "drain_waited"))
        assert details.get("drain/landing_wait", (0.0, 0, 0.0))[1] == waited
        assert landed + waited >= 1     # the job fired rows
        polls = details["drain/push_wait"][1]
        assert polls == details["drain/link_wait"][1] >= landed + waited
        assert polls >= m["profile.phase.drain.deliver.n"] / 40
        # the loop's side of the same lock: a plain counter of seconds
        assert m["profile.phase.push_wait_s"] >= 0.0
        assert "profile.phase.push_wait_s.n" not in m


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
        # the leaves: a detail (``<leaf>/<name>``) lies inside its leaf
        return sorted((s, e, n) for n, s, e, _st in events
                      if n.startswith(PHASE_PREFIXES) and "/" not in n)

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
    # its waits (drain/landing_wait, drain/link_wait, drain/push_wait)
    # are counters with no leaf open: no event carries their names
    assert not [n for n in names if n.startswith("drain/")]
    assert set(details_of(out["job"][0].metrics)) >= {
        "drain/link_wait", "drain/push_wait"}

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


class TestDetail:
    def test_a_detail_leaves_the_partition_alone(self):
        """Two clocks driven alike, one with details: the same leaves, the
        same counts, and every reading of the leaves is the phase
        switches' own, which a detail does not make."""
        plain, detailed = PhaseClock(), PhaseClock()
        for c in (plain, detailed):
            t0 = c.phase("leaf.a")
            if c is detailed:
                with c.detail("x"):
                    assert c.open_phase() == "leaf.a"
                with c.detail("x"):
                    pass
                with c.detail("y"):
                    pass
            t1 = c.phase("leaf.b")
            t2 = c.stop()
            snap = c.snapshot()
            assert set(snap) == {"leaf.a", "leaf.b"}
            assert snap["leaf.a"]["count"] == snap["leaf.b"]["count"] == 1
            assert snap["leaf.a"]["seconds"] == pytest.approx(
                t1 - t0, abs=1e-9)
            assert snap["leaf.a"]["longest_ms"] == pytest.approx(
                1e3 * (t1 - t0), abs=1e-6)
            assert sum(v["seconds"] for v in snap.values()) == pytest.approx(
                t2 - t0, abs=1e-9)
        assert plain.details() == {}
        d = detailed.details()
        assert set(d) == {"leaf.a/x", "leaf.a/y"}
        assert d["leaf.a/x"]["count"] == 2 and d["leaf.a/y"]["count"] == 1
        for st in d.values():
            assert 0 <= st["longest_ms"] <= 1e3 * st["seconds"] + 1e-9
        # children: together no more than their leaf
        assert sum(st["seconds"] for st in d.values()) <= \
            detailed.snapshot()["leaf.a"]["seconds"]

    def test_time_in_another_leaf_is_that_leafs(self):
        """A block may switch the phase (the general lane's ``prepare``
        meets ``state.reclaim``): the detail stands still meanwhile and
        goes on when its leaf is open again; one that never sees its leaf
        again (``preagg_gate`` past its switch to ``window.pack``) ends
        where the leaf did."""
        c = PhaseClock()
        c.phase("leaf.a")
        with c.detail("x"):
            with c.span("leaf.other") as other:
                time.sleep(0.01)
            assert c.open_phase() == "leaf.a"
        with c.detail("gate"):
            t_switch = c.phase("leaf.pack")
            time.sleep(0.01)
        t_end = c.stop()
        snap, d = c.snapshot(), c.details()
        assert d["leaf.a/x"]["count"] == d["leaf.a/gate"]["count"] == 1
        assert snap["leaf.a"]["count"] == 2
        assert other.seconds >= 0.01 and t_end - t_switch >= 0.01
        # neither sleep is the details': they fit into what is left of
        # the leaf, which holds no sleep
        assert d["leaf.a/x"]["seconds"] + d["leaf.a/gate"]["seconds"] <= \
            snap["leaf.a"]["seconds"] + 1e-9
        assert snap["leaf.other"]["seconds"] == pytest.approx(
            other.seconds, abs=1e-9)

    def test_no_leaf_open_is_a_counter_under_its_own_name(self):
        c = PhaseClock()
        with c.detail("drain/landing_wait"):
            pass
        with c.span("drain.fetch"):
            pass
        with c.detail("drain/landing_wait"):
            with c.span("drain.deliver"):    # a leaf's time is not a wait
                time.sleep(0.005)
        assert set(c.snapshot()) == {"drain.fetch", "drain.deliver"}
        d = c.details()
        assert set(d) == {"drain/landing_wait"}
        assert d["drain/landing_wait"]["count"] == 2
        assert c.open_phase() is None

    def test_one_level_and_exceptions(self):
        c = PhaseClock()
        c.phase("leaf.a")
        with pytest.raises(RuntimeError, match="one level"):
            with c.detail("x"):
                with c.detail("y"):
                    pass
        with pytest.raises(ValueError):
            with c.detail("x"):
                raise ValueError("boom")
        with c.detail("x"):         # none was left open
            pass
        c.stop()
        assert c.details()["leaf.a/x"]["count"] == 3
        assert set(c.details()) == {"leaf.a/x"}

    def test_threads_add_up(self):
        c = PhaseClock()

        def work():
            c.phase("leaf.a")
            for _ in range(50):
                with c.detail("x"):
                    pass
            c.stop()

        threads = [threading.Thread(target=work, daemon=True)
                   for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
            assert not t.is_alive()
        assert c.details()["leaf.a/x"]["count"] == 200
        assert c.snapshot()["leaf.a"]["count"] == 4


def test_a_detail_is_a_host_event_nested_in_its_leafs(tmp_path):
    """Under ``jax.profiler``: with a leaf open a detail is the host event
    ``<leaf>/<name>`` inside the leaf's own, on the same thread; with none
    open (a thread that waits between its spans) it leaves no event."""
    import jax

    c = PhaseClock()

    def traced():
        c.phase("leaf.a")
        time.sleep(0.002)
        with c.detail("x"):
            time.sleep(0.002)
        with c.detail("y"):
            with c.span("leaf.other"):      # the child ends before its leaf
                time.sleep(0.002)
            time.sleep(0.002)
        c.stop()
        with c.detail("wait/idle"):
            time.sleep(0.002)

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path / "trace"), profiler_options=opts)
    try:
        worker = threading.Thread(target=traced, daemon=True)
        worker.start()
        worker.join(timeout=60)
        assert not worker.is_alive()
    finally:
        jax.profiler.stop_trace()
    (line,) = [evs for evs in read_host_lines(str(tmp_path / "trace"))
               if any(n == "leaf.a" for n, *_ in evs)]
    mine = sorted((s, e, n) for n, s, e, _st in line
                  if n.startswith(("leaf.", "wait/")))
    assert [n for _s, _e, n in mine] == [
        "leaf.a", "leaf.a/x", "leaf.a/y", "leaf.other", "leaf.a", "leaf.a/y"]
    (a0s, a0e, _), (xs, xe, _), (y0s, y0e, _), (os_, _oe, _), \
        (a1s, a1e, _), (y1s, y1e, _) = mine
    assert a0s <= xs <= xe <= y0s <= y0e <= a0e <= os_
    assert a1s <= y1s <= y1e <= a1e
    assert set(c.details()) == {"leaf.a/x", "leaf.a/y", "wait/idle"}
    assert c.details()["leaf.a/y"]["count"] == 1


def test_nested_events_change_no_reading_of_the_benchmarks():
    """A trace in which ``window.key_scan`` holds its details, as this
    program's now does: ``hostkey.ms_per_batch.*``'s reader still sums the
    leaf alone (its pattern is anchored), and an idle gap of the device
    under the key scan is still the leaf's (the parent covers at least
    what its child covers, and comes first)."""
    import importlib

    from benchmark import trace_reduce as tr

    def host(details):
        evs = [("ingest.route", 0.0, 100.0),
               ("window.key_scan", 100.0, 1000.0),
               ("window.step_dispatch", 1100.0, 100.0)]
        if details:
            evs += [("window.key_scan/panes", 110.0, 300.0),
                    ("window.key_scan/assign", 420.0, 500.0),
                    ("window.key_scan/slot_mask", 930.0, 160.0)]
        return evs

    def trace_of(details):
        # the device works 1150..1200: idle 0..1150, all but 150 of it
        # under the key scan, 500 of those under its longest detail
        dev = tr.DeviceTrace("/device:TPU:0", [("jit_step(1)", 1150.0, 50.0)],
                             [("fusion.1", 1150.0, 50.0)])
        return tr.Trace([dev], host(details), (0.0, 1200.0))

    read = importlib.import_module("benchmark.readers.trace_host").read
    args = {"match": r"^window\.key_scan$", "per": "batches", "scale": 1000.0}
    plain, nested = trace_of(False), trace_of(True)
    want = read({"trace": plain, "trace_batches": 1}, **args)
    assert want == pytest.approx(1000.0 / 1e9 / 1.0 * 1000.0)
    assert read({"trace": nested, "trace_batches": 1}, **args) == want
    for trace in (plain, nested):
        gaps = dict(trace.labelled_gaps(trace.busiest()))
        assert gaps == {"window.key_scan": pytest.approx(1150.0 / 1e9)}


def test_a_trace_summary_keeps_the_step_spans_whatever_their_rank(tmp_path):
    """``summarize_trace_dir`` lists a plane's longest ops; the spans the
    loop sends a step out under stay in the list when a compile inside
    the traced span puts fifty longer names above them."""
    import gzip
    import json

    from flink_tpu.obs.profiling import STEP_SPANS, summarize_trace_dir
    events = [{"ph": "M", "name": "process_name", "pid": 1,
               "args": {"name": "/host:CPU"}}]
    events += [{"ph": "X", "pid": 1, "name": f"llvm.pass.{i}", "dur": 900 + i}
               for i in range(50)]
    events += [{"ph": "X", "pid": 1, "name": n, "dur": 100}
               for n in STEP_SPANS + ("window.pack",)]
    d = tmp_path / "plugins"
    d.mkdir()
    with gzip.open(d / "x.trace.json.gz", "wt") as f:
        json.dump({"traceEvents": events}, f)
    ops = summarize_trace_dir(str(tmp_path), top=40)["planes"][0]["ops"]
    names = [o["op"] for o in ops]
    assert len(names) == 42 and set(STEP_SPANS) <= set(names)
    assert "window.pack" not in names and names[0] == "llvm.pass.49"
    assert len(summarize_trace_dir(str(tmp_path), top=40, keep=())[
        "planes"][0]["ops"]) == 40
