"""Who holds the delivery lock, and for what (PR 48).

``Driver._push_lock`` guards what BOTH of a job's threads can reach and
nothing else. The drain holds it for a poll's whole delivery; the
loop's thread takes it only on entering a node such a delivery can
enter (``Driver._drain_reach``, worked out from the plan when the job is
built) and where it delivers fired rows in line, never through an
operator's ``process_batch`` or ``advance_watermark``.

- a delivery completes while the loop's thread stands inside an
  operator's ``process_batch`` (a window job, a device-session job);
- the set, for five plans;
- a sink and a host GROUP BY that raise on a second writer, over a few
  hundred batches: no overlap, the rows of the in-line run;
- ``push.loop_lock_takes`` and ``profile.phase.push_wait_s``;
- the lock itself: a thread that holds it goes on, and is not counted.
"""
import sys
import threading
import time

import numpy as np
import pytest

import flink_tpu  # noqa: F401 — x64 before other jax users
from flink_tpu.api.environment import StreamExecutionEnvironment
from flink_tpu.api.sinks import CollectSink, FnSink
from flink_tpu.api.sources import GeneratorSource, Source
from flink_tpu.api.windowing import (
    SlidingEventTimeWindows, TumblingEventTimeWindows)
from flink_tpu.config import Configuration
from flink_tpu.graph.compiler import compile_job
from flink_tpu.nexmark.queries import (
    q4_category_avg, q5_hot_items, q11_user_sessions)
from flink_tpu.ops.global_agg import GlobalAggregateOperator
from flink_tpu.ops.session_device import DeviceSessionOperator
from flink_tpu.ops.window import FiredWindows, WindowOperator
from flink_tpu.runtime.driver import Driver, _LoopHold, _PushLock
from flink_tpu.time.watermarks import WatermarkStrategy
from test_join_device import q4_stream

BATCH = 1024
SPAN = 500              # ms of event time a batch
WINDOW, SLIDE, DELAY, GAP = 10_000, 2_000, 1_000, 3_000
WAIT_S = 30.0           # a bound on every wait for an event: liveness only


def bids(n_batches, key="auction", keys=50):
    def gen(split, i):
        if i >= n_batches:
            return None
        rng = np.random.default_rng(i)
        ts = np.sort(i * SPAN + rng.integers(0, SPAN, BATCH)).astype(np.int64)
        return {key: rng.integers(0, keys, BATCH).astype(np.int64)}, ts
    return GeneratorSource(gen)


def from_bids(env, source):
    return env.from_source(
        source, WatermarkStrategy.for_bounded_out_of_orderness(DELAY))


def counts_of(stream):
    return (stream.key_by("auction")
            .window(SlidingEventTimeWindows.of(WINDOW, SLIDE)).count())


# -- the plans ---------------------------------------------------------------

def window_to_sink(env, sink, n=40):
    q5_hot_items(env, bids(n), sink, window_ms=WINDOW, slide_ms=SLIDE,
                 out_of_orderness_ms=DELAY)


def sessions_to_sink(env, sink, n=40):
    q11_user_sessions(env, bids(n, "bidder", keys=3000), sink, gap_ms=GAP,
                      out_of_orderness_ms=DELAY)


def shared_under_a_union(env, sink, n=40):
    """ONE sink node below a union that the source's rows and the
    window's fired rows both enter."""
    stream = from_bids(env, bids(n))
    stream.map(dict, name="raw").union(counts_of(stream)).add_sink(sink)


def shared_by_two_nodes(env, sink, n=40):
    """Two sink nodes, one fed by the source and one by the window, that
    write to ONE sink object."""
    stream = from_bids(env, bids(n))
    stream.add_sink(sink, name="raw")
    counts_of(stream).add_sink(sink, name="fired")


def window_below_a_window(env, sink, n=40):
    (counts_of(from_bids(env, bids(n)))
     .map(lambda d: {"wstart": d["window_start"], "cnt": d["count"]})
     .key_by(lambda d: np.asarray(d["wstart"], np.int64) // SLIDE)
     .window(TumblingEventTimeWindows.of(SLIDE)).max("cnt").add_sink(sink))


def stateless(env, sink, n=40):
    from_bids(env, bids(n)).map(dict, name="copy").add_sink(sink)


def join_over_its_group_by(env, sink, n=6, width=512):
    """q4's shape: the device join's changelog into a host GROUP BY that
    nothing else feeds."""
    stream = q4_stream(5, n, width)

    class Batches(Source):
        def open_split(self, split, start_pos=0):
            yield from stream

    q4_category_avg(env, Batches(), sink)


def make_env(**conf):
    return StreamExecutionEnvironment(Configuration({
        "pipeline.microbatch-size": BATCH, "state.num-key-shards": 8,
        "state.slots-per-shard": 512, "analysis.fail-on": "off", **conf}))


def built(build, **conf):
    """The plan's driver, built and not run."""
    env = make_env(**conf)
    build(env, CollectSink())
    plan = compile_job(env._transforms, env.config, env._watermark_strategy)
    return Driver(plan, env.config)


def executed(build, sink, n, **conf):
    env = make_env(**conf)
    build(env, sink, n)
    return env.execute("push-lock").metrics, env._driver


def kinds(driver, nids):
    return sorted(driver.plan.node(n).kind for n in nids)


def loop_side(driver):
    """The nodes the loop's thread pushes a source's batch into: from the
    sources down to the first operator on each path (what an operator
    hands on is a fired path)."""
    seen, stack = set(), list(driver.plan.sources)
    while stack:
        for d in driver.plan.node(stack.pop()).downstream:
            if d not in seen:
                seen.add(d)
                if d not in driver._ops:
                    stack.append(d)
    return seen


# -- the set, for five plans ---------------------------------------------------

class TestWhereTheDrainCanReach:
    def test_window_to_sink_the_sources_side_is_empty(self):
        d = built(window_to_sink)
        assert kinds(d, d._drain_reach) == ["chain", "sink"]
        assert not loop_side(d) & d._drain_reach

    @pytest.mark.parametrize("build,reach,shared", [
        (shared_under_a_union, ["sink", "union"], ["sink", "union"]),
        (shared_by_two_nodes, ["sink", "sink"], ["sink"])])
    def test_a_sink_that_a_source_path_and_a_window_both_feed(
            self, build, reach, shared):
        """The loop takes the lock where it enters the set: at the union
        (and holds it for the sink below), or at its own sink node, which
        is of the set as every sink is where the drain delivers anything
        (``records_out`` is one counter, and two nodes may write to one
        sink object). The source's own chain is not of it."""
        d = built(build)
        assert kinds(d, d._drain_reach) == reach
        assert kinds(d, loop_side(d) & d._drain_reach) == shared

    def test_a_second_window_below_a_first(self):
        d = built(window_below_a_window)
        first, second = sorted(n for n in d._ops
                               if d.plan.node(n).kind == "window")
        assert not d._drain_may_deliver(first)      # in line, on the loop
        assert d._drain_may_deliver(second)
        assert kinds(d, d._drain_reach) == ["sink"]
        assert second not in d._drain_reach
        assert not loop_side(d) & d._drain_reach

    def test_the_device_join_over_its_group_by(self):
        d = built(join_over_its_group_by)
        join, = (n for n in d._ops if d.plan.node(n).kind == "keyed_join")
        agg, = (n for n in d._ops if d.plan.node(n).kind == "global_agg")
        assert hasattr(d._ops[join], "emit_ring")           # the device lane
        assert isinstance(d._ops[agg], GlobalAggregateOperator)
        assert d._drain_may_deliver(join)
        assert agg in d._drain_reach and join not in d._drain_reach
        assert "sink" in kinds(d, d._drain_reach)
        assert not loop_side(d) & d._drain_reach

    def test_a_plan_the_drain_serves_no_operator_of(self):
        """No operator, no fired row, nothing the drain's thread ever
        delivers: the loop's thread takes the lock nowhere."""
        d = built(stateless)
        assert not d._ops and d._drain_reach == frozenset()
        m, d = executed(stateless, CollectSink(), 10)
        assert m["push.loop_lock_takes"] == 0 < m["records_out"]

    def test_the_set_is_worked_out_when_the_job_is_built(self, monkeypatch):
        d = built(window_to_sink)
        assert isinstance(d._drain_reach, frozenset)
        monkeypatch.setattr(Driver, "_drain_may_deliver",
                            lambda self, nid: False)
        assert built(window_to_sink)._drain_reach == frozenset()


# -- a delivery beside the loop's process_batch -------------------------------

class Keeper:
    """Stands the loop's thread at the door of an operator's
    ``process_batch``, once: at the first call after the job's first fire
    with rows was handed to the drain. The drain's poll for that fire
    begins only when the loop stands there."""

    def __init__(self, monkeypatch, op_class):
        self.armed, self.inside = threading.Event(), threading.Event()
        self.release, self.sunk = threading.Event(), threading.Event()
        self.released_in_time = None
        process, emit = op_class.process_batch, Driver._emit_fired
        landing = FiredWindows.await_landing
        keeper = self

        def emit_fired(driver, nid, fired):
            if getattr(fired, "cohort", None) is not None:
                keeper.armed.set()
            return emit(driver, nid, fired)

        def process_batch(op, *a, **k):
            if keeper.armed.is_set() and not keeper.inside.is_set():
                keeper.inside.set()
                keeper.released_in_time = keeper.release.wait(WAIT_S)
            return process(op, *a, **k)

        def await_landing(fireds, until):
            if keeper.armed.is_set():
                keeper.inside.wait(WAIT_S)
            return landing(fireds, until)

        monkeypatch.setattr(Driver, "_emit_fired", emit_fired)
        monkeypatch.setattr(op_class, "process_batch", process_batch)
        monkeypatch.setattr(FiredWindows, "await_landing",
                            staticmethod(await_landing))

    def sink(self, rows):
        def write(batch):
            rows.append({k: np.array(v) for k, v in batch.items()})
            self.sunk.set()
        return FnSink(write)


@pytest.mark.parametrize("build,op_class,conf", [
    (window_to_sink, WindowOperator, {}),
    # the general lane: the advance leads its batch
    (window_to_sink, WindowOperator, {"state.slots-per-shard": 16384}),
    (sessions_to_sink, DeviceSessionOperator,
     {"state.slots-per-shard": 2048})],
    ids=["window", "window_led", "device_session"])
def test_a_delivery_completes_while_the_loop_stands_in_process_batch(
        build, op_class, conf, monkeypatch):
    want = CollectSink()
    executed(build, want, 40, **conf)           # compiles; the reference
    keeper = Keeper(monkeypatch, op_class)
    rows, out = [], {}

    def job():
        out["metrics"], out["driver"] = executed(
            build, keeper.sink(rows), 40, **conf)

    t = threading.Thread(target=job, daemon=True)
    t.start()
    try:
        assert keeper.inside.wait(WAIT_S)       # the loop is in process_batch
        # and the fired rows reach the sink while it stands there
        assert keeper.sunk.wait(WAIT_S)
        assert rows and not keeper.release.is_set()
    finally:
        keeper.release.set()
        t.join(WAIT_S)
    assert not t.is_alive() and keeper.released_in_time
    d = out["driver"]
    assert isinstance(next(iter(d._ops.values())), op_class)
    assert out["metrics"]["push.loop_lock_takes"] == 0
    got = sorted(tuple(sorted((k, int(v[i])) for k, v in b.items()))
                 for b in rows for i in range(len(next(iter(b.values())))))
    assert got == sorted(tuple(sorted((k, int(v)) for k, v in r.items()))
                         for r in want.rows) != []


# -- one writer at a time, where both threads reach ---------------------------

class OneWriter:
    """A guard that raises on a second writer; the work under it lets go
    of the interpreter, so that a second one would get in."""

    def __init__(self):
        self.gate = threading.Lock()
        self.threads, self.calls = set(), 0

    def __enter__(self):
        if not self.gate.acquire(blocking=False):
            raise AssertionError("a second writer")
        self.threads.add(threading.get_ident())
        self.calls += 1
        time.sleep(0.0002)

    def __exit__(self, *exc):
        self.gate.release()


def probe_sink(guard, rows):
    def write(batch):
        with guard:
            n = len(next(iter(batch.values())))
            rows.extend(tuple(sorted((k, int(v[i])) for k, v in batch.items()))
                        for i in range(n))
    return FnSink(write)


def in_line(monkeypatch, build, n, **conf):
    """The rows of a run in which the loop's thread delivers everything
    itself (no fired row is handed to the drain)."""
    rows = []
    with monkeypatch.context() as mp:
        mp.setattr(Driver, "_drain_may_deliver", lambda self, nid: False)
        m, d = executed(build, probe_sink(OneWriter(), rows), n, **conf)
    assert d._drain_reach == frozenset()
    return rows, m


@pytest.mark.parametrize("build", [shared_under_a_union, shared_by_two_nodes])
def test_a_shared_sink_has_one_writer_at_a_time(build, monkeypatch):
    n = 300
    want, m_line = in_line(monkeypatch, build, n)
    guard, rows = OneWriter(), []
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)     # threads switch every 10 us
    try:
        m, d = executed(build, probe_sink(guard, rows), n)
    finally:
        sys.setswitchinterval(old)
    assert len(guard.threads) == 2              # both threads wrote
    assert sorted(rows) == sorted(want) != []
    assert m["records_out"] == m_line["records_out"] == len(rows)
    assert m["fired_windows"] == m_line["fired_windows"] > 0
    # the loop took the lock once a batch, at the node both threads
    # reach, and says what it waited for it
    assert m["push.loop_lock_takes"] == m["batches"] == n
    assert m["profile.phase.push_lock_takes"] == n
    assert m["profile.phase.push_wait_s"] >= 0.0
    # in line the lock is taken for each delivery with rows
    assert m_line["push.loop_lock_takes"] > 0


def test_the_group_by_below_the_join_has_one_writer_under_the_lock(
        monkeypatch):
    n = 300
    guard, held, drivers = OneWriter(), [], []
    process = GlobalAggregateOperator.process_batch
    emit = Driver._emit_fired

    def emit_fired(driver, nid, fired):
        drivers.append(driver)
        return emit(driver, nid, fired)

    def process_batch(op, *a, **k):
        held.append(drivers[-1]._push_lock.held_by_caller())
        with guard:
            return process(op, *a, **k)

    monkeypatch.setattr(Driver, "_emit_fired", emit_fired)
    monkeypatch.setattr(GlobalAggregateOperator, "process_batch",
                        process_batch)
    def build(env, sink, n):
        join_over_its_group_by(env, sink, n, width=256)

    # ~15 auctions new a batch, and a join's key never leaves
    conf = {"state.slots-per-shard": 2048}
    want, m_line = in_line(monkeypatch, build, n, **conf)
    calls_in_line, guard.threads = guard.calls, set()
    del held[:]
    sink_guard, rows = OneWriter(), []
    m, d = executed(build, probe_sink(sink_guard, rows), n, **conf)
    assert rows == want != []               # one FIFO: the same order too
    assert held and all(held)               # under the lock, every fold
    assert guard.calls - calls_in_line == calls_in_line > 0
    # on the drain's thread alone, and the loop never took the lock
    assert len(guard.threads) == 1 == len(sink_guard.threads)
    assert guard.threads == sink_guard.threads
    assert m["push.loop_lock_takes"] == 0 < m_line["push.loop_lock_takes"]
    assert m["records_out"] == m_line["records_out"] == len(rows)


# -- the counters ----------------------------------------------------------------

def test_the_loop_takes_no_lock_for_window_to_sink_and_says_what_it_waited():
    sink = CollectSink()
    m, d = executed(window_to_sink, sink, 40)
    assert sink.rows and m["wm.advances"] > 0
    assert m["push.loop_lock_takes"] == 0
    assert m["profile.phase.push_lock_takes"] == 0
    assert m["profile.phase.push_wait_s"] == 0.0
    assert "profile.phase.push_lock_takes.n" not in m


def test_a_window_below_a_window_delivers_in_line_under_the_lock():
    sink = CollectSink()
    m, d = executed(window_below_a_window, sink, 40)
    assert sink.rows
    # what the first window hands on goes to the second on the loop's
    # thread, each hand-over under the lock (one a watermark pass, rows or
    # none); the second's rows leave by the drain
    assert 0 < m["push.loop_lock_takes"] <= m["batches"] + 1
    assert m["profile.phase.push_wait_s"] >= 0.0


# -- the lock itself ---------------------------------------------------------------

class TestTheLock:
    def test_a_thread_that_holds_it_goes_on_and_is_not_counted(self):
        lock = _PushLock()
        hold = _LoopHold(lock)
        with hold:
            assert lock.locked() and lock.held_by_caller()
            with hold:                      # a node below the first
                with hold:
                    assert lock.locked()
                assert lock.locked()
        assert not lock.locked() and not lock.held_by_caller()
        assert hold.takes == 1 and hold.waited_s == 0.0

    def test_the_drains_way_is_the_plain_lock_and_the_loops_way_sees_it(self):
        """The drain takes the lock itself (``acquire``); inside its
        delivery ``_push`` meets nodes of the set and must go on."""
        lock = _PushLock()
        hold = _LoopHold(lock)
        assert lock.acquire()
        try:
            with hold:
                assert lock.locked()
            assert lock.locked() and lock.held_by_caller()
        finally:
            lock.release()
        assert hold.takes == 0 and not lock.locked()

    def test_another_threads_hold_is_waited_for_and_the_wait_is_added_up(self):
        lock = _PushLock()
        hold = _LoopHold(lock)
        holding, let_go = threading.Event(), threading.Event()

        def other():
            lock.acquire()
            holding.set()
            let_go.wait(WAIT_S)
            time.sleep(0.01)
            lock.release()

        t = threading.Thread(target=other, daemon=True)
        t.start()
        assert holding.wait(WAIT_S)
        assert lock.locked() and not lock.held_by_caller()
        assert not lock.acquire(blocking=False)
        assert not lock.acquire(timeout=0.01)
        let_go.set()
        with hold:
            assert lock.held_by_caller()
        t.join(WAIT_S)
        assert hold.takes == 1 and hold.waited_s > 0.0
