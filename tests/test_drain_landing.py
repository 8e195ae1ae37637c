"""What the drain waits for (PR 42): not an age, the landing of the rows.

The driver's drain thread takes a fire's marker off the emit queue and,
holding NOTHING the loop can need, waits until the announced version
that holds the fire's rows has landed on the host; then it takes its
locks, and the read is local. Here the landing is the test's to decide:
an announced version is swapped for a stand-in (``Gate``) that says
"not landed" until the test opens it, and every step is awaited on an
event, never on the clock.

- (a) not landed: nothing delivered, and the locks are all free;
- (b) landed: the rows once, in fire order, the stamps in order;
- (c) a barrier during the wait: the pinned fetch, every row, none twice;
- (d) a stop during the wait, aborted and not;
- (e) a marker without rows waits for nothing;
- (f) a row-carrying fire announces its own version, whatever the cadence;
- (g) the session operator's passes and a pack fire's buffers likewise;
- (h) ``drain_landed`` / ``drain_waited`` and ``drain/landing_wait``;
- (i) an explicit ``pipeline.emit-defer`` ages the marker first, and auto
  is no age on any backend;
- (j) under the fair gate the waiting tenant does not hold the turn;
- (k) a batch of markers without rows hurries no one: the drain holds it
  until a marker that may carry rows, a barrier or a stop (or the
  announce cadence, which the other cases set to nothing);
- (l) an abort while the drain stands before ``_push_lock``: the re-check
  under the lock delivers nothing (PR 48).
"""
import queue
import threading

import numpy as np
import pytest

import flink_tpu  # noqa: F401 — x64 before other jax users
from flink_tpu.api.environment import StreamExecutionEnvironment
from flink_tpu.api.sinks import CollectSink
from flink_tpu.api.sources import GeneratorSource
from flink_tpu.api.windowing import SlidingEventTimeWindows
from flink_tpu.config import Configuration
from flink_tpu.graph.compiler import compile_job
from flink_tpu.nexmark.queries import q5_hot_items
from flink_tpu.ops.aggregates import count
from flink_tpu.ops.emit_ring import ANNOUNCE_INTERVAL_S
from flink_tpu.ops.session_device import DeviceSessionOperator
from flink_tpu.ops.window import FiredWindows, WindowOperator
from flink_tpu.runtime.driver import Driver
from flink_tpu.runtime.session import FairDrainGate

BATCH = 4096
WAIT_S = 30.0       # a bound on every wait for an event: liveness only
STAMPS = ("t_fetch0", "t_ready", "t_fetch1", "t_push0", "t_sink")


class Gate:
    """An announced version (or buffer) whose landing the test decides.
    ``polled`` is set once the drain has asked often enough to be inside
    its wait loop (the choice asks once, the count once); ``under``
    remembers what ``probe()`` said at each ask: which locks were held."""

    IN_THE_LOOP = 5

    def __init__(self, rows, landed=False, probe=None):
        self.rows = np.asarray(rows)
        self.landed = threading.Event()
        if landed:
            self.landed.set()
        self.polled = threading.Event()
        self.asks, self.probe, self.under = 0, probe, []

    def is_ready(self):
        self.asks += 1
        if self.probe is not None:
            self.under.append(self.probe())
        if self.asks >= self.IN_THE_LOOP:
            self.polled.set()
        return self.landed.is_set()

    def copy_to_host_async(self):
        pass

    def __array__(self, dtype=None, copy=None):
        return self.rows

    def __getitem__(self, ix):
        return self.rows[ix]

    def __len__(self):
        return len(self.rows)


def held_from_another_thread(lock) -> bool:
    """Whether ``lock`` is held just now (an RLock counts its owner's
    re-entry as free, so the question is asked from a thread of its
    own)."""
    out = []

    def ask():
        got = lock.acquire(blocking=False)
        if got:
            lock.release()
        out.append(not got)

    t = threading.Thread(target=ask)
    t.start()
    t.join(WAIT_S)
    return out[0]


def joined(q: "queue.Queue") -> bool:
    """``q.join()`` with a bound: True when every item was delivered."""
    t = threading.Thread(target=q.join, daemon=True)
    t.start()
    t.join(WAIT_S)
    return not t.is_alive()


class Harness:
    """A host-fed Q5 job's driver, built and not run: the window
    operator is driven by hand, its fires go through the driver's own
    ``_emit_fired`` to the driver's own drain thread."""

    def __init__(self, **conf):
        env = StreamExecutionEnvironment(Configuration({
            "pipeline.microbatch-size": BATCH, "state.num-key-shards": 8,
            "state.slots-per-shard": 64, "analysis.fail-on": "off", **conf}))
        self.sink = CollectSink()
        q5_hot_items(env, GeneratorSource(lambda split, i: None), self.sink,
                     window_ms=10_000, slide_ms=2_000,
                     out_of_orderness_ms=1_000)
        plan = compile_job(env._transforms, env.config,
                           env._watermark_strategy)
        self.driver = d = Driver(plan, env.config)
        (self.nid, self.op), = d._ops.items()
        self.ring = self.op.emit_ring
        # the cadence is never due: only a fire with rows announces
        self.ring.announce_interval_s = float("inf")
        self.ring.last_announce = 0.0
        self.op.phases = d.phases
        # and a marker without rows is polled at once (but see (k))
        assert d._rowless_hold_s == ANNOUNCE_INTERVAL_S
        d._rowless_hold_s = 0.0
        d._emit_q = queue.Queue()
        self.rng = np.random.default_rng(0)
        self.batch_no = 0
        self.thread = None

    def start(self):
        self.thread = threading.Thread(
            target=self.driver._drain_entry, daemon=True)
        self.thread.start()
        return self

    def fire(self) -> FiredWindows:
        """One batch of 2 s of event time and the advance behind it:
        from the second on, a window end fires."""
        i = self.batch_no
        self.batch_no += 1
        ts = np.sort(i * 2_000 + self.rng.integers(0, 2_000, BATCH))
        self.op.process_batch(
            self.rng.integers(0, 50, BATCH).astype(np.int64),
            ts.astype(np.int64), {})
        return self.op.advance_watermark(i * 2_000 + 999)

    def gate_newest(self, landed=False, probe=None) -> Gate:
        """Swap the newest announced version for a stand-in."""
        no, arr = self.ring.versions[-1]
        gate = Gate(np.asarray(arr), landed, probe)
        self.ring.versions[-1] = (no, gate)
        return gate

    def queue(self, fired) -> None:
        self.driver._emit_fired(self.nid, fired)

    def locks_held(self):
        d = self.driver
        return {"link": d._link_lock.locked(), "push": d._push_lock.locked()}

    def ends(self):
        return [int(r["window_end"]) for r in self.sink.rows]

    def stop(self, discard=False):
        d = self.driver
        d._drain_discard[0] = discard
        d._hurry_drain()
        d._emit_q.put(None)
        self.thread.join(WAIT_S)
        return not self.thread.is_alive()


@pytest.fixture
def h():
    h = Harness().start()
    h.queue(h.fire())       # a first batch: no window end yet, no rows
    assert joined(h.driver._emit_q) and not h.sink.rows
    yield h
    if h.thread.is_alive():
        for _, v in h.ring.versions:
            if isinstance(v, Gate):
                v.landed.set()
        assert h.stop(discard=True)


def reference_ends(n_fires: int):
    """The window ends ``n_fires`` fires of the harness's stream give an
    operator that is drained in line."""
    ref = Harness()
    out = []
    for _ in range(n_fires + 1):
        out.extend(int(e) for e in dict(ref.fire())["window_end"])
    return out


class TestTheWaitHoldsNothing:
    def test_a_version_that_has_not_landed_delivers_nothing_and_holds_no_lock(
            self, h):
        fired = h.fire()
        gate = h.gate_newest(probe=h.locks_held)
        h.queue(fired)
        assert gate.polled.wait(WAIT_S)         # the drain is in its wait
        d = h.driver
        for lock in (d._link_lock, h.ring.lock, d._push_lock):
            assert not held_from_another_thread(lock)
        # and the loop's own way to each is open
        with d._link_lock:
            pass
        with d._loop_push:
            pass
        with h.ring.lock:
            assert h.ring.read_no < fired._ring_no
        assert not h.sink.rows and d._emit_q.unfinished_tasks == 1
        assert fired.cohort.get("t_fetch0") is None
        # asked often, and never with a lock of the driver's held
        assert gate.asks >= Gate.IN_THE_LOOP
        assert not any(u["link"] or u["push"] for u in gate.under)
        assert d._loop_push.waited_s == 0.0
        gate.landed.set()
        assert joined(d._emit_q)
        assert h.ends() == [int(e) for e in fired.cohort["window_ends"]]

    def test_it_lands_the_rows_once_in_fire_order_the_stamps_in_order(self, h):
        cohorts, gates = [], []
        for _ in range(3):
            fired = h.fire()
            gates.append(h.gate_newest())
            cohorts.append(fired.cohort)
            h.queue(fired)
            assert gates[-1].polled.wait(WAIT_S)
            assert len(h.sink.rows) == len(cohorts) - 1
            gates[-1].landed.set()
            assert joined(h.driver._emit_q)
        assert h.ends() == reference_ends(3) == [
            int(c["window_ends"][0]) for c in cohorts]
        for c in cohorts:
            stamps = [c["t_fire"], c["t_queued"]] + [c[k] for k in STAMPS]
            assert stamps == sorted(stamps), c
        # a record a window end, all eight stamps there
        recs = h.driver.fire_records()
        assert [r["window_end"] for r in recs] == h.ends()
        assert all(r[k] is not None for r in recs for k in STAMPS)

    def test_under_the_fair_gate_the_waiting_tenant_does_not_hold_the_turn(
            self):
        h = Harness()
        gate = h.driver._drain_gate = FairDrainGate()
        gate.register("peer")
        h.start()
        h.queue(h.fire())
        assert joined(h.driver._emit_q)
        fired = h.fire()
        version = h.gate_newest()
        h.queue(fired)
        assert version.polled.wait(WAIT_S)
        took = threading.Event()

        def peer():
            with gate.turn("peer"):
                took.set()

        t = threading.Thread(target=peer, daemon=True)
        t.start()
        assert took.wait(WAIT_S)        # the turn was free
        t.join(WAIT_S)
        assert gate.members == 2 and not h.sink.rows
        version.landed.set()
        assert joined(h.driver._emit_q)
        assert h.ends() == [int(e) for e in fired.cohort["window_ends"]]
        assert h.stop()


class TestBarrierAndStop:
    def test_a_barrier_during_the_wait_goes_to_the_pinned_fetch(self, h):
        """``_flush_emits`` while the drain waits for a landing: the wait
        ends at once, the barrier's fetch names its version and waits for
        it under the locks as it always has, every queued row is
        delivered before ``_flush_emits`` returns, none twice."""
        first, second = h.fire(), None
        g1 = h.gate_newest(probe=h.locks_held)
        h.queue(first)
        assert g1.polled.wait(WAIT_S)
        second = h.fire()               # a second fire queues behind it
        g2 = h.gate_newest(probe=h.locks_held)
        h.queue(second)
        flushed = threading.Event()

        def flush():
            h.driver._flush_emits()
            flushed.set()

        t = threading.Thread(target=flush, daemon=True)
        t.start()
        # each marker's pinned fetch waits for its version UNDER _link_lock
        for n_before, gate in enumerate((g1, g2)):
            while not any(u["link"] for u in gate.under):
                assert not flushed.is_set()
                assert gate.polled.wait(WAIT_S)
                gate.polled.clear()
                gate.asks = 0
            assert len(h.sink.rows) == n_before
            g2.under.clear()    # the first fetch's choice asked it too
            gate.landed.set()
        assert flushed.wait(WAIT_S)
        t.join(WAIT_S)
        assert not h.driver._flush_req.is_set()
        assert h.ends() == reference_ends(2)
        assert h.ring.fires_decoded == second._ring_no == h.ring.read_no
        for c in (first.cohort, second.cohort):
            assert c["t_queued"] <= c["t_fetch0"] <= c["t_ready"] \
                <= c["t_fetch1"] <= c["t_push0"] <= c["t_sink"]
        # nothing is left for a later poll, and nothing comes twice
        h.queue(FiredWindows(op=h.op, ring=True, ring_no=h.ring.version_no))
        assert joined(h.driver._emit_q)
        assert h.ends() == reference_ends(2)

    def test_an_aborted_run_stops_during_the_wait_and_delivers_nothing(
            self, h):
        fired = h.fire()
        gate = h.gate_newest()
        h.queue(fired)
        assert gate.polled.wait(WAIT_S)
        assert h.stop(discard=True)     # never landed: the thread is gone
        assert not h.sink.rows and not gate.landed.is_set()
        assert h.driver._emit_q.unfinished_tasks == 0

    def test_an_abort_while_the_drain_stands_before_the_lock_delivers_nothing(
            self, h):
        """The re-check under ``_push_lock``: the rows have landed and are
        host arrays, the drain waits for the lock (another delivery, or
        the loop inside a node both threads reach), the run aborts
        meanwhile: once the drain has the lock it delivers nothing."""
        d = h.driver
        assert d._push_lock.acquire(timeout=WAIT_S)
        try:
            fired = h.fire()
            gate = h.gate_newest(landed=True)
            h.queue(fired)
            # fetched and decoded: what is left is the lock
            for _ in range(int(WAIT_S / 0.001)):
                if fired.cohort.get("t_fetch1") is not None:
                    break
                threading.Event().wait(0.001)
            assert gate.asks and not h.sink.rows
            assert d._emit_q.unfinished_tasks == 1
            d._drain_discard[0] = True
        finally:
            d._push_lock.release()
        assert joined(d._emit_q)
        assert not h.sink.rows and fired.cohort.get("t_sink") is None
        assert h.stop(discard=True)

    def test_a_stop_during_the_wait_delivers_what_was_queued(self, h):
        fired = h.fire()
        gate = h.gate_newest(probe=h.locks_held)
        h.queue(fired)
        assert gate.polled.wait(WAIT_S)
        d = h.driver
        d._flush_req.set()
        d._emit_q.put(None)
        # the wait ended; the stop's fetch is a barrier's: under the lock
        while not any(u["link"] for u in gate.under):
            assert gate.polled.wait(WAIT_S)
            gate.polled.clear()
            gate.asks = 0
        gate.landed.set()
        h.thread.join(WAIT_S)
        assert not h.thread.is_alive()
        assert h.ends() == [int(e) for e in fired.cohort["window_ends"]]


    def test_barriers_beside_fires_lose_no_row_and_repeat_none(self, h):
        """Fires handed over as fast as they come, a barrier now and
        then from the loop's side (as a checkpoint's flush), threads
        switching every 10 us: whichever of the landing wait, the
        barrier's pinned fetch and its second pass delivers a row, every
        row comes once and in fire order."""
        import sys

        n = 24
        want = reference_ends(n)    # a tie at the top is two rows an end
        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            for i in range(n):
                h.queue(h.fire())
                if i % 5 == 4:
                    h.driver._flush_emits()
                    # every row queued before the barrier is out
                    assert h.ends() == [e for e in want
                                        if e <= 2_000 * (i + 1)]
            assert joined(h.driver._emit_q)
        finally:
            sys.setswitchinterval(old)
        assert h.ends() == want
        assert h.ring.fires_decoded == h.ring.read_no == h.ring.version_no


class TestWhatIsWaitedFor:
    def test_a_marker_without_rows_waits_for_nothing(self, h):
        """An advance that fired no window end: its marker's poll reads
        what has landed and is delivered empty; an unread version that
        has not landed is not waited for, and nothing is counted."""
        with h.ring.lock:
            h.ring.version_no += 1
            h.ring.announce(h.ring.live)
        gate = h.gate_newest()
        h.queue(FiredWindows(op=h.op, ring=True, ring_no=h.ring.version_no))
        assert joined(h.driver._emit_q)
        assert not h.sink.rows and not gate.landed.is_set()
        assert gate.asks <= 2 and h.ring.read_no < h.ring.version_no
        assert "drain/landing_wait" not in h.driver.phases.details()
        assert h.op.prof["drain_landed"] == h.op.prof["drain_waited"] == 0
        assert h.driver.phases.details()["drain/link_wait"]["count"] == 2

    def test_rows_an_earlier_poll_took_are_not_waited_for_again(self, h):
        """A barrier read through the fire's version before its marker
        was polled: nothing is owed, nothing is waited for."""
        fired = h.fire()
        assert len(h.op.drain_ring(min_no=fired._ring_no)["window_end"]) == 1
        with h.ring.lock:
            h.ring.version_no += 1
            h.ring.announce(h.ring.live)
        gate = h.gate_newest()
        h.queue(fired)
        assert joined(h.driver._emit_q)
        assert not h.sink.rows and gate.asks <= 2
        assert h.op.prof["drain_waited"] == 0

    @pytest.mark.parametrize("n_fires", [1, 4])
    def test_a_row_carrying_fire_announces_its_own_version(self, h, n_fires):
        """Fires a batch apart, the announce cadence never due: the
        version that holds a fire's rows is announced when the fire
        returns, so the drain's wait is for THAT version; an advance
        without rows keeps the cadence and announces nothing."""
        for _ in range(n_fires):
            fired = h.fire()
            assert fired.cohort is not None
            no, arr = h.ring.versions[-1]
            assert no == fired._ring_no == h.ring.version_no
            assert arr is h.ring.live
            before = [n for n, _ in h.ring.versions]
            idle = h.op._ring_after_fire([])        # an advance without rows
            assert idle.cohort is None
            assert [n for n, _ in h.ring.versions] == before
            h.queue(fired)
            h.queue(idle)
        assert joined(h.driver._emit_q)
        assert h.ends() == reference_ends(n_fires)
        polls = h.op.prof["drain_landed"] + h.op.prof["drain_waited"]
        assert 1 <= polls <= n_fires

    def test_the_counters_say_which_polls_waited(self, h):
        waited = h.fire()
        gate = h.gate_newest()
        h.queue(waited)
        assert gate.polled.wait(WAIT_S)
        gate.landed.set()
        assert joined(h.driver._emit_q)
        landed = h.fire()
        h.gate_newest(landed=True)
        h.queue(landed)
        assert joined(h.driver._emit_q)
        assert (h.op.prof["drain_waited"], h.op.prof["drain_landed"]) == (1, 1)
        wait = h.driver.phases.details()["drain/landing_wait"]
        assert wait["count"] == 1 and wait["seconds"] > 0
        # the wait is the drain's between its spans: drain.fetch holds
        # the local reads, two of them with rows
        assert h.op.prof["drain_fetches"] >= 2
        # the fetch began where the drain began to want the rows
        for c in (waited.cohort, landed.cohort):
            assert c["t_queued"] <= c["t_fetch0"] <= c["t_ready"] \
                <= c["t_fetch1"]
        res_keys = {k for k in h.op.prof}
        assert {"drain_landed", "drain_waited"} <= res_keys


class TestTheOtherFires:
    def _session_op(self):
        return DeviceSessionOperator(1_000, count(), num_shards=8,
                                     slots_per_shard=64,
                                     max_out_of_orderness_ms=0)

    def _session_fire(self, op):
        rng = np.random.default_rng(3)
        keys = rng.integers(0, 40, 512).astype(np.int64)
        op.process_batch(keys, np.sort(rng.integers(0, 500, 512)
                                       ).astype(np.int64), {})
        return op.advance_watermark(5_000)

    def test_the_session_operators_passes(self):
        """Every pass announces itself at its dispatch; the drain waits
        for the passes up to its marker's under no lock of the ring, and
        the read after it is local."""
        want = dict(self._session_fire(self._session_op()))
        op = self._session_op()
        fired = self._session_fire(op)
        ring = op.emit_ring
        assert [no for no, _ in ring.versions] == [fired._ring_no]
        no, (head, rows) = ring.versions[0]
        gates = (Gate(np.asarray(head)), Gate(np.asarray(rows)))
        ring.versions[0] = (no, gates)
        until = threading.Event()
        t = threading.Thread(target=FiredWindows.await_landing,
                             args=([fired], until), daemon=True)
        t.start()
        assert gates[0].polled.wait(WAIT_S)
        assert not held_from_another_thread(ring.lock)
        assert t.is_alive() and ring.read_no == 0
        for g in gates:
            g.landed.set()
        t.join(WAIT_S)
        assert not t.is_alive()
        asks = [g.asks for g in gates]
        FiredWindows.materialize_many([fired])
        assert [g.asks - a for g, a in zip(gates, asks)] <= [2, 2]
        got = dict(fired)
        order = np.argsort(got["key"], kind="stable")
        worder = np.argsort(want["key"], kind="stable")
        assert set(got) == set(want)
        for k in want:
            np.testing.assert_array_equal(got[k][order], want[k][worder])
        c = fired.cohort
        assert c["t_fetch0"] <= c["t_ready"] <= c["t_fetch1"]
        assert (op.prof["drain_waited"], op.prof["drain_landed"]) == (1, 0)
        assert op.phases.details()["drain/landing_wait"]["count"] == 1

    def _pack_op(self):
        return WindowOperator(SlidingEventTimeWindows.of(10_000, 2_000),
                              count(), num_shards=8, slots_per_shard=64)

    def _pack_fire(self, op):
        rng = np.random.default_rng(4)
        op.process_batch(rng.integers(0, 30, 1024).astype(np.int64),
                         np.sort(rng.integers(0, 2_000, 1024)
                                 ).astype(np.int64), {})
        return op.advance_watermark(1_999)

    @pytest.mark.parametrize("landed", [False, True])
    def test_a_pack_fires_buffers(self, landed):
        want = dict(self._pack_fire(self._pack_op()))
        op = self._pack_op()
        fired = self._pack_fire(op)
        assert fired._packs and not fired._ring
        gates = [Gate(np.asarray(buf), landed) for _, buf in fired._packs]
        fired._packs = [(lo, g) for (lo, _), g in zip(fired._packs, gates)]
        until = threading.Event()
        t = threading.Thread(target=FiredWindows.await_landing,
                             args=([fired], until), daemon=True)
        t.start()
        if not landed:
            assert gates[0].polled.wait(WAIT_S)
            assert not held_from_another_thread(op.emit_ring.lock)
            assert t.is_alive() and fired._data is None
            for g in gates:
                g.landed.set()
        t.join(WAIT_S)
        assert not t.is_alive()
        FiredWindows.materialize_many([fired])
        got = dict(fired)
        assert len(got["window_end"]) > 0 and set(got) == set(want)
        for k in want:
            np.testing.assert_array_equal(got[k], want[k])
        c = fired.cohort
        assert c["t_fire"] <= c["t_fetch0"] <= c["t_ready"] <= c["t_fetch1"]
        assert (op.prof["drain_waited"], op.prof["drain_landed"]) == (
            (1, 0) if not landed else (0, 1))

    def test_until_ends_the_wait_of_either(self):
        op = self._pack_op()
        fired = self._pack_fire(op)
        gates = [Gate(np.asarray(buf)) for _, buf in fired._packs]
        fired._packs = [(lo, g) for (lo, _), g in zip(fired._packs, gates)]
        until = threading.Event()
        t = threading.Thread(target=FiredWindows.await_landing,
                             args=([fired], until), daemon=True)
        t.start()
        assert gates[0].polled.wait(WAIT_S)
        until.set()
        t.join(WAIT_S)
        assert not t.is_alive() and not gates[0].landed.is_set()


class _SpyEvent(threading.Event):
    """``_flush_req`` (``_drain_wake``) that tells of the waits made on
    it."""

    def __init__(self):
        super().__init__()
        self.timeouts, self.entered = [], threading.Event()

    def wait(self, timeout=None):
        self.timeouts.append(timeout)
        self.entered.set()
        return super().wait(timeout)


class TestMarkersWithoutRowsHurryNoOne:
    """(k) In a replay a window end fires once in ~86 batches: a drain
    that polled after every batch met the loop in the gap between two
    batches, every time."""

    @staticmethod
    def held():
        """A drain that holds: the cadence is an hour."""
        h = Harness()
        h.driver._rowless_hold_s = 3600.0
        spy = h.driver._drain_wake = _SpyEvent()
        return h.start(), spy

    @staticmethod
    def marker(h):
        with h.ring.lock:
            h.ring.version_no += 1
        return FiredWindows(op=h.op, ring=True, ring_no=h.ring.version_no)

    def asleep_on(self, h, spy, n):
        """The drain holds ``n`` markers, asleep for what is left of the
        hour, with no lock of the driver's."""
        assert spy.entered.wait(WAIT_S)
        spy.entered.clear()
        d = h.driver
        while len(spy.timeouts) < n:    # a wait a marker at the most
            assert spy.entered.wait(WAIT_S)
            spy.entered.clear()
        assert 3590.0 < spy.timeouts[-1] <= 3600.0
        assert d._emit_q.unfinished_tasks == n and not h.sink.rows
        for lock in (d._link_lock, h.ring.lock, d._push_lock):
            assert not held_from_another_thread(lock)

    @pytest.mark.parametrize("fired, rowless", [
        (dict(ring=True, ring_no=3), True),
        (dict(ring=True, ring_no=3, cohort={"window_ends": [1]}), False),
        (dict(data={"key": np.zeros(0), "window_end": np.zeros(0)}), True),
        (dict(data={"key": np.ones(2), "window_end": np.ones(2)}), False),
        (dict(packs=[(1, np.zeros(4))], pack_no=1), False),
        (dict(fetch=lambda: {}), False),
    ])
    def test_which_markers_are_without_rows(self, fired, rowless):
        assert FiredWindows(**fired).rowless is rowless

    def test_rows_end_the_hold_and_everything_leaves_in_order(self):
        h, spy = self.held()
        h.queue(h.fire())           # no window end yet
        self.asleep_on(h, spy, 1)
        h.queue(self.marker(h))
        # no one woke the drain: it sleeps on, with one marker
        assert not spy.is_set() and len(spy.timeouts) == 1
        fired = h.fire()
        h.gate_newest(landed=True)
        h.queue(fired)              # rows: the drain is woken
        assert joined(h.driver._emit_q)
        assert h.ends() == [int(e) for e in fired.cohort["window_ends"]]
        details = h.driver.phases.details()
        assert details["drain/hold"]["count"] == 1
        assert details["drain/link_wait"]["count"] == 1     # ONE poll
        assert h.ring.fires_decoded == fired._ring_no
        assert h.stop()

    def test_a_barrier_ends_the_hold(self):
        h, spy = self.held()
        h.queue(h.fire())
        self.asleep_on(h, spy, 1)
        h.driver._flush_emits()
        assert h.driver._emit_q.unfinished_tasks == 0 and not h.sink.rows
        assert h.stop()

    def test_a_stop_ends_the_hold(self):
        h, spy = self.held()
        h.queue(h.fire())
        self.asleep_on(h, spy, 1)
        assert h.stop()
        assert h.driver._emit_q.unfinished_tasks == 0

    def test_the_cadence_ends_the_hold(self):
        """A batch as old as the cadence is polled: nothing left."""
        h = Harness()
        h.driver._rowless_hold_s = 0.0
        spy = h.driver._drain_wake = _SpyEvent()
        h.start()
        h.queue(h.fire())
        assert joined(h.driver._emit_q)
        assert not spy.timeouts
        assert "drain/hold" not in h.driver.phases.details()
        assert h.stop()


class TestTheOption:
    def test_an_explicit_defer_ages_the_marker_first(self):
        """An hour of ``pipeline.emit-defer``: the marker ages (the wait
        is on ``_flush_req``, for what is left of the hour) BEFORE the
        drain chooses or asks for any version; a barrier ends it."""
        h = Harness(**{"pipeline.emit-defer": "3600s"})
        assert h.driver._emit_defer_s == 3600.0
        spy = h.driver._flush_req = _SpyEvent()
        h.start()
        h.fire()
        fired = h.fire()
        gate = h.gate_newest(landed=True)
        h.queue(fired)
        assert spy.entered.wait(WAIT_S)
        assert 3590.0 < spy.timeouts[0] <= 3600.0
        assert gate.asks == 0 and not h.sink.rows
        h.driver._flush_emits()
        assert h.ends() == [int(e) for e in fired.cohort["window_ends"]]
        d = h.driver.phases.details()
        assert d["drain/defer"]["count"] >= 1
        assert "drain/landing_wait" not in d
        assert h.stop()

    @pytest.mark.parametrize("backend", ["cpu", "tpu", "gpu"])
    def test_auto_is_no_age_whatever_the_backend(self, backend, monkeypatch):
        import jax

        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        h = Harness()
        assert h.driver._emit_defer_s == 0.0
        assert Harness(**{"pipeline.emit-defer": "25ms"}
                       ).driver._emit_defer_s == 0.025
