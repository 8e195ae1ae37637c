"""ADVANCE FIRST (PR 45): the watermark pass a batch implies goes ahead of
the batch's push wherever none of its records lies at or below that
watermark (``Driver._lead_advance``; the operators' answer is
``lead_advance``), so a window fires when the batch that completes it
arrives and not after that batch has been keyed, packed and uploaded.

The two orders must be ONE result. The old order (push, then advance)
lives on here as the reference: the operators' answer patched to "no".

- the operators, driven by hand in both orders: fired rows,
  ``late_records``, the final watermark, a mid-stream snapshot's
  rows by key, and a restore from that snapshot that then continues;
- the driver's loop through ``env.execute``: committed rows and the
  counters ``wm.advances`` / ``wm.advances_led``; the batches and the
  jobs that keep the old order.
"""
import numpy as np
import pytest

import flink_tpu  # noqa: F401 — x64 before other jax users
from flink_tpu.api.environment import StreamExecutionEnvironment
from flink_tpu.api.sinks import CollectSink
from flink_tpu.api.sources import GeneratorSource
from flink_tpu.api.windowing import SlidingEventTimeWindows
from flink_tpu.config import Configuration
from flink_tpu.nexmark.queries import (
    q5_hot_items, q11_user_sessions, q17_auction_stats)
from flink_tpu.ops.aggregates import count, multi, sum_of
from flink_tpu.ops.session_device import DeviceSessionOperator
from flink_tpu.ops.window import FUSED_DOMAIN_MAX, WindowOperator
from flink_tpu.time.watermarks import LONG_MIN

BATCH = 512
SPAN = 500              # ms of event time a batch
WINDOW, SLIDE = 10_000, 2_000
DELAY = 1_000
GAP = 3_000
KEYS = 3_000            # about as many as records: the general lane
SHARDS = 8
# 8 x 16,384 slots x 11 ring columns: past what the fused step's upload
# can address, so no batch is ever stashed (WindowOperator._may_stash)
BIG = 16_384
N_BATCHES = 44
RETURNING = 7_777_777   # the key of ``key_returns``


# -- streams -----------------------------------------------------------------

def in_order(n=N_BATCHES, seed=3):
    """Batches in order, 500 ms of event time each: one in four ends a
    window (a slide is 2 s)."""
    out = []
    for i in range(n):
        rng = np.random.default_rng(seed * 1000 + i)
        ts = np.sort(i * SPAN + rng.integers(0, SPAN, BATCH)).astype(np.int64)
        keys = rng.integers(0, KEYS, BATCH).astype(np.int64)
        out.append((keys, ts, {"v": rng.integers(1, 9, BATCH).astype(
            np.float32)}))
    return out


def disordered(n=N_BATCHES):
    """Every sixth batch reaches 1.6 s back: its earliest record lies at
    or below the watermark it implies, and it must take the old order."""
    out = in_order(n)
    for i in range(6, n, 6):
        keys, ts, data = out[i]
        ts = ts.copy()
        ts[:8] -= 1_600
        out[i] = (keys, ts, data)
    return out


LATE_FROM = 28


def late(n=N_BATCHES):
    """One record a batch from ``LATE_FROM`` on is stamped 13 s back: its
    pane is dead at a lateness of 0 (a late record, dropped and counted);
    within a lateness of 8 s it re-fires windows that have fired."""
    out = in_order(n)
    for i in range(LATE_FROM, n):
        keys, ts, data = out[i]
        ts = ts.copy()
        ts[0] -= 13_000
        out[i] = (keys, ts, data)
    return out


def key_returns(n=N_BATCHES):
    """One key is seen in pane 0 alone, and again in the very batch whose
    watermark purges pane 0 (and for a session: closes its session): with
    the advance first it is released and inserted anew, with the batch
    first it survives."""
    out = in_order(n)
    for i in range(n):
        keys, ts, data = out[i]
        # pane 0's last window ends at 10 s: dead at watermark 9,999,
        # which the batch holding 11,000 implies (i = 22); the key's
        # session (gap 3 s, last < 500) closes at ~3.5 s + delay (i = 9)
        if i == 0 or (ts.min() <= 11_000 <= ts.max()) \
                or (ts.min() <= 4_600 <= ts.max()):
            keys = keys.copy()
            keys[-3:] = RETURNING
            out[i] = (keys, ts, data)
    return out


def idle_gap(n=N_BATCHES):
    """Event time jumps 40 s ahead after the twentieth batch: the advance
    that leads the first batch behind the gap enumerates its window ends
    before ``_max_pane_seen`` has jumped too (those that can hold data:
    the same rows), and purges everything."""
    out = in_order(n)
    return out[:20] + [(k, ts + 40_000, d) for k, ts, d in out[20:]]


STREAMS = {"in_order": in_order, "disordered": disordered, "late": late,
           "key_returns": key_returns, "idle_gap": idle_gap}


# -- operators ---------------------------------------------------------------

def topn(lateness=0):
    return WindowOperator(
        SlidingEventTimeWindows.of(WINDOW, SLIDE), count(),
        num_shards=SHARDS, slots_per_shard=BIG, allowed_lateness_ms=lateness,
        max_out_of_orderness_ms=DELAY, top_n=("count", 1))


def pack(lateness=0):
    """No top-n: ``fire_pack_kernel``, a count and a summed column."""
    return WindowOperator(
        SlidingEventTimeWindows.of(WINDOW, SLIDE),
        multi(count(), sum_of("v")), num_shards=SHARDS, slots_per_shard=2048,
        allowed_lateness_ms=lateness, max_out_of_orderness_ms=DELAY)


def sessions(lateness=0, delay=DELAY):
    assert lateness == 0
    return DeviceSessionOperator(
        GAP, count(), num_shards=SHARDS, slots_per_shard=2048,
        max_out_of_orderness_ms=delay)


OPERATORS = {"topn": (topn, 0), "topn_lateness": (topn, 8_000),
             "pack": (pack, 0), "pack_lateness": (pack, 8_000),
             "sessions": (sessions, 0)}


class CountsMin:
    """A batch's timestamps as ``lead_advance`` sees them, counting the
    passes ``min`` makes over them."""

    def __init__(self, ts, calls):
        self._ts, self._calls = ts, calls

    def min(self):
        self._calls.append(len(self._ts))
        return self._ts.min()

    def __len__(self):
        return len(self._ts)


def drive(op, batches, lead, snapshot_at=None, max_seen=LONG_MIN,
          delay=DELAY):
    """Feed ``batches`` as the driver's plain loop does: the watermark a
    batch implies (bounded out-of-orderness) goes AHEAD of it where
    ``lead`` and the operator says yes, behind it otherwise. -> (fired
    batches, index of the led batches, ``min`` passes, snapshot)."""
    fired, led, mins, snap = [], [], [], None
    for i, (keys, ts, data) in enumerate(batches):
        if i == snapshot_at:
            snap = op.snapshot_state()
        mx = int(ts.max())
        max_seen = max(max_seen, mx)
        wm = max_seen - delay - 1
        if lead and op.lead_advance(
                wm, ts if isinstance(op, DeviceSessionOperator)
                else CountsMin(ts, mins)):
            fired.append(dict(op.advance_watermark(wm)))
            op.run_pending_release()
            led.append(i)
        op.process_batch(keys, ts, data)
        fired.append(dict(op.advance_watermark(wm)))
        op.run_pending_release()
    fired.append(dict(op.advance_watermark(op.final_watermark())))
    op.run_pending_release()
    return fired, led, mins, snap


def rows_of(fired):
    """Fired batches as one sorted list of rows."""
    rows = []
    for f in fired:
        n = len(f.get("window_end", ()))
        cols = [np.asarray(f[k]) for k in sorted(f)]
        rows.extend(tuple(c[j].item() for c in cols) for j in range(n))
    return sorted(rows)


def state_by_key(op, snap):
    """A snapshot's contents by what they mean, not by where they lie: a
    window operator's (key, pane) -> lanes over the panes that are
    alive, a session operator's open sessions; and the time fields."""
    if isinstance(op, DeviceSessionOperator):
        cols = snap["columns"]
        return ({"sessions": sorted(zip(*(np.asarray(cols[c]).tolist()
                                          for c in ("key", "start", "last",
                                                    "count"))))},
                (snap["watermark"], snap["late_records"]))
    d = snap["directory"]
    used = np.flatnonzero(d["rev_used"])
    counts = np.asarray(snap["panes"].counts)
    sums = (None if snap["panes"].sums is None
            else np.asarray(snap["panes"].sums))
    cells = {}
    ring = snap["ring"]
    lo = max(snap["cleared_below"], snap["min_pane_seen"])
    for pane in range(lo, snap["max_pane_seen"] + 1):
        col = pane % ring
        for slot in used[counts[used, col] > 0].tolist():
            cells[(int(d["rev_keys"][slot]), pane)] = (
                int(counts[slot, col]),
                None if sums is None else sums[slot, col].tolist())
    # nothing counted lies outside the used slots' live panes
    assert int(counts[:-1].sum()) == sum(c for c, _ in cells.values())
    return cells, tuple(snap[k] for k in (
        "watermark", "cleared_below", "fired_below_end", "min_pane_seen",
        "max_pane_seen", "refire", "late_records", "records_dropped_full"))


@pytest.mark.parametrize("stream", sorted(STREAMS))
@pytest.mark.parametrize("kind", sorted(OPERATORS))
def test_both_orders_are_one_result(kind, stream):
    make, lateness = OPERATORS[kind]
    batches = STREAMS[stream]()
    at = 34     # mid-stream: windows have fired, panes have been purged
    a, b = make(lateness), make(lateness)
    fired_a, led, mins, snap_a = drive(a, batches, True, snapshot_at=at)
    fired_b, led_b, _, snap_b = drive(b, batches, False, snapshot_at=at)
    assert led_b == []
    assert rows_of(fired_a) == rows_of(fired_b) != []
    assert a.late_records == b.late_records
    assert a.watermark == b.watermark
    assert a.records_dropped_full == b.records_dropped_full == 0
    assert a.slots_returned_early == b.slots_returned_early == 0
    assert state_by_key(a, snap_a) == state_by_key(b, snap_b)

    # which batches led: none before something is folded in; under
    # sessions every in-order batch, under windows those whose watermark
    # passes a window end or a purge horizon (a slide is four batches)
    assert led and led[0] >= 1
    if stream == "late":
        assert a.late_records == (0 if lateness else N_BATCHES - LATE_FROM)
        assert not [i for i in led if i >= LATE_FROM]   # never
    if stream == "disordered":
        assert not [i for i in led if i % 6 == 0]
    if kind == "sessions":
        if stream in ("in_order", "key_returns"):
            assert led == list(range(1, N_BATCHES))
    else:
        assert len(led) <= N_BATCHES // 4 + 2
        # ``min`` is taken for the batches whose watermark has something
        # to do and for no other (one pass each)
        assert len(mins) <= N_BATCHES // 4 + 2 and set(mins) == {BATCH}
        assert len(mins) >= len(led)

    # a restore from the mid-stream snapshot that then continues, in
    # either order, fires what the uninterrupted run fired from there on
    seen = max(int(ts.max()) for _, ts, _ in batches[:at])
    tails = []
    for snap, lead in ((snap_a, True), (snap_b, False), (snap_a, False)):
        if lead is False and snap is snap_a:
            # a snapshot's device buffers go to the operator restored
            # from it: take a fresh one for the third
            snap = drive(make(lateness), batches[:at + 1], True,
                         snapshot_at=at)[3]
        op = make(lateness)
        op.restore_state(snap)
        tails.append(rows_of(drive(op, batches[at:], lead,
                                   max_seen=seen)[0]))
    assert tails[0] == tails[1] == tails[2] != []
    before = rows_of(drive(make(lateness), batches[:at], False)[0][:-1])
    # (the uninterrupted run's rows, less those fired before the
    # snapshot; a top-n's re-fired window replaces its earlier row)
    if lateness == 0:
        whole = rows_of(fired_b)
        for r in before:
            whole.remove(r)
        assert tails[0] == whole


def test_a_purged_key_named_again_by_the_same_batch():
    """``key_returns`` under the window operator, looked at closely: the
    advance that purges pane 0 runs ahead of the batch that names the
    key again, so the key is released and inserted anew (the slot
    counters move by it) where the old order keeps it; the rows are
    equal (above) and nothing comes back early."""
    batches = key_returns()
    a, b = pack(), pack()
    drive(a, batches, True)
    drive(b, batches, False)
    ca, cb = a.state_counters(), b.state_counters()
    assert ca["state.slots_returned_early"] == 0
    assert cb["state.slots_returned_early"] == 0
    # released once more, allocated once more
    assert ca["state.slots_released"] >= cb["state.slots_released"]
    assert (ca["state.slots_allocated"] - cb["state.slots_allocated"]
            == ca["state.slots_released"] - cb["state.slots_released"])


# -- a session takes a record in up to a whole gap behind its last -----------

def one_stamp_batches(n=40, step=GAP // 3, rest=64):
    """Batches whose records share ONE timestamp (the next batch's lies
    ``step`` later), and one key with events exactly a gap apart: in
    every third batch. Under a delay of 0 the watermark a batch implies
    is its timestamp less one, so the batch's earliest record lies at
    ``wm + 1``, and the session of the key is due at that very
    watermark: ``last + gap - 1 = wm``. The record extends it (``t2 - t1
    <= gap``) if it comes first and opens a second session if the
    advance does."""
    out = []
    for i in range(n):
        rng = np.random.default_rng(7_000 + i)
        keys = rng.integers(1, KEYS, rest).astype(np.int64)
        if i % 3 == 0:
            keys[0] = RETURNING
        else:
            keys[keys == RETURNING] = 1
        out.append((keys, np.full(rest, 10_000 + i * step, np.int64), {}))
    return out


def at_the_millisecond_above(n=40):
    """In-order batches under the usual delay, but every eighth spans
    exactly ``DELAY``, so its earliest record lies at ``wm + 1``; that
    record belongs to a key whose last event came exactly ``GAP``
    earlier (six batches back): its session is due at ``wm``."""
    out = in_order(n)
    for i in range(8, n, 8):
        keys, ts, data = out[i]
        keys, ts = keys.copy(), ts.copy()
        lo = int(ts[0])                     # sorted: the earliest
        ts[-1] = lo + DELAY                 # the newest: wm = lo - 1
        keys[0] = RETURNING
        out[i] = (keys, ts, data)
        keys, ts, data = out[(lo - GAP) // SPAN]
        keys, ts = keys.copy(), ts.copy()
        ts[5], keys[5] = lo - GAP, RETURNING
        out[(lo - GAP) // SPAN] = (keys, ts, data)
    return out


@pytest.mark.parametrize("case", ["delay_0_one_stamp", "wm_plus_1"])
def test_a_record_a_gap_behind_a_due_session_extends_it_in_both_orders(case):
    """The session gate is ``min > wm + 1``, not ``min > wm``: a batch
    whose earliest record lies at the millisecond above the watermark
    takes the old order, since that record may extend a session the
    advance would close."""
    if case == "delay_0_one_stamp":
        batches, delay = one_stamp_batches(), 0
    else:
        batches, delay = at_the_millisecond_above(), DELAY
    a, b = sessions(delay=delay), sessions(delay=delay)
    fired_a, led, _, _ = drive(a, batches, True, delay=delay)
    fired_b, _, _, _ = drive(b, batches, False, delay=delay)
    rows = rows_of(fired_a)
    assert rows == rows_of(fired_b) != []
    assert a.late_records == b.late_records == 0
    # the key's events a gap apart are ONE session each time they meet
    mine = [dict(zip(sorted(fired_b[-1]), r)) for r in rows
            if r[sorted(fired_b[-1]).index("key")] == RETURNING]
    assert mine and max(r["count"] for r in mine) >= 2
    for i, (_, ts, _) in enumerate(batches):
        wm = int(max(t.max() for _, t, _ in batches[:i + 1])) - delay - 1
        if int(ts.min()) == wm + 1:
            assert i not in led
    if case == "delay_0_one_stamp":
        assert led == []        # every batch lies at wm + 1
    else:
        assert led              # the batches between them lead


def test_the_session_gate_at_the_boundary():
    op = sessions()
    op.process_batch(np.arange(8, dtype=np.int64), np.arange(8, dtype=np.int64),
                     {})
    ts = np.arange(5_000, 5_008, dtype=np.int64)
    assert op.lead_advance(4_998, ts)
    assert not op.lead_advance(4_999, ts)       # min == wm + 1
    assert not op.lead_advance(5_000, ts)


def test_the_lane_decides_before_a_batch_is_looked_at():
    """A job whose batches CAN ride the fused step (small state, top-n,
    count only) answers no for every batch, its first included; the same
    job past the fused upload's reach, without a top-n, or with a summed
    lane answers by the batch."""
    ts = np.arange(20_000, 20_000 + BATCH, dtype=np.int64)

    def warmed(op):
        op.process_batch(np.arange(BATCH, dtype=np.int64) % 50,
                         np.arange(BATCH, dtype=np.int64), {
                             "v": np.ones(BATCH, np.float32)})
        return op

    small = WindowOperator(
        SlidingEventTimeWindows.of(WINDOW, SLIDE), count(), num_shards=SHARDS,
        slots_per_shard=64, max_out_of_orderness_ms=DELAY,
        top_n=("count", 1))
    assert small._may_stash() and not small.may_lead_advance()
    assert small.layout.slots * small.plan.ring <= FUSED_DOMAIN_MAX
    assert not warmed(small).lead_advance(15_000, ts)
    for op in (topn(), pack()):
        assert not op._may_stash() and op.may_lead_advance()
        # nothing folded in yet: no
        assert not op.lead_advance(15_000, ts)
        warmed(op)
        assert op.lead_advance(15_000, ts)
        # a record at the watermark: no; a watermark that does not move: no
        assert not op.lead_advance(int(ts[0]), ts)
        op.advance_watermark(15_000)
        assert not op.lead_advance(15_000, ts)
        # the next watermark that passes no window end and no horizon: no
        assert not op.lead_advance(15_100, ts)


# -- the driver's loop ---------------------------------------------------------

def source_of(batches, key):
    def gen(split, i):
        if i >= len(batches):
            return None
        keys, ts, data = batches[i]
        return {key: keys, **{k: v for k, v in data.items()}}, ts
    return GeneratorSource(gen)


def build_q5(env, batches, sink):
    q5_hot_items(env, source_of(batches, "auction"), sink, window_ms=WINDOW,
                 slide_ms=SLIDE, out_of_orderness_ms=DELAY)


def build_pack(env, batches, sink, lateness=0):
    from flink_tpu.time.watermarks import WatermarkStrategy
    w = (env.from_source(source_of(batches, "auction"),
                         WatermarkStrategy.for_bounded_out_of_orderness(DELAY))
         .key_by("auction")
         .window(SlidingEventTimeWindows.of(WINDOW, SLIDE)))
    if lateness:
        w = w.allowed_lateness(lateness)
    w.aggregate(multi(count(), sum_of("v"))).add_sink(sink)


def build_q11(env, batches, sink):
    q11_user_sessions(env, source_of(batches, "bidder"), sink, gap_ms=GAP,
                      out_of_orderness_ms=DELAY)


def run_job(build, batches, lead=True, monkeypatch=None, **conf):
    """-> (committed rows sorted, metrics, driver). ``lead`` False: the
    operators' answer patched to "no", the old order."""
    if not lead:
        for cls in (WindowOperator, DeviceSessionOperator):
            monkeypatch.setattr(cls, "lead_advance",
                                lambda self, wm, ts: False)
    env = StreamExecutionEnvironment(Configuration({
        "pipeline.microbatch-size": BATCH, "state.num-key-shards": SHARDS,
        "state.slots-per-shard": 2048, "analysis.fail-on": "off", **conf}))
    sink = CollectSink()
    build(env, batches, sink)
    res = env.execute("advance-first")
    rows = sorted(tuple((k, np.asarray(r[k]).item()) for k in sorted(r))
                  for r in sink.rows)
    return rows, res.metrics, env._driver


JOBS = {
    "q5_general_lane": (build_q5, {"state.slots-per-shard": BIG}),
    "pack_count_and_sum": (build_pack, {}),
    "q11_sessions": (build_q11, {"state.slots-per-shard": 2048}),
}


@pytest.mark.parametrize("stream", sorted(STREAMS))
@pytest.mark.parametrize("job", sorted(JOBS))
def test_the_loop_commits_the_same_rows_in_either_order(job, stream,
                                                        monkeypatch):
    build, conf = JOBS[job]
    batches = STREAMS[stream]()
    rows, m, driver = run_job(build, batches, **conf)
    assert len(driver._lead_ops) == 1
    old, m_old, _ = run_job(build, batches, lead=False,
                            monkeypatch=monkeypatch, **conf)
    assert rows == old != []
    for k in ("late_records", "records_dropped_full", "records_in",
              "fired_windows", "state.slots_returned_early"):
        assert m[k] == m_old[k], k
    assert m["late_records"] == (
        N_BATCHES - LATE_FROM if stream == "late" else 0)
    # the old order led nothing, and as many passes fired or purged
    assert m_old["wm.advances_led"] == 0
    assert m["wm.advances"] == m_old["wm.advances"] > 0
    assert 0 < m["wm.advances_led"] < m["wm.advances"]      # the flush
    assert m["profile.phase.wm_advances"] == m["wm.advances"]
    assert m["profile.phase.wm_advances_led"] == m["wm.advances_led"]
    if stream == "in_order":
        # every pass that fired or purged but the first window's (nothing
        # was folded in before the first batch) and the flush's
        assert m["wm.advances_led"] >= m["wm.advances"] - 2
    if stream == "late":
        # from ``LATE_FROM`` on every batch holds a record below its
        # watermark: the old order, not counted
        assert m["wm.advances_led"] <= LATE_FROM
    if stream in ("late", "disordered"):
        # the passes of those batches fired or purged all the same, in
        # the old order: four or more of them are not among the led
        assert m["wm.advances_led"] <= m["wm.advances"] - 5
    # a led fire's stamps stay in order
    for f in m["trace.fires"]:
        assert f["t_input"] <= f["t_fire"]
        if f["t_queued"] is not None:
            assert f["t_fire"] <= f["t_queued"]


def test_the_loop_at_delay_0_keeps_a_session_whole(monkeypatch):
    """``forMonotonousTimestamps``: the watermark a batch implies is its
    newest timestamp less one. Batches of one timestamp lie at ``wm + 1``
    every time and take the old order; a key with events exactly a gap
    apart keeps its one session."""
    def build(env, batches, sink):
        q11_user_sessions(env, source_of(batches, "bidder"), sink,
                          gap_ms=GAP, out_of_orderness_ms=0)

    batches = one_stamp_batches(rest=BATCH)
    rows, m, driver = run_job(build, batches)
    assert len(driver._lead_ops) == 1
    old, m_old, _ = run_job(build, batches, lead=False,
                            monkeypatch=monkeypatch)
    assert rows == old != []
    assert m["wm.advances_led"] == 0 and m["late_records"] == 0
    assert m["wm.advances"] == m_old["wm.advances"] > 0


def test_a_batch_that_ends_no_window_is_not_looked_at(monkeypatch):
    """The question costs a pass over the batch's timestamps: it is put
    only for a batch whose watermark has something to do."""
    asked, looked = [], []
    lead = WindowOperator.lead_advance

    def spy(self, wm, ts):
        asked.append(wm)
        return lead(self, wm, CountsMin(ts, looked))

    monkeypatch.setattr(WindowOperator, "lead_advance", spy)
    rows, m, _ = run_job(build_pack, in_order())
    assert rows
    assert len(asked) == N_BATCHES          # every batch of the plain loop
    assert m["wm.advances_led"] <= len(looked) <= N_BATCHES // 4 + 2


def old_order(m, driver):
    assert driver._lead_ops == ()
    assert m["wm.advances_led"] == 0
    assert m["wm.advances"] > 0


def window_op(driver):
    return next(op for op in driver._ops.values()
                if isinstance(op, WindowOperator))


def test_the_fused_lane_keeps_the_old_order():
    """Small state, top-n, count only: the stash, the fires and the purge
    ride one launch, and the advance never leads: the job's loop asks no
    operator and reads a batch's newest timestamp behind the push."""
    batches = [(k % 50, ts, d) for k, ts, d in in_order()]
    rows, m, driver = run_job(build_q5, batches)
    assert rows
    old_order(m, driver)
    assert window_op(driver)._may_stash()


def test_a_mesh_keeps_the_old_order():
    import jax
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    rows, m, driver = run_job(
        build_q5, in_order(), **{"cluster.mesh-devices": 4,
                                 "state.slots-per-shard": BIG})
    assert rows
    old_order(m, driver)
    assert window_op(driver).mesh_plan is not None


@pytest.mark.parametrize("job", ["pack_count_and_sum", "q11_sessions",
                                 "q5_general_lane"])
def test_a_conf_that_still_sets_the_removed_sub_batches_key_leads(job):
    """``pipeline.sub-batches`` went with its loop (PR 46): the key is
    unknown, the job runs the one loop there is and leads like any
    other, one step a batch (it kept the old order at 4, in 4 slices)."""
    build, conf = JOBS[job]
    plain, m_plain, _ = run_job(build, in_order(), **conf)
    rows, m, driver = run_job(build, in_order(),
                              **{**conf, "pipeline.sub-batches": 4})
    assert rows == plain != []
    assert len(driver._lead_ops) == 1
    assert m["wm.advances_led"] == m_plain["wm.advances_led"] > 0
    assert m["batches"] == m_plain["batches"] == N_BATCHES


def test_global_agg_keeps_the_old_order():
    batches = [(k, ts, {"price": np.asarray(d["v"], np.int64)})
               for k, ts, d in in_order(12)]
    env = StreamExecutionEnvironment(Configuration({
        "pipeline.microbatch-size": BATCH, "state.num-key-shards": SHARDS,
        "state.slots-per-shard": 1024, "analysis.fail-on": "off"}))
    sink = CollectSink()
    q17_auction_stats(env, source_of(batches, "auction"), sink)
    m = env.execute("advance-first-q17").metrics
    assert sink.rows
    # a chain function ahead of the operator, and no watermark fire
    assert env._driver._lead_ops == ()
    assert m["wm.advances_led"] == m["wm.advances"] == 0


def test_a_two_source_join_keeps_the_old_order():
    from flink_tpu.api.windowing import TumblingEventTimeWindows
    from flink_tpu.time.watermarks import WatermarkStrategy
    batches = in_order(16)
    env = StreamExecutionEnvironment(Configuration({
        "pipeline.microbatch-size": BATCH, "state.num-key-shards": SHARDS,
        "state.slots-per-shard": 1024, "analysis.fail-on": "off"}))
    sink = CollectSink()
    wms = WatermarkStrategy.for_bounded_out_of_orderness(DELAY)
    left = env.from_source(source_of(batches, "k"), wms)
    right = env.from_source(source_of(batches[::-1][:8][::-1], "k"), wms)
    (left.join(right).where("k").equal_to("k")
     .window(TumblingEventTimeWindows.of(SLIDE))
     .apply(mode="aggregate").add_sink(sink))
    m = env.execute("advance-first-join").metrics
    assert env._driver._lead_ops == ()
    assert m["wm.advances_led"] == 0


def test_a_chain_ahead_of_the_window_keeps_the_old_order():
    """A chain function may return other timestamps: where one lies
    between the source and the operator the loop cannot know that the
    orders commute, and keeps today's."""
    def build(env, batches, sink):
        from flink_tpu.time.watermarks import WatermarkStrategy
        (env.from_source(source_of(batches, "auction"),
                         WatermarkStrategy.for_bounded_out_of_orderness(DELAY))
         .map(lambda d: d, name="identity")
         .key_by("auction")
         .window(SlidingEventTimeWindows.of(WINDOW, SLIDE))
         .aggregate(multi(count(), sum_of("v"))).add_sink(sink))

    rows, m, driver = run_job(build, in_order())
    assert rows
    old_order(m, driver)
    assert rows == run_job(build_pack, in_order())[0]
