"""The device session lane (ops/session_device.py) against a plain
per-key reference, against the host registry (ops/session.py), and its
ring, release and snapshot plumbing. CPU, small sizes, seeded."""
import numpy as np
import pytest

from flink_tpu.ops import aggregates, session_device
from flink_tpu.ops.session import SessionOperator
from flink_tpu.ops.session_device import (
    MAX_LANES, DeviceSessionOperator, device_lane_fits, lanes_needed)
from flink_tpu.time.watermarks import LONG_MIN

GAP = 100


class Reference:
    """Sessions record by batch, per key, in plain Python: the rule of
    the issue's section 1. ``sessions[k]`` = list of [start, last,
    count, sum, max]."""

    def __init__(self, gap):
        self.gap, self.wm = gap, LONG_MIN
        self.sessions, self.late, self.rows = {}, 0, []

    def batch(self, keys, ts, price):
        g = self.gap
        order = np.lexsort((ts, keys))
        live = []
        for i in order:
            k, t = int(keys[i]), int(ts[i])
            if self.wm != LONG_MIN and t + g - 1 <= self.wm and not any(
                    s[0] <= t + g and t <= s[1] + g
                    for s in self.sessions.get(k, [])):
                self.late += 1
                continue
            live.append((k, t, float(price[i])))
        # the batch's own runs first (as both lanes make them), then
        # each run into the key's open sessions
        runs = []
        for k, t, p in live:
            if runs and runs[-1][0] == k and t - runs[-1][2] <= g:
                r = runs[-1]
                r[2], r[3], r[4], r[5] = t, r[3] + 1, r[4] + p, max(r[5], p)
            else:
                runs.append([k, t, t, 1, p, p])
        for k, s0, s1, c, sm, mx in runs:
            mine = self.sessions.setdefault(k, [])
            hit = [s for s in mine if s0 <= s[1] + g and s[0] <= s1 + g]
            for s in hit:
                mine.remove(s)
                s0, s1 = min(s0, s[0]), max(s1, s[1])
                c, sm, mx = c + s[2], sm + s[3], max(mx, s[4])
            mine.append([s0, s1, c, sm, mx])

    def advance(self, wm):
        self.wm = max(self.wm, wm)
        for k, mine in self.sessions.items():
            for s in [s for s in mine if s[1] + self.gap - 1 <= self.wm]:
                mine.remove(s)
                self.rows.append((k, s[0], s[1] + self.gap, s[2], s[3], s[4]))


def rows_of(fired, fields=("count",)):
    d = dict(fired)
    cols = [np.asarray(d[f]).tolist()
            for f in ("key", "window_start", "window_end") + tuple(fields)]
    return list(zip(*cols))


def device_op(agg=None, **kw):
    kw.setdefault("num_shards", 4)
    kw.setdefault("slots_per_shard", 64)
    kw.setdefault("max_out_of_orderness_ms", 40)
    return DeviceSessionOperator(GAP, agg or aggregates.count(), **kw)


def drive(op, batches, wms):
    """Batches and the watermark after each; every fired row. Either
    lane: the registry has no release to run."""
    release = getattr(op, "run_pending_release", lambda: None)
    rows = []
    for (keys, ts, data), wm in zip(batches, wms):
        op.process_batch(keys, ts, data)
        rows += rows_of(op.advance_watermark(wm))
        release()
    rows += rows_of(op.advance_watermark(op.final_watermark()))
    release()
    return rows


def random_stream(seed, n_batches=12, n=120, keys=24, span=90, hot=False):
    """In-order batches ``span`` ms apart whose records scatter over
    3 spans: in-batch gaps, runs that bridge, keys with two sessions
    open, and records late by the time they come."""
    rng = np.random.default_rng(seed)
    out, wms = [], []
    for b in range(n_batches):
        k = rng.integers(0, keys, n)
        if hot:
            k[rng.random(n) < 0.75] = 7
        t = b * span + rng.integers(0, 3 * span, n)
        # some keys fall silent for a while: sessions end
        quiet = (k + b) % 3 == 0
        k, t = k[~quiet], t[~quiet]
        out.append((k.astype(np.int64), t.astype(np.int64),
                    {"price": rng.integers(1, 1000, len(k)).astype(np.int64)}))
        wms.append(int(b * span + 3 * span - 40))
    return out, wms


@pytest.mark.parametrize("seed", [1, 2, 3, 4])
@pytest.mark.parametrize("hot", [False, True])
def test_device_lane_equals_the_plain_reference(seed, hot):
    batches, wms = random_stream(seed, hot=hot)
    ref = Reference(GAP)
    for (k, t, d), wm in zip(batches, wms):
        ref.batch(k, t, d["price"])
        ref.advance(wm)
    ref.advance(10**9)
    op = device_op()
    got = drive(op, batches, wms)
    assert sorted(got) == sorted(r[:4] for r in ref.rows)
    assert len(got) == len(set(got))
    assert op.late_records == ref.late
    c = op.state_counters()
    assert c["session.fired"] == len(got)
    assert c["session.opened"] + c["session.merged"] >= len(got)
    assert c["state.slots_returned_early"] == 0


def test_the_stream_holds_what_the_issue_names():
    """The random streams really have in-batch gaps, bridges, two open
    sessions a key and late records of both kinds (else the test above
    would hold for less than it says)."""
    seen = dict(gaps=0, bridges=0, two=0, late=0, rescued=0)
    for seed in (1, 2, 3, 4):
        batches, wms = random_stream(seed)
        ref = Reference(GAP)
        for (k, t, d), wm in zip(batches, wms):
            dead = t + GAP - 1 <= ref.wm if ref.wm != LONG_MIN else t < 0
            late0 = ref.late
            before = {key: len(v) for key, v in ref.sessions.items()}
            ref.batch(k, t, d["price"])
            seen["late"] += ref.late - late0
            seen["rescued"] += int(dead.sum()) - (ref.late - late0)
            for key in np.unique(k).tolist():
                tk = np.sort(t[k == key])
                seen["gaps"] += int((np.diff(tk) > GAP).any())
                now = len(ref.sessions.get(key, []))
                seen["bridges"] += int(now < before.get(key, 0))
                seen["two"] += int(now >= 2)
            ref.advance(wm)
    assert all(v > 0 for v in seen.values()), seen


@pytest.mark.parametrize("seed", [5, 6])
def test_lifted_lanes_sum_and_max(seed):
    agg = aggregates.multi(aggregates.sum_of("price"),
                             aggregates.max_of("price"))
    batches, wms = random_stream(seed)
    ref = Reference(GAP)
    for (k, t, d), wm in zip(batches, wms):
        ref.batch(k, t, d["price"])
        ref.advance(wm)
    ref.advance(10**9)
    op = device_op(agg)
    rows = []
    for (k, t, d), wm in zip(batches, wms):
        op.process_batch(k, t, d)
        rows += rows_of(op.advance_watermark(wm),
                        ("count", "sum_price", "max_price"))
    rows += rows_of(op.advance_watermark(op.final_watermark()),
                    ("count", "sum_price", "max_price"))
    assert sorted(rows) == sorted(ref.rows)


@pytest.mark.parametrize("seed", [1, 3])
def test_both_lanes_give_the_same_rows(seed):
    batches, wms = random_stream(seed)
    host = SessionOperator(GAP, aggregates.count(),
                           max_out_of_orderness_ms=40)
    assert sorted(drive(device_op(), batches, wms)) == sorted(
        drive(host, batches, wms))


@pytest.mark.parametrize("to_device", [True, False])
def test_a_snapshot_of_one_lane_restores_on_the_other(to_device):
    batches, wms = random_stream(2)
    cut = 6

    def make(device):
        return device_op() if device else SessionOperator(
            GAP, aggregates.count(), max_out_of_orderness_ms=40)

    whole = make(to_device)
    want = drive(whole, batches, wms)
    a, b = make(not to_device), make(to_device)
    rows = []
    for (k, t, d), wm in zip(batches[:cut], wms[:cut]):
        a.process_batch(k, t, d)
        rows += rows_of(a.advance_watermark(wm))
    snap = a.snapshot_state()
    assert set(snap) == {"watermark", "late_records", "columns"}
    b.restore_state(snap)
    for (k, t, d), wm in zip(batches[cut:], wms[cut:]):
        b.process_batch(k, t, d)
        rows += rows_of(b.advance_watermark(wm))
    rows += rows_of(b.advance_watermark(b.final_watermark()))
    assert sorted(rows) == sorted(want)
    assert a.late_records <= b.late_records == whole.late_records


def test_a_fire_of_more_rows_than_a_pass_holds_takes_several(monkeypatch):
    monkeypatch.setattr(session_device, "FIRE_CAP", 16)
    op = device_op(slots_per_shard=256)
    n = 300
    keys = np.arange(n, dtype=np.int64)
    op.process_batch(keys, np.zeros(n, np.int64) + np.arange(n) % 7, {})
    assert len(rows_of(op.advance_watermark(50))) == 0
    fired = op.advance_watermark(10_000)
    rows = rows_of(fired)
    assert sorted(r[0] for r in rows) == keys.tolist()
    c = op.state_counters()
    assert c["session.fire_passes"] == -(-n // 16)
    assert c["session.fire_advances"] == 1
    assert c["session.fire_rows_max"] == n and c["session.live"] == 0
    # nothing left: a later advance fires nothing, and needs no version
    no = op.emit_ring.version_no
    assert len(rows_of(op.advance_watermark(20_000))) == 0
    assert op.emit_ring.version_no == no


def test_a_hot_key_with_thousands_of_records_in_a_batch():
    op = device_op()
    rng = np.random.default_rng(7)
    n = 6000
    keys = np.where(rng.random(n) < 0.9, 5, rng.integers(0, 20, n))
    ts = rng.integers(0, 80, n)
    op.process_batch(keys.astype(np.int64), ts.astype(np.int64), {})
    rows = rows_of(op.advance_watermark(10_000))
    want = {int(k): int((keys == k).sum()) for k in np.unique(keys)}
    assert {r[0]: r[3] for r in rows} == want


def test_two_unfired_sessions_on_one_key_and_a_bridge():
    op = device_op()
    one = np.ones(1, np.int64)
    op.process_batch(one, 0 * one, {})
    op.advance_watermark(-40)
    op.process_batch(one, 250 * one, {})        # second session: 250 > 0+100
    op.advance_watermark(60)
    assert op.state_counters()["session.second_lane_peak"] == 0  # read at fires
    op.process_batch(np.ones(2, np.int64), np.array([100, 150]), {})
    fired = rows_of(op.advance_watermark(98))
    assert fired == []                          # bridged: [0, 250] is one
    assert rows_of(op.advance_watermark(400)) == [(1, 0, 350, 4)]
    assert op.state_counters()["session.merged"] == 1


def test_late_records_dropped_and_rescued():
    op = device_op()
    k = np.array([1, 2], np.int64)
    op.process_batch(k, np.array([0, 150], np.int64), {})
    assert rows_of(op.advance_watermark(120)) == [(1, 0, 100, 1)]
    # key 1 at 10: dead (10 + 99 <= 120) and its session is gone: late.
    # key 2 at 60: dead, but within the gap of key 2's open [150, 150]
    op.process_batch(k, np.array([10, 60], np.int64), {})
    rows = rows_of(op.advance_watermark(1000))
    assert rows == [(2, 60, 250, 2)]
    assert op.late_records == 1


def one_key_sessions(n, step=GAP + 1):
    """One batch in which key 1 has ``n`` sessions (a record every
    ``step`` ms, just over the gap) beside a key with one."""
    keys = np.r_[np.ones(n, np.int64), 2]
    return keys, np.r_[np.arange(n) * step, 0].astype(np.int64)


def test_a_key_with_three_sessions_in_a_batch_grows_the_lanes():
    """No watermark moves inside a batch: at delay 0 a slot starts with
    two lanes, and the third session of one batch needs a third."""
    op = device_op(max_out_of_orderness_ms=0)
    assert op.lanes == 2
    keys, ts = one_key_sessions(3)
    op.process_batch(keys, ts, {})
    assert op.lanes == 3 and op.state.count.shape == (3 * op.slots,)
    assert rows_of(op.advance_watermark(50)) == []
    op.process_batch(np.ones(1, np.int64), np.full(1, 250, np.int64), {})
    got = rows_of(op.advance_watermark(op.final_watermark()))
    assert sorted(got) == [(1, 0, 100, 1), (1, 101, 201, 1),
                           (1, 202, 350, 2), (2, 0, 100, 1)]
    c = op.state_counters()
    assert c["session.lane_grows"] == 1 and c["session.on_registry"] == 0


def test_a_jump_of_event_time_between_batches_needs_no_more_lanes():
    """Batches ten gaps apart, every key's last session still open when
    the next comes: by the spans alone a key might hold three (two old
    ones within delay + gap, one new), but the device counted ONE a key
    at the last fire, so the slots stay at two lanes."""
    op = device_op()
    keys = np.arange(10, dtype=np.int64)
    rows = []
    for i in range(6):
        op.process_batch(keys, i * 1000 + 5 * keys, {})
        rows += rows_of(op.advance_watermark(i * 1000 + 45 - 40))
        assert op._may_hold == 1
    rows += rows_of(op.advance_watermark(op.final_watermark()))
    assert sorted(rows) == sorted(
        (int(k), i * 1000 + 5 * int(k), i * 1000 + 5 * int(k) + 100, 1)
        for i in range(6) for k in keys)
    c = op.state_counters()
    assert c["session.lanes"] == 2 and c["session.lane_grows"] == 0
    assert c["session.on_registry"] == 0


@pytest.mark.parametrize("n", [3, MAX_LANES + 2])
def test_any_batch_gives_the_registrys_rows(n):
    """Whatever a batch holds, the device operator gives the rows the
    host registry gives: with more lanes, or, where a key may need more
    than ``MAX_LANES``, from a registry of its own that took over the
    sessions open by then."""
    i64 = lambda *v: np.array(v, np.int64)      # noqa: E731
    keys, ts = one_key_sessions(n)
    batches = [(i64(9, 1, 3), i64(-300, 0, 40), {}),
               (keys, ts + 80, {}),             # key 1: [0, 80], 181, 282...
               (i64(1, 3), i64(60, 500), {})]   # key 1 at 60: late by then
    wms = [40, 200, 8000]
    op = device_op(max_out_of_orderness_ms=0)
    host = SessionOperator(GAP, aggregates.count())
    got = drive(op, batches, wms)
    assert sorted(got) == sorted(drive(host, batches, wms))
    assert len(got) == n + 4 and (1, 0, 180, 2) in got
    assert op.late_records == host.late_records == 1
    c = op.state_counters()
    assert c["session.on_registry"] == (n > MAX_LANES)
    assert (op.state is None) == (n > MAX_LANES)
    # key 9's row had fired on the device before the second batch came:
    # it left through the ring whoever ran the rest
    assert c["session.fired"] == (1 if n > MAX_LANES else n + 4)


@pytest.mark.parametrize("n", [3, MAX_LANES + 1])
def test_a_snapshot_restores_whatever_a_key_holds(n):
    keys, ts = one_key_sessions(n)

    def host():
        op = SessionOperator(GAP, aggregates.count())
        op.process_batch(keys, ts, {})
        return op

    op = device_op(max_out_of_orderness_ms=0)
    op.restore_state(host().snapshot_state())
    assert op.state_counters()["session.on_registry"] == (n > MAX_LANES)
    assert op.lanes == min(n, 2 if n > MAX_LANES else n)
    assert sorted(rows_of(op.advance_watermark(10**6))) == sorted(
        rows_of(host().advance_watermark(10**6)))


def test_a_span_the_offsets_cannot_hold_goes_to_the_registry():
    """int32 offsets hold +-24 days around the job's first timestamp; a
    record beyond is the registry's, never a wrapped offset."""
    op = device_op()
    one = np.ones(1, np.int64)
    op.process_batch(one, 0 * one, {})
    op.process_batch(one, np.full(1, 2**31, np.int64), {})
    assert op.state_counters()["session.on_registry"] == 1
    assert "int32" in op.why_registry
    got = rows_of(op.advance_watermark(op.final_watermark()))
    assert sorted(got) == [(1, 0, 100, 1), (1, 2**31, 2**31 + 100, 1)]


def test_a_snapshot_with_more_sessions_a_key_than_lanes_restores():
    host = SessionOperator(GAP, aggregates.count())
    keys, ts = one_key_sessions(MAX_LANES + 1)
    host.process_batch(keys, ts, {})
    want = rows_of(host.advance_watermark(host.final_watermark()))
    for n, on_registry in ((3, 0), (MAX_LANES + 1, 1)):
        src = SessionOperator(GAP, aggregates.count())
        src.process_batch(keys[-n - 1:], ts[-n - 1:], {})
        op = device_op(max_out_of_orderness_ms=0)
        op.restore_state(src.snapshot_state())
        assert op.state_counters()["session.on_registry"] == on_registry
        got = rows_of(op.advance_watermark(op.final_watermark()))
        assert sorted(got) == sorted(want)[-n - 1:] or n == 3


def churn(op, rounds=10, n=40):
    """Keys that never come back: each round's sessions fire in the
    next round's advance. The drain is the test's, and it lags: after
    each advance it decodes every pass but the one just dispatched, so
    a release always runs with a fire in flight."""
    ring = op.emit_ring
    rows = []
    for r in range(rounds):
        keys = (r * n + np.arange(n)).astype(np.int64)
        op.process_batch(keys, np.full(n, r * 200, np.int64), {})
        fired = op.advance_watermark(r * 200 - 40)
        if getattr(fired, "_ring", False):
            newest = ring.versions.pop()
            rows += rows_of(op.drain_ring(min_no=0))
            ring.versions.append(newest)
            op.run_pending_release()
    rows += rows_of(op.advance_watermark(op.final_watermark()))
    return rows


def test_slots_are_released_and_reused_under_the_reuse_rule():
    op = device_op(num_shards=2, slots_per_shard=128)   # 256 < 400 keys
    rows = churn(op)
    assert sorted(r[0] for r in rows) == list(range(400))
    c = op.state_counters()
    assert c["state.slots_released"] >= 320
    assert c["state.slots_reused"] > 0
    assert c["state.slots_returned_early"] == 0
    assert c["state.live_keys_peak"] <= 160


def test_the_tripwire_reads_when_the_rule_is_switched_off(monkeypatch):
    monkeypatch.setattr(DeviceSessionOperator, "_drained_through",
                        lambda self: 1 << 60)
    op = device_op(num_shards=2, slots_per_shard=128)
    churn(op)
    assert op.state_counters()["state.slots_returned_early"] > 0


def test_a_key_touched_after_its_fire_keeps_its_slot():
    op = device_op()
    one = np.ones(1, np.int64)
    op.process_batch(one, 0 * one, {})
    fired = op.advance_watermark(200)
    op.process_batch(one, 500 * one, {})     # before the drain decodes
    assert rows_of(fired) == [(1, 0, 100, 1)]
    op.run_pending_release()
    assert op.directory.num_keys() == 1
    assert rows_of(op.advance_watermark(1000)) == [(1, 500, 600, 1)]
    op.run_pending_release()
    assert op.directory.num_keys() == 0


def test_the_rule_that_chooses_the_lane():
    fits = dict(gap_ms=10_000, agg=aggregates.count(), allowed_lateness_ms=0,
                retract=False, mesh=False, max_out_of_orderness_ms=4_000,
                slots=1 << 22)
    assert device_lane_fits(**fits)
    for change in (dict(retract=True), dict(allowed_lateness_ms=1),
                   dict(mesh=True), dict(agg=object()),
                   dict(gap_ms=10, max_out_of_orderness_ms=4_000),
                   dict(gap_ms=1 << 30), dict(slots=1 << 29)):
        assert not device_lane_fits(**{**fits, **change}), change
    assert lanes_needed(10_000, 4_000) == 2
    assert lanes_needed(1_000, 4_000) == 5


def test_the_state_is_on_the_device_and_counted():
    op = device_op()
    assert op.hbm_bytes() >= op.lanes * op.slots * 12
    assert op.state.count.shape == (op.lanes * op.slots,)


# -- through env.execute(), the driver and the drain ---------------------------

def _q11_gen(split, i):
    """Bidders that come and go: batch i's ids lie around 40 i, a hot
    one taking most bids; event time 100 ms a batch."""
    if i >= 30:
        return None
    rng = np.random.default_rng(900 + i)
    n = 512
    bidder = 40 * i + rng.integers(0, 60, n)
    bidder[rng.random(n) < 0.6] = 40 * i + 1
    ts = np.sort(i * 100 + rng.integers(0, 100, n))
    return ({"bidder": bidder.astype(np.int64),
             "price": rng.integers(1, 100, n).astype(np.int64)},
            ts.astype(np.int64))


def _run_q11(build=None, gen=None, **conf):
    from flink_tpu.api.environment import StreamExecutionEnvironment
    from flink_tpu.api.sinks import FnSink
    from flink_tpu.api.sources import GeneratorSource
    from flink_tpu.config import Configuration
    from flink_tpu.nexmark.queries import q11_user_sessions

    rows = []
    env = StreamExecutionEnvironment(Configuration({
        "state.num-key-shards": 8, "state.slots-per-shard": 64,
        "pipeline.microbatch-size": 512, **conf}))
    sink = FnSink(lambda b: rows.append(
        {k: np.asarray(v).copy() for k, v in b.items()}))
    (build or (lambda env, src, sink: q11_user_sessions(
        env, src, sink, gap_ms=300, out_of_orderness_ms=100)))(
        env, GeneratorSource(gen or _q11_gen), sink)
    result = env.execute("q11-test")
    ops = list(env._driver._ops.values())
    return rows, result.metrics, ops


def _q11_reference():
    ref = Reference(300)
    i = 0
    while (b := _q11_gen(0, i)) is not None:
        ref.batch(b[0]["bidder"], b[1], b[0]["price"])
        i += 1
    ref.advance(10**9)
    return sorted((k, c, s, e) for k, s, e, c, _, _ in ref.rows)


def test_q11_runs_on_the_device_lane_through_the_driver():
    rows, m, ops = _run_q11()
    assert [type(op).__name__ for op in ops
            if hasattr(op, "gap")] == ["DeviceSessionOperator"]
    got = sorted(zip(*(np.concatenate([r[f] for r in rows]).tolist() for f in (
        "bidder", "bid_count", "starttime", "endtime"))))
    # no record is late (the source's timestamps ascend), so the rows
    # are the sessions of the whole stream, whatever fired them
    assert got == _q11_reference()
    assert m["late_records"] == 0 and m["records_dropped_full"] == 0
    assert m["state.slots_returned_early"] == 0
    # 1,200+ bidders through 512 slots: released and handed out again
    assert m["state.slots_released"] > 512 and m["state.slots_reused"] > 0
    assert m["session.fired"] == len(got) == m["session.opened"]
    assert m["session.fire_passes"] >= 1 and m["session.live"] == 0
    assert m["memory.hbm_state_bytes"] > 0 and m["session.on_registry"] == 0
    # the fires went the window operator's way: cohorts with the drain
    fires = m["trace.fires"]
    assert fires and all(
        f[k] is not None for f in fires
        for k in ("t_input", "t_fire", "t_fetch0", "t_ready", "t_fetch1",
                  "t_push0", "t_sink"))
    for leaf in ("window.key_scan", "window.pack", "window.h2d",
                 "window.step_dispatch", "window.fire_dispatch",
                 "state.release", "state.reclaim", "drain.fetch",
                 "drain.deliver"):
        assert m[f"profile.phase.{leaf}"] > 0, leaf
    assert m["profile.detail.window.key_scan/assign"] > 0
    assert m["profile.detail.window.key_scan/note_ts"] > 0


@pytest.mark.parametrize("sessions", [3, 12])
def test_a_key_with_many_sessions_in_one_micro_batch_at_delay_0(sessions):
    """A bounded or replayed job's batch can span many gaps of one
    key's time, and no watermark moves inside it. The job is accepted
    for the device lane (no lateness, no retraction, no mesh) and has
    to give the sessions' rows all the same: with a third lane, or
    from the registry the device operator hands its sessions to."""
    from flink_tpu.nexmark.queries import q11_user_sessions

    def gen(split, i):
        if i >= 4:
            return None
        # bidder 7: a bid every 150 ms (gap 100): a session each;
        # bidders 20 + i: one session each, in this batch alone
        span = 150 * sessions
        ts = np.r_[i * span + 150 * np.arange(sessions),
                   i * span + np.arange(8)]
        order = np.argsort(ts, kind="stable")
        bidder = np.r_[np.full(sessions, 7), np.full(8, 20 + i)]
        return ({"bidder": bidder[order].astype(np.int64),
                 "price": np.ones(len(ts), np.int64)},
                ts[order].astype(np.int64))

    rows, m, ops = _run_q11(
        lambda env, src, sink: q11_user_sessions(
            env, src, sink, gap_ms=100, out_of_orderness_ms=0), gen=gen)
    op, = (op for op in ops if hasattr(op, "gap"))
    assert type(op).__name__ == "DeviceSessionOperator"
    got = sorted(zip(*(np.concatenate([r[f] for r in rows]).tolist() for f in (
        "bidder", "bid_count", "starttime", "endtime"))))
    want = sorted(
        [(7, 1, t, t + 100) for t in 150 * np.arange(4 * sessions)]
        + [(20 + i, 8, i * 150 * sessions, i * 150 * sessions + 107)
           for i in range(4)])
    assert got == want
    assert m["late_records"] == 0
    assert m["session.on_registry"] == (sessions > MAX_LANES)
    assert m["session.lanes"] == (2 if sessions > MAX_LANES else 4)


@pytest.mark.parametrize("job", ["lateness", "retract", "mesh"])
def test_other_session_jobs_keep_the_host_registry(job):
    from flink_tpu.api.windowing import EventTimeSessionWindows
    from flink_tpu.time.watermarks import WatermarkStrategy

    def build(env, src, sink):
        w = (env.from_source(
            src, WatermarkStrategy.for_bounded_out_of_orderness(100))
            .key_by("bidder")
            .window(EventTimeSessionWindows.with_gap(300)))
        if job == "lateness":
            w = w.allowed_lateness(50)
        out = (w.aggregate(aggregates.count(), retract=True)
               if job == "retract" else w.count())
        out.add_sink(sink)

    conf = {"cluster.mesh-devices": 2} if job == "mesh" else {}
    rows, m, ops = _run_q11(build, **conf)
    assert [type(op).__name__ for op in ops
            if hasattr(op, "gap")] == ["SessionOperator"]
    assert m["session.on_registry"] == 1    # the benchmark cell holds it at 0
    got = sorted(zip(*(np.concatenate([r[f] for r in rows]).tolist() for f in (
        "key", "count", "window_start", "window_end"))))
    assert got == _q11_reference()
