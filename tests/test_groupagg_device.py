"""The device lane of the unwindowed aggregation (ops/groupagg_device.py)
against the plain reference of the Q17 configuration and a per-record
loop, against the host operator (ops/global_agg.py), and its integer
lanes, emit buffer and snapshot plumbing. CPU, small sizes, seeded."""
import numpy as np
import pytest

from benchmark.configs import nexmark_q17_auction_stats as q17ref
from flink_tpu.ops import aggregates, groupagg_device
from flink_tpu.ops.global_agg import GlobalAggregateOperator
from flink_tpu.ops.groupagg_device import (
    DeviceGroupAggOperator, device_lane_fits)

BANDS = (10_000, 1_000_000)
DAY = 86_400_000
T0 = 20_000 * DAY + 12_345          # a real epoch: offsets, not timestamps
FIELDS = ("total_bids", "rank1_bids", "rank2_bids", "rank3_bids",
          "min_price", "max_price", "avg_price", "sum_price", "last_bid_ms")


def q17_agg():
    lo, hi = BANDS
    return aggregates.multi(
        aggregates.count("total_bids"),
        aggregates.count_if("price", None, lo, "rank1_bids"),
        aggregates.count_if("price", lo, hi, "rank2_bids"),
        aggregates.count_if("price", hi, None, "rank3_bids"),
        aggregates.int_min_of("price", "min_price"),
        aggregates.int_max_of("price", "max_price"),
        aggregates.int_sum_of("price", "sum_price", avg_field="avg_price"),
        aggregates.latest_event_time("last_bid_ms"))


def device_op(agg=None, **kw):
    return DeviceGroupAggOperator(agg or q17_agg(), num_shards=8,
                                  slots_per_shard=64, **kw)


def host_op(agg=None, **kw):
    return GlobalAggregateOperator(agg or q17_agg(), num_shards=8,
                                   slots_per_shard=64, **kw)


def loop_reference(batches):
    """Q17's rows by a per-record Python loop: after each batch one row
    (key, the nine fields) per key it touched, over every record so
    far."""
    acc, rows = {}, []
    for keys, ts, price in batches:
        touched = []
        for k, t, p in zip(keys.tolist(), ts.tolist(), price.tolist()):
            a = acc.setdefault(k, [0, 0, 0, 0, None, None, 0, None])
            a[0] += 1
            a[1 + (p >= BANDS[0]) + (p >= BANDS[1])] += 1
            a[4] = p if a[4] is None else min(a[4], p)
            a[5] = p if a[5] is None else max(a[5], p)
            a[6] += p
            a[7] = t if a[7] is None else max(a[7], t)
            if k not in touched:
                touched.append(k)
        rows += [(k, *acc[k][:6], acc[k][6] // acc[k][0], acc[k][6],
                  acc[k][7]) for k in touched]
    return sorted(rows)


def rows_of(fired):
    out = dict(fired)
    return sorted(zip(out["key"].tolist(),
                      *(out[f].tolist() for f in FIELDS)))


def run(op, batches):
    rows = []
    for keys, ts, price in batches:
        op.process_batch(keys, ts, {"price": price})
        fired = op.take_fired()
        if fired is not None:
            rows += rows_of(fired)
    return sorted(rows)


def stream(case: str, seed: int = 0):
    """Seeded batches of (keys, ts, price), int64."""
    rng = np.random.default_rng(seed)

    def batch(i, n=400, n_keys=30, lo_key=0):
        keys = lo_key + rng.integers(0, n_keys, n)
        price = np.rint(10.0 ** (rng.random(n) * 6.0) * 100.0)
        ts = T0 + i * 1000 + np.sort(rng.integers(0, 1000, n))
        return [keys.astype(np.int64), ts.astype(np.int64),
                price.astype(np.int64)]

    if case == "recurring":
        return [batch(i) for i in range(5)]
    if case == "come_and_go":       # the suite's shape: keys of a batch's own
        return [batch(i, lo_key=25 * i) for i in range(5)]
    if case == "three_batches":
        out = [batch(i, lo_key=100 * i) for i in range(4)]
        for b in out[:3]:
            b[0][:7] = 5_000       # one key in three batches, then never
        return out
    if case == "hot_key":
        out = [batch(i) for i in range(3)]
        for b in out:               # ~30 x 90 M a batch: the sum passes
            b[0][:30] = 7           # 2^31 in the first, the max 2^24
            b[2][:30] = 90_000_000 + rng.integers(0, 1000, 30)
        return out
    if case == "threshold_edges":
        out = [batch(i, n=64, n_keys=4) for i in range(2)]
        edges = [BANDS[0] - 1, BANDS[0], BANDS[0] + 1, BANDS[1] - 1,
                 BANDS[1], BANDS[1] + 1, 0, 1, 2**24, 2**24 + 1, 2**31 - 1]
        out[0][2][:len(edges)] = edges
        return out
    if case == "day_boundary":
        out = [batch(i) for i in range(3)]
        edge = (T0 // DAY + 1) * DAY
        out[1][1] = np.sort(edge - 200 + rng.integers(0, 400, 400))
        out[2][1] = out[2][1] - out[2][1].min() + edge + 300
        # the key carries the day, as the query's does
        for b in out:
            b[0] = ((b[1] // DAY) << 40) | b[0]
        return out
    raise AssertionError(case)


CASES = ["recurring", "come_and_go", "three_batches", "hot_key",
         "threshold_edges", "day_boundary"]


@pytest.mark.parametrize("case", CASES)
def test_device_lane_equals_the_plain_reference(case):
    batches = stream(case, seed=len(case))
    got = run(device_op(), batches)
    assert got == loop_reference(batches)
    # and the configuration's own reference (numpy, int64), which
    # builds its key from (auction, day) itself
    mask = (1 << 40) - 1
    parts = [q17ref.batch_partials(k & mask, p, t, BANDS)
             for k, t, p in batches]
    if case != "day_boundary":
        # the day comes from the timestamp there: take it out of the rows
        day = T0 // DAY
        got = [((day << 40) | r[0], *r[1:]) for r in got]
    exp, _ = q17ref.running_rows(parts)
    assert sorted(got) == sorted(zip(*(c.tolist() for c in exp)))


def test_the_streams_hold_what_the_issue_names():
    hot = loop_reference(stream("hot_key", seed=7))
    seven = [r for r in hot if r[0] == 7]
    assert seven[0][8] > 2**31 and seven[0][6] > 2**24     # sum, max
    assert len(seven) == 3 and seven[-1][1] > seven[0][1]
    three = loop_reference(stream("three_batches", seed=13))
    assert len([r for r in three if r[0] == 5_000]) == 3
    days = {r[0] >> 40 for r in loop_reference(stream("day_boundary", 12))}
    assert len(days) == 2
    edges = stream("threshold_edges", seed=15)[0][2]
    assert {BANDS[0] - 1, BANDS[0], BANDS[1] - 1, BANDS[1]} <= set(
        edges.tolist())


LANE_JOBS = {
    "q17": (q17_agg, FIELDS),
    "count_min": (lambda: aggregates.multi(
        aggregates.count(), aggregates.min_of("price")), ("min_price",)),
    "float_sum_max": (lambda: aggregates.multi(
        aggregates.sum_of("price"), aggregates.max_of("price")),
        ("sum_price", "max_price")),
    "int_sum": (lambda: aggregates.int_sum_of("price"), ("sum_price",)),
}


@pytest.mark.parametrize("job", sorted(LANE_JOBS))
@pytest.mark.parametrize("case", ["recurring", "hot_key"])
def test_both_lanes_give_the_same_rows(job, case):
    """Column for column, dtype for dtype and in the same order. (The
    float job's prices are cut to small integers: a float32 SUM is
    exact there, as counts, maxs and mins always are.)"""
    make, fields = LANE_JOBS[job]
    batches = stream(case, seed=3)
    if job == "float_sum_max":
        for b in batches:
            b[2] %= 1000
    dev, host = device_op(make()), host_op(make())
    for keys, ts, price in batches:
        dev.process_batch(keys, ts, {"price": price})
        host.process_batch(keys, ts, {"price": price})
        a, b = dict(dev.take_fired()), dict(host.take_fired())
        assert list(a) == list(b)
        for f in a:
            assert a[f].dtype == b[f].dtype, f
            assert np.array_equal(a[f], b[f]), f
        assert set(fields) <= set(a)


@pytest.mark.parametrize("job", ["q17", "float_sum_max"])
@pytest.mark.parametrize("to_device", [True, False])
def test_a_snapshot_of_one_lane_restores_on_the_other(to_device, job):
    make, _ = LANE_JOBS[job]
    batches = stream("recurring", seed=5)
    if job == "float_sum_max":
        for b in batches:
            b[2] %= 1000
    first, second = ((host_op, device_op) if to_device
                     else (device_op, host_op))
    a, twin = first(make()), first(make())
    for keys, ts, price in batches[:3]:
        for op in (a, twin):
            op.process_batch(keys, ts, {"price": price})
            dict(op.take_fired())       # delivered: the ring is read
    snap = a.snapshot_state()
    assert snap["kind"] == "global_agg"
    twin_snap = twin.snapshot_state()
    for f in ("counts", "sums", "maxs", "mins"):
        assert snap[f].dtype == twin_snap[f].dtype
        if job == "q17":
            assert snap[f].dtype == np.int64    # widened to integer lanes
    b = second(make())
    b.restore_state(snap)
    for keys, ts, price in batches[3:]:
        twin.process_batch(keys, ts, {"price": price})
        b.process_batch(keys, ts, {"price": price})
        assert rows_like(b.take_fired()) == rows_like(twin.take_fired())
    # and the two lanes' snapshots are one format, value for value
    s1, s2 = b.snapshot_state(), twin.snapshot_state()
    assert set(s1) == set(s2)
    for f in ("counts", "sums", "maxs", "mins"):
        assert np.array_equal(s1[f], s2[f]), f
    assert s1["time_base"] == s2["time_base"]


def rows_like(fired):
    out = dict(fired)
    return {k: v.tolist() for k, v in out.items()}


def test_more_rows_than_the_emit_buffer_leave_in_passes(monkeypatch):
    monkeypatch.setattr(groupagg_device, "EMIT_CAP", 8)
    batches = stream("recurring", seed=21)      # ~30 keys a batch
    op = device_op()
    got = run(op, batches)
    assert got == loop_reference(batches)       # none lost, none twice
    c = op.state_counters()
    assert c["groupagg.rows_emitted"] == len(got)
    assert c["groupagg.emit_passes"] == sum(
        -(-len(set(k.tolist())) // 8) for k, _, _ in batches)
    assert c["groupagg.emit_passes"] > c["groupagg.batches"] == len(batches)


@pytest.mark.parametrize("sizes", [(100, 700, 1024, 1500), (1, 2, 3000)])
def test_ragged_batches_meet_a_handful_of_shapes(sizes):
    rng = np.random.default_rng(17)
    batches = []
    for i, n in enumerate(sizes):
        batches.append((rng.integers(0, 50, n).astype(np.int64),
                        T0 + i * 10 + np.zeros(n, np.int64),
                        rng.integers(1, 10**8, n).astype(np.int64)))
    assert run(device_op(), batches) == loop_reference(batches)
    shapes = {groupagg_device._batch_size(n) for n in sizes}
    assert shapes <= {1024, 2048, 4096}


def test_a_validity_mask_takes_records_out():
    keys, ts, price = stream("recurring", seed=2)[0]
    valid = np.arange(len(keys)) % 3 != 0
    op = device_op()
    op.process_batch(keys, ts, {"price": price}, valid)
    assert rows_of(op.take_fired()) == loop_reference(
        [(keys[valid], ts[valid], price[valid])])
    # keys named by masked records alone took no slot
    assert op.directory.num_keys() == len(set(keys[valid].tolist()))


@pytest.mark.parametrize("lane", ["device", "host"])
def test_a_lane_overflow_is_refused_and_counted(lane):
    """A price that 32 bits cannot hold never wraps into the int32
    min / max lanes: its record is refused, counted, and every other
    record of the batch is folded in; so too a timestamp beyond the
    int32 offsets from the job's first."""
    keys, ts, price = stream("recurring", seed=4)[0]
    price = price.copy()
    price[[3, 50]] = [2**31, -(2**31) - 1]
    keep = np.ones(len(keys), bool)
    keep[[3, 50]] = False
    op = device_op() if lane == "device" else host_op()
    op.process_batch(keys, ts, {"price": price})
    assert rows_of(op.take_fired()) == loop_reference(
        [(keys[keep], ts[keep], price[keep])])
    assert op.state_counters()["groupagg.lane_overflow"] == 2
    # 2^31 - 1 fits
    price[:] = 2**31 - 1
    op.process_batch(keys, ts, {"price": price})
    assert op.state_counters()["groupagg.lane_overflow"] == 2
    # an event time 30 days on: past the offsets
    far = ts.copy()
    far[0] += 30 * DAY
    op.process_batch(keys, far, {"price": price})
    op.take_fired()
    assert op.state_counters()["groupagg.lane_overflow"] == 3


def test_the_rule_that_chooses_the_lane():
    ok = dict(agg=q17_agg(), retract=False, mesh=False, slots=512)
    assert device_lane_fits(**ok)
    assert device_lane_fits(**{**ok, "agg": aggregates.sum_of("v")})
    assert not device_lane_fits(**{**ok, "retract": True})
    assert not device_lane_fits(**{**ok, "mesh": True})
    assert not device_lane_fits(**{**ok, "agg": object()})
    assert not device_lane_fits(**{**ok, "slots": 1 << 30})
    with pytest.raises(ValueError, match="device_lane_fits"):
        DeviceGroupAggOperator(object(), num_shards=8, slots_per_shard=64)


def test_the_state_is_on_the_device_and_counted():
    op = device_op()
    # count + 3 band counts + the sum's two words + max + last + min
    assert op.hbm_bytes() == op.slots * 36
    assert op.state.shape == (9, op.slots) and op.state.dtype == np.int32
    # untouched: count 0, sums 0, maxs at int32's least, mins at its most
    assert np.asarray(op.state[:, 5]).tolist() == [
        0, 0, 0, 0, 0, 0, -2**31, -2**31, 2**31 - 1]
    f = device_op(aggregates.multi(aggregates.sum_of("v"),
                                   aggregates.max_of("v")))
    assert f.hbm_bytes() == f.slots * 12 and f.state.shape == (3, f.slots)
    assert np.asarray(f.state[:, 0]).view(np.float32).tolist() == [
        0.0, 0.0, -np.inf]


# -- the integer scan and combine against numpy ------------------------------

@pytest.mark.parametrize("dtype,op_name", [
    ("int32", "add"), ("int64", "add"), ("int32", "maximum"),
    ("int32", "minimum"), ("int64", "maximum")])
def test_integer_run_scan_against_reduceat(dtype, op_name):
    import jax.numpy as jnp
    from flink_tpu.ops.window import _run_scan

    rng = np.random.default_rng(31)
    n = 4096
    x = rng.integers(-10**8, 10**8, n).astype(dtype)
    if dtype == "int64":
        x = x * 10**4        # run sums far past 2^31
    heads = rng.random(n) < 0.02
    heads[0] = True
    got = np.asarray(_run_scan(getattr(jnp, op_name), jnp.asarray(heads),
                               jnp.asarray(x)))
    assert got.dtype == np.dtype(dtype)
    first = np.flatnonzero(heads)
    last = np.r_[first[1:], n] - 1
    want = getattr(np, op_name).reduceat(x, first)
    assert np.array_equal(got[last], want)


@pytest.mark.parametrize("columns", ["typed", "float"])
def test_combine_cells_with_integer_columns_against_reduceat(columns):
    import jax.numpy as jnp
    from flink_tpu.ops.window import NO_CELL, combine_cells

    rng = np.random.default_rng(37)
    n, n_rows = 2048, 300
    rows = rng.integers(0, n_rows, n).astype(np.int32)
    valid = rng.random(n) < 0.9
    price = rng.integers(0, 10**8, n)
    if columns == "typed":
        lanes = {"sums": (jnp.asarray(price, jnp.int64) * 1000,
                          jnp.asarray(price < 10**7, jnp.int32)),
                 "maxs": (jnp.asarray(price, jnp.int32),), "mins": ()}
    else:
        lanes = {"sums": jnp.asarray((price % 1000)[:, None], jnp.float32),
                 "maxs": jnp.asarray(price[:, None], jnp.float32)}
    cells, starts, scans, n_cells, n_records = combine_cells(
        n_rows, jnp.asarray(rows), jnp.zeros(n, jnp.int32),
        jnp.asarray(valid), lanes)
    n_cells, starts = int(n_cells), np.asarray(starts)
    order = np.argsort(rows[valid], kind="stable")
    srt, p = rows[valid][order], price[valid][order]
    first = np.flatnonzero(np.r_[True, srt[1:] != srt[:-1]])
    assert n_cells == len(first) and int(n_records) == valid.sum()
    assert np.array_equal(np.asarray(cells)[:n_cells], srt[first])
    assert (np.asarray(cells)[n_cells:] == NO_CELL).all()
    assert np.array_equal(starts[:n_cells + 1], np.r_[first, len(srt)])
    last = starts[1:n_cells + 1] - 1
    if columns == "typed":
        assert scans["sums"][0].dtype == jnp.int64
        assert np.array_equal(np.asarray(scans["sums"][0])[last],
                              np.add.reduceat(p * 1000, first))
        assert np.array_equal(np.asarray(scans["sums"][1])[last],
                              np.add.reduceat((p < 10**7) * 1, first))
        assert scans["mins"] == []
    else:
        assert np.array_equal(np.asarray(scans["sums"][0])[last],
                              np.add.reduceat(p % 1000, first))
    # a float32 lane holds a price of 10^8 to 8 only: the integer one is
    # exact
    want = np.maximum.reduceat(p, first)
    got = np.asarray(scans["maxs"][0])[last]
    if columns == "typed":
        assert np.array_equal(got, want)
    else:
        assert np.array_equal(got, want.astype(np.float32))
        assert not np.array_equal(got.astype(np.int64), want)


# -- the aggregates -----------------------------------------------------------

def test_the_integer_aggregates_layout_and_finalize():
    agg = q17_agg()
    assert agg.typed and agg.lane_dtypes == (
        ("int32", "int32", "int32", "int64"), ("int32", "int32"),
        ("int32",))
    assert (agg.sum_width, agg.max_width, agg.min_width) == (4, 2, 1)
    assert agg.time_lanes == ((), (1,), ())
    assert agg.narrow_fields == ("price",)
    assert set(agg.fields) == {"price", aggregates.EVENT_TIME_FIELD}
    assert sorted(aggregates.result_fields(agg)) == sorted(FIELDS)
    res = agg.finalize(
        (np.array([1]), np.array([2]), np.array([0]), np.array([7 * 10**10])),
        (np.array([9]), np.array([5])), (np.array([4]),), np.array([3]))
    assert res["avg_price"][0] == 7 * 10**10 // 3
    assert (res["total_bids"][0], res["rank2_bids"][0]) == (3, 2)
    with pytest.raises(ValueError, match="float32 lanes"):
        aggregates.multi(aggregates.sum_of("v"), aggregates.int_sum_of("v"))
    # a float aggregate's layout is what it was
    f = aggregates.multi(aggregates.count(), aggregates.sum_of("v"))
    assert not f.typed and f.lane_dtypes is None


@pytest.mark.parametrize("kind", ["window", "session", "operator"])
def test_windowed_operators_refuse_integer_lanes(kind):
    from flink_tpu.api.environment import StreamExecutionEnvironment
    from flink_tpu.api.windowing import (
        EventTimeSessionWindows, TumblingEventTimeWindows)
    from flink_tpu.config import Configuration
    from flink_tpu.ops.window import WindowOperator

    agg = aggregates.int_sum_of("v")
    with pytest.raises(NotImplementedError, match="integer lanes"):
        if kind == "operator":
            WindowOperator(TumblingEventTimeWindows.of(10), agg)
        else:
            env = StreamExecutionEnvironment(Configuration({}))
            w = (EventTimeSessionWindows.with_gap(10) if kind == "session"
                 else TumblingEventTimeWindows.of(10))
            (env.from_collection({"k": np.zeros(4, np.int64),
                                  "v": np.ones(4, np.int64)},
                                 np.arange(4), batch_size=4)
             .key_by("k").window(w).aggregate(agg).print())
            env.execute("refused")


# -- through env.execute(), the driver and the drain ---------------------------

def _q17_gen(split, i):
    """The suite's shape in small: batch i's auctions lie around 40 i,
    a hot one taking most bids; a day boundary inside batch 4."""
    if i >= 12:
        return None
    rng = np.random.default_rng(700 + i)
    n = 512
    auction = 1000 + 40 * i + rng.integers(0, 60, n)
    auction[rng.random(n) < 0.5] = 1000 + 40 * i + 1
    price = np.rint(10.0 ** (rng.random(n) * 6.0) * 100.0)
    ts = np.sort(20_001 * DAY - 450 + i * 100 + rng.integers(0, 100, n))
    return ({"auction": auction.astype(np.int64),
             "price": price.astype(np.int64)}, ts.astype(np.int64))


def _run(build, gen=_q17_gen, **conf):
    from flink_tpu.api.environment import StreamExecutionEnvironment
    from flink_tpu.api.sinks import FnSink
    from flink_tpu.api.sources import GeneratorSource
    from flink_tpu.config import Configuration

    rows = []
    env = StreamExecutionEnvironment(Configuration({
        "state.num-key-shards": 8, "state.slots-per-shard": 128,
        "pipeline.microbatch-size": 512, **conf}))
    sink = FnSink(lambda b: rows.append(
        {k: np.asarray(v).copy() for k, v in b.items()}))
    build(env, GeneratorSource(gen), sink)
    result = env.execute("q17-test")
    return rows, result.metrics, list(env._driver._ops.values())


def _q17_expected():
    parts, i = [], 0
    while (b := _q17_gen(0, i)) is not None:
        parts.append(q17ref.batch_partials(
            b[0]["auction"], b[0]["price"], b[1], BANDS))
        i += 1
    exp, _ = q17ref.running_rows(parts)
    return sorted(zip(*(c.tolist() for c in exp)))


def test_q17_runs_on_the_device_lane_through_the_driver():
    from flink_tpu.nexmark.queries import Q17_COLUMNS, q17_auction_stats

    rows, m, ops = _run(q17_auction_stats)
    assert [type(op).__name__ for op in ops] == ["DeviceGroupAggOperator"]
    assert all(tuple(r) == Q17_COLUMNS for r in rows)
    got = q17ref.collect(rows, {})
    assert all(c.dtype == np.int64 for c in got)
    want = _q17_expected()
    assert sorted(zip(*(c.tolist() for c in got))) == want
    assert {r[0] >> 40 for r in want} == {20_000, 20_001}
    assert m["late_records"] == 0 and m["records_dropped_full"] == 0
    assert m["groupagg.on_host"] == 0 and m["groupagg.lane_overflow"] == 0
    assert m["memory.hbm_state_bytes"] == 8 * 128 * 36
    assert m["groupagg.rows_emitted"] == len(want) == m["records_out"]
    keys = len({r[0] for r in want})
    assert m["groupagg.keys_new"] == m["groupagg.live_keys"] == keys
    assert m["groupagg.emit_passes"] == m["groupagg.batches"] == 12
    assert m["groupagg.slots"] == 1024
    op_id, = (k.split(".")[1] for k in m if k.endswith(".apply_cells"))
    assert m[f"profile.{op_id}.apply_cells"] == len(want)
    assert m[f"profile.{op_id}.apply_records"] == 12 * 512
    assert m[f"profile.{op_id}.apply_trips"] == 12
    # a batch's rows went the window operator's way: a cohort with the
    # drain, every stamp set (``t_queued`` is None where the poll for
    # the batch before had taken these rows too before they were queued)
    fires = m["trace.fires"]
    assert len(fires) == 12 and all(
        f[k] is not None for f in fires
        for k in ("t_input", "t_fire", "t_fetch0", "t_ready", "t_fetch1",
                  "t_push0", "t_sink"))
    assert all("t_queued" in f for f in fires)
    assert any(f["t_queued"] is not None for f in fires)
    for leaf in ("window.key_scan", "window.pack", "window.h2d",
                 "window.step_dispatch", "drain.fetch", "drain.deliver"):
        assert m[f"profile.phase.{leaf}"] > 0, leaf
    for d in ("prepare", "assign", "slot_mask"):
        assert m[f"profile.detail.window.key_scan/{d}"] > 0, d


@pytest.mark.parametrize("job", ["retract", "mesh"])
def test_other_jobs_keep_the_host_operator(job):
    def build(env, src, sink):
        from flink_tpu.time.watermarks import WatermarkStrategy

        (env.from_source(src, WatermarkStrategy.for_monotonous_timestamps())
         .key_by("auction")
         .running_aggregate(aggregates.multi(
             aggregates.count(), aggregates.int_sum_of("price")),
             retract=(job == "retract"))
         .add_sink(sink))

    conf = {"cluster.mesh-devices": 2} if job == "mesh" else {}
    rows, m, ops = _run(build, **conf)
    assert [type(op).__name__ for op in ops] == ["GlobalAggregateOperator"]
    assert m["groupagg.on_host"] == 1   # the benchmark cell holds it at 0
    assert m["groupagg.lane_overflow"] == 0
    # the upsert contract either way: a key's last row is its total
    total = {}
    i = 0
    while (b := _q17_gen(0, i)) is not None:
        for a, p in zip(b[0]["auction"].tolist(), b[0]["price"].tolist()):
            c, s = total.get(a, (0, 0))
            total[a] = (c + 1, s + p)
        i += 1
    seen = {}
    for r in rows:
        ops_col = r.get("__op__")
        for j, (k, c, s) in enumerate(zip(
                r["key"].tolist(), r["count"].tolist(),
                r["sum_price"].tolist())):
            if ops_col is None or ops_col[j] != 1:     # not a -U row
                seen[k] = (c, s)
    assert seen == total


def test_a_float_job_runs_on_the_device_lane_too():
    def build(env, src, sink):
        from flink_tpu.time.watermarks import WatermarkStrategy

        (env.from_source(src, WatermarkStrategy.for_monotonous_timestamps())
         .key_by("auction")
         .running_aggregate(aggregates.multi(
             aggregates.count(), aggregates.avg_of("price"),
             aggregates.max_of("price")))
         .add_sink(sink))

    rows, m, ops = _run(build)
    assert [type(op).__name__ for op in ops] == ["DeviceGroupAggOperator"]
    assert m["groupagg.on_host"] == 0
    last = {}
    for r in rows:
        for k, c, a, mx in zip(r["key"].tolist(), r["count"].tolist(),
                               r["avg_price"].tolist(),
                               r["max_price"].tolist()):
            last[k] = (c, a, mx)
    want = {}
    i = 0
    while (b := _q17_gen(0, i)) is not None:
        for a, p in zip(b[0]["auction"].tolist(), b[0]["price"].tolist()):
            c, s, mx = want.get(a, (0, 0.0, 0.0))
            want[a] = (c + 1, s + p, max(mx, p))
        i += 1
    assert set(last) == set(want)
    for k, (c, s, mx) in want.items():
        assert last[k][0] == c
        assert last[k][1] == pytest.approx(s / c, rel=1e-5)
        assert last[k][2] == pytest.approx(mx, rel=1e-6)


# -- the two ways a cell is merged: dense blocks and gather trips -------------

def _keys_by_shard(op, per_shard, lo):
    """``per_shard[h]`` distinct keys from ``lo`` up that hash to shard
    h, shuffled, and the next unused key."""
    want = np.asarray(per_shard)
    got = [[] for _ in want]
    k = lo
    while any(len(g) < w for g, w in zip(got, want)):
        cand = np.arange(k, k + 4096, dtype=np.int64)
        for h, c in zip(op.directory.shard_of(cand).tolist(), cand.tolist()):
            if len(got[h]) < want[h]:
                got[h].append(c)
        k += 4096
    keys = np.asarray([c for g in got for c in g], np.int64)
    return np.random.default_rng(lo).permutation(keys), k


def _records(keys, i, size, float_prices=False):
    """A batch of ``size`` records that names every key of ``keys``."""
    rng = np.random.default_rng(1000 + i)
    ks = np.concatenate([keys, rng.choice(keys, size - len(keys))])
    rng.shuffle(ks)
    price = 10.0 ** (rng.random(size) * 6.0) * 100.0
    price = (price.astype(np.float32).astype(np.float64) if float_prices
             else np.rint(price))
    ts = T0 + i * 1000 + np.sort(rng.integers(0, 1000, size))
    return ks.astype(np.int64), ts.astype(np.int64), (
        price if float_prices else price.astype(np.int64))


def _expected_merge(op, before, keys, refused, size):
    """(rows dense, pieces, gather trips) of the batch just folded in,
    by a per-piece Python loop over what the directory handed out."""
    G = groupagg_device
    sps = op.directory.slots_per_shard
    named = op.directory.assign(keys)           # every key is known now
    named = set(named[(named >= 0) & ~refused].tolist())
    n_pieces = 2 * op.directory.num_shards
    width = G.piece_width(size, n_pieces, op.slots)
    pieces = [(h * sps + lo, min(width, after - lo))
              for h, (b, after) in enumerate(zip(
                  before.tolist(), op.directory.free_pointers().tolist()))
              for lo in range(b, after, width)][:n_pieces]
    held = [n for first, n in pieces
            if all(s in named for s in range(first, first + n))]
    rest = len(named) - sum(held)
    chunk = G.merge_chunk(size)
    if len(named) > chunk and rest <= G.sparse_chunk(size):
        return sum(held), len(held), int(rest > 0)
    return 0, 0, -(-len(named) // chunk)


def _merge_case(case):
    """``(agg maker, slots a shard, [(per-shard new keys, old keys,
    refused keys)] a batch)``: old keys are drawn from those folded in
    before, refused ones (all of a new key's records carry a price 32
    bits cannot hold) from the batch's new ones."""
    even = lambda n: [n] * 8                                    # noqa: E731
    return {
        "all_new": (q17_agg, 2048, [(even(190), 0, 0), (even(150), 0, 0)]),
        "all_recurring": (q17_agg, 2048, [(even(190), 0, 0),
                                          (even(0), 1500, 0)]),
        "mixed": (q17_agg, 2048, [(even(190), 0, 0), (even(170), 300, 0),
                                  (even(80), 1200, 0)]),
        "long_runs_and_unlisted_pieces": (
            q17_agg, 2048, [([700, 300, 300, 300, 100, 100, 100, 100], 0, 0),
                            ([130, 260, 30, 400, 400, 300, 0, 200], 200, 0)]),
        "refused_fresh_slot": (q17_agg, 2048, [(even(190), 0, 3),
                                               (even(150), 100, 2)]),
        "last_columns_and_full_shards": (
            q17_agg, 256, [(even(150), 0, 0), (even(150), 300, 0),
                           (even(10), 1100, 0)]),
        "float": (LANE_JOBS["float_sum_max"][0], 2048, [
            (even(190), 0, 0), (even(170), 300, 0), (even(0), 1500, 0)]),
    }[case]


MERGE_CASES = ["all_new", "all_recurring", "mixed",
               "long_runs_and_unlisted_pieces", "refused_fresh_slot",
               "last_columns_and_full_shards", "snapshot_restore", "float"]


@pytest.mark.parametrize("case", MERGE_CASES)
def test_dense_blocks_and_gather_trips_give_the_reference_rows(
        case, monkeypatch):
    """Every way a batch's cells are merged (first-time runs written as
    dense blocks, the other cells in one small gather trip; or, where
    that cannot pay, every cell in gather trips as before) against the
    host operator row for row, against the same operator with no piece
    ever listed word for word, and the header's counters against a
    per-piece loop."""
    restore = case == "snapshot_restore"
    make, sps, plan = _merge_case("mixed" if restore else case)
    is_float = case == "float"
    size = 4096

    def ops():
        kw = dict(num_shards=8, slots_per_shard=sps)
        trio = (DeviceGroupAggOperator(make(), **kw),
                DeviceGroupAggOperator(make(), **kw),
                GlobalAggregateOperator(make(), **kw))
        for op in trio:
            op.allow_drops = True
        return trio

    dev, plain, host = ops()
    # the twin is told of no first-time slot: every cell takes a gather trip
    listed = groupagg_device.fresh_pieces
    monkeypatch.setattr(
        groupagg_device, "fresh_pieces",
        lambda *a: (listed(*a) if fold_on is dev
                    else np.zeros_like(listed(*a))))
    folded, next_key, seen, since = np.zeros(0, np.int64), 10_000, [], 0
    for i, (per_shard, n_old, n_refused) in enumerate(plan):
        if restore and i == 1:
            # both lanes' snapshots, each restored on the device lane
            snaps = [dev.snapshot_state(), host.snapshot_state()]
            (dev, plain, host), since = ops(), len(seen)
            dev.restore_state(snaps[1])
            plain.restore_state(snaps[0])
            host.restore_state(snaps[0])
        new, next_key = _keys_by_shard(dev, per_shard, next_key)
        rng = np.random.default_rng(50 + i)
        old = rng.choice(folded, n_old, replace=False)
        keys, ts, price = _records(np.concatenate([new, old]), i, size,
                                   float_prices=is_float)
        refused = np.isin(keys, new[:n_refused])
        price[refused] = 2**31 + 5
        folded = np.union1d(folded, new)
        before = dev.directory.free_pointers().copy()
        rows = []
        for fold_on in (dev, plain, host):
            fold_on.process_batch(keys, ts, {"price": price})
            rows.append(dict(fold_on.take_fired()))
        a, b, c = rows
        assert list(a) == list(b) == list(c)
        for f in a:
            # the float32 sum against the host's float64: to its eighth digit
            assert np.array_equal(a[f], b[f]), f
            if is_float and f.startswith("sum"):
                np.testing.assert_allclose(a[f], c[f], rtol=1e-5)
            else:
                assert a[f].dtype == c[f].dtype
                assert np.array_equal(a[f], c[f]), f
        dense, pieces, trips = _expected_merge(dev, before, keys, refused,
                                               size)
        seen.append((dense, pieces, trips, len(a["key"])))
        counters = dev.state_counters()
        assert counters["groupagg.rows_dense"] == sum(s[0] for s in seen[since:])
        assert counters["groupagg.dense_pieces"] == sum(s[1] for s in seen[since:])
        assert dev.prof["apply_trips"] == sum(s[2] for s in seen[since:])
        assert counters["groupagg.rows_emitted"] == sum(s[3] for s in seen[since:])
        assert plain.state_counters()["groupagg.rows_dense"] == 0
        assert (counters["groupagg.lane_overflow"]
                == host.state_counters()["groupagg.lane_overflow"])
        assert dev.records_dropped_full == host.records_dropped_full
    # the state word for word, and the snapshot value for value
    assert np.array_equal(np.asarray(dev.state), np.asarray(plain.state))
    if not is_float:
        s1, s2 = dev.snapshot_state(), host.snapshot_state()
        for f in ("counts", "sums", "maxs", "mins"):
            assert np.array_equal(s1[f], s2[f]), f
    # what each case is there for
    dense, pieces, trips, cells = (np.asarray(x) for x in zip(*seen))
    chunk = groupagg_device.merge_chunk(size)
    assert (cells[:2] > chunk).all()
    if case == "all_new":
        assert (dense == cells).all() and (trips == 0).all()
    if case in ("all_recurring", "float"):
        assert dense[-1] == 0 and trips[-1] == -(-cells[-1] // chunk) == 2
    if case in ("mixed", "snapshot_restore", "float"):
        assert 0 < dense[1] < cells[1] and trips[1] == 1
    if case == "mixed":
        assert dense[2] == 0 and trips[2] == 2      # too many for one trip
    if case == "long_runs_and_unlisted_pieces":
        width = groupagg_device.piece_width(size, 16, dev.slots)
        assert pieces[0] == 16 and dense[0] == 700 + 300 + 300 + 300 + 100
        assert 700 > 5 * width and trips[0] == 1
        # batch 1: the 16 pieces end inside shard 5; shard 7 is not listed
        assert pieces[1] == 16 and trips[1] == 1
        assert dense[1] == 130 + 260 + 30 + 400 + 400 + 2 * width
    if case == "refused_fresh_slot":
        # a piece with a slot no record names fails its check, whole
        assert ((13 <= pieces) & (pieces < 16)).all() and (trips == 1).all()
        assert dev.lane_overflow > 0
    if case == "last_columns_and_full_shards":
        assert dev.records_dropped_full > 0
        assert (dev.directory.free_pointers() == sps).all()
        assert pieces[1] == 8 and dense[1] == 8 * (sps - 150)
        # a batch of one gather trip as it is has nothing to save
        assert cells[2] <= chunk and dense[2] == 0 and trips[2] == 1
        # the last piece's block starts before it: the state ends there
        assert sps - 150 < groupagg_device.piece_width(size, 16, dev.slots)
