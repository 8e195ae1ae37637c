"""The unbounded keyed join (ops/join_device.py, ops/join_host.py) and
the retracting integer lanes that fold its changelog, each against a
per-record reference over two dictionaries on seeded streams."""
import numpy as np
import pytest

from flink_tpu.ops import aggregates
from flink_tpu.ops.global_agg import GlobalAggregateOperator
from flink_tpu.ops.join_device import (
    EARLY_LANES, DeviceKeyedJoinOperator, device_lane_fits)
from flink_tpu.ops.join_host import MINIBATCH_FIELD, HostKeyedJoinOperator
from flink_tpu.records import (
    OP_FIELD, OP_INSERT, OP_UPDATE_AFTER, OP_UPDATE_BEFORE)

FIELDS = dict(until_field="expires", carry_field="category",
              value_field="price", result_field="final")
LANES = {"device": DeviceKeyedJoinOperator, "host": HostKeyedJoinOperator}


def make(lane, **kw):
    return LANES[lane](num_shards=8, slots_per_shard=64, **FIELDS, **kw)


def rec(key, ts, *, expires=None, category=0, price=0):
    """One record: an auction where ``expires`` is given, else a bid."""
    return (key, ts, expires is not None, expires or 0, category, price)


def batch_of(records):
    k, t, left, until, cat, price = (np.asarray(c) for c in zip(*records))
    return (k.astype(np.int64), t.astype(np.int64), left.astype(bool),
            {"expires": until.astype(np.int64),
             "category": cat.astype(np.int64),
             "price": price.astype(np.int64)})


def reference(batches):
    """Per batch the changelog entries ``{key: (category, final before
    or None, final after, newest event time of the key in the batch)}``,
    record by record over two dictionaries."""
    auctions, bids, final, out = {}, {}, {}, []
    for records in batches:
        newest = {}
        for key, ts, left, until, cat, price in records:
            newest[key] = max(newest.get(key, ts), ts)
            if left:
                auctions[key] = (ts, until, cat)
            else:
                bids.setdefault(key, []).append((ts, price))
        entries = {}
        for key in newest:
            if key not in auctions:
                continue
            lo, hi, cat = auctions[key]
            ok = [v for t, v in bids.get(key, ()) if lo <= t <= hi]
            if ok and max(ok) != final.get(key):
                entries[key] = (cat, final.get(key), max(ok), newest[key])
                final[key] = max(ok)
        out.append(entries)
    return out


def entries_of(rows):
    """``reference``'s entries of one mini-batch's changelog rows."""
    if rows is None:
        return {}
    rows = {k: np.asarray(v) for k, v in dict(rows).items()}
    old = {int(k): int(v) for k, v, o in zip(
        rows["key"], rows["final"], rows[OP_FIELD]) if o == OP_UPDATE_BEFORE}
    out = {}
    for k, c, v, o, t in zip(rows["key"], rows["category"], rows["final"],
                             rows[OP_FIELD], rows["__ts__"]):
        if o == OP_UPDATE_BEFORE:
            continue
        assert (o == OP_UPDATE_AFTER) == (int(k) in old)
        assert o in (OP_INSERT, OP_UPDATE_AFTER)
        out[int(k)] = (int(c), old.get(int(k)), int(v), int(t))
    assert len(set(rows[MINIBATCH_FIELD].tolist())) <= 1
    return out


def run(op, batches):
    out = []
    for records in batches:
        k, t, left, data = batch_of(records)
        op.process_batch(k, t, left, data)
        out.append(entries_of(op.take_fired()))
    return out


def seeded(seed, n=3000, keys=200, early=0.3):
    """A stream of auctions (one a key) and bids in event-time order,
    a share of the bids ahead of their auction, some outside its life."""
    r = np.random.default_rng(seed)
    born = np.sort(r.integers(100, 160, keys))
    records = [rec(1000 + i, int(born[i]), expires=int(born[i])
                   + int(r.integers(1, 4)), category=10 + int(r.integers(5)))
               for i in range(keys)]
    for _ in range(n):
        i = int(r.integers(keys))
        dt = int(r.integers(-2, 0)) if r.random() < early \
            else int(r.integers(0, 5))
        records.append(rec(1000 + i, int(born[i]) + dt,
                           price=int(r.integers(1, 1 << 30))))
    records.sort(key=lambda x: (x[1], r.random()))
    return records


def cut(records, parts):
    edges = np.linspace(0, len(records), parts + 1).astype(int)
    return [records[a:b] for a, b in zip(edges[:-1], edges[1:]) if b > a]


AUCTION = rec(7, 100, expires=102, category=12)
BIDS = [rec(7, 100, price=50), rec(7, 101, price=80), rec(7, 102, price=60)]


@pytest.mark.parametrize("lane", sorted(LANES))
@pytest.mark.parametrize("order", [
    "auction_first", "bids_first", "same_batch", "three_batches"])
def test_the_order_of_arrival_decides_nothing(lane, order):
    batches = {"auction_first": [[AUCTION], BIDS],
               "bids_first": [BIDS, [AUCTION]],
               "same_batch": [BIDS[:1] + [AUCTION] + BIDS[1:]],
               "three_batches": [BIDS[:2], [AUCTION], BIDS[2:]]}[order]
    got = run(make(lane), batches)
    assert got == reference(batches)
    finals = [e[7][2] for e in got if 7 in e]
    assert finals[-1] == 80 and got[-1] != {} or order == "three_batches"
    # whatever the order: the key ends at the one final
    state = {}
    for e in got:
        state.update({k: v[2] for k, v in e.items()})
    assert state == {7: 80}


@pytest.mark.parametrize("lane", sorted(LANES))
@pytest.mark.parametrize("bid_ts,matches", [
    (100, True), (102, True), (99, False), (103, False)])
def test_both_ends_of_the_predicate_are_inclusive(lane, bid_ts, matches):
    for batches in ([[AUCTION], [rec(7, bid_ts, price=9)]],
                    [[rec(7, bid_ts, price=9)], [AUCTION]],
                    [[rec(7, bid_ts, price=9), AUCTION]]):
        op = make(lane)
        got = run(op, batches)
        assert got == reference(batches)
        assert (got[-1] == {7: (12, None, 9, max(
            r[1] for r in batches[-1]))}) == matches
        c = op.state_counters()
        assert c["join.bids_matched"] + c["join.lanes_matched"] \
            == int(matches)
        assert c["join.bids_refused"] + c["join.lanes_refused"] \
            == int(not matches)


@pytest.mark.parametrize("lane", sorted(LANES))
def test_an_early_bid_in_the_millisecond_before_is_parked_then_refused(lane):
    op = make(lane)
    batches = [[rec(7, 99, price=500), rec(7, 100, price=20)], [AUCTION]]
    got = run(op, batches[:1])
    c = op.state_counters()
    assert got == [{}] and c["join.bids_parked"] == 2
    assert c["join.bids_refused"] == c["join.lanes_refused"] == 0
    got += run(op, batches[1:])
    assert got == reference(batches) == [{}, {7: (12, None, 20, 100)}]
    c = op.state_counters()
    assert c["join.lanes_refused"] == 1 and c["join.bids_parked"] == 2
    assert c["join.keys_changed"] == c["join.changelog_rows"] == 1


def test_a_key_that_needs_a_third_early_lane_is_handed_over_and_counted():
    early = [rec(7, 97 + i, price=10 * (i + 1)) for i in range(4)]
    other = [rec(8, 98, price=5), rec(8, 100, price=6)]
    batches = [early[:2] + other, early[2:], [AUCTION, rec(
        8, 99, expires=100, category=11)], [rec(7, 101, price=70)]]
    assert len({r[1] for r in early}) > EARLY_LANES
    op = make("device")
    got = run(op, batches)
    assert got == reference(batches)
    assert got[2] == {7: (12, None, 40, 100), 8: (11, None, 6, 99)}
    assert got[3] == {7: (12, 40, 70, 101)}
    c = op.state_counters()
    assert c["join.pending_overflow"] == 1      # key 7, not key 8
    host = make("host")
    assert run(host, batches) == got
    for counters in (c, host.state_counters()):
        assert counters["join.bids_parked"] == 6
        assert counters["join.bids_matched"] == 1       # the bid at 101
        assert counters["join.lanes_matched"] == 2      # (7, 100), (8, 100)
        assert counters["join.lanes_refused"] == 4


@pytest.mark.parametrize("lane", sorted(LANES))
def test_a_price_past_32_bits_is_refused_and_counted(lane):
    batches = [[AUCTION, rec(7, 101, price=(1 << 31) + 5),
                rec(7, 101, price=33)]]
    op = make(lane)
    got = run(op, batches)
    if lane == "device":
        assert got == [{7: (12, None, 33, 101)}]
        assert op.state_counters()["join.lane_overflow"] == 1
    else:   # the host's words are 64 bits wide
        assert got == reference(batches)
        assert op.state_counters()["join.lane_overflow"] == 0


@pytest.mark.parametrize("lane", sorted(LANES))
def test_a_final_that_rises_three_times_retracts_what_was_emitted(lane):
    batches = [[AUCTION, rec(7, 100, price=10)], [rec(7, 100, price=5)],
               [rec(7, 101, price=30), rec(7, 101, price=20)],
               [rec(7, 102, price=31)]]
    op = make(lane)
    rows = []
    for records in batches:
        k, t, left, data = batch_of(records)
        op.process_batch(k, t, left, data)
        fired = op.take_fired()
        rows.append(None if fired is None or not len(dict(fired)["key"])
                    else {k: np.asarray(v).tolist()
                          for k, v in dict(fired).items()})
    assert rows[1] is None          # a lower bid changes nothing
    assert [(r[OP_FIELD], r["final"]) for r in rows if r] == [
        ([OP_INSERT], [10]),
        ([OP_UPDATE_BEFORE, OP_UPDATE_AFTER], [10, 30]),
        ([OP_UPDATE_BEFORE, OP_UPDATE_AFTER], [30, 31])]
    # folded by the retracting lanes: what the outer AVG holds
    agg = aggregates.changelog_int_sum_of("final", "sum", count_field="n")
    outer = GlobalAggregateOperator(agg, num_shards=2, slots_per_shard=8)
    for r in rows:
        if r:
            outer.process_batch(
                np.asarray(r["category"]), np.asarray(r["__ts__"]),
                {"final": np.asarray(r["final"]),
                 OP_FIELD: np.asarray(r[OP_FIELD], np.int8)})
            out = dict(outer.take_fired())
    assert out["sum"].tolist() == [31] and out["n"].tolist() == [1]


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("parts", [1, 3, 8])
def test_both_lanes_give_the_reference_rows_on_seeded_streams(seed, parts):
    batches = cut(seeded(seed), parts)
    want = reference(batches)
    dev, host = make("device"), make("host")
    assert run(dev, batches) == want
    assert run(host, batches) == want
    d, h = dev.state_counters(), host.state_counters()
    assert d.pop("join.on_host") == 0 and h.pop("join.on_host") == 1
    d.pop("join.pending_overflow"), h.pop("join.pending_overflow")
    assert {k: v for k, v in d.items() if k.startswith("join.")} \
        == {k: v for k, v in h.items() if k.startswith("join.")}
    assert d["join.bids_parked"] > 100 and d["join.keys_changed"] > 100


@pytest.mark.parametrize("into", sorted(LANES))
@pytest.mark.parametrize("lane", sorted(LANES))
def test_snapshot_and_restore_in_mid_stream_continue_to_the_same_rows(
        lane, into):
    batches = cut(seeded(5, early=0.5), 6)
    want = reference(batches)
    op = make(lane)
    assert run(op, batches[:3]) == want[:3]
    if lane == "device":
        dict(op._empty())       # nothing undelivered: the drain is flushed
    snap = op.snapshot_state()
    assert snap["kind"] == "keyed_join"
    twin = make(into)
    twin.restore_state(snap)
    assert run(twin, batches[3:]) == want[3:]
    assert run(op, batches[3:]) == want[3:]


def test_the_device_lane_is_for_one_device_and_int32_cell_keys():
    assert device_lane_fits(mesh=False, slots=128 * 262144)
    assert not device_lane_fits(mesh=True, slots=1024)
    assert not device_lane_fits(mesh=False, slots=1 << 30)


# -- the lanes that fold the changelog ---------------------------------------

def test_changelog_int_sum_is_exact_past_53_bits_and_counts_signed_rows():
    agg = aggregates.multi(
        aggregates.changelog_int_sum_of("v", "sum", count_field="n",
                                        avg_field="avg"),
        aggregates.latest_event_time("last", since_last_row=True))
    op = GlobalAggregateOperator(agg, num_shards=2, slots_per_shard=8)
    big = (1 << 60) + 1
    ops = np.asarray([OP_INSERT, OP_INSERT, OP_UPDATE_BEFORE,
                      OP_UPDATE_AFTER], np.int8)
    op.process_batch(np.zeros(4, np.int64), np.asarray([5, 9, 7, 7]),
                     {"v": np.asarray([big, 3, 3, 4]), OP_FIELD: ops})
    out = dict(op.take_fired())
    assert out["sum"].tolist() == [big + 4] and out["n"].tolist() == [2]
    assert out["avg"].tolist() == [(big + 4) // 2]
    assert out["count"].tolist() == [4] and out["last"].tolist() == [9]
    # the rowtime lane starts anew with every row: an older batch reads
    # its own newest, not the key's
    op.process_batch(np.zeros(1, np.int64), np.asarray([6]),
                     {"v": np.asarray([1]), OP_FIELD: ops[:1]})
    out = dict(op.take_fired())
    assert out["last"].tolist() == [6] and out["n"].tolist() == [3]
    twin = GlobalAggregateOperator(agg, num_shards=2, slots_per_shard=8)
    twin.restore_state(op.snapshot_state())
    for o in (op, twin):
        o.process_batch(np.zeros(1, np.int64), np.asarray([2]),
                        {"v": np.asarray([big]), OP_FIELD: ops[2:3]})
        out = dict(o.take_fired())
        assert out["sum"].tolist() == [5] and out["last"].tolist() == [2]


def test_a_lane_that_starts_anew_with_every_row_keeps_the_host_operator():
    from flink_tpu.ops.groupagg_device import device_lane_fits as fits

    plain = aggregates.latest_event_time("last")
    anew = aggregates.latest_event_time("last", since_last_row=True)
    assert fits(agg=plain, retract=False, mesh=False, slots=64)
    assert not fits(agg=anew, retract=False, mesh=False, slots=64)
    assert aggregates.multi(aggregates.count(), anew).emission_lanes \
        == ((), (0,), ())


# -- the job: q4 through env.execute() -----------------------------------------

Q4_PARAMS = {"person_proportion": 1, "auction_proportion": 3,
             "bid_proportion": 46, "num_in_flight_auctions": 100,
             "hot_auction_ratio": 2, "num_active_people": 1000,
             "hot_bidders_ratio": 4, "events_per_ms_all": 100,
             "pool_batches": 4}


def q4_stream(seed, batches, n=2048):
    from benchmark.configs import nexmark_q4_category_avg as q4

    pool = q4.EventPool(seed, n, Q4_PARAMS)
    rate = q4.offered_per_ms(Q4_PARAMS)
    return [(pool[i], (i * n + np.arange(n, dtype=np.int64)) // rate)
            for i in range(batches)]


def run_q4(stream, **conf_over):
    from flink_tpu.api.environment import StreamExecutionEnvironment
    from flink_tpu.api.sinks import FnSink
    from flink_tpu.api.sources import Source
    from flink_tpu.config import Configuration
    from flink_tpu.nexmark.queries import q4_category_avg

    class Batches(Source):
        def open_split(self, split, start_pos=0):
            yield from stream

    conf = Configuration()
    for k, v in {"state.num-key-shards": 8, "state.slots-per-shard": 512,
                 "analysis.fail-on": "off", **conf_over}.items():
        conf.set(k, v)
    rows = []
    env = StreamExecutionEnvironment(conf)
    q4_category_avg(env, Batches(), FnSink(
        lambda b: rows.append({k: np.array(v) for k, v in b.items()})))
    return rows, env.execute("q4").metrics


@pytest.mark.parametrize("lane,conf", [
    ("device", {}), ("host", {"cluster.mesh-devices": 4})])
def test_q4_commits_the_reference_rows_on_either_lane(lane, conf):
    from benchmark.configs import nexmark_q4_category_avg as q4

    stream = q4_stream(11, 7)
    rows, m = run_q4(stream, **conf)
    cmp_ = q4.compare(q4.category_rows(iter(stream)), q4.collect(rows, {}))
    assert cmp_["rows_expected"] == 7 * 5 == cmp_["rows_got"]
    assert not (cmp_["rows_missing"] or cmp_["rows_not_in_reference"]
                or cmp_["rows_duplicated"]), cmp_
    # the factory chose the lane by what the job is, and says which
    assert m["join.on_host"] == (lane == "host")
    assert m["groupagg.on_host"] == 1       # the outer AVG: 5 keys, host
    assert m["records_in"] == 7 * 2048 == m["join.auctions_in"] \
        + m["join.bids_in"]
    assert m["join.batches"] == 7 and m["join.lane_overflow"] == 0
    assert m["join.bids_parked"] > 0 < m["join.bids_refused"]
    assert m["join.changelog_rows"] == m["join.keys_changed"] \
        <= m["join.rows_emitted"]
    assert m["records_out"] == 35


def test_q4s_spans_counters_and_fire_records_on_the_device_lane():
    rows, m = run_q4(q4_stream(12, 5))
    for leaf in ("ingest.route", "window.key_scan", "window.pack",
                 "window.h2d", "window.step_dispatch", "drain.fetch",
                 "drain.deliver"):
        assert m[f"profile.phase.{leaf}"] > 0, leaf
    for detail in ("window.key_scan/prepare", "window.key_scan/assign",
                   "window.key_scan/slot_mask", "drain.deliver/fold"):
        assert m[f"profile.detail.{detail}"] > 0, detail
    # the fold runs once a mini-batch, whatever the drain's polls held
    assert m["profile.detail_n.drain.deliver/fold"] == 5
    assert m["memory.hbm_state_bytes"] == 8 * 512 * 9 * 4
    op = [k.split(".")[1] for k in m if k.endswith(".apply_trips")][0]
    assert m[f"profile.{op}.apply_records"] == 5 * 2048
    assert m[f"profile.{op}.apply_cells"] >= m["join.keys_changed"]
    fires = m["trace.fires"]
    assert len(fires) == 5
    for f in fires:
        stamps = [f[k] for k in ("t_input", "t_fire", "t_queued", "t_fetch0",
                                 "t_ready", "t_fetch1", "t_push0", "t_sink")
                  if f[k] is not None]
        assert stamps == sorted(stamps) and len(stamps) >= 7
    assert m["wm.advances_led"] == 0    # nobody leads a join's batch


def test_a_delivery_of_several_mini_batches_is_folded_one_at_a_time():
    from flink_tpu.runtime.driver import _minibatches

    out = {"key": np.arange(6), MINIBATCH_FIELD: np.asarray(
        [3, 3, 4, 6, 6, 6])}
    parts = list(_minibatches(out, np.arange(6) * 10))
    assert [p["key"].tolist() for p, _ in parts] == [[0, 1], [2], [3, 4, 5]]
    assert [t.tolist() for _, t in parts] == [[0, 10], [20], [30, 40, 50]]
    assert all(MINIBATCH_FIELD not in p for p, _ in parts)
    plain = {"key": np.arange(3)}
    assert [p for p, _ in _minibatches(plain, np.arange(3))] == [plain]


def test_an_unbounded_join_takes_two_views_of_one_stream():
    from flink_tpu.api.environment import StreamExecutionEnvironment
    from flink_tpu.api.sources import Source
    from flink_tpu.config import Configuration

    env = StreamExecutionEnvironment(Configuration())
    a = env.from_source(Source())
    b = env.from_source(Source())
    left, right = a.where_equals("kind", 1), b.where_equals("kind", 2)
    with pytest.raises(NotImplementedError, match="two views of ONE"):
        (left.join(right).where("id").equal_to("ref")
         .right_time_within_left(until="until").max("v", carry="c"))
    # a view read as a stream is its filter, made when it is read
    n = len(env._transforms)
    view = a.where_equals("kind", 1)
    assert len(env._transforms) == n
    assert view.transform.kind == "filter"
    assert len(env._transforms) == n + 1


def test_a_crash_in_mid_stream_restores_both_operators_exactly_once(tmp_path):
    """The join's state on the device and the outer aggregate's on the
    host are one consistent checkpoint: a job that dies between two
    batches restores both, replays, and commits the reference's rows
    once each."""
    from benchmark.configs import nexmark_q4_category_avg as q4
    from flink_tpu.api.environment import StreamExecutionEnvironment
    from flink_tpu.api.sinks import TransactionalCollectSink
    from flink_tpu.api.sources import Source
    from flink_tpu.config import Configuration
    from flink_tpu.nexmark.queries import q4_category_avg
    from flink_tpu.runtime.supervisor import run_with_recovery

    stream = q4_stream(31, 12)
    starts, crashed = [], []

    class Resuming(Source):
        def open_split(self, split, start_pos=0):
            starts.append(start_pos)
            for i in range(start_pos, len(stream)):
                if i == 7 and not crashed:
                    crashed.append(i)
                    raise RuntimeError("the task dies before batch 7")
                yield stream[i]

    sink = TransactionalCollectSink()

    def build_env(conf):
        env = StreamExecutionEnvironment(conf)
        q4_category_avg(env, Resuming(), sink)
        return env

    res = run_with_recovery(build_env, Configuration({
        "state.num-key-shards": 8, "state.slots-per-shard": 512,
        "analysis.fail-on": "off", "pipeline.source-prefetch": 0,
        "execution.checkpointing.interval": 1,
        "execution.checkpointing.dir": str(tmp_path),
        "restart-strategy.type": "fixed-delay",
        "restart-strategy.fixed-delay.attempts": 2,
        "restart-strategy.fixed-delay.delay": 1}), job_name="q4-crash")
    assert crashed == [7] and len(starts) == 2 and 0 < starts[1] <= 7
    cmp_ = q4.compare(q4.category_rows(iter(stream)), [
        tuple(int(r[f]) for f in q4.ROW_FIELDS) for r in sink.committed])
    assert cmp_["rows_expected"] == 60 == cmp_["rows_got"], cmp_
    assert not (cmp_["rows_missing"] or cmp_["rows_not_in_reference"]
                or cmp_["rows_duplicated"]), cmp_
    assert res.metrics["join.on_host"] == 0
    assert res.metrics["records_in"] == 12 * 2048
