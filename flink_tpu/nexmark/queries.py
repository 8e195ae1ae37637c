"""NEXMark query pipelines over the DataStream API.

ref: BASELINE.json configs — Q4 average price for a category (the
unbounded join), Q5 sliding hot items, Q7 tumbling highest bid, Q8
tumbling new-user join, Q11 user sessions, Q17 auction statistics (the
unbounded GROUP BY); semantics per the nexmark/nexmark
query definitions (SQL in the external repo; validated shapes in
SURVEY §7).
"""
from __future__ import annotations

from typing import Optional

import numpy as np

from flink_tpu.api.datastream import DataStream
from flink_tpu.api.environment import StreamExecutionEnvironment
from flink_tpu.api.sinks import Sink
from flink_tpu.api.windowing import (
    EventTimeSessionWindows, SlidingEventTimeWindows, TumblingEventTimeWindows)
from flink_tpu.ops import aggregates
from flink_tpu.time.watermarks import WatermarkStrategy


def q5_hot_items(
    env: StreamExecutionEnvironment,
    bids,
    sink: Sink,
    *,
    window_ms: int = 10_000,
    slide_ms: int = 1_000,
    out_of_orderness_ms: int = 0,
) -> DataStream:
    """Q5: which auctions have the most bids per sliding window?

    Stage 1 (device): per-auction COUNT over the sliding window — the
    north-star hot path. Stage 2 (host, per fired batch): argmax per
    window over the per-auction counts; all fires of one window land in
    one batch (one watermark advance fires a window exactly once), so
    the per-batch group-by is exact.
    """
    stream = env.from_source(
        bids, WatermarkStrategy.for_bounded_out_of_orderness(out_of_orderness_ms))
    top = (
        stream.key_by("auction")
        .window(SlidingEventTimeWindows.of(window_ms, slide_ms))
        .count()
        # per-window argmax (ties kept) FUSED into the device fire path:
        # the full per-auction count tensor never leaves HBM; only each
        # window's hot items cross to the host
        .top(1, by="count")
    )

    def rename(data):
        return {"auction": data["key"], "window_end": data["window_end"],
                "bid_count": data["count"]}

    out = top.map(rename, name="q5_rename")
    out.add_sink(sink)
    return out


def q7_highest_bid(
    env: StreamExecutionEnvironment,
    bids,
    sink: Sink,
    *,
    window_ms: int = 10_000,
    out_of_orderness_ms: int = 0,
) -> DataStream:
    """Q7: highest bid per tumbling window — the windowAll/global reduce
    shape, WITHOUT the reference's parallelism-1 funnel: the global max
    folds per pane host-side (see ops/window_all.py for the measured
    bandwidth rationale), so no key shard or device is a hotspot."""
    stream = env.from_source(
        bids, WatermarkStrategy.for_bounded_out_of_orderness(out_of_orderness_ms))
    out = (
        stream.window_all(TumblingEventTimeWindows.of(window_ms))
        .max("price")
    )
    out.add_sink(sink)
    return out


def q8_monitor_new_users(
    env: StreamExecutionEnvironment,
    persons,
    auctions,
    sink: Sink,
    *,
    window_ms: int = 10_000,
    out_of_orderness_ms: int = 0,
) -> DataStream:
    """Q8: persons who created an auction in the same tumbling window
    they registered in (person ⋈ auction-on-seller)."""
    wm = WatermarkStrategy.for_bounded_out_of_orderness(out_of_orderness_ms)
    p = env.from_source(persons, wm)
    a = env.from_source(auctions, wm)
    out = (
        p.join(a).where("person").equal_to("seller")
        .window(TumblingEventTimeWindows.of(window_ms))
        .apply(left_fields=("state_id",), right_fields=("reserve",))
    )
    out.add_sink(sink)
    return out


def q11_user_sessions(
    env: StreamExecutionEnvironment,
    bids,
    sink: Sink,
    *,
    gap_ms: int = 10_000,
    out_of_orderness_ms: int = 0,
) -> DataStream:
    """Q11: how many bids did a user make in each session they were
    active? (nexmark-flink ``queries/q11.sql``: ``SELECT B.bidder,
    count(*) AS bid_count, SESSION_START(..), SESSION_END(..) FROM bid B
    GROUP BY B.bidder, SESSION(B.dateTime, INTERVAL '10' SECOND)``.)

    Per-bidder COUNT over gap-merged session windows; a session ends
    ``gap_ms`` after its last bid. With no allowed lateness and no
    retraction the session state lives on the device
    (ops/session_device.py; the driver chooses the lane)."""
    stream = env.from_source(
        bids, WatermarkStrategy.for_bounded_out_of_orderness(out_of_orderness_ms))
    sessions = (
        stream.key_by("bidder")
        .window(EventTimeSessionWindows.with_gap(gap_ms))
        .count()
    )

    def rename(data):
        return {"bidder": data["key"], "bid_count": data["count"],
                "starttime": data["window_start"],
                "endtime": data["window_end"]}

    out = sessions.map(rename, name="q11_rename")
    out.add_sink(sink)
    return out


MS_PER_DAY = 86_400_000
# (auction, day) as ONE int64 key: the day number above an auction id of
# up to 40 bits (the generator's ids stay under 2^40 for 10^11 events)
Q17_AUCTION_BITS = 40
Q17_PRICE_BANDS = (10_000, 1_000_000)


def q17_auction_stats(
    env: StreamExecutionEnvironment,
    bids,
    sink: Sink,
    *,
    price_bands=Q17_PRICE_BANDS,
) -> DataStream:
    """Q17: auction statistics report, an UNBOUNDED group aggregation
    (nexmark-flink ``queries/q17.sql``: ``SELECT auction, DATE_FORMAT(
    dateTime, 'yyyy-MM-dd') AS day, count(*) AS total_bids, count(*)
    FILTER (WHERE price < 10000) AS rank1_bids, count(*) FILTER (WHERE
    price >= 10000 AND price < 1000000) AS rank2_bids, count(*) FILTER
    (WHERE price >= 1000000) AS rank3_bids, min(price), max(price),
    avg(price), sum(price) FROM bid GROUP BY auction, DATE_FORMAT(..)``).

    No window and no TTL: after every microbatch (the suite's
    mini-batch) one upsert row for each (auction, day) it touched, over
    every bid of the key so far; a row replaces the key's earlier row.
    Every column is BIGINT and exact (integer lanes: ``avg_price`` is
    the integer quotient); ``day`` is the whole days since the epoch
    where the source formats a string. ``last_bid_ms`` is the row's
    event time, the newest ``dateTime`` among the key's bids, which
    Flink carries beside the row and a SQL sink drops. The accumulators
    live on the device (ops/groupagg_device.py; the driver chooses the
    lane)."""
    lo, hi = (int(b) for b in price_bands)
    shift, mask = Q17_AUCTION_BITS, (1 << Q17_AUCTION_BITS) - 1

    def keyed(data, ts, valid):
        auction = np.asarray(data["auction"], np.int64)
        if len(auction) and not 0 <= int(auction.min()) \
                <= int(auction.max()) <= mask:
            raise ValueError(
                f"q17: an auction id outside [0, 2^{shift}) cannot share "
                "an int64 key with its day")
        out = dict(data)
        t = np.asarray(ts, np.int64)
        first, last = ((int(t.min()) // MS_PER_DAY, int(t.max()) // MS_PER_DAY)
                       if len(t) else (0, 0))
        # a batch within one day (all but one in 750,000 at the suite's
        # rate) takes one add; the one that crosses midnight divides
        out["auction_day"] = (auction + (first << shift) if first == last
                              else ((t // MS_PER_DAY) << shift) | auction)
        return out, ts, valid

    stream = env.from_source(
        bids, WatermarkStrategy.for_monotonous_timestamps())
    stats = (
        stream.map_with_timestamps(keyed, name="q17_key")
        .key_by("auction_day")
        .running_aggregate(aggregates.multi(
            aggregates.count("total_bids"),
            aggregates.count_if("price", None, lo, "rank1_bids"),
            aggregates.count_if("price", lo, hi, "rank2_bids"),
            aggregates.count_if("price", hi, None, "rank3_bids"),
            aggregates.int_min_of("price", "min_price"),
            aggregates.int_max_of("price", "max_price"),
            aggregates.int_sum_of("price", "sum_price",
                                  avg_field="avg_price"),
            aggregates.latest_event_time("last_bid_ms")),
            name="q17_auction_stats")
    )

    def rename(data):
        out = {"auction": data["key"] & mask, "day": data["key"] >> shift}
        out.update({k: data[k] for k in Q17_COLUMNS[2:]})
        return out

    out = stats.map(rename, name="q17_rename")
    out.add_sink(sink)
    return out


Q17_COLUMNS = ("auction", "day", "total_bids", "rank1_bids", "rank2_bids",
               "rank3_bids", "min_price", "max_price", "avg_price",
               "sum_price", "last_bid_ms")


# the suite's ``datagen`` table tells its rows apart by ``event_type``
# (ddl_views.sql: person 0, auction 1, bid 2)
EVENT_AUCTION, EVENT_BID = 1, 2
Q4_COLUMNS = ("category", "avg_final", "sum_final", "auctions",
              "last_event_ms")


def q4_category_avg(
    env: StreamExecutionEnvironment,
    events,
    sink: Sink,
) -> DataStream:
    """Q4: average price for a category (nexmark-flink ``queries/
    q4.sql``: ``SELECT Q.category, AVG(Q.final) FROM (SELECT
    MAX(B.price) AS final, A.category FROM auction A, bid B WHERE A.id =
    B.auction AND B.dateTime BETWEEN A.dateTime AND A.expires GROUP BY
    A.id, A.category) Q GROUP BY Q.category``).

    ``events`` is the suite's one ``datagen`` table: a row is an auction
    (``event_type`` 1: ``auction_id``, ``auction_category``,
    ``auction_expires``) or a bid (2: ``bid_auction``, ``bid_price``),
    its ``dateTime`` the row's timestamp; ``auction`` and ``bid`` are
    its two views (ddl_views.sql). Inner query: the UNBOUNDED join of
    the two views on the auction id, both sides kept without a TTL (a
    bid that comes before its auction waits for it), reduced to ``final
    = MAX(price)`` per auction under the predicate, leaving as a
    changelog (ops/join_device.py; the factory chooses the lane). Outer
    query: the changelog folded per category with exact integer lanes
    (a -U row takes out what its +I / +U put in), one row a category a
    microbatch: ``avg_final = sum_final // auctions`` (the sink's
    BIGINT), ``sum_final`` and ``auctions`` (the accumulator AVG
    holds), and ``last_event_ms``, the row's event time: the newest
    ``dateTime`` among the rows of the keys whose ``final`` appeared or
    changed in that microbatch."""
    table = env.from_source(
        events, WatermarkStrategy.for_monotonous_timestamps())
    auction = table.where_equals("event_type", EVENT_AUCTION)
    bid = table.where_equals("event_type", EVENT_BID)
    final = (
        auction.join(bid).where("auction_id").equal_to("bid_auction")
        .right_time_within_left(until="auction_expires")
        .max("bid_price", carry="auction_category", result_field="final",
             name="q4_join")
    )
    avg = (
        final.key_by("auction_category")
        .running_aggregate(aggregates.multi(
            aggregates.changelog_int_sum_of(
                "final", "sum_final", count_field="auctions",
                avg_field="avg_final"),
            aggregates.latest_event_time("last_event_ms",
                                         since_last_row=True)),
            name="q4_category_avg")
    )

    def rename(data):
        out = {"category": data["key"]}
        out.update({k: data[k] for k in Q4_COLUMNS[1:]})
        return out

    out = avg.map(rename, name="q4_rename")
    out.add_sink(sink)
    return out
