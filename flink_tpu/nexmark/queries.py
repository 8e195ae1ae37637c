"""NEXMark query pipelines over the DataStream API.

ref: BASELINE.json configs — Q5 sliding hot items, Q7 tumbling highest
bid, Q8 tumbling new-user join, Q11 user sessions; semantics per the
nexmark/nexmark query definitions (SQL in the external repo; validated
shapes in SURVEY §7).
"""
from __future__ import annotations

from typing import Optional

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
