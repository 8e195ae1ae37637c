"""NEXmark query 17 "auction statistics" on the suite's own bid stream:
the job (``flink_tpu.nexmark.queries.q17_auction_stats``), its records
(the bids of ``nexmark_q5_large_keys``, imported: the same ``LazyPool``)
and a plain reference.

``queries/q17.sql`` is an UNBOUNDED group aggregation: ``GROUP BY
auction, DATE_FORMAT(dateTime, 'yyyy-MM-dd')`` with no window and no
TTL; per key ``count(*)``, three ``count(*) FILTER`` over price bands,
``min``, ``max``, ``avg`` and ``sum`` of the BIGINT price. Under the
suite's ``table.exec.mini-batch.*`` a key touched in a mini-batch emits
ONE row for it; this program's microbatch is its mini-batch. So after
batch i every key with a bid in batch i has one row: its aggregate over
every bid of the key in batches 0..i, exact integers at 64 bits
(``avg_price`` the integer quotient), plus ``last_bid_ms``, the newest
``dateTime`` among them (the configuration's ``assumed``). A row
replaces the key's earlier row; nothing is late, nothing expires, the
end of input flushes nothing.

What the stream is (``nexmark_q5.py`` has the generator's formulas): 3
auctions new per 50 events, so ~68,400 keys no earlier batch has named
with every 2^20-bid batch; an auction is among the ~111 in flight for
0.17 ms, so its ~15 bids lie within a millisecond and it is touched in
one batch (two where it straddles an edge); one id in 100 is hot and
takes ~770 bids. No key ever leaves.

The reference is numpy only, int64 throughout, and takes nothing from
the program: per batch the records sorted by key and reduced per key
(``reduceat``), then the per-batch partial rows of the whole stream
sorted by (key, batch) and accumulated within each key (a running sum;
a running min and max with every key's values in a band of their own).
It assumes neither that a key lies in one batch nor order within a
batch.

``make_pool`` refuses a program without the device operator of the
unwindowed aggregation: on such a program the job would run on the host
operator, whose rows leave no cohort and whose rate at this density is
unknown; the run ends there, before any job is built, with another exit
code than 0. That the RUN kept its accumulators on the device is held
by a zero counter (``groupagg.on_host``).
"""
from __future__ import annotations

from typing import Tuple

import numpy as np

from benchmark.configs import nexmark_q5 as q5
from benchmark.configs import nexmark_q5_large_keys as large
from benchmark.reference_util import blocks_in_order

SCHEMA = large.SCHEMA
# the latency's handle: a row is stamped with the newest bid it holds
WINDOW_END_FIELD = "last_bid_ms"
ROW_FIELDS = ("auction", "day", "total_bids", "rank1_bids", "rank2_bids",
              "rank3_bids", "min_price", "max_price", "avg_price",
              "sum_price", "last_bid_ms")
MS_PER_DAY = 86_400_000
AUCTION_BITS = 40           # key = day << 40 | auction, in the reference too


def fire_delay_ms(p: dict) -> int:
    """No window and no watermark: a row may leave as soon as its
    newest bid has arrived. ``stats.fire_latencies_ms`` then reads, per
    millisecond W, the first arrival of a row whose newest bid is
    stamped W minus the due time of W."""
    return 0


def device_groupagg() -> bool:
    """Whether the program has Q17 and an unwindowed aggregation whose
    accumulators are on the device."""
    try:
        from flink_tpu.nexmark.queries import q17_auction_stats  # noqa: F401
        from flink_tpu.ops.groupagg_device import (  # noqa: F401
            DeviceGroupAggOperator)
    except ImportError:
        return False
    return True


def make_pool(seed: int, n: int, p: dict):
    if not device_groupagg():
        raise NotImplementedError(
            "this configuration runs NEXmark Q17 with the accumulators of "
            "the unwindowed aggregation on the device (memory"
            ".hbm_state_bytes > 0, exact integer lanes, the batch's rows "
            "through the emit ring and the drain); the program in this "
            "checkout has no q17_auction_stats or no device operator "
            "(flink_tpu/ops/groupagg_device.py): it does not support this "
            "configuration")
    return large.make_pool(seed, n, p)


# -- what the harness asks a configuration's module ------------------------

def warmup_event_ms(p: dict) -> int:
    """Event time a warm-up pass has to span: a few batches (every batch
    runs the one program and emits; there is no fire to reach)."""
    return 350


def zero_counters(p: dict) -> Tuple[str, ...]:
    """Job metrics that the guarantees hold at 0: nothing dropped or
    late, no value refused by a lane, and the aggregation not on the
    host operator (the driver's choice of lane: it reads 1 there, and
    the run is then not this configuration's)."""
    return ("records_dropped_full", "late_records", "groupagg.on_host",
            "groupagg.lane_overflow")


def keys_per_batch(p: dict, batch: int) -> int:
    """Distinct auctions a batch of ``batch`` bids names, by the
    generator's formulas: every auction new in it and the window in
    flight it starts with."""
    return (batch * int(p["auction_proportion"]) // int(p["bid_proportion"])
            + int(p["num_in_flight_auctions"]) + 1 + q5.AUCTION_ID_LEAD)


def step_shapes(p: dict, batch: int, events_per_ms: float) -> dict:
    """What ``upsert_step_bytes`` needs to know of one batch: from the
    deployment's shapes, not from the program."""
    return {"records": batch, "keys": keys_per_batch(p, batch),
            "slots": int(p["state_slots"])}


def build(env, source, sink, p: dict) -> None:
    from flink_tpu.nexmark.queries import q17_auction_stats

    q17_auction_stats(env, source, sink, price_bands=(
        int(p["rank1_below"]), int(p["rank3_from"])))


# -- the plain reference ---------------------------------------------------

def batch_partials(auction, price, ts, bands):
    """One batch's per-key partial aggregates as int64 columns (key,
    bids, rank1, rank2, rank3, min, max, sum, last): its records sorted
    by key = day << 40 | auction and reduced per key."""
    lo, hi = bands
    t = np.asarray(ts, np.int64)
    key = ((t // MS_PER_DAY) << AUCTION_BITS) | np.asarray(auction, np.int64)
    order = np.argsort(key, kind="stable")
    key, t = key[order], t[order]
    pr = np.asarray(price, np.int64)[order]
    first = np.flatnonzero(np.r_[True, key[1:] != key[:-1]])
    add = np.add.reduceat
    return (key[first], np.diff(np.r_[first, len(key)]),
            add((pr < lo).astype(np.int64), first),
            add(((pr >= lo) & (pr < hi)).astype(np.int64), first),
            add((pr >= hi).astype(np.int64), first),
            np.minimum.reduceat(pr, first), np.maximum.reduceat(pr, first),
            add(pr, first), np.maximum.reduceat(t, first))


def running_rows(parts):
    """The upsert rows of a stream from its batches' partials, in
    stream order: one row per (key, batch that touched it), the
    partials of the key's batches so far accumulated. Columns as
    ``ROW_FIELDS`` with the key in place of (auction, day), sorted by
    (key, total_bids), and the bids each row's own batch added."""
    if not parts:
        z = np.zeros(0, np.int64)
        return (z,) * 10, z
    cols = [np.concatenate([p[i] for p in parts]) for i in range(9)]
    # stable: a key's partials stay in batch order
    order = np.argsort(cols[0], kind="stable")
    key, n, r1, r2, r3, mn, mx, sm, last = (c[order] for c in cols)
    new = np.r_[True, key[1:] != key[:-1]]
    first = np.flatnonzero(new)
    group = np.cumsum(new) - 1

    def run_sum(x):
        c = np.cumsum(x)
        return c - (c[first] - x[first])[group]

    def run_ext(x, ufunc, sign):
        # every key's values in a band of its own, ascending for a
        # running max and descending for a running min, so that ONE
        # accumulate keeps the keys apart
        base = int(x.min())
        band = int(x.max()) - base + 1
        assert band * len(first) < 1 << 62, "the bands leave int64"
        off = sign * group * band - base
        return ufunc.accumulate(x + off) - off

    total = run_sum(n)
    sums = run_sum(sm)
    return (key, total, run_sum(r1), run_sum(r2), run_sum(r3),
            run_ext(mn, np.minimum, -1), run_ext(mx, np.maximum, 1),
            sums // total, sums, run_ext(last, np.maximum, 1)), n


def upserts(stream, p: dict):
    """``running_rows`` of a stream of ``(data, ts)`` batches."""
    bands = (int(p["rank1_below"]), int(p["rank3_from"]))
    return running_rows(list(blocks_in_order(
        stream, lambda data, ts: batch_partials(
            data["auction"], data["price"], ts, bands))))


def collect(sink_batches, p: dict):
    """The committed rows, the key in place of (auction, day)."""
    if not sink_batches:
        return (np.zeros(0, np.int64),) * 10
    cols = {f: np.concatenate([np.asarray(b[f], np.int64)
                               for b in sink_batches]) for f in ROW_FIELDS}
    key = (cols["day"] << AUCTION_BITS) | cols["auction"]
    return (key,) + tuple(cols[f] for f in ROW_FIELDS[2:])


def compare(exp, exp_bids, got) -> dict:
    """Every committed row against the reference's. A reference row is
    found by (key, total_bids), which no two rows share (a key's total
    grows with every row), and then held to its other eight columns."""
    e_key, e_total = exp[0], exp[1]
    g_key, g_total = got[0], got[1]
    keys = e_key[np.r_[True, e_key[1:] != e_key[:-1]]] if len(e_key) \
        else e_key
    width = int(max(e_total.max(initial=0), g_total.max(initial=0))) + 1
    e_id = np.searchsorted(keys, e_key) * width + e_total   # ascending
    rank = np.minimum(np.searchsorted(keys, g_key), max(len(keys) - 1, 0))
    known = (np.zeros(len(g_key), bool) if not len(keys)
             else keys[rank] == g_key)
    g_id = rank * width + g_total
    at = np.minimum(np.searchsorted(e_id, g_id), max(len(e_id) - 1, 0))
    match = known & (e_id[at] == g_id) if len(e_id) else known
    for e, g in zip(exp[2:], got[2:]):
        match &= e[at] == g if len(e_id) else False
    hits = np.bincount(at[match], minlength=len(e_id))
    missing = hits == 0
    # a key's LAST row is its aggregate over the whole stream
    last_of_key = np.r_[e_key[1:] != e_key[:-1], True] if len(e_key) \
        else np.zeros(0, bool)
    wrong = np.flatnonzero(~match)[:3]
    lost = np.flatnonzero(missing)[:3]
    return {
        "rows_expected": int(len(e_id)),
        "rows_got": int(len(g_id)),
        "rows_duplicated": int((hits[hits > 1] - 1).sum()),
        "rows_missing": int(missing.sum()),
        "rows_not_in_reference": int((~match).sum()),
        "keys_expected": int(len(keys)),
        "keys_without_final_row": int((missing & last_of_key).sum()),
        # the bids a missing row's own batch added count as failed
        "events_without_result": int(exp_bids[missing].sum()),
        "first_differences": (
            [["missing"] + [int(c[i]) for c in exp] for i in lost]
            + [["not_in_reference"] + [int(c[i]) for c in got]
               for i in wrong]),
    }


def check(stream, max_ts: int, sink_batches, p: dict) -> dict:
    """Every committed row against the reference's; all limits are 0."""
    exp, exp_bids = upserts(stream, p)
    return compare(exp, exp_bids, collect(sink_batches, p))
