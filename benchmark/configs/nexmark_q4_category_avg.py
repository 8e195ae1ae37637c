"""NEXmark query 4 "average price for a category" on the suite's own
auction and bid streams: the job (``flink_tpu.nexmark.queries
.q4_category_avg``), its records and a plain reference.

``queries/q4.sql``::

    SELECT Q.category, AVG(Q.final)
    FROM (SELECT MAX(B.price) AS final, A.category
          FROM auction A, bid B
          WHERE A.id = B.auction
            AND B.dateTime BETWEEN A.dateTime AND A.expires
          GROUP BY A.id, A.category) Q
    GROUP BY Q.category;

an UNBOUNDED two-stream join (no window, no TTL: both sides are kept)
feeding a retracting AVG. ``auction`` and ``bid`` are views of the
suite's one ``datagen`` table (``ddl_views.sql``: ``event_type`` 1 and
2); this source hands over that table without its persons, interleaved
as the generator interleaves them: of every 50 events 1 person (not
offered), then 3 auctions, then 46 bids, so offered event ``e`` belongs
to epoch ``e // 49`` and is an auction where ``e % 49 < 3``.

**Semantics, to the letter** (``check`` implements exactly this and
takes nothing from the program):

- an auction row is (id, category, dateTime, expires); a bid row is
  (auction, price, dateTime);
- a bid MATCHES when an auction with ``id = auction`` has been seen
  (earlier, later or in the same batch) and ``A.dateTime <= B.dateTime
  <= A.expires``, both ends inclusive;
- ``final(id)`` after batch i = the maximum price over the matching
  bids of batches 0..i; a key without a matching bid has no ``final``
  and is in no category's average (an inner join);
- after batch i, for every category in which some key's ``final``
  appeared or changed in batch i, ONE row ``(category, avg_final,
  sum_final, auctions, last_event_ms)``: ``sum_final`` = the sum of
  ``final`` over the category's keys, ``auctions`` = how many have
  one, ``avg_final = sum_final // auctions``, ``last_event_ms`` = over
  the category's keys whose ``final`` appeared or changed in batch i,
  the newest event time among THAT key's records (auction or bids,
  matching or not) in batch i. The microbatch is the mini-batch.

**The stream.** Bids as ``nexmark_q5_large_keys`` makes them (the draws
are ``_Draws`` of that module, a draw an OFFERED event of the pool
batch; the auction of a cold bid is uniform over ``[newest - 100, newest
+ AUCTION_ID_LEAD]``, so ~9 % of cold bids name an auction that has not
been created yet: it comes within 4 epochs, mostly in the same
millisecond, sometimes in the next, and then the bid must NOT match).
Auctions by ``AuctionGenerator``'s formulas, written from memory
(``assumed.auction_generator``): id ``FIRST_AUCTION_ID + epoch * 3 +
k``; ``category = FIRST_CATEGORY_ID + nextInt(NUM_CATEGORIES)`` (10 +
one of 5); ``expires = dateTime + 1 + nextLong(max(2 * horizonMs, 1))``
with ``horizonMs`` the event-time distance of ``numInFlightAuctions *
50 / 3`` = 1,666 events of the whole stream (persons included): 0 or 1
ms at the suite's 10,000 events per ms. Seller, item name, description,
initial bid and reserve are not carried (q4 reads none). ``suite_events``
is those formulas written out directly; ``EventPool`` gives the same
batches from per-epoch arrays.

The reference is numpy only, int64 throughout: batch by batch, every
auction's (category, dateTime, expires) in arrays indexed by id; every
bid (the batch's, and those still waiting for their auction) held to
the predicate against its auction once that is known: its EFFECTIVE
batch is the later of its own and its auction's; per key a running
maximum; per (batch, category) the deltas of the changed keys and the
first appearances, accumulated. It assumes neither order within a batch
nor that an auction comes soon.

``make_pool`` refuses a program without the device join before any job
is built (the run ends there with another exit code than 0).
"""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

from benchmark.configs import nexmark_q5 as q5
from benchmark.configs import nexmark_q5_large_keys as large
from benchmark.reference_util import blocks_in_order

# the suite's datagen table, without what q4 does not read
SCHEMA = {"event_type": "int64", "auction_id": "int64",
          "auction_category": "int64", "auction_expires": "int64",
          "bid_auction": "int64", "bid_price": "int64"}
# the latency's handle: a row is stamped with the newest event it holds
WINDOW_END_FIELD = "last_event_ms"
ROW_FIELDS = ("category", "avg_final", "sum_final", "auctions",
              "last_event_ms")
AUCTION, BID = 1, 2                 # ddl_views.sql event_type
FIRST_CATEGORY_ID = 10              # GeneratorConfig.FIRST_CATEGORY_ID
NUM_CATEGORIES = 5                  # AuctionGenerator.NUM_CATEGORIES
PROPORTION_DENOMINATOR = 50         # 1 + 3 + 46
NONE = np.iinfo(np.int64).min
POOL_LEN = large.POOL_LEN


def fire_delay_ms(p: dict) -> int:
    """No window and no watermark: a row may leave as soon as its
    newest event has arrived."""
    return 0


def device_join() -> bool:
    """Whether the program has Q4 and an unbounded join whose state is
    on the device."""
    try:
        from flink_tpu.nexmark.queries import q4_category_avg  # noqa: F401
        from flink_tpu.ops.join_device import (  # noqa: F401
            DeviceKeyedJoinOperator)
    except ImportError:
        return False
    return True


def _offered(p: dict) -> Tuple[int, int, int]:
    """(auctions, bids, offered events) an epoch."""
    a, b = int(p["auction_proportion"]), int(p["bid_proportion"])
    return a, b, a + b


def offered_per_ms(p: dict) -> int:
    """Offered events a millisecond of event time: the whole stream's
    density less its persons."""
    whole = int(p["events_per_ms_all"]) * _offered(p)[2]
    assert whole % PROPORTION_DENOMINATOR == 0
    return whole // PROPORTION_DENOMINATOR


def horizon_events(p: dict) -> int:
    """``numInFlightAuctions * PROPORTION_DENOMINATOR /
    AUCTION_PROPORTION``: how far ahead ``nextAuctionLengthMs`` looks,
    in events of the whole stream."""
    return (int(p["num_in_flight_auctions"]) * PROPORTION_DENOMINATOR
            // int(p["auction_proportion"]))


def suite_events(seed: int, i: int, n: int, p: dict
                 ) -> Dict[str, np.ndarray]:
    """Offered events ``[i * n, (i + 1) * n)`` by the suite's formulas,
    directly, over the draws of pool batch ``i % pool_batches`` (a draw
    an offered event). What ``EventPool`` must give; slow (64-bit
    divisions per event), so the window never calls it."""
    auctions, bids, both = _offered(p)
    persons = int(p["person_proportion"])
    in_flight = int(p["num_in_flight_auctions"])
    rate = int(p["events_per_ms_all"])
    d = large._Draws(seed, i % int(p["pool_batches"]), n, p)
    e = i * n + np.arange(n, dtype=np.int64)
    epoch, r = e // both, e % both
    is_auction = r < auctions
    # the event's number in the whole stream (persons come first in an
    # epoch) and its timestamp there: the schedule's, e // offered_per_ms
    number = epoch * PROPORTION_DENOMINATOR + persons + r
    ts = number // rate
    # AuctionGenerator.nextAuction
    auction_id = q5.FIRST_AUCTION_ID + epoch * auctions + r
    category = FIRST_CATEGORY_ID + (d.u_bidder * NUM_CATEGORIES
                                    ).astype(np.int64)
    horizon = (number + horizon_events(p)) // rate - ts
    expires = ts + 1 + (d.u_auction * np.maximum(2 * horizon, 1)
                        ).astype(np.int64)
    # BidGenerator.nextBid (nexmark_q5_large_keys.suite_batch's lines)
    newest = epoch * auctions + (auctions - 1)
    lo = np.maximum(newest - in_flight, 0)
    cold = lo + (d.u_auction * (newest - lo + 1 + q5.AUCTION_ID_LEAD)
                 ).astype(np.int64)
    bid_auction = q5.FIRST_AUCTION_ID + np.where(
        d.hot_auction,
        newest // q5.HOT_AUCTION_RATIO * q5.HOT_AUCTION_RATIO, cold)
    zero = np.zeros(n, np.int64)
    return {"event_type": np.where(is_auction, AUCTION, BID),
            "auction_id": np.where(is_auction, auction_id, zero),
            "auction_category": np.where(is_auction, category, zero),
            "auction_expires": np.where(is_auction, expires, zero),
            "bid_auction": np.where(is_auction, zero, bid_auction),
            "bid_price": np.where(is_auction, zero, d.price)}


class EventPool:
    """``pool[i]``: batch ``i`` of the offered stream, made on request
    from the draws of pool batch ``i % pool_batches``: what belongs to
    an EPOCH (ids, the hot auction, the timestamp's millisecond) is
    worked out per epoch (~21,400 a batch of 2^20) and gathered."""

    def __init__(self, seed: int, n: int, p: dict) -> None:
        self.seed, self.n, self.p = int(seed), int(n), p
        self.draws = [large._Draws(seed, j, n, p)
                      for j in range(int(p["pool_batches"]))]
        # what a draw gives whatever the batch: an auction's category
        self.category = [FIRST_CATEGORY_ID + (d.u_bidder * NUM_CATEGORIES
                                              ).astype(np.int64)
                         for d in self.draws]
        both = _offered(p)[2]
        k = np.arange(n, dtype=np.int64)
        self._quot = (k // both).astype(np.int32)
        self._rem = (k % both).astype(np.int32)

    def __len__(self) -> int:
        return POOL_LEN

    def __getitem__(self, i: int) -> Dict[str, np.ndarray]:
        i = int(i)
        if not 0 <= i < POOL_LEN:
            raise IndexError(i)
        p, n = self.p, self.n
        auctions, bids, both = _offered(p)
        persons = int(p["person_proportion"])
        in_flight = int(p["num_in_flight_auctions"])
        rate = int(p["events_per_ms_all"])
        first, s0 = divmod(i * n, both)         # the batch's first epoch
        if first * auctions + auctions - 1 < in_flight:
            # the stream's start, before the window of auctions in
            # flight is full
            return suite_events(self.seed, i, n, p)
        j = i % len(self.draws)
        d = self.draws[j]
        wrap = self._rem >= both - s0
        e_ix = self._quot + wrap                # index into own epochs
        r = self._rem + s0 - both * wrap
        at = np.flatnonzero(r < auctions)       # the batch's auctions
        epochs = first + np.arange(int(e_ix[-1]) + 1, dtype=np.int64)
        newest = epochs * auctions + (auctions - 1)
        hot = (newest // q5.HOT_AUCTION_RATIO * q5.HOT_AUCTION_RATIO
               + q5.FIRST_AUCTION_ID)
        lo = newest - in_flight + q5.FIRST_AUCTION_ID
        bid_auction = np.where(d.hot_auction, hot[e_ix],
                               lo[e_ix] + d.cold_auction)
        bid_auction[at] = 0
        bid_price = d.price.copy()
        bid_price[at] = 0
        # the auctions alone (3 in 49): AuctionGenerator.nextAuction
        a_epoch = epochs[e_ix[at]]
        number = a_epoch * PROPORTION_DENOMINATOR + persons + r[at]
        ts = number // rate
        horizon = (number + horizon_events(p)) // rate - ts
        expires = ts + 1 + (d.u_auction[at] * np.maximum(2 * horizon, 1)
                            ).astype(np.int64)

        def column(fill, values):
            col = np.full(n, fill, np.int64)
            col[at] = values
            return col

        return {"event_type": column(BID, AUCTION),
                "auction_id": column(0, a_epoch * auctions + r[at]
                                     + q5.FIRST_AUCTION_ID),
                "auction_category": column(0, self.category[j][at]),
                "auction_expires": column(0, expires),
                "bid_auction": bid_auction, "bid_price": bid_price}


def make_pool(seed: int, n: int, p: dict) -> EventPool:
    if not device_join():
        raise NotImplementedError(
            "this configuration runs NEXmark Q4 with both sides of its "
            "unbounded join on the device (memory.hbm_state_bytes > 0, the "
            "changelog through the emit ring and the drain into the outer "
            "aggregate); the program in this checkout has no "
            "q4_category_avg or no device join (flink_tpu/ops/"
            "join_device.py): it does not support this configuration")
    return EventPool(seed, n, p)


# -- what the harness asks a configuration's module ------------------------

def warmup_event_ms(p: dict) -> int:
    """Event time a warm-up pass has to span: a few batches (every batch
    runs the one program and emits; there is no fire to reach)."""
    return 350


def zero_counters(p: dict) -> Tuple[str, ...]:
    """Job metrics that the guarantees hold at 0: nothing dropped or
    late, the join not on the host operator (the factory's choice of
    lane: it reads 1 there), no value refused by a 32-bit word, no key
    handed over for want of an early-bid lane."""
    return ("records_dropped_full", "late_records", "join.on_host",
            "join.lane_overflow", "join.pending_overflow")


def keys_per_batch(p: dict, batch: int) -> int:
    """Distinct auctions a batch of ``batch`` offered events names, by
    the generator's formulas: every auction created in it, the window
    in flight it starts with, and the ids its last bids run ahead."""
    auctions, _, both = _offered(p)
    return (batch * auctions // both + int(p["num_in_flight_auctions"])
            + 1 + q5.AUCTION_ID_LEAD)


def step_shapes(p: dict, batch: int, events_per_ms: float) -> dict:
    """What ``join_step_bytes`` needs to know of one batch: from the
    deployment's shapes, not from the program. Every key a batch names
    changes its ``final`` in it but the few ahead of their auction."""
    keys = keys_per_batch(p, batch)
    return {"records": batch, "keys": keys,
            "changed": keys - q5.AUCTION_ID_LEAD,
            "slots": int(p["state_slots"])}


def build(env, source, sink, p: dict) -> None:
    from flink_tpu.nexmark.queries import q4_category_avg

    q4_category_avg(env, source, sink)


# -- the plain reference ---------------------------------------------------

def batch_parts(data, ts):
    """One batch's auctions, its bids SORTED by the auction they name,
    and per key its newest event time in the batch (any record of the
    key): all int64, each with its position in the batch."""
    t = np.asarray(ts, np.int64)
    kind = np.asarray(data["event_type"], np.int64)
    a, b = kind == AUCTION, kind == BID
    key = np.where(a, data["auction_id"], data["bid_auction"])
    order = np.argsort(key, kind="stable")
    order = order[(a | b)[order]]
    key, tk = key[order], t[order]
    first = np.flatnonzero(np.r_[True, key[1:] != key[:-1]])
    bids = order[b[order]]              # the bids, by auction
    at = np.flatnonzero(a)
    get = lambda name, ix: np.asarray(data[name], np.int64)[ix]  # noqa: E731
    return ((get("auction_id", at), get("auction_category", at), t[at],
             get("auction_expires", at), at),
            (get("bid_auction", bids), get("bid_price", bids), t[bids], bids),
            (key[first], np.maximum.reduceat(tk, first) if len(first)
             else tk))


def per_key(auc, price):
    """(keys, their largest price, their bids) of bids sorted by key."""
    first = np.flatnonzero(np.r_[True, auc[1:] != auc[:-1]]) if len(auc) \
        else np.zeros(0, np.int64)
    return (auc[first], np.maximum.reduceat(price, first) if len(first)
            else price, np.diff(np.r_[first, len(auc)]))


class JoinState:
    """What the reference keeps between batches: the auctions by id, the
    running ``final`` by id, the bids whose auction has not come, and
    per category the accumulated (sum, count)."""

    def __init__(self, predicate: bool = True, keep_early: bool = True,
                 dtype=np.int64) -> None:
        # the controls: the predicate ignored, early bids dropped, and
        # ``final`` / ``sum_final`` held in a narrower type
        self.predicate, self.keep_early, self.dtype = (
            predicate, keep_early, dtype)
        self.size = self.batch = 0
        # where in the stream an auction came (the early-bids control)
        self.cat = self.lo = self.hi = self.at = np.zeros(0, np.int64)
        self.final = np.zeros(0, dtype)
        self.has = np.zeros(0, bool)
        self.waiting = tuple(np.zeros(0, np.int64) for _ in range(4))
        self.totals: Dict[int, list] = {}

    def _fit(self, top: int) -> None:
        if top < self.size:
            return
        size = max(2 * self.size, top + 1, 1 << 16)
        grow = lambda a, fill: np.concatenate(  # noqa: E731
            [a, np.full(size - self.size, fill, a.dtype)])
        self.cat, self.lo, self.hi, self.at = (grow(a, NONE) for a in (
            self.cat, self.lo, self.hi, self.at))
        self.final, self.has = grow(self.final, 0), grow(self.has, False)
        self.size = size

    def fold(self, auctions, bids, newest):
        """One batch -> its rows ``[(category, avg, sum, count, last,
        matched bids)]``."""
        a_id, a_cat, a_t, a_exp, a_at = auctions
        here = self.batch << 32
        self.batch += 1
        bids = bids[:3] + (bids[3] + here,)
        self._fit(int(max(a_id.max(initial=0), bids[0].max(initial=0),
                          self.waiting[0].max(initial=0))))
        self.cat[a_id], self.lo[a_id], self.hi[a_id] = a_cat, a_t, a_exp
        self.at[a_id] = a_at + here
        # the bids that waited (few: sorted here), then the batch's
        # (sorted by the threads): each held to the predicate
        order = np.argsort(self.waiting[0], kind="stable")
        groups, still = [], []
        for b_auc, b_price, b_t, b_at in (
                tuple(x[order] for x in self.waiting), bids):
            known = self.cat[b_auc] != NONE
            ok = known.copy()
            if self.predicate:
                ok &= (self.lo[b_auc] <= b_t) & (b_t <= self.hi[b_auc])
            if self.keep_early:
                still.append(tuple(x[~known] for x in (
                    b_auc, b_price, b_t, b_at)))
            else:   # an operator that keeps the auction side alone
                ok &= self.at[b_auc] < b_at
            groups.append(per_key(b_auc[ok], b_price[ok]))
        if self.keep_early:
            self.waiting = tuple(np.concatenate(x) for x in zip(*still))
        both = np.concatenate([g[0] for g in groups])
        keys, inv = np.unique(both, return_inverse=True)
        best = np.full(len(keys), NONE)
        np.maximum.at(best, inv, np.concatenate([g[1] for g in groups]))
        matched = np.zeros(len(keys), np.int64)
        np.add.at(matched, inv, np.concatenate([g[2] for g in groups]))
        had, old = self.has[keys], self.final[keys].copy()
        best = best.astype(self.dtype)
        new = np.where(had, np.maximum(old, best), best)
        ch = ~had | (new != old)
        keys, had, old, new, matched = (
            x[ch] for x in (keys, had, old, new, matched))
        self.final[keys], self.has[keys] = new, True
        cats = self.cat[keys]
        last = newest[1][np.searchsorted(newest[0], keys)]
        rows = []
        for c in np.unique(cats).tolist():
            m = cats == c
            tot = self.totals.setdefault(c, [self.dtype(0), 0])
            tot[0] = tot[0] + (new[m].sum(dtype=self.dtype)
                               - old[m][had[m]].sum(dtype=self.dtype))
            tot[1] += int((~had[m]).sum())
            s = int(tot[0])
            rows.append((c, s // tot[1], s, tot[1], int(last[m].max()),
                         int(matched[m].sum())))
        return rows


def category_rows(stream, **control):
    """The rows of a stream of ``(data, ts)`` batches, in stream order:
    ``(category, avg_final, sum_final, auctions, last_event_ms)`` and
    the matched bids each stands for."""
    state = JoinState(**control)
    rows = []
    for parts in blocks_in_order(stream, batch_parts):
        rows.extend(state.fold(*parts))
    return rows


def collect(sink_batches, p: dict):
    """The committed rows as tuples of ``ROW_FIELDS``."""
    out = []
    for b in sink_batches:
        out.extend(zip(*(np.asarray(b[f], np.int64).tolist()
                         for f in ROW_FIELDS)))
    return out


def compare(exp, got) -> dict:
    """Every committed row against the reference's, whole rows as
    tuples: (category, auctions, last_event_ms) names a row, no two
    share it (a test proves it on the pool), so a row that differs in
    another column reads as one missing and one not in the reference."""
    e_rows = {r[:5]: r[5] for r in exp}
    g_set = set(got)
    missing = set(e_rows) - g_set
    wrong = g_set - set(e_rows)
    return {
        "rows_expected": len(e_rows),
        "rows_got": len(got),
        "rows_duplicated": len(got) - len(g_set),
        "rows_missing": len(missing),
        "rows_not_in_reference": len(wrong),
        # the matching bids a missing row stands for count as failed
        "events_without_result": int(sum(e_rows[r] for r in missing)),
        "first_differences": (
            [["missing", *r] for r in sorted(missing)[:3]]
            + [["not_in_reference", *r] for r in sorted(wrong)[:3]]),
    }


def check(stream, max_ts: int, sink_batches, p: dict) -> dict:
    """Every committed row against the reference's; all limits are 0."""
    return compare(category_rows(stream), collect(sink_batches, p))
