"""NEXmark query 5 on the generator's own auction ids, on a feed that is
NOT in order: the job of ``nexmark_q5_large_keys`` (imported: the same
``q5_hot_items``), its generator formulas and draws, and the delay model
of the generator's configuration (``probDelayedEvent``,
``occasionalDelaySec``): a drawn tenth of the bids is handed over up to
3 s after it happened, with the timestamp it happened at. 3 s lies
inside the DDL's 4 s watermark, so nothing is late, and the committed
rows are those the same bids give in timestamp order.

WHICH bid each row of each batch is, ``traffic_kinds/
constant_rate_delayed.py`` ``Arrivals`` says (sorted by due time, cut by
count; that file has the construction). What a row HOLDS is the bid of
its own generator index ``k``: ``nexmark_q5_large_keys.suite_batch``'s
formulas at the true epoch ``k // 46``, over the draws of
``k mod (pool_batches x n)``. So a held-back bid names the auction and
the bidder of the epoch it happened in, up to 3 s (1,800,000 auction
ids) behind the batch's newest: a key that is not new, far from the
batch's other keys. ``make_pool`` is given no traffic parameters, so the
three delay parameters and the rate stand in the configuration's
``params`` too, and a test holds them equal to the mix's.

A batch costs a few 32-bit vector passes: past the ramp batch ``i`` is
``i * n`` plus a fixed vector of offsets, so every row's draws are a
fixed gather of the pool's (made once per ``i mod pool_batches`` in
set-up), and its epoch is the batch's lowest plus a small number: the
ids are worked out relative to that epoch in int32 and widened at the
end. The ramp's ~30 batches gather their draws and divide for their
epochs when asked; the stream's first ~46,000 bids, from before the
windows of auctions in flight and active people are full, take the
formulas directly (``bids_at``).

The reference is numpy only, takes nothing from the program and does
not look at the order rows arrive in: ``nexmark_q5_large_keys``'s
per-pane counts and hot items, with each batch's old rows counted apart
from its recent ones (the dense block a batch is counted in is as wide
as its id range: 1.8 M ids x 3 panes with the held-back rows in,
~140,000 x 2 without).
"""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

from benchmark.configs import nexmark_q5 as q5
from benchmark.configs import nexmark_q5_large_keys as large
from benchmark.traffic_kinds.constant_rate_delayed import Arrivals

SCHEMA = large.SCHEMA
WINDOW_END_FIELD = large.WINDOW_END_FIELD
build = large.build
fire_delay_ms = large.fire_delay_ms
collect = large.collect
warmup_event_ms = large.warmup_event_ms
step_shapes = large.step_shapes

# a row stamped this far behind its batch's newest is counted apart by
# the reference (two batches' fill at the suite's rate; any value gives
# the same counts)
RECENT_MS = 256

DRAWS = ("u_auction", "hot_auction", "u_bidder", "hot_bidder", "price",
         "channel", "url")


def arrivals(n: int, p: dict) -> Arrivals:
    return Arrivals(n, p["events_per_ms"], p["prob_delayed"],
                    p["occasional_delay_ms"], p["delay_seed"])


def bids_at(k: np.ndarray, draws: Dict[str, np.ndarray], p: dict
            ) -> Dict[str, np.ndarray]:
    """The bids of generator indices ``k`` by the suite's formulas,
    directly (``nexmark_q5_large_keys.suite_batch``'s lines at any
    indices); ``draws``: the pool's, bid ``j``'s at ``j``."""
    persons, auctions, bids = large._proportions(p)
    in_flight = int(p["num_in_flight_auctions"])
    active = int(p["num_active_people"])
    d = {f: draws[f][k % len(draws["price"])] for f in DRAWS}
    epoch = k // bids
    newest = epoch * auctions + (auctions - 1)
    lo = np.maximum(newest - in_flight, 0)
    cold = lo + (d["u_auction"] * (newest - lo + 1 + q5.AUCTION_ID_LEAD)
                 ).astype(np.int64)
    auction = q5.FIRST_AUCTION_ID + np.where(
        d["hot_auction"],
        newest // q5.HOT_AUCTION_RATIO * q5.HOT_AUCTION_RATIO, cold)
    people = epoch * persons + persons
    act = np.minimum(people, active)
    cold = people - act + (d["u_bidder"] * (act + q5.PERSON_ID_LEAD)
                           ).astype(np.int64)
    bidder = q5.FIRST_PERSON_ID + np.where(
        d["hot_bidder"],
        (people - 1) // q5.HOT_BIDDER_RATIO * q5.HOT_BIDDER_RATIO + 1, cold)
    return {"auction": auction, "bidder": bidder, "price": d["price"],
            "channel": d["channel"], "url": d["url"]}


class _Rows:
    """The draws of a batch's rows: a gather of the pool's at ``at``."""

    def __init__(self, at: np.ndarray, draws: Dict[str, np.ndarray]) -> None:
        (self.hot_auction, self.hot_bidder, self.price, self.channel,
         self.url, self.cold_auction, self.cold_bidder) = (
            draws[f][at] for f in (
                "hot_auction", "hot_bidder", "price", "channel", "url",
                "cold_auction", "cold_bidder"))


class DelayedPool:
    """``pool[i]``: the bids batch ``i`` of the offered stream carries."""

    def __init__(self, seed: int, n: int, p: dict) -> None:
        self.n, self.p = int(n), p
        self.arrivals = arrivals(n, p)
        made = [large._Draws(seed, j, n, p)
                for j in range(int(p["pool_batches"]))]
        self.draws = {f: np.concatenate([getattr(d, f) for d in made])
                      for f in DRAWS}
        # the cold offsets once every auction / person window is full,
        # from the window's newest id, in 32 bits
        in_flight = int(p["num_in_flight_auctions"])
        active = int(p["num_active_people"])
        self.draws["cold_auction"] = np.concatenate(
            [d.cold_auction for d in made]).astype(np.int32) - in_flight
        self.draws["cold_bidder"] = np.concatenate(
            [d.cold_bidder for d in made]).astype(np.int32) - active
        del made
        persons, auctions, bids = large._proportions(p)
        # the first epoch at which both windows are full
        self._full_from = max(-(-(in_flight - auctions + 1) // auctions),
                              -(-(active - persons) // persons))
        # past the ramp batch i is i * n + off: its rows' draws are a
        # fixed gather for each i mod pool_batches, and its epochs
        # (start + off) // bids = start // bids + off // bids
        #                         + (start % bids + off % bids >= bids)
        off = self.arrivals.offsets
        self._steady = [_Rows((j * self.n + off) % self._span, self.draws)
                        for j in range(int(p["pool_batches"]))]
        quot = off // bids
        self._quot_lo = int(quot.min())
        self._quot = (quot - self._quot_lo).astype(np.int32)
        self._rem = (off % bids).astype(np.int32)

    @property
    def _span(self) -> int:
        return len(self.draws["price"])

    def __len__(self) -> int:
        return large.POOL_LEN

    def __getitem__(self, i: int) -> Dict[str, np.ndarray]:
        i = int(i)
        if not 0 <= i < large.POOL_LEN:
            raise IndexError(i)
        p, start = self.p, i * self.n
        persons, auctions, bids = large._proportions(p)
        # ``base``: the batch's lowest epoch; a row's is base + de
        if i >= self.arrivals.steady_from:
            k = None
            d = self._steady[i % len(self._steady)]
            base = start // bids + self._quot_lo
            de = self._quot + (self._rem >= bids - start % bids)
        else:
            k = self.arrivals.indices(i)
            d = _Rows(k % self._span, self.draws)
            epoch = k // bids
            base = int(epoch.min())
            de = (epoch - base).astype(np.int32)
        # relative to base's newest auction, in int32: a row's newest is
        # auctions * de further, the hot auction that rounded down to a
        # hundred, a cold one the row's offset from it
        newest = base * auctions + auctions - 1
        x = de * np.int32(auctions)
        hot = x - (x + np.int32(newest % q5.HOT_AUCTION_RATIO)
                   ) % np.int32(q5.HOT_AUCTION_RATIO)
        x += d.cold_auction
        auction = np.where(d.hot_auction, hot, x).astype(np.int64)
        auction += newest + q5.FIRST_AUCTION_ID
        # and the bidder, relative to base's people
        people = base * persons + persons
        y = de * np.int32(persons)
        hot = y - (y + np.int32((people - 1) % q5.HOT_BIDDER_RATIO)
                   ) % np.int32(q5.HOT_BIDDER_RATIO)
        y += d.cold_bidder
        bidder = np.where(d.hot_bidder, hot, y).astype(np.int64)
        bidder += people + q5.FIRST_PERSON_ID
        if base < self._full_from:
            # the stream's first ~46,000 bids, wherever they turn up
            rows = np.flatnonzero(de < self._full_from - base)
            if k is None:
                k = start + self.arrivals.offsets
            early = bids_at(k[rows], self.draws, p)
            auction[rows], bidder[rows] = early["auction"], early["bidder"]
        return {"auction": auction, "bidder": bidder, "price": d.price,
                "channel": d.channel, "url": d.url}


def make_pool(seed: int, n: int, p: dict) -> DelayedPool:
    return DelayedPool(seed, n, p)


def zero_counters(p: dict) -> Tuple[str, ...]:
    """``nexmark_q5_large_keys``'s, and no window end fired twice: every
    held-back bid reaches its pane before a window over it has fired."""
    return large.zero_counters(p) + ("refire_ends",)


# -- the plain reference ---------------------------------------------------

def old_rows_apart(stream):
    """Every batch as its rows stamped within ``RECENT_MS`` of its newest
    and its older rows: the same events, each part over an id range of
    its own."""
    for data, ts in stream:
        ts = np.asarray(ts, np.int64)
        recent = ts >= int(ts.max()) - RECENT_MS
        if recent.all():
            yield data, ts
            continue
        for part in (~recent, recent):
            yield ({"auction": np.asarray(data["auction"])[part]},
                   ts[part])


def check(stream, max_ts: int, sink_batches, p: dict) -> dict:
    """Every committed row against the reference's; all limits are 0.
    Counting is free of arrival order, so this is the answer
    ``nexmark_q5_large_keys.check`` gives on the same bids in timestamp
    order (benchmark/tests/test_q5_delayed.py holds the two together)."""
    return large.check(old_rows_apart(stream), max_ts, sink_batches, p)
