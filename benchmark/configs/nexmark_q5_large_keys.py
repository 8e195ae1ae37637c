"""NEXmark query 5 on the auction ids the suite's generator makes: the
job of ``nexmark_q5`` (imported: the same ``q5_hot_items``), its records
WITHOUT the fold onto ``auction_id_wrap`` ids, and a plain reference
that can hold them.

What the stream is (``nexmark_q5.py`` has the generator's formulas; the
constants are imported from it): bid ``k`` belongs to epoch ``k // 46``,
the newest auction then is ``epoch * 3 + 2``, so a batch of 2^20 bids
spans 22,795 epochs and names ~68,400 auctions that no earlier batch
has named; half its bids go to the hot auction of the moment
(``newest // 100 * 100``), half uniformly over the ~111 in flight. A key
receives all of its bids within ~1,700 events and never again: keys come
and go, and the job must let them go.

``make_pool`` returns a sequence whose item ``i`` is made when asked
for, because no run can hold its stream: the random DRAWS of bid ``k``
(hot or cold, the cold offset, bidder, price, channel) are those of bid
``k mod (pool_batches x n)``, made once in set-up by the draws of
``nexmark_q5.make_pool`` in its order; the auction and bidder IDS are
worked out from the true epoch of bid ``i * n + j``. So batch ``i``
names exactly the ids the suite's formula gives with those draws
(``suite_batch`` below is that formula, written out directly; the tests
hold the two together), the same whenever and however often it is asked
for.

The reference is numpy only and takes nothing from the program. It
cannot be dense over (panes, ids) as ``nexmark_q5``'s is: a pane is
counted over the id range that pane names (a batch's ids lie within
~68,500 of each other, a 2 s pane's within ~1.2 M), and a window is the
sum of its 5 panes over the union of their ranges.
"""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

from benchmark.configs import nexmark_q5 as q5
from benchmark.reference_util import blocks_in_order

SCHEMA = q5.SCHEMA
WINDOW_END_FIELD = q5.WINDOW_END_FIELD
build = q5.build
fire_delay_ms = q5.fire_delay_ms
collect = q5.collect

# no run hands over this many batches: the harness reads
# ``pool[i % len(pool)]``, which is then ``pool[i]``
POOL_LEN = 1 << 40


def _proportions(p: dict) -> Tuple[int, int, int]:
    return tuple(int(p[k]) for k in (
        "person_proportion", "auction_proportion", "bid_proportion"))


class _Draws:
    """The random draws of one pool batch, in the order of
    ``nexmark_q5.make_pool``'s (the accepted configuration's bid k and
    this one's draw alike)."""

    def __init__(self, seed: int, j: int, n: int, p: dict) -> None:
        rng = np.random.default_rng([int(seed), j])
        self.u_auction = rng.random(n)
        self.hot_auction = rng.integers(0, int(p["hot_auction_ratio"]), n) > 0
        self.u_bidder = rng.random(n)
        self.hot_bidder = rng.integers(0, int(p["hot_bidders_ratio"]), n) > 0
        self.price = np.rint(10.0 ** (rng.random(n) * 6.0) * 100.0
                             ).astype(np.int64)
        self.channel = np.where(
            rng.integers(0, q5.HOT_CHANNELS_RATIO, n) > 0,
            rng.integers(0, q5.HOT_CHANNELS, n),
            q5.HOT_CHANNELS + rng.integers(0, q5.CHANNELS_NUMBER, n)
        ).astype(np.int64)
        self.url = self.channel.copy()
        # the cold offsets once every auction / person window is full
        # (newest - lo = in_flight, act = active): what a bid adds to
        # its epoch's ``lo`` / ``people - act``
        self.cold_auction = (self.u_auction * (
            int(p["num_in_flight_auctions"]) + 1 + q5.AUCTION_ID_LEAD)
        ).astype(np.int64)
        self.cold_bidder = (self.u_bidder * (
            int(p["num_active_people"]) + q5.PERSON_ID_LEAD)
        ).astype(np.int64)


def suite_batch(seed: int, i: int, n: int, p: dict) -> Dict[str, np.ndarray]:
    """Bids ``[i * n, (i + 1) * n)`` by the suite's formulas, directly:
    ``nexmark_q5.make_pool``'s lines with the true event ids and without
    the ``% wrap``, over the draws of pool batch ``i % pool_batches``.
    What ``LazyPool`` must give; slow (64-bit division per bid), so the
    window never calls it."""
    persons, auctions, bids = _proportions(p)
    in_flight = int(p["num_in_flight_auctions"])
    active = int(p["num_active_people"])
    d = _Draws(seed, i % int(p["pool_batches"]), n, p)
    epoch = (i * n + np.arange(n, dtype=np.int64)) // bids
    newest = epoch * auctions + (auctions - 1)
    lo = np.maximum(newest - in_flight, 0)
    cold = lo + (d.u_auction * (newest - lo + 1 + q5.AUCTION_ID_LEAD)
                 ).astype(np.int64)
    auction = q5.FIRST_AUCTION_ID + np.where(
        d.hot_auction,
        newest // q5.HOT_AUCTION_RATIO * q5.HOT_AUCTION_RATIO, cold)

    people = epoch * persons + persons
    act = np.minimum(people, active)
    cold = people - act + (d.u_bidder * (act + q5.PERSON_ID_LEAD)
                           ).astype(np.int64)
    bidder = q5.FIRST_PERSON_ID + np.where(
        d.hot_bidder,
        (people - 1) // q5.HOT_BIDDER_RATIO * q5.HOT_BIDDER_RATIO + 1, cold)
    return {"auction": auction, "bidder": bidder, "price": d.price,
            "channel": d.channel, "url": d.url}


class LazyPool:
    """``pool[i]``: batch ``i`` of the suite's bid stream, made on
    request from the draws of pool batch ``i % pool_batches``. Ids are
    worked out per EPOCH (22,795 of them a batch, not 2^20 bids) and
    gathered: a few vector passes a batch on the thread that asks."""

    def __init__(self, seed: int, n: int, p: dict) -> None:
        self.seed, self.n, self.p = int(seed), int(n), p
        self.draws = [_Draws(seed, j, n, p)
                      for j in range(int(p["pool_batches"]))]
        bids = _proportions(p)[2]
        k = np.arange(n, dtype=np.int64)
        # (start + k) // bids = start // bids + (start % bids + k) // bids
        self._quot = (k // bids).astype(np.int32)
        self._rem = (k % bids).astype(np.int32)

    def __len__(self) -> int:
        return POOL_LEN

    def __getitem__(self, i: int) -> Dict[str, np.ndarray]:
        i = int(i)
        if not 0 <= i < POOL_LEN:
            raise IndexError(i)
        p, n = self.p, self.n
        persons, auctions, bids = _proportions(p)
        in_flight = int(p["num_in_flight_auctions"])
        active = int(p["num_active_people"])
        first = i * n // bids                       # the batch's first epoch
        if first * auctions + auctions - 1 < in_flight \
                or first * persons + persons < active:
            # the stream's first ~46,000 bids, before the windows of
            # auctions in flight and of active people are full
            return suite_batch(self.seed, i, n, p)
        d = self.draws[i % len(self.draws)]
        # each bid's epoch, as an index into the batch's own epochs
        e_ix = self._quot + (self._rem >= bids - i * n % bids)
        epochs = first + np.arange(int(e_ix[-1]) + 1, dtype=np.int64)
        newest = epochs * auctions + (auctions - 1)
        hot_a = newest // q5.HOT_AUCTION_RATIO * q5.HOT_AUCTION_RATIO
        lo_a = newest - in_flight
        auction = np.where(d.hot_auction, hot_a[e_ix],
                           lo_a[e_ix] + d.cold_auction)
        auction += q5.FIRST_AUCTION_ID
        people = epochs * persons + persons
        hot_b = (people - 1) // q5.HOT_BIDDER_RATIO * q5.HOT_BIDDER_RATIO + 1
        lo_b = people - active
        bidder = np.where(d.hot_bidder, hot_b[e_ix],
                          lo_b[e_ix] + d.cold_bidder)
        bidder += q5.FIRST_PERSON_ID
        return {"auction": auction, "bidder": bidder, "price": d.price,
                "channel": d.channel, "url": d.url}


def make_pool(seed: int, n: int, p: dict) -> LazyPool:
    return LazyPool(seed, n, p)


# -- what the harness asks a configuration's module ------------------------

def warmup_event_ms(p: dict) -> int:
    """Event time a warm-up pass has to span: until the first pane has
    been purged (window + watermark delay + its own slide), its keys
    released and, two slides on, their slots handed out again; then the
    end-of-input flush fires the rest. The measured window then builds
    no program."""
    slide = int(p["slide_ms"])
    return (int(p["window_ms"]) + fire_delay_ms(p) + slide
            + 2 * slide + slide // 4)


def zero_counters(p: dict) -> Tuple[str, ...]:
    """Job metrics that the guarantees hold at 0: nothing dropped or
    late, no overflow between chips, and no slot given back to the
    allocator while a fire dispatched before its release was still
    undrained (the program's own tripwire on its reuse rule)."""
    return ("records_dropped_full", "late_records", "exchange_overflow",
            "state.slots_returned_early")


def step_shapes(p: dict, batch: int, events_per_ms: float) -> dict:
    """What ``large_keys_step_bytes`` needs to know of one batch."""
    return {"records": batch}


# -- the plain reference ---------------------------------------------------

class PaneCounts:
    """Bid counts of one pane over the id range it names:
    ``counts[a - base]`` for ids ``[base, base + len(counts))``, grown
    as batches bring ids outside it."""

    def __init__(self) -> None:
        self.base = 0
        self.counts = np.zeros(0, np.int64)

    def add(self, lo: int, row: np.ndarray) -> None:
        hi = lo + len(row)
        if not len(self.counts):
            self.base = lo
            self.counts = np.zeros(max(len(row), 1 << 16), np.int64)
        end = self.base + len(self.counts)
        if lo < self.base or hi > end:
            new_base = min(lo, self.base)
            # ids ascend: leave room ahead, so a pane's ~18 batches cost
            # a few copies and not one each
            new_end = max(hi + (hi - new_base) // 2, end)
            grown = np.zeros(new_end - new_base, np.int64)
            grown[self.base - new_base:end - new_base] = self.counts
            self.base, self.counts = new_base, grown
        self.counts[lo - self.base:hi - self.base] += row


def pane_counts(stream, slide: int) -> Dict[int, PaneCounts]:
    """``{pane: PaneCounts}`` of a stream of ``(data, ts)`` batches;
    pane = ts // slide. A batch is counted over its own id range."""
    def one(data, ts):
        b = np.asarray(ts, np.int64) // slide
        a = np.asarray(data["auction"], np.int64)
        b0, b1 = int(b.min()), int(b.max())
        lo, hi = int(a.min()), int(a.max())
        width = hi - lo + 1
        b -= b0
        b *= width
        b += a
        b -= lo
        return b0, lo, np.bincount(
            b, minlength=(b1 - b0 + 1) * width).reshape(-1, width)

    panes: Dict[int, PaneCounts] = {}
    for b0, lo, block in blocks_in_order(stream, one):
        for k, row in enumerate(block):
            panes.setdefault(b0 + k, PaneCounts()).add(lo, row)
    return panes


def hot_items(panes: Dict[int, PaneCounts], n_panes: int, p: dict):
    """Q5's answer from per-pane counts: per sliding window the
    auction(s) with the most bids, ties kept. Sorted (window_end,
    auction, bid_count) columns. The window ending at ``e * slide`` is
    panes ``[e - ppw, e)``."""
    slide = int(p["slide_ms"])
    ppw = int(p["window_ms"]) // slide
    we, au, ct = [], [], []
    for e in range(1, n_panes + ppw):
        live = [panes[q] for q in range(max(e - ppw, 0), min(e, n_panes))
                if q in panes]
        if not live:
            continue
        base = min(pc.base for pc in live)
        win = np.zeros(max(pc.base + len(pc.counts) for pc in live) - base,
                       np.int64)
        for pc in live:
            win[pc.base - base:pc.base - base + len(pc.counts)] += pc.counts
        best = int(win.max())
        if best <= 0:
            continue
        hit = np.nonzero(win == best)[0]
        we.append(np.full(len(hit), e * slide, np.int64))
        au.append(hit.astype(np.int64) + base)
        ct.append(np.full(len(hit), best, np.int64))
    if not we:
        z = np.zeros(0, np.int64)
        return z, z, z
    return np.concatenate(we), np.concatenate(au), np.concatenate(ct)


def check(stream, max_ts: int, sink_batches, p: dict) -> dict:
    """Every committed row against the reference's; all limits are 0.
    The answer contract is ``nexmark_q5.check``'s."""
    slide = int(p["slide_ms"])
    n_panes = max_ts // slide + 1
    exp = hot_items(pane_counts(stream, slide), n_panes, p)
    got = collect(sink_batches, p)
    e_rows = set(zip(*(c.tolist() for c in exp)))
    g_rows = list(zip(*(c.tolist() for c in got)))
    g_set = set(g_rows)
    missing = e_rows - g_set
    wrong = g_set - e_rows
    return {
        "rows_expected": len(e_rows),
        "rows_got": len(g_rows),
        "rows_duplicated": len(g_rows) - len(g_set),
        "rows_missing": len(missing),
        "rows_not_in_reference": len(wrong),
        # every bid of a window whose answer is missing counts as failed
        "events_without_result": int(sum(r[2] for r in missing)),
        "first_differences": sorted(missing ^ wrong)[:6],
    }
