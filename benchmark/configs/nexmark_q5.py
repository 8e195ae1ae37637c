"""NEXmark query 5 "hot items": the job, its records and its plain
reference. Used by every configuration whose JSON names this module.

The records follow the NEXmark suite's generator (github.com/nexmark/
nexmark, ``nexmark-flink`` ``generator/model/BidGenerator.java`` and
``GeneratorConfig.java``; Apache Beam's ``sdks/java/testing/nexmark`` has
the same code), written again here in numpy:

- of every ``person + auction + bid`` proportion (1 + 3 + 46 = 50) event
  ids, the last 46 are bids; the job is handed the ``bid`` view, so bid
  ``k`` of the stream is event ``k // 46 * 50 + 4 + k % 46``;
- the newest auction at that event is ``epoch * 3 + 2`` (base 0); a bid
  goes to the HOT auction ``newest // 100 * 100`` unless
  ``nextInt(hotAuctionRatio) == 0``, and then uniformly to one of the
  ``numInFlightAuctions`` before the newest, itself, or one of 10 "lead"
  ids after it;
- the bidder likewise (``hotBiddersRatio``, ``numActivePeople``, lead 10),
  the price is ``round(10 ** (U * 6) * 100)`` cents, the channel one of 4
  hot ones with probability 1/2 or else one of 10,000;
- ids start at 1000 (``FIRST_AUCTION_ID``, ``FIRST_PERSON_ID``).

What is NOT the suite's is in the configuration's ``reduced`` and
``assumed``: auction ids are taken modulo ``auction_id_wrap`` (the
program's key directory is insert-only), the ``url`` and ``channel``
strings are their dictionary ids and ``extra`` is not carried (the
program's records are numeric columns), and the content cycles through
``pool_batches`` batches.

The reference is numpy only and takes nothing from the program: it is
handed the stream the window offered, regenerated from the seed.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Tuple

import numpy as np

from benchmark.reference_util import counts_by_bucket

# the suite's Bid(auction, bidder, price, channel, url, dateTime, extra):
# dateTime is the batch's timestamp vector
SCHEMA = {"auction": "int64", "bidder": "int64", "price": "int64",
          "channel": "int64", "url": "int64"}
WINDOW_END_FIELD = "window_end"

# constants of the suite's generator (not configuration)
HOT_AUCTION_RATIO = 100     # AuctionGenerator.HOT_AUCTION_RATIO
HOT_BIDDER_RATIO = 100      # PersonGenerator.HOT_BIDDER_RATIO
AUCTION_ID_LEAD = 10
PERSON_ID_LEAD = 10
FIRST_AUCTION_ID = 1000
FIRST_PERSON_ID = 1000
HOT_CHANNELS = 4
HOT_CHANNELS_RATIO = 2
CHANNELS_NUMBER = 10_000


def key_domain(p: dict) -> int:
    """Auction ids lie in ``[FIRST_AUCTION_ID, key_domain)``."""
    return FIRST_AUCTION_ID + int(p["auction_id_wrap"])


def make_pool(seed: int, n: int, p: dict) -> List[Dict[str, np.ndarray]]:
    """``pool_batches`` batches of ``n`` bids; batch ``j`` holds bids
    ``[j * n, (j + 1) * n)`` of the suite's stream."""
    persons, auctions, bids = (int(p[k]) for k in (
        "person_proportion", "auction_proportion", "bid_proportion"))
    in_flight = int(p["num_in_flight_auctions"])
    active = int(p["num_active_people"])
    wrap = int(p["auction_id_wrap"])
    pool = []
    for j in range(int(p["pool_batches"])):
        rng = np.random.default_rng([int(seed), j])
        epoch = (j * n + np.arange(n, dtype=np.int64)) // bids
        newest = epoch * auctions + (auctions - 1)   # lastBase0AuctionId
        lo = np.maximum(newest - in_flight, 0)
        cold = lo + (rng.random(n) * (newest - lo + 1 + AUCTION_ID_LEAD)
                     ).astype(np.int64)
        hot = rng.integers(0, int(p["hot_auction_ratio"]), n) > 0
        auction = np.where(
            hot, newest // HOT_AUCTION_RATIO * HOT_AUCTION_RATIO, cold)
        auction = FIRST_AUCTION_ID + auction % wrap

        people = epoch * persons + persons           # lastBase0PersonId + 1
        act = np.minimum(people, active)
        cold = people - act + (rng.random(n) * (act + PERSON_ID_LEAD)
                               ).astype(np.int64)
        hot = rng.integers(0, int(p["hot_bidders_ratio"]), n) > 0
        bidder = FIRST_PERSON_ID + np.where(
            hot, (people - 1) // HOT_BIDDER_RATIO * HOT_BIDDER_RATIO + 1,
            cold)

        price = np.rint(10.0 ** (rng.random(n) * 6.0) * 100.0
                        ).astype(np.int64)
        channel = np.where(
            rng.integers(0, HOT_CHANNELS_RATIO, n) > 0,
            rng.integers(0, HOT_CHANNELS, n),
            HOT_CHANNELS + rng.integers(0, CHANNELS_NUMBER, n)
        ).astype(np.int64)
        pool.append({"auction": auction, "bidder": bidder, "price": price,
                     "channel": channel, "url": channel.copy()})
    return pool


# -- what the harness asks a configuration's module ------------------------

def fire_delay_ms(p: dict) -> int:
    """A window ending at ``W`` fires once an event stamped ``W`` plus
    this has been seen (the watermark strategy's bounded lateness)."""
    return int(p["out_of_orderness_ms"])


def warmup_event_ms(p: dict) -> int:
    """Event time a warm-up pass has to span: two fires on the
    watermark, then the end-of-input flush fires the rest."""
    return fire_delay_ms(p) + 2 * int(p["slide_ms"]) + int(p["slide_ms"]) // 4


def zero_counters(p: dict) -> Tuple[str, ...]:
    """Job metrics that the guarantees hold at 0: nothing dropped or
    late, no overflow between chips, no plane but the host-fed step."""
    return ("records_dropped_full", "late_records", "exchange_overflow",
            "device_chain_attached", "device_chain_batches",
            "device_chain_fallback_batches")


def step_shapes(p: dict, batch: int, events_per_ms: float) -> dict:
    """What ``step_bytes.step_bytes`` needs to know of one device step."""
    return {"records": batch, "keys": int(p["auction_id_wrap"]),
            "panes_per_batch":
                int(batch / events_per_ms // int(p["slide_ms"])) + 2}


def build(env, source, sink, p: dict) -> None:
    from flink_tpu.nexmark.queries import q5_hot_items

    q5_hot_items(env, source, sink, window_ms=int(p["window_ms"]),
                 slide_ms=int(p["slide_ms"]),
                 out_of_orderness_ms=int(p["out_of_orderness_ms"]))


def pane_counts(stream: Iterable[Tuple[Dict[str, np.ndarray], np.ndarray]],
                n_panes: int, p: dict) -> np.ndarray:
    """(n_panes, key_domain) bid counts; pane = ts // slide."""
    return counts_by_bucket(stream, int(p["slide_ms"]), "auction",
                            key_domain(p), n_panes)


def hot_items(counts: np.ndarray, p: dict):
    """Q5's answer from per-pane counts: per sliding window the
    auction(s) with the most bids, ties kept. Sorted (window_end,
    auction, bid_count) columns. The window ending at ``e * slide`` is
    panes ``[e - ppw, e)``."""
    slide = int(p["slide_ms"])
    ppw = int(p["window_ms"]) // slide
    n_panes = counts.shape[0]
    we, au, ct = [], [], []
    for e in range(1, n_panes + ppw):
        win = counts[max(e - ppw, 0):min(e, n_panes)].sum(
            axis=0, dtype=np.int64)
        best = int(win.max())
        if best <= 0:
            continue
        hit = np.nonzero(win == best)[0]
        we.append(np.full(len(hit), e * slide, np.int64))
        au.append(hit.astype(np.int64))
        ct.append(np.full(len(hit), best, np.int64))
    if not we:
        z = np.zeros(0, np.int64)
        return z, z, z
    return np.concatenate(we), np.concatenate(au), np.concatenate(ct)


def collect(sink_batches, p: dict):
    """The committed rows as sorted (window_end, auction, bid_count)."""
    cols = []
    for f in ("window_end", "auction", "bid_count"):
        cols.append(np.concatenate(
            [np.asarray(b[f], np.int64) for b in sink_batches])
            if sink_batches else np.zeros(0, np.int64))
    order = np.lexsort((cols[2], cols[1], cols[0]))
    return tuple(c[order] for c in cols)


def check(stream, max_ts: int, sink_batches, p: dict) -> dict:
    """Every committed row against the reference's; all limits are 0."""
    n_panes = max_ts // int(p["slide_ms"]) + 1
    exp = hot_items(pane_counts(stream, n_panes, p), p)
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
