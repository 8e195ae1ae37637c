"""NEXMark event generators — the benchmark workload source.

ref: the Nexmark benchmark suite the reference is measured against
(BASELINE.json configs 1-3; upstream queries live in the external
nexmark/nexmark repo — semantics validated against the published query
definitions: Q5 hot items, Q7 highest bid, Q8 monitor new users).

Event model (numeric-only — strings are dictionary ids, SURVEY §8.4
item 7): PERSON(id, state_id), AUCTION(id, seller, category, reserve),
BID(auction, bidder, price). Proportions follow the classic NEXMark
1 person : 3 auctions : 46 bids mix. Generation is vectorized numpy and
deterministic in (split, batch_index) — the replayable-source contract
checkpoint/resume depends on.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import numpy as np

from flink_tpu.api.sources import GeneratorSource

PERSON_PROPORTION = 1
AUCTION_PROPORTION = 3
BID_PROPORTION = 46
TOTAL_PROPORTION = PERSON_PROPORTION + AUCTION_PROPORTION + BID_PROPORTION

# hot-key skew knobs (ref: nexmark generator config hotAuctionRatio etc.)
HOT_AUCTION_RATIO = 100
HOT_BIDDER_RATIO = 100
HOT_SELLER_RATIO = 100


@dataclasses.dataclass(frozen=True)
class NexmarkConfig:
    batch_size: int = 8192
    n_batches: int = 100
    events_per_ms: int = 100       # event-time density
    n_splits: int = 1
    num_active_auctions: int = 1000
    num_active_people: int = 500
    hot_ratio: int = 2             # 1/hot_ratio of bids go to hot auctions


# Declared record schemas (field -> numpy dtype name) of the three
# event streams -- seeds the plan analyzer's schema lattice so a Q5/Q7/
# Q8 pipeline's field references are checked at compile time
# (analysis/dataflow.py; the generators' output dicts must match).
BID_SCHEMA = {"auction": "int64", "bidder": "int64", "price": "float32"}
PERSON_SCHEMA = {"person": "int64", "state_id": "int64"}
AUCTION_SCHEMA = {"auction": "int64", "seller": "int64",
                  "category": "int64", "reserve": "float32"}


def _event_ids(cfg: NexmarkConfig, split: int, index: int) -> Tuple[np.ndarray, np.ndarray]:
    """Global event ids + event-time for one batch (monotone per split,
    interleaved across splits)."""
    b = cfg.batch_size
    base = (index * cfg.n_splits + split) * b
    ids = base + np.arange(b, dtype=np.int64)
    ts = ids // cfg.events_per_ms
    return ids, ts


def bid_stream(cfg: NexmarkConfig) -> GeneratorSource:
    """Bids only (Q5/Q7 input): fields auction, bidder, price. Hot
    auctions get 1/hot_ratio of the traffic (zipf-ish skew)."""

    def gen(split: str, i: int) -> Optional[Tuple[Dict[str, np.ndarray], np.ndarray]]:
        if i >= cfg.n_batches:
            return None
        ids, ts = _event_ids(cfg, int(split), i)
        b = cfg.batch_size
        n_hot = max(1, cfg.num_active_auctions // HOT_AUCTION_RATIO)
        # C fast path: on the single-core bench host the numpy RNG body
        # costs ~116ms per 2^20 batch (the log-normal price dominates) —
        # more than the whole rest of the pipeline. Same distributions,
        # different (still deterministic) stream.
        from flink_tpu.native_codec import nexmark_bids_native
        native = nexmark_bids_native(
            (int(split) << 20) | i, b, cfg.hot_ratio, n_hot,
            cfg.num_active_auctions, cfg.num_active_people)
        if native is not None:
            auction, bidder, price = native
            return ({"auction": auction, "bidder": bidder,
                     "price": price}, ts)
        rng = np.random.default_rng((int(split) << 20) | i)
        hot = rng.integers(0, cfg.hot_ratio, b) == 0
        auction = np.where(
            hot,
            rng.integers(0, n_hot, b),
            rng.integers(0, cfg.num_active_auctions, b),
        ).astype(np.int64)
        bidder = rng.integers(0, cfg.num_active_people, b).astype(np.int64)
        price = np.round(np.exp(rng.normal(6.0, 1.0, b)), 2).astype(np.float32)
        return ({"auction": auction, "bidder": bidder, "price": price}, ts)

    return GeneratorSource(gen, n_splits=cfg.n_splits,
                           schema=BID_SCHEMA)


def person_stream(cfg: NexmarkConfig) -> GeneratorSource:
    """New-person events (Q8 left input): fields person, state_id."""

    def gen(split: str, i: int) -> Optional[Tuple[Dict[str, np.ndarray], np.ndarray]]:
        if i >= cfg.n_batches:
            return None
        ids, ts = _event_ids(cfg, int(split), i)
        rng = np.random.default_rng(0x9E3779B9 ^ ((int(split) << 20) | i))
        b = cfg.batch_size
        person = (ids * PERSON_PROPORTION // TOTAL_PROPORTION) % (
            cfg.num_active_people) + rng.integers(0, 2, b)
        return ({"person": person.astype(np.int64),
                 "state_id": rng.integers(0, 50, b).astype(np.int64)}, ts)

    return GeneratorSource(gen, n_splits=cfg.n_splits,
                           schema=PERSON_SCHEMA)


def auction_stream(cfg: NexmarkConfig) -> GeneratorSource:
    """New-auction events (Q8 right input): fields auction, seller,
    category, reserve."""

    def gen(split: str, i: int) -> Optional[Tuple[Dict[str, np.ndarray], np.ndarray]]:
        if i >= cfg.n_batches:
            return None
        ids, ts = _event_ids(cfg, int(split), i)
        rng = np.random.default_rng(0x85EBCA6B ^ ((int(split) << 20) | i))
        b = cfg.batch_size
        seller = rng.integers(0, cfg.num_active_people, b).astype(np.int64)
        return ({
            "auction": ids,
            "seller": seller,
            "category": rng.integers(0, 5, b).astype(np.int64),
            "reserve": np.round(np.exp(rng.normal(6.0, 1.0, b)), 2).astype(np.float32),
        }, ts)

    return GeneratorSource(gen, n_splits=cfg.n_splits,
                           schema=AUCTION_SCHEMA)
