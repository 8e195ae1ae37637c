"""NEXmark query 11 "user sessions" on the suite's own bid stream: the
job (``flink_tpu.nexmark.queries.q11_user_sessions``), its records (the
bids of ``nexmark_q5_large_keys``, imported: the same ``LazyPool``, the
key is now the BIDDER) and a plain reference.

``queries/q11.sql``: per bidder, COUNT over session windows with a gap
of 10 s: event (k, t) opens [t, t + gap); two windows of one key merge
when they intersect (events t1 <= t2 share a session iff t2 - t1 <=
gap, transitively); a session is [min t, max t + gap) and its row is
(bidder, bid_count, starttime = min t, endtime = max t + gap). Under
``ddl_gen.sql``'s watermark (-4 s) a session fires once an event
stamped ``endtime + 4 s`` or later is seen, and the end-of-input
watermark fires the rest.

What the stream is (``nexmark_q5.py`` has the generator's formulas): a
bid goes to the hot bidder of the moment with probability 3/4
(``(lastPerson / 100) * 100 + 1``, which moves every 100 persons = 0.5
ms at 200 persons per ms) and else uniformly to one of the newest
1,000 persons (+10 lead). Every person becomes a key, receives all its
bids within ~5 ms and never again: ~22,800 keys arrive with every
2^20-bid batch, each with ONE session, which closes ~14 s later.

The reference is numpy only and takes nothing from the program: per
batch the records sorted by (bidder, ts) and cut into runs at gaps over
``gap_ms`` (on a few threads), then the runs of the whole stream sorted
by (bidder, start) and merged by the same rule. It assumes neither one
session a key nor timestamps in order WITHIN a batch; it does assume
that no event is late (the configuration's ``assumed.timestamps``, and
``late_records`` = 0 is held beside it).

``make_pool`` refuses a program whose session operator keeps no state
on the device: on such a program the job would run on the host
registry, whose fires leave no cohort and whose rate at this density is
unknown; the run ends there, before any job is built, with another
exit code than 0. That the RUN kept its sessions on the device is held
by a zero counter (``session.on_registry``).
"""
from __future__ import annotations

from typing import Tuple

import numpy as np

from benchmark.configs import nexmark_q5 as q5
from benchmark.configs import nexmark_q5_large_keys as large
from benchmark.reference_util import blocks_in_order

SCHEMA = large.SCHEMA
fire_delay_ms = q5.fire_delay_ms
WINDOW_END_FIELD = "endtime"
ROW_FIELDS = ("bidder", "bid_count", "starttime", "endtime")


def device_sessions() -> bool:
    """Whether the program has Q11 and a session operator whose state
    is on the device."""
    try:
        from flink_tpu.nexmark.queries import q11_user_sessions  # noqa: F401
        from flink_tpu.ops.session_device import (  # noqa: F401
            DeviceSessionOperator)
    except ImportError:
        return False
    return True


def make_pool(seed: int, n: int, p: dict):
    if not device_sessions():
        raise NotImplementedError(
            "this configuration runs NEXmark Q11 with the session state "
            "on the device (memory.hbm_state_bytes > 0 for the session "
            "operator, fired rows through the emit ring and the drain); "
            "the program in this checkout has no q11_user_sessions or no "
            "device session operator (flink_tpu/ops/session_device.py): "
            "it does not support this configuration")
    return large.make_pool(seed, n, p)


# -- what the harness asks a configuration's module ------------------------

def warmup_event_ms(p: dict) -> int:
    """Event time a warm-up pass has to span: until the first sessions
    have closed (gap + watermark delay), fired, been decoded, released
    and their slots handed out again, a second of batches on; then the
    end-of-input flush fires the rest in several passes."""
    return int(p["gap_ms"]) + fire_delay_ms(p) + 1000


def zero_counters(p: dict) -> Tuple[str, ...]:
    """Job metrics that the guarantees hold at 0: nothing dropped or
    late, no slot given back to the allocator while a fire dispatched
    before its release was still undrained, and no session operator of
    the job on the host registry (the driver's choice of lane, or the
    device operator's hand-over in mid-run: either reads 1, and the
    run is then not this configuration's)."""
    return ("records_dropped_full", "late_records",
            "state.slots_returned_early", "session.on_registry")


def keys_per_batch(p: dict, batch: int) -> int:
    """Distinct bidders a batch of ``batch`` bids names, by the
    generator's formulas: every person new in it (one per
    ``bid_proportion`` bids) and the active window it starts with."""
    return (batch * int(p["person_proportion"]) // int(p["bid_proportion"])
            + int(p["num_active_people"]) + q5.PERSON_ID_LEAD)


def step_shapes(p: dict, batch: int, events_per_ms: float) -> dict:
    """What ``session_step_bytes`` needs to know of one batch and one
    fire: from the deployment's shapes, not from the program."""
    gap, delay = int(p["gap_ms"]), int(p["out_of_orderness_ms"])
    return {"records": batch, "keys": keys_per_batch(p, batch),
            "slots": int(p["state_slots"]),
            "lanes": -(-delay // gap) + 1}


def build(env, source, sink, p: dict) -> None:
    from flink_tpu.nexmark.queries import q11_user_sessions

    q11_user_sessions(env, source, sink, gap_ms=int(p["gap_ms"]),
                      out_of_orderness_ms=int(p["out_of_orderness_ms"]))


# -- the plain reference ---------------------------------------------------

def batch_runs(bidder: np.ndarray, ts: np.ndarray, gap: int):
    """One batch's runs as (bidder, start, last, count) columns: its
    records sorted by (bidder, ts), cut where the bidder changes or two
    neighbours lie more than ``gap`` apart."""
    b = np.asarray(bidder, np.int64)
    t = np.asarray(ts, np.int64)
    b0, t0 = int(b.min()), int(t.min())
    span = int(t.max()) - t0 + 1
    enc = (b - b0) * span
    enc += t - t0
    enc.sort()
    sb = enc // span
    st = enc - sb * span
    cut = np.empty(len(enc), bool)
    cut[0] = True
    np.not_equal(sb[1:], sb[:-1], out=cut[1:])
    cut[1:] |= (st[1:] - st[:-1]) > gap
    first = np.flatnonzero(cut)
    last = np.r_[first[1:], len(enc)] - 1
    return sb[first] + b0, st[first] + t0, st[last] + t0, last - first + 1


def sessions(stream, gap: int):
    """Every session of the stream as sorted (bidder, bid_count,
    starttime, endtime) columns."""
    parts = list(blocks_in_order(
        stream, lambda data, ts: batch_runs(data["bidder"], ts, gap)))
    if not parts:
        z = np.zeros(0, np.int64)
        return z, z, z, z
    b, s, e, c = (np.concatenate([p[i] for p in parts]) for i in range(4))
    order = np.lexsort((s, b))
    b, s, e, c = b[order], s[order], e[order], c[order]
    # a run joins the session before it iff it starts within the gap of
    # that session's latest event so far: the running maximum of
    # ``last`` within a bidder, every bidder's timeline in a band of
    # its own so that ONE accumulate keeps them apart
    base = int(s.min())
    band = int(e.max()) - base + gap + 2
    rank = np.cumsum(np.r_[0, b[1:] != b[:-1]])
    chain = np.maximum.accumulate(rank * band + (e - base))
    new = np.r_[True, rank[1:] * band + (s[1:] - base) > chain[:-1] + gap]
    first = np.flatnonzero(new)
    return (b[first], np.add.reduceat(c, first), s[first],
            np.maximum.reduceat(e, first) + gap)


def collect(sink_batches, p: dict):
    """The committed rows as (bidder, bid_count, starttime, endtime)."""
    return tuple(
        np.concatenate([np.asarray(b[f], np.int64) for b in sink_batches])
        if sink_batches else np.zeros(0, np.int64) for f in ROW_FIELDS)


def compare(exp, got) -> dict:
    """Every committed row against the reference's. A reference row is
    found by (bidder, starttime), which no two sessions share, and then
    held to its count and end."""
    eb, ec, es, ee = exp
    gb, gc, gs, ge = got
    t_lo = int(min(es.min(initial=0), gs.min(initial=0)))
    width = int(max(es.max(initial=0), gs.max(initial=0))) - t_lo + 1
    e_key = eb * width + (es - t_lo)
    order = np.argsort(e_key, kind="stable")
    e_key, ec, ee = e_key[order], ec[order], ee[order]
    g_key = gb * width + (gs - t_lo)
    at = np.minimum(np.searchsorted(e_key, g_key), max(len(e_key) - 1, 0))
    match = (np.zeros(len(g_key), bool) if not len(e_key) else
             (e_key[at] == g_key) & (ec[at] == gc) & (ee[at] == ge))
    hits = np.bincount(at[match], minlength=len(e_key))
    missing = hits == 0
    wrong = np.flatnonzero(~match)[:3]
    lost = np.flatnonzero(missing)[:3]
    return {
        "rows_expected": int(len(e_key)),
        "rows_got": int(len(g_key)),
        "rows_duplicated": int((hits[hits > 1] - 1).sum()),
        "rows_missing": int(missing.sum()),
        "rows_not_in_reference": int((~match).sum()),
        # every bid of a session whose row is missing counts as failed
        "events_without_result": int(ec[missing].sum()),
        "first_differences": (
            [["missing", int(eb[order[i]]), int(ec[i]),
              int(es[order[i]]), int(ee[i])] for i in lost]
            + [["not_in_reference", int(gb[i]), int(gc[i]), int(gs[i]),
                int(ge[i])] for i in wrong]),
    }


def check(stream, max_ts: int, sink_batches, p: dict) -> dict:
    """Every committed row against the reference's, the end-of-input
    flush included; all limits are 0."""
    return compare(sessions(stream, int(p["gap_ms"])),
                   collect(sink_batches, p))
