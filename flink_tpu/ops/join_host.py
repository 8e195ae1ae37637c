"""The unbounded keyed join, host lane, and what both lanes share.

``SELECT l.key, l.carry, MAX(r.value) FROM l, r WHERE l.key = r.key AND
r.rowtime BETWEEN l.rowtime AND l.until GROUP BY l.key, l.carry`` with
no window and no TTL (NEXmark q4's inner query: auction JOIN bid): the
left side has ONE row a key, the right side is reduced (MAX), the
predicate is a range of the right row's event time within the left
row's event time and its ``until`` column, both ends inclusive. Both
sides are kept: a right row that comes BEFORE its left row waits, and
the predicate is applied to it when the left row arrives. The result
after a batch depends on the SET of rows seen, not on their order within
or across batches.

After every microbatch (the mini-batch) the changelog of the keys whose
``MAX`` appeared or changed in it: ``+I`` (first result), or ``-U`` (the
value last emitted) and ``+U``; one entry a key a batch however often it
rose (``changelog_rows``). Every row carries the key's newest event
time among ITS rows of that batch (``__ts__``) and the number of the
mini-batch it left in (``MINIBATCH_FIELD``: the driver hands a stateful
consumer the rows one mini-batch at a time, so that it emits once a
mini-batch: the marker of ``table.exec.mini-batch``).

This file is the HOST lane (``join.on_host`` 1; the driver builds it
where ``ops/join_device.py`` ``device_lane_fits`` says no: a mesh, slots
past int32 cell keys): per-slot numpy arrays behind a ``KeyDirectory``
and an unbounded list of waiting right rows. The device operator also
holds one, small, for the keys whose waiting rows outgrew a slot's
lanes (``join.pending_overflow``). The snapshot format is one.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import numpy as np

from flink_tpu.ops.window import FiredWindows
from flink_tpu.records import (
    MINIBATCH_FIELD, OP_DTYPE, OP_FIELD, OP_INSERT, OP_UPDATE_AFTER,
    OP_UPDATE_BEFORE)
from flink_tpu.state.keyed import KeyDirectory, account_full_drop
from flink_tpu.time.watermarks import LONG_MIN

NONE64 = np.iinfo(np.int64).min      # no left row yet / no result yet

COUNTERS = ("lefts_in", "rights_in", "rights_matched", "rights_parked",
            "rights_refused", "lanes_matched", "lanes_refused",
            "keys_changed",
            "changelog_rows", "rows_emitted", "batches",
            "pending_overflow", "lane_overflow")


def changelog_rows(keys: np.ndarray, carry: np.ndarray, old: np.ndarray,
                   new: np.ndarray, newest: np.ndarray, minibatch: int,
                   carry_field: str, result_field: str
                   ) -> Dict[str, np.ndarray]:
    """The changelog of one mini-batch from its changed keys (``old``
    is ``NONE64`` where the key had no result): the ``-U`` block, then
    the ``+I`` / ``+U`` block, a key at most once in each."""
    had = old != NONE64
    n_u = int(had.sum())

    def both(x):
        return np.concatenate([x[had], x])

    out = {"key": both(keys), carry_field: both(carry),
           result_field: np.concatenate([old[had], new]),
           OP_FIELD: np.concatenate([
               np.full(n_u, OP_UPDATE_BEFORE, OP_DTYPE),
               np.where(had, OP_UPDATE_AFTER, OP_INSERT).astype(OP_DTYPE)]),
           "__ts__": both(newest)}
    out[MINIBATCH_FIELD] = np.full(len(out["key"]), minibatch, np.int64)
    return out


def empty_rows(carry_field: str, result_field: str) -> Dict[str, np.ndarray]:
    z = np.zeros(0, np.int64)
    return changelog_rows(z, z, z, z, z, 0, carry_field, result_field)


def concat_rows(parts) -> Dict[str, np.ndarray]:
    parts = list(parts)
    if len(parts) == 1:
        return parts[0]
    return {k: np.concatenate([p[k] for p in parts]) for k in parts[0]}


class HostKeyedJoinOperator:
    """The join on the host (module docstring). ``process_batch`` takes
    one batch of both sides: ``left`` marks the left rows, ``data``
    holds ``until`` and ``carry`` (read at left rows) and ``value``
    (read at right rows) under the names given here."""

    on_host = 1

    def __init__(self, *, until_field: str, carry_field: str,
                 value_field: str, result_field: str, num_shards: int,
                 slots_per_shard: int) -> None:
        self.until_field, self.carry_field = until_field, carry_field
        self.value_field, self.result_field = value_field, result_field
        self.directory = KeyDirectory(num_shards, slots_per_shard)
        n = self.directory.local_slots
        self.carry = np.full(n, NONE64)     # NONE64: no left row yet
        self.lo = np.full(n, NONE64)
        self.hi = np.full(n, NONE64)
        self.result = np.full(n, NONE64)    # NONE64: no match yet
        self.newest = np.full(n, NONE64)
        # right rows whose left row has not come: (slot, time, value)
        self.pending = np.zeros((3, 0), np.int64)
        self.watermark = LONG_MIN
        self.late_records = 0               # no window: nothing is late
        self.records_dropped_full = 0
        self.allow_drops = False
        self.state_version = 0
        self.minibatch = 0
        self.counters = dict.fromkeys(COUNTERS, 0)
        self._fired: Optional[Dict[str, np.ndarray]] = None

    # -- data plane ------------------------------------------------------
    def process_batch(self, keys, ts, left, data: Dict[str, np.ndarray],
                      valid=None) -> None:
        self.state_version += 1
        self.minibatch += 1
        self.counters["batches"] += 1
        keys = np.asarray(keys, np.int64)
        ts = np.asarray(ts, np.int64)
        left = np.asarray(left, bool)
        cols = [np.asarray(data[f], np.int64) for f in (
            self.until_field, self.carry_field, self.value_field)]
        slots = self.directory.assign(keys)
        keep = slots >= 0
        if valid is not None:
            keep &= np.asarray(valid, bool)
        if not (slots >= 0).all():
            account_full_drop(self, int((slots < 0).sum()))
        if not keep.all():
            slots, ts, left = slots[keep], ts[keep], left[keep]
            cols = [c[keep] for c in cols]
        self._fired = self._fold(slots, ts, left, *cols)

    def _fold(self, slots, ts, left, until, carry, value
              ) -> Optional[Dict[str, np.ndarray]]:
        """One batch of both sides into the state -> its changelog."""
        c = self.counters
        touched = np.unique(slots)
        before = self.result[touched]
        # the key's newest event time among its rows of THIS batch
        batch_newest = np.full(len(touched), NONE64)
        np.maximum.at(batch_newest, np.searchsorted(touched, slots), ts)
        ls = slots[left]
        c["lefts_in"] += len(ls)
        # one left row a key: a second one is merged field by field, so
        # that the order of arrival still decides nothing
        had_left = self.carry[touched] != NONE64
        np.maximum.at(self.carry, ls, carry[left])
        np.maximum.at(self.lo, ls, ts[left])
        np.maximum.at(self.hi, ls, until[left])
        rs, rt, rv = slots[~left], ts[~left], value[~left]
        c["rights_in"] += len(rs)
        # "came before its left row": none in the state, and the batch's
        # own (if any) lies behind it in the batch
        pos = np.flatnonzero(~left)
        first_left = np.full(len(touched), len(slots), np.int64)
        np.minimum.at(first_left, np.searchsorted(touched, ls),
                      np.flatnonzero(left))
        at = np.searchsorted(touched, rs)
        c["rights_parked"] += int((~had_left[at] & (pos < first_left[at]))
                                  .sum())
        waiting = self.pending
        known = self.carry[rs] != NONE64
        ok = known & (self.lo[rs] <= rt) & (rt <= self.hi[rs])
        c["rights_matched"] += int(ok.sum())
        c["rights_refused"] += int((known & ~ok).sum())
        np.maximum.at(self.result, rs[ok], rv[ok])
        # the rows that waited, now that this batch's left rows are in:
        # counted per (key, time), as the device's lanes hold them
        ws, wt, wv = waiting
        w_known = self.carry[ws] != NONE64
        w_ok = w_known & (self.lo[ws] <= wt) & (wt <= self.hi[ws])
        np.maximum.at(self.result, ws[w_ok], wv[w_ok])
        c["lanes_matched"] += np.unique(waiting[:2, w_ok], axis=1).shape[1]
        c["lanes_refused"] += np.unique(
            waiting[:2, w_known & ~w_ok], axis=1).shape[1]
        self.pending = np.concatenate(
            [waiting[:, ~w_known],
             np.stack([rs[~known], rt[~known], rv[~known]])], axis=1)
        np.maximum.at(self.newest, slots, ts)
        after = self.result[touched]
        ch = after != before
        n = int(ch.sum())
        c["keys_changed"] += n
        if not n:
            return None
        rows = changelog_rows(
            self.directory.key_of_slots(touched[ch]),
            self.carry[touched[ch]], before[ch], after[ch],
            batch_newest[ch], self.minibatch, self.carry_field,
            self.result_field)
        c["changelog_rows"] += n
        c["rows_emitted"] += len(rows["key"])
        return rows

    def take_fired(self) -> Optional[FiredWindows]:
        rows, self._fired = self._fired, None
        return None if rows is None else FiredWindows(data=rows)

    # -- time plane / the driver's protocol --------------------------------
    def advance_watermark(self, wm: int) -> FiredWindows:
        if wm > self.watermark:
            self.watermark = wm
        return FiredWindows(data=empty_rows(self.carry_field,
                                            self.result_field))

    def final_watermark(self) -> int:
        return self.watermark if self.watermark != LONG_MIN else 0

    def quiesce(self) -> None:
        pass

    def throttle(self) -> None:
        pass

    def state_counters(self) -> Dict[str, Any]:
        return join_counters(self.counters, self.directory, on_host=1)

    # -- snapshot: one format from either lane -----------------------------
    def snapshot_state(self) -> Dict[str, Any]:
        return {"kind": "keyed_join",
                "directory": self.directory.snapshot(),
                "carry": self.carry.copy(), "lo": self.lo.copy(),
                "hi": self.hi.copy(), "result": self.result.copy(),
                "newest": self.newest.copy(),
                "pending": self.pending.copy(),
                "minibatch": self.minibatch,
                "watermark": self.watermark,
                "counters": dict(self.counters),
                "records_dropped_full": self.records_dropped_full}

    def restore_state(self, snap: Dict[str, Any]) -> None:
        self.directory = KeyDirectory.restore(
            self.directory.num_shards, self.directory.slots_per_shard,
            snap["directory"],
            (self.directory.shard_lo, self.directory.shard_hi))
        for name in ("carry", "lo", "hi", "result", "newest"):
            setattr(self, name, np.asarray(snap[name], np.int64).copy())
        self.pending = np.asarray(snap["pending"], np.int64).reshape(3, -1)
        self.minibatch = int(snap["minibatch"])
        self.watermark = snap["watermark"]
        self.counters = {**dict.fromkeys(COUNTERS, 0), **snap["counters"]}
        self.records_dropped_full = snap.get("records_dropped_full", 0)
        self._fired = None


def join_counters(counters: Dict[str, int], directory: KeyDirectory,
                  on_host: int) -> Dict[str, Any]:
    """What a job reports of its join, under the names of NEXmark q4's
    sides (the left rows are auctions, the right rows bids)."""
    c = counters
    grows, grow_s, buckets = directory.table_growth()
    return {"join.on_host": on_host,
            "join.auctions_in": c["lefts_in"],
            "join.bids_in": c["rights_in"],
            "join.bids_matched": c["rights_matched"],
            "join.bids_parked": c["rights_parked"],
            "join.bids_refused": c["rights_refused"],
            "join.lanes_matched": c["lanes_matched"],
            "join.lanes_refused": c["lanes_refused"],
            "join.keys_new": directory.slots_allocated,
            "join.keys_changed": c["keys_changed"],
            "join.live_keys": directory.num_keys(),
            "join.live_keys_peak": directory.keys_peak,
            "join.slots": directory.local_slots,
            "join.changelog_rows": c["changelog_rows"],
            "join.rows_emitted": c["rows_emitted"],
            "join.batches": c["batches"],
            "join.lane_overflow": c["lane_overflow"],
            "join.pending_overflow": c["pending_overflow"],
            "state.slots_allocated": directory.slots_allocated,
            "state.slots_reused": directory.slots_reused,
            "state.slots_released": directory.slots_released,
            "state.live_keys": directory.num_keys(),
            "state.live_keys_peak": directory.keys_peak,
            "state.table_grows": grows, "state.table_grow_s": grow_s,
            "state.table_buckets": buckets}
