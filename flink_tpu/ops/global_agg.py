"""Unwindowed keyed running aggregation — the UPSERT/changelog path.

ref: table/runtime aggregate/GroupAggFunction + the retract/changelog
stream model (SURVEY §3.8): `SELECT k, agg FROM t GROUP BY k` with no
window emits an ever-updating result per key. For INSERT-ONLY input
(the streaming source contract here) the changelog degenerates to an
UPSERT stream — each emitted row REPLACES the previous row for its
key. Sinks consume it either raw (`FnSink` sees every upsert — the
kafka-upsert shape) or materialized (`UpsertSink` keeps latest-by-key).

``retract=True`` emits the FULL changelog instead (ref: the retract
stream of SURVEY §3.8, RowKind-typed rows): each update becomes a
``-U`` row carrying the previously emitted values followed by a
``+U`` replacement (first emission: ``+I``), op-typed via the
``records.OP_FIELD`` int8 lane. This is what downstream changelog
consumers need — window aggregation that SUBTRACTS retracted rows
(ops/aggregates.changelog_* lanes), `RetractSink`, and the SQL
HAVING-over-unwindowed-aggregation rewrite all fold these rows.

Which jobs run here: this is the HOST lane of the unwindowed
aggregation. The driver builds it (``runtime/driver.py``, the one place
the lane is chosen) for ``retract=True`` and under a device mesh; every
other job with a ``LaneAggregate`` keeps its accumulators on the device
(``ops/groupagg_device.py`` ``DeviceGroupAggOperator``, the same rows
and the same snapshot format). ``groupagg.on_host`` reads 1 here.

Host shape: per-key accumulators live in flat host arrays behind
the same KeyDirectory slot map the pane backend uses; a batch folds in
with one argsort + reduceat per lane (no per-record Python), and the
upserts emitted per microbatch are exactly the keys the batch touched
— the mini-batch aggregation emission model (ref: table-runtime
MiniBatchGroupAggFunction). Integer lanes (``LaneAggregate
.lane_dtypes``) are held at int64 and are exact, as on the device; the
float lanes add in float64 here and in float32 there, so a float SUM
may differ between the lanes in its last bits (counts, maxs and mins
never do).
"""
from __future__ import annotations

import time
from typing import Any, Dict, Optional

import numpy as np

from flink_tpu import faults
from flink_tpu.ops.aggregates import LANE_FAMILIES, lane_identity
from flink_tpu.ops.groupagg_device import (
    finalize_rows, host_lane_dtypes, host_records, lane_layout,
    restored_lanes, snapshot_lanes)
from flink_tpu.ops.window import FiredWindows, account_full_drop
from flink_tpu.records import (
    OP_DTYPE,
    OP_FIELD,
    OP_INSERT,
    OP_UPDATE_AFTER,
    OP_UPDATE_BEFORE,
)
from flink_tpu.state.keyed import KeyDirectory
from flink_tpu.time.watermarks import LONG_MIN


def _columns(*arrays: np.ndarray):
    """``(n, width)`` lane arrays as tuples of their columns."""
    return [tuple(a[:, j] for j in range(a.shape[1])) for a in arrays]


def _as_2d(family, n: int) -> np.ndarray:
    """A lifted lane family as ``(n, width)``: it comes so for float
    lanes, as a tuple of (n,) columns for integer ones."""
    if not isinstance(family, tuple):
        return np.asarray(family)
    if not family:
        return np.zeros((n, 0), np.int64)
    return np.stack([np.asarray(c) for c in family], axis=1)


class GlobalAggregateOperator:
    """Driver-protocol operator: per-step upsert emission via
    ``take_fired`` (the count_window/process emission pattern).

    ``retract=True`` switches the output from the degenerate upsert
    stream to the full changelog (ref: GroupAggFunction's
    generateUpdateBefore path): a touched key whose result was emitted
    before first RETRACTS the stale row (``-U``, finalized from the
    accumulators as they stood at the previous emission) and then emits
    the replacement (``+U``); a key's first result is ``+I``. Rows carry
    the op type in the ``__op__`` int8 column (records.OP_FIELD). The
    ``-U`` block precedes the ``+I/+U`` block within one emission — a
    key appears at most once in each, so per-key changelog order holds.
    """

    def __init__(self, agg, *, num_shards: int,
                 slots_per_shard: int, retract: bool = False) -> None:
        self.agg = agg
        self.retract = bool(retract)
        self.directory = KeyDirectory(num_shards, slots_per_shard)
        n = self.directory.local_slots
        self.counts = np.zeros(n, np.int64)
        self.sums, self.maxs, self.mins = self._identity_lanes(n)
        if self.retract:
            # accumulators AS EMITTED — the -U row's payload; a slot
            # retracts only after its first emission (emitted mask)
            self.prev_counts = np.zeros(n, np.int64)
            self.prev_sums, self.prev_maxs, self.prev_mins = \
                self._identity_lanes(n)
            self.emitted = np.zeros(n, bool)
        # integer lanes that read the event time hold ``ts - _base``
        # (the first batch's earliest), as the device lane's do
        self._base: Optional[int] = None
        self.lane_overflow = 0         # records a 32-bit lane refused
        self.watermark = LONG_MIN
        self.late_records = 0          # unwindowed: nothing is late
        self.records_dropped_full = 0
        self.allow_drops = False
        self.state_version = 0
        self._touched: Optional[np.ndarray] = None

    def _identity_lanes(self, n: int):
        """(sums, maxs, mins) of ``n`` untouched slots, ``(n, width)``
        each: float64 / float32 / float32 for the float aggregates,
        int64 for integer lanes (``host_lane_dtypes``)."""
        dts = host_lane_dtypes(self.agg)
        out = []
        for fam, lanes in zip(LANE_FAMILIES, lane_layout(self.agg)):
            arr = np.empty((n, len(lanes)), dts[fam])
            for j, dt in enumerate(lanes):
                arr[:, j] = lane_identity(fam, dt)
            out.append(arr)
        return out

    def _finalize(self, counts, sums, maxs, mins) -> Dict[str, np.ndarray]:
        if not self.agg.typed:
            return {k: np.asarray(v) for k, v in self.agg.finalize(
                sums.astype(np.float32), maxs, mins, counts).items()}
        return finalize_rows(self.agg, counts, _columns(sums, maxs, mins),
                             self._base or 0)

    # -- data plane ------------------------------------------------------

    def process_batch(self, keys, ts, data: Dict[str, np.ndarray],
                      valid=None) -> None:
        self.state_version += 1
        keys = np.asarray(keys, np.int64)
        valid = (np.ones(len(keys), bool) if valid is None
                 else np.asarray(valid, bool))
        if not valid.any():
            return
        keys, ts = keys[valid], np.asarray(ts, np.int64)[valid]
        data = {k: np.asarray(v)[valid] for k, v in data.items()}
        slots = self.directory.assign(keys)
        keep = slots >= 0
        if not keep.all():
            account_full_drop(self, int((~keep).sum()))
        if self.agg.typed:
            # the columns as the device lane uploads them: a value a
            # 32-bit lane cannot hold is refused with its record
            if self._base is None and any(self.agg.time_lanes):
                self._base = int(ts.min())
            data, over = host_records(self.agg, ts, data, self._base or 0)
            if over is not None:
                self.lane_overflow += int((over & keep).sum())
                keep &= ~over
        if not keep.all():
            slots = slots[keep]
            data = {k: v[keep] for k, v in data.items()}
            if not len(slots):
                return
        order = np.argsort(slots, kind="stable")
        so = slots[order]
        bnd = np.empty(len(so), bool)
        bnd[0] = True
        bnd[1:] = so[1:] != so[:-1]
        starts = np.nonzero(bnd)[0]
        uslots = so[starts]
        self.counts[uslots] += np.add.reduceat(
            np.ones(len(so), np.int64), starts)
        if self.agg.sum_width or self.agg.max_width or self.agg.min_width:
            lifted = self.agg.lift_masked(
                {k: v[order] for k, v in data.items()},
                np.ones(len(so), bool))
            s_l, mx_l, mn_l = (_as_2d(fam, len(so)) for fam in lifted)
            if self.agg.sum_width:
                self.sums[uslots] += np.add.reduceat(
                    s_l.astype(self.sums.dtype), starts, axis=0)
            if self.agg.max_width:
                self.maxs[uslots] = np.maximum(
                    self.maxs[uslots],
                    np.maximum.reduceat(mx_l, starts, axis=0))
            if self.agg.min_width:
                self.mins[uslots] = np.minimum(
                    self.mins[uslots],
                    np.minimum.reduceat(mn_l, starts, axis=0))
        self._touched = (uslots if self._touched is None
                         else np.union1d(self._touched, uslots))

    def take_fired(self) -> Optional["FiredWindows"]:
        """Emit the upsert rows for every key this step touched (or the
        -U/+U changelog pairs in retract mode)."""
        if self._touched is None or not len(self._touched):
            self._touched = None
            return None
        sl = self._touched
        self._touched = None
        wm = self.watermark if self.watermark != LONG_MIN else 0
        if not self.retract:
            out: Dict[str, np.ndarray] = {
                "key": self.directory.key_of_slots(sl)}
            out["count"] = self.counts[sl]
            out.update(self._finalize(
                self.counts[sl], self.sums[sl], self.maxs[sl],
                self.mins[sl]))
            # upserts carry the emission-time watermark as their
            # timestamp (the process-function emission contract,
            # driver _emit_fired)
            out["__ts__"] = np.full(len(sl), wm, np.int64)
            self._restart_emission_lanes(sl)
            return FiredWindows(data=out)
        # retract mode: fired BEFORE any emission bookkeeping mutates,
        # so an injected failure here leaves (prev_*, emitted) exactly
        # as the last successful emission left them — recovery replays
        # the whole step and the changelog stays consistent
        faults.fire("changelog.retract.emit", exc=RuntimeError,
                    touched=len(sl))
        retr = sl[self.emitted[sl]]
        keys_new = self.directory.key_of_slots(sl)
        blocks = []
        if len(retr):
            old: Dict[str, np.ndarray] = {
                "key": self.directory.key_of_slots(retr),
                "count": self.prev_counts[retr]}
            old.update(self._finalize(
                self.prev_counts[retr], self.prev_sums[retr],
                self.prev_maxs[retr], self.prev_mins[retr]))
            old[OP_FIELD] = np.full(len(retr), OP_UPDATE_BEFORE,
                                    OP_DTYPE)
            blocks.append(old)
        new: Dict[str, np.ndarray] = {"key": keys_new,
                                      "count": self.counts[sl]}
        new.update(self._finalize(
            self.counts[sl], self.sums[sl], self.maxs[sl], self.mins[sl]))
        new[OP_FIELD] = np.where(self.emitted[sl], OP_UPDATE_AFTER,
                                 OP_INSERT).astype(OP_DTYPE)
        blocks.append(new)
        out = {k: np.concatenate([b[k] for b in blocks])
               for k in blocks[-1]}
        out["__ts__"] = np.full(len(out["key"]), wm, np.int64)
        # the emitted view is now the current accumulators
        self.prev_counts[sl] = self.counts[sl]
        self.prev_sums[sl] = self.sums[sl]
        self.prev_maxs[sl] = self.maxs[sl]
        self.prev_mins[sl] = self.mins[sl]
        self.emitted[sl] = True
        return FiredWindows(data=out)

    def _restart_emission_lanes(self, sl: np.ndarray) -> None:
        """A lane that holds "since the key's last row"
        (``LaneAggregate.emission_lanes``) starts anew for the keys
        whose row has just left."""
        for fam, arr, lanes, dts in zip(
                LANE_FAMILIES, (self.sums, self.maxs, self.mins),
                self.agg.emission_lanes, lane_layout(self.agg)):
            for j in lanes:
                arr[sl, j] = lane_identity(fam, dts[j])

    # -- time plane ------------------------------------------------------

    def advance_watermark(self, wm: int):
        if wm > self.watermark:
            self.watermark = wm
        return FiredWindows(data=dict(self._empty()))

    def _empty(self) -> Dict[str, np.ndarray]:
        out = {"key": np.zeros(0, np.int64),
               "count": np.zeros(0, np.int64)}
        out.update(self._finalize(
            np.zeros(0, np.int64), *self._identity_lanes(0)))
        if self.retract:
            out[OP_FIELD] = np.zeros(0, OP_DTYPE)
        return out

    def final_watermark(self) -> int:
        return self.watermark if self.watermark != LONG_MIN else 0

    def quiesce(self) -> None:
        pass

    def throttle(self) -> None:
        pass

    # -- snapshot seam ---------------------------------------------------

    def _lanes_out(self, counts, sums, maxs, mins, prefix: str = ""):
        """The snapshot's lane arrays (copies; an event-time lane as
        timestamps: ``snapshot_lanes``, either lane's format)."""
        lanes = snapshot_lanes(self.agg, counts, _columns(sums, maxs, mins),
                               self._base or 0)
        return {prefix + fam: a for fam, a in lanes.items()}

    def _lanes_in(self, snap: Dict[str, Any], prefix: str = ""):
        """(sums, maxs, mins) of a snapshot, either lane's."""
        view = {fam: snap[prefix + fam] for fam in LANE_FAMILIES}
        view["counts"] = snap[prefix + "counts"]
        view["time_base"] = snap.get("time_base", self._base)
        base, fams = restored_lanes(self.agg, view)
        if self._base is None:
            self._base = base
        n, dts = len(np.asarray(view["counts"])), host_lane_dtypes(self.agg)
        return [np.stack(cols, axis=1).astype(dts[fam]) if cols
                else np.zeros((n, 0), dts[fam])
                for fam, cols in zip(LANE_FAMILIES, fams)]

    def snapshot_state(self) -> Dict[str, Any]:
        snap = {
            "kind": "global_agg",
            "directory": self.directory.snapshot(),
            "counts": self.counts.copy(),
            **self._lanes_out(self.counts, self.sums, self.maxs, self.mins),
            "time_base": self._base,
            "watermark": self.watermark,
            "records_dropped_full": self.records_dropped_full,
            "lane_overflow": self.lane_overflow,
        }
        if self.retract:
            snap["prev_counts"] = self.prev_counts.copy()
            snap.update(self._lanes_out(
                self.prev_counts, self.prev_sums, self.prev_maxs,
                self.prev_mins, "prev_"))
            snap["emitted"] = self.emitted.copy()
        return snap

    def restore_state(self, snap: Dict[str, Any]) -> None:
        self.directory = KeyDirectory.restore(
            self.directory.num_shards, self.directory.slots_per_shard,
            snap["directory"],
            (self.directory.shard_lo, self.directory.shard_hi))
        self.counts = np.asarray(snap["counts"]).astype(np.int64)
        self._base = snap.get("time_base")
        self.sums, self.maxs, self.mins = self._lanes_in(snap)
        if self.retract:
            # a pre-retract snapshot restoring into a retract-mode op:
            # treat the restored view as already emitted so the first
            # post-restore update retracts it (no double +I)
            if "prev_counts" in snap:
                self.prev_counts = np.asarray(snap["prev_counts"]).copy()
                self.prev_sums, self.prev_maxs, self.prev_mins = \
                    self._lanes_in(snap, "prev_")
            else:
                self.prev_counts = self.counts.copy()
                self.prev_sums, self.prev_maxs, self.prev_mins = (
                    self.sums.copy(), self.maxs.copy(), self.mins.copy())
            self.emitted = np.asarray(snap.get(
                "emitted", self.counts > 0)).copy()
        self.watermark = snap["watermark"]
        self.records_dropped_full = snap.get("records_dropped_full", 0)
        self.lane_overflow = snap.get("lane_overflow", 0)
        self._touched = None

    def state_counters(self) -> Dict[str, Any]:
        """What the job reports of this lane (the device lane's names):
        ``groupagg.on_host`` 1 = the driver built the host operator."""
        d = self.directory
        return {"groupagg.on_host": 1,
                "groupagg.lane_overflow": self.lane_overflow,
                "groupagg.keys_new": d.slots_allocated,
                "groupagg.live_keys": d.num_keys(),
                "groupagg.slots": d.local_slots}
