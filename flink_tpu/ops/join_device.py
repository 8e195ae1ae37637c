"""The unbounded keyed join with both sides' state on the device.

The lane of ``ops/join_host.py`` (its docstring has the semantics, row
for row) for a job on one device whose slots int32 cell keys hold
(``device_lane_fits``; the factory chooses, no option does). What it
shares with ``ops/groupagg_device.py``: ``KeyDirectory.assign``, the
strip-laid ``(words, slots)`` int32 state and its chunked merge
(``merge_chunk``), ``ops/window.py``'s sort by slot with payload and
``_run_scan``, ``EmitRing`` (a fourth holder), the drain's protocol.

State, nine words a slot (``WORDS``): the left row once it has come
(its carried column, ``I32_MIN`` = not yet; its event time and its
``until`` as int32 offsets from the job's first timestamp), the result
(``MAX`` of the matching right rows' value, ``I32_MIN`` = none), the
right rows that came BEFORE their left row as ``EARLY_LANES`` = 2 lanes
of (event time, max value at that time), and the newest event time
folded in. A lane compresses nothing the predicate needs: it is applied
per event time. A key that needs a third lane is HANDED OVER: its strip
is left as it is but for the mark ``HANDED``, the device takes no part
in it from then on, and the operator's small host lane
(``HostKeyedJoinOperator``) takes the key over from the strip and the
batch's own rows, which the operator keeps until the drain has decoded
the batch (``join.pending_overflow`` counts such keys).

Per batch ONE program (``join_apply_kernel``): (1) the rows sorted by
(slot, side, event time) with their columns as payload, left rows first
within a key; (2) the batch's left row broadcast to its key's rows by a
scan; (3) the state's strips of the batch's DISTINCT slots gathered,
``merge_chunk`` a trip, and the state's left row put at each run's head
and broadcast likewise; (4) the predicate per right row against
whichever left row there is, the matching values and the early rows'
two lanes reduced per key by scans; (5) the strips merged, written back
with sorted unique indices, and for every key of the batch one changelog
entry (slot, carried column, result before, result after, the key's
newest event time in the batch) written into the batch's emit buffer in
the same trip. The host keeps the entries whose result changed.
"""
from __future__ import annotations

import collections
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from flink_tpu.hostsync import ready_wait
from flink_tpu.obs.tracing import PhaseClock
from flink_tpu.ops.emit_ring import EmitRing
from flink_tpu.ops.groupagg_device import _batch_size, merge_chunk
from flink_tpu.ops.join_host import (
    COUNTERS, NONE64, HostKeyedJoinOperator, changelog_rows, concat_rows,
    empty_rows, join_counters)
from flink_tpu.ops.window import NO_CELL, FiredWindows, _run_scan, apply_chunk
from flink_tpu.records import OP_FIELD, OP_UPDATE_BEFORE
from flink_tpu.state.keyed import KeyDirectory, account_full_drop
from flink_tpu.time.watermarks import LONG_MIN

I32 = np.iinfo(np.int32)
NONE = int(I32.min)         # a word that holds nothing yet
HANDED = NONE + 1           # word 0: the key is the host lane's
WORDS = 9                   # carry, lo, hi, result, 2 x (time, value), newest
EARLY_LANES = 2
ROW_WORDS = 5               # slot, carry, result before, after, newest
HEAD_WORDS = 16
# header words: [distinct slots, rows, trips, lefts, rights, matched,
# refused, parked, lanes refused, keys changed, keys handed over, lanes
# matched]
(H_CELLS, H_ROWS, H_TRIPS, H_LEFTS, H_RIGHTS, H_MATCHED, H_REFUSED,
 H_PARKED, H_LANES_REFUSED, H_CHANGED, H_HANDED, H_LANES_MATCHED) = range(12)
SIDE_SHARDS, SIDE_SLOTS = 8, 4096   # the host lane of the handed-over keys


def device_lane_fits(*, mesh: bool, slots: int) -> bool:
    """Whether the join keeps its state on the device: one device, and
    slots that ``2 * slot + side`` holds in an int32 sort key."""
    return not mesh and 0 < int(slots) < (1 << 30)


def init_join_state(slots: int) -> jax.Array:
    """``(WORDS, slots)`` int32, every word ``NONE``: a slot's state a
    column strip (``groupagg_device.init_groupagg_state`` says why)."""
    return _FILL(slots=slots)


_FILL = jax.jit(lambda slots: jnp.full((WORDS, slots), NONE, jnp.int32),
                static_argnames=("slots",))


def join_apply_kernel(
    state: jax.Array,   # (WORDS, slots) i32
    key2: jax.Array,    # (B,) i32: 2 * slot + (1 = right row); NO_CELL = none
    t: jax.Array,       # (B,) i32: event time, offset from the job's base
    val: jax.Array,     # (B,) i32: a left row's until offset, a right's value
    carry: jax.Array,   # (B,) i32: a left row's carried column
    *,
    slots: int,
    cap: int,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Fold one batch of both sides into the state. Returns the state,
    the int32 header (``H_*``), the first ``cap`` entries ``(ROW_WORDS,
    cap)`` and all of them ``(ROW_WORDS, B + chunk)``: entry j (j <
    distinct slots, slots ascending) is (slot, carried column or
    ``HANDED``, result before, result after, newest event time of the
    key in this batch)."""
    batch = key2.shape[0]
    mx, mn = jnp.maximum, jnp.minimum
    none = jnp.int32(NONE)
    ix = jnp.arange(batch, dtype=jnp.int32)
    key2, t, val, carry, ix = lax.sort((key2, t, val, carry, ix), num_keys=2)
    live = key2 != NO_CELL
    slot = key2 >> 1
    right = live & ((key2 & 1) == 1)
    left = live & ~right
    first = jnp.concatenate([jnp.ones(1, bool), slot[1:] != slot[:-1]])
    head = first & live
    n_rows = jnp.sum(live, dtype=jnp.int32)
    n_cells = jnp.sum(head, dtype=jnp.int32)

    def down(op, x):        # a run's reduction so far, at each of its rows
        return _run_scan(op, first, x)

    # the batch's own left row, at every row of its key (it sorts first)
    b_carry = down(mx, jnp.where(left, carry, none))
    b_lo = down(mx, jnp.where(left, t, none))
    b_hi = down(mx, jnp.where(left, val, none))
    b_ix = down(mn, jnp.where(left, ix, batch))
    newest = down(mx, jnp.where(live, t, none))

    # the distinct slots to the front, with where each one's rows start
    cells, starts = lax.sort(
        (jnp.where(head, slot, NO_CELL),
         jnp.where(head, jnp.arange(batch, dtype=jnp.int32), batch)),
        num_keys=1, is_stable=False)
    j = jnp.arange(batch + 1, dtype=jnp.int32)
    starts = jnp.where(j < n_cells, jnp.concatenate([starts, starts[:1]]),
                       n_rows)
    chunk = merge_chunk(batch)
    lane_i = jnp.arange(chunk, dtype=jnp.int32)
    cells = jnp.concatenate([cells, jnp.full(chunk, NO_CELL, jnp.int32)])
    starts = jnp.concatenate([starts, jnp.zeros(chunk, jnp.int32)])

    # the state's strips of those slots, and the state's left row at the
    # head of each run
    def gather(c):
        held_all, at_head, done = c
        k = lax.dynamic_slice(cells, (done,), (chunk,))
        s = lax.dynamic_slice(starts, (done,), (chunk,))
        mine = done + lane_i < n_cells
        held = state[:, jnp.where(mine, k, 0)]
        held_all = lax.dynamic_update_slice(held_all, held,
                                            (jnp.int32(0), done))
        at_head = at_head.at[:, jnp.where(mine, s, batch + lane_i)].set(
            held[:3], indices_are_sorted=True, unique_indices=True,
            mode="drop")
        return held_all, at_head, done + jnp.sum(mine, dtype=jnp.int32)

    held_all, at_head, _ = lax.while_loop(
        lambda c: c[2] < n_cells, gather,
        (jnp.full((WORDS, batch + chunk), none, jnp.int32),
         jnp.full((3, batch), none, jnp.int32), jnp.int32(0)))
    s_carry, s_lo, s_hi = (down(mx, at_head[i]) for i in range(3))

    # the predicate, per right row, against whichever left row there is
    handed = s_carry == HANDED
    a_lo, a_hi = mx(s_lo, b_lo), mx(s_hi, b_hi)
    present = ~handed & (mx(s_carry, b_carry) != none)
    match = right & present & (a_lo <= t) & (t <= a_hi)
    refused = right & present & ~match
    early = right & ~present & ~handed
    # came before its left row: none in the state, and the batch's own
    # (if any: ``b_ix`` is the batch's length where none) lies behind it
    parked = right & ~handed & (s_carry == none) & (ix < b_ix)
    big = jnp.int32(I32.max)
    m_val = down(mx, jnp.where(match, val, none))
    # the early rows' first two event times (they ascend within a run)
    # and the largest value at each; whether there is a third
    e0_t = down(mn, jnp.where(early, t, big))
    e0_v = down(mx, jnp.where(early & (t == e0_t), val, none))
    e1_t = down(mn, jnp.where(early & (t > e0_t), t, big))
    e1_v = down(mx, jnp.where(early & (t == e1_t), val, none))
    e_more = down(mx, (early & (t > e1_t)).astype(jnp.int32))
    per_run = jnp.stack([b_carry, b_lo, b_hi, newest, m_val,
                         e0_t, e0_v, e1_t, e1_v, e_more])

    def merge(c):
        state, out, done, trips, counts = c
        k = lax.dynamic_slice(cells, (done,), (chunk,))
        s = lax.dynamic_slice(starts, (done,), (chunk + 1,))
        mine = done + lane_i < n_cells
        held = lax.dynamic_slice(held_all, (jnp.int32(0), done),
                                 (WORDS, chunk))
        (r_carry, r_lo, r_hi, r_new, r_val, x0t, x0v, x1t, x1v,
         r_more) = per_run[:, jnp.maximum(s[1:] - 1, 0)]
        h_carry, h_lo, h_hi, h_res, l0t, l0v, l1t, l1v, h_new = held
        gone = h_carry == HANDED
        lo, hi = mx(h_lo, r_lo), mx(h_hi, r_hi)
        carry_ = mx(h_carry, r_carry)
        here = ~gone & (carry_ != none)
        # the left row is here: this batch's matches, and the rows that
        # waited in the lanes, each held to the predicate
        ok0 = here & (l0t != none) & (lo <= l0t) & (l0t <= hi)
        ok1 = here & (l1t != none) & (lo <= l1t) & (l1t <= hi)
        res = jnp.where(here, mx(h_res, r_val), h_res)
        res = jnp.where(ok0, mx(res, l0v), res)
        res = jnp.where(ok1, mx(res, l1v), res)
        lanes_refused = (here & (l0t != none) & ~ok0).astype(jnp.int32) \
            + (here & (l1t != none) & ~ok1).astype(jnp.int32)
        # it is not: the batch's early rows join the lanes, by event time
        over = r_more > 0
        for xt, xv in ((x0t, x0v), (x1t, x1v)):
            new = xt != big
            in0, in1 = new & (xt == l0t), new & (xt == l1t)
            to0 = new & ~in0 & ~in1 & (l0t == none)
            to1 = new & ~in0 & ~in1 & ~to0 & (l1t == none)
            over |= new & ~in0 & ~in1 & ~to0 & ~to1
            l0v = jnp.where(in0, mx(l0v, xv), jnp.where(to0, xv, l0v))
            l0t = jnp.where(to0, xt, l0t)
            l1v = jnp.where(in1, mx(l1v, xv), jnp.where(to1, xv, l1v))
            l1t = jnp.where(to1, xt, l1t)
        hand = mine & ~gone & ~here & over
        keep = gone | hand      # the strip stays as the state holds it
        merged = jnp.stack([
            jnp.where(hand, jnp.int32(HANDED), carry_), lo, hi, res,
            *(jnp.where(here, none, w) for w in (l0t, l0v, l1t, l1v)),
            mx(h_new, r_new)])
        merged = jnp.where(keep[None], held.at[0].set(
            jnp.where(hand, jnp.int32(HANDED), h_carry)), merged)
        # padding takes distinct slots past the last: uniqueness is true
        state = state.at[:, jnp.where(mine, k, slots + lane_i)].set(
            merged, indices_are_sorted=True, unique_indices=True,
            mode="drop")
        changed = mine & ~keep & (res != h_res)
        # a key handed over in THIS batch says so once, in its entry
        out = lax.dynamic_update_slice(out, jnp.stack([
            k, jnp.where(gone, none, merged[0]), h_res,
            jnp.where(keep, h_res, res), r_new]), (jnp.int32(0), done))
        counts = counts + jnp.stack([
            jnp.sum(jnp.where(mine & ~keep, lanes_refused, 0),
                    dtype=jnp.int32),
            jnp.sum(changed, dtype=jnp.int32),
            jnp.sum(hand, dtype=jnp.int32),
            jnp.sum(jnp.where(mine & ~keep, ok0.astype(jnp.int32)
                              + ok1.astype(jnp.int32), 0),
                    dtype=jnp.int32)])
        return (state, out, done + jnp.sum(mine, dtype=jnp.int32),
                trips + 1, counts)

    state, out, _, trips, counts = lax.while_loop(
        lambda c: c[2] < n_cells, merge,
        (state, jnp.zeros((ROW_WORDS, batch + chunk), jnp.int32),
         jnp.int32(0), jnp.int32(0), jnp.zeros(4, jnp.int32)))

    def total(mask):
        return jnp.sum(mask, dtype=jnp.int32)

    head_words = jnp.zeros(HEAD_WORDS, jnp.int32).at[:12].set(jnp.stack([
        n_cells, n_rows, trips, total(left), total(right), total(match),
        total(refused), total(parked), *counts]))
    return state, head_words, out[:, :cap], out


_JIT_JOIN_APPLY = jax.jit(join_apply_kernel, static_argnames=("slots", "cap"),
                          donate_argnums=(0,))


def _narrow(v: np.ndarray, shift: int
            ) -> Tuple[np.ndarray, Optional[np.ndarray]]:
    """``v - shift`` at 32 bits and the mask of what does not fit
    (None: all does). The two lowest values are the state's marks, the
    highest the scans' "no time"."""
    v = np.asarray(v, np.int64)
    lo, hi, bad = NONE + 2 + shift, int(I32.max) - 1 + shift, None
    if len(v) and (int(v.min()) < lo or int(v.max()) > hi):
        bad = (v < lo) | (v > hi)
    if shift:
        v = v - shift
    return v.astype(np.int32), bad


def _wide(words: np.ndarray, shift: int = 0) -> np.ndarray:
    """A state word as the snapshot's int64 (``NONE64`` = nothing)."""
    w = np.asarray(words).astype(np.int64)
    return np.where(w == NONE, NONE64, w + shift)


class DeviceKeyedJoinOperator:
    """The unbounded keyed join, both sides' state on the device (module
    docstring). The surface is ``HostKeyedJoinOperator``'s; towards the
    drain it is ``WindowOperator``'s (``emit_ring``, ``drain_ring``,
    ``take_delivered_fires``)."""

    on_host = 0

    def __init__(self, *, until_field: str, carry_field: str,
                 value_field: str, result_field: str, num_shards: int = 128,
                 slots_per_shard: int = 1024,
                 max_inflight_steps: int = 3) -> None:
        self.until_field, self.carry_field = until_field, carry_field
        self.value_field, self.result_field = value_field, result_field
        self.directory = KeyDirectory(num_shards, slots_per_shard)
        self.slots = self.directory.local_slots
        if not device_lane_fits(mesh=False, slots=self.slots):
            raise ValueError("this join does not fit the device lane "
                             "(device_lane_fits); it runs on ops/join_host.py")
        self.state = init_join_state(self.slots)
        # the dispatch donates the state; the drain reads a handed-over
        # key's strip from it (rare): one at a time
        self._state_lock = threading.Lock()
        self.watermark = LONG_MIN
        self.late_records = 0
        self.records_dropped_full = 0
        self.allow_drops = False
        self.state_version = 0
        self.phases = PhaseClock()
        self.prof: Dict[str, float] = collections.defaultdict(float)
        self.max_inflight_steps = int(max_inflight_steps)
        self.external_throttle = False
        self._inflight: collections.deque = collections.deque()
        self.emit_ring = EmitRing(keep=None)    # a version a batch
        # version -> (the batch's full emit buffer; its rows as the host
        # had them, for the keys the host lane holds)
        self._tails: Dict[int, Tuple[jax.Array, tuple]] = {}
        self._base: Optional[int] = None
        self._pending: Optional[FiredWindows] = None
        self.counters = dict.fromkeys(COUNTERS, 0)
        # the keys handed over (``join.pending_overflow``), on the
        # drain's thread alone
        self._side: Optional[HostKeyedJoinOperator] = None
        self._side_keys = np.zeros(0, np.int64)

    def _fields(self) -> Dict[str, str]:
        return {"until_field": self.until_field,
                "carry_field": self.carry_field,
                "value_field": self.value_field,
                "result_field": self.result_field}

    # -- ingest ------------------------------------------------------------
    def process_batch(self, keys, ts, left, data: Dict[str, np.ndarray],
                      valid=None) -> None:
        ph, detail = self.phases.phase, self.phases.detail
        with self.phases.span("window.key_scan"):
            with detail("prepare"):
                self.state_version += 1
                keys = np.asarray(keys, np.int64)
                ts = np.asarray(ts, np.int64)
                left = np.asarray(left, bool)
                cols = [np.asarray(data[f]) for f in (
                    self.until_field, self.carry_field, self.value_field)]
                if valid is not None and not np.all(valid):
                    valid = np.asarray(valid, bool)
                    keys, ts, left = keys[valid], ts[valid], left[valid]
                    cols = [c[valid] for c in cols]
                n = len(keys)
                if not n:
                    return
                if self._base is None:
                    self._base = int(ts.min())
            with detail("assign"):
                slots = self.directory.assign(keys)
                self.prof["assign_records"] = self.directory.assign_records
                self.prof["assign_memo_hits"] = \
                    self.directory.assign_memo_hits
            with detail("slot_mask"):
                bad = slots < 0
                if bad.any():
                    account_full_drop(self, int(bad.sum()))
            ph("window.pack")
            until, carry, value = cols
            # the left rows are few (3 in 49 of the suite's stream):
            # their columns are narrowed where they are, not row by row
            li = np.flatnonzero(left)
            t32, over = _narrow(ts, self._base)
            val32, o = _narrow(value, 0)    # a right row's value
            if o is not None:               # a left row's says nothing
                o[li] = False
                over = o if over is None else over | o
            c32 = np.zeros(n, np.int32)
            # a left row's until rides the value's column
            for col, src, shift in ((val32, until, self._base),
                                    (c32, carry, 0)):
                col[li], o = _narrow(src[li], shift)
                if o is not None:
                    over = np.zeros(n, bool) if over is None else over
                    over[li[o]] = True
            key2 = slots.astype(np.int32)
            key2 <<= 1
            key2 |= ~left
            if over is not None:
                # a row on a full directory was counted there
                self.counters["lane_overflow"] += int((over & ~bad).sum())
                key2[over] = NO_CELL
            key2[bad] = NO_CELL
            size = _batch_size(n)
            up = [key2, t32, val32, c32]
            if size != n:
                pad = np.zeros(size - n, np.int32)
                up = [np.concatenate([key2, pad + NO_CELL])] + [
                    np.concatenate([c, pad]) for c in up[1:]]
            ph("window.h2d")
            up = [jnp.asarray(c) for c in up]
            ph("window.step_dispatch")
            cap = min(apply_chunk(size), size)
            with self._state_lock:
                self.state, head, rows, full = _JIT_JOIN_APPLY(
                    self.state, *up, slots=self.slots, cap=cap)
            ring = self.emit_ring
            cohort = {"window_ends": [int(ts.max()) + 1],
                      "t_fire": time.perf_counter()}
            with ring.lock:
                ring.version_no += 1
                ring.announce((head, rows))
                self._tails[ring.version_no] = (
                    full, (keys, ts, left, until, carry, value))
                ring.stamp(cohort)
                self._pending = FiredWindows(
                    op=self, ring=True, ring_no=ring.version_no,
                    cohort=cohort)
            self.counters["batches"] += 1
            self._inflight.append(head)
            if not self.external_throttle:
                ph("ingest.throttle")
                self.throttle()

    def take_fired(self) -> Optional[FiredWindows]:
        """The marker of the batch just folded in: its changelog is the
        drain's to fetch."""
        fired, self._pending = self._pending, None
        return fired

    def throttle(self) -> None:
        while len(self._inflight) > self.max_inflight_steps:
            ready_wait(self._inflight.popleft())

    def quiesce(self) -> None:
        while self._inflight:
            ready_wait(self._inflight.popleft())

    # -- time --------------------------------------------------------------
    def advance_watermark(self, wm: int) -> FiredWindows:
        if wm > self.watermark:
            self.watermark = wm
        return self._empty()

    def final_watermark(self) -> int:
        return self.watermark if self.watermark != LONG_MIN else 0

    # -- the changelog: the drain's side ---------------------------------------
    def _empty(self) -> FiredWindows:
        return FiredWindows(data=empty_rows(self.carry_field,
                                            self.result_field))

    def _hand_over(self, slots: np.ndarray) -> None:
        """The keys of ``slots`` are the host lane's from here on: its
        state of each is the strip the device left (the rows that
        waited in its lanes)."""
        if self._side is None:
            self._side = HostKeyedJoinOperator(
                num_shards=SIDE_SHARDS, slots_per_shard=SIDE_SLOTS,
                **self._fields())
        with self._state_lock:
            strips = np.asarray(self.state[:, jnp.asarray(slots)])
        keys = self.directory.key_of_slots(slots.astype(np.int64))
        self._seed_side(keys, _wide(strips[8], self._base), np.stack([
            np.repeat(np.arange(len(keys)), EARLY_LANES),
            _wide(strips[[4, 6]].T.ravel(), self._base),
            _wide(strips[[5, 7]].T.ravel())]))
        self.counters["pending_overflow"] += len(keys)

    def _seed_side(self, keys, newest, waiting) -> None:
        """``waiting``: (index into ``keys``, time, value) rows."""
        side = self._side
        at = side.directory.assign(keys)
        if (at < 0).any():
            raise RuntimeError(
                "join: more keys handed over to the host lane than it "
                f"holds ({SIDE_SHARDS} x {SIDE_SLOTS})")
        side.newest[at] = newest
        waiting = waiting[:, waiting[1] != NONE64]
        waiting[0] = at[waiting[0]]
        side.pending = np.concatenate([side.pending, waiting], axis=1)
        self._side_keys = np.union1d(self._side_keys, keys)

    def _decode(self, no: int, body: np.ndarray, raw: tuple
                ) -> Optional[Dict[str, np.ndarray]]:
        """The changelog of mini-batch ``no`` from its entries."""
        slot, carry, old, new, newest = body
        hand = carry == HANDED
        if hand.any():
            self._hand_over(slot[hand])
            # the device counted their rows of this batch as parked, and
            # the host lane is about to
            self.counters["rights_parked"] -= int((~raw[2] & np.isin(
                raw[0], self.directory.key_of_slots(
                    slot[hand].astype(np.int64)))).sum())
        ch = (new != old) & ~hand
        n = int(ch.sum())
        parts = []
        if n:
            parts.append(changelog_rows(
                self.directory.key_of_slots(slot[ch].astype(np.int64)),
                carry[ch].astype(np.int64), _wide(old[ch]),
                new[ch].astype(np.int64), _wide(newest[ch], self._base),
                no, self.carry_field, self.result_field))
        if len(self._side_keys):
            keys, ts, left, until, carry_, value = raw
            m = np.isin(keys, self._side_keys)
            side = self._side
            side.minibatch = no - 1
            was = dict(side.counters)
            side.process_batch(keys[m], ts[m], left[m], {
                self.until_field: until[m], self.carry_field: carry_[m],
                self.value_field: value[m]})
            for name in ("rights_matched", "rights_refused",
                         "rights_parked", "lanes_matched", "lanes_refused",
                         "keys_changed"):
                self.counters[name] += side.counters[name] - was[name]
            if side._fired is not None:
                parts.append(side._fired)
        if not parts:
            return None
        rows = concat_rows(parts)
        self.counters["changelog_rows"] += int(
            (rows[OP_FIELD] != OP_UPDATE_BEFORE).sum())
        self.counters["rows_emitted"] += len(rows["key"])
        return rows

    def drain_ring(self, min_no: Optional[int] = None
                   ) -> Dict[str, np.ndarray]:
        """Fetch the entries of every batch announced since the last
        drain (a periodic poll, ``min_no`` 0: those that have landed,
        and the oldest if none has) and decode them, mini-batch by
        mini-batch."""
        ring = self.emit_ring
        with ring.lock:
            need = ring.version_no if min_no is None else min_no
            wanted = ring.take_wanted(min_no == 0)
            bufs, no_read = ring.fetch_unread(opportunistic=(min_no == 0))
            first = (no_read or 0) - len(bufs) + 1
            tails = [self._tails.pop(no)
                     for no in range(first, (no_read or 0) + 1)]
        if no_read is None:
            return self._empty().materialize()
        with self.phases.span("drain.fetch", ring=need) as fetch:
            ready_wait(bufs)
            t_ready = wanted.t_landed if wanted else time.perf_counter()
            bodies = []
            for (head, rows), (tail, _raw) in zip(bufs, tails):
                h = np.asarray(head)
                n, cap = int(h[H_CELLS]), rows.shape[1]
                self.prof["apply_cells"] += n
                self.prof["apply_records"] += int(h[H_ROWS])
                self.prof["apply_trips"] += int(h[H_TRIPS])
                for name, at in (("lefts_in", H_LEFTS),
                                 ("rights_in", H_RIGHTS),
                                 ("rights_matched", H_MATCHED),
                                 ("rights_refused", H_REFUSED),
                                 ("rights_parked", H_PARKED),
                                 ("lanes_refused", H_LANES_REFUSED),
                                 ("lanes_matched", H_LANES_MATCHED),
                                 ("keys_changed", H_CHANGED)):
                    self.counters[name] += int(h[at])
                parts = [np.asarray(rows)[:, :min(n, cap)]]
                parts.extend(np.asarray(tail[:, lo:min(lo + cap, n)])
                             for lo in range(cap, n, cap))
                bodies.append(parts[0] if len(parts) == 1
                              else np.concatenate(parts, axis=1))
        with ring.lock:
            ring.deliver_stamps(no_read, wanted.t_want if wanted
                                else fetch.t0, t_ready, fetch.t1)
        self.prof["drain_fetch"] += fetch.seconds
        self.prof["drain_fetches"] += 1
        out = [rows for rows in (
            self._decode(first + i, body, tails[i][1])
            for i, body in enumerate(bodies)) if rows is not None]
        ring.note_decoded(no_read)
        if not out:
            return self._empty().materialize()
        return concat_rows(out)

    def take_delivered_fires(self) -> List[Dict[str, Any]]:
        return self.emit_ring.take_delivered()

    # -- what the job reports ------------------------------------------------
    def hbm_bytes(self) -> int:
        """The state's words (the chip lays nine out as sixteen)."""
        return self.slots * 4 * WORDS

    def state_counters(self) -> Dict[str, Any]:
        return join_counters(self.counters, self.directory, on_host=0)

    # -- snapshot: HostKeyedJoinOperator's format ----------------------------
    def snapshot_state(self) -> Dict[str, Any]:
        """Called with the drain flushed (every batch decoded)."""
        self.quiesce()
        with self._state_lock:
            w = np.asarray(self.state)
        base = self._base or 0
        gone = np.flatnonzero(w[0] == HANDED)
        lanes_t = _wide(w[[4, 6]], base)
        lanes_v = _wide(w[[5, 7]])
        held = (lanes_t != NONE64) & (w[0] != HANDED)[None]
        slot = np.broadcast_to(np.arange(self.slots), held.shape)
        snap = {"kind": "keyed_join",
                "directory": self.directory.snapshot(),
                "carry": np.where(w[0] == HANDED, NONE64, _wide(w[0])),
                "lo": _wide(w[1], base), "hi": _wide(w[2], base),
                "result": _wide(w[3]), "newest": _wide(w[8], base),
                "pending": np.stack([slot[held], lanes_t[held],
                                     lanes_v[held]]),
                "minibatch": self.emit_ring.version_no,
                "time_base": self._base, "watermark": self.watermark,
                "counters": dict(self.counters),
                "records_dropped_full": self.records_dropped_full}
        if len(gone):
            # a handed-over key's state is the host lane's, under the
            # key's own slot here: one format
            side = self._side
            at = side.directory.assign(self.directory.key_of_slots(gone))
            for name in ("carry", "lo", "hi", "result", "newest"):
                snap[name][gone] = getattr(side, name)[at]
            theirs = side.pending.copy()
            theirs[0] = _remap(theirs[0], at, gone)
            snap["pending"] = np.concatenate([snap["pending"], theirs],
                                             axis=1)
        return snap

    def restore_state(self, snap: Dict[str, Any]) -> None:
        self._inflight.clear()
        self._tails.clear()
        self._pending = None
        self.emit_ring.reset()
        self.emit_ring.version_no = int(snap["minibatch"])
        self.emit_ring.read_no = self.emit_ring.version_no
        self.emit_ring.fires_decoded = self.emit_ring.version_no
        self.directory = KeyDirectory.restore(
            self.directory.num_shards, self.directory.slots_per_shard,
            snap["directory"],
            (self.directory.shard_lo, self.directory.shard_hi))
        carry, lo, hi, result, newest = (np.asarray(snap[k], np.int64) for k
                                         in ("carry", "lo", "hi", "result",
                                             "newest"))
        self._base = snap.get("time_base")
        if self._base is None and (newest != NONE64).any():
            self._base = int(newest[newest != NONE64].min())
        base = self._base or 0

        def word(x, shift=0):
            return np.where(x == NONE64, NONE, x - shift).astype(np.int32)

        w = np.full((WORDS, self.slots), NONE, np.int32)
        w[0], w[1], w[2] = word(carry), word(lo, base), word(hi, base)
        w[3], w[8] = word(result), word(newest, base)
        # the waiting rows back into the lanes, by (slot, time); a slot
        # with more than the lanes hold is the host lane's again
        self._side, self._side_keys = None, np.zeros(0, np.int64)
        p = np.asarray(snap["pending"], np.int64).reshape(3, -1)
        if p.shape[1]:
            order = np.lexsort((p[1], p[0]))
            p = p[:, order]
            new = np.r_[True, (p[0, 1:] != p[0, :-1]) | (p[1, 1:] != p[1, :-1])]
            start = np.flatnonzero(new)
            ps, pt = p[0, start], p[1, start]
            pv = np.maximum.reduceat(p[2], start)
            rank = np.arange(len(ps)) - np.searchsorted(ps, ps)
            many = np.unique(ps[rank >= EARLY_LANES])
            fits = ~np.isin(ps, many)
            for r in range(EARLY_LANES):
                m = fits & (rank == r)
                w[4 + 2 * r, ps[m]] = (pt[m] - base).astype(np.int32)
                w[5 + 2 * r, ps[m]] = pv[m].astype(np.int32)
            if len(many):
                self._side = HostKeyedJoinOperator(
                    num_shards=SIDE_SHARDS, slots_per_shard=SIDE_SLOTS,
                    **self._fields())
                m = ~fits
                self._seed_side(
                    self.directory.key_of_slots(many), newest[many],
                    np.stack([np.searchsorted(many, ps[m]), pt[m], pv[m]]))
                w[0, many] = HANDED
        self.state = jnp.asarray(w)
        self.watermark = snap["watermark"]
        self.counters = {**dict.fromkeys(COUNTERS, 0), **snap["counters"]}
        self.records_dropped_full = snap.get("records_dropped_full", 0)


def _remap(values: np.ndarray, src: np.ndarray, dst: np.ndarray) -> np.ndarray:
    """``dst[i]`` for each value equal to ``src[i]``."""
    order = np.argsort(src)
    return dst[order[np.searchsorted(src[order], values)]]
