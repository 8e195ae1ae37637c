"""Session windows with the session state on the device.

The lane of ``ops/session.py`` for a job without retraction, allowed
lateness or a mesh (``device_lane_fits``; the driver chooses, no option
does), and total for every job it accepts: before a batch is folded in
the host bounds, from the watermark, the batch's span and the device's
own count at the last fire, how many sessions one key can then hold
open (``_lanes_for``); the slots grow
lanes to that (``_grow``), and past ``MAX_LANES``, as for a span the
int32 offsets cannot hold, the operator hands its open sessions to the
host registry in their snapshot's format and runs the rest of the job
there (``_to_registry``), the rows already fired leaving through the
ring as before. Semantics are the host registry's, row for row: event (k, t)
opens [t, t + gap); windows of one key merge when they intersect
(events t1 <= t2 share a session iff t2 - t1 <= gap, transitively); a
session [min t, max t + gap) fires once when ``watermark >= max t + gap
- 1``; a record is late iff its own window is dead AND it merges into
no session still open.

State: per key slot ``lanes`` session lanes of (start, last, count and
the aggregate's lifted sum / max / min columns), every column a flat
1-D device array indexed ``lane * slots + slot`` (a 2-D array with a
minor dimension of 2 would be padded to 128 on the chip). Timestamps on
the device are int32 offsets from the first batch's earliest (a job
whose event time leaves ``+-(2^31 - 2 gap)`` ms of it goes to the
registry, never wraps).

Per batch ONE program (``session_apply_kernel``): sort by (slot, ts),
cut runs where the slot changes or the gap is exceeded, reduce each run
(``ops/window.py``'s segmented scan), bring the run heads to the front
(a second sort) and merge the runs into their slots' lanes, a chunk of
distinct slots a trip: a run joins the open session it intersects,
bridges two that it connects, or opens a free lane. Per advance a fire
program over all lanes (``session_fire_kernel``): ``last + gap - 1 <=
watermark``, ``first_true_indices`` to at most ``FIRE_CAP`` rows, their
columns into a buffer of the pass's own, their lanes cleared; when more
rows are due than a pass holds, further passes run until none is.

The host keeps the key directory and one int64 a slot (the newest
timestamp noted, for release) and sorts nothing per record. Fired rows
leave as ``WindowOperator``'s do: a ``FiredWindows`` with a cohort
through the driver's drain, which fetches the passes' buffers
(``EmitRing.fetch_unread``), decodes slot -> key and hands the (slot,
last) pairs back; the loop's thread then releases every key whose
fired session was its newest and that no batch has touched since, under
the window operator's reuse rule (``state/keyed.py`` ``ReuseRule``).
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from flink_tpu.hostsync import ready_wait
from flink_tpu.obs.tracing import PhaseClock
from flink_tpu.ops.aggregates import LaneAggregate, probe_finalize
from flink_tpu.ops.emit_ring import EmitRing
from flink_tpu.ops.window import (
    LANE_OPS, FiredWindows, _empty_fired, _run_scan, first_true_indices)
from flink_tpu.state.keyed import (
    KeyDirectory, ReuseRule, account_full_drop, note_newest)
from flink_tpu.time.watermarks import LONG_MIN

NO_SLOT = np.iinfo(np.int32).max    # sorts past every real slot
I32_MAX = np.iinfo(np.int32).max
I64_MAX = np.iinfo(np.int64).max
# open sessions a slot's state can grow to hold. The kernels unroll over
# the lanes; the benchmark's cell and most jobs run at 2, a shape of 3
# or 4 compiles when a batch first needs it (``_grow``)
MAX_LANES = 4
MAX_GAP_MS = 1 << 29                # the int32 offsets need 2 gap of room
FIRE_CAP = 32768                    # rows one fire pass can emit (tests patch)
HEAD_WORDS = 8                      # a pass's header (session_fire_kernel)
LANE_FILL = {"sums": 0.0, "maxs": -np.inf, "mins": np.inf}


def session_lanes(span_ms: int, gap_ms: int) -> int:
    """Sessions ONE key can hold whose last events lie within
    ``span_ms`` of each other: the lasts of two are more than a gap
    apart (none where the span is negative)."""
    return 0 if span_ms < 0 else int(span_ms) // (int(gap_ms) + 1) + 1


def lanes_needed(gap_ms: int, max_out_of_orderness_ms: int) -> int:
    """Unfired sessions one key can hold right after an advance: their
    lasts lie in ``[watermark - gap + 2, watermark + delay]``."""
    return session_lanes(
        max(int(max_out_of_orderness_ms), 0) + int(gap_ms) - 2, gap_ms)


def device_lane_fits(*, gap_ms: int, agg: Any, allowed_lateness_ms: int,
                     retract: bool, mesh: bool,
                     max_out_of_orderness_ms: int, slots: int) -> bool:
    """Whether a session job starts with its state on the device: no
    retract rows, no re-fires within allowed lateness, one device, a
    lane aggregate, and the sessions a key holds open between advances
    fit the lanes a slot can have. Everything else keeps the host
    registry. (What the DATA then asks for is met batch by batch:
    ``DeviceSessionOperator._fit``.)"""
    return (not retract and int(allowed_lateness_ms) == 0 and not mesh
            and isinstance(agg, LaneAggregate)
            and 0 < int(gap_ms) <= MAX_GAP_MS
            and lanes_needed(gap_ms, max_out_of_orderness_ms) <= MAX_LANES
            and MAX_LANES * int(slots) < (1 << 30))


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class SessionState:
    """Device-resident session lanes; every array is ``(lanes * slots,)``,
    lane-major. ``count`` 0 = the lane is free (its other columns then
    hold nothing that counts). ``sums`` / ``maxs`` / ``mins``: one
    array per lane of the aggregate's width, possibly none."""

    start: jax.Array     # i32 offset of the session's first event
    last: jax.Array      # i32 offset of its last event; end = last + gap
    count: jax.Array     # i32 records
    sums: Tuple[jax.Array, ...]
    maxs: Tuple[jax.Array, ...]
    mins: Tuple[jax.Array, ...]

    def tree_flatten(self):
        return (self.start, self.last, self.count, self.sums, self.maxs,
                self.mins), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def init_session_state(agg: LaneAggregate, lanes: int,
                       slots: int) -> SessionState:
    n = lanes * slots
    widths = {"sums": agg.sum_width, "maxs": agg.max_width,
              "mins": agg.min_width}
    return SessionState(
        start=jnp.zeros(n, jnp.int32), last=jnp.zeros(n, jnp.int32),
        count=jnp.zeros(n, jnp.int32),
        **{name: tuple(jnp.full(n, LANE_FILL[name], jnp.float32)
                       for _ in range(w)) for name, w in widths.items()})


def session_chunk(batch: int) -> int:
    """Runs ONE trip of the apply's merge loop can take, from the
    batch's shape alone: a batch names far fewer keys than records (the
    suite's 2^20 bids ~24,000), so a thirty-second of it, the whole of
    a small batch."""
    return max(batch // 32, min(batch, 1024))


def _set(arr, idx, vals):
    return arr.at[idx].set(vals, indices_are_sorted=True,
                           unique_indices=True, mode="drop")


def session_apply_kernel(
    state: SessionState,
    slot: jax.Array,        # (B,) i32; < 0 = the record takes part in nothing
    ts: jax.Array,          # (B,) i32 offsets
    wm: jax.Array,          # () i32 offset of the watermark
    data: Dict[str, jax.Array],
    *,
    agg: LaneAggregate,
    gap: int,
    lanes: int,
    slots: int,
) -> Tuple[SessionState, jax.Array]:
    """Fold one batch into the session lanes. Returns the state and the
    int32 report [runs, records, opened, merged, overflow, late, trips,
    extra rounds], which no later step donates."""
    batch = slot.shape[0]
    g = jnp.int32(gap)
    n_flat = lanes * slots
    valid = slot >= 0
    own = jnp.where(valid, slot, 0)

    # late: the record's own window is dead and no open session of its
    # key takes it in. The gathers (a lane's three columns a record) run
    # only in a batch that holds a dead record.
    dead = valid & (ts <= wm - (g - 1))

    def unrescued(_):
        hit = jnp.zeros(batch, bool)
        for l in range(lanes):
            f = l * slots + own
            hit |= ((state.count[f] > 0) & (state.start[f] <= ts + g)
                    & (ts <= state.last[f] + g))
        return dead & ~hit

    late = lax.cond(jnp.any(dead), unrescued,
                    lambda _: jnp.zeros(batch, bool), None)
    valid = valid & ~late
    n_late = jnp.sum(late, dtype=jnp.int32)

    lifted = dict(zip((n for n, _, _ in LANE_OPS),
                      agg.lift_masked(data, valid)))
    widths = {n: len(getattr(state, n)) for n, _, _ in LANE_OPS}
    cols = [lifted[n][:, j] for n, _, _ in LANE_OPS for j in range(widths[n])]
    key = jnp.where(valid, slot, NO_SLOT)
    # stable where lanes ride along: a run's records keep their arrival
    # order among equal timestamps, so its float sum depends on them alone
    key, t, *cols = lax.sort((key, ts, *cols), num_keys=2,
                             is_stable=len(cols) > 0)
    live = key != NO_SLOT
    n_records = jnp.sum(live, dtype=jnp.int32)
    first = jnp.concatenate([
        jnp.ones(1, bool),
        (key[1:] != key[:-1]) | (t[1:] - t[:-1] > g)])
    head = first & live
    n_runs = jnp.sum(head, dtype=jnp.int32)
    scans, c0 = {}, 0
    for name, op, _ in LANE_OPS:
        scans[name] = [_run_scan(op, first, c)
                       for c in cols[c0:c0 + widths[name]]]
        c0 += widths[name]

    # the run heads to the front, in (slot, ts) order
    pos = jnp.arange(batch, dtype=jnp.int32)
    r_first, r_slot, r_start = lax.sort(
        (jnp.where(head, pos, batch), key, t), num_keys=1, is_stable=False)
    in_run = pos < n_runs
    r_end = jnp.where(pos < n_runs - 1,
                      jnp.concatenate([r_first[1:], r_first[:1]]) - 1,
                      n_records - 1)
    # several runs of one slot (a gap inside the batch) meet its lanes
    # one after the other: a round per rank, ranks sorted apart
    dup = in_run & (pos > 0) & (r_slot == jnp.concatenate(
        [r_slot[:1], r_slot[:-1]]))
    rank0 = jnp.where(in_run, jnp.int32(0), jnp.int32(NO_SLOT))

    def by_rank(_):
        rank = pos - lax.cummax(jnp.where(dup, 0, pos))
        return lax.sort((jnp.where(in_run, rank, jnp.int32(NO_SLOT)), r_slot,
                         r_first, r_start, r_end), num_keys=2)

    r_rank, r_slot, r_first, r_start, r_end = lax.cond(
        jnp.any(dup), by_rank,
        lambda _: (rank0, r_slot, r_first, r_start, r_end), None)

    chunk = session_chunk(batch)
    lane_i = jnp.arange(chunk, dtype=jnp.int32)

    def padded(x, fill):
        return jnp.concatenate([x, jnp.full(chunk, fill, x.dtype)])

    r_rank, r_slot = padded(r_rank, NO_SLOT), padded(r_slot, 0)
    r_first, r_start, r_end = (padded(x, 0)
                               for x in (r_first, r_start, r_end))

    def trip(carry):
        state, done, stats = carry

        def sl(x):
            return lax.dynamic_slice(x, (done,), (chunk,))

        rk, sl_, fi, rs, en = (sl(x) for x in (
            r_rank, r_slot, r_first, r_start, r_end))
        # the chunk's runs of its first run's rank: a prefix, of
        # ascending distinct slots
        mine = (done + lane_i < n_runs) & (rk == rk[0])
        en = jnp.clip(en, 0, batch - 1)
        rl = t[en]
        m_start, m_last, m_count = rs, rl, en - fi + 1
        m_lanes = {name: [c[en] for c in scans[name]] for name in scans}
        at = jnp.where(mine, sl_, 0)
        got, hits, free = [], [], []
        for l in range(lanes):
            f = l * slots + at
            s0, e0, c0 = state.start[f], state.last[f], state.count[f]
            ln = {name: [a[f] for a in getattr(state, name)]
                  for name in scans}
            hit = (c0 > 0) & (rs <= e0 + g) & (s0 <= rl + g)
            m_start = jnp.where(hit, jnp.minimum(m_start, s0), m_start)
            m_last = jnp.where(hit, jnp.maximum(m_last, e0), m_last)
            m_count = m_count + jnp.where(hit, c0, 0)
            for name, op, _ in LANE_OPS:
                m_lanes[name] = [jnp.where(hit, op(m, x), m) for m, x
                                 in zip(m_lanes[name], ln[name])]
            got.append((s0, e0, c0, ln))
            hits.append(hit)
            free.append(c0 == 0)
        any_hit = jnp.any(jnp.stack(hits), axis=0)
        # the merged session takes the first lane it joined, else the
        # first free one
        target = jnp.where(any_hit, jnp.argmax(jnp.stack(hits), axis=0),
                           jnp.argmax(jnp.stack(free), axis=0))
        placed = mine & (any_hit | jnp.any(jnp.stack(free), axis=0))
        for l in range(lanes):
            s0, e0, c0, ln = got[l]
            is_t = placed & (target == l)
            gone = mine & hits[l] & ~is_t      # bridged into the target
            idx = jnp.where(mine, l * slots + sl_, n_flat + lane_i)
            state = SessionState(
                start=_set(state.start, idx, jnp.where(is_t, m_start, s0)),
                last=_set(state.last, idx, jnp.where(is_t, m_last, e0)),
                count=_set(state.count, idx, jnp.where(
                    is_t, m_count, jnp.where(gone, 0, c0))),
                **{name: tuple(
                    _set(a, idx, jnp.where(is_t, m, x)) for a, m, x in zip(
                        getattr(state, name), m_lanes[name], ln[name]))
                   for name in scans})
        n_mine = jnp.sum(mine, dtype=jnp.int32)
        stats = stats + jnp.stack([
            jnp.sum(placed & ~any_hit, dtype=jnp.int32),
            jnp.sum(mine & any_hit, dtype=jnp.int32),
            jnp.sum(mine & ~placed, dtype=jnp.int32),
            jnp.int32(1)])
        return state, done + n_mine, stats

    state, _, stats = lax.while_loop(
        lambda c: c[1] < n_runs, trip,
        (state, jnp.int32(0), jnp.zeros(4, jnp.int32)))
    rounds = jnp.max(jnp.where(r_rank == NO_SLOT, 0, r_rank))
    return state, jnp.concatenate([
        jnp.stack([n_runs, n_records]), stats[:3],
        jnp.stack([n_late, stats[3], rounds])])


def session_fire_kernel(
    state: SessionState,
    wm: jax.Array,          # () i32 offset of the watermark
    *,
    agg: LaneAggregate,
    gap: int,
    lanes: int,
    slots: int,
    cap: int,
) -> Tuple[SessionState, jax.Array, jax.Array]:
    """One fire pass: the first ``cap`` due sessions in (lane, slot)
    order leave their lanes. Returns the state, the pass's rows
    ``(4 + result fields, cap)`` int32 (slot, start, last, count, the
    finalized fields bit for bit; the rows past the emitted count hold
    nothing) and its header [emitted, due before the pass, sessions
    live after it, slots with two or more live after it, the most any
    slot holds after it, 0...]."""
    n_flat = lanes * slots
    due = (state.count > 0) & (state.last + jnp.int32(gap - 1) <= wm)
    n_due = jnp.sum(due, dtype=jnp.int32)
    idx = first_true_indices(due, cap).astype(jnp.int32)
    ok = idx < n_flat
    at = jnp.where(ok, idx, 0)
    counts = jnp.where(ok, state.count[at], 0)
    # (cap, width) of each lane family, width 0 included
    res = agg.finalize(
        *(jnp.concatenate(
            [jnp.zeros((cap, 0), jnp.float32)]
            + [a[at][:, None] for a in getattr(state, name)], axis=1)
          for name, _, _ in LANE_OPS), counts)

    def as_i32(f):
        f = jnp.asarray(f)
        if jnp.issubdtype(f.dtype, jnp.integer):
            return f.astype(jnp.int32)
        return lax.bitcast_convert_type(f.astype(jnp.float32), jnp.int32)

    rows = jnp.stack(
        [at % slots, state.start[at], state.last[at], counts]
        + [as_i32(res[k]) for k in sorted(res) if k != "count"])
    count = _set(state.count,
                 jnp.where(ok, idx, n_flat + jnp.arange(cap, dtype=jnp.int32)),
                 jnp.zeros(cap, jnp.int32))
    held = jnp.sum((count > 0).reshape(lanes, slots), axis=0,
                   dtype=jnp.int32)
    head = jnp.zeros(HEAD_WORDS, jnp.int32).at[:5].set(jnp.stack([
        jnp.minimum(n_due, cap), n_due, jnp.sum(held, dtype=jnp.int32),
        jnp.sum(held >= 2, dtype=jnp.int32), jnp.max(held)]))
    return dataclasses.replace(state, count=count), rows, head


_JIT_SESSION_APPLY = jax.jit(
    session_apply_kernel,
    static_argnames=("agg", "gap", "lanes", "slots"), donate_argnums=(0,))
_JIT_SESSION_FIRE = jax.jit(
    session_fire_kernel,
    static_argnames=("agg", "gap", "lanes", "slots", "cap"),
    donate_argnums=(0,))


class DeviceSessionOperator(ReuseRule):
    """Keyed event-time session aggregation, the state on the device
    (module docstring). The surface the driver and the checkpointing
    use is ``SessionOperator``'s; towards the drain it is
    ``WindowOperator``'s (``emit_ring``, ``drain_ring``,
    ``take_delivered_fires``, ``run_pending_release``)."""

    retract = False
    lateness = 0

    def __init__(
        self,
        gap_ms: int,
        agg: LaneAggregate,
        *,
        num_shards: int = 128,
        slots_per_shard: int = 1024,
        max_out_of_orderness_ms: int = 0,
        max_inflight_steps: int = 3,
        host_pool: Optional[Any] = None,
    ) -> None:
        if gap_ms <= 0:
            raise ValueError("session gap must be positive")
        self.gap = int(gap_ms)
        self.agg = agg
        self.directory = KeyDirectory(num_shards, slots_per_shard)
        self.slots = self.directory.local_slots
        # two to start with: a batch that crosses one gap of a key's
        # time needs the second
        self.lanes = max(2, lanes_needed(gap_ms, max_out_of_orderness_ms))
        if not device_lane_fits(
                gap_ms=gap_ms, agg=agg, allowed_lateness_ms=0, retract=False,
                mesh=False, max_out_of_orderness_ms=max_out_of_orderness_ms,
                slots=self.slots):
            raise ValueError(
                "this session job does not fit the device lane "
                "(device_lane_fits); it runs on ops/session.py's registry")
        # what the registry needs, should the job's sessions go there
        self._delay = max(int(max_out_of_orderness_ms), 0)
        self._host_pool = host_pool
        self._registry: Optional[Any] = None
        self.why_registry: Optional[str] = None
        # device timestamps are ``ts - _base`` (the first batch's
        # earliest), held to +-_span so that no sum with the gap wraps
        self._base: Optional[int] = None
        self._span = I32_MAX - 2 * self.gap - 2
        self.state = init_session_state(agg, self.lanes, self.slots)
        self._kw = dict(agg=agg, gap=self.gap, lanes=self.lanes,
                        slots=self.slots)
        self.watermark = LONG_MIN
        self.late_records = 0
        self.records_dropped_full = 0
        self.state_version = 0
        self.phases = PhaseClock()
        self.prof: Dict[str, float] = collections.defaultdict(float)
        # bounded in-flight dispatch, as WindowOperator's: an advance
        # waits for its own fire, so the deque stays one deep unless
        # batches arrive without a watermark between them
        self.max_inflight_steps = int(max_inflight_steps)
        self.external_throttle = False
        self._inflight: collections.deque = collections.deque()
        self._step_reports: collections.deque = collections.deque()
        # fired rows on their way out (ops/emit_ring.py): a version a
        # fire pass, each with rows of its own
        self.emit_ring = EmitRing(keep=None)
        # newest timestamp noted per slot (release: a fired session
        # whose ``last`` equals it was its key's newest, and nothing has
        # touched the key since)
        self._newest = np.full(self.slots, LONG_MIN, np.int64)
        self._min_ts, self._max_ts = I64_MAX, LONG_MIN
        # no key holds more open sessions than this: the device's own
        # count after a fire, the bounds of the batches since added
        self._may_hold = 0
        # (slots, lasts) of decoded fired rows, from the drain's thread
        # to the loop's, which alone writes the directory
        self._release_q: collections.deque = collections.deque()
        self._init_reuse()      # state/keyed.py ReuseRule
        self.releases = 0
        # the finalized fields a fired row carries past (slot, start,
        # last, count), in the fire kernel's order, and which are ints
        res = probe_finalize(agg)
        self._fields = [k for k in sorted(res) if k != "count"]
        self._res_is_int = {k: np.issubdtype(np.asarray(res[k]).dtype,
                                             np.integer) for k in res}
        self.counters = {k: 0 for k in (
            "opened", "merged", "fired", "live", "live_peak",
            "second_lane_peak", "fire_passes", "fire_rows_max",
            "fire_advances", "lane_grows", "on_registry")}

    # -- ingest ------------------------------------------------------------
    def process_batch(self, keys, ts, data: Dict[str, np.ndarray],
                      valid=None) -> None:
        if self._registry is not None:
            return self._on_registry("process_batch", keys, ts, data, valid)
        self.run_pending_release()      # ahead of this batch's allocations
        ph, detail = self.phases.phase, self.phases.detail
        with self.phases.span("window.key_scan"):
            with detail("prepare"):
                self._return_released()
                self.state_version += 1
                keys = np.asarray(keys, np.int64)
                ts = np.asarray(ts, np.int64)
                if not len(ts):
                    return
                lo, hi = int(ts.min()), int(ts.max())
                if not self._fit(lo, hi):
                    # the registry's from here on, this batch included
                    return self.process_batch(keys, ts, data, valid)
                whole = valid is None
                valid = (np.ones(len(ts), bool) if whole
                         else np.asarray(valid, bool))
            with detail("assign"):
                slots = self.directory.assign(keys)
                self.prof["assign_records"] = self.directory.assign_records
                self.prof["assign_memo_hits"] = \
                    self.directory.assign_memo_hits
            with detail("slot_mask"):
                bad = valid & (slots < 0)
                if bad.any():
                    account_full_drop(self, int(bad.sum()))
                    valid = valid & ~bad
                    whole = False
            with detail("note_ts"):
                # a record whose own window is dead never becomes its
                # session's last (it is dropped, or joins a session
                # that ends later): it is not noted
                noted = valid
                if self.watermark != LONG_MIN \
                        and lo + self.gap - 1 <= self.watermark:
                    noted = valid & (ts + self.gap - 1 > self.watermark)
                note_newest(self._newest, slots, ts, noted)
                self._min_ts = min(self._min_ts, lo)
                self._max_ts = max(self._max_ts, hi)
            ph("window.pack")
            from flink_tpu.records import device_cast
            if self.agg.fields is not None:
                data = {k: data[k] for k in self.agg.fields}
            data = {k: device_cast(np.asarray(v)) for k, v in data.items()}
            slot32 = slots.astype(np.int32)
            if not whole:
                slot32[~valid] = -1
            ts32 = (ts - self._base).astype(np.int32)
            ph("window.h2d")
            dslot, dts = jnp.asarray(slot32), jnp.asarray(ts32)
            dwm = jnp.asarray(self._wm_offset(self.watermark))
            ddata = {k: jnp.asarray(v) for k, v in data.items()}
            ph("window.step_dispatch")
            self.state, report = _JIT_SESSION_APPLY(
                self.state, dslot, dts, dwm, ddata, **self._kw)
            report.copy_to_host_async()
            self._step_reports.append(report)
            self._inflight.append(report)
            if not self.external_throttle:
                ph("ingest.throttle")
                self.throttle()

    def _wm_offset(self, wm: int) -> np.int32:
        if wm == LONG_MIN or self._base is None:
            return np.int32(-self._span - self.gap)
        return np.int32(min(max(wm - self._base, -self._span - self.gap),
                            self._span + self.gap))

    # -- what the data asks of the lanes -----------------------------------
    def _lanes_for(self, lo: int, hi: int) -> int:
        """The most sessions one key can hold open once a batch whose
        timestamps span ``[lo, hi]`` is in, from what the host knows
        without looking at a record. An open session's last event is
        not below ``floor``: the watermark's fire took every session
        with ``last + gap - 1 <= watermark``, and a record whose own
        window is dead opens none; before any watermark, the earliest
        timestamp seen. Either all of a key's lasts, old and new, lie in
        one stretch up to the newest timestamp, or the batch's lie in
        its own beside what a key held before (``_may_hold``: counted
        by the device at the last fire): the smaller count holds."""
        g, wm = self.gap, self.watermark
        floor = wm - g + 2 if wm != LONG_MIN else min(self._min_ts, lo)
        return min(
            session_lanes(max(self._max_ts, hi) - floor, g),
            self._may_hold + session_lanes(hi - max(lo, floor), g))

    def _fit(self, lo: int, hi: int) -> bool:
        """Make the device lane hold a batch spanning ``[lo, hi]``
        before anything of it is folded in: more lanes a slot where a
        key may need them; the job's sessions to the registry (False)
        where ``MAX_LANES`` or the int32 offsets cannot hold it."""
        base = lo if self._base is None else self._base
        if lo - base < -self._span or hi - base > self._span:
            self._to_registry(
                f"timestamps [{lo}, {hi}] leave the +-{self._span} ms "
                f"around the job's first ({base}) that int32 offsets hold")
            return False
        need = self._lanes_for(lo, hi)
        if need > MAX_LANES:
            self._to_registry(
                f"a batch spanning [{lo}, {hi}] at watermark "
                f"{self.watermark} may leave a key {need} open sessions, "
                f"a slot holds {MAX_LANES}")
            return False
        self._base, self._may_hold = base, need
        if need > self.lanes:
            self._grow(need)
        return True

    def _grow(self, lanes: int) -> None:
        """More lanes a slot. The columns are lane-major, so the new
        lanes are appended and no session moves; the programs compile
        again for the new shape."""
        more = init_session_state(self.agg, lanes - self.lanes, self.slots)
        self.state = jax.tree_util.tree_map(
            lambda a, b: jnp.concatenate([a, b]), self.state, more)
        self.lanes = self._kw["lanes"] = lanes
        self.counters["lane_grows"] += 1

    def _to_registry(self, why: str,
                     snap: Optional[Dict[str, Any]] = None) -> None:
        """Hand the open sessions (``snap``: a snapshot being restored,
        else the state as it stands) to ``ops/session.py``'s registry,
        which runs the rest of the job; the rows fired so far are in
        the ring and leave through the drain as before."""
        from flink_tpu.ops.session import SessionOperator

        reg = SessionOperator(
            self.gap, self.agg, num_shards=self.directory.num_shards,
            slots_per_shard=self.directory.slots_per_shard,
            max_out_of_orderness_ms=self._delay, host_pool=self._host_pool)
        reg.restore_state(self.snapshot_state() if snap is None else snap)
        self.watermark, self.late_records = reg.watermark, reg.late_records
        self._registry, self.why_registry = reg, why
        self.state = None       # the lanes' memory goes back
        self.counters["on_registry"] = 1

    def _on_registry(self, method: str, *args):
        out = getattr(self._registry, method)(*args)
        self.state_version += 1
        self.watermark = self._registry.watermark
        self.late_records = self._registry.late_records
        return out

    def throttle(self) -> None:
        while len(self._inflight) > self.max_inflight_steps:
            ready_wait(self._inflight.popleft())
        self._resolve_reports(bound=self.max_inflight_steps)

    def quiesce(self) -> None:
        self.run_pending_release()
        while self._inflight:
            ready_wait(self._inflight.popleft())
        self._resolve_reports()

    def _resolve_reports(self, bound: int = 0) -> None:
        """Read the reports of retired applies into the counters. A run
        that found no lane is ``_lanes_for``'s fault (its bound did not
        hold): an error, never a dropped session."""
        while len(self._step_reports) > bound:
            (runs, records, opened, merged, overflow, late, trips,
             rounds) = (int(x) for x in np.asarray(
                 self._step_reports.popleft()))
            self.late_records += late
            self.counters["opened"] += opened
            self.counters["merged"] += merged
            self.prof["apply_runs"] += runs
            self.prof["apply_records"] += records
            self.prof["apply_trips"] += trips
            self.prof["apply_extra_rounds"] += rounds
            if overflow:
                raise RuntimeError(
                    f"session lanes overflow: {overflow} run(s) of a batch "
                    f"met a key whose {self.lanes} session lanes were all "
                    "open, which DeviceSessionOperator._lanes_for bounds "
                    "from above: a fault of this operator, not of the job")

    # -- time --------------------------------------------------------------
    def may_lead_advance(self) -> bool:
        """Asked once, when the job is built: can an advance of this
        operator ever go ahead of its batch (``lead_advance``)? Yes:
        what stands in the way (the hand-over to a registry) is known
        only batch by batch."""
        return True

    def lead_advance(self, wm: int, ts: np.ndarray) -> bool:
        """May the advance to ``wm`` go AHEAD of the batch with the
        timestamps ``ts`` (the driver's question, put before it pushes
        the batch that implied ``wm``)? Yes while the sessions are on
        the device, something was folded in, the watermark moves (any
        advance may close sessions: the device alone knows) and every
        record is stamped above ``wm + 1``. A session the advance closes
        has ``last + gap - 1 <= wm``, and a record joins a session up to
        ``ts = last + gap`` (``t2 - t1 <= gap``): the one stamped
        ``wm + 1`` may be exactly that far from a session the advance
        would close, and in the old order extends it. Past ``wm + 1``
        a record opens a session of its own in either order and is late
        under neither watermark: the same rows fire."""
        if (self._registry is not None or self._base is None
                or wm <= self.watermark):
            return False
        return int(ts.min()) > wm + 1

    def advance_watermark(self, wm: int) -> FiredWindows:
        """Advance event time and fire every session it completes, in
        as many passes as their number needs; returns once the last
        pass has been dispatched."""
        if self._registry is not None:
            return self._on_registry("advance_watermark", wm)
        if wm <= self.watermark:
            return self._empty()
        self.run_pending_release()
        self.state_version += 1
        self.watermark = wm
        if self._base is None:
            return self._empty()    # nothing was ever folded in
        ring = self.emit_ring
        with self.phases.span("window.fire_dispatch"):
            dwm = jnp.asarray(self._wm_offset(wm))
            cohort = {"window_ends": [int(wm) + 1],
                      "t_fire": time.perf_counter()}
            first = self._fire_pass(dwm)
            # how many rows are due the device alone knows: the first
            # pass's header says, and the advance is complete when none
            # is left (a record of the next batch must not meet a
            # session that has ended)
            self.phases.phase("ingest.throttle")
            emitted, due, live, two, self._may_hold = (
                int(x) for x in np.asarray(first[0])[:5])
            self._inflight.clear()      # everything before the fire is done
            self._resolve_reports()
            self.phases.phase("window.fire_dispatch")
            if due == 0:
                return self._empty()    # no version: nothing for the drain
            c = self.counters
            c["fire_advances"] += 1
            self._announce(first)
            more = -(-(due - emitted) // FIRE_CAP)    # ceil
            for _ in range(more):
                last = self._fire_pass(dwm)
                self._announce(last)
            if more:
                live, two, self._may_hold = (
                    int(x) for x in np.asarray(last[0])[2:5])
            c["fire_passes"] += 1 + more
            c["fired"] += due
            c["fire_rows_max"] = max(c["fire_rows_max"], due)
            c["live"] = live
            c["live_peak"] = max(c["live_peak"], live + due)
            c["second_lane_peak"] = max(c["second_lane_peak"], two)
            with ring.lock:
                ring.stamp(cohort)
                return FiredWindows(op=self, ring=True,
                                    ring_no=ring.version_no, cohort=cohort)

    def _fire_pass(self, dwm) -> Tuple[jax.Array, jax.Array]:
        """Dispatch one pass; its (header, rows), their copies begun."""
        self.state, rows, head = _JIT_SESSION_FIRE(
            self.state, dwm, cap=FIRE_CAP, **self._kw)
        head.copy_to_host_async()
        rows.copy_to_host_async()
        return head, rows

    def _announce(self, buf) -> None:
        """A pass that holds rows is the ring's next version."""
        ring = self.emit_ring
        with ring.lock:
            ring.version_no += 1
            ring.announce(buf)

    def take_fired(self):
        return None     # no per-step rows: retract mode is the registry's

    def final_watermark(self) -> int:
        if self._registry is not None:
            return self._registry.final_watermark()
        if self._max_ts == LONG_MIN:
            return self.watermark if self.watermark != LONG_MIN else 0
        return self._max_ts + self.gap + 1

    # -- fired rows: the drain's side ----------------------------------------
    def _empty(self) -> FiredWindows:
        if not hasattr(self, "_empty_cache"):
            self._empty_cache = _empty_fired(self.agg)
        return FiredWindows(data=dict(self._empty_cache))

    def drain_ring(self, min_no: Optional[int] = None
                   ) -> Dict[str, np.ndarray]:
        """Fetch every fire pass's buffer announced since the last
        drain (a periodic poll, ``min_no`` 0: those that have landed,
        and the oldest if none has) and decode their rows."""
        ring = self.emit_ring
        with ring.lock:
            need = ring.version_no if min_no is None else min_no
            # the driver's drain has waited for these passes already,
            # under no lock (EmitRing.await_landing): the fetch began
            # where it began to want them
            wanted = ring.take_wanted(min_no == 0)
            bufs, no_read = ring.fetch_unread(opportunistic=(min_no == 0))
        if no_read is None:
            return self._empty().materialize()
        with self.phases.span("drain.fetch", ring=need) as fetch:
            ready_wait(bufs)
            t_ready = wanted.t_landed if wanted else time.perf_counter()
            host = [(np.asarray(h), np.asarray(r)) for h, r in bufs]
        with ring.lock:
            ring.deliver_stamps(no_read, wanted.t_want if wanted
                                else fetch.t0, t_ready, fetch.t1)
        self.prof["drain_fetch"] += fetch.seconds
        self.prof["drain_fetches"] += 1
        body = np.concatenate([r[:, :int(h[0])] for h, r in host], axis=1)
        slot = body[0].astype(np.int64)
        start = body[1].astype(np.int64) + self._base
        last = body[2].astype(np.int64) + self._base
        out: Dict[str, np.ndarray] = {
            "key": self.directory.key_of_slots(slot),
            "window_start": start,
            "window_end": last + self.gap,
            "count": body[3],
        }
        for i, k in enumerate(self._fields):
            col = np.ascontiguousarray(body[4 + i])
            out[k] = col if self._res_is_int[k] else col.view(np.float32)
        # rows are keys now: the reuse rule may let go of slots released
        # up to the pass this fetch read through, and the loop's thread
        # may release the keys these rows were the last of
        ring.note_decoded(no_read)
        self._release_q.append((slot, last))
        return out

    def take_delivered_fires(self) -> List[Dict[str, Any]]:
        return self.emit_ring.take_delivered()

    # -- keys that leave, and the reuse rule ----------------------------------
    def run_pending_release(self) -> None:
        """On the loop's thread: release the keys whose newest session
        the drain has decoded and that nothing has touched since. A
        fired session whose ``last`` is its slot's newest noted
        timestamp was the key's newest; the older ones were due with it
        and fired in the same advance or before, and every pass of that
        advance was dispatched before this runs: the released slot is
        stamped with the passes dispatched so far and goes back to the
        allocator once all of them have been decoded."""
        if self._registry is not None:
            self._release_q.clear()     # the directory allocates no more
        if not self._release_q:
            return
        with self.phases.span("state.release"):
            parts = []
            while self._release_q:
                parts.append(self._release_q.popleft())
            slot = np.concatenate([p[0] for p in parts])
            last = np.concatenate([p[1] for p in parts])
            rel = slot[self._newest[slot] == last]
            self.releases += 1
            if len(rel):
                self.directory.release_slots(rel)
                self._newest[rel] = LONG_MIN
                self._hold_released(self.emit_ring.version_no, rel)

    # -- what the job reports ------------------------------------------------
    def hbm_bytes(self) -> int:
        """The session lanes and one fire pass's buffer."""
        per_lane = 3 + self.agg.sum_width + self.agg.max_width \
            + self.agg.min_width
        rows = 4 + len(self._fields)
        return (self.lanes * self.slots * per_lane * 4
                + (rows * FIRE_CAP + HEAD_WORDS) * 4)

    def state_counters(self) -> Dict[str, Any]:
        d = self.directory
        grows, grow_s, buckets = d.table_growth()
        out = {"state.slots_allocated": d.slots_allocated,
               "state.slots_reused": d.slots_reused,
               "state.slots_released": d.slots_released,
               "state.slots_returned_early": self.slots_returned_early,
               "state.releases": self.releases,
               "state.live_keys": d.num_keys(),
               "state.live_keys_peak": d.keys_peak,
               "state.slots_waiting_peak": self.slots_waiting_peak,
               "state.table_grows": grows,
               "state.table_grow_s": grow_s,
               "state.table_buckets": buckets,
               "session.lanes": self.lanes,
               "session.slots": self.slots}
        out.update({f"session.{k}": v for k, v in self.counters.items()})
        return out

    # -- snapshot: the registry's columnar format ----------------------------
    def _merged_columns(self) -> Dict[str, np.ndarray]:
        """The open sessions as ``SessionOperator._merged_columns``
        gives them: one (key, start)-sorted block."""
        self.quiesce()
        st = jax.device_get(self.state)
        flat = np.flatnonzero(st.count > 0)
        slot = flat % self.slots
        base = self._base or 0
        n = len(flat)

        def lanes(arrs, width):
            return (np.stack([a[flat] for a in arrs], axis=1)
                    if width else np.zeros((n, 0), np.float32))

        cols = {
            "key": self.directory.key_of_slots(slot).astype(np.int64),
            "start": st.start[flat].astype(np.int64) + base,
            "last": st.last[flat].astype(np.int64) + base,
            "sums": lanes(st.sums, self.agg.sum_width),
            "maxs": lanes(st.maxs, self.agg.max_width),
            "mins": lanes(st.mins, self.agg.min_width),
            "count": st.count[flat].astype(np.int64),
            "fired": np.zeros(n, bool), "refire": np.zeros(n, bool),
            "retracted": np.zeros(n, bool)}
        order = np.lexsort((cols["start"], cols["key"]))
        return {c: v[order] for c, v in cols.items()}

    def snapshot_state(self) -> Dict[str, Any]:
        if self._registry is not None:
            return self._registry.snapshot_state()
        return {"watermark": self.watermark,
                "late_records": self.late_records,
                "columns": self._merged_columns()}

    def restore_state(self, snap: Dict[str, Any]) -> None:
        self._registry = self.why_registry = None
        self.counters["on_registry"] = 0
        self._release_q.clear()
        self._forget_waiting()
        self._inflight.clear()
        self._step_reports.clear()
        self.emit_ring.reset()
        self.emit_ring.fires_decoded = self.emit_ring.version_no
        if "columns" not in snap:
            return self._to_registry(
                "a snapshot in the legacy per-key format", snap)
        self.watermark = snap["watermark"]
        self.late_records = snap["late_records"]
        cols = {c: np.asarray(v) for c, v in snap["columns"].items()}
        # a fired span is one the registry retains for allowed lateness:
        # at lateness 0, where this lane runs, none outlives its advance
        keep = ~cols["fired"].astype(bool)
        order = np.lexsort((cols["start"][keep], cols["key"][keep]))
        cols = {c: v[keep][order] for c, v in cols.items()}
        key = cols["key"].astype(np.int64)
        n = len(key)
        self._base = int(cols["start"].min()) if n else None
        self._min_ts = int(cols["last"].min()) if n else I64_MAX
        self._max_ts = int(cols["last"].max()) if n else LONG_MIN
        # a key's sessions take its lanes in start order
        new_key = np.r_[True, key[1:] != key[:-1]]
        lane = np.arange(n) - np.maximum.accumulate(
            np.where(new_key, np.arange(n), 0))
        lanes = int(lane.max()) + 1 if n else 0
        if lanes > MAX_LANES or (n and self._max_ts - self._base > self._span):
            return self._to_registry(
                f"a snapshot in which a key holds {lanes} open sessions "
                f"over [{self._base}, {self._max_ts}]", snap)
        self.lanes = self._kw["lanes"] = max(self.lanes, lanes)
        self._may_hold = lanes
        d = self.directory = KeyDirectory(
            self.directory.num_shards, self.directory.slots_per_shard)
        self._newest = np.full(self.slots, LONG_MIN, np.int64)
        host = jax.tree_util.tree_map(np.array, jax.device_get(
            init_session_state(self.agg, self.lanes, self.slots)))
        if n:
            slot = d.assign(key)
            if (slot < 0).any():
                raise RuntimeError(
                    "session restore: the snapshot's keys do not fit "
                    "state.num-key-shards x state.slots-per-shard")
            flat = lane * self.slots + slot
            host.start[flat] = cols["start"] - self._base
            host.last[flat] = cols["last"] - self._base
            host.count[flat] = cols["count"]
            for name in ("sums", "maxs", "mins"):
                for j, a in enumerate(getattr(host, name)):
                    a[flat] = cols[name][:, j]
            np.maximum.at(self._newest, slot, cols["last"].astype(np.int64))
        self.state = jax.device_put(host)
