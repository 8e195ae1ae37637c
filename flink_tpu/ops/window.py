"""The window operator — the north-star component.

ref: streaming/runtime/operators/windowing/WindowOperator.java
(processElement: assign windows → per-(key,window) state add → trigger;
onEventTime: fire → emit via InternalWindowFunction → purge) and the
timer loop it rides (streaming/api/operators/InternalTimerServiceImpl
.advanceWatermark — a per-timer heap poll).

TPU-first redesign (SURVEY §6.7, §8): no per-element window lists, no
timer heap, no per-key callbacks. Three dense kernels over a
``(slots, pane_ring)`` accumulator tensor:

- ``apply``: one microbatch → pane index per record → the batch
  combined per (slot, pane) cell on the device (a sort and a segmented
  add/max/min) → one update per distinct cell. Sliding windows cost ONE
  write per element (the Table-runtime slicing trick, ref
  SliceAssigner), not ``size/slide`` writes like the reference's
  DataStream WindowOperator.
- ``fire``: a watermark advance makes whole *windows* fireable at once;
  each is a gather of its ``panes_per_window`` ring columns + a
  sum/max/min reduction over the pane axis — vectorized over every key
  slot simultaneously (the batched Trigger.onEventTime).
- ``clear``: panes no window can ever need again (watermark past
  end + allowed lateness) are reset to identities; the ring reuses them.

The host-side ``WindowOperator`` class owns the watermark clock, the ring
bookkeeping (which global pane lives in which ring column), allowed
lateness / late side output, and late re-firing — control flow the
reference keeps in triggers/timers, which is inherently scalar and cheap,
so it stays on the host while all per-record and per-key work is on
device.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import threading
import time
from collections.abc import Mapping
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from flink_tpu.api.windowing import WindowAssigner
from flink_tpu.hostsync import ready_wait
from flink_tpu.native_codec import ts_order_stats
from flink_tpu.obs.tracing import PhaseClock
from flink_tpu.utils.jaxcompat import shard_map
from flink_tpu.ops.aggregates import LaneAggregate, require_float_lanes
from flink_tpu.ops.emit_ring import EmitRing, await_arrays
from flink_tpu.parallel.mesh import AXIS, MeshPlan
from flink_tpu.state.keyed import (
    KeyDirectory, PaneState, PaneStateLayout, ReuseRule, account_full_drop,
    init_state)
from flink_tpu.state.spill import HostSpillStore
from flink_tpu.time.watermarks import LONG_MIN


# ---------------------------------------------------------------------------
# Pure kernels (jittable; operate on LOCAL slot ids).
# ---------------------------------------------------------------------------

def apply_kernel(
    state: PaneState,
    packed: jax.Array,     # (B,) int: slot * ring + ring_ix; < 0 = invalid
    data: Dict[str, jax.Array],
    *,
    agg: LaneAggregate,
    ring: int,
    dump_row: int,
) -> Tuple[PaneState, jax.Array]:
    """Fold one microbatch into pane state (the processElement hot loop,
    batched). The host pre-packs each record's (slot, pane-ring column)
    into ONE integer — the only per-record value the device needs — so
    ingest ships a single narrow array instead of (slots, timestamps,
    validity) three-wide: fewer host→device bytes per record.
    Negative = invalid: the record takes part in nothing. The batch is
    combined per (slot, ring column) on the device and pane state is
    updated once per DISTINCT cell (see ``_scatter_panes``). Returns the
    new state and the int32 pair (distinct cells updated, valid records
    folded), which no later step donates."""
    valid = packed >= 0
    p = jnp.where(valid, packed, 0)
    rows = jnp.where(valid, p // ring, dump_row).astype(jnp.int32)
    ring_ix = (p % ring).astype(jnp.int32)
    return _scatter_panes(state, rows, ring_ix, valid, data, agg)


NO_CELL = np.iinfo(np.int32).max  # sorts past every real cell key


def apply_chunk(batch: int) -> int:
    """Distinct cells ONE trip of the apply's chunk loop can update,
    from the batch's shape alone: an eighth of the batch (a batch whose
    keys recur, the common case, is one trip; a batch of all-distinct
    cells in one ring column is eight), the whole of a small batch."""
    return max(batch // 8, min(batch, 1024))


def _run_scan(op, heads: jax.Array, x: jax.Array) -> jax.Array:
    """Inclusive scan of ``op`` over the 1-D ``x`` that starts anew at
    every element whose ``heads`` flag is set (element 0's must be): at
    a run's last element, the run's reduction. log2(len) doubling steps
    of shifted elementwise ops: a float32 sum is added pairwise, by
    blocks that double back from the run's END, so it is a function of
    the run's own elements in their order, wherever the run lies in
    ``x`` (a job cut into other batches or over other devices adds the
    same floats the same way). ``lax.associative_scan``'s tree is laid
    over absolute positions, and takes the chip's compiler a minute at
    2^20."""
    d = 1
    while d < x.shape[0]:
        # an element within d of the front has its flag set by now
        x = jnp.where(heads, x, op(jnp.concatenate([x[:d], x[:-d]]), x))
        heads = heads | jnp.concatenate([heads[:d], heads[:-d]])
        d *= 2
    return x


# a lane family of PaneState: its reduction over a cell's records, and
# the indexed update that folds the result into the cell
LANE_OPS = (("sums", jnp.add, "add"), ("maxs", jnp.maximum, "max"),
            ("mins", jnp.minimum, "min"))


def _lane_columns(lane) -> list:
    """The 1-D columns of one lane family."""
    if isinstance(lane, (tuple, list)):
        return list(lane)
    return [lane[:, j] for j in range(lane.shape[1])]


def combine_cells(n_rows: int, rows, ring_ix, valid, lanes):
    """Combine a batch per (row, ring column) cell: two sorts and no
    scatter. ``lanes``: the lifted (B, width) arrays by the name of
    their PaneState family (see ``LANE_OPS``). Returns

    - ``cells`` (B,): the batch's DISTINCT cell keys ``ring_ix * n_rows
      + row`` ascending (column by column, rows ascending within one),
      then ``NO_CELL``;
    - ``starts`` (B + 1,): cell j's records are ``starts[j] ..
      starts[j + 1]`` of the batch sorted by key (its count: their
      difference);
    - ``scans``: by family, per lane of its width the ``_run_scan``
      over the sorted batch, whose element ``starts[j + 1] - 1`` is
      cell j's reduction;
    - ``n_cells``, ``n_records``: distinct cells, valid records."""
    batch = rows.shape[0]
    key = jnp.where(valid, ring_ix * n_rows + rows, NO_CELL)
    # a family is a (B, width) float32 array or, for integer lanes
    # (LaneAggregate.lane_dtypes), a tuple of (B,) columns of any dtype:
    # the sort's payload and the scan take either
    lanes = {name: _lane_columns(l) for name, l in lanes.items()}
    cols = [c for l in lanes.values() for c in l]
    # stable where lanes ride along: a cell's records then keep their
    # arrival order, so its float sum depends on them alone (_run_scan)
    key, *cols = lax.sort((key, *cols), num_keys=1, is_stable=len(lanes) > 0)
    live = key != NO_CELL
    first = jnp.concatenate(
        [jnp.ones(1, bool), key[1:] != key[:-1]])
    head = first & live
    n_records = jnp.sum(live, dtype=jnp.int32)
    n_cells = jnp.sum(head, dtype=jnp.int32)
    scans, at = {}, 0
    for name, op, _ in LANE_OPS:
        if name in lanes:
            w = len(lanes[name])
            scans[name] = [_run_scan(op, first, c) for c in cols[at:at + w]]
            at += w
    # the run heads to the front, in key order: a second sort, whose
    # payload is each head's position in the sorted batch
    cells, starts = lax.sort(
        (jnp.where(head, key, NO_CELL),
         jnp.where(head, jnp.arange(batch, dtype=jnp.int32), batch)),
        num_keys=1, is_stable=False)
    j = jnp.arange(batch + 1, dtype=jnp.int32)
    starts = jnp.where(
        j < n_cells, jnp.concatenate([starts, starts[:1]]), n_records)
    return cells, starts, scans, n_cells, n_records


def _update_column(arr, col, rows, vals, kind: str):
    """``arr[rows, col] <kind>= vals`` for ONE ring column ``col`` and
    ascending, distinct ``rows`` (out of range = dropped): the column is
    lifted out of the donated tensor, updated as a 1-D array and put
    back. A scatter into the whole (rows, ring[, width]) tensor makes
    XLA copy all of it to a flat layout and back (on the chip two
    passes over the state a batch); this touches one column of it."""
    n_rows = arr.shape[0]
    zero = jnp.zeros((), col.dtype)
    start = (zero, col) + (zero,) * (arr.ndim - 2)
    size = (n_rows, 1) + arr.shape[2:]
    column = lax.dynamic_slice(arr, start, size).reshape(
        (n_rows,) + arr.shape[2:])
    column = getattr(column.at[rows], kind)(
        vals, indices_are_sorted=True, unique_indices=True, mode="drop")
    return lax.dynamic_update_slice(arr, column.reshape(size), start)


def apply_cells(state: PaneState, cells, starts, scans,
                n_cells) -> Tuple[PaneState, jax.Array]:
    """Add ``combine_cells``' distinct cells to pane state, ``apply_chunk``
    of them a trip and one ring column a trip: trips = the sum over the
    columns the batch names of ceil(its cells / chunk), whatever the
    batch holds. Returns the state and the trips made."""
    n_rows = state.counts.shape[0]
    batch = cells.shape[0]
    chunk = apply_chunk(batch)
    lane = jnp.arange(chunk, dtype=jnp.int32)
    # a slice of `chunk` from any cell index stays inside
    cells = jnp.concatenate([cells, jnp.full(chunk, NO_CELL, jnp.int32)])
    starts = jnp.concatenate([starts, jnp.zeros(chunk, jnp.int32)])

    def trip(carry):
        state, done, trips = carry
        k = lax.dynamic_slice(cells, (done,), (chunk,))
        s = lax.dynamic_slice(starts, (done,), (chunk + 1,))
        col = k[0] // n_rows
        # the chunk's cells of its first cell's column: a prefix (the
        # keys ascend, and NO_CELL lies past every column's end)
        mine = (done + lane < n_cells) & (k < (col + 1) * n_rows)
        # padding takes distinct rows past the last: uniqueness is true
        rows = jnp.where(mine, k - col * n_rows, n_rows + lane)
        last = jnp.maximum(s[1:] - 1, 0)
        lanes = {name: _update_column(
                     getattr(state, name), col, rows,
                     jnp.stack([c[last] for c in scans[name]], axis=1), kind)
                 for name, _, kind in LANE_OPS if name in scans}
        counts = _update_column(
            state.counts, col, rows, jnp.where(mine, s[1:] - s[:-1], 0),
            "add")
        return (PaneState(sums=lanes.get("sums"), maxs=lanes.get("maxs"),
                          mins=lanes.get("mins"), counts=counts),
                done + jnp.sum(mine, dtype=jnp.int32), trips + 1)

    zero = n_cells - n_cells    # under shard_map: varying as n_cells is
    state, _, trips = lax.while_loop(
        lambda c: c[1] < n_cells, trip, (state, zero, zero))
    return state, trips


def _scatter_panes(state, rows, ring_ix, valid, data, agg):
    """The one place the per-record lanes meet (``apply_kernel``,
    ``apply_kernel_split``, the mesh's ``apply_shard``): combine the
    batch per (row, ring column) on the device, then update pane state
    once per distinct cell with sorted, unique indices. Counts are run
    lengths; sums are added in float32 by a segmented scan, maxs and
    mins likewise. The same adds reach the same cells as a scatter of
    the records would make. Returns (state, [cells, records])."""
    lifted = zip(LANE_OPS, agg.lift_masked(data, valid))
    lanes = {name: lane for (name, _, _), lane in lifted
             if getattr(state, name) is not None}
    cells, starts, scans, n_cells, n_records = combine_cells(
        state.counts.shape[0], rows, ring_ix, valid, lanes)
    state, _ = apply_cells(state, cells, starts, scans, n_cells)
    return state, jnp.stack([n_cells, n_records])


INVALID_SLOT_U16 = 0xFFFF  # sentinel slot for invalid rows in split uploads


def split_decode(sc: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """(B,3) uint8 → ((B,) uint16 slot, (B,) uint8 ring column). Bytes
    0-1 are the little-endian slot id (bitcast, matching numpy
    ``.view(uint8)`` on the host), byte 2 the ring column. Record-major
    layout so a shard_map partition along axis 0 keeps records whole."""
    slot = lax.bitcast_convert_type(sc[:, :2], jnp.uint16)
    return slot, sc[:, 2]


def split_encode(slots: np.ndarray, cols: np.ndarray,
                 valid: np.ndarray) -> np.ndarray:
    """Host half of ``split_decode``: (B,) slots + (B,) ring columns →
    (B,3) uint8 with 0xFFFF marking invalid rows."""
    n = len(slots)
    sl = np.where(valid, slots, INVALID_SLOT_U16).astype(np.uint16)
    sc = np.empty((n, 3), np.uint8)
    sc[:, :2] = sl.view(np.uint8).reshape(n, 2)
    sc[:, 2] = cols
    return sc


def apply_kernel_split(
    state: PaneState,
    sc: jax.Array,         # (B, 3) uint8: see split_decode
    data: Dict[str, jax.Array],
    *,
    agg: LaneAggregate,
    dump_row: int,
) -> PaneState:
    """``apply_kernel`` with the (slot, ring column) pair shipped as one
    (B,3) uint8 buffer instead of a packed int32 — 3 bytes/record on the
    host→device link instead of 4, in ONE transfer (a second buffer
    is a second transfer). Built on the premise that the link, not the
    MXU, bounds host-fed Q5; what a byte or a transfer costs on the
    current chip: not measured. The kernel body is identical — the
    rows/ring_ix it needs decode in two device ops."""
    slot, col = split_decode(sc)
    valid = slot != INVALID_SLOT_U16
    rows = jnp.where(valid, slot.astype(jnp.int32), dump_row)
    ring_ix = col.astype(jnp.int32)
    return _scatter_panes(state, rows, ring_ix, valid, data, agg)


def apply_preagg_u16_kernel(
    state: PaneState,
    buf: jax.Array,        # (P, 3) uint16: [pair lo16, pair hi16, count]
    *,
    ring: int,
    dump_row: int,
) -> PaneState:
    """Fold a HOST-PRE-AGGREGATED microbatch in: the host combined the
    batch per (slot, ring column) pair with np.bincount (the mini-batch
    local-aggregation trick, ref: table/runtime mini-batch agg), so the
    upload carries one (pair id, count) triple per DISTINCT pair —
    ~6 bytes × (keys × panes touched) instead of 3 bytes × records.
    For Q5's 2^20-record batches over 10k keys that is ~0.6 B/record on
    a link that is the pipeline ceiling, and the device scatter shrinks
    by the same records/pairs ratio. Count-only shape (sum lanes ride
    the i32 variant). Sentinel pair 0xFFFFFFFF marks padding."""
    b = buf.astype(jnp.int32)
    pair = b[:, 0] | (b[:, 1] << 16)   # sentinel decodes to -1
    ok = pair >= 0
    p = jnp.where(ok, pair, 0)
    rows = jnp.where(ok, p // ring, dump_row).astype(jnp.int32)
    cols = (p % ring).astype(jnp.int32)
    cnt = jnp.where(ok, b[:, 2], 0)
    return PaneState(sums=state.sums, maxs=state.maxs, mins=state.mins,
                     counts=state.counts.at[rows, cols].add(cnt))


def apply_preagg_u32_kernel(
    state: PaneState,
    buf: jax.Array,        # (P,) uint32: pair << 12 | count (count < 0xFFF)
    *,
    ring: int,
    dump_row: int,
) -> PaneState:
    """Tightest count-only pre-agg upload: ONE u32 per distinct pair —
    20-bit pair id + 12-bit count. Eligible when the pair domain fits
    2^20 and every per-pair count < 0xFFF (the host checks both and
    falls back to the u16 triple otherwise). 4 bytes/pair — the
    fewest upload bytes of the pre-agg encodings (cost per byte not
    measured on the current chip). Padding entries are 0xFFFFFFFF
    (pair 0xFFFFF, beyond the strict domain < 2^20 the eligibility gate
    enforces)."""
    return _apply_preagg_u32_core(state, buf, ring=ring, dump_row=dump_row)


def _apply_preagg_u32_core(state, buf, *, ring, dump_row):
    pair = lax.shift_right_logical(buf, jnp.int32(12))  # bit pattern, not sign
    cnt = buf & jnp.int32(0xFFF)
    # real entries always have count < 0xFFF (host gate); the padding
    # word 0xFFFFFFFF decodes to count 0xFFF — so the count field alone
    # distinguishes padding even when the pair domain fills 2^20
    ok = (cnt != 0xFFF) & (pair < dump_row * ring)
    p = jnp.where(ok, pair, 0)
    rows = jnp.where(ok, p // ring, dump_row).astype(jnp.int32)
    cols = (p % ring).astype(jnp.int32)
    return PaneState(sums=state.sums, maxs=state.maxs, mins=state.mins,
                     counts=state.counts.at[rows, cols].add(
                         jnp.where(ok, cnt, 0)))


def apply_preagg_i32_kernel(
    state: PaneState,
    buf: jax.Array,        # (P, 2 + sum_width) int32:
                           # [pair, count, f32-bitcast sum lanes...]
    *,
    sum_width: int,
    ring: int,
    dump_row: int,
) -> PaneState:
    """``apply_preagg_u16_kernel`` with per-pair pre-combined SUM lanes
    (sum/avg aggregates whose lanes are identity lifts — see
    LaneAggregate.sum_fields). Pair < 0 marks padding."""
    pair = buf[:, 0]
    ok = pair >= 0
    p = jnp.where(ok, pair, 0)
    rows = jnp.where(ok, p // ring, dump_row).astype(jnp.int32)
    cols = (p % ring).astype(jnp.int32)
    cnt = jnp.where(ok, buf[:, 1], 0)
    counts = state.counts.at[rows, cols].add(cnt)
    sums = state.sums
    if sum_width:
        lanes = lax.bitcast_convert_type(buf[:, 2:2 + sum_width], jnp.float32)
        lanes = jnp.where(ok[:, None], lanes, 0.0)
        sums = sums.at[rows, cols].add(lanes)
    return PaneState(sums=sums, maxs=state.maxs, mins=state.mins,
                     counts=counts)


def preagg_combine(
    slots: np.ndarray, cols: np.ndarray, valid: np.ndarray,
    data: Dict[str, np.ndarray], sum_fields: Tuple[str, ...],
    *, ring: int, domain: int,
) -> Tuple[np.ndarray, np.ndarray, List[np.ndarray]]:
    """Host half: combine one batch per (slot, ring column) pair.
    Returns (pair ids, counts, per-lane pre-summed f32 arrays)."""
    pk = (slots[valid] * ring + cols[valid]).astype(np.int32)
    # compact to observed pairs (O(nv log nv)) — a dense
    # minlength=domain histogram would allocate and zero O(domain)
    # per batch, which at the 2^23 eligibility bound dwarfs the h2d
    # bytes this path exists to save
    pairs, inv, cnts = np.unique(pk, return_inverse=True,
                                 return_counts=True)
    lanes = []
    for f in sum_fields:
        acc = np.zeros(len(pairs), np.float64)
        np.add.at(acc, inv, np.asarray(data[f], np.float64)[valid])
        lanes.append(acc.astype(np.float32))
    return pairs, cnts, lanes


def preagg_encode_u16(pairs: np.ndarray, cnts: np.ndarray,
                      cap: int) -> np.ndarray:
    """(pairs, counts) → one (cap, 3) uint16 buffer (ONE h2d transfer;
    a second buffer pays a second round trip). Padding rows carry the
    0xFFFF/0xFFFF sentinel pair."""
    n = len(pairs)
    buf = np.empty((cap, 3), np.uint16)
    pu = pairs.astype(np.uint32)
    buf[:n, 0] = pu & 0xFFFF
    buf[:n, 1] = pu >> 16
    buf[:n, 2] = cnts.astype(np.uint16)
    buf[n:] = 0xFFFF
    return buf


def preagg_encode_i32(pairs: np.ndarray, cnts: np.ndarray,
                      lanes: List[np.ndarray], cap: int) -> np.ndarray:
    """(pairs, counts, sum lanes) → one (cap, 2+W) int32 buffer with
    f32 lanes bitcast into the int columns. Padding pair = -1."""
    n = len(pairs)
    buf = np.empty((cap, 2 + len(lanes)), np.int32)
    buf[:n, 0] = pairs
    buf[n:, 0] = -1
    buf[:n, 1] = cnts
    buf[n:, 1] = 0
    for i, ln in enumerate(lanes):
        buf[:n, 2 + i] = ln.view(np.int32)
        buf[n:, 2 + i] = 0
    return buf


def reads_live_columns(width: int) -> bool:
    """Whether a fire of ``width`` window ends makes its counts by the
    masked reduction over the ring axis (``fire_kernel``): the one-end
    fire does, a wider one takes ``prefix_sum_counts``. Static, so the
    operator knows at dispatch which form its program took
    (``_count_fire``). The rule is the chip's (``tools/fire_micro.py``,
    device ms a call, masked / prefix; my chip runs, PR 44): at
    16,777,217 rows x 12 columns 5.9 / 17.4 at one end, and 89.6 /
    76.9, 104.7 / 86.7, 118.2 / 108.0 at 2, 4 and 8; at 32,769 rows x 8
    columns 0.050 / 0.057 at one end and 0.670 / 0.562 at 64."""
    return width == 1


def prefix_sum_counts(counts, end_panes, w_valid, pane_lo, *,
                      panes_per_window, ring):
    """The counts of a fire of SEVERAL window ends: roll the ring so
    column j holds pane (pane_lo + j), cumsum along the ring, and each
    window's count is one prefix difference. Integer prefix sums are
    exact, and every column outside the live [pane_lo, pane_hi] span is
    provably ZERO (purged panes are cleared, unwritten panes never
    incremented — the same ring-aliasing invariant the mask form relies
    on), so out-of-range prefixes contribute nothing. The roll and the
    prefix sum are each a read and a write of the whole state (4.7 and
    5.5 ms at 16,777,217 rows x 12 columns), whatever W is; the masked
    reduction's rows x W x ring selects grow with W (0.10 ms against
    ~0.03 at 32,769 rows x 64 ends) and its (rows, W) result comes out
    column-major, so that the row-major grid behind it costs two more
    passes of 10-13 ms at 16.8 M rows (``reads_live_columns`` has the
    calls)."""
    ppw = panes_per_window
    roll_amt = (pane_lo % ring).astype(jnp.int32)
    rolled = jnp.roll(counts, -roll_amt, axis=1)
    cs = jnp.cumsum(rolled, axis=1)                                        # (rows, ring)
    e_hi = jnp.clip(end_panes - 1 - pane_lo, -1, ring - 1).astype(jnp.int32)
    e_lo = jnp.clip(end_panes - ppw - 1 - pane_lo, -1,
                    ring - 1).astype(jnp.int32)
    hiv = jnp.where(e_hi[None, :] >= 0,
                    jnp.take(cs, jnp.clip(e_hi, 0, ring - 1), axis=1), 0)
    lov = jnp.where(e_lo[None, :] >= 0,
                    jnp.take(cs, jnp.clip(e_lo, 0, ring - 1), axis=1), 0)
    return jnp.where(w_valid[None, :], hiv - lov, 0)


def fire_kernel(
    state: PaneState,
    end_panes: jax.Array,  # (W,) int64 global pane ids (window end, exclusive)
    w_valid: jax.Array,    # (W,) bool
    pane_lo: jax.Array,    # scalar int64: oldest written-and-uncleared pane
    pane_hi: jax.Array,    # scalar int64: newest written pane
    *,
    panes_per_window: int,
    ring: int,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Evaluate every (key, fireable-window) pair at once.

    Returns (sums (rows,W,sw), maxs, mins, counts (rows,W)) — the lane
    reduction over each window's pane span. ref role: WindowOperator.
    onEventTime → emitWindowContents, for all keys in one shot.

    The [pane_lo, pane_hi] range masks ring aliasing: a window's pane that
    was never written (or already purged) may share a ring column with a
    newer pane; such cells read as identity. The ingest-side ring guard
    ensures at most one live pane per column within the range.
    """
    ppw = panes_per_window
    want = end_panes[:, None] - ppw + jnp.arange(ppw)[None, :]            # (W, ppw) global panes
    live = (want >= pane_lo) & (want <= pane_hi)                           # (W, ppw)
    rows_n = state.counts.shape[0]
    W = end_panes.shape[0]
    # (W, ring) column-selection mask instead of a per-(window, pane)
    # GATHER: arr[:, ring_ix] gathers rows × W × ppw elements, and a
    # large gather is a slow op on TPU, while the mask form is a
    # broadcast + reduce the fuser can stream (the max / min lanes'
    # cost on the chip: not measured, no cell runs them; the COUNT
    # lane's is below). Within [pane_lo, pane_hi] at
    # most one live pane occupies a column (the ingest ring guard), so
    # a window's reduction over its live COLUMNS equals the reduction
    # over its live panes.
    colmask = jnp.any(
        ((want % ring)[:, :, None] == jnp.arange(ring)[None, None, :])
        & live[:, :, None], axis=1)                                        # (W, ring)

    def lane_red(arr, red, identity):
        # None lanes (zero declared width) reduce to a zero-width
        # INTERNAL value — never a runtime buffer, so free
        if arr is None:
            return jnp.zeros((rows_n, W, 0), jnp.float32)
        m = colmask[None, :, :, None]
        return red(jnp.where(m, arr[:, None, :, :], identity), axis=2)

    # SUM lanes ride matmuls over the column mask — the MXU does the
    # window reduction without materializing the (rows, W, ring)
    # broadcast the mask-reduce form needs (33 MB per fire at Q5 shape).
    # Counts are integers: below.
    sel_t = colmask.astype(jnp.float32).T                                  # (ring, W)
    if state.sums is None:
        sums = jnp.zeros((rows_n, W, 0), jnp.float32)
    else:
        # HIGHEST: at default precision a TPU multiplies f32 operands
        # in one bf16 pass, which rounds every pane sum to 8 bits of
        # mantissa (seen on a v5e: sums off by 3.9e-3 relative); the
        # full-f32 passes give the mask-reduce form's precision class
        sums = jnp.einsum("rcs,cw->rws", state.sums, sel_t,
                          precision=lax.Precision.HIGHEST)
    maxs = lane_red(state.maxs, jnp.max, -jnp.inf)
    mins = lane_red(state.mins, jnp.min, jnp.inf)
    # COUNTS, integer adds in both forms, the same counts element for
    # element (``tests/test_fire_reduction.py``). ONE WINDOW END (every
    # fire of the large-keys cells but a catch-up's and the flush's; the
    # fused step's one-end fires): one masked reduction over the ring
    # axis under the same column mask, full tiles in, a (rows,) result
    # in whole tiles out, a single read of the state: 1.42 ms over
    # 1.07 GB as laid out (756 GB/s) at 16,777,217 rows x 12 columns,
    # where the roll and the prefix sum took 4.7 + 5.5. (Lifting the
    # window's five columns out one by one reads less and took 3.68: a
    # lifted column fills one sublane in eight of the (8 columns x 128
    # rows) tiles; my chip runs, PR 44.) SEVERAL ENDS: prefix sums.
    if reads_live_columns(W):
        counts = jnp.sum(
            jnp.where((colmask & w_valid[:, None])[None, :, :],
                      state.counts[:, None, :], 0),
            axis=2, dtype=state.counts.dtype)
    else:
        counts = prefix_sum_counts(
            state.counts, end_panes, w_valid, pane_lo,
            panes_per_window=ppw, ring=ring)
    return sums, maxs, mins, counts


_END_SENTINEL = np.int64(-(2**62))  # pads the window axis in fire params


def _unpack_fire_params(params: jax.Array):
    """One packed i64 operand per fire — [pane_lo, pane_hi, anchor,
    end_pane...] with sentinel-padded ends — instead of five separate
    host→device transfers (each pays a transport round trip)."""
    pane_lo = params[0]
    pane_hi = params[1]
    anchor = params[2]
    end_panes = params[3:]
    w_valid = end_panes > _END_SENTINEL // 2
    return pane_lo, pane_hi, anchor, end_panes, w_valid


def first_true_indices(flat: jax.Array, cap: int) -> jax.Array:
    """(cap,) int32: the positions of the first ``cap`` True entries of
    the 1-D mask ``flat`` in ascending order, ``len(flat)`` where it has
    fewer — the fire's compaction (selected candidates in row-major
    order, padded to a fixed shape). Two forms, chosen by the static
    shapes. FEW WINNERS OF MANY CANDIDATES (every top-n fire of a grid
    worth naming: ``cap`` 256 of 32,769 to 16.8 M): a prefix sum of the
    mask and a binary search of it for the j-th winner, j = 1..cap; at
    16,777,217 candidates the fire takes 19.0 ms a call where the
    stable argsort of the negated mask it replaced took 95.6, 80.1 of
    them the sort, and at 32,769 x 64 0.56 against 5.9. AS MANY WINNERS
    AS CANDIDATES (``fire_pack_kernel``, whose ``cap`` bounds every row
    that may fire; tiny grids): ``cap`` searches are ``cap`` x log2 k
    gathers, ~7.5 ns each on the chip (18.3 ms a call at 131,076
    candidates and a cap of 131,072, where the argsort's call took
    2.9), so there the positions are sorted once, the unselected ones
    masked to ``len(flat)``: a sort costs what the candidates cost,
    whatever the cap. (``tools/fire_micro.py``; my chip runs, PR 40.)"""
    k = flat.shape[0]
    if 2 * cap * k.bit_length() <= k:
        return searched_true_indices(flat, cap)
    return sorted_true_indices(flat, cap)


def searched_true_indices(flat: jax.Array, cap: int) -> jax.Array:
    c = jnp.cumsum(flat, dtype=jnp.int32)
    return jnp.searchsorted(
        c, jnp.arange(1, cap + 1, dtype=jnp.int32), side="left")


def sorted_true_indices(flat: jax.Array, cap: int) -> jax.Array:
    k = flat.shape[0]
    idx = lax.sort(jnp.where(flat, jnp.arange(k, dtype=jnp.int32), k))[:cap]
    if cap > k:  # tiny grids: pad to the fixed selection shape
        idx = jnp.concatenate([idx, jnp.full(cap - k, k, jnp.int32)])
    return idx


def fire_pack_kernel(
    state: PaneState,
    params: jax.Array,      # packed: see _unpack_fire_params
    used_mask: jax.Array,   # (rows,) bool — registered-key rows
    *,
    agg: LaneAggregate,
    panes_per_window: int,
    ring: int,
    out_cap: int,
    packed2: bool = False,
) -> jax.Array:
    """fire + select + finalize + COMPACT entirely on device, packed
    into ONE int32 buffer so the host pays exactly one transfer per
    firing advance. The device→host transfer is the throughput ceiling
    of the emit path (bytes × link bandwidth + per-fetch latency), so
    the buffer holds only the fired (key, window) rows — ``out_cap`` of
    them, a host-chosen bound ≥ registered keys × windows, which can
    therefore never truncate — not the full slots × windows grid:
    row 0 = [n, 0, ...]; rows 1..n = [slot_row, end_pane delta vs
    pane_lo, count, f32-bitcast result lanes...] with result columns in
    sorted-field order.

    ref role: the whole onEventTime → emitWindowContents →
    Collector.collect chain, batched."""
    pane_lo, pane_hi, _anchor, end_panes, w_valid = _unpack_fire_params(params)
    sums, maxs, mins, counts = fire_kernel(
        state, end_panes, w_valid, pane_lo, pane_hi,
        panes_per_window=panes_per_window, ring=ring)
    rows = counts.shape[0]
    W = end_panes.shape[0]
    nz = (counts > 0) & used_mask[:, None] & w_valid[None, :]
    flat = nz.reshape(-1)
    k = rows * W
    idx = first_true_indices(flat, out_cap)
    row = jnp.minimum(idx // W, rows - 1).astype(jnp.int32)
    wi = (idx % W).astype(jnp.int32)
    sel_counts = jnp.where(idx < k, counts[row, wi], 0)
    res = agg.finalize(sums[row, wi], maxs[row, wi], mins[row, wi], sel_counts)
    end_delta = (end_panes[wi] - pane_lo).astype(jnp.int32)
    if packed2:
        # count-only 2-column layout: (row << 8 | delta, count) — 8
        # bytes/row instead of 12; valid when the op's static shape
        # bounds fit (slots < 2^23, delta < 2^8 — see _fire_packed2).
        # Egress bytes are what the WordCount family moves most of.
        cols = [(row << 8) | end_delta, sel_counts.astype(jnp.int32)]
    else:
        cols = [row, end_delta, sel_counts.astype(jnp.int32)]
    for name in ([] if packed2 else sorted(res)):
        if name == "count":
            continue  # column 2 already carries it — for count-only
            # aggregates (WordCount) this is 25% of the egress bytes
        v = res[name].reshape(out_cap)
        if jnp.issubdtype(v.dtype, jnp.integer):
            # integer result lanes (counts) stay exact i32; float lanes
            # ride as f32 bitcasts (decode reads the dtype probe)
            cols.append(v.astype(jnp.int32))
        else:
            cols.append(lax.bitcast_convert_type(v.astype(jnp.float32), jnp.int32))
    body = jnp.stack(cols, axis=1)                       # (out_cap, C)
    head = jnp.zeros((1, body.shape[1]), jnp.int32).at[0, 0].set(
        jnp.sum(flat).astype(jnp.int32))
    return jnp.concatenate([head, body])                 # (out_cap+1, C)


def top_values(v: jax.Array, k: int) -> jax.Array:
    """(W, k): each window's ``k`` largest ranking values of the
    (candidates, W) grid ``v``, largest first. The top 1 (Q5's
    ``.top(1, by="count")``) is a max, one streaming reduction where
    ``lax.top_k`` over 16.8 M candidates is a program of its own; the
    same value, -inf where a window has no candidate."""
    if k == 1:
        return jnp.max(v, axis=0)[:, None]
    return lax.top_k(v.T, k)[0]


def _topn_select_append(
    emit_ring: jax.Array,
    sums, maxs, mins, counts,
    nz: jax.Array,          # (rows, W) candidate mask
    v: jax.Array,           # (rows, W) ranking values (-inf non-candidates)
    thresh: jax.Array,      # (W,) n-th value per window (may be -inf)
    end_panes: jax.Array,
    anchor,
    *,
    agg: LaneAggregate,
    sel_cap: int,
    row_offset,             # scalar added to row ids (device block base)
) -> jax.Array:
    """Shared tail of both top-n fire kernels (local + mesh): select
    rows at/above the per-window threshold (ties kept; -inf thresh ⇒
    all candidates), finalize lanes, append winners to the emit ring.
    Head col 0 = monotone appended total; col 1 accumulates rows
    TRUNCATED by sel_cap (tie explosion) — drain_ring raises on it, the
    loud-overflow contract."""
    rows, W = counts.shape
    sel = nz & (v >= thresh[None, :])
    flat = sel.reshape(-1)
    K = rows * W
    idx = first_true_indices(flat, sel_cap)
    row = jnp.minimum(idx // W, rows - 1).astype(jnp.int32)
    wi = (idx % W).astype(jnp.int32)
    total_sel = jnp.sum(flat).astype(jnp.int32)
    n = jnp.minimum(total_sel, sel_cap)
    # the winners' counts by their position in the flat grid, as ``flat``
    # is read: at one window end a (rows, 1) array indexed [row, 0] is
    # laid out anew on the chip, 128 words a row (13.3 ms and 8.6 GB at
    # 16.8 M rows; ``tools/fire_micro.py``, my chip run, PR 44)
    sel_counts = jnp.where(
        idx < K, counts.reshape(-1)[jnp.minimum(idx, K - 1)], 0)
    res_sel = agg.finalize(sums[row, wi], maxs[row, wi], mins[row, wi], sel_counts)
    end_delta = (end_panes[wi] - anchor).astype(jnp.int32)
    cols = [row + row_offset, end_delta, sel_counts.astype(jnp.int32)]
    for name in sorted(res_sel):
        if name == "count":
            continue  # column 2 already carries it (see fire_pack_kernel)
        u = res_sel[name].reshape(sel_cap)
        if jnp.issubdtype(u.dtype, jnp.integer):
            cols.append(u.astype(jnp.int32))
        else:
            cols.append(lax.bitcast_convert_type(u.astype(jnp.float32), jnp.int32))
    body = jnp.stack(cols, axis=1)                         # (sel_cap, C)
    row_cap = emit_ring.shape[0] - 2
    total = emit_ring[0, 0]
    ar = jnp.arange(sel_cap)
    pos = (total + ar) % row_cap + 1
    safe_pos = jnp.where(ar < n, pos, row_cap + 1)         # dump row
    out = emit_ring.at[safe_pos].set(body)
    return out.at[0, 0].add(n).at[0, 1].add(total_sel - n)


def ring_append_topn_kernel(
    state: PaneState,
    emit_ring: jax.Array,   # (row_cap + 2, C) i32: row 0 = [total, ...],
                            # rows 1..row_cap = data ring, last row = dump
    params: jax.Array,      # packed: see _unpack_fire_params
    used_mask: jax.Array,
    *,
    agg: LaneAggregate,
    panes_per_window: int,
    ring: int,
    sel_cap: int,
    by: str,
    topn: int,
    fire_pad: int = 0,
) -> jax.Array:
    """Top-n fire that APPENDS winners to a device-resident emit ring
    instead of returning a fresh buffer. The host polls the ring — one
    fixed-shape array whose row 0 carries a monotone total-appended
    counter — at its own cadence, so N watermark advances cost ONE
    device→host fetch and zero per-fire transfers. This is the emit
    architecture for transports where a device→host read pays a large
    fixed latency (and starves under concurrent ingest): results stay
    in HBM until the host opens a quiet window.

    Overflow (appends since last poll > row_cap) is detected host-side
    from the counter, never silent. ref role: RecordWriter's buffer ring
    + PipelinedSubpartition, collapsed into device memory.

    ``fire_pad``: how many of the params' window-end slots this program
    READS (0: all), as in the fused step (``_fused_fire_clear``): the
    upload keeps its fixed MIN_FIRE_PAD ends, the fire's rows x W
    subgraph is as wide as the ends that are real. At 16.8 M rows the
    full 64 does not fit the device."""
    pane_lo, pane_hi, anchor, end_panes, w_valid = _unpack_fire_params(params)
    if fire_pad:
        end_panes, w_valid = end_panes[:fire_pad], w_valid[:fire_pad]
    return _ring_append_topn_core(
        state, emit_ring, pane_lo, pane_hi, anchor, end_panes, w_valid,
        used_mask, agg=agg, panes_per_window=panes_per_window, ring=ring,
        sel_cap=sel_cap, by=by, topn=topn)


def _ring_append_topn_core(
    state, emit_ring, pane_lo, pane_hi, anchor, end_panes, w_valid,
    used_mask, *, agg, panes_per_window, ring, sel_cap, by, topn,
):
    sums, maxs, mins, counts = fire_kernel(
        state, end_panes, w_valid, pane_lo, pane_hi,
        panes_per_window=panes_per_window, ring=ring)
    rows = counts.shape[0]
    nz = (counts > 0) & used_mask[:, None] & w_valid[None, :]
    res = agg.finalize(sums, maxs, mins, counts)
    v = jnp.where(nz, res[by].astype(jnp.float32), -jnp.inf)
    k = min(topn, rows)
    # thresh = -inf when a window has fewer than n candidates (the
    # non-candidates' -inf fills the top k); nz already excludes them, so
    # v >= -inf correctly selects ALL of that window's real rows
    thresh = top_values(v, k)[:, k - 1]
    return _topn_select_append(
        emit_ring, sums, maxs, mins, counts, nz, v, thresh,
        end_panes, anchor, agg=agg, sel_cap=sel_cap,
        row_offset=jnp.int32(0))


# fused-step header layout, in i32 words:
# [0:2]=pane_lo i64, [2:4]=pane_hi i64, [4:6]=anchor i64,
# [6]=unused, [7]=clear-mask bits (ring<=32), [8:8+MIN_FIRE_PAD]=window-
# end deltas vs pane_lo (sentinel INT32_MIN = padding), zero pad to
# FUSED_HDR = 128 words = 512 bytes
# (sized so the header was never a "tiny" upload; whether tiny uploads
# cost extra on the current chip: not measured)
FUSED_HDR = 128
_DELTA_SENTINEL = -(2**30)
# fire params are sentinel-padded to at least this many window ends in
# the HEADER (keeps the upload's size fixed and never tiny — see
# clear_kernel); the KERNEL reads only its static fire_pad
# prefix of them (pow2-bucketed to the real end count, _fire_pad_bucket)
MIN_FIRE_PAD = 64
assert 8 + MIN_FIRE_PAD <= FUSED_HDR   # the deltas stay inside the header


def fused_step_kernel(
    state: PaneState,
    emit_ring: jax.Array,
    buf: jax.Array,        # (FUSED_HDR + P,) int32: header + u32 pairs
    used_mask: jax.Array,
    *,
    agg: LaneAggregate,
    panes_per_window: int,
    ring: int,
    sel_cap: int,
    by: str,
    topn: int,
    dump_row: int,
    fire_pad: int = MIN_FIRE_PAD,
) -> Tuple[PaneState, jax.Array, jax.Array]:
    """ONE device dispatch per microbatch: pre-aggregated apply +
    watermark fire (top-n ring append) + pane clear, with the fire
    parameters riding in the SAME upload as the pair list. The fusion
    collapses per-batch stream traffic to one upload + one launch (+
    the cadenced ring announce) instead of a launch and a transfer per
    stage; what a launch or a transfer costs on the current chip: not
    measured. ref: 4.B/4.D hot paths, dispatched as one program.

    Third output: the emit ring's HEAD ROW after this step's fire —
    the step's in-flight token (announced at dispatch; the throttle
    consumes it, and its [total, truncated] words stand in for a
    ring-header poll)."""
    hdr = buf[:FUSED_HDR]
    pairs = buf[FUSED_HDR:]
    state = _apply_preagg_u32_core(
        state, pairs, ring=ring, dump_row=dump_row)
    state, emit_ring = _fused_fire_clear(
        state, emit_ring, hdr, used_mask, agg=agg,
        panes_per_window=panes_per_window, ring=ring, sel_cap=sel_cap,
        by=by, topn=topn, fire_pad=fire_pad)
    return state, emit_ring, emit_ring[0]


def _hdr_i64(hdr: jax.Array, i: int) -> jax.Array:
    """The little-endian i64 in header words [i, i+1], rebuilt with
    shifts. NOT ``lax.bitcast_convert_type(i32[1,2] -> i64)``: an i64
    made that way and then captured by the fire gate's ``lax.cond``
    aborts XLA:TPU's compiler (libtpu 0.0.34, HloReplicationAnalysis:
    "Invalid index {0} for shape u32[1,2]") — seen on a v5e; the shift
    form compiles and is bit-equal."""
    lo = hdr[i].astype(jnp.int64) & jnp.int64(0xFFFFFFFF)
    return (hdr[i + 1].astype(jnp.int64) << 32) | lo


def _fused_fire_clear(state, emit_ring, hdr, used_mask, *, agg,
                      panes_per_window, ring, sel_cap, by, topn,
                      fire_pad=MIN_FIRE_PAD):
    """Fire + clear tail of the one-dispatch step kernel: the fire
    parameters and the purge mask ride the FUSED_HDR header.

    The fire/top-n/ring-append subgraph — whose selection scan + top_k
    would otherwise run on every dispatch whether or not any window
    fires — runs under a ``lax.cond`` keyed on the header's window-end
    list, and the pane purge under a second cond keyed on the clear
    words. The host fills both header fields before dispatch
    (``_fused_fill_header``), so a non-firing sub-batch skips the fire
    entirely. Byte-identical by construction: with no valid ends the
    fire core selects zero rows and leaves ring bytes and head counters
    unchanged, and a zero clear mask is the identity — the cond only
    skips provably-no-op work.

    ``fire_pad``: how many of the header's MIN_FIRE_PAD window-end
    slots this program READS — the static width of the whole fire
    subgraph (fire_kernel's rows×W reductions, the rows×W selection
    scan, the W-way top_k). The host buckets it to the next power
    of two ≥ the sub-batch's real end count (``_fire_pad_bucket``), so
    K sub-batch dispatches of ~W/K ends each cost ≈ ONE W-wide fire —
    without this, every dispatch paid the full 64-wide subgraph and
    sub-batching traded throughput ∝ K for its latency win.
    Sentinel-padded slots never select rows, so the
    bucket width never changes bytes, only skipped work."""
    pane_lo = _hdr_i64(hdr, 0)
    pane_hi = _hdr_i64(hdr, 2)
    anchor = _hdr_i64(hdr, 4)
    clear_lo = hdr[7]
    clear_hi = hdr[6]
    deltas = hdr[8:8 + fire_pad]
    w_valid = deltas > _DELTA_SENTINEL
    end_panes = jnp.where(w_valid, pane_lo + deltas.astype(jnp.int64),
                          _END_SENTINEL)

    def _fire(ring_in):
        return _ring_append_topn_core(
            state, ring_in, pane_lo, pane_hi, anchor, end_panes, w_valid,
            used_mask, agg=agg, panes_per_window=panes_per_window,
            ring=ring, sel_cap=sel_cap, by=by, topn=topn)

    emit_ring = lax.cond(jnp.any(w_valid), _fire,
                         lambda ring_in: ring_in, emit_ring)
    # 64-bit clear mask split over header words [7] (columns 0-31)
    # and [6] (columns 32-63) — rings up to 64 stay on the one-dispatch
    # fused path (a 2^22-record batch's event span outgrows 32)
    cm = (lax.shift_right_logical(
        clear_lo, jnp.arange(min(ring, 32), dtype=jnp.int32))
        & jnp.int32(1)) != 0
    if ring > 32:
        cm_hi = (lax.shift_right_logical(
            clear_hi, jnp.arange(min(ring - 32, 32), dtype=jnp.int32))
            & jnp.int32(1)) != 0
        cm = jnp.concatenate([cm, cm_hi])
    if ring > 64:
        cm = jnp.concatenate([cm, jnp.zeros(ring - 64, bool)])
    state = lax.cond(
        (clear_lo != 0) | (clear_hi != 0),
        lambda s: clear_kernel(s, cm.astype(jnp.int32)),
        lambda s: s, state)
    return state, emit_ring


_JIT_FUSED_STEP = jax.jit(
    fused_step_kernel,
    static_argnames=("agg", "panes_per_window", "ring", "sel_cap", "by",
                     "topn", "dump_row", "fire_pad"),
    donate_argnums=(0,))


def clear_kernel(state: PaneState, clear_mask: jax.Array) -> PaneState:
    """Reset ring columns selected by clear_mask to identities (ref
    role: WindowOperator.clearAllState / registerCleanupTimer).

    ``clear_mask`` is int32, padded to >=64 elements so the upload's
    size is fixed and never tiny whatever the ring (often 16 columns);
    whether tiny uploads cost extra on the current chip: not measured.
    Only the first ``ring`` entries are meaningful."""
    ring = state.counts.shape[1]
    cm = clear_mask[:ring] != 0
    m3 = cm[None, :, None]
    m2 = cm[None, :]

    def cl(arr, fill):
        return None if arr is None else jnp.where(m3, fill, arr)

    return PaneState(
        sums=cl(state.sums, 0.0),
        maxs=cl(state.maxs, -jnp.inf),
        mins=cl(state.mins, jnp.inf),
        counts=jnp.where(m2, 0, state.counts),
    )


# state is donated: each microbatch's update reuses the previous state's
# HBM buffers in place instead of allocating four fresh tensors (the
# caller always rebinds ``self.state = apply(self.state, ...)``, and
# checkpoint snapshots copy to host eagerly, so no stale reference ever
# reads a donated buffer)
_JIT_APPLY = jax.jit(
    apply_kernel,
    static_argnames=("agg", "ring", "dump_row"),
    donate_argnums=(0,))
_JIT_APPLY_SPLIT = jax.jit(
    apply_kernel_split,
    static_argnames=("agg", "dump_row"),
    donate_argnums=(0,))
_JIT_PREAGG_U16 = jax.jit(
    apply_preagg_u16_kernel,
    static_argnames=("ring", "dump_row"),
    donate_argnums=(0,))
_JIT_PREAGG_U32 = jax.jit(
    apply_preagg_u32_kernel,
    static_argnames=("ring", "dump_row"),
    donate_argnums=(0,))
_JIT_PREAGG_I32 = jax.jit(
    apply_preagg_i32_kernel,
    static_argnames=("sum_width", "ring", "dump_row"),
    donate_argnums=(0,))
_JIT_FIRE_PACK = jax.jit(
    fire_pack_kernel,
    static_argnames=("agg", "panes_per_window", "ring", "out_cap",
                     "packed2"))
# NOTE: emit_ring is NOT donated — the drain thread may be fetching the
# previous ring array concurrently with the next append dispatch, and
# donation would delete the buffer under that read. The append copies
# the (small, fixed) ring on device instead.
_JIT_RING_TOPN = jax.jit(
    ring_append_topn_kernel,
    static_argnames=("agg", "panes_per_window", "ring", "sel_cap", "by",
                     "topn", "fire_pad"))
_JIT_CLEAR = jax.jit(clear_kernel, donate_argnums=(0,))


def ring_remap_kernel(state: PaneState, src: jax.Array,
                      keep: jax.Array) -> PaneState:
    """Move every live pane column old→new when the pane ring is
    resized: new column j takes old column src[j] where keep[j], else
    the identity fill. Module-level jit so a growth (rare but on the
    latency path) compiles once per (old_ring, new_ring) shape pair per
    process, not once per growth event."""

    def cols(arr, fill):
        if arr is None:
            return None
        g = arr[:, src]
        m = keep[None, :, None] if g.ndim == 3 else keep[None, :]
        return jnp.where(m, g, fill)

    return PaneState(
        sums=cols(state.sums, 0.0),
        maxs=cols(state.maxs, -jnp.inf),
        mins=cols(state.mins, jnp.inf),
        counts=cols(state.counts, 0),
    )


# no donation: the remapped output has a different ring width than the
# input, so XLA could never reuse the buffers anyway (it would only warn)
_JIT_RING_REMAP = jax.jit(ring_remap_kernel)


def snapshot_clone_kernel(state: PaneState) -> PaneState:
    """A copy of every pane tensor in buffers of its own: what a
    checkpoint's freeze keeps while later steps donate ``state``'s. One
    program with a name (``jit_snapshot_clone_kernel``), so a device
    trace can be searched for it; it reads and writes the tensors once,
    as laid out. The input is not donated and ``copy`` is an operation
    of the program, so the output never aliases it."""
    return jax.tree_util.tree_map(jnp.copy, state)


_JIT_SNAPSHOT_CLONE = jax.jit(snapshot_clone_kernel)


def used_mask_kernel(next_free: jax.Array, *, slots_per_shard: int
                     ) -> jax.Array:
    """(rows,) bool: the rows of slots that have ever held a key, those
    below their shard's free pointer (``KeyDirectory.ever_used_mask``,
    made where it is read: the pointers go up, 4 bytes a shard, and not
    a byte a slot), and False for the dump row behind them."""
    used = (jnp.arange(slots_per_shard, dtype=jnp.int32)[None, :]
            < next_free[:, None])
    return jnp.concatenate([used.reshape(-1), jnp.zeros(1, bool)])


_JIT_USED_MASK = jax.jit(used_mask_kernel,
                         static_argnames=("slots_per_shard",))

# catch-up fires are evaluated in chunks of this many windows so they
# reuse the steady-state compiled kernels (pow2 pads: 1,2,4) and keep
# each packed buffer bounded — device→host bandwidth is the emit ceiling
# and chunked buffers still fetch together in one round trip
MAX_FIRE_CHUNK = 4
# the ring/top-n path appends in HBM (no per-fire fetch buffer), so it
# takes a steady advance's whole window list in ONE dispatch
MAX_FIRE_CHUNK_RING = 16
# the most cells a chunked top-n fire's slots x MIN_FIRE_PAD grid may
# have before the fire narrows to the ends that are real (_fire_ends):
# up to 32,768 slots (2^21 cells, 8 MB an intermediate) the full width
# costs little and one program serves every end count; the cost grows
# with the slots, and at 16.8 M of them the 64 columns are 4 GB an
# intermediate and do not compile for a 16 GB chip
FIRE_GRID_CELLS = 1 << 21
# the most (slot, ring column) cells a block may have for a count-only
# batch to go up as u32 pairs, the one upload the fused step can take
# (_process_batch_fused): a larger block never stashes a batch
FUSED_DOMAIN_MAX = 1 << 20


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


# ---------------------------------------------------------------------------
# Planning: static layout from assigner + timing characteristics.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class WindowPlan:
    pane_ms: int
    offset_ms: int
    size_ms: int
    slide_ms: int
    panes_per_window: int
    panes_per_slide: int
    ring: int
    allowed_lateness_ms: int

    @classmethod
    def plan(
        cls,
        assigner: WindowAssigner,
        *,
        allowed_lateness_ms: int = 0,
        max_out_of_orderness_ms: int = 0,
        headroom_panes: int = 4,
    ) -> "WindowPlan":
        pane = assigner.pane_ms
        # Live pane span: a pane stays until wm >= pane_start + size +
        # lateness; the newest writable pane is at max_ts = wm + delay.
        # headroom covers event time running ahead of the watermark clock
        # between advances (one microbatch's worth of time progress).
        span_ms = assigner.size_ms + allowed_lateness_ms + max_out_of_orderness_ms
        ring = -(-span_ms // pane) + 1 + headroom_panes
        if ring > 65536:
            raise ValueError(
                f"pane ring of {ring} panes (pane={pane}ms from gcd(size={assigner.size_ms},"
                f" slide={assigner.slide_ms})) is degenerate — choose a slide that divides"
                " the window size (or shares a larger common divisor)")
        return cls(
            pane_ms=pane,
            offset_ms=assigner.offset_ms,
            size_ms=assigner.size_ms,
            slide_ms=assigner.slide_ms,
            panes_per_window=assigner.panes_per_window,
            panes_per_slide=assigner.panes_per_slide,
            ring=ring,
            allowed_lateness_ms=allowed_lateness_ms,
        )

    def pane_of(self, ts: np.ndarray) -> np.ndarray:
        return (ts - self.offset_ms) // self.pane_ms

    def window_end_ms(self, end_pane: int) -> int:
        return int(end_pane) * self.pane_ms + self.offset_ms

    def window_dead(self, end_pane: int, wm: int) -> bool:
        """A window is dead (late beyond lateness) iff
        window.maxTimestamp() + allowedLateness <= watermark
        (ref: WindowOperator.isWindowLate / isCleanupTime)."""
        end_ms = end_pane * self.pane_ms + self.offset_ms
        return end_ms - 1 + self.allowed_lateness_ms <= wm

    def first_dead_pane(self, wm: int) -> int:
        """Panes strictly below this are finally purged at watermark wm:
        the LAST window containing the pane is dead. Exact reference
        boundary: ((p//pps)*pps + ppw) is that window's end pane."""
        if wm == LONG_MIN:
            return np.iinfo(np.int64).min // 2
        pps, ppw = self.panes_per_slide, self.panes_per_window
        t = wm + 1 - self.allowed_lateness_ms - self.offset_ms
        q = t // self.pane_ms - ppw
        return (q // pps + 1) * pps

    def fireable_end_panes(
        self, wm_prev: int, wm_now: int, min_pane_seen: Optional[int] = None
    ) -> List[int]:
        """Slide-aligned window end panes e with wm_prev < end-1 <= wm_now
        — the first-time firings this advance unlocks (batched
        EventTimeTrigger: fire iff wm >= window.maxTimestamp).

        min_pane_seen bounds the range at job start (windows entirely
        before the first record are empty and never emit anyway).
        """
        if wm_now == LONG_MIN:
            return []
        pps, ppw = self.panes_per_slide, self.panes_per_window
        # Window STARTS are slide-aligned (multiples of pps), so END panes
        # satisfy e ≡ ppw (mod pps) — not e ≡ 0 unless size % slide == 0.
        def align_down(m: int) -> int:
            return m - ((m - ppw) % pps)

        # window end time must satisfy end - 1 <= wm  => end_ms <= wm + 1
        hi_end = align_down((wm_now + 1 - self.offset_ms) // self.pane_ms)
        if wm_prev == LONG_MIN:
            if min_pane_seen is None:
                return []
            lo_end = align_down(min_pane_seen)
        else:
            lo_end = align_down((wm_prev + 1 - self.offset_ms) // self.pane_ms)
        out = []
        e = lo_end + pps
        while e <= hi_end:
            out.append(int(e))
            e += pps
        return out

    # -- shared host control-plane math (keyed WindowOperator and the
    # global WindowAllOperator both fire with EXACTLY these rules; any
    # semantic fix lands here once) --------------------------------------

    def late_refire_ends(self, late_panes: np.ndarray,
                         fired_below_end: int, wm: int) -> List[int]:
        """Ends of already-fired, still-live windows that a late-within-
        lateness record in ``late_panes`` must re-fire (ref:
        EventTimeTrigger.onElement fires immediately for late elements;
        isWindowLate skips dead windows)."""
        out: List[int] = []
        pps, ppw = self.panes_per_slide, self.panes_per_window
        for p in np.unique(late_panes).tolist():
            # windows containing pane p end at (p//pps)*pps + ppw,
            # stepping down by pps while > p
            e = (p // pps) * pps + ppw
            while e > p:
                if e <= fired_below_end and not self.window_dead(e, wm):
                    out.append(int(e))
                e -= pps
        return out

    def fire_frontier(self, wm: int) -> int:
        """Highest slide-aligned end pane the watermark has passed — the
        fired frontier late records compare against."""
        pps, ppw = self.panes_per_slide, self.panes_per_window
        m = (wm + 1 - self.offset_ms) // self.pane_ms
        return m - ((m - ppw) % pps)

    def last_data_end_ms(self, max_pane_seen: int) -> int:
        """End time (ms) of the last window that can contain data."""
        pps = self.panes_per_slide
        last_end = (max_pane_seen // pps) * pps + self.panes_per_window
        return last_end * self.pane_ms + self.offset_ms

    def enumerate_fire_ends(self, prev_wm: int, wm: int,
                            min_pane_seen: Optional[int],
                            max_pane_seen: Optional[int]) -> List[int]:
        """First-time fireable end panes for a prev_wm → wm advance,
        clamped to windows that can contain data (a big idle jump must
        not enumerate provably-empty windows)."""
        if max_pane_seen is None:
            return []
        ends_wm = min(wm, self.last_data_end_ms(max_pane_seen) - 1)
        if prev_wm != LONG_MIN and prev_wm >= ends_wm:
            return []
        return self.fireable_end_panes(prev_wm, ends_wm, min_pane_seen)

    def final_watermark_for(self, watermark: int,
                            max_pane_seen: Optional[int]) -> int:
        """Watermark completing (and purging) every window that can hold
        data — the end-of-input flush point."""
        if max_pane_seen is None:
            return watermark if watermark != LONG_MIN else 0
        return (self.last_data_end_ms(max_pane_seen)
                + self.allowed_lateness_ms + 1)


# ---------------------------------------------------------------------------
# Host-side operator runtime (single shard range; the sharded pipeline in
# exchange/ reuses the same kernels inside shard_map).
# ---------------------------------------------------------------------------

class _ShardedKernels(NamedTuple):
    """The jitted ``shard_map`` programs of one mesh configuration."""

    init: Any
    apply: Any
    apply_split: Any
    apply_pairs: Any
    fire_pack: Any
    ring_topn: Any
    clear: Any


@functools.lru_cache(maxsize=32)
def _sharded_kernels(mp: MeshPlan, agg, layout: PaneStateLayout,
                     panes_per_window: int,
                     exchange_capacity: Optional[int], exchange_impl: str,
                     topn_spec) -> _ShardedKernels:
    """The mesh path's programs, built once per configuration and shared
    by every operator of that configuration in the process (the local
    path's module-level jits do the same through their static
    arguments): an operator that built its own closures made every job
    trace and compile them again — inside the job's first batches.
    The fire and top-n programs take their compaction capacity as a
    static argument, so each pow2 bucket is one entry of the jit's own
    cache. Like any module-level jit, an entry lives as long as the
    process; what it keeps alive is its mesh and programs, not an
    operator.

    ``layout`` is one device's block."""
    from flink_tpu.exchange.spi import get_shuffle

    keyby_exchange = get_shuffle(exchange_impl)
    mesh = mp.mesh
    n_dev = mp.n_devices
    spd = mp.slots_per_device
    ring_len = layout.ring
    rows_local = layout.rows
    total_rows = n_dev * rows_local

    @functools.partial(jax.jit, out_shardings=mp.row_sharding())
    def init():
        def lane(width, fill):
            if width == 0:
                return None
            return jnp.full((total_rows, ring_len, width),
                            fill, jnp.float32)

        return PaneState(
            sums=lane(layout.sum_width, 0.0),
            maxs=lane(layout.max_width, -jnp.inf),
            mins=lane(layout.min_width, jnp.inf),
            counts=jnp.zeros((total_rows, ring_len), jnp.int32),
        )

    def exchange_report(overflow, records, cells=None):
        # what the exchange did, for the host to read when the step has
        # retired (see _resolve_reports): word 0 the entries the
        # all_to_all dropped over all devices, word 1 + d the RECORDS
        # device d received and scattered and, on the per-record lane,
        # a last word: the distinct cells the devices' applies updated
        # (see _scatter_panes) — one psum
        report = (jnp.zeros(1 + n_dev + (cells is not None), jnp.int32)
                  .at[0].set(jnp.sum(overflow).astype(jnp.int32))
                  .at[1 + lax.axis_index(AXIS)].set(
                      jnp.sum(records).astype(jnp.int32)))
        if cells is not None:
            report = report.at[1 + n_dev].set(cells)
        return lax.psum(report, AXIS)

    def apply_shard(state, packed, data):
        # packed = global_slot * ring + ring_ix (see apply_kernel);
        # route by owner device, then rebase to the local slot block
        cap = exchange_capacity or packed.shape[0]
        valid = packed >= 0
        p = jnp.where(valid, packed, 0)
        slot = p // ring_len
        dest = jnp.where(valid, slot // spd, 0).astype(jnp.int32)
        payload = {"__sp__": packed, **data}
        recv, rvalid, overflow = keyby_exchange(
            dest, valid, payload, n_devices=n_dev, capacity=cap)
        my = lax.axis_index(AXIS)
        rp = recv["__sp__"]
        rvalid = rvalid & (rp >= 0)
        rq = jnp.where(rvalid, rp, 0)
        local_packed = jnp.where(
            rvalid,
            (rq // ring_len - my * spd) * ring_len + rq % ring_len,
            -1)
        new_state, applied = apply_kernel(
            state, local_packed,
            {k: v for k, v in recv.items() if not k.startswith("__")},
            agg=agg, ring=ring_len, dump_row=layout.slots)
        return new_state, exchange_report(overflow, rvalid, applied[0])

    def lane_spec(width):
        return None if width == 0 else P(AXIS)

    state_spec = PaneState(
        sums=lane_spec(layout.sum_width), maxs=lane_spec(layout.max_width),
        mins=lane_spec(layout.min_width), counts=P(AXIS))
    batch_spec = P(AXIS)
    rep = P()

    apply = jax.jit(
        shard_map(
            apply_shard, mesh=mesh,
            in_specs=(state_spec, batch_spec, batch_spec),
            out_specs=(state_spec, rep),
        ),
        donate_argnums=(0,),
    )

    def apply_shard_split(state, sc, data):
        # 3-byte upload (see apply_kernel_split): decode + recombine
        # to the packed form on device — the host link gets the byte
        # savings; the ICI exchange keeps its existing layout
        slot, col = split_decode(sc)
        packed = jnp.where(
            slot == INVALID_SLOT_U16,
            jnp.int32(-1),
            slot.astype(jnp.int32) * ring_len + col.astype(jnp.int32))
        return apply_shard(state, packed, data)

    apply_split = jax.jit(
        shard_map(
            apply_shard_split, mesh=mesh,
            in_specs=(state_spec, batch_spec, batch_spec),
            out_specs=(state_spec, rep),
        ),
        donate_argnums=(0,),
    )

    def apply_pairs_shard(state, buf):
        # local-global aggregation: the host pre-aggregated the batch
        # (see _preagg_dispatch), so an entry of the exchange is one
        # (global slot, ring column) pair with its count and pre-added
        # sum lanes — preagg_encode_i32's buffer, pair < 0 = padding —
        # and not a record. Same routing as apply_shard: by the owner
        # of the pair's slot, then rebased to the local slot block
        cap = exchange_capacity or buf.shape[0]
        pair = buf[:, 0]
        valid = pair >= 0
        dest = jnp.where(valid, pair // ring_len // spd, 0).astype(jnp.int32)
        cols = {f"c{i}": buf[:, i] for i in range(buf.shape[1])}
        recv, rvalid, overflow = keyby_exchange(
            dest, valid, cols, n_devices=n_dev, capacity=cap)
        my = lax.axis_index(AXIS)
        rvalid = rvalid & (recv["c0"] >= 0)
        cnt = jnp.where(rvalid, recv["c1"], 0)
        local = jnp.stack(
            [jnp.where(rvalid, recv["c0"] - my * (spd * ring_len), -1), cnt]
            + [recv[f"c{i}"] for i in range(2, buf.shape[1])], axis=1)
        new_state = apply_preagg_i32_kernel(
            state, local, sum_width=layout.sum_width, ring=ring_len,
            dump_row=layout.slots)
        # in RECORDS: the sum of the counts of the pairs received
        return new_state, exchange_report(overflow, cnt)

    apply_pairs = jax.jit(
        shard_map(
            apply_pairs_shard, mesh=mesh,
            in_specs=(state_spec, batch_spec),
            out_specs=(state_spec, rep),
        ),
        donate_argnums=(0,),
    )

    # compaction capacity is a static shape → one compiled program per
    # pow2 bucket (the bucket grows with registered keys), kept by the
    # jit's own cache as the local path's static arguments are
    @functools.partial(jax.jit, static_argnames=("out_cap",))
    def fire_shard(state, params, used_mask, out_cap: int):
        def body(state, params, used_mask):
            packed = fire_pack_kernel(
                state, params, used_mask,
                agg=agg, panes_per_window=panes_per_window,
                ring=ring_len, out_cap=out_cap)
            # globalize row ids (each device block carries its own
            # rows); column 0 of body rows is the slot row, head
            # row 0 holds n
            my = lax.axis_index(AXIS).astype(jnp.int32)
            offset = jnp.zeros_like(packed[:, 0]).at[1:].set(
                my * rows_local)
            return packed.at[:, 0].add(offset)

        return shard_map(
            body, mesh=mesh, in_specs=(state_spec, rep, P(AXIS)),
            out_specs=P(AXIS))(state, params, used_mask)

    topn_shard = None
    if topn_spec is not None:
        by, topn = topn_spec

        @functools.partial(jax.jit, static_argnames=("sel_cap",))
        def topn_shard(state, emit_ring, params, used_mask, sel_cap: int):
            def body(state, emit_ring, params, used_mask):
                lo, hi, anchor, end_panes, w_valid = (
                    _unpack_fire_params(params))
                # Global per-window threshold: each device ranks its
                # local rows, the top-k candidates ride one tiny
                # all_gather over ICI, every device selects its local
                # rows against the GLOBAL n-th value (distributed
                # RANK() <= n), and appends winners to ITS OWN block
                # of the emit ring.
                sums, maxs, mins, counts = fire_kernel(
                    state, end_panes, w_valid, lo, hi,
                    panes_per_window=panes_per_window, ring=ring_len)
                rows = counts.shape[0]
                nz = ((counts > 0) & used_mask[:, None]
                      & w_valid[None, :])
                res = agg.finalize(sums, maxs, mins, counts)
                v = jnp.where(nz, res[by].astype(jnp.float32), -jnp.inf)
                k = min(topn, rows)
                local_top = top_values(v, k)                   # (W, k)
                all_top = lax.all_gather(
                    local_top, AXIS, axis=1, tiled=True)       # (W, n_dev*k)
                # -inf thresh (< n global candidates) selects all real
                # rows — nz masks out non-candidates
                thresh = top_values(all_top.T, k)[:, k - 1]
                my = lax.axis_index(AXIS).astype(jnp.int32)
                return _topn_select_append(
                    emit_ring, sums, maxs, mins, counts, nz, v,
                    thresh, end_panes, anchor, agg=agg,
                    sel_cap=sel_cap, row_offset=my * rows_local)

            return shard_map(
                body, mesh=mesh,
                in_specs=(state_spec, P(AXIS), rep, P(AXIS)),
                out_specs=P(AXIS))(state, emit_ring, params, used_mask)

    clear = jax.jit(
        shard_map(
            clear_kernel, mesh=mesh,
            in_specs=(state_spec, rep),
            out_specs=state_spec,
        ),
        donate_argnums=(0,),
    )
    return _ShardedKernels(init, apply, apply_split, apply_pairs,
                           fire_shard, topn_shard, clear)


class WindowOperator(ReuseRule):
    """Drives the kernels for one keyed window aggregation.

    Semantics golden-checked against the reference's WindowOperatorTest
    behaviours (ref: flink-streaming-java/src/test/.../windowing/
    WindowOperatorTest.java): event-time firing, allowed lateness with
    late re-firings, late-beyond-lateness side output, purge on cleanup.
    """

    def __init__(
        self,
        assigner: WindowAssigner,
        agg: LaneAggregate,
        *,
        num_shards: int = 128,
        slots_per_shard: int = 1024,
        allowed_lateness_ms: int = 0,
        max_out_of_orderness_ms: int = 0,
        shard_range: Optional[Tuple[int, int]] = None,
        mesh_plan: Optional[MeshPlan] = None,
        exchange_capacity: Optional[int] = None,
        top_n: Optional[Tuple[str, int]] = None,
        spill: bool = False,
        spill_store: Optional[Any] = None,
        exchange_impl: str = "all-to-all",
        host_pool: Optional[Any] = None,
        fold_chunk_records: Optional[int] = None,
    ) -> None:
        require_float_lanes(agg, "WindowOperator")
        self.assigner = assigner
        self.agg = agg
        self.mesh_plan = mesh_plan
        self.exchange_impl = exchange_impl
        # piggybacked ring-header knowledge (coalesced readback): tokens
        # carry the emit ring's [total, truncated] head words; once a
        # token AT OR AFTER the last row-carrying fire has landed, an
        # opportunistic drain poll whose known total equals the drained
        # count skips the ring fetch outright (see drain_ring).
        self._token_seq = 0
        self._rowfire_token_seq = 0  # tokens below this predate a fire
        self._ring_head_seq = 0
        self._ring_head_known = False
        self._ring_head_total = 0
        # processing-time mode (ref: TumblingProcessingTimeWindows +
        # ProcessingTimeTrigger + the proc-time half of the timer
        # service): records are stamped with the operator clock at
        # ingest and fires ride advance_processing_time — the SAME pane
        # machinery with the clock as the time axis. No lateness, no
        # out-of-orderness, by construction.
        self.uses_processing_time = bool(
            getattr(assigner, "is_processing_time", False))
        self.clock = None
        if self.uses_processing_time:
            from flink_tpu.time.clock import SystemProcessingTimeService
            self.clock = SystemProcessingTimeService()
            if allowed_lateness_ms:
                raise ValueError(
                    "allowed lateness is event-time-only; processing-"
                    "time windows cannot see late records")
            max_out_of_orderness_ms = 0
        if exchange_capacity is not None and exchange_capacity < 0:
            raise ValueError(
                f"exchange_capacity must be >= 0, got {exchange_capacity}")
        # 0 means auto everywhere (matches the config option), not
        # "capacity zero" — normalize here so direct construction and
        # the Driver path agree
        self.exchange_capacity = exchange_capacity or None
        # (result_field, n): fire only each window's top-n rows by that
        # field (ties kept) — evaluated on device, shrinking the emit
        # transfer to the winners (Q5 hot-items shape)
        self._topn = top_n
        # device-resident emit ring (top-n path): fires append winners in
        # HBM; the host polls one array at its own cadence (see
        # ring_append_topn_kernel). The array (``emit_ring.live``, lazy:
        # its shape needs the result arity), its announced versions,
        # the version counter, the fetch and the fire cohorts on their
        # way to delivery are ``ops/emit_ring.py``'s, which the device
        # session operator holds too; the names below are this
        # operator's views of them.
        self.emit_ring = EmitRing()
        self._ring_drained = 0
        self._ring_anchor: Optional[int] = None
        # 2048 rows ≈ 33KB: large against the tens of rows a steady
        # advance appends between polls, small against the ~1MB/s
        # effective cost of each announced device→host ring copy
        # (overflow is detected, loud, and names this knob)
        self.EMIT_RING_ROWS = 2048
        # bounded in-flight dispatch (credit-based flow control
        # analogue): ingest blocks on the oldest outstanding step once
        # this many are in flight, keeping the transport queue shallow
        # so emit polls/checkpoints never wait behind a deep backlog
        self.max_inflight_steps = 3
        # True when the runtime driver applies backpressure itself by
        # calling ``throttle()`` outside its push lock (see throttle())
        self.external_throttle = False
        self._inflight = collections.deque()
        # small device outputs of the per-record applies and the
        # sharded steps, read when their step has retired (see
        # _resolve_reports) — never block the pipeline per batch
        self._step_reports = collections.deque()
        # state.backend='spill': keys past HBM capacity aggregate on the
        # host (exact, slower) instead of dropping with a counter; the
        # shared host pool parallelizes its per-pane merges and
        # per-window fires
        # state.backend='lsm' passes an externally-built disk-tier
        # store (state/lsm.py, duck-type-compatible) via spill_store;
        # plain 'spill' builds the RAM store here
        self._spill = (spill_store if spill_store is not None
                       else HostSpillStore(
                           agg, pool=host_pool,
                           fold_chunk_records=fold_chunk_records)
                       if spill else None)
        # ranges the count-only lane's native key scan may run side by
        # side (_process_batch_fused): the shared pool's parallelism,
        # so host.parallelism = 1 (or no pool) is the serial scan
        self._scan_threads = (host_pool.parallelism
                              if host_pool is not None else 1)
        # a snapshot copies the directory's slot-sized arrays in ranges
        # through the shared pool
        self._host_pool = host_pool
        # top-n + spill: host rows can't ride per-fire markers because
        # device rows flow through the SHARED emit ring (a coalesced
        # drain would re-rank against the wrong fires). They queue here
        # and the drain merges them atomically with its ring poll.
        self._pending_ring_extras = collections.deque()
        # fused-lane pending upload (header space + u32 pairs), applied
        # by the next advance's single fused dispatch (see
        # fused_step_kernel) or flushed by _flush_stash
        self._stash_u32: Optional[np.ndarray] = None
        self.plan = WindowPlan.plan(
            assigner,
            allowed_lateness_ms=allowed_lateness_ms,
            max_out_of_orderness_ms=max_out_of_orderness_ms,
        )
        if mesh_plan is not None:
            slots_per_shard = mesh_plan.slots_per_shard
            if shard_range is None:
                # single-host mesh: the directory covers every shard;
                # devices own contiguous row blocks of it
                num_shards = mesh_plan.num_shards
            elif shard_range[1] - shard_range[0] != mesh_plan.num_shards:
                # cross-host: the LOCAL mesh spans exactly this
                # process's shard range; the directory keeps the GLOBAL
                # shard space so misrouted keys are detected (-1), and
                # its LOCAL slot ids line up with the mesh row blocks
                raise ValueError(
                    f"local mesh covers {mesh_plan.num_shards} shards "
                    f"but this process's range {shard_range} spans "
                    f"{shard_range[1] - shard_range[0]}")
        self.directory = KeyDirectory(num_shards, slots_per_shard, shard_range)
        # keys leave: a key whose newest pane has been purged gives its
        # slot back (_release_dead_keys; the reuse rule is there). Not
        # under a spill store: a key that failed allocation lives on the
        # host from then on, and the two stores' key sets must stay
        # disjoint (state/spill.py), so there nothing is released.
        self._releases = self._spill is None
        if self._releases:
            self.directory.track_panes()
        self._init_reuse()      # state/keyed.py ReuseRule
        # a purge whose release has not run yet (_defer_release), the
        # fire cohort of the advance that purged, and how many releases
        # ran, how many of them after their cohort had been queued for
        # the drain (``t_queued`` stamped)
        self._release_pending = False
        self._release_cohort: Optional[Dict[str, Any]] = None
        self.releases = 0
        self.releases_after_queue = 0
        # pack-mode fires (no top-n): the latest's number, and those
        # whose buffers the drain has not decoded yet
        self._pack_no = 0
        self._packs_open: set = set()
        per_block_slots = (
            mesh_plan.slots_per_device if mesh_plan else self.directory.local_slots)
        self.layout = PaneStateLayout(
            slots=per_block_slots,
            ring=self.plan.ring,
            sum_width=agg.sum_width,
            max_width=agg.max_width,
            min_width=agg.min_width,
        )
        self.watermark = LONG_MIN
        self._cleared_below = self.plan.first_dead_pane(LONG_MIN)  # panes < this are dead
        self._fired_below_end: Optional[int] = None  # highest end pane fired
        self._refire: set[int] = set()
        self._min_pane_seen: Optional[int] = None
        self._max_pane_seen: Optional[int] = None
        # the newest timestamp folded in on the general lane
        # (_count_disorder), and the window ends fired a second time for
        # a record that came after their first fire
        self._max_ts_seen: Optional[int] = None
        self.refire_ends: int = 0
        self.late_records: int = 0
        self.exchange_overflow: int = 0
        # what the keyed exchange of a mesh did over the job: steps
        # dispatched (a batch is one chunk unless a capacity splits it),
        # bytes handed to the upload, and the records each mesh device
        # received — counted on the device that scattered them and read
        # with the overflow word (see _resolve_reports)
        self.exchange_chunks: int = 0
        self.exchange_upload_bytes: int = 0
        # valid entries handed to the all_to_all, padding not counted: a
        # record on the per-record lane, a pre-aggregated pair on the
        # pair lane (see _exchange_pairs)
        self.exchange_entries: int = 0
        self.exchange_records = np.zeros(
            mesh_plan.n_devices if mesh_plan is not None else 0, np.int64)
        # bumped on every mutation; checkpointing reuses the previous
        # blob when unchanged (incremental, RocksDB shared-SST analogue)
        self.state_version: int = 0
        # records dropped because the key directory shard was FULL —
        # always accounted, surfaced in metrics/JobResult (never silent)
        self.records_dropped_full: int = 0
        # where the time goes: the phase clock (the driver hands over
        # its run's; a bare operator keeps this one) names every stretch
        # of process_batch / advance_watermark / the ring fetch, always
        # on; prof holds COUNTERS (ring fetches and skips, batches that
        # took a pre-aggregated upload) and the per-operator drain_fetch
        # seconds (profile.opN.*), fed by the drain.fetch phase
        self.phases = PhaseClock()
        self.prof: Dict[str, float] = collections.defaultdict(float)

        if mesh_plan is None:
            self.state = init_state(self.layout)
            self._build_local_kernels()
        else:
            self.state = self._init_sharded_state()
            self._build_sharded_kernels()

    # -- kernel construction --------------------------------------------
    def _build_local_kernels(self) -> None:
        # module-level jits (statics in the cache key) so operators with
        # equal configuration — across jobs in one process — share one
        # compiled kernel instead of recompiling per instance
        self._apply = functools.partial(
            _JIT_APPLY,
            agg=self.agg,
            ring=self.plan.ring,
            dump_row=self.layout.slots,
        )
        # 3-byte/record upload path: eligible while the slot id fits
        # uint16 (dump row included; 0xFFFF reserved for invalid) and the
        # ring column fits uint8. Re-checked here after every ring growth.
        self._split_upload = (
            self.layout.rows <= INVALID_SLOT_U16 and self.plan.ring <= 256)
        self._apply_split = functools.partial(
            _JIT_APPLY_SPLIT, agg=self.agg, dump_row=self.layout.slots)
        self._plan_preagg()
        self._preagg_u16 = functools.partial(
            _JIT_PREAGG_U16, ring=self.plan.ring, dump_row=self.layout.slots)
        self._preagg_u32 = functools.partial(
            _JIT_PREAGG_U32, ring=self.plan.ring, dump_row=self.layout.slots)
        self._preagg_i32 = functools.partial(
            _JIT_PREAGG_I32, sum_width=self.agg.sum_width,
            ring=self.plan.ring, dump_row=self.layout.slots)
        self._fire_pack = functools.partial(
            _JIT_FIRE_PACK,
            agg=self.agg,
            panes_per_window=self.plan.panes_per_window,
            ring=self.plan.ring,
            packed2=self._fire_packed2(),
        )
        if self._topn is not None:
            by, n = self._topn
            self._ring_topn = functools.partial(
                _JIT_RING_TOPN,
                agg=self.agg,
                panes_per_window=self.plan.panes_per_window,
                ring=self.plan.ring,
                by=by,
                topn=n,
            )
            # one-dispatch-per-batch path (apply + fire + clear fused;
            # see fused_step_kernel) — ring must fit the 64-bit clear
            # word in the header
            self._fused_step = (functools.partial(
                _JIT_FUSED_STEP,
                agg=self.agg,
                panes_per_window=self.plan.panes_per_window,
                ring=self.plan.ring,
                by=by,
                topn=n,
                dump_row=self.layout.slots,
            ) if self.plan.ring <= 64 else None)
        else:
            self._fused_step = None
        self._clear = _JIT_CLEAR

    def _plan_preagg(self) -> None:
        """Host pre-aggregation path: eligible when every accumulator
        lane is a host-combinable sum (LaneAggregate.sum_fields) and
        the (slot, ring column) pair domain keeps the host bincount
        cheap. The domain spans the directory's slot space
        (``directory.local_slots``): one block locally, every device's
        block under a mesh, where slots are GLOBAL (apply_shard routes
        by slot // spd). The per-batch choice (pairs vs records bytes)
        is dynamic — see _preagg_dispatch."""
        self._preagg_lanes = None
        self._preagg_ws = None  # lazy; domain changes on ring growth
        if (self.agg.max_width == 0 and self.agg.min_width == 0
                and self.agg.sum_fields is not None
                and len(self.agg.sum_fields) == self.agg.sum_width
                and self.directory.local_slots * self.plan.ring
                <= (1 << 23)):
            self._preagg_lanes = self.agg.sum_fields

    def _fire_pad_bucket(self, n_ends: int) -> int:
        """Static width of a fused dispatch's fire subgraph: the pow2
        bucket ≥ the sub-batch's real end count — at most
        log2(MIN_FIRE_PAD)+1 compiled buckets, shared process-wide
        through the module-level jit cache, and a steady cadence hits
        one or two of them. The fire cost (fire_kernel's rows×W
        reductions, the rows×W selection scan, the W-way top_k)
        scales with the bucket, so K sub-batch dispatches of ~W/K real
        ends each cost ≈ one W-wide fire instead of K full-pad fires —
        the other half of the sub-batching tax next to the zero-end
        cond skip."""
        return min(MIN_FIRE_PAD, _next_pow2(max(n_ends, 1)))

    def _count_fire(self, width: int) -> None:
        """One top-n fire dispatched at a static width of ``width`` window
        ends: ``fires``, and ``fires_direct`` where its program reads the
        window's live columns (``reads_live_columns``, known when the
        program is built)."""
        self.prof["fires"] += 1
        if reads_live_columns(width):
            self.prof["fires_direct"] += 1

    def _topn_cap(self, w: int) -> int:
        """Winner-buffer capacity: n rows per window plus generous tie
        headroom (ties beyond this raise at decode). Deliberately
        INDEPENDENT of the chunk's window count so every top-n fire
        buffer of this operator has one shape — the drain thread's
        stack-and-fetch then compiles exactly once."""
        n = self._topn[1]
        return _next_pow2(MAX_FIRE_CHUNK * max(64, 8 * n))

    def _fire_cap(self, w: int) -> int:
        """Static compaction capacity for a W-window fire buffer: fired
        rows per window never exceed registered keys (only used slots
        with data fire) nor the per-block slot count, so the pow2 bucket
        of that bound can never truncate. Buckets grow with key count →
        a handful of retraces over a job's life."""
        per_block = self.layout.slots
        nk = max(1, self.directory.num_keys())
        return _next_pow2(min(nk, per_block) * w)

    def _init_sharded_state(self) -> PaneState:
        return self._sharded_kernels().init()

    def _sharded_kernels(self) -> "_ShardedKernels":
        return _sharded_kernels(
            self.mesh_plan, self.agg, self.layout,
            self.plan.panes_per_window, self.exchange_capacity,
            self.exchange_impl, self._topn)

    def _build_sharded_kernels(self) -> None:
        """The full distributed hot path: per-device bucket-by-owner →
        all_to_all over the mesh (keyBy repartition on ICI) → local pane
        scatter. Fire/clear are embarrassingly parallel over row blocks.
        The programs are shared process-wide by configuration (see
        ``_sharded_kernels``), as the local path's module-level jits
        are: a second job of the same shape traces and compiles nothing.
        """
        k = self._sharded_kernels()
        self._apply_sharded = k.apply
        self._apply_sharded_split = k.apply_split
        # local-global aggregation: pre-aggregated pairs cross the
        # exchange where the batch allows it (see _exchange_pairs); the
        # fused apply+fire+clear step stays one-chip only
        self._apply_sharded_pairs = k.apply_pairs
        self._plan_preagg()
        self._fused_step = None
        # global slot ids must fit uint16 with 0xFFFF reserved
        self._split_upload = (
            self.mesh_plan.n_devices * self.mesh_plan.slots_per_device
            < INVALID_SLOT_U16 and self.plan.ring <= 256)
        self._fire_pack = k.fire_pack
        if self._topn is not None:
            self._ring_topn = k.ring_topn
        self._clear = k.clear

    # -- data path -------------------------------------------------------
    def process_batch(
        self,
        keys: np.ndarray,
        ts: np.ndarray,
        data: Dict[str, np.ndarray],
        valid: Optional[np.ndarray] = None,
    ) -> None:
        """Fold a batch of records in. Late-beyond-lateness rows are
        dropped (side output; ref: WindowOperator sideOutput/
        numLateRecordsDropped) and late-within-lateness rows mark their
        windows for re-firing."""
        if self.uses_processing_time:
            # the record's time axis IS the clock at ingest
            ts = np.full(len(np.asarray(ts)), self.clock.now_ms(),
                         np.int64)
        self.run_pending_release()    # ahead of this batch's allocations
        # count-only fused fast lane: ONE native scan does panes, late
        # masking, drop accounting, min/max, refire candidates, and the
        # pre-agg histogram (the numpy path below makes ~6 full-array
        # passes — real milliseconds on the single-core bench host)
        with self.phases.span("window.key_scan"):
            if (self._spill is None and self._preagg_lanes == ()
                    and (valid is None or bool(np.all(valid)))
                    and self._process_batch_fused(keys, ts)):
                return
            self._process_batch_general(keys, ts, data, valid)

    def _process_batch_general(self, keys, ts, data, valid) -> None:
        """The numpy lane of ``process_batch``: any aggregate, validity
        mask or spill store, and every batch whose pairs the fused scan
        did not make."""
        # the stretch of ``window.key_scan`` from here to the first phase
        # switch is named below the leaf: each statement lies in one
        # detail (``window.key_scan/<name>``); what a block spends in
        # another leaf (a stashed upload, ``state.reclaim``, the pack
        # the gate lets through) is that leaf's
        ph, detail = self.phases.phase, self.phases.detail
        with detail("prepare"):
            self._flush_stash()
            self._return_released()
            self.state_version += 1
            keys = np.asarray(keys, dtype=np.int64)
            ts = np.asarray(ts, dtype=np.int64)
            # ``whole``: every record is valid (none masked by the
            # caller, none late, none without a slot): the masks below
            # then cost no copies of the batch
            whole = valid is None and len(ts) > 0
            valid = (np.ones(len(ts), bool) if valid is None
                     else np.asarray(valid, bool))
        with detail("panes"):
            panes = self.plan.pane_of(ts)

            dead = self._cleared_below
            # the batch's oldest and newest record, and how many lie
            # behind the newest folded in before: one native pass. The
            # lowest and the highest pane follow (a pane is monotone in
            # the timestamp), and a batch whose lowest pane is alive has
            # no late record: the mask is made only where it has
            seen = self._max_ts_seen
            order = (ts_order_stats(ts, LONG_MIN if seen is None else seen)
                     if whole else None)
            if order is None or self.plan.pane_of(order[1]) < dead:
                late_mask = valid & (panes < dead)
                n_late = int(np.count_nonzero(late_mask))
                if n_late:
                    self.late_records += n_late
                    valid = valid & ~late_mask
                    whole, order = False, None

            some = whole or bool(valid.any())
            if some:
                if order is None:
                    order = ts_order_stats(
                        ts[valid], LONG_MIN if seen is None else seen)
                behind, oldest, newest = order
                mn = int(self.plan.pane_of(oldest))
                mx = int(self.plan.pane_of(newest))
                self._count_disorder(behind, oldest, newest, mx - mn + 1)
                prev_min = self._min_pane_seen
                prev_max = self._max_pane_seen
                if prev_min is None or mn < prev_min:
                    self._min_pane_seen = mn
                if prev_max is None or mx > prev_max:
                    self._max_pane_seen = mx

                # ring capacity guard: at most one live pane per ring
                # column. When event time runs ahead of the watermark
                # clock beyond plan bounds (big microbatches, stalled
                # watermark), GROW the ring and remap live columns
                # instead of failing — the backpressure answer is more
                # memory, not a crash. The remap range must cover only
                # panes ALREADY APPLIED to state (prev_min..prev_max) —
                # this batch's panes land after the grow, and remapping
                # their columns would alias unrelated live panes' data
                # into them.
                # the live span runs to the OPERATOR max (not just this
                # batch's): a late-but-allowed record far below the live
                # range must also trigger growth, or its column write
                # would alias a newer live pane
                live_lo = max(dead, self._min_pane_seen)
                live_hi = self._max_pane_seen
                if live_hi - live_lo >= self.plan.ring:
                    self._grow_ring(
                        live_hi - live_lo + 1, prev_min, prev_max)

            # late-but-allowed → re-fire affected, already-fired windows
            # with updated contents (ref: EventTimeTrigger.onElement
            # fires immediately for late elements within allowed
            # lateness)
            # (the batch's lowest valid pane says whether any record can
            # be one: in a stream inside its watermark's bound none is)
            if (some and self._fired_below_end is not None
                    and mn < self._fired_below_end):
                late_ok = valid & (panes < self._fired_below_end)
                self.prof["refire_probe_records"] += int(
                    np.count_nonzero(late_ok))
                self._refire.update(self.plan.late_refire_ends(
                    panes[late_ok], self._fired_below_end,
                    self.watermark))

        with detail("assign"):
            slots = self.directory.assign(keys)
            self.prof["assign_records"] = self.directory.assign_records
            self.prof["assign_memo_hits"] = self.directory.assign_memo_hits
        with detail("slot_mask"):
            bad = valid & (slots < 0)
            if bad.any():
                full = bad & (slots == KeyDirectory.FULL)
                if self._spill is not None and full.any():
                    # shard full: the key aggregates on the host instead
                    # — exact results at host speed (see state/spill.py)
                    sub = {k: data[k][full] for k in
                           (self.agg.fields if self.agg.fields is not None
                            else data)}
                    self._spill.absorb(keys[full], panes[full], sub)
                    bad = bad & ~full
                # remaining negatives: shard-full without a spill store,
                # or misrouted (-1: key outside this operator's
                # shard_range — a routing error the spill store must NOT
                # absorb, or the key would aggregate on two workers at
                # once). Default policy FAILS the job;
                # state.allow-drops=true drops with accounting (see
                # account_full_drop).
                if bad.any():
                    account_full_drop(self, int(bad.sum()))
                valid = valid & ~bad & ~full
                whole = False
        if self._releases:
            with detail("note_panes"):
                self.directory.note_panes(slots, panes, valid)
        with detail("preagg_gate"):
            # up to the gate's "no", or to its switch to window.pack
            took = self._preagg_dispatch(slots, panes, valid, data)
        if took:
            self._throttle_unless_external()
            return
        ph("window.pack")
        from flink_tpu.records import device_cast
        # upload ONLY the lanes the aggregate reads: e.g. Q5's count()
        # needs no record fields at all
        if self.agg.fields is not None:
            data = {k: data[k] for k in self.agg.fields}
        data = {k: device_cast(v) for k, v in data.items()}
        # pack (slot, ring column) into one narrow array — the only
        # per-record value the device scatter needs (see apply_kernel)
        ring = self.plan.ring
        local_split = self.mesh_plan is None and self._split_upload
        if not local_split:
            if whole:
                # a batch's valid panes lie within one ring's length of
                # its lowest (the ring guard above): a subtraction and a
                # wrap where ``%`` would divide 2^20 times
                cols = panes - (mn - mn % ring)
                cols[cols >= ring] -= ring
                packed = slots * ring
                packed += cols
            else:
                packed = slots * ring + panes % ring
                packed[~valid] = -1
            # dtype bound uses GLOBAL rows: in mesh mode slots are global
            # (apply_shard routes by slot // spd), so the max packed value
            # is n_devices × the local-block bound
            n_blocks = self.mesh_plan.n_devices if self.mesh_plan else 1
            dt = np.int32 if (n_blocks * self.layout.rows + 1) * ring < 2**31 else np.int64
            packed = packed.astype(dt, copy=False)
        if self.mesh_plan is None:
            if local_split:
                packed = split_encode(
                    slots, (panes % ring).astype(np.uint8), valid)
            ph("window.h2d")
            dpacked = jnp.asarray(packed)
            ddata = {k: jnp.asarray(v) for k, v in data.items()}
            ph("window.step_dispatch")
            self.state, report = (
                self._apply_split if local_split
                else self._apply)(self.state, dpacked, ddata)
            # what the apply combined (cells, records), read when the
            # step has retired; also the in-flight token: an output of
            # the step that is not donated
            self._step_reports.append(report)
            self._note_dispatch(report)
        else:
            n_dev = self.mesh_plan.n_devices
            ov_total = None
            self.exchange_entries += int(np.count_nonzero(valid))
            ph("window.exchange_split")
            for pk, dt_chunk, target in self._split_for_exchange(
                    packed, data, n_dev):
                # the chunk length was pow2-bucketed + device-aligned by
                # the splitter (its capacity check ran against THIS
                # padded layout); pad to it so the device-side arrival
                # split sees exactly the blocks the check saw — and so
                # data-dependent split sizes don't compile a fresh
                # shard_map program per novel shape
                pad = target - len(pk)
                if pad:
                    pk = np.concatenate([pk, np.full(pad, -1, dt)])
                    dt_chunk = {
                        k: np.concatenate(
                            [v, np.zeros((pad,) + v.shape[1:], v.dtype)])
                        for k, v in dt_chunk.items()}
                ph("window.pack")
                if self._split_upload:
                    pv = pk >= 0
                    pk = split_encode(
                        np.where(pv, pk // ring, 0),
                        np.where(pv, pk % ring, 0).astype(np.uint8), pv)
                ph("window.h2d")
                dpk = jnp.asarray(pk)
                ddata = {k: jnp.asarray(v) for k, v in dt_chunk.items()}
                self.exchange_chunks += 1
                self.exchange_upload_bytes += pk.nbytes + sum(
                    v.nbytes for v in dt_chunk.values())
                ph("window.step_dispatch")
                self.state, report = (
                    self._apply_sharded_split if self._split_upload
                    else self._apply_sharded)(self.state, dpk, ddata)
                # LAZY exchange accounting: reading the step's report
                # (records dropped, records each device received) would
                # block the pipeline on every step. One device-side sum
                # per PUSH (not per chunk) so the marker deque stays 1:1
                # with _inflight and throttle() never touches an
                # in-flight chunk's report. The host-side split makes
                # overflow structurally impossible — the counter is the
                # backstop.
                ov_total = report if ov_total is None else ov_total + report
                ph("window.exchange_split")   # the next chunk's padding
            if ov_total is not None:
                self._step_reports.append(ov_total)
            ph("window.step_dispatch")
            # inflight token: a tiny scalar DERIVED from the new state —
            # the state buffers themselves are donated to the next step,
            # so holding them would read deleted buffers
            self._note_dispatch(self.state.counts[0, 0])
        self._throttle_unless_external()

    def _count_disorder(self, behind: int, oldest: int, newest: int,
                        panes_spanned: int) -> None:
        """What a batch's valid records say of the stream's order, on the
        general lane (``ts_order_stats`` counted them): ``behind`` of
        them are stamped below the newest timestamp the operator had
        folded in before the batch (``disorder_records``), the farthest
        by ``disorder_max_ms``; ``batch_panes`` sums the panes a batch's
        records span."""
        prof, seen = self.prof, self._max_ts_seen
        prof["batch_panes"] += panes_spanned
        prof["disorder_records"] += behind
        # (a lane's first batch makes the counters: they read a number
        # from then on, 0 where nothing was counted)
        prof["refire_probe_records"] += 0
        if behind and seen - oldest > prof["disorder_max_ms"]:
            prof["disorder_max_ms"] = float(seen - oldest)
        else:
            prof["disorder_max_ms"] += 0
        if seen is None or newest > seen:
            self._max_ts_seen = newest

    def _throttle_unless_external(self) -> None:
        if not self.external_throttle:
            self.phases.phase("ingest.throttle")
            self.throttle()

    def _may_stash(self) -> bool:
        """Whether ANY batch of this operator can be stashed for the
        fused step, whose launch then carries the batch, the fires and
        the purge together (``_advance_fused``): the gates of
        ``process_batch`` and ``_process_batch_fused`` that no batch can
        move, read off the static shapes. The one place that knows them:
        the stash below asks it, and so does ``may_lead_advance``."""
        return (self._fused_step is not None and self._spill is None
                and self.mesh_plan is None and self._preagg_lanes == ()
                and self.layout.slots * self.plan.ring <= FUSED_DOMAIN_MAX)

    def _process_batch_fused(self, keys: np.ndarray, ts: np.ndarray) -> bool:
        """Count-only ingest via codec.cc ingest_fused_scan: ONE C pass
        does the key→slot directory probe AND the pane/late/refire/
        histogram scan (the separate assign pass wrote+reread an 8 MB
        slots array per 2^20 batch), and the
        finalize emits the packed u32 upload buffer straight from C.
        Returns False (no pane state touched; at most new keys
        registered in the directory, which assign would do anyway) when
        the native lib is missing, the batch looks high-cardinality, or
        the refire span is degenerate — the caller then runs the
        general path."""
        from flink_tpu.native_codec import (
            NativeHashTable, PreaggWorkspace,
            ingest_fused_finalize_pairs_native,
            ingest_fused_finalize_u32_native, ingest_fused_scan_native)
        if not isinstance(self.directory._table, NativeHashTable):
            return False
        keys = np.asarray(keys, dtype=np.int64)
        ts = np.asarray(ts, dtype=np.int64)
        n = len(ts)
        ring = self.plan.ring
        nk = self.directory.num_keys()
        cap = _next_pow2(max(min(n, max(nk, 1) * ring), 256))
        if 4 * cap > 2 * n or cap > (1 << 21):
            return False
        dead = self._cleared_below
        refire_below = (self._fired_below_end
                        if self._fired_below_end is not None
                        else np.iinfo(np.int64).min)
        bits = 0
        if refire_below > dead:
            span = refire_below - dead
            if span > (1 << 20):
                return False  # degenerate lateness span: general path
            bits = int(span)
        prev_min, prev_max = self._min_pane_seen, self._max_pane_seen
        ph = self.phases.phase
        self._return_released()
        for _attempt in (0, 1):
            domain = self.directory.local_slots * self.plan.ring
            if (self._preagg_ws is None or self._preagg_ws.domain != domain
                    or self._preagg_ws.nlanes != 0):
                self._preagg_ws = PreaggWorkspace(domain, 0)
            scan = ingest_fused_scan_native(
                keys, ts, self.directory._table, self.plan.pane_ms,
                self.plan.offset_ms, self.plan.ring, self._preagg_ws,
                cap, dead, refire_below, bits, miss_cap=n,
                threads=self._scan_threads)
            if scan is None:
                return False
            res, miss_ix = scan
            if len(miss_ix):
                # new keys this batch: allocate + insert (no second
                # lookup — the probe already proved absence), then
                # continue the SAME scan over just the missed records
                self.directory.register_misses(keys[miss_ix])
                scan = ingest_fused_scan_native(
                    keys[miss_ix], ts[miss_ix], self.directory._table,
                    self.plan.pane_ms, self.plan.offset_ms,
                    self.plan.ring, self._preagg_ws, cap, dead,
                    refire_below, bits, cont=res, miss_cap=1)
                if scan is None:
                    return False
                res, miss2 = scan
                if len(miss2):  # can't happen post-registration
                    self._preagg_ws.rezero()
                    return False
            (n_valid, n_late, n_bad, pmin, pmax, n_refire, _nmiss,
             cmax, pane_moves) = (int(x) for x in res.stats)
            if n_valid == 0:
                break
            if self._min_pane_seen is None or pmin < self._min_pane_seen:
                self._min_pane_seen = pmin
            if self._max_pane_seen is None or pmax > self._max_pane_seen:
                self._max_pane_seen = pmax
            live_lo = max(dead, self._min_pane_seen)
            live_hi = self._max_pane_seen
            if live_hi - live_lo >= self.plan.ring and _attempt == 0:
                # ring too small for the live span: grow (remapping only
                # panes applied BEFORE this batch) and redo the scan —
                # its histogram columns were taken mod the old ring
                self._preagg_ws.rezero()
                self._grow_ring(live_hi - live_lo + 1, prev_min, prev_max)
                continue
            break
        self.state_version += 1
        self.late_records += n_late
        # how often the scan had to divide for a pane: 1-2 a batch on an
        # in-order stream (one more for each further range of a split
        # scan), ~n when panes alternate (codec.cc PaneCursor); and the
        # record ranges the first pass ran in side by side: the pool's
        # parallelism where the batch was long enough, 1 where not
        self.prof["scan_pane_moves"] += pane_moves
        self.prof["scan_ranges"] += res.ranges
        if n_bad:
            account_full_drop(self, n_bad)
        if n_refire:
            late_panes = (np.flatnonzero(
                np.unpackbits(res.bitmap, bitorder="little")) + dead)
            self._refire.update(self.plan.late_refire_ends(
                late_panes, self._fired_below_end, self.watermark))
        if n_valid == 0:
            return True
        if self._releases:
            # once a distinct (slot, pane) pair, not once a record
            self.directory.note_pairs(
                res.out_pairs[:res.npairs], self.plan.ring, pmin)
        ph("window.pack")
        self.prof["preagg_batches"] += 1
        if self.mesh_plan is not None:
            self._exchange_pairs(*ingest_fused_finalize_pairs_native(
                res, self._preagg_ws), [])
            self._throttle_unless_external()
            return True
        domain = self.layout.slots * self.plan.ring
        cap = _next_pow2(max(res.npairs, 256))
        if cmax < 0xFFF and domain <= FUSED_DOMAIN_MAX:
            # u32 pack emitted straight from C, with fused-step header
            # space reserved up front: the pending advance fills it and
            # dispatches apply+fire+clear as ONE program with ONE upload
            buf = ingest_fused_finalize_u32_native(
                res, self._preagg_ws, FUSED_HDR, cap)
            if self._may_stash() and self._stash_u32 is None:
                self._stash_u32 = buf
                return True
            buf, step = buf[FUSED_HDR:], self._preagg_u32
        else:
            pairs, cnts = ingest_fused_finalize_pairs_native(
                res, self._preagg_ws)
            if cmax <= 0xFFFF:
                buf, step = preagg_encode_u16(pairs, cnts, cap), \
                    self._preagg_u16
            else:
                buf, step = preagg_encode_i32(pairs, cnts, [], cap), \
                    self._preagg_i32
        self._upload_and_step(step, buf)
        self._throttle_unless_external()
        return True

    def _upload_and_step(self, step, buf: np.ndarray) -> None:
        """One pre-aggregated pair buffer to the device and through its
        apply program, as the phases window.h2d and
        window.step_dispatch."""
        self.phases.phase("window.h2d")
        dbuf = jnp.asarray(buf)
        self.phases.phase("window.step_dispatch")
        self.state = step(self.state, dbuf)
        self._note_dispatch(self.state.counts[0, 0])

    def _flush_stash(self) -> None:
        """Dispatch a pending fused-lane pair buffer as a plain apply —
        every consumer of up-to-date state (non-fused advances, fire
        chunking, snapshots, quiesce, ring growth, the general ingest
        path) calls this first."""
        buf = self._stash_u32
        if buf is None:
            return
        self._stash_u32 = None
        with self.phases.span("window.h2d"):   # then the caller's again
            self._upload_and_step(self._preagg_u32, buf[FUSED_HDR:])

    def _preagg_dispatch(
        self,
        slots: np.ndarray,
        panes: np.ndarray,
        valid: np.ndarray,
        data: Dict[str, np.ndarray],
    ) -> bool:
        """Try the host-pre-aggregated upload: combine the batch per
        (slot, ring column) pair on the host and ship one small pair
        buffer instead of per-record ids. Dispatches and returns True
        when the pair buffer is decisively smaller than the per-record
        upload (fewer link bytes; the link's rate on the current chip:
        not measured); False falls through to the per-record paths
        unchanged."""
        lanes_f = self._preagg_lanes
        if lanes_f is None:
            return False
        nv = int(valid.sum())
        if nv == 0:
            return False
        ring = self.plan.ring
        pv = panes[valid]
        span = int(pv.max() - pv.min()) + 1
        nk = self.directory.num_keys()
        bound = min(nv, max(nk, 1) * min(span, ring))
        bpp = 6 if not lanes_f else 4 * (2 + len(lanes_f))
        cap = _next_pow2(max(bound, 256))
        # decisive-win gate vs the 3 B/record split upload; high-
        # cardinality batches keep the per-record path
        if bpp * cap > 2 * len(panes):
            return False
        domain = self.directory.local_slots * ring
        native = None
        if cap <= (1 << 21):
            from flink_tpu.native_codec import (
                PreaggWorkspace, preagg_combine_native)
            if (self._preagg_ws is None
                    or self._preagg_ws.domain != domain
                    or self._preagg_ws.nlanes != len(lanes_f)):
                self._preagg_ws = PreaggWorkspace(domain, len(lanes_f))
            native = preagg_combine_native(
                slots, panes, valid, [data[f] for f in lanes_f],
                ring, self._preagg_ws, cap)
        if native is not None:
            pairs, cnts, lanes = native
        else:
            pairs, cnts, lanes = preagg_combine(
                slots, panes % ring, valid, data, lanes_f,
                ring=ring, domain=domain)
        self.phases.phase("window.pack")
        self.prof["preagg_batches"] += 1
        if self.mesh_plan is not None:
            self._exchange_pairs(pairs, cnts, lanes)
            return True
        cap = _next_pow2(max(len(pairs), 256))
        cmax = 0 if len(cnts) == 0 else int(cnts.max())
        if not lanes and cmax < 0xFFF and domain <= (1 << 20):
            # tightest: one u32 per pair (pair<<12 | count)
            buf = np.full(cap, -1, np.int32)
            buf[:len(pairs)] = (pairs.astype(np.int64) << 12
                                | cnts.astype(np.int64)).astype(np.uint32
                                                                ).view(np.int32)
            step = self._preagg_u32
        elif not lanes and cmax <= 0xFFFF:
            buf, step = preagg_encode_u16(pairs, cnts, cap), self._preagg_u16
        else:
            buf, step = preagg_encode_i32(pairs, cnts, lanes, cap), \
                self._preagg_i32
        self._upload_and_step(step, buf)
        return True

    def _exchange_pairs(self, pairs: np.ndarray, cnts: np.ndarray,
                        lanes: List[np.ndarray]) -> None:
        """The mesh's pair lane (local-global aggregation, ref: the
        mini-batch two-phase aggregate of table/runtime): one batch's
        pre-aggregated (global slot, ring column) pairs, their counts
        and pre-added sum lanes cross the keyed exchange, not its
        records. The buffer is preagg_encode_i32's (32-bit counts: a hot
        key puts half a batch on one pair), padded and cut into arrival
        blocks exactly as a record chunk is, so the capacity check of
        ``_split_for_exchange`` holds for pairs as it does for records.
        Dispatched at once: nothing is stashed under a mesh."""
        ph = self.phases.phase
        ph("window.exchange_split")
        names = [f"lane{i}" for i in range(len(lanes))]
        cols = {"cnt": cnts, **dict(zip(names, lanes))}
        report = None
        for pk, dt, target in self._split_for_exchange(
                pairs, cols, self.mesh_plan.n_devices, floor=256):
            ph("window.pack")
            buf = preagg_encode_i32(
                pk, dt["cnt"], [dt[k] for k in names], target)
            ph("window.h2d")
            dbuf = jnp.asarray(buf)
            self.exchange_chunks += 1
            self.exchange_upload_bytes += buf.nbytes
            ph("window.step_dispatch")
            self.state, chunk_report = self._apply_sharded_pairs(
                self.state, dbuf)
            report = (chunk_report if report is None
                      else report + chunk_report)
            ph("window.exchange_split")   # the next chunk's padding
        self.exchange_entries += len(pairs)
        ph("window.step_dispatch")
        # one report a push (see the per-record lane), which is also the
        # in-flight token: an output of the step that is not donated
        self._step_reports.append(report)
        self._note_dispatch(report)

    def hbm_bytes(self) -> int:
        """Static device-state footprint PER DEVICE: pane tensors +
        emit ring. HBM is a per-chip resource — state shards one layout
        block per device, so widening the mesh leaves the per-chip
        footprint constant and the memory.hbm-budget check must not
        scale with fleet size."""
        state = self.layout.bytes()
        ring = 0
        if self._topn is not None:
            cols = 3 + len(self._pack_fields())
            ring = (self.EMIT_RING_ROWS + 2) * cols * 4
        return state + ring

    def exchange_stats(self) -> Optional[Dict[str, Any]]:
        """What the mesh's keyed exchange did so far, ``None`` without a
        mesh: ``chunks`` (sharded steps dispatched), ``upload_bytes``,
        ``entries`` (valid entries handed to the all_to_all: records, or
        pairs on the pair lane),
        ``records`` (per mesh device, the records it received from the
        all_to_all and scattered; steps still in flight are waited for)
        and ``state_rows`` (per mesh device, the pane-state rows that
        lie on it)."""
        mp = self.mesh_plan
        if mp is None:
            return None
        self._resolve_reports()
        rows = {sh.device: int(sh.data.shape[0])
                for sh in self.state.counts.addressable_shards}
        return {"chunks": self.exchange_chunks,
                "upload_bytes": self.exchange_upload_bytes,
                "entries": self.exchange_entries,
                "records": self.exchange_records.copy(),
                "state_rows": np.asarray(
                    [rows.get(d, 0) for d in mp.mesh.devices.flat], np.int64)}

    def _note_dispatch(self, token, head=None) -> None:
        """Record one dispatched device step on the in-flight credit
        deque. ``token``: a tiny non-donated output of the step, which
        is ANNOUNCED (copy_to_host_async) here — the throttle retires
        the step by CONSUMING that in-flight copy, a wait on a transfer
        and never a separate is_ready poll of the backend. The ingest
        dispatches pass a derived scalar (``state.counts[0, 0]``, the
        sharded step's report); the fused step passes the emit ring's
        head row, and ``head=(i_total, i_trunc)`` names the ring header
        words such a token carries (coalesced readback).

        THE announce happens here, once, for every step (re-announcing
        an already-announced array is a no-op): a token that skipped its
        announce would silently turn the throttle's consume into an
        unannounced blocking round trip."""
        if hasattr(token, "copy_to_host_async"):
            token.copy_to_host_async()
        self._token_seq += 1
        self._inflight.append((token, head, self._token_seq))

    def _retire_step(self) -> None:
        """Retire the oldest in-flight step: consume its announced
        token (a wait on an in-flight transfer, not an extra control
        round trip)."""
        token, head, seq = self._inflight.popleft()
        arr = np.asarray(token)  # blocks on the announced copy only
        if head is not None:
            self._note_ring_head(arr, head, seq)

    @staticmethod
    def _raise_truncation(truncated: int) -> None:
        """The ONE top-n winner-buffer overflow error — raised from the
        ring fetch (drain_ring) and from a landed step token's
        head words, which detect it without a fetch."""
        raise RuntimeError(
            f"top-n winner-buffer truncation: {truncated} selected "
            "rows exceeded the per-fire selection capacity (tie "
            "explosion at the n-th value); raise n or aggregate "
            "first")

    def _note_ring_head(self, arr: np.ndarray, head, seq: int) -> None:
        """Fold a landed token's emit-ring head words into host
        knowledge: loud truncation detection without a ring fetch, and
        the drain-skip fact (see drain_ring) — the head is trusted only
        once the token postdates every row-carrying fire."""
        total = int(arr[head[0]])
        truncated = int(arr[head[1]])
        if truncated > 0:
            self._raise_truncation(truncated)
        with self.emit_ring.lock:
            if seq >= self._rowfire_token_seq and seq > self._ring_head_seq:
                self._ring_head_seq = seq
                self._ring_head_known = True
                self._ring_head_total = total

    def throttle(self) -> None:
        """Apply ingest backpressure: block on the oldest outstanding
        step once more than ``max_inflight_steps`` are in flight. The
        driver sets ``external_throttle`` and calls this OUTSIDE its
        push lock — the block is where transfer-bound pipelines spend
        most of their time, and holding the lock through it would stall
        the drain thread's deliveries behind it (emit latency)."""
        while len(self._inflight) > self.max_inflight_steps:
            self._retire_step()
        # overflow markers older than the steps just retired are ready
        # (int() is a cheap host read); draining to the same bound keeps
        # the deque finite in jobs that never checkpoint
        self._resolve_reports(bound=self.max_inflight_steps)

    def quiesce(self) -> None:
        """Block until every dispatched step has completed. The driver
        calls this before the FINAL watermark advance so the flush fires
        dispatch onto an idle device — their emit latency then measures
        fire+fetch, not the whole tail of the ingest pipeline."""
        self.run_pending_release()
        self._flush_stash()
        while self._inflight:
            self._retire_step()
        ready_wait(self.state.counts)
        self._resolve_reports()

    def _resolve_reports(self, bound: int = 0) -> None:
        """Read the reports of retired steps (all but the newest
        ``bound``) into the counters: what the per-record apply
        combined (``profile.opN.apply_cells`` distinct cells updated,
        ``apply_records`` valid records folded) and, on a mesh, what the
        exchange did. With the host-side batch split, any non-zero
        overflow is a routing bug — fail loudly, not under-count."""
        n_dev = len(self.exchange_records)
        while len(self._step_reports) > bound:
            report = np.asarray(self._step_reports.popleft())
            if self.mesh_plan is None:
                cells, records = report
            else:
                self.exchange_overflow += int(report[0])
                self.exchange_records += report[1:1 + n_dev]
                if len(report) == 1 + n_dev:    # the pair lane
                    continue
                cells, records = report[1 + n_dev], report[1:1 + n_dev].sum()
            self.prof["apply_cells"] += int(cells)
            self.prof["apply_records"] += int(records)
        if self.exchange_overflow:
            raise RuntimeError(
                f"exchange overflow: {self.exchange_overflow} records "
                "dropped by the keyBy all_to_all despite the host-side "
                "split — per-destination routing bug")

    @staticmethod
    def _pow2_target(b: int, n_dev: int) -> int:
        """Dispatch length for a ``b``-record chunk: next pow2, then
        aligned to the device count (one compiled program per bucket)."""
        t = max(n_dev, _next_pow2(max(b, 1)))
        return t + (-t) % n_dev

    def _split_for_exchange(
            self, packed: np.ndarray, data: Dict[str, np.ndarray],
            n_dev: int, floor: int = 1) -> List[Tuple[np.ndarray, Dict, int]]:
        """Split a batch so no (source-block, destination) bucket of the
        all_to_all exchange exceeds ``exchange_capacity`` — data loss
        becomes structurally impossible instead of counted (the
        credit-based no-loss property, ref: SURVEY §3.6; a skewed key
        routing everything to one shard simply costs more steps).

        Yields ``(chunk, data, target)`` where ``target`` is the padded
        dispatch length — the capacity check runs against the SAME
        padded block layout the device-side arrival split will use
        (block length ``target // n_dev``), so an accepted chunk cannot
        overflow after padding. Capacity None = block-sized buckets,
        which can never overflow — one chunk, no check. ``b == 1`` is
        the termination backstop: a single record occupies one bucket,
        safe for any capacity ≥ 1 (enforced at config load). ``packed``
        holds records or, on the pair lane, pre-aggregated pairs (the
        same slot * ring + column ids); ``floor`` is the least dispatch
        length, which keeps small pair counts off a program each."""
        cap = self.exchange_capacity
        if cap is None:
            return [(packed, data,
                     self._pow2_target(max(len(packed), floor), n_dev))]
        ring = self.plan.ring
        spd = self.mesh_plan.slots_per_device
        out: List[Tuple[np.ndarray, Dict, int]] = []
        stack = [(packed, data)]
        while stack:
            pk, dt = stack.pop()
            b = len(pk)
            if not b:
                continue
            target = self._pow2_target(max(b, floor), n_dev)
            L = target // n_dev  # arrival-split block length AT DISPATCH
            valid = pk >= 0
            dest = np.where(valid, (pk // ring) // spd, 0)
            block = np.arange(b) // L  # < n_dev since b <= target
            flat = np.where(valid, block * n_dev + dest, n_dev * n_dev)
            counts = np.bincount(flat, minlength=n_dev * n_dev + 1)
            if counts[:n_dev * n_dev].max(initial=0) <= cap or b <= 1:
                out.append((pk, dt, target))
            else:
                mid = b // 2
                stack.append((pk[mid:], {k: v[mid:] for k, v in dt.items()}))
                stack.append((pk[:mid], {k: v[:mid] for k, v in dt.items()}))
        return out

    def _grow_ring(
        self, need: int, applied_min: Optional[int], applied_max: Optional[int]
    ) -> None:
        """Resize the pane ring to hold ≥ ``need`` live panes and remap
        every live column old→new (global pane p moves from column
        p % old_ring to p % new_ring). Rare — a watermark stall or an
        oversized microbatch — and costs one gather + a kernel rebuild
        (recompile on next dispatch).

        ``applied_min``/``applied_max`` bound the panes actually written
        to state so far (the caller's pane-seen range BEFORE the batch
        that triggered the grow) — remapping beyond them would copy
        whatever live pane aliases those old ring columns into the new
        columns, duplicating data into phantom windows."""
        self._flush_stash()  # stashed pairs are encoded in OLD ring columns
        old_ring = self.plan.ring
        new_ring = _next_pow2(need + 4)
        lo = self._cleared_below
        if applied_min is not None:
            lo = max(lo, applied_min)
        hi = applied_max if applied_max is not None else lo - 1
        # column map: new column -> old column (or -1 = identity fill)
        cmap = np.full(new_ring, -1, np.int64)
        if hi >= lo:
            ps = np.arange(lo, hi + 1)
            cmap[ps % new_ring] = ps % old_ring

        src = jnp.asarray(np.maximum(cmap, 0).astype(np.int32))
        keep = jnp.asarray(cmap >= 0)
        new_state = _JIT_RING_REMAP(self.state, src, keep)
        if self.mesh_plan is not None:
            new_state = jax.device_put(new_state, self.mesh_plan.row_sharding())
        self.state = new_state
        self.plan = dataclasses.replace(self.plan, ring=new_ring)
        self.layout = dataclasses.replace(self.layout, ring=new_ring)
        if self.mesh_plan is None:
            self._build_local_kernels()
        else:
            self._build_sharded_kernels()

    # -- time path -------------------------------------------------------
    def advance_processing_time(self) -> "FiredWindows":
        """Fire windows the processing-time clock has passed (the
        batched ProcessingTimeTrigger). Driven by the runtime between
        steps; tests drive a ManualProcessingTimeService directly."""
        return self.advance_watermark(self.clock.now_ms() - 1)

    def advance_watermark(self, wm: int) -> "FiredWindows":
        """Advance event time; fire newly-complete windows plus pending
        re-fires; purge dead panes. Returns the fired-window batch
        (key, window_start, window_end, count, result fields...) as a
        lazy ``FiredWindows`` — the device work is dispatched here, the
        single device→host transfer happens on first access."""
        if wm < self.watermark or (wm == self.watermark and not self._refire):
            return self._empty()
        with self.phases.span("window.fire_dispatch"):
            return self._advance_watermark(wm)

    def may_lead_advance(self) -> bool:
        """Asked once, when the job is built: can an advance of this
        operator ever go ahead of its batch (``lead_advance``)? Only
        where the batch and the advance are launches of their own: one
        device, event time, no spill store, and no batch ever rides the
        fused step (``_may_stash``: there the stash, the fires and the
        purge are ONE launch; led, the fire takes a launch and a clear
        of its own. Tried on the chip in PR 45, when the drain's
        delivery still queued behind the batch's push: the fused lane
        leading read 17.75 / 18.52 / 17.92 ms p50 beside 18.00 / 18.54 /
        18.45 in ``q5_hostfed_paced`` (``input_to_fire`` 5.19 -> 1.61
        ms, ``push_wait`` 0.09 -> 2.71) and 174.35 / 178.14 M events/s
        beside 174.15 / 176.10 in ``q5_hostfed_replay``: inside the
        cells' noise. Since PR 48 the loop holds the push lock through
        no batch (``Driver._find_drain_reach``), so that wait is gone
        and the trial is worth making again: ROADMAP S2 (c), D19). The
        shapes that decide it move
        one way only (a ring that grows takes the fused lane away, never
        brings it), so a job that answers no keeps today's order
        throughout."""
        return not (self.mesh_plan is not None or self._spill is not None
                    or self.uses_processing_time or self._may_stash())

    def lead_advance(self, wm: int, ts: np.ndarray) -> bool:
        """May the advance to ``wm`` go AHEAD of the batch with the
        timestamps ``ts`` (the driver's question, put before it pushes
        the batch that implied ``wm``)? Yes where the two commute and the
        advance has something to do:

        - ``may_lead_advance``: the same answer for every batch of a job;
        - something was folded in already, and ``wm`` passes a window
          end, a purge horizon or a pending re-fire: an advance that
          does nothing is not worth a pass over ``ts``;
        - every record of the batch is stamped above ``wm``: it lies in
          windows that end after ``wm`` (a window's newest timestamp is
          at least ``ts``) and in panes the purge leaves alone, and is
          late under neither watermark, so the same rows fire, the same
          panes are cleared and the same state results in either order
          (a key whose newest pane the purge takes and which this batch
          names again is released and inserted anew where it would have
          survived: its rows are equal)."""
        if (not self.may_lead_advance() or self._min_pane_seen is None
                or wm <= self.watermark):
            return False
        if not (self._refire
                or self._fired_below_end is None
                or self.plan.fire_frontier(wm) > self._fired_below_end
                or self.plan.first_dead_pane(wm) > self._cleared_below):
            return False
        # of the advances with something to do, those the batch's oldest
        # record lets lead (a stream in order: all; one that is not, as
        # long as its disorder stays inside the watermark's bound)
        self.prof["advances_with_work"] += 1
        led = int(ts.min()) > wm
        self.prof["advances_led"] += led
        return led

    def _advance_watermark(self, wm: int) -> "FiredWindows":
        self.run_pending_release()    # one purge, one release
        self.state_version += 1
        prev = self.watermark
        self.watermark = wm

        ends = sorted(set(self.plan.enumerate_fire_ends(
            prev, wm, self._min_pane_seen, self._max_pane_seen))
            | self._refire)
        # the fired frontier must track the WATERMARK, not just enumerated
        # ends: a late-within-lateness record landing in any window the
        # watermark already passed (fired or empty-skipped) must trigger
        # an immediate late firing (ref: EventTimeTrigger.onElement FIREs
        # when window.maxTimestamp() <= currentWatermark)
        frontier = self.plan.fire_frontier(wm)
        if self._fired_below_end is None or frontier > self._fired_below_end:
            self._fired_below_end = frontier
        self.refire_ends += len(self._refire)
        self._refire.clear()
        # fused path: the pending ingest stash + these fires + the purge
        # ride ONE device dispatch with ONE upload
        if (self._stash_u32 is not None and self._fused_step is not None
                and self._spill is None and self.mesh_plan is None):
            out = self._advance_fused(wm, ends)
            if out is not None:
                return out
        self._flush_stash()
        # host-store keys fire on the SAME ends list (incl. refires) —
        # disjoint key sets, so rows simply ride along
        extra = (self._spill.fire(
            ends, self.plan.panes_per_window, self.plan.pane_ms,
            self.plan.offset_ms, self.plan.size_ms)
            if self._spill is not None and ends else None)
        if self._topn is not None and self._spill is not None:
            # top-n + spill: drain the ring SYNCHRONOUSLY at each fire.
            # Device rows flow through a shared ring with no per-fire
            # attribution, so letting the drain thread coalesce fires
            # would re-rank one fire's host rows against another fire's
            # device winners (and a refired window's stale rows would
            # poison the union — rank fields aren't monotone across
            # refires). One fire per drain makes the union re-rank
            # trivially exact. The cost — a blocking ring fetch per
            # advance — lands only in spill mode, which has already
            # traded peak speed for capacity.
            with self.emit_ring.lock:
                out = self._fire_ends(ends)
                if extra is not None:
                    self._pending_ring_extras.append(extra)
            if out._ring or extra is not None:
                out = FiredWindows(data=self.drain_ring(),
                                   cohort=out.cohort)
        else:
            out = self._fire_ends(ends)
            if extra is not None:
                out._extra = extra
                out._topn_spec = self._topn

        # purge panes no window can need anymore; only columns actually
        # written (>= min pane seen) can hold data
        new_dead = self.plan.first_dead_pane(wm)
        if new_dead > self._cleared_below:
            lo = self._cleared_below
            if self._min_pane_seen is not None:
                lo = max(lo, self._min_pane_seen)
            else:
                lo = new_dead  # nothing written yet — nothing to clear
            hi = new_dead
            if hi > lo:
                # padded i32 mask — see clear_kernel's transfer note
                mask = np.zeros(max(self.plan.ring, 64), dtype=np.int32)
                if hi - lo >= self.plan.ring:
                    mask[:self.plan.ring] = 1
                else:
                    ring_positions = np.arange(lo, hi) % self.plan.ring
                    mask[ring_positions] = 1
                self.state = self._clear(self.state, jnp.asarray(mask))
            self._cleared_below = new_dead
            if self._spill is not None:
                self._spill.purge_below(new_dead)
            self._defer_release(out)
        return out

    # -- keys that leave, and the reuse rule ------------------------------
    def _defer_release(self, fired: "FiredWindows") -> None:
        """A purge has moved ``_cleared_below``: its release is left
        PENDING, and the advance returns. The release is the long part
        of a purging advance (~1.2 M deletes, ~0.1 s, at 8.5 M live
        keys) and no fired row needs it: the caller first hands
        ``fired`` to whoever delivers it, then calls
        ``run_pending_release`` (the driver: outside its push lock, so
        the drain decodes and delivers meanwhile). Whatever needs the
        directory up to date runs it first: the next batch, the next
        advance, a snapshot, ``quiesce``."""
        fired.purged = True
        if self._releases:
            self._release_pending = True
            self._release_cohort = getattr(fired, "cohort", None)

    def run_pending_release(self) -> None:
        """Run the release a purging advance left pending; nothing
        where none is. On the thread that drives the operator: the
        directory keeps its one writer. The drain thread may decode
        fired rows meanwhile: of the directory it reads ``_rev_keys``
        alone (``key_of_slots``), which a release does not write."""
        if self._release_pending:
            self._release_dead_keys()

    def _release_dead_keys(self) -> None:
        """After a purge has moved ``_cleared_below``: release every key
        whose newest pane lies below it. ``first_dead_pane`` already
        holds allowed lateness and refires, so no record that the
        operator would still accept can reach such a key's panes: its
        next record starts a new life in a pane that is alive, in
        whatever slot the allocator then gives it. The device rows of a
        released slot are identities already: the purge that moved the
        horizon cleared every column its key wrote (relied on by the
        fire, which tells a row that counts from one that does not by
        its counts alone, and asserted in tests/test_key_release.py, not
        here). Costs what the directory examines (keys born or last
        seen alive in the purged panes), never the slot space.

        The released slots wait under THE REUSE RULE (``state/keyed.py``
        ``ReuseRule``), stamped with the number of fires dispatched when
        the release RUNS (never earlier than at its purge: it runs
        before the next batch and the next advance): the one-chip emit
        ring, the mesh's ring blocks, the chunked ``_fire_ends`` and the
        fused step all number their fires by the ring version they bump,
        pack-mode fires by ``_pack_no``; the drain's decodes are
        ``drain_ring`` and ``_decode_packs``."""
        cohort, self._release_cohort = self._release_cohort, None
        self._release_pending = False
        self.releases += 1
        if cohort is not None and cohort.get("t_queued") is not None:
            self.releases_after_queue += 1
        with self.phases.span("state.release"):
            rel = self.directory.release_below(self._cleared_below)
            if len(rel):
                self._hold_released(self._fires_so_far(), rel)

    def _fires_so_far(self) -> int:
        """The number of the latest fire dispatched."""
        return (self.emit_ring.version_no if self._topn is not None
                else self._pack_no)

    def _note_pack_decoded(self, pack_no: int) -> None:
        """A pack-mode fire's buffers were decoded (or dropped unread):
        decoded runs through the fire before the oldest still open."""
        with self.emit_ring.lock:
            self._packs_open.discard(pack_no)
            self.emit_ring.note_decoded(min(self._packs_open) - 1
                               if self._packs_open else self._pack_no)

    def state_counters(self) -> Dict[str, int]:
        """The keyed state's life so far (``JobResult.metrics``)."""
        d = self.directory
        grows, grow_s, buckets = d.table_growth()
        return {"state.slots_allocated": d.slots_allocated,
                "state.slots_reused": d.slots_reused,
                "state.slots_released": d.slots_released,
                "state.slots_returned_early": self.slots_returned_early,
                # purges whose release ran, and those of them that ran
                # once their fire's cohort was with the drain
                "state.releases": self.releases,
                "state.releases_after_queue": self.releases_after_queue,
                "state.live_keys": d.num_keys(),
                "state.live_keys_peak": d.keys_peak,
                "state.slots_waiting_peak": self.slots_waiting_peak,
                # the key table's doublings, each a standstill of the
                # batch that met it, and its buckets now
                "state.table_grows": grows,
                "state.table_grow_s": grow_s,
                "state.table_buckets": buckets,
                # the pane tensors' geometry on one device: what a byte
                # model of a program over the whole state needs
                "state.pane_rows": self.layout.rows,
                "state.ring_columns": self.plan.ring}

    def _fused_fill_header(self, wm: int, ends: List[int],
                           buf: np.ndarray) -> Optional[Tuple[List[int], int]]:
        """Fill the FUSED_HDR-word fused-step header in place: pane bounds,
        ring anchor, clear word, fire-end deltas. Returns
        (fired_ends, cleared_below_after) or None when the fire list
        overflows the fused window slots."""
        ppw = self.plan.panes_per_window
        if self._max_pane_seen is None:
            ends_f: List[int] = []
            lo = self._cleared_below
        else:
            lo = max(self._cleared_below, self._min_pane_seen)
            hi = self._max_pane_seen
            ends_f = [e for e in ends if e > lo and e - ppw <= hi]
        if len(ends_f) > MIN_FIRE_PAD:
            return None
        ring = self.plan.ring
        # purge decision (mirrors the non-fused tail): mask bits ride
        # the header's clear word
        new_dead = self.plan.first_dead_pane(wm)
        clear_word = 0
        cleared_after = self._cleared_below
        if new_dead > self._cleared_below:
            clo = self._cleared_below
            if self._min_pane_seen is not None:
                clo = max(clo, self._min_pane_seen)
            else:
                clo = new_dead
            if new_dead > clo:
                if new_dead - clo >= ring:
                    clear_word = (1 << ring) - 1
                else:
                    for p in range(clo, new_dead):
                        clear_word |= 1 << (p % ring)
            cleared_after = new_dead
        if self._ring_anchor is None:
            self._ring_anchor = lo
        hi_v = self._max_pane_seen if self._max_pane_seen is not None else lo - 1
        buf[:6] = np.array([lo, hi_v, self._ring_anchor],
                           np.int64).view(np.int32)
        cw = np.array([clear_word], np.uint64).view(np.int32)
        buf[7], buf[6] = cw[0], cw[1]
        deltas = np.full(MIN_FIRE_PAD, _DELTA_SENTINEL, np.int64)
        if ends_f:
            deltas[:len(ends_f)] = np.asarray(ends_f, np.int64) - lo
        buf[8:8 + MIN_FIRE_PAD] = deltas.astype(np.int32)
        return ends_f, cleared_after

    def _advance_fused(self, wm: int, ends: List[int]) -> Optional["FiredWindows"]:
        """One-dispatch advance: apply the stashed pair upload, fire up
        to MIN_FIRE_PAD window ends, and purge dead panes in a single
        fused program (see fused_step_kernel). Returns None when the
        fire list overflows the fused window slots — the caller then
        flushes the stash and takes the chunked path."""
        buf = self._stash_u32
        hdr = self._fused_fill_header(wm, ends, buf)
        if hdr is None:
            return None
        ends_f, cleared_after = hdr
        self._stash_u32 = None
        used = self._used_mask_device()
        self.phases.phase("window.h2d")
        dbuf = jnp.asarray(buf)
        self.phases.phase("window.fire_dispatch")
        fire_pad = self._fire_pad_bucket(len(ends_f))
        self.state, self.emit_ring.live, token = self._fused_step(
            self.state, self._ensure_ring(), dbuf, used,
            sel_cap=self._topn_cap(MIN_FIRE_PAD), fire_pad=fire_pad)
        if ends_f:   # without an end the step's cond skips the fire
            self._count_fire(fire_pad)
        # the kernel's ring-head row is the step's token
        # (_note_dispatch announces it): the throttle's wait is a
        # consume of that in-flight copy, and its head words stand in
        # for a ring-header poll
        self._note_dispatch(token, head=(0, 1))
        purged = cleared_after > self._cleared_below
        self._cleared_below = cleared_after
        out = self._ring_after_fire(ends_f, covered=True)
        if purged:
            self._defer_release(out)
        return out

    def _fire_cohort(self, end_panes: List[int]) -> Dict[str, Any]:
        """The record of one fire dispatch: its window ends (ms) and
        ``t_fire``, on ``time.perf_counter()``. The fetch that makes its
        rows host-visible adds ``t_fetch0`` / ``t_ready`` / ``t_fetch1``;
        the driver adds ``op``, ``t_input``, ``t_queued``, ``t_push0``
        and ``t_sink`` (see ``Driver.fire_records``)."""
        return {"window_ends": [e * self.plan.pane_ms + self.plan.offset_ms
                                for e in end_panes],
                "t_fire": time.perf_counter()}

    def _ring_after_fire(self, ends: List[int],
                         covered: bool = False) -> "FiredWindows":
        """Post-fire ring bookkeeping shared by the fused and chunked
        top-n paths: version bump + announce (see EmitRing.versions).
        ``covered``: this fire rode a dispatch whose
        token carries the POST-fire ring head (the fused step) — that
        token (or any later one) re-validates the
        piggybacked head; a chunked fire has no token of its own, so
        only a FUTURE dispatch's token can."""
        n_ends = len(ends)
        cohort = None
        with self.emit_ring.lock:
            self.emit_ring.version_no += 1
            if n_ends > 0:
                # row-carrying fire: stamp the cohort for host-visibility
                # latency attribution (EmitRing.fire_stamps)
                cohort = self._fire_cohort(ends)
                self.emit_ring.stamp(cohort)
                # rows may have been appended: the piggybacked ring head
                # goes stale until a token at/after this fire lands
                self._ring_head_known = False
                self._rowfire_token_seq = (
                    self._token_seq if covered else self._token_seq + 1)
            # a fire that carries rows announces the version that holds
            # them: the drain waits for THAT copy to land, and must find
            # it begun; a zero-row advance keeps the cadence
            ring = self.emit_ring
            if (n_ends > 0 or time.perf_counter() - ring.last_announce
                    >= ring.announce_interval_s):
                ring.announce(ring.live)
            return FiredWindows(op=self, ring=True,
                                ring_no=self.emit_ring.version_no,
                                cohort=cohort)

    def _fire_ends(self, ends: List[int]) -> "FiredWindows":
        if not ends or self._max_pane_seen is None:
            return self._empty()
        # windows entirely outside the written pane range are empty — skip
        lo = max(self._cleared_below, self._min_pane_seen)
        hi = self._max_pane_seen
        ppw = self.plan.panes_per_window
        ends = [e for e in ends if e > lo and e - ppw <= hi]
        if not ends:
            return self._empty()
        # pad the window axis to a power of two (compile once per bucket
        # size, not per distinct fire count) and CHUNK large fires at
        # MAX_FIRE_CHUNK windows: a catch-up advance reuses the small
        # steady-state kernels instead of compiling a one-off giant one
        used = self._used_mask_device()
        packs = []
        step = MAX_FIRE_CHUNK_RING if self._topn is not None else MAX_FIRE_CHUNK
        for c0 in range(0, len(ends), step):
            chunk = ends[c0:c0 + step]
            W = len(chunk)
            Wp = 1
            while Wp < W:
                Wp *= 2
            if self._topn is not None and self._ring_anchor is None:
                self._ring_anchor = lo
            ends_padded = chunk + [int(_END_SENTINEL)] * (
                max(Wp, MIN_FIRE_PAD) - W)
            params = jnp.asarray(np.asarray(
                [lo, hi, self._ring_anchor or 0] + ends_padded, dtype=np.int64))
            if self._topn is not None:
                # the fire's rows x W grid is all MIN_FIRE_PAD ends of
                # the params where that is small (ONE program, whatever
                # the end count: nothing to build when a catch-up brings
                # two ends), and the real ends' pow2 bucket where 64
                # columns over the rows would not fit the device
                width = ({"fire_pad": Wp} if self.mesh_plan is None
                         and self.layout.slots * MIN_FIRE_PAD > FIRE_GRID_CELLS
                         else {})
                self.emit_ring.live = self._ring_topn(
                    self.state, self._ensure_ring(), params, used,
                    sel_cap=self._topn_cap(Wp), **width)
                self._count_fire(width.get("fire_pad", len(ends_padded)))
            else:
                buf = self._fire_pack(
                    self.state, params, used, out_cap=self._fire_cap(Wp))
                # start the device→host copy NOW: by the time the drain
                # polls, the bytes are host-cached and np.asarray is a
                # local read instead of a blocking device round trip
                buf.copy_to_host_async()
                packs.append((lo, buf))
        if self._topn is not None:
            return self._ring_after_fire(ends)
        with self.emit_ring.lock:
            self._pack_no += 1
            self._packs_open.add(self._pack_no)
        return FiredWindows(op=self, packs=packs, pack_no=self._pack_no,
                            cohort=self._fire_cohort(ends))

    def _fire_packed2(self) -> bool:
        """Static gate of the 2-column packed fire layout (local
        path): count-only aggregate, slot ids < 2^23, end deltas < 2^8
        (delta <= live ring span + panes_per_window). All plan facts —
        never data-dependent."""
        return (self.mesh_plan is None and not self._pack_fields()
                and self.layout.slots < (1 << 23)
                and self.plan.ring + self.plan.panes_per_window
                < (1 << 8))

    def _pack_fields(self) -> List[str]:
        """Result lanes as stored in packed buffers / the emit ring —
        the result fields MINUS 'count', which always rides the exact
        i32 column 2 (storing it twice was 25% of WordCount's egress
        bytes)."""
        return [f for f in self._result_fields() if f != "count"]

    def _result_fields(self) -> List[str]:
        """Sorted result-lane field names — the packed buffer's column
        order past [row, end_delta, count]. MUST mirror
        fire_pack_kernel's ``sorted(res)`` exactly (including a result
        field named 'count' if the aggregate emits one)."""
        if not hasattr(self, "_res_fields"):
            from flink_tpu.ops.aggregates import probe_finalize

            res = probe_finalize(self.agg)
            self._res_fields = sorted(res)
            self._res_is_int = {
                k: np.issubdtype(np.asarray(res[k]).dtype, np.integer)
                for k in res
            }
        return self._res_fields

    def _decode_packs(self, packs, bufs) -> Dict[str, np.ndarray]:
        """Host-side decode of fetched fire buffers (bitcast lanes,
        slot → key, pane → window times). Each buffer's layout is read
        from ITS OWN width — decode is lazy (drain thread), and a ring
        growth between fire dispatch and materialization can flip the
        op's packed2 gate while 2-column packs are still in flight."""
        pack_fields = self._pack_fields()
        segs = []  # (buffer_body_slice, lo)
        for (lo, _), buf in zip(packs, bufs):
            if self.mesh_plan is None:
                n = int(buf[0, 0])
                self._check_fire_cap(n, len(buf) - 1)
                segs.append((buf[1:1 + n], lo))
            else:
                blk = len(buf) // self.mesh_plan.n_devices
                for d in range(self.mesh_plan.n_devices):
                    block = buf[d * blk:(d + 1) * blk]
                    n = int(block[0, 0])
                    self._check_fire_cap(n, blk - 1)
                    segs.append((block[1:1 + n], lo))
        rows_l, ep_l, cnt_l, lane_l = [], [], [], []
        for body, lo in segs:
            if body.shape[1] == 2:   # packed2: (row << 8 | delta, count)
                rows_l.append(body[:, 0] >> 8)
                ep_l.append(lo + (body[:, 0] & 0xFF).astype(np.int64))
                cnt_l.append(body[:, 1])
                # packed2 is gated to count-only aggs: no extra lanes
            else:
                rows_l.append(body[:, 0])
                ep_l.append(lo + body[:, 1].astype(np.int64))
                cnt_l.append(body[:, 2])
                lane_l.append(body[:, 3:])
        if rows_l:
            rows = np.concatenate(rows_l)
            end_pane = np.concatenate(ep_l)
            count = np.concatenate(cnt_l)
        else:
            rows = np.zeros(0, np.int32)
            end_pane = np.zeros(0, np.int64)
            count = np.zeros(0, np.int32)
        window_end = end_pane * self.plan.pane_ms + self.plan.offset_ms
        out: Dict[str, np.ndarray] = {
            "key": self.directory.key_of_slots(self._slot_of_rows(rows)),
            "window_start": window_end - self.plan.size_ms,
            "window_end": window_end,
            "count": count,
        }
        # "count" rides an exact i32 column; the pack carries only the
        # OTHER result lanes (see fire_pack_kernel)
        if pack_fields:
            lanes = (np.concatenate(lane_l) if lane_l
                     else np.zeros((0, len(pack_fields)), np.int32))
            for i, k in enumerate(pack_fields):
                col = np.ascontiguousarray(lanes[:, i])
                out[k] = (col if self._res_is_int[k]
                          else col.view(np.float32))
        return out

    def _ensure_ring(self) -> jax.Array:
        """Lazily allocate the device emit ring: row 0 = monotone counter
        head, rows 1..cap = data, last row = scatter dump."""
        if self.emit_ring.live is None:
            C = 3 + len(self._pack_fields())
            shape = (self.EMIT_RING_ROWS + 2, C)
            if self.mesh_plan is not None:
                n_dev = self.mesh_plan.n_devices
                self.emit_ring.live = jax.device_put(
                    np.zeros((n_dev * shape[0], C), np.int32),
                    self.mesh_plan.row_sharding())
                self._ring_drained_blocks = [0] * n_dev
            else:
                self.emit_ring.live = jnp.zeros(shape, jnp.int32)
        return self.emit_ring.live

    def drain_ring(self, min_no: Optional[int] = None) -> Dict[str, np.ndarray]:
        """Fetch the emit ring ONCE and decode every row appended since
        the previous drain (the host-side poll of the device emit
        buffer). Overflow — more appends than the ring holds between
        polls — is detected from the monotone counter and raises.

        ``min_no``: the oldest ring version this drain may read (a
        barrier passes its fire's version so its rows are guaranteed
        present; None = latest). The fetch prefers the newest version
        whose announced copy already landed — see EmitRing.versions;
        the driver's drain chooses and waits ahead of the poll, under
        no lock (``FiredWindows.await_landing``)."""
        with self.emit_ring.lock:
            # the version the drain chose ahead of this poll and has
            # waited for (EmitRing.await_landing), where it did
            wanted = self.emit_ring.take_wanted(min_no == 0)
            # pop pending host-spill extras together with the ring read:
            # the appender holds the same lock across (ring dispatch,
            # extra enqueue), so the rows observed here are exactly the
            # fires whose extras we pop — per-fire attribution without
            # per-fire ring segmentation
            extras = list(self._pending_ring_extras)
            self._pending_ring_extras.clear()
            # the fire (ring version) through which this call has every
            # row in hand; None when it fetched nothing
            seen_no = None
            if self.emit_ring.live is None or self._ring_anchor is None:
                arr = None
            elif (min_no == 0 and self.mesh_plan is None
                  and self._ring_head_known
                  and self._ring_head_total == self._ring_drained):
                # coalesced readback: a landed step token that postdates
                # every row-carrying fire says the ring's appended total
                # equals what this host already drained — there is
                # provably nothing to fetch, so the opportunistic poll
                # skips the device round trip outright. Barrier drains
                # (min_no > 0 / None) always fetch. The same proof
                # covers every pending fire stamp (a stamped fire
                # postdating the trusted token would have invalidated
                # the head): their rows are already host-visible, so
                # deliver the stamps NOW — a zero-row fire cohort's
                # latency sample must not age across skipped polls.
                now = time.perf_counter()
                seen_no = self.emit_ring.version_no
                self.emit_ring.deliver_stamps(seen_no, now, now, now)
                self.prof["drain_skips"] += 1
                arr = None
            else:
                need = self.emit_ring.version_no if min_no is None else min_no
                with self.phases.span("drain.fetch", ring=need) as fetch:
                    arr, no_read, t_ready = self.emit_ring.fetch_version(
                        need, opportunistic=(min_no == 0), wanted=wanted)
                if no_read is not None:
                    # every fire cohort at or below the fetched version
                    # just became host-visible — hand it, with this
                    # fetch's stamps (it began where the drain began to
                    # want the rows), to the latency accounting
                    self.emit_ring.deliver_stamps(
                        no_read, wanted.t_want if wanted else fetch.t0,
                        t_ready, fetch.t1)
                    seen_no = no_read
                self.prof["drain_fetch"] += fetch.seconds
                self.prof["drain_fetches"] += 1
        if arr is None:
            if seen_no is not None:
                self.emit_ring.note_decoded(seen_no)
            out = dict(self._empty())
            if extras:
                out = _drain_merge_extras(out, extras, self._topn)
            return out
        row_cap = self.EMIT_RING_ROWS
        bodies = []
        if self.mesh_plan is None:
            blocks = [(arr, 0)]
        else:
            blk = len(arr) // self.mesh_plan.n_devices
            blocks = [(arr[d * blk:(d + 1) * blk], d)
                      for d in range(self.mesh_plan.n_devices)]
        for block, d in blocks:
            drained = (self._ring_drained if self.mesh_plan is None
                       else self._ring_drained_blocks[d])
            total = int(block[0, 0])
            truncated = int(block[0, 1])
            if truncated > 0:
                self._raise_truncation(truncated)
            new = total - drained
            if new > row_cap:
                raise RuntimeError(
                    f"emit ring overflow: {new} rows appended since last "
                    f"drain > capacity {row_cap}; drain more often or "
                    "raise EMIT_RING_ROWS")
            if new > 0:
                ix = (drained + np.arange(new)) % row_cap + 1
                bodies.append(block[ix])
            if self.mesh_plan is None:
                self._ring_drained = total
            else:
                self._ring_drained_blocks[d] = total
        fields = self._pack_fields()
        if bodies:
            body = np.concatenate(bodies)
        else:
            body = np.zeros((0, 3 + len(fields)), np.int32)
        rows = body[:, 0]
        end_pane = self._ring_anchor + body[:, 1].astype(np.int64)
        window_end = end_pane * self.plan.pane_ms + self.plan.offset_ms
        out: Dict[str, np.ndarray] = {
            "key": self.directory.key_of_slots(self._slot_of_rows(rows)),
            "window_start": window_end - self.plan.size_ms,
            "window_end": window_end,
            "count": body[:, 2],
        }
        # rows are keys now: the reuse rule may let go of slots released
        # up to the fire this fetch read through
        self.emit_ring.note_decoded(seen_no)
        for i, k in enumerate(fields):
            col = np.ascontiguousarray(body[:, 3 + i])
            out[k] = col if self._res_is_int[k] else col.view(np.float32)
        if extras:
            out = _drain_merge_extras(out, extras, self._topn)
        return out

    def take_delivered_fires(self) -> List[Dict[str, Any]]:
        """Pop the fire cohorts (``_fire_cohort``) whose rows became
        host-visible since the last call (``EmitRing.take_delivered``)."""
        return self.emit_ring.take_delivered()

    def _check_fire_cap(self, n: int, cap: int) -> None:
        """A packed buffer reporting more fired rows than its capacity
        means truncation — only reachable on the top-n path when ties at
        the n-th value exceed the 8× headroom. Fail loudly rather than
        emit a silently-incomplete result set."""
        if n > cap:
            raise RuntimeError(
                f"fired-row buffer overflow: {n} rows > capacity {cap} "
                "(top-n tie explosion); raise n or aggregate first")

    def _used_mask_device(self) -> jax.Array:
        """(rows,) bool on device, marking the rows of slots that have
        held a key; re-pushed only when the directory took a never-used
        slot (h2d is cheap and one-way; the d2h round trip is what the
        packed fire avoids)."""
        nk = self.directory.slots_ever_used()
        if getattr(self, "_used_pushed", -1) != nk:
            # every slot that EVER held a key: a released slot's rows
            # are identities (_release_dead_keys) and fire nothing, so
            # the mask need not follow the keys that come and go, only
            # the allocator's high-water marks
            if self.mesh_plan is None:
                # made on the device from the shards' free pointers: at
                # 16.8 M slots the host's mask took ~9 ms to make and
                # upload, on the way of every fire behind an allocation
                d = self.directory
                self._used_dev = _JIT_USED_MASK(
                    jnp.asarray(d.free_pointers().astype(np.int32)),
                    slots_per_shard=d.slots_per_shard)
            else:
                used = np.zeros(
                    self.layout.rows * self.mesh_plan.n_devices, bool)
                ever = self.directory.ever_used_mask()
                used[self._row_of_slots(np.nonzero(ever)[0])] = True
                self._used_dev = jax.device_put(
                    used, self.mesh_plan.row_sharding())
            self._used_pushed = nk
        return self._used_dev

    def _row_of_slots(self, slots: np.ndarray) -> np.ndarray:
        """Global slot id → row in the state array (sharded state carries
        one dump row per device block)."""
        if self.mesh_plan is None:
            return slots
        return self.mesh_plan.global_slot_to_row(slots)

    def _slot_of_rows(self, rows: np.ndarray) -> np.ndarray:
        if self.mesh_plan is None:
            return rows
        return rows - rows // self.layout.rows

    def _last_data_end_ms(self) -> int:
        return self.plan.last_data_end_ms(self._max_pane_seen)

    def final_watermark(self) -> int:
        """ref role: advancing to Watermark.MAX_WATERMARK on input end,
        kept finite here — see WindowPlan.final_watermark_for."""
        return self.plan.final_watermark_for(
            self.watermark, self._max_pane_seen)

    def _empty(self) -> "FiredWindows":
        """Cached empty fired-batch (a fresh one would dispatch tiny
        device ops on every no-op watermark advance)."""
        if not hasattr(self, "_empty_cache"):
            self._empty_cache = _empty_fired(self.agg)
        return FiredWindows(data=dict(self._empty_cache))

    # -- snapshot seam (checkpoint/ uses this) ---------------------------
    @property
    def records_spilled(self) -> int:
        return self._spill.records_spilled if self._spill is not None else 0

    def snapshot_state(self) -> Dict[str, Any]:
        # the snapshot holds no key a purge has left behind
        self.run_pending_release()
        # the snapshot must include stashed records
        self._flush_stash()
        self._resolve_reports()  # a checkpoint must not hide pending loss
        spill_snap = (self._spill.snapshot()
                      if self._spill is not None else None)
        # lsm changelog cut: sealed-run files ride the checkpoint as
        # hardlinks, not serialized state — lift their name→path map to
        # the top level where the coordinator pops it for storage's
        # op_aux plane (checkpoint/storage.py save_v2)
        aux_files = (spill_snap.pop("aux_files", None)
                     if isinstance(spill_snap, dict) else None)
        # on-device CLONE, not a fetch: the freeze stays in-loop and
        # cheap; the checkpoint executor's fetch (persist.fetch) does the
        # device→host transfer off the hot path (SURVEY §6.4 async
        # snapshot part). A clone is required — later steps DONATE
        # self.state's buffers, so holding the refs would read deleted
        # buffers. One program, jit_snapshot_clone_kernel; this leaf is
        # its DISPATCH, the device runs it behind the steps in flight.
        with self.phases.span("state.snapshot_clone"):
            panes = _JIT_SNAPSHOT_CLONE(self.state)
        # slots waiting on the reuse rule go into the snapshot as free
        # ones: a checkpoint flushes the emits first, and a restore
        # starts a new emit ring, so no row of the snapshot's timeline
        # can name them any more
        with self.phases.span("state.snapshot_directory"):
            directory = self._directory_snapshot()
        out = {
            "spill": spill_snap,
            "n_dev": self.mesh_plan.n_devices if self.mesh_plan else 1,
            "ring": self.plan.ring,
            "panes": panes,
            "directory": directory,
            "watermark": self.watermark,
            "cleared_below": self._cleared_below,
            "fired_below_end": self._fired_below_end,
            "min_pane_seen": self._min_pane_seen,
            "max_pane_seen": self._max_pane_seen,
            "refire": sorted(self._refire),
            "late_records": self.late_records,
            "records_dropped_full": self.records_dropped_full,
            "max_ts_seen": self._max_ts_seen,
            "refire_ends": self.refire_ends,
        }
        if aux_files:
            out["__aux_files__"] = aux_files
        return out

    def _directory_snapshot(self) -> Dict[str, np.ndarray]:
        out = self.directory.snapshot(
            None if self._host_pool is None else self._host_pool.run_tasks)
        if self._waiting:
            out["free_slots"] = np.concatenate(
                [out["free_slots"]] + [sl for _no, sl in self._waiting])
        return out

    def restore_state(self, snap: Dict[str, Any]) -> None:
        panes = snap["panes"]
        snap_ring = snap.get("ring", self.plan.ring)
        if snap_ring != self.plan.ring:
            # the snapshotted operator had auto-grown its pane ring —
            # adopt that geometry before loading the arrays
            self.plan = dataclasses.replace(self.plan, ring=snap_ring)
            self.layout = dataclasses.replace(self.layout, ring=snap_ring)
            if self.mesh_plan is None:
                self._build_local_kernels()
            else:
                self._build_sharded_kernels()
        snap_dev = snap.get("n_dev", 1)
        cur_dev = self.mesh_plan.n_devices if self.mesh_plan else 1
        if snap_dev != cur_dev:
            # RESHARD: the key-shard space is fixed (the maxParallelism
            # contract) but the device count changed — re-block the row
            # axis, dropping the old per-block dump rows and inserting
            # fresh ones (ref role: StateAssignmentOperation re-splitting
            # key-group ranges on rescale)
            panes = _reblock_panes(panes, snap_dev, cur_dev)
        state = jax.tree_util.tree_map(jnp.asarray, panes)
        if self.mesh_plan is not None:
            state = jax.device_put(state, self.mesh_plan.row_sharding())
        self.state = state
        old = self.directory
        self.directory = KeyDirectory.restore(
            old.num_shards, old.slots_per_shard,
            snap["directory"], (old.shard_lo, old.shard_hi))
        for k in ("slots_allocated", "slots_reused", "slots_released",
                  "assign_records", "assign_memo_looks", "assign_memo_hits"):
            setattr(self.directory, k, getattr(old, k))   # the job's
        self.watermark = snap["watermark"]
        self._cleared_below = snap["cleared_below"]
        self._fired_below_end = snap["fired_below_end"]
        self._min_pane_seen = snap["min_pane_seen"]
        self._max_pane_seen = snap["max_pane_seen"]
        if self._releases:
            bare = self.directory._newest is None
            self.directory.track_panes()
            if bare and self._max_pane_seen is not None:
                # a snapshot without newest panes (an older one, one of
                # a spill backend, one merged for a rescale): no key has
                # a pane past the newest seen
                self.directory.note_all(self._max_pane_seen)
        # pre-restore fires are a dead timeline, and the snapshot holds
        # what waited as free (see snapshot_state)
        self._release_pending, self._release_cohort = False, None
        self._forget_waiting()
        self._packs_open.clear()
        self.emit_ring.fires_decoded = self._fires_so_far()
        self._refire = set(snap["refire"])
        self.late_records = snap["late_records"]
        self.records_dropped_full = snap.get("records_dropped_full", 0)
        self._max_ts_seen = snap.get("max_ts_seen")
        self.refire_ends = snap.get("refire_ends", 0)
        # pre-restore device steps are from a dead timeline (their
        # in-flight tokens included — a stale token's ring head must
        # never be folded into the restored timeline's facts)
        self._inflight.clear()
        snap_spill = snap.get("spill")
        if self._spill is not None and snap_spill is not None:
            if isinstance(self._spill, HostSpillStore):
                if snap_spill.get("kind") == "lsm":
                    # lsm→spill flip: the delta restores (same pane
                    # form) but sealed runs hold state a RAM store has
                    # no files for — refuse rather than silently drop
                    if snap_spill.get("runs"):
                        raise ValueError(
                            "snapshot carries "
                            f"{len(snap_spill['runs'])} sealed lsm "
                            "run(s) the RAM spill store cannot adopt; "
                            "restore with state.backend='lsm'")
                    self._spill.restore(snap_spill["delta"])
                else:
                    self._spill.restore(snap_spill)
            else:
                # disk tier: accepts both the lsm form (aux maps run
                # name → checkpoint hardlink, injected by storage.load)
                # and a plain spill snapshot (spill→lsm backend flip)
                self._spill.restore(
                    snap_spill, aux_paths=snap.get("__aux_paths__"))
        elif self._spill is None and snap_spill and (
                snap_spill.get("panes") or snap_spill.get("runs")
                or (snap_spill.get("delta") or {}).get("panes")):
            # the snapshot carries live host-resident aggregates this
            # operator (state.backend='hbm') cannot hold — restoring
            # would silently lose them
            raise ValueError(
                "snapshot contains host-spill state but state.backend "
                "is 'hbm'; restore with state.backend='spill' or 'lsm'")
        self._used_pushed = -1  # directory changed: invalidate device used-mask
        # emit ring resets: everything it held was delivered before the
        # snapshot (checkpoint flushes emits first); replay re-fires
        self.emit_ring.reset()
        self._ring_drained = 0
        self._ring_anchor = None
        # piggybacked ring-head facts describe the pre-restore timeline
        self._ring_head_known = False
        self._ring_head_seq = self._token_seq
        self._rowfire_token_seq = self._token_seq + 1
        # a stash from the pre-restore attempt belongs to a replayed
        # stream position — never apply it to restored state
        self._stash_u32 = None


def _reblock_panes(panes: PaneState, old_dev: int, new_dev: int) -> PaneState:
    """Re-block state rows from old_dev device blocks to new_dev blocks.
    Each block is (slots_local + 1 dump) rows; logical slot order is
    preserved (global slot = shard * slots_per_shard, contiguous)."""

    def reblock(arr: np.ndarray, dump_fill) -> np.ndarray:
        arr = np.asarray(arr)
        rpl = arr.shape[0] // old_dev          # rows per old block
        blocks = [arr[d * rpl:(d + 1) * rpl - 1] for d in range(old_dev)]
        logical = np.concatenate(blocks)       # (total_slots, ...)
        if logical.shape[0] % new_dev != 0:
            raise ValueError(
                f"cannot reshard {logical.shape[0]} slots onto {new_dev} "
                "devices — num_shards * slots_per_shard must be divisible "
                "by the device count (the key-group contract)")
        slots_new = logical.shape[0] // new_dev
        out = []
        for d in range(new_dev):
            blk = logical[d * slots_new:(d + 1) * slots_new]
            dump = np.full((1,) + arr.shape[1:], dump_fill, dtype=arr.dtype)
            out.append(np.concatenate([blk, dump]))
        return np.concatenate(out)

    return PaneState(
        sums=None if panes.sums is None else reblock(panes.sums, 0.0),
        maxs=None if panes.maxs is None else reblock(panes.maxs, -np.inf),
        mins=None if panes.mins is None else reblock(panes.mins, np.inf),
        counts=reblock(panes.counts, 0),
    )


class FiredWindows(Mapping):
    """A fired-window batch with lazy host materialization.

    The device work (fire + select + finalize) was already dispatched
    when this object was created; only the device→host transfer is
    deferred to first access. The runtime driver drains these on a
    separate thread — the analogue of the reference handing serialized
    buffers to Netty's IO thread off the mailbox thread (ref:
    runtime/io/network/api/writer/RecordWriter.java → PipelinedSubpartition
    .notifyDataAvailable), so emission latency never blocks ingest.
    ``materialize_many`` fetches a whole backlog of fires in ONE
    device→host round trip (the transport serializes round trips, so
    one per fire is the emit-path latency floor — batch them)."""

    def __init__(self, data: Optional[Dict[str, np.ndarray]] = None,
                 fetch=None, op=None, packs=None, ring: bool = False,
                 ring_no: int = 0, cohort: Optional[Dict] = None,
                 pack_no: int = 0):
        # the fire's record (WindowOperator._fire_cohort), None for a
        # batch that fired no window end
        self.cohort = cohort
        # the advance that made this batch moved the purge horizon
        # (WindowOperator._defer_release)
        self.purged = False
        self._data = data
        self._fetch = fetch
        self._op = op
        self._packs = packs
        self._ring = ring
        self._ring_no = ring_no
        # a pack-mode fire's number (WindowOperator._pack_no): told to
        # the operator once the buffers are decoded, or dropped unread
        self._pack_no = pack_no
        # host-spill rows fired alongside this batch (disjoint keys);
        # merged in at materialization, reranked if a top-n is active
        self._extra: Optional[Dict[str, np.ndarray]] = None
        self._topn_spec: Optional[Tuple[str, int]] = None
        # a pack fire's landing wait (await_landing): when the drain
        # began to want its buffers, and when they had landed
        self._t_want = self._t_landed = None

    @property
    def rowless(self) -> bool:
        """Provably nothing for a sink: the ring marker of an advance
        that fired no window end, or host columns without a row. The
        driver's drain hurries for no such marker."""
        if (self.cohort is not None or self._packs is not None
                or self._fetch is not None or self._extra is not None):
            return False
        if self._data is not None:
            return not any(len(v) for v in self._data.values())
        return self._ring

    def materialize(self) -> Dict[str, np.ndarray]:
        if self._data is None:
            if self._fetch is not None:
                self._data = self._fetch()
                self._fetch = None
            elif self._ring:
                self._data = self._op.drain_ring()
                self._op = None
            else:
                self._fetch_packs(polled=False)
        if self._extra is not None:
            self._data = _merge_spill_rows(
                self._data, self._extra, self._topn_spec)
            self._extra = None
        return self._data

    def _fetch_packs(self, polled: bool) -> None:
        """Fetch (``drain.fetch``) and decode this fire's pack buffers.
        ``polled``, the drain's way: wait for the copies the fire
        started (where ``await_landing`` has not already, under no
        lock), then read them locally; else one blocking get."""
        bufs = [b for _, b in self._packs]
        with self._op.phases.span("drain.fetch") as fetch:
            if polled:
                ready_wait(bufs)
                t_ready = self._t_landed or time.perf_counter()
                bufs = [np.asarray(b) for b in bufs]
            else:
                bufs = jax.device_get(bufs)
                t_ready = time.perf_counter()
        if self.cohort is not None:
            self.cohort.update(t_fetch0=self._t_want or fetch.t0,
                               t_ready=t_ready, t_fetch1=fetch.t1)
        self._data = self._op._decode_packs(self._packs, bufs)
        self._op._note_pack_decoded(self._pack_no)
        self._packs = self._op = None

    def __del__(self) -> None:
        # buffers nobody will read name no key: the reuse rule need not
        # wait for them
        if getattr(self, "_packs", None) is not None \
                and self._op is not None:
            self._op._note_pack_decoded(self._pack_no)

    @staticmethod
    def await_landing(fireds: List["FiredWindows"],
                      until: threading.Event) -> None:
        """The drain's step ahead of a periodic ``materialize_many``,
        holding NO lock the loop can need: wait until the rows of the
        fires in ``fireds`` have landed on the host, or ``until`` (a
        barrier, a stop) is set. Per ring operator once, for the
        version that holds the rows of its newest row-carrying fire
        here (``EmitRing.await_landing``: the choice the poll then
        reads); per pack fire for its buffers. A marker of a fire
        without rows waits for nothing."""
        need: Dict[int, int] = {}
        ops = {}
        for f in fireds:
            if f._data is None and f._ring:
                ops[id(f._op)] = f._op
                if f.cohort is not None:
                    need[id(f._op)] = max(need.get(id(f._op), 0), f._ring_no)
        for i, op in ops.items():
            op.emit_ring.await_landing(
                need.get(i, 0), until, op.phases, op.prof)
        for f in fireds:
            if f._data is None and f._packs is not None:
                f._t_want = time.perf_counter()
                await_arrays([b for _, b in f._packs], until,
                             f._op.phases, f._op.prof)
                f._t_landed = time.perf_counter()

    @staticmethod
    def materialize_many(fireds: List["FiredWindows"],
                         barrier: bool = False) -> None:
        """Fetch every pending buffer across ``fireds`` in as few
        device→host round trips as possible, then decode each.

        Every fire dispatch already issued ``copy_to_host_async`` on its
        buffers (see _fire_ends), so by drain time the bytes are
        host-cached and each np.asarray is a local read instead of a
        blocking device round trip. A buffer whose copy has not landed
        yet simply blocks on its own in-flight copy — never a second
        transfer."""
        # ring-mode entries: ONE ring poll per operator serves every
        # pending marker of that operator (later markers read empty —
        # the first drain already took the appended rows)
        # A periodic drain reads the version await_landing chose and
        # waited for (min_no=0): its rows have landed, so under the
        # drain's locks it NEVER parks behind a just-dispatched fire's
        # compute. A barrier drain (checkpoint
        # flush, end of job) pins each op's newest marker version so
        # every enqueued row is guaranteed fetched.
        need: Dict[int, int] = {}
        for f in fireds:
            if f._data is None and f._ring:
                cur = need.get(id(f._op), 0)
                need[id(f._op)] = (max(cur, f._ring_no) if barrier else 0)
        ring_ops = {}
        for f in fireds:
            if f._data is None and f._ring:
                op = f._op
                if id(op) not in ring_ops:
                    ring_ops[id(op)] = op.drain_ring(min_no=need[id(op)])
                    f._data = ring_ops[id(op)]
                else:
                    f._data = op._empty().materialize()
                f._op = None
        for f in fireds:
            if f._data is None and f._packs is not None:
                f._fetch_packs(polled=True)

    def __getitem__(self, key: str) -> np.ndarray:
        return self.materialize()[key]

    def __iter__(self):
        return iter(self.materialize())

    def __len__(self) -> int:
        return len(self.materialize())


def _merge_spill_rows(
    dev: Dict[str, np.ndarray], extra: Dict[str, np.ndarray],
    topn: Optional[Tuple[str, int]],
) -> Dict[str, np.ndarray]:
    """Concatenate device-fired and host-spill-fired rows (pack-mode
    path — per-fire attribution is exact there, and pack mode never has
    a top-n, so this is a plain field-wise concat; the ``topn`` arg is
    accepted for symmetry and future-proofing)."""
    out = {k: np.concatenate([np.asarray(dev[k]), np.asarray(extra[k])])
           for k in dev}
    if topn is None or len(out["window_end"]) == 0:
        return out
    field, n = topn
    keep = _topn_keep(out["window_end"], np.asarray(out[field]), n)
    return {k: val[keep] for k, val in out.items()}


def _topn_keep(we: np.ndarray, v: np.ndarray, n: int,
               windows: Optional[np.ndarray] = None) -> np.ndarray:
    """Boolean keep-mask for per-window top-n with ties kept. When
    ``windows`` is given, only those windows are filtered; rows of other
    windows pass through."""
    keep = np.ones(len(we), bool)
    for w in (np.unique(we) if windows is None else windows):
        grp = np.flatnonzero(we == w)
        if len(grp) > n:
            gv = v[grp]
            thresh = np.partition(gv, len(gv) - n)[len(gv) - n]
            keep[grp[gv < thresh]] = False  # ties at thresh stay
    return keep


def _drain_merge_extras(
    dev: Dict[str, np.ndarray], extras: List[Dict[str, np.ndarray]],
    topn: Optional[Tuple[str, int]],
) -> Dict[str, np.ndarray]:
    """Merge host-spill extras into a ring-drain batch and re-rank the
    windows the extras touch.

    The device's ring rows are top-n of RESIDENT keys only; the global
    top-n is always a subset of device-winners ∪ host rows, so the
    union re-rank over a SINGLE fire is exact — and spill+top-n mode
    drains synchronously per fire (see advance_watermark), so a drain
    never mixes fires. Windows with no host rows pass through."""
    ex = {k: np.concatenate([np.asarray(e[k]) for e in extras])
          for k in extras[0]}
    comb = {k: np.concatenate([np.asarray(dev[k]), ex[k]]) for k in dev}
    if topn is None:
        return comb
    field, n = topn
    keep = _topn_keep(comb["window_end"], np.asarray(comb[field]), n,
                      windows=np.unique(ex["window_end"]))
    return {k: v[keep] for k, v in comb.items()}


def _empty_fired(agg: LaneAggregate) -> Dict[str, np.ndarray]:
    out = {
        "key": np.zeros(0, np.int64),
        "window_start": np.zeros(0, np.int64),
        "window_end": np.zeros(0, np.int64),
        "count": np.zeros(0, np.int32),
    }
    res = agg.finalize(
        jnp.zeros((0, agg.sum_width)), jnp.zeros((0, agg.max_width)),
        jnp.zeros((0, agg.min_width)), jnp.zeros((0,), jnp.int32))
    for k, v in res.items():
        out[k] = np.asarray(v)
    return out
