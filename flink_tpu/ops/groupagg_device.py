"""Unwindowed keyed aggregation with the accumulators on the device.

The lane of ``ops/global_agg.py`` for a job without retract rows or a
mesh whose aggregate is a ``LaneAggregate`` (``device_lane_fits``; the
driver chooses, no option does). Semantics are that operator's, row for
row: ``SELECT k, agg(..) FROM t GROUP BY k`` with no window; after every
microbatch ONE upsert row for each key the batch touched, holding the
key's aggregate over every record so far (the mini-batch emission of
``MiniBatchGroupAggFunction``). Nothing is late, nothing expires, no
slot is ever released.

State: ONE ``(words, slots)`` int32 device array, a key slot's
accumulators a column strip of it: the record count, then each lane of
the aggregate at its own dtype's words: float32 (bit for bit) for the
float aggregates, int32 / int64 (low, high) for the integer lanes of
``ops/aggregates.py`` (``LaneAggregate.lane_dtypes``), which are exact
at the source's widths (``init_groupagg_state`` says why one array).

Per batch ONE program (``groupagg_apply_kernel``): the batch is
combined per slot (``ops/window.py`` ``combine_cells``: a sort by slot
with the lifted lanes as payload, run heads, one segmented scan a lane,
the heads brought to the front), each distinct slot's fresh row (the
slot, its records, each lane's reduction of them) is written into the
batch's emit buffer, ``merge_chunk`` of them a trip, with no word of
the state read, and then the DISTINCT slots are merged into the donated
state one of two ways. A gather and a scatter are paid by the call and
by the index, hardly by the word (``init_groupagg_state``), a
``dynamic_update_slice`` by the byte; and a slot the batch's own
``assign`` handed out for the first time holds the identity, so its
merged strip IS its fresh row, and such slots are neighbours: with no
slot ever released a shard's first-time keys take the slots from its
free pointer up. So:

- **dense blocks.** The host lists those runs, cut into pieces
  (``fresh_pieces``: the free pointers before and after ``assign``);
  the kernel writes a piece only where the batch's cells bear it out
  (its slots are all named, so they are neighbours among the distinct
  slots too: a slot whose every record was refused for a lane overflow
  fails its piece): one block of the emit buffer into one block of the
  state, read-modify-write, a trip a piece;
- **gather trips.** Every other cell (a key that recurs, a piece that
  failed or was not listed): gather its strip, reduce it with the fresh
  row, write both back with sorted unique indices.

The dense write and the ONE small trip (``sparse_chunk``) for the cells
it leaves are taken where they pay: the batch has more cells than one
``merge_chunk`` trip and the cells in no piece fit the small trip (the
suite's batch: 68,400 of 68,500 keys are new, five trips of 16,384
become 128 blocks and one trip of 2,048). Any other batch, one whose
keys mostly recur among them, takes ``merge_chunk`` trips over all its
cells and no block. The header counts both (``groupagg.rows_dense``,
``groupagg.dense_pieces``, ``profile.opN.apply_trips``).

So the emission is part of the batch's program: it needs no watermark
and no pass over the slots. The buffer's first ``emit_cap`` rows are a small
array of their own whose copy to the host starts at the dispatch; a
batch that touched more keys leaves the rest in the full buffer, which
the drain reads in further passes of ``emit_cap`` rows, none lost and
none twice.

The rows of a batch leave as ``WindowOperator``'s fired rows do: a
``FiredWindows`` with a cohort (``take_fired``) through the driver's
drain, which waits for the landing under no lock
(``EmitRing.await_landing``), decodes slot -> key, puts the 64-bit
words together and finalizes on the host (``avg = sum // count``).

What the host does per record: ``KeyDirectory.assign`` and the pack;
per batch, the table of its fresh runs. A value a lane cannot hold (a
``narrow_fields`` value or an event-time offset beyond 32 bits) is
refused with its record and counted (``groupagg.lane_overflow``), never
wrapped.
"""
from __future__ import annotations

import collections
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from flink_tpu.hostsync import ready_wait
from flink_tpu.obs.tracing import PhaseClock
from flink_tpu.ops.aggregates import (
    EVENT_TIME_FIELD, LANE_FAMILIES, LaneAggregate, lane_identity)
from flink_tpu.ops.emit_ring import EmitRing
from flink_tpu.ops.window import (
    LANE_OPS, NO_CELL, FiredWindows, apply_chunk, combine_cells)
from flink_tpu.state.keyed import KeyDirectory, account_full_drop
from flink_tpu.time.watermarks import LONG_MIN

I32 = np.iinfo(np.int32)
# a batch's header: [distinct slots, records, gather trips, rows written
# dense, pieces written, 0..]
HEAD_WORDS = 8
# rows of a batch's emit buffer whose copy starts at the dispatch; None:
# ``apply_chunk`` of the batch, an eighth of it (tests patch a number)
EMIT_CAP: Optional[int] = None
MIN_BATCH = 1024    # a batch is padded to a power of two from here up


def device_lane_fits(*, agg: Any, retract: bool, mesh: bool,
                     slots: int) -> bool:
    """Whether an unwindowed aggregation keeps its accumulators on the
    device: no retract rows (the -U row needs the accumulators as last
    emitted), one device, a lane aggregate without lanes that start
    anew with every row (``emission_lanes``), and slots that int32 cell
    keys hold. Everything else keeps ``GlobalAggregateOperator``."""
    return (not retract and not mesh and isinstance(agg, LaneAggregate)
            and not any(agg.emission_lanes)
            and 0 < int(slots) < (1 << 30))


def lane_layout(agg: LaneAggregate) -> Tuple[Tuple[str, ...], ...]:
    """Per family (sums, maxs, mins) the dtype of each lane."""
    if agg.typed:
        return agg.lane_dtypes
    return tuple(("float32",) * w for w in (
        agg.sum_width, agg.max_width, agg.min_width))


def lane_words(agg: LaneAggregate) -> int:
    """int32 words of one emitted row: slot, count, then the lanes."""
    return 2 + sum(2 if dt == "int64" else 1
                   for fam in lane_layout(agg) for dt in fam)


def state_words(agg: LaneAggregate) -> int:
    """int32 words a slot's accumulators take: count, then the lanes."""
    return lane_words(agg) - 1


def init_groupagg_state(agg: LaneAggregate, slots: int) -> jax.Array:
    """The accumulators of ``slots`` untouched keys: ONE ``(words,
    slots)`` int32 array, a slot's accumulators a column strip of it:
    word 0 the record count (0 = the slot holds no key yet), then each
    lane at its identity, a float32 lane bit for bit, an int64 lane as
    (low, high). One array and not one a lane because the chip pays for
    a gather or a scatter by the index far more than by the word: nine
    ``(slots,)`` lanes cost 18.2 + 10.4 ms a gather and a sorted
    scatter of 131,072 slots, the strips 3.6 + 13.5, and at 16,384
    slots 3.3 + 5.3 against 1.0 + 2.2 (``tools/gather_micro.py``, at
    33.5 M slots). The chip lays the words out in rows of eight, so nine
    words take the room of sixteen."""
    ident = [0]
    for fam, dts in zip(LANE_FAMILIES, lane_layout(agg)):
        for dt in dts:
            ident.extend(host_words(np.asarray(
                [lane_identity(fam, dt)], dt)).ravel().tolist())
    return _FILL(jnp.asarray(ident, jnp.int32), slots=slots)


# one buffer of the state's size, not a zero one and the sum beside it
_FILL = jax.jit(lambda ident, slots: jnp.broadcast_to(
    ident[:, None], (ident.shape[0], slots)), static_argnames=("slots",))


def merge_chunk(batch: int) -> int:
    """Distinct slots ONE gather trip of a batch takes where the batch
    has no dense write to make (``groupagg_apply_kernel``), from the
    batch's shape alone. A strip gather and scatter cost the chip 3.3 ms
    at 16,384 slots and 17 at 131,072, whether a slot is a batch's or
    the chunk's padding (``init_groupagg_state``), so the chunk is
    small: a sixty-fourth of the batch (a 2^20 batch whose ~68,500 keys
    all recur: five trips of 16,384, a sixth of them padding), the whole
    of a small batch."""
    return max(batch // 64, min(batch, 1024))


def sparse_chunk(batch: int) -> int:
    """Distinct slots the ONE gather trip takes that follows a batch's
    dense write: the cells in no verified piece, by their positions
    among the batch's cells. Such a trip gathers and scatters the emit
    buffer's rows as well as the state's strips (its cells are not
    neighbours), all four paid by the index, so it is an eighth of
    ``merge_chunk``: 2,048 of a 2^20 batch, whose ~80 recurring keys
    and a failed piece or two fit many times over."""
    return max(merge_chunk(batch) // 8, min(batch, 1024))


def piece_width(batch: int, pieces: int, slots: int) -> int:
    """Slots of ONE piece of a batch's fresh runs, the block a dense
    write moves, from the upload's size and the table's length (twice
    the directory's shards) alone: a power of two near an eighth of the
    records a shard takes of a full batch (2^20 over 128 shards: 1,024,
    which holds the ~534 first-time keys a shard gets of the suite's
    batch in one piece), within ``merge_chunk`` and the state."""
    per_shard = max(1, 2 * batch // max(pieces, 1))
    return min(merge_chunk(batch), slots,
               max(128, 1 << (max(per_shard // 8, 1).bit_length() - 1)))


def fresh_pieces(before: np.ndarray, after: np.ndarray,
                 slots_per_shard: int, batch: int) -> np.ndarray:
    """The slots the ``assign`` of a batch (``batch`` records as
    uploaded) handed out for the first time, as the ``(2, length)``
    int32 table ``groupagg_apply_kernel`` takes: (first slot, slots) of
    each piece, ascending, then zeros; two entries a shard. Shard h's
    free pointer went ``before[h] -> after[h]``: with no slot ever
    released those are consecutive slots nothing was folded into. A run
    longer than ``piece_width`` is cut; pieces past the table's end are
    not listed (their cells take the gather trip)."""
    runs = after - before
    length = 2 * len(runs)
    width = piece_width(batch, length, len(runs) * slots_per_shard)
    per = -(-runs // width)
    shard = np.repeat(np.arange(len(runs)), per)[:length]
    rank = (np.arange(int(per.sum()))
            - np.repeat(np.cumsum(per) - per, per))[:length]
    table = np.zeros((2, length), np.int32)
    table[0, :len(shard)] = (shard * slots_per_shard + before[shard]
                             + rank * width)
    table[1, :len(shard)] = np.minimum(width, runs[shard] - rank * width)
    return table


def _words(v: jax.Array) -> List[jax.Array]:
    """A lane's values as int32 words: float32 bit for bit, int64 as
    (low, high)."""
    if v.dtype == jnp.int64:
        return [v.astype(jnp.int32), (v >> 32).astype(jnp.int32)]
    if v.dtype == jnp.float32:
        return [lax.bitcast_convert_type(v, jnp.int32)]
    return [v.astype(jnp.int32)]


def _lane(words: jax.Array, at: int, dtype: str) -> jax.Array:
    """The lane whose words start at row ``at`` of ``words``."""
    if dtype == "int64":
        return ((words[at + 1].astype(jnp.int64) << 32)
                | (words[at].astype(jnp.int64) & 0xFFFFFFFF))
    if dtype == "float32":
        return lax.bitcast_convert_type(words[at], jnp.float32)
    return words[at]


def _merge_strips(state: jax.Array, fresh: jax.Array, mine: jax.Array,
                  layout, slots: int) -> Tuple[jax.Array, jax.Array]:
    """Fold the rows ``fresh`` ``(1 + words, n)`` (slot, then what the
    batch alone gives the slot; slots ascending where ``mine``) into the
    strips the state holds for their slots: gather, reduce, write back
    with sorted unique indices. Returns the state and the merged rows."""
    k = fresh[0]
    held = state[:, jnp.where(mine, k, 0)]
    words, at = [k, held[0] + fresh[1]], 1
    for (_, op, _), dts in zip(LANE_OPS, layout):
        for dt in dts:
            words.extend(_words(op(_lane(held, at, dt),
                                   _lane(fresh, at + 1, dt))))
            at += 2 if dt == "int64" else 1
    merged = jnp.stack(words)
    # padding takes distinct slots past the last: uniqueness is true
    pad = slots + jnp.arange(k.shape[0], dtype=jnp.int32)
    state = state.at[:, jnp.where(mine, k, pad)].set(
        merged[1:], indices_are_sorted=True, unique_indices=True,
        mode="drop")
    return state, merged


def groupagg_apply_kernel(
    state: jax.Array,       # (words, slots) i32: init_groupagg_state
    slot: jax.Array,        # (B,) i32; < 0 = the record takes part in nothing
    data: Dict[str, jax.Array],
    pieces: jax.Array,      # (2, R) i32: fresh_pieces
    *,
    agg: LaneAggregate,
    slots: int,
    cap: int,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Fold one batch into the accumulators and gather the merged rows
    of the slots it touched. Returns the state, the int32 header
    [distinct slots, valid records, gather trips, rows written dense,
    pieces written, 0...], the first ``cap`` rows ``(1 + words, cap)``
    and all of them ``(1 + words, B + chunk)``: row j (j < distinct
    slots, slots ascending) is its slot, then the slot's accumulators
    as the state holds them; the rows past them hold nothing.

    A cell is merged one of two ways (module docstring). ``pieces`` is
    the host's word on which slots are first-time ones; the kernel
    writes a piece dense only where the batch's own cells bear it out:
    the piece's slots are ``n`` neighbours among the distinct slots, in
    the state's range, above every piece listed before it."""
    batch = slot.shape[0]
    valid = slot >= 0
    rows = jnp.where(valid, slot, 0)
    layout = lane_layout(agg)
    n_words = lane_words(agg)
    lifted = agg.lift_masked(data, valid)
    lanes = {fam: lane for fam, lane, dts in zip(
        LANE_FAMILIES, lifted, layout) if dts}
    cells, starts, scans, n_cells, n_records = combine_cells(
        slots, rows, jnp.zeros_like(rows), valid, lanes)

    chunk, few = merge_chunk(batch), sparse_chunk(batch)
    width = piece_width(batch, pieces.shape[1], slots)
    cells = jnp.concatenate([cells, jnp.full(chunk, NO_CELL, jnp.int32)])
    starts = jnp.concatenate([starts, jnp.zeros(chunk, jnp.int32)])
    out = jnp.zeros((n_words, batch + chunk), jnp.int32)

    # what the batch alone gives each distinct slot, the row of a slot
    # nothing was folded into before: no word of the state is read
    def fresh_trip(carry):
        out, done = carry
        k = lax.dynamic_slice(cells, (done,), (chunk,))
        s = lax.dynamic_slice(starts, (done,), (chunk + 1,))
        last = jnp.maximum(s[1:] - 1, 0)
        words = [k, s[1:] - s[:-1]]
        for (fam, op, _), dts in zip(LANE_OPS, layout):
            for dt, scan in zip(dts, scans.get(fam, ())):
                words.extend(_words(op(
                    jnp.asarray(lane_identity(fam, dt), dt), scan[last])))
        out = lax.dynamic_update_slice(
            out, jnp.stack(words), (jnp.int32(0), done))
        return out, done + chunk

    out, _ = lax.while_loop(
        lambda c: c[1] < n_cells, fresh_trip, (out, jnp.int32(0)))

    # the pieces the cells bear out: distinct ascending slots whose
    # first is ``first`` and whose n-th is ``first + n - 1`` are those n
    first, length = pieces[0], pieces[1]
    at = jnp.searchsorted(cells, first).astype(jnp.int32)
    end = first + length
    above = jnp.concatenate([jnp.zeros(1, jnp.int32), lax.cummax(end)[:-1]])
    ok = ((length > 0) & (length <= width) & (first >= above)
          & (first < slots) & (end <= slots)
          & (cells[at] == first) & (cells[at + length - 1] == end - 1))
    took = jnp.where(ok, length, 0)
    rest = n_cells - jnp.sum(took, dtype=jnp.int32)
    # a dense write and its one gather trip pay where they stand in for
    # several gather trips and the cells they leave fit that one trip
    dense = (n_cells > chunk) & (rest <= few)
    took = jnp.where(dense, took, 0)
    lane_p = jnp.arange(width, dtype=jnp.int32)

    def piece_trip(r, state):
        # read-modify-write of the block that holds the piece: a short
        # piece leaves its neighbours, and the state's last columns
        # push the block's start back and the piece along it
        col = jnp.minimum(first[r], slots - width)
        shift = first[r] - col
        new = jnp.roll(lax.dynamic_slice(
            out, (jnp.int32(0), at[r]), (n_words, width))[1:], shift, axis=1)
        old = lax.dynamic_slice(
            state, (jnp.int32(0), col), (n_words - 1, width))
        put = (lane_p >= shift) & (lane_p < shift + took[r])
        return lax.dynamic_update_slice(
            state, jnp.where(put, new, old), (jnp.int32(0), col))

    listed = jnp.max(jnp.where(
        took > 0, jnp.arange(1, took.shape[0] + 1, dtype=jnp.int32), 0))
    state = lax.fori_loop(0, listed, piece_trip, state)

    def few_trip(state, out):
        # the j-th cell in no piece lies behind the pieces that have at
        # most j such cells before them
        j = jnp.arange(few, dtype=jnp.int32)
        before = at - (jnp.cumsum(took, dtype=jnp.int32) - took)
        p = j + jnp.sum(jnp.where(
            before[None, :] <= j[:, None], took[None, :], 0), axis=1,
            dtype=jnp.int32)
        mine = j < rest
        state, merged = _merge_strips(
            state, out[:, jnp.where(mine, p, 0)], mine, layout, slots)
        out = out.at[:, jnp.where(mine, p, batch + chunk + j)].set(
            merged, indices_are_sorted=True, unique_indices=True,
            mode="drop")
        return state, out

    state, out = lax.cond(dense & (rest > 0), few_trip,
                          lambda state, out: (state, out), state, out)

    def full_trip(carry):
        state, out, done = carry
        mine = done + jnp.arange(chunk, dtype=jnp.int32) < n_cells
        state, merged = _merge_strips(
            state, lax.dynamic_slice(out, (jnp.int32(0), done),
                                     (n_words, chunk)), mine, layout, slots)
        out = lax.dynamic_update_slice(out, merged, (jnp.int32(0), done))
        return state, out, done + chunk

    state, out, done = lax.while_loop(
        lambda c: ~dense & (c[2] < n_cells), full_trip,
        (state, out, jnp.int32(0)))
    trips = jnp.where(dense, (rest > 0).astype(jnp.int32), done // chunk)
    head = jnp.zeros(HEAD_WORDS, jnp.int32).at[:5].set(jnp.stack([
        n_cells, n_records, trips, jnp.sum(took, dtype=jnp.int32),
        jnp.sum(took > 0, dtype=jnp.int32)]))
    return state, head, out[:, :cap], out


_JIT_GROUPAGG_APPLY = jax.jit(
    groupagg_apply_kernel, static_argnames=("agg", "slots", "cap"),
    donate_argnums=(0,))


def decode_words(agg: LaneAggregate, words: np.ndarray
                 ) -> Tuple[np.ndarray, List[Tuple[np.ndarray, ...]]]:
    """``(counts, [sums, maxs, mins])`` of accumulators ``(words, n)``
    as the state and an emitted row hold them: each family a tuple of
    columns of its lanes' dtypes (the int64 words put together)."""
    at = 1
    fams = []
    for dts in lane_layout(agg):
        cols = []
        for dt in dts:
            if dt == "int64":
                cols.append((words[at + 1].astype(np.int64) << 32)
                            | (words[at].astype(np.int64) & 0xFFFFFFFF))
                at += 2
            else:
                col = np.ascontiguousarray(words[at])
                cols.append(col.view(np.float32) if dt == "float32" else col)
                at += 1
        fams.append(tuple(cols))
    return words[0].astype(np.int64), fams


def host_words(col: np.ndarray) -> np.ndarray:
    """A lane's host column as its int32 words ``(1 or 2, n)``."""
    col = np.ascontiguousarray(col)
    if col.dtype == np.int64:
        return np.stack([col.astype(np.int32), (col >> 32).astype(np.int32)])
    return col.view(np.int32)[None]


def time_lanes_to_ts(agg: LaneAggregate, counts: np.ndarray, fams,
                     base: int):
    """The families with every event-time lane a timestamp again
    (int64; a slot without records keeps the lane's identity)."""
    fams = [list(cols) for cols in fams]
    for cols, lanes in zip(fams, agg.time_lanes):
        for j in lanes:
            cols[j] = np.where(counts > 0, cols[j].astype(np.int64) + base,
                               cols[j].astype(np.int64))
    return [tuple(cols) for cols in fams]


def finalize_rows(agg: LaneAggregate, counts: np.ndarray, fams,
                  base: int) -> Dict[str, np.ndarray]:
    """The aggregate's result columns of accumulators on the host, the
    same from either lane of the unwindowed aggregation: integer lanes
    as column tuples (the event-time lanes as timestamps), float lanes
    as (n, width) float32."""
    if agg.typed:
        # every integer result leaves at 64 bits, whatever its lane held
        res = agg.finalize(*(
            tuple(c.astype(np.int64) for c in cols)
            for cols in time_lanes_to_ts(agg, counts, fams, base)), counts)
    else:
        n = len(counts)
        res = agg.finalize(*(
            np.stack(cols, axis=1) if cols else np.zeros((n, 0), np.float32)
            for cols in fams), counts)
    return {k: np.asarray(v) for k, v in res.items()}


def host_records(agg: LaneAggregate, ts: np.ndarray,
                 data: Dict[str, np.ndarray], base: int
                 ) -> Tuple[Dict[str, np.ndarray], Optional[np.ndarray]]:
    """The columns the lanes read, as they are uploaded, and the mask
    of records a 32-bit lane cannot hold (None: none). The fields the
    aggregate names alone; ``narrow_fields`` as int32; the event time,
    where a lane reads it, as int32 offsets from ``base``."""
    from flink_tpu.records import device_cast

    names = agg.fields if agg.fields is not None else tuple(data)
    cols: Dict[str, np.ndarray] = {}
    over = None

    def narrow(v: np.ndarray, shift: int = 0) -> np.ndarray:
        """``v - shift`` at 32 bits; what does not fit is marked."""
        nonlocal over
        lo, hi = I32.min + shift, I32.max + shift
        if len(v) and (int(v.min()) < lo or int(v.max()) > hi):
            bad = (v < lo) | (v > hi)
            over = bad if over is None else over | bad
        if not shift:
            return v.astype(np.int32)
        return np.subtract(v, shift, dtype=np.int64).astype(np.int32)

    for k in names:
        if k == EVENT_TIME_FIELD:
            cols[k] = narrow(ts, base)
            continue
        v = device_cast(np.asarray(data[k]))
        if (k in agg.narrow_fields and v.dtype.kind in "iu"
                and v.dtype.itemsize > 4):
            v = narrow(v)
        cols[k] = v
    return cols, over


def _batch_size(n: int) -> int:
    """The upload's length for ``n`` records: a power of two, so that a
    stream of ragged batches meets a handful of program shapes."""
    return max(MIN_BATCH, 1 << (int(n) - 1).bit_length())


class DeviceGroupAggOperator:
    """Unwindowed keyed aggregation, the accumulators on the device
    (module docstring). The surface the driver and the checkpointing
    use is ``GlobalAggregateOperator``'s; towards the drain it is
    ``WindowOperator``'s (``emit_ring``, ``drain_ring``,
    ``take_delivered_fires``)."""

    retract = False

    def __init__(self, agg: LaneAggregate, *, num_shards: int = 128,
                 slots_per_shard: int = 1024,
                 max_inflight_steps: int = 3) -> None:
        self.agg = agg
        self.directory = KeyDirectory(num_shards, slots_per_shard)
        self.slots = self.directory.local_slots
        if not device_lane_fits(agg=agg, retract=False, mesh=False,
                                slots=self.slots):
            raise ValueError(
                "this aggregation does not fit the device lane "
                "(device_lane_fits); it runs on ops/global_agg.py")
        self.state = init_groupagg_state(agg, self.slots)
        self.watermark = LONG_MIN
        self.late_records = 0          # unwindowed: nothing is late
        self.records_dropped_full = 0
        self.lane_overflow = 0
        self.allow_drops = False
        self.state_version = 0
        self.phases = PhaseClock()
        self.prof: Dict[str, float] = collections.defaultdict(float)
        self.max_inflight_steps = int(max_inflight_steps)
        self.external_throttle = False
        self._inflight: collections.deque = collections.deque()
        # a version a batch, each with rows of its own
        self.emit_ring = EmitRing(keep=None)
        # version -> (the batch's full emit buffer, on the device until
        # the drain has read the version; the watermark it was folded
        # in under)
        self._tails: Dict[int, Tuple[jax.Array, int]] = {}
        # event-time lanes hold ``ts - _base`` (the first batch's earliest)
        self._base: Optional[int] = None
        self._pending: Optional[FiredWindows] = None
        self.counters = {"rows_emitted": 0, "emit_passes": 0, "batches": 0,
                         "rows_dense": 0, "dense_pieces": 0}

    # -- ingest ------------------------------------------------------------
    def process_batch(self, keys, ts, data: Dict[str, np.ndarray],
                      valid=None) -> None:
        ph, detail = self.phases.phase, self.phases.detail
        with self.phases.span("window.key_scan"):
            with detail("prepare"):
                self.state_version += 1
                keys = np.asarray(keys, np.int64)
                ts = np.asarray(ts, np.int64)
                if valid is not None and not np.all(valid):
                    valid = np.asarray(valid, bool)
                    keys, ts = keys[valid], ts[valid]
                    data = {k: np.asarray(v)[valid] for k, v in data.items()}
                n = len(keys)
                if not n:
                    return
                if self._base is None and any(self.agg.time_lanes):
                    self._base = int(ts.min())
            with detail("assign"):
                fresh_from = self.directory.free_pointers().copy()
                slots = self.directory.assign(keys)
                self.prof["assign_records"] = self.directory.assign_records
                self.prof["assign_memo_hits"] = \
                    self.directory.assign_memo_hits
            with detail("slot_mask"):
                bad = slots < 0
                n_bad = int(bad.sum())
                if n_bad:
                    account_full_drop(self, n_bad)
            ph("window.pack")
            cols, over = host_records(self.agg, ts, data, self._base or 0)
            slot32 = slots.astype(np.int32)
            if over is not None:
                # a record on a full directory was counted there
                self.lane_overflow += int((over & ~bad).sum())
                slot32[over] = -1
            if n_bad:
                slot32[bad] = -1
            size = _batch_size(n)
            if size != n:
                slot32 = np.concatenate(
                    [slot32, np.full(size - n, -1, np.int32)])
                cols = {k: np.concatenate(
                    [v, np.zeros((size - n,) + v.shape[1:], v.dtype)])
                    for k, v in cols.items()}
            pieces = fresh_pieces(
                fresh_from, self.directory.free_pointers(),
                self.directory.slots_per_shard, size)
            ph("window.h2d")
            dslot = jnp.asarray(slot32)
            ddata = {k: jnp.asarray(v) for k, v in cols.items()}
            dpieces = jnp.asarray(pieces)
            ph("window.step_dispatch")
            cap = min(EMIT_CAP or apply_chunk(size), size)
            self.state, head, rows, full = _JIT_GROUPAGG_APPLY(
                self.state, dslot, ddata, dpieces, agg=self.agg,
                slots=self.slots, cap=cap)
            ring = self.emit_ring
            cohort = {"window_ends": [int(ts.max()) + 1],
                      "t_fire": time.perf_counter()}
            with ring.lock:
                ring.version_no += 1
                ring.announce((head, rows))
                self._tails[ring.version_no] = (full, self.watermark)
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
        """The marker of the batch just folded in: its upsert rows are
        the drain's to fetch."""
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

    # -- emitted rows: the drain's side --------------------------------------
    def _columns(self, body: np.ndarray, wms: np.ndarray
                 ) -> Dict[str, np.ndarray]:
        """Upsert rows as ``GlobalAggregateOperator.take_fired`` makes
        them: key, count, the finalized fields, and the emission-time
        watermark as the rows' timestamp."""
        counts, fams = decode_words(self.agg, body[1:])
        out: Dict[str, np.ndarray] = {
            "key": self.directory.key_of_slots(body[0].astype(np.int64)),
            "count": counts}
        out.update(finalize_rows(self.agg, counts, fams, self._base or 0))
        out["__ts__"] = wms
        return out

    def _empty(self) -> FiredWindows:
        if not hasattr(self, "_empty_cache"):
            self._empty_cache = self._columns(
                np.zeros((lane_words(self.agg), 0), np.int32),
                np.zeros(0, np.int64))
        return FiredWindows(data=dict(self._empty_cache))

    def drain_ring(self, min_no: Optional[int] = None
                   ) -> Dict[str, np.ndarray]:
        """Fetch the rows of every batch announced since the last drain
        (a periodic poll, ``min_no`` 0: those that have landed, and the
        oldest if none has) and decode them. A batch that touched more
        slots than its first buffer holds is read on in passes of as
        many rows from its full buffer, which stayed on the device."""
        ring = self.emit_ring
        with ring.lock:
            need = ring.version_no if min_no is None else min_no
            wanted = ring.take_wanted(min_no == 0)
            bufs, no_read = ring.fetch_unread(opportunistic=(min_no == 0))
            tails = [self._tails.pop(no)
                     for no in range((no_read or 0) - len(bufs) + 1,
                                     (no_read or 0) + 1)]
        if no_read is None:
            return self._empty().materialize()
        with self.phases.span("drain.fetch", ring=need) as fetch:
            ready_wait(bufs)
            t_ready = wanted.t_landed if wanted else time.perf_counter()
            parts, wms = [], []
            for (head, rows), (tail, wm) in zip(bufs, tails):
                n, records, trips, dense, pieces = (
                    int(x) for x in np.asarray(head)[:5])
                self.prof["apply_cells"] += n
                self.prof["apply_records"] += records
                self.prof["apply_trips"] += trips
                self.counters["rows_dense"] += dense
                self.counters["dense_pieces"] += pieces
                cap = rows.shape[1]
                parts.append(np.asarray(rows)[:, :min(n, cap)])
                parts.extend(np.asarray(tail[:, lo:min(lo + cap, n)])
                             for lo in range(cap, n, cap))
                self.counters["emit_passes"] += max(1, -(-n // cap))
                wms.append(np.full(n, wm if wm != LONG_MIN else 0, np.int64))
        with ring.lock:
            ring.deliver_stamps(no_read, wanted.t_want if wanted
                                else fetch.t0, t_ready, fetch.t1)
        self.prof["drain_fetch"] += fetch.seconds
        self.prof["drain_fetches"] += 1
        body = parts[0] if len(parts) == 1 else np.concatenate(parts, axis=1)
        self.counters["rows_emitted"] += body.shape[1]
        ring.note_decoded(no_read)
        return self._columns(body, np.concatenate(wms))

    def take_delivered_fires(self) -> List[Dict[str, Any]]:
        return self.emit_ring.take_delivered()

    # -- what the job reports ------------------------------------------------
    def hbm_bytes(self) -> int:
        """The accumulators' words (the chip lays them out in rows of
        eight, ``init_groupagg_state``; a batch's emit buffer comes and
        goes)."""
        return self.slots * 4 * state_words(self.agg)

    def state_counters(self) -> Dict[str, Any]:
        d = self.directory
        grows, grow_s, buckets = d.table_growth()
        out = {"state.slots_allocated": d.slots_allocated,
               "state.slots_reused": d.slots_reused,
               "state.slots_released": d.slots_released,
               "state.live_keys": d.num_keys(),
               "state.live_keys_peak": d.keys_peak,
               "state.table_grows": grows,
               "state.table_grow_s": grow_s,
               "state.table_buckets": buckets,
               "groupagg.keys_new": d.slots_allocated,
               "groupagg.live_keys": d.num_keys(),
               "groupagg.slots": self.slots,
               "groupagg.lane_overflow": self.lane_overflow,
               "groupagg.on_host": 0}
        out.update({f"groupagg.{k}": v for k, v in self.counters.items()})
        return out

    # -- snapshot: GlobalAggregateOperator's format ---------------------------
    def snapshot_state(self) -> Dict[str, Any]:
        self.quiesce()
        counts, fams = decode_words(self.agg, np.asarray(self.state))
        return {
            "kind": "global_agg",
            "directory": self.directory.snapshot(),
            "counts": counts,
            **snapshot_lanes(self.agg, counts, fams, self._base or 0),
            "time_base": self._base,
            "watermark": self.watermark,
            "records_dropped_full": self.records_dropped_full,
            "lane_overflow": self.lane_overflow,
        }

    def restore_state(self, snap: Dict[str, Any]) -> None:
        self._inflight.clear()
        self._tails.clear()
        self._pending = None
        self.emit_ring.reset()
        self.emit_ring.fires_decoded = self.emit_ring.version_no
        self.directory = KeyDirectory.restore(
            self.directory.num_shards, self.directory.slots_per_shard,
            snap["directory"],
            (self.directory.shard_lo, self.directory.shard_hi))
        self._base, fams = restored_lanes(self.agg, snap)
        self.state = jnp.asarray(np.concatenate(
            [np.asarray(snap["counts"]).astype(np.int32)[None]]
            + [host_words(c.astype(dt)) for cols, dts in zip(
                fams, lane_layout(self.agg)) for c, dt in zip(cols, dts)]))
        self.watermark = snap["watermark"]
        self.records_dropped_full = snap.get("records_dropped_full", 0)
        self.lane_overflow = snap.get("lane_overflow", 0)


# -- the snapshot's lanes: one format from either lane -----------------------

def host_lane_dtypes(agg: LaneAggregate) -> Dict[str, Any]:
    """The dtype of each family's ``(slots, width)`` array in a
    ``global_agg`` snapshot and in the host operator: int64 for integer
    lanes; float64 sums and float32 maxs / mins for the float ones."""
    if agg.typed:
        return {fam: np.int64 for fam in LANE_FAMILIES}
    return {"sums": np.float64, "maxs": np.float32, "mins": np.float32}


def snapshot_lanes(agg: LaneAggregate, counts: np.ndarray, fams,
                   base: int) -> Dict[str, np.ndarray]:
    """Lane columns as the snapshot's ``(slots, width)`` arrays, the
    event-time lanes as timestamps (no base to agree on at a restore
    or a rescale)."""
    dts = host_lane_dtypes(agg)
    fams = time_lanes_to_ts(agg, counts, [
        tuple(np.asarray(c) for c in cols) for cols in fams], base)
    return {fam: (np.stack(cols, axis=1).astype(dts[fam]) if cols
                  else np.zeros((len(counts), 0), dts[fam]))
            for fam, cols in zip(LANE_FAMILIES, fams)}


def restored_lanes(agg: LaneAggregate, snap: Dict[str, Any]
                   ) -> Tuple[Optional[int], List[Tuple[np.ndarray, ...]]]:
    """``(time base, [sums, maxs, mins])`` of a snapshot: each family
    as a tuple of columns, the event-time lanes offsets from the base
    again (the snapshot's own, else the earliest timestamp it holds)."""
    counts = np.asarray(snap["counts"])
    fams = [[np.asarray(snap[fam])[:, j] for j in range(len(dts))]
            for fam, dts in zip(LANE_FAMILIES, lane_layout(agg))]
    base = snap.get("time_base")
    held = counts > 0
    for i, lanes in enumerate(agg.time_lanes):
        for j in lanes:
            col = fams[i][j].astype(np.int64)
            if base is None and held.any():
                base = int(col[held].min())
            fams[i][j] = np.where(held, col - (base or 0), col)
    return base, [tuple(cols) for cols in fams]
