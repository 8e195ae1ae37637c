"""Aggregate lowering: user aggregation → dense accumulator lanes.

The reference evaluates ``AggregateFunction.add`` once per element against
a per-(key, window) heap/RocksDB accumulator object (ref: flink-core/.../
api/common/functions/AggregateFunction.java, applied in streaming/runtime/
operators/windowing/WindowOperator.processElement via AggregatingState).

TPU-first redesign: accumulators become fixed-width **lanes** in a dense
``(slots, panes, width)`` tensor, and a whole microbatch is folded in with
three scatter ops (add / max / min) — one per combine class. Anything
expressible as per-lane sum/max/min composes freely: count, sum, avg
(sum+count), max, min, argmax-by-packing, etc. This covers every
BASELINE.json config. ``lower_aggregate`` adapts the reference-style
AggregateFunction class to this form when its merge is recognizably
per-leaf sum/max/min.

Invariants:
- identity elements: sum→0, max→-inf, min→+inf (padding rows lift to
  identities, so invalid records are no-ops).
- ``finalize`` maps lane vectors back to user-visible results and also
  receives the built-in count lane (number of elements in the cell).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Arrays = Dict[str, jax.Array]

F32_NEG_INF = float("-inf")
F32_POS_INF = float("inf")

LANE_FAMILIES = ("sums", "maxs", "mins")
# the record field under which an operator hands integer lanes the
# event time, as int32 offsets (LaneAggregate.time_lanes)
EVENT_TIME_FIELD = "__event_time__"


def lane_identity(family: str, dtype: str):
    """The identity of a lane family's reduction at ``dtype``: 0 for a
    sum; for max / min the float infinities or the integer type's
    extremes (which a real value may equal: the reduction is still
    exact)."""
    if family == "sums":
        return 0
    if np.issubdtype(np.dtype(dtype), np.integer):
        info = np.iinfo(dtype)
        return info.min if family == "maxs" else info.max
    return F32_NEG_INF if family == "maxs" else F32_POS_INF


@dataclasses.dataclass(frozen=True)
class LaneAggregate:
    """A windowed aggregation as sum/max/min lanes.

    lift(data)  -> (sum (B,S), max (B,M), min (B,m)) per-record lane values
    finalize(sums, maxs, mins, counts) -> result dict; inputs have shape
    (..., width) / counts (...,) and must broadcast over leading dims.
    """

    sum_width: int
    max_width: int
    min_width: int
    lift: Callable[[Arrays], Tuple[jax.Array, jax.Array, jax.Array]]
    finalize: Callable[[jax.Array, jax.Array, jax.Array, jax.Array], Arrays]
    name: str = "agg"
    # record fields ``lift`` reads. The operator uploads ONLY these to
    # the device — unused lanes never ride the host→device link (count()
    # uploads nothing but the packed slot ids). None = unknown: keep all.
    fields: Optional[Tuple[str, ...]] = None
    # When every sum lane is the IDENTITY lift of one record field
    # (lane i == f32(data[sum_fields[i]])), the host can pre-combine a
    # microbatch per (key, pane) pair with np.bincount before upload —
    # the mini-batch local-aggregation trick (ref: table/runtime
    # mini-batch agg, SURVEY §3.8) that shrinks both the host→device
    # bytes and the device scatter from records to distinct pairs.
    # None = lift is opaque; the operator must ship raw records.
    sum_fields: Optional[Tuple[str, ...]] = None
    # INTEGER lanes (exact at the source's widths): per family (sums,
    # maxs, mins) the dtype name of each lane, "int32" or "int64". None
    # = every lane float32, the (B, width) layout above. Where set,
    # ``lift`` returns per family a TUPLE of 1-D columns of those dtypes
    # and ``finalize`` receives such tuples (host arrays: numpy ops
    # only). Run by the unwindowed aggregation (ops/groupagg_device.py,
    # ops/global_agg.py); the windowed operators refuse them.
    lane_dtypes: Optional[Tuple[Tuple[str, ...], Tuple[str, ...],
                                Tuple[str, ...]]] = None
    # record fields every lane reads at 32 bits: uploaded as int32, a
    # record whose value does not fit is refused and counted by the
    # operator (``groupagg.lane_overflow``), never wrapped
    narrow_fields: Tuple[str, ...] = ()
    # per family the lanes that hold an event time: the operator hands
    # ``lift`` int32 offsets from the job's first timestamp under
    # ``EVENT_TIME_FIELD`` and makes the lane a timestamp again (int64)
    # before ``finalize`` and in a snapshot
    time_lanes: Tuple[Tuple[int, ...], Tuple[int, ...],
                      Tuple[int, ...]] = ((), (), ())
    # per family the lanes that hold "since the key's last row": the
    # host operator of the unwindowed aggregation puts them back to
    # their identity once a row has left (ops/global_agg.py; the device
    # lane does not run them: ``device_lane_fits``)
    emission_lanes: Tuple[Tuple[int, ...], Tuple[int, ...],
                          Tuple[int, ...]] = ((), (), ())

    @property
    def typed(self) -> bool:
        return self.lane_dtypes is not None

    def lift_masked(self, data: Arrays, valid: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """Lift a batch, mapping invalid rows to identity elements.
        Normalizes shape to (B, width) even when lift can't know B
        (e.g. count() over a batch with no data fields). Integer lanes:
        per family a tuple of (B,) columns."""
        if self.typed:
            # host columns stay host columns (the host operator folds a
            # changelog on the drain's thread: no trip to the device)
            xp = np if isinstance(valid, np.ndarray) else jnp
            return tuple(
                tuple(xp.where(valid, c.astype(dt),
                               xp.asarray(lane_identity(fam, dt), dt))
                      for c, dt in zip(cols, dts))
                for fam, cols, dts in zip(LANE_FAMILIES, self.lift(data),
                                          self.lane_dtypes))
        b = valid.shape[0]
        s, mx, mn = self.lift(data)

        v = valid[:, None]

        def norm(x, width, fill):
            if width == 0:
                return jnp.full((b, width), fill, dtype=jnp.float32)
            if x is None or x.ndim != 2 or x.shape[0] != b or x.shape[-1] != width:
                raise ValueError(
                    f"aggregate '{self.name}': lift returned shape "
                    f"{None if x is None else x.shape}, expected ({b}, {width})")
            return jnp.where(v, x, jnp.full_like(x, fill))

        return (
            norm(s, self.sum_width, 0.0),
            norm(mx, self.max_width, F32_NEG_INF),
            norm(mn, self.min_width, F32_POS_INF),
        )


def require_float_lanes(agg: Any, where: str) -> None:
    """Integer lanes are run by the unwindowed aggregation alone; the
    windowed operators hold (rows, ring, width) float32 families."""
    if getattr(agg, "typed", False):
        raise NotImplementedError(
            f"{where}: aggregate '{agg.name}' has integer lanes "
            "(count_if / int_sum_of / int_min_of / int_max_of / "
            "latest_event_time), which only KeyedStream.running_aggregate "
            "runs; a window takes the float32 lanes (sum_of, max_of, ...)")


def _empty_lanes(b: jax.Array) -> jax.Array:
    return jnp.zeros(b.shape[:1] + (0,), dtype=jnp.float32)


def probe_finalize(agg: LaneAggregate) -> Arrays:
    """``finalize`` evaluated on EMPTY lanes — THE result-field probe.
    Single-sourced here because three consumers must agree on the
    fired-row result columns: :func:`result_fields`, the compiler's
    ``ExecNode.out_schema`` recording (graph/compiler.py), and
    ``WindowOperator._result_fields``' dtype classification."""
    if agg.typed:
        return agg.finalize(
            *(tuple(np.zeros(0, dt) for dt in dts)
              for dts in agg.lane_dtypes), np.zeros((0,), np.int32))
    return agg.finalize(
        np.zeros((0, agg.sum_width), np.float32),
        np.zeros((0, agg.max_width), np.float32),
        np.zeros((0, agg.min_width), np.float32),
        np.zeros((0,), np.int32))


def result_fields(agg: LaneAggregate) -> Tuple[str, ...]:
    """The result-field names an aggregate's finalize produces (probed on
    empty lanes; mirrors WindowOperator._result_fields ordering)."""
    return tuple(sorted(probe_finalize(agg)))


def _cached(factory):
    """Memoize built-in aggregate factories so equal configurations share
    one LaneAggregate instance — and therefore one compiled kernel
    (jit caches key on the aggregate object)."""
    import functools

    return functools.lru_cache(maxsize=None)(factory)


@_cached
def count(result_field: str = "count") -> LaneAggregate:
    """COUNT(*) — pure count-lane read (Nexmark Q5's per-key COUNT).
    ref role: CountAggregator in windowed WordCount examples."""

    def lift(data: Arrays):
        b = next(iter(data.values())) if data else jnp.zeros((0,))
        z = _empty_lanes(b)
        return z, z, z

    def finalize(sums, maxs, mins, counts):
        return {result_field: counts}

    return LaneAggregate(0, 0, 0, lift, finalize, name="count", fields=(),
                         sum_fields=())


@_cached
def sum_of(field: str, result_field: Optional[str] = None) -> LaneAggregate:
    out = result_field or f"sum_{field}"

    def lift(data: Arrays):
        s = data[field].astype(jnp.float32)[:, None]
        z = _empty_lanes(data[field])
        return s, z, z

    def finalize(sums, maxs, mins, counts):
        return {out: sums[..., 0]}

    return LaneAggregate(1, 0, 0, lift, finalize, name=f"sum({field})",
                         fields=(field,), sum_fields=(field,))


@_cached
def max_of(field: str, result_field: Optional[str] = None) -> LaneAggregate:
    out = result_field or f"max_{field}"

    def lift(data: Arrays):
        m = data[field].astype(jnp.float32)[:, None]
        z = _empty_lanes(data[field])
        return z, m, z

    def finalize(sums, maxs, mins, counts):
        return {out: maxs[..., 0]}

    return LaneAggregate(0, 1, 0, lift, finalize, name=f"max({field})",
                         fields=(field,))


@_cached
def min_of(field: str, result_field: Optional[str] = None) -> LaneAggregate:
    out = result_field or f"min_{field}"

    def lift(data: Arrays):
        m = data[field].astype(jnp.float32)[:, None]
        z = _empty_lanes(data[field])
        return z, z, m

    def finalize(sums, maxs, mins, counts):
        return {out: mins[..., 0]}

    return LaneAggregate(0, 0, 1, lift, finalize, name=f"min({field})",
                         fields=(field,))


@_cached
def avg_of(field: str, result_field: Optional[str] = None) -> LaneAggregate:
    out = result_field or f"avg_{field}"

    def lift(data: Arrays):
        s = data[field].astype(jnp.float32)[:, None]
        z = _empty_lanes(data[field])
        return s, z, z

    def finalize(sums, maxs, mins, counts):
        c = jnp.maximum(counts, 1).astype(jnp.float32)
        return {out: sums[..., 0] / c}

    return LaneAggregate(1, 0, 0, lift, finalize, name=f"avg({field})",
                         fields=(field,), sum_fields=(field,))


# ---------------------------------------------------------------------------
# Integer lanes: exact at the source's widths (LaneAggregate.lane_dtypes).
# ---------------------------------------------------------------------------

def _typed(sums=(), maxs=(), mins=()):
    return (tuple(sums), tuple(maxs), tuple(mins))


@_cached
def count_if(field: str, lo: Optional[int] = None, hi: Optional[int] = None,
             result_field: Optional[str] = None) -> LaneAggregate:
    """``COUNT(*) FILTER (WHERE lo <= field < hi)`` (either bound may be
    left out): an int32 lane that adds 1 for every record under the
    predicate."""
    out = result_field or f"count_{field}_{lo}_{hi}"

    def lift(data: Arrays):
        x = data[field]
        ok = jnp.ones(x.shape, bool)
        if lo is not None:
            ok &= x >= lo
        if hi is not None:
            ok &= x < hi
        return (ok.astype(jnp.int32),), (), ()

    def finalize(sums, maxs, mins, counts):
        return {out: sums[0]}

    return LaneAggregate(1, 0, 0, lift, finalize,
                         name=f"count_if({lo}<={field}<{hi})",
                         fields=(field,), lane_dtypes=_typed(["int32"]),
                         narrow_fields=(field,))


@_cached
def int_sum_of(field: str, result_field: Optional[str] = None,
               avg_field: Optional[str] = None) -> LaneAggregate:
    """SUM over an integer column, exact at 64 bits (the chip has no
    native int64: XLA carries the lane as two words). ``avg_field``:
    also the integer quotient ``sum // count`` under that name, SQL's
    AVG over a BIGINT column, from the same lane."""
    out = result_field or f"sum_{field}"

    def lift(data: Arrays):
        return (data[field].astype(jnp.int64),), (), ()

    def finalize(sums, maxs, mins, counts):
        res = {out: sums[0]}
        if avg_field is not None:
            res[avg_field] = sums[0] // np.maximum(
                np.asarray(counts, np.int64), 1)
        return res

    return LaneAggregate(1, 0, 0, lift, finalize, name=f"int_sum({field})",
                         fields=(field,), lane_dtypes=_typed(["int64"]))


@_cached
def int_max_of(field: str, result_field: Optional[str] = None
               ) -> LaneAggregate:
    """MAX over an integer column, exact: an int32 lane (a value that
    32 bits cannot hold is refused by the operator, never wrapped)."""
    out = result_field or f"max_{field}"

    def lift(data: Arrays):
        return (), (data[field].astype(jnp.int32),), ()

    def finalize(sums, maxs, mins, counts):
        return {out: maxs[0]}

    return LaneAggregate(0, 1, 0, lift, finalize, name=f"int_max({field})",
                         fields=(field,), lane_dtypes=_typed(maxs=["int32"]),
                         narrow_fields=(field,))


@_cached
def int_min_of(field: str, result_field: Optional[str] = None
               ) -> LaneAggregate:
    """MIN over an integer column, exact: see :func:`int_max_of`."""
    out = result_field or f"min_{field}"

    def lift(data: Arrays):
        return (), (), (data[field].astype(jnp.int32),)

    def finalize(sums, maxs, mins, counts):
        return {out: mins[0]}

    return LaneAggregate(0, 0, 1, lift, finalize, name=f"int_min({field})",
                         fields=(field,), lane_dtypes=_typed(mins=["int32"]),
                         narrow_fields=(field,))


@_cached
def latest_event_time(result_field: str = "last_ts",
                      since_last_row: bool = False) -> LaneAggregate:
    """The newest event time among a key's records (the row's rowtime,
    which Flink carries beside the row): an int32 max lane over the
    offsets the operator provides under ``EVENT_TIME_FIELD``; the
    result is the timestamp itself (``time_lanes``).
    ``since_last_row``: among the records folded in since the key's
    last row left, the rowtime of THAT row (``emission_lanes``)."""

    def lift(data: Arrays):
        return (), (data[EVENT_TIME_FIELD].astype(jnp.int32),), ()

    def finalize(sums, maxs, mins, counts):
        return {result_field: maxs[0]}

    return LaneAggregate(0, 1, 0, lift, finalize, name="latest_event_time",
                         fields=(EVENT_TIME_FIELD,),
                         lane_dtypes=_typed(maxs=["int32"]),
                         time_lanes=((), (0,), ()),
                         emission_lanes=((), (0,), ()) if since_last_row
                         else ((), (), ()))


@_cached
def multi(*aggs: LaneAggregate) -> LaneAggregate:
    """Compose several aggregations over one window into one lane layout
    (e.g. Q7 needs max(price); a dashboard wants count+sum+max at once)."""
    sw = sum(a.sum_width for a in aggs)
    mw = sum(a.max_width for a in aggs)
    nw = sum(a.min_width for a in aggs)
    if any(a.typed for a in aggs):
        return _multi_typed(aggs, sw, mw, nw)

    def lift(data: Arrays):
        ss, ms, ns = [], [], []
        for a in aggs:
            s, m, n = a.lift(data)
            ss.append(s)
            ms.append(m)
            ns.append(n)
        return (
            jnp.concatenate(ss, axis=-1) if ss else None,
            jnp.concatenate(ms, axis=-1) if ms else None,
            jnp.concatenate(ns, axis=-1) if ns else None,
        )

    def finalize(sums, maxs, mins, counts):
        out: Arrays = {}
        so = mo = no = 0
        for a in aggs:
            r = a.finalize(
                sums[..., so : so + a.sum_width],
                maxs[..., mo : mo + a.max_width],
                mins[..., no : no + a.min_width],
                counts,
            )
            out.update(r)
            so += a.sum_width
            mo += a.max_width
            no += a.min_width
        return out

    comp_fields = _merged_fields(aggs, "fields")
    comp_sum: Optional[Tuple[str, ...]] = ()
    for a in aggs:
        if a.sum_fields is None:
            comp_sum = None
            break
        comp_sum = comp_sum + a.sum_fields
    return LaneAggregate(sw, mw, nw, lift, finalize,
                         name="+".join(a.name for a in aggs),
                         fields=comp_fields, sum_fields=comp_sum)


def _merged_fields(aggs, attr: str) -> Optional[Tuple[str, ...]]:
    out: Tuple[str, ...] = ()
    for a in aggs:
        if getattr(a, attr) is None:
            return None
        out = tuple(dict.fromkeys(out + getattr(a, attr)))
    return out


def _multi_typed(aggs, sw: int, mw: int, nw: int) -> LaneAggregate:
    """``multi`` over integer-lane aggregates (and lane-less ones such
    as ``count()``): the families' column tuples side by side."""
    for a in aggs:
        if not a.typed and a.sum_width + a.max_width + a.min_width:
            raise ValueError(
                f"multi: '{a.name}' has float32 lanes and cannot share a "
                "layout with integer lanes; use the int_* aggregates "
                "throughout")
    widths = [(a.sum_width, a.max_width, a.min_width) for a in aggs]

    def lift(data: Arrays):
        fams = ([], [], [])
        for a in aggs:
            if a.typed:
                for fam, cols in zip(fams, a.lift(data)):
                    fam.extend(cols)
        return tuple(tuple(f) for f in fams)

    def finalize(sums, maxs, mins, counts):
        out: Arrays = {}
        at = [0, 0, 0]
        for a, w in zip(aggs, widths):
            if a.typed:
                args = [fam[o:o + n] for fam, o, n in zip(
                    (sums, maxs, mins), at, w)]
            else:   # no lanes: whatever empty layout it expects
                args = [np.zeros((len(counts), 0), np.float32)] * 3
            out.update(a.finalize(*args, counts))
            at = [o + n for o, n in zip(at, w)]
        return out

    dtypes = tuple(
        tuple(dt for a in aggs if a.typed for dt in a.lane_dtypes[i])
        for i in range(3))
    times, since, at = ([], [], []), ([], [], []), [0, 0, 0]
    for a, w in zip(aggs, widths):
        for i in range(3):
            times[i].extend(at[i] + j for j in a.time_lanes[i])
            since[i].extend(at[i] + j for j in a.emission_lanes[i])
            at[i] += w[i]
    return LaneAggregate(
        sw, mw, nw, lift, finalize, name="+".join(a.name for a in aggs),
        fields=_merged_fields(aggs, "fields"), lane_dtypes=dtypes,
        narrow_fields=_merged_fields(aggs, "narrow_fields"),
        time_lanes=tuple(tuple(t) for t in times),
        emission_lanes=tuple(tuple(t) for t in since))


# ---------------------------------------------------------------------------
# Changelog-consuming lanes: windowed aggregation over op-typed input.
# ---------------------------------------------------------------------------

def _op_sign(data: Arrays) -> jax.Array:
    """Per-record +1/-1 from the changelog op column (records.OP_FIELD):
    +I/+U add, -U/-D subtract — retraction folding as arithmetic, the
    table-runtime ``retract()`` call vectorized into the lift (ref:
    table/runtime AggsHandleFunction.retract)."""
    from flink_tpu.records import OP_DELETE, OP_FIELD, OP_UPDATE_BEFORE

    ops = data[OP_FIELD].astype(jnp.int32)
    return jnp.where((ops == OP_UPDATE_BEFORE) | (ops == OP_DELETE),
                     -1.0, 1.0).astype(jnp.float32)


@_cached
def changelog_count(result_field: str = "count") -> LaneAggregate:
    """COUNT(*) over a changelog stream — each -U/-D row erases the +I/+U
    it supersedes, so the count is the SUM OF SIGNS, not the row count
    (the built-in count lane would double-count every update pair).
    Opaque lift (``sum_fields=None``): the sign is derived, not an
    identity field read, so the host bincount pre-agg stays off."""
    from flink_tpu.records import OP_FIELD

    def lift(data: Arrays):
        s = _op_sign(data)[:, None]
        z = _empty_lanes(s[:, 0])
        return s, z, z

    def finalize(sums, maxs, mins, counts):
        return {result_field: jnp.round(sums[..., 0]).astype(jnp.int32)}

    return LaneAggregate(1, 0, 0, lift, finalize, name="changelog_count",
                         fields=(OP_FIELD,))


@_cached
def changelog_sum_of(field: str,
                     result_field: Optional[str] = None) -> LaneAggregate:
    """SUM(field) over a changelog stream: sign-weighted values, so a
    -U retraction subtracts exactly what its +I/+U contributed."""
    from flink_tpu.records import OP_FIELD

    out = result_field or f"sum_{field}"

    def lift(data: Arrays):
        s = (data[field].astype(jnp.float32) * _op_sign(data))[:, None]
        z = _empty_lanes(data[field])
        return s, z, z

    def finalize(sums, maxs, mins, counts):
        return {out: sums[..., 0]}

    return LaneAggregate(1, 0, 0, lift, finalize,
                         name=f"changelog_sum({field})",
                         fields=(field, OP_FIELD))


@_cached
def changelog_avg_of(field: str,
                     result_field: Optional[str] = None) -> LaneAggregate:
    """AVG(field) over a changelog stream: signed sum / signed count —
    the operator's built-in count lane counts ROWS (retractions
    included), so the divisor must be a dedicated signed lane."""
    from flink_tpu.records import OP_FIELD

    out = result_field or f"avg_{field}"

    def lift(data: Arrays):
        sign = _op_sign(data)
        s = jnp.stack([data[field].astype(jnp.float32) * sign, sign],
                      axis=-1)
        z = _empty_lanes(data[field])
        return s, z, z

    def finalize(sums, maxs, mins, counts):
        c = jnp.maximum(jnp.round(sums[..., 1]), 1.0)
        return {out: sums[..., 0] / c}

    return LaneAggregate(2, 0, 0, lift, finalize,
                         name=f"changelog_avg({field})",
                         fields=(field, OP_FIELD))


@_cached
def changelog_int_sum_of(field: str, result_field: Optional[str] = None,
                         count_field: Optional[str] = None,
                         avg_field: Optional[str] = None) -> LaneAggregate:
    """SUM(field) over a changelog stream of an integer column, exact
    at 64 bits: a -U / -D row takes out exactly what its +I / +U put
    in. ``count_field``: the rows the sum stands for (+1 / -1 a row: the
    built-in count lane counts retractions too); ``avg_field``: their
    integer quotient, SQL's AVG over a BIGINT column. Two int64 sum
    lanes; numpy in, numpy out (``lift_masked``)."""
    from flink_tpu.records import OP_DELETE, OP_FIELD, OP_UPDATE_BEFORE

    out = result_field or f"sum_{field}"

    def lift(data: Arrays):
        ops = data[OP_FIELD]
        sign = 1 - 2 * ((ops == OP_UPDATE_BEFORE)
                        | (ops == OP_DELETE)).astype(jnp.int64)
        return (data[field].astype(jnp.int64) * sign, sign), (), ()

    def finalize(sums, maxs, mins, counts):
        res = {out: sums[0]}
        if count_field is not None:
            res[count_field] = sums[1]
        if avg_field is not None:
            res[avg_field] = sums[0] // np.maximum(sums[1], 1)
        return res

    return LaneAggregate(2, 0, 0, lift, finalize,
                         name=f"changelog_int_sum({field})",
                         fields=(field, OP_FIELD),
                         lane_dtypes=_typed(["int64", "int64"]))


def changelog_max_of(field: str, result_field: Optional[str] = None) -> None:
    """Refused: max is a monoid fold — it cannot retract. Once a value
    has raised the lane, subtracting its -U row cannot lower it back
    (that needs the full value multiset, i.e. an evicting window)."""
    raise NotImplementedError(
        "MAX over a changelog stream cannot retract: max(a, b) forgets "
        "the loser, so a -U row cannot undo its +U. Materialize the "
        "stream first (RetractSink / UpsertSink) or keep the raw rows "
        "with an evicting window.")


def changelog_min_of(field: str, result_field: Optional[str] = None) -> None:
    """Refused for the same reason as :func:`changelog_max_of`."""
    raise NotImplementedError(
        "MIN over a changelog stream cannot retract: min(a, b) forgets "
        "the loser, so a -U row cannot undo its +U. Materialize the "
        "stream first (RetractSink / UpsertSink) or keep the raw rows "
        "with an evicting window.")


# ---------------------------------------------------------------------------
# Lowering reference-style AggregateFunction classes.
# ---------------------------------------------------------------------------

def lower_aggregate(fn: Any, probe_fields: Dict[str, Any]) -> LaneAggregate:
    """Adapt a user AggregateFunction (create_accumulator/add/merge/
    get_result, ref: AggregateFunction.java) to the lane layout.

    Strategy: trace ``merge`` on symbolic accumulators and classify each
    accumulator leaf as sum-merged (a+b), max-merged, or min-merged by
    evaluating merge on probe values. Leaves that don't match any lane
    class are rejected — the caller should fall back to composing
    built-in lane aggregates (sum_of/max_of/...) or restructure.

    probe_fields: field name → numpy dtype, the record schema the
    aggregate will see (needed to build probe batches).
    """
    import numpy as np

    acc0 = fn.create_accumulator()
    leaves0, treedef = jax.tree_util.tree_flatten(acc0)

    # classify each leaf by behaviour of merge on probe numbers
    probes_a = [np.float64(3.0)] * len(leaves0)
    probes_b = [np.float64(5.0)] * len(leaves0)
    a = jax.tree_util.tree_unflatten(treedef, [jnp.asarray(p) for p in probes_a])
    b = jax.tree_util.tree_unflatten(treedef, [jnp.asarray(p) for p in probes_b])
    merged = fn.merge(a, b)
    mleaves = [float(x) for x in jax.tree_util.tree_leaves(merged)]

    kinds = []
    for m in mleaves:
        if abs(m - 8.0) < 1e-9:
            kinds.append("sum")
        elif abs(m - 5.0) < 1e-9:
            kinds.append("max")
        elif abs(m - 3.0) < 1e-9:
            kinds.append("min")
        else:
            raise NotImplementedError(
                f"accumulator leaf merges as neither sum/max/min (got {m} from "
                "merge(3,5)); compose flink_tpu.ops built-in lane aggregates "
                "instead")
    # disambiguate max vs min with a second probe (merge(5,3))
    a2 = jax.tree_util.tree_unflatten(treedef, [jnp.asarray(np.float64(5.0))] * len(leaves0))
    b2 = jax.tree_util.tree_unflatten(treedef, [jnp.asarray(np.float64(3.0))] * len(leaves0))
    m2 = [float(x) for x in jax.tree_util.tree_leaves(fn.merge(a2, b2))]
    for i, (k, v) in enumerate(zip(kinds, m2)):
        if k == "max" and abs(v - 5.0) > 1e-9:
            raise NotImplementedError("non-commutative merge")
        if k == "min" and abs(v - 3.0) > 1e-9:
            kinds[i] = "max"  # merge(3,5)=3? then (5,3)=5 would be 'first'; reject
            raise NotImplementedError("non-commutative merge")

    sum_ix = [i for i, k in enumerate(kinds) if k == "sum"]
    max_ix = [i for i, k in enumerate(kinds) if k == "max"]
    min_ix = [i for i, k in enumerate(kinds) if k == "min"]

    def lift(data: Arrays):
        # one vmapped add against a fresh accumulator lifts each record
        def one(row: Arrays):
            acc = fn.create_accumulator()
            return fn.add(row, acc)

        accs = jax.vmap(one)(data)
        leaves = jax.tree_util.tree_leaves(accs)
        cols = [l.astype(jnp.float32).reshape(l.shape[0], -1) for l in leaves]

        def gather(ix):
            if not ix:
                return jnp.zeros((cols[0].shape[0], 0), dtype=jnp.float32)
            return jnp.concatenate([cols[i] for i in ix], axis=-1)

        return gather(sum_ix), gather(max_ix), gather(min_ix)

    def finalize(sums, maxs, mins, counts):
        leaves = [None] * len(leaves0)
        for j, i in enumerate(sum_ix):
            leaves[i] = sums[..., j]
        for j, i in enumerate(max_ix):
            leaves[i] = maxs[..., j]
        for j, i in enumerate(min_ix):
            leaves[i] = mins[..., j]
        acc = jax.tree_util.tree_unflatten(treedef, leaves)
        res = fn.get_result(acc)
        if not isinstance(res, dict):
            res = {"result": res}
        return res

    return LaneAggregate(len(sum_ix), len(max_ix), len(min_ix), lift, finalize,
                         name=type(fn).__name__,
                         fields=tuple(probe_fields))
