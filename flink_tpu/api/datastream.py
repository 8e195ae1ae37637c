"""The fluent DataStream API.

ref: streaming/api/datastream/{DataStream,KeyedStream,WindowedStream,
DataStreamSource,SingleOutputStreamOperator,JoinedStreams}.java — the
reference's primary user API. Each call appends a Transformation; nothing
runs until ``StreamExecutionEnvironment.execute()``.

TPU-first deltas: user functions are jax-traceable **batch** functions
over struct-of-arrays dicts (fused into one compiled step per stage, the
chaining analogue), filter is a validity-mask AND (no compaction under
jit), flat_map has a static max fan-out, and keys are int64 columns
(strings must be dictionary-encoded in a prior map — strings never reach
the device).
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Sequence, Tuple, Union

import numpy as np

from flink_tpu.api.windowing import (
    EventTimeSessionWindows,
    Trigger,
    WindowAssigner,
)
from flink_tpu.graph.transformations import (
    KeyByTransformation,
    MapTransformation,
    SessionAggregateTransformation,
    SinkTransformation,
    Transformation,
    UnionTransformation,
    WindowAggregateTransformation,
    BroadcastConnectTransformation,
    WindowJoinTransformation,
)
from flink_tpu.ops.aggregates import LaneAggregate
from flink_tpu.time.watermarks import WatermarkStrategy


class DataStream:
    """ref: streaming/api/datastream/DataStream.java"""

    def __init__(self, env: "StreamExecutionEnvironment", transform: Transformation):
        self.env = env
        self.transform = transform

    # -- stateless ops (chained) -----------------------------------------
    def map(self, fn: Callable, name: str = "map") -> "DataStream":
        """``fn(data_dict) -> data_dict`` over (B,) field arrays —
        jax-traceable, traced once into the stage step function
        (ref: DataStream.map → StreamMap)."""

        def op(data, ts, valid):
            return fn(data), ts, valid

        return self._append(MapTransformation(name, (self.transform,), fn=op, kind="map"))

    def map_with_timestamps(self, fn: Callable, name: str = "map_ts") -> "DataStream":
        """``fn(data, ts, valid) -> (data, ts, valid)`` — full-control map
        (reassign timestamps, e.g. event-time extraction)."""
        return self._append(MapTransformation(name, (self.transform,), fn=fn, kind="map"))

    def filter(self, pred: Callable, name: str = "filter") -> "DataStream":
        """``pred(data_dict) -> (B,) bool`` (ref: DataStream.filter →
        StreamFilter). Lowered to a validity-mask AND."""

        def op(data, ts, valid):
            return data, ts, valid & pred(data)

        return self._append(MapTransformation(name, (self.transform,), fn=op, kind="filter"))

    def where_equals(self, field: str, value: int) -> "ViewStream":
        """The rows whose ``field`` equals ``value``: a VIEW of this
        stream (the suite's ``CREATE VIEW bid AS SELECT .. FROM datagen
        WHERE event_type = 2``). Used as a stream it is that filter;
        two views of one stream over one field can be joined without
        either being made (``JoinBuilder.right_time_within_left``)."""
        return ViewStream(self, field, value)

    def flat_map(self, fn: Callable, name: str = "flat_map") -> "DataStream":
        """``fn(data, ts, valid) -> (data', ts', valid')`` with any output
        length (ref: DataStream.flatMap → StreamFlatMap). Ingest chains
        execute on the HOST (numpy), so fan-out is unconstrained here;
        only device-fused functions need the static-fan-out form
        (api/functions.FlatMapFunction.max_fanout)."""
        return self._append(MapTransformation(name, (self.transform,), fn=fn, kind="flatmap"))

    def assign_timestamps_and_watermarks(
        self, strategy: WatermarkStrategy, ts_field: Optional[str] = None,
        name: str = "assign_ts",
    ) -> "DataStream":
        """ref: DataStream.assignTimestampsAndWatermarks. With ts_field,
        record timestamps are re-read from that column."""
        self.env._watermark_strategy = strategy
        if ts_field is None:
            return self

        def op(data, ts, valid):
            return data, data[ts_field].astype(np.int64), valid

        return self._append(MapTransformation(name, (self.transform,), fn=op, kind="map"))

    def union(self, *others: "DataStream") -> "DataStream":
        inputs = (self.transform,) + tuple(o.transform for o in others)
        return self._append(UnionTransformation("union", inputs))

    # -- keying ----------------------------------------------------------
    def key_by(self, key: Union[str, Callable], name: str = "keyBy") -> "KeyedStream":
        """ref: DataStream.keyBy → KeyedStream. ``key`` is an int64 column
        name, or a device fn(data_dict)->(B,) int64 evaluated in-stage."""
        if callable(key):
            t = KeyByTransformation(name, (self.transform,), key_field="__key__", key_fn=key)
            t.key_field = f"__key_{t.id}__"  # unique per keyBy: two keyBys
            # off one stream must not clobber each other's derived column
        else:
            t = KeyByTransformation(name, (self.transform,), key_field=key)
        self.env._register(t)
        return KeyedStream(self.env, t)

    def async_io(self, fn: Any, capacity: int = 8,
                 timeout_ms: int = 60_000, ordered: bool = True,
                 name: str = "async_io") -> "DataStream":
        """Async external enrichment (ref: AsyncDataStream.orderedWait /
        unorderedWait). ``fn`` is an api.functions-style AsyncFunction
        (invoke_batch) or a plain callable ``(data, ts) -> data'`` doing
        the external lookup for a whole microbatch; up to ``capacity``
        batches overlap on a worker pool while ingest continues.
        ``ordered=False`` releases batches as they complete; watermarks
        never overtake pending batches either way."""
        from flink_tpu.graph.transformations import AsyncIOTransformation

        return self._append(AsyncIOTransformation(
            name, (self.transform,), fn=fn, capacity=capacity,
            timeout_ms=timeout_ms, ordered=ordered))

    # -- non-keyed partitioning (ref: DataStream.{rebalance,rescale,
    # shuffle,broadcast,global} → PartitionTransformation) --------------
    def rebalance(self) -> "DataStream":
        """Round-robin across parallel subtasks — exact equal spread."""
        return self._partition("rebalance")

    def rescale(self) -> "DataStream":
        """Round-robin within the local scale group (never cross-host)."""
        return self._partition("rescale")

    def shuffle(self) -> "DataStream":
        """Uniform-random subtask per record (seeded → replay-stable)."""
        return self._partition("shuffle")

    def broadcast(self) -> "DataStream":
        """Replicate every record to every subtask."""
        return self._partition("broadcast")

    def global_(self) -> "DataStream":
        """Send everything to subtask 0 (trailing underscore: ``global``
        is a Python keyword)."""
        return self._partition("global")

    def _partition(self, strategy: str) -> "DataStream":
        from flink_tpu.graph.transformations import PartitionTransformation

        return self._append(PartitionTransformation(
            strategy, (self.transform,), strategy=strategy))

    def window_all(self, assigner: WindowAssigner) -> "AllWindowedStream":
        """Global (non-keyed) window over ALL records (ref: DataStream.
        windowAll → AllWindowedStream). Lowered without the reference's
        parallelism-1 funnel — see ops/window_all.py."""
        return AllWindowedStream(self, assigner)

    # -- joins -----------------------------------------------------------
    def join(self, other: "DataStream") -> "JoinBuilder":
        """ref: DataStream.join → JoinedStreams (where/equalTo/window)."""
        return JoinBuilder(self, other)

    def connect(self, broadcast: "DataStream") -> "BroadcastConnectedStream":
        """Connect THIS (data) stream with a low-volume CONTROL stream
        whose elements replicate into broadcast state (ref: DataStream
        .connect(BroadcastStream) → BroadcastConnectedStream; the
        broadcast state pattern). ``.process(fn)`` with a
        BroadcastProcessFunction completes the pair."""
        return BroadcastConnectedStream(self, broadcast)

    # -- sinks -----------------------------------------------------------
    def add_sink(self, sink: Any, name: str = "sink") -> "DataStream":
        return self._append(SinkTransformation(name, (self.transform,), sink=sink))

    def print(self, prefix: str = "", limit: Optional[int] = None) -> "DataStream":
        from flink_tpu.api.sinks import PrintSink

        return self.add_sink(PrintSink(prefix, limit), name="print")

    def collect(self) -> "Any":
        """Attach a CollectSink and return it (materializes at execute();
        ref: DataStream.executeAndCollect)."""
        from flink_tpu.api.sinks import CollectSink

        sink = CollectSink()
        self.add_sink(sink, name="collect")
        return sink

    def _append(self, t: Transformation) -> "DataStream":
        self.env._register(t)
        return DataStream(self.env, t)


class ViewStream(DataStream):
    """``parent.where_equals(field, value)``; its filter is appended
    only when something reads the view as a stream."""

    def __init__(self, parent: DataStream, field: str, value: int) -> None:
        self.env = parent.env
        self.parent, self.field, self.value = parent, field, value
        self._transform: Optional[Transformation] = None

    @property
    def transform(self) -> Transformation:
        if self._transform is None:
            field, value = self.field, self.value
            self._transform = self.parent.filter(
                lambda d: np.asarray(d[field]) == value,
                name=f"view_{field}_{value}").transform
        return self._transform


class KeyedStream(DataStream):
    """ref: streaming/api/datastream/KeyedStream.java"""

    def window(self, assigner: WindowAssigner) -> "WindowedStream":
        if isinstance(assigner, EventTimeSessionWindows):
            return SessionWindowedStream(self, assigner)
        return WindowedStream(self, assigner)

    def count_window(self, size: int) -> "CountWindowedStream":
        """Fires every ``size`` elements per key (ref: KeyedStream.
        countWindow = GlobalWindows + PurgingTrigger(CountTrigger)).
        Trigger evaluation is per microbatch — see ops/count_window.py
        for the documented batching semantics."""
        return CountWindowedStream(self, size, purge=True)

    def running_aggregate(self, agg, name: str = "running_agg",
                          retract: bool = False) -> "DataStream":
        """Unwindowed keyed running aggregation emitting an UPSERT
        stream: each microbatch emits updated (key, aggregates) rows
        for every key it touched, each row replacing the previous one
        for its key (ref: table-runtime GroupAggFunction — the
        retract/changelog model degenerated to upserts for insert-only
        input). Materialize latest-by-key with ``UpsertSink``.

        Where it runs: the accumulators of a ``LaneAggregate`` live on
        the device (ops/groupagg_device.py), one program a microbatch
        folding it in and gathering the touched keys' rows; integer
        lanes (``aggregates.count_if / int_sum_of / int_min_of /
        int_max_of / latest_event_time``) are exact there. ``retract=
        True`` and a job on a device mesh run the host operator
        (ops/global_agg.py): the same rows, the same snapshot format.

        ``retract=True`` emits the full CHANGELOG instead: updates
        become -U (stale row out) / +U (replacement in) pairs, first
        results are +I, op-typed in the ``__op__`` int8 column
        (records.OP_FIELD). Downstream consumers must fold retractions
        — ``RetractSink`` materializes exactly-once, and the
        ``changelog_*`` lanes of ops/aggregates.py subtract -U rows in
        a downstream window aggregation."""
        from flink_tpu.graph.transformations import (
            GlobalAggregateTransformation)

        kt = self.transform
        t = GlobalAggregateTransformation(
            name, (kt,), aggregate=agg, key_field=kt.key_field,
            retract=retract)
        self.env._register(t)
        return DataStream(self.env, t)

    def process(self, fn: Any, name: str = "keyed_process") -> "DataStream":
        """General keyed processing with state + timers (ref: KeyedStream
        .process(KeyedProcessFunction)). ``fn`` implements
        api.functions.KeyedProcessFunction — batch-vectorized hooks, or
        the per-element adapter."""
        from flink_tpu.graph.transformations import KeyedProcessTransformation

        kt = self.transform
        assert isinstance(kt, KeyByTransformation)
        t = KeyedProcessTransformation(
            name, (kt,), fn=fn, key_field=kt.key_field)
        self.env._register(t)
        return DataStream(self.env, t)

    # keyed reduce without windows = running aggregate over an eternal
    # window; expressible via GlobalWindows + custom trigger (later).


class _AggregateShortcuts:
    """count/sum/max/min sugar shared by every windowed-stream flavor;
    each delegates to the subclass's aggregate()."""

    def count(self):
        from flink_tpu.ops.aggregates import count as count_agg

        return self.aggregate(count_agg())

    def sum(self, field: str):
        from flink_tpu.ops.aggregates import sum_of

        return self.aggregate(sum_of(field))

    def max(self, field: str):
        from flink_tpu.ops.aggregates import max_of

        return self.aggregate(max_of(field))

    def min(self, field: str):
        from flink_tpu.ops.aggregates import min_of

        return self.aggregate(min_of(field))


class WindowedStream(_AggregateShortcuts):
    """ref: streaming/api/datastream/WindowedStream.java"""

    def __init__(self, keyed: KeyedStream, assigner: WindowAssigner):
        self.keyed = keyed
        self.assigner = assigner
        self._lateness = 0
        self._trigger: Optional[Trigger] = None
        self._evictor = None

    def allowed_lateness(self, ms: int) -> "WindowedStream":
        self._lateness = ms
        return self

    def trigger(self, trigger: Trigger) -> "WindowedStream":
        self._trigger = trigger
        return self

    def evictor(self, evictor) -> "WindowedStream":
        """ref: WindowedStream.evictor — routes the window onto the
        element-buffer operator (ops/evicting_window.py): eviction
        needs the window's elements at fire time, which the incremental
        pane kernels never materialize (the reference pays the same
        price — EvictingWindowOperator switches to ListState)."""
        self._evictor = evictor
        return self

    def _element_path(self) -> bool:
        """True when this window must run on the element-buffer
        operator: an evictor is set, or the trigger is outside the
        vectorized families (user Trigger subclasses, CountTrigger on
        time windows — exact per-element semantics)."""
        from flink_tpu.api.windowing import (
            EventTimeTrigger, ProcessingTimeTrigger, PurgingTrigger)

        if getattr(self, "_evictor", None) is not None:
            return True
        t = self._trigger
        if t is None or isinstance(t, (EventTimeTrigger,
                                       ProcessingTimeTrigger)):
            return False
        if isinstance(t, PurgingTrigger) and isinstance(
                t.inner, EventTimeTrigger) and self._lateness == 0:
            return False
        return True

    def apply(self, window_fn, name: str = "evicting_window") -> DataStream:
        """Element-path window function: ``window_fn(elements)`` sees
        the window's surviving elements (field arrays + ``__ts__``)
        and returns the result row's fields (ref: WindowFunction.apply
        over the evicted iterable)."""
        self._check_element_path()
        kt = self.keyed.transform
        assert isinstance(kt, KeyByTransformation)
        from flink_tpu.graph.transformations import (
            EvictingWindowTransformation)

        t = EvictingWindowTransformation(
            name, (kt,), assigner=self.assigner, window_fn=window_fn,
            trigger=self._trigger, evictor=getattr(self, "_evictor", None),
            allowed_lateness_ms=self._lateness, key_field=kt.key_field)
        self.keyed.env._register(t)
        return DataStream(self.keyed.env, t)

    def _check_element_path(self) -> None:
        """Validate combinations BEFORE building an element-buffer
        operator: that operator assigns windows by event timestamps and
        fires on the event watermark, so a processing-time assigner or
        ProcessingTimeTrigger here would silently produce wrong results
        (the pane path's _check_trigger rejects these; the element path
        must too)."""
        from flink_tpu.api.windowing import (
            ProcessingTimeTrigger, PurgingTrigger)

        if bool(getattr(self.assigner, "is_processing_time", False)):
            raise NotImplementedError(
                "processing-time window assigners are not supported on "
                "the element-buffer (evictor/custom-trigger) path — it "
                "assigns and fires on event time; use an event-time "
                "assigner or drop the evictor/custom trigger")
        t = self._trigger
        inner = t.inner if isinstance(t, PurgingTrigger) else t
        if isinstance(inner, ProcessingTimeTrigger):
            raise NotImplementedError(
                "ProcessingTimeTrigger is not supported on the element-"
                "buffer (evictor/custom-trigger) path — fires are "
                "driven by the event watermark there")

    def _check_trigger(self) -> None:
        """Validate the trigger/window combination at build time —
        unsupported combinations must raise, never be silently ignored
        (ref: WindowedStream.trigger contract)."""
        from flink_tpu.api.windowing import (
            CountTrigger, EventTimeTrigger, ProcessingTimeTrigger,
            PurgingTrigger)

        proc_assigner = bool(getattr(self.assigner, "is_processing_time",
                                     False))
        if proc_assigner and self._lateness:
            raise NotImplementedError(
                "allowed lateness is an event-time concept; processing-"
                "time windows cannot see late records (ref: "
                "WindowedStream.allowedLateness is event-time only)")
        t = self._trigger
        if isinstance(t, ProcessingTimeTrigger):
            if proc_assigner:
                return  # the proc-time assigners' default trigger
            raise NotImplementedError(
                "ProcessingTimeTrigger requires a processing-time window "
                "assigner (Tumbling/SlidingProcessingTimeWindows)")
        if proc_assigner and isinstance(t, EventTimeTrigger):
            raise NotImplementedError(
                "EventTimeTrigger on processing-time windows is not "
                "supported — the window's time axis is the clock")
        if t is None or isinstance(t, EventTimeTrigger):
            return
        if isinstance(t, PurgingTrigger) and isinstance(
                t.inner, EventTimeTrigger):
            # FIRE_AND_PURGE at the watermark: with zero allowed
            # lateness the window's state is purged at its lateness
            # horizon — i.e. AT the fire — so the purging wrapper is
            # exactly the default behavior. With lateness it would
            # change late-record semantics (fresh state instead of
            # re-aggregation), which the pane backend doesn't express.
            if self._lateness == 0:
                return
            raise NotImplementedError(
                "PurgingTrigger(EventTimeTrigger) with allowed lateness "
                "> 0 is not supported (late records would need "
                "fresh-state semantics); drop the lateness or the "
                "purging wrapper")
        inner = t.inner if isinstance(t, PurgingTrigger) else t
        if isinstance(inner, CountTrigger):
            raise NotImplementedError(
                "count triggers on time windows are not supported; use "
                "key_by(...).count_window(n) (GlobalWindows + "
                "CountTrigger, the reference's countWindow lowering)")
        raise NotImplementedError(
            f"unsupported trigger {type(t).__name__} for time windows")

    def aggregate(self, agg: LaneAggregate, name: str = "window_agg") -> "WindowedAggregateStream":
        """ref: WindowedStream.aggregate(AggregateFunction) — but taking
        the lane-lowered form directly; ``lower_aggregate`` adapts
        reference-style AggregateFunction classes."""
        if self._element_path():
            return self.apply(_element_window_fn(agg), name=name)
        self._check_trigger()
        kt = self.keyed.transform
        assert isinstance(kt, KeyByTransformation)
        t = WindowAggregateTransformation(
            name, (kt,),
            assigner=self.assigner, aggregate=agg, trigger=self._trigger,
            allowed_lateness_ms=self._lateness, key_field=kt.key_field)
        self.keyed.env._register(t)
        return WindowedAggregateStream(self.keyed.env, t)



def _element_window_fn(agg: LaneAggregate):
    """Adapt a LaneAggregate to the element-path window-function
    contract: reduce the surviving elements' lifted lanes and finalize.
    Host-side per (key, window) — the compatibility path's cost."""
    import numpy as np

    def fn(elements):
        data = {k: v for k, v in elements.items() if k != "__ts__"}
        n = len(elements["__ts__"])
        import jax.numpy as jnp

        s, mx, mn = agg.lift_masked(
            {k: jnp.asarray(np.asarray(v)) for k, v in data.items()},
            jnp.ones(n, bool))
        res = agg.finalize(jnp.sum(s, axis=0), jnp.max(mx, axis=0),
                           jnp.min(mn, axis=0), jnp.asarray(n, jnp.int32))
        return {k: np.asarray(v) for k, v in res.items()}

    return fn


class AllWindowedStream(_AggregateShortcuts):
    """ref: streaming/api/datastream/AllWindowedStream.java"""

    def __init__(self, stream: DataStream, assigner: WindowAssigner):
        self.stream = stream
        self.assigner = assigner
        self._lateness = 0

    def allowed_lateness(self, ms: int) -> "AllWindowedStream":
        self._lateness = ms
        return self

    def aggregate(self, agg: LaneAggregate,
                  name: str = "window_all_agg") -> DataStream:
        from flink_tpu.graph.transformations import (
            WindowAllAggregateTransformation)

        t = WindowAllAggregateTransformation(
            name, (self.stream.transform,), assigner=self.assigner,
            aggregate=agg, allowed_lateness_ms=self._lateness)
        self.stream.env._register(t)
        return DataStream(self.stream.env, t)


class CountWindowedStream(_AggregateShortcuts):
    """ref: KeyedStream.countWindow — GlobalWindows + (Purging)Count
    trigger, lowered to the vectorized per-step mask (ops/count_window)."""

    def __init__(self, keyed: KeyedStream, size: int, purge: bool = True):
        self.keyed = keyed
        self.size = size
        self.purge = purge

    def aggregate(self, agg: LaneAggregate,
                  name: str = "count_window_agg") -> DataStream:
        from flink_tpu.graph.transformations import (
            CountWindowAggregateTransformation)

        kt = self.keyed.transform
        assert isinstance(kt, KeyByTransformation)
        t = CountWindowAggregateTransformation(
            name, (kt,), size=self.size, purge=self.purge,
            aggregate=agg, key_field=kt.key_field)
        self.keyed.env._register(t)
        return DataStream(self.keyed.env, t)



class WindowedAggregateStream(DataStream):
    """The stream of fired (key, window, result...) rows. Exposes
    post-aggregation shapes that FUSE into the window operator's device
    fire path instead of running on the host."""

    def top(self, n: int, by: Optional[str] = None,
            name: str = "window_top") -> DataStream:
        """Keep only each window's top-``n`` rows ranked by result field
        ``by`` (ties at the n-th value kept — SQL RANK() <= n, the
        Nexmark Q5 hot-items shape). Evaluated ON DEVICE inside the fire
        kernel, so only winners ever cross to the host — the whole
        per-key result set stays in HBM. ``by`` defaults to the
        aggregate's single result field."""
        t = self.transform
        if by is None:
            from flink_tpu.ops.aggregates import result_fields

            fields = result_fields(t.aggregate)
            if len(fields) != 1:
                raise ValueError(
                    f"aggregate produces {fields}; pass by= explicitly")
            by = fields[0]
        t.top_n = (by, n)
        return self


class SessionWindowedStream(WindowedStream):
    def aggregate(self, agg: LaneAggregate, name: str = "session_agg",
                  retract: bool = False) -> DataStream:
        """``retract=True``: session-merge refires op-type their rows —
        a merge consuming an already-fired span emits -U for the stale
        (key, window) row before the merged session fires +I/+U (see
        ops/session.py retract mode)."""
        self._check_trigger()
        kt = self.keyed.transform
        assert isinstance(kt, KeyByTransformation)
        t = SessionAggregateTransformation(
            name, (kt,), gap_ms=self.assigner.gap, aggregate=agg,
            allowed_lateness_ms=self._lateness, key_field=kt.key_field,
            retract=retract)
        self.keyed.env._register(t)
        return DataStream(self.keyed.env, t)


class JoinBuilder:
    """where/equalTo/window/apply chain (ref: JoinedStreams.java)."""

    def __init__(self, left: DataStream, right: DataStream):
        self._left = left
        self._right = right
        self._left_key: Optional[str] = None
        self._right_key: Optional[str] = None

    def where(self, key_field: str) -> "JoinBuilder":
        self._left_key = key_field
        return self

    def equal_to(self, key_field: str) -> "JoinBuilder":
        self._right_key = key_field
        return self

    def window(self, assigner: WindowAssigner) -> "WindowedJoin":
        return WindowedJoin(self, assigner)

    def right_time_within_left(self, until: str) -> "UnboundedJoin":
        """No window: the join keeps both sides for ever, and a right
        row matches the left row of its key when its event time lies
        between the left row's event time and the left row's ``until``
        column, both ends inclusive (``r.rowtime BETWEEN l.rowtime AND
        l.until``). The left side has ONE row a key. Both sides must be
        views of one stream over one field (``where_equals``)."""
        return UnboundedJoin(self, until)


class UnboundedJoin:
    def __init__(self, builder: JoinBuilder, until: str):
        self.b, self.until = builder, until

    def max(self, field: str, carry: str, result_field: Optional[str] = None,
            name: str = "keyed_join") -> DataStream:
        """``SELECT l.key, l.carry, MAX(r.field) .. GROUP BY l.key,
        l.carry`` as a CHANGELOG: after every microbatch, for each key
        whose maximum appeared or changed in it, ``+I``, or ``-U`` (the
        value last emitted) and ``+U`` (``records.OP_FIELD``); columns
        ``key``, ``carry``, ``result_field`` (default ``max_<field>``).
        Both sides' state lives on the device (ops/join_device.py; the
        factory chooses the lane, ops/join_host.py otherwise). A
        stateful consumer (``running_aggregate`` over the changelog)
        folds it one mini-batch at a time."""
        from flink_tpu.graph.transformations import KeyedJoinTransformation

        left, right = self.b._left, self.b._right
        if not (isinstance(left, ViewStream) and isinstance(right, ViewStream)
                and left.parent is right.parent
                and left.field == right.field
                and left.value != right.value):
            raise NotImplementedError(
                "an unbounded join takes two views of ONE stream over one "
                "field (stream.where_equals(field, a).join(stream"
                ".where_equals(field, b))); two streams of their own "
                "would need a two-input keyed operator that is not built")
        env = left.env
        t = KeyedJoinTransformation(
            name, (left.parent.transform,), side_field=left.field,
            left_value=left.value, right_value=right.value,
            left_key=self.b._left_key or "key",
            right_key=self.b._right_key or "key", until_field=self.until,
            carry_field=carry, value_field=field,
            result_field=result_field or f"max_{field}")
        env._register(t)
        return DataStream(env, t)


class WindowedJoin:
    def __init__(self, builder: JoinBuilder, assigner: WindowAssigner):
        self.b = builder
        self.assigner = assigner

    def apply(
        self,
        left_fields: Sequence[str] = (),
        right_fields: Sequence[str] = (),
        name: str = "window_join",
        mode: str = "pairs",
    ) -> DataStream:
        """``mode='pairs'`` (default): one row per matching left×right
        pair — the reference's exact JoinFunction semantics.
        ``mode='aggregate'``: one row per (key, window) present on both
        sides with per-side count + max-carried fields (cogroup-style
        summary). See ops/join.py."""
        env = self.b._left.env
        t = WindowJoinTransformation(
            name, (self.b._left.transform, self.b._right.transform),
            assigner=self.assigner,
            left_key=self.b._left_key or "key",
            right_key=self.b._right_key or "key",
            left_fields=tuple(left_fields), right_fields=tuple(right_fields),
            mode=mode)
        env._register(t)
        return DataStream(env, t)


class BroadcastConnectedStream:
    """ref: BroadcastConnectedStream — the (data, control) pair awaiting
    its BroadcastProcessFunction."""

    def __init__(self, data: DataStream, control: DataStream) -> None:
        self._data = data
        self._control = control

    def process(self, fn: Any,
                name: str = "broadcast_connect") -> DataStream:
        t = BroadcastConnectTransformation(
            name, (self._data.transform, self._control.transform), fn=fn)
        self._data.env._register(t)
        return DataStream(self._data.env, t)
