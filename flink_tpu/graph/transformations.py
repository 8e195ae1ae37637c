"""Logical transformation DAG — what the fluent API builds.

ref: streaming/api/transformations/{OneInputTransformation,
PartitionTransformation,SourceTransformation,SinkTransformation,
UnionTransformation}.java — each fluent call appends one node; nothing
executes until the graph is lowered and run (lazy, like the reference's
StreamExecutionEnvironment.execute()).

TPU-first notes: transformations carry no parallelism (parallelism is a
property of the device mesh chosen at execution, not of graph nodes), and
the stateless ones carry jax-traceable batch functions that the lowering
step fuses into one compiled step function per stage (the operator
chaining analogue; ref: StreamingJobGraphGenerator.isChainable).
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Any, Callable, Dict, List, Optional, Tuple

from flink_tpu.api.windowing import Trigger, WindowAssigner
from flink_tpu.ops.aggregates import LaneAggregate
from flink_tpu.time.watermarks import WatermarkStrategy

_ids = itertools.count()


@dataclasses.dataclass
class Transformation:
    """Base DAG node. ``inputs`` are upstream transformations."""

    name: str
    inputs: Tuple["Transformation", ...] = ()

    def __post_init__(self) -> None:
        self.id = next(_ids)

    def __hash__(self) -> int:
        return self.id

    def __eq__(self, other: object) -> bool:
        return self is other


@dataclasses.dataclass(eq=False)
class SourceTransformation(Transformation):
    """ref: SourceTransformation.java + the FLIP-27 Source seam
    (flink-core/.../api/connector/source/Source.java)."""

    source: Any = None  # flink_tpu.api.sources.Source
    watermark_strategy: Optional[WatermarkStrategy] = None


@dataclasses.dataclass(eq=False)
class MapTransformation(Transformation):
    """map/filter/flatMap — chainable stateless batch fns
    (ref: OneInputTransformation wrapping StreamMap/StreamFilter/
    StreamFlatMap operators)."""

    # fn(data: dict, ts, valid) -> (data, ts, valid); traced into the
    # stage step function
    fn: Optional[Callable] = None
    kind: str = "map"  # map | filter | flatmap | process


@dataclasses.dataclass(eq=False)
class KeyByTransformation(Transformation):
    """Hash partition by key (ref: PartitionTransformation with
    KeyGroupStreamPartitioner). key_field names an int64 column; key_fn
    optionally derives it on device first."""

    key_field: str = "key"
    key_fn: Optional[Callable] = None


@dataclasses.dataclass(eq=False)
class WindowAggregateTransformation(Transformation):
    """Keyed window + aggregate (ref: WindowedStream.aggregate →
    WindowOperator via WindowOperatorBuilder)."""

    assigner: Optional[WindowAssigner] = None
    aggregate: Optional[LaneAggregate] = None
    trigger: Optional[Trigger] = None
    allowed_lateness_ms: int = 0
    key_field: str = "key"
    # (result_field, n): fuse a per-window top-n (ties kept) into the
    # window operator's device fire path (set via DataStream.top)
    top_n: Optional[Tuple[str, int]] = None


@dataclasses.dataclass(eq=False)
class EvictingWindowTransformation(Transformation):
    """Keyed window with an evictor and/or a custom user trigger — the
    element-buffer path (ref: WindowedStream.evictor/trigger →
    EvictingWindowOperator; see ops/evicting_window.py for why this
    cannot ride the pane kernels)."""

    assigner: Optional[WindowAssigner] = None
    window_fn: Any = None        # fn(elements dict incl __ts__) -> row dict
    trigger: Optional[Trigger] = None
    evictor: Any = None
    allowed_lateness_ms: int = 0
    key_field: str = "key"


@dataclasses.dataclass(eq=False)
class AsyncIOTransformation(Transformation):
    """Async external enrichment (ref: AsyncDataStream.orderedWait /
    unorderedWait -> AsyncWaitOperator; see ops/async_io.py)."""

    fn: Any = None                # AsyncFunction or callable(data, ts)
    capacity: int = 8
    timeout_ms: int = 60_000
    ordered: bool = True


@dataclasses.dataclass(eq=False)
class PartitionTransformation(Transformation):
    """Non-keyed redistribution (ref: PartitionTransformation.java with
    the streaming/runtime/partitioner family). ``strategy`` is one of
    rebalance|rescale|shuffle|broadcast|global|forward — lowered to an
    exchange boundary that breaks operator chaining; the subtask
    assignment itself lives in exchange/partitioners.py."""

    strategy: str = "rebalance"


@dataclasses.dataclass(eq=False)
class CepTransformation(Transformation):
    """Keyed pattern matching (ref: cep/PatternStream → CepOperator;
    see flink_tpu/cep.py)."""

    pattern: Any = None
    key_field: str = "key"


@dataclasses.dataclass(eq=False)
class KeyedProcessTransformation(Transformation):
    """Keyed process function with state + timers (ref: KeyedStream
    .process → KeyedProcessOperator; see ops/process.py)."""

    fn: Any = None  # api.functions.KeyedProcessFunction
    key_field: str = "key"


@dataclasses.dataclass(eq=False)
class WindowAllAggregateTransformation(Transformation):
    """Non-keyed global window + aggregate (ref: DataStream.windowAll →
    AllWindowedStream at parallelism 1; here a host-side pane reduce
    with NO single-shard funnel — see ops/window_all.py)."""

    assigner: Optional[WindowAssigner] = None
    aggregate: Optional[LaneAggregate] = None
    allowed_lateness_ms: int = 0


@dataclasses.dataclass(eq=False)
class CountWindowAggregateTransformation(Transformation):
    """Keyed count window (ref: KeyedStream.countWindow = GlobalWindows
    + PurgingTrigger(CountTrigger(n)); lowered to a vectorized per-step
    trigger mask — see ops/count_window.py)."""

    size: int = 0
    purge: bool = True
    aggregate: Optional[LaneAggregate] = None
    key_field: str = "key"


@dataclasses.dataclass(eq=False)
class GlobalAggregateTransformation(Transformation):
    """Unwindowed keyed running aggregation emitting an upsert stream
    (ref: table-runtime GroupAggFunction / retract-changelog semantics
    degenerated to upserts for insert-only input — see
    ops/global_agg.py). ``retract=True`` emits the full op-typed
    changelog instead (-U/+U pairs, records.OP_FIELD lane)."""

    aggregate: Optional[LaneAggregate] = None
    key_field: str = "key"
    retract: bool = False


@dataclasses.dataclass(eq=False)
class WindowJoinTransformation(Transformation):
    """Two-input tumbling-window equi-join (ref: streaming/api/datastream/
    JoinedStreams.java lowered onto WindowOperator with a union state;
    here a dedicated two-family pane join — Q8)."""

    assigner: Optional[WindowAssigner] = None
    left_key: str = "key"
    right_key: str = "key"
    left_fields: Tuple[str, ...] = ()
    right_fields: Tuple[str, ...] = ()
    mode: str = "pairs"  # "pairs" (exact) | "aggregate" (cogroup summary)


@dataclasses.dataclass(eq=False)
class KeyedJoinTransformation(Transformation):
    """Unbounded equi-join of two VIEWS of one stream (rows told apart
    by ``side_field``, as the NEXmark suite's ``auction`` and ``bid``
    views of its one ``datagen`` table are), the left side one row a
    key, the right side reduced (``MAX(value_field)``) under ``r.rowtime
    BETWEEN l.rowtime AND l.until_field``; emits the result's changelog
    a mini-batch (ref: table-runtime StreamingJoinOperator feeding a
    GroupAggFunction, both keeping state without a TTL; see
    ops/join_host.py). The one input is the views' common stream."""

    side_field: str = "side"
    left_value: int = 0
    right_value: int = 1
    left_key: str = "key"
    right_key: str = "key"
    until_field: str = "until"
    carry_field: str = "carry"
    value_field: str = "value"
    result_field: str = "max_value"


@dataclasses.dataclass(eq=False)
class SessionAggregateTransformation(Transformation):
    """Keyed session windows (ref: EventTimeSessionWindows +
    MergingWindowSet) — session lanes on the device, or the host's span
    registry where the job retracts, re-fires within allowed lateness or
    runs on a mesh (the driver chooses).
    ``retract=True`` op-types the output: a merge that consumes an
    already-fired span retracts its stale row (-U) before the merged
    session (re)fires (+U)."""

    gap_ms: int = 0
    aggregate: Optional[LaneAggregate] = None
    allowed_lateness_ms: int = 0
    key_field: str = "key"
    retract: bool = False


@dataclasses.dataclass(eq=False)
class SinkTransformation(Transformation):
    """ref: SinkTransformation.java + Sink API v2
    (flink-core/.../api/connector/sink2/Sink.java)."""

    sink: Any = None  # flink_tpu.api.sinks.Sink


@dataclasses.dataclass(eq=False)
class UnionTransformation(Transformation):
    """ref: UnionTransformation.java — merge same-schema streams."""


@dataclasses.dataclass(eq=False)
class BroadcastConnectTransformation(Transformation):
    """Two-input broadcast connect: inputs = (data stream, control
    stream); the control side replicates into broadcast state (ref:
    BroadcastConnectedStream + CoBroadcastWithNonKeyedOperator)."""

    fn: Any = None
