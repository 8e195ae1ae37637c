"""Graph lowering: Transformation DAG → executable stage plan.

ref: the two-step lowering StreamGraphGenerator (streaming/api/graph/
StreamGraphGenerator.java) → StreamingJobGraphGenerator.createJobGraph
(chaining decided in ``isChainable``). Here the chaining analogue fuses
every run of stateless transformations between stateful/exchange
boundaries into ONE host ingest function per stage — and the heavy
lifting (keyed window state, shuffles, aggregation) is inside the
stateful ops' compiled device programs.

The plan is a DAG of ExecNodes the driver walks per microbatch:
  ExecSource → ExecChain (fused stateless fns) → ExecWindowAgg /
  ExecSessionAgg / ExecJoin → ExecChain → ExecSink
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from flink_tpu.config import Configuration, PipelineOptions, StateOptions
from flink_tpu.graph.transformations import (
    EvictingWindowTransformation,
    BroadcastConnectTransformation,
    KeyByTransformation,
    MapTransformation,
    AsyncIOTransformation,
    CepTransformation,
    CountWindowAggregateTransformation,
    GlobalAggregateTransformation,
    KeyedJoinTransformation,
    KeyedProcessTransformation,
    PartitionTransformation,
    SessionAggregateTransformation,
    WindowAllAggregateTransformation,
    SinkTransformation,
    SourceTransformation,
    Transformation,
    UnionTransformation,
    WindowAggregateTransformation,
    WindowJoinTransformation,
)
from flink_tpu.time.watermarks import WatermarkStrategy


@dataclasses.dataclass
class ExecNode:
    id: int
    kind: str                 # source | chain | window | session | join | sink | union
    downstream: List[int] = dataclasses.field(default_factory=list)
    # kind-specific payloads
    source: Any = None
    watermark_strategy: Optional[WatermarkStrategy] = None
    fns: List[Callable] = dataclasses.field(default_factory=list)
    key_field: str = "key"
    key_fn: Optional[Callable] = None
    window_transform: Any = None
    sink: Any = None
    # join: which input edge is left/right (by upstream node id)
    left_input: Optional[int] = None
    right_input: Optional[int] = None
    # partition: non-keyed redistribution strategy (exchange boundary)
    partition_strategy: Optional[str] = None
    # keyed stateful ops: whether the op's input edge came through a
    # keyBy exchange (the lowering folds KeyByTransformation into the
    # op, so the plan must remember the exchange existed — the
    # analyzer's KEYED_WITHOUT_KEYBY rule reads this)
    keyed_input: bool = False
    # declared OUTPUT record schema (field → numpy dtype name) of this
    # node's emitted rows, recorded at lowering for the operator kinds
    # whose fired-row shape is a plan fact (key/window columns + the
    # aggregate's probed result fields). None = not statically known
    # (chains, opaque window fns, CEP matches). The analyzer's dataflow
    # plane reads this the way KEYED_OP_WITHOUT_KEYBY reads
    # ``keyed_input`` (analysis/dataflow.py).
    out_schema: Optional[Dict[str, str]] = None
    name: str = ""


@dataclasses.dataclass
class ExecutionPlan:
    nodes: Dict[int, ExecNode]
    sources: List[int]
    topo_order: List[int]
    watermark_strategy: WatermarkStrategy
    # bounded-execution plan (execution.runtime-mode=batch, SURVEY
    # §3.7): stage_of levels every node into a topological wave;
    # blocking_edges are the (upstream, stateful-consumer) edges the
    # driver materializes through the blocking shuffle instead of
    # pushing through. Empty/default in streaming mode.
    runtime_mode: str = "streaming"
    stage_of: Dict[int, int] = dataclasses.field(default_factory=dict)
    blocking_edges: List[Tuple[int, int]] = dataclasses.field(
        default_factory=list)

    def node(self, nid: int) -> ExecNode:
        return self.nodes[nid]


# Stateful operator kinds whose input edge becomes BLOCKING in batch
# mode — the exchange boundary of the reference's batch shuffles
# (§3.6): the consumer must not see a single record until the producer
# stage ran to completion. Chains/unions/partitions/sinks stay
# pipelined within their stage (the isChainable rule: only exchange
# edges block). async_io blocks too: its in-flight draining is driven
# by the per-step watermark pass that batch mode deliberately skips,
# so the batch driver owns its submit/poll cycle at a stage head.
STAGE_HEAD_KINDS = frozenset((
    "window", "session", "join", "count_window", "window_all",
    "process", "cep", "evicting_window", "global_agg", "keyed_join",
    "broadcast_connect", "async_io",
))


def assign_stages(
    nodes: Dict[int, ExecNode], topo: List[int],
) -> Tuple[Dict[int, int], List[Tuple[int, int]]]:
    """Level every node into topological waves: a stateful consumer
    lives one wave below its producers (its input edges block); every
    other node joins its deepest producer's wave (pipelined). The wave
    number IS the scheduling order (runtime/scheduler.py runs waves
    sequentially — the topological-wave analogue of batch pipelined-
    region scheduling over BLOCKING result partitions)."""
    upstream: Dict[int, List[int]] = {nid: [] for nid in nodes}
    for n in nodes.values():
        for d in n.downstream:
            upstream[d].append(n.id)
    stage_of: Dict[int, int] = {}
    blocking: List[Tuple[int, int]] = []
    for nid in topo:
        ups = upstream[nid]
        base = max((stage_of[u] for u in ups), default=0)
        if nodes[nid].kind in STAGE_HEAD_KINDS:
            if len(set(ups)) != len(ups):
                # s.join(s) / s.connect(s): both inputs are the SAME
                # producer node, so the two logical edges collapse onto
                # one (u, v) key — the partition-file exchange cannot
                # tell the sides apart. Reject rather than corrupt.
                raise NotImplementedError(
                    f"batch mode does not support a two-input operator "
                    f"({nodes[nid].kind} {nodes[nid].name!r}) fed twice "
                    "by the same upstream node (self-join/self-connect)"
                    " — materialize one side through a distinct map "
                    "first")
            stage_of[nid] = base + 1
            blocking.extend((u, nid) for u in ups)
        else:
            stage_of[nid] = base
    return stage_of, blocking


def _probe_result_schema(agg) -> Dict[str, str]:
    """Result-field names + coarse dtypes of a LaneAggregate, via the
    shared empty-lane probe (ops/aggregates.probe_finalize — the same
    source WindowOperator._result_fields classifies dtypes from):
    integer-classified lanes emit int64 columns, the rest float32."""
    from flink_tpu.ops.aggregates import probe_finalize

    return {
        k: ("int64" if np.issubdtype(np.asarray(v).dtype, np.integer)
            else "float32")
        for k, v in probe_finalize(agg).items()}


def _op_out_schema(node: ExecNode) -> Optional[Dict[str, str]]:
    """The statically-known fired-row schema of a stateful op — the
    (key, window_start, window_end, count) columns every windowed
    operator emits plus the aggregate's probed result fields (kept in
    lockstep with ops/{window,session,count_window,global_agg,
    window_all,join}.py output assembly). None when the output shape is
    not a plan fact (opaque window fns, CEP match rows, async
    enrichment)."""
    wt = node.window_transform
    try:
        if node.kind in ("window", "session", "count_window"):
            out = {"key": "int64", "window_start": "int64",
                   "window_end": "int64", "count": "int64"}
            out.update(_probe_result_schema(wt.aggregate))
            if getattr(wt, "retract", False):
                out["__op__"] = "int8"  # records.OP_FIELD changelog lane
            return out
        if node.kind == "window_all":
            out = {"window_start": "int64", "window_end": "int64",
                   "count": "int64"}
            out.update(_probe_result_schema(wt.aggregate))
            return out
        if node.kind == "global_agg":
            out = {"key": "int64", "count": "int64"}
            out.update(_probe_result_schema(wt.aggregate))
            if getattr(wt, "retract", False):
                out["__op__"] = "int8"  # records.OP_FIELD changelog lane
            return out
        if node.kind == "keyed_join":
            # ops/join_host.py changelog_rows
            return {"key": "int64", wt.carry_field: "int64",
                    wt.result_field: "int64", "__op__": "int8",
                    "__minibatch__": "int64"}
        if node.kind == "join":
            out = {"key": "int64", "window_start": "int64",
                   "window_end": "int64"}
            if wt.mode == "aggregate":
                out["left_count"] = "int64"
                out["right_count"] = "int64"
            for f in wt.left_fields:
                out[f"left_{f}"] = "float32"
            for f in wt.right_fields:
                out[f"right_{f}"] = "float32"
            return out
    except Exception:
        # schema recording must never fail a lowering the runtime would
        # accept (a user aggregate whose finalize rejects empty lanes)
        return None
    return None


def compile_job(
    transforms: Sequence[Transformation],
    config: Configuration,
    default_wm: WatermarkStrategy,
    strict: bool = True,
) -> ExecutionPlan:
    """Lower the transformation list. Chaining rule (the isChainable
    analogue): consecutive Map/Filter/FlatMap nodes with a single
    consumer fuse into one ExecChain; KeyBy folds into the downstream
    stateful op (the exchange lives inside its device program).

    ``strict=False`` lowers a plan that strict compilation would
    reject (unbounded sources in batch mode) so the plan ANALYZER can
    report the violation as a structured finding instead of dying on
    the first hard error — the execution path always compiles strict."""
    # consumers per transformation
    consumers: Dict[int, List[Transformation]] = {}
    for t in transforms:
        for up in t.inputs:
            consumers.setdefault(up.id, []).append(t)

    nodes: Dict[int, ExecNode] = {}
    t2node: Dict[int, int] = {}  # transformation id -> exec node id
    next_id = [0]

    def new_node(kind: str, name: str, **kw) -> ExecNode:
        wt = kw.get("window_transform")
        if kind != "global_agg" and wt is not None:
            from flink_tpu.ops.aggregates import require_float_lanes

            require_float_lanes(getattr(wt, "aggregate", None),
                                f"{kind} '{name}'")
        n = ExecNode(id=next_id[0], kind=kind, name=name, **kw)
        next_id[0] += 1
        nodes[n.id] = n
        return n

    def keyed_in(t: Transformation) -> bool:
        """Whether t's input edge is a keyBy exchange (KeyBy folds
        into the downstream stateful op, so the plan records the
        exchange on the op — analysis/plan_rules.py
        KEYED_OP_WITHOUT_KEYBY reads this)."""
        return isinstance(t.inputs[0], KeyByTransformation)

    def node_for(t: Transformation) -> int:
        """Exec node that PRODUCES t's output batches."""
        if t.id in t2node:
            return t2node[t.id]
        if isinstance(t, SourceTransformation):
            n = new_node("source", t.name, source=t.source,
                         watermark_strategy=t.watermark_strategy)
        elif isinstance(t, MapTransformation):
            up = node_for(t.inputs[0])
            upn = nodes[up]
            # chain into upstream chain node if it's a chain with a
            # single consumer path (always true here: we create chains
            # per linear run)
            if upn.kind == "chain" and len(consumers.get(t.inputs[0].id, [])) == 1:
                upn.fns.append(t.fn)
                t2node[t.id] = upn.id
                return upn.id
            n = new_node("chain", t.name, fns=[t.fn])
            upn.downstream.append(n.id)
        elif isinstance(t, KeyByTransformation):
            # keyBy is virtual: the downstream stateful op reads key_field
            up = node_for(t.inputs[0])
            t2node[t.id] = up
            # key_fn materializes the key column via an appended chain fn;
            # fuse into the upstream chain only when this keyBy is its
            # sole consumer (sibling branches must not see the injected
            # key column — same single-consumer rule as map chaining)
            if t.key_fn is not None:
                fn = t.key_fn

                def add_key(data, ts, valid, _fn=fn, _kf=t.key_field):
                    data = dict(data)
                    data[_kf] = np.asarray(_fn(data), np.int64)
                    return data, ts, valid

                upn = nodes[up]
                if (upn.kind == "chain"
                        and len(consumers.get(t.inputs[0].id, [])) == 1):
                    upn.fns.append(add_key)
                else:
                    n = new_node("chain", "key_extract", fns=[add_key])
                    upn.downstream.append(n.id)
                    t2node[t.id] = n.id
                    return n.id
            return up
        elif isinstance(t, WindowAggregateTransformation):
            up = node_for(t.inputs[0])
            n = new_node("window", t.name, window_transform=t,
                         key_field=t.key_field, keyed_input=keyed_in(t))
            nodes[up].downstream.append(n.id)
        elif isinstance(t, EvictingWindowTransformation):
            up = node_for(t.inputs[0])
            n = new_node("evicting_window", t.name, window_transform=t,
                         key_field=t.key_field, keyed_input=keyed_in(t))
            nodes[up].downstream.append(n.id)
        elif isinstance(t, AsyncIOTransformation):
            up = node_for(t.inputs[0])
            n = new_node("async_io", t.name, window_transform=t)
            nodes[up].downstream.append(n.id)
        elif isinstance(t, PartitionTransformation):
            # an exchange boundary: always its own node (breaks the
            # chain — the isChainable rule excludes non-forward edges)
            up = node_for(t.inputs[0])
            n = new_node("partition", t.name, partition_strategy=t.strategy)
            nodes[up].downstream.append(n.id)
        elif isinstance(t, CepTransformation):
            up = node_for(t.inputs[0])
            n = new_node("cep", t.name, window_transform=t,
                         key_field=t.key_field, keyed_input=keyed_in(t))
            nodes[up].downstream.append(n.id)
        elif isinstance(t, KeyedProcessTransformation):
            up = node_for(t.inputs[0])
            n = new_node("process", t.name, window_transform=t,
                         key_field=t.key_field, keyed_input=keyed_in(t))
            nodes[up].downstream.append(n.id)
        elif isinstance(t, WindowAllAggregateTransformation):
            up = node_for(t.inputs[0])
            n = new_node("window_all", t.name, window_transform=t)
            nodes[up].downstream.append(n.id)
        elif isinstance(t, CountWindowAggregateTransformation):
            up = node_for(t.inputs[0])
            n = new_node("count_window", t.name, window_transform=t,
                         key_field=t.key_field, keyed_input=keyed_in(t))
            nodes[up].downstream.append(n.id)
        elif isinstance(t, GlobalAggregateTransformation):
            up = node_for(t.inputs[0])
            n = new_node("global_agg", t.name, window_transform=t,
                         key_field=t.key_field, keyed_input=keyed_in(t))
            nodes[up].downstream.append(n.id)
        elif isinstance(t, KeyedJoinTransformation):
            # the key is worked out per row from the side's own column
            # (the driver's split), so no keyBy precedes it
            up = node_for(t.inputs[0])
            n = new_node("keyed_join", t.name, window_transform=t,
                         keyed_input=True)
            nodes[up].downstream.append(n.id)
        elif isinstance(t, SessionAggregateTransformation):
            up = node_for(t.inputs[0])
            n = new_node("session", t.name, window_transform=t,
                         key_field=t.key_field, keyed_input=keyed_in(t))
            nodes[up].downstream.append(n.id)
        elif isinstance(t, WindowJoinTransformation):
            lup = node_for(t.inputs[0])
            rup = node_for(t.inputs[1])
            n = new_node("join", t.name, window_transform=t,
                         left_input=lup, right_input=rup)
            nodes[lup].downstream.append(n.id)
            nodes[rup].downstream.append(n.id)
        elif isinstance(t, BroadcastConnectTransformation):
            # left = data stream, right = control (broadcast) stream
            lup = node_for(t.inputs[0])
            rup = node_for(t.inputs[1])
            n = new_node("broadcast_connect", t.name, window_transform=t,
                         left_input=lup, right_input=rup)
            nodes[lup].downstream.append(n.id)
            nodes[rup].downstream.append(n.id)
        elif isinstance(t, SinkTransformation):
            up = node_for(t.inputs[0])
            n = new_node("sink", t.name, sink=t.sink)
            nodes[up].downstream.append(n.id)
        elif isinstance(t, UnionTransformation):
            n = new_node("union", t.name)
            for inp in t.inputs:
                up = node_for(inp)
                nodes[up].downstream.append(n.id)
        else:
            raise NotImplementedError(f"transformation {type(t).__name__}")
        t2node[t.id] = n.id
        return n.id

    for t in transforms:
        node_for(t)

    sources = [n.id for n in nodes.values() if n.kind == "source"]
    if not sources:
        raise ValueError("job has no sources")
    sinks = [n for n in nodes.values() if n.kind == "sink"]
    if not sinks:
        raise ValueError("job has no sinks (add_sink/print/collect)")

    topo = _topo_order(nodes, sources)

    # record each stateful op's declared output schema (the analyzer's
    # dataflow plane seeds field-reference checks downstream of the op
    # from this, the way keyed_input records the folded keyBy exchange)
    for n in nodes.values():
        n.out_schema = _op_out_schema(n)

    from flink_tpu.config import ExecutionOptions

    mode = str(config.get(ExecutionOptions.RUNTIME_MODE)).strip().lower()
    if mode not in ("streaming", "batch"):
        raise ValueError(
            f"execution.runtime-mode must be 'streaming' or 'batch', "
            f"got {mode!r}")
    stage_of: Dict[int, int] = {}
    blocking: List[Tuple[int, int]] = []
    if mode == "batch":
        from flink_tpu.api.sources import source_is_bounded

        unbounded = [nodes[sid].name or str(sid) for sid in sources
                     if not source_is_bounded(nodes[sid].source)]
        if unbounded and strict:
            raise ValueError(
                "execution.runtime-mode=batch requires every source to "
                f"be bounded; unbounded source(s): {unbounded} (run "
                "them in streaming mode, or bound the generator)")
        stage_of, blocking = assign_stages(nodes, topo)
    return ExecutionPlan(nodes=nodes, sources=sources, topo_order=topo,
                         watermark_strategy=default_wm, runtime_mode=mode,
                         stage_of=stage_of, blocking_edges=blocking)


def _topo_order(nodes: Dict[int, ExecNode], sources: List[int]) -> List[int]:
    indeg: Dict[int, int] = {nid: 0 for nid in nodes}
    for n in nodes.values():
        for d in n.downstream:
            indeg[d] += 1
    order: List[int] = []
    ready = [nid for nid, d in indeg.items() if d == 0]
    while ready:
        nid = ready.pop()
        order.append(nid)
        for d in nodes[nid].downstream:
            indeg[d] -= 1
            if indeg[d] == 0:
                ready.append(d)
    if len(order) != len(nodes):
        raise ValueError("cycle in transformation graph")
    return order
