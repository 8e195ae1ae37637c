"""Operator factory SPI — the pluggable seam between compiled plan
nodes and runtime operator implementations.

ref: streaming/api/operators/{StreamOperatorFactory,
OneInputStreamOperatorFactory,SimpleOperatorFactory}.java — the
north-star SPI (SURVEY §2): upstream swaps the hot-path implementation
(e.g. a different window operator) by registering a factory, without
touching the user API or the graph compiler. Here the registry maps a
plan-node KIND to a factory; the Driver consults it FIRST, so a
registered factory overrides the built-in construction for that kind —
swap the device kernels behind ``.window().aggregate()`` and every
pipeline picks it up unchanged.

A factory receives the ``ExecNode`` and an ``OperatorBuildContext``
(config-derived knobs + mesh plan) and returns the operator instance.
The built-in window operator registers here too, so the seam is the
REAL construction path, not a bypass for third parties only.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

__all__ = ["OperatorBuildContext", "register_operator_factory",
           "lookup_operator_factory", "unregister_operator_factory"]


@dataclasses.dataclass(frozen=True)
class OperatorBuildContext:
    """Everything a factory may need, pre-resolved from Configuration
    (factories must not re-read raw config — one resolution point)."""

    config: Any
    mesh_plan: Optional[Any]
    num_shards: int
    slots_per_shard: int
    max_inflight_steps: int
    exchange_capacity: Optional[int]
    backend: str
    exchange_impl: str
    max_out_of_orderness_ms: int
    # cross-host jobs: this process's contiguous key-shard span (the
    # key-group range of its "subtask"); None = whole shard space
    shard_range: Optional[Any] = None
    # the driver's shared host worker pool (parallel/hostpool.py) for
    # host-resident operator paths; None = serial
    host_pool: Optional[Any] = None
    # host.fold-chunk-records, the spill store's tree-fold batch floor;
    # None = the declared config default
    fold_chunk_records: Optional[int] = None
    # state.backend='lsm' (disk spill tier, state/lsm.py): memtable
    # budget, run-file root, and the compaction trigger
    memory_budget_bytes: int = 64 * 1024 * 1024
    lsm_dir: str = "/tmp/flink-tpu-state"
    lsm_compact_min_runs: int = 4


OperatorFactory = Callable[[Any, OperatorBuildContext], Any]

_FACTORIES: Dict[str, OperatorFactory] = {}


def register_operator_factory(kind: str, factory: OperatorFactory) -> None:
    _FACTORIES[kind] = factory


def unregister_operator_factory(kind: str) -> None:
    _FACTORIES.pop(kind, None)


def lookup_operator_factory(kind: str) -> Optional[OperatorFactory]:
    return _FACTORIES.get(kind)


# -- built-in factories (the default hot path registers through its own
# seam; ref: SimpleOperatorFactory wrapping the built-in operators) ----

def _window_factory(node, ctx: OperatorBuildContext):
    from flink_tpu.ops.window import WindowOperator

    t = node.window_transform
    spill_store = None
    if ctx.backend == "lsm":
        import os
        import uuid

        from flink_tpu.state.lsm import LsmSpillStore

        # unique per operator INSTANCE: run files are owned by one
        # store for its lifetime (checkpoints hardlink them out; a
        # restore links them back into the successor's fresh dir)
        store_dir = os.path.join(
            ctx.lsm_dir,
            f"op{node.id}-{os.getpid()}-{uuid.uuid4().hex[:8]}")
        spill_store = LsmSpillStore(
            t.aggregate, store_dir=store_dir,
            memory_budget_bytes=ctx.memory_budget_bytes,
            num_shards=ctx.num_shards,
            compact_min_runs=ctx.lsm_compact_min_runs,
            pool=ctx.host_pool,
            fold_chunk_records=ctx.fold_chunk_records)
    op = WindowOperator(
        t.assigner, t.aggregate,
        num_shards=ctx.num_shards,
        slots_per_shard=ctx.slots_per_shard,
        allowed_lateness_ms=t.allowed_lateness_ms,
        max_out_of_orderness_ms=max(ctx.max_out_of_orderness_ms, 0),
        mesh_plan=ctx.mesh_plan,
        shard_range=ctx.shard_range,
        top_n=t.top_n,
        exchange_capacity=ctx.exchange_capacity,
        spill=(ctx.backend == "spill"),
        spill_store=spill_store,
        exchange_impl=ctx.exchange_impl,
        host_pool=ctx.host_pool,
        fold_chunk_records=ctx.fold_chunk_records,
    )
    op.max_inflight_steps = ctx.max_inflight_steps
    # backpressure blocks happen OUTSIDE the push lock (the ingest loop
    # calls throttle() after releasing it), so drain deliveries never
    # queue behind a transfer wait
    op.external_throttle = True
    return op


register_operator_factory("window", _window_factory)


def _keyed_join_factory(node, ctx: OperatorBuildContext):
    """The unbounded keyed join: the ONE place its lane is chosen, by
    what the job is: both sides' state on the device where one device
    holds it, the host operator of the same semantics under a mesh or
    past the slots an int32 cell key holds (``join.on_host`` 1)."""
    from flink_tpu.ops.join_device import (
        DeviceKeyedJoinOperator, device_lane_fits)
    from flink_tpu.ops.join_host import HostKeyedJoinOperator

    t = node.window_transform
    fields = {"until_field": t.until_field, "carry_field": t.carry_field,
              "value_field": t.value_field, "result_field": t.result_field}
    if device_lane_fits(mesh=ctx.mesh_plan is not None,
                        slots=ctx.num_shards * ctx.slots_per_shard):
        op = DeviceKeyedJoinOperator(
            num_shards=ctx.num_shards, slots_per_shard=ctx.slots_per_shard,
            max_inflight_steps=ctx.max_inflight_steps, **fields)
        # as the window factory: the loop throttles outside its push lock
        op.external_throttle = True
        return op
    return HostKeyedJoinOperator(
        num_shards=ctx.num_shards, slots_per_shard=ctx.slots_per_shard,
        **fields)


register_operator_factory("keyed_join", _keyed_join_factory)
