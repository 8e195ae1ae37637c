"""Non-keyed (global) windowed aggregation — the windowAll shape.

The reference lowers ``windowAll`` to a parallelism-1 WindowOperator:
every record funnels to ONE subtask (ref: streaming/api/datastream/
AllWindowedStream.java; DataStream.windowAll forces parallelism 1).
Round 2 mirrored that with a constant key — a single-shard hotspot on
any mesh (the exact skew the exchange exists to avoid).

TPU-first redesign: a global lane aggregate per pane is a few floats of
state, and folding a record into it is one segment-reduce — the work is
BANDWIDTH, not FLOPs: shipping every record over the host↔device link
to compute a running max moves more bytes than the host reads to do
the whole reduction itself. Whether the link of the current chip is
fast enough to change that: not measured. So the fold runs HOST-SIDE,
vectorized, per pane
(reusing the spill store's (key, pane) machinery with a constant key),
and nothing ever crosses the link. On a mesh this also deletes the
hotspot outright: there is no keyed exchange, and in a multi-host
deployment each runner pre-reduces its own arrivals — the cross-runner
combine is panes x width floats, the "per-device partial + tiny global
reduce" shape.

Fire/lateness/refire semantics mirror WindowOperator's (same WindowPlan
pane math, same fireable-ends enumeration, same late-within-lateness
re-fire rule).
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional

import numpy as np

from flink_tpu.api.windowing import WindowAssigner
from flink_tpu.ops.aggregates import LaneAggregate
from flink_tpu.ops.host_control import HostPaneControl
from flink_tpu.ops.window import FiredWindows, WindowPlan, _empty_fired
from flink_tpu.state.spill import HostSpillStore


class WindowAllOperator:
    """Global tumbling/sliding window over ALL records (no key)."""

    def __init__(
        self,
        assigner: WindowAssigner,
        agg: LaneAggregate,
        *,
        allowed_lateness_ms: int = 0,
        max_out_of_orderness_ms: int = 0,
        host_pool: Optional[Any] = None,
        fold_chunk_records: Optional[int] = None,
    ) -> None:
        self.agg = agg
        self.plan = WindowPlan.plan(
            assigner,
            allowed_lateness_ms=allowed_lateness_ms,
            max_out_of_orderness_ms=max_out_of_orderness_ms)
        # the global fold is ONE logical key, so key-sharding cannot
        # apply; scaling is the store's chunked tree fold over batch
        # slices + per-window parallel fires, gated on
        # the fold_chunk_records batch floor
        self.store = HostSpillStore(agg, pool=host_pool,
                                    fold_chunk_records=fold_chunk_records)
        self.ctl = HostPaneControl(self.plan)
        self.state_version = 0
        self._empty_cache: Optional[Dict[str, np.ndarray]] = None

    @property
    def watermark(self) -> int:
        return self.ctl.watermark

    @property
    def late_records(self) -> int:
        return self.ctl.late_records

    # -- data plane ------------------------------------------------------

    def process_batch(
        self,
        ts: np.ndarray,
        data: Dict[str, np.ndarray],
        valid: Optional[np.ndarray] = None,
    ) -> None:
        self.state_version += 1
        ts = np.asarray(ts, dtype=np.int64)
        b = len(ts)
        valid = np.ones(b, bool) if valid is None else np.asarray(valid, bool)
        panes, valid = self.ctl.absorb_panes(ts, valid)
        if not valid.any():
            return

        sub = {k: np.asarray(data[k])[valid] for k in
               (self.agg.fields if self.agg.fields is not None else data)}
        self.store.absorb(np.zeros(int(valid.sum()), np.int64),
                          panes[valid], sub)

    # -- time plane ------------------------------------------------------

    def advance_watermark(self, wm: int) -> FiredWindows:
        ends = self.ctl.begin_advance(wm)
        if ends is None:
            return self._empty()
        self.state_version += 1
        rows = self.store.fire(ends, self.plan.panes_per_window,
                               self.plan.pane_ms, self.plan.offset_ms,
                               self.plan.size_ms)
        new_dead = self.ctl.purge_horizon(wm)
        if new_dead is not None:
            self.store.purge_below(new_dead)
        if rows is None:
            return self._empty()
        rows.pop("key")  # global window: no key column in the output
        return FiredWindows(data=rows)

    def final_watermark(self) -> int:
        return self.ctl.final_watermark()

    def quiesce(self) -> None:
        pass

    def throttle(self) -> None:
        pass

    def _empty(self) -> FiredWindows:
        if self._empty_cache is None:
            cache = _empty_fired(self.agg)
            cache.pop("key", None)
            self._empty_cache = cache
        return FiredWindows(data=dict(self._empty_cache))

    # -- snapshot seam ----------------------------------------------------

    def snapshot_state(self) -> Dict[str, Any]:
        return {
            "kind": "window_all",
            "store": self.store.snapshot(),
            **self.ctl.snapshot(),
        }

    def restore_state(self, snap: Dict[str, Any]) -> None:
        self.store.restore(snap["store"])
        self.ctl.restore(snap)
