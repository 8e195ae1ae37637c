"""The per-host driver loop — the StreamTask/mailbox analogue.

ref: streaming/runtime/tasks/{StreamTask,OneInputStreamTask}.java and
tasks/mailbox/MailboxProcessor.runMailboxLoop — the reference's
single-threaded event loop where the default action processes input and
control actions (checkpoints, timers) interleave as mails.

TPU-first redesign: the loop's unit is a **microbatch**, not a record.
One iteration = pull a batch from a source, run the fused host ingest
chain, fold it into the stateful ops' device state, advance the
watermark clock, and hand fired windows to downstream nodes/sinks.
Control actions (checkpoint snapshots) happen between iterations — a
step boundary is a global barrier (SURVEY §6.4), which is what makes
exactly-once cheap here.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import threading
import time
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

import numpy as np

from flink_tpu.config import (
    CheckpointingOptions,
    ClusterOptions,
    Configuration,
    PipelineOptions,
    StateOptions,
)
from flink_tpu.graph.compiler import (
    STAGE_HEAD_KINDS,
    ExecNode,
    ExecutionPlan,
)
from flink_tpu.obs.tracing import PhaseClock
from flink_tpu.records import MINIBATCH_FIELD
from flink_tpu.time.watermarks import LONG_MIN, WatermarkTracker, make_generator

# fire cohorts kept for JobResult "trace.fires" (and its records: the
# newest this many)
FIRE_RECORDS = 4096
# a record's stamps, in the order a fired row meets them
FIRE_STAMPS = ("t_input", "t_fire", "t_queued", "t_fetch0", "t_ready",
               "t_fetch1", "t_push0", "t_sink")

# the six phases of phase_breakdown(), each the sum of these leaves of
# the run's PhaseClock. "ingest.bookkeeping" and "drain.deliver" are in
# none: the six never covered them.
PHASE_LEAVES = {
    "source": ("ingest.source_wait",),
    "dispatch": ("ingest.link_wait", "ingest.route", "window.key_scan",
                 "window.exchange_split", "window.pack", "window.h2d",
                 "window.step_dispatch"),
    "throttle": ("ingest.throttle",),
    "drain": ("drain.fetch",),
    "advance": ("wm.advance", "state.release", "state.reclaim"),
    "fire": ("window.fire_dispatch",),
}

# a checkpoint's freeze on the loop's thread: the trigger and what no
# other leaf names (ingest.checkpoint), the barrier on the drain
# (_flush_emits), the sinks' staging, the snapshot tree, and inside it
# the window operator's device clone (its dispatch) and directory copy.
# Their sum over the loop's wall is ``checkpoint.loop_share``. The
# persist runs beside the loop on the checkpoint executor's thread:
# persist.fetch, persist.encode, persist.write (checkpoint/coordinator.py)
CHECKPOINT_FREEZE_LEAVES = (
    "ingest.checkpoint", "ingest.checkpoint_flush",
    "ingest.checkpoint_stage", "ingest.checkpoint_snapshot",
    "state.snapshot_clone", "state.snapshot_directory")
CHECKPOINT_PERSIST_LEAVES = ("persist.fetch", "persist.encode",
                             "persist.write")
CHECKPOINT_COUNTERS = (
    "checkpoint.triggered", "checkpoint.completed", "checkpoint.failed",
    "checkpoint.aborted", "checkpoint.bytes_last", "checkpoint.bytes_total")

Batch = Tuple[Dict[str, np.ndarray], np.ndarray, np.ndarray]  # data, ts, valid


def _fired_or_purged(fired) -> bool:
    """Whether an advance's fired batch says the advance did something:
    it may carry rows (``FiredWindows.rowless`` says it cannot; a host
    operator's plain columns hold one), or the purge horizon moved."""
    rowless = getattr(fired, "rowless", None)
    if rowless is None:
        rowless = not any(len(col) for col in fired.values())
    return not rowless or getattr(fired, "purged", False)


class _PushLock:
    """The delivery lock: a plain lock that knows which thread holds it,
    so that a thread already delivering under it (the drain in its poll,
    a stateful consumer's rows leaving in line inside that poll) does
    not take it a second time (``_LoopHold``)."""

    __slots__ = ("_lock", "_owner")

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._owner: Optional[int] = None

    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        got = self._lock.acquire(blocking, timeout)
        if got:
            self._owner = threading.get_ident()
        return got

    def release(self) -> None:
        self._owner = None
        self._lock.release()

    def locked(self) -> bool:
        return self._lock.locked()

    def held_by_caller(self) -> bool:
        return self._owner == threading.get_ident()


class _LoopHold:
    """``with`` the delivery lock on a thread that is not inside the
    drain's poll (the ingest loop's), adding up what it waited for it
    (``waited_s``: two clock reads around an acquire that did not
    succeed at once, none where it did) and how often it took it
    (``takes``). A thread that holds the lock already goes on."""

    __slots__ = ("_lock", "_nested", "waited_s", "takes")

    def __init__(self, lock: _PushLock) -> None:
        self._lock = lock
        self._nested = 0    # written by the lock's holder alone
        self.waited_s = 0.0
        self.takes = 0

    def __enter__(self) -> None:
        if self._lock.held_by_caller():
            self._nested += 1
            return
        if not self._lock.acquire(blocking=False):
            t0 = time.perf_counter()
            self._lock.acquire()
            self.waited_s += time.perf_counter() - t0
        self.takes += 1

    def __exit__(self, exc_type, exc, tb) -> None:
        if self._nested:
            self._nested -= 1
        else:
            self._lock.release()


class JobCancelledError(RuntimeError):
    """Raised inside the run loop when the job's cancel flag is set —
    the cooperative cancellation point (ref: Task.cancelExecution /
    StreamTask cancellation). The run() cleanup path treats it like any
    abort: drain discarded, sinks' uncommitted output dropped."""


class Driver:
    """Single-process execution of a lowered plan (the LocalExecutor /
    MiniCluster path; multi-host runs the same loop per host runner under
    the coordinator, ref: runtime/minicluster/MiniCluster.java)."""

    def __init__(self, plan: ExecutionPlan, config: Configuration,
                 mesh_plan: Optional[Any] = None):
        self.plan = plan
        self.config = config
        self.mesh_plan = mesh_plan
        # submit-time plan analysis results (execute() refreshes this;
        # an empty list before/without analysis keeps the surface total)
        self.analysis_findings: List[Any] = []
        self._upstream: Dict[int, List[int]] = {nid: [] for nid in plan.nodes}
        for n in plan.nodes.values():
            for d in n.downstream:
                self._upstream[d].append(n.id)
        self._ops: Dict[int, Any] = {}
        self._partitioners: Dict[int, Any] = {}
        self._out_wm: Dict[int, int] = {nid: LONG_MIN for nid in plan.nodes}
        self._wm_gens: Dict[int, Any] = {}
        self._max_ts: Dict[int, int] = {}
        self.metrics: Dict[str, int] = {
            "records_in": 0, "records_out": 0, "batches": 0, "fired_windows": 0,
            # watermark passes in which an operator fired or purged, and
            # those of them that went ahead of the batch that implied
            # their watermark (_lead_advance)
            "wm.advances": 0, "wm.advances_led": 0,
        }
        from flink_tpu.obs.metrics import MetricRegistry

        # ref: TaskIOMetricGroup numRecordsIn/Out + latency markers (§6.1)
        self.registry = MetricRegistry()
        g = self.registry.group("driver")
        g.gauge("records_in", lambda: self.metrics["records_in"])
        g.gauge("records_out", lambda: self.metrics["records_out"])
        g.gauge("fired_windows", lambda: self.metrics["fired_windows"])
        # loss counters — directory-full drops and exchange overflow must
        # be observable live, not just at job end
        g.gauge("records_dropped_full", lambda: sum(
            getattr(op, "records_dropped_full", 0)
            for op in self._ops.values()))
        g.gauge("exchange_overflow", lambda: sum(
            getattr(op, "exchange_overflow", 0)
            for op in self._ops.values()))
        self._eps_meter = g.meter("records_per_sec")
        # FIRE→SINK latency, not ingest→sink: the clock starts when the
        # watermark advance DISPATCHES a fired window (see
        # _emit_fired_sync) and stops at sink delivery — the
        # latency-marker analogue (LatencyMarker.java). Time a record
        # spends queued before its step dispatches is NOT included;
        # artifacts quoting this metric must say "fire→sink", never
        # "end-to-end".
        self._lat_hist = g.histogram("emit_latency_ms")
        self._wm_lag = g.gauge("watermark_lag_ms")
        # adaptive microbatch debloater (ref: BufferDebloater): when a
        # latency target is set, ingest re-chunks source batches; the
        # chunk halves while recent emit p99 overshoots the target and
        # regrows while it sits under half of it
        self._debloat_target = float(
            config.get(PipelineOptions.TARGET_LATENCY))
        self._debloat_chunk: Optional[int] = None
        self._debloat_min = 4096
        self._debloat_seen = 0  # histogram count at last control step
        g.gauge("debloat_chunk",
                lambda: float(self._debloat_chunk or 0))
        # where the ingest loop and the drain thread spend their time
        # (obs/tracing.py PhaseClock; a fresh one per run, shared with
        # the operators) — JobResult's profile.phase.*, and host spans
        # of any profiler trace
        self.phases = PhaseClock()
        # per-window fire records (JobResult "trace.fires"): one cohort
        # per fire dispatch, stamped on time.perf_counter() as it moves
        # input -> fire -> fetch -> sink. _t_input: when the source
        # handed over its latest batch (or said it had no more)
        self._fires: collections.deque = collections.deque(
            maxlen=FIRE_RECORDS)
        self._t_input = 0.0
        self._t_loop = 0.0       # when the ingest loop began, and how
        self._loop_wall_s = 0.0  # long it ran: its leaves sum to this
        self._emit_q = None
        self._profiler = None  # armed per run (pipeline.profile-dir)
        self._drain_error: Optional[BaseException] = None
        # per-run discard cell: set on abort so the run's drain thread
        # drops (never delivers) everything it still holds. One CELL per
        # run — an abandoned (wedged, timed-out) drain keeps its own
        # permanently-set cell, so it can never deliver into, nor be
        # re-armed by, a later run on the same Driver.
        self._drain_discard = [False]
        self._stateless_cache: Dict[int, bool] = {}
        self._drain_ok_cache: Dict[int, bool] = {}
        # the drain thread of the run (``_drain_entry``): a stateful
        # operator it folds rows into hands ITS rows on in line
        self._drain_ident: Optional[int] = None
        # batch (bounded) mode: open blocking-edge writers, keyed by
        # (from_node, to_node); _push diverts matching edges into the
        # shuffle spool instead of the consumer. Always a dict (empty
        # on the streaming path) so the hot-path check is one truth test.
        self._batch_capture: Dict[Tuple[int, int], Any] = {}
        import threading

        # set while a barrier (checkpoint / end-of-input) or a stop is
        # waiting on the emit queue: ends the drain's wait for a landing
        # (and an explicit deferral) at once
        self._flush_req = threading.Event()
        # set where the drain has something to hurry for: a marker that
        # may carry rows was queued, a barrier, a stop. A batch of
        # markers without rows hurries no one, and the drain holds it
        # asleep on this, for as long as a ring lets pass between two
        # announces without rows (see _drain_loop)
        self._drain_wake = threading.Event()
        from flink_tpu.ops.emit_ring import ANNOUNCE_INTERVAL_S

        self._rowless_hold_s = ANNOUNCE_INTERVAL_S
        # Link-quiet handshake: a device→host fetch can starve behind
        # continuous host→device ingest traffic and dispatches (whether
        # it does on the current chip: not measured). The drain holds
        # this lock during its
        # fetch; the ingest loop acquires it once per batch boundary —
        # so a pending fetch gets a quiet link within one batch, and
        # ingest resumes the moment the fetch lands. The drain's wait
        # for the device comes BEFORE it (FiredWindows.await_landing):
        # what it holds the lock for is a local read.
        self._link_lock = threading.Lock()
        # an explicit pipeline.emit-defer is an age floor ahead of the
        # drain's wait for the landing; auto (-1) is none, whatever the
        # backend: the wait is as long as the rows' copy takes
        self._emit_defer_s = max(
            0, self.config.get(PipelineOptions.EMIT_DEFER_MS)) / 1000.0

        # the delivery lock: one writer at a time in what BOTH threads
        # can reach, and nowhere else. The drain holds it for a poll's
        # whole delivery (chains, partitions, sinks, a host GROUP BY
        # that only its delivery feeds: _drain_may_deliver); the loop's
        # thread takes it only on entering a node such a delivery can
        # enter (_drain_reach, in _push) and where it delivers fired
        # rows in line (_emit_fired_sync), never through an operator's
        # process_batch or advance_watermark: those are the loop's
        # alone, and what an operator shares with the drain (the ring,
        # the decode, the reuse rule's marks) it guards itself. Under it
        # on both threads: every sink.write, records_out, fired_windows,
        # the emit-latency histogram
        self._push_lock = _PushLock()
        # the loop thread's way to it: what the loop loses to the
        # drain's delivery is profile.phase.push_wait_s (the drain's own
        # wait is its detail drain/push_wait), how often it took the
        # lock at all push.loop_lock_takes
        self._loop_push = _LoopHold(self._push_lock)
        # fair drain scheduling (session-cluster mode): co-resident
        # jobs' drain fetches take round-robin turns on the process-
        # global gate so one tenant's fire burst cannot starve a
        # peer's emit ring on the shared device→host link. Off (None)
        # outside session deploys — the single-job path is untouched.
        from flink_tpu.config import SessionOptions as _SO

        self._drain_gate = None
        self._gate_token = f"drv-{id(self)}"
        if bool(self.config.get(_SO.FAIR_DRAIN)):
            from flink_tpu.runtime.session import drain_gate

            self._drain_gate = drain_gate()
        self._build_ops()
        self._lead_ops = self._find_lead_ops()
        self._drain_reach = self._find_drain_reach()
        # plan-time HBM budgeting: dense static layouts make the device
        # footprint computable BEFORE the first step — fail at build
        # with a breakdown, not mid-run in the XLA allocator (ref:
        # MemoryManager managed-memory budgets; memory.hbm-budget)
        from flink_tpu.config import MemoryOptions
        from flink_tpu.memory import MemoryBudget

        self.memory = MemoryBudget(int(config.get(MemoryOptions.HBM_BUDGET)))
        for nid, op in self._ops.items():
            if hasattr(op, "hbm_bytes"):
                n = self.plan.node(nid)
                self.memory.register(
                    f"{n.kind}:{n.name or nid}", op.hbm_bytes(),
                    detail=f"layout={getattr(op, 'layout', None)}")
        self.memory.check()
        g2 = self.registry.group("memory")
        g2.gauge("hbm_state_bytes", lambda: float(self.memory.hbm_total))
        g2.gauge("host_spill_bytes", lambda: float(sum(
            getattr(getattr(op, "_spill", None), "bytes_used", lambda: 0)()
            for op in self._ops.values()
            if getattr(op, "_spill", None) is not None)))

    # -- construction ----------------------------------------------------
    def _build_ops(self) -> None:
        num_shards = self.config.get(StateOptions.NUM_KEY_SHARDS)
        slots = self.config.get(StateOptions.SLOTS_PER_SHARD)
        inflight = int(self.config.get(PipelineOptions.MAX_INFLIGHT_STEPS))
        # session resource shares (runtime/session.py): the dispatcher
        # stamps session.concurrent-jobs = K (the STATIC slot-
        # proportional denominator: jobs of this quota that fit one
        # runner) into the deploy config; this job's in-flight step
        # credit and host-pool worker count each take a 1/K share so
        # co-resident jobs cannot oversubscribe the transport queue or
        # the host cores, regardless of deploy order — the host-pool /
        # in-flight legs of the admission quota. K = 1 (every
        # non-session run) changes nothing.
        from flink_tpu.config import SessionOptions

        self._session_share = max(
            1, int(self.config.get(SessionOptions.CONCURRENT_JOBS)))
        if self._session_share > 1:
            inflight = max(1, inflight // self._session_share)
        xcap = self.config.get(PipelineOptions.EXCHANGE_CAPACITY)
        if xcap < 0:
            raise ValueError(
                f"pipeline.exchange-capacity must be >= 0 (0 = auto), "
                f"got {xcap}")
        xcap = xcap or None
        backend = self.config.get(StateOptions.BACKEND)
        if backend not in ("hbm", "spill", "lsm"):
            raise ValueError(
                f"state.backend must be 'hbm', 'spill' or 'lsm', "
                f"got {backend!r}")
        # pane-ring sizing must cover the worst watermark lag of ANY
        # source feeding the job (per-source strategies override the
        # plan default)
        ooos = [self.plan.watermark_strategy.max_out_of_orderness_ms]
        for n in self.plan.nodes.values():
            if n.kind == "source" and n.watermark_strategy is not None:
                ooos.append(n.watermark_strategy.max_out_of_orderness_ms)
        wm = dataclasses.replace(self.plan.watermark_strategy,
                                 max_out_of_orderness_ms=max(ooos))
        # operator factory SPI (ref: OneInputStreamOperatorFactory): a
        # registered factory for a kind owns its construction — the
        # built-in window operator goes through its own registered
        # factory, third parties override by registering theirs
        from flink_tpu.ops.factory import (
            OperatorBuildContext,
            lookup_operator_factory,
        )

        # cross-host jobs: each process owns a contiguous shard span
        # (records arrive pre-routed through the DCN exchange)
        shard_range = None
        nproc = int(self.config.get(ClusterOptions.NUM_PROCESSES))
        if nproc > 1:
            pid = int(self.config.get(ClusterOptions.PROCESS_ID))
            spp = num_shards // nproc
            shard_range = (pid * spp, (pid + 1) * spp)
        # ONE shared host worker pool per driver (flink_tpu/
        # parallel/hostpool.py): sized by host.parallelism, handed to
        # every operator with host-resident parallel work; parallelism 1
        # creates no threads and keeps the exact serial paths
        from flink_tpu.config import HostOptions
        from flink_tpu.parallel.hostpool import HostPool

        host_w = int(self.config.get(HostOptions.PARALLELISM))
        if self._session_share > 1:
            # the host-pool share of the session quota: K co-resident
            # jobs split the configured worker count instead of each
            # claiming all of it
            host_w = max(1, host_w // self._session_share)
        self.host_pool = HostPool(host_w, registry=self.registry)
        fold_chunk = int(self.config.get(HostOptions.FOLD_CHUNK_RECORDS))
        if fold_chunk < 1:
            raise ValueError(
                f"host.fold-chunk-records must be >= 1, got {fold_chunk}")
        ctx = OperatorBuildContext(
            config=self.config, mesh_plan=self.mesh_plan,
            num_shards=num_shards, slots_per_shard=slots,
            max_inflight_steps=inflight, exchange_capacity=xcap,
            backend=backend,
            exchange_impl=self.config.get(ClusterOptions.EXCHANGE_IMPL),
            max_out_of_orderness_ms=wm.max_out_of_orderness_ms,
            shard_range=shard_range,
            host_pool=self.host_pool,
            fold_chunk_records=fold_chunk,
            memory_budget_bytes=int(
                self.config.get(StateOptions.MEMORY_BUDGET_BYTES)),
            lsm_dir=str(self.config.get(StateOptions.LSM_DIR)),
            lsm_compact_min_runs=int(
                self.config.get(StateOptions.LSM_COMPACT_MIN_RUNS)),
        )
        allow_drops = bool(self.config.get(StateOptions.ALLOW_DROPS))
        for n in self.plan.nodes.values():
            factory = lookup_operator_factory(n.kind)
            if factory is not None:
                self._ops[n.id] = factory(n, ctx)
            elif n.kind == "async_io":
                from flink_tpu.ops.async_io import AsyncIOOperator

                t = n.window_transform
                fn = t.fn
                call = (fn.invoke_batch
                        if hasattr(fn, "invoke_batch") else fn)
                self._ops[n.id] = AsyncIOOperator(
                    call, capacity=t.capacity, timeout_ms=t.timeout_ms,
                    ordered=t.ordered)
            elif n.kind == "cep":
                from flink_tpu.cep import CepOperator

                t = n.window_transform
                self._ops[n.id] = CepOperator(
                    t.pattern, num_shards=num_shards,
                    slots_per_shard=slots)
            elif n.kind == "process":
                from flink_tpu.ops.process import KeyedProcessOperator

                t = n.window_transform
                self._ops[n.id] = KeyedProcessOperator(
                    t.fn, num_shards=num_shards, slots_per_shard=slots)
            elif n.kind == "window_all":
                from flink_tpu.ops.window_all import WindowAllOperator

                t = n.window_transform
                self._ops[n.id] = WindowAllOperator(
                    t.assigner, t.aggregate,
                    allowed_lateness_ms=t.allowed_lateness_ms,
                    max_out_of_orderness_ms=max(wm.max_out_of_orderness_ms, 0),
                    host_pool=self.host_pool,
                    fold_chunk_records=fold_chunk)
            elif n.kind == "count_window":
                from flink_tpu.ops.count_window import CountWindowOperator

                if self.mesh_plan is not None:
                    raise NotImplementedError(
                        "count windows on a device mesh are not yet "
                        "supported; run without cluster.mesh-devices")
                t = n.window_transform
                self._ops[n.id] = CountWindowOperator(
                    t.aggregate, t.size, purge=t.purge,
                    num_shards=num_shards, slots_per_shard=slots)
            elif n.kind == "global_agg":
                from flink_tpu.ops.global_agg import GlobalAggregateOperator
                from flink_tpu.ops.groupagg_device import (
                    DeviceGroupAggOperator, device_lane_fits)

                t = n.window_transform
                retract = getattr(t, "retract", False)
                # the ONE place the lane of an unwindowed aggregation
                # is chosen, by what the job is: accumulators on the
                # device for a lane aggregate; the host operator for
                # retract rows (the -U row is the accumulators as last
                # emitted) and under a mesh (no accumulators sharded
                # over devices yet: the host folds them, where this
                # refused the job before)
                if device_lane_fits(
                        agg=t.aggregate, retract=retract,
                        mesh=self.mesh_plan is not None,
                        slots=num_shards * slots):
                    op = self._ops[n.id] = DeviceGroupAggOperator(
                        t.aggregate, num_shards=num_shards,
                        slots_per_shard=slots,
                        max_inflight_steps=inflight)
                    # as the window factory: the loop throttles outside
                    # its push lock
                    op.external_throttle = True
                else:
                    self._ops[n.id] = GlobalAggregateOperator(
                        t.aggregate, num_shards=num_shards,
                        slots_per_shard=slots, retract=retract)
            elif n.kind == "session":
                from flink_tpu.ops.session import SessionOperator
                from flink_tpu.ops.session_device import (
                    DeviceSessionOperator, device_lane_fits)

                t = n.window_transform
                delay = max(wm.max_out_of_orderness_ms, 0)
                retract = getattr(t, "retract", False)
                # the ONE place the session lane is chosen, by what the
                # job is: state on the device where it can hold it, the
                # host registry for retract rows, re-fires within
                # allowed lateness and a mesh. (What the DATA asks for
                # the device operator meets itself, batch by batch: more
                # lanes a slot, or its sessions handed to a registry of
                # its own for the rest of the job.)
                if device_lane_fits(
                        gap_ms=t.gap_ms, agg=t.aggregate,
                        allowed_lateness_ms=t.allowed_lateness_ms,
                        retract=retract, mesh=self.mesh_plan is not None,
                        max_out_of_orderness_ms=delay,
                        slots=num_shards * slots):
                    op = self._ops[n.id] = DeviceSessionOperator(
                        gap_ms=t.gap_ms, agg=t.aggregate,
                        num_shards=num_shards, slots_per_shard=slots,
                        max_out_of_orderness_ms=delay,
                        max_inflight_steps=inflight,
                        host_pool=self.host_pool)
                    # as the window factory: the loop throttles outside
                    # its push lock
                    op.external_throttle = True
                else:
                    self._ops[n.id] = SessionOperator(
                        gap_ms=t.gap_ms, agg=t.aggregate,
                        allowed_lateness_ms=t.allowed_lateness_ms,
                        num_shards=num_shards, slots_per_shard=slots,
                        max_out_of_orderness_ms=delay,
                        host_pool=self.host_pool, retract=retract)
            elif n.kind == "evicting_window":
                from flink_tpu.ops.evicting_window import (
                    EvictingWindowOperator)

                t = n.window_transform
                self._ops[n.id] = EvictingWindowOperator(
                    t.assigner, t.window_fn, trigger=t.trigger,
                    evictor=t.evictor,
                    allowed_lateness_ms=t.allowed_lateness_ms)
            elif n.kind == "broadcast_connect":
                from flink_tpu.ops.broadcast import BroadcastConnectOperator

                self._ops[n.id] = BroadcastConnectOperator(
                    n.window_transform.fn)
            elif n.kind == "join":
                from flink_tpu.ops.join import WindowJoinOperator

                t = n.window_transform
                self._ops[n.id] = WindowJoinOperator(
                    t.assigner,
                    left_fields=t.left_fields, right_fields=t.right_fields,
                    num_shards=num_shards, slots_per_shard=slots,
                    max_out_of_orderness_ms=max(wm.max_out_of_orderness_ms, 0),
                    mode=getattr(t, "mode", "pairs"),
                )
        # default-safe state policy: full-directory drops FAIL the job
        # unless explicitly allowed (see state.keyed.account_full_drop)
        for op in self._ops.values():
            op.allow_drops = allow_drops

    def _find_lead_ops(self) -> Tuple[Any, ...]:
        """The operators the plain ingest loop asks whether a batch's
        watermark pass may go AHEAD of the batch (``_lead_advance``):
        the stateful operators the one source's records reach, or none
        where the job's shape does not let the order be known to
        commute. It does for one source whose batches are handed on as
        they are (``partition`` and ``union`` do; a ``chain`` function
        may return other timestamps) to window or session operators that
        can answer (``lead_advance``), whose lane lets an advance lead at
        all (``may_lead_advance``: the fused lane, a mesh and processing
        time do not) and which feed no stateful operator in turn; the
        DCN plane runs a loop of its own. A job with none runs the loop
        in today's order to the letter."""
        if (len(self.plan.sources) != 1
                or self.plan.runtime_mode == "batch"
                or int(self.config.get(ClusterOptions.NUM_PROCESSES)) > 1):
            return ()
        heads, seen = [], set()
        stack = list(self.plan.node(self.plan.sources[0]).downstream)
        while stack:
            nid = stack.pop()
            if nid in seen:
                continue
            seen.add(nid)
            n = self.plan.node(nid)
            if n.kind in ("partition", "union"):
                stack.extend(n.downstream)
            elif (n.kind in ("window", "session")
                  and hasattr(self._ops[nid], "lead_advance")
                  and self._ops[nid].may_lead_advance()
                  and self._stateless_downstream(nid)):
                heads.append(self._ops[nid])
            else:
                return ()
        return tuple(heads)

    def _find_drain_reach(self) -> frozenset:
        """The nodes a delivery on the drain's thread can enter, read
        off the plan when the job is built: everything below an
        operator whose fired rows the drain may deliver
        (``_drain_may_deliver``), through a host GROUP BY that only
        such a delivery feeds and on below it. These, and nothing else,
        are what the loop's thread takes ``_push_lock`` for (``_push``):
        a sink or chain that a source path AND a fired path reach, a
        blocking edge into such a node. Where the drain delivers
        anything, every sink is of the set, since ``records_out`` is one
        counter all sinks share; a plan no operator of which the drain
        serves has an empty set, and its loop takes the lock nowhere."""
        reach: set = set()
        stack = [d for nid in self._ops if self._drain_may_deliver(nid)
                 for d in self.plan.node(nid).downstream]
        while stack:
            nid = stack.pop()
            if nid not in reach:
                reach.add(nid)
                stack.extend(self.plan.node(nid).downstream)
        if reach:
            reach.update(nid for nid, n in self.plan.nodes.items()
                         if n.kind == "sink")
        return frozenset(reach)

    # -- checkpointing ---------------------------------------------------
    def _setup_checkpointing(self, job_name: str):
        from flink_tpu.checkpoint.coordinator import CheckpointCoordinator
        from flink_tpu.checkpoint.storage import FsCheckpointStorage

        interval = self.config.get(CheckpointingOptions.INTERVAL)
        restore = self.config.get(CheckpointingOptions.RESTORE)
        if interval <= 0 and not restore:
            return None
        nproc = int(self.config.get(ClusterOptions.NUM_PROCESSES))
        if nproc > 1:
            # cross-host jobs: each process snapshots ITS shard span
            # under its own directory; the ids align because the
            # checkpoint decision rides the step rendezvous
            pid = int(self.config.get(ClusterOptions.PROCESS_ID))
            job_name = f"{job_name}-p{pid}"
        storage = FsCheckpointStorage(
            self.config.get(CheckpointingOptions.DIRECTORY),
            job_id=job_name.replace("/", "_"),
            retained=self.config.get(CheckpointingOptions.RETAINED),
            compression=self.config.get(CheckpointingOptions.COMPRESSION),
            # coordinator-deployed attempts fence storage writes on the
            # attempt epoch: a deposed attempt's in-flight persist must
            # not clobber its successor's checkpoints (see
            # FsCheckpointStorage._check_fence); 0 = local unfenced
            epoch=int(self.config.get_raw("cluster.attempt", 0)))
        coordinator = CheckpointCoordinator(storage)
        found = None if restore or nproc > 1 else storage.latest()
        if found is not None:
            # an earlier job of this name wrote here and this one does
            # not restore from it: its own checkpoints are numbered past
            # what it found, since retention keeps the newest ids and a
            # chk-1 beside an older job's chk-7 would be retired as it
            # landed. (A restore resumes the numbering itself; across
            # processes the ids must stay aligned, so each starts at 1.)
            coordinator.resume_numbering(
                {"checkpoint_id": found.checkpoint_id})
        return coordinator

    def _snapshot(self, allow_reuse: bool = True) -> Dict[str, Any]:
        from flink_tpu.checkpoint.storage import ReusedOpState

        # incremental reuse (RocksDB shared-SST analogue): an operator
        # whose state_version is unchanged since the base (last
        # completed) checkpoint hardlinks that checkpoint's blob instead
        # of re-serializing. Savepoints stay self-contained.
        base = (self._ckpt_base
                if allow_reuse
                and self.config.get(CheckpointingOptions.INCREMENTAL)
                else None)
        ops: Dict[Any, Any] = {}
        versions: Dict[str, int] = {}
        for nid, op in self._ops.items():
            v = getattr(op, "state_version", None)
            versions[str(nid)] = -1 if v is None else int(v)
            if (v is not None and base is not None
                    and base["versions"].get(nid) == v
                    and nid in base["files"]):
                ops[nid] = ReusedOpState(
                    base["files"][nid], int(v),
                    # changelog aux (lsm runs) re-links from the BASE
                    # checkpoint's own hardlinks, never the store's
                    # live files — reuse must survive store compaction
                    aux=(base.get("aux") or {}).get(nid))
            else:
                ops[nid] = op.snapshot_state()
        self._last_freeze_versions = {
            nid: getattr(op, "state_version", -1)
            for nid, op in self._ops.items()}
        return {
            "sources": {sid: dict(pos) for sid, pos in self._positions.items()},
            "wm_gens": {sid: [g.snapshot() for g in gens]
                        for sid, gens in self._wm_gens.items()},
            "max_ts": dict(self._max_ts),
            "out_wm": dict(self._out_wm),
            "operators": ops,
            "op_versions": versions,
            "partitioners": {nid: p.snapshot()
                             for nid, p in self._partitioners.items()},
            # staged-but-uncommitted 2PC sink epochs (prepare ran before
            # this snapshot, so the in-flight epoch is included) — the
            # TwoPhaseCommitSinkFunction pending-transaction-in-state rule
            "sinks": {
                nid: staged
                for nid, n in self.plan.nodes.items()
                if n.kind == "sink"
                and (staged := n.sink.snapshot_staged()) is not None
            },
            "metrics": dict(self.metrics),
            # key-group identity of the writing process: restore checks
            # it against the restoring process's shape and routes a
            # mismatch through checkpoint/repartition.py (the
            # StateAssignmentOperation role — see _load_repartitioned)
            "rescale": self._rescale_identity(),
        }

    def _rescale_identity(self) -> Dict[str, Any]:
        nproc = int(self.config.get(ClusterOptions.NUM_PROCESSES))
        pid = (int(self.config.get(ClusterOptions.PROCESS_ID))
               if nproc > 1 else 0)
        num_shards = int(self.config.get(StateOptions.NUM_KEY_SHARDS))
        spp = num_shards // max(nproc, 1)
        return {"nproc": nproc, "pid": pid, "num_shards": num_shards,
                "shard_range": [pid * spp, (pid + 1) * spp]}

    def _restore(self, payload: Dict[str, Any]) -> None:
        self._positions = {sid: dict(pos)
                           for sid, pos in payload["sources"].items()}
        # source positions count LOGICAL batches. A checkpoint that
        # records a sub-batch factor other than 1 was written by a
        # device-chained source (removed), whose positions counted
        # SUB-batches: reading them as logical positions would skip
        # records silently
        chained = {k: int(v)
                   for k, v in payload.get("sub_factors", {}).items()
                   if int(v) != 1}
        if chained:
            raise ValueError(
                f"checkpoint field 'sub_factors' records a sub-batch "
                f"factor other than 1 for source(s) {chained}: its "
                "source positions count sub-batches of a device-chained "
                "generator source, which this version no longer has — "
                "it cannot be restored as logical batch positions")
        # time-state keys may be absent: a state-processor savepoint
        # with reset_watermarks() restarts event time from scratch
        for sid, states in payload.get("wm_gens", {}).items():
            for g, s in zip(self._wm_gens[sid], states):
                g.restore(s)
        self._max_ts.update(payload.get("max_ts", {}))
        self._out_wm.update(payload.get("out_wm", {}))
        for nid, snap in payload["operators"].items():
            self._ops[nid].restore_state(snap)
        from flink_tpu.exchange.partitioners import make_partitioner

        for nid, psnap in payload.get("partitioners", {}).items():
            n = self.plan.node(nid)
            p = make_partitioner(n.partition_strategy, seed=nid)
            p.restore(psnap)
            self._partitioners[nid] = p
        # v2 incremental restore: adopt the checkpoint's per-op state
        # versions and make it the reuse base — an operator untouched
        # after restore hardlinks its blob at the very next checkpoint
        file_versions = payload.get("op_file_versions")
        # blob reuse keeps the ORIGINAL bytes; if the restored
        # checkpoint was written with a different compression than this
        # run's, hardlinking its blobs under the new manifest would make
        # later checkpoints undecodable — skip seeding the base
        if (file_versions and payload.get("op_file_compression", "none")
                != self.config.get(CheckpointingOptions.COMPRESSION)):
            file_versions = None
        if file_versions:
            for nid, v in file_versions.items():
                if nid in self._ops and hasattr(
                        self._ops[nid], "state_version"):
                    self._ops[nid].state_version = v
            self._ckpt_base = {
                "files": dict(payload.get("op_files", {})),
                "versions": dict(file_versions),
                "aux": {nid: dict(m) for nid, m in
                        (payload.get("op_aux_paths") or {}).items()},
            }
        self.metrics.update(payload["metrics"])
        staged_sinks = payload.get("sinks", {})
        cid = int(payload.get("checkpoint_id", 0))
        for nid, n in self.plan.nodes.items():
            if n.kind != "sink":
                continue
            if nid in staged_sinks:
                # re-commit epochs the completed checkpoint covers; a crash
                # between manifest write and commit must not lose them
                n.sink.restore_staged(staged_sinks[nid], cid)
            elif hasattr(n.sink, "abort_uncommitted"):
                n.sink.abort_uncommitted()

    def _abort_sinks(self) -> None:
        """Drop every sink's pending (never-committed) rows — the failed
        or superseded attempt's output must not leak into a later
        attempt that reuses the sink instances."""
        for n in self.plan.nodes.values():
            if n.kind == "sink" and hasattr(n.sink, "abort_uncommitted"):
                n.sink.abort_uncommitted()

    # -- rescale restore -------------------------------------------------
    def _rescale_from_paths(self) -> List[str]:
        """The savepoint set (one per OLD process, pid order) the last
        rescale redeploy restored from — injected by the coordinator as
        cluster.rescale-from so EVERY later attempt, not just the first,
        can find the pre-rescale cut (see the restore floor below)."""
        raw = str(self.config.get(ClusterOptions.RESCALE_FROM) or "")
        return [p.strip() for p in raw.split(",") if p.strip()]

    @staticmethod
    def _savepoint_seq(path: str) -> int:
        """Checkpoint-sequence number a savepoint directory was written
        under (paths end in savepoint-<n>; ids are fleet-aligned)."""
        import re

        m = re.findall(r"savepoint-(\d+)", str(path).replace("\\", "/"))
        return int(m[-1]) if m else -1

    def _load_repartitioned(self, primary: str) -> Dict[str, Any]:
        """Load an explicit restore path; when its key-group identity
        (writer nproc/pid) differs from this process's, load the FULL
        savepoint set named by cluster.rescale-from and merge it down to
        this process's shard range (checkpoint/repartition.py)."""
        from flink_tpu.checkpoint.storage import FsCheckpointStorage

        payload = FsCheckpointStorage.load(primary)
        me = self._rescale_identity()
        ident = payload.get("rescale")
        if ident is None or (
                int(ident.get("nproc", 1)) == me["nproc"]
                and int(ident.get("pid", 0)) == me["pid"]):
            # same shape (or a pre-identity snapshot): plain restore
            return payload
        from flink_tpu.checkpoint.repartition import merge_payloads

        paths = self._rescale_from_paths() or [primary]
        payloads = [payload if p == primary else FsCheckpointStorage.load(p)
                    for p in paths]
        payloads.sort(
            key=lambda pl: int((pl.get("rescale") or {}).get("pid", 0)))
        op_kinds = {nid: n.kind for nid, n in self.plan.nodes.items()
                    if nid in payload.get("operators", {})}
        return merge_payloads(
            payloads, new_pid=me["pid"], new_nproc=me["nproc"],
            num_shards=me["num_shards"],
            slots_per_shard=int(self.config.get(
                StateOptions.SLOTS_PER_SHARD)),
            op_kinds=op_kinds)

    def checkpoint_now(self, savepoint: bool = False):
        """Trigger one SYNCHRONOUS checkpoint at the current step
        boundary (ref: CheckpointCoordinator.triggerCheckpoint;
        savepoint=True for the manually-triggered retained form). The
        interval path in the run loop uses the async form instead —
        this entry point waits for durability before returning."""
        assert self._coordinator is not None, "checkpointing not configured"
        # the checkpoint already in flight is a PERIODIC one — its
        # failure is tolerable; the one triggered here is not
        self._complete_pending_checkpoint(wait=True, tolerate=True)
        self._ckpt_pending = self._begin_checkpoint(savepoint=savepoint)
        return self._complete_pending_checkpoint(wait=True)

    def _begin_checkpoint(self, savepoint: bool = False):
        """In-loop freeze + background persistence kickoff. The only
        loop-thread work is the emit flush, sink staging, and the
        snapshot freeze (device leaves are dispatched on-device clones);
        fetching/serializing/writing runs on the checkpoint executor.
        Each part is a leaf of the run's clock
        (``CHECKPOINT_FREEZE_LEAVES``); what none of them names stays in
        the caller's ``ingest.checkpoint``."""
        self.metrics["checkpoint.triggered"] += 1
        # barrier part 1: in-flight async-I/O batches are NOT in the
        # snapshot (their source positions already advanced) — drain
        # them downstream first so the checkpoint covers their effects
        for nid, op in self._ops.items():
            if self.plan.node(nid).kind == "async_io":
                for b in op.poll(drain=True):
                    self._push_downstream(nid, b)
        # barrier: staged epoch must be complete. A fire in flight is
        # waited for here, on the loop
        with self.phases.span("ingest.checkpoint_flush"):
            self._flush_emits()
        sinks = [n.sink for n in self.plan.nodes.values() if n.kind == "sink"]
        commit_fns = [s.notify_checkpoint_complete for s in sinks]
        commit_fns.extend(self._source_offset_committers())
        pend = self._coordinator.trigger_async(
            lambda: self._snapshot(allow_reuse=not savepoint),
            commit_fns=commit_fns,
            prepare_fns=[s.prepare_commit for s in sinks],
            # abandon() (attempt failure with this checkpoint in
            # flight) notifies 2PC sinks to roll THIS epoch's staged
            # transaction back — recovery rolls uncommitted log
            # segments/parts back durably, not just in memory
            abort_fns=[s.notify_checkpoint_abort for s in sinks],
            executor=self._ckpt_executor,
            savepoint=savepoint,
        )
        pend.frozen_versions = dict(self._last_freeze_versions)
        pend.is_savepoint = savepoint
        return pend

    # -- cross-host data plane (SURVEY §3.6: the DCN exchange) -----------

    def _dcn_connect(self):
        """Build + connect this process's exchange endpoint and validate
        the v1 topology constraints (one source, one keyed window
        stage, shards divisible by the process count)."""
        from flink_tpu.exchange.dcn import DcnExchange

        cfg = self.config
        n = int(cfg.get(ClusterOptions.NUM_PROCESSES))
        pid = int(cfg.get(ClusterOptions.PROCESS_ID))
        peers = [p.strip() for p in
                 str(cfg.get(ClusterOptions.DCN_PEERS)).split(",")
                 if p.strip()]
        rendezvous = (not peers and str(cfg.get(
            ClusterOptions.DCN_RENDEZVOUS)).strip() == "coordinator")
        if not rendezvous and len(peers) != n:
            raise ValueError(
                f"cluster.dcn-peers must list {n} host:port entries, "
                f"got {len(peers)}")
        if len(self.plan.sources) != 1:
            raise NotImplementedError(
                "cross-process jobs support exactly one source in v1")
        keyed = [nd for nd in self.plan.nodes.values()
                 if nd.kind == "window"]
        if len(keyed) != 1:
            raise NotImplementedError(
                "cross-process jobs support exactly one keyed window "
                "stage in v1")
        num_shards = int(cfg.get(StateOptions.NUM_KEY_SHARDS))
        if num_shards % n:
            raise ValueError(
                f"state.num-key-shards ({num_shards}) must divide by "
                f"cluster.num-processes ({n}) — shards are the rescale "
                "unit (the key-group contract)")
        lat = keyed[0].window_transform.allowed_lateness_ms
        if lat:
            raise NotImplementedError(
                "allowed lateness across processes needs a refire "
                "consensus the v1 exchange does not carry")
        bind = str(cfg.get(ClusterOptions.DCN_BIND)).strip()
        if bind == "auto":
            # widen past loopback only when the configured topology is
            # actually cross-machine (see ClusterOptions.DCN_BIND)
            local = ("", "127.0.0.1", "localhost")
            hosts = [p.rpartition(":")[0].strip() for p in str(
                cfg.get(ClusterOptions.DCN_PEERS)).split(",") if p.strip()]
            hosts.append(str(cfg.get_raw("cluster.dcn-host", "")).strip())
            bind = ("0.0.0.0" if any(h and h not in local for h in hosts)
                    else "127.0.0.1")
        ex = DcnExchange(pid, n,
                         listen_port=int(cfg.get(ClusterOptions.DCN_PORT)),
                         bind_host=bind,
                         attempt=int(cfg.get_raw("cluster.attempt", 1)),
                         secret=str(cfg.get(
                             ClusterOptions.DCN_SECRET) or "") or None,
                         io_threads=int(cfg.get(
                             ClusterOptions.DCN_IO_THREADS)),
                         buffer_bytes=int(cfg.get(
                             ClusterOptions.DCN_BUFFER_BYTES)))
        try:
            if rendezvous:
                # coordinator-deployed job: publish this process's
                # listener and poll until the whole fleet registered
                # (ref: the reference's TaskManagers learning partition
                # locations from the JobMaster's deployment descriptors)
                from flink_tpu.runtime.rpc import RpcClient

                addr = str(cfg.get_raw("cluster.coordinator", "")).strip()
                job_id = str(cfg.get_raw("cluster.job-id", "job")).strip()
                attempt = int(cfg.get_raw("cluster.attempt", 1))
                dcn_host = str(cfg.get_raw("cluster.dcn-host",
                                           "127.0.0.1")).strip()
                host, _, port = addr.partition(":")
                c = RpcClient(host, int(port), timeout_s=5.0)
                try:
                    c.call("dcn_register", job_id=job_id, attempt=attempt,
                           process_id=pid, host=dcn_host, port=ex.port)
                    deadline = time.time() + 60.0
                    while True:
                        resp = c.call("dcn_peers", job_id=job_id,
                                      attempt=attempt, n_processes=n)
                        if resp.get("ready"):
                            peers = resp["peers"]
                            break
                        if time.time() > deadline:
                            raise TimeoutError(
                                "DCN rendezvous incomplete after 60s")
                        time.sleep(0.1)
                finally:
                    c.close()
            ex.connect(peers)
        except BaseException:
            # a half-connected endpoint must not outlive the attempt: a
            # LEAKED listener (live accept thread on a fixed
            # cluster.dcn-port) turns every recovery retry into
            # EADDRINUSE — the attempt could never rebind its own port
            ex.close()
            raise
        self._dcn_key_field = keyed[0].key_field
        self._dcn_shards = num_shards
        return ex

    def _dcn_negotiated_restore(self):
        """Agree on ONE checkpoint id across processes (the min of
        everyone's latest) and load it; None when any process has no
        checkpoint — everyone then replays from scratch together."""
        latest = self._coordinator.storage.latest()
        my_id = latest.checkpoint_id if latest is not None else -1
        _, metas = self._dcn.exchange({}, {"latest": int(my_id)})
        common = min(int(m["latest"]) for m in metas)
        if common < 0:
            return None
        from flink_tpu.checkpoint.storage import FsCheckpointStorage

        # last match: list_complete sorts by (id, epoch), so among
        # fence-epoch duplicates of the negotiated id the successor's
        # (highest-epoch) directory wins
        match = [h for h in self._coordinator.storage.list_complete()
                 if h.checkpoint_id == common and not h.is_savepoint]
        if match:
            payload = FsCheckpointStorage.load(match[-1])
            self._coordinator.resume_numbering(payload)
            return payload
        raise RuntimeError(
            f"negotiated checkpoint id {common} is missing locally — "
            "retention removed it; raise state.checkpoints.num-retained")

    def _ingest_loop_dcn(self, srcs, interval_ms: int,
                         job_name: str = "job") -> None:
        """The cross-host step loop: ingest a local batch, route records
        to their shard owners, RENDEZVOUS (the step barrier carrying
        watermark / termination / checkpoint consensus), then run the
        local pipeline on this process's share. See exchange/dcn.py for
        why the rendezvous replaces flow control, in-band watermarks,
        and barrier alignment.

        STEP OVERLAP (``cluster.dcn-overlap``, default on): step k+1's
        frames are dispatched BEFORE step k's are consumed, so one
        step's exchange is always in flight while the device computes
        the previous step's records and the host ingests/routes the
        next — the rendezvous barrier moves from dispatch to
        consumption. The per-step consensus is untouched (metas are
        identical fleet-wide, so every process makes the same
        checkpoint/termination decision one step later), and a
        checkpoint barrier DRAINS the one in-flight step first
        (``cluster.dcn-overlap-drain``) so the cut still covers every
        routed record — disabling the drain is the analyzer-flagged
        at-most-once trade (DCN_OVERLAP_UNSAFE)."""
        from flink_tpu.exchange.partitioners import hybrid_route

        cfg = self.config
        n = int(cfg.get(ClusterOptions.NUM_PROCESSES))
        pid = int(cfg.get(ClusterOptions.PROCESS_ID))
        key_field = self._dcn_key_field
        (sid,) = list(self.plan.sources)
        d = srcs[sid]
        order = sorted(d)
        ex = self._dcn
        overlap = (bool(cfg.get(ClusterOptions.DCN_OVERLAP))
                   and ex.supports_async)
        drain_at_barrier = bool(cfg.get(ClusterOptions.DCN_OVERLAP_DRAIN))
        st = _DcnStepState(last_chk=time.time())
        pending_x = None        # the ONE in-flight overlapped step
        stale_ckpt = False      # drain-off mode: the undrained step's
        # meta was dispatched BEFORE the snapshot it rode behind, so
        # its ckpt flag is stale — absorb it once (symmetric: every
        # process just checkpointed at the same boundary), or the
        # fleet double-checkpoints back-to-back every interval
        stale_sp = False        # same staleness for the savepoint flag:
        # the in-flight step's meta predates the savepoint just served
        ph = self.phases.phase
        while True:
            if self._cancel is not None and self._cancel.is_set():
                # stop-with-savepoint (rescale) sets cancel from the
                # savepoint completion callback; exit symmetrically at
                # the next boundary — every process served the request
                # at the SAME rendezvous, so the fleet leaves together
                raise JobCancelledError(job_name)
            batch = None
            batch_ix = None
            while order:
                ix = order[0]
                ph("ingest.source_wait")
                nxt = next(d[ix], None)
                self._t_input = ph("ingest.bookkeeping")
                if nxt is None:
                    order.pop(0)
                    continue
                batch = nxt
                batch_ix = ix
                self._advance_position(sid, ix, nxt[0], nxt[1])
                break
            shares: Dict[int, Any] = {}
            if batch is not None:
                data, ts = batch
                ts = np.asarray(ts, np.int64)
                if len(ts):
                    mx = int(ts.max())
                    self._max_ts[sid] = max(self._max_ts[sid], mx)
                    self._wm_gens[sid][batch_ix].on_batch(mx)
                ph("ingest.route")
                keys = np.asarray(data[key_field], np.int64)
                # process destination from the ONE routing truth the
                # hybrid mesh plan also uses (exchange/partitioners.py):
                # intra-slice records (dest == pid) never touch the
                # wire — they ride shares[pid] straight into the local
                # push, and the in-process device mesh distributes them
                # over ICI
                dest, _ = hybrid_route(keys, self._dcn_shards, n)
                for j in range(n):
                    m = dest == j
                    if m.any():
                        shares[j] = {
                            "data": {k: np.asarray(v)[m]
                                     for k, v in data.items()},
                            "ts": ts[m]}
            ph("ingest.bookkeeping")
            local_wm = (min(self._wm_gens[sid][i].current() for i in order)
                        if order else _FINAL)
            want_ckpt = (pid == 0 and self._coordinator is not None
                         and interval_ms > 0
                         and (time.time() - st.last_chk) * 1000
                         >= interval_ms)
            sp_rq = self._savepoint_request
            meta = {"wm": int(local_wm), "done": batch is None,
                    "ckpt": bool(want_ckpt),
                    # savepoint consensus: the coordinator triggers the
                    # request on EVERY process (require-all push); the
                    # flag rides the rendezvous so the fleet serves it
                    # at ONE common step boundary — the savepoint set
                    # is a globally consistent cut, like "ckpt" but
                    # all-set instead of any-set (no clock owner)
                    "sp": bool(sp_rq is not None and sp_rq.is_set()),
                    # 2PC phase-2 ack: the id this process has DURABLY
                    # persisted (commit waits until everyone has it —
                    # the reference's all-acks-then-notifyComplete rule,
                    # 4.C, carried on the rendezvous instead of RPC)
                    "persisted": int(st.persisted_id)}
            ph("ingest.exchange")
            h = ex.exchange_async(shares, meta)
            ph("ingest.bookkeeping")
            if overlap and pending_x is None:
                # prime the double buffer: nothing to consume yet
                pending_x = h
                continue
            target, pending_x = (pending_x, h) if overlap else (h, None)
            all_done, ckpt_req, sp_req = self._dcn_consume_step(
                sid, target, st, deferred=overlap)
            if stale_ckpt:
                ckpt_req = False
                stale_ckpt = False
            if stale_sp:
                sp_req = False
                stale_sp = False
            if not (all_done or ckpt_req or sp_req):
                continue
            if pending_x is not None and (all_done or drain_at_barrier):
                # drain the in-flight step so the snapshot cut (or the
                # final barrier) covers its routed records. Its own
                # consensus flags are ABSORBED — metas are identical
                # fleet-wide, so every process absorbs the same ones —
                # except termination, which must still be honored.
                done2, _, _ = self._dcn_consume_step(sid, pending_x, st,
                                                     absorb=True,
                                                     deferred=True)
                all_done = all_done or done2
                pending_x = None
            if ckpt_req:
                # checkpoint consensus: process 0's clock decided, the
                # flag rode the rendezvous, so EVERY process snapshots
                # at this same step boundary — a globally consistent
                # cut (SURVEY §6.4's step-barrier insight). With the
                # drain above there are no in-flight records; with
                # cluster.dcn-overlap-drain=false the one in-flight
                # step's records are NOT covered (the analyzer-warned
                # at-most-once trade).
                if self._coordinator is not None and st.pending is None:
                    ph("ingest.checkpoint")
                    st.pending = self._begin_checkpoint()
                    self._ckpt_pending = st.pending
                    st.pending.future.result()  # durable before acking
                    st.pending_id = st.pending.checkpoint_id
                    st.persisted_id = st.pending_id
                st.last_chk = time.time()
                ph("ingest.bookkeeping")
                # without the drain, the in-flight step still carries
                # its pre-snapshot ckpt flag — consume it ABSORBED
                stale_ckpt = pending_x is not None
            if sp_req:
                # every process has the pending request (all-set above):
                # serve it HERE, at the common boundary, each with its
                # own token/stop identity. The savepoint commits
                # synchronously fleet-wide — symmetric, so no ack dance.
                self._maybe_take_savepoint()
                if (st.pending is not None
                        and self._ckpt_pending is not st.pending):
                    # the savepoint path completed the in-flight
                    # periodic checkpoint (checkpoint_now waits on it);
                    # forgetting that here would double-complete it at
                    # the next persisted-ack consensus
                    st.pending = None
                stale_sp = pending_x is not None
            if all_done:
                if st.pending is not None:
                    # end of input doubles as the final barrier: every
                    # process reached it, so the last cut is global
                    self._count_completed(st.pending.complete())
                    self._ckpt_pending = None
                return

    def _dcn_consume_step(self, sid: int, handle, st: "_DcnStepState",
                          absorb: bool = False,
                          deferred: bool = False):
        """Consume ONE rendezvous step: barrier on the handle, push the
        merged share through the local pipeline, apply the global
        watermark, and run the 2PC persisted-ack check. Returns
        (all_done, ckpt_requested, savepoint_requested); ``absorb``
        suppresses the ckpt and savepoint flags
        (the drained step rides the barrier that drained it);
        ``deferred`` marks an OVERLAPPED consume — the only place the
        dcn.overlap.consume fault point fires, so a chaos bisect of
        the overlap seam stays quiet on lockstep runs."""
        if deferred:
            from flink_tpu import faults

            faults.fire("dcn.overlap.consume", exc=ConnectionError)
        ph = self.phases.phase
        ph("ingest.exchange")   # the rendezvous: peers' shares and metas
        payloads, metas = handle.result()
        ph("ingest.route")
        parts = [p for p in payloads if p is not None
                 and len(p["ts"])]
        if parts:
            md = {k: np.concatenate([p["data"][k] for p in parts])
                  for k in parts[0]["data"]}
            mts = np.concatenate([p["ts"] for p in parts])
            self.metrics["records_in"] += len(mts)
            self.metrics["batches"] += 1
            self._push_downstream(
                sid, (md, mts, np.ones(len(mts), bool)))
            self._throttle_ops()
            self._eps_meter.mark(len(mts))
        ph("ingest.bookkeeping")
        # identical global watermark on every process: min of the
        # piggybacked locals (exhausted processes report _FINAL so
        # they stop pinning the clock)
        gwm = min(int(m["wm"]) for m in metas)
        if gwm != _FINAL and gwm > self._out_wm[sid]:
            self._out_wm[sid] = gwm
        ph("wm.advance")
        self._advance_time()
        ph("ingest.bookkeeping")
        self._check_drain_error()
        # commit the PREVIOUS checkpoint once every process acked
        # durability (phase 2): only then may 2PC sinks publish
        if (st.pending is not None
                and all(int(m.get("persisted", -1)) >= st.pending_id
                        for m in metas)):
            self._count_completed(st.pending.complete())
            self._ckpt_pending = None
            st.pending = None
        ckpt_req = (not absorb) and any(bool(m.get("ckpt")) for m in metas)
        # all-set (vs ckpt's any-set): a savepoint is triggered per
        # process over RPC, so the LAST process to receive it gates the
        # barrier — serving before everyone holds the request would cut
        # at different steps and the set would not be a consistent cut
        sp_req = (not absorb) and all(bool(m.get("sp")) for m in metas)
        return all(bool(m["done"]) for m in metas), ckpt_req, sp_req

    def _enumerate_owned(self, sid: int, n_splits: int) -> List[int]:
        """Which split indices THIS runner reads (ref: FLIP-27
        SplitEnumerator on the JM assigning splits to readers — SURVEY
        §3.3 source runtime). 'local' (default) = all splits (single-
        process execution); 'coordinator' = ask the job coordinator for
        this runner's share, so multiple runners of one job divide the
        source without overlap."""
        from flink_tpu.config import SourceOptions

        mode = self.config.get(SourceOptions.ENUMERATION)
        nproc = int(self.config.get(ClusterOptions.NUM_PROCESSES))
        if mode == "local" and nproc > 1:
            # cross-host job without a coordinator-side enumerator:
            # deterministic strided shares (the same disjointness rule
            # rpc_enumerate_splits uses)
            pid = int(self.config.get(ClusterOptions.PROCESS_ID))
            return list(range(pid, n_splits, nproc))
        if mode == "local" or n_splits == 0:
            return list(range(n_splits))
        if mode != "coordinator":
            raise ValueError(
                f"source.enumeration must be 'local' or 'coordinator', "
                f"got {mode!r}")
        from flink_tpu.runtime.rpc import RpcClient

        addr = str(self.config.get_raw("cluster.coordinator", "")).strip()
        job_id = str(self.config.get_raw("cluster.job-id", "")).strip()
        runner_id = str(self.config.get_raw("cluster.runner-id", "")).strip()
        if not (addr and job_id and runner_id):
            raise ValueError(
                "source.enumeration=coordinator needs cluster.coordinator"
                ", cluster.job-id and cluster.runner-id (the runner "
                "injects them on deploy)")
        host, _, port = addr.partition(":")
        c = RpcClient(host, int(port), timeout_s=10.0)
        try:
            resp = c.call("enumerate_splits", job_id=job_id,
                          source_id=sid, n_splits=n_splits,
                          runner_id=runner_id)
        finally:
            c.close()
        return [int(i) for i in resp["splits"]]

    def _debloat_split(self, data, ts):
        """Re-chunk one source batch to the debloater's current chunk
        size (no-op generator when the debloater is off or the batch
        already fits). Slicing preserves record order, so watermark
        semantics are untouched — the generators see the same max ts."""
        n = len(ts)
        chunk = self._debloat_chunk
        if self._debloat_target <= 0 or chunk is None or n <= chunk:
            if self._debloat_target > 0 and self._debloat_chunk is None and n:
                self._debloat_chunk = n  # seed at the source batch size
                # (empty first batches — unbounded sources idling — must
                # not seed a zero chunk)
            yield data, ts
            return
        chunk = max(1, chunk)
        for lo in range(0, n, chunk):
            hi = min(lo + chunk, n)
            yield ({k: v[lo:hi] for k, v in data.items()}, ts[lo:hi])

    def _debloat_adjust(self) -> None:
        """One control step (ref: BufferDebloater.recalculateBufferSize):
        recent emit p99 > target → halve the chunk; p99 < target/2 →
        grow 2x (cap: whatever the source produces — _debloat_split
        never merges). Needs a few fresh samples to act."""
        if self._debloat_target <= 0 or self._debloat_chunk is None:
            return
        # act only on FRESH samples: the ingest loop passes far more
        # often than windows fire, and re-halving on the same stale
        # window would pin the chunk at the floor after one slow burst
        c = self._lat_hist.count
        if c - self._debloat_seen < 2:
            return
        self._debloat_seen = c
        p99 = self._lat_hist.quantile_recent(0.99, window=16)
        if p99 > self._debloat_target:
            self._debloat_chunk = max(self._debloat_min,
                                      self._debloat_chunk // 2)
        elif p99 < self._debloat_target / 2:
            self._debloat_chunk *= 2

    def _maybe_take_savepoint(self) -> None:
        """Operator-triggered savepoint (CLI `savepoint`): synchronous +
        retained, at a batch boundary; the completed path is pushed to
        the requester's on_complete hook (runner → coordinator → CLI
        status). A request with no checkpoint storage is rejected at the
        runner, so _coordinator is always set when the flag can be."""
        req = self._savepoint_request
        if req is None or not req.is_set():
            return
        # snapshot the request's identity BEFORE clearing: the moment
        # the event clears, a new trigger may overwrite stop_after/token
        # on the shared request object while the (long, synchronous)
        # savepoint write runs — completion must report the values of
        # the request it actually served
        stop_after = getattr(req, "stop_after", False)
        token = getattr(req, "token", None)
        req.clear()
        if self._coordinator is None:
            return  # unreachable via the runner path (validated there)
        with self.phases.span("ingest.checkpoint"):
            h = self.checkpoint_now(savepoint=True)
        self.last_savepoint = h.path
        cb = getattr(req, "on_complete", None)
        if cb is not None:
            # arity by signature, NOT by catching TypeError — a TypeError
            # raised INSIDE the callback must not trigger a second,
            # wrongly-argumented invocation (double savepoint report)
            import inspect

            try:
                params = inspect.signature(cb).parameters
                rich = ("stop_after" in params
                        or any(p.kind == p.VAR_KEYWORD
                               for p in params.values()))
            except (TypeError, ValueError):
                rich = False
            if rich:
                cb(h.path, stop_after=stop_after, token=token)
            else:
                cb(h.path)  # simple callbacks (tests) take path only

    def _complete_pending_checkpoint(self, wait: bool = False,
                                     tolerate: bool = False):
        """Apply the 2PC commit of a finished background checkpoint on
        the LOOP thread (the asynchronous notifyCheckpointComplete of
        the reference). Non-blocking unless ``wait``.

        ``tolerate``: the PERIODIC path rides out up to
        execution.checkpointing.tolerable-failures consecutive
        persist/commit failures instead of failing the job — the failed
        id left no manifest at its final name, so restore ignores it,
        and the staged 2PC epoch simply commits with the next
        successful checkpoint. Savepoints and the final end-of-input
        checkpoint never tolerate (their durability IS the contract)."""
        import os as _os

        p = self._ckpt_pending
        if p is None:
            return None
        if not p.done():
            if not wait:
                return None
            # a synchronous checkpoint (the job's last, a savepoint):
            # the loop's thread stands until the persist is durable
            from concurrent.futures import wait as _fwait

            with self.phases.span("ingest.checkpoint_wait"):
                _fwait([p.future])
        try:
            handle = p.complete()
        except Exception as e:  # noqa: BLE001 — persist/commit failure
            self.metrics["checkpoint.failed"] += 1
            self._ckpt_pending = None
            if p.is_savepoint:
                # savepoints neither count toward nor reset the
                # CONSECUTIVE-PERIODIC-failure budget (the option's
                # documented unit)
                raise
            self._ckpt_failures += 1
            tol = int(self.config.get(
                CheckpointingOptions.TOLERABLE_FAILURES))
            if not tolerate or self._ckpt_failures > tol:
                raise
            from flink_tpu.obs.tracing import tracer

            self.metrics["checkpoint_failures"] = (
                self.metrics.get("checkpoint_failures", 0) + 1)
            with tracer.span("checkpoint.failed",
                             checkpoint_id=p.checkpoint_id,
                             consecutive=self._ckpt_failures,
                             error=f"{type(e).__name__}: {e}"):
                pass
            return None
        if not p.is_savepoint:
            # a savepoint landing between two periodic failures must
            # not reset the consecutive-periodic counter either
            self._ckpt_failures = 0
        self._ckpt_pending = None
        self._count_completed(handle)
        if not p.is_savepoint:
            names = handle.op_files or {}
            aux_names = handle.op_aux or {}
            self._ckpt_base = {
                "files": {nid: _os.path.join(
                    handle.path, names.get(str(nid), f"op-{nid}.blob"))
                    for nid in self._ops},
                "versions": dict(p.frozen_versions),
                "aux": {nid: {logical: _os.path.join(handle.path, fn)
                              for logical, fn in
                              aux_names.get(str(nid), {}).items()}
                        for nid in self._ops
                        if aux_names.get(str(nid))},
            }
        return handle

    def _count_completed(self, handle) -> None:
        """A checkpoint is durable and its 2PC epoch committed."""
        size = max(handle.size_bytes, 0)
        self.metrics["checkpoint.completed"] += 1
        self.metrics["checkpoint.bytes_last"] = size
        self.metrics["checkpoint.bytes_total"] += size

    # -- run loop --------------------------------------------------------
    def run(self, job_name: str = "job", cancel=None,
            savepoint_request=None):
        """``cancel``: optional threading.Event checked at every batch
        boundary; when set the run aborts with JobCancelledError through
        the normal failure cleanup (no output reaches sinks).
        ``savepoint_request``: optional threading.Event; when set, the
        loop takes a SAVEPOINT at the next batch boundary (the CLI's
        `savepoint` command rides this), clears the event, and records
        the path in ``self.last_savepoint``."""
        self._cancel = cancel
        self._savepoint_request = savepoint_request
        self.last_savepoint = None
        if self.plan.runtime_mode == "batch":
            # bounded-mode recovery is re-execution (ref: batch jobs
            # have no checkpoints — RestartAllFailoverStrategy re-runs
            # the regions); a configured interval/restore is a config
            # contradiction, not something to silently ignore
            if self.config.get(CheckpointingOptions.INTERVAL) > 0:
                raise ValueError(
                    "execution.checkpointing.interval is incompatible "
                    "with execution.runtime-mode=batch (bounded-mode "
                    "recovery is re-execution; 2PC sinks commit once "
                    "at end of input)")
            restore = self.config.get(CheckpointingOptions.RESTORE)
            if restore == "latest":
                # coordinator/supervisor redeploys inject
                # restore=latest on every retry attempt; for a batch
                # job there is never a checkpoint to resume, and its
                # documented recovery model IS re-execution — degrade
                # to a fresh run instead of burning the restart budget
                # on a config error that masks the original failure
                self.config.set(CheckpointingOptions.RESTORE, "")
            elif restore:
                raise ValueError(
                    "execution.checkpointing.restore is incompatible "
                    "with execution.runtime-mode=batch (nothing "
                    "checkpoints in batch mode — re-run the job)")
        # compile-time plan analysis at submit (flink_tpu/analysis/):
        # findings surface BEFORE the first record flows; the fail-on
        # threshold decides which severities abort the run, everything
        # else stays inspectable on driver.analysis_findings. Runs
        # after the explicit batch-mode contradictions above so their
        # long-standing error messages keep first claim.
        from flink_tpu.config import AnalysisOptions

        fail_on = str(self.config.get(AnalysisOptions.FAIL_ON)).strip().lower()
        self.analysis_findings = []
        if fail_on != "off":
            from flink_tpu.analysis import AnalysisError, analyze
            from flink_tpu.analysis.core import blocking

            # eval_chains=False: the automatic submit pass must never
            # CALL user chain fns (a side-effecting map would observe a
            # phantom empty batch); schema facts go opaque at the first
            # unevaluated chain. `env.analyze()` / the CLI evaluate.
            self.analysis_findings = analyze(self.plan, self.config,
                                             eval_chains=False)
            blockers = blocking(self.analysis_findings, fail_on)
            if blockers:
                raise AnalysisError(blockers, fail_on)
        import queue
        import threading

        from flink_tpu.obs.metrics import METRICS_BIND, METRICS_PORT, MetricsServer

        self._coordinator = self._setup_checkpointing(job_name)
        # announce this attempt's fencing epoch to transactional sinks
        # BEFORE any restore/write: epoch-qualified in-progress names
        # (part files, log segments) keep a deposed attempt's late
        # renames off a successor's committed output — the same
        # chk-<id>.e<epoch> discipline checkpoint storage uses
        attempt_epoch = int(self.config.get_raw("cluster.attempt", 0))
        for n in self.plan.nodes.values():
            if n.kind == "sink":
                setter = getattr(n.sink, "set_attempt_epoch", None)
                if setter is not None:
                    setter(attempt_epoch)
                # the shared HostPool rides the same announcement seam:
                # transactional log sinks route per-partition segment
                # writes + the group-fsync pass through it so a
                # multi-partition stage() scales with cores
                pool_setter = getattr(n.sink, "set_host_pool", None)
                if pool_setter is not None:
                    pool_setter(self.host_pool)
        from concurrent.futures import ThreadPoolExecutor

        from flink_tpu import faults
        from flink_tpu.fs import install_enospc_policy_from_config

        # the disk-full degradation policy (storage.enospc-policy):
        # installed process-wide at run start so every durable write
        # seam — checkpoint persists, log segment stages, sink part
        # writes — follows the job's declared retry/fail behavior
        install_enospc_policy_from_config(self.config)
        # fault-scope propagation (session tenant isolation): the run
        # executes on a thread the runner already scoped to this job;
        # the threads the DRIVER owns — drain, checkpoint executor —
        # must carry the same scope or a tenant's checkpoint/upload
        # fault rules would miss its own background work
        self._fault_scope = faults.current_scope()
        self._ckpt_executor = (ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="ckpt",
            initializer=faults.set_thread_scope,
            initargs=(self._fault_scope,))
            if self._coordinator is not None else None)
        self._ckpt_pending = None
        self._ckpt_base = None
        self._ckpt_failures = 0  # consecutive, for tolerable-failures
        self._last_freeze_versions: Dict[Any, int] = {}
        interval_ms = self.config.get(CheckpointingOptions.INTERVAL)
        restore = self.config.get(CheckpointingOptions.RESTORE)
        self._positions: Dict[int, Dict[int, int]] = {}
        port = self.config.get(METRICS_PORT)
        bind = self.config.get(METRICS_BIND)
        self._metrics_server = (
            MetricsServer(self.registry, port, bind) if port else None)
        self._emit_q = queue.Queue()
        self._drain_discard = [False]  # fresh cell per run (see __init__)
        self.phases = PhaseClock()
        self._loop_push.waited_s, self._loop_push.takes = 0.0, 0
        for op in self._ops.values():
            op.phases = self.phases
        if self._coordinator is not None:
            self._coordinator.phases = self.phases
            for k in CHECKPOINT_COUNTERS:
                self.metrics.setdefault(k, 0)
        self._fires.clear()
        # per-op device profiling window (pipeline.profile-dir): wraps
        # N warm driver steps in jax.profiler.trace and reduces the
        # trace to a per-op summary (obs/profiling.py)
        from flink_tpu.obs.profiling import StepProfiler

        self._profiler = StepProfiler.from_config(self.config)
        # self-maintaining bus tier (log.cleaner.enabled): one leased
        # background cleaner service per LogSink topic, running
        # compaction + retention at log.cleaner.interval-ms under the
        # cleaner lease + the per-topic maintenance lock — racing this
        # run's own producer/consumers by design (the manifest-swap
        # discipline keeps reads byte-identical). A second driver on
        # the same topic fails the acquire and runs WITHOUT a cleaner
        # (the lease's point: exactly one cleaner per topic).
        self._cleaners = []
        from flink_tpu.config import LogOptions

        if bool(self.config.get(LogOptions.CLEANER_ENABLED)):
            from flink_tpu.log.cleaner import LogCleaner
            from flink_tpu.log.connectors import LogSink
            from flink_tpu.log.topic import LogError

            seen = set()
            for n in self.plan.nodes.values():
                if n.kind != "sink" or not isinstance(n.sink, LogSink):
                    continue
                if n.sink.path in seen:
                    continue
                seen.add(n.sink.path)
                cleaner = LogCleaner(n.sink.path, self.config)
                try:
                    cleaner.start()
                except LogError:
                    continue  # a live cleaner service owns this topic
                self._cleaners.append(cleaner)
        drain = threading.Thread(target=self._drain_entry, daemon=True)
        drain.start()
        try:
            return self._run_loop(job_name, drain, interval_ms, restore)
        except BaseException:
            self.phases.stop()  # a failed run leaves no phase open
            # Failed attempt: an in-flight background checkpoint must
            # NOT commit its 2PC epoch (its snapshot may cover state the
            # failure invalidated); abandon it uncommitted — the
            # manifest may still land, which is harmless: restore picks
            # it up with its staged (uncommitted) epochs exactly like a
            # crash between manifest and commit.
            if getattr(self, "_ckpt_pending", None) is not None:
                self._ckpt_pending.abandon()
                self.metrics["checkpoint.aborted"] += 1
                # bounded wait for a persist already running: the next
                # attempt may reuse this checkpoint id, and two live
                # writers on one id is the corruption the unique tmp
                # dirs defend against — prefer not to race at all (a
                # wedged network fs must still not turn a crash into a
                # hang, hence the timeout)
                from concurrent.futures import wait as _fwait

                _fwait([self._ckpt_pending.future], timeout=30.0)
                self._ckpt_pending = None
            # Stop the drain thread BEFORE the exception
            # escapes, discarding everything it still holds. A daemon
            # drain left running would deliver this attempt's fires into
            # sinks reused by the next attempt — duplicate output after
            # recovery (exactly-once ref: StreamTask.cleanUpInternal
            # cancels the mailbox + output flusher before failover).
            self._drain_discard[0] = True
            self._hurry_drain()
            if self._emit_q is not None:
                self._emit_q.put(None)
                # bounded: the drain may be wedged inside the very device
                # fetch that killed the run — never convert a crash into
                # a hang. An abandoned drain is a daemon and keeps its
                # (permanently-set) discard cell: a late wakeup delivers
                # nothing, ever.
                drain.join(timeout=10.0)
                self._emit_q = None
            self._drain_error = None
            self._flush_req.clear()
            # a DCN endpoint alive past its attempt (the negotiated
            # restore or source setup failed before the ingest loop's
            # own close) would hold its fixed cluster.dcn-port —
            # every recovery rebind then dies with EADDRINUSE
            if getattr(self, "_dcn", None) is not None:
                self._dcn.close()
                self._dcn = None
            # rows delivered BEFORE the crash still sit in sink buffers;
            # drop them here too — the restore path only runs when the
            # next attempt configures restore (ref: StreamTask
            # .cleanUpInternal aborts pending transactions in cleanup)
            self._abort_sinks()
            # unblock + join prefetch feeders: one blocked thread and
            # `depth` buffered batches would leak per split per attempt.
            # Duck-typed: covers _Prefetcher AND source iterators that
            # own background work of their own (LogSource's segment
            # readahead exposes close() on its split iterator)
            for its in getattr(self, "_srcs", {}).values():
                for it in its.values():
                    closer = getattr(it, "close", None)
                    if closer is not None:
                        closer()
            if self._metrics_server is not None:
                self._metrics_server.close()
            for nid, op in self._ops.items():
                if self.plan.node(nid).kind == "async_io":
                    op.close()
            # a trace window left open by the failure must be stopped —
            # a dangling jax profiler session would poison the next run
            if self._profiler is not None:
                self._profiler.close()
            raise
        finally:
            # cleaners die with the run, releasing their leases so a
            # successor (or a manual pass) acquires immediately — on
            # EVERY exit path (a crashed process skips this; ttl
            # expiry + epoch bump is that takeover path)
            for cleaner in getattr(self, "_cleaners", []):
                try:
                    cleaner.stop()
                except Exception:
                    pass  # teardown must not mask the run's outcome
            self._cleaners = []
            if self._ckpt_executor is not None:
                # non-blocking: an abandoned persist may still be
                # writing; letting it finish is safe (manifest-last)
                self._ckpt_executor.shutdown(wait=False)
                self._ckpt_executor = None
            # the shared host pool dies with the run (a wedged task
            # must not hang teardown: shutdown is non-waiting, and a
            # post-close straggler call degrades to the inline path)
            self.host_pool.close()

    def _run_loop(self, job_name: str, drain, interval_ms: int,
                  restore) -> "JobResult":
        from flink_tpu.api.environment import JobResult
        for sid in self.plan.sources:
            n = self.plan.node(sid)
            strategy = n.watermark_strategy or self.plan.watermark_strategy
            # one watermark generator PER SPLIT, combined with min — the
            # per-channel rule (ref: StatusWatermarkValve; a lagging split
            # must hold the source watermark back or its records would be
            # dropped as late)
            self._wm_gens[sid] = [make_generator(strategy)
                                  for _ in n.source.splits()]
            self._max_ts[sid] = LONG_MIN
            self._positions[sid] = {i: 0 for i in range(len(n.source.splits()))}

        # cross-host data plane: bring the DCN exchange up BEFORE
        # restore — the restore id is negotiated across processes (a
        # crash can leave one process a checkpoint ahead; replaying
        # from mismatched ids would double-count the laggard's records
        # in the leader's shard ranges)
        self._dcn = None
        if int(self.config.get(ClusterOptions.NUM_PROCESSES)) > 1:
            if self.plan.runtime_mode == "batch":
                raise NotImplementedError(
                    "execution.runtime-mode=batch is single-process in "
                    "v1 — the DCN rendezvous is a per-step streaming "
                    "protocol; cross-host batch needs a partition-file "
                    "transfer plane (out of scope, see COMPONENTS #57)")
            self._dcn = self._dcn_connect()

        if restore:
            if restore == "latest":
                payload = (self._dcn_negotiated_restore()
                           if self._dcn is not None
                           else self._coordinator.restore_latest())
                # durable rescale floor: cluster.rescale-from names the
                # savepoint set the last rescale redeploy restored from.
                # A checkpoint OLDER than that set predates the cut —
                # at 1->2->1 the final process count reuses the original
                # (unsuffixed) checkpoint directory, whose latest entry
                # is PRE-rescale state; resurrecting it would replay
                # records both savepoint cuts already cover, at a stale
                # key-group geometry. The savepoints win unless a
                # checkpoint at least as new exists.
                paths = self._rescale_from_paths()
                if paths:
                    floor = max(self._savepoint_seq(p) for p in paths)
                    have = (int(payload.get("checkpoint_id", -1))
                            if payload is not None else -1)
                    if have < floor:
                        payload = self._load_repartitioned(paths[0])
                        self._coordinator.resume_numbering(payload)
            else:
                payload = self._load_repartitioned(restore)
                self._coordinator.resume_numbering(payload)
            if payload is not None:
                self._restore(payload)
            else:
                # restore requested but nothing to restore (crash before
                # the first checkpoint): a sink instance reused across
                # attempts still holds the crashed attempt's staged rows —
                # the full replay would commit them twice
                self._abort_sinks()

        # registered on self INCREMENTALLY so prefetchers opened before a
        # mid-construction open_split failure are reachable from run()'s
        # failure cleanup. Keyed by GLOBAL split index: with
        # coordinator-side enumeration this runner opens only the
        # indices the enumerator assigned it, but positions/watermark
        # state stay globally indexed (checkpoints are runner-agnostic).
        srcs = self._srcs = {}
        self._owned_splits: Dict[int, List[int]] = {}
        prefetch = self.config.get(PipelineOptions.SOURCE_PREFETCH)
        for sid in self.plan.sources:
            n = self.plan.node(sid)
            splits = n.source.splits()
            owned = self._enumerate_owned(sid, len(splits))
            self._owned_splits[sid] = owned
            if not owned and self._dcn is None:
                # this runner owns nothing of the source: exhausted from
                # birth — its watermark must not pin downstream at the
                # floor while peers' shares flow. NOT under the DCN
                # exchange: there out_wm[sid] is the GLOBAL watermark
                # applied downstream (the rendezvous meta carries the
                # per-process local, already _FINAL for an empty
                # process) — pinning it to _FINAL here made a
                # zero-split process fire its windows immediately and
                # drop every routed record as late (found by the chaos
                # suite's DCN peer-death soak).
                self._out_wm[sid] = _FINAL
            d = srcs[sid] = {}
            for i in owned:
                it = n.source.open_split(splits[i],
                                         self._positions[sid].get(i, 0))
                d[i] = (_Prefetcher(it, depth=prefetch)
                        if prefetch > 0 else it)

        ph = self.phases.phase
        self._t_loop = ph("ingest.bookkeeping")
        if self.plan.runtime_mode == "batch":
            return self._run_batch(job_name, srcs, drain)

        last_chk = time.time()
        if self._dcn is not None:
            try:
                self._ingest_loop_dcn(srcs, interval_ms, job_name)
            finally:
                self._dcn.close()
                self._dcn = None
            active = {}
        else:
            active = {sid: sorted(its) for sid, its in srcs.items()}
        while any(active.values()):
            for sid, splits_alive in list(active.items()):
                if not splits_alive:
                    continue
                for split_ix in list(splits_alive):
                    if self._cancel is not None and self._cancel.is_set():
                        raise JobCancelledError(job_name)
                    it = srcs[sid][split_ix]
                    ph("ingest.source_wait")
                    nxt = next(it, None)
                    self._t_input = ph("ingest.bookkeeping")
                    if nxt is None:
                        splits_alive.remove(split_ix)
                        continue
                    data, ts = nxt
                    ts = np.asarray(ts, np.int64)
                    # where an operator may lead, the split's
                    # generator learns the batch's newest timestamp
                    # BEFORE the push (nothing reads it until the
                    # source's watermark is recombined): the watermark
                    # the batch implies is known while its records are
                    # still in hand
                    lead = bool(self._lead_ops) and len(splits_alive) == 1
                    if lead and self._note_max_ts(sid, split_ix, ts):
                        self._lead_advance(sid, splits_alive, ts)
                    for data_c, ts_c in self._debloat_split(data, ts):
                        self._push_source_chunk(sid, data_c, ts_c)
                    if not lead:
                        self._note_max_ts(sid, split_ix, ts)
                    self._advance_position(sid, split_ix, data, ts)
                    self._eps_meter.mark(len(ts))
                # exhausted splits stop holding the watermark back
                # (ref: idle-channel handling in the valve)
                self._recombine_source_wm(sid, splits_alive)
                ph("wm.advance")
                self._advance_time()
                ph("ingest.bookkeeping")
                self._check_drain_error()
            if self._profiler is not None:
                self._profiler.step()
            self._debloat_adjust()
            # operator-triggered savepoint (CLI `savepoint` command):
            # synchronous + retained, at this batch boundary
            self._maybe_take_savepoint()
            # async checkpointing: commit any finished background
            # checkpoint (never blocks), then kick off the next one when
            # the interval elapsed and no persistence is in flight
            self._complete_pending_checkpoint(wait=False, tolerate=True)
            if (self._coordinator is not None and interval_ms > 0
                    and self._ckpt_pending is None
                    and (time.time() - last_chk) * 1000 >= interval_ms):
                ph("ingest.checkpoint")
                self._ckpt_pending = self._begin_checkpoint()
                last_chk = time.time()
                ph("ingest.bookkeeping")

        # end of input: final watermark per stateful op flushes everything.
        # Quiesce the device pipeline first (outside the push lock — the
        # drain keeps delivering) so the flush fires don't queue behind
        # in-flight ingest steps and their latency stays steady-state.
        ph("ingest.throttle")
        for op in self._ops.values():
            if hasattr(op, "quiesce"):
                op.quiesce()
        for sid in self.plan.sources:
            self._out_wm[sid] = _FINAL
        self._t_input = ph("wm.advance")
        self._advance_time(final=True)
        # the loop is over: what follows waits for the drain and commits
        self._loop_wall_s = self.phases.stop() - self._t_loop
        self._flush_emits()
        # a savepoint requested after the last batch boundary must still
        # land (bounded inputs can finish before the next loop pass)
        self._maybe_take_savepoint()
        if self._coordinator is not None and interval_ms > 0:
            # final epoch commit for 2PC sinks (completes any pending
            # background checkpoint first). Its freeze, and the wait for
            # its persist, are the loop thread's too: its leaves extend
            # the loop's wall, so they still sum to it
            t_last = self.phases.phase("ingest.checkpoint")
            self.checkpoint_now()
            self._loop_wall_s += self.phases.stop() - t_last
        else:
            # bounded job WITHOUT checkpointing: transactional sinks
            # still owe a final commit — end of input is the terminal
            # barrier and the run either completes whole or replays
            # whole, so commit-at-end preserves exactly-once (ref:
            # StreamTask.endInput → final checkpoint committing
            # pending transactions even with checkpointing disabled).
            self._commit_final_epoch()
        return self._finish_run(job_name, drain)

    def _source_offset_committers(self):
        """One commit-round fn per source that publishes externally
        visible committed offsets (log.LogSource consumer groups):
        called with the checkpoint id AFTER the checkpoint is durable,
        with the replay positions FROZEN at this barrier — the group
        floor can never outrun the checkpoint that proves the rows
        were consumed exactly once."""
        fns = []
        for sid in self.plan.sources:
            src = self.plan.node(sid).source
            if src is None or not hasattr(src, "commit_offsets"):
                continue
            frozen = dict(self._positions.get(sid, {}))

            def _commit(cid, _src=src, _frozen=frozen):
                _src.commit_offsets(cid, _frozen)

            fns.append(_commit)
        return fns

    def _commit_final_epoch(self) -> None:
        """2PC sinks' terminal commit for a bounded run without
        checkpointing — end of input is the terminal barrier. The epoch
        id must not collide with ANY earlier run's ids in a reused sink
        directory (a replayed id silently drops this run's staged
        output as "already committed") — a ms timestamp is unique
        across runs and above any coordinator-numbered epoch. Consumer-
        group sources publish their final offsets under the same
        terminal barrier (the run completes whole or replays whole)."""
        final_epoch = int(time.time() * 1000)
        for n in self.plan.nodes.values():
            if n.kind == "sink" and hasattr(n.sink, "prepare_commit"):
                n.sink.prepare_commit(final_epoch)
                n.sink.notify_checkpoint_complete(final_epoch)
        if getattr(self, "_positions", None):
            for fn in self._source_offset_committers():
                fn(final_epoch)

    def _finish_run(self, job_name: str, drain) -> "JobResult":
        """Shared happy-path epilogue of both runtime modes: stop the
        drain, close sinks/ops/servers, fold counters into the
        JobResult."""
        from flink_tpu.api.environment import JobResult

        self._hurry_drain()   # a stop ends the drain's waits at once
        self._emit_q.put(None)
        drain.join()
        self._flush_req.clear()
        self._emit_q = None
        self._check_drain_error()
        for n in self.plan.nodes.values():
            if n.kind == "sink":
                n.sink.close()
        for nid, op in self._ops.items():
            if self.plan.node(nid).kind == "async_io":
                op.close()
        if self._metrics_server is not None:
            self._metrics_server.close()
        for nid, op in self._ops.items():
            for counter in ("late_records", "records_dropped_full",
                            "exchange_overflow", "records_spilled",
                            "refire_ends"):
                if hasattr(op, counter):
                    self.metrics[counter] = (
                        self.metrics.get(counter, 0) + getattr(op, counter))
        self.metrics.update(self._exchange_metrics())
        # keyed state's comings and goings (WindowOperator
        # .state_counters): slots handed out, reused, released; keys
        # live now and at most; the most slots the reuse rule held back
        for op in self._ops.values():
            if hasattr(op, "state_counters"):
                for k, v in op.state_counters().items():
                    self.metrics[k] = self.metrics.get(k, 0) + v
        if self.metrics.get("state.slots_allocated"):
            # allocations served from a released slot, as a share
            self.metrics["state.reuse_share"] = (
                self.metrics["state.slots_reused"]
                / self.metrics["state.slots_allocated"])
        final = dict(self.metrics)
        final.update(self.registry.snapshot())
        # the per-phase breakdown (dispatch/throttle/drain/advance/fire)
        # under the ONE shared accounting (phase_breakdown)
        for k, v in self.phase_breakdown().items():
            final[f"profile.phase.{k}"] = round(v, 6)
        # the leaves themselves: seconds, count and longest interval
        # each, and the run's longest — every value a number
        final["profile.phase.loop_wall_s"] = round(self._loop_wall_s, 6)
        leaves = self.phases.snapshot()
        for leaf, st in leaves.items():
            final[f"profile.phase.{leaf}"] = round(st["seconds"], 6)
            final[f"profile.phase.{leaf}.n"] = st["count"]
            final[f"profile.phase.longest_ms.{leaf}"] = st["longest_ms"]
        if self._coordinator is not None:
            # per-checkpoint values are these over checkpoint.completed
            freeze_s, persist_s = (
                sum(leaves[n]["seconds"] for n in names if n in leaves)
                for names in (CHECKPOINT_FREEZE_LEAVES,
                              CHECKPOINT_PERSIST_LEAVES))
            final["checkpoint.freeze_s"] = round(freeze_s, 6)
            final["checkpoint.persist_s"] = round(persist_s, 6)
            if self._loop_wall_s > 0:
                final["checkpoint.loop_share"] = freeze_s / self._loop_wall_s
        worst = max(leaves.values(), key=lambda st: st["longest_ms"],
                    default={"longest_ms": 0.0, "longest_at_s": 0.0})
        final["profile.phase.longest_ms"] = worst["longest_ms"]
        final["profile.phase.longest_at_s"] = worst["longest_at_s"]
        # what the loop lost to the drain's delivery: its waits for
        # _push_lock, which lie inside whichever leaf was open, and how
        # often it took the lock at all (a node the drain's delivery
        # can enter too, an in-line delivery): a job total, and once
        # more where the benchmark's detail line reads
        final["profile.phase.push_wait_s"] = round(
            self._loop_push.waited_s, 6)
        final["push.loop_lock_takes"] = final[
            "profile.phase.push_lock_takes"] = self._loop_push.takes
        # the watermark passes that fired or purged, and those that went
        # ahead of their batch: once more where the benchmark's detail
        # line reads (no dot in the name: a leaf's has one)
        final["profile.phase.wm_advances"] = self.metrics["wm.advances"]
        final["profile.phase.wm_advances_led"] = self.metrics[
            "wm.advances_led"]
        # one level below the leaves (PhaseClock.detail): seconds under
        # profile.detail.<leaf>/<name> and nothing else there, so a
        # pattern over the prefix sums seconds
        for key, st in self.phases.details().items():
            final[f"profile.detail.{key}"] = round(st["seconds"], 6)
            final[f"profile.detail_n.{key}"] = st["count"]
            final[f"profile.detail_longest_ms.{key}"] = st["longest_ms"]
        final["trace.fires"] = self.fire_records()
        if self._profiler is not None:
            summary = self._profiler.close()
            if summary is not None:
                final["profile.trace_summary"] = summary
        for nid, op in self._ops.items():
            for k, v in getattr(op, "prof", {}).items():
                final[f"profile.op{nid}.{k}"] = final.get(
                    f"profile.op{nid}.{k}", 0.0) + v
                if k in ("scan_pane_moves", "scan_ranges",
                         "assign_records", "assign_memo_hits",
                         "fires", "fires_direct"):
                    # once more beside the leaf they explain: whatever
                    # reads profile.phase.* (the benchmark's detail
                    # line) then shows whether
                    # window.key_scan ran on the pane cursor's cheap
                    # path, over how many record ranges at once, how
                    # many of the general lane's records the directory's
                    # memo served, and how many of the top-n fires read
                    # their window's live columns
                    final[f"profile.phase.{k}"] = final.get(
                        f"profile.phase.{k}", 0.0) + v
        return JobResult(job_name, final)

    def _exchange_metrics(self) -> Dict[str, float]:
        """What the mesh's keyed exchange did over the job, summed over
        the operators that ran on the mesh (``exchange_stats``); empty
        when none did. ``exchange_chunks`` (sharded steps dispatched),
        ``exchange_upload_bytes``, ``exchange_entries`` (valid entries
        handed to the all_to_all: a record each, or a pre-aggregated
        pair where the batch took the pair lane),
        ``exchange_records.<d>`` (records mesh
        device ``d`` received) with ``exchange_records_max`` / ``_mean``
        and their ratio ``exchange_shard_skew``, and the gauge
        ``exchange_devices_idle``: mesh devices that held no pane-state
        rows or received no record, 0 on a sound run."""
        meshed = [st for st in (
            op.exchange_stats() for op in self._ops.values()
            if hasattr(op, "exchange_stats")) if st is not None]
        if not meshed:
            return {}
        records = sum(st["records"] for st in meshed)
        out: Dict[str, float] = {
            "exchange_devices_idle": sum(
                int(np.count_nonzero((st["records"] == 0)
                                     | (st["state_rows"] == 0)))
                for st in meshed),
            "exchange_chunks": sum(st["chunks"] for st in meshed),
            "exchange_upload_bytes": sum(
                st["upload_bytes"] for st in meshed),
            "exchange_entries": sum(st["entries"] for st in meshed),
            "exchange_records_max": int(records.max()),
            "exchange_records_mean": float(records.mean())}
        for d, n in enumerate(records):
            out[f"exchange_records.{d}"] = int(n)
        if records.sum() > 0:
            out["exchange_shard_skew"] = float(
                records.max() / records.mean())
        return out

    # -- bounded execution (execution.runtime-mode=batch) ----------------
    def _run_batch(self, job_name: str, srcs, drain) -> "JobResult":
        """Wave-ordered bounded execution (SURVEY §3.6/§3.7): stages
        run in the topological order the compiler leveled them into
        (runtime/scheduler.py BatchStageScheduler); every blocking edge
        materializes in full as columnar partition files
        (exchange/blocking.py) before its consumer starts; stateful
        operators fire exactly ONCE, at end-of-input — no per-step fire
        scans, which is the mode's entire performance case on bounded
        inputs."""
        from flink_tpu.config import ExecutionOptions
        from flink_tpu.exchange.blocking import BlockingShuffle
        from flink_tpu.runtime.scheduler import BatchStageScheduler

        cfg = self.config
        # re-execution exactly-once: a crashed prior attempt (kill -9
        # skips run()'s cleanup) may have left staged rows in reused
        # sink directories; this run must commit ONLY its own output
        self._abort_sinks()
        sched = BatchStageScheduler(self.plan)
        shuffle = BlockingShuffle(
            str(cfg.get(ExecutionOptions.BATCH_SHUFFLE_DIR)), job_name,
            n_partitions=int(cfg.get(
                ExecutionOptions.BATCH_SHUFFLE_PARTITIONS)),
            cleanup=bool(cfg.get(ExecutionOptions.BATCH_SHUFFLE_CLEANUP)))
        # every writer opens up front and stays open across waves (a
        # union may merge wave-0 and wave-1 producers into one blocking
        # edge); an edge seals exactly when its CONSUMER's wave starts —
        # by then every producer wave has finished
        for u, v in self.plan.blocking_edges:
            self._batch_capture[(u, v)] = shuffle.open_edge(
                u, v, key_field=self._edge_key_field(u, v))
        t0 = time.perf_counter()
        try:
            for stage in sched.waves:
                self._batch_reject_savepoint()
                for u, v in stage.in_edges:
                    self._batch_capture.pop((u, v)).seal()
                sched.start(stage)
                if stage.index == 0:
                    for sid in stage.heads:
                        self._batch_drain_source(sid, srcs[sid], job_name)
                else:
                    for v in stage.heads:
                        self._batch_feed_head(v, stage, shuffle,
                                              job_name)
                self._batch_finalize_wave(stage)
                sched.finish(stage)
            self.metrics["shuffle_bytes_spooled"] = shuffle.bytes_written
            self.metrics["shuffle_rows_spooled"] = shuffle.rows_spooled
            self.metrics["batch_waves"] = len(sched.waves)
            # a request armed DURING the last wave must fail too —
            # the streaming path covers this window with its post-loop
            # _maybe_take_savepoint; returning FINISHED while the
            # requester waits forever would be the silent alternative
            self._batch_reject_savepoint()
        finally:
            self._batch_capture = {}
            shuffle.close()
        self._loop_wall_s = self.phases.stop() - self._t_loop
        self._commit_final_epoch()
        self.metrics["batch_wall_s"] = round(time.perf_counter() - t0, 3)
        return self._finish_run(job_name, drain)

    def _batch_reject_savepoint(self) -> None:
        """The runner rejects savepoint triggers for jobs without
        checkpoint storage (which batch jobs are), so only a direct
        caller can arm the request — fail loudly rather than leave the
        requester waiting on a completion that can never come."""
        if (self._savepoint_request is not None
                and self._savepoint_request.is_set()):
            raise ValueError(
                "savepoints are not supported in "
                "execution.runtime-mode=batch (nothing checkpoints; "
                "recovery is re-execution)")

    def _edge_key_field(self, u: int, v: int) -> Optional[str]:
        """Key column routing a blocking edge's partition files (None =
        single partition). Join edges key on their side's column."""
        n = self.plan.node(v)
        if n.kind == "join":
            t = n.window_transform
            return t.left_key if u == n.left_input else t.right_key
        if n.kind in ("window", "session", "count_window", "process",
                      "cep", "evicting_window", "global_agg"):
            return n.key_field
        return None  # window_all / async_io / broadcast_connect

    def _batch_drain_source(self, sid: int, d, job_name: str) -> None:
        """Wave 0: run one source's splits to exhaustion, pushing every
        batch through its stage's pipelined (stateless) chain — and
        into blocking-edge spools at the stage boundary. No watermark
        propagation per batch: time only moves at the wave finalize."""
        ph = self.phases.phase
        for split_ix in sorted(d):
            it = d[split_ix]
            while True:
                if self._cancel is not None and self._cancel.is_set():
                    raise JobCancelledError(job_name)
                ph("ingest.source_wait")
                nxt = next(it, None)
                self._t_input = ph("ingest.route")
                if nxt is None:
                    break
                data, ts = nxt
                ts = np.asarray(ts, np.int64)
                self.metrics["records_in"] += len(ts)
                self.metrics["batches"] += 1
                self._push_downstream(
                    sid, (dict(data), ts, np.ones(len(ts), bool)))
                self._throttle_ops()
                ph("ingest.bookkeeping")
                self._advance_position(sid, split_ix, data, ts)
                self._eps_meter.mark(len(ts))
                if len(ts):
                    self._max_ts[sid] = max(self._max_ts[sid],
                                            int(ts.max()))
                self._check_drain_error()
        self._out_wm[sid] = _FINAL

    def _batch_feed_head(self, v: int, stage, shuffle,
                         job_name: str) -> None:
        """Replay a stage head's sealed input partitions into the
        operator. Broadcast state builds fully before the main input
        (the batch BroadcastState discipline); join feeds left then
        right (watermark-blind until the wave finalize, so side order
        is semantics-free)."""
        n = self.plan.node(v)
        # the scheduler's in_edges are the single source of truth for
        # which partitions exist (the seal loop used the same list)
        edges = [(u2, v2) for u2, v2 in stage.in_edges if v2 == v]
        if n.kind == "broadcast_connect":
            edges.sort(key=lambda e: 0 if e[0] == n.right_input else 1)
        elif n.kind == "join":
            edges.sort(key=lambda e: 0 if e[0] == n.left_input else 1)
        op = self._ops.get(v)
        for u, _ in edges:
            for data, ts in shuffle.edge(u, v).read():
                if self._cancel is not None and self._cancel.is_set():
                    raise JobCancelledError(job_name)
                self._t_input = self.phases.phase("ingest.route")
                self.metrics["shuffle_records_replayed"] = (
                    self.metrics.get("shuffle_records_replayed", 0)
                    + len(ts))
                self._push(v, (data, ts, np.ones(len(ts), bool)),
                           from_node=u)
                if n.kind == "async_io":
                    # keep enrichment results flowing mid-stage —
                    # nothing else polls between wave finalizes
                    for b in op.poll():
                        self._push_downstream(v, b)
                self._throttle_ops()
                self.phases.phase("ingest.bookkeeping")
                self._check_drain_error()

    def _batch_finalize_wave(self, stage) -> None:
        """End-of-input for one wave: quiesce its device pipelines,
        then ONE final watermark pass over exactly this wave's nodes —
        the single fire scan of the whole bounded run for each stateful
        op — and barrier the emit drain so fires are fully delivered
        (and captured into downstream blocking edges) before the wave
        is declared finished."""
        only = set(stage.nodes)
        self.phases.phase("ingest.throttle")
        for nid in only:
            op = self._ops.get(nid)
            if op is not None and hasattr(op, "quiesce"):
                op.quiesce()
        self._t_input = self.phases.phase("wm.advance")
        self._advance_time(final=True, only=only)
        self.phases.phase("ingest.drain_wait")
        self._flush_emits()
        self.phases.phase("ingest.bookkeeping")

    def _push_source_chunk(self, sid: int, data_c, ts_c) -> None:
        """Push ONE ingest chunk downstream (the hot-loop body):
        link-quiet handshake, the push and the loop's own counters,
        backpressure wait. Under no lock: the operators the records
        reach are this thread's alone, and ``_push`` takes
        ``_push_lock`` itself where the drain's delivery can enter too
        (``_drain_reach``), so a fired row is delivered while its
        successor batch is keyed, packed and uploaded."""
        ph = self.phases.phase
        # yield the transport to a drain fetch in progress (see
        # _link_lock): blocks only while one is active
        ph("ingest.link_wait")
        with self._link_lock:
            pass
        ph("ingest.route")   # the operator below opens its window.* phases
        valid = np.ones(len(ts_c), bool)
        self.metrics["records_in"] += len(ts_c)
        self.metrics["batches"] += 1
        self._push_downstream(sid, (dict(data_c), ts_c, valid))
        self._throttle_ops()
        ph("ingest.bookkeeping")

    def _note_max_ts(self, sid: int, split_ix: int, ts: np.ndarray) -> bool:
        """A batch's newest timestamp, told to its split's watermark
        generator and the lag gauge. -> whether it has a record."""
        if not len(ts):
            return False
        mx = int(ts.max())
        self._max_ts[sid] = max(self._max_ts[sid], mx)
        self._wm_gens[sid][split_ix].on_batch(mx)
        self._wm_lag.set(mx - self._out_wm[sid])
        return True

    def _lead_advance(self, sid: int, splits_alive, ts: np.ndarray) -> None:
        """ADVANCE FIRST: the watermark pass that a batch implies,
        made ahead of the batch's push wherever the two commute, so the
        windows the batch completes fire when it ARRIVES and not after
        its ~10^6 records have been keyed, packed and uploaded. The
        reference emits the watermark a record implies behind that
        record, not behind a million later ones; batch-then-advance is
        an artefact of the microbatch.

        The order is read off what the loop can see: every operator the
        records reach (``_find_lead_ops``) says yes for THIS batch
        (``lead_advance``: its advance and the batch are launches of
        their own, the advance would fire or purge something, and no
        record is stamped at or below the watermark; for sessions, which
        take a record in up to a whole gap behind their last, nor at the
        millisecond above it). Anything else (a
        disordered or late batch, a batch that ends no window) takes
        today's order: the pass after the push, where this one then
        finds nothing left to do."""
        self._recombine_source_wm(sid, splits_alive)
        wm = self._out_wm[sid]
        if not all(op.lead_advance(wm, ts) for op in self._lead_ops):
            return
        self.phases.phase("wm.advance")
        self._advance_time(led=True)
        self.phases.phase("ingest.bookkeeping")

    def _throttle_ops(self) -> None:
        self.phases.phase("ingest.throttle")
        for op in self._ops.values():
            if hasattr(op, "throttle"):
                op.throttle()

    def _recombine_source_wm(self, sid: int, splits_alive) -> None:
        """Source watermark = min over ALIVE split generators (a
        lagging split must hold it back); exhausted splits drop out.
        Combines run over OWNED splits only — an enumerator-assigned
        subset must not let never-advancing foreign splits pin the
        watermark at the floor."""
        gens = [self._wm_gens[sid][i] for i in splits_alive]
        owned = self._owned_splits.get(sid) or []
        if gens:
            self._out_wm[sid] = min(g.current() for g in gens)
        elif owned:
            self._out_wm[sid] = min(
                self._wm_gens[sid][i].current() for i in owned)

    def _advance_position(self, sid: int, split_ix: int, data, ts) -> None:
        """One consumed source batch: the SOURCE defines what the next
        replay position is (api/sources.py position_after — batch
        count by default; record OFFSETS for offset-addressed sources
        like log.LogSource, so a restore resumes mid-partition)."""
        src = self.plan.node(sid).source
        pos = self._positions[sid][split_ix]
        self._positions[sid][split_ix] = src.position_after(pos, data, ts)

    # -- data plane ------------------------------------------------------
    def phase_breakdown(self) -> Dict[str, float]:
        """Cumulative per-phase wall seconds of this run — ONE
        accounting shared by the JobResult (``profile.phase.*``) and
        the web-UI backpressure gauge. Each phase is the sum of the
        leaves of the run's phase clock that ``PHASE_LEAVES`` gives it:
          source   — waiting for the source iterator's next()
          dispatch — link-quiet wait, routing down to the operator, host
                     keying, packing, upload and the step's launch
          throttle — backpressure waits
          drain    — emit-ring / pack fetches, whichever thread makes them
          advance  — watermark propagation outside the fire, and the
                     purge's host side: keys released, slots returned
          fire     — the operator's advance_watermark: fire-list header
                     and the fire / fused-step launch"""
        snap = self.phases.snapshot()
        return {k: sum(snap[leaf]["seconds"] for leaf in leaves
                       if leaf in snap)
                for k, leaves in PHASE_LEAVES.items()}

    def live_metrics(self) -> Dict[str, Any]:
        """Racy-read live counters for the heartbeat-carried job
        metrics (cluster web UI gauges; ref: the TaskManager metric
        report feeding the REST vertices/backpressure endpoints)."""
        ph = self.phase_breakdown()
        # the gauges read the SAME phase accounting as the artifacts
        # (phase_breakdown), split per THREAD so each busy fraction is
        # a share of one thread's wall: backpressure = the INGEST
        # loop's waits (throttle + advance bookkeeping, so advance
        # stalls are visible too); the
        # drain thread's link-held time is its own gauge — folding it
        # into the ingest fraction would read ~100% backpressure on a
        # healthy pipeline whose drain merely holds the link.
        tw = ph["throttle"] + ph["advance"]
        dw = ph["drain"]
        now = time.perf_counter()
        last_t, last_w, last_d = getattr(
            self, "_lm_prev", (now - 1e-9, tw, dw))
        self._lm_prev = (now, tw, dw)
        # DELTA busy fraction since the previous sample — a cumulative
        # counter over heartbeat age would peg at 100% forever
        span = max(now - last_t, 1e-9)
        bp = max(0.0, min(1.0, (tw - last_w) / span))
        dp = max(0.0, min(1.0, (dw - last_d) / span))
        out: Dict[str, Any] = {
            "records_in": int(self.metrics.get("records_in", 0)),
            "records_out": int(self.metrics.get("records_out", 0)),
            "fired_windows": int(self.metrics.get("fired_windows", 0)),
            "eps": round(self._eps_meter.rate, 1),
            "wm_lag_ms": float(getattr(self._wm_lag, "value", 0.0) or 0),
            "backpressure_pct": round(100 * bp),
            "drain_busy_pct": round(100 * dp),
        }
        if self._coordinator is not None:
            # in-memory stats, NOT a storage listing: this runs on the
            # heartbeat thread every beat — filesystem I/O here could
            # stall liveness on a slow checkpoint store
            out["checkpoints"] = [
                {"id": st.checkpoint_id, "ts": st.trigger_ts_ms,
                 "bytes": st.size_bytes}
                for st in self._coordinator.stats[-3:]]
        return out

    def _push_downstream(self, nid: int, batch: Batch) -> None:
        for d in self.plan.node(nid).downstream:
            self._push(d, batch, from_node=nid)

    def _push(self, nid: int, batch: Batch, from_node: int) -> None:
        """``batch`` into node ``nid``: under ``_push_lock`` where the
        drain's delivery can enter the node too (``_drain_reach``: the
        lock is taken at the first such node on the way down and held
        for what lies below it, which is of the set as well), under no
        lock anywhere else."""
        if nid in self._drain_reach:
            with self._loop_push:
                self._route(nid, batch, from_node)
        else:
            self._route(nid, batch, from_node)

    def _route(self, nid: int, batch: Batch, from_node: int) -> None:
        if self._batch_capture:
            # bounded mode: a blocking edge diverts into its shuffle
            # spool — the consumer sees nothing until its wave replays
            # the sealed partition files (SURVEY §3.7)
            w = self._batch_capture.get((from_node, nid))
            if w is not None:
                w.write(*batch)
                return
        n = self.plan.node(nid)
        data, ts, valid = batch
        if n.kind == "chain":
            for fn in n.fns:
                data, ts, valid = fn(data, ts, valid)
            self._push_downstream(nid, (data, ts, valid))
        elif n.kind == "union":
            self._push_downstream(nid, batch)
        elif n.kind == "async_io":
            op = self._ops[nid]
            ups = self._upstream[nid]
            in_wm = min((self._out_wm[u] for u in ups), default=LONG_MIN)
            op.submit(batch, in_wm)
        elif n.kind == "partition":
            # single local driver = parallelism 1: every strategy is a
            # pass-through here (identical to the reference at p=1). The
            # subtask assignment still runs so round-robin cursors and
            # shuffle streams advance deterministically — the state the
            # multi-runner scheduler consumes (exchange/partitioners.py)
            part = self._partitioners.get(nid)
            if part is None:
                from flink_tpu.exchange.partitioners import make_partitioner

                # node-id seed: stacked shuffles must not correlate
                part = self._partitioners[nid] = make_partitioner(
                    n.partition_strategy, seed=nid)
            if not part.broadcast:
                part.advance(len(batch[1]), 1)  # no allocation at p=1
            self._push_downstream(nid, batch)
        elif n.kind == "window_all":
            op = self._ops[nid]
            dev_data = {k: v for k, v in data.items()
                        if np.asarray(v).dtype != object}
            op.process_batch(ts, dev_data, valid)
        elif n.kind in ("window", "session", "count_window", "process",
                        "cep", "evicting_window", "global_agg"):
            op = self._ops[nid]
            keys = np.asarray(data[n.key_field], np.int64)
            dev_data = {k: v for k, v in data.items()
                        if np.asarray(v).dtype != object}
            # on the drain's thread (a changelog folded into its GROUP
            # BY inside drain.deliver) the fold is a detail of that leaf
            with (self.phases.detail("fold")
                  if threading.get_ident() == self._drain_ident
                  else contextlib.nullcontext()):
                op.process_batch(keys, ts, dev_data, valid)
            if n.kind in ("count_window", "process", "cep",
                          "evicting_window", "global_agg", "session"):
                # these emit per-step, not (only) per-watermark
                # (session: retract-mode -U rows from merges that
                # consumed an already-fired span)
                fired = op.take_fired()
                if fired is not None:
                    self._emit_fired(nid, fired)
        elif n.kind == "keyed_join":
            # the split of the one stream into its two views (the open
            # leaf is ingest.route): which side a row is, and its key
            # from that side's own column
            op = self._ops[nid]
            t = n.window_transform
            side = np.asarray(data[t.side_field])
            left = side == t.left_value
            keys = np.where(left, data[t.left_key], data[t.right_key])
            valid = valid & (left | (side == t.right_value))
            op.process_batch(keys, ts, left, data, valid)
            fired = op.take_fired()
            if fired is not None:
                self._emit_fired(nid, fired)
        elif n.kind == "join":
            op = self._ops[nid]
            t = n.window_transform
            if from_node == n.left_input:
                keys = np.asarray(data[t.left_key], np.int64)
                op.process_left(keys, ts, data, valid)
            else:
                keys = np.asarray(data[t.right_key], np.int64)
                op.process_right(keys, ts, data, valid)
        elif n.kind == "broadcast_connect":
            op = self._ops[nid]
            if from_node == n.right_input:
                op.process_broadcast(ts, data, valid)
            else:
                op.process_main(ts, data, valid)
            fired = op.take_fired()
            if fired is not None:
                self._emit_fired(nid, fired)
        elif n.kind == "sink":
            compact = {k: v[valid] for k, v in data.items()}
            nrec = int(valid.sum())
            if nrec:
                self.metrics["records_out"] += nrec
                n.sink.write(compact)
        else:
            raise AssertionError(f"unroutable node kind {n.kind}")

    # -- time plane ------------------------------------------------------
    def _advance_time(self, final: bool = False, only=None,
                      led: bool = False) -> None:
        """One watermark pass and then, the fired cohorts with the drain
        (``t_queued``), the releases that the purging advances left
        pending (``WindowOperator._defer_release``): the long part of
        such an advance does not hold back the cohort. Under no lock:
        ``advance_watermark`` is the loop's thread's alone, as every
        other write of a key directory; what the pass hands on takes
        ``_push_lock`` where it must (``_emit_fired_sync`` in line,
        ``_push`` for async I/O's results).
        ``led``: the pass goes ahead of the batch that implied its
        watermark (``_lead_advance``)."""
        acted = self._propagate_watermarks(final=final, only=only)
        if acted:
            self.metrics["wm.advances"] += 1
            self.metrics["wm.advances_led"] += led
        for op in self._ops.values():
            if hasattr(op, "run_pending_release"):
                op.run_pending_release()

    def _propagate_watermarks(self, final: bool = False,
                              only=None) -> bool:
        """Advance node watermarks in topo order (the StatusWatermarkValve
        min-over-inputs rule applied at node granularity, ref: streaming/
        runtime/watermarkstatus/StatusWatermarkValve.java). -> whether
        an operator's advance fired rows or moved its purge horizon.

        ``only``: restrict to a node-id set — the batch runtime's
        per-wave finalize (a later wave's still-empty operators must
        not see a final watermark before their input stage ran)."""
        acted = False
        for nid in self.plan.topo_order:
            if only is not None and nid not in only:
                continue
            n = self.plan.node(nid)
            if n.kind == "source":
                continue
            ups = self._upstream[nid]
            in_wm = min(self._out_wm[u] for u in ups) if ups else LONG_MIN
            # count_window is deliberately absent: it is event-time-blind
            # (fires ride process_batch), so advancing it would only
            # queue guaranteed-empty fires through the drain
            if n.kind in ("window", "session", "join", "window_all",
                          "process", "evicting_window"):
                op = self._ops[nid]
                if getattr(op, "uses_processing_time", False):
                    # proc-time windows: the clock, not the event
                    # watermark, drives fires; end of input drains
                    # (fires everything seen — the stop-with-drain
                    # semantics of the reference)
                    if in_wm == _FINAL or final:
                        fired = op.advance_watermark(op.final_watermark())
                    else:
                        fired = op.advance_processing_time()
                    self.phases.phase("wm.advance")
                    acted |= _fired_or_purged(fired)
                    self._emit_fired(nid, fired)
                    self._out_wm[nid] = in_wm
                    continue
                wm = in_wm
                if in_wm == _FINAL:
                    wm = op.final_watermark()
                if wm > op.watermark or final:
                    fired = op.advance_watermark(wm)
                    # the operator opened window.* phases for its fire
                    self.phases.phase("wm.advance")
                    acted |= _fired_or_purged(fired)
                    self._emit_fired(nid, fired)
                # processing-time TIMERS (KeyedProcessFunction) fire on
                # the clock alongside the event-time advance
                adv_proc = getattr(op, "advance_processing_time_timers",
                                   None)
                if adv_proc is not None:
                    fired2 = adv_proc(fire_all=(in_wm == _FINAL or final))
                    if fired2 is not None:
                        self._emit_fired(nid, fired2)
                self._out_wm[nid] = in_wm
            elif n.kind == "async_io":
                op = self._ops[nid]
                final_in = in_wm == _FINAL
                if not final_in:
                    op.note_watermark(in_wm)
                for b in op.poll(drain=final_in):
                    self._push_downstream(nid, b)
                # a watermark must never overtake buffered batches
                self._out_wm[nid] = _FINAL if final_in else op.watermark
            else:
                self._out_wm[nid] = in_wm
        return acted

    def _emit_fired(self, nid: int, fired) -> None:
        """Route fired windows downstream. When the downstream subtree is
        stateless (chains/sinks only), materialization and delivery
        happen on the drain thread — the device→host fetch leaves the
        hot loop, the way the reference hands buffers to Netty's IO
        thread off the mailbox thread (ref: PipelinedSubpartition
        .notifyDataAvailable), and the delivery runs beside the loop's
        next batch: the loop holds ``_push_lock`` only where that
        delivery can reach (``_drain_reach``). Stateful downstream (a
        second window stage) keeps the in-line path so operator state
        is touched by one thread only."""
        cohort = getattr(fired, "cohort", None)
        if cohort is not None:
            # the operator stamped t_fire at its dispatch; the batch that
            # carried the watermark past these ends is the latest handed
            # over (with several splits: an upper bound)
            cohort["op"] = nid
            cohort["t_input"] = self._t_input
            self._fires.append(cohort)
        # t_queued: the cohort leaves the advance that fired it (the
        # clear's dispatch lies between t_fire and here; the release of
        # dead keys comes after, see _advance_time) for the drain, or
        # for the delivery below
        stamp = time.perf_counter()
        if cohort is not None:
            cohort["t_queued"] = stamp
        if (self._emit_q is not None and self._drain_may_deliver(nid)
                and threading.get_ident() != self._drain_ident):
            self._emit_q.put((nid, fired, stamp))
            if not getattr(fired, "rowless", False):
                self._drain_wake.set()
            return
        self._emit_fired_sync(nid, fired, stamp)

    def _emit_fired_sync(self, nid: int, fired, stamp: float,
                         t_push0: Optional[float] = None) -> None:
        """``t_push0``: when the drain took ``_push_lock`` for the poll
        this delivery belongs to. In line (no stamp given) the rows are
        brought to hand first and the lock is taken for the delivery
        alone, where the stamp then lies; a thread that holds it (the
        drain inside its poll: a host GROUP BY's rows leaving in line)
        goes on."""
        ring_origin = getattr(fired, "_ring", False)
        attrs = {"ring": fired._ring_no} if ring_origin else {}
        with self.phases.span("drain.deliver", **attrs):
            out = dict(fired)  # materializes lazy FiredWindows
            with self._loop_push:
                if t_push0 is None:
                    t_push0 = time.perf_counter()
                # the fire cohorts whose rows this delivery makes visible at
                # the sink. Emit-ring fires: every cohort the drain's fetch
                # made host-visible (one poll coalesces several
                # fires; each keeps its OWN dispatch stamp); a pack fire: its
                # own; other operators' emissions have none
                if ring_origin:
                    cohorts = self._ops[nid].take_delivered_fires()
                else:
                    cohorts = [c for c in (getattr(fired, "cohort", None),)
                               if c is not None]
                if "__ts__" in out:
                    # process-function emissions: explicit per-row timestamps
                    ts = np.asarray(out.pop("__ts__"), np.int64)
                    nrec = len(ts)
                else:
                    nrec = len(out.get("window_end", ()))  # windowed schemas
                    # (keyed rows also carry "key"; windowAll rows don't)
                    ts = (np.asarray(out["window_end"], np.int64) - 1
                          if nrec else np.zeros(0, np.int64))
                if nrec:
                    self.metrics["fired_windows"] += nrec
                    for part, part_ts in _minibatches(out, ts):
                        self._push_downstream(
                            nid, (part, part_ts, np.ones(len(part_ts), bool)))
                # latency marker: fire dispatch → delivered at sink (ref:
                # streaming/runtime/streamrecord/LatencyMarker.java), read
                # off the fire records; an emission without one is stamped
                # where it was handed to the drain
                now = time.perf_counter()
                for c in cohorts:
                    c["t_push0"], c["t_sink"] = t_push0, now
                    self._lat_hist.update((now - c["t_fire"]) * 1000.0)
                if nrec and not cohorts and not ring_origin:
                    self._lat_hist.update((now - stamp) * 1000.0)

    def fire_records(self) -> List[Dict[str, Any]]:
        """One record per window end of each fire cohort (the newest
        ``FIRE_RECORDS``): ``op``, ``window_end`` and, on
        ``time.perf_counter()`` and in this order, ``t_input`` (the
        source handed over the batch that carried the watermark past the
        end), ``t_fire`` (fire dispatched), ``t_queued`` (the advance
        that fired it has returned, its release of dead keys still
        pending, and the cohort is handed to the drain),
        ``t_fetch0`` (the drain began to want its rows: its wait for
        their landing began, under no lock), ``t_ready`` (they had
        landed), ``t_fetch1`` (the rows are host arrays), ``t_push0``
        (the delivery holds ``_push_lock``: what lies between is the
        drain's way to the lock and its wait for another delivery or
        for the loop inside a node both threads reach, never for a
        batch's key scan, pack and upload), ``t_sink`` (``sink.write``
        returned). A stamp
        the cohort never reached is ``None``, and so is ``t_queued``
        where an EARLIER poll's fetch took the rows (a newer ring
        version had landed) before the cohort was queued."""
        out = []
        for c in list(self._fires):
            rec = {k: c.get(k) for k in FIRE_STAMPS}
            if (rec["t_queued"] is not None and rec["t_fetch0"] is not None
                    and rec["t_queued"] > rec["t_fetch0"]):
                rec["t_queued"] = None
            out.extend({"op": c.get("op"), "window_end": int(we), **rec}
                       for we in c["window_ends"])
        return out[-FIRE_RECORDS:]

    def _drain_may_deliver(self, nid: int) -> bool:
        """Whether the drain thread may deliver ``nid``'s fired rows:
        nothing stateful below it, or (a device operator that feeds a
        stateful one: the join's changelog into its GROUP BY) below it
        only HOST aggregations of an unwindowed GROUP BY that nothing
        else feeds and whose own rows meet nothing stateful. Such an
        operator's state is touched by pushes alone (no watermark pass
        advances it, no throttle, a snapshot comes after the drain's
        flush), and every push into it enters through ``_push`` at a
        node of ``_drain_reach``, under ``_push_lock`` whichever
        thread makes it: one thread at a time. Its rows leave in the
        same delivery (``_emit_fired`` on the drain's thread is in
        line). The loop's thread holds that lock nowhere else, so
        this rule is also what says where the loop must take it
        (``_find_drain_reach``)."""
        if nid not in self._drain_ok_cache:
            from flink_tpu.ops.global_agg import GlobalAggregateOperator

            ok = self._stateless_downstream(nid)
            if not ok and hasattr(self._ops.get(nid), "emit_ring"):
                ok, stack, seen = True, list(
                    self.plan.node(nid).downstream), set()
                while ok and stack:
                    d = stack.pop()
                    if d in seen:
                        continue
                    seen.add(d)
                    n = self.plan.node(d)
                    if n.kind not in STAGE_HEAD_KINDS:
                        stack.extend(n.downstream)
                    else:
                        ok = (isinstance(self._ops.get(d),
                                         GlobalAggregateOperator)
                              and all(u == nid or u in seen
                                      for u in self._upstream[d])
                              and self._stateless_downstream(d))
            self._drain_ok_cache[nid] = ok
        return self._drain_ok_cache[nid]

    def _stateless_downstream(self, nid: int) -> bool:
        """True iff nothing stateful (window/session/join) is reachable
        below nid — the async-drain safety condition."""
        if nid not in self._stateless_cache:
            seen = set()
            stack = list(self.plan.node(nid).downstream)
            ok = True
            while stack:
                d = stack.pop()
                if d in seen:
                    continue
                seen.add(d)
                # STAGE_HEAD_KINDS is the authoritative stateful set —
                # a stateful node below must keep fires on the loop
                # thread (single-writer operator state)
                if self.plan.node(d).kind in STAGE_HEAD_KINDS:
                    ok = False
                    break
                stack.extend(self.plan.node(d).downstream)
            self._stateless_cache[nid] = ok
        return self._stateless_cache[nid]

    def _drain_entry(self) -> None:
        """Drain-thread trampoline: carries the job's fault scope (a
        session tenant's scoped plan must see this thread as the job's)
        and the fair-drain gate membership across the loop's lifetime."""
        from flink_tpu import faults

        self._drain_ident = threading.get_ident()
        gate = self._drain_gate
        if gate is not None:
            gate.register(self._gate_token)
        try:
            with faults.job_scope(getattr(self, "_fault_scope", None)):
                self._drain_loop()
        finally:
            self._drain_ident = None    # the id may be another thread's next
            if gate is not None:
                gate.unregister(self._gate_token)

    def _drain_loop(self) -> None:
        import queue as _q

        from flink_tpu.ops.window import FiredWindows

        # local refs: an abandoned (timed-out) drain must keep operating
        # on ITS queue and ITS discard cell even after run() nulls
        # self._emit_q / re-arms for a successor run
        emit_q = self._emit_q
        discard = self._drain_discard
        gate = self._drain_gate
        # the drain's waits, between its spans: no leaf is open, so each
        # is a counter (profile.detail.drain/...) and no host event: a
        # thread that waits names no idle gap of the device. The wait
        # for a landing (drain/landing_wait) is counted where it is
        # made, in ops/emit_ring.py
        detail = self.phases.detail
        while True:
            items = [emit_q.get()]
            # an explicit pipeline.emit-defer: the marker ages first. A
            # pending barrier (_flush_req) cancels the wait instantly.
            if self._emit_defer_s > 0 and items[0] is not None:
                wait = self._emit_defer_s - (time.perf_counter()
                                             - items[0][2])
                if wait > 0:
                    with detail("drain/defer"):
                        self._flush_req.wait(wait)
            # opportunistically take the whole backlog: N queued fires
            # materialize in ONE device→host round trip instead of N.
            # A batch in which no marker carries rows has nothing that
            # anyone waits for, and no poll between two announces of a
            # ring could read anything new: the drain holds it, asleep
            # on _drain_wake, until a marker that may carry rows is
            # queued, a barrier, a stop, or the batch is as old as the
            # announce cadence. A loop that fires no window end for many
            # batches (a replay: one end per ~86) then meets the drain
            # once per cadence and not in the gap after every batch.
            while True:
                self._drain_wake.clear()
                while True:
                    try:
                        items.append(emit_q.get_nowait())
                    except _q.Empty:
                        break
                if (self._flush_req.is_set() or not all(
                        i is not None and getattr(i[1], "rowless", False)
                        for i in items)):
                    break
                hold = self._rowless_hold_s - (time.perf_counter()
                                               - items[0][2])
                if hold <= 0:
                    break
                with detail("drain/hold"):
                    self._drain_wake.wait(hold)
            stop = any(i is None for i in items)
            # aborted run: the attempt's output must never reach sinks —
            # a later attempt may reuse them (exactly-once would break)
            batch = ([] if discard[0]
                     else [i for i in items if i is not None])
            # barrier batches (job end, checkpoint flush) must fetch
            # every enqueued row and wait for it under the locks, as
            # they always have; a periodic one first waits, HOLDING
            # NOTHING the loop can need (no gate turn, no _link_lock, no
            # ring lock, no _push_lock), until the rows of its fires
            # have landed: the device's time after a fire is spent
            # here, and the fetch below is a local read. A barrier or a
            # stop ends that wait at once.
            # Read the flag BEFORE materializing: _flush_emits closes
            # the set-after-read race with a second pinned-marker pass.
            barrier = stop or self._flush_req.is_set()
            try:
                if batch and not barrier:
                    FiredWindows.await_landing(
                        [f for _, f, _ in batch], self._flush_req)
                    barrier = self._flush_req.is_set()
                    if discard[0]:
                        batch = []
                # fair-drain turn: the device fetch — the part that
                # holds the shared device→host link — waits its round-
                # robin turn among co-resident jobs; the host-side
                # decode/push below stays outside the turn
                with contextlib.ExitStack() as link:
                    with detail("drain/link_wait"):
                        if gate is not None:
                            link.enter_context(gate.turn(self._gate_token))
                        link.enter_context(self._link_lock)
                    FiredWindows.materialize_many(
                        [f for _, f, _ in batch], barrier=barrier)
                with detail("drain/push_wait"):
                    self._push_lock.acquire()
                try:
                    t_push0 = time.perf_counter()
                    # re-check under the push lock: the run may have
                    # aborted (and aborted the sinks) while this batch
                    # was wedged in the device fetch above — delivering
                    # it now would pollute a successor attempt's sinks
                    if not discard[0]:
                        for nid, fired, stamp in batch:
                            self._emit_fired_sync(
                                nid, fired, stamp, t_push0)
                finally:
                    self._push_lock.release()
            except BaseException as e:  # surface at the next barrier —
                # a silently-dead drain thread would deadlock join()
                self._drain_error = e
                for _ in items:
                    emit_q.task_done()
                # keep consuming so task_done accounting stays balanced
                while True:
                    it = emit_q.get()
                    emit_q.task_done()
                    if it is None:
                        return
            else:
                for _ in items:
                    emit_q.task_done()
            if stop:
                return

    def _hurry_drain(self) -> None:
        """A barrier or a stop: end every wait of the drain at once."""
        self._flush_req.set()
        self._drain_wake.set()

    def _check_drain_error(self) -> None:
        if self._drain_error is not None:
            e = self._drain_error
            self._drain_error = None
            raise e

    def _flush_emits(self) -> None:
        """Barrier: all enqueued fires fully delivered (checkpoint
        consistency + end-of-job ordering). Ends the drain's wait for a
        landing, and an explicit deferral, for anything in flight."""
        if self._emit_q is not None:
            self._hurry_drain()
            try:
                self._emit_q.join()
                # a drain batch already in flight when the flag was set
                # may have materialized as a periodic (non-barrier)
                # poll, leaving announced-but-unfetched ring rows on
                # device. Requeue one marker per ring operator pinned at
                # its CURRENT version; the flag is still set, so this
                # second pass drains everything.
                from flink_tpu.ops.window import FiredWindows
                extra = False
                for nid, op in self._ops.items():
                    ring = getattr(op, "emit_ring", None)
                    no = ring.pending_marker_no() if ring is not None else 0
                    if no:
                        self._emit_q.put(
                            (nid, FiredWindows(op=op, ring=True, ring_no=no),
                             time.perf_counter()))
                        extra = True
                if extra:
                    self._emit_q.join()
            finally:
                self._flush_req.clear()
        self._check_drain_error()


def _minibatches(out: Dict[str, np.ndarray], ts: np.ndarray):
    """``(rows, their timestamps)`` one mini-batch at a time: a
    changelog's rows carry the number of the mini-batch they left their
    operator in (``records.MINIBATCH_FIELD``, ascending), and one
    delivery may hold several. A stateful consumer then emits once a
    mini-batch, as behind ``table.exec.mini-batch``'s marker. The
    column goes no further."""
    seq = out.pop(MINIBATCH_FIELD, None)
    if seq is None or seq[0] == seq[-1]:
        yield out, ts
        return
    cuts = [0, *(np.flatnonzero(np.diff(seq)) + 1).tolist(), len(seq)]
    for lo, hi in zip(cuts[:-1], cuts[1:]):
        yield {k: v[lo:hi] for k, v in out.items()}, ts[lo:hi]


@dataclasses.dataclass
class _DcnStepState:
    """Per-run mutable state of the cross-host step loop, threaded
    through ``_dcn_consume_step`` so the overlapped and lockstep paths
    share one consume implementation."""

    last_chk: float = 0.0
    pending: Any = None     # persisted-but-uncommitted checkpoint
    pending_id: int = -1
    persisted_id: int = -1  # newest id THIS process holds durably


class _Prefetcher:
    """Pulls source batches ahead on a feeder thread so record
    generation/decode overlaps the main loop's keying + h2d + dispatch
    work (ref: the FLIP-27 SourceReader's split-fetcher threads,
    runtime/source — IO off the processing thread). Exceptions from the
    source surface on the consuming side, at the batch where they
    occurred."""

    def __init__(self, it, depth: int = 2) -> None:
        import queue
        import threading

        self._q: "queue.Queue" = queue.Queue(maxsize=max(1, depth))
        self._it = it
        self._done = False
        self._closed = False
        self._thread = threading.Thread(target=self._feed, daemon=True)
        self._thread.start()

    def _feed(self) -> None:
        try:
            for item in self._it:
                if self._closed:
                    return
                self._q.put(item)
                if self._closed:
                    return
            self._q.put(StopIteration())
        except BaseException as e:  # surfaced on consume
            self._q.put(e)

    def close(self) -> bool:
        """Unblock and join the feeder (failed-run cleanup: a feeder
        left blocked on its full queue would leak one thread + its
        buffered batches per attempt). Returns False when the feeder is
        still alive after a bounded wait — e.g. blocked inside the
        source iterator itself, where only its own completion (gated on
        ``_closed``) can end it; it stays a daemon and delivers nowhere."""
        self._closed = True
        self._done = True
        while True:  # empty the queue so a blocked put() completes
            try:
                self._q.get_nowait()
            except Exception:
                break
        self._thread.join(timeout=1.0)
        # a wrapped iterator with its OWN background work (LogSource
        # segment readahead) must be closed through this prefetcher,
        # or its feeder thread outlives the attempt
        inner_close = getattr(self._it, "close", None)
        if inner_close is not None:
            try:
                inner_close()
            except ValueError:
                pass  # a plain generator still executing on the
                # feeder thread refuses close(); the feeder is ending
                # anyway (_closed is set)
        return not self._thread.is_alive()

    def __iter__(self):
        return self

    def __next__(self):
        if self._done:
            raise StopIteration
        item = self._q.get()
        if isinstance(item, StopIteration):
            self._done = True
            raise StopIteration
        if isinstance(item, BaseException):
            self._done = True
            raise item
        return item


_FINAL = np.iinfo(np.int64).max  # end-of-input marker watermark
