"""Checkpoint coordination — trigger, collect, complete, restore.

ref: runtime/checkpoint/CheckpointCoordinator.java (triggerCheckpoint /
receiveAcknowledgeMessage / restoreLatestCheckpointedStateToAll) and the
task-side SubtaskCheckpointCoordinatorImpl.checkpointState.

TPU-first simplification (SURVEY §6.4): a microbatch step boundary IS a
global barrier — no in-band barrier alignment, no channel state. A
checkpoint is: freeze (source positions, per-operator state snapshots,
watermarks), upload, mark complete, notify sinks to commit their staged
epoch. Exactly-once = replayable sources (positions) + state rollback +
transactional sinks.

Asynchrony (the HeapSnapshotStrategy async-part analogue, SURVEY §6.4):
the in-loop part of a checkpoint is only the FREEZE — sink staging plus
per-operator snapshots whose device leaves are dispatched on-device
clones (no device→host transfer, no serialization). The expensive part
— fetching the clones to host, encoding, writing, fsync — runs on a
background thread via ``trigger_async``; the 2PC commit happens only
after the manifest is durable, applied back on the loop thread when it
polls ``PendingCheckpoint`` (the asynchronous notifyCheckpointComplete
of the reference). Ingest never waits on storage.

Where the time goes is on the run's ``PhaseClock`` (``phases``; the
driver puts its own there), a leaf a part: on the caller's thread
``ingest.checkpoint_stage`` (the sinks stage their epoch) and
``ingest.checkpoint_snapshot`` (the snapshot tree; an operator times its
own parts inside it), on the executor's ``persist.fetch`` (device →
host of the small leaves; a large pane tensor stays on its device as
``DeviceRows``), ``persist.encode`` (blob headers; the arrays stay where
they are) and ``persist.write`` (arrays to the files from their own
buffers, a ``DeviceRows`` fetched piece by piece as it is written,
fsyncs, manifest last, rename; the waits for those pieces are
``persist.fetch`` intervals inside it).
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import pickle
import time
from concurrent.futures import Future
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from flink_tpu.checkpoint.storage import (
    CheckpointHandle, FsCheckpointStorage, ReusedOpState)
from flink_tpu.obs.tracing import PhaseClock


# a two-axis device leaf at least this large on ONE device is laid out
# row-major there, this many rows a trip, and comes to the host piece by
# piece as its blob is written (DeviceRows)
ROW_MAJOR_ON_DEVICE_MIN_BYTES = 64 << 20
_ROW_MAJOR_BLOCK_ROWS = 1 << 18
# under the 32 MB above which glibc maps every allocation anew: a
# piece's host buffer is then memory the one before it gave back
_FETCH_PIECE_BYTES = 8 << 20


@jax.jit
def _row_major(x: jax.Array) -> jax.Array:
    """``x`` ``[rows, cols]`` as the flat array of its C-order
    elements. Block by block: turned in one piece, a tensor of few
    columns passes through a layout that pads them to a tile's 128
    lanes (8.6 GB for 16.8 M rows x 12); a block's share of that is
    134 MB."""
    rows, cols = x.shape
    block = min(_ROW_MAJOR_BLOCK_ROWS, rows)
    whole = rows // block

    def turn(i, out):
        piece = jax.lax.dynamic_slice(x, (i * block, 0), (block, cols))
        return jax.lax.dynamic_update_slice(
            out, piece.reshape(-1), (i * block * cols,))

    out = jax.lax.fori_loop(
        0, whole, turn, jnp.zeros((rows * cols,), x.dtype))
    if rows % block:
        out = jax.lax.dynamic_update_slice(
            out, x[whole * block:].reshape(-1), (whole * block * cols,))
    return out


@functools.partial(jax.jit, static_argnums=2)
def _piece(flat: jax.Array, start, n: int) -> jax.Array:
    return jax.lax.dynamic_slice(flat, (start,), (n,))


class DeviceRows:
    """A large two-axis leaf of a snapshot that STAYS on its device
    until its blob is written, and then crosses piece by piece
    (``blobformat`` asks ``raw_pieces`` for an array's C-order bytes).

    Why not one fetch: it hands back the device's own layout (a TPU
    keeps a pane tensor ``[rows, ring]`` column-major), so the host had
    to turn every byte into C order (numpy, ~0.5 s a 0.8 GB tensor), and
    it lands in 0.8 GB of memory never touched before: 200 k page
    faults and as many pages zeroed, beside a loop that lives on the
    host's memory and stood at half speed meanwhile. Here the device
    lays the leaf out row-major (``_row_major``: some ten ms on a device
    that waits for the host anyway, a second buffer of the leaf's size
    until the blob is written), and pieces of ``_FETCH_PIECE_BYTES``
    are cut from that, fetched one ahead of the one being written, each
    into memory the one before it gave back. The bytes are the ones the
    whole fetch and the host's turn gave."""

    def __init__(self, x: jax.Array) -> None:
        self.shape, self.dtype = tuple(x.shape), np.dtype(x.dtype)
        self.size = int(np.prod(self.shape))
        self.nbytes = self.size * self.dtype.itemsize
        self._flat = _row_major(x)
        # the run's clock, where the coordinator has one: a wait for a
        # piece is a persist.fetch interval of the writing thread
        self.phases: Optional[PhaseClock] = None

    @staticmethod
    def wanted(x: Any) -> bool:
        return (x.ndim == 2 and x.nbytes >= ROW_MAJOR_ON_DEVICE_MIN_BYTES
                and len(x.sharding.device_set) == 1)

    def raw_pieces(self):
        """The C-order bytes as buffers, in order; one that is yielded
        is valid until the next is asked for."""
        n = min(self.size, max(1, _FETCH_PIECE_BYTES // self.dtype.itemsize))
        # the last piece starts early enough to be whole, and its head
        # (bytes the piece before it held) is dropped here
        starts = [min(o, self.size - n) for o in range(0, self.size, n)]

        def ask(i):
            p = _piece(self._flat, starts[i], n)
            p.copy_to_host_async()
            return p

        ahead = ask(0) if starts else None
        for i, start in enumerate(starts):
            cur, ahead = ahead, (ask(i + 1) if i + 1 < len(starts)
                                 else None)
            with (self.phases.span("persist.fetch") if self.phases
                  else contextlib.nullcontext()):
                host = np.asarray(cur)
            yield memoryview(host[i * n - start:].view(np.uint8))


def materialize_snapshot(obj: Any, lazy: Optional[List[DeviceRows]] = None
                         ) -> Any:
    """Recursively fetch device leaves of a frozen snapshot to host.
    Runs on the BACKGROUND thread — the freeze left cloned jax arrays in
    the tree precisely so this transfer leaves the hot loop. With a
    ``lazy`` list, a leaf that ``DeviceRows`` wants becomes one (and is
    appended to the list): it crosses when its blob is written."""
    if isinstance(obj, jax.Array):
        if lazy is not None and DeviceRows.wanted(obj):
            lazy.append(DeviceRows(obj))
            return lazy[-1]
        return jax.device_get(obj)
    if isinstance(obj, dict):
        return {k: materialize_snapshot(v, lazy) for k, v in obj.items()}
    if isinstance(obj, tuple):
        return tuple(materialize_snapshot(v, lazy) for v in obj)
    if isinstance(obj, list):
        return [materialize_snapshot(v, lazy) for v in obj]
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        return dataclasses.replace(obj, **{
            f.name: materialize_snapshot(getattr(obj, f.name), lazy)
            for f in dataclasses.fields(obj)})
    return obj


class PendingCheckpoint:
    """An in-flight async checkpoint: freeze done, persistence running.
    ``complete()`` (loop thread) blocks if needed, then commits the 2PC
    epoch and records stats; ``abandon()`` drops it without committing."""

    def __init__(self, coordinator: "CheckpointCoordinator", cid: int,
                 future: "Future[CheckpointHandle]",
                 commit_fns: List[Callable[[int], None]],
                 t0: float,
                 abort_fns: Optional[List[Callable[[int], None]]] = None,
                 ) -> None:
        self.coordinator = coordinator
        self.checkpoint_id = cid
        self.future = future
        self._commit_fns = commit_fns
        self._abort_fns = list(abort_fns or [])
        self._t0 = t0
        self._end_cell: List[Optional[float]] = [None]

    @property
    def persist_end(self) -> Optional[float]:
        return self._end_cell[0]

    def done(self) -> bool:
        return self.future.done()

    def complete(self) -> CheckpointHandle:
        handle = self.future.result()  # re-raises persistence errors
        for c in self._commit_fns:
            c(self.checkpoint_id)
        # size and persist duration were computed on the BACKGROUND
        # thread (handle fields); the loop-thread commit does no storage
        # I/O — that is the whole point of the async split
        self.coordinator.stats.append(CheckpointStats(
            self.checkpoint_id, int(self._t0 * 1000),
            (self.persist_end - self._t0) * 1000
            if self.persist_end else (time.time() - self._t0) * 1000,
            max(handle.size_bytes, 0)))
        return handle

    def abandon(self) -> None:
        """Drop the in-flight checkpoint without committing, and
        deliver ABORT notifications to the 2PC sinks (ref:
        CheckpointCoordinator.sendAbortedMessages →
        notifyCheckpointAborted): the epoch staged at this barrier
        replays from the previous checkpoint's source positions, so
        its staged transaction may be rolled back durably. Runs on the
        attempt's failure path — a broken abort hook must not mask the
        original failure, so errors are recorded, not raised."""
        self.future.cancel()
        from flink_tpu.obs.tracing import tracer

        for a in self._abort_fns:
            try:
                a(self.checkpoint_id)
            except Exception as e:  # noqa: BLE001 — cleanup best-effort
                with tracer.span("checkpoint.abort-notify-failed",
                                 checkpoint_id=self.checkpoint_id,
                                 error=f"{type(e).__name__}: {e}"):
                    pass


@dataclasses.dataclass
class CheckpointStats:
    """ref: CheckpointStatsTracker — per-checkpoint visibility."""

    checkpoint_id: int
    trigger_ts_ms: int
    duration_ms: float
    size_bytes: int


class CheckpointCoordinator:
    def __init__(self, storage: FsCheckpointStorage) -> None:
        self.storage = storage
        # a driver puts its run's clock here; a bare coordinator's own
        self.phases = PhaseClock()
        self._next_id = 1
        self.stats: List[CheckpointStats] = []

    def trigger(
        self,
        snapshot_fn: Callable[[], Dict[str, Any]],
        commit_fns: List[Callable[[int], None]],
        prepare_fns: List[Callable[[int], None]],
        savepoint: bool = False,
        executor=None,
        abort_fns: Optional[List[Callable[[int], None]]] = None,
    ) -> CheckpointHandle:
        """One full SYNCHRONOUS checkpoint cycle — freeze, persist,
        commit, in the caller's thread (savepoints, final checkpoints,
        tests). The interval path uses ``trigger_async``."""
        pending = self.trigger_async(
            snapshot_fn, commit_fns, prepare_fns,
            executor=executor, savepoint=savepoint, abort_fns=abort_fns)
        return pending.complete()

    def trigger_async(
        self,
        snapshot_fn: Callable[[], Dict[str, Any]],
        commit_fns: List[Callable[[int], None]],
        prepare_fns: List[Callable[[int], None]],
        executor=None,
        savepoint: bool = False,
        abort_fns: Optional[List[Callable[[int], None]]] = None,
    ) -> PendingCheckpoint:
        """Freeze in the caller's thread, persist in the background:
        1. (loop) sinks stage their epoch (prepareCommit)
        2. (loop) freeze: snapshot tree with on-device cloned leaves
        3. (bg)   fetch leaves, serialize, write, manifest last
        4. (loop, via PendingCheckpoint.complete) sinks commit (2PC)
        """
        from flink_tpu.obs.tracing import tracer

        cid = self._next_id
        self._next_id += 1
        t0 = time.time()
        # checkpoint spans (ref: CheckpointStatsTracker's checkpointing
        # spans, SURVEY §6.1):
        # 'checkpoint.freeze' = the sync part stalling the loop,
        # 'checkpoint.persist' = the async upload (persist.fetch,
        # persist.encode, persist.write) — the two durations that matter
        # are separate spans, not one blended number
        phases = self.phases
        with tracer.span("checkpoint.freeze", checkpoint_id=cid,
                         savepoint=savepoint):
            with phases.span("ingest.checkpoint_stage"):
                for p in prepare_fns:
                    p(cid)
            with phases.span("ingest.checkpoint_snapshot"):
                payload = snapshot_fn()
        payload["checkpoint_id"] = cid
        end_cell: List[Optional[float]] = [None]

        def persist() -> CheckpointHandle:
            psp = tracer.span("checkpoint.persist", checkpoint_id=cid)
            try:
                with psp:
                    # the async-upload fault seam: a raise here fails the
                    # persistence future exactly like a dead background
                    # uploader — the loop thread sees it at complete()
                    from flink_tpu import faults

                    faults.fire("checkpoint.upload", exc=OSError,
                                checkpoint_id=cid)
                    from flink_tpu.fs import enospc_retry

                    # the operators' large device leaves stay where
                    # they are until their blob is written (DeviceRows)
                    waiting: List[DeviceRows] = []
                    with phases.span("persist.fetch"):
                        ops = payload.get("operators")
                        mat = materialize_snapshot({
                            k: v for k, v in payload.items()
                            if k != "operators"})
                        if ops is not None:
                            ops = materialize_snapshot(ops, waiting)
                        for rows in waiting:
                            rows.phases = phases
                    if ops is None:
                        # whole-save ENOSPC retry (storage.enospc-
                        # policy=retry): each attempt writes a FRESH
                        # unique tmp dir, so a failed attempt leaves
                        # only sweepable debris — retention freeing
                        # space between attempts is the degrade path
                        with phases.span("persist.write"):
                            h = enospc_retry(lambda: self.storage.save(
                                cid, mat, savepoint=savepoint))
                    else:
                        blobs: Dict[str, Any] = {}
                        reuse: Dict[str, ReusedOpState] = {}
                        op_aux: Dict[str, Dict[str, str]] = {}
                        from flink_tpu.checkpoint import blobformat

                        with phases.span("persist.encode"):
                            for nid, snap in ops.items():
                                if isinstance(snap, ReusedOpState):
                                    reuse[str(nid)] = snap
                                    continue
                                # changelog plane (lsm runs): the files
                                # named here ride as hardlinks, never
                                # through the serializer
                                if isinstance(snap, dict):
                                    aux = snap.pop("__aux_files__", None)
                                    if aux:
                                        op_aux[str(nid)] = aux
                                # self-describing v3 blob, not pickle
                                # (schema evolution; SURVEY §3.1): the
                                # header now, the arrays from their own
                                # buffers when storage writes the file
                                blobs[str(nid)] = blobformat.encode_lazy(
                                    snap)
                        with phases.span("persist.write"):
                            h = enospc_retry(lambda: self.storage.save_v2(
                                cid, mat, blobs, reuse,
                                savepoint=savepoint, op_aux=op_aux))
                    psp.set("bytes", getattr(h, "size_bytes", None))
                    return h
            finally:
                end_cell[0] = time.time()

        if executor is None:
            fut: Future = Future()
            try:
                fut.set_result(persist())
            except BaseException as e:  # sync fallback mirrors a bg error
                fut.set_exception(e)
        else:
            fut = executor.submit(persist)
        pend = PendingCheckpoint(self, cid, fut, commit_fns, t0,
                                 abort_fns=abort_fns)
        pend._end_cell = end_cell
        return pend

    def restore_latest(self) -> Optional[Dict[str, Any]]:
        from flink_tpu.obs.tracing import tracer

        h = self.storage.latest()
        if h is None:
            return None
        with tracer.span("restore", path=getattr(h, "path", None)) as sp:
            payload = FsCheckpointStorage.load(h)
            sp.set("checkpoint_id", payload.get("checkpoint_id"))
        self.resume_numbering(payload)
        return payload

    def resume_numbering(self, payload: Dict[str, Any]) -> None:
        """Checkpoint ids must keep increasing across restores — id reuse
        would clobber retained checkpoints and replay 2PC epoch ids
        (ref: CheckpointIDCounter in HA services)."""
        self._next_id = max(self._next_id,
                            int(payload.get("checkpoint_id", 0)) + 1)
