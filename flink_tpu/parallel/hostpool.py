"""Shared host worker-pool plane — the multi-core host operator runtime.

The three host-resident operator paths (session span registry, windowAll
pane fold, host spill store) all serialized on one core. This module
is the shared plane they
scale on: ONE ``HostPool`` per driver, sized by ``host.parallelism``,
handed to every operator that owns host-parallel work. The heavy passes
are numpy-dominated and release the GIL inside C loops, so a thread
pool (no pickling, shared address space) is the right executor shape.

Determinism contract (the measurement/correctness gate):

- ``host.parallelism = 1`` is the EXACT serial path: no executor is
  created, tasks run inline on the caller thread in submission order —
  single-core numbers stay reproducible.
- At any parallelism, ``run_tasks`` returns results in SUBMISSION
  order, so callers combine partials in a schedule-independent order.
  Every client combine is associative and exact on its lane monoids
  (max/min/count always; sums whenever the lane values are exactly
  representable, e.g. integer-valued f32 below 2**24 — the golden
  configs), so parallel results are byte-identical to serial. The one
  place the reduction TREE changes shape is the spill store's chunked
  tree fold, and it is gated on a batch-size floor
  (``host.fold-chunk-records``) with a chunk size that does not depend
  on the worker count.

One client takes the pool's SIZE and not its threads: the count-only
window lane's native key scan (``ops/window.py`` ``_process_batch_fused``
-> ``native/codec.cc`` ``ingest_fused_scan_split``) runs ``parallelism``
record ranges at once on native threads of its own inside one ctypes
call, because tasks here start and end under the GIL: with one Python
thread busy beside them (a source's feeder, the drain) four ranges
submitted through ``run_tasks`` took 53-83 ms a 2^20-record batch on
the chip's host where the one native call took 8.5-9.8 (PERF.md, PR
30). The contract is the same: 1 is the exact serial call, and any
width gives the serial call's bytes.

Fault seam: every task submission passes the registered
``host.pool.task`` fault point (on the CALLER thread, before dispatch,
so per-point invocation indices follow deterministic submission order,
not worker interleaving). The chaos suite drives the sessions and
spill-overflow pipelines through recovery with this point armed at
``host.parallelism = 4``.

Observability: per-task metrics under the ``hostpool`` group —
``tasks_total``, ``task_ms`` (per-task wall), ``parallelism``.

Shared-state discipline (LINTED — ``HOSTPOOL_SHARED_WRITE`` in
analysis/pylints.py walks every ``run_tasks`` call site): a submitted
closure runs on a pool worker thread, so it must either

- **return a partial** and let the caller combine (results come back
  in submission order — the merge discipline every client here uses), or
- **guard shared writes with a lock** — the lint recognizes a
  ``with <...lock...>:`` block by name (the spill store's per-pane
  locks, metrics' ``_lock``), so name your locks ``*lock*``.

An unguarded ``self.total += n`` / ``shared[k] = v`` inside a task
closure is the read-modify-write race PR 5 fixed by hand in
obs/metrics.py — the lint keeps it from coming back.
"""
from __future__ import annotations

import os
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, List, Optional, Sequence

from flink_tpu import faults
from flink_tpu.config import HostOptions

__all__ = ["HostPool"]

# the task-submit fault seam; registered in faults.KNOWN_FAULT_POINTS
TASK_FAULT_POINT = "host.pool.task"


class HostPool:
    """Lifecycle-managed shared worker pool for host-resident operator
    work. One per driver; operators receive it at construction and
    submit batches of independent thunks through ``run_tasks``."""

    def __init__(self, parallelism: int,
                 *, registry: Optional[Any] = None) -> None:
        parallelism = int(parallelism)
        if parallelism < 1:
            raise ValueError(
                f"host.parallelism must be >= 1 (1 = serial path), "
                f"got {parallelism}")
        self.parallelism = parallelism
        # parallelism 1 NEVER creates an executor: the serial path must
        # be exactly the pre-pool code path, thread-free
        self._executor: Optional[ThreadPoolExecutor] = (
            None if parallelism == 1 else ThreadPoolExecutor(
                max_workers=parallelism, thread_name_prefix="hostpool"))
        self._closed = False
        self._tasks = None
        self._task_ms = None
        if registry is not None:
            g = registry.group("hostpool")
            self._tasks = g.counter("tasks_total")
            self._task_ms = g.histogram("task_ms")
            g.gauge("parallelism").set(float(parallelism))

    @classmethod
    def from_config(cls, config, *, registry: Optional[Any] = None
                    ) -> "HostPool":
        """Size from ``host.parallelism`` (declared default:
        ``min(4, os.cpu_count())``). Values < 1 fail loudly here; the
        plan analyzer (HOST_PARALLELISM_INVALID) flags them — and
        oversubscription past ``os.cpu_count()`` — at submit."""
        return cls(int(config.get(HostOptions.PARALLELISM)),
                   registry=registry)

    # -- execution -------------------------------------------------------

    def _timed(self, fn: Callable[[], Any]) -> Any:
        t0 = time.perf_counter()
        try:
            return fn()
        finally:
            if self._task_ms is not None:
                self._task_ms.update((time.perf_counter() - t0) * 1e3)

    def run_tasks(self, fns: Sequence[Callable[[], Any]]) -> List[Any]:
        """Run independent thunks; results in SUBMISSION order (the
        determinism contract's combine order). A task exception
        re-raises the first one by submission index. After ``close``
        (or at parallelism 1) tasks run inline on the caller thread."""
        if not fns:
            return []
        if self._executor is None or self._closed:
            out = []
            for fn in fns:
                faults.fire(TASK_FAULT_POINT)
                if self._tasks is not None:
                    self._tasks.inc()
                out.append(self._timed(fn))
            return out
        futures = []
        try:
            for fn in fns:
                # the fault seam sits at SUBMIT, on the caller thread:
                # injection schedules follow deterministic submission
                # order
                faults.fire(TASK_FAULT_POINT)
                if self._tasks is not None:
                    self._tasks.inc()
                futures.append(self._executor.submit(self._timed, fn))
        except BaseException:
            # a fault at the submit seam must drain what was already
            # dispatched before the error escapes — same no-orphan
            # guarantee as the result loop below: no worker may still
            # be mutating operator state when the caller's recovery
            # path resumes
            for f in futures:
                try:
                    f.result()
                except BaseException:
                    pass
            raise
        out: List[Any] = []
        first_err: Optional[BaseException] = None
        for f in futures:
            try:
                out.append(f.result())
            except BaseException as e:  # keep draining: no orphan task
                # may still be mutating operator state when the caller
                # resumes (recovery re-builds operators, but THIS
                # attempt's teardown must not race its own workers)
                if first_err is None:
                    first_err = e
                out.append(None)
        if first_err is not None:
            raise first_err
        return out

    # -- lifecycle -------------------------------------------------------

    def close(self) -> None:
        """Shut the executor down without waiting (a wedged task must
        not turn job teardown into a hang); later ``run_tasks`` calls
        degrade to the inline serial path."""
        self._closed = True
        if self._executor is not None:
            self._executor.shutdown(wait=False)
            self._executor = None

    def __repr__(self) -> str:  # pragma: no cover
        return f"HostPool(parallelism={self.parallelism})"


def default_parallelism() -> int:
    """The declared default: ``min(4, os.cpu_count())``."""
    return min(4, os.cpu_count() or 1)
