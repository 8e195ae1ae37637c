"""NEXmark query 5 on the generator's own auction ids WITH the suite's
exactly-once checkpointing switched on: the job, records and plain
reference of ``nexmark_q5_large_keys`` (imported, not copied: the
generator and the reference know nothing of checkpoints, so they are the
same code with the guarantee on or off), plus what only a checkpointing
deployment has:

- ``build`` switches the guarantee on: ``execution.checkpointing
  .interval`` = the configuration's ``checkpoint_interval``, and
  ``execution.checkpointing.dir`` = a FRESH directory under the
  machine's temporary space for every job built here, the warm-up jobs
  included, so no job of a process finds another's checkpoints. The
  directory of the job before is removed then (its job has ended); the
  last one's is the probe's to read back and remove
  (``CHECKPOINT_DIRS``; whatever is left goes at exit).
- ``zero_counters`` adds ``checkpoint.failed`` and
  ``checkpoint.aborted``. The harness reads an absent counter as 0,
  which for these would call a guarantee held that nothing counted, so
  ``make_pool`` refuses a program that does not count its checkpoints:
  that program cannot run this configuration, and a run of it ends
  there, before any job is built, with another exit code than 0.
- ``step_shapes`` is ``nexmark_q5_large_keys``'s; the checkpoint's
  device clone takes its shapes from the program's own gauges
  (``readers/trace_roofline_exactly_once.py``).

Checkpoints are taken by the driver's own interval test, persisted by
its executor and completed on its loop: nothing here calls into them.
"""
from __future__ import annotations

import atexit
import shutil
import tempfile
from typing import List, Tuple

from benchmark.configs import nexmark_q5_large_keys as large

SCHEMA = large.SCHEMA
WINDOW_END_FIELD = large.WINDOW_END_FIELD
check = large.check
fire_delay_ms = large.fire_delay_ms
warmup_event_ms = large.warmup_event_ms
step_shapes = large.step_shapes
pane_counts = large.pane_counts

# the checkpoint directories of the jobs built here that still exist,
# oldest first: the last is that of the latest job
CHECKPOINT_DIRS: List[str] = []


def remove_checkpoints(keep_last: bool = False) -> None:
    """Remove the directories of the jobs built here (all but the
    latest's with ``keep_last``)."""
    while len(CHECKPOINT_DIRS) > (1 if keep_last else 0):
        shutil.rmtree(CHECKPOINT_DIRS.pop(0), ignore_errors=True)


atexit.register(remove_checkpoints)


def counts_checkpoints() -> bool:
    """Whether the program counts the checkpoints a job completed,
    failed and abandoned (``JobResult.metrics`` ``checkpoint.*``)."""
    try:
        from flink_tpu.runtime.driver import CHECKPOINT_COUNTERS
    except ImportError:
        return False
    return {"checkpoint.completed", "checkpoint.failed",
            "checkpoint.aborted"} <= set(CHECKPOINT_COUNTERS)


def make_pool(seed: int, n: int, p: dict):
    if not counts_checkpoints():
        raise NotImplementedError(
            "this configuration holds checkpoint.failed and "
            "checkpoint.aborted at 0 and asks for checkpoint.completed "
            ">= 4 in a run; the program in this checkout does not count "
            "its checkpoints (no checkpoint.* job metrics), and the "
            "harness reads an absent counter as 0: the guarantee could "
            "not be held to. It does not support this configuration")
    return large.make_pool(seed, n, p)


def zero_counters(p: dict) -> Tuple[str, ...]:
    return large.zero_counters(p) + ("checkpoint.failed",
                                     "checkpoint.aborted")


def build(env, source, sink, p: dict) -> None:
    from flink_tpu.config import CheckpointingOptions

    remove_checkpoints()    # the job before has ended
    CHECKPOINT_DIRS.append(tempfile.mkdtemp(prefix="q5-exactly-once-"))
    env.config.set(CheckpointingOptions.INTERVAL,
                   int(p["checkpoint_interval"]))
    env.config.set(CheckpointingOptions.DIRECTORY, CHECKPOINT_DIRS[-1])
    large.build(env, source, sink, p)
