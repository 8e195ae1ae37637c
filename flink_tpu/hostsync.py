"""Host-side completion waiting for device values, and the host's own
CPU device.

``ready_wait`` observes completion with a cooperative ``is_ready()``
spin and a short sleep instead of parking the thread in
``jax.block_until_ready``: a hot-path wait then costs at most one poll
interval past completion whatever the backend's blocking-wait
granularity is, and the thread stays interruptible. What either form
costs the loop's waits on the current chip: not measured (on the
drain's wait, below, a 2 ms interval cost 2 ms a fired row).

Every hot-path wait in the runtime goes through ``ready_wait``; cold
paths (tests, shutdown) may keep ``block_until_ready``.

``landing_wait`` is the drain thread's wait for a fired row's copy: the
same probe at a finer interval, asleep on an event that a barrier or a
stop sets, so the wait ends with whichever comes first.
"""
from __future__ import annotations

import os
import threading
import time

import jax

# 2ms: well under the per-microbatch budget, far over the ~0.4us cost
# of an is_ready() probe
POLL_S = 0.002


def _probes(x):
    """The ``is_ready`` probes of ``x``'s array leaves."""
    probes = (getattr(leaf, "is_ready", None)
              for leaf in jax.tree_util.tree_leaves(x))
    return [p for p in probes if p is not None]


def ready_wait(x, poll_s: float = POLL_S):
    """Wait until every array leaf of ``x`` is ready, without parking
    the thread on the backend's coarse blocking-wait quantum. Returns
    ``x`` for chaining."""
    try:
        for is_ready in _probes(x):
            while not is_ready():
                time.sleep(poll_s)
    except RuntimeError:
        # deleted/donated buffers surface here; the caller's next
        # use raises the real error with context
        pass
    return x


def landed(x) -> bool:
    """Whether every array leaf of ``x`` is ready now."""
    try:
        return all(is_ready() for is_ready in _probes(x))
    except RuntimeError:
        return True     # a deleted buffer: as ready_wait


# the drain's wait is on a fired row's way to the sink, and the drain
# is not the loop: a probe every 0.2 ms costs that thread ~5 wake-ups
# per ms of device time it waits for, and puts at most 0.2 ms between
# the landing and the read (PERF.md section 6, PR 42: what this and the
# parked form read on the chip)
LANDING_POLL_S = 0.0002


def landing_wait(x, until: threading.Event,
                 poll_s: float = LANDING_POLL_S) -> bool:
    """Wait until every array leaf of ``x`` has landed (``is_ready``:
    the device's work is done and the copy begun at the dispatch is on
    its way; its last bytes, ~0.15 ms for 33 KB, are the read's) or
    ``until`` is set, whichever is first; True where every leaf
    landed. The caller holds no lock: this is where the drain spends
    the device's time."""
    try:
        for is_ready in _probes(x):
            while not is_ready():
                if until.wait(poll_s):
                    return False
    except RuntimeError:
        pass        # a deleted buffer: as ready_wait
    return True


def host_cpu_device():
    """JAX's CPU device on this host, for lane math that must stay off
    the accelerator (session segments, spilled keys: per-batch-tiny
    work that would pay a device round trip each). The CPU backend is
    only there when the platform list admits it — leave
    ``JAX_PLATFORMS`` unset or list it (``tpu,cpu``); under
    ``JAX_PLATFORMS=tpu`` alone this raises, naming the setting."""
    try:
        return jax.local_devices(backend="cpu")[0]
    except RuntimeError as e:
        raise RuntimeError(
            "host-pinned lane math needs JAX's CPU backend beside the "
            "accelerator: leave JAX_PLATFORMS unset or set it to "
            "'tpu,cpu' (found JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS')!r})") from e
