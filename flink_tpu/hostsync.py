"""Host-side completion waiting for device values, and the host's own
CPU device.

``ready_wait`` observes completion with a cooperative ``is_ready()``
spin and a short sleep instead of parking the thread in
``jax.block_until_ready``: a hot-path wait then costs at most one poll
interval past completion whatever the backend's blocking-wait
granularity is, and the thread stays interruptible. What either form
costs on the current chip: not measured.

Every hot-path wait in the runtime goes through ``ready_wait``; cold
paths (tests, shutdown) may keep ``block_until_ready``.
"""
from __future__ import annotations

import os
import time

import jax

# 2ms: well under the per-microbatch budget, far over the ~0.4us cost
# of an is_ready() probe
POLL_S = 0.002


def ready_wait(x, poll_s: float = POLL_S):
    """Wait until every array leaf of ``x`` is ready, without parking
    the thread on the backend's coarse blocking-wait quantum. Returns
    ``x`` for chaining."""
    for leaf in jax.tree_util.tree_leaves(x):
        is_ready = getattr(leaf, "is_ready", None)
        if is_ready is None:
            continue
        try:
            while not is_ready():
                time.sleep(poll_s)
        except RuntimeError:
            # deleted/donated buffers surface here; the caller's next
            # use raises the real error with context
            return x
    return x


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
