"""The jax mesh symbols the package uses, in one import site.

Written for the one installation there is (jax 0.9.0): ``jax.shard_map``
and ``jax.experimental.mesh_utils.create_hybrid_device_mesh`` both exist;
every shard_map consumer (ops/window.py, exchange parity tests)
imports it from here.
"""
from __future__ import annotations

import jax
from jax.experimental.mesh_utils import create_hybrid_device_mesh

shard_map = jax.shard_map


def hybrid_device_mesh(mesh_shape, dcn_mesh_shape, devices):
    """``create_hybrid_device_mesh`` with a reshape fallback: the ICI
    axes (``mesh_shape``) index within a slice, the DCN axes
    (``dcn_mesh_shape``) across slices (SNIPPETS.md [1] — the hybrid
    topology that keeps intra-slice collectives off the slow plane).
    Returns a device ndarray of elementwise shape ``dcn * ici``.

    The jax helper groups devices by process granule; on a
    single-granule fleet (one process's local devices, or the virtual
    CPU mesh) it rejects multi-slice shapes, so a single-granule call
    falls back to a plain C-order reshape, which is exactly the hybrid
    layout when the device list is already slice-major."""
    import numpy as np

    devices = list(devices)
    shape = tuple(d * i for d, i in zip(dcn_mesh_shape, mesh_shape))
    if any(d > 1 for d in dcn_mesh_shape):
        granules = {getattr(d, "process_index", 0) for d in devices}
        if len(granules) > 1:
            return create_hybrid_device_mesh(
                mesh_shape, dcn_mesh_shape, devices)
    return np.asarray(devices, dtype=object).reshape(shape)
