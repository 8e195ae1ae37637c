"""flink_tpu — a TPU-native stateful stream-processing framework.

Capabilities of Apache Flink (reference: kenkenk13/flink), designed from
scratch for JAX/XLA on TPU: keyed event-time windowed dataflows with
exactly-once fault tolerance, where per-key window panes are dense
``(key_shard, pane)`` tensors in HBM, aggregations are vectorized lane
reductions, keyBy repartitioning is an ICI ``all_to_all``, and watermarks
drive batched trigger evaluation on device. See SURVEY.md for the
blueprint and the reference structure this mirrors.
"""

__version__ = "0.1.0"

import os as _os

import jax as _jax

# Event-time is epoch milliseconds (int64) and keys are 64-bit — both
# non-negotiable for a streaming framework, so x64 is enabled globally.
# TPU supports s64 (emulated). No f64 DATA reaches the device: every float
# lane is created as explicit float32 and host float64 inputs are cast at
# the device boundary (records.device_cast). What the lowered programs do
# carry as f64 is weak-typed Python scalars (-inf, 0.0: constants XLA folds
# to f32) and the zero-size placeholder lanes of ops/window.py
# _empty_fired; both compile and run on a v5e.
_jax.config.update("jax_enable_x64", True)


def configure_compile_cache() -> str:
    """Place JAX's persistent compilation cache and return its
    directory. ``JAX_COMPILATION_CACHE_DIR`` decides when it is set
    (JAX reads it itself; nothing is set in code). Otherwise the cache
    goes to ``<checkout>/.jax_cache`` — a FIXED path, because the path
    is part of what a later process must repeat to hit the cache."""
    env = _os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = _os.path.join(
        _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
        ".jax_cache")
    _jax.config.update("jax_compilation_cache_dir", path)
    return path


# every entry point (benchmark/run.py, chip_smoke.py, python -m flink_tpu,
# the cluster runner) imports this package before it compiles anything
configure_compile_cache()

from flink_tpu.config import Configuration
from flink_tpu.records import RecordBatch, Schema

__all__ = ["Configuration", "RecordBatch", "Schema", "__version__"]
