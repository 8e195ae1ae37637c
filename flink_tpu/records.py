"""Record batches — the unit of data flow.

The reference moves one ``StreamRecord`` at a time through
deserializers and operator calls (ref: flink-core/.../api/common/typeutils/
TypeSerializer.java; streaming/runtime/streamrecord/StreamRecord.java).
A TPU cannot afford per-record dispatch: the unit here is a fixed-size
**microbatch** laid out as a struct-of-arrays pytree so every field is a
dense ``(B,)`` array the MXU/VPU can chew on, with a validity mask instead
of a dynamic length (static shapes keep XLA happy).

Schema  ≈ TypeInformation (ref: api/common/typeinfo/TypeInformation.java)
RecordBatch ≈ a buffer's worth of StreamRecords after deserialization.
Strings never reach the device: the host codec hashes/dictionary-encodes
them to int64 ids (ref: the PyFlink Cython coders play this role,
flink-python/pyflink/fn_execution/coder_impl_fast.pyx).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Iterable, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# Timestamps are epoch milliseconds, int64 — same convention as the
# reference (StreamRecord.timestamp). MIN_TS marks "no timestamp".
TS_DTYPE = np.int64
MIN_TS = np.int64(np.iinfo(np.int64).min)

# -- changelog plane: RowKind as a small-int lane -------------------------
# ref: org.apache.flink.types.RowKind — the op type of a changelog row.
# The reference carries it as a header byte on every StreamRecord; here
# it is an ordinary int8 data column (``__op__``) that exists ONLY on
# changelog streams (retract-mode unwindowed aggregation, session-merge
# refires). Insert-only streams carry no ``__op__`` column at all, so
# the plane costs nothing until a retract-producing op creates it.
OP_FIELD = "__op__"
# the number of the mini-batch a changelog row left its operator in
# (ascending within a delivery): the driver hands a stateful consumer
# the rows one mini-batch at a time and drops the column
# (runtime/driver.py _minibatches; ops/join_host.py)
MINIBATCH_FIELD = "__minibatch__"
OP_DTYPE = np.int8
OP_INSERT = 0         # +I  first result for its key
OP_UPDATE_BEFORE = 1  # -U  retraction of the previously emitted row
OP_UPDATE_AFTER = 2   # +U  the replacement row
OP_DELETE = 3         # -D  final deletion for its key
OP_NAMES = ("+I", "-U", "+U", "-D")

# RowKind → accumulation sign: +1 for rows that ADD to a downstream
# fold (+I/+U), -1 for rows that SUBTRACT (-U/-D). Kept as a lookup
# table so both host (numpy) and device (jax take) use the same map.
_OP_SIGNS = (1, -1, 1, -1)


def op_sign(ops) -> np.ndarray:
    """(B,) accumulation signs of an ``__op__`` column (host side)."""
    return np.asarray(_OP_SIGNS, np.int64)[np.asarray(ops, np.int64)]


def is_retraction(ops) -> np.ndarray:
    """(B,) bool — True for -U/-D rows (host side)."""
    ops = np.asarray(ops, np.int64)
    return (ops == OP_UPDATE_BEFORE) | (ops == OP_DELETE)


@dataclasses.dataclass(frozen=True)
class Schema:
    """Named, fixed-dtype record fields (ref: TypeInformation extraction,
    api/java/typeutils/TypeExtractor.java — here schemas are explicit, not
    reflected, because device layouts must be static)."""

    fields: Tuple[Tuple[str, Any], ...]  # (name, numpy dtype)

    @classmethod
    def of(cls, **fields: Any) -> "Schema":
        return cls(tuple((k, np.dtype(v)) for k, v in fields.items()))

    @property
    def names(self) -> Tuple[str, ...]:
        return tuple(n for n, _ in self.fields)

    def dtype(self, name: str) -> Any:
        for n, d in self.fields:
            if n == name:
                return d
        raise KeyError(name)

    def with_field(self, name: str, dtype: Any) -> "Schema":
        return Schema(self.fields + ((name, np.dtype(dtype)),))


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class RecordBatch:
    """A fixed-capacity microbatch of records as struct-of-arrays.

    data: field name → (B,) array.
    timestamps: (B,) int64 event times.
    valid: (B,) bool — padding mask (False rows are holes, never data).
    """

    data: Dict[str, jax.Array]
    timestamps: jax.Array
    valid: jax.Array

    # -- pytree protocol -------------------------------------------------
    def tree_flatten(self):
        names = tuple(sorted(self.data))
        children = tuple(self.data[n] for n in names) + (self.timestamps, self.valid)
        return children, names

    @classmethod
    def tree_unflatten(cls, names, children):
        *field_vals, timestamps, valid = children
        return cls(dict(zip(names, field_vals)), timestamps, valid)

    # -- construction ----------------------------------------------------
    @classmethod
    def from_numpy(
        cls,
        data: Mapping[str, np.ndarray],
        timestamps: np.ndarray,
        valid: Optional[np.ndarray] = None,
        capacity: Optional[int] = None,
    ) -> "RecordBatch":
        """Build from host arrays, padding up to ``capacity``."""
        n = len(timestamps)
        cap = capacity or n
        if n > cap:
            raise ValueError(f"{n} records exceed capacity {cap}")
        v = np.ones(n, dtype=bool) if valid is None else np.asarray(valid, dtype=bool)
        out: Dict[str, np.ndarray] = {}
        for name, arr in data.items():
            arr = device_cast(arr)
            if len(arr) != n:
                raise ValueError(f"field {name}: length {len(arr)} != {n}")
            out[name] = _pad(arr, cap)
        return cls(
            data={k: jnp.asarray(a) for k, a in out.items()},
            timestamps=jnp.asarray(_pad(np.asarray(timestamps, dtype=TS_DTYPE), cap)),
            valid=jnp.asarray(_pad(v, cap)),
        )

    @classmethod
    def empty(cls, schema: Schema, capacity: int) -> "RecordBatch":
        return cls(
            data={n: jnp.zeros((capacity,), dtype=d) for n, d in schema.fields},
            timestamps=jnp.full((capacity,), MIN_TS, dtype=TS_DTYPE),
            valid=jnp.zeros((capacity,), dtype=bool),
        )

    # -- views -----------------------------------------------------------
    @property
    def capacity(self) -> int:
        return int(self.valid.shape[0])

    def num_valid(self) -> jax.Array:
        return jnp.sum(self.valid)

    def field(self, name: str) -> jax.Array:
        return self.data[name]

    def with_data(self, **updates: jax.Array) -> "RecordBatch":
        return RecordBatch({**self.data, **updates}, self.timestamps, self.valid)

    def mask(self, keep: jax.Array) -> "RecordBatch":
        """Narrow validity (filter): rows stay in place, holes appear."""
        return RecordBatch(self.data, self.timestamps, self.valid & keep)

    def to_numpy(self) -> Tuple[Dict[str, np.ndarray], np.ndarray, np.ndarray]:
        return (
            {k: np.asarray(v) for k, v in self.data.items()},
            np.asarray(self.timestamps),
            np.asarray(self.valid),
        )

    def compacted_rows(self) -> Dict[str, np.ndarray]:
        """Host-side: drop padding, return only valid rows (sink path)."""
        data, ts, valid = self.to_numpy()
        out = {k: v[valid] for k, v in data.items()}
        out["__ts__"] = ts[valid]
        return out


def device_cast(arr: np.ndarray) -> np.ndarray:
    """Cast host arrays to device-safe dtypes: float64 → float32 (TPU has
    no f64); integer widths are preserved (s64 is supported)."""
    arr = np.asarray(arr)
    if arr.dtype == np.float64:
        return arr.astype(np.float32)
    return arr


def _pad(arr: np.ndarray, cap: int) -> np.ndarray:
    if len(arr) == cap:
        return arr
    pad_val = MIN_TS if arr.dtype == TS_DTYPE else 0
    out = np.full((cap,) + arr.shape[1:], pad_val, dtype=arr.dtype)
    out[: len(arr)] = arr
    return out


# ---------------------------------------------------------------------------
# Key hashing — the keyBy routing function.
# ---------------------------------------------------------------------------

def hash_keys_device(keys: jax.Array) -> jax.Array:
    """64-bit mix of integer keys, on device (traceable).

    The reference routes by murmur(key.hashCode()) → key group (ref:
    runtime/state/KeyGroupRangeAssignment.assignToKeyGroup). Here the
    same role is a splitmix64 finalizer — cheap on the VPU, good
    avalanche so ``hash % num_shards`` spreads hot key spaces.
    """
    x = keys.astype(jnp.uint64)
    x = (x ^ (x >> 30)) * jnp.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> 27)) * jnp.uint64(0x94D049BB133111EB)
    x = x ^ (x >> 31)
    return x.astype(jnp.int64) & jnp.int64(0x7FFFFFFFFFFFFFFF)


def hash_keys_numpy(keys: np.ndarray) -> np.ndarray:
    """Same mix on host — MUST stay bit-identical to hash_keys_device
    (host routes at ingest; device routes at in-step keyBy). Large
    batches take the C path when the codec library is built (parity
    asserted in tests); the numpy mix below is the fallback and the
    reference definition."""
    if len(keys) >= 4096:
        from flink_tpu.native_codec import hash_keys_native

        out = hash_keys_native(np.ascontiguousarray(keys, np.int64))
        if out is not None:
            return out
    with np.errstate(over="ignore"):
        x = keys.astype(np.uint64)
        x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        x = x ^ (x >> np.uint64(31))
    return (x & np.uint64(0x7FFFFFFFFFFFFFFF)).astype(np.int64)


def hash_string_key(s: str) -> int:
    """Stable 63-bit FNV-1a for string keys, host side (strings never go
    to device; ref role: StringSerializer + key-group hash)."""
    h = np.uint64(0xCBF29CE484222325)
    with np.errstate(over="ignore"):
        for b in s.encode("utf-8"):
            h = np.uint64(h ^ np.uint64(b)) * np.uint64(0x100000001B3)
    return int(h & np.uint64(0x7FFFFFFFFFFFFFFF))
