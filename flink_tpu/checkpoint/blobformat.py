"""Self-describing checkpoint blob format (format_version 3).

ref: the role of TypeSerializerSnapshot (flink-core/.../api/common/
typeutils/TypeSerializerSnapshot.java) — snapshots must be readable
across code changes and from non-JVM tooling. The v1/v2 payloads were
raw pickle: moving a dataclass field between save and restore, or
reading a savepoint from anything but this exact Python codebase,
broke. v3 is:

    [8B magic b"FTCKPT3\\n"][u32 header_len][header JSON][array section]

The header's ``tree`` mirrors the payload structure as plain JSON with
tagged placeholders; numpy/jax array leaves live in the array section
(raw C-order bytes, 64-byte-aligned offsets, dtype+shape in the
header's ``arrays`` table). Schema evolution = dict-field evolution:
readers use .get with defaults, unknown fields are preserved, and any
tool that can parse JSON + memmap raw arrays can read a savepoint.

Tags (JSON objects with one reserved key):
    {"__nd__": i}                     array-section index i
    {"__tup__": [...]}                tuple
    {"__kdict__": [[k, v], ...]}      dict with non-string keys
    {"__np__": [dtype, value]}        numpy scalar
    {"__bytes__": base64}             bytes
    {"__strs__": [shape, [str, ...]]} all-string object-dtype array
                                      (text columns; no pickle needed)
    {"__panestate__": {...}}          state.keyed.PaneState
    {"__pickle__": base64}            escape hatch for foreign objects
                                      (framework snapshots produce none
                                      — tests assert the counter stays
                                      zero; user-defined operator state
                                      may still need it)
"""
from __future__ import annotations

import base64
import json
import struct
from typing import Any, Dict, List, Tuple

import numpy as np

MAGIC = b"FTCKPT3\n"
_ALIGN = 64


class _Encoder:
    def __init__(self) -> None:
        self.arrays: List[np.ndarray] = []
        self.pickle_escapes = 0

    def enc(self, v: Any) -> Any:
        if v is None or isinstance(v, (bool, int, float, str)):
            return v
        if isinstance(v, bytes):
            return {"__bytes__": base64.b64encode(v).decode()}
        if isinstance(v, np.generic):
            return {"__np__": [str(v.dtype), v.item()]}
        if isinstance(v, np.ndarray):
            # object-dtype arrays have no raw-byte form — np.frombuffer
            # can't decode them, so the array section would produce an
            # unrestorable checkpoint. ALL-STRING object arrays (the
            # common case: text columns from socket/file sources) get a
            # native JSON tag, so they stay readable by foreign tooling
            # AND cross the pickle-rejecting DCN decoder
            # (allow_pickle=False); anything else still takes the
            # counted pickle escape hatch.
            if v.dtype.hasobject:
                flat = v.ravel()
                if all(isinstance(x, str) for x in flat):
                    return {"__strs__": [list(v.shape), list(flat)]}
                import pickle

                self.pickle_escapes += 1
                return {"__pickle__": base64.b64encode(pickle.dumps(
                    v, protocol=pickle.HIGHEST_PROTOCOL)).decode()}
            self.arrays.append(_laid_out(v))
            return {"__nd__": len(self.arrays) - 1}
        # an array that hands over its C-order bytes piece by piece as
        # the blob is written (the coordinator's DeviceRows, still on
        # its device): placed in the array section like any other
        if hasattr(v, "raw_pieces"):
            self.arrays.append(v)
            return {"__nd__": len(self.arrays) - 1}
        # jax arrays (avoid importing jax here for tool-side reuse)
        if type(v).__module__.startswith("jax") and hasattr(v, "dtype"):
            self.arrays.append(_laid_out(np.asarray(v)))
            return {"__nd__": len(self.arrays) - 1}
        if isinstance(v, tuple):
            return {"__tup__": [self.enc(x) for x in v]}
        if isinstance(v, list):
            return [self.enc(x) for x in v]
        if isinstance(v, dict):
            if all(isinstance(k, str) and not k.startswith("__") for k in v):
                return {k: self.enc(x) for k, x in v.items()}
            return {"__kdict__": [[self.enc(k), self.enc(x)]
                                  for k, x in v.items()]}
        pane = _as_panestate_fields(v)
        if pane is not None:
            return {"__panestate__": {k: self.enc(x)
                                      for k, x in pane.items()}}
        import pickle

        self.pickle_escapes += 1
        return {"__pickle__": base64.b64encode(
            pickle.dumps(v, protocol=pickle.HIGHEST_PROTOCOL)).decode()}


def _as_panestate_fields(v: Any):
    from flink_tpu.state.keyed import PaneState

    if isinstance(v, PaneState):
        return {"sums": v.sums, "maxs": v.maxs, "mins": v.mins,
                "counts": v.counts}
    return None


def _laid_out(v: np.ndarray) -> np.ndarray:
    """``v`` as the array section can take it: C-contiguous, or
    column-major (what a device->host fetch of a TPU's pane tensor
    hands back: the device's own layout), which ``EncodedBlob`` turns
    into C order block by block as it writes; any other strides are
    copied here."""
    if v.flags.c_contiguous or (v.ndim >= 2 and v.flags.f_contiguous):
        return v
    # ascontiguousarray promotes 0-d to (1,) — restore the shape
    return np.ascontiguousarray(v).reshape(v.shape)


# a piece handed to a file at once, and the rows turned into C order at
# once (a block that stays in cache while its columns are gathered)
_PIECE_BYTES = 32 << 20
_TURN_ROWS = 1 << 16


class EncodedBlob:
    """A payload encoded but not yet laid out as ONE bytes object: the
    prefix (magic, header length, header JSON) and the arrays the header
    places in the array section. ``write_to`` hands the file each
    array's raw C-order bytes in pieces of at most ``_PIECE_BYTES``: a
    C-contiguous array's own buffer, sliced; a column-major one's rows
    turned into C order in ONE reused buffer; and from an array that
    brings its own ``raw_pieces`` (one still on its device, which
    fetches them as they are asked for) whatever it yields, its
    ``dtype``, ``shape`` and ``nbytes`` in the header. So a checkpoint of a GB of
    state is written without a second copy of it in host memory, and
    without the passes over one (each of them seconds of page faults,
    some with the interpreter lock held) that building the whole blob
    took. ``tobytes`` is the one-object form, byte for byte what
    ``write_to`` writes."""

    def __init__(self, prefix: bytes, arrays: List[np.ndarray],
                 offsets: List[int]) -> None:
        self.prefix, self.arrays, self.offsets = prefix, arrays, offsets
        self.nbytes = len(prefix) + (
            offsets[-1] + arrays[-1].nbytes if arrays else 0)

    @staticmethod
    def _raw(a: np.ndarray):
        """``a``'s C-order bytes as buffers; one that is yielded is
        valid until the next is asked for."""
        if hasattr(a, "raw_pieces"):
            yield from a.raw_pieces()
            return
        if a.flags.c_contiguous:
            flat = a.reshape(-1).view(np.uint8)
            for o in range(0, len(flat), _PIECE_BYTES):
                yield memoryview(flat[o:o + _PIECE_BYTES])
            return
        rows = max(1, _PIECE_BYTES // (a.nbytes // a.shape[0]))
        buf = np.empty((min(rows, a.shape[0]),) + a.shape[1:], a.dtype)
        for i in range(0, a.shape[0], rows):
            block = a[i:i + rows]
            out = buf[:len(block)]
            for j in range(0, len(block), _TURN_ROWS):
                np.copyto(out[j:j + _TURN_ROWS], block[j:j + _TURN_ROWS])
            yield memoryview(out.reshape(-1).view(np.uint8))

    def _pieces(self):
        """The blob as buffers, in file order: the prefix, then per array
        the zero padding up to its aligned offset and its raw C-order
        bytes (an empty array has none)."""
        yield self.prefix
        pos = 0
        for a, off in zip(self.arrays, self.offsets):
            if off > pos:
                yield b"\0" * (off - pos)
            if a.nbytes:
                yield from self._raw(a)
            pos = off + a.nbytes

    def write_to(self, f) -> None:
        for piece in self._pieces():
            f.write(piece)

    def tobytes(self) -> bytes:
        # each piece copied as it comes: the next may reuse its buffer
        return b"".join(bytes(piece) for piece in self._pieces())


def encode_lazy(payload: Any) -> EncodedBlob:
    """Payload tree -> the self-describing v3 blob, arrays by reference
    (the caller must not change them before the blob is written)."""
    e = _Encoder()
    tree = e.enc(payload)
    offsets = []
    pos = 0
    for a in e.arrays:
        pos = (pos + _ALIGN - 1) // _ALIGN * _ALIGN
        offsets.append(pos)
        pos += a.nbytes
    header = json.dumps({
        "tree": tree,
        "arrays": [{"dtype": str(a.dtype), "shape": list(a.shape),
                    "offset": off, "nbytes": a.nbytes}
                   for a, off in zip(e.arrays, offsets)],
        "pickle_escapes": e.pickle_escapes,
    }).encode()
    return EncodedBlob(MAGIC + struct.pack("<I", len(header)) + header,
                       e.arrays, offsets)


def encode(payload: Any) -> bytes:
    """Payload tree → self-describing v3 bytes."""
    return encode_lazy(payload).tobytes()


class _Decoder:
    def __init__(self, arrays: List[np.ndarray],
                 allow_pickle: bool = True) -> None:
        self.arrays = arrays
        self.allow_pickle = allow_pickle

    def dec(self, v: Any) -> Any:
        if isinstance(v, list):
            return [self.dec(x) for x in v]
        if not isinstance(v, dict):
            return v
        if "__nd__" in v:
            return self.arrays[v["__nd__"]]
        if "__tup__" in v:
            return tuple(self.dec(x) for x in v["__tup__"])
        if "__kdict__" in v:
            return {_key(self.dec(k)): self.dec(x)
                    for k, x in v["__kdict__"]}
        if "__np__" in v:
            dt, val = v["__np__"]
            return np.dtype(dt).type(val)
        if "__bytes__" in v:
            return base64.b64decode(v["__bytes__"])
        if "__panestate__" in v:
            from flink_tpu.state.keyed import PaneState

            f = {k: self.dec(x) for k, x in v["__panestate__"].items()}
            return PaneState(sums=f.get("sums"), maxs=f.get("maxs"),
                             mins=f.get("mins"), counts=f.get("counts"))
        if "__strs__" in v:
            shape, items = v["__strs__"]
            a = np.empty(len(items), dtype=object)
            a[:] = items
            return a.reshape(shape)
        if "__pickle__" in v:
            if not self.allow_pickle:
                # network-facing decoders (the DCN exchange) must never
                # unpickle: an attacker-controlled __pickle__ tag is
                # arbitrary code execution on load
                raise ValueError(
                    "__pickle__ escape rejected (allow_pickle=False): "
                    "payload carries a foreign object where only "
                    "framework-built arrays are expected")
            import pickle

            return pickle.loads(base64.b64decode(v["__pickle__"]))
        return {k: self.dec(x) for k, x in v.items()}


def _key(k: Any) -> Any:
    # dict keys must stay hashable after decode; lists decode from JSON
    # arrays, so a tuple key round-trips via __tup__ already
    return k


def read_header(raw: bytes) -> Tuple[Dict[str, Any], int]:
    """Parse just the JSON header without touching the array section.
    Returns (header, array_section_base_offset)."""
    if len(raw) < len(MAGIC) + 4 or raw[:len(MAGIC)] != MAGIC:
        raise ValueError("not a FTCKPT3 blob (bad magic)")
    hstart = len(MAGIC) + 4
    hlen = struct.unpack("<I", raw[len(MAGIC):hstart])[0]
    return json.loads(raw[hstart:hstart + hlen].decode()), hstart + hlen


def decode(raw: bytes, allow_pickle: bool = True) -> Any:
    """v3 bytes → payload tree (arrays are read-only views when the
    input buffer allows zero-copy). ``allow_pickle=False`` rejects the
    ``__pickle__`` escape — required for any decoder fed from the
    network (see exchange/dcn.py)."""
    header, base = read_header(raw)
    arrays: List[np.ndarray] = []
    for spec in header["arrays"]:
        off = base + spec["offset"]
        a = np.frombuffer(raw, dtype=np.dtype(spec["dtype"]),
                          count=int(np.prod(spec["shape"], dtype=np.int64))
                          if spec["shape"] else 1,
                          offset=off).reshape(spec["shape"])
        arrays.append(a)
    return _Decoder(arrays, allow_pickle=allow_pickle).dec(header["tree"])


def is_v3(raw: bytes) -> bool:
    return raw[:len(MAGIC)] == MAGIC
