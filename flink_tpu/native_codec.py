"""Native host codec bindings — build, load, and numpy fallbacks.

ref roles: SURVEY §3.10 item 2 (PyFlink Cython coder fast paths →
C++ record codec + ingest shim). The shared library builds on demand
from ``native/codec.cc`` with the system toolchain; every entry point
has a pure-numpy fallback so the package works unbuilt (the .so is a
fast path, not a dependency).

The token/string hash here is bit-identical to
``records.hash_string_key`` — host-encoded keys and Python-hashed keys
must route to the same key shard.
"""
from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import subprocess
from typing import List, Optional, Tuple

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SRC = os.path.join(_REPO, "native", "codec.cc")

_lib: Optional[ctypes.CDLL] = None
_tried = False
# why the library is not loaded (build stderr / OSError / missing
# symbol); None while untried or once loaded — see unavailable_reason()
_reason: Optional[str] = None


class CodecBuildError(RuntimeError):
    """The codec library could not be built or loaded; the message is
    the compiler's stderr, the loader's OSError or the missing symbol."""


def library_path(src: str = _SRC) -> str:
    """Where the library built from ``src`` lives. The name carries a
    hash of the source's CONTENT, so a library is only ever loaded for
    the exact ``codec.cc`` beside it — a ``.so`` carried over from
    another tree or machine (whatever its mtime) has another name and
    is never picked up."""
    with open(src, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(os.path.dirname(src),
                        f"libflinktpucodec-{digest}.so")


def build_library(src: str = _SRC, cxx: str = "g++") -> str:
    """Path of the shared library built from ``src``, compiling it
    (``cxx -O3``) unless the library named for this source content is
    already there. Raises :class:`CodecBuildError` with the reason."""
    try:
        so = library_path(src)
    except OSError as e:
        raise CodecBuildError(f"cannot read codec source: {e}") from e
    if os.path.exists(so):
        return so
    # compile beside the target and rename into place: a concurrent
    # process never dlopens a half-written file
    tmp = f"{so}.{os.getpid()}.tmp"
    try:
        subprocess.run(
            [cxx, "-O3", "-shared", "-fPIC", "-std=c++17", "-pthread", src,
             "-o", tmp],
            check=True, capture_output=True)
        os.replace(tmp, so)
    except FileNotFoundError as e:
        raise CodecBuildError(f"compiler not found: {e}") from e
    except subprocess.CalledProcessError as e:
        raise CodecBuildError(
            f"{cxx} exited {e.returncode} on {src}:\n"
            + e.stderr.decode("utf-8", "replace")) from e
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    # libraries of earlier source contents are dead weight now
    for old in glob.glob(os.path.join(os.path.dirname(so),
                                      "libflinktpucodec*.so")):
        if old != so:
            try:
                os.unlink(old)
            except OSError:
                pass
    return so


def load_library(so: str) -> ctypes.CDLL:
    """dlopen ``so`` and declare every entry point's signature. Raises
    :class:`CodecBuildError` naming the loader error or the symbol the
    library lacks."""
    try:
        lib = ctypes.CDLL(so)
    except OSError as e:
        raise CodecBuildError(f"cannot load {so}: {e}") from e
    i64p = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
    f32p = np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS")
    try:
        _bind(lib, i64p, f32p)
    except AttributeError as e:
        raise CodecBuildError(f"{so} lacks a symbol: {e}") from e
    return lib


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried, _reason
    if _lib is not None or _tried:
        return _lib
    _tried = True
    try:
        _lib = load_library(build_library(_SRC))
    except CodecBuildError as e:
        # the numpy fallbacks keep an unbuilt package working; the
        # reason stays retrievable (unavailable_reason)
        _reason = str(e)
    return _lib


def unavailable_reason() -> Optional[str]:
    """Why ``native_available()`` is False — the build's stderr, the
    loader's error or the missing symbol; None when the library is
    loaded."""
    _load()
    return _reason


def _bind(lib, i64p, f32p) -> None:
    lib.tokenize_hash.restype = ctypes.c_int64
    lib.tokenize_hash.argtypes = [
        ctypes.c_char_p, ctypes.c_int64, i64p, ctypes.c_int64,
        i64p, i64p, ctypes.c_int64]
    lib.hash_strings.restype = None
    lib.hash_strings.argtypes = [ctypes.c_char_p, i64p, ctypes.c_int64, i64p]
    lib.parse_i64_table.restype = ctypes.c_int64
    lib.parse_i64_table.argtypes = [
        ctypes.c_char_p, ctypes.c_int64, ctypes.c_char, ctypes.c_int64,
        i64p, ctypes.c_int64]
    lib.parse_f32_table.restype = ctypes.c_int64
    lib.parse_f32_table.argtypes = [
        ctypes.c_char_p, ctypes.c_int64, ctypes.c_char, ctypes.c_int64,
        f32p, ctypes.c_int64]
    lib.encode_i64_rows.restype = ctypes.c_int64
    lib.encode_i64_rows.argtypes = [
        i64p, ctypes.c_int64, ctypes.c_int64, ctypes.c_char,
        ctypes.c_char_p, ctypes.c_int64]
    u8p = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")
    lib.ht_new.restype = ctypes.c_void_p
    lib.ht_new.argtypes = [ctypes.c_int64]
    lib.ht_free.restype = None
    lib.ht_free.argtypes = [ctypes.c_void_p]
    lib.ht_count.restype = ctypes.c_int64
    lib.ht_count.argtypes = [ctypes.c_void_p]
    lib.ht_growth.restype = None
    lib.ht_growth.argtypes = [ctypes.c_void_p, i64p]
    lib.ht_lookup.restype = None
    lib.ht_lookup.argtypes = [
        ctypes.c_void_p, i64p, ctypes.c_int64, i64p, u8p]
    lib.ht_insert.restype = None
    lib.ht_insert.argtypes = [ctypes.c_void_p, i64p, i64p, ctypes.c_int64]
    lib.ht_lookup_claim.restype = ctypes.c_int64
    lib.ht_lookup_claim.argtypes = [
        ctypes.c_void_p, i64p, ctypes.c_int64, i64p, i64p]
    lib.ht_assign.restype = None
    lib.ht_assign.argtypes = [
        ctypes.c_void_p, i64p, ctypes.c_int64, i64p, ctypes.c_int64,
        ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, i64p, i64p,
        np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"), i64p, u8p,
        i64p, i64p]
    lib.ht_delete.restype = ctypes.c_int64
    lib.ht_delete.argtypes = [ctypes.c_void_p, i64p, ctypes.c_int64]
    lib.ht_longest_run.restype = ctypes.c_int64
    lib.ht_longest_run.argtypes = [ctypes.c_void_p]
    lib.slot_panes_note.restype = None
    lib.slot_panes_note.argtypes = [ctypes.c_int64, i64p, i64p, u8p, i64p]
    lib.ts_order_stats.restype = None
    lib.ts_order_stats.argtypes = [i64p, ctypes.c_int64, ctypes.c_int64, i64p]
    lib.hash_keys.restype = None
    lib.hash_keys.argtypes = [i64p, ctypes.c_int64, i64p]
    lib.crc32_zlib.restype = ctypes.c_uint32
    lib.crc32_zlib.argtypes = [u8p, ctypes.c_int64, ctypes.c_uint32]
    lib.sr_listen.restype = ctypes.c_void_p
    lib.sr_listen.argtypes = [ctypes.c_int]
    lib.sr_port.restype = ctypes.c_int
    lib.sr_port.argtypes = [ctypes.c_void_p]
    lib.sr_accept.restype = ctypes.c_int
    lib.sr_accept.argtypes = [ctypes.c_void_p, ctypes.c_int]
    lib.sr_read_block.restype = ctypes.c_int64
    lib.sr_read_block.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64, ctypes.c_int]
    lib.sr_close.restype = None
    lib.sr_close.argtypes = [ctypes.c_void_p]
    i32p = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")
    f64p = np.ctypeslib.ndpointer(np.float64, flags="C_CONTIGUOUS")
    lib.preagg_combine.restype = ctypes.c_int64
    lib.preagg_combine.argtypes = [
        ctypes.c_int64, i64p, i64p, u8p, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_int64, f64p, i32p, f64p, i32p, i32p, f32p, ctypes.c_int64]
    lib.nexmark_bids.restype = None
    lib.nexmark_bids.argtypes = [
        ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_int64, ctypes.c_int64, i64p, i64p, f32p]
    lib.ingest_combine.restype = ctypes.c_int64
    lib.ingest_combine.argtypes = [
        ctypes.c_int64, i64p, i64p, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        i32p, i32p, i32p, ctypes.c_int64, i64p, u8p, ctypes.c_int64,
        ctypes.c_int64]
    lib.ingest_fused_scan.restype = ctypes.c_int64
    lib.ingest_fused_scan.argtypes = [
        ctypes.c_int64, i64p, i64p, ctypes.c_void_p, ctypes.c_int64,
        ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        i32p, i32p, ctypes.c_int64, ctypes.c_int64, i64p, u8p,
        ctypes.c_int64, ctypes.c_int64, i64p, ctypes.c_int64]
    lib.ingest_fused_scan_split.restype = ctypes.c_int64
    lib.ingest_fused_scan_split.argtypes = [
        ctypes.c_int64, i64p, i64p, ctypes.c_void_p, ctypes.c_int64,
        ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        i32p, i32p, ctypes.c_int64, i64p, u8p, ctypes.c_int64,
        ctypes.c_int64, i64p, ctypes.c_int64, ctypes.c_void_p,
        ctypes.c_int64, i32p, ctypes.c_int64]
    lib.scan_workers_new.restype = ctypes.c_void_p
    lib.scan_workers_new.argtypes = []
    lib.scan_workers_free.restype = None
    lib.scan_workers_free.argtypes = [ctypes.c_void_p]
    lib.ingest_fused_finalize_u32.restype = None
    lib.ingest_fused_finalize_u32.argtypes = [
        ctypes.c_int64, i32p, i32p, i32p, ctypes.c_int64, ctypes.c_int64]
    lib.ingest_fused_finalize_pairs.restype = None
    lib.ingest_fused_finalize_pairs.argtypes = [
        ctypes.c_int64, i32p, i32p, i32p]
    lib.slot_panes_note_pairs.restype = None
    lib.slot_panes_note_pairs.argtypes = [
        ctypes.c_int64, i32p, ctypes.c_int64, ctypes.c_int64, i64p]


def native_available() -> bool:
    return _load() is not None


def tokenize_hash(lines: List[str]) -> Tuple[np.ndarray, np.ndarray]:
    """Tokenize lines on whitespace → (token_hash_ids, line_index).
    WordCount's ingest hot path (flat_map tokenize + dictionary encode
    in one native pass)."""
    lib = _load()
    if lib is None:
        return _tokenize_hash_numpy(lines)
    enc = [s.encode("utf-8") for s in lines]
    offs = np.zeros(len(enc) + 1, np.int64)
    np.cumsum([len(b) + 1 for b in enc], out=offs[1:])
    buf = b"\n".join(enc) + b"\n"
    cap = max(len(buf), 16)
    ids = np.empty(cap, np.int64)
    line_ix = np.empty(cap, np.int64)
    n = lib.tokenize_hash(buf, len(buf), offs, len(enc), ids, line_ix, cap)
    assert n >= 0
    return ids[:n].copy(), line_ix[:n].copy()


def _tokenize_hash_numpy(lines: List[str]) -> Tuple[np.ndarray, np.ndarray]:
    from flink_tpu.records import hash_string_key

    ids, lix = [], []
    for i, line in enumerate(lines):
        for w in line.split():
            ids.append(hash_string_key(w))
            lix.append(i)
    return np.asarray(ids, np.int64), np.asarray(lix, np.int64)


def hash_strings(strings: List[str]) -> np.ndarray:
    """Dictionary-encode a string column to stable 63-bit ids."""
    lib = _load()
    if lib is None:
        from flink_tpu.records import hash_string_key

        return np.asarray([hash_string_key(s) for s in strings], np.int64)
    enc = [s.encode("utf-8") for s in strings]
    offs = np.zeros(len(enc) + 1, np.int64)
    np.cumsum([len(b) for b in enc], out=offs[1:])
    buf = b"".join(enc)
    out = np.empty(len(enc), np.int64)
    lib.hash_strings(buf, offs, len(enc), out)
    return out


def parse_i64_table(data: bytes, n_cols: int, delim: str = ",",
                    max_rows: Optional[int] = None) -> np.ndarray:
    """Delimited text → (rows, n_cols) int64 (CSV ingest fast path)."""
    lib = _load()
    cap = max_rows if max_rows is not None else data.count(b"\n") + 1
    if lib is None:
        rows = [r.split(delim.encode()) for r in data.splitlines() if r]
        out = np.zeros((min(len(rows), cap), n_cols), np.int64)
        for i, r in enumerate(out):
            for c in range(n_cols):
                try:
                    r[c] = int(rows[i][c])
                except (IndexError, ValueError):
                    r[c] = 0
        return out
    out = np.zeros((cap, n_cols), np.int64)
    n = lib.parse_i64_table(data, len(data), delim.encode(), n_cols,
                            out.reshape(-1), cap)
    return out[:n]


def parse_f32_table(data: bytes, n_cols: int, delim: str = ",",
                    max_rows: Optional[int] = None) -> np.ndarray:
    lib = _load()
    cap = max_rows if max_rows is not None else data.count(b"\n") + 1
    if lib is None:
        rows = [r.split(delim.encode()) for r in data.splitlines() if r]
        out = np.zeros((min(len(rows), cap), n_cols), np.float32)
        for i in range(out.shape[0]):
            for c in range(n_cols):
                try:
                    out[i, c] = float(rows[i][c])
                except (IndexError, ValueError):
                    out[i, c] = 0.0
        return out
    out = np.zeros((cap, n_cols), np.float32)
    n = lib.parse_f32_table(data, len(data), delim.encode(), n_cols,
                            out.reshape(-1), cap)
    return out[:n]


def encode_i64_rows(vals: np.ndarray, delim: str = ",") -> bytes:
    """(rows, cols) int64 → delimited text (egress fast path)."""
    vals = np.ascontiguousarray(vals, np.int64)
    lib = _load()
    if lib is None:
        d = delim
        return ("".join(d.join(str(int(v)) for v in row) + "\n"
                        for row in vals)).encode()
    cap = vals.size * 22 + vals.shape[0] + 16
    buf = ctypes.create_string_buffer(cap)
    n = lib.encode_i64_rows(vals.reshape(-1), vals.shape[0],
                            vals.shape[1] if vals.ndim > 1 else 1,
                            delim.encode(), buf, cap)
    assert n >= 0
    return buf.raw[:n]


#: buffers below this go straight to zlib (ctypes call overhead and the
#: numpy view wrap cost more than the GIL hold on a few KB)
_CRC_NATIVE_MIN = 1 << 14


def crc32(buf, value: int = 0) -> int:
    """CRC-32 of a bytes-like buffer, BIT-IDENTICAL to ``zlib.crc32``
    — but computed WITHOUT the GIL on the native path (slice-by-8 in
    codec.cc), so concurrent frame checksums of the DCN exchange's
    per-peer I/O threads actually overlap. CPython 3.10's zlib holds
    the GIL for the whole pass; on a multi-peer exchange that
    serializes every checksum in the process. Falls back to zlib
    (same result) when the .so is unavailable."""
    import zlib

    mv = memoryview(buf)
    if mv.format != "B":
        mv = mv.cast("B")
    lib = _load()
    if lib is None or mv.nbytes < _CRC_NATIVE_MIN:
        return zlib.crc32(mv, value)
    arr = np.frombuffer(mv, np.uint8)
    return int(lib.crc32_zlib(arr, arr.size, value & 0xFFFFFFFF))


def hash_keys_native(keys: np.ndarray) -> Optional[np.ndarray]:
    """splitmix64-finalize a key batch in C (bit-identical to
    ``records.hash_keys_numpy``); None when the library is unbuilt."""
    lib = _load()
    if lib is None:
        return None
    keys = np.ascontiguousarray(keys, np.int64)
    out = np.empty(len(keys), np.int64)
    lib.hash_keys(keys, len(keys), out)
    return out


#: records between two looks of ``NativeHashTable.assign``'s memo at its
#: own hit share (codec.cc MEMO_STRETCH)
MEMO_STRETCH = 4096
#: stretches the memo sits out after one in which it did not pay, before
#: it looks again (codec.cc MEMO_REST)
MEMO_REST = 7


class NativeHashTable:
    """int64 → int64 open-addressing table in C (the KeyDirectory probe
    loop; ref role: CopyOnWriteStateMap.get/put batched). Interface
    mirrors ``state.keyed._NumpyHashTable``; construct via
    ``NativeHashTable.create()`` which returns None when the codec
    library is unavailable so callers can fall back."""

    def __init__(self, lib, capacity_hint: int) -> None:
        self._lib = lib
        self._h = lib.ht_new(capacity_hint)
        # assign's: the slots a call handed out (grows to the longest
        # batch) and its four counts
        self._handed_out = np.empty(0, np.int64)
        self._stats = np.zeros(4, np.int64)

    @classmethod
    def create(cls, capacity_hint: int = 1024) -> Optional["NativeHashTable"]:
        lib = _load()
        return cls(lib, capacity_hint) if lib is not None else None

    def __del__(self):
        h, self._h = getattr(self, "_h", None), None
        if h:
            self._lib.ht_free(h)

    @property
    def _count(self) -> int:
        return int(self._lib.ht_count(self._h))

    def growth(self):
        """``(doublings so far, the seconds they took, buckets now)``,
        counted inside the table (codec.cc ``ht_grow``), whichever call
        grew it."""
        out = np.empty(3, np.int64)
        self._lib.ht_growth(self._h, out)
        return int(out[0]), out[1] / 1e9, int(out[2])

    def lookup_keys(self, keys: np.ndarray):
        """(values, found) — hashes computed inline in C."""
        keys = np.ascontiguousarray(keys, np.int64)
        vals = np.empty(len(keys), np.int64)
        found = np.empty(len(keys), np.uint8)
        self._lib.ht_lookup(self._h, keys, len(keys), vals, found)
        return vals, found.astype(bool)

    def insert_batch(self, keys: np.ndarray, key_hashes, vals: np.ndarray) -> None:
        """Insert-or-update; ``key_hashes`` accepted for interface parity
        with the numpy table (the C side re-derives them)."""
        keys = np.ascontiguousarray(keys, np.int64)
        vals = np.ascontiguousarray(vals, np.int64)
        self._lib.ht_insert(self._h, keys, vals, len(keys))

    PENDING = -16   # codec.cc HT_PENDING
    _NO_STACKS = np.empty(0, np.int32)   # no slot has come back yet

    def lookup_claim(self, keys: np.ndarray):
        """``(values, distinct missed keys)``: a lookup that enters each
        absent key with the placeholder ``PENDING - u`` (``u`` its index
        among the distinct misses, in first-occurrence order), which
        every record of that key reads back. The caller stores a real
        value for each of them (``insert_batch``) before anything else
        reads the table, and resolves the placeholders it holds. What
        ``KeyDirectory.assign`` was built from before ``assign`` below;
        its parity test and ``tools/scan_micro.py`` still build that."""
        keys = np.ascontiguousarray(keys, np.int64)
        vals = np.empty(len(keys), np.int64)
        uniq = np.empty(len(keys), np.int64)
        n = self._lib.ht_lookup_claim(self._h, keys, len(keys), vals, uniq)
        return vals, uniq[:n]

    def assign(self, keys: np.ndarray, num_shards: int, shard_lo: int,
               shard_hi: int, slots_per_shard: int, next_free: np.ndarray,
               n_free: np.ndarray, free_stacks: Optional[np.ndarray],
               rev_keys: np.ndarray, rev_used: np.ndarray):
        """``KeyDirectory.assign`` in one native call (codec.cc
        ``ht_assign``): the slot of every key, absent keys given slots
        from the directory's allocator arrays (updated in place, with
        ``_alloc_slots``' outcome to the slot) and entered. Behind a
        memo of the call's own that serves a record whose key a record
        shortly before it had, and steps aside for ``MEMO_REST``
        stretches where a stretch of ``MEMO_STRETCH`` records shows it
        does not pay. Returns ``(slots,
        slots handed out, of them reclaimed ones, memo hits, records
        that consulted the memo)``."""
        keys = np.ascontiguousarray(keys, np.int64)
        n = len(keys)
        if len(self._handed_out) < n:
            self._handed_out = np.empty(n, np.int64)
        slots = np.empty(n, np.int64)
        st = self._stats
        self._lib.ht_assign(
            self._h, keys, n, slots, num_shards, shard_lo, shard_hi,
            slots_per_shard, next_free, n_free,
            self._NO_STACKS if free_stacks is None else free_stacks,
            rev_keys, rev_used.view(np.uint8), self._handed_out, st)
        return (slots, self._handed_out[:st[2]].copy(), int(st[3]), int(st[0]),
                int(st[1]))

    def delete_batch(self, keys: np.ndarray) -> int:
        """Delete by backward shift (codec.cc ht_delete): no tombstones,
        probes stay as short as the load allows. Absent keys are
        skipped; returns how many were there."""
        keys = np.ascontiguousarray(keys, np.int64)
        return int(self._lib.ht_delete(self._h, keys, len(keys)))

    def longest_run(self) -> int:
        """The longest run of occupied buckets: a probe's worst case."""
        return int(self._lib.ht_longest_run(self._h))


def slot_panes_note_native(slots: np.ndarray, panes: np.ndarray,
                           valid: np.ndarray, newest: np.ndarray) -> bool:
    """``newest[slot] = max(newest[slot], pane)`` over a batch's valid
    records, in C; False when the library is unavailable."""
    lib = _load()
    if lib is None:
        return False
    lib.slot_panes_note(
        len(slots), np.ascontiguousarray(slots, np.int64),
        np.ascontiguousarray(panes, np.int64),
        np.ascontiguousarray(valid).view(np.uint8), newest)
    return True


def ts_order_stats(ts: np.ndarray, seen: int) -> Tuple[int, int, int]:
    """``(records stamped below seen, the oldest, the newest)`` of a
    batch's int64 timestamps (not empty): one pass in C, three in numpy
    where the library is unavailable."""
    lib = _load()
    if lib is None:
        return int(np.count_nonzero(ts < seen)), int(ts.min()), int(ts.max())
    out = np.empty(3, np.int64)
    lib.ts_order_stats(np.ascontiguousarray(ts, np.int64), len(ts), seen, out)
    return int(out[0]), int(out[1]), int(out[2])


def slot_panes_note_pairs_native(pairs: np.ndarray, ring: int, pane_lo: int,
                                 newest: np.ndarray) -> None:
    """The same from a fused scan's distinct (slot * ring + column)
    pairs, whose panes lie in ``[pane_lo, pane_lo + ring)``."""
    _load().slot_panes_note_pairs(len(pairs), pairs, ring, pane_lo, newest)


class NativeSocketReader:
    """Line-framed TCP ingest socket in C (SURVEY §3.10 item 3 — the
    Netty-native-transport analogue feeding the codec). One listener,
    one connection; ``read_block`` returns byte blocks that END at a
    newline, ready for the table parsers. ``create()`` returns None
    when the library is unavailable (callers fall back to the pure-
    Python reader)."""

    def __init__(self, lib, handle) -> None:
        self._lib = lib
        self._h = handle

    @classmethod
    def create(cls, port: int = 0) -> Optional["NativeSocketReader"]:
        lib = _load()
        if lib is None:
            return None
        h = lib.sr_listen(port)
        return cls(lib, h) if h else None

    @property
    def port(self) -> int:
        return int(self._lib.sr_port(self._h))

    def accept(self, timeout_ms: int = 100) -> int:
        """1 = connected, 0 = timeout, -1 = error."""
        return int(self._lib.sr_accept(self._h, timeout_ms))

    def read_block(self, cap: int = 1 << 20,
                   timeout_ms: int = 100) -> Optional[bytes]:
        """Complete-line block (bytes), b'' on timeout, None on EOF.
        Raises on transport errors / oversized lines. The scratch
        buffer is reused across calls — idle polls (b'' every
        ``timeout_ms``) must not allocate+zero a megabyte each."""
        buf = getattr(self, "_buf", None)
        if buf is None or len(buf) < cap:
            buf = self._buf = ctypes.create_string_buffer(cap)
        n = int(self._lib.sr_read_block(self._h, buf, cap, timeout_ms))
        if n > 0:
            return buf.raw[:n]
        if n == 0:
            return b""
        if n == -1:
            return None
        raise IOError("socket reader error (closed early or a line "
                      f"exceeded {cap} bytes)")

    def close(self) -> None:
        h, self._h = self._h, None
        if h:
            self._lib.sr_close(h)


class PreaggWorkspace:
    """Caller-owned zeroed workspaces for ``preagg_combine`` (see
    native/codec.cc): kept across batches so steady state never pays a
    full-domain clear — the C side resets only touched entries."""

    def __init__(self, domain: int, nlanes: int) -> None:
        self._workers = None
        self.domain = domain
        self.nlanes = nlanes
        self.hist = np.zeros(domain, np.int32)
        self.lane_acc = np.zeros(max(domain * nlanes, 1), np.float64)
        # what a split scan keeps between batches (see
        # ingest_fused_scan_native), made when a scan first asks: the
        # later ranges' private histograms, zero like ``hist``, and
        # the native threads that run them
        self.range_hist = np.zeros((0, domain), np.int32)

    def split_state(self, ranges: int) -> Tuple[int, np.ndarray]:
        """The worker set's handle and ``ranges - 1`` or more zeroed
        histograms, flat, for ``ingest_fused_scan_split``."""
        if len(self.range_hist) < ranges - 1:
            self.range_hist = np.zeros((ranges - 1, self.domain), np.int32)
        if self._workers is None:
            self._workers = _load().scan_workers_new()
        return self._workers, self.range_hist.reshape(-1)

    def rezero(self) -> None:
        self.hist[:] = 0
        self.lane_acc[:] = 0.0
        self.range_hist[:] = 0

    def __del__(self) -> None:
        if self._workers is not None and _lib is not None:
            _lib.scan_workers_free(self._workers)   # joins idle threads
            self._workers = None


def preagg_combine_native(
    slots: np.ndarray, panes: np.ndarray, valid: np.ndarray,
    lane_data: List[np.ndarray], ring: int, ws: PreaggWorkspace,
    cap: int,
) -> Optional[Tuple[np.ndarray, np.ndarray, List[np.ndarray]]]:
    """C fast path of the window operator's host combine. Returns
    (pairs, counts, lanes) or None (library unavailable / cap
    overflow — fall back to the numpy path)."""
    lib = _load()
    if lib is None:
        return None
    n = len(slots)
    nl = ws.nlanes
    out_pairs = np.empty(cap, np.int32)
    out_counts = np.empty(cap, np.int32)
    out_lanes = np.empty((cap, nl) if nl else (1, 1), np.float32)
    if nl:
        lanes = np.ascontiguousarray(
            np.stack([np.asarray(a, np.float64) for a in lane_data]))
    else:
        lanes = np.zeros(1, np.float64)
    npairs = lib.preagg_combine(
        n, np.ascontiguousarray(slots, np.int64),
        np.ascontiguousarray(panes, np.int64),
        np.ascontiguousarray(valid).view(np.uint8), ring, ws.domain,
        nl, lanes.reshape(-1) if nl else lanes,
        ws.hist, ws.lane_acc, out_pairs, out_counts,
        out_lanes.reshape(-1), cap)
    if npairs < 0:
        ws.rezero()
        return None
    return (out_pairs[:npairs], out_counts[:npairs],
            [out_lanes[:npairs, i].copy() for i in range(nl)])


def nexmark_bids_native(
    seed: int, n: int, hot_ratio: int, n_hot: int,
    n_auctions: int, n_people: int,
) -> Optional[Tuple[np.ndarray, np.ndarray, np.ndarray]]:
    """C fast path of the Nexmark bid generator (auction, bidder,
    price). None when the library is unavailable."""
    lib = _load()
    if lib is None:
        return None
    auction = np.empty(n, np.int64)
    bidder = np.empty(n, np.int64)
    price = np.empty(n, np.float32)
    lib.nexmark_bids(seed, n, hot_ratio, n_hot, n_auctions, n_people,
                     auction, bidder, price)
    return auction, bidder, price


class IngestFusedResult:
    """Output of one fully-fused ingest over a batch (see codec.cc
    ingest_fused_scan): running pair list + accumulated stats, with the
    finalize step deferred so a miss-registration re-scan can continue
    the same workspace."""

    __slots__ = ("npairs", "out_pairs", "stats", "bitmap", "ranges")

    def __init__(self, npairs, out_pairs, stats, bitmap, ranges=1):
        self.npairs = npairs
        self.out_pairs = out_pairs
        self.stats = stats
        self.bitmap = bitmap
        # record ranges the first pass was scanned in, side by side
        self.ranges = ranges


# Fewest records a range of a split scan is worth a thread for. On the
# chip's host (PERF.md, PR 30; ms a batch in order, serial / 2 / 4
# ranges): 2^17 records 0.95 / 0.99 / 0.72, 2^16 records 0.52 / 0.62 /
# 0.49 — two ranges of 65,536 cost what one of 131,072 does, and under
# that waking the workers and merging cost more than the ranges save.
SCAN_RANGE_MIN_RECORDS = 1 << 16


def ingest_fused_scan_native(
    keys: np.ndarray, ts: np.ndarray, table: "NativeHashTable",
    pane_ms: int, offset_ms: int, ring: int, ws: "PreaggWorkspace",
    cap: int, dead_below: int, refire_below: int, bitmap_bits: int,
    *, cont: Optional["IngestFusedResult"] = None, miss_cap: int = 0,
    threads: int = 1,
) -> Optional[Tuple["IngestFusedResult", np.ndarray]]:
    """One fused probe+ingest scan (codec.cc ingest_fused_scan).
    Returns (result, miss_indices) or None (unavailable / cap
    overflow — the workspace was re-zeroed; caller falls back). Pass
    ``cont`` to continue a previous scan's pair list and stats (the
    miss-registration second pass).

    ``threads`` > 1 lets a first pass run as that many contiguous
    record ranges side by side (codec.cc ingest_fused_scan_split: one
    native thread a range, private workspaces, an ordered merge), as
    long as every range keeps ``SCAN_RANGE_MIN_RECORDS`` records; a
    shorter batch takes fewer ranges, down to the serial call.
    Everything returned but ``pane_moves`` is what the serial call
    returns, element for element; ``result.ranges`` says how many
    ranges ran.

    ``result.stats`` is ``[n_valid, n_late, n_bad, pane_min, pane_max,
    n_refire, n_miss, cmax, pane_moves]``, summed (min / max taken)
    over a scan and its ``cont`` calls. The ninth, ``pane_moves``, is
    how many records had their pane worked out by division because
    they did not lie in the pane of the record before: 1-2 a batch on
    an in-order stream, ~n where panes alternate record by record
    (each range of a split scan seeks its own first pane: in order, up
    to ``ranges`` + 1 a batch)."""
    lib = _load()
    if lib is None:
        return None
    n = len(ts)
    if cont is None:
        ranges = max(1, min(threads, n // SCAN_RANGE_MIN_RECORDS))
        out_pairs = np.empty(cap, np.int32)
        stats = np.zeros(9, np.int64)
        stats[3] = np.iinfo(np.int64).max   # pmin seed
        stats[4] = np.iinfo(np.int64).min   # pmax seed
        bitmap = np.zeros(max((bitmap_bits + 7) // 8, 1), np.uint8)
        np_in = 0
    else:
        out_pairs, stats, bitmap = cont.out_pairs, cont.stats, cont.bitmap
        np_in = cont.npairs
        ranges = cont.ranges    # the first pass's; this one is serial
    miss_cap = max(miss_cap, 1)
    out_miss = np.empty(miss_cap, np.int64)
    stats[6] = 0  # miss list restarts each scan
    keys = np.ascontiguousarray(keys, np.int64)
    ts = np.ascontiguousarray(ts, np.int64)
    if cont is None and ranges > 1:
        workers, range_hist = ws.split_state(ranges)
        npairs = lib.ingest_fused_scan_split(
            n, keys, ts, table._h, pane_ms, offset_ms, ring, dead_below,
            refire_below, ws.hist, out_pairs, cap, stats, bitmap,
            dead_below, len(bitmap), out_miss, miss_cap, workers, ranges,
            range_hist, ws.domain)
    else:
        npairs = lib.ingest_fused_scan(
            n, keys, ts, table._h, pane_ms, offset_ms, ring, dead_below,
            refire_below, ws.hist, out_pairs, np_in, cap, stats, bitmap,
            dead_below, len(bitmap), out_miss, miss_cap)
    if npairs < 0:
        ws.rezero()
        return None
    res = IngestFusedResult(int(npairs), out_pairs, stats, bitmap, ranges)
    return res, out_miss[:int(stats[6])]


def ingest_fused_finalize_u32_native(
    res: "IngestFusedResult", ws: "PreaggWorkspace", hdr: int,
    cap_out: int) -> np.ndarray:
    """Emit the packed u32 upload buffer (hdr -1 region + pair<<12|count
    + -1 padding) straight from C, resetting the workspace."""
    lib = _load()
    buf = np.empty(hdr + cap_out, np.int32)
    lib.ingest_fused_finalize_u32(
        res.npairs, ws.hist, res.out_pairs, buf, hdr, cap_out)
    return buf


def ingest_fused_finalize_pairs_native(
    res: "IngestFusedResult", ws: "PreaggWorkspace",
) -> Tuple[np.ndarray, np.ndarray]:
    """Extract (pairs, counts) and reset the workspace — the path for
    counts too large for the 12-bit u32 pack."""
    lib = _load()
    counts = np.empty(max(res.npairs, 1), np.int32)
    lib.ingest_fused_finalize_pairs(
        res.npairs, ws.hist, res.out_pairs, counts)
    return res.out_pairs[:res.npairs], counts[:res.npairs]


def ingest_combine_native(
    ts: np.ndarray, slots: np.ndarray, pane_ms: int, offset_ms: int,
    ring: int, ws: PreaggWorkspace, cap: int, dead_below: int,
    refire_below: int, bitmap_bits: int,
) -> Optional[Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]]:
    """Fused window-ingest pass (see codec.cc ingest_combine). Returns
    (pairs, counts, stats[6], refire_bitmap) or None (unavailable /
    cap overflow — caller falls back to the numpy path)."""
    lib = _load()
    if lib is None:
        return None
    n = len(ts)
    out_pairs = np.empty(cap, np.int32)
    out_counts = np.empty(cap, np.int32)
    stats = np.zeros(6, np.int64)
    bitmap = np.zeros(max((bitmap_bits + 7) // 8, 1), np.uint8)
    npairs = lib.ingest_combine(
        n, np.ascontiguousarray(ts, np.int64),
        np.ascontiguousarray(slots, np.int64),
        pane_ms, offset_ms, ring, ws.domain, dead_below, refire_below,
        ws.hist, out_pairs, out_counts, cap, stats, bitmap,
        dead_below, len(bitmap))
    if npairs < 0:
        ws.rezero()
        return None
    return out_pairs[:npairs], out_counts[:npairs], stats, bitmap
