"""Keyed state: dense HBM pane tensors + host key directory.

This is the HeapKeyedStateBackend replacement (ref: flink-runtime/.../
runtime/state/heap/{HeapKeyedStateBackend,CopyOnWriteStateTable,
CopyOnWriteStateMap}.java — a per-record nested-hash-map probe), redesigned
for TPU: state lives as dense ``(slots, panes, width)`` accumulator
tensors in HBM so a whole microbatch folds in with three scatters, and the
hash-map role (key → state address) moves to a **host-side directory**
that assigns each distinct key a slot inside its key shard, its own for
as long as the key is registered (a window operator releases a key once
its last pane has been purged; the slot is then handed out again).

Key shards (ref: runtime/state/KeyGroupRangeAssignment.java — key groups,
default max-parallelism 128) decouple the logical key space from physical
devices: shard = splitmix64(key) % num_shards; a device owns a contiguous
shard range; global slot = shard * slots_per_shard + local index. Rescale
= re-assign shard ranges (checkpoint/reshard reads this layout).

Copy-on-write snapshot isolation comes free: jax arrays are immutable, so
a checkpoint simply keeps a reference to the state pytree of a step
boundary while processing continues on new arrays (the CopyOnWriteStateTable
role collapses into XLA donation semantics).
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import time
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from flink_tpu.records import hash_keys_numpy


@dataclasses.dataclass(frozen=True)
class PaneStateLayout:
    """Static shape of one window-operator state family (per device shard
    range when sharded; ``slots`` is the LOCAL slot count).

    One extra "dump" row at index ``slots`` swallows scatters from
    padding rows — branchless masking, no dynamic shapes.
    """

    slots: int          # local key capacity (num_local_shards * slots_per_shard)
    ring: int           # pane ring length (>= live pane span, see plan())
    sum_width: int
    max_width: int
    min_width: int

    @property
    def rows(self) -> int:
        return self.slots + 1  # + dump row

    def bytes(self) -> int:
        per_cell = 4 * (self.sum_width + self.max_width + self.min_width) + 4
        return self.rows * self.ring * per_cell


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PaneState:
    """Device-resident accumulator tensors. counts is always present (it
    is the COUNT lane, the trigger-count source, and the non-empty mask).

    Zero-width lane families are ``None``, NOT zero-size arrays: None is
    an empty pytree, so jit in/out carries no buffer for them. A
    zero-size runtime buffer is not free on every backend: each one is
    still an argument the runtime has to pass per step (its cost on the
    current chip: not measured)."""

    sums: Optional[jax.Array]   # (rows, ring, sum_width) f32, None if width 0
    maxs: Optional[jax.Array]   # (rows, ring, max_width) f32, None if width 0
    mins: Optional[jax.Array]   # (rows, ring, min_width) f32, None if width 0
    counts: jax.Array  # (rows, ring) i32

    def tree_flatten(self):
        return (self.sums, self.maxs, self.mins, self.counts), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def init_state(layout: PaneStateLayout) -> PaneState:
    def lane(width: int, fill: float) -> Optional[jax.Array]:
        if width == 0:
            return None
        return jnp.full((layout.rows, layout.ring, width), fill, jnp.float32)

    return PaneState(
        sums=lane(layout.sum_width, 0.0),
        maxs=lane(layout.max_width, -float("inf")),
        mins=lane(layout.min_width, float("inf")),
        counts=jnp.zeros((layout.rows, layout.ring), jnp.int32),
    )


class _NumpyHashTable:
    """Open-addressing int64→int64 map with fully vectorized batch
    lookup AND batch insert/update (linear probing; load factor kept
    ≤ 0.5 by doubling) — key churn costs numpy probe rounds, never a
    Python loop per key."""

    def __init__(self, capacity_hint: int = 1024) -> None:
        # doublings so far and the seconds they took (``growth``)
        self._grows = 0
        self._grow_s = 0.0
        self._alloc(capacity_hint)

    def _alloc(self, capacity_hint: int) -> None:
        size = 1
        while size < max(capacity_hint * 2, 16):
            size *= 2
        self._keys = np.zeros(size, dtype=np.int64)
        self._vals = np.zeros(size, dtype=np.int64)
        self._used = np.zeros(size, dtype=bool)
        self._count = 0

    def growth(self) -> Tuple[int, float, int]:
        """``(doublings so far, the seconds they took, buckets now)``:
        what the native table counts in ``ht_grow``."""
        return self._grows, self._grow_s, len(self._keys)

    def lookup_keys(self, keys: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        return self.lookup(keys, hash_keys_numpy(keys))

    def lookup(self, keys: np.ndarray, key_hashes: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """(values, found) for a batch. Vectorized probe: each round
        resolves every query that hits its key or an empty bucket."""
        mask = len(self._keys) - 1
        ix = (key_hashes & mask).astype(np.int64)
        out = np.full(len(keys), -1, dtype=np.int64)
        found = np.zeros(len(keys), dtype=bool)
        pending = np.arange(len(keys))
        for _ in range(len(self._keys)):
            if len(pending) == 0:
                break
            pix = ix[pending]
            hit = self._used[pix] & (self._keys[pix] == keys[pending])
            empty = ~self._used[pix]
            out[pending[hit]] = self._vals[pix[hit]]
            found[pending[hit]] = True
            pending = pending[~hit & ~empty]
            ix[pending] = (ix[pending] + 1) & mask
        return out, found

    def insert(self, key: int, key_hash: int, val: int) -> None:
        self.insert_batch(
            np.asarray([key], np.int64),
            np.asarray([key_hash], np.uint64),
            np.asarray([val], np.int64))

    def insert_batch(self, keys: np.ndarray, key_hashes: np.ndarray,
                     vals: np.ndarray) -> None:
        """Vectorized linear-probe insert for a batch of DISTINCT keys.
        Each probe round settles every query whose bucket holds its key
        (update) or wins an empty bucket (one writer per bucket per
        round); the rest step forward — same round structure as lookup,
        so key churn costs O(rounds) numpy passes, not a Python loop
        per key."""
        n = len(keys)
        if n == 0:
            return
        while (self._count + n) * 2 > len(self._keys):
            self._grow()
        keys = np.asarray(keys, np.int64)
        vals = np.asarray(vals, np.int64)
        mask = len(self._keys) - 1
        ix = (key_hashes & mask).astype(np.int64)
        pending = np.arange(n)
        while len(pending):
            pix = ix[pending]
            used = self._used[pix]
            samekey = used & (self._keys[pix] == keys[pending])
            upd = pending[samekey]
            self._vals[ix[upd]] = vals[upd]
            emp = pending[~used]
            _, first = np.unique(ix[emp], return_index=True)
            win = emp[first]
            self._keys[ix[win]] = keys[win]
            self._vals[ix[win]] = vals[win]
            self._used[ix[win]] = True
            self._count += len(win)
            settled = np.zeros(n, dtype=bool)
            settled[upd] = True
            settled[win] = True
            pending = pending[~settled[pending]]
            ix[pending] = (ix[pending] + 1) & mask

    def delete_batch(self, keys: np.ndarray) -> int:
        """Delete by backward shift, the native table's algorithm
        (codec.cc ht_delete) bucket for bucket, so the two stay in
        parity: the hole a deleted entry leaves is filled by the next
        entry of its run whose home bucket does not lie after the hole,
        to the run's end. No tombstones; a probe is never longer than
        the load (<= 0.5) makes it. One Python step per deleted key and
        per shifted entry: this is the fallback, not the hot path.
        Absent keys are skipped; returns how many were there."""
        keys = np.asarray(keys, np.int64)
        mask = len(self._keys) - 1
        home = (hash_keys_numpy(keys) & mask).astype(np.int64)
        gone = 0
        for key, ix in zip(keys.tolist(), home.tolist()):
            while self._used[ix] and self._keys[ix] != key:
                ix = (ix + 1) & mask
            if not self._used[ix]:
                continue
            hole, j = ix, (ix + 1) & mask
            while self._used[j]:
                h = int(hash_keys_numpy(self._keys[j:j + 1])[0]) & mask
                if ((j - h) & mask) >= ((j - hole) & mask):
                    self._keys[hole] = self._keys[j]
                    self._vals[hole] = self._vals[j]
                    hole = j
                j = (j + 1) & mask
            self._used[hole] = False
            self._count -= 1
            gone += 1
        return gone

    def longest_run(self) -> int:
        """The longest run of occupied buckets: a probe's worst case."""
        if self._used.all():
            return len(self._used)
        # start after an empty bucket, so no run wraps the end
        u = np.roll(self._used, -(int(np.argmin(self._used)) + 1))
        edges = np.flatnonzero(np.diff(np.r_[0, u.astype(np.int8), 0]))
        return int((edges[1::2] - edges[::2]).max(initial=0))

    def _grow(self) -> None:
        began = time.perf_counter()
        old_keys, old_vals, old_used = self._keys, self._vals, self._used
        self._alloc(capacity_hint=len(old_keys))
        live = np.nonzero(old_used)[0]
        if len(live):
            self.insert_batch(
                old_keys[live], hash_keys_numpy(old_keys[live]), old_vals[live])
        self._grows += 1
        self._grow_s += time.perf_counter() - began


# "no pane noted": below every pane, so such a key is released at the
# next purge (a key whose every record was late or invalid)
_NO_PANE = np.iinfo(np.int64).min


# a snapshot copies each slot-sized array in at most this many ranges,
# none shorter than this many entries (a short array is one copy)
_SNAPSHOT_RANGES = 4
_SNAPSHOT_RANGE_MIN = 1 << 20


def _runs(sorted_ids: np.ndarray):
    """(starts, lengths, ranks) of the equal-id runs of a sorted array:
    rank = an element's place within its run."""
    starts = np.r_[0, np.nonzero(np.diff(sorted_ids))[0] + 1]
    lens = np.diff(np.r_[starts, len(sorted_ids)])
    ranks = np.arange(len(sorted_ids)) - np.repeat(starts, lens)
    return starts, lens, ranks


class KeyDirectory:
    """Host-side key → slot mapping (the hash-map half of the state
    backend; ref role: CopyOnWriteStateMap.get/put, but amortized over a
    batch and off the device hot path).

    Batch lookups are fully vectorized over a numpy open-addressing
    table; only never-before-seen keys take the per-key insert path.

    A key keeps its slot for as long as it is registered, across
    checkpoints too (the directory is part of the snapshot manifest).
    An owner that tracks panes (``track_panes``: the window operator on
    the hbm backend) releases a key once its newest pane has been
    purged (``release_below``): the key leaves the table, its slot
    leaves ``used_mask``, and the OWNER holds the slot back until no
    fired row that names it can still be read (ops/window.py, "the
    reuse rule") before it hands it to ``reclaim``. The allocator then
    takes a reclaimed slot of the key's shard before a never-used one,
    so the rows the device scatter touches stay as few as the live keys
    need. Owners that never call ``track_panes`` (count windows, global
    aggregates, process functions, CEP) keep every key for the life of
    the job, as before.
    """

    FULL = -2  # sentinel: shard out of slots (spill backend takes over)

    def __init__(self, num_shards: int, slots_per_shard: int,
                 shard_range: Tuple[int, int] | None = None) -> None:
        self.num_shards = num_shards
        self.slots_per_shard = slots_per_shard
        # shard range owned by this directory (global view: (0, num_shards))
        self.shard_lo, self.shard_hi = shard_range or (0, num_shards)
        # C fast path when the codec library is available (same probe
        # semantics, same splitmix64 hash — parity-tested); numpy
        # otherwise
        from flink_tpu.native_codec import NativeHashTable

        self._table = NativeHashTable.create() or _NumpyHashTable()
        self._next_free = np.zeros(num_shards, dtype=np.int64)
        n_local = (self.shard_hi - self.shard_lo) * slots_per_shard
        self._rev_keys = np.zeros(n_local, dtype=np.int64)
        self._rev_used = np.zeros(n_local, dtype=bool)
        self._n_keys = 0
        # per local shard, a stack of reclaimed local indices and its
        # depth; the stacks are made when the first slot comes back
        self._free: Optional[np.ndarray] = None
        self._n_free = np.zeros(self.shard_hi - self.shard_lo, np.int64)
        # pane tracking (track_panes): per slot the newest pane a record
        # of its key was folded into, and where release_below looks:
        # slots allocated since it last ran, and slots it found alive,
        # by the newest pane they had then. A slot is in exactly one of
        # those lists, so a purge costs what it examines, not the space.
        self._newest: Optional[np.ndarray] = None
        self._fresh: list = []
        self._by_pane: Dict[int, list] = {}
        # over the directory's life: slots handed out, those of them
        # that were reclaimed ones, keys released, and the most keys
        # registered at once
        self.slots_allocated = 0
        self.slots_reused = 0
        self.slots_released = 0
        self.keys_peak = 0
        # the native assign's: records it was handed, those of them its
        # memo was consulted for, and those the memo served
        self.assign_records = 0
        self.assign_memo_looks = 0
        self.assign_memo_hits = 0

    @property
    def local_slots(self) -> int:
        return (self.shard_hi - self.shard_lo) * self.slots_per_shard

    def shard_of(self, keys: np.ndarray) -> np.ndarray:
        return hash_keys_numpy(np.asarray(keys, dtype=np.int64)) % self.num_shards

    def assign(self, keys: np.ndarray) -> np.ndarray:
        """Map raw int64 keys → LOCAL slot ids (relative to shard_lo).

        Returns -1 where the key's shard is outside this directory's
        range (caller routed wrong) and FULL where the shard is out of
        slots (spill-layer responsibility).
        """
        keys = np.asarray(keys, dtype=np.int64)
        native = getattr(self._table, "assign", None)
        if native is not None:
            # ONE native call, per record a memo hit and a store: the
            # lookup, the allocation below for the distinct new keys and
            # their entry, slot for slot (tests/test_directory_assign.py)
            slots, ok, reused, hits, looks = native(
                keys, self.num_shards, self.shard_lo, self.shard_hi,
                self.slots_per_shard, self._next_free, self._n_free,
                self._free, self._rev_keys, self._rev_used)
            self.slots_reused += reused
            self._note_allocated(ok)
            self.assign_records += len(keys)
            self.assign_memo_hits += hits
            self.assign_memo_looks += looks
            return slots
        slots, found = self._table.lookup_keys(keys)
        if not found.all():
            miss_ix = np.nonzero(~found)[0]
            # allocate + register each distinct new key once, vectorized
            # (key churn is per-batch steady state in rotating-key
            # workloads like Nexmark; a Python loop here was 60ms/batch);
            # only the DISTINCT misses are hashed on the Python side —
            # the hit path's hashes live inside the table lookup
            uniq, inv = np.unique(keys[miss_ix], return_inverse=True)
            slots[miss_ix] = self._register(uniq)[inv]
        return slots

    def register_misses(self, miss_keys: np.ndarray) -> None:
        """Register keys KNOWN to be absent (the fused C scan already
        probed them — codec.cc ingest_fused_scan): allocate + insert
        without repeating the lookup pass."""
        self._register(np.unique(np.asarray(miss_keys, np.int64)))

    def _register(self, uniq: np.ndarray) -> np.ndarray:
        """Slots for DISTINCT absent keys. A key whose shard is full (or
        not this directory's) is entered with that verdict, as it always
        was: it stays refused (or on the host, under a spill store) even
        if slots of its shard come back later."""
        uh = hash_keys_numpy(uniq)
        alloc = self._alloc_slots(uniq, uh)
        self._table.insert_batch(uniq, uh, alloc)
        return alloc

    def _alloc_slots(self, keys: np.ndarray, hashes: np.ndarray) -> np.ndarray:
        """Assign shard-local slots to a batch of DISTINCT new keys:
        group by shard; within a shard the first keys take its reclaimed
        slots (newest first), the rest contiguous indices from its free
        pointer; FULL past capacity. Pure numpy — no per-key Python."""
        shards = (hashes % self.num_shards).astype(np.int64)
        out = np.full(len(keys), -1, dtype=np.int64)
        inr = (shards >= self.shard_lo) & (shards < self.shard_hi)
        if not inr.any():
            return out
        sub = np.nonzero(inr)[0]
        order = np.argsort(shards[sub], kind="stable")
        sub = sub[order]
        sh = shards[sub]
        starts, run_lens, ranks = _runs(sh)
        run_sh = sh[starts]
        room = self.slots_per_shard - self._next_free[run_sh]
        if self._free is not None and self._n_free.any():
            run_ls = run_sh - self.shard_lo
            depth = self._n_free[run_ls]
            taken = np.minimum(depth, run_lens)
            self._n_free[run_ls] = depth - taken
            taken_k = np.repeat(taken, run_lens)
            reuse = ranks < taken_k
            fresh_rank = ranks - taken_k
            top = np.repeat(depth, run_lens) - 1 - ranks
            local_ix = np.where(
                reuse, self._free[sh - self.shard_lo, np.maximum(top, 0)],
                self._next_free[sh] + fresh_rank)
            self.slots_reused += int(taken.sum())
        else:
            taken = 0
            reuse = np.zeros(len(sh), bool)
            fresh_rank = ranks
            local_ix = self._next_free[sh] + ranks
        full = ~reuse & (fresh_rank >= np.repeat(room, run_lens))
        self._next_free[run_sh] += np.minimum(run_lens - taken, room)
        slot = (sh - self.shard_lo) * self.slots_per_shard + local_ix
        slot[full] = self.FULL
        ok = slot[~full]
        self._rev_keys[ok] = keys[sub[~full]]
        self._rev_used[ok] = True
        self._note_allocated(ok)
        out[sub] = slot
        return out

    def _note_allocated(self, ok: np.ndarray) -> None:
        """Slots just handed out, in the allocator's order."""
        self._n_keys += len(ok)
        self.slots_allocated += len(ok)
        if self._n_keys > self.keys_peak:
            self.keys_peak = self._n_keys
        if self._newest is not None and len(ok):
            self._fresh.append(ok)

    def key_of_slots(self, slots: np.ndarray) -> np.ndarray:
        return self._rev_keys[slots]

    def used_mask(self) -> np.ndarray:
        """(local_slots,) bool — which slots hold a registered key."""
        return self._rev_used

    def ever_used_mask(self) -> np.ndarray:
        """(local_slots,) bool — which slots have ever held a key (those
        below their shard's free pointer). Grows only; what the device
        needs to tell pane rows from rows nothing ever wrote."""
        return (np.arange(self.slots_per_shard)[None, :]
                < self.free_pointers()[:, None]).reshape(-1)

    def free_pointers(self) -> np.ndarray:
        """(local shards,) the slots of each shard that have ever held a
        key: ``ever_used_mask`` in 8 bytes a shard."""
        return self._next_free[self.shard_lo:self.shard_hi]

    def slots_ever_used(self) -> int:
        return int(self.free_pointers().sum())

    def num_keys(self) -> int:
        return self._n_keys

    def table_growth(self) -> Tuple[int, float, int]:
        """``(doublings of the key table so far, the seconds they took,
        its buckets now)``, counted inside the table whichever lane's
        call grew it."""
        return self._table.growth()

    # -- release and reuse ----------------------------------------------
    def track_panes(self) -> None:
        """From here on the owner tells the newest pane of every key it
        folds (``note_panes`` / ``note_pairs``) and asks at each purge
        which keys have none left (``release_below``)."""
        if self._newest is None:
            self._newest = np.full(self.local_slots, _NO_PANE, np.int64)
            used = np.flatnonzero(self._rev_used)
            self._fresh = [used] if len(used) else []
            self._by_pane = {}

    def note_panes(self, slots: np.ndarray, panes: np.ndarray,
                   valid: np.ndarray) -> None:
        """``newest[slot] = max(newest[slot], pane)`` for a batch's valid
        records that have a slot."""
        note_newest(self._newest, slots, panes, valid)

    def note_pairs(self, pairs: np.ndarray, ring: int, pane_lo: int) -> None:
        """The same from a fused scan's distinct (slot * ring + column)
        pairs (native only: that scan is), whose panes lie in
        ``[pane_lo, pane_lo + ring)``."""
        from flink_tpu.native_codec import slot_panes_note_pairs_native

        slot_panes_note_pairs_native(pairs, ring, pane_lo, self._newest)

    def note_all(self, pane: int) -> None:
        """Every registered key as if seen in ``pane``: what a restore
        from a snapshot without newest panes falls back to (an upper
        bound keeps a key too long, never too short)."""
        self._newest[self._rev_used] = pane

    def release_below(self, dead: int) -> np.ndarray:
        """Release every key whose newest pane is below ``dead`` (the
        first pane still alive): out of the table, out of ``used_mask``.
        Returns their slots, which stay out of the allocator until
        ``reclaim``. Examines the slots allocated since the last call
        and those last seen alive in a pane below ``dead``; a slot found
        alive goes to the list of the pane it now has."""
        cands = self._fresh
        self._fresh = []
        for p in [p for p in self._by_pane if p < dead]:
            cands.extend(self._by_pane.pop(p))
        if not cands:
            return np.zeros(0, np.int64)
        c = cands[0] if len(cands) == 1 else np.concatenate(cands)
        newest = self._newest[c]
        gone = newest < dead
        if not gone.all():
            stay, pn = c[~gone], newest[~gone]
            order = np.argsort(pn, kind="stable")
            stay, pn = stay[order], pn[order]
            starts, lens, _ = _runs(pn)
            for a, n in zip(starts.tolist(), lens.tolist()):
                self._by_pane.setdefault(int(pn[a]), []).append(
                    stay[a:a + n])
            if not gone.any():
                return np.zeros(0, np.int64)
        rel = c[gone]
        self.release_slots(rel)
        return rel

    def release_slots(self, slots: np.ndarray) -> None:
        """Release the keys of ``slots`` (registered, distinct): out of
        the table, out of ``used_mask``. The slots stay out of the
        allocator until ``reclaim``. For an owner that knows itself
        which keys have nothing left (the device session operator: a
        key whose last session has fired), as ``release_below`` is for
        one that asks by pane."""
        self._table.delete_batch(self._rev_keys[slots])
        self._rev_used[slots] = False
        if self._newest is not None:
            self._newest[slots] = _NO_PANE
        self._n_keys -= len(slots)
        self.slots_released += len(slots)

    def reclaim(self, slots: np.ndarray) -> None:
        """Released slots back to their shards' allocators."""
        if not len(slots):
            return
        spd = self.slots_per_shard
        if self._free is None:
            self._free = np.empty((len(self._n_free), spd), np.int32)
        ls = slots // spd
        order = np.argsort(ls, kind="stable")
        ls, li = ls[order], (slots % spd)[order]
        starts, lens, ranks = _runs(ls)
        self._free[ls, self._n_free[ls] + ranks] = li
        self._n_free[ls[starts]] += lens

    def free_slots(self) -> np.ndarray:
        """The reclaimed slots the allocator holds, each shard's in the
        order ``reclaim`` would need to give them back."""
        if self._free is None:
            return np.zeros(0, np.int64)
        return np.concatenate([np.zeros(0, np.int64)] + [
            s * self.slots_per_shard + self._free[s, :n].astype(np.int64)
            for s, n in enumerate(self._n_free.tolist()) if n])

    # -- snapshot (part of the checkpoint manifest) ----------------------
    def snapshot(self, run_tasks=None) -> Dict[str, np.ndarray]:
        """Copies of the arrays a restore needs. The slot-sized ones
        (``rev_keys``, ``newest_pane``, ``rev_used``: 17 bytes a slot)
        are the whole cost at millions of slots, and the caller's loop
        stands still meanwhile: ``run_tasks`` (a host pool's: thunks in,
        run side by side, all done on return) takes them in ranges;
        numpy copies without the interpreter lock."""
        tasks = []

        def copied(a: np.ndarray) -> np.ndarray:
            out = np.empty_like(a)
            step = max(_SNAPSHOT_RANGE_MIN, -(-len(a) // _SNAPSHOT_RANGES))
            for o in range(0, len(a), step):
                tasks.append(functools.partial(
                    np.copyto, out[o:o + step], a[o:o + step]))
            return out

        out = {
            "rev_keys": copied(self._rev_keys),
            "rev_used": copied(self._rev_used),
            "next_free": self._next_free.copy(),
            "free_slots": self.free_slots(),
        }
        if self._newest is not None:
            out["newest_pane"] = copied(self._newest)
        if run_tasks is None:
            for task in tasks:
                task()
        else:
            run_tasks(tasks)
        return out

    @classmethod
    def restore(cls, num_shards: int, slots_per_shard: int,
                snap: Dict[str, np.ndarray],
                shard_range: Tuple[int, int] | None = None) -> "KeyDirectory":
        d = cls(num_shards, slots_per_shard, shard_range)
        d._rev_keys = np.array(snap["rev_keys"], np.int64)
        d._rev_used = np.array(snap["rev_used"], bool)
        d._next_free = np.array(snap["next_free"], np.int64)
        used = np.nonzero(d._rev_used)[0]
        keys = d._rev_keys[used]
        if len(used):
            d._table.insert_batch(keys, hash_keys_numpy(keys), used)
        d._n_keys = d.keys_peak = len(used)
        free = snap.get("free_slots")
        if free is None:
            # a snapshot that carries no allocator state (an older one,
            # or one merged for a rescale): every slot below its shard's
            # free pointer that holds no key is one that was released
            free = np.flatnonzero(d.ever_used_mask() & ~d._rev_used)
        d.reclaim(np.asarray(free, np.int64))
        if snap.get("newest_pane") is not None:
            d._newest = np.array(snap["newest_pane"], np.int64)
            d._fresh = [used] if len(used) else []
        return d


class ReuseRule:
    """THE REUSE RULE, written once for every operator that releases
    keys while fired rows still name their slots (``WindowOperator``:
    by pane purges; the device session operator: by the fire itself).
    Fired rows leave the device as SLOT numbers and become keys on the
    host only when the drain decodes them, after its deferral and on
    another thread; a fire, the release of its keys and the next
    batch's allocation can all come before that. So a released slot is
    stamped with the number of fires dispatched when its release runs
    (``_hold_released``) and goes back to its shard's allocator
    (``_return_released``) only once every fire up to that number has
    had its rows decoded (``_drained_through``). Fires dispatched after
    the release cannot name the slot for its old key. The operator
    brings ``directory``, ``emit_ring`` (whose ``fires_decoded`` the
    drain moves) and ``phases``."""

    def _init_reuse(self) -> None:
        # released slots the allocator may not have yet, oldest first:
        # (fires dispatched at the release, slots)
        self._waiting: collections.deque = collections.deque()
        self._n_waiting = 0
        self.slots_waiting_peak = 0
        # the rule's tripwire: stays 0 while it holds
        self.slots_returned_early = 0

    def _hold_released(self, fires_so_far: int, slots: np.ndarray) -> None:
        """``slots`` were released with ``fires_so_far`` fires
        dispatched: they wait for those fires' decode."""
        self._waiting.append((fires_so_far, slots))
        self._n_waiting += len(slots)
        if self._n_waiting > self.slots_waiting_peak:
            self.slots_waiting_peak = self._n_waiting

    def _drained_through(self) -> int:
        """Every fire numbered up to this has had its rows decoded into
        keys by the drain (the reuse rule's other half)."""
        return self.emit_ring.fires_decoded

    def _return_released(self) -> None:
        """Ahead of a batch's allocations: hand the allocator every
        waiting slot the reuse rule lets go. One comparison when none
        is due."""
        if not self._waiting:
            return
        through = self._drained_through()
        if self._waiting[0][0] > through:
            return
        with self.phases.span("state.reclaim"):
            back = []
            while self._waiting and self._waiting[0][0] <= through:
                stamp, slots = self._waiting.popleft()
                if stamp > self.emit_ring.fires_decoded:
                    self.slots_returned_early += len(slots)
                back.append(slots)
            slots = back[0] if len(back) == 1 else np.concatenate(back)
            self._n_waiting -= len(slots)
            self.directory.reclaim(slots)

    def _forget_waiting(self) -> None:
        """After a restore: pre-restore fires are a dead timeline."""
        self._waiting.clear()
        self._n_waiting = 0


def note_newest(newest: np.ndarray, slots: np.ndarray, values: np.ndarray,
                valid: np.ndarray) -> None:
    """``newest[slot] = max(newest[slot], value)`` over a batch's valid
    records that have a slot (int64 all: a pane number, a timestamp), in
    C where the codec library is there."""
    from flink_tpu.native_codec import slot_panes_note_native

    if slot_panes_note_native(slots, values, valid, newest):
        return
    ok = valid & (slots >= 0)
    sl, v = slots[ok], values[ok]
    if len(v) > 1 and (v[1:] < v[:-1]).any():
        order = np.argsort(v, kind="stable")
        sl, v = sl[order], v[order]
    # the values ascend, and of a repeated index the last value is the
    # one assigned: each slot gets its largest of the batch
    newest[sl] = np.maximum(newest[sl], v)


def account_full_drop(op, n: int) -> None:
    """Key-directory overflow policy (ref: the RocksDB role — the
    reference DEGRADES on state growth, it never drops, SURVEY §3.4).
    The default refuses to lose data: a full shard FAILS the job with
    the remediation options; ``state.allow-drops=true`` opts into
    dropping with accounting (the records_dropped_full gauge stays)."""
    if n <= 0:
        return
    if not getattr(op, "allow_drops", False):
        raise RuntimeError(
            f"key directory shard full: {n} record(s) have no state "
            "slot (state.num-key-shards x state.slots-per-shard "
            "exceeded, or keys routed outside this worker's shard "
            "range). A window operator on the hbm backend releases a "
            "key's slot once its last pane has been purged, and hands "
            "it out again once every fire dispatched before that has "
            "been drained: the budget has to hold the keys alive at "
            "once, plus those waiting for the drain. The default "
            "policy never drops data - use state.backend='spill' for "
            "exact host-side degradation, raise the slot budget, or "
            "set state.allow-drops=true to drop with accounting "
            "(records_dropped_full).")
    op.records_dropped_full += n
