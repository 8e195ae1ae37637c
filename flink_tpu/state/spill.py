"""Host spill store: exact windowed aggregation for keys beyond HBM.

The dense pane-tensor backend (state/keyed.py) holds a FIXED number of
key slots per shard in HBM. The reference degrades gracefully past RAM
via RocksDB (ref: runtime/state/RocksDBKeyedStateBackend role, SURVEY
§3.4): state beyond memory gets slower, never wrong. This module is the
TPU-native analogue — but instead of swapping slots over the
host↔device link the way RocksDB pages SSTs, it exploits that every
lane aggregate is a commutative monoid (sum/max/min/count): records
whose keys cannot get an HBM slot are aggregated ON THE HOST in
vectorized numpy, per (key, pane), and the host partials fire
alongside the device partials. A key lives in
exactly one store: the directory enters a key that failed slot
allocation with that verdict, and it keeps it; and under a spill store
the window operator releases no slot (on the plain hbm backend a key's
slot is released when its last pane is purged and handed out again:
here that could bring a host-resident key a slot and split its panes
over both stores). So the two stores'
key sets are disjoint and their fired rows simply concatenate: exact
results, no cross-store merge. Hot early keys keep HBM speed; overflow
keys degrade to host speed. (LRU slot eviction — promoting a late-hot
key into HBM — is a possible refinement; it would add per-eviction
link round trips (cost not measured on the current chip), so v1 keeps
placement static.)

Fire/refire/purge mirror the device path exactly: the operator passes
the SAME fired-ends list (including re-fires of late-within-lateness
data) to both stores, and purges both at the same lateness horizon.

Host-parallel plane: given a ``HostPool`` the
store runs its independent units as pool tasks — per-pane merges in
``absorb`` (absorb already buckets by pane and ``_merge_pane`` touches
only that pane's table), per-window combines in ``fire`` (windows own
disjoint pane ranges), and above the ``host.fold-chunk-records`` batch
floor a chunked TREE fold: chunks group independently, pane partials
combine in chunk order (the windowAll scaling shape — one global key,
so key-sharding cannot apply). The pane→table dict's serial point is
guarded by one lock PER PANE ENTRY, not a global lock. Chunk size is
independent of the worker count, so the reduction tree — and the
output bytes for the exact lane monoids — never change with
``host.parallelism``; pool absent or parallelism 1 is the exact
serial path.
"""
from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from flink_tpu.hostsync import host_cpu_device

_NEG_INF = np.float32(-np.inf)
_POS_INF = np.float32(np.inf)


class HostSpillStore:
    """Per-(key, pane) lane accumulators in host numpy arrays.

    Layout: ``panes[p] = (keys sorted (K,), sums (K,S), maxs (K,M),
    mins (K,m), counts (K,))``. Batch absorption is one lexsort +
    segment reduce; merging into a pane is a sorted-union splice. Both
    are O(records + keys) vectorized numpy — no per-key Python loops
    (the round-2 session-registry mistake, not repeated here).
    """

    def __init__(self, agg, *, pool=None,
                 fold_chunk_records: Optional[int] = None):
        # NOTE: deliberately untyped — the state layer sits BELOW ops in
        # the layer map (tests/test_architecture.py) and only needs the
        # lane contract: sum/max/min_width, lift_masked, finalize.
        # ``pool`` is equally duck-typed (parallel.hostpool.HostPool):
        # .parallelism + .run_tasks(fns) — None or parallelism 1 keeps
        # the exact serial path.
        self.agg = agg
        self.panes: Dict[int, Tuple[np.ndarray, np.ndarray, np.ndarray,
                                    np.ndarray, np.ndarray]] = {}
        self.records_spilled = 0
        self._pool = (pool if pool is not None
                      and pool.parallelism > 1 else None)
        if fold_chunk_records is None:
            # None = the declared config default — the floor is
            # single-sourced at HostOptions.FOLD_CHUNK_RECORDS so a
            # retune there reaches directly-constructed stores too
            from flink_tpu.config import HostOptions
            fold_chunk_records = HostOptions.FOLD_CHUNK_RECORDS.default
        self.fold_chunk_records = int(fold_chunk_records)
        # one lock PER PANE entry, never a global lock. Within
        # one run_tasks batch every pane has at most one merge task
        # (absorb's spans are pane-contiguous; the tree fold combines
        # all of a pane's chunk partials inside a single task), and
        # the operator's absorb/fire entry points run sequentially on
        # the driver loop today — the locks are the pane tables'
        # read-modify-write guard for any caller that DOES overlap
        # absorb batches, so the store's safety never depends on that
        # entry discipline. Fire-side reads stay lock-free:
        # _merge_pane replaces a pane's tuple atomically.
        self._pane_locks: Dict[int, threading.Lock] = {}
        self._locks_guard = threading.Lock()

    def _pane_lock(self, pane: int) -> threading.Lock:
        with self._locks_guard:
            return self._pane_locks.setdefault(pane, threading.Lock())

    # -- ingest ----------------------------------------------------------

    def _lift(self, data: Dict[str, np.ndarray], n: int
              ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Evaluate the aggregate's lane lift for ``n`` host rows.
        ``lift_masked`` is written in jnp; pin it to the CPU backend so
        spilled records never ride the device link (that's the whole
        point). No CPU backend visible is an error (host_cpu_device)."""
        valid = np.ones(n, bool)
        with jax.default_device(host_cpu_device()):
            s, mx, mn = self.agg.lift_masked(data, valid)
        return np.asarray(s), np.asarray(mx), np.asarray(mn)

    def absorb(self, keys: np.ndarray, panes: np.ndarray,
               data: Dict[str, np.ndarray]) -> None:
        """Fold overflow records into the per-(key, pane) accumulators."""
        n = len(keys)
        if n == 0:
            return
        self.records_spilled += n
        if self._pool is not None and n >= self.fold_chunk_records:
            self._absorb_tree(keys, panes, data)
            return
        groups = self._group_batch(keys, panes, data)
        self._splice_groups(*groups)

    def _group_batch(self, keys: np.ndarray, panes: np.ndarray,
                     data: Dict[str, np.ndarray]) -> Tuple[np.ndarray, ...]:
        """One vectorized (pane, key) grouping pass: lexsort + boundary
        flags + segment reduce. Returns pane-contiguous group arrays."""
        n = len(keys)
        sums, maxs, mins = self._lift(data, n)
        o = np.lexsort((keys, panes))
        pk, kk = panes[o], keys[o]
        new_grp = np.empty(n, bool)
        new_grp[0] = True
        new_grp[1:] = (pk[1:] != pk[:-1]) | (kk[1:] != kk[:-1])
        gid = np.cumsum(new_grp) - 1
        G = int(gid[-1]) + 1
        S, M, m = self.agg.sum_width, self.agg.max_width, self.agg.min_width
        g_sum = np.zeros((G, S), np.float32)
        np.add.at(g_sum, gid, sums[o])
        g_max = np.full((G, M), _NEG_INF, np.float32)
        np.maximum.at(g_max, gid, maxs[o])
        g_min = np.full((G, m), _POS_INF, np.float32)
        np.minimum.at(g_min, gid, mins[o])
        g_cnt = np.bincount(gid, minlength=G).astype(np.int64)
        return pk[new_grp], kk[new_grp], g_sum, g_max, g_min, g_cnt

    @staticmethod
    def _pane_spans(g_pane: np.ndarray) -> List[Tuple[int, int]]:
        bounds = np.flatnonzero(
            np.concatenate([[True], g_pane[1:] != g_pane[:-1], [True]]))
        return [(int(bounds[i]), int(bounds[i + 1]))
                for i in range(len(bounds) - 1)]

    def _splice_groups(self, g_pane, g_key, g_sum, g_max, g_min,
                       g_cnt) -> None:
        """Splice each touched pane (few per batch — event-time
        locality); independent per pane, so with a pool the merges run
        as parallel tasks under their pane locks (§9.3)."""
        spans = self._pane_spans(g_pane)

        def merge(a: int, b: int) -> None:
            pane = int(g_pane[a])
            with self._pane_lock(pane):
                self._merge_pane(pane, g_key[a:b], g_sum[a:b],
                                 g_max[a:b], g_min[a:b], g_cnt[a:b])

        if self._pool is not None and len(spans) > 1:
            self._pool.run_tasks(
                [lambda a=a, b=b: merge(a, b) for a, b in spans])
        else:
            for a, b in spans:
                merge(a, b)

    def _absorb_tree(self, keys: np.ndarray, panes: np.ndarray,
                     data: Dict[str, np.ndarray]) -> None:
        """Chunked tree fold (§9.2, the windowAll scaling shape): group
        fixed-size chunks on the pool, then combine each pane's chunk
        partials IN CHUNK ORDER. The chunk size is a config constant
        (never derived from the worker count), so the reduction tree is
        identical at every host.parallelism > 1."""
        n = len(keys)
        chunk = self.fold_chunk_records
        data = {k: np.asarray(v) for k, v in data.items()}
        spans = [(lo, min(lo + chunk, n)) for lo in range(0, n, chunk)]
        parts = self._pool.run_tasks(
            [lambda lo=lo, hi=hi: self._group_batch(
                keys[lo:hi], panes[lo:hi],
                {k: v[lo:hi] for k, v in data.items()})
             for lo, hi in spans])
        # pane → its chunk partials, insertion-ordered by chunk index
        per_pane: Dict[int, List[Tuple[np.ndarray, ...]]] = {}
        for g_pane, g_key, g_sum, g_max, g_min, g_cnt in parts:
            for a, b in self._pane_spans(g_pane):
                per_pane.setdefault(int(g_pane[a]), []).append(
                    (g_key[a:b], g_sum[a:b], g_max[a:b], g_min[a:b],
                     g_cnt[a:b]))

        def combine(pane: int, pieces) -> None:
            with self._pane_lock(pane):
                for piece in pieces:  # chunk order: deterministic tree
                    self._merge_pane(pane, *piece)

        self._pool.run_tasks(
            [lambda p=p, pcs=pcs: combine(p, pcs)
             for p, pcs in per_pane.items()])

    def _merge_pane(self, pane: int, keys, sums, maxs, mins, counts) -> None:
        cur = self.panes.get(pane)
        if cur is None:
            self.panes[pane] = (keys.copy(), sums.copy(), maxs.copy(),
                                mins.copy(), counts.copy())
            return
        ck, cs, cx, cn, cc = cur
        union = np.union1d(ck, keys)
        K = len(union)
        S, M, m = self.agg.sum_width, self.agg.max_width, self.agg.min_width
        us = np.zeros((K, S), np.float32)
        ux = np.full((K, M), _NEG_INF, np.float32)
        un = np.full((K, m), _POS_INF, np.float32)
        uc = np.zeros(K, np.int64)
        po = np.searchsorted(union, ck)
        pn = np.searchsorted(union, keys)
        us[po] = cs
        us[pn] += sums
        ux[po] = cx
        ux[pn] = np.maximum(ux[pn], maxs)
        un[po] = cn
        un[pn] = np.minimum(un[pn], mins)
        uc[po] = cc
        uc[pn] += counts
        self.panes[pane] = (union, us, ux, un, uc)

    # -- fire ------------------------------------------------------------

    def fire(self, ends: List[int], panes_per_window: int, pane_ms: int,
             offset_ms: int, size_ms: int) -> Optional[Dict[str, np.ndarray]]:
        """Fired rows for the given end panes, combined across each
        window's panes with the same monoid ops the device kernel uses.
        Returns None when no stored pane intersects any window (the
        common case — keep the hot path allocation-free)."""
        if not self.panes or not ends:
            return None
        ppw = panes_per_window
        lo_stored = min(self.panes)
        hi_stored = max(self.panes)
        live = [e for e in ends if e > lo_stored and e - ppw <= hi_stored]
        if not live:
            return None
        # windows own disjoint pane ranges' COMBINE work (reads only),
        # so per-window fires are independent pool tasks (§9.3);
        # results assemble in the fired-ends order either way
        if self._pool is not None and len(live) > 1:
            fired = self._pool.run_tasks(
                [lambda e=e: self._fire_window(e, ppw) for e in live])
        else:
            fired = [self._fire_window(e, ppw) for e in live]
        keys_out: List[np.ndarray] = []
        ends_out: List[np.ndarray] = []
        cnt_out: List[np.ndarray] = []
        res_cols: Dict[str, List[np.ndarray]] = {}
        for hit in fired:
            if hit is None:
                continue
            e, kk, wc_has, res = hit
            keys_out.append(kk)
            ends_out.append(np.full(len(kk), e, np.int64))
            cnt_out.append(wc_has)
            for f, v in res.items():
                if f == "count":
                    continue  # the exact element count wins (mirrors
                    # _decode_packs preferring the i32 count column)
                res_cols.setdefault(f, []).append(np.asarray(v))
        if not keys_out:
            return None
        end_pane = np.concatenate(ends_out)
        window_end = end_pane * pane_ms + offset_ms
        out: Dict[str, np.ndarray] = {
            "key": np.concatenate(keys_out),
            "window_start": window_end - size_ms,
            "window_end": window_end,
            "count": np.concatenate(cnt_out),
        }
        for f, cols in res_cols.items():
            out[f] = np.concatenate(cols)
        return out

    def _fire_window(self, e: int, ppw: int
                     ) -> Optional[Tuple[int, np.ndarray, np.ndarray, Dict]]:
        """Combine one window's panes with the same monoid ops the
        device kernel uses; returns (end_pane, keys, counts, finalize
        fields) or None when the window holds nothing."""
        S, M, m = self.agg.sum_width, self.agg.max_width, self.agg.min_width
        span = [self.panes[p] for p in range(e - ppw, e)
                if p in self.panes]
        if not span:
            return None
        union = span[0][0] if len(span) == 1 else np.unique(
            np.concatenate([s[0] for s in span]))
        K = len(union)
        ws = np.zeros((K, S), np.float32)
        wx = np.full((K, M), _NEG_INF, np.float32)
        wn = np.full((K, m), _POS_INF, np.float32)
        wc = np.zeros(K, np.int64)
        for ck, cs, cx, cn, cc in span:
            pos = np.searchsorted(union, ck)
            ws[pos] += cs
            wx[pos] = np.maximum(wx[pos], cx)
            wn[pos] = np.minimum(wn[pos], cn)
            wc[pos] += cc
        has = wc > 0
        if not has.any():
            return None
        with jax.default_device(host_cpu_device()):
            res = self.agg.finalize(ws[has], wx[has], wn[has],
                                    wc[has].astype(np.int32))
        return e, union[has], wc[has], res

    # -- lifecycle -------------------------------------------------------

    def purge_below(self, dead_pane: int) -> None:
        for p in [p for p in self.panes if p < dead_pane]:
            del self.panes[p]
        with self._locks_guard:  # locks track live panes, never grow
            for p in [p for p in self._pane_locks if p < dead_pane]:
                del self._pane_locks[p]

    def bytes_used(self) -> int:
        """Host memory held by spilled panes (memory.host_spill_bytes).
        Called from the metrics scrape thread while ingest mutates the
        dict — list() snapshots the values atomically under the GIL."""
        return sum(sum(a.nbytes for a in arrs)
                   for arrs in list(self.panes.values()))

    @property
    def key_count(self) -> int:
        if not self.panes:
            return 0
        ks = [t[0] for t in self.panes.values()]
        return len(np.unique(np.concatenate(ks)))

    def snapshot(self) -> Dict[str, Any]:
        return {
            "panes": {int(p): tuple(a.copy() for a in t)
                      for p, t in self.panes.items()},
            "records_spilled": self.records_spilled,
        }

    def restore(self, snap: Dict[str, Any]) -> None:
        self.panes = {int(p): tuple(np.asarray(a) for a in t)
                      for p, t in snap["panes"].items()}
        self.records_spilled = int(snap["records_spilled"])
