"""Session windows — gap-merged, dynamically-bounded windows.

ref: streaming/api/windowing/assigners/EventTimeSessionWindows.java and
the merge machinery MergingWindowSet.java + WindowOperator's merging
branch (each element opens [ts, ts+gap) and overlapping windows merge,
state merges via namespace re-targeting).

This module is the HOST lane: the session operator of jobs with allowed
lateness, retract rows or a mesh. Every other session job keeps its
sessions on the device (``ops/session_device.py``; the driver chooses,
``device_lane_fits``); the two give the same rows for a job both can
run and write one snapshot format (``_merged_columns``).

Dynamic merging cannot be a static pane layout (SURVEY §8.4 item 3), so
the decomposition here is:
- **batch sessionization is vectorized**: sort the microbatch by
  (key, ts); session boundaries are where the key changes or the time
  gap exceeds ``gap``; per-batch-session aggregates come from numpy
  ``reduceat`` segments (C-speed host work — the per-RECORD cost is
  vectorized away, matching how the reference's cost is per element).
- the **span registry is COLUMNAR** (struct-of-arrays sorted by
  (key, start), one row per open/retained session — the
  MergingWindowSet role at fleet scale): batch segments merge into it
  with one lexsort + an offset-encoded interval-union scan + reduceat
  combines. No per-key Python objects, no per-span loops — a 1M-key
  churn batch costs a few array passes (the round-2 registry held a
  Python list of dataclasses per key and died at exactly that scale).
- fired sessions stay in the registry until allowed lateness expires so
  late records re-open/merge and re-fire (late firing semantics).
- the registry is **key-sharded onto the host pool**:
  under ``host.parallelism = W > 1`` it splits into W independent span
  stores (``key % W`` — the key-group discipline), and the per-shard
  merge/fire/expiry passes run as pool tasks. Sessions never merge
  across keys, so no cross-shard invariant exists; fired shards'
  rows re-sort by (key, start) so output bytes match the serial path
  exactly (the §9 determinism contract). ``host.parallelism = 1`` IS
  the serial path: one store, no partitioning, no pool threads.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from flink_tpu.ops.aggregates import LaneAggregate
from flink_tpu.records import (
    OP_DTYPE,
    OP_FIELD,
    OP_INSERT,
    OP_UPDATE_AFTER,
    OP_UPDATE_BEFORE,
)
from flink_tpu.time.watermarks import LONG_MIN


class _SpanStore:
    """Columnar open/retained-session registry, sorted by (key, start).

    Invariant: per key, spans are disjoint and separated by more than
    ``gap`` (anything closer would have merged), so two REGISTRY spans
    can only merge when a new batch segment bridges them.
    """

    def __init__(self, sum_w: int, max_w: int, min_w: int) -> None:
        self.key = np.zeros(0, np.int64)
        self.start = np.zeros(0, np.int64)
        self.last = np.zeros(0, np.int64)   # max event ts; end = last+gap
        self.sums = np.zeros((0, sum_w), np.float32)
        self.maxs = np.zeros((0, max_w), np.float32)
        self.mins = np.zeros((0, min_w), np.float32)
        self.count = np.zeros(0, np.int64)
        self.fired = np.zeros(0, bool)
        self.refire = np.zeros(0, bool)
        # retract mode: True after a -U was emitted for a consumed
        # predecessor — the span's next fire is +U, not +I
        self.retracted = np.zeros(0, bool)

    def __len__(self) -> int:
        return len(self.key)

    _COLS = ("key", "start", "last", "sums", "maxs", "mins", "count",
             "fired", "refire", "retracted")

    def _take(self, idx) -> Tuple[np.ndarray, ...]:
        return tuple(getattr(self, c)[idx] for c in self._COLS)

    def _filter(self, keep: np.ndarray) -> None:
        for c in self._COLS:
            setattr(self, c, getattr(self, c)[keep])

    def ranges_for(self, uk: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """[lo, hi) row ranges of (sorted, unique) keys ``uk``."""
        return (np.searchsorted(self.key, uk, "left"),
                np.searchsorted(self.key, uk, "right"))

    def rows_for(self, uk: np.ndarray) -> np.ndarray:
        """All row indices whose key is in ``uk`` (sorted unique)."""
        lo, hi = self.ranges_for(uk)
        lens = hi - lo
        total = int(lens.sum())
        if total == 0:
            return np.zeros(0, np.int64)
        # concatenated aranges: repeat each lo, add a per-range arange
        reps = np.repeat(lo - np.concatenate(([0], np.cumsum(lens)[:-1])),
                         lens)
        return reps + np.arange(total)

    def insert_sorted(self, cols: Tuple[np.ndarray, ...]) -> None:
        """Insert merge results, keeping (key, start) order — one
        searchsorted + np.insert per column. The store may still hold a
        key's COLD prefix (spans the merge's participation cut passed
        through); every inserted span of that key starts later than its
        cold spans, so inserting at the key block's RIGHT edge preserves
        within-key start order."""
        pos = np.searchsorted(self.key, cols[0], side="right")
        n_old, n_new = len(self.key), len(cols[0])
        # manual two-way merge: compute the destination mask ONCE and
        # fancy-assign each column (np.insert re-derives it per call —
        # measured ~20ms/batch across the 9 columns)
        new_at = pos + np.arange(n_new)
        old_mask = np.ones(n_old + n_new, bool)
        old_mask[new_at] = False
        for c, new in zip(self._COLS, cols):
            cur = getattr(self, c)
            out = np.empty((n_old + n_new,) + cur.shape[1:], cur.dtype)
            out[old_mask] = cur
            out[new_at] = new
            setattr(self, c, out)


class SessionOperator:
    """Keyed event-time session aggregation with allowed lateness."""

    def __init__(
        self,
        gap_ms: int,
        agg: LaneAggregate,
        *,
        allowed_lateness_ms: int = 0,
        num_shards: int = 128,
        slots_per_shard: int = 1024,
        max_out_of_orderness_ms: int = 0,
        host_pool: Optional[Any] = None,
        retract: bool = False,
    ) -> None:
        if gap_ms <= 0:
            raise ValueError("session gap must be positive")
        self.gap = int(gap_ms)
        self.agg = agg
        self.retract = bool(retract)
        # retract rows produced by merges this step, drained by
        # take_fired immediately after each process_batch — the buffer
        # is always empty at checkpoint boundaries (snapshots happen
        # between steps, after emission), so it carries no state
        self._pending_retracts: List[Dict[str, np.ndarray]] = []
        self.lateness = int(allowed_lateness_ms)
        self.watermark = LONG_MIN
        self.late_records = 0
        self.state_version = 0
        # key-sharded registry: W independent stores at
        # host.parallelism = W; exactly one (the serial path) at W = 1
        self._pool = (host_pool if host_pool is not None
                      and host_pool.parallelism > 1 else None)
        n_shards = self._pool.parallelism if self._pool is not None else 1
        self._shards: List[_SpanStore] = [
            _SpanStore(agg.sum_width, agg.max_width, agg.min_width)
            for _ in range(n_shards)]
        self._has_refire = False

    # -- ingest ----------------------------------------------------------
    def process_batch(self, keys, ts, data: Dict[str, np.ndarray], valid=None) -> None:
        self.state_version += 1
        keys = np.asarray(keys, np.int64)
        ts = np.asarray(ts, np.int64)
        valid = np.ones(len(ts), bool) if valid is None else np.asarray(valid, bool)
        if self._pool is None:
            late, refire, retr = self._process_shard(
                self._shards[0], keys, ts, data, valid)
            self.late_records += late
            self._has_refire = self._has_refire or refire
            if retr is not None:
                self._pending_retracts.append(retr)
            return
        # partition by key shard; per-key work is identical to serial
        # (no session logic crosses keys), so per-shard passes compose
        # to the exact serial result
        n_shards = len(self._shards)
        shard = keys % n_shards
        data = {k: np.asarray(v) for k, v in data.items()}
        tasks = []
        for w in range(n_shards):
            m = shard == w
            if not bool(m.any()):
                continue
            tasks.append(lambda st=self._shards[w], m=m: self._process_shard(
                st, keys[m], ts[m],
                {k: v[m] for k, v in data.items()}, valid[m]))
        results = self._pool.run_tasks(tasks)
        self.late_records += sum(late for late, _, _ in results)
        self._has_refire = self._has_refire or any(
            refire for _, refire, _ in results)
        self._pending_retracts.extend(
            retr for _, _, retr in results if retr is not None)

    def _process_shard(self, st: _SpanStore, keys, ts,
                       data: Dict[str, np.ndarray], valid
                       ) -> Tuple[int, bool, Optional[Dict[str, np.ndarray]]]:
        """Full ingest pass for one shard's records against its store;
        returns (beyond-lateness drop count, refire-pending flag,
        retract rows from consumed fired spans or None). At
        host.parallelism=1 this IS the whole batch — the serial path.
        The results ride the return value rather than being written to
        ``self`` so pool-shard passes never touch shared state; the
        caller folds the per-shard results on its own thread."""
        late_count = 0
        # drop beyond-lateness records (side output accounting): a record
        # is late iff its singleton session is dead AND it cannot merge
        # into any retained span (the reference checks isWindowLate on
        # the POST-merge window — a record touching a live retained
        # session rides that session's lateness)
        if self.watermark != LONG_MIN:
            late = valid & (ts + self.gap - 1 + self.lateness <= self.watermark)
            cand = np.nonzero(late)[0]
            if len(cand):
                # Vectorized merge-rescue check (was a per-candidate
                # Python loop — tens of ms per batch at 2% lateness):
                # per key, spans are disjoint and > gap apart, so the
                # ONLY span a record t can merge with is the rightmost
                # one with start <= t + gap — one searchsorted over the
                # candidate keys' span subset finds it.
                uk = np.unique(keys[cand])
                rows = st.rows_for(uk)
                if len(rows):
                    sk_sub = st.key[rows]
                    ss_sub = st.start[rows]
                    sl_sub = st.last[rows]
                    tmin = int(ss_sub.min())
                    span = int(ss_sub.max()) - tmin + 2
                    if (len(uk) + 1) * span < 2**62:
                        krank = np.searchsorted(uk, sk_sub).astype(np.int64)
                        enc = krank * span + (ss_sub - tmin)
                        ck = np.searchsorted(uk, keys[cand]).astype(np.int64)
                        q = ck * span + np.clip(
                            ts[cand] + self.gap - tmin, 0, span - 1)
                        pos = np.searchsorted(enc, q, "right") - 1
                        posc = np.clip(pos, 0, len(rows) - 1)
                        ok = ((pos >= 0) & (krank[posc] == ck)
                              & (ts[cand] <= sl_sub[posc] + self.gap)
                              & (ss_sub[posc] <= ts[cand] + self.gap))
                        late[cand[ok]] = False
                    else:  # pathological time range (same guard as the
                        # merge's encoding): per-candidate check
                        lo, hi = st.ranges_for(uk)
                        p = np.searchsorted(uk, keys[cand])
                        for j, i in enumerate(cand):
                            a, b = lo[p[j]], hi[p[j]]
                            t = ts[i]
                            if a < b and bool(np.any(
                                    (st.start[a:b] <= t + self.gap)
                                    & (t <= st.last[a:b] + self.gap))):
                                late[i] = False
            late_count = int(late.sum())
            valid = valid & ~late
        if not valid.any():
            return late_count, False, None
        keys = keys[valid]
        ts = ts[valid]
        data = {k: np.asarray(v)[valid] for k, v in data.items()}

        # vectorized batch sessionization: sort by (key, ts) — an
        # encoded single-key argsort (key band + in-batch ts offset)
        # beats np.lexsort ~3x at this size
        tmin = int(ts.min())
        tspan = int(ts.max()) - tmin + 1
        if int(np.abs(keys).max()) < (2**62) // max(tspan, 1):
            enc = keys * tspan + (ts - tmin)
            if data:
                order = np.argsort(enc, kind="stable")
                sk, st_ = keys[order], ts[order]
            else:
                es = np.sort(enc)
                sk, st_ = es // tspan, es % tspan + tmin
                order = None
        else:  # astronomically wide key domain: fall back
            order = np.lexsort((ts, keys))
            sk, st_ = keys[order], ts[order]
        sdata = ({k: v[order] for k, v in data.items()}
                 if data else {})
        new_seg = np.empty(len(sk), bool)
        new_seg[0] = True
        new_seg[1:] = (sk[1:] != sk[:-1]) | (st_[1:] - st_[:-1] > self.gap)
        seg_starts = np.nonzero(new_seg)[0]

        # per-segment lane aggregates (host lift on CPU jax → numpy)
        s_l, mx_l, mn_l = self._host_lift(sdata, np.ones(len(sk), bool))
        G = len(seg_starts)
        seg_sum = (np.add.reduceat(s_l, seg_starts, axis=0)
                   if s_l.shape[1] else np.zeros((G, 0), np.float32))
        seg_max = (np.maximum.reduceat(mx_l, seg_starts, axis=0)
                   if mx_l.shape[1] else np.zeros((G, 0), np.float32))
        seg_min = (np.minimum.reduceat(mn_l, seg_starts, axis=0)
                   if mn_l.shape[1] else np.zeros((G, 0), np.float32))
        seg_ends = np.append(seg_starts[1:], len(sk))
        refire, retr = self._merge_segments(
            st, sk[seg_starts], st_[seg_starts], st_[seg_ends - 1],
            seg_sum, seg_max, seg_min,
            (seg_ends - seg_starts).astype(np.int64))
        return late_count, refire, retr

    def _host_lift(self, data, valid) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Run the aggregate's lift on the host CPU backend (session lane
        math is per-batch-segment, tiny — shipping it to the accelerator
        would cost a round trip per batch)."""
        import jax

        from flink_tpu.hostsync import host_cpu_device

        with jax.default_device(host_cpu_device()):
            import jax.numpy as jnp

            s, mx, mn = self.agg.lift_masked(
                {k: jnp.asarray(v) for k, v in data.items()}, jnp.asarray(valid))
            return np.asarray(s), np.asarray(mx), np.asarray(mn)

    def _merge_segments(self, st: _SpanStore, seg_key, seg_tmin, seg_tmax,
                        seg_sum, seg_max, seg_min, seg_count
                        ) -> Tuple[bool, Optional[Dict[str, np.ndarray]]]:
        """Merge batch segments into shard registry ``st`` — the
        MergingWindowSet role, fully vectorized: pull every touched
        key's spans, run one interval-union scan over (touched ∪ new)
        sorted by (key, start), combine groups with reduceat, splice
        the results back. In retract mode, returns a -U row for every
        FIRED registry span a merge consumes: its emitted (key,
        window_start, window_end, aggregates) row is now stale and the
        accumulators still hold exactly the values it fired with (a
        fired span only changes by being consumed, which destroys it)."""
        gap = self.gap
        uk, first = np.unique(seg_key, return_index=True)
        touched_idx = st.rows_for(uk)
        if len(touched_idx):
            # participation cut: a registry span whose chain end
            # (last + gap) precedes its key's OLDEST new segment can
            # neither merge with nor be bridged to anything in this
            # batch (registry spans of one key are already > gap
            # apart), so it passes through untouched. Under lateness
            # retention most of a key's spans are such cold history —
            # pulling them through the merge was most of its cost.
            key_min = seg_tmin[first]  # segments are (key, ts)-sorted
            kr = np.searchsorted(uk, st.key[touched_idx])
            touched_idx = touched_idx[
                st.last[touched_idx] + gap >= key_min[kr]]
        (tk, tstart, tlast, tsum, tmax, tmin, tcount, tfired,
         trefire, tretr) = st._take(touched_idx)
        if len(touched_idx):
            keep = np.ones(len(st), bool)
            keep[touched_idx] = False
            st._filter(keep)

        n_t = len(tk)
        all_key = np.concatenate([tk, seg_key])
        all_start = np.concatenate([tstart, seg_tmin])
        all_last = np.concatenate([tlast, seg_tmax])
        all_sum = np.concatenate([tsum, seg_sum])
        all_max = np.concatenate([tmax, seg_max])
        all_min = np.concatenate([tmin, seg_min])
        all_count = np.concatenate([tcount, seg_count])
        all_fired = np.concatenate([tfired, np.zeros(len(seg_key), bool)])
        all_refire = np.concatenate([trefire, np.zeros(len(seg_key), bool)])
        all_retr = np.concatenate([tretr, np.zeros(len(seg_key), bool)])
        is_new = np.concatenate(
            [np.zeros(n_t, bool), np.ones(len(seg_key), bool)])

        order = np.lexsort((all_start, all_key))
        k_o = all_key[order]
        s_o = all_start[order]
        l_o = all_last[order]

        # interval-union scan with offset encoding: give each key's
        # timeline its own disjoint numeric band so ONE global
        # maximum.accumulate implements the per-key running chain-end
        # (merge iff start <= chain_last + gap)
        base = int(s_o.min())
        span = int(l_o.max()) + gap - base + 2
        krank = np.searchsorted(uk, k_o).astype(np.int64)
        if (len(uk) + 1) * span < 2**62:
            enc_start = krank * span + (s_o - base)
            enc_chain = krank * span + (l_o - base) + gap
            cm = np.maximum.accumulate(enc_chain)
            grp = np.empty(len(order), bool)
            grp[0] = True
            grp[1:] = enc_start[1:] > cm[:-1]
        else:  # pathological time range: per-key reset scan (rare)
            grp = np.empty(len(order), bool)
            grp[0] = True
            chain = l_o[0]
            for i in range(1, len(order)):
                if k_o[i] != k_o[i - 1] or s_o[i] > chain + gap:
                    grp[i] = True
                    chain = l_o[i]
                else:
                    grp[i] = False
                    chain = max(chain, l_o[i])

        gs = np.nonzero(grp)[0]
        m_key = k_o[gs]
        m_start = s_o[gs]  # group min: sorted by start within key
        m_last = np.maximum.reduceat(l_o, gs)
        m_sum = (np.add.reduceat(all_sum[order], gs, axis=0)
                 if all_sum.shape[1] else np.zeros((len(gs), 0), np.float32))
        m_max = (np.maximum.reduceat(all_max[order], gs, axis=0)
                 if all_max.shape[1] else np.zeros((len(gs), 0), np.float32))
        m_min = (np.minimum.reduceat(all_min[order], gs, axis=0)
                 if all_min.shape[1] else np.zeros((len(gs), 0), np.float32))
        m_count = np.add.reduceat(all_count[order], gs)
        fired_any = np.logical_or.reduceat(all_fired[order], gs)
        refire_any = np.logical_or.reduceat(all_refire[order], gs)
        retr_any = np.logical_or.reduceat(all_retr[order], gs)
        new_any = np.logical_or.reduceat(is_new[order], gs)
        size1 = np.append(gs[1:], len(order)) - gs == 1

        # untouched singleton registry spans pass through unchanged; any
        # group absorbing new content resets fired and inherits refire:
        # a late merge into a FIRED span, or a segment already complete
        # at the current watermark, (re-)fires at the next advance
        complete_now = (self.watermark != LONG_MIN) & (
            m_last + gap - 1 <= self.watermark)
        passthrough = size1 & ~new_any
        m_fired = np.where(passthrough, fired_any, False)
        m_refire = np.where(passthrough, refire_any,
                            fired_any | refire_any | complete_now)
        # a merged span whose constituents emitted (and now retract) a
        # row, or that inherited a still-pending retraction, (re)fires
        # as +U rather than +I
        m_retr = np.where(passthrough, retr_any, fired_any | retr_any)
        retract_rows = None
        if self.retract:
            # -U one row per consumed FIRED registry span (member-level
            # mask: registry member, fired, in a non-passthrough group)
            grp_sizes = np.append(gs[1:], len(order)) - gs
            pass_m = np.repeat(passthrough, grp_sizes)
            rm = ~is_new[order] & all_fired[order] & ~pass_m
            if rm.any():
                retract_rows = self._emit((
                    k_o[rm], s_o[rm], l_o[rm], all_sum[order][rm],
                    all_max[order][rm], all_min[order][rm],
                    all_count[order][rm]))
                retract_rows[OP_FIELD] = np.full(
                    int(rm.sum()), OP_UPDATE_BEFORE, OP_DTYPE)
        st.insert_sorted((m_key, m_start, m_last, m_sum, m_max, m_min,
                          m_count, m_fired, m_refire, m_retr))
        return bool(m_refire.any()), retract_rows

    # -- time ------------------------------------------------------------
    def advance_watermark(self, wm: int):
        from flink_tpu.ops.window import FiredWindows

        if wm < self.watermark and not self._has_refire:
            return FiredWindows(data=self._empty())
        self.state_version += 1
        self.watermark = max(self.watermark, wm)
        self._has_refire = False
        if self._pool is None:
            rows = self._advance_shard(self._shards[0])
        else:
            # per-shard fire/expiry on the pool; shard rows re-sort by
            # (key, start) — the serial store's emit order — so output
            # bytes are independent of the shard count
            parts = [r for r in self._pool.run_tasks(
                [lambda st=st: self._advance_shard(st)
                 for st in self._shards]) if r is not None]
            if not parts:
                rows = None
            elif len(parts) == 1:
                rows = parts[0]
            else:
                cat = {k: np.concatenate([p[k] for p in parts])
                       for k in parts[0]}
                order = np.lexsort((cat["window_start"], cat["key"]))
                rows = {k: v[order] for k, v in cat.items()}
        if rows is None:
            return FiredWindows(data=self._empty())
        return FiredWindows(data=rows)

    def _advance_shard(self, st: _SpanStore) -> Optional[Dict[str, np.ndarray]]:
        """Fire + expiry pass for one shard at the current watermark;
        returns the shard's emitted rows (store order: (key, start))."""
        if not len(st):
            return None
        end1 = st.last + self.gap - 1
        complete = end1 <= self.watermark
        emit = complete & (~st.fired | st.refire)
        rows = None
        if emit.any():
            idx = np.nonzero(emit)[0]
            rows = self._emit(st._take(idx))
            if self.retract:
                # spans whose predecessors were retracted (re)fire as
                # +U; first firings are +I — the row now stands, so the
                # pending-retraction flag clears
                rows[OP_FIELD] = np.where(
                    st.retracted[idx], OP_UPDATE_AFTER,
                    OP_INSERT).astype(OP_DTYPE)
                st.retracted[idx] = False
        st.fired |= complete
        st.refire[:] = False
        dead = end1 + self.lateness <= self.watermark
        if dead.any():
            st._filter(~dead)
        return rows

    def _emit(self, cols: Tuple[np.ndarray, ...]) -> Dict[str, np.ndarray]:
        import jax

        from flink_tpu.hostsync import host_cpu_device

        key, start, last, sums, maxs, mins, count = cols[:7]
        with jax.default_device(host_cpu_device()):
            import jax.numpy as jnp

            res = self.agg.finalize(
                jnp.asarray(sums), jnp.asarray(maxs), jnp.asarray(mins),
                jnp.asarray(count.astype(np.int32)))
        out = {
            "key": key.astype(np.int64),
            "window_start": start.astype(np.int64),
            "window_end": (last + self.gap).astype(np.int64),
            "count": count.astype(np.int32),
        }
        # finalize's fields win, including one named "count" — an
        # aggregate built with result_field="count" must not have its
        # output shadowed by the raw record count
        for k, v in res.items():
            out[k] = np.asarray(v)
        return out

    def _empty(self) -> Dict[str, np.ndarray]:
        if not hasattr(self, "_empty_cache"):
            w = self.agg
            self._empty_cache = self._emit((
                np.zeros(0, np.int64), np.zeros(0, np.int64),
                np.zeros(0, np.int64), np.zeros((0, w.sum_width), np.float32),
                np.zeros((0, w.max_width), np.float32),
                np.zeros((0, w.min_width), np.float32),
                np.zeros(0, np.int64)))
            if self.retract:
                self._empty_cache[OP_FIELD] = np.zeros(0, OP_DTYPE)
        return dict(self._empty_cache)

    # -- per-step retraction drain ---------------------------------------
    def take_fired(self):
        """Drain the -U rows merges produced this step (retract mode;
        None otherwise). Called by the driver right after each
        process_batch, so a consumed fired span's retraction reaches
        the sink BEFORE the merged session's eventual (re)fire."""
        from flink_tpu.ops.window import FiredWindows

        if not self._pending_retracts:
            return None
        parts = self._pending_retracts
        self._pending_retracts = []
        if len(parts) == 1:
            rows = parts[0]
        else:
            rows = {k: np.concatenate([p[k] for p in parts])
                    for k in parts[0]}
        # deterministic emission order across host-pool shard counts
        order = np.lexsort((rows["window_start"], rows["key"]))
        return FiredWindows(data={k: v[order] for k, v in rows.items()})

    def state_counters(self) -> Dict[str, int]:
        """``JobResult.metrics``: this job's sessions are on the host
        (the device operator reports 0 under the same name until it
        hands its sessions over)."""
        return {"session.on_registry": 1}

    def final_watermark(self) -> int:
        lasts = [int(st.last.max()) for st in self._shards if len(st)]
        if not lasts:
            return self.watermark if self.watermark != LONG_MIN else 0
        return max(lasts) + self.gap + self.lateness + 1

    # -- snapshot --------------------------------------------------------
    def _merged_columns(self) -> Dict[str, np.ndarray]:
        """The registry's columns as ONE (key, start)-sorted block — the
        checkpoint format is shard-count-independent, so snapshots move
        freely across host.parallelism settings (and stay byte-stable
        for the incremental-checkpoint reuse check)."""
        if len(self._shards) == 1:
            st = self._shards[0]
            return {c: getattr(st, c).copy() for c in st._COLS}
        cols = {c: np.concatenate([getattr(st, c) for st in self._shards])
                for c in _SpanStore._COLS}
        order = np.lexsort((cols["start"], cols["key"]))
        return {c: v[order] for c, v in cols.items()}

    def snapshot_state(self) -> Dict[str, Any]:
        return {
            "watermark": self.watermark,
            "late_records": self.late_records,
            "columns": self._merged_columns(),
        }

    def restore_state(self, snap: Dict[str, Any]) -> None:
        self.watermark = snap["watermark"]
        self.late_records = snap["late_records"]
        st = _SpanStore(self.agg.sum_width, self.agg.max_width,
                        self.agg.min_width)
        if "columns" in snap:
            n = len(snap["columns"]["key"])
            for c in st._COLS:
                if c == "retracted" and c not in snap["columns"]:
                    # snapshots predating retract mode: nothing fired as
                    # +I has been merged away yet
                    setattr(st, c, np.zeros(n, bool))
                    continue
                # copy: advance_watermark mutates columns in place
                # (fired |= ..., refire[:] = ...); aliasing the caller's
                # snapshot would corrupt it for reuse (recovery retries,
                # rescale fan-out)
                setattr(st, c, np.array(snap["columns"][c]))
        else:  # legacy per-key dict format (pre-columnar checkpoints)
            rows = [(k, s0, s1, su, mx, mn, ct, fi, rf)
                    for k, spans in snap["spans"].items()
                    for (s0, s1, su, mx, mn, ct, fi, rf) in spans]
            rows.sort(key=lambda r: (r[0], r[1]))
            if rows:
                st.key = np.array([r[0] for r in rows], np.int64)
                st.start = np.array([r[1] for r in rows], np.int64)
                st.last = np.array([r[2] for r in rows], np.int64)
                st.sums = np.stack([r[3] for r in rows]).astype(np.float32)
                st.maxs = np.stack([r[4] for r in rows]).astype(np.float32)
                st.mins = np.stack([r[5] for r in rows]).astype(np.float32)
                st.count = np.array([r[6] for r in rows], np.int64)
                st.fired = np.array([r[7] for r in rows], bool)
                st.refire = np.array([r[8] for r in rows], bool)
                st.retracted = np.zeros(len(rows), bool)
        self._install_store(st)
        self._has_refire = bool(st.refire.any())

    def _install_store(self, st: _SpanStore) -> None:
        """Adopt a merged (key, start)-sorted store, re-sharding it to
        this operator's parallelism (restore is shard-count-agnostic:
        a snapshot taken at W=1 restores into W=4 and vice versa)."""
        n_shards = len(self._shards)
        if n_shards == 1:
            self._shards = [st]
            return
        shards = []
        sh = st.key % n_shards
        for w in range(n_shards):
            part = _SpanStore(self.agg.sum_width, self.agg.max_width,
                              self.agg.min_width)
            m = sh == w
            for c in st._COLS:  # mask keeps (key, start) order per shard
                setattr(part, c, getattr(st, c)[m])
            shards.append(part)
        self._shards = shards
