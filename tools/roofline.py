"""Device-kernel roofline for the Q5 hot path.

Measures ON-CHIP time for each kernel the Q5 pipeline dispatches —
apply (3B split upload), apply (packed i32), fire+topn+ring append,
clear — at the benchmark shape (2^20-record batches, 128x256 slots,
ring 16, count aggregate), plus candidate kernels for the next
optimization step (host pre-aggregated sparse apply at several pair
counts). Reports per-kernel ms and achieved HBM GB/s against the
tensor traffic each kernel necessarily moves.

Method: upload inputs once, chain N donated kernel steps, block once;
per-step time = (t_chain - t_noop) / N. The chain amortizes the
block_until_ready round trip so the number is device time, not
dispatch time.

Run: JAX_PLATFORMS=<backend> python tools/roofline.py
"""
from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from flink_tpu.ops import aggregates
from flink_tpu.ops.window import (
    _JIT_APPLY, _JIT_APPLY_SPLIT, _JIT_CLEAR, _JIT_RING_TOPN,
    split_encode, _next_pow2,
)
from flink_tpu.state.keyed import PaneStateLayout, init_state

B = 1 << 20          # benchmark microbatch
SLOTS = 128 * 256    # 128 shards x 256 slots
RING = 16            # Q5 plan: 10s/1s sliding + 1s ooo -> ring 16
NKEYS = 10_000       # active auctions
PANES_PER_BATCH = 11 # 2^20 records at 100 ev/ms spans ~10.5s of event time
W = 10               # window-ends per advance (one advance per batch)
PPW = 10


def _mk_state(layout):
    return init_state(layout)


def time_chain(fn, state, *args, n=24):
    """Per-call seconds for `state = fn(state, *args)` chained n times."""
    # warm compile + one settle
    state = fn(state, *args)
    jax.block_until_ready(state)
    t0 = time.perf_counter()
    for _ in range(n):
        state = fn(state, *args)
    jax.block_until_ready(state)
    t1 = time.perf_counter()
    return (t1 - t0) / n, state


def time_chain_ring(fn, ring_buf, state, *args, n=24):
    """Same, but the mutated operand is the emit ring (arg 0 stays)."""
    ring_buf = fn(state, ring_buf, *args)
    jax.block_until_ready(ring_buf)
    t0 = time.perf_counter()
    for _ in range(n):
        ring_buf = fn(state, ring_buf, *args)
    jax.block_until_ready(ring_buf)
    t1 = time.perf_counter()
    return (t1 - t0) / n, ring_buf


def h2d_seconds(arr_np, n=8):
    """Steady-state host->device seconds per transfer (forced consume)."""
    probe = jax.jit(lambda x: x.reshape(-1)[:1].astype(jnp.int32).sum())
    x = jnp.asarray(arr_np)
    jax.block_until_ready(probe(x))
    t0 = time.perf_counter()
    for _ in range(n):
        x = jnp.asarray(arr_np)
        jax.block_until_ready(probe(x))
    t1 = time.perf_counter()
    return (t1 - t0) / n


def main():
    agg = aggregates.count()
    layout = PaneStateLayout(slots=SLOTS, ring=RING, sum_width=agg.sum_width,
                             max_width=agg.max_width, min_width=agg.min_width)
    rows = layout.rows
    rng = np.random.default_rng(7)
    print(f"# backend={jax.default_backend()} rows={rows} ring={RING} B={B}")
    out = {}

    # --- input shapes (Q5-realistic: 10k hot-skewed keys, ~11 panes) ---
    slots = rng.integers(0, NKEYS, B).astype(np.int64)
    cols = (rng.integers(0, PANES_PER_BATCH, B) % RING).astype(np.uint8)
    valid = np.ones(B, bool)

    # --- apply: 3-byte split upload (current bench path) ---
    sc = split_encode(slots, cols, valid)
    sc_d = jnp.asarray(sc)
    jax.block_until_ready(sc_d)
    state = _mk_state(layout)
    import functools
    apply_split = functools.partial(_JIT_APPLY_SPLIT, agg=agg, dump_row=SLOTS)
    dt, state = time_chain(lambda s, b: apply_split(s, b, {})[0], state, sc_d)
    # traffic floor: read 3B*B input + counts r/w is sparse (<= B cells)
    out["apply_split_ms"] = dt * 1e3
    out["apply_split_Mrec_s"] = B / dt / 1e6

    # --- apply: packed i32 (4B) path ---
    packed = (slots * RING + cols).astype(np.int32)
    pk_d = jnp.asarray(packed)
    jax.block_until_ready(pk_d)
    apply_p = functools.partial(_JIT_APPLY, agg=agg, ring=RING, dump_row=SLOTS)
    state2 = _mk_state(layout)
    dt, state2 = time_chain(lambda s, b: apply_p(s, b, {})[0], state2, pk_d)
    out["apply_packed_ms"] = dt * 1e3
    out["apply_packed_Mrec_s"] = B / dt / 1e6

    # --- candidate: pre-aggregated sparse apply at several pair counts ---
    # host combiner ships (pair_id, count) for the <=(keys x panes) pairs
    # a batch actually touches; the scatter shrinks by B/P.
    def apply_agg(counts, pairs, cnts):
        pid = pairs
        ok = pid >= 0
        r = jnp.where(ok, pid // RING, SLOTS).astype(jnp.int32)
        c = jnp.where(ok, pid % RING, 0).astype(jnp.int32)
        return counts.at[r, c].add(jnp.where(ok, cnts, 0))

    japply_agg = jax.jit(apply_agg, donate_argnums=(0,))
    for cap_pow in (17, 18):
        P = 1 << cap_pow
        pairs = np.full(P, -1, np.int32)
        npair = min(NKEYS * PANES_PER_BATCH, P)
        pairs[:npair] = rng.choice(SLOTS * RING, npair, replace=False)
        cnts = np.full(P, B // max(npair, 1), np.int32)
        pr_d, ct_d = jnp.asarray(pairs), jnp.asarray(cnts)
        jax.block_until_ready((pr_d, ct_d))
        counts = jnp.zeros((rows, RING), jnp.int32)
        dt, counts = time_chain(lambda s, p, c: japply_agg(s, p, c),
                                counts, pr_d, ct_d)
        out[f"apply_preagg_2e{cap_pow}_ms"] = dt * 1e3

    # --- fire + top-n + emit-ring append (the per-advance kernel) ---
    by, topn = "count", 1
    sel_cap = _next_pow2(8 * 64)
    ring_topn = functools.partial(
        _JIT_RING_TOPN, agg=agg, panes_per_window=PPW, ring=RING,
        by=by, topn=topn, sel_cap=sel_cap)
    n_res = 1  # count()
    emit_ring = jnp.zeros((8192 + 2, 3 + n_res), jnp.int32)
    ends = np.arange(100, 100 + W, dtype=np.int64)
    params = np.concatenate([[90, 111, 90], ends]).astype(np.int64)
    params_d = jnp.asarray(params)
    used = jnp.ones((rows,), bool)
    jax.block_until_ready((params_d, used))
    dt, emit_ring = time_chain_ring(
        lambda s, r, p, u: ring_topn(s, r, p, u), emit_ring, state2,
        params_d, used)
    out["fire_topn_W10_ms"] = dt * 1e3
    # necessary traffic: counts gather rows x W x ppw x 4B (widths are 0)
    fire_bytes = rows * W * PPW * 4
    out["fire_topn_GBs"] = fire_bytes / dt / 1e9

    # --- clear ---
    cmask = np.zeros(RING, bool)
    cmask[:2] = True
    cm_d = jnp.asarray(cmask)
    jax.block_until_ready(cm_d)
    state3 = _mk_state(layout)
    dt, state3 = time_chain(lambda s, m: _JIT_CLEAR(s, m), state3, cm_d)
    out["clear_ms"] = dt * 1e3
    out["clear_GBs"] = (rows * RING * 4 * 2) / dt / 1e9

    # --- transport reference points (steady-state, forced consume) ---
    out["h2d_3MB_ms"] = h2d_seconds(sc) * 1e3              # 3B/rec batch
    out["h2d_1MB_ms"] = h2d_seconds(
        np.zeros((1 << 17, 8), np.uint8)) * 1e3            # pair buffer
    out["h2d_4MB_ms"] = h2d_seconds(packed) * 1e3          # 4B/rec batch

    for k, v in out.items():
        print(f"{k}: {v:.3f}")
    print(json.dumps({k: round(v, 3) for k, v in out.items()}))


if __name__ == "__main__":
    main()
