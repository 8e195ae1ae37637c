"""Micro-check of the per-record apply: device ms a batch, on the chip.

Times the apply program of the large-keys lane alone (``ops/window.py``
``apply_kernel``: ``jit_apply_kernel`` in a trace) at the shapes of the
cell ``q5_large_keys_replay``: a donated int32 pane state of 16,777,217
rows x 12 ring columns and batches of 2^20 packed ids, the auction ids of
the suite's bid stream (``benchmark/configs/nexmark_q5_large_keys.py``
``suite_batch``; slot = id mod the slot count, ring column from the
bid's pane at 9,200 bids per ms). Variants, one jitted program each, each
checked against ``np.add.at`` over the cells its batches touch:

- ``a_scatter``: the per-record scatter as it was before PR 32,
  ``counts.at[rows, ring_ix].add(valid)``;
- ``b_sort``: one sort of the 2^20 cell keys, alone;
- ``b_combine``: ``combine_cells`` (the two sorts; nothing applied);
- ``c_2d``: combine, then the distinct cells added to the WHOLE tensor
  by 2-D index, ``apply_chunk`` a trip, sorted and unique;
- ``c_flat``: the same through ``counts.reshape(-1)``;
- ``c_column``: ``apply_kernel`` as the operator runs it (the distinct
  cells added to one ring column a trip, lifted out and put back);
- ``d_distinct``: ``apply_kernel`` on batches of 2^20 DISTINCT cells of
  one ring column (the sort for nothing, then eight trips).

Per variant: ms a batch on the host clock (the median of ``--reps`` calls
that end in ``block_until_ready``), device ms a call of the program and
its ``--top`` ops by device time from a ``jax.profiler`` trace of those
calls, and whether a ``while``, ``dynamic-update-slice``, ``sort`` or
``copy`` is among its ops (the scatter itself is a custom fusion the
trace names ``fusion.N``). One JSON line; the traces go under
``chiprun_out/apply_micro/``.

    chiprun -- python tools/apply_micro.py [--reps 8] [--seed 1]
    python tools/apply_micro.py --slots-per-shard 64 --n 4096   # CPU: answers only
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402

from benchmark import trace_reduce  # noqa: E402
from benchmark.configs import nexmark_q5_large_keys as large_keys  # noqa: E402
from flink_tpu.ops import window as W  # noqa: E402
from flink_tpu.ops.aggregates import count  # noqa: E402
from flink_tpu.state.keyed import PaneState  # noqa: E402

SHARDS = 128
RING = 12
BATCHES = 4         # distinct batches a variant cycles through


def suite_cells(seed: int, i: int, n: int, slots: int, cfg: dict,
                rate: int) -> np.ndarray:
    """Batch ``i`` of the suite's bids as packed cells, slot * RING +
    ring column: what the operator uploads once the directory has given
    every auction a slot (here id mod slots: ids a batch apart differ)."""
    p = cfg["params"]
    auction = large_keys.suite_batch(seed, i, n, p)["auction"]
    ts = (i * n + np.arange(n, dtype=np.int64)) // rate
    pane = ts // int(p["slide_ms"])
    return ((auction % slots) * RING + pane % RING).astype(np.int32)


def distinct_cells(i: int, n: int, slots: int) -> np.ndarray:
    """2^20 distinct cells of one ring column, shuffled."""
    rows = (np.arange(n, dtype=np.int64) * 13 + i * n) % slots
    rng = np.random.default_rng(i)
    return (rng.permutation(rows) * RING + i % RING).astype(np.int32)


def decode(packed):
    valid = packed >= 0
    p = jnp.where(valid, packed, 0)
    return (p // RING).astype(jnp.int32), (p % RING).astype(jnp.int32), valid


def a_scatter(counts, packed):
    rows, ring_ix, valid = decode(packed)
    return (counts.at[rows, ring_ix].add(valid.astype(jnp.int32)),
            jnp.sum(valid, dtype=jnp.int32)[None])


def b_sort(counts, packed):
    rows, ring_ix, valid = decode(packed)
    key = jnp.where(valid, ring_ix * counts.shape[0] + rows, W.NO_CELL)
    key = lax.sort(key, is_stable=False)
    return counts, key[:1]


def b_combine(counts, packed):
    rows, ring_ix, valid = decode(packed)
    cells, starts, _, n_cells, n_records = W.combine_cells(
        counts.shape[0], rows, ring_ix, valid, {})
    return counts, jnp.stack([n_cells, n_records, cells[0], starts[1]])


def _whole_tensor(counts, packed, flat: bool):
    """Combine, then add the distinct cells to the whole tensor: keys
    row-major (``combine_cells`` with rows and columns changed over), a
    chunk a trip, ceil(cells / chunk) trips."""
    rows, ring_ix, valid = decode(packed)
    cells, starts, _, n_cells, n_records = W.combine_cells(
        RING, ring_ix, rows, valid, {})
    n_rows = counts.shape[0]
    chunk = W.apply_chunk(packed.shape[0])
    lane = jnp.arange(chunk, dtype=jnp.int32)
    cells = jnp.concatenate([cells, jnp.full(chunk, W.NO_CELL, jnp.int32)])
    starts = jnp.concatenate([starts, jnp.zeros(chunk, jnp.int32)])

    def trip(carry):
        counts, done = carry
        k = lax.dynamic_slice(cells, (done,), (chunk,))
        s = lax.dynamic_slice(starts, (done,), (chunk + 1,))
        mine = done + lane < n_cells
        add = jnp.where(mine, s[1:] - s[:-1], 0)
        kw = dict(indices_are_sorted=True, unique_indices=True, mode="drop")
        if flat:
            at = jnp.where(mine, k, n_rows * RING + lane)
            counts = counts.reshape(-1).at[at].add(add, **kw).reshape(
                n_rows, RING)
        else:
            r = jnp.where(mine, k // RING, n_rows + lane)
            counts = counts.at[r, k % RING].add(add, **kw)
        return counts, done + chunk

    counts, _ = lax.while_loop(lambda c: c[1] < n_cells, trip,
                               (counts, jnp.int32(0)))
    return counts, jnp.stack([n_cells, n_records])


def c_2d(counts, packed):
    return _whole_tensor(counts, packed, flat=False)


def c_flat(counts, packed):
    return _whole_tensor(counts, packed, flat=True)


def c_column(counts, packed):
    state, report = W.apply_kernel(
        PaneState(None, None, None, counts), packed, {}, agg=count(),
        ring=RING, dump_row=counts.shape[0] - 1)
    return state.counts, report


VARIANTS = [("a_scatter", a_scatter, False), ("b_sort", b_sort, False),
            ("b_combine", b_combine, False), ("c_2d", c_2d, False),
            ("c_flat", c_flat, False), ("c_column", c_column, False),
            ("d_distinct", c_column, True)]


def run_variant(name, fn, batches, rows, reps, top, out_dir):
    step = jax.jit(fn, donate_argnums=(0,))
    counts = jnp.zeros((rows, RING), jnp.int32)
    t0 = time.perf_counter()
    out = step(counts, batches[0])          # compiles; also the warm-up
    jax.block_until_ready(out)
    compile_s = time.perf_counter() - t0
    trace_dir = os.path.join(out_dir, name)
    times = []
    jax.profiler.start_trace(trace_dir)
    for i in range(reps):
        t0 = time.perf_counter()
        out = step(out[0], batches[(1 + i) % len(batches)])
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    jax.profiler.stop_trace()
    counts = out[0]
    res = {"ms_per_batch_host": 1e3 * statistics.median(times),
           "compile_s": round(compile_s, 2)}
    if np.asarray(out[1]).size >= 2:
        res["cells_records"] = np.asarray(out[1])[:2].tolist()
    if not name.startswith("b_"):
        # the answers: the cells the batches touched against np.add.at
        ref: dict = {}
        for i in range(reps + 1):
            b = np.asarray(batches[i % len(batches)])
            cells, cnt = np.unique(b[b >= 0], return_counts=True)
            for c, k in zip(cells.tolist(), cnt.tolist()):
                ref[c] = ref.get(c, 0) + k
        at = np.fromiter(ref, np.int64, len(ref))
        got = np.asarray(counts[at // RING, at % RING])
        res["answers_equal"] = bool(
            (got == np.fromiter(ref.values(), np.int64, len(ref))).all()
            and int(jnp.sum(counts, dtype=jnp.int32)) == sum(ref.values()))
    path = trace_reduce.newest_xplane(trace_dir)
    dev = trace_reduce.reduce_file(path).busiest() if path else None
    if dev is not None and dev.module_totals:
        calls, secs = dev.seconds(trace_reduce.MODULES_LINE,
                                  f"^jit_{fn.__name__}$")
        if calls:
            res["device_ms_per_call"] = 1e3 * secs / calls
        ops = sorted(dev.op_totals.items(), key=lambda kv: -kv[1][1])
        res["top_ops_ms_per_call"] = [
            [n, round(1e3 * s / max(calls, 1), 3)] for n, (_c, s) in ops[:top]]
        names = " ".join(dev.op_totals)
        res["lowering"] = {k: k in names for k in
                           ("while", "dynamic-update-slice", "sort", "copy")}
    return res


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--n", type=int, default=1 << 20)
    ap.add_argument("--slots-per-shard", type=int, default=131072)
    ap.add_argument("--reps", type=int, default=8)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--top", type=int, default=8)
    ap.add_argument("--only", default="",
                    help="comma list of variants (default: all)")
    args = ap.parse_args()
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "nexmark_q5_large_keys.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "replay_suite.json")) as f:
        rate = int(json.load(f)["events_per_ms"])
    slots = SHARDS * args.slots_per_shard
    rows = slots + 1                        # + the dump row
    out_dir = os.path.join(ROOT, "chiprun_out", "apply_micro")
    dev = jax.devices()[0]
    out = {"n": args.n, "rows": rows, "ring": RING, "reps": args.reps,
           "seed": args.seed, "chunk": W.apply_chunk(args.n),
           "device": {"platform": dev.platform, "kind": dev.device_kind}}
    # batches 20.. of the stream: past its first epochs, ~68,400 new ids each
    suite = [jnp.asarray(suite_cells(args.seed, 20 + i, args.n, slots, cfg,
                                     rate)) for i in range(BATCHES)]
    distinct = [jnp.asarray(distinct_cells(i, args.n, slots))
                for i in range(BATCHES)]
    out["distinct_cells_per_batch"] = [
        int(len(np.unique(np.asarray(b)))) for b in suite]
    only = set(filter(None, args.only.split(",")))
    for name, fn, all_distinct in VARIANTS:
        if only and name not in only:
            continue
        try:
            out[name] = run_variant(
                name, fn, distinct if all_distinct else suite, rows,
                args.reps, args.top, out_dir)
        except Exception as e:     # one variant out of memory: the rest run
            out[name] = {"error": f"{type(e).__name__}: {e}"[:400]}
        print(f"# {name}: {json.dumps(out[name])}", file=sys.stderr,
              flush=True)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
