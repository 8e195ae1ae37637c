"""Micro-check of the top-n fire: device ms a call, on the chip.

Times the top-n fire program (``ops/window.py`` ``ring_append_topn_kernel``:
``jit_ring_append_topn_kernel`` in a trace) with one of its parts swapped,
at these grids:

- ``large``: the large-keys cells' fire, 16,777,217 rows x 12 ring
  columns, one window end (``fire_pad`` 1), about half the rows counting;
  ``large_w2`` / ``large_w4`` / ``large_w8``: the same state at the
  widths a catch-up or the end-of-input flush takes there, every end real;
- ``small_w1`` / ``small_w64``: the small-state cells' 32,769 rows x 8
  columns at one end and at the full 64 (one real, 63 padding);

and ``fire_pack_kernel`` (no top-n; run by no cell) at 32,769 rows x 4
ends with ``out_cap`` 131,072. Two axes of variants, each run with the
tree's choice on the other axis.

THE COMPACTION (``first_true_indices``: which candidates of the rows x W
grid were selected, in row-major order, padded to ``sel_cap``; the
operator chooses between ``scan`` and ``sort`` by the static shapes):

- ``argsort``: the expression the fire had before PR 40, a stable
  argsort of the negated mask (it lives on in
  ``tests/test_fire_compaction.py`` as the reference; taken from there);
- ``scan``: a prefix sum of the mask and a binary search of it for the
  j-th winner;
- ``sort``: one sort of the positions, the unselected ones masked to
  the grid's size;
- ``blocks``: counts per block of 1,024 candidates, the search over the
  blocks' prefix sums, then a prefix sum inside the ``cap`` blocks that
  hold a winner (not for ``fire_pack``: ``cap`` x 1,024 cells).

WHAT RUNS BEFORE IT (``fire_kernel``'s count lane + ``top_values``;
named ``<counts>+<threshold>``; the operator takes ``masked`` at one
window end and ``prefix`` at any wider fire (``reads_live_columns``,
which a variant here overrides), ``max`` for a top 1; not for
``fire_pack``, which ranks nothing):

- counts ``prefix``: ``prefix_sum_counts``, the expression every fire
  had before PR 44: a roll of the whole ring, its prefix sum and two
  columns of that; ``masked``: one masked reduction over the ring axis,
  each window's live columns alone passing the mask;
- threshold ``top_k``: ``lax.top_k`` over every candidate, as before
  PR 44; ``max``: a reduction.

Per grid and variant: ms a call on the host clock (median of ``--reps``
calls that end in ``block_until_ready``), device ms a call of the
program and its ``--top`` ops from a ``jax.profiler`` trace of those
calls, peak device memory so far, and whether the emit ring it returns
equals the grid's first variant's (``argsort``, with ``prefix+top_k``
the whole of the old fire), element for element. One JSON line, also
written to ``--out``; traces go under ``chiprun_out/fire_micro/``.

    chiprun -- python tools/fire_micro.py [--reps 6] [--grids large]
        [--compactions scan] [--before prefix+max,masked+max]
        [--out chiprun_out/pr44/micro.json]
    python tools/fire_micro.py --cpu      # the sandbox: answers only, small
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
sys.path[:0] = [ROOT, os.path.join(ROOT, "tests")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmark import trace_reduce  # noqa: E402
from flink_tpu.ops import window as W  # noqa: E402
from flink_tpu.ops.aggregates import count  # noqa: E402
from flink_tpu.state.keyed import PaneState  # noqa: E402
from test_fire_compaction import argsort_compaction  # noqa: E402
from test_fire_reduction import top_k_values  # noqa: E402

PPW = 5             # Q5: a 10 s window of 2 s panes
BLOCK = 1024
EMIT_RING_ROWS = 2048   # the operator's


def blocks_compaction(flat, cap):
    k = flat.shape[0]
    nb = -(-k // BLOCK)
    blocks = jnp.pad(flat, (0, nb * BLOCK - k)).reshape(nb, BLOCK)
    per = jnp.sum(blocks, axis=1, dtype=jnp.int32)
    ends = jnp.cumsum(per)
    j = jnp.arange(1, cap + 1, dtype=jnp.int32)
    b = jnp.searchsorted(ends, j, side="left")
    bb = jnp.minimum(b, nb - 1)
    rank = j - (ends[bb] - per[bb])
    inner = jnp.cumsum(blocks[bb], axis=1, dtype=jnp.int32)
    off = jnp.sum(inner < rank[:, None], axis=1, dtype=jnp.int32)
    return jnp.where(b < nb, bb * BLOCK + off, k)


COMPACTIONS = {"argsort": argsort_compaction,
               "scan": W.searched_true_indices,
               "sort": W.sorted_true_indices, "blocks": blocks_compaction}
COUNTS = {"prefix": lambda width: False, "masked": lambda width: True}
THRESHOLDS = {"top_k": top_k_values, "max": W.top_values}
BEFORE = ["prefix+top_k", "prefix+max", "masked+top_k", "masked+max"]
# what the fire traces: swapped for a variant, put back at the end
SWAPPED = ("first_true_indices", "reads_live_columns", "top_values")


def make_counts(rows: int, ring: int, seed: int):
    """About half the rows alive, ~15 bids a live cell; the dump row 0."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    alive = jax.random.bernoulli(k1, 0.5, (rows, 1))
    c = jax.random.poisson(k2, 15.0, (rows, ring)).astype(jnp.int32)
    return jnp.where(alive, c, 0).at[rows - 1].set(0)


def params_for(ring: int, real_ends: int):
    """Panes 100..100+ring-2 written; ``real_ends`` window ends fire, the
    rest of the MIN_FIRE_PAD slots are padding."""
    lo, hi = 100, 100 + ring - 2
    ends = [lo + PPW + i for i in range(real_ends)]
    pad = [int(W._END_SENTINEL)] * (W.MIN_FIRE_PAD - real_ends)
    return jnp.asarray(np.asarray([lo, hi, lo] + ends + pad, np.int64))


def time_program(step, args, reps: int, top: int, trace_dir: str, name: str):
    t0 = time.perf_counter()
    out = jax.block_until_ready(step(*args))      # compiles; the warm-up
    res = {"compile_s": round(time.perf_counter() - t0, 2)}
    times = []
    jax.profiler.start_trace(trace_dir)
    for _ in range(reps):
        t0 = time.perf_counter()
        out = jax.block_until_ready(step(*args))
        times.append(time.perf_counter() - t0)
    jax.profiler.stop_trace()
    res["ms_per_call_host"] = 1e3 * statistics.median(times)
    path = trace_reduce.newest_xplane(trace_dir)
    dev = trace_reduce.reduce_file(path).busiest() if path else None
    if dev is not None and dev.module_totals:
        calls, secs = dev.seconds(trace_reduce.MODULES_LINE, f"^{name}$")
        if calls:
            res["device_ms_per_call"] = 1e3 * secs / calls
        ops = sorted(dev.op_totals.items(), key=lambda kv: -kv[1][1])
        res["top_ops_ms_per_call"] = [
            [n, round(1e3 * s / max(calls, 1), 3)] for n, (_c, s) in ops[:top]]
    return np.asarray(out), res


def variants_of(pack: bool, args):
    """(name, {module attribute: stand-in}) per run of a grid: the
    compactions under the tree's counts and threshold, then what runs
    before the compaction under the tree's compaction."""
    wanted = lambda given, names: [
        n for n in names if not given or n in given.split(",")]
    runs = [(name, {"first_true_indices": COMPACTIONS[name]})
            for name in wanted(args.compactions, COMPACTIONS)
            if not (pack and name == "blocks")]
    for name in ([] if pack else wanted(args.before, BEFORE)):
        counts, thresh = name.split("+")
        runs.append((name, {"reads_live_columns": COUNTS[counts],
                            "top_values": THRESHOLDS[thresh]}))
    return runs


def run_grid(grid: str, rows: int, ring: int, n_ends: int, real_ends: int,
             pack: bool, args, out_dir: str, tree: dict):
    counts = make_counts(rows, ring, args.seed)
    state = PaneState(None, None, None, counts)
    used = jnp.ones(rows, bool).at[rows - 1].set(False)
    params = params_for(ring, real_ends)
    res, want = {}, None
    for name, swap in variants_of(pack, args):
        for attr in SWAPPED:                  # read when the jit traces
            setattr(W, attr, swap.get(attr, tree[attr]))
        if pack:
            def fire(s, p, u):
                return W.fire_pack_kernel(
                    s, p[:3 + n_ends], u, agg=count(), panes_per_window=PPW,
                    ring=ring, out_cap=min(131072, rows * n_ends))
            call = (state, params, used)
        else:
            def fire(s, e, p, u):
                return W.ring_append_topn_kernel(
                    s, e, p, u, agg=count(), panes_per_window=PPW, ring=ring,
                    sel_cap=256, by="count", topn=1, fire_pad=n_ends)
            call = (state, jnp.zeros((EMIT_RING_ROWS + 2, 3), jnp.int32),
                    params, used)
        try:
            got, r = time_program(jax.jit(fire), call, args.reps, args.top,
                                  os.path.join(out_dir, grid, name),
                                  "jit_fire")
        except Exception as e:    # one variant out of memory: the rest run
            res[name] = {"error": f"{type(e).__name__}: {e}"[:400]}
            continue
        if want is None:
            want = got
            r["rows_fired"] = int(got[0, 0])
        r["equal_to_first"] = bool(np.array_equal(got, want))
        stats = jax.devices()[0].memory_stats() or {}
        r["peak_bytes_so_far"] = stats.get("peak_bytes_in_use")
        res[name] = r
        print(f"# {grid}/{name}: {json.dumps(r)}", file=sys.stderr, flush=True)
    return res


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--reps", type=int, default=6)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--top", type=int, default=6)
    ap.add_argument("--cpu", action="store_true",
                    help="small grids: the answers only")
    ap.add_argument("--grids", default="",
                    help="comma list of grids (default: all)")
    ap.add_argument("--compactions", default="",
                    help=f"comma list of {list(COMPACTIONS)} (default: all)")
    ap.add_argument("--before", default="",
                    help=f"comma list of {BEFORE} (default: all)")
    ap.add_argument("--out", default="",
                    help="a file the JSON line is written to as well")
    args = ap.parse_args()
    big, small = ((8 * 64 + 1, 32 * 8 + 1) if args.cpu
                  else (128 * 131072 + 1, 128 * 256 + 1))
    out_dir = os.path.join(ROOT, "chiprun_out", "fire_micro")
    dev = jax.devices()[0]
    out = {"reps": args.reps, "seed": args.seed,
           "device": {"platform": dev.platform, "kind": dev.device_kind}}
    tree = {attr: getattr(W, attr) for attr in SWAPPED}
    try:
        for grid, rows, ring, n_ends, real_ends, pack in [
                ("large", big, 12, 1, 1, False),
                ("large_w2", big, 12, 2, 2, False),
                ("large_w4", big, 12, 4, 4, False),
                ("large_w8", big, 12, 8, 6, False),
                ("small_w1", small, 8, 1, 1, False),
                ("small_w64", small, 8, 64, 1, False),
                ("fire_pack", small, 8, 4, 4, True)]:
            if args.grids and grid not in args.grids.split(","):
                continue
            out[grid] = {"rows": rows, "ring": ring, "ends": n_ends,
                         "real_ends": real_ends,
                         **run_grid(grid, rows, ring, n_ends, real_ends, pack,
                                    args, out_dir, tree)}
    finally:
        for attr, fn in tree.items():
            setattr(W, attr, fn)
    line = json.dumps(out)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.write(line + "\n")
    print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
