"""Micro-check: what a gather and a sorted, unique scatter of K slots
cost on the chip, by the state's layout.

The unwindowed aggregation's merge (``ops/groupagg_device.py``) reads
and writes, per distinct key of a batch, one word in each of nine
``(slots,)`` arrays. This times that access pattern alone at the cell's
size (33,554,432 slots, ascending slot ids ~490 apart on average) beside
two other layouts of the same words:

- ``lanes_1d``: nine ``(slots,)`` int32 arrays, a gather and a
  ``.at[idx].set`` (sorted, unique, ``mode="drop"``) each; ``one_int32``
  / ``one_int64``: one such lane alone, at one word and at the two that
  XLA's emulated int64 is;
- ``strip_2d``: ONE ``(9, slots)`` int32 array, a gather and a set of
  ``(9, K)`` column strips;
- ``rows_128``: ONE ``(slots / 8, 128)`` int32 array (eight slots of
  sixteen words a row), a gather of K whole rows (no scatter: two keys
  of a chunk may share a row).

Per variant and K: ms a call on the host clock (median of ``--reps``,
each ending in ``block_until_ready``), gather and scatter apart. One
JSON line.

    chiprun -- python tools/gather_micro.py
    python tools/gather_micro.py --slots 65536 --ks 1024      # CPU: runs only
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

W = 9


def timed(fn, args, reps, donate_first=False):
    out = fn(*args)
    jax.block_until_ready(out)
    times = []
    for _ in range(reps):
        if donate_first:
            args = (out,) + args[1:]
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    return round(1e3 * statistics.median(times), 4)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--slots", type=int, default=128 * 262144)
    ap.add_argument("--ks", default="16384,131072")
    ap.add_argument("--reps", type=int, default=10)
    args = ap.parse_args()
    slots = args.slots
    dev = jax.devices()[0]
    out = {"slots": slots, "reps": args.reps,
           "device": {"platform": dev.platform, "kind": dev.device_kind}}
    rng = np.random.default_rng(1)

    def set_(a, idx, v):
        return a.at[idx].set(v, indices_are_sorted=True,
                             unique_indices=True, mode="drop")

    g1 = jax.jit(lambda lanes, at: tuple(a[at] for a in lanes))
    s1 = jax.jit(lambda lanes, idx, vals: tuple(
        set_(a, idx, v) for a, v in zip(lanes, vals)), donate_argnums=(0,))
    g2 = jax.jit(lambda st, at: st[:, at])
    s2 = jax.jit(lambda st, idx, vals: st.at[:, idx].set(
        vals, indices_are_sorted=True, unique_indices=True, mode="drop"),
        donate_argnums=(0,))
    g3 = jax.jit(lambda st, at: st[at >> 3])

    for k in (int(x) for x in args.ks.split(",")):
        idx = jnp.asarray(np.sort(rng.choice(slots, k, replace=False))
                          .astype(np.int32))
        res = {}
        lanes = tuple(jnp.zeros(slots, jnp.int32) for _ in range(W))
        vals = tuple(jnp.ones(k, jnp.int32) for _ in range(W))
        res["lanes_1d_gather_ms"] = timed(g1, (lanes, idx), args.reps)
        res["lanes_1d_scatter_ms"] = timed(
            s1, (lanes, idx, vals), args.reps, donate_first=True)
        del lanes
        # one int64 lane (two words under XLA's emulation) beside one int32
        for dt in ("int32", "int64"):
            one = (jnp.zeros(slots, dt),)
            res[f"one_{dt}_gather_ms"] = timed(g1, (one, idx), args.reps)
            res[f"one_{dt}_scatter_ms"] = timed(
                s1, (one, idx, (jnp.ones(k, dt),)), args.reps,
                donate_first=True)
            del one
        st = jnp.zeros((W, slots), jnp.int32)
        v2 = jnp.ones((W, k), jnp.int32)
        res["strip_2d_gather_ms"] = timed(g2, (st, idx), args.reps)
        res["strip_2d_scatter_ms"] = timed(
            s2, (st, idx, v2), args.reps, donate_first=True)
        del st
        rows = jnp.zeros((slots // 8, 128), jnp.int32)
        res["rows_128_gather_ms"] = timed(g3, (rows, idx), args.reps)
        del rows
        out[f"k{k}"] = res
        print(f"# k={k}: {json.dumps(res)}", file=sys.stderr, flush=True)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
