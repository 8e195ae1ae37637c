"""Micro-check of the unwindowed aggregation's apply: ms a batch on the
chip, and what the 64-bit sum lane costs.

Times ``ops/groupagg_device.py`` ``groupagg_apply_kernel`` alone at the
shapes of the cell ``q17_upserts_paced``: donated accumulators of
33,554,432 slots and batches of 2^20 bids of the suite's stream
(``benchmark/configs/nexmark_q5_large_keys.py`` ``LazyPool``; slot = a
dense rank of the auction id, as the directory hands them out). Variants,
one jitted program each:

- ``q17``: the query's aggregate as the job runs it (three int32 band
  counts, an int32 min, max and newest event time, the int64 sum that
  XLA carries as two words);
- ``sum32``: the same with the sum lane at ONE word (int32: it wraps, the
  answers are wrong; the floor of what any lane costs);
- ``no_sum``: the same without a sum lane.

``q17 - no_sum`` is what the emulated int64 lane costs, ``sum32 -
no_sum`` what one word costs: two words with a hand-written carry cost
at least twice that. Per variant the median of ``--reps`` calls on the
host clock, each ending in ``block_until_ready``, and the first call's
seconds (the compile). One JSON line.

    chiprun --timeout 1800 -- python tools/groupagg_micro.py [--reps 8]
    python tools/groupagg_micro.py --slots-per-shard 64 --n 4096   # CPU: runs only
"""
from __future__ import annotations

import argparse
import dataclasses
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

import flink_tpu  # noqa: E402,F401 — x64
from benchmark.configs import nexmark_q5_large_keys as large_keys  # noqa: E402
from flink_tpu.ops import aggregates as A  # noqa: E402
from flink_tpu.ops import groupagg_device as G  # noqa: E402
from flink_tpu.ops.window import apply_chunk  # noqa: E402

SHARDS = 128
BATCHES = 4


def variants():
    def q17(sum_agg):
        return A.multi(*filter(None, (
            A.count("total_bids"),
            A.count_if("price", None, 10_000, "rank1_bids"),
            A.count_if("price", 10_000, 1_000_000, "rank2_bids"),
            A.count_if("price", 1_000_000, None, "rank3_bids"),
            A.int_min_of("price", "min_price"),
            A.int_max_of("price", "max_price"), sum_agg,
            A.latest_event_time("last_bid_ms"))))

    wide = A.int_sum_of("price", "sum_price", avg_field="avg_price")
    one_word = dataclasses.replace(
        wide, lift=lambda data: ((data["price"].astype(jnp.int32),), (), ()),
        lane_dtypes=(("int32",), (), ()), name="int_sum32(price)")
    return [("q17", q17(wide)), ("sum32", q17(one_word)),
            ("no_sum", q17(None))]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--n", type=int, default=1 << 20)
    ap.add_argument("--slots-per-shard", type=int, default=262144)
    ap.add_argument("--reps", type=int, default=8)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--only", default="")
    args = ap.parse_args()
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "nexmark_q17_auction_stats.json")) as f:
        params = json.load(f)["params"]
    slots = SHARDS * args.slots_per_shard
    n = args.n
    pool = large_keys.make_pool(args.seed, n, params)
    first = 20      # past the stream's first epochs: ~68,400 new ids each
    lo = int(pool[first]["auction"].min())
    batches = []
    for i in range(BATCHES):
        b = pool[first + i]
        batches.append((
            jnp.asarray(((b["auction"] - lo) % slots).astype(np.int32)),
            {"price": jnp.asarray(b["price"].astype(np.int32)),
             A.EVENT_TIME_FIELD: jnp.asarray(
                 ((i * n + np.arange(n)) // 9200).astype(np.int32))}))
    dev = jax.devices()[0]
    out = {"n": n, "slots": slots, "reps": args.reps, "seed": args.seed,
           "chunk": apply_chunk(n),
           "device": {"platform": dev.platform, "kind": dev.device_kind}}
    only = set(filter(None, args.only.split(",")))
    for name, agg in variants():
        if only and name not in only:
            continue
        state = G.init_groupagg_state(agg, slots)
        times, cells = [], []
        for r in range(args.reps + 1):
            slot, data = batches[r % BATCHES]
            t0 = time.perf_counter()
            state, head, rows, full = G._JIT_GROUPAGG_APPLY(
                state, slot, data, agg=agg, slots=slots, cap=apply_chunk(n))
            jax.block_until_ready((state, head, rows))
            times.append(time.perf_counter() - t0)
            cells.append(int(np.asarray(head)[0]))
            del full
        out[name] = {"first_call_s": round(times[0], 3),
                     "ms_per_batch": round(
                         1e3 * statistics.median(times[1:]), 4),
                     "ms_all": [round(1e3 * t, 3) for t in times[1:]],
                     "cells_per_batch": cells[:BATCHES],
                     "state_bytes": slots * 4 * G.state_words(agg)}
        del state
        print(f"# {name}: {json.dumps(out[name])}", file=sys.stderr,
              flush=True)
    if {"q17", "sum32", "no_sum"} <= set(out):
        base = out["no_sum"]["ms_per_batch"]
        out["int64_lane_ms"] = round(out["q17"]["ms_per_batch"] - base, 4)
        out["one_word_lane_ms"] = round(
            out["sum32"]["ms_per_batch"] - base, 4)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
