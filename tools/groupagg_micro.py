"""Micro-check of the unwindowed aggregation's apply: ms a batch on the
chip by the share of a batch's keys that recur, and what the 64-bit sum
lane costs.

Times ``ops/groupagg_device.py`` ``groupagg_apply_kernel`` alone at the
shapes of the cell ``q17_upserts_paced``: donated accumulators of
33,554,432 slots and batches of 2^20 bids of the suite's stream
(``benchmark/configs/nexmark_q5_large_keys.py`` ``LazyPool``), every
call a batch of its own. The slots are a real ``KeyDirectory``'s (128
shards x 262,144), so a batch's first-time keys are 128 runs of
consecutive slots as in the job, and the table of those runs goes up
with the batch (``fresh_pieces``). ``--recurring-share`` (a list; 0,
0.5, 1.0): that share of a batch's distinct keys is drawn from the keys
already folded in, which no cell of the benchmark offers. Per share:
the median ms of ``--reps`` calls on the host clock, each ending in
``block_until_ready``, after ``--warm`` untimed batches of new keys
(the first compiles: ``first_call_s``), and from the program's header a
batch's cells, gather trips, rows written dense and pieces written.
``--trace-dir D`` runs the timed calls of each share under the profiler
and adds the program's device ms a call and its ten longest ops.

Variants (``--only``), one jitted program each:

- ``q17``: the query's aggregate as the job runs it (three int32 band
  counts, an int32 min, max and newest event time, the int64 sum that
  XLA carries as two words);
- ``sum32``: the same with the sum lane at ONE word (int32: it wraps, the
  answers are wrong; the floor of what any lane costs);
- ``no_sum``: the same without a sum lane.

``q17 - no_sum`` is what the emulated int64 lane costs, ``sum32 -
no_sum`` what one word costs (at the first share listed): two words
with a hand-written carry cost at least twice that. One JSON line.

    chiprun --timeout 1800 -- python tools/groupagg_micro.py --only q17 \
        --recurring-share 0,0.5,1.0
    python tools/groupagg_micro.py --slots-per-shard 4096 --n 8192   # CPU: runs only
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


def batches_of(pool, first: int, count: int, warm: int, share: float,
               slots_per_shard: int, seed: int):
    """``count`` batches as the program takes them (slots, columns,
    pieces), on the device: the pool's from ``first`` on, from the
    ``warm``-th with ``share`` of each batch's distinct keys replaced
    by keys of the batches before it."""
    from flink_tpu.state.keyed import KeyDirectory

    directory = KeyDirectory(SHARDS, slots_per_shard)
    rng = np.random.default_rng(seed)
    folded = np.zeros(0, np.int64)
    out = []
    for i in range(count):
        b = pool[first + i]
        keys = b["auction"].astype(np.int64)
        n = len(keys)
        ids = np.unique(keys)
        if i >= warm and share > 0:
            pick = rng.random(len(ids)) < share
            table = ids.copy()
            table[pick] = rng.choice(folded, int(pick.sum()), replace=False)
            keys = table[np.searchsorted(ids, keys)]
            ids = np.unique(keys)
        folded = np.union1d(folded, ids)
        before = directory.free_pointers().copy()
        slot = directory.assign(keys)
        if (slot < 0).any():
            raise SystemExit("a shard ran full: more --slots-per-shard")
        args = [jnp.asarray(slot.astype(np.int32)),
                {"price": jnp.asarray(b["price"].astype(np.int32)),
                 A.EVENT_TIME_FIELD: jnp.asarray(
                     ((i * n + np.arange(n)) // 9200).astype(np.int32))}]
        if hasattr(G, "fresh_pieces"):   # a tree from before PR 49: none
            args.append(jnp.asarray(G.fresh_pieces(
                before, directory.free_pointers(), slots_per_shard, n)))
        out.append(args)
    return out


def device_ops(trace_dir: str) -> dict:
    """The apply's device ms a call and its longest ops, from the trace."""
    from benchmark import trace_reduce

    path = trace_reduce.newest_xplane(trace_dir)
    dev = trace_reduce.reduce_file(path).busiest() if path else None
    if dev is None or not dev.module_totals:
        return {}
    calls, secs = dev.seconds(trace_reduce.MODULES_LINE,
                              "^jit_groupagg_apply_kernel$")
    ops = sorted(dev.op_totals.items(), key=lambda kv: -kv[1][1])[:10]
    return {"device_ms_per_call": round(1e3 * secs / max(calls, 1), 4),
            "top_ops_ms_per_call": [
                [n, c / max(calls, 1), round(1e3 * t / max(calls, 1), 4)]
                for n, (c, t) in ops]}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--n", type=int, default=1 << 20)
    ap.add_argument("--slots-per-shard", type=int, default=262144)
    ap.add_argument("--reps", type=int, default=8)
    ap.add_argument("--warm", type=int, default=2)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--only", default="")
    ap.add_argument("--recurring-share", default="0")
    ap.add_argument("--trace-dir", default="")
    args = ap.parse_args()
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "nexmark_q17_auction_stats.json")) as f:
        params = json.load(f)["params"]
    slots = SHARDS * args.slots_per_shard
    n = args.n
    shares = [float(x) for x in args.recurring_share.split(",")]
    pool = large_keys.make_pool(args.seed, n, params)
    first = 20      # past the stream's first epochs: ~68,400 new ids each
    dev = jax.devices()[0]
    out = {"n": n, "slots": slots, "reps": args.reps, "warm": args.warm,
           "seed": args.seed, "chunk": G.merge_chunk(n),
           "device": {"platform": dev.platform, "kind": dev.device_kind}}
    only = set(filter(None, args.only.split(",")))
    for name, agg in variants():
        if only and name not in only:
            continue
        out[name] = {"state_bytes": slots * 4 * G.state_words(agg)}
        for share in shares:
            batches = batches_of(pool, first, args.warm + args.reps,
                                 args.warm, share, args.slots_per_shard,
                                 args.seed)
            state = G.init_groupagg_state(agg, slots)
            times, heads = [], []
            trace_dir = args.trace_dir and os.path.join(
                args.trace_dir, f"{name}_{share:g}")
            for i, batch in enumerate(batches):
                if trace_dir and i == args.warm:
                    jax.profiler.start_trace(trace_dir)
                t0 = time.perf_counter()
                state, head, rows, full = G._JIT_GROUPAGG_APPLY(
                    state, *batch, agg=agg, slots=slots, cap=apply_chunk(n))
                jax.block_until_ready((state, head, rows))
                times.append(time.perf_counter() - t0)
                heads.append(np.asarray(head)[[0, 2, 3, 4]].tolist())
                del full
            if trace_dir:
                jax.profiler.stop_trace()
            del state, batches
            timed = times[args.warm:]
            cells, trips, dense, pieces = (
                statistics.median(h[j] for h in heads[args.warm:])
                for j in range(4))
            out[name][f"share_{share:g}"] = {
                "first_call_s": round(times[0], 3),
                "ms_per_batch": round(1e3 * statistics.median(timed), 4),
                "ms_all": [round(1e3 * t, 3) for t in timed],
                "cells_per_batch": cells, "trips_per_batch": trips,
                "rows_dense_per_batch": dense,
                "pieces_per_batch": pieces}
            if trace_dir:
                out[name][f"share_{share:g}"].update(device_ops(trace_dir))
            print(f"# {name} share {share:g}: "
                  f"{json.dumps(out[name][f'share_{share:g}'])}",
                  file=sys.stderr, flush=True)
    if {"q17", "sum32", "no_sum"} <= set(out):
        at = f"share_{shares[0]:g}"
        base = out["no_sum"][at]["ms_per_batch"]
        out["int64_lane_ms"] = round(
            out["q17"][at]["ms_per_batch"] - base, 4)
        out["one_word_lane_ms"] = round(
            out["sum32"][at]["ms_per_batch"] - base, 4)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
