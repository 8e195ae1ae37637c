"""Micro-check of the native key scan: ns a record, on the host it runs on.

Times ``ingest_fused_scan`` (``native/codec.cc``; the span
``window.key_scan`` of the count-only lane) alone, one call a batch as
the operator makes it, on the benchmark configuration's own keys
(``benchmark/configs/nexmark_q5.py`` ``make_pool``) and the replay
mix's timestamps, two ways:

- ``in_order``: the stream as the cells offer it, so a batch has one
  pane or two and the scan's pane cursor hardly moves;
- ``shuffled``: the same keys with timestamps drawn record by record
  from 5 panes, so the cursor moves on about four records in five and
  every move pays the floored division.

``--threads`` times each ordering once more per entry of the list, the
call made as the operator makes it under ``host.parallelism`` = that
many: the first pass split by record range over native threads and
merged in range order (``ingest_fused_scan_split``). The floor under
which the operator stays serial (``SCAN_RANGE_MIN_RECORDS`` a range) is
lifted here, so that a short ``--n`` shows what the floor is there for.

``--mode assign`` times the general lane's keying instead:
``KeyDirectory.assign`` (one native call, ``ht_assign``: a memo in front
of the probe, native allocation, slots written in place) beside the
two-step path it replaced (``lookup_claim`` + numpy ``_register`` +
numpy placeholder resolution; ``tests/test_directory_assign.py`` holds
the two to the same slots), each on a directory of its own at the
large-keys cell's size, over four key orders: ``suite`` (the cell's own
stream: ``nexmark_q5_large_keys`` ``make_pool``), ``in_order`` (distinct
ascending ids), ``shuffled`` (distinct ids in random order: no
locality) and ``zipf_drift`` (Zipf ranks over a hot set that moves with
every batch). ``--warm`` batches of the suite's stream are registered
first, so the table has that many x ~68,400 keys when the timing starts.

Host only: no device program runs and nothing here is a benchmark
metric. One JSON line: ns a record and ms a batch (best and median of
``--reps`` calls), ranges scanned and cursor moves a batch, per entry of
``--threads`` (``assign``: per key order, both paths, with the memo's
hit share and the share of records it was consulted for), and what the
machine gives the process — CPU model, ``os.cpu_count()``, the affinity
mask's size, the cgroup's CPU quota.

    python tools/scan_micro.py [--n 1048576] [--reps 30] [--seed 1]
                               [--threads 1,2,4,8]
    python tools/scan_micro.py --mode assign [--n 1048576] [--reps 8]
                               [--warm 8] [--seed 1]
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

from benchmark.configs import nexmark_q5, nexmark_q5_large_keys  # noqa: E402
from flink_tpu import native_codec  # noqa: E402
from flink_tpu.api.windowing import SlidingEventTimeWindows  # noqa: E402
from flink_tpu.config import Configuration  # noqa: E402
from flink_tpu.ops.window import WindowPlan  # noqa: E402
from flink_tpu.state.keyed import KeyDirectory  # noqa: E402

PANES_SHUFFLED = 5


def machine() -> dict:
    model = None
    try:
        with open("/proc/cpuinfo") as f:
            model = next((ln.split(":", 1)[1].strip() for ln in f
                          if ln.startswith("model name")), None)
    except OSError:
        pass
    quota = None
    for path in ("/sys/fs/cgroup/cpu.max",
                 "/sys/fs/cgroup/cpu/cpu.cfs_quota_us"):
        try:
            with open(path) as f:
                quota = f.read().strip()
            break
        except OSError:
            continue
    return {"cpu_model": model, "cpu_count": os.cpu_count(),
            "sched_affinity": len(os.sched_getaffinity(0)),
            "cgroup_cpu_quota": quota}


def load_cell(config: str):
    """(the configuration's json, its job conf with the overrides)."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           config + ".json")) as f:
        cfg = json.load(f)
    conf = Configuration.from_file(os.path.join(ROOT, "confs", cfg["conf"]))
    for k, v in cfg.get("conf_overrides", {}).items():
        conf.set(k, v)
    return cfg, conf


def assign_main(args) -> int:
    # the path assign replaced lives on in its parity test alone
    sys.path.insert(0, os.path.join(ROOT, "tests"))
    from test_directory_assign import TwoStepDirectory

    cfg, conf = load_cell("nexmark_q5_large_keys")
    n = args.n or int(conf.get_raw("pipeline.microbatch-size"))
    shards = int(conf.get_raw("state.num-key-shards"))
    spd = int(conf.get_raw("state.slots-per-shard"))
    pool = nexmark_q5_large_keys.make_pool(args.seed, n, cfg["params"])
    rng = np.random.default_rng(args.seed)
    far = 1 << 44       # ids no suite batch of a run reaches

    def suite(i):
        return pool[args.warm + i]["auction"]

    def in_order(i):
        return far + i * n + np.arange(n, dtype=np.int64)

    def shuffled(i):
        return 2 * far + rng.permutation(n).astype(np.int64) + i * n

    def zipf_drift(i):
        # rank r of batch i is id (i * 4,096 + r), spread over the id
        # space so that neighbours in rank are not neighbours in the memo
        ranks = np.minimum(rng.zipf(1.2, n), 1 << 20).astype(np.int64)
        return 3 * far + ((ranks + i * 4096) * 2654435761) % (1 << 40)

    out = {"mode": "assign", "n": n, "reps": args.reps, "seed": args.seed,
           "warm_batches": args.warm, "shards": shards,
           "slots_per_shard": spd,
           "library": os.path.basename(native_codec.build_library())}
    for name, make in (("suite", suite), ("in_order", in_order),
                       ("shuffled", shuffled), ("zipf_drift", zipf_drift)):
        dirs = {"two_step": TwoStepDirectory(shards, spd),
                "native": KeyDirectory(shards, spd)}
        for i in range(args.warm):
            for d in dirs.values():
                d.assign(pool[i]["auction"])
        new = dirs["native"]
        base = (new.assign_records, new.assign_memo_looks,
                new.assign_memo_hits, new.num_keys())
        times = {k: [] for k in dirs}
        for i in range(args.reps):
            keys = make(i)
            got = {}
            for k, d in dirs.items():
                t0 = time.perf_counter()
                got[k] = d.assign(keys)
                times[k].append(time.perf_counter() - t0)
            if not np.array_equal(got["two_step"], got["native"]):
                print(json.dumps({"error": f"slots differ: {name} {i}"}))
                return 1
        records = new.assign_records - base[0]
        out[name] = {
            "keys_at_start": base[3],
            "new_keys_per_batch": (new.num_keys() - base[3]) / args.reps,
            "memo_hit_share": (new.assign_memo_hits - base[2]) / records,
            "memo_consulted_share":
                (new.assign_memo_looks - base[1]) / records}
        for k, ts in times.items():
            out[name][k] = {
                "ns_per_record_best": 1e9 * min(ts) / n,
                "ns_per_record_median": 1e9 * statistics.median(ts) / n,
                "ms_per_batch_median": 1e3 * statistics.median(ts)}
    out["machine"] = machine()
    print(json.dumps(out))
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--mode", choices=("scan", "assign"), default="scan")
    ap.add_argument("--n", type=int, default=None,
                    help="records a batch (default: the job conf's)")
    ap.add_argument("--reps", type=int, default=None,
                    help="timed calls (default: scan 30, assign 8)")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--threads", default="1",
                    help="scan: comma list, ranges the first pass is "
                         "split in")
    ap.add_argument("--warm", type=int, default=8,
                    help="assign: suite batches registered before timing")
    args = ap.parse_args()
    if not native_codec.native_available():
        print(json.dumps({"error": native_codec.unavailable_reason()}))
        return 1
    if args.reps is None:
        args.reps = 30 if args.mode == "scan" else 8
    if args.mode == "assign":
        return assign_main(args)
    threads = [int(t) for t in args.threads.split(",")]
    native_codec.SCAN_RANGE_MIN_RECORDS = 1     # the curve, floor lifted

    cfg, conf = load_cell("nexmark_q5")
    with open(os.path.join(ROOT, "benchmark", "traffic", "replay.json")) as f:
        rate = int(json.load(f)["events_per_ms"])
    p = cfg["params"]
    n = args.n or int(conf.get_raw("pipeline.microbatch-size"))
    pool = nexmark_q5.make_pool(args.seed, n, p)

    # the job's own pane plan: q5.sql's HOP under the suite's watermark delay
    plan = WindowPlan.plan(
        SlidingEventTimeWindows.of(int(p["window_ms"]), int(p["slide_ms"])),
        max_out_of_orderness_ms=int(p["out_of_orderness_ms"]))
    pane_ms, ring = plan.pane_ms, plan.ring
    directory = KeyDirectory(int(conf.get_raw("state.num-key-shards")),
                             int(conf.get_raw("state.slots-per-shard")))
    ws = native_codec.PreaggWorkspace(directory.local_slots * ring, 0)
    cap = 1 << 19
    rng = np.random.default_rng(args.seed)
    lo = np.iinfo(np.int64).min

    def scan(keys, ts, t=1):
        t0 = time.perf_counter()
        out = native_codec.ingest_fused_scan_native(
            keys, ts, directory._table, pane_ms, plan.offset_ms, ring, ws,
            cap, lo, lo, 0, miss_cap=len(ts), threads=t)
        dt = time.perf_counter() - t0
        res, miss = out
        ranges = res.ranges
        if len(miss):   # the operator's own second pass, untimed here
            directory.register_misses(keys[miss])
            res, _ = native_codec.ingest_fused_scan_native(
                keys[miss], ts[miss], directory._table, pane_ms,
                plan.offset_ms, ring, ws, cap, lo, lo, 0, cont=res,
                miss_cap=1)
        moves = int(res.stats[8])
        native_codec.ingest_fused_finalize_pairs_native(res, ws)
        return dt, moves, ranges

    def ts_in_order(i):
        return (i * n + np.arange(n, dtype=np.int64)) // rate

    def ts_shuffled(i):
        return (i * PANES_SHUFFLED * pane_ms
                + rng.integers(0, PANES_SHUFFLED * pane_ms, n))

    out = {"n": n, "reps": args.reps, "seed": args.seed, "ring": ring,
           "library": os.path.basename(native_codec.build_library())}
    for name, make_ts in (("in_order", ts_in_order),
                          ("shuffled", ts_shuffled)):
        for i in range(len(pool)):      # registers the keys, warms caches
            scan(pool[i]["auction"], make_ts(i))
        out[name] = {}
        for t in threads:
            scan(pool[0]["auction"], make_ts(0), t)   # its workspaces
            times, moves = [], []
            for i in range(args.reps):
                dt, mv, ranges = scan(pool[i % len(pool)]["auction"],
                                      make_ts(i), t)
                times.append(dt)
                moves.append(mv)
            out[name][f"threads_{t}"] = {
                "ranges": ranges,
                "ns_per_record_best": 1e9 * min(times) / n,
                "ns_per_record_median": 1e9 * statistics.median(times) / n,
                "ms_per_batch_median": 1e3 * statistics.median(times),
                "pane_moves_per_batch": statistics.mean(moves)}
    out["keys"] = directory.num_keys()
    out["machine"] = machine()
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
