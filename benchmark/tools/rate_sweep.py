"""Find the highest rate a paced cell's job sustains: one process, one
warm-up, then the cell's job at each rate for a short window.

    python benchmark/tools/rate_sweep.py --workload q5_hostfed_paced \
        --rates 40000,48000,56000,64000 --seconds 12 --seed 7

Prints one JSON line per rate: the source's lag at the end of the window,
its slope over the window (ms gained per second: near zero = sustained),
and the latency percentiles. The knee is the highest rate whose lag does
not grow; the cell's traffic file takes four fifths of it. This is how
``benchmark/traffic/paced.json`` got its rate (PERF.md section 4).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[0] = ROOT


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="events per ms, commas")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args()

    from benchmark import run as R, stats

    _bench, cell, _devices = R.start(argparse.Namespace(
        workload=args.workload, rehearsal=False))
    from benchmark.loadgen import BenchSource, RecordingSink
    from flink_tpu.config import PipelineOptions

    conf = cell.conf()
    batch = int(conf.get(PipelineOptions.MICROBATCH_SIZE))
    p = cell.params
    pool = cell.module.make_pool(args.seed, batch, p)
    watch = R.CompileWatch()
    rates = [int(r) for r in args.rates.split(",")]
    R.warm_up(cell, conf, pool, cell.schedule(events_per_ms=rates[0]), batch,
              watch)
    for rate in rates:
        sched = cell.schedule(events_per_ms=rate)
        src = BenchSource(pool, sched, batch, schema=cell.module.SCHEMA,
                          paced=True, seconds=args.seconds)
        sink = RecordingSink()
        m = watch.mark()
        R.run_job(cell.module.build, conf, p, src, sink, f"sweep-{rate}")
        lat = stats.fire_latencies_ms(
            sink.first_arrival_by(cell.module.WINDOW_END_FIELD), src.t_open,
            cell.module.fire_delay_ms(p), src.max_ts)
        late = [1e3 * x for x in src.late_s]
        print(json.dumps({
            "rate_events_per_ms": rate, "batches": src.batches,
            "batch_interval_ms": batch / rate,
            "late_ms_first": late[0], "late_ms_p50": stats.percentile(late, 50),
            "late_ms_max": max(late), "late_ms_last": late[-1],
            "lag_slope_ms_per_s": stats.lag_slope_ms_per_s(
                src.release_s, src.late_s),
            "latency_samples": len(lat),
            "latency_p50_ms": stats.percentile(lat, 50) if lat else None,
            "latency_max_ms": max(lat) if lat else None,
            "compiled": watch.since(m)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
