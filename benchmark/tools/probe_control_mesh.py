"""The mesh float-sum probe's two readings, on the devices, at the probe's
own size, through the job itself.

    python benchmark/tools/probe_control_mesh.py --seeds 1,2,3 \
        --control-seeds 4,5,6 [--harness-seed 7] [--cpu]

For each of ``--seeds``: the configuration's probe (``float_sum_mesh``:
one 2^20-bid batch through ``count + sum_of("price")`` under
``cluster.mesh-devices``) exactly as ``run.py`` runs it after a window,
and beside its reading the numpy lane of ``float_sum_mesh.mesh_lane_sums``
(which should agree to the last bit). Then THE CONTROL, for each of
``--control-seeds``: the same job with the dot of the program's fire
lowered from ``Precision.HIGHEST`` to ``Precision.HIGH``, the nearest
precision below the one the program states (every program is traced
again for it; the tool says how many dots it lowered). A control has to
come out as not holding. ``--harness-seed`` then runs the whole cell
through ``run.py``'s ``main`` for 2 s, still lowered, to show the result
line's ``correct`` false with the probe's number beside its limit (with
``--cpu``: the rehearsal, whose dot cannot be lowered).
``--cpu`` is for a look at the sound readings without a chip (4 forced
host devices); a CPU has no lower dot precision, so its control reads
what the sound run reads.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from unittest import mock

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[0] = ROOT
CELL = "q5_mesh4_replay"


def seeds_of(text: str) -> list:
    return [int(s) for s in text.split(",") if s]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--harness-seed", type=int, default=None)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4").strip()

    import jax
    import jax.numpy as jnp

    if args.cpu:
        jax.config.update("jax_enable_compilation_cache", False)
    elif jax.devices()[0].platform != "tpu":
        print("probe_control_mesh: no TPU (--cpu for the sound readings "
              "alone)", file=sys.stderr)
        return 2
    from benchmark import run as R
    from flink_tpu.config import PipelineOptions
    from flink_tpu.ops import window

    bench = R.load_json(ROOT, "BENCHMARK.json")
    cell = R.Cell(bench, CELL, False)
    spec = cell.cfg["probe"]
    probe = R.load_module("probes", spec["module"])
    fs = probe.float_sum
    p = cell.params
    ppw = int(p["window_ms"]) // int(p["slide_ms"])
    n = int(cell.conf().get(PipelineOptions.MICROBATCH_SIZE))
    ts = R.load_module("traffic_kinds", "constant_rate").Schedule(
        {"events_per_ms": spec["events_per_ms"]}).batch_ts(0, n)

    lowered = []

    def one(seed: int, what: str) -> dict:
        out = probe.run(cell, spec, seed, False, R)
        data = probe.records(cell.module, seed, n, p)
        ref = fs.sliding(fs.pane_sums(data, ts, p)[0], ppw)
        lane = probe.mesh_lane_sums(data, ts, p)
        line = {"what": what, "seed": seed,
                "devices": f"{len(jax.devices())} x "
                           f"{jax.devices()[0].device_kind}",
                "sum_max_rel_err": out["sum_max_rel_err"],
                "limit": probe.SUM_RTOL, "holds": out["holds"],
                "rows": out["rows_got"], "seconds": out["seconds"],
                "numpy_lane": fs.gap(fs.window_sums_f32(lane, ppw), ref),
                "numpy_lane_high": fs.gap(
                    fs.lower_precision_sums(lane, ppw), ref),
                "dots_lowered": len(lowered)}
        print(json.dumps(line), flush=True)
        return line

    bad = 0
    for seed in seeds_of(args.seeds):
        bad += not one(seed, "sound")["holds"]

    real = jnp.einsum

    def einsum_high(*a, **kw):
        if kw.get("precision") == jax.lax.Precision.HIGHEST:
            kw["precision"] = jax.lax.Precision.HIGH
            lowered.append(a[0])
        return real(*a, **kw)

    if args.control_seeds or args.harness_seed is not None:
        with mock.patch.object(jnp, "einsum", einsum_high):
            # nothing traced at HIGHEST may be reused
            window._sharded_kernels.cache_clear()
            jax.clear_caches()
            for seed in seeds_of(args.control_seeds):
                bad += one(seed, "control")["holds"] and not args.cpu
            if args.harness_seed is not None:
                rc = R.main(["--workload", CELL, "--seed",
                             str(args.harness_seed), "--seconds", "2",
                             "--trace", "0"]
                            + (["--rehearsal"] if args.cpu else []))
                print(json.dumps({"what": "control through run.py",
                                  "rc": rc, "dots_lowered": len(lowered)}),
                      flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
