"""The float-sum probe's control, on the chip, at the probe's own size.

    python benchmark/tools/probe_control.py --config nexmark_q5 --seeds 1,2,3

For each seed: the probe's one batch of bids, its float64 pane sums, and
the window sums of their float32 values through the dot the program's
fire uses (``einsum("rcs,cw->rws")``: rows x panes x lanes against the
pane-in-window membership matrix) at ``Precision.HIGHEST`` (what the
program states), ``HIGH`` (THE CONTROL: the nearest precision below) and
``DEFAULT`` (bfloat16 products, the fault PR 21 found). Prints each
one's widest relative gap to the float64 reference beside the numpy
emulation kept in ``probes/float_sum.py`` ``lower_precision_sums``. The probe's limit
lies between the HIGHEST and the HIGH reading (PERF.md section 2).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[0] = ROOT


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="nexmark_q5")
    ap.add_argument("--seeds", default="1,2,3")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        print("probe_control: no TPU", file=sys.stderr)
        return 2
    from benchmark import run as R

    cfg = R.load_json(ROOT, "benchmark", "configs", args.config + ".json")
    p = cfg["params"]
    mod = R.load_module("configs", cfg["module"])
    fp = R.load_module("probes", cfg["probe"]["module"])
    n = 1 << 20
    ppw = int(p["window_ms"]) // int(p["slide_ms"])
    ts = R.load_module("traffic_kinds", "constant_rate").Schedule(
        {"events_per_ms": cfg["probe"]["events_per_ms"]}).batch_ts(0, n)
    for seed in (int(s) for s in args.seeds.split(",")):
        data = fp.records(mod, seed, n, p)
        sm, _cnt = fp.pane_sums(data, ts, p)
        ref = fp.sliding(sm, ppw)
        n_panes = sm.shape[0]
        ends = np.arange(n_panes + ppw)
        member = ((np.arange(n_panes)[:, None] < ends[None, :])
                  & (np.arange(n_panes)[:, None] >= ends[None, :] - ppw)
                  ).astype(np.float32)                      # (c, w)
        x = jnp.asarray(sm.T.astype(np.float32)[:, :, None])  # (r, c, 1)
        out = {"seed": seed, "rows": int((ref > 0).sum())}
        for name, prec in (("highest", jax.lax.Precision.HIGHEST),
                           ("high", jax.lax.Precision.HIGH),
                           ("default", jax.lax.Precision.DEFAULT)):
            y = jnp.einsum("rcs,cw->rws", x, jnp.asarray(member),
                           precision=prec)
            out[name] = fp.gap(np.asarray(y)[:, :, 0].T, ref)
        out["emulated_high"] = fp.gap(fp.lower_precision_sums(sm, ppw), ref)
        out["emulated_float32"] = fp.gap(fp.window_sums_f32(sm, ppw), ref)
        out["limit"] = fp.SUM_RTOL
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
