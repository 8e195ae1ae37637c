"""The float-sum probe: a check that every run of a configuration which
names it makes (``"probe": {"module": "float_sum", ...}`` in the
configuration's JSON), not a cell.

Every lane of the NEXmark Q5 cells is an integer count, which a loss of
precision on the device cannot move. The one numeric fault this program
has had on the chip was exactly that loss (PR 21: ``fire_kernel`` summed
float lanes through a default-precision dot, so every window's price sum
was rounded to bfloat16, 3.9e-3 off). So after the measured window,
outside every timing, each run pushes one seeded batch of NEXmark bids
through ``count + sum_of("price")`` on the configuration's sliding
windows, on one device, and holds every committed sum to the float64
reference. The records are the configuration's own first batch (its
module's ``make_pool``), the price taken from cents to float32 units.

The control (``lower_precision_sums``) is the reference computed the way
the next precision down would: the window sum of float32 pane sums
through a dot at ``Precision.HIGH``, which splits each operand into two
bfloat16 pieces and so keeps 16 bits of mantissa (about 8e-6 relative).
``SUM_RTOL`` lies between what sound runs read and what that control
reads; PERF.md section 2 gives the readings it was set from.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

# limit on max |sum - f64 reference| / reference over all committed rows
SUM_RTOL = 1e-6

SCHEMA = {"auction": "int64", "bidder": "int64", "price": "float32"}


def records(module, seed: int, n: int, p: dict) -> Dict[str, np.ndarray]:
    """The probe's one batch: the configuration's bids, the price in
    float32 units (the lane the probe is there for)."""
    bids = module.make_pool(seed, n, p)[0]
    return {"auction": bids["auction"], "bidder": bids["bidder"],
            "price": (bids["price"] / 100.0).astype(np.float32)}


def build(env, source, sink, p: dict) -> None:
    from flink_tpu.api.windowing import SlidingEventTimeWindows
    from flink_tpu.ops.aggregates import count, multi, sum_of
    from flink_tpu.time.watermarks import WatermarkStrategy

    (env.from_source(
        source, WatermarkStrategy.for_bounded_out_of_orderness(
            int(p["out_of_orderness_ms"])))
        .key_by("auction")
        .window(SlidingEventTimeWindows.of(int(p["window_ms"]),
                                           int(p["slide_ms"])))
        .aggregate(multi(count(), sum_of("price")))
        .add_sink(sink))


def pane_sums(data: Dict[str, np.ndarray], ts: np.ndarray, p: dict):
    """(n_panes, auction ids) float64 price sums and int64 counts."""
    a = int(data["auction"].max()) + 1
    pane = np.asarray(ts, np.int64) // int(p["slide_ms"])
    n_panes = int(pane.max()) + 1
    cell = pane * a + data["auction"]
    cnt = np.bincount(cell, minlength=n_panes * a).reshape(n_panes, a)
    sm = np.bincount(cell, weights=data["price"].astype(np.float64),
                     minlength=n_panes * a).reshape(n_panes, a)
    return sm, cnt


def sliding(panes: np.ndarray, ppw: int) -> np.ndarray:
    """(n_panes, A) -> per-window sums; row ``e`` ends at ``e * slide``."""
    n = panes.shape[0]
    cs = np.concatenate([np.zeros((1, panes.shape[1]), panes.dtype),
                         np.cumsum(panes, axis=0)])
    ends = np.arange(n + ppw)
    return cs[np.minimum(ends, n)] - cs[np.maximum(ends - ppw, 0)]


def window_sums_f32(panes: np.ndarray, ppw: int) -> np.ndarray:
    """Per-window sums of float32 pane values, each window's panes added
    in float32 (no cumulative sums: their differences cancel digits)."""
    x = np.asarray(panes, np.float32)
    n = x.shape[0]
    return np.stack([x[max(e - ppw, 0):min(e, n)].sum(axis=0,
                                                      dtype=np.float32)
                     for e in range(n + ppw)])


def bf16_round(x: np.ndarray) -> np.ndarray:
    """float32 -> nearest bfloat16 (ties to even), returned as float32."""
    b = np.asarray(x, np.float32).view(np.uint32)
    r = ((b >> 16) & 1) + np.uint32(0x7FFF)
    return ((b + r) & np.uint32(0xFFFF0000)).view(np.float32)


def lower_precision_sums(pane_sm: np.ndarray, ppw: int) -> np.ndarray:
    """THE CONTROL: window sums of float32 pane sums as a dot at
    ``Precision.HIGH`` computes them — each pane sum enters as the sum of
    two bfloat16 pieces (hi + lo), the membership ones are exact, the
    accumulation is float32."""
    x = pane_sm.astype(np.float32)
    hi = bf16_round(x)
    return window_sums_f32(hi + bf16_round(x - hi), ppw)


def gap(values: np.ndarray, ref: np.ndarray) -> float:
    """max relative distance over the cells the reference has bids in."""
    mask = ref > 0
    if not mask.any():
        return float("nan")
    return float(np.max(np.abs(values[mask].astype(np.float64) - ref[mask])
                        / ref[mask]))


def check_rows(sink_batches, data, ts, p: dict) -> dict:
    """Every committed (window, auction) row's count exactly and sum
    within SUM_RTOL of the float64 reference."""
    ppw = int(p["window_ms"]) // int(p["slide_ms"])
    slide = int(p["slide_ms"])
    sm, cnt = pane_sums(data, ts, p)
    ref_s, ref_c = sliding(sm, ppw), sliding(cnt, ppw)
    cols = {f: (np.concatenate([np.asarray(b[f]) for b in sink_batches])
                if sink_batches else np.zeros(0))
            for f in ("window_end", "key", "count", "sum_price")}
    e = cols["window_end"].astype(np.int64) // slide
    k = cols["key"].astype(np.int64)
    inside = (e >= 0) & (e < ref_c.shape[0]) & (k >= 0) & (k < ref_c.shape[1])
    e, k = e[inside], k[inside]
    out = {
        "rows_expected": int((ref_c > 0).sum()),
        "rows_got": int(len(inside)),
        "rows_unique": int(len(np.unique(e * ref_c.shape[1] + k))),
        "counts_differing": int((cols["count"][inside].astype(np.int64)
                                 != ref_c[e, k]).sum())
        + int((~inside).sum()),
        "sum_dtype": str(cols["sum_price"].dtype),
        "sum_rtol": SUM_RTOL,
    }
    ref = ref_s[e, k]
    got = cols["sum_price"][inside].astype(np.float64)
    ok = ref > 0
    rel = np.abs(got[ok] - ref[ok]) / ref[ok]
    out["sum_max_rel_err"] = (float(rel.max()) if len(rel) and
                              np.isfinite(rel).all() else float("inf"))
    out["holds"] = bool(
        out["rows_got"] == out["rows_expected"] == out["rows_unique"]
        and out["counts_differing"] == 0
        and out["sum_max_rel_err"] <= SUM_RTOL)
    return out


def run(config, spec: dict, seed: int, rehearsal: bool, harness) -> dict:
    """Push the batch through the job and hold it to the reference.
    ``config`` is the run's ``Config``, ``spec`` the ``probe`` entry of
    its JSON, ``harness`` the run module (``run_job``, ``make_conf``,
    ``load_module``). Returns ``{"compared": {name: [value, limit]}, ...}``."""
    import time

    from benchmark.loadgen import BenchSource, RecordingSink
    from flink_tpu.config import PipelineOptions

    p = config.params
    conf = harness.make_conf(spec["conf"], config.conf_overrides)
    batch = int(conf.get(PipelineOptions.MICROBATCH_SIZE))
    sched = harness.load_module("traffic_kinds", "constant_rate").Schedule(
        {"events_per_ms": 1 if rehearsal else spec["events_per_ms"]})
    sink = RecordingSink()
    t0 = time.perf_counter()
    res = harness.run_job(
        build, conf, p,
        BenchSource([records(config.module, seed, batch, p)], sched, batch,
                    schema=SCHEMA, max_batches=1), sink, "float-sum-probe")
    out = check_rows(sink.batches, records(config.module, seed, batch, p),
                     sched.batch_ts(0, batch), p)
    out["seconds"] = round(time.perf_counter() - t0, 3)
    out["dropped"] = int(res.metrics.get("records_dropped_full", 0)) + int(
        res.metrics.get("late_records", 0))
    out["compared"] = {
        "sum_max_rel_err": [out["sum_max_rel_err"], SUM_RTOL],
        "counts_differing": [out["counts_differing"], 0],
        "rows_got_minus_expected": [
            out["rows_got"] - out["rows_expected"], 0],
        "rows_got_minus_unique": [out["rows_got"] - out["rows_unique"], 0],
        "dropped": [out["dropped"], 0]}
    return out
