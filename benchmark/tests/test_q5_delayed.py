"""The configuration ``nexmark_q5_delayed`` and its cell
``q5_delayed_paced``: what the construction of the offered stream must
keep ((a)-(e) of ISSUE 50: every bid once, no row before its time, a
tenth held back 0-3 s, a batch the same whenever asked and cheap, a row's
content that of its own generator index), the delay parameters equal in
the mix and the configuration, the reference against the in-order one,
the files as ``BENCHMARK.json`` names them (found BY NAME), and the
cell's rehearsal end to end."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark.configs import nexmark_q5_delayed as delayed
from benchmark.configs import nexmark_q5_large_keys as large
from benchmark.traffic_kinds import constant_rate
from benchmark.traffic_kinds.constant_rate_delayed import Arrivals, Schedule

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL = "q5_delayed_paced"
CONFIG = "nexmark_q5_delayed"
MIX = "paced_suite_delayed"
DELAY_KEYS = ("prob_delayed", "occasional_delay_ms", "delay_seed")


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


CFG = load(BENCH, "configs", CONFIG + ".json")
TRAFFIC = load(BENCH, "traffic", MIX + ".json")
# the rehearsal's density: a batch of 4,096 spans 256 ms, the longest
# delay 12 batches
PARAMS = {**CFG["params"], **CFG["rehearsal"]["params"]}
SMALL = {**TRAFFIC, **TRAFFIC["rehearsal"]}
N = 4096


def offered(n_batches, n=N, seed=2**31 + 9):
    pool, sched = delayed.make_pool(seed, n, PARAMS), Schedule(SMALL)
    return pool, sched, [(pool[i], sched.batch_ts(i, n))
                         for i in range(n_batches)]


# -- the stream ------------------------------------------------------------

def test_every_bid_is_offered_once_and_none_before_its_time():
    """(a), (b): over the first 60 batches every generator index below
    the oldest still held back turns up exactly once; every batch has n
    rows; the last row carries the batch's largest timestamp, which is
    what ``BenchSource`` releases the batch on; no row is offered before
    a batch in which an on-time bid of its own millisecond or later is."""
    arr = Arrivals(N, SMALL["events_per_ms"], *(SMALL[k] for k in DELAY_KEYS))
    sched = Schedule(SMALL)
    rate, seen, last = SMALL["events_per_ms"], [], -1
    for i in range(60):
        k = arr.indices(i)
        ts = sched.batch_ts(i, N)
        assert len(k) == N == len(ts) and ts.dtype == np.int64
        assert np.array_equal(ts, k // rate)
        assert ts[-1] == ts.max() and k.min() >= 0
        assert ts[-1] >= last          # releases never go backwards
        last = int(ts[-1])
        seen.append(k)
    seen = np.concatenate(seen)
    assert len(np.unique(seen)) == len(seen)
    missing = np.setdiff1d(np.arange(seen.max() + 1), seen)
    # what is missing is still held back: it happened within the longest
    # delay of the newest bid offered
    assert len(missing) and missing.min() >= (
        seen.max() - SMALL["occasional_delay_ms"] * rate - N)
    # a tenth of the bids, for half the longest delay on average
    assert 0.04 < len(missing) / (SMALL["occasional_delay_ms"] * rate) < 0.06


def test_a_tenth_of_a_batch_is_held_back_by_up_to_three_seconds():
    """(c): once the stream has run 3 s, the rows of a batch that
    happened before the batch's fill began are a tenth of it, and their
    age is uniform on 0-3 s (plus the fill)."""
    arr = Arrivals(N, SMALL["events_per_ms"], *(SMALL[k] for k in DELAY_KEYS))
    sched = Schedule(SMALL)
    fill = N // SMALL["events_per_ms"]
    assert arr.steady_from <= 3000 // fill + 2
    for i in (arr.steady_from, arr.steady_from + 7, 5000):
        ts = sched.batch_ts(i, N)
        age = ts[-1] - ts
        old = age[age >= fill]
        assert 0.08 < len(old) / N < 0.115
        assert age.max() < SMALL["occasional_delay_ms"] + fill + 2
        # uniform: the quartiles of the old rows' ages lie a quarter of
        # the delay apart
        q = np.percentile(old - fill, [25, 50, 75]) / (
            SMALL["occasional_delay_ms"] - fill)
        assert np.allclose(q, [0.25, 0.5, 0.75], atol=0.06), q
        # the held-back rows come first (a tenth of the batch, in due
        # order), the on-time rows after them in the order they happened
        assert (np.diff(ts[int(0.115 * N):]) >= 0).all()
        assert (age[:int(0.085 * N)] >= fill).mean() > 0.9
    # the ramp: fewer bids fall due than happen, so a batch takes longer
    # to fill and holds fewer old rows
    ts0 = sched.batch_ts(0, N)
    age0 = ts0[-1] - ts0
    assert ts0[-1] > fill and (age0 > ts0[-1] - fill).mean() > 0.85


def test_a_batch_is_the_same_whenever_asked_and_costs_no_division():
    """(d): batch ``i`` of the pool and of the schedule, asked for twice
    and out of order, and equal to the construction made anew; past the
    ramp the timestamps come from the table (three passes, no division),
    which equals the division."""
    pool, sched, _ = offered(0)
    again = delayed.make_pool(2**31 + 9, N, PARAMS)
    first = {}
    for i in [40, 0, 13, 40, 3, 10**6 + 1, 13, 12, 0]:
        got, ts = pool[i], sched.batch_ts(i, N)
        assert set(got) == set(delayed.SCHEMA)
        assert all(v.dtype == np.int64 and len(v) == N for v in got.values())
        if i in first:
            assert all(np.array_equal(got[f], first[i][0][f]) for f in got)
            assert np.array_equal(ts, first[i][1])
        first[i] = ({f: v.copy() for f, v in got.items()}, ts.copy())
        assert all(np.array_equal(got[f], again[i][f]) for f in got)
        assert np.array_equal(
            ts, pool.arrivals.indices(i) // SMALL["events_per_ms"])
    assert sched.arrivals(N).steady_from < 40


@pytest.mark.parametrize("n", [4096, 46 * 150])
def test_a_rows_content_is_that_of_its_own_generator_index(n):
    """(e): every row of every kind of batch (the first, with the
    stream's first bids; the ramp's; steady ones; one far out) holds
    what ``nexmark_q5_large_keys.suite_batch`` gives at the row's own
    generator index, over the draws of that index mod the pool."""
    seed = 2**31 + 5
    pool = delayed.make_pool(seed, n, PARAMS)
    steady = pool.arrivals.steady_from
    made = {}
    for i in [0, 1, steady - 1, steady, steady + 1, steady + 6, 10**5 + 3]:
        got, k = pool[i], pool.arrivals.indices(i)
        for q in np.unique(k // n).tolist():
            if q not in made:
                made[q] = large.suite_batch(seed, q, n, PARAMS)
            rows = k // n == q
            for f in got:
                assert np.array_equal(got[f][rows], made[q][f][k[rows] % n]
                                      ), (i, q, f)
    # a held-back row names an auction far behind the batch's newest
    a = pool[steady + 6]["auction"]
    assert a.max() - a.min() > 20 * (np.median(a) - a.min()) or \
        a.max() - np.median(a) < (a.max() - a.min()) / 10


def test_the_delay_parameters_stand_equal_in_both_files():
    """``make_pool`` is given no traffic parameters and ``Schedule`` no
    configuration: both files state the rate and the delay model, at the
    cell's size and at the rehearsal's."""
    keys = ("events_per_ms",) + DELAY_KEYS
    assert {k: CFG["params"][k] for k in keys} == {
        k: TRAFFIC[k] for k in keys}
    assert {k: PARAMS[k] for k in keys} == {k: SMALL[k] for k in keys}
    assert (TRAFFIC["prob_delayed"], TRAFFIC["occasional_delay_ms"]) == (
        0.1, 3000)
    # inside the watermark's bound with a batch's fill to spare
    fill = (1 << 20) / TRAFFIC["events_per_ms"]
    assert TRAFFIC["occasional_delay_ms"] + fill < CFG["params"][
        "out_of_orderness_ms"]
    # the in-order kind's stamp: event k happens at k // rate
    plain = constant_rate.Schedule({"events_per_ms": 16})
    assert np.array_equal(plain.batch_ts(3, N), np.arange(3 * N, 4 * N) // 16)


# -- the reference ---------------------------------------------------------

def in_order(stream, n):
    """The same events as batches of ``n`` in timestamp order."""
    auction = np.concatenate([d["auction"] for d, _ in stream])
    ts = np.concatenate([t for _, t in stream])
    order = np.argsort(ts, kind="stable")
    return [({"auction": auction[o]}, ts[o])
            for o in np.split(order, len(order) // n)]


def test_the_reference_equals_the_in_order_one_on_the_same_events():
    _, _, stream = offered(90)
    max_ts = max(int(ts[-1]) for _, ts in stream)
    slide = int(PARAMS["slide_ms"])
    n_panes = max_ts // slide + 1
    want = large.hot_items(large.pane_counts(in_order(stream, N), slide),
                           n_panes, PARAMS)
    assert len(want[0]) > 10
    rows = [{"window_end": want[0], "auction": want[1],
             "bid_count": want[2]}]
    for check in (delayed.check, large.check):
        got = check(iter(stream), max_ts, rows, PARAMS)
        assert got["rows_expected"] == got["rows_got"] == len(want[0])
        assert (got["rows_missing"], got["rows_not_in_reference"],
                got["rows_duplicated"]) == (0, 0, 0)
    # a held-back bid counted in the pane it arrived in, not the one it
    # happened in, is another answer: the reference refuses it
    moved = [(d, np.full(len(ts), ts[-1])) for d, ts in stream]
    wrong = large.hot_items(large.pane_counts(moved, slide), n_panes, PARAMS)
    bad = delayed.check(iter(stream), max_ts, [
        {"window_end": wrong[0], "auction": wrong[1],
         "bid_count": wrong[2]}], PARAMS)
    assert bad["rows_missing"] > 0 and bad["rows_not_in_reference"] > 0
    # old rows apart: the same events, in parts
    parts = list(delayed.old_rows_apart(iter(stream)))
    assert len(parts) > len(stream)
    assert sum(len(t) for _, t in parts) == len(stream) * N


# -- the files -------------------------------------------------------------

def test_the_files_are_what_benchmark_json_names():
    bench = load(ROOT, "BENCHMARK.json")
    (row,) = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert row["source"] == CFG["source"] and len(CFG["source"]) <= 200
    assert row["file"] == f"benchmark/configs/{CONFIG}.json"
    assert row["reduced"] == CFG["reduced"] == ["pool_batches"]
    assert set(CFG["reduced_why"]) == {"pool_batches"}
    one = load(BENCH, "configs", "nexmark_q5_large_keys.json")
    assert {k: v for k, v in CFG["params"].items()
            if k not in ("events_per_ms",) + DELAY_KEYS} == one["params"]
    for k in ("conf", "conf_overrides", "chips", "probe"):
        assert CFG[k] == one[k], k
    assert set(CFG["assumed"]) == set(one["assumed"]) | {"delay_model"}
    assert CFG["assumed"]["timestamps"] != one["assumed"]["timestamps"]
    assert len(CFG["guarantees"]) == len(one["guarantees"]) + 1
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell == {"name": CELL, "config": CONFIG, "traffic": MIX,
                    "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200
    assert (TRAFFIC["kind"], TRAFFIC["events_per_ms"], TRAFFIC["paced"]) == (
        "constant_rate_delayed", 9200, True)
    by_name = {m["name"]: m for s in ("end_to_end", "per_layer")
               for m in bench[s]}
    reports = {name for name, m in by_name.items()
               if "workloads" not in m or CELL in m["workloads"]}
    new = {"disorder.delayed_share.paced", "disorder.max_ms.paced",
           "disorder.panes_per_batch.paced",
           "disorder.refire_probe_per_batch.paced",
           "disorder.lead_share.paced", "hostkey.memo_hit_share.paced",
           "apply.cells_per_batch.paced", "apply_roofline.q5_delayed"}
    assert new | {"event_latency_p50_ms", "setup_s", "state.hbm_bytes",
                  "source.late_p95_ms.paced", "hostkey.ms_per_batch.paced",
                  "apply.device_ms_per_batch.paced",
                  "state.release_ms_per_batch.paced",
                  "device.idle_share.paced"} <= reports
    for name in new:
        assert by_name[name]["workloads"] == [CELL], name
        assert by_name[name]["moves"] == "event_latency_p50_ms"
    # every metric the sibling paced cell reports, but its checkpoints'
    twin = {name for name, m in by_name.items()
            if "q5_exactly_once_paced" in m.get("workloads", ())}
    assert {n for n in twin if not n.startswith("checkpoint.")} <= reports
    assert not {n for n in reports if n.startswith("checkpoint.")}
    for name in reports - {"event_latency_p50_ms", "setup_s"}:
        assert os.path.isfile(os.path.join(
            BENCH, "layer_metrics", name + ".json")), name
    # the reference takes nothing from the program
    src = open(os.path.join(BENCH, "configs", CONFIG + ".py")).read()
    assert "flink_tpu" not in src
    assert delayed.zero_counters(PARAMS) == large.zero_counters(PARAMS) + (
        "refire_ends",)
    for name in ("build", "warmup_event_ms", "fire_delay_ms", "step_shapes",
                 "collect"):
        assert getattr(delayed, name) is getattr(large, name), name


# -- the cell, end to end, at rehearsal size -------------------------------

def test_the_cells_rehearsal_is_correct_and_nothing_is_late():
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", str(2**31 + 77), "--seconds", "6",
         "--trace", "1", "--rehearsal"],
        cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    out, detail = json.loads(lines[-1]), json.loads(lines[-2])
    assert out["correct"] is True and out["failed"] == 0
    assert out["rehearsal"] is True
    assert all(v["value"] is None for v in out["metrics"].values())
    # the counters the new metrics read are there (no number on a CPU)
    assert {"disorder.delayed_share.paced", "disorder.max_ms.paced",
            "disorder.panes_per_batch.paced",
            "disorder.refire_probe_per_batch.paced",
            "hostkey.memo_hit_share.paced",
            "apply.cells_per_batch.paced"} <= set(out["metrics"])
    cmp_ = detail["compare"]
    assert cmp_["rows_expected"] == cmp_["rows_got"] > 5
    assert all(v == 0 for v in detail["counters"].values()), detail["counters"]
    assert {"late_records", "refire_ends", "records_dropped_full",
            "state.slots_returned_early"} <= set(detail["counters"])
    assert detail["probe"]["holds"] is True
    assert detail["compiled_in_window"]["programs"] == \
        detail["compiled_in_window"]["cache_hits"]
    # the window offered batches of the ramp and of the steady stream
    assert detail["window"]["batches"] >= 8
