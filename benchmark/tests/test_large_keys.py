"""The configuration ``nexmark_q5_large_keys`` and its cell
``q5_large_keys_replay``: the lazy pool against the suite's formula, the
sparse reference against the dense one and against a control, the byte
model's arithmetic, the files as ``BENCHMARK.json`` names them, and the
cell's rehearsal end to end."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import large_keys_step_bytes as lk_bytes
from benchmark.configs import nexmark_q5, nexmark_q5_large_keys as large
from benchmark.readers import trace_roofline_large_keys
from benchmark.traffic_kinds.constant_rate import Schedule

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL = "q5_large_keys_replay"
CONFIG = "nexmark_q5_large_keys"


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


PARAMS = load(BENCH, "configs", CONFIG + ".json")["params"]


# -- the records -----------------------------------------------------------

@pytest.mark.parametrize("n", [4096, 46 * 300])
def test_the_lazy_pools_item_is_the_suites_formula_in_any_order(n):
    """Item ``i`` equals the suite's formula for bids ``[i*n, (i+1)*n)``
    computed directly, whenever and however often it is asked for; the
    seed is as large as the driver's."""
    seed = 2**31 + 5
    pool = large.make_pool(seed, n, PARAMS)
    assert len(pool) > 10**9
    order = [7, 0, 3, 7, 1, 10**6 + 3, 12, 3, 0, 11]
    first = {}
    for i in order:
        got = pool[i]
        want = large.suite_batch(seed, i, n, PARAMS)
        assert set(got) == set(large.SCHEMA) == set(want)
        for k in want:
            assert got[k].dtype == np.int64 and len(got[k]) == n
            assert np.array_equal(got[k], want[k]), (i, k)
        if i in first:
            assert all(np.array_equal(got[k], first[i][k]) for k in got)
        first[i] = {k: v.copy() for k, v in got.items()}
    # the draws repeat every pool_batches batches, the ids do not
    a, b = pool[2], pool[2 + PARAMS["pool_batches"]]
    assert np.array_equal(a["price"], b["price"])
    assert a["auction"].max() < b["auction"].min()


def test_the_draws_are_the_accepted_configurations():
    """With a wrap beyond every id (which folds nothing) the accepted
    module's ``make_pool`` is the suite's formula for the first
    ``pool_batches`` batches: ``suite_batch`` gives the same bids, draw
    for draw."""
    n, seed = 46 * 100, 2**31 + 9
    wide = dict(PARAMS, auction_id_wrap=1 << 40)
    for j, want in enumerate(nexmark_q5.make_pool(seed, n, wide)):
        got = large.suite_batch(seed, j, n, PARAMS)
        for k in want:
            assert np.array_equal(got[k], want[k]), (j, k)


def test_a_batch_names_auctions_no_earlier_batch_has():
    """What the configuration exists for: 2^20 bids span 22,795 epochs,
    ~68,400 new auctions, and all of a key's bids fall within ~1,700
    events."""
    n = 1 << 20
    pool = large.make_pool(7, n, PARAMS)
    a, b = pool[5]["auction"], pool[6]["auction"]
    new = np.setdiff1d(b, a)
    assert 68_000 < len(new) < 68_800
    assert 68_000 < len(np.unique(b)) < 69_000
    order = np.argsort(b, kind="stable")
    ids, start = np.unique(b[order], return_index=True)
    spans = np.maximum.reduceat(order, start) - np.minimum.reduceat(
        order, start)
    assert np.percentile(spans, 99) < 2000
    # and the generator's own proportions: half the bids on hot auctions
    hot = (b - nexmark_q5.FIRST_AUCTION_ID) % 100 == 0
    assert 0.49 < hot.mean() < 0.52


# -- the reference ---------------------------------------------------------

def short_stream(n_batches=30, n=4096, rate=2, seed=11):
    pool = large.make_pool(seed, n, PARAMS)
    sched = Schedule({"events_per_ms": rate})
    return [(pool[i], sched.batch_ts(i, n)) for i in range(n_batches)]


def expected_rows(stream):
    slide = PARAMS["slide_ms"]
    n_panes = int(stream[-1][1][-1]) // slide + 1
    return large.hot_items(large.pane_counts(iter(stream), slide),
                           n_panes, PARAMS)


def test_the_sparse_reference_equals_the_dense_one():
    """On a short unwrapped stream, ``nexmark_q5.py``'s dense reference
    (given a wrap beyond every id, which folds nothing) and this one
    give the same rows."""
    stream = short_stream()
    top = max(int(d["auction"].max()) for d, _ in stream)
    dense_p = dict(PARAMS, auction_id_wrap=top + 1)
    n_panes = int(stream[-1][1][-1]) // PARAMS["slide_ms"] + 1
    dense = nexmark_q5.hot_items(
        nexmark_q5.pane_counts(iter(stream), n_panes, dense_p), dense_p)
    sparse = expected_rows(stream)
    assert len(sparse[0]) > 20
    for a, b in zip(dense, sparse):
        assert np.array_equal(a, b)


def test_check_passes_its_own_rows_and_refuses_a_moved_key():
    stream = short_stream()
    we, au, ct = expected_rows(stream)
    max_ts = int(stream[-1][1][-1])

    def sink(auction):
        return [{"window_end": we, "auction": auction, "bid_count": ct}]

    ok = large.check(iter(stream), max_ts, sink(au), PARAMS)
    assert ok["rows_expected"] == len(we) == ok["rows_got"]
    assert (ok["rows_missing"], ok["rows_not_in_reference"],
            ok["rows_duplicated"], ok["events_without_result"]) == (0,) * 4
    # THE CONTROL: one key's counts under another id (what a slot handed
    # out again too early does to a row)
    moved = au.copy()
    moved[len(moved) // 2] += 3
    bad = large.check(iter(stream), max_ts, sink(moved), PARAMS)
    assert bad["rows_missing"] == 1 == bad["rows_not_in_reference"]
    assert bad["events_without_result"] == int(ct[len(ct) // 2])
    # a row twice
    twice = large.check(iter(stream), max_ts, sink(au) + sink(au), PARAMS)
    assert twice["rows_duplicated"] == len(we)


# -- the byte model and its reader -----------------------------------------

def test_the_byte_models_arithmetic():
    n = 1 << 20
    assert lk_bytes.apply_bytes(records=n) == n * (4 + 2 * 32)
    assert lk_bytes.fire_bytes(state_bytes=805_306_416) == 805_306_416


class _Dev:
    def __init__(self, table):
        self.table = table

    def seconds(self, line, match):
        import re
        rx = re.compile(match)
        hit = [v for k, v in self.table.items() if rx.search(k)]
        return sum(c for c, _ in hit), sum(s for _, s in hit)


class _Trace:
    def __init__(self, table):
        self.dev = _Dev(table)

    def busiest(self):
        return self.dev


def test_the_roofline_reader_divides_by_calls_or_by_batches():
    peak = 819e9
    table = {"jit_apply_kernel": (20, 20 * 0.010),
             "jit_ring_append_topn_kernel": (2, 2 * 0.050),
             "jit_clear_kernel": (2, 0.004)}
    ctx = {"trace": _Trace(table), "trace_batches": 20, "chips": 1,
           "device_kind": "TPU v5 lite",
           "step_shapes": {"records": 1 << 20},
           "job_metrics": {"memory.hbm_state_bytes": 805_306_416}}
    read = trace_roofline_large_keys.read
    apply_share = read(ctx, of="apply", match="^jit_apply_kernel$")
    assert apply_share == pytest.approx(
        100 * ((1 << 20) * 68 / peak) / 0.010)
    fire_share = read(ctx, of="fire", match="^jit_ring_append_topn_kernel$")
    assert fire_share == pytest.approx(100 * (805_306_416 / peak) / 0.050)
    assert 0 < apply_share < 100 and 0 < fire_share < 100
    # nothing to read: no such program, no trace, no shapes, no gauge
    assert read(ctx, of="fire", match="^jit_fused_step_kernel$") is None
    assert read({**ctx, "trace": None}, of="apply", match=".") is None
    assert read({**ctx, "step_shapes": None}, of="apply", match=".") is None
    assert read({**ctx, "job_metrics": {}}, of="fire", match=".") is None


# -- the files -------------------------------------------------------------

def test_the_files_are_what_benchmark_json_names():
    bench = load(ROOT, "BENCHMARK.json")
    cfg = load(BENCH, "configs", CONFIG + ".json")
    (row,) = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert row["source"] == cfg["source"] and len(cfg["source"]) <= 200
    assert row["reduced"] == cfg["reduced"] == ["pool_batches"]
    assert set(cfg["reduced_why"]) == {"pool_batches"}
    assert "auction_id_wrap" not in cfg["params"]
    one = load(BENCH, "configs", "nexmark_q5.json")
    assert {k: v for k, v in one["params"].items()
            if k != "auction_id_wrap"} == cfg["params"]
    assert cfg["conf"] == one["conf"] and cfg["chips"] == 1
    assert cfg["conf_overrides"] == {"state.slots-per-shard": 131072}
    assert cfg["probe"]["module"] == "float_sum_large_keys"
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell == {"name": CELL, "config": CONFIG,
                    "traffic": "replay_suite", "chips": 1,
                    "why": cell["why"]}
    mix = load(BENCH, "traffic", "replay_suite.json")
    assert (mix["kind"], mix["events_per_ms"], mix["paced"]) == (
        "constant_rate", 9200, False)
    reports = {m["name"] for s in ("end_to_end", "per_layer")
               for m in bench[s]
               if "workloads" not in m or CELL in m["workloads"]}
    assert {"throughput_events_s", "setup_s", "state.hbm_bytes",
            "state.released_per_batch.replay", "state.reuse_share.replay",
            "state.live_keys_peak", "state.release_ms_per_batch.replay",
            "fire.device_ms_per_batch.replay",
            "clear.device_ms_per_batch.replay", "fire_roofline.large_keys",
            "apply_roofline.large_keys", "device.idle_share.replay",
            "hostkey.ms_per_batch.replay"} <= reports
    # not the share whose byte model charges two passes over the state,
    # nor the split scan's width (that scan does not run here)
    assert not {"step_roofline.replay",
                "hostkey.scan_ranges_per_batch.replay"} & reports
    for name in reports - {"throughput_events_s", "setup_s"}:
        assert os.path.isfile(os.path.join(
            BENCH, "layer_metrics", name + ".json")), name
    # the module's reference takes nothing from the program
    src = open(os.path.join(BENCH, "configs", CONFIG + ".py")).read()
    assert "flink_tpu" not in src.replace(
        "from flink_tpu.nexmark", "").split("def check")[1]
    assert large.warmup_event_ms(PARAMS) >= 14_000 + 2 * 2_000
    assert "state.slots_returned_early" in large.zero_counters(PARAMS)
    assert not any("device_chain" in k for k in large.zero_counters(PARAMS))


# -- the cell, end to end, at rehearsal size -------------------------------

def test_the_cells_rehearsal_purges_releases_and_reuses():
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", str(2**31 + 77), "--seconds", "3",
         "--trace", "0", "--rehearsal"],
        cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    out, detail = json.loads(lines[-1]), json.loads(lines[-2])
    assert out["correct"] is True and out["failed"] == 0
    assert out["rehearsal"] is True
    assert set(out["metrics"]) == {"throughput_events_s", "setup_s"}
    assert all(v["value"] is None for v in out["metrics"].values())
    cmp_ = detail["compare"]
    assert cmp_["rows_expected"] == cmp_["rows_got"] > 50
    assert all(v == 0 for v in detail["counters"].values()), detail["counters"]
    assert detail["probe"]["holds"] is True
    assert detail["probe"]["sum_rtol"] == 5e-6
    assert detail["compiled_in_window"]["programs"] == \
        detail["compiled_in_window"]["cache_hits"]
    phases = detail["phase_s"]
    # the purge's host side ran, and slots went back to the allocator
    assert phases["state.release"] > 0 and phases["state.release.n"] > 10
    assert phases["state.reclaim"] > 0 and phases["state.reclaim.n"] > 0
