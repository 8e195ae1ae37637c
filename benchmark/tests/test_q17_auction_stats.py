"""The configuration ``nexmark_q17_auction_stats`` and its cell
``q17_upserts_paced``: the files as ``BENCHMARK.json`` names them, the
plain reference against a per-record loop and against its controls (the
float32 lanes among them), the byte model's arithmetic, the module's
refusal of a program without the device operator, and the cell's
rehearsal end to end."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import upsert_step_bytes as ubytes
from benchmark.configs import nexmark_q17_auction_stats as q17
from benchmark.readers import trace_roofline_upserts

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL = "q17_upserts_paced"
CONFIG = "nexmark_q17_auction_stats"
DAY = 86_400_000


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


CFG = load(BENCH, "configs", CONFIG + ".json")
PARAMS = CFG["params"]
BANDS = (PARAMS["rank1_below"], PARAMS["rank3_from"])


# -- the files -------------------------------------------------------------

def test_the_files_are_what_benchmark_json_names():
    bench = load(ROOT, "BENCHMARK.json")
    (row,) = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert row["source"] == CFG["source"] and len(CFG["source"]) <= 200
    assert "q17.sql" in CFG["source"]
    assert row["reduced"] == CFG["reduced"] == ["pool_batches"]
    assert set(CFG["reduced_why"]) == {"pool_batches"}
    assert row["file"] == f"benchmark/configs/{CONFIG}.json"
    large = load(BENCH, "configs", "nexmark_q5_large_keys.json")
    # the generator's defaults as the accepted configuration carries them
    shared = [k for k in PARAMS if k in large["params"]]
    assert len(shared) == 8
    for k in shared:
        assert PARAMS[k] == large["params"][k], k
    assert CFG["reduced_why"] == large["reduced_why"]
    assert BANDS == (10_000, 1_000_000)
    assert CFG["conf"] == large["conf"] and CFG["chips"] == 1
    conf = open(os.path.join(ROOT, "confs", CFG["conf"])).read()
    assert "state.num-key-shards: 128" in conf
    assert PARAMS["state_slots"] == 128 * CFG["conf_overrides"][
        "state.slots-per-shard"] == 33_554_432
    assert "probe" not in CFG
    assert {"last_bid_ms", "slots", "day", "mini_batch", "sink",
            "bids_only"} <= set(CFG["assumed"])
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell == {"name": CELL, "config": CONFIG, "traffic": "paced_suite",
                    "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200
    reports = {m["name"]: m for s in ("end_to_end", "per_layer")
               for m in bench[s]
               if "workloads" not in m or CELL in m["workloads"]}
    new = {"groupagg.apply_device_ms_per_batch.paced",
           "groupagg.rows_per_batch.paced",
           "groupagg.keys_new_per_batch.paced", "groupagg.live_keys_peak",
           "drain.deliver_ms_per_batch.paced", "groupagg_apply_roofline.q17"}
    assert new | {"event_latency_p50_ms", "setup_s", "state.hbm_bytes",
                  "hostkey.ms_per_batch.paced", "hostkey.table_grow_ms.paced",
                  "latency.fetch_wait_ms.paced",
                  "driver.dispatch_ms_per_batch.paced"} <= set(reports)
    for name in new:
        m = reports[name]
        assert m["workloads"] == [CELL]
        assert m["layer"] == ("emit ring and drain" if name.startswith(
            "drain.") else "unwindowed aggregation")
        assert m["moves"] == "event_latency_p50_ms"
    # new entries stand at the end of their lists
    assert [m["name"] for m in bench["per_layer"][-6:]] == [
        "groupagg.apply_device_ms_per_batch.paced",
        "groupagg.rows_per_batch.paced", "groupagg.keys_new_per_batch.paced",
        "groupagg.live_keys_peak", "drain.deliver_ms_per_batch.paced",
        "groupagg_apply_roofline.q17"]
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["configs"][-1]["name"] == CONFIG
    # not the pane ring's programs, a session's, a release or a
    # checkpoint's; nor the fetch per distinct end (PERF.md section 7 (i))
    assert not [n for n in reports if n.startswith(
        ("checkpoint.", "fire.", "apply.", "session", "step_roofline",
         "state.release", "drain.fetch_ms_per_fire"))]
    for name in set(reports) - {"event_latency_p50_ms", "setup_s",
                                "throughput_events_s"}:
        assert os.path.isfile(os.path.join(
            BENCH, "layer_metrics", name + ".json")), name
    # the module's reference takes nothing from the program
    src = open(os.path.join(BENCH, "configs", CONFIG + ".py")).read()
    assert "flink_tpu" not in src.split("# -- the plain reference")[1]
    assert q17.WINDOW_END_FIELD == "last_bid_ms"
    assert q17.fire_delay_ms(PARAMS) == 0
    assert q17.zero_counters(PARAMS) == (
        "records_dropped_full", "late_records", "groupagg.on_host",
        "groupagg.lane_overflow")


def test_the_stream_is_what_the_configuration_reckons():
    """~68,400 auctions new a 2^20-bid batch, ~68,500 named; a hot
    key's sum passes 2^31, most keys' max passes 2^24, 13 % of the bids
    lie past float32's integers."""
    n = 1 << 20
    pool = q17.make_pool(2**31 + 5, n, PARAMS)
    a, b = pool[40], pool[41]
    keys_a, keys_b = np.unique(a["auction"]), np.unique(b["auction"])
    fresh = np.setdiff1d(keys_b, keys_a)
    assert abs(len(fresh) - n * 3 // 46) < 120
    assert abs(len(keys_b) - q17.keys_per_batch(PARAMS, n)) < 120
    assert q17.keys_per_batch(PARAMS, n) == 68_496
    assert q17.step_shapes(PARAMS, n, 9200) == {
        "records": n, "keys": 68_496, "slots": 33_554_432}
    ts = np.arange(n, dtype=np.int64) // 9200
    key, bids, _, _, _, _, mx, sm, _ = q17.batch_partials(
        b["auction"], b["price"], ts, BANDS)
    assert 600 < (sm > 2**31).sum() < 760          # ~680 keys a batch
    assert 0.6 < (mx > 2**24).mean() < 0.9
    assert 0.12 < (b["price"] >= 2**24).mean() < 0.14
    assert 14 < bids.mean() < 16.5
    assert int(b["price"].max()) <= 10**8 < 2**31


# -- the plain reference and its controls ----------------------------------

def per_record_loop(batches):
    """Q17's rows by a per-record Python loop: after each batch one row
    per key it touched, over every bid of the key so far."""
    acc, rows = {}, []
    for data, ts in batches:
        touched = {}
        for a, p, t in zip(data["auction"].tolist(),
                           data["price"].tolist(), ts.tolist()):
            k = ((t // DAY) << 40) | a
            s = acc.setdefault(k, [0, 0, 0, 0, p, p, 0, t])
            s[0] += 1
            s[1 + (p >= BANDS[0]) + (p >= BANDS[1])] += 1
            s[4], s[5] = min(s[4], p), max(s[5], p)
            s[6] += p
            s[7] = max(s[7], t)
            touched[k] = True
        rows += [(k, *acc[k][:6], acc[k][6] // acc[k][0], acc[k][6],
                  acc[k][7]) for k in touched]
    return sorted(rows)


def short_stream(seed, n_batches=8, n=500):
    """Batches whose keys recur across batches, a hot key whose sum
    passes 2^31, prices at the bands' edges, a day boundary inside
    batch 3, and timestamps out of order within a batch."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n_batches):
        a = 1000 + rng.integers(0, 25, n) + 10 * i
        a[:40] = 1007
        p = np.rint(10.0 ** (rng.random(n) * 6.0) * 100.0)
        p[:40] = 95_000_000
        p[40:46] = [9_999, 10_000, 999_999, 1_000_000, 2**24, 2**24 + 1]
        t = 3 * DAY - 1200 + i * 400 + rng.integers(0, 400, n)
        out.append(({"auction": a.astype(np.int64),
                     "price": p.astype(np.int64)}, t.astype(np.int64)))
    return out


def sink_of(rows, cut=4):
    """Committed rows as sink batches of the job's fields."""
    cols = [np.asarray(c, np.int64) for c in zip(*rows)]
    cols = [cols[0] & ((1 << 40) - 1), cols[0] >> 40] + cols[1:]
    return [dict(zip(q17.ROW_FIELDS, (c[i::cut] for c in cols)))
            for i in range(cut)]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_reference_equals_a_per_record_loop(seed):
    batches = short_stream(seed)
    want = per_record_loop(batches)
    exp, bids = q17.upserts(iter(batches), PARAMS)
    assert sorted(zip(*(c.tolist() for c in exp))) == want
    assert all(c.dtype == np.int64 for c in exp)
    assert int(bids.sum()) == sum(len(ts) for _, ts in batches)
    # keys in several batches, two days, a sum past one word
    keys = [r[0] for r in want]
    assert len(keys) > len(set(keys))
    assert {k >> 40 for k in keys} == {2, 3}
    assert max(r[8] for r in want) > 2**31


def test_check_passes_its_own_rows_and_reads_each_control():
    batches = short_stream(5)
    rows = per_record_loop(batches)
    max_ts = max(int(ts.max()) for _, ts in batches)
    ok = q17.check(iter(batches), max_ts, sink_of(rows), PARAMS)
    assert (ok["rows_expected"], ok["rows_got"]) == (len(rows), len(rows))
    assert ok["rows_missing"] == ok["rows_not_in_reference"] == 0
    assert ok["rows_duplicated"] == ok["events_without_result"] == 0
    assert ok["keys_without_final_row"] == 0
    assert ok["keys_expected"] == len({r[0] for r in rows})
    # one batch dropped by the job: every later row of its keys is
    # short of the reference's, which saw every batch
    short = per_record_loop(batches[:4] + batches[5:])
    got = q17.check(iter(batches), max_ts, sink_of(short), PARAMS)
    assert got["rows_missing"] > 0 and got["rows_not_in_reference"] > 0
    assert got["events_without_result"] > 0
    assert got["keys_without_final_row"] > 0
    # one sum altered in the sink adapter
    bad = [list(r) for r in rows]
    bad[7][8] += 1
    got = q17.check(iter(batches), max_ts, sink_of(bad), PARAMS)
    assert (got["rows_missing"], got["rows_not_in_reference"]) == (1, 1)
    # one row committed twice
    got = q17.check(iter(batches), max_ts, sink_of(rows + rows[:1]), PARAMS)
    assert got["rows_duplicated"] == 1 and got["rows_missing"] == 0
    assert got["rows_not_in_reference"] == 0
    # a row of a key the stream never named
    got = q17.check(iter(batches), max_ts,
                    sink_of(rows + [(5, 1, 1, 0, 0, 7, 7, 7, 7, 9)]), PARAMS)
    assert got["rows_not_in_reference"] == 1 and got["rows_missing"] == 0
    # nothing committed
    got = q17.check(iter(batches), max_ts, [], PARAMS)
    assert got["rows_missing"] == len(rows) and got["rows_got"] == 0
    assert got["events_without_result"] == sum(len(t) for _, t in batches)


def test_float32_lanes_fail_the_comparison():
    """The control of the precision: the same job with the lanes the
    program had before this configuration (float32 sum, max and min)
    commits rows the reference does not have."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from flink_tpu.ops import aggregates as A
    from flink_tpu.ops.groupagg_device import DeviceGroupAggOperator

    batches = short_stream(9)
    exact = per_record_loop(batches)
    floats = A.multi(A.count(), A.sum_of("price"), A.max_of("price"),
                     A.min_of("price"))
    op = DeviceGroupAggOperator(floats, num_shards=8, slots_per_shard=64)
    rows = []
    by_id = {(r[0], r[1]): r for r in exact}
    for data, ts in batches:
        key = ((ts // DAY) << 40) | data["auction"]
        op.process_batch(key, ts, {"price": data["price"]})
        out = dict(op.take_fired())
        for k, c, s, mx, mn in zip(
                out["key"].tolist(), out["count"].tolist(),
                out["sum_price"].tolist(), out["max_price"].tolist(),
                out["min_price"].tolist()):
            ref = by_id[(k, c)]     # the counts are exact either way
            rows.append((k, c, *ref[2:5], int(mn), int(mx),
                         int(s) // c, int(s), ref[9]))
    max_ts = max(int(ts.max()) for _, ts in batches)
    got = q17.check(iter(batches), max_ts, sink_of(rows), PARAMS)
    assert got["rows_got"] == got["rows_expected"]
    assert got["rows_not_in_reference"] > 0.3 * len(rows)
    assert got["rows_missing"] == got["rows_not_in_reference"]


# -- the byte model and its reader -------------------------------------------

class _Dev:
    def __init__(self, table):
        self.table = table

    def seconds(self, line, match):
        import re
        hits = [v for k, v in self.table.items() if re.search(match, k)]
        return sum(c for c, _ in hits), sum(s for _, s in hits)


class _Trace:
    def __init__(self, table):
        self.dev = _Dev(table)

    def busiest(self):
        return self.dev


def test_the_byte_models_arithmetic_and_its_reader():
    shapes = q17.step_shapes(PARAMS, 1 << 20, 9200)
    # 12 bytes a record up; 64 read + 64 written + a 40-byte row a key
    assert ubytes.apply_bytes(**shapes) == (1 << 20) * 12 + 68_496 * 168 \
        == 24_090_240
    peak = 819e9
    ctx = {"trace": _Trace({"jit_groupagg_apply_kernel": (26, 26 * 0.015)}),
           "trace_batches": 26, "chips": 1, "device_kind": "TPU v5 lite",
           "step_shapes": shapes, "job_metrics": {}}
    read = trace_roofline_upserts.read
    a = read(ctx, match="^jit_groupagg_apply_kernel$")
    assert a == pytest.approx(100 * (24_090_240 / peak) / 0.015)
    assert 0 < a < 100
    # nothing to read: no such program (the parent's), no trace, no
    # batch in the traced span, or the shapes of another configuration
    assert read(ctx, match="^jit_session_apply_kernel$") is None
    assert read({**ctx, "trace": None}, match=".") is None
    assert read({**ctx, "trace_batches": 0}, match=".") is None
    assert read({**ctx, "step_shapes": {"records": 1 << 20}},
                match=".") is None
    assert read({**ctx, "step_shapes": None}, match=".") is None


# -- the refusal ---------------------------------------------------------------

def test_a_program_without_the_device_operator_is_refused(monkeypatch):
    assert q17.device_groupagg()
    monkeypatch.setitem(sys.modules, "flink_tpu.ops.groupagg_device", None)
    assert not q17.device_groupagg()
    with pytest.raises(NotImplementedError, match="does not support"):
        q17.make_pool(7, 64, PARAMS)


# -- the cell, end to end, at rehearsal size -------------------------------

def test_the_cells_rehearsal_upserts_every_batch():
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", str(2**31 + 43), "--seconds", "6",
         "--trace", "0", "--rehearsal"],
        cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    out, detail = json.loads(lines[-1]), json.loads(lines[-2])
    assert out["correct"] is True and out["failed"] == 0
    assert out["rehearsal"] is True
    assert set(out["metrics"]) == {"event_latency_p50_ms", "setup_s"}
    assert all(v["value"] is None for v in out["metrics"].values())
    cmp_ = detail["compare"]
    assert cmp_["rows_expected"] == cmp_["rows_got"] > 5000
    assert cmp_["keys_without_final_row"] == 0
    assert all(v == 0 for v in detail["counters"].values())
    assert set(detail["counters"]) == {
        "records_dropped_full", "late_records", "groupagg.on_host",
        "groupagg.lane_overflow", "records_in_minus_offered"}
    assert detail["latency"]["samples"] > 1000
    assert detail["generator"]["paced"] is True
    phases = detail["phase_s"]
    for leaf in ("window.key_scan", "window.pack", "window.h2d",
                 "window.step_dispatch", "drain.fetch", "drain.deliver"):
        assert phases[leaf] > 0, leaf
