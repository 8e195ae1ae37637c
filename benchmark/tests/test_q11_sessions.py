"""The configuration ``nexmark_q11_sessions`` and its cell
``q11_sessions_paced``: the files as ``BENCHMARK.json`` names them, the
plain reference against a brute-force per-key loop and against its
controls, the byte models' arithmetic, the module's refusal of a program
without device session state, and the cell's rehearsal end to end."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import session_step_bytes as sbytes
from benchmark.configs import nexmark_q11_sessions as q11
from benchmark.readers import trace_roofline_sessions
from benchmark.traffic_kinds.constant_rate import Schedule

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL = "q11_sessions_paced"
CONFIG = "nexmark_q11_sessions"


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


CFG = load(BENCH, "configs", CONFIG + ".json")
PARAMS = CFG["params"]


# -- the files -------------------------------------------------------------

def test_the_files_are_what_benchmark_json_names():
    bench = load(ROOT, "BENCHMARK.json")
    (row,) = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert row["source"] == CFG["source"] and len(CFG["source"]) <= 200
    assert row["reduced"] == CFG["reduced"] == ["pool_batches"]
    assert set(CFG["reduced_why"]) == {"pool_batches"}
    assert row["file"] == f"benchmark/configs/{CONFIG}.json"
    large = load(BENCH, "configs", "nexmark_q5_large_keys.json")
    # the generator's defaults as the accepted configuration carries them
    for k, v in PARAMS.items():
        if k in large["params"] and k != "out_of_orderness_ms":
            assert v == large["params"][k], k
    assert (PARAMS["gap_ms"], PARAMS["out_of_orderness_ms"]) == (10000, 4000)
    assert CFG["conf"] == large["conf"] and CFG["chips"] == 1
    conf = open(os.path.join(ROOT, "confs", CFG["conf"])).read()
    assert "state.num-key-shards: 128" in conf
    assert PARAMS["state_slots"] == 128 * CFG["conf_overrides"][
        "state.slots-per-shard"] == 4_194_304
    assert "probe" not in CFG
    assert bench["workloads"][-1] == {
        "name": CELL, "config": CONFIG, "traffic": "paced_suite",
        "chips": 1, "why": bench["workloads"][-1]["why"]}
    assert len(bench["workloads"][-1]["why"]) <= 200
    mix = load(BENCH, "traffic", "paced_suite.json")
    assert (mix["kind"], mix["events_per_ms"], mix["paced"]) == (
        "constant_rate", 9200, True)
    reports = {m["name"]: m for s in ("end_to_end", "per_layer")
               for m in bench[s]
               if "workloads" not in m or CELL in m["workloads"]}
    new = {"session.apply_device_ms_per_batch.paced",
           "session.fire_device_ms_per_batch.paced",
           "session_apply_roofline.q11", "session_fire_roofline.q11",
           "session.fired_rows_per_batch.paced", "session.live_peak",
           "session.fire_passes_per_advance.paced"}
    assert new | {"event_latency_p50_ms", "setup_s", "state.hbm_bytes",
                  "hostkey.ms_per_batch.paced",
                  "state.release_ms_per_batch.paced",
                  "hostkey.table_grow_ms.paced",
                  "latency.fetch_wait_ms.paced",
                  "driver.dispatch_ms_per_batch.paced"} <= set(reports)
    for name in new:
        m = reports[name]
        assert m["workloads"] == [CELL] and m["layer"] == "session operator"
        assert m["moves"] == "event_latency_p50_ms"
    # not the pane ring's programs, nor a checkpoint's
    assert not [n for n in reports if n.startswith(
        ("checkpoint.", "fire.", "apply.", "step_roofline"))]
    for name in set(reports) - {"event_latency_p50_ms", "setup_s",
                                "throughput_events_s"}:
        assert os.path.isfile(os.path.join(
            BENCH, "layer_metrics", name + ".json")), name
    # the module's reference takes nothing from the program
    src = open(os.path.join(BENCH, "configs", CONFIG + ".py")).read()
    assert "flink_tpu" not in src.split("# -- the plain reference")[1]
    assert q11.WINDOW_END_FIELD == "endtime"
    assert q11.fire_delay_ms(PARAMS) == 4000
    assert q11.warmup_event_ms(PARAMS) > 14_000
    assert q11.zero_counters(PARAMS) == (
        "records_dropped_full", "late_records", "state.slots_returned_early",
        "session.on_registry")


def test_the_stream_is_what_the_configuration_reckons():
    """22,795 persons new a 2^20-bid batch, every one a key; three
    quarters of a batch's bids on the few hot bidders of its moments."""
    n = 1 << 20
    pool = q11.make_pool(2**31 + 3, n, PARAMS)
    a, b = pool[40]["bidder"], pool[41]["bidder"]
    keys_a, keys_b = np.unique(a), np.unique(b)
    fresh = np.setdiff1d(keys_b, keys_a)
    assert abs(len(fresh) - n // 46) < 60
    assert abs(len(keys_b) - q11.keys_per_batch(PARAMS, n)) < 300
    counts = np.sort(np.bincount(b - b.min()))[::-1]
    assert 0.74 < counts[:240].sum() / n < 0.77
    shapes = q11.step_shapes(PARAMS, n, 9200)
    assert shapes == {"records": n, "keys": 23_805, "slots": 4_194_304,
                      "lanes": 2}


# -- the plain reference and its controls ----------------------------------

def brute_force(batches, gap):
    """Sessions by a per-key loop over every event in time order."""
    by_key = {}
    for data, ts in batches:
        for k, t in zip(data["bidder"].tolist(), ts.tolist()):
            by_key.setdefault(k, []).append(t)
    rows = []
    for k, ts in by_key.items():
        ts.sort()
        start, last, n = ts[0], ts[0], 0
        for t in ts:
            if t - last > gap:
                rows.append((k, n, start, last + gap))
                start, n = t, 0
            last, n = t, n + 1
        rows.append((k, n, start, last + gap))
    return sorted(rows)


def short_stream(seed, n_batches=9, n=600, gap=50):
    """Batches whose keys recur across and within batches, with gaps
    inside a batch, sessions that span batches and sessions that a
    later batch bridges (timestamps ascend over the stream only on the
    whole: a batch scatters over three spans)."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n_batches):
        k = rng.integers(0, 40, n).astype(np.int64)
        t = (i * 60 + rng.integers(0, 180, n)).astype(np.int64)
        quiet = (k + i) % 4 == 0
        out.append(({"bidder": k[~quiet]}, t[~quiet]))
    return out, gap


def sink_of(rows, cut=4):
    """Committed rows as sink batches of the job's fields."""
    cols = [np.asarray(c, np.int64) for c in zip(*rows)]
    return [dict(zip(q11.ROW_FIELDS, (c[i::cut] for c in cols)))
            for i in range(cut)]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_reference_equals_a_brute_force_per_key_loop(seed):
    batches, gap = short_stream(seed)
    want = brute_force(batches, gap)
    got = sorted(zip(*(c.tolist() for c in q11.sessions(batches, gap))))
    assert got == want
    # more than one session a key, sessions across batches
    keys = [r[0] for r in want]
    assert len(keys) > len(set(keys))
    assert any(r[3] - gap - r[2] > 180 for r in want)


def test_check_passes_its_own_rows_and_reads_each_control():
    batches, gap = short_stream(5)
    p = {**PARAMS, "gap_ms": gap}
    rows = brute_force(batches, gap)
    max_ts = max(int(ts.max()) for _, ts in batches)
    ok = q11.check(iter(batches), max_ts, sink_of(rows), p)
    assert (ok["rows_expected"], ok["rows_got"]) == (len(rows), len(rows))
    assert ok["rows_missing"] == ok["rows_not_in_reference"] == 0
    assert ok["rows_duplicated"] == ok["events_without_result"] == 0
    # one batch dropped by the job: the rows it committed are not the
    # reference's, which saw every batch
    short = brute_force(batches[:4] + batches[5:], gap)
    got = q11.check(iter(batches), max_ts, sink_of(short), p)
    assert got["rows_missing"] > 0 and got["rows_not_in_reference"] > 0
    assert got["events_without_result"] > 0
    # one count altered in the sink adapter
    bad = [list(r) for r in rows]
    bad[7][1] += 1
    got = q11.check(iter(batches), max_ts, sink_of(bad), p)
    assert (got["rows_missing"], got["rows_not_in_reference"]) == (1, 1)
    # one session split in two
    i = max(range(len(rows)), key=lambda j: rows[j][1])
    k, c, s, e = rows[i]
    mid = (s + e - gap) // 2
    split = rows[:i] + [(k, c - 1, s, mid + gap), (k, 1, mid + 1, e)] \
        + rows[i + 1:]
    got = q11.check(iter(batches), max_ts, sink_of(split), p)
    assert got["rows_missing"] == 1 and got["rows_not_in_reference"] == 2
    # one row committed twice
    got = q11.check(iter(batches), max_ts, sink_of(rows + rows[:1]), p)
    assert got["rows_duplicated"] == 1 and got["rows_missing"] == 0
    # nothing committed
    got = q11.check(iter(batches), max_ts, [], p)
    assert got["rows_missing"] == len(rows) and got["rows_got"] == 0


# -- the byte models and their reader --------------------------------------

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


def test_the_byte_models_arithmetic_and_their_reader():
    shapes = q11.step_shapes(PARAMS, 1 << 20, 9200)
    assert sbytes.apply_bytes(**shapes) == (1 << 20) * 8 + 23_805 * 64
    assert sbytes.fire_bytes(**shapes) == 4_194_304 * 2 * 8 + 23_805 * 16
    peak = 819e9
    ctx = {"trace": _Trace({"jit_session_apply_kernel": (26, 26 * 0.012),
                            "jit_session_fire_kernel": (26, 26 * 0.008)}),
           "trace_batches": 26, "chips": 1, "device_kind": "TPU v5 lite",
           "step_shapes": shapes, "job_metrics": {}}
    read = trace_roofline_sessions.read
    a = read(ctx, of="apply", match="^jit_session_apply_kernel$")
    f = read(ctx, of="fire", match="^jit_session_fire_kernel$")
    assert a == pytest.approx(100 * (sbytes.apply_bytes(**shapes) / peak)
                              / 0.012)
    assert f == pytest.approx(100 * (sbytes.fire_bytes(**shapes) / peak)
                              / 0.008)
    assert 0 < a < 100 and 0 < f < 100
    # nothing to read: no such program (the parent's), no trace, or the
    # shapes of another configuration
    assert read(ctx, of="fire", match="^jit_fused_step_kernel$") is None
    assert read({**ctx, "trace": None}, of="apply", match=".") is None
    assert read({**ctx, "step_shapes": {"records": 1 << 20}},
                of="apply", match=".") is None
    assert read({**ctx, "step_shapes": None}, of="fire", match=".") is None


# -- the refusal ---------------------------------------------------------------

def test_a_program_without_device_session_state_is_refused(monkeypatch):
    assert q11.device_sessions()
    monkeypatch.setitem(sys.modules, "flink_tpu.ops.session_device", None)
    assert not q11.device_sessions()
    with pytest.raises(NotImplementedError, match="does not support"):
        q11.make_pool(7, 64, PARAMS)


# -- the cell, end to end, at rehearsal size -------------------------------

def test_the_cells_rehearsal_fires_releases_and_reuses():
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", str(2**31 + 41), "--seconds", "6",
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
    assert cmp_["rows_expected"] == cmp_["rows_got"] > 1000
    assert all(v == 0 for v in detail["counters"].values())
    assert detail["latency"]["samples"] > 100
    assert detail["generator"]["paced"] is True
    phases = detail["phase_s"]
    for leaf in ("window.key_scan", "window.fire_dispatch", "state.release",
                 "state.reclaim", "drain.fetch", "drain.deliver"):
        assert phases[leaf] > 0, leaf
    # the schedule is the suite's density cut to the rehearsal's
    sched = Schedule({"events_per_ms": 16})
    assert int(sched.batch_ts(1, 8192)[0]) == 512
