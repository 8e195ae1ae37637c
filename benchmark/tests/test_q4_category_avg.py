"""The configuration ``nexmark_q4_category_avg`` and its cell
``q4_join_paced``: the files as ``BENCHMARK.json`` names them (found by
name), the generator against ``AuctionGenerator``'s and
``BidGenerator``'s formulas written out, the reckoned counts read off a
pool, the plain reference against a per-record loop over two
dictionaries and against its seven controls, the byte model's
arithmetic, the module's refusal of a program without the device join,
and the cell's rehearsal end to end."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import join_step_bytes as jbytes
from benchmark.configs import nexmark_q4_category_avg as q4
from benchmark.readers import trace_roofline_join

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL = "q4_join_paced"
CONFIG = "nexmark_q4_category_avg"
MIX = "paced_suite_auctions_bids"


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


CFG = load(BENCH, "configs", CONFIG + ".json")
PARAMS = CFG["params"]
SMALL = {**PARAMS, **CFG["rehearsal"]["params"]}    # 100 events per ms


def stream_of(seed, batches, n, p):
    pool = q4.EventPool(seed, n, p)
    rate = q4.offered_per_ms(p)
    return [(pool[i], (i * n + np.arange(n, dtype=np.int64)) // rate)
            for i in range(batches)]


# -- the files -------------------------------------------------------------

def test_the_files_are_what_benchmark_json_names():
    bench = load(ROOT, "BENCHMARK.json")
    (row,) = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert row["source"] == CFG["source"] and len(CFG["source"]) <= 200
    assert "queries/q4.sql" in CFG["source"]
    assert row["reduced"] == CFG["reduced"] == ["pool_batches"]
    assert set(CFG["reduced_why"]) == {"pool_batches"}
    assert row["file"] == f"benchmark/configs/{CONFIG}.json"
    large = load(BENCH, "configs", "nexmark_q5_large_keys.json")
    # the generator's defaults as the accepted configurations carry them
    shared = [k for k in PARAMS if k in large["params"]]
    assert len(shared) == 8
    for k in shared:
        assert PARAMS[k] == large["params"][k], k
    assert CFG["conf"] == large["conf"] and CFG["chips"] == 1
    assert PARAMS["state_slots"] == 128 * CFG["conf_overrides"][
        "state.slots-per-shard"] == 33_554_432
    assert "probe" not in CFG
    for key in ("two_views", "columns", "auction_generator", "mini_batch",
                "avg", "sum_final_auctions_last_event_ms",
                "early_bid_lanes", "slots", "sink"):
        assert key in CFG["assumed"], key
    assert "FROM MEMORY" in CFG["assumed"]["auction_generator"]
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell == {"name": CELL, "config": CONFIG, "traffic": MIX,
                    "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200 and "fill" in cell["why"]
    mix = load(BENCH, "traffic", MIX + ".json")
    assert mix["kind"] == "constant_rate" and mix["paced"] is True
    assert mix["events_per_ms"] == 9800 == q4.offered_per_ms(PARAMS)
    assert mix["rehearsal"]["events_per_ms"] == q4.offered_per_ms(SMALL)
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert CELL in e2e["event_latency_p50_ms"]["workloads"]
    assert CELL not in e2e["throughput_events_s"]["workloads"]
    mine = {m["name"]: m for m in bench["per_layer"]
            if CELL in m.get("workloads", ())}
    own = {n for n, m in mine.items() if m["workloads"] == [CELL]}
    assert own == {"join.apply_device_ms_per_batch.paced",
                   "join_apply_roofline.q4",
                   "join.keys_changed_per_batch.paced",
                   "join.bids_parked_per_batch.paced", "join.live_keys_peak"}
    for name in own:
        assert mine[name]["layer"] == "unbounded join"
        assert mine[name]["moves"] == "event_latency_p50_ms"
    for name in ("device.idle_share.paced", "state.hbm_bytes",
                 "driver.dispatch_ms_per_batch.paced",
                 "hostkey.ms_per_batch.paced", "hostkey.table_grow_ms.paced",
                 "drain.deliver_ms_per_batch.paced", "latency.max_ms.paced",
                 "latency.fetch_wait_ms.paced", "latency.push_wait_ms.paced",
                 "host.longest_phase_ms.paced"):
        assert name in mine, name
    assert "drain.fetch_ms_per_fire.paced" not in mine
    for name in mine:
        spec = load(BENCH, "layer_metrics", name + ".json")
        assert os.path.exists(os.path.join(
            BENCH, "readers", spec["reader"] + ".py")), name


# -- the stream ------------------------------------------------------------

@pytest.mark.parametrize("p", [PARAMS, SMALL], ids=["suite", "rehearsal"])
def test_the_pool_gives_the_generators_formulas(p):
    n = 1 << 13
    pool = q4.EventPool(5, n, p)
    for i in (0, 1, 2, 9, 1000, 123_457):
        got, want = pool[i], q4.suite_events(5, i, n, p)
        assert set(got) == set(want) == set(q4.SCHEMA)
        for k in want:
            assert np.array_equal(got[k], want[k]), (i, k)
        assert pool[i]["bid_price"] is not got["bid_price"]


def test_an_event_is_what_the_generators_would_make_of_its_draws():
    """``suite_events`` against the formulas, an event at a time."""
    n, p, i = 4096, PARAMS, 77
    from benchmark.configs.nexmark_q5_large_keys import _Draws

    ev = q4.suite_events(3, i, n, p)
    d = _Draws(3, i % p["pool_batches"], n, p)
    ts = (i * n + np.arange(n)) // q4.offered_per_ms(p)
    for j in list(range(0, n, 97)) + [n - 1]:
        e = i * n + j
        epoch, r = divmod(e, 49)
        number = epoch * 50 + 1 + r             # person 0, auctions 1-3
        assert number // 10_000 == ts[j]        # the schedule's timestamp
        if r < 3:
            assert ev["event_type"][j] == 1
            assert ev["auction_id"][j] == 1000 + epoch * 3 + r
            assert ev["auction_category"][j] == 10 + int(d.u_bidder[j] * 5)
            horizon = (number + 100 * 50 // 3) // 10_000 - number // 10_000
            assert horizon in (0, 1)
            assert ev["auction_expires"][j] == ts[j] + 1 + int(
                d.u_auction[j] * max(2 * horizon, 1))
            assert ev["bid_auction"][j] == ev["bid_price"][j] == 0
        else:
            newest = epoch * 3 + 2
            cold = newest - 100 + int(d.u_auction[j] * 111)
            assert ev["event_type"][j] == 2
            assert ev["bid_auction"][j] == 1000 + (
                newest // 100 * 100 if d.hot_auction[j] else cold)
            assert ev["bid_price"][j] == d.price[j]
            assert ev["auction_id"][j] == ev["auction_expires"][j] == 0


def test_the_stream_is_what_the_configuration_reckons():
    """The counts ``assumed.rates`` reckons from the formulas, read off
    a pool at the suite's density (a 2^20-event batch well into the
    stream, and the one behind it for what crosses the edge)."""
    n = 1 << 20
    pool = q4.EventPool(9, n, PARAMS)
    rate = q4.offered_per_ms(PARAMS)
    i = 300
    a, b = pool[i], pool[i + 1]
    ts = (i * n + np.arange(2 * n, dtype=np.int64)) // rate
    assert ts[n - 1] - ts[0] in (106, 107)          # 106.998 ms a batch
    kind = np.concatenate([a["event_type"], b["event_type"]])
    auc = kind == 1
    assert int(auc[:n].sum()) in (64_197, 64_198, 64_199)
    ids = np.concatenate([a["auction_id"], b["auction_id"]])[auc]
    assert np.array_equal(ids, np.arange(ids[0], ids[0] + len(ids)))
    born = np.full(int(ids[-1]) + 64, np.iinfo(np.int64).max)
    born[ids] = np.flatnonzero(auc)                 # position in the stream
    t0 = np.zeros(len(born), np.int64)
    t0[ids] = ts[auc]
    exp = np.concatenate([a["auction_expires"], b["auction_expires"]])
    t1 = np.zeros(len(born), np.int64)
    t1[ids] = exp[auc]
    life = exp[auc] - ts[auc]
    assert set(np.unique(life).tolist()) == {1, 2}  # horizon 0 or 1 ms
    # the first batch's bids, each against its auction
    bid = np.flatnonzero(a["event_type"] == 2)
    to = a["bid_auction"][bid]
    known = to >= ids[0]        # the ~111 in flight at the edge lie behind
    early = known & (born[to] > bid)
    assert 43_000 < early.sum() < 45_500            # ~44,300 reckoned
    assert early.sum() / (bid.size / 2) == pytest.approx(10 / 111, rel=0.03)
    # it follows within 4 epochs
    assert (born[to[early]] - bid[early]).max() <= 4 * 49
    before = early & (ts[bid] < t0[to])
    assert 300 < before.sum() < 700                 # hundreds: refused
    assert (ts[bid][before] == t0[to][before] - 1).all()
    # every other bid of a known auction lies within its life
    inside = known & ~before
    assert (ts[bid][inside] >= t0[to][inside]).all()
    late = inside & (ts[bid] > t1[to])
    assert late.sum() == 0
    # keys a batch names, and those whose final changes in it
    named = np.unique(np.concatenate([a["auction_id"][a["event_type"] == 1],
                                      to]))
    assert abs(len(named) - q4.keys_per_batch(PARAMS, n)) < 40
    shapes = q4.step_shapes(PARAMS, n, rate)
    assert shapes == {"records": n, "keys": 64_309, "changed": 64_299,
                      "slots": 33_554_432}
    # an early bid lies in its auction's millisecond or the one before:
    # two lanes a slot suffice
    assert (t0[to[early]] - ts[bid][early]).max() == 1
    # most keys' final is past float32's integers
    best = np.zeros(len(born), np.int64)
    np.maximum.at(best, to[inside], a["bid_price"][bid][inside])
    assert (best[best > 0] > 1 << 24).mean() > 0.6


def test_no_two_rows_share_category_auctions_and_last_event_ms():
    stream = stream_of(4, 40, 8192, SMALL)
    rows = q4.category_rows(iter(stream))
    assert len(rows) == 40 * 5
    assert len({(r[0], r[3], r[4]) for r in rows}) == len(rows)
    # a category's sum passes 2^31 within its first batch
    assert all(r[2] > 1 << 31 for r in rows[:5])


# -- the reference -----------------------------------------------------------

def loop_rows(stream):
    """Q4 by a per-record loop over two dictionaries."""
    auctions, bids, final, totals, rows = {}, {}, {}, {}, []
    for data, ts in stream:
        newest = {}
        for j in range(len(ts)):
            t = int(ts[j])
            if data["event_type"][j] == 1:
                key = int(data["auction_id"][j])
                auctions[key] = (int(data["auction_category"][j]), t,
                                 int(data["auction_expires"][j]))
            else:
                key = int(data["bid_auction"][j])
                bids.setdefault(key, []).append(
                    (t, int(data["bid_price"][j])))
            newest[key] = max(newest.get(key, t), t)
        changed = {}
        for key, last in newest.items():
            if key not in auctions:
                continue
            cat, lo, hi = auctions[key]
            ok = [v for t, v in bids.get(key, ()) if lo <= t <= hi]
            if ok and max(ok) != final.get(key):
                tot = totals.setdefault(cat, [0, 0])
                tot[0] += max(ok) - final.get(key, 0)
                tot[1] += key not in final
                final[key] = max(ok)
                changed[cat] = max(changed.get(cat, last), last)
        rows.extend((c, totals[c][0] // totals[c][1], *totals[c], last)
                    for c, last in sorted(changed.items()))
    return rows


@pytest.mark.parametrize("seed", [1, 2])
def test_the_reference_equals_a_per_record_loop(seed):
    stream = stream_of(seed, 6, 2048, SMALL)
    want = loop_rows(stream)
    got = [r[:5] for r in q4.category_rows(iter(stream))]
    assert len(want) == 30 and got == want
    # nor does it lean on the order within a batch
    r = np.random.default_rng(seed)
    mixed = []
    for data, ts in stream:
        o = r.permutation(len(ts))
        mixed.append(({k: v[o] for k, v in data.items()}, ts[o]))
    assert [r[:5] for r in q4.category_rows(iter(mixed))] == want


def sink_of(rows):
    return [{f: np.asarray([r[i] for r in rows[j:j + 5]])
             for i, f in enumerate(q4.ROW_FIELDS)}
            for j in range(0, len(rows), 5)]


CONTROLS = {
    "one_batch_dropped": lambda s: dict(stream=s[:3] + s[4:]),
    "one_final_altered": lambda s: dict(alter=True),
    "a_row_committed_twice": lambda s: dict(twice=True),
    "nothing_committed": lambda s: dict(nothing=True),
    "the_predicate_ignored": lambda s: dict(control={"predicate": False}),
    "early_bids_dropped": lambda s: dict(control={"keep_early": False}),
    "float32_lanes": lambda s: dict(control={"dtype": np.float32}),
}


@pytest.mark.parametrize("name", ["sound", *CONTROLS])
def test_check_passes_the_sound_rows_and_reads_each_control(name):
    stream = stream_of(6, 8, 4096, SMALL)
    how = CONTROLS[name](stream) if name != "sound" else {}
    rows = [r[:5] for r in q4.category_rows(
        iter(how.get("stream", stream)), **how.get("control", {}))]
    if how.get("alter"):
        c, avg, s, n, last = rows[17]
        rows[17] = (c, (s + 1) // n, s + 1, n, last)
    if how.get("twice"):
        rows = rows + rows[7:8]
    if how.get("nothing"):
        rows = []
    cmp_ = q4.check(iter(stream), int(stream[-1][1][-1]),
                    sink_of(rows) if not how.get("twice") else
                    sink_of(rows[:-1]) + sink_of(rows[-1:] * 5)[:1], PARAMS)
    assert cmp_["rows_expected"] == 40
    bad = (cmp_["rows_missing"], cmp_["rows_not_in_reference"],
           cmp_["rows_duplicated"])
    if name == "sound":
        assert bad == (0, 0, 0) and cmp_["events_without_result"] == 0
    elif name == "a_row_committed_twice":
        assert bad[0] == bad[1] == 0 and bad[2] > 0
    elif name == "nothing_committed":
        assert bad == (40, 0, 0) and cmp_["events_without_result"] > 10_000
    else:
        assert bad[0] > 0 and bad[1] > 0, name
    if name in ("float32_lanes", "the_predicate_ignored",
                "early_bids_dropped"):
        # hardly a row survives: every sum is past float32's integers,
        # and a wrong final never leaves its category's sum
        assert bad[0] >= 35


# -- the byte model --------------------------------------------------------

class _Dev:
    def __init__(self, programs):
        self.programs = programs

    def seconds(self, line, match):
        import re
        hit = [v for k, v in self.programs.items() if re.search(match, k)]
        return sum(c for c, _ in hit), sum(s for _, s in hit)


class _Trace:
    def __init__(self, programs):
        self.dev = _Dev(programs)

    def busiest(self):
        return self.dev


def test_the_byte_models_arithmetic_and_its_reader():
    shapes = q4.step_shapes(PARAMS, 1 << 20, 9800)
    # 16 bytes a record up; 64 read + 64 written a key; 20 an entry
    assert jbytes.apply_bytes(**shapes) == (1 << 20) * 16 \
        + 64_309 * 128 + 64_299 * 20 == 26_294_748
    ctx = {"trace": _Trace({"jit_join_apply_kernel": (28, 28 * 0.025)}),
           "trace_batches": 28, "chips": 1, "device_kind": "TPU v5 lite",
           "step_shapes": shapes, "job_metrics": {}}
    read = trace_roofline_join.read
    a = read(ctx, match="^jit_join_apply_kernel$")
    assert a == pytest.approx(100 * (26_294_748 / 819e9) / 0.025)
    assert 0 < a < 100
    # nothing to read: no such program (the parent's), no trace, no
    # batch in the traced span, or the shapes of another configuration
    assert read(ctx, match="^jit_groupagg_apply_kernel$") is None
    assert read({**ctx, "trace": None}, match=".") is None
    assert read({**ctx, "trace_batches": 0}, match=".") is None
    assert read({**ctx, "step_shapes": {"records": 1, "keys": 1}},
                match=".") is None
    assert read({**ctx, "step_shapes": None}, match=".") is None


# -- the refusal ---------------------------------------------------------------

def test_a_program_without_the_device_join_is_refused(monkeypatch):
    assert q4.device_join()
    monkeypatch.setitem(sys.modules, "flink_tpu.ops.join_device", None)
    assert not q4.device_join()
    with pytest.raises(NotImplementedError, match="does not support"):
        q4.make_pool(7, 64, PARAMS)


# -- the cell, end to end, at rehearsal size -------------------------------

def test_the_cells_rehearsal_commits_five_rows_a_batch():
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", str(2**31 + 47), "--seconds", "6",
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
    batches = detail["window"]["batches"]
    assert cmp_["rows_expected"] == cmp_["rows_got"] == 5 * batches > 300
    assert all(v == 0 for v in detail["counters"].values())
    assert set(detail["counters"]) == {
        "records_dropped_full", "late_records", "join.on_host",
        "join.lane_overflow", "join.pending_overflow",
        "records_in_minus_offered"}
    # one or two samples a batch: the batch's newest change
    assert batches <= detail["latency"]["samples"] <= 2 * batches
    assert detail["generator"]["paced"] is True
    phases = detail["phase_s"]
    for leaf in ("ingest.route", "window.key_scan", "window.pack",
                 "window.h2d", "window.step_dispatch", "drain.fetch",
                 "drain.deliver"):
        assert phases[leaf] > 0, leaf
