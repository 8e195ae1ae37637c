"""The configuration ``nexmark_q5_mesh4`` and its cell ``q5_mesh4_replay``:
the file against ``nexmark_q5``'s, the byte model's arithmetic, the
roofline reader on a made-up trace, the thin module's refusals, and the
cell's rehearsal on four CPU devices (counts only) with the exchange's
counters. (``test_run.py`` rehearses every cell of ``BENCHMARK.json``
untraced, this one included.)"""
import json
import os
import sys

import pytest

from benchmark.mesh_step_bytes import mesh_step_bytes
from benchmark.readers import trace_roofline_mesh
from benchmark.trace_reduce import DeviceTrace, Trace

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL = "q5_mesh4_replay"


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def test_the_file_is_nexmark_q5_plus_the_cluster():
    one = load(BENCH, "configs", "nexmark_q5.json")
    four = load(BENCH, "configs", "nexmark_q5_mesh4.json")
    extra = {"parallelism": 4, "mesh_devices": 4}
    assert four["params"] == {**one["params"], **extra}
    assert list(four["params"])[:len(one["params"])] == list(one["params"])
    assert four["conf"] == one["conf"]
    # the float-sum probe under the mesh's own limit, and why
    assert four["probe"] == {**one["probe"], "module": "float_sum_mesh"}
    assert "5e-6" in four["probe_why"] and "1e-6" in four["probe_why"]
    assert four["conf_overrides"] == {"cluster.mesh-devices": 4}
    assert four["chips"] == 4 and four["module"] == "nexmark_q5_mesh4"
    assert four["guarantees"][:len(one["guarantees"])] == one["guarantees"]
    assert len(four["guarantees"]) == len(one["guarantees"]) + 2
    assert four["reduced"] == one["reduced"] + ["parallelism"]
    assert set(one["assumed"]) < set(four["assumed"])
    assert four["rehearsal"] == one["rehearsal"]
    bench = load(ROOT, "BENCHMARK.json")
    assert bench["workloads"][-1] == {
        "name": CELL, "config": "nexmark_q5_mesh4", "traffic": "replay",
        "chips": 4, "why": bench["workloads"][-1]["why"]}
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


def test_the_module_is_nexmark_q5s_with_the_meshs_counters():
    from benchmark.configs import nexmark_q5, nexmark_q5_mesh4

    p = load(BENCH, "configs", "nexmark_q5_mesh4.json")["params"]
    for name in ("SCHEMA", "WINDOW_END_FIELD", "make_pool", "check",
                 "fire_delay_ms", "warmup_event_ms"):
        assert getattr(nexmark_q5_mesh4, name) is getattr(nexmark_q5, name)
    assert nexmark_q5_mesh4.zero_counters(p) == nexmark_q5.zero_counters(
        p) + ("exchange_devices_idle",)
    assert "exchange_overflow" in nexmark_q5_mesh4.zero_counters(p)
    assert nexmark_q5_mesh4.step_shapes(p, 1 << 20, 45000) == {
        "records": 1 << 20, "devices": 4}


@pytest.mark.parametrize("asked", ["", "2", "all"])
def test_build_refuses_a_conf_that_is_not_the_configurations_mesh(asked):
    from benchmark.configs import nexmark_q5_mesh4
    from flink_tpu.api.environment import StreamExecutionEnvironment
    from flink_tpu.config import Configuration

    p = load(BENCH, "configs", "nexmark_q5_mesh4.json")["params"]
    env = StreamExecutionEnvironment(Configuration(
        {"cluster.mesh-devices": asked} if asked else {}))
    with pytest.raises(ValueError, match="mesh of 4 devices"):
        nexmark_q5_mesh4.build(env, None, None, p)


@pytest.mark.parametrize("records,devices,state,fires,want", [
    # a 2^20 batch on 4 devices: block 2^18; upload 3 B a record; four
    # buckets of 2^18 five-byte entries, written and read on either side
    (1 << 20, 4, 400_000, False,
     3 * (1 << 18) + 4 * (4 * (1 << 18) * 5) + 2 * 400_000),
    (1 << 20, 4, 400_000, True,
     3 * (1 << 18) + 4 * (4 * (1 << 18) * 5) + 3 * 400_000),
    # one device: the exchange still buckets and copies the block
    (1024, 1, 0, False, 3 * 1024 + 4 * 1024 * 5),
    # a batch that does not divide: the block is rounded up
    (10, 4, 100, True, 3 * 3 + 4 * (4 * 3 * 5) + 300),
])
def test_byte_model_arithmetic(records, devices, state, fires, want):
    assert mesh_step_bytes(records=records, devices=devices,
                           state_bytes=state, fires=fires) == want


def test_byte_model_grows_with_each_of_its_terms():
    base = dict(records=1 << 20, devices=4, state_bytes=400_000, fires=False)
    b = mesh_step_bytes(**base)
    assert mesh_step_bytes(**{**base, "fires": True}) == b + 400_000
    assert mesh_step_bytes(**{**base, "state_bytes": 400_001}) == b + 2
    assert mesh_step_bytes(**{**base, "records": 1 << 21}) > 1.9 * (
        b - 800_000)
    # the buffers hold devices x block entries: the whole batch, whatever
    # the device count, so more devices shrink only the upload block
    assert mesh_step_bytes(**{**base, "devices": 8}) == b - 3 * (1 << 17)


def made_up_ctx(step_ms, batches, **over):
    step_ns = step_ms * 1e6
    mods = [("jit_apply_shard_split(1)", i * 2 * step_ns, step_ns)
            for i in range(batches)]
    busy = DeviceTrace("/device:TPU:0", mods, [])
    idle = DeviceTrace("/device:TPU:1", mods[:1], [])
    trace = Trace([busy, idle], [], (0.0, batches * 2 * step_ns))
    ctx = {"trace": trace, "trace_batches": batches, "fires": 1,
           "job_metrics": {"memory.hbm_state_bytes": 400_000.0},
           "device_kind": "TPU v5 lite",
           "step_shapes": {"records": 1 << 20, "devices": 4}}
    ctx.update(over)
    return ctx


def test_roofline_reader_is_bytes_over_peak_over_device_time():
    need = mesh_step_bytes(records=1 << 20, devices=4, state_bytes=400_000,
                           fires=True)
    got = trace_roofline_mesh.read(made_up_ctx(18.0, 10), match=".")
    assert got == pytest.approx(100.0 * (need / 819e9) / 18e-3)
    assert 0.1 < got < 0.3
    # twice the device time a batch, half the share
    assert trace_roofline_mesh.read(made_up_ctx(36.0, 10), match=".") \
        == pytest.approx(got / 2)


@pytest.mark.parametrize("over", [
    {"trace": None}, {"trace_batches": 0}, {"step_shapes": None},
    {"job_metrics": {}},
    # the one-chip module's shapes: not this model's
    {"step_shapes": {"records": 1, "keys": 1, "panes_per_batch": 1}}])
def test_roofline_reader_reads_nothing_without_its_inputs(over):
    assert trace_roofline_mesh.read(made_up_ctx(18.0, 10, **over),
                                    match=".") is None
    assert trace_roofline_mesh.read(made_up_ctx(18.0, 10),
                                    match="no_such_program") is None


def test_traced_rehearsal_reports_the_exchanges_counters():
    """The cell, traced, in a process of its own (the rehearsal makes
    its 4 CPU devices itself)."""
    import subprocess

    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", str(2**31 + 7), "--seconds", "3", "--trace", "1",
         "--rehearsal"], cwd=ROOT, capture_output=True, text=True,
        timeout=300, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert p.returncode == 0, p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    out, detail = json.loads(lines[-1]), json.loads(lines[-2])
    assert out["correct"] is True and out["failed"] == 0
    assert out["device"] == {"platform": "cpu", "kind": "cpu", "count": 4,
                             "memory_peak_bytes": None, "busy_s": None,
                             "window_s": None}
    # counts only, and what reads a device trace finds nothing on a CPU
    assert all(m["value"] is None for m in out["metrics"].values())
    assert {"exchange.chunks_per_batch.replay", "exchange.shard_skew.replay",
            "state.hbm_bytes", "driver.dispatch_ms_per_batch.replay"
            } <= set(out["metrics"])
    assert not {"exchange.all_to_all_ms_per_batch.replay",
                "exchange.split_ms_per_batch.replay", "step_roofline.mesh4",
                "step_roofline.replay"} & set(out["metrics"])
    assert detail["counters"]["exchange_devices_idle"] == 0
    assert detail["counters"]["exchange_overflow"] == 0
    assert detail["phase_s"]["window.exchange_split"] > 0
    # the lane the mesh is kept off: the fused scan never ran
    assert "scan_pane_moves" not in detail["phase_s"]
    # the measured job traced and built nothing
    assert detail["compiled_in_window"]["programs"] == 0
    # the probe ran on the mesh, at its own limit
    assert detail["probe"]["holds"] and detail["probe"]["sum_rtol"] == 5e-6


# the rest of a rehearsal, on a pool whose every auction lies in the
# shard block of mesh device 0 (the rehearsal's 8 shards, 2 a device):
# the reference is made from the same pool, so every row still agrees
ONE_DEVICE_CONTROL = """
import sys
import numpy as np
sys.path.insert(0, {root!r})
from benchmark import run
from benchmark.configs import nexmark_q5
from flink_tpu.state.keyed import KeyDirectory

real = nexmark_q5.make_pool

def make_pool(seed, n, p):
    ids = np.arange(nexmark_q5.FIRST_AUCTION_ID, nexmark_q5.key_domain(p))
    mine = ids[KeyDirectory(8, 64).shard_of(ids) // 2 == 0]
    pool = real(seed, n, p)
    for b in pool:
        b["auction"] = mine[b["auction"] % len(mine)]
    return pool

nexmark_q5.make_pool = make_pool
sys.exit(run.main(["--workload", {cell!r}, "--seed", "77", "--seconds",
                   "3", "--trace", "0", "--rehearsal"]))
"""


def test_a_run_that_reaches_one_device_only_is_not_correct():
    """THE CONTROL of ``exchange_devices_idle``, through ``run.py``'s own
    comparison: three of four devices receive no record, nothing else
    is amiss, and the result line says not correct."""
    import subprocess

    p = subprocess.run(
        [sys.executable, "-c",
         ONE_DEVICE_CONTROL.format(root=ROOT, cell=CELL)],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert p.returncode == 0, p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    out, detail = json.loads(lines[-1]), json.loads(lines[-2])
    assert out["correct"] is False and out["failed"] == 0
    assert detail["counters"].pop("exchange_devices_idle") == 3
    assert set(detail["counters"].values()) == {0}
    c = detail["compare"]
    assert c["rows_expected"] == c["rows_got"] > 0
    assert (c["rows_missing"], c["rows_not_in_reference"],
            c["rows_duplicated"]) == (0, 0, 0)
    assert detail["probe"]["holds"]
    assert "compared exchange_devices_idle = 3 (limit 0)" in p.stderr


SUITE = load(BENCH, "configs", "nexmark_q5_mesh4.json")["params"]


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 7, 3000000019])
def test_mesh_probe_passes_the_lanes_sums_and_fails_its_control(seed):
    """Sound: every price added to its pane in float32 in arrival order
    (the mesh lane), panes added in float32 (the fire at HIGHEST).
    Control: those pane sums through a dot at Precision.HIGH. The limit
    lies between with room on both sides; ``float_sum``'s own limit
    would refuse the sound lane."""
    import numpy as np

    from benchmark.probes import float_sum as fs
    from benchmark.probes import float_sum_mesh as fm
    from benchmark.configs import nexmark_q5

    n = 1 << 20
    ppw = SUITE["window_ms"] // SUITE["slide_ms"]
    data = fm.records(nexmark_q5, seed, n, SUITE)
    ts = np.arange(n, dtype=np.int64) // 100         # the probe's 100 / ms
    lane = fm.mesh_lane_sums(data, ts, SUITE)
    counts = fs.sliding(fs.pane_sums(data, ts, SUITE)[1], ppw)

    def rows(win_sums):
        e, k = np.nonzero(counts > 0)
        return [{"window_end": e * SUITE["slide_ms"], "key": k,
                 "count": counts[e, k], "sum_price": win_sums[e, k]}]

    ok = fm.check_rows(rows(fs.window_sums_f32(lane, ppw)), data, ts, SUITE)
    bad = fm.check_rows(rows(fs.lower_precision_sums(lane, ppw)), data, ts,
                        SUITE)
    assert ok["holds"] and ok["sum_rtol"] == fm.SUM_RTOL == 5e-6
    assert fs.SUM_RTOL < ok["sum_max_rel_err"] < fm.SUM_RTOL / 1.4
    assert not bad["holds"] and bad["counts_differing"] == 0
    assert bad["sum_max_rel_err"] > 1.4 * fm.SUM_RTOL
    if seed == 3000000019:
        # what four v5e chips and four CPU devices read (PERF.md, PR 26)
        assert ok["sum_max_rel_err"] == 2.2435574542136992e-06
