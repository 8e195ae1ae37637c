"""The configuration ``nexmark_q5_exactly_once`` and its cell
``q5_exactly_once_paced``: the file against ``nexmark_q5_large_keys``'s
and ``BENCHMARK.json``'s, the clone's byte model, the readers of the
checkpoint layer's metrics, the module's checkpoint directories, the
refusal of a program that does not count its checkpoints, the cell's
rehearsal end to end with every compared number beside its limit, and
the read-back's controls on what that rehearsal's twin wrote."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import exactly_once_step_bytes as eo_bytes
from benchmark.configs import nexmark_q5_exactly_once as exactly_once
from benchmark.configs import nexmark_q5_large_keys as large
from benchmark.probes import exactly_once_readback as readback
from benchmark.readers import job_metric_ratio, trace_roofline_exactly_once

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL = "q5_exactly_once_paced"
CONFIG = "nexmark_q5_exactly_once"
TWIN = "nexmark_q5_large_keys"
NEW_METRICS = {
    "checkpoint.freeze_ms_per_checkpoint.paced",
    "checkpoint.flush_wait_ms_per_checkpoint.paced",
    "checkpoint.d2h_ms_per_checkpoint.paced",
    "checkpoint.write_ms_per_checkpoint.paced",
    "checkpoint.persist_ms_per_checkpoint.paced",
    "checkpoint.bytes_per_checkpoint", "checkpoint.completed.paced",
    "checkpoint.loop_share.paced", "checkpoint.clone_roofline.paced",
    "fire.device_ms_per_batch.paced", "apply.device_ms_per_batch.paced",
    "state.release_ms_per_batch.paced"}


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def test_the_file_is_large_keys_plus_the_suites_checkpoint_block():
    cfg = load(BENCH, "configs", CONFIG + ".json")
    twin = load(BENCH, "configs", TWIN + ".json")
    # no shape changes: the records, the job conf, the slots, the cuts
    for k in ("conf", "conf_overrides", "chips"):
        assert cfg[k] == twin[k], k
    assert cfg["params"] == {**twin["params"], "checkpoint_interval": 8000}
    assert cfg["reduced_why"]["pool_batches"] == \
        twin["reduced_why"]["pool_batches"]
    assert "8,000 ms" in cfg["reduced_why"]["checkpoint_interval"]
    assert cfg["guarantees"][:3] == twin["guarantees"][:3]
    assert len(cfg["guarantees"]) == 4
    assert "exactly-once STATE, switched on" in cfg["guarantees"][3]
    assert not any("checkpointing is off" in g for g in cfg["guarantees"])
    for k in ("flink_conf_yaml", "full_snapshots", "mode", "checkpoint_dir",
              "sink"):
        assert k in cfg["assumed"], k
    assert cfg["rehearsal"]["conf_overrides"] == \
        twin["rehearsal"]["conf_overrides"]
    assert 0 < cfg["rehearsal"]["params"]["checkpoint_interval"] <= 1000
    # the probe entry is the float-sum probe's own, plus the count asked
    probe = dict(cfg["probe"])
    assert probe.pop("module") == "exactly_once_readback"
    assert probe.pop("checkpoints_wanted") == 4
    assert probe == {k: v for k, v in twin["probe"].items()
                     if k != "module"}


def test_benchmark_json_names_the_file_the_cell_and_its_metrics():
    bench = load(ROOT, "BENCHMARK.json")
    cfg = load(BENCH, "configs", CONFIG + ".json")
    (row,) = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert row["source"] == cfg["source"] and len(row["source"]) <= 200
    assert row["reduced"] == cfg["reduced"] == [
        "pool_batches", "checkpoint_interval"]
    assert set(cfg["reduced"]) <= set(cfg["params"])
    assert row["file"] == f"benchmark/configs/{CONFIG}.json"
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "paced_suite", 1)
    assert len(cell["why"]) <= 200
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    assert len(bench["workloads"]) == 5
    mix = load(BENCH, "traffic", "paced_suite.json")
    assert (mix["kind"], mix["events_per_ms"], mix["paced"]) == (
        "constant_rate", 9200, True)
    mine = {m["name"]: m for m in bench["per_layer"]
            if m.get("workloads") == [CELL]}
    assert set(mine) == NEW_METRICS and len(mine) <= 14
    for name, m in mine.items():
        assert m["moves"] == "event_latency_p50_ms", name
        assert os.path.isfile(os.path.join(
            BENCH, "layer_metrics", name + ".json")), name
    # the cell reports every .paced metric the accepted paced cell does
    for m in bench["per_layer"]:
        if "q5_hostfed_paced" in m.get("workloads", ()):
            assert CELL in m["workloads"], m["name"]
    (p50,) = [m for m in bench["end_to_end"]
              if m["name"] == "event_latency_p50_ms"]
    assert p50["workloads"] == ["q5_hostfed_paced", CELL]
    assert p50["bound"] == 0.07
    # the three device and release metrics read what the replay cell's do
    for new, old in (("fire.device_ms_per_batch", "replay"),
                     ("apply.device_ms_per_batch", "replay"),
                     ("state.release_ms_per_batch", "replay")):
        assert load(BENCH, "layer_metrics", f"{new}.paced.json") == load(
            BENCH, "layer_metrics", f"{new}.{old}.json")


# -- the byte model and the readers ----------------------------------------

def test_the_clone_moves_the_tensor_as_laid_out_twice():
    # 16,777,217 rows x 12 ring columns of int32: 805,306,416 bytes of
    # counts; in tiles of 8 columns x 128 rows 16,777,344 x 16 cells
    assert eo_bytes.laid_out_bytes(rows=16_777_217, ring=12) == \
        16_777_344 * 16 * 4 == 1_073_750_016
    assert eo_bytes.clone_bytes(rows=16_777_217, ring=12) == 2_147_500_032
    # already whole tiles: nothing is padded
    assert eo_bytes.laid_out_bytes(rows=1024, ring=16) == 1024 * 16 * 4
    assert eo_bytes.laid_out_bytes(rows=1, ring=1) == 128 * 8 * 4
    assert eo_bytes.clone_bytes(rows=129, ring=9, lanes=2) == \
        2 * 2 * 256 * 16 * 4


class _Device:
    def __init__(self, calls, secs):
        self._got = (calls, secs)

    def seconds(self, line, match):
        assert line == "XLA Modules"
        assert match == "^jit_snapshot_clone_kernel$"
        return self._got


class _Trace:
    def __init__(self, dev):
        self._dev = dev

    def busiest(self):
        return self._dev


def test_the_clones_share_of_its_roofline_and_what_it_is_silent_on():
    args = load(BENCH, "layer_metrics",
                "checkpoint.clone_roofline.paced.json")["args"]
    jm = {"state.pane_rows": 16_777_217, "state.ring_columns": 12}
    ctx = {"trace": _Trace(_Device(1, 0.004)), "job_metrics": jm,
           "device_kind": "TPU v5 lite"}
    # 2,147,500,032 bytes / 819 GB/s = 2.622 ms at the roofline
    assert trace_roofline_exactly_once.read(ctx, **args) == pytest.approx(
        100 * 2_147_500_032 / 819e9 / 0.004)
    two = dict(ctx, trace=_Trace(_Device(2, 0.008)))
    assert trace_roofline_exactly_once.read(two, **args) == pytest.approx(
        trace_roofline_exactly_once.read(ctx, **args))
    # no checkpoint in the traced span, no trace, or a program without
    # the gauges (the parent): nothing, and no error
    assert trace_roofline_exactly_once.read(
        dict(ctx, trace=_Trace(_Device(0, 0.0))), **args) is None
    assert trace_roofline_exactly_once.read(
        dict(ctx, trace=None), **args) is None
    assert trace_roofline_exactly_once.read(
        dict(ctx, job_metrics={}), **args) is None


def test_a_ratio_of_job_metrics_and_what_it_is_silent_on():
    jm = {"checkpoint.completed": 6, "checkpoint.freeze_s": 1.8,
          "profile.phase.persist.write": 7.2,
          "checkpoint.bytes_total": 6_600_000_000}

    def read(name, metrics=jm):
        spec = load(BENCH, "layer_metrics", name + ".json")
        assert spec["reader"] == "job_metric_ratio"
        return job_metric_ratio.read({"job_metrics": metrics}, **spec["args"])

    assert read("checkpoint.freeze_ms_per_checkpoint.paced") == \
        pytest.approx(300.0)
    assert read("checkpoint.write_ms_per_checkpoint.paced") == \
        pytest.approx(1200.0)
    assert read("checkpoint.bytes_per_checkpoint") == pytest.approx(1.1e9)
    # the parent counts none of it; a job that completed none has no mean
    assert read("checkpoint.freeze_ms_per_checkpoint.paced", {}) is None
    assert read("checkpoint.freeze_ms_per_checkpoint.paced",
                dict(jm, **{"checkpoint.completed": 0})) is None
    assert read("checkpoint.d2h_ms_per_checkpoint.paced") is None


# -- the module ------------------------------------------------------------

def test_the_module_is_large_keys_with_two_more_zero_counters():
    p = load(BENCH, "configs", CONFIG + ".json")["params"]
    assert exactly_once.check is large.check
    assert exactly_once.pane_counts is large.pane_counts
    assert exactly_once.zero_counters(p) == large.zero_counters(p) + (
        "checkpoint.failed", "checkpoint.aborted")
    assert exactly_once.counts_checkpoints()
    assert exactly_once.make_pool(7, 64, p)[3].keys() == \
        large.make_pool(7, 64, p)[3].keys()


def test_a_program_that_does_not_count_its_checkpoints_is_refused(
        monkeypatch):
    import flink_tpu.runtime.driver as driver

    monkeypatch.delattr(driver, "CHECKPOINT_COUNTERS")
    assert not exactly_once.counts_checkpoints()
    with pytest.raises(NotImplementedError, match="does not count"):
        exactly_once.make_pool(
            7, 64, load(BENCH, "configs", CONFIG + ".json")["params"])


# -- the cell, end to end, at rehearsal size -------------------------------

@pytest.fixture(scope="module")
def rehearsal():
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", str(2**31 + 91), "--seconds", "8",
         "--trace", "0", "--rehearsal"],
        cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    return json.loads(lines[-1]), json.loads(lines[-2])


def test_the_cells_rehearsal_checkpoints_and_reads_them_back(rehearsal):
    out, detail = rehearsal
    assert out["correct"] is True and out["failed"] == 0
    assert out["rehearsal"] is True
    assert set(out["metrics"]) == {"event_latency_p50_ms", "setup_s"} or \
        set(out["metrics"]) == {"setup_s"}   # under two latency samples
    assert all(v["value"] is None for v in out["metrics"].values())
    cmp_ = detail["compare"]
    assert cmp_["rows_expected"] == cmp_["rows_got"] > 0
    assert set(detail["counters"]) >= {"checkpoint.failed",
                                       "checkpoint.aborted"}
    assert all(v == 0 for v in detail["counters"].values()), \
        detail["counters"]
    probe = detail["probe"]
    assert probe["holds"] is True and probe["sum_rtol"] == 5e-6
    back = probe["read_back"]
    assert back["short"] == 0 and back["completed_in_window"] >= 4
    assert back["cells_differing"] == 0
    assert back["position_mismatches"] == 0
    assert back["keys_in_directory"] > 1000
    assert detail["generator"]["paced"] is True
    phases = detail["phase_s"]
    for leaf in ("ingest.checkpoint_flush", "state.snapshot_clone",
                 "state.snapshot_directory", "persist.fetch",
                 "persist.write"):
        assert phases[leaf + ".n"] == back["completed_in_window"] + 1, leaf
    # the run left no checkpoint directory behind
    assert exactly_once.CHECKPOINT_DIRS == []


# -- the read-back's controls, on what the cell's own job writes ------------

def test_an_altered_count_and_a_reference_a_batch_off_are_both_refused():
    from benchmark import run as R
    from benchmark.loadgen import BenchSource, RecordingSink
    from flink_tpu.config import PipelineOptions

    cell = R.Cell(load(ROOT, "BENCHMARK.json"), CELL, True)
    p = dict(cell.params, checkpoint_interval=1)   # at every boundary
    conf = cell.conf()
    batch = int(conf.get(PipelineOptions.MICROBATCH_SIZE))
    sched, seed = cell.schedule(), 2**31 + 92
    pool = cell.module.make_pool(seed, batch, p)
    try:
        R.run_job(cell.module.build, conf, p,
                  BenchSource(pool, sched, batch, schema=cell.module.SCHEMA,
                              max_batches=40), RecordingSink(), "controls")
        root = cell.module.CHECKPOINT_DIRS[-1]

        def read_back(**kw):
            return readback.read_back(root, cell.module, pool, sched, batch,
                                      p, wanted=2, **kw)

        sound = read_back()
        assert (sound["short"], sound["cells_differing"],
                sound["position_mismatches"]) == (0, 0, 0), sound
        assert sound["keys_in_directory"] > 1000
        for shift in (1, -1):
            off = read_back(shift=shift)
            assert off["cells_differing"] > 0, shift
            assert off["position_mismatches"] > 0, shift
        # one count altered in the written blob
        _cid, d, manifest = readback.list_checkpoints(root)[-2]
        for entry in manifest["ops"].values():
            path = os.path.join(d, entry["file"])
            if "panes" not in readback.read_blob(path):
                continue
            counts = readback.read_blob(path, "r+")["panes"]["counts"]
            row, col = np.argwhere(np.asarray(counts) > 0)[0]
            counts[row, col] += 1
            counts.flush()
        assert read_back()["cells_differing"] == 1
    finally:
        cell.module.remove_checkpoints()
    assert exactly_once.CHECKPOINT_DIRS == []
