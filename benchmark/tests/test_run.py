"""The command end to end: what it refuses, a whole run at rehearsal
size, a run whose timed path is broken underneath, and a cell added by
new files alone."""
import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def bench_json(root=ROOT):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def run_cli(args, cwd=ROOT, env=None, timeout=300):
    e = dict(os.environ)
    e.update(env or {})
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "benchmark", "run.py"), *args],
        cwd=cwd, env=e, capture_output=True, text=True, timeout=timeout)


def result_of(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    return json.loads(lines[-1]), json.loads(lines[-2])


def test_refuses_without_the_cells_chips_and_prints_no_result():
    cell = bench_json()["workloads"][0]["name"]
    p = run_cli(["--workload", cell, "--seed", "1", "--seconds", "1",
                 "--trace", "0"], env={"JAX_PLATFORMS": "cpu"})
    assert p.returncode == 2 and p.stdout == ""
    assert "tpu device" in p.stderr


def test_refuses_where_only_the_benchmark_is(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    cell = bench_json()["workloads"][0]["name"]
    p = run_cli(["--workload", cell, "--seed", "1", "--seconds", "1",
                 "--trace", "0"], cwd=str(tmp_path))
    assert p.returncode not in (0, 2) and p.stdout == ""
    assert "not in this checkout" in p.stderr


def test_refuses_an_unknown_workload():
    p = run_cli(["--workload", "no_such_cell", "--seed", "1", "--seconds",
                 "1", "--trace", "0", "--rehearsal"])
    assert p.returncode != 0 and p.stdout == ""


@pytest.mark.parametrize("cell", [w["name"] for w in
                                  bench_json()["workloads"]])
def test_rehearsal_runs_every_cell_end_to_end_with_counts_only(cell):
    bench = bench_json()
    # 9 s: long enough for a paced cell to fire two windows on its own
    # (the first ends at 2 s and is released by the event stamped 6 s)
    p = run_cli(["--workload", cell, "--seed", str(2**31 + 5), "--seconds",
                 "9", "--trace", "0", "--rehearsal"],
                env={"BENCH_RUN": "ignored"})
    out, detail = result_of(p)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0 and out["rehearsal"] is True
    assert detail["compare"]["rows_expected"] == detail["compare"][
        "rows_got"] > 0
    # no number from a CPU run under a metric's name
    assert out["metrics"] and all(m["value"] is None
                                  for m in out["metrics"].values())
    assert out["device"]["platform"] == "cpu"
    assert out["device"]["memory_peak_bytes"] is None
    want = {m["name"] for m in bench["end_to_end"]
            if "workloads" not in m or cell in m["workloads"]}
    assert set(out["metrics"]) == want and "setup_s" in want


def test_traced_rehearsal_reports_layer_metrics_and_no_device_number():
    bench = bench_json()
    cell = bench["workloads"][0]["name"]
    p = run_cli(["--workload", cell, "--seed", "9", "--seconds", "3",
                 "--trace", "1", "--rehearsal"])
    out, _ = result_of(p)
    assert out["correct"] is True
    named = {m["name"] for m in bench["per_layer"] if cell in m["workloads"]}
    assert out["metrics"] and set(out["metrics"]) <= named
    # the CPU has no device plane: what reads the trace finds nothing
    trace_read = {m["name"] for m in bench["per_layer"]
                  if m["source"] == "device_trace"}
    assert not (set(out["metrics"]) & trace_read)
    assert all(m["value"] is None for m in out["metrics"].values())
    assert "breakdown" not in out


def _main_in_process(monkeypatch, capsys, cell, seed):
    from benchmark import run

    monkeypatch.setattr(sys, "path", list(sys.path))
    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds", "3",
                   "--trace", "0", "--rehearsal"])
    lines = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(lines[-1]), json.loads(lines[-2])


@pytest.mark.parametrize("cell,field", [
    ("q5_hostfed_replay", "bid_count"), ("q5_hostfed_paced", "bid_count")])
def test_an_answer_altered_where_it_is_produced_is_not_correct(
        monkeypatch, capsys, cell, field):
    """The rest of a run (no look for a chip: the rehearsal's devices),
    with the program's sink adapter adding one to one count of the
    measured job's first committed batch."""
    from flink_tpu.api import sinks

    real = sinks.FnSink.write
    hit = []

    def write(self, batch):
        name = getattr(getattr(self.fn, "__self__", None), "job", "")
        if not hit and field in batch and len(batch[field]) and not \
                str(name).endswith("warmup"):
            hit.append(1)
            batch = dict(batch)
            batch[field] = np.array(batch[field])
            batch[field][0] += 1
        return real(self, batch)

    rc, sound, _ = _main_in_process(monkeypatch, capsys, cell, 11)
    assert rc == 0 and sound["correct"] is True
    # break it for the measured job only: warm-up passes come first and
    # their sinks are thrown away, so arm the fault when the window opens
    from benchmark import loadgen

    opened = loadgen.BenchSource.open_split

    def open_split(self, split, start_pos=0):
        if self.seconds is not None:
            monkeypatch.setattr(sinks.FnSink, "write", write)
        return opened(self, split, start_pos)

    monkeypatch.setattr(loadgen.BenchSource, "open_split", open_split)
    rc, broken, detail = _main_in_process(monkeypatch, capsys, cell, 11)
    assert rc == 0 and hit
    assert broken["correct"] is False and broken["failed"] > 0
    c = detail["compare"]
    assert c["rows_missing"] + c["rows_not_in_reference"] >= 1


def test_benchmark_json_and_the_files_it_names_agree():
    b = bench_json()
    assert b["paths"] == ["benchmark"]
    cells = {w["name"]: w for w in b["workloads"]}
    configs = {c["name"]: c for c in b["configs"]}
    e2e = {m["name"]: m for m in b["end_to_end"]}
    for name in list(cells) + list(configs) + list(e2e) + [
            m["name"] for m in b["per_layer"]]:
        assert NAME.match(name), name
    for w in cells.values():
        assert w["config"] in configs
        mix = json.load(open(os.path.join(BENCH, "traffic",
                                          w["traffic"] + ".json")))
        assert os.path.isfile(os.path.join(BENCH, "traffic_kinds",
                                           mix["kind"] + ".py"))
        assert isinstance(mix["paced"], bool) and mix["why"]
        assert 1 <= len(w["why"]) <= 200 and w["chips"] in (1, 4)
    for c in configs.values():
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        assert cfg["source"] == c["source"] and len(c["source"]) <= 200
        assert cfg["reduced"] == c["reduced"] and cfg["guarantees"]
        assert set(cfg["reduced"]) == set(cfg.get("reduced_why", {}))
        assert set(cfg["reduced"]) <= set(cfg["params"])
        if "probe" in cfg:
            assert os.path.isfile(os.path.join(
                BENCH, "probes", cfg["probe"]["module"] + ".py"))
        assert os.path.isfile(os.path.join(
            BENCH, "configs", cfg.get("module", c["name"]) + ".py"))
        assert os.path.isfile(os.path.join(ROOT, "confs", cfg["conf"]))
        assert any(w["config"] == c["name"] for w in cells.values())

    def reports(metric, cell):
        return "workloads" not in metric or cell in metric["workloads"]

    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for cell in cells:
        assert sum(reports(m, cell) for m in e2e.values()) >= 2
        assert any(reports(m, cell) for m in b["per_layer"])
    for m in e2e.values():
        spec = json.load(open(os.path.join(BENCH, "end_to_end",
                                           m["name"] + ".json")))
        assert os.path.isfile(os.path.join(BENCH, "readers",
                                           spec["reader"] + ".py"))
    for m in b["per_layer"]:
        spec = json.load(open(os.path.join(BENCH, "layer_metrics",
                                           m["name"] + ".json")))
        assert os.path.isfile(os.path.join(BENCH, "readers",
                                           spec["reader"] + ".py"))
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        for cell in m["workloads"]:
            assert cell in cells and reports(e2e[m["moves"]], cell)


def test_a_cell_is_added_by_new_files_and_entries_alone(tmp_path):
    """A later PR's move, in a copy: a configuration, a traffic kind and
    a mix of it, an end-to-end and a per-layer metric, and a cell — new
    files and new entries, no edit to a file that was there."""
    for d in ("flink_tpu", "confs", "native"):
        os.symlink(os.path.join(ROOT, d), tmp_path / d)
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: open(p, "rb").read() for p in
              (str(x) for x in (tmp_path / "benchmark").rglob("*"))
              if os.path.isfile(p)}
    b = bench_json()
    cfg = json.load(open(os.path.join(BENCH, "configs", "nexmark_q5.json")))
    cfg["source"] = "throw-away: nexmark_q5 with a 4 s window and no probe"
    cfg["module"] = "nexmark_q5"
    cfg["params"]["window_ms"] = 4000
    del cfg["probe"]
    bdir = tmp_path / "benchmark"
    (bdir / "configs" / "q5_short_window.json").write_text(json.dumps(cfg))
    (bdir / "traffic_kinds" / "two_rates.py").write_text(
        "import numpy as np\n"
        "class Schedule:\n"
        "    def __init__(self, params):\n"
        "        self.a, self.b = params['rates']\n"
        "        self.events_per_ms = (self.a + self.b) / 2\n"
        "    def batch_ts(self, index, n):\n"
        "        ids = index * n + np.arange(n, dtype=np.int64)\n"
        "        cyc, rem = np.divmod(ids, 500 * (self.a + self.b))\n"
        "        return cyc * 1000 + np.where(rem < 500 * self.a, "
        "rem // self.a, 500 + (rem - 500 * self.a) // self.b)\n")
    (bdir / "traffic" / "replay_bursty.json").write_text(json.dumps({
        "why": "throw-away", "kind": "two_rates", "rates": [1, 6],
        "paced": False}))
    (bdir / "end_to_end" / "rows_per_s.json").write_text(json.dumps({
        "reader": "job_metric", "args": {"sum": ["records_out"],
                                         "per": "window_s"}}))
    (bdir / "layer_metrics" / "driver.source_wait_ms_per_batch.json"
     ).write_text(json.dumps({"reader": "job_metric", "args": {
         "sum": ["profile.phase.source"], "per": "batches",
         "scale": 1000.0}}))
    b["configs"].append({"name": "q5_short_window", "source": cfg["source"],
                         "file": "benchmark/configs/q5_short_window.json",
                         "reduced": cfg["reduced"], "why": "throw-away"})
    b["workloads"].append({"name": "q5_short_bursty",
                           "config": "q5_short_window",
                           "traffic": "replay_bursty", "chips": 1,
                           "why": "throw-away"})
    b["end_to_end"].append({"name": "rows_per_s", "unit": "rows/s",
                            "better": "higher", "bound": 0.05,
                            "source": "host_clock",
                            "workloads": ["q5_short_bursty"]})
    b["per_layer"].append({
        "name": "driver.source_wait_ms_per_batch", "unit": "ms",
        "better": "lower", "source": "program_counter",
        "layer": "driver ingest loop", "moves": "rows_per_s",
        "workloads": ["q5_short_bursty"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    args = ["--workload", "q5_short_bursty", "--seed", "3", "--seconds", "2",
            "--rehearsal", "--trace"]
    out, detail = result_of(run_cli(args + ["1"], cwd=str(tmp_path)))
    assert out["correct"] is True and out["attempted"] > 0
    assert set(out["metrics"]) == {"driver.source_wait_ms_per_batch"}
    assert detail["compare"]["rows_expected"] > 0 and detail["probe"] is None
    out, _ = result_of(run_cli(args + ["0"], cwd=str(tmp_path)))
    assert set(out["metrics"]) == {"rows_per_s", "setup_s"}
    for path, content in before.items():
        assert open(path, "rb").read() == content, path
