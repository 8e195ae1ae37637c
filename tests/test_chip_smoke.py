"""chip_smoke.py's contract as far as a CPU can show it, and the
compile-cache placement rule it reports.

The smoke itself only means something on a TPU (the driver runs it
there); here the rehearsal mode proves the command runs end to end —
every plane matched against the in-file reference — and the plain form
refuses to carry on without a chip."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, env_extra, timeout=120):
    env = dict(os.environ, **env_extra)
    return subprocess.run([sys.executable, SMOKE, *args], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=timeout)


class TestChipSmoke:
    def test_cpu_rehearsal_runs_every_plane_and_fills_the_cache(
            self, tmp_path):
        cache = str(tmp_path / "cache")
        p = _run(["--cpu-rehearsal"], {
            "JAX_COMPILATION_CACHE_DIR": cache,
            "JAX_ENABLE_COMPILATION_CACHE": "true",
            # tiny CPU programs compile in well under jax's 1 s floor
            "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
        })
        assert p.returncode == 0, p.stderr[-3000:]
        out, last = map(json.loads, p.stdout.strip().splitlines()[-2:])
        # the result line: exactly the keys the driver's check fixes
        assert set(last) == {"ok", "device"} and last["ok"] is True
        dev = last["device"]  # count: the conftest's virtual devices
        assert set(dev) == {"platform", "kind", "count"}
        assert (dev["platform"], dev["kind"]) == ("cpu", "cpu")
        assert isinstance(dev["count"], int) and dev["count"] >= 1
        # the report line before it
        assert out["rehearsal"] is True and out["device"] == dev
        assert out["native_codec"].startswith("libflinktpucodec-")
        planes = out["planes"]
        assert set(planes) == {"host_fed", "sum_lane"}
        for name, plane in planes.items():
            # matched: the job's rows equal the in-file numpy
            # reference's over the same bid_stream records
            assert plane["matched"] is True and plane["rows"] > 0, name
            assert plane["records_dropped_full"] == 0
            assert plane["late_records"] == 0
            assert plane["compile_s"] > 0 and plane["run_s"] > 0
        assert planes["host_fed"]["events"] == (
            planes["host_fed"]["batches"] * (1 << 13))
        assert planes["sum_lane"]["sum_max_rel_err"] <= 1e-5
        # the cache went where the environment said, set from outside
        cc = out["compile_cache"]
        assert cc["dir"] == cache and cc["from_env"] is True
        assert cc["entries_after"] > cc["entries_before"] == 0
        assert cc["entries_after"] == sum(
            f.endswith("-cache") for f in os.listdir(cache))

    def test_cpu_rehearsal_traces_the_host_fed_job(self, tmp_path):
        """``--trace-dir`` runs one more HOST-FED Q5 under
        pipeline.profile-dir, and its summary names the span under
        which a host-fed batch's device step goes out."""
        p = _run(["--cpu-rehearsal", "--trace-dir", str(tmp_path / "tr")],
                 {"JAX_ENABLE_COMPILATION_CACHE": "false"}, timeout=240)
        assert p.returncode == 0, p.stderr[-3000:]
        out = json.loads(p.stdout.strip().splitlines()[-2])
        trace = out["trace"]
        by_span = {op["op"]: op["count"] for op in trace["step_dispatch"]}
        assert by_span.get("window.step_dispatch", 0) >= 1, trace
        assert os.path.exists(trace["trace_file"])
        assert set(out["planes"]) == {"host_fed", "sum_lane"}

    def test_cpu_rehearsal_four_chips_equals_one(self):
        """``--chips 4``: the host-fed job on one device and over a
        mesh of four (virtual) devices commit the same rows, and every
        device received records."""
        p = _run(["--cpu-rehearsal", "--chips", "4"],
                 {"JAX_ENABLE_COMPILATION_CACHE": "false"}, timeout=240)
        assert p.returncode == 0, p.stderr[-3000:]
        out, last = map(json.loads, p.stdout.strip().splitlines()[-2:])
        assert last["ok"] is True and out["chips_used"] == 4
        assert set(out["planes"]) == {"host_fed", "host_fed_mesh4"}
        mesh = out["planes"]["host_fed_mesh4"]
        assert mesh["matched"] is True and mesh["equals_one_chip"] is True
        assert len(mesh["records_per_device"]) == 4
        assert sum(mesh["records_per_device"]) == mesh["events"]

    def test_plain_form_refuses_to_run_without_a_tpu(self):
        p = _run([], {"JAX_PLATFORMS": "cpu"})
        assert p.returncode not in (0, None)
        assert p.stdout.strip() == ""          # no result line
        assert "tpu" in p.stderr and "cpu" in p.stderr


class TestCompileCachePlacement:
    """``flink_tpu.configure_compile_cache``: the environment decides
    when it speaks; otherwise one fixed in-checkout path."""

    def test_env_var_set_means_code_sets_nothing(self, monkeypatch):
        import jax

        import flink_tpu

        calls = []
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        monkeypatch.setattr(jax.config, "update",
                            lambda *a, **k: calls.append(a))
        assert flink_tpu.configure_compile_cache() == "/some/dir"
        assert calls == []

    def test_unset_means_the_fixed_in_checkout_path(self, monkeypatch):
        import jax

        import flink_tpu

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        before = jax.config.jax_compilation_cache_dir
        try:
            path = flink_tpu.configure_compile_cache()
            assert path == os.path.join(REPO, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == path
            assert flink_tpu.configure_compile_cache() == path  # fixed
        finally:
            jax.config.update("jax_compilation_cache_dir", before)

    def test_no_other_code_sets_the_cache_directory(self):
        setters = []
        for root, dirs, files in os.walk(REPO):
            dirs[:] = [d for d in dirs if not d.startswith(".")
                       and d not in ("tests", "__pycache__",
                                     "chiprun_out")]
            for fn in files:
                if not fn.endswith(".py"):
                    continue
                path = os.path.join(root, fn)
                with open(path, encoding="utf-8") as f:
                    text = f.read()
                if ('"jax_compilation_cache_dir"' in text
                        or "JAX_COMPILATION_CACHE_DIR\"] =" in text):
                    setters.append(os.path.relpath(path, REPO))
        assert setters == [os.path.join("flink_tpu", "__init__.py")]
