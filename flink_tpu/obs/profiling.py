"""Per-op device profiling seam (``pipeline.profile-dir``).

ref role: the reference's flame-graph/async-profiler integration on the
TaskManager (rest/profiler endpoints) — here the accelerator analogue:
wrap N WARM driver steps in ``jax.profiler.trace`` and reduce the
emitted Chrome-trace events to a per-op device-time summary, so a
"which op costs what" question is answered by measurement instead of
black-box bisection (a composition cost inside one fused program does
not yield to A/B splitting — only a per-op trace can name it).

Two artifacts per profiled run, both under the configured directory:

- the raw ``plugins/profile/<ts>/*.xplane.pb`` + ``*.trace.json.gz``
  TensorBoard/xprof trace (open with xprof for the full timeline);
- ``profile_summary.json`` — the self-contained per-op reduction this
  module computes from the Chrome trace with nothing but stdlib
  (gzip + json): per trace plane (device or host), total/self ms and
  call count per op name, sorted by total time.

Everything here is failure-tolerant by design: profiling must never
take down the job it observes — errors are recorded in the summary,
not raised into the driver loop.
"""
from __future__ import annotations

import collections
import glob
import gzip
import json
import os
import time
from typing import Any, Dict, List, Optional

__all__ = ["StepProfiler", "summarize_trace_dir"]

# host-side python interpreter events (the profiler's own tracing of
# the driver process) start with '$' — noise for a per-OP summary
_PY_EVENT_PREFIX = "$"


def _device_plane(name: str) -> bool:
    """True for planes that carry accelerator op events (the per-op
    answer lives there); host planes are kept in the summary but
    ranked after device planes."""
    n = name.lower()
    return "tpu" in n or "gpu" in n or "device" in n or "/xla" in n


# the spans under which the ingest loop sends a batch's device step out
# (its phase clock names them): a summary keeps them whatever their rank
STEP_SPANS = ("window.step_dispatch", "window.fire_dispatch")


def summarize_trace_dir(trace_dir: str, top: int = 40,
                        keep=STEP_SPANS) -> Dict[str, Any]:
    """Reduce the newest ``*.trace.json.gz`` under ``trace_dir`` to a
    per-op summary: for every trace plane, op name → {total_ms, count},
    device planes first, each plane's ops sorted by total time: the
    ``top`` longest, and those named in ``keep`` wherever they rank (a
    compile that lands inside the traced span puts some twenty names of
    its own above a 0.2 ms dispatch). Returns ``{"error": ...}`` instead
    of raising when nothing is parseable."""
    pattern = os.path.join(trace_dir, "**", "*.trace.json.gz")
    files = sorted(glob.glob(pattern, recursive=True),
                   key=lambda p: os.path.getmtime(p))
    if not files:
        return {"error": f"no trace.json.gz under {trace_dir!r} — did "
                         "the profiled run dispatch any steps?"}
    try:
        with gzip.open(files[-1], "rt", encoding="utf-8") as f:
            trace = json.load(f)
    except Exception as e:  # noqa: BLE001 — summary must not raise
        return {"error": f"trace parse failed: {type(e).__name__}: {e}"}
    events = trace.get("traceEvents", [])
    plane_names: Dict[Any, str] = {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            plane_names[e.get("pid")] = str(
                (e.get("args") or {}).get("name", e.get("pid")))
    # (plane, op) → [total_us, count]
    agg: Dict[Any, Dict[str, List[float]]] = collections.defaultdict(
        lambda: collections.defaultdict(lambda: [0.0, 0]))
    for e in events:
        if e.get("ph") != "X":
            continue
        name = str(e.get("name", ""))
        if not name or name.startswith(_PY_EVENT_PREFIX):
            continue
        plane = plane_names.get(e.get("pid"), str(e.get("pid")))
        cell = agg[plane][name]
        cell[0] += float(e.get("dur", 0))
        cell[1] += 1
    planes = []
    for plane, ops in agg.items():
        rows = sorted(
            ({"op": op, "total_ms": round(us / 1000.0, 3), "count": n}
             for op, (us, n) in ops.items()),
            key=lambda r: -r["total_ms"])
        rows = rows[:top] + [r for r in rows[top:] if r["op"] in keep]
        planes.append({
            "plane": plane,
            "device": _device_plane(plane),
            "total_ms": round(
                sum(us for us, _ in ops.values()) / 1000.0, 3),
            "ops": rows,
        })
    planes.sort(key=lambda p: (not p["device"], -p["total_ms"]))
    return {"trace_file": files[-1], "planes": planes}


class StepProfiler:
    """Driver-side trace window: skip ``skip`` warm logical batches,
    trace the next ``steps``, then stop and write
    ``<dir>/profile_summary.json``. ``step()`` is called once per
    logical batch from the ingest loop; ``close()`` (idempotent) stops
    a still-open trace — runs shorter than skip+steps still produce a
    trace of whatever ran inside the window."""

    def __init__(self, trace_dir: str, skip: int = 4,
                 steps: int = 8) -> None:
        self.trace_dir = trace_dir
        self.skip = max(int(skip), 0)
        self.steps = max(int(steps), 1)
        self._seen = 0
        self._active = False
        self._done = False
        self.summary: Optional[Dict[str, Any]] = None
        self.error: Optional[str] = None

    @classmethod
    def from_config(cls, config) -> Optional["StepProfiler"]:
        from flink_tpu.config import PipelineOptions

        d = str(config.get(PipelineOptions.PROFILE_DIR) or "").strip()
        if not d:
            return None
        return cls(d, skip=int(config.get(PipelineOptions.PROFILE_SKIP)),
                   steps=int(config.get(PipelineOptions.PROFILE_STEPS)))

    def step(self) -> None:
        """One logical-batch boundary. Never raises (see module doc)."""
        if self._done:
            return
        self._seen += 1
        try:
            if not self._active and self._seen > self.skip:
                import jax

                os.makedirs(self.trace_dir, exist_ok=True)
                jax.profiler.start_trace(self.trace_dir)
                self._active = True
                self._t0 = time.perf_counter()
            elif self._active and self._seen > self.skip + self.steps:
                self._stop()
        except Exception as e:  # noqa: BLE001
            self.error = f"{type(e).__name__}: {e}"
            self._done = True

    def _stop(self) -> None:
        import jax

        wall = time.perf_counter() - self._t0
        jax.profiler.stop_trace()
        self._active = False
        self._done = True
        self.summary = summarize_trace_dir(self.trace_dir)
        self.summary.setdefault("steps", self.steps)
        self.summary["window_wall_s"] = round(wall, 3)
        try:
            path = os.path.join(self.trace_dir, "profile_summary.json")
            with open(path, "w", encoding="utf-8") as f:
                json.dump(self.summary, f, indent=2)
            self.summary["summary_file"] = path
        except OSError as e:
            self.summary["error"] = f"summary write failed: {e}"

    def close(self) -> Optional[Dict[str, Any]]:
        """Stop a still-open trace (short runs / failure cleanup) and
        return the summary (None when the window never opened)."""
        if self._active:
            try:
                self._stop()
            except Exception as e:  # noqa: BLE001
                self.error = f"{type(e).__name__}: {e}"
                self._active = False
                self._done = True
        if self.error is not None and self.summary is None:
            return {"error": self.error}
        return self.summary
