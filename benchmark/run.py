"""One run of one benchmark cell.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of ``workloads`` in ``BENCHMARK.json``: a configuration
(``benchmark/configs/<config>.json`` + the module it names) under a
traffic mix (``benchmark/traffic/<mix>.json``). This file knows no cell,
configuration, mix or per-layer metric by name: it finds each by the name
``BENCHMARK.json`` gives (see ``benchmark/README.md``).

A run: refuse unless JAX finds the cell's TPU chips and the program's
native codec loads; make the records from ``--seed``; warm the job up
(the same job, unpaced, until a pass compiles nothing); open the window
and run the job for ``--seconds``; then, outside every timing, hold what
the job committed to the plain reference and make the configuration's
probe, if its file names one.
Everything before the window opens is ``setup_s``. The last line of
stdout is the result object; the line before it carries the detail.

``--rehearsal`` runs the same code at a tiny size on the CPU and prints
counts only: every metric is ``null`` there.
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

TRACE_START_SHARE = 0.35    # of the window, before the profiler starts
TRACE_SECONDS = 3.0         # at most; a fifth of the window if shorter
WARMUP_MAX_PASSES = 4


def log(msg: str) -> None:
    print(f"[bench +{time.perf_counter() - _T0:7.2f}s] {msg}",
          file=sys.stderr, flush=True)


def refuse(code: int, msg: str) -> "NoReturn":  # noqa: F821
    print(f"benchmark/run.py: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(directory: str, name: str):
    path = os.path.join(HERE, directory, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark.{directory}.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(bench: dict, section: str, cell: str) -> list:
    """The metrics of ``section`` this cell reports: those that list it,
    and those that list no cells at all."""
    return [m for m in bench[section]
            if "workloads" not in m or cell in m["workloads"]]


class CompileWatch:
    """Counts what XLA had to build, or fetch from the persistent cache,
    between two marks, from jax.monitoring's own events; the programs'
    names come from jax's own compile log."""

    def __init__(self) -> None:
        import logging

        import jax.monitoring as mon

        self.requests = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.names: list = []
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)
        watch = self

        class Names(logging.Handler):
            def emit(self, record) -> None:
                msg = record.getMessage()
                if msg.startswith("Finished XLA compilation of "):
                    watch.names.append(msg.split(" ")[4])

        log_ = logging.getLogger("jax._src.dispatch")
        log_.addHandler(Names(level=logging.DEBUG))
        if log_.getEffectiveLevel() > logging.DEBUG:
            log_.setLevel(logging.DEBUG)
            log_.propagate = False

    def _on_duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1
            self.seconds += secs

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def mark(self):
        return (self.requests, self.seconds, self.cache_hits,
                len(self.names))

    def since(self, mark) -> dict:
        return {"programs": self.requests - mark[0],
                "seconds": round(self.seconds - mark[1], 3),
                "cache_hits": self.cache_hits - mark[2],
                "names": sorted(set(self.names[mark[3]:]))}


def merged(base: dict, over: dict) -> dict:
    out = dict(base)
    out.update(over or {})
    return out


class Config:
    """What a configuration's file says, with the rehearsal's cuts applied."""

    def __init__(self, bench: dict, name: str, rehearsal: bool) -> None:
        row = [c for c in bench["configs"] if c["name"] == name][0]
        self.cfg = load_json(ROOT, row["file"])
        cut = self.cfg.get("rehearsal", {}) if rehearsal else {}
        self.params = merged(self.cfg["params"], cut.get("params"))
        self.conf_overrides = merged(self.cfg.get("conf_overrides", {}),
                                     cut.get("conf_overrides"))
        self.module = load_module("configs", self.cfg.get("module", name))

    def conf(self):
        return make_conf(self.cfg["conf"], self.conf_overrides)


class Cell(Config):
    """A configuration under a traffic mix: one entry of ``workloads``."""

    def __init__(self, bench: dict, name: str, rehearsal: bool) -> None:
        rows = [w for w in bench["workloads"] if w["name"] == name]
        if not rows:
            refuse(5, f"no workload {name!r} in BENCHMARK.json; there are "
                      f"{[w['name'] for w in bench['workloads']]}")
        super().__init__(bench, rows[0]["config"], rehearsal)
        self.name = name
        self.chips = int(rows[0]["chips"])
        traffic = load_json(HERE, "traffic", rows[0]["traffic"] + ".json")
        self.traffic = merged(traffic, traffic.get("rehearsal")
                              if rehearsal else None)
        self.paced = bool(traffic["paced"])

    def schedule(self, **over):
        """The mix's schedule, of the kind its file names."""
        return load_module("traffic_kinds", self.traffic["kind"]).Schedule(
            merged(self.traffic, over))


def make_conf(conf_file: str, overrides: dict):
    """A job conf of the program's ``confs/``, read by name."""
    from flink_tpu.config import Configuration

    conf = Configuration.from_file(os.path.join(ROOT, "confs", conf_file))
    for k, v in overrides.items():
        conf.set(k, v)
    return conf


def run_job(build, conf, params, source, rec_sink, name: str):
    from flink_tpu.api.environment import StreamExecutionEnvironment

    env = StreamExecutionEnvironment(conf)
    build(env, source, rec_sink.sink, params)
    return env.execute(name)


def warm_up(cell: Cell, conf, pool, schedule, batch: int, watch) -> list:
    """The cell's own job, unpaced at the cell's own event density, long
    enough to cross the fires its module asks for and the end-of-input
    flush, repeated until a pass builds nothing (a job's own jitted
    closures are traced anew per job and fetched from the persistent
    cache: that is not a build)."""
    from benchmark.loadgen import BenchSource, RecordingSink

    p = cell.params
    n = max(3, int(cell.module.warmup_event_ms(p) * schedule.events_per_ms
                   / batch) + 2)
    passes = []
    for i in range(WARMUP_MAX_PASSES):
        m = watch.mark()
        t0 = time.perf_counter()
        src = BenchSource(pool, schedule, batch, schema=cell.module.SCHEMA,
                          max_batches=n)
        run_job(cell.module.build, conf, p, src, RecordingSink(),
                f"{cell.name}-warmup-{i}")
        got = watch.since(m)
        got["wall_s"] = round(time.perf_counter() - t0, 3)
        got["batches"] = n
        passes.append(got)
        log(f"warm-up pass {i}: {got}")
        if got["programs"] == got["cache_hits"]:
            break   # nothing was built: fetches from the cache are not compiles
    return passes


class TraceWindow:
    """Starts ``jax.profiler`` part-way into the window for a few seconds
    of wall time, from a thread of its own."""

    def __init__(self, source, seconds: float) -> None:
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.source = source
        self.length = min(TRACE_SECONDS, 0.2 * seconds)
        self.delay = TRACE_START_SHARE * seconds
        self.error = None
        self.started = None
        self.thread = threading.Thread(target=self._run, daemon=True)

    def _run(self) -> None:
        import jax

        try:
            while self.source.t_open is None:
                time.sleep(0.005)
            time.sleep(max(0.0, self.source.t_open + self.delay
                           - time.perf_counter()))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            # the traced span starts here; how long it lasts the trace
            # itself says (four chips go on recording for seconds after
            # stop_trace is called)
            self.started = time.perf_counter()
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            time.sleep(self.length)
            jax.profiler.stop_trace()
        except Exception as e:  # noqa: BLE001 — reported with the run
            self.error = repr(e)

    def reduce(self):
        from benchmark import trace_reduce

        self.thread.join()
        if self.error:
            raise RuntimeError(f"the traced span failed: {self.error}")
        path = trace_reduce.newest_xplane(self.dir)
        if path is None:
            raise RuntimeError(f"no .xplane.pb under {self.dir}")
        return trace_reduce.reduce_file(path)

    def close(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)


def start(args):
    """Everything a run refuses over, then JAX and the program: returns
    ``(bench, cell, devices)``."""
    if not os.path.isdir(os.path.join(ROOT, "flink_tpu")):
        refuse(4, f"the program is not in this checkout ({ROOT} has no "
                  "flink_tpu/): the benchmark measures it, it does not "
                  "carry it")
    bench = load_json(ROOT, "BENCHMARK.json")
    sys.path[0] = ROOT   # not this directory: its modules are benchmark.*
    cell = Cell(bench, args.workload, args.rehearsal)

    if args.rehearsal:
        # the one mode that picks a platform: it must not take the chip
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={cell.chips}"
        ).strip()
    import jax

    if args.rehearsal:
        # a rehearsal leaves no CPU programs in the chip's compile cache
        jax.config.update("jax_enable_compilation_cache", False)
    devices = jax.devices()
    want = "cpu" if args.rehearsal else "tpu"
    if devices[0].platform != want or len(devices) < cell.chips:
        refuse(2, f"{cell.name} needs {cell.chips} {want} device(s); JAX "
                  f"found {len(devices)} x {devices[0].platform} "
                  f"({devices[0].device_kind}); JAX_PLATFORMS="
                  f"{os.environ.get('JAX_PLATFORMS')!r}")

    import flink_tpu  # noqa: F401 — x64, and the compile cache's place
    from flink_tpu import native_codec

    if not native_codec.native_available():
        refuse(3, "the program's native codec did not build or load here, "
                  "so the job would run on its numpy fallbacks:\n"
                  f"{native_codec.unavailable_reason()}")
    # every program goes to the cache, not only those that took a second
    # to build: a warm run's set-up must not depend on which did
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    log(f"{len(devices)} x {devices[0].platform} "
        f"({devices[0].device_kind}); cell {cell.name}; compile cache "
        f"{jax.config.jax_compilation_cache_dir}")
    return bench, cell, devices


def verify(cell, args, source, sink, schedule, batch, jm) -> dict:
    """Outside every timing: the reference over the stream the window
    offered (regenerated from the seed), the counters the configuration
    holds at 0, and the probe its file names, if any. Every number beside
    its limit."""
    p = cell.params
    t0 = time.perf_counter()
    fresh = cell.module.make_pool(args.seed, batch, p)
    stream = ((fresh[i % len(fresh)], schedule.batch_ts(i, batch))
              for i in range(source.batches))
    cmp_ = cell.module.check(stream, source.max_ts, sink.batches, p)
    reference_s = time.perf_counter() - t0
    offered = source.batches * batch
    counters = {k: int(jm.get(k, 0)) for k in cell.module.zero_counters(p)}
    counters["records_in_minus_offered"] = int(jm.get("records_in", 0)) \
        - offered
    compared = {
        **{k: [cmp_[k], 0] for k in (
            "rows_missing", "rows_not_in_reference", "rows_duplicated")},
        **{k: [v, 0] for k, v in counters.items()}}
    probe = None
    spec = cell.cfg.get("probe")
    if spec:
        probe = load_module("probes", spec["module"]).run(
            cell, spec, args.seed, args.rehearsal, sys.modules[__name__])
        compared.update({f"probe.{k}": v
                         for k, v in probe.pop("compared").items()})
    for k, (v, lim) in compared.items():
        log(f"compared {k} = {v} (limit {lim})")
    return {
        "correct": bool(offered > 0 and cmp_["rows_expected"] > 0 and all(
            abs(v) <= lim for v, lim in compared.values())),
        "failed": min(offered, sum(
            counters.get(k, 0) for k in ("records_dropped_full",
                                         "late_records"))
                      + cmp_["events_without_result"]),
        "compare": cmp_, "counters": counters, "probe": probe,
        "reference_s": reference_s}


def read_metrics(bench, section, directory, cell, ctx) -> dict:
    """``{name: (value, unit)}`` of the cell's metrics of one section of
    ``BENCHMARK.json``: each metric's file names its reader; a reader
    that finds nothing to read returns ``None`` and the metric is left
    out."""
    values = {}
    for m in cell_metrics(bench, section, cell.name):
        spec = load_json(HERE, directory, m["name"] + ".json")
        v = load_module("readers", spec["reader"]).read(
            ctx, **spec.get("args", {}))
        if v is not None:
            values[m["name"]] = (float(v), m["unit"])
    return values


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny sizes on the CPU; counts only")
    args = ap.parse_args(argv)
    bench, cell, devices = start(args)

    import contextlib

    import jax
    import jaxlib

    from benchmark import stats
    from benchmark.loadgen import (BenchSource, Heartbeat, RecordingSink,
                                   counters_since, host_cpu_counters)
    from flink_tpu.config import PipelineOptions

    watch = CompileWatch()
    conf = cell.conf()
    batch = int(conf.get(PipelineOptions.MICROBATCH_SIZE))
    p = cell.params
    schedule = cell.schedule()
    pool = cell.module.make_pool(args.seed, batch, p)
    log(f"records made: {len(pool)} x {batch}")
    warm = warm_up(cell, conf, pool, schedule, batch, watch)

    # -- the measured window: everything before it opens is set-up ------
    source = BenchSource(pool, schedule, batch, schema=cell.module.SCHEMA,
                         paced=cell.paced, seconds=args.seconds)
    sink = RecordingSink()
    tracer = TraceWindow(source, args.seconds) if args.trace else None
    if tracer is not None:
        tracer.thread.start()
    in_window = watch.mark()
    host_cpu = host_cpu_counters()
    # the heartbeat is a thread of the benchmark's own: traced runs only
    with (Heartbeat() if args.trace else contextlib.nullcontext()) \
            as heartbeat:
        jm = run_job(cell.module.build, conf, p, source, sink,
                     cell.name).metrics
    t_returned = time.perf_counter()
    host_cpu = counters_since(host_cpu)
    compiled_in_window = watch.since(in_window)
    t_open = source.t_open
    t_last = sink.last_arrival() or t_returned
    window_s = t_last - t_open
    offered = source.batches * batch
    log(f"window: {source.batches} batches, {offered} events in "
        f"{window_s:.3f}s; execute returned {t_returned - t_last:.3f}s "
        f"after the last row; compiled in window {compiled_in_window}")

    checked = verify(cell, args, source, sink, schedule, batch, jm)

    # -- the numbers: every one through its metric's reader --------------
    firsts = sink.first_arrival_by(cell.module.WINDOW_END_FIELD)
    lat = (stats.fire_latencies_ms(firsts, t_open,
                                   cell.module.fire_delay_ms(p),
                                   source.max_ts) if cell.paced else [])
    trace = None
    if tracer is not None:
        try:
            trace = tracer.reduce()
        finally:
            tracer.close()
    shapes = getattr(cell.module, "step_shapes", None)
    ctx = {
        "setup_s": t_open - _T0, "window_s": window_s,
        "events_offered": offered, "events_failed": checked["failed"],
        "job_metrics": jm, "batches": source.batches, "fires": len(firsts),
        "generator": {"late_s": source.late_s, "gen_s": source.gen_s},
        "latencies_ms": lat,
        "stall_s": None if heartbeat is None else heartbeat.stall_s(),
        "trace": trace,
        # batches the source handed over while the profiler ran
        "trace_batches": 0 if trace is None else sum(
            0.0 <= t - tracer.started <= trace.window_s
            for t in source.release_s),
        "device_kind": devices[0].device_kind, "chips": cell.chips,
        "step_shapes": None if shapes is None else shapes(
            p, batch, schedule.events_per_ms),
    }
    e2e = read_metrics(bench, "end_to_end", "end_to_end", cell, ctx)
    values = (read_metrics(bench, "per_layer", "layer_metrics", cell, ctx)
              if args.trace else e2e)

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices[:cell.chips]]
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": max((x for x in peaks if x), default=None)}
    if trace is not None:
        device["busy_s"] = trace.mean_busy_s()
        device["window_s"] = trace.window_s

    late = [1e3 * x for x in source.late_s]
    detail = {
        "detail": cell.name, "seed": args.seed, "seconds": args.seconds,
        "rehearsal": args.rehearsal,
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                     "python": sys.version.split()[0]},
        "window": {"batches": source.batches, "events_offered": offered,
                   "window_s": window_s, "fires": len(firsts),
                   "rows": checked["compare"]["rows_got"],
                   "returned_after_last_row_s": t_returned - t_last,
                   "max_event_time_ms": source.max_ts},
        "warm_up": warm, "compiled_in_window": compiled_in_window,
        **{k: checked[k] for k in ("compare", "counters", "probe",
                                   "reference_s")},
        "latency": {"samples": len(lat), **(
            {"p50_ms": stats.percentile(lat, 50), "max_ms": max(lat),
             "all_ms": [round(x, 1) for x in lat]} if lat else {})},
        "generator": {
            "paced": cell.paced,
            "gen_ms_mean": 1e3 * sum(source.gen_s) / len(source.gen_s),
            **({"late_ms_p50": stats.percentile(late, 50),
                "late_ms_max": max(late), "late_ms_last": late[-1],
                "lag_slope_ms_per_s": stats.lag_slope_ms_per_s(
                    source.release_s, source.late_s),
                # [seconds into the window, ms late], the five latest
                "latest": sorted(
                    ([round(t - t_open, 3), round(x, 1)]
                     for t, x in zip(source.release_s, late)),
                    key=lambda r: -r[1])[:5]} if late else {})},
        # the container's quota, the machine's stolen CPU, this process's
        # CPU seconds and context switches: over the window
        "host_cpu": host_cpu,
        # when the whole process stood still: [s into the window, ms, ms
        # throttled by the quota, ms stolen] (traced runs)
        "heartbeat_gaps": None if heartbeat is None
        else heartbeat.longest(t_open),
        # the garbage collector's long pauses: [s into the window, gen, ms]
        "gc_pauses": None if heartbeat is None
        else heartbeat.gc_pauses(t_open),
        "phase_s": {k[len("profile.phase."):]: v for k, v in jm.items()
                    if k.startswith("profile.phase.")},
        "end_to_end_all": None if args.rehearsal else {
            k: v for k, (v, _u) in e2e.items()},
        "trace": None if trace is None else {
            "started_s": tracer.started - t_open,
            "length_s": trace.window_s},
        "total_s": time.perf_counter() - _T0,
    }
    print(json.dumps(detail, default=str), flush=True)

    out = {"correct": checked["correct"], "attempted": offered,
           "failed": checked["failed"],
           "metrics": {k: {"value": None if args.rehearsal else v,
                           "unit": u} for k, (v, u) in values.items()},
           "device": device}
    if args.rehearsal:
        # no number from a CPU run under a device's name
        out["rehearsal"] = True
        for k in ("memory_peak_bytes", "busy_s", "window_s"):
            if k in device:
                device[k] = None
    elif trace is not None:
        out["breakdown"] = trace.breakdown()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
