"""chip_smoke.py — the quickest proof that the system starts on the chip.

Drives NEXmark Q5 (10 s / 1 s sliding per-auction COUNT, top(1), 1 s
out-of-orderness) through the entry points a user calls —
``q5_hot_items`` on a ``StreamExecutionEnvironment``, ``env.execute()``,
rows collected by a sink — on one TPU chip in ONE process, at the sizes
the repo commits, and checks every committed row against a plain numpy
reference kept in this file:

- ``host_fed``: ``confs/bench_q5_host_fed.conf`` (2^20-record batches),
  ``bid_stream`` — records cross the host-device link, as in every
  deployment;
- ``sum_lane``: count + ``sum_of("price")`` on the same bids under the
  host-fed conf — the only job here whose fire carries a float SUM lane,
  checked against an f64 sum within ``SUM_RTOL``.

Each plane runs its job twice — a warm-up that compiles every program
the job needs, then the measured run — so compile time and run time are
reported apart (``compile_s`` is the warm-up's wall, ``run_s`` the
measured run's; ``compiled_in_run`` should be zero programs).

    python chip_smoke.py                   # one chip; what the driver runs
    python chip_smoke.py --chips 4         # host-fed Q5 over a 4-chip mesh
    python chip_smoke.py --trace-dir DIR   # + one traced host-fed run
    python chip_smoke.py --cpu-rehearsal   # same code, tiny sizes, CPU

It exits non-zero, with the reason on stderr and no result line, when JAX
finds no TPU (it never selects a platform itself outside the rehearsal),
when the native codec cannot be built here, when a record was dropped, or
when any row differs from the reference. On success it prints two JSON
lines: the
report (per-plane events, rows, compile_s / run_s, counters, versions,
compile cache; ``"rehearsal": true`` in the rehearsal — elapsed seconds
and counts only, this is not a benchmark), and, as the last stdout line,
exactly ``{"ok": true, "device": {"platform", "kind", "count"}}`` with the
device as JAX reports it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

WINDOW_MS = 10_000
SLIDE_MS = 1_000
OUT_OF_ORDERNESS_MS = 1_000
PANES_PER_WINDOW = WINDOW_MS // SLIDE_MS

# the generator settings bench.run_q5 uses, and the rehearsal's (a key
# domain that fits its 8x64 slots; one pane per 1,000 records)
NEXMARK = dict(events_per_ms=100, num_active_auctions=10_000, hot_ratio=4)
NEXMARK_REHEARSAL = dict(events_per_ms=1, num_active_auctions=400,
                         hot_ratio=4)

# f32 accumulation of a key's few thousand prices stays within ~1e-6 of
# the f64 sum; a product rounded to bf16 on the way (what a
# default-precision f32 dot does on a TPU) is off by ~1e-3
SUM_RTOL = 1e-5

HERE = os.path.dirname(os.path.abspath(__file__))


class SmokeFailure(Exception):
    """A phase did not hold; the message says which and why."""


_T0 = time.perf_counter()


def log(msg: str) -> None:
    print(f"[chip_smoke +{time.perf_counter() - _T0:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


# -- plain reference ---------------------------------------------------------

def reference_panes(cfg, with_sums: bool):
    """Per-(pane, auction) bid counts (and f64 price sums) of the host
    generator's bids, in plain numpy: pane = ts // slide, one bincount
    per batch."""
    import numpy as np

    from flink_tpu.nexmark.generator import bid_stream

    src = bid_stream(cfg)
    n_auctions = cfg.num_active_auctions
    total = cfg.batch_size * cfg.n_batches
    n_panes = (total - 1) // cfg.events_per_ms // SLIDE_MS + 1
    cells = n_panes * n_auctions
    counts = np.zeros(cells, np.int64)
    sums = np.zeros(cells, np.float64) if with_sums else None
    for i in range(cfg.n_batches):
        data, ts = src.gen("0", i)
        cell = (np.asarray(ts, np.int64) // SLIDE_MS) * n_auctions \
            + data["auction"]
        counts += np.bincount(cell, minlength=cells)
        if with_sums:
            sums += np.bincount(
                cell, weights=data["price"].astype(np.float64),
                minlength=cells)
    shape = (n_panes, n_auctions)
    return counts.reshape(shape), (sums.reshape(shape) if with_sums
                                   else None)


def sliding(panes):
    """(n_panes, A) per-pane values -> per-window sums; row ``e`` is the
    window that ends at ``e * SLIDE_MS`` (panes [e - 10, e))."""
    import numpy as np

    n_panes = panes.shape[0]
    cs = np.concatenate([np.zeros((1, panes.shape[1]), panes.dtype),
                         np.cumsum(panes, axis=0)])
    ends = np.arange(n_panes + PANES_PER_WINDOW)
    return (cs[np.minimum(ends, n_panes)]
            - cs[np.maximum(ends - PANES_PER_WINDOW, 0)])


def reference_hot_items(win_counts):
    """Q5's answer: per window the auction(s) with the most bids, ties
    kept — sorted (window_end, auction, bid_count) rows."""
    import numpy as np

    best = win_counts.max(axis=1)
    e, a = np.nonzero((win_counts == best[:, None]) & (best[:, None] > 0))
    return sorted(zip((e * SLIDE_MS).tolist(), a.tolist(),
                      win_counts[e, a].tolist()))


# -- the jobs ----------------------------------------------------------------

def load_conf(name: str, overrides: dict):
    from flink_tpu.config import Configuration

    conf = Configuration.from_file(os.path.join(HERE, "confs", name))
    for k, v in overrides.items():
        conf.set(k, v)
    return conf


def collecting_sink():
    from flink_tpu.api.sinks import FnSink

    batches = []
    return batches, FnSink(batches.append)


def columns(batches, fields):
    import numpy as np

    return [np.concatenate([np.asarray(b[f]) for b in batches])
            if batches else np.zeros(0, np.int64) for f in fields]


def run_q5(conf, cfg):
    """One Q5 job through env.execute(); (JobResult, sorted rows, env)."""
    from flink_tpu.api.environment import StreamExecutionEnvironment
    from flink_tpu.nexmark.generator import bid_stream
    from flink_tpu.nexmark.queries import q5_hot_items

    env = StreamExecutionEnvironment(conf)
    batches, sink = collecting_sink()
    q5_hot_items(env, bid_stream(cfg), sink, window_ms=WINDOW_MS,
                 slide_ms=SLIDE_MS,
                 out_of_orderness_ms=OUT_OF_ORDERNESS_MS)
    res = env.execute("chip-smoke-q5")
    we, au, ct = columns(batches, ("window_end", "auction", "bid_count"))
    return res, sorted(zip(we.tolist(), au.tolist(), ct.tolist())), env


def run_count_sum(conf, cfg):
    """count + sum(price) per (auction, sliding window), host-fed, every
    row emitted (no top-n): the fire reduces a float sum lane."""
    from flink_tpu.api.environment import StreamExecutionEnvironment
    from flink_tpu.api.windowing import SlidingEventTimeWindows
    from flink_tpu.nexmark.generator import bid_stream
    from flink_tpu.ops.aggregates import count, multi, sum_of
    from flink_tpu.time.watermarks import WatermarkStrategy

    env = StreamExecutionEnvironment(conf)
    batches, sink = collecting_sink()
    (env.from_source(
        bid_stream(cfg),
        WatermarkStrategy.for_bounded_out_of_orderness(OUT_OF_ORDERNESS_MS))
        .key_by("auction")
        .window(SlidingEventTimeWindows.of(WINDOW_MS, SLIDE_MS))
        .aggregate(multi(count(), sum_of("price")))
        .add_sink(sink))
    res = env.execute("chip-smoke-count-sum")
    return res, columns(batches, ("window_end", "key", "count", "sum_price"))


def check_counters(name: str, metrics: dict) -> dict:
    """The driver's loss counters; a dropped record fails the smoke."""
    got = {k: int(metrics.get(k, 0)) for k in (
        "records_dropped_full", "late_records")}
    if got["records_dropped_full"]:
        raise SmokeFailure(f"{name}: records_dropped_full = "
                           f"{got['records_dropped_full']}")
    return got


def phase_seconds(metrics: dict) -> dict:
    """The driver's own per-phase host-clock attribution of the job
    (Driver.phase_breakdown), carried along as a sighting."""
    pre = "profile.phase."
    return {k[len(pre):]: round(float(v), 3)
            for k, v in sorted(metrics.items()) if k.startswith(pre)}


class CompileWatch:
    """Counts what XLA had to build (or fetch from the persistent cache)
    between two marks, from jax.monitoring's own events."""

    def __init__(self) -> None:
        import jax.monitoring as mon

        self.requests = 0      # programs compiled or fetched
        self.seconds = 0.0     # time spent doing so
        self.cache_hits = 0    # of which served by the persistent cache
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1
            self.seconds += secs

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def mark(self):
        return (self.requests, self.seconds, self.cache_hits)

    def since(self, mark) -> dict:
        return {"programs": self.requests - mark[0],
                "seconds": round(self.seconds - mark[1], 3),
                "cache_hits": self.cache_hits - mark[2]}


def timed_plane(name: str, watch: CompileWatch, warm, measured) -> dict:
    """Run the warm-up job, then the measured one; report both walls and
    what was compiled in each."""
    m0 = watch.mark()
    t0 = time.perf_counter()
    warm()
    compile_s = time.perf_counter() - t0
    in_warmup = watch.since(m0)
    m1 = watch.mark()
    log(f"{name}: warm-up job {compile_s:.1f}s, compiled {in_warmup}")
    t1 = time.perf_counter()
    out = measured()
    run_s = time.perf_counter() - t1
    in_run = watch.since(m1)
    log(f"{name}: measured job {run_s:.1f}s, compiled {in_run}")
    out.update(compile_s=round(compile_s, 3), run_s=round(run_s, 3),
               compiled_in_warmup=in_warmup, compiled_in_run=in_run)
    return out


def job_setup(conf_file, overrides, nexmark, n_batches):
    """(job conf, generator config) of one plane."""
    from flink_tpu.config import PipelineOptions
    from flink_tpu.nexmark.generator import NexmarkConfig

    conf = load_conf(conf_file, overrides)
    batch = int(conf.get(PipelineOptions.MICROBATCH_SIZE))
    return conf, NexmarkConfig(batch_size=batch, n_batches=n_batches,
                               **nexmark)


def q5_plane(name, conf_file, overrides, nexmark, n_batches,
             watch, inspect=None) -> dict:
    """One Q5 plane: warm-up, measured run, counters, rows against the
    reference. ``inspect(env, pane_counts)`` adds a plane's own checks
    on the finished job (the mesh's state placement)."""
    conf, cfg = job_setup(conf_file, overrides, nexmark, n_batches)

    def measured():
        res, rows, env = run_q5(conf, cfg)
        counters = check_counters(name, res.metrics)
        pane_counts = reference_panes(cfg, False)[0]
        expect = reference_hot_items(sliding(pane_counts))
        if rows != expect:
            raise SmokeFailure(
                f"{name}: {len(rows)} rows differ from the reference's "
                f"{len(expect)}; first differences "
                f"{sorted(set(rows) ^ set(expect))[:6]}")
        return {"conf": conf_file, "events": cfg.batch_size * n_batches,
                "batches": n_batches,
                "rows": len(rows), "matched": True, **counters,
                "phase_s": phase_seconds(res.metrics),
                **(inspect(env, pane_counts) if inspect else {}),
                "rows_sorted": rows}

    return timed_plane(name, watch, lambda: run_q5(conf, cfg), measured)


def sum_lane_plane(overrides, nexmark, n_batches, watch) -> dict:
    import numpy as np

    name = "sum_lane"
    conf, cfg = job_setup("bench_q5_host_fed.conf", overrides, nexmark,
                          n_batches)
    n_auctions = cfg.num_active_auctions

    def measured():
        res, (we, key, cnt, sm) = run_count_sum(conf, cfg)
        counters = check_counters(name, res.metrics)
        ref_c, ref_s = map(sliding, reference_panes(cfg, True))
        e = we // SLIDE_MS
        if len(np.unique(e * n_auctions + key)) != len(e):
            raise SmokeFailure(f"{name}: a (window, auction) row was "
                               "emitted twice")
        if len(e) != int((ref_c > 0).sum()):
            raise SmokeFailure(
                f"{name}: {len(e)} rows, the reference has "
                f"{int((ref_c > 0).sum())} non-empty (window, auction)s")
        if not np.array_equal(cnt, ref_c[e, key]):
            bad = [(int(we[i]), int(key[i]), int(cnt[i]),
                    int(ref_c[e[i], key[i]]))
                   for i in np.nonzero(cnt != ref_c[e, key])[0][:4]]
            raise SmokeFailure(
                f"{name}: counts differ; (window_end, auction, got, "
                f"reference): {bad}")
        if sm.dtype != np.float32:
            raise SmokeFailure(f"{name}: sum lane came back as {sm.dtype}")
        rel = np.abs(sm.astype(np.float64) - ref_s[e, key]) / ref_s[e, key]
        worst = int(np.argmax(rel))
        if not np.isfinite(rel).all() or rel[worst] > SUM_RTOL:
            raise SmokeFailure(
                f"{name}: sum lane off by {rel[worst]:.3e} relative "
                f"(limit {SUM_RTOL}) at window_end={int(we[worst])} "
                f"auction={int(key[worst])}: got {float(sm[worst])!r}, "
                f"f64 reference {float(ref_s[e[worst], key[worst]])!r} "
                f"over {int(cnt[worst])} bids")
        return {"conf": "bench_q5_host_fed.conf",
                "events": cfg.batch_size * n_batches, "batches": n_batches,
                "rows": int(len(e)), "matched": True, **counters,
                "phase_s": phase_seconds(res.metrics),
                "sum_max_rel_err": float(rel[worst]),
                "sum_rtol": SUM_RTOL,
                "max_bids_per_row": int(cnt.max())}

    return timed_plane(
        name, watch, lambda: run_count_sum(conf, cfg), measured)


# the spans under which a host-fed batch's device step goes out: the
# ingest loop's phase clock names them, and they are host events of any
# trace the job is run under
STEP_SPANS = ("window.step_dispatch", "window.fire_dispatch")


def traced_run(trace_dir, overrides, nexmark, n_batches,
               want_device_plane: bool) -> dict:
    """One host-fed Q5 under the existing pipeline.profile-dir seam
    (obs/profiling.py): the summary must show the job's step dispatch,
    and on a chip a device plane with its ops."""
    conf, cfg = job_setup(
        "bench_q5_host_fed.conf",
        {**overrides, "pipeline.profile-dir": trace_dir}, nexmark, n_batches)
    res, _, _ = run_q5(conf, cfg)
    summary = res.metrics.get("profile.trace_summary")
    if not summary or summary.get("error"):
        raise SmokeFailure(f"traced run left no summary: {summary}")
    planes = summary["planes"]
    step = [{"plane": p["plane"], **op} for p in planes for op in p["ops"]
            if op["op"] in STEP_SPANS]
    device = [p for p in planes if p["device"]]
    if not step or (want_device_plane and not device):
        raise SmokeFailure(
            "the trace summary lacks the step dispatch or a device plane: "
            f"planes {[(p['plane'], p['device']) for p in planes]}, "
            f"{STEP_SPANS} events {step}")
    return {"trace_file": summary["trace_file"],
            "steps": summary.get("steps"),
            "window_wall_s": summary.get("window_wall_s"),
            "planes": [{"plane": p["plane"], "device": p["device"],
                        "total_ms": p["total_ms"]} for p in planes],
            "step_dispatch": step,
            "device_top_ops": [{"plane": p["plane"], "ops": p["ops"][:12]}
                               for p in device[:2]]}


# -- four chips --------------------------------------------------------------

def mesh_inspector(name, n_chips, devices):
    """The mesh plane's own checks, as a q5_plane ``inspect`` hook: pane
    state key-sharded over ``n_chips`` distinct devices, a 1/n share of
    the rows on each, memory in use grown on every one, records received
    by every one."""
    from jax.sharding import NamedSharding

    def in_use():
        return [(d.memory_stats() or {}).get("bytes_in_use")
                for d in devices[:n_chips]]

    before = in_use()

    def inspect(env, pane_counts) -> dict:
        ops = [op for op in env._driver._ops.values()
               if getattr(op, "mesh_plan", None) is not None]
        if len(ops) != 1:
            raise SmokeFailure(f"{name}: no mesh-planned window operator")
        op = ops[0]
        counts = op.state.counts
        shards = counts.addressable_shards
        devs = [s.device for s in shards]
        rows_per = [int(s.data.shape[0]) for s in shards]
        if (not isinstance(counts.sharding, NamedSharding)
                or len(set(devs)) != n_chips
                or any(d.platform != devices[0].platform for d in devs)
                or rows_per != [counts.shape[0] // n_chips] * n_chips):
            raise SmokeFailure(
                f"{name}: pane state is not 1/{n_chips} per device: "
                f"sharding={counts.sharding} devices={devs} "
                f"rows={rows_per}")
        grew = [None if b is None else a - b
                for a, b in zip(in_use(), before)]
        if any(g is not None and g <= 0 for g in grew):
            raise SmokeFailure(
                f"{name}: bytes_in_use did not grow on every device "
                f"(before {before}, growth {grew})")
        # records per device: the program's own count (what each device
        # received from the keyed exchange), which has to add up to the
        # reference's bids; every device has to have received some
        per_dev = op.exchange_stats()["records"]
        if (len(per_dev) != n_chips or int(per_dev.min()) <= 0
                or int(per_dev.sum()) != int(pane_counts.sum())):
            raise SmokeFailure(
                f"{name}: the exchange's records per device "
                f"{per_dev.tolist()} do not add up to the reference's "
                f"{int(pane_counts.sum())} bids on {n_chips} devices")
        return {"state_sharding": str(counts.sharding.spec),
                "state_devices": [str(d) for d in devs],
                "state_rows_per_device": rows_per,
                "bytes_in_use_grew": grew,
                "records_per_device": [int(x) for x in per_dev],
                "records_per_device_max_over_mean":
                    round(float(per_dev.max() / per_dev.mean()), 4)}

    return inspect


# -- main --------------------------------------------------------------------

def cache_entries(path: str) -> int:
    if not path or not os.path.isdir(path):
        return 0
    return sum(1 for f in os.listdir(path) if f.endswith("-cache"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: the host-fed Q5 over a four-chip mesh")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="same code at a tiny size on the CPU")
    ap.add_argument("--trace-dir", default="",
                    help="also run one traced host-fed job "
                         "(pipeline.profile-dir) and report its planes")
    args = ap.parse_args(argv)

    if args.cpu_rehearsal:
        # the one mode that selects a platform: it must not take the
        # chip from anyone, and it stands in for N chips with N virtual
        # CPU devices
        os.environ["JAX_PLATFORMS"] = "cpu"
        if args.chips > 1:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={args.chips}"
            ).strip()

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    want = "cpu" if args.cpu_rehearsal else "tpu"
    if platform != want or len(devices) < args.chips:
        print(f"chip_smoke: need {args.chips} {want} device(s), JAX found "
              f"{len(devices)} x {platform} ({devices[0].device_kind}); "
              f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}",
              file=sys.stderr)
        return 2

    import flink_tpu  # noqa: F401 — x64 + compile-cache placement
    from flink_tpu import native_codec

    if not native_codec.native_available():
        print("chip_smoke: the native codec did not build/load here:\n"
              f"{native_codec.unavailable_reason()}", file=sys.stderr)
        return 3
    log(f"{len(devices)} x {platform} ({devices[0].device_kind}); codec "
        f"{os.path.basename(native_codec.library_path())}")

    cache_dir = jax.config.jax_compilation_cache_dir
    entries_before = cache_entries(cache_dir)
    watch = CompileWatch()

    if args.cpu_rehearsal:
        # the committed confs cut to 2^13-record logical batches and
        # 8x64 slots; everything else as on the chip
        overrides = {"pipeline.microbatch-size": 1 << 13,
                     "state.num-key-shards": 8,
                     "state.slots-per-shard": 64}
        nexmark = NEXMARK_REHEARSAL
        n_batches, n_sum, n_trace = 3, 2, 6
    else:
        # >= 8 logical batches per Q5 plane; one 2^20 batch (10.5 s of
        # event time: full 10 s windows, ~2,700 bids on a hot key) for
        # the sum lane, whose every fire width is a ~45 s compile
        overrides = {}
        nexmark = NEXMARK
        n_batches, n_sum, n_trace = 8, 1, 16

    # every phase runs even after one failed — a chip call is too dear to
    # stop at the first finding — and any failure fails the smoke
    planes: dict = {}
    failures: dict = {}

    def phase(name, fn):
        try:
            planes[name] = fn()
        except Exception as e:  # noqa: BLE001 — reported, exit code 1
            failures[name] = (str(e) if isinstance(e, SmokeFailure)
                              else traceback.format_exc())
            log(f"{name}: FAILED — {failures[name]}")

    def q5(name, conf_file, extra=None, inspect=None):
        phase(name, lambda: q5_plane(
            name, conf_file, {**overrides, **(extra or {})}, nexmark,
            n_batches, watch, inspect))

    if args.chips == 1:
        q5("host_fed", "bench_q5_host_fed.conf")
        phase("sum_lane", lambda: sum_lane_plane(
            overrides, nexmark, n_sum, watch))
    else:
        # the same host-fed job on one chip, then over the mesh: one
        # process driving every chip, the two row sets identical
        mesh = f"host_fed_mesh{args.chips}"
        q5("host_fed", "bench_q5_host_fed.conf")
        q5(mesh, "bench_q5_host_fed.conf",
           extra={"cluster.mesh-devices": args.chips},
           inspect=mesh_inspector(mesh, args.chips, devices))
        if mesh in planes and "host_fed" in planes:
            same = (planes[mesh]["rows_sorted"]
                    == planes["host_fed"]["rows_sorted"])
            planes[mesh]["equals_one_chip"] = same
            if not same:
                failures[mesh] = "rows differ from the one-chip run's"
    if args.trace_dir:
        phase("trace", lambda: traced_run(
            args.trace_dir, overrides, nexmark, n_trace,
            want_device_plane=not args.cpu_rehearsal))
    trace = planes.pop("trace", None)
    for p in planes.values():
        p.pop("rows_sorted", None)
    if failures:
        print("chip_smoke: FAILED\n" + "\n".join(
            f"- {k}: {v}" for k, v in failures.items()), file=sys.stderr)
        return 1

    import jaxlib

    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = None
    stats = [d.memory_stats() or {} for d in devices[:args.chips]]
    # the report: one JSON line of what was seen, then — last — the
    # result line, whose keys the driver fixes and which carries no more
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    print(json.dumps({
        "report": "chip_smoke",
        "device": device,
        "rehearsal": args.cpu_rehearsal,
        "chips_used": args.chips,
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                     "libtpu": libtpu_version,
                     "python": sys.version.split()[0]},
        "jax_platforms_env": os.environ.get("JAX_PLATFORMS"),
        "native_codec": os.path.basename(native_codec.library_path()),
        "planes": planes,
        "trace": trace,
        "peak_bytes_in_use": [s.get("peak_bytes_in_use") for s in stats],
        "compile_cache": {
            "dir": cache_dir,
            "from_env": bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
            "entries_before": entries_before,
            "entries_after": cache_entries(cache_dir)},
        "elapsed_s": round(time.perf_counter() - _T0, 1),
    }))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
