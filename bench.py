"""Flagship benchmark: Nexmark Q5 (sliding hot items) END TO END.

Runs the real pipeline — Nexmark bid generator → fluent DataStream API →
driver loop → keyed sliding-window COUNT on device → host top-items →
sink — on whatever jax backend is live (the real TPU chip under the
driver; CPU elsewhere), and reports steady-state events/sec.

A short warmup job with identical operator configuration populates the
compile caches (kernels are module-level jits keyed on static config, so
jobs share compilations); the measured job then runs at steady state.

Prints ONE json line: {"metric", "value", "unit", "vs_baseline"}.

``vs_baseline`` divides by ASSUMED_FLINK_EVENTS_PER_SEC: single-node
Apache Flink with HeapKeyedStateBackend on Nexmark Q5 sustains roughly
2M events/s (order of magnitude from public Nexmark runs; the reference
repo publishes no numbers). The north-star target is 20x.
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

ASSUMED_FLINK_EVENTS_PER_SEC = 2_000_000.0

WINDOW_MS = 10_000
SLIDE_MS = 1_000

# Every bench env spreads this in: submit-time plan analysis is OFF so
# the measured clocks contain zero analyzer cost (analysis overhead is
# excluded from bench timings; the tier-1 dogfood gate separately keeps
# these pipelines/configs at zero findings).
BENCH_CONF = {"analysis.fail-on": "off"}


def _phase_summary(metrics: dict, wall_s: float) -> dict:
    """Per-trial phase breakdown, derived from the JobResult's
    profile.phase.* keys — driver.phase_breakdown() is the ONE shared
    accounting, so the artifact mirrors whatever phases it emits
    (hardcoding the list here would silently drop a future phase) —
    plus the throttle-wait share of batch wall."""
    pref = "profile.phase."
    ph = {k[len(pref):]: round(float(v), 3)
          for k, v in sorted(metrics.items()) if k.startswith(pref)}
    ph["wall_s"] = round(wall_s, 3)
    ph["throttle_share_pct"] = round(
        100.0 * ph.get("throttle", 0.0) / max(wall_s, 1e-9), 1)
    return ph


# -- committed job confs -----------------------------------------------------
# One conf builder per benched config; `job_confs()` instantiates each
# at its suite/headline parameters. The files under confs/ are
# GENERATED from this (`python bench.py --dump-confs confs`) and kept
# in lockstep by the tier-1 gate (tests/test_dataflow.py): staleness is
# a test failure, and every committed conf must cold-analyze clean
# (`python -m flink_tpu analyze confs/<f> --fail-on warn`).

def _q5_conf(batch_size: int, shards: int, slots: int,
             sub_batches: int) -> dict:
    return {**BENCH_CONF,
            "state.num-key-shards": shards,
            "state.slots-per-shard": slots,
            "pipeline.microbatch-size": batch_size,
            "pipeline.sub-batches": sub_batches}


def _q7_conf(batch_size: int) -> dict:
    return {**BENCH_CONF, "pipeline.microbatch-size": batch_size}


def _q8_conf(batch_size: int) -> dict:
    return {**BENCH_CONF, "pipeline.microbatch-size": batch_size,
            "state.num-key-shards": 128, "state.slots-per-shard": 1024}


def _wordcount_conf(batch_size: int) -> dict:
    return {**BENCH_CONF,
            "state.num-key-shards": 128, "state.slots-per-shard": 512,
            "pipeline.microbatch-size": batch_size,
            "pipeline.max-inflight-steps": 1}


def _log_producer_conf(batch_size: int) -> dict:
    return {**BENCH_CONF, "pipeline.microbatch-size": batch_size}


def _sessions_conf(batch_size: int) -> dict:
    return {**BENCH_CONF,
            "state.num-key-shards": 128, "state.slots-per-shard": 512,
            "pipeline.microbatch-size": batch_size,
            "pipeline.max-inflight-steps": 1}


def _q5_lsm_conf(batch_size: int) -> dict:
    # Q5 on the DISK state tier (ISSUE 17, flink_tpu/state/lsm.py): a
    # 1 MiB delta budget far below the key domain's footprint, so the
    # run exercises seal → compact → changelog-checkpoint end to end
    # rather than staying RAM-resident
    return {**BENCH_CONF,
            "state.num-key-shards": 128,
            "state.slots-per-shard": 256,
            "state.backend": "lsm",
            "state.memory-budget-bytes": 1 << 20,
            "pipeline.microbatch-size": batch_size,
            "pipeline.sub-batches": 1}


def _q5_backfill_conf(batch_size: int) -> dict:
    # the backfill-then-live consumer's conf (ISSUE 9): a consumer
    # group over a key-compacted topic — compaction keyed on the
    # unique event id, so the rewrite merges segments without dropping
    # rows and the committed output is comparable to a never-compacted
    # reference run row for row
    return {**BENCH_CONF,
            "state.num-key-shards": 128, "state.slots-per-shard": 256,
            "pipeline.microbatch-size": batch_size,
            "log.group.name": "q5-backfill",
            "log.compaction.key-field": "event_id",
            "log.compaction.min-segments": 1,
            # the perf-tier read/write knobs ARE part of the benched
            # config (ISSUE 13): group fsync on the producer, read
            # batches coalesced to the microbatch size, double-buffered
            # segment readahead (zero-copy decode is the default)
            "log.fsync-mode": "group",
            "log.read-batch-records": batch_size,
            "log.prefetch-segments": 1}


def job_confs() -> dict:
    """Every benched config's job conf at its committed suite/headline
    parameters, keyed by the confs/ file stem."""
    return {
        "bench_q5_host_fed": _q5_conf(Q5_BATCH, 128, 256, 1),
        "bench_q7": _q7_conf(1 << 18),
        "bench_q8": _q8_conf(1 << 18),
        "bench_wordcount": _wordcount_conf(1 << 20),
        "bench_wordcount_log_fed": _wordcount_conf(1 << 18),
        "bench_sessions": _sessions_conf(1 << 20),
        "bench_q5_backfill": _q5_backfill_conf(1 << 18),
        "bench_q5_lsm": _q5_lsm_conf(1 << 18),
    }


def render_conf(name: str, conf: dict) -> str:
    """`key: value` file body of one committed conf (the
    Configuration.from_file grammar; comments survive as lines the
    loader skips)."""
    lines = [f"# {name} — generated by `python bench.py --dump-confs "
             "confs`; do not edit (tier-1 staleness gate).",
             "# Cold-analyzed clean by tests/test_dataflow.py:",
             f"#   python -m flink_tpu analyze confs/{name}.conf "
             "--fail-on warn"]
    lines += [f"{k}: {conf[k]}" for k in sorted(conf)]
    return "\n".join(lines) + "\n"


def dump_confs(out_dir: str) -> None:
    os.makedirs(out_dir, exist_ok=True)
    for name, conf in job_confs().items():
        path = os.path.join(out_dir, f"{name}.conf")
        with open(path, "w", encoding="utf-8") as f:
            f.write(render_conf(name, conf))
        print(path)


def _counting_sink():
    """(cell, sink) counting emitted rows; tolerates empty batches."""
    from flink_tpu.api.sinks import FnSink

    cell = [0]

    def count(b):
        vals = list(b.values())
        if vals:
            cell[0] += len(vals[0])

    return cell, FnSink(count)


def run_q5(batch_size: int, n_batches: int, *, shards: int, slots: int,
           sub_batches: int = 1, profile_dir: str = "") -> dict:
    from flink_tpu.api.environment import StreamExecutionEnvironment
    from flink_tpu.config import Configuration
    from flink_tpu.nexmark.generator import NexmarkConfig, bid_stream
    from flink_tpu.nexmark.queries import q5_hot_items

    # events_per_ms=100 → one 131k batch spans ~1.3s of event time, so
    # 10s/1s sliding windows fire steadily throughout the run (the
    # steady-state regime Q5 measures, not a single end-of-input flush)
    cfg = NexmarkConfig(
        batch_size=batch_size, n_batches=n_batches,
        events_per_ms=100, num_active_auctions=10_000, hot_ratio=4)
    # sub-batch fire/emit decoupling: fires reach
    # the host at ~batch_wall/K cadence instead of riding the drain
    # behind one full logical-batch device step
    conf = _q5_conf(batch_size, shards, slots, sub_batches)
    if profile_dir:
        # per-op device trace of N warm steps (obs/profiling.py); the
        # summary rides JobResult.metrics["profile.trace_summary"]
        conf["pipeline.profile-dir"] = profile_dir
    env = StreamExecutionEnvironment(Configuration(conf))
    emitted, sink = _counting_sink()
    q5_hot_items(env, bid_stream(cfg), sink,
                 window_ms=WINDOW_MS, slide_ms=SLIDE_MS,
                 out_of_orderness_ms=1_000)
    res = env.execute("nexmark-q5")
    res.metrics["emitted"] = emitted[0]
    return res.metrics


# the Q5 job's batch (confs/bench_q5_host_fed.conf): 2^20 records, one
# device step per batch
Q5_BATCH = 1 << 20


def _q5_trial(batch, n_meas, sub_batches, profile_dir=""):
    start = time.perf_counter()
    metrics = run_q5(batch, n_meas, shards=128, slots=256,
                     sub_batches=sub_batches, profile_dir=profile_dir)
    elapsed = time.perf_counter() - start
    assert metrics["emitted"] > 0, "q5 emitted nothing"
    assert metrics.get("records_dropped_full", 0) == 0, "q5 dropped records"
    trial = {
        "events_per_sec": round(batch * n_meas / elapsed),
        "batch": batch,
        "sub_batches": sub_batches,
        "p50_latency_ms": round(metrics.get("driver.emit_latency_ms.p50", 0.0), 1),
        "p90_latency_ms": round(metrics.get("driver.emit_latency_ms.p90", 0.0), 1),
        "p99_latency_ms": round(metrics.get("driver.emit_latency_ms.p99", 0.0), 1),
        "max_latency_ms": round(metrics.get("driver.emit_latency_ms.max", 0.0), 1),
        # per-phase wall attribution (dispatch/throttle/drain/advance/
        # fire) — the win is attributed, not asserted
        "phase_breakdown": _phase_summary(metrics, elapsed),
    }
    return trial, metrics


def _profile_top_ops(batch, sub_batches, n_batches=16):
    """One short PROFILED Q5 run (pipeline.profile-dir): returns the
    per-op device-time summary so the bench ARTIFACT itself names the
    expensive ops — never fails the bench."""
    import tempfile

    try:
        d = tempfile.mkdtemp(prefix="flink-tpu-bench-prof-")
        _, metrics = _q5_trial(batch, n_batches, sub_batches,
                               profile_dir=d)
        summary = metrics.get("profile.trace_summary") or {}
        if summary.get("error"):
            return {"error": summary["error"]}
        planes = summary.get("planes", [])
        device = [p for p in planes if p.get("device")] or planes[:1]
        return {
            "trace_dir": d,
            "steps": summary.get("steps"),
            "window_wall_s": summary.get("window_wall_s"),
            "top_ops": [
                {"plane": p["plane"], "ops": p["ops"][:10]}
                for p in device[:2]],
        }
    except Exception as e:  # noqa: BLE001 — profiling is best-effort
        return {"error": f"{type(e).__name__}: {e}"}


def main() -> None:
    # the host-fed Q5 job: every record is materialized on the host and
    # pays keying + h2d + dispatch
    batch = Q5_BATCH
    sub = 1
    # warmup: same operator configs → shared compiled kernels (covers
    # apply, steady fires, ring growth + remap, catch-up fires, clear,
    # emit-ring drain)
    run_q5(batch, 12, shards=128, slots=256, sub_batches=sub)

    # long enough that the fixed end-of-input flush is amortized — the
    # metric is STEADY-STATE throughput, which is what Nexmark measures.
    # THREE trials: the headline is the MEDIAN, and the artifact carries
    # every trial's throughput + latency histogram AND sub-batch config
    # so run-to-run spread and the benched config are part of the
    # claim, not folklore.
    n_meas = 48
    trials = []
    for _ in range(3):
        trial, _ = _q5_trial(batch, n_meas, sub)
        trials.append(trial)
    rates = sorted(t["events_per_sec"] for t in trials)
    eps = rates[len(rates) // 2]
    med = next(t for t in trials if t["events_per_sec"] == eps)
    print(json.dumps({
        "metric": "nexmark_q5_hot_items_end_to_end_events_per_sec",
        "value": eps,
        "unit": "events/sec/chip",
        # vs an ASSUMED single-node CPU-Flink baseline (no network in
        # this environment to measure the real one)
        "vs_baseline": round(eps / ASSUMED_FLINK_EVENTS_PER_SEC, 3),
        "baseline_assumed": True,
        "batch": batch,
        "sub_batches": sub,
        "throughput_min": rates[0],
        "throughput_max": rates[-1],
        "spread_pct": round((rates[-1] - rates[0]) / eps * 100, 1),
        "trials": trials,
        # fire-dispatch → sink-delivery latency of fired windows (the
        # latency-marker analogue), from the
        # median-throughput trial. Samples are stamped per fire cohort
        # at actual host-visibility (drain fetch), not at queue-item
        # delivery — see driver._note_ring_latency.
        "p99_latency_ms": med["p99_latency_ms"],
        "p50_latency_ms": med["p50_latency_ms"],
        # the median trial's per-phase wall attribution
        # (throttle/drain/advance vs dispatch/fire)
        "phase_breakdown": med["phase_breakdown"],
        # per-op device-time summary from one short profiled run
        # (jax.profiler.trace via pipeline.profile-dir;
        # obs/profiling.py)
        "profile_top_ops": _profile_top_ops(batch, sub),
    }))


def run_q7(batch_size: int, n_batches: int) -> float:
    """Q7 highest bid — the windowAll/global-reduce shape (host pane
    fold, no funnel). Returns events/sec."""
    from flink_tpu.api.environment import StreamExecutionEnvironment
    from flink_tpu.config import Configuration
    from flink_tpu.nexmark.generator import NexmarkConfig, bid_stream
    from flink_tpu.nexmark.queries import q7_highest_bid

    cfg = NexmarkConfig(batch_size=batch_size, n_batches=n_batches,
                        events_per_ms=100, num_active_auctions=10_000,
                        hot_ratio=4)
    env = StreamExecutionEnvironment(Configuration(
        _q7_conf(batch_size)))
    n, sink = _counting_sink()
    q7_highest_bid(env, bid_stream(cfg), sink, window_ms=10_000,
                   out_of_orderness_ms=1_000)
    t0 = time.perf_counter()
    env.execute("nexmark-q7")
    el = time.perf_counter() - t0
    assert n[0] > 0, "q7 emitted nothing"
    return batch_size * n_batches / el


def run_q8(batch_size: int, n_batches: int) -> float:
    """Q8 new users — exact pairs windowed join. Returns events/sec
    over BOTH inputs."""
    from flink_tpu.api.environment import StreamExecutionEnvironment
    from flink_tpu.config import Configuration
    from flink_tpu.nexmark.generator import (
        NexmarkConfig, auction_stream, person_stream)
    from flink_tpu.nexmark.queries import q8_monitor_new_users

    # num_active_people=100k is THE knob that sets join-key cardinality
    # (person ids and sellers both derive from it): it keeps
    # per-(key, window) multiplicities ~O(1) — the bench generator
    # re-emits ids while real person registrations are one-time — so
    # the EXACT pair join measures throughput, not a synthetic
    # cross-product explosion
    cfg = NexmarkConfig(batch_size=batch_size, n_batches=n_batches,
                        events_per_ms=100, num_active_people=100_000)
    env = StreamExecutionEnvironment(Configuration(
        _q8_conf(batch_size)))
    n, sink = _counting_sink()
    # 1s windows: the bench generator re-emits person ids every batch
    # (real registrations are one-time), so a 10s window would square
    # into a pair explosion the operator rightly refuses; 1s keeps
    # per-(key, window) multiplicities realistic for the join bench
    q8_monitor_new_users(env, person_stream(cfg), auction_stream(cfg),
                         sink, window_ms=1_000, out_of_orderness_ms=1_000)
    t0 = time.perf_counter()
    env.execute("nexmark-q8")
    el = time.perf_counter() - t0
    assert n[0] > 0, "q8 emitted nothing"
    return 2 * batch_size * n_batches / el


def run_wordcount(batch_size: int, n_batches: int) -> float:
    """BASELINE.json config #0: streaming WordCount, 1s tumbling count
    window. The source generates pre-tokenized word-id batches (the C
    tokenizer's output shape — `bench_micro.py` measures the raw
    tokenizer at ~450 MB/s separately); zipf-ish skew over a 30k-word
    vocabulary. Returns events(words)/sec."""
    from flink_tpu.api.environment import StreamExecutionEnvironment
    from flink_tpu.api.sources import GeneratorSource
    from flink_tpu.api.windowing import TumblingEventTimeWindows
    from flink_tpu.config import Configuration
    from flink_tpu.time.watermarks import WatermarkStrategy

    vocab = 30_000

    def gen(split, i):
        if i >= n_batches:
            return None
        rng = np.random.default_rng(i)
        # zipf-ish: squared uniform concentrates mass on low ids
        u = rng.random(batch_size)
        words = (u * u * vocab).astype(np.int64)
        ts = (i * batch_size + np.arange(batch_size, dtype=np.int64)) // 100
        return ({"word": words}, ts)

    env = StreamExecutionEnvironment(Configuration(
        _wordcount_conf(batch_size)))
    n, sink = _counting_sink()
    (env.from_source(GeneratorSource(gen),
                     WatermarkStrategy.for_bounded_out_of_orderness(0))
        .key_by("word")
        .window(TumblingEventTimeWindows.of(1000))
        .count()
        .add_sink(sink))
    t0 = time.perf_counter()
    env.execute("wordcount")
    el = time.perf_counter() - t0
    assert n[0] > 0, "wordcount emitted nothing"
    return batch_size * n_batches / el


def run_wordcount_log_fed(batch_size: int, n_batches: int) -> float:
    """Log-fed WordCount — the host→device INGEST/TRANSPORT plane's
    number. A producer pass commits the word stream into an embedded
    durable-log topic (flink_tpu/log/, sealed columnar segments +
    commit markers);
    the MEASURED pass replays the topic's committed offsets through
    LogSource, so every record pays deserialization + host keying +
    h2d + dispatch — the path a job chained behind another job's
    LogSink actually runs. Returns consumer events(words)/sec; the
    producer/commit pass is setup, not part of the clock."""
    import shutil
    import tempfile

    from flink_tpu.api.environment import StreamExecutionEnvironment
    from flink_tpu.api.sources import GeneratorSource
    from flink_tpu.api.windowing import TumblingEventTimeWindows
    from flink_tpu.config import Configuration
    from flink_tpu.log import LogSink, LogSource
    from flink_tpu.time.watermarks import WatermarkStrategy

    vocab = 30_000

    def gen(split, i):
        if i >= n_batches:
            return None
        rng = np.random.default_rng(i)
        u = rng.random(batch_size)
        words = (u * u * vocab).astype(np.int64)
        ts = ((i * batch_size
               + np.arange(batch_size, dtype=np.int64)) // 100)
        return ({"word": words, "ts_ms": ts}, ts)

    root = tempfile.mkdtemp(prefix="flink-tpu-bench-log-")
    topic = os.path.join(root, "wordcount")
    try:
        penv = StreamExecutionEnvironment(Configuration(
            _log_producer_conf(batch_size)))
        penv.from_source(GeneratorSource(gen)).add_sink(
            LogSink(topic, segment_records=batch_size))
        penv.execute("wordcount-log-producer")

        env = StreamExecutionEnvironment(Configuration(
            _wordcount_conf(batch_size)))
        n, sink = _counting_sink()
        (env.from_source(LogSource(topic, ts_field="ts_ms"),
                         WatermarkStrategy.for_bounded_out_of_orderness(0))
            .key_by("word")
            .window(TumblingEventTimeWindows.of(1000))
            .count()
            .add_sink(sink))
        t0 = time.perf_counter()
        env.execute("wordcount-log-consumer")
        el = time.perf_counter() - t0
        assert n[0] > 0, "log-fed wordcount emitted nothing"
        return batch_size * n_batches / el
    finally:
        shutil.rmtree(root, ignore_errors=True)


def run_q5_backfill(batch_size: int = 1 << 18, n_hist: int = 8,
                    n_live: int = 4,
                    artifact: "str | None" = None) -> None:
    """Backfill-then-live Q5 (ISSUE 9, ROADMAP item 4's day-scale
    replay shape): a producer commits bid HISTORY into a durable-log
    topic, the topic is KEY-COMPACTED (keyed on the unique event id —
    a segment-merging rewrite that drops nothing, so output is
    comparable row for row), then a fresh consumer-group job
    BOOTSTRAPS from the compacted history; a second producer pass
    appends the LIVE tail and the same group CUTS OVER to it (resuming
    past its committed offsets — the consumer-generation path). One
    JSON line reports ev/s for both phases.

    Correctness rides in the artifact: the identical two-phase
    consumer runs against an identical NEVER-COMPACTED topic and the
    committed outputs must match exactly (``matches_reference``) —
    the acceptance contract, measured every round, not asserted
    once."""
    import shutil
    import tempfile

    from flink_tpu.api.environment import StreamExecutionEnvironment
    from flink_tpu.api.sinks import CollectSink
    from flink_tpu.api.sources import GeneratorSource
    from flink_tpu.config import Configuration
    from flink_tpu.log import Compactor, LogSink, LogSource
    from flink_tpu.nexmark.queries import q5_hot_items

    def bid_gen(n_batches, start_batch=0):
        def gen(split, i):
            if i >= n_batches:
                return None
            j = start_batch + i
            rng = np.random.default_rng(9200 + j)
            auction = rng.integers(0, 10_000, batch_size).astype(np.int64)
            price = rng.integers(100, 10_000, batch_size).astype(np.int64)
            eid = j * batch_size + np.arange(batch_size, dtype=np.int64)
            ts = eid // 100  # 100 events/ms — steady sliding fires
            return ({"auction": auction, "price": price,
                     "event_id": eid, "ts_ms": ts}, ts)
        return gen

    def produce(topic, n_batches, start_batch=0):
        env = StreamExecutionEnvironment(Configuration(
            _log_producer_conf(batch_size)))
        env.from_source(GeneratorSource(bid_gen(n_batches, start_batch)
                                        )).add_sink(
            LogSink(topic, key_field="event_id", partitions=2))
        env.execute("q5-backfill-producer")

    def consume(topic, phase, group=None):
        # the MEASURED run uses the committed conf's group verbatim
        # (confs/bench_q5_backfill.conf is the record of the benched
        # parameters); only the reference topic overrides it
        conf = dict(_q5_backfill_conf(batch_size))
        if group is not None:
            conf["log.group.name"] = group
        group = conf["log.group.name"]
        env = StreamExecutionEnvironment(Configuration(conf))
        sink = CollectSink()
        q5_hot_items(env, LogSource(topic, ts_field="ts_ms",
                                    group=group),
                     sink, window_ms=WINDOW_MS, slide_ms=SLIDE_MS,
                     out_of_orderness_ms=1_000)
        t0 = time.perf_counter()
        res = env.execute(f"q5-{phase}-{group}")
        el = time.perf_counter() - t0
        rows = sorted((int(r["window_end"]), int(r["auction"]),
                       int(r["bid_count"])) for r in sink.rows)
        return rows, int(res.metrics.get("records_in", 0)), el

    root = tempfile.mkdtemp(prefix="flink-tpu-bench-backfill-")
    topic = os.path.join(root, "bids")
    ref_topic = os.path.join(root, "bids-ref")
    try:
        for t in (topic, ref_topic):
            produce(t, n_hist)
        comp = Compactor(topic, min_segments=1).compact()
        assert comp["gen"] == 1, comp

        # phase 1: bootstrap from compacted history (and the
        # never-compacted reference — same two-phase shape)
        rows_b, n_b, el_b = consume(topic, "backfill")
        ref_b, ref_nb, _ = consume(ref_topic, "backfill", group="ref")
        assert n_b == n_hist * batch_size, (n_b, n_hist * batch_size)

        # the live tail lands, the SAME groups cut over past their
        # committed offsets
        for t in (topic, ref_topic):
            produce(t, n_live, start_batch=n_hist)
        rows_l, n_l, el_l = consume(topic, "live")
        ref_l, ref_nl, _ = consume(ref_topic, "live", group="ref")
        assert n_l == n_live * batch_size, (n_l, n_live * batch_size)

        matches = (rows_b == ref_b and rows_l == ref_l
                   and n_b == ref_nb and n_l == ref_nl)
        line = {
            "metric": "nexmark_q5_backfill_then_live_events_per_sec",
            "unit": "events/sec/chip",
            "value": round(n_b / el_b),  # headline = the backfill
            "backfill_events_per_sec": round(n_b / el_b),
            "live_events_per_sec": round(n_l / el_l),
            "batch": batch_size,
            "history_batches": n_hist,
            "live_batches": n_live,
            # the perf-tier knobs this number was measured under
            # (ISSUE 13 — the conf record is confs/bench_q5_backfill)
            "log_tier": {"fsync_mode": "group", "zero_copy": True,
                         "read_batch_records": batch_size,
                         "prefetch_segments": 1},
            # the ISSUE 13 acceptance bar: >= 3x the r09-committed
            # backfill number (~104k ev/s on this container class) —
            # only meaningful at the committed conf's shape, so a
            # differently-parameterized run carries no verdict
            **({"target": ">= 312000 ev/s backfill (3x the r09 "
                          "artifact)",
                "target_met": (n_b / el_b) >= 312_000}
               if (batch_size, n_hist, n_live) == (1 << 18, 8, 4)
               else {"target": "n/a (non-default shape; the bar is "
                               "defined at batch=2^18, hist=8, "
                               "live=4)"}),
            "compaction": {"gen": comp["gen"],
                           "rows_in": sum(
                               e["rows_in"]
                               for e in comp["partitions"].values()),
                           "rows_out": sum(
                               e["rows_out"]
                               for e in comp["partitions"].values())},
            # the acceptance contract: committed output equals the
            # never-compacted reference run's, both phases
            "matches_reference": matches,
        }
        print(json.dumps(line))
        if artifact:
            with open(artifact, "w", encoding="utf-8") as f:
                json.dump(line, f, indent=1)
            print(f"# backfill artifact -> {artifact}")
        assert matches, "backfill-then-live output diverged from the " \
                        "never-compacted reference"
    finally:
        shutil.rmtree(root, ignore_errors=True)


def run_sessions(batch_size: int, n_batches: int,
                 host_parallelism: "int | None" = None) -> float:
    """BASELINE.json config #4 shape: session-window clickstream
    aggregation with event time + allowed lateness (the Criteo-style
    workload: many users, bursty activity separated by gaps). Returns
    events/sec. ``host_parallelism`` pins host.parallelism for the
    thread-count sweep; None = the declared default."""
    from flink_tpu.api.environment import StreamExecutionEnvironment
    from flink_tpu.api.sources import GeneratorSource
    from flink_tpu.api.windowing import EventTimeSessionWindows
    from flink_tpu.config import Configuration
    from flink_tpu.time.watermarks import WatermarkStrategy

    users = 50_000

    def gen(split, i):
        if i >= n_batches:
            return None
        rng = np.random.default_rng(i)
        user = rng.integers(0, users, batch_size).astype(np.int64)
        base = i * batch_size // 100
        # bursty: activity clustered inside 1s bursts, 2% of records
        # arrive up to 3s late (inside the allowed lateness)
        ts = base + rng.integers(0, 1000, batch_size)
        late = rng.random(batch_size) < 0.02
        ts = np.where(late, np.maximum(ts - 3000, 0), ts).astype(np.int64)
        return ({"user": user}, ts)

    conf = _sessions_conf(batch_size)
    if host_parallelism is not None:
        conf["host.parallelism"] = host_parallelism
    env = StreamExecutionEnvironment(Configuration(conf))
    n, sink = _counting_sink()
    (env.from_source(GeneratorSource(gen),
                     WatermarkStrategy.for_bounded_out_of_orderness(1000))
        .key_by("user")
        .window(EventTimeSessionWindows.with_gap(500))
        .allowed_lateness(5_000)
        .count()
        .add_sink(sink))
    t0 = time.perf_counter()
    env.execute("sessions")
    el = time.perf_counter() - t0
    assert n[0] > 0, "sessions emitted nothing"
    return batch_size * n_batches / el


def suite() -> None:
    """Full bench suite (`python bench.py --suite`): every implemented
    BASELINE.json config — one JSON line per config (the driver's
    graded metric remains the default Q5 single line)."""
    # per-config batch sizes, hand-picked per workload (bigger batches
    # amortize per-step overheads until a config-specific ceiling;
    # where that ceiling is on the current chip: not measured)
    run_wordcount(1 << 20, 4)  # warmup
    eps0 = run_wordcount(1 << 20, 24)
    print(json.dumps({"metric": "wordcount_tumbling_1s_events_per_sec",
                      "value": round(eps0), "unit": "events/sec/chip"}))
    run_q7(1 << 18, 4)  # warmup
    eps7 = run_q7(1 << 18, 24)
    print(json.dumps({"metric": "nexmark_q7_highest_bid_events_per_sec",
                      "value": round(eps7), "unit": "events/sec/chip"}))
    run_q8(1 << 18, 4)  # warmup
    eps8 = run_q8(1 << 18, 24)
    print(json.dumps({"metric": "nexmark_q8_new_users_events_per_sec",
                      "value": round(eps8), "unit": "events/sec/chip"}))
    run_sessions(1 << 20, 4)  # warmup
    eps4 = run_sessions(1 << 20, 12)
    print(json.dumps({"metric": "session_clickstream_events_per_sec",
                      "value": round(eps4), "unit": "events/sec/chip"}))
    # log-fed WordCount: the job-chaining ingest plane (durable-log
    # replay → host keying → h2d → dispatch): a regression in
    # columnar deserialization, LogSource replay, or the h2d path
    # lands here every round.
    run_wordcount_log_fed(1 << 18, 4)  # warmup
    epsl = run_wordcount_log_fed(1 << 18, 24)
    print(json.dumps({"metric": "wordcount_log_fed_events_per_sec",
                      "value": round(epsl), "unit": "events/sec/chip"}))
    # backfill-then-live Q5: the message-bus tier's permanent line — a
    # consumer group bootstraps from key-compacted history and cuts
    # over to the live tail, with the never-compacted reference match
    # verified inside the artifact (ISSUE 9 / ROADMAP item 4)
    run_q5_backfill(1 << 18, n_hist=8, n_live=4)
    main()  # host-fed Q5 last


def session_bench_build(env) -> None:
    """Entry point of the ``--concurrent-jobs`` bench jobs — the
    session cluster's runner imports it by name (``bench:
    session_bench_build``) like any deployed job. Same sessions
    workload as :func:`run_sessions`, parameterized through ``test.*``
    conf keys so every submission builds the identical pipeline."""
    from flink_tpu.api.sinks import FnSink
    from flink_tpu.api.sources import GeneratorSource
    from flink_tpu.api.windowing import EventTimeSessionWindows
    from flink_tpu.time.watermarks import WatermarkStrategy

    batch_size = int(env.config.get_raw("test.batch-size", 1 << 18))
    n_batches = int(env.config.get_raw("test.n-batches", 8))
    users = int(env.config.get_raw("test.users", 50_000))

    def gen(split, i):
        if i >= n_batches:
            return None
        rng = np.random.default_rng(i)
        user = rng.integers(0, users, batch_size).astype(np.int64)
        base = i * batch_size // 100
        ts = base + rng.integers(0, 1000, batch_size)
        late = rng.random(batch_size) < 0.02
        ts = np.where(late, np.maximum(ts - 3000, 0), ts).astype(np.int64)
        return ({"user": user}, ts)

    (env.from_source(GeneratorSource(gen),
                     WatermarkStrategy.for_bounded_out_of_orderness(1000))
        .key_by("user")
        .window(EventTimeSessionWindows.with_gap(500))
        .allowed_lateness(5_000)
        .count()
        .add_sink(FnSink(lambda b: None)))


def concurrent_jobs_bench(k: int, batch_size: int = 1 << 18,
                          n_batches: int = 8) -> None:
    """``python bench.py --concurrent-jobs K``: K identical sessions
    jobs through ONE session cluster (runtime/session.py) on one
    shared runner, vs a single job through the same cluster — the
    multi-tenant throughput artifact of ROADMAP item 3. Per-job and
    aggregate ev/s are measured from the dispatcher's own lifecycle
    stamps (first deploy → terminal), so the clocked path includes the
    real admission/deploy plane.

    CORE-COUNT GUARD (the ``--host-parallelism`` pattern): the ≥1.5×
    aggregate target presumes the CHIP sits partly idle under one job
    (idle share on the current chip: not measured) — K co-resident jobs
    overlap into the idle part. On a CPU host with fewer than 2K cores
    the K jobs are compute-bound on the SAME cores, so the ratio
    measures scheduler contention, not the subsystem; such hosts get
    an explicit SKIPPED line for the target while the measured numbers
    still print (the measurement path itself runs everywhere)."""
    from flink_tpu.config import Configuration
    from flink_tpu.runtime.session import LocalSessionCluster

    if k < 1:
        raise SystemExit("--concurrent-jobs needs a count >= 1")
    events = batch_size * n_batches
    job_conf = {
        **_sessions_conf(batch_size),
        "test.batch-size": batch_size,
        "test.n-batches": n_batches,
    }
    cluster_conf = Configuration({
        "heartbeat.interval": "500ms",
        "session.runner-slots": max(4, k),
        "session.max-jobs": max(8, k),
        "session.autoscale": False,  # fixed local fleet: no scaling noise
    })

    def run_one(cluster, job_id):
        r = cluster.submit("bench:session_bench_build", config=job_conf,
                           job_id=job_id)
        assert r.get("admitted"), r
        state = cluster.wait(job_id, timeout=600)
        assert state == "FINISHED", (job_id, state)
        j = cluster.dispatcher.jobs[job_id]
        return j.started_at, j.finished_at

    with LocalSessionCluster(cluster_conf, runners=1) as cluster:
        run_one(cluster, "warmup")  # shared compiled kernels
        s0, f0 = run_one(cluster, "single")
        single_eps = events / (f0 - s0)
        ids = [f"conc-{i}" for i in range(k)]
        for jid in ids:
            r = cluster.submit("bench:session_bench_build",
                               config=job_conf, job_id=jid)
            assert r.get("admitted"), r
        spans = []
        for jid in ids:
            state = cluster.wait(jid, timeout=900)
            assert state == "FINISHED", (jid, state)
            j = cluster.dispatcher.jobs[jid]
            spans.append((j.started_at, j.finished_at))
    per_job = [events / (f - s) for s, f in spans]
    agg_wall = max(f for _, f in spans) - min(s for s, _ in spans)
    agg_eps = k * events / agg_wall
    ratio = agg_eps / single_eps
    cores = os.cpu_count() or 1
    required = 2 * k
    artifact = {
        "metric": "session_cluster_concurrent_jobs_events_per_sec",
        "unit": "events/sec/chip",
        "jobs": k,
        "batch": batch_size,
        "n_batches": n_batches,
        "single_job_events_per_sec": round(single_eps),
        "per_job_events_per_sec": [round(x) for x in per_job],
        "aggregate_events_per_sec": round(agg_eps),
        "aggregate_ratio": round(ratio, 3),
        "cores": cores,
    }
    if cores < required:
        print(json.dumps({
            "metric": "session_cluster_concurrent_jobs_ratio",
            "skipped": "insufficient-cores",
            "cores": cores,
            "required_cores": required,
            "detail": "the >=1.5x aggregate target presumes the chip "
                      "sits partly idle under one job; on a "
                      f"{cores}-core CPU host {k} "
                      "concurrent CPU-bound jobs share the same cores, "
                      "so the ratio measures contention, not the "
                      "subsystem — re-run on the chip host"}))
    else:
        artifact["target"] = 1.5
        artifact["target_met"] = ratio >= 1.5
    print(json.dumps(artifact))


def host_parallelism_sweep(spec: str) -> None:
    """`python bench.py --host-parallelism 1,2,4,8`: the
    thread-count sweep on the sessions config (#4) — one JSON line per
    worker count, same generator/batch shape as the suite's sessions
    line. The PR-notes win claim is the ratio AT THE DECLARED DEFAULT
    (min(4, os.cpu_count())), never the best point of the sweep.

    CORE-COUNT GUARD (ROADMAP carry-over): the
    ≥1.25× @W=4 target is only MEASURABLE on a host with ≥ 4 physical
    cores — on fewer, W=4 is pure oversubscription and the sweep would
    print a silent parity-or-worse number that reads like a subsystem
    regression. Such hosts get an explicit SKIPPED line instead."""
    ws = [int(x) for x in spec.split(",") if x.strip()]
    if not ws:
        raise SystemExit("--host-parallelism needs a list, e.g. 1,2,4,8")
    cores = os.cpu_count() or 1
    over = [w for w in ws if w > cores]
    if cores < 4 and over:
        # only the oversubscribed points are meaningless — measure the
        # w <= cores points normally (they ARE this host's subsystem)
        print(json.dumps({
            "metric": "session_clickstream_host_parallelism_sweep",
            "skipped": "insufficient-cores",
            "skipped_points": over,
            "cores": cores,
            "required_cores": 4,
            "detail": "the >=1.25x @W=4 validation "
                      "needs >=4 cores (os.cpu_count; SMT threads "
                      "inflate this — prefer physical-core hosts); "
                      "W>cores would print oversubscription, not the "
                      "subsystem — re-run on the chip host"}))
        ws = [w for w in ws if w <= cores]
        if not ws:
            return
    run_sessions(1 << 20, 4)  # warmup (shared compiled kernels)
    by_w = {}
    for w in ws:
        eps = run_sessions(1 << 20, 12, host_parallelism=w)
        by_w[w] = eps
        print(json.dumps({
            "metric": "session_clickstream_events_per_sec",
            "host_parallelism": w,
            "value": round(eps), "unit": "events/sec/chip"}))
    if 1 in by_w and 4 in by_w:
        # the carried-over target line (ROADMAP item: ≥1.25× @W=4,
        # within-run ratio so link/host weather cancels)
        ratio = by_w[4] / by_w[1]
        print(json.dumps({
            "metric": "session_clickstream_host_parallelism_ratio_w4",
            "value": round(ratio, 3),
            "target": 1.25,
            "target_met": ratio >= 1.25,
            "cores": cores}))


def rescale_bench_build(env) -> None:
    """Entry point of the ``--rescale-at-batch`` bench job — the
    spawned runner imports it by name (``bench:rescale_bench_build``)
    from the repo root, the same "job jar" contract as the deployed
    session bench. The Q5 per-auction count plane (bid stream →
    keyBy(auction) → sliding COUNT → file-backed 2PC sink, one sink
    directory per process) — the plane whose committed rows stay
    byte-identical across a process-level rescale cut."""
    import dataclasses

    from flink_tpu.api.sinks import FileTransactionalSink
    from flink_tpu.api.windowing import SlidingEventTimeWindows
    from flink_tpu.nexmark.generator import NexmarkConfig, bid_stream
    from flink_tpu.time.watermarks import WatermarkStrategy

    n_batches = int(env.config.get_raw("test.n-batches", 48))
    batch_size = int(env.config.get_raw("test.batch-size", 1 << 11))
    sleep_ms = int(env.config.get_raw("test.batch-sleep-ms", 0))
    sink_dir = env.config.get_raw("test.sink-dir")
    assert sink_dir, "test.sink-dir must be set"
    pid = int(env.config.get_raw("cluster.process-id", 0))

    # events_per_ms=4 stretches event time so a short run spans many
    # slide panes; 64 active auctions keep every shard's live key set
    # well under slots-per-shard at num-key-shards=8
    cfg = NexmarkConfig(batch_size=batch_size, n_batches=n_batches,
                        n_splits=2, events_per_ms=4,
                        num_active_auctions=64, num_active_people=32)
    src = bid_stream(cfg)
    inner = src.gen

    def gen(split, i):
        b = inner(split, i)
        if b is not None and sleep_ms:
            # paced ingest: the run must still be LIVE when the cut
            # lands (an instant run would finish before the savepoint)
            time.sleep(sleep_ms / 1000.0)
        return b

    stream = env.from_source(
        dataclasses.replace(src, gen=gen),
        WatermarkStrategy.for_bounded_out_of_orderness(1000))
    (stream.key_by("auction")
           .window(SlidingEventTimeWindows.of(2_000, 1_000))
           .count()
           .add_sink(FileTransactionalSink(f"{sink_dir}-p{pid}")))


def rescale_bench(at_batch: int, to_procs: int, *,
                  batch_size: int = 1 << 11, n_batches: int = 48,
                  artifact: "str | None" = None) -> None:
    """``python bench.py --rescale-at-batch B --rescale-to N``: a LIVE
    process-level rescale on the Q5 count plane (ROADMAP item 3 /
    ISSUE 16). One coordinator + N single-device runner processes; the
    job runs at 1 process until ~batch B of ingested progress, then
    ``rescale_job`` cuts it over to N processes (savepoint-set barrier
    → key-group repartition → redeploy). The artifact reports
    time-to-rescale (the coordinator's own arm→redeploy histogram) and
    the ingest rate on each side of the cut, and asserts the
    exactly-once invariant on the committed output (no (key, window)
    row committed twice across the cut).

    CORE-COUNT GUARD (the ``--concurrent-jobs`` pattern): the
    post/pre-cut rate ratio only reflects the SUBSYSTEM when the host
    can actually run N runner processes side by side — on fewer than
    2N+1 cores the post-cut processes contend for the same cores and
    the ratio measures the scheduler, so such hosts get an explicit
    SKIPPED line for the ratio while time-to-rescale (a control-plane
    number, not compute-bound) still prints everywhere.

    ONE PROCESS PER CHIP: a chip belongs to one process, so N runner
    processes on one chip host cannot each have it. The runners here
    are forced onto the CPU by design (``JAX_PLATFORMS=cpu``) and the
    line says so (``runner_platform``): its rates are CPU-runner
    rates, never events/sec/chip. This parent never initializes a JAX
    backend."""
    import shutil
    import subprocess
    import sys as _sys
    import tempfile

    from flink_tpu.api.sinks import FileTransactionalSink
    from flink_tpu.config import Configuration
    from flink_tpu.runtime.coordinator import JobCoordinator
    from flink_tpu.runtime.rpc import RpcServer

    shards = 8
    if at_batch < 1 or at_batch >= n_batches:
        raise SystemExit(f"--rescale-at-batch must be in [1, "
                         f"{n_batches - 1}] (n-batches={n_batches})")
    if to_procs < 1 or shards % to_procs != 0:
        raise SystemExit(f"--rescale-to must divide the key-shard "
                         f"count ({shards}): 1, 2, 4 or 8")

    repo = os.path.dirname(os.path.abspath(__file__))

    def spawn(port, rid):
        env = dict(os.environ)
        env["PYTHONPATH"] = repo
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("XLA_FLAGS", None)  # single-CPU-device runner
        return subprocess.Popen(
            [_sys.executable, "-m", "flink_tpu.runtime.runner",
             "--coordinator", f"127.0.0.1:{port}", "--runner-id", rid],
            env=env, cwd=repo,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT)

    def wait(pred, timeout, what):
        deadline = time.time() + timeout
        while time.time() < deadline:
            if pred():
                return
            time.sleep(0.1)
        raise TimeoutError(f"timed out waiting for {what}")

    tmp = tempfile.mkdtemp(prefix="bench-rescale-")
    sink_dir = os.path.join(tmp, "sink")
    coord = JobCoordinator(Configuration({
        "heartbeat.interval": "300ms",
        "heartbeat.timeout": "8s",
        "restart-strategy.type": "fixed-delay",
        "restart-strategy.fixed-delay.attempts": 6,
        "restart-strategy.fixed-delay.delay": "100ms",
    }))
    srv = RpcServer(coord)
    procs = []
    events_total = batch_size * n_batches * 2  # n_splits=2
    try:
        for i in range(to_procs):
            procs.append(spawn(srv.port, f"bench-r{i}"))
        wait(lambda: len(coord.runners) == to_procs, 90,
             "runners registered")
        t_submit = time.perf_counter()
        coord.rpc_submit_job(
            "bench-rescale", entry="bench:rescale_bench_build",
            config={
                "test.n-batches": n_batches,
                "test.batch-size": batch_size,
                "test.batch-sleep-ms": 60,
                "test.sink-dir": sink_dir,
                "execution.checkpointing.dir": os.path.join(tmp, "chk"),
                "execution.checkpointing.interval": "300ms",
                "state.num-key-shards": shards,
                "state.slots-per-shard": 64,
            })
        j = coord.jobs["bench-rescale"]
        # live committed progress, then ~batch B of ingest, THEN cut
        wait(lambda: len(FileTransactionalSink.committed_rows(
                 f"{sink_dir}-p0")) > 0, 120, "first committed epoch")
        wait(lambda: (j.last_metrics or {}).get(
                 "records_in", 0) >= at_batch * batch_size, 300,
             f"batch {at_batch} ingested")
        pre_records = int((j.last_metrics or {}).get("records_in", 0))
        t_arm = time.perf_counter()
        resp = coord.rpc_rescale_job("bench-rescale", devices=1,
                                     processes=to_procs)
        assert resp.get("ok"), resp
        wait(lambda: (j.state == "RUNNING"
                      and int(j.config.get("cluster.num-processes", 1))
                      == to_procs)
             or j.state == "FINISHED", 300,
             f"running at {to_procs} processes")
        t_resume = time.perf_counter()
        wait(lambda: j.state == "FINISHED", 600, "job FINISHED")
        t_end = time.perf_counter()

        # exactly-once across the cut: no (key, window) row committed
        # twice by ANY process, and the output is non-empty
        seen, rows = set(), 0
        for pid in range(to_procs):
            for r in FileTransactionalSink.committed_rows(
                    f"{sink_dir}-p{pid}"):
                kk = (int(r["key"]), int(r["window_start"]))
                assert kk not in seen, f"duplicate emission for {kk}"
                seen.add(kk)
                rows += 1
        assert rows > 0, "rescale bench committed nothing"

        rm = coord.rpc_job_status("bench-rescale")["rescale"]["metrics"]
        assert rm.get("coordinator.rescale.duration_ms.count", 0) >= 1
        cores = os.cpu_count() or 1
        required = 2 * to_procs + 1
        pre_eps = pre_records / max(t_arm - t_submit, 1e-9)
        post_eps = ((events_total - pre_records)
                    / max(t_end - t_resume, 1e-9))
        line = {
            "metric": "q5_live_process_rescale",
            "unit": "ms",
            # the runners are CPU processes by design (see docstring)
            "runner_platform": "cpu",
            "rescale_at_batch": at_batch,
            "rescale_to_processes": to_procs,
            "batch": batch_size,
            "n_batches": n_batches,
            "time_to_rescale_ms": round(
                rm["coordinator.rescale.duration_ms.max"], 1),
            "rescales_armed": int(rm.get("coordinator.rescale.armed", 0)),
            "rescales_completed": int(
                rm.get("coordinator.rescale.duration_ms.count", 0)),
            "pre_cut_events_per_sec": round(pre_eps),
            "post_cut_events_per_sec": round(post_eps),
            "committed_rows": rows,
            "exactly_once_verified": True,
            "cores": cores,
        }
        if cores < required:
            print(json.dumps({
                "metric": "q5_live_process_rescale_recovery_ratio",
                "skipped": "insufficient-cores",
                "cores": cores,
                "required_cores": required,
                "detail": "the post/pre-cut rate ratio only reflects "
                          f"the subsystem with {to_procs} runner "
                          "processes on dedicated cores; on a "
                          f"{cores}-core host they contend for the "
                          "same cores and the ratio measures the "
                          "scheduler — time_to_rescale_ms is still "
                          "valid (control-plane, not compute-bound)"}))
        else:
            line["recovery_ratio"] = round(
                post_eps / max(pre_eps, 1e-9), 3)
        print(json.dumps(line))
        if artifact:
            with open(artifact, "w") as f:
                json.dump(line, f, indent=1)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        srv.close()
        coord.close()
        shutil.rmtree(tmp, ignore_errors=True)


def state_backend_bench(backend: str, key_domain: int,
                        artifact: str = "BENCH_STATE.json") -> None:
    """``python bench.py --state-backend lsm --key-domain N``: the
    keyed-state tier microbench (ISSUE 17). Drives one spill store —
    'lsm' (disk tier, state/lsm.py) or 'spill' (RAM ledger) — through
    the three access shapes the window operator issues:

    - **put**: absorb batches uniform over a key domain far beyond the
      lsm delta budget (seal + compact on the real durable path);
    - **get**: fire complete sliding windows (the pane-range-pruned
      run fold);
    - **scan**: a full fold of every live run + delta (the restore /
      key_count shape).

    Then two changelog checkpoints through the REAL storage plane
    (save_v2 + op_aux hardlinks) measure what the tier is for:
    ``checkpoint_fresh_bytes`` (delta blob + manifest — the bytes the
    second checkpoint actually wrote, st_nlink==1) vs
    ``full_state_bytes`` (the store's whole footprint) — incremental
    cost tracks the write rate, not the key domain.

    CORE-COUNT CONSTRAINT: this container runs 1–2 CPU cores, so the
    ev/s figures are single-host, contended-core numbers — valid for
    the delta-vs-full ratio and lsm/spill RELATIVE comparison, not as
    steady-state throughput claims (the ``cores`` field rides the
    artifact so readers can tell)."""
    import shutil
    import tempfile

    from flink_tpu.checkpoint import blobformat
    from flink_tpu.checkpoint.storage import FsCheckpointStorage
    from flink_tpu.state.lsm import LsmSpillStore
    from flink_tpu.state.spill import HostSpillStore

    if backend not in ("lsm", "spill"):
        raise SystemExit("--state-backend needs lsm|spill")

    class _BenchAgg:
        # the Q5 lane shape: one f32 value lane in each monoid + count
        sum_width, max_width, min_width = 1, 1, 1

        def lift_masked(self, data, valid):
            v = np.asarray(data["v"], np.float32)[:, None]
            return v, v, v

        def finalize(self, s, x, n, c):
            return {"sum_v": s[:, 0], "max_v": x[:, 0],
                    "min_v": n[:, 0], "count": c}

    budget = 1 << 20  # the committed bench_q5_lsm.conf budget
    rows_per_batch = 1 << 15
    n_batches = 48
    panes = 24  # sliding 8-pane windows over these fire 17 full ends
    tmp = tempfile.mkdtemp(prefix="bench-state-")
    rng = np.random.default_rng(17)
    try:
        if backend == "lsm":
            store = LsmSpillStore(
                _BenchAgg(), store_dir=os.path.join(tmp, "store"),
                memory_budget_bytes=budget, num_shards=128)
        else:
            store = HostSpillStore(_BenchAgg())

        # put: uniform keys over the domain, pane-stamped round-robin
        t0 = time.perf_counter()
        for b in range(n_batches):
            keys = rng.integers(0, key_domain,
                                rows_per_batch).astype(np.int64)
            pane = np.full(rows_per_batch, b % panes, np.int64)
            store.absorb(keys, pane,
                         {"v": rng.normal(
                             size=rows_per_batch).astype(np.float32)})
        put_wall = time.perf_counter() - t0
        put_eps = rows_per_batch * n_batches / put_wall

        # get: fire every complete 8-pane window once (Q5's shape)
        ppw = 8
        ends = list(range(ppw, panes + 1))
        t0 = time.perf_counter()
        fired = store.fire(ends, ppw, 1_000, 0, ppw * 1_000)
        get_wall = time.perf_counter() - t0
        fired_rows = 0 if fired is None else len(fired["key"])
        get_eps = fired_rows / max(get_wall, 1e-9)

        # scan: the full fold every key passes through (restore shape)
        t0 = time.perf_counter()
        n_keys = store.key_count
        scan_wall = time.perf_counter() - t0
        if backend == "lsm":
            stored_rows = (sum(r["rows"] for r in store._runs)
                           + sum(len(t[0])
                                 for t in store._delta.panes.values()))
        else:
            stored_rows = sum(len(t[0]) for t in store.panes.values())
        scan_rps = stored_rows / max(scan_wall, 1e-9)

        # changelog checkpoints through the real storage plane: ckpt 1
        # seals the baseline, more puts, ckpt 2's FRESH bytes (delta
        # blob + manifests + runs sealed since ckpt 1) are the
        # incremental cost the tier exists to bound. Compact first so
        # the gap churn stays below compact_min_runs — a compaction
        # inside the gap rewrites the whole keyspace and would measure
        # compaction cost, not checkpoint cost
        if backend == "lsm":
            store.compact()
        storage = FsCheckpointStorage(os.path.join(tmp, "chk"), "bench")
        full_bytes = int(store.bytes_used())
        chk_bytes = {}
        prev_aux: set = set()
        for cid in (1, 2):
            snap = store.snapshot()
            aux = (snap.pop("aux_files", None)
                   if isinstance(snap, dict) else None) or {}
            h = storage.save_v2(
                cid, {"checkpoint_id": cid},
                {"1": blobformat.encode(snap)}, {},
                op_aux=({"1": aux} if aux else None))
            # fresh = bytes this checkpoint introduced: the delta blob
            # + manifests (st_nlink==1) plus runs sealed SINCE the
            # previous checkpoint (hardlinked, but new writes — runs
            # already in the prior cut cost nothing again)
            carried = {f"st-1-{name}" for name in prev_aux}
            prev_aux = set(aux)
            total = fresh = 0
            for name in os.listdir(h.path):
                st = os.stat(os.path.join(h.path, name))
                total += st.st_size
                if st.st_nlink == 1 or name not in carried:
                    fresh += st.st_size
            chk_bytes[cid] = {"total": total, "fresh": fresh}
            if cid == 1:
                for b in range(2):  # ~2 budget-fills of fresh writes
                    keys = rng.integers(0, key_domain,
                                        rows_per_batch).astype(np.int64)
                    store.absorb(
                        keys, np.full(rows_per_batch, panes, np.int64),
                        {"v": rng.normal(
                            size=rows_per_batch).astype(np.float32)})
                full_bytes = int(store.bytes_used())

        line = {
            "metric": "keyed_state_backend_bench",
            "backend": backend,
            "key_domain": key_domain,
            "memory_budget_bytes": budget if backend == "lsm" else None,
            "put_events_per_sec": round(put_eps),
            "get_events_per_sec": round(get_eps),
            "get_fired_rows": fired_rows,
            "scan_rows_per_sec": round(scan_rps),
            "scanned_keys": int(n_keys),
            "stored_rows": int(stored_rows),
            "runs_sealed": getattr(store, "seals", 0),
            "compactions": getattr(store, "compactions", 0),
            "live_runs": getattr(store, "run_count", 0),
            "full_state_bytes": full_bytes,
            "checkpoint_total_bytes": chk_bytes[2]["total"],
            "checkpoint_fresh_bytes": chk_bytes[2]["fresh"],
            "delta_vs_full_ratio": round(
                chk_bytes[2]["fresh"] / max(full_bytes, 1), 6),
            "cores": os.cpu_count(),
            "constraint": "1-2 core container: single-host contended-"
                          "core rates — read the delta_vs_full_ratio "
                          "and lsm/spill relative numbers, not the "
                          "absolute ev/s",
        }
        print(json.dumps(line))
        if artifact:
            with open(artifact, "w") as f:
                json.dump(line, f, indent=1)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    import sys

    if "--dump-confs" in sys.argv:
        ix = sys.argv.index("--dump-confs")
        if ix + 1 >= len(sys.argv):
            raise SystemExit("--dump-confs needs a directory, "
                             "e.g. confs")
        dump_confs(sys.argv[ix + 1])
    elif "--host-parallelism" in sys.argv:
        ix = sys.argv.index("--host-parallelism")
        if ix + 1 >= len(sys.argv):
            raise SystemExit("--host-parallelism needs a list, "
                             "e.g. 1,2,4,8")
        host_parallelism_sweep(sys.argv[ix + 1])
    elif "--concurrent-jobs" in sys.argv:
        ix = sys.argv.index("--concurrent-jobs")
        if ix + 1 >= len(sys.argv):
            raise SystemExit("--concurrent-jobs needs a count, e.g. 2")
        concurrent_jobs_bench(int(sys.argv[ix + 1]))
    elif "--rescale-at-batch" in sys.argv or "--rescale-to" in sys.argv:
        if ("--rescale-at-batch" not in sys.argv
                or "--rescale-to" not in sys.argv):
            raise SystemExit("--rescale-at-batch B and --rescale-to N "
                             "go together, e.g. --rescale-at-batch 8 "
                             "--rescale-to 2")
        ib = sys.argv.index("--rescale-at-batch")
        it = sys.argv.index("--rescale-to")
        if ib + 1 >= len(sys.argv) or it + 1 >= len(sys.argv):
            raise SystemExit("--rescale-at-batch/--rescale-to need "
                             "integer values")
        rescale_bench(int(sys.argv[ib + 1]), int(sys.argv[it + 1]),
                      artifact="BENCH_RESCALE.json")
    elif "--state-backend" in sys.argv:
        ix = sys.argv.index("--state-backend")
        if ix + 1 >= len(sys.argv):
            raise SystemExit("--state-backend needs lsm|spill")
        kd = 1 << 20
        if "--key-domain" in sys.argv:
            ik = sys.argv.index("--key-domain")
            if ik + 1 >= len(sys.argv):
                raise SystemExit("--key-domain needs a count, "
                                 "e.g. 1048576")
            kd = int(sys.argv[ik + 1])
        state_backend_bench(sys.argv[ix + 1], kd)
    elif "--backfill" in sys.argv:
        run_q5_backfill(artifact="BENCH_BACKFILL.json")
    elif "--suite" in sys.argv:
        suite()
    else:
        main()
