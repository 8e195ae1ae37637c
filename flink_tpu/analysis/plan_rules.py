"""The plan-analysis rule catalog.

Every rule is a single walk over the lowered ``ExecutionPlan`` and/or
the ``Configuration`` — the static preconditions of guarantees PRs 1–3
proved dynamically (exactly-once, job chaining, bounded execution),
checked before the first record flows (ref: the validation layer of
StreamGraph translation, SURVEY §3.2/§3.6).

Severity contract (what ``analysis.fail-on`` keys on):

- **error** — the job WILL fail or corrupt output at runtime: an
  unbounded source in batch mode, a window that can never fire, two
  writers on one log topic, a chaos rule injecting nothing, keyed
  state with no key exchange, checkpointing in batch mode.
- **warn** — correctness smells that depend on intent: event-time
  windows riding the default watermark strategy, non-transactional
  sinks under exactly-once checkpointing, config keys outside the
  declared grammar (typos).
"""
from __future__ import annotations

import difflib
import fnmatch
import os
from typing import Any, Iterable, Iterator, List, Set

from flink_tpu.analysis.core import Finding, config_rule, plan_rule

# a rule fills message/location; analyze() stamps the registered
# rule id + severity on every finding it yields
def _f(message: str, fix: str = "", node=None, node_name: str = "",
       file: str = "", line: int = 0) -> Finding:
    return Finding(rule="", severity="warn", message=message, fix=fix,
                   node=node, node_name=node_name, file=file, line=line)


def _upstream_sources(plan, nid: int) -> Iterator[Any]:
    """Source nodes transitively feeding ``nid``."""
    upstream = {n: [] for n in plan.nodes}
    for n in plan.nodes.values():
        for d in n.downstream:
            upstream[d].append(n.id)
    seen: Set[int] = set()
    stack = [nid]
    while stack:
        cur = stack.pop()
        for u in upstream[cur]:
            if u in seen:
                continue
            seen.add(u)
            node = plan.nodes[u]
            if node.kind == "source":
                yield node
            else:
                stack.append(u)


def _runtime_mode(config) -> str:
    from flink_tpu.config import ExecutionOptions

    return str(config.get(ExecutionOptions.RUNTIME_MODE)).strip().lower()


# kinds whose operator keys state by node.key_field and therefore needs
# the keyBy exchange the lowering folds into it (keyed_input)
KEYED_KINDS = frozenset((
    "window", "evicting_window", "session", "count_window", "process",
    "cep", "global_agg",
))

# kinds that evaluate event-time semantics against the watermark clock
_EVENT_TIME_KINDS = frozenset((
    "window", "evicting_window", "window_all", "join", "session", "cep",
))


def _is_event_time(node) -> bool:
    if node.kind in ("session", "cep"):
        return True  # session gaps / CEP within-windows are event-time
    assigner = getattr(node.window_transform, "assigner", None)
    if assigner is None:
        return False
    return bool(getattr(assigner, "is_event_time", True))


@plan_rule("EVENT_TIME_NO_WATERMARK", "warn",
           fix="pass a WatermarkStrategy to from_source()")
def event_time_no_watermark(plan, config) -> Iterable[Finding]:
    """Event-time op fed by a source with no explicit watermark
    strategy: the pipeline-default monotonous clock treats ANY
    out-of-order timestamp as late and silently drops it."""
    for node in plan.nodes.values():
        if node.kind not in _EVENT_TIME_KINDS or not _is_event_time(node):
            continue
        for src in _upstream_sources(plan, node.id):
            if src.watermark_strategy is None:
                yield _f(
                    f"event-time {node.kind} {node.name!r} is fed by "
                    f"source {src.name!r} with no watermark strategy — "
                    "out-of-order records will be dropped as late under "
                    "the default monotonous clock",
                    fix="pass a WatermarkStrategy to from_source(), e.g. "
                        "WatermarkStrategy.for_bounded_out_of_orderness("
                        "ms)",
                    node=node.id, node_name=node.name)


@plan_rule("NON_TRANSACTIONAL_SINK", "warn",
           fix="use a TwoPhaseCommitSink or disable checkpointing")
def non_transactional_sink(plan, config) -> Iterable[Finding]:
    """Checkpointing is on (exactly-once intended) but a sink writes
    through: a recovery replays the uncheckpointed tail into it —
    at-least-once output, duplicates on every restore."""
    from flink_tpu.api.sinks import sink_is_transactional
    from flink_tpu.config import CheckpointingOptions

    if config.get(CheckpointingOptions.INTERVAL) <= 0:
        return
    for node in plan.nodes.values():
        if node.kind != "sink" or node.sink is None:
            continue
        if not sink_is_transactional(node.sink):
            yield _f(
                f"sink {node.name!r} ({type(node.sink).__name__}) is not "
                "transactional but execution.checkpointing.interval is "
                "set — recovery will replay the un-checkpointed tail "
                "into it (duplicates; at-least-once, not exactly-once)",
                fix="use a TwoPhaseCommitSink (LogSink, FileSink, "
                    "FileTransactionalSink) or disable checkpointing",
                node=node.id, node_name=node.name)


@plan_rule("UNBOUNDED_SOURCE_IN_BATCH", "error",
           fix="bound the source or run in streaming mode")
def unbounded_source_in_batch(plan, config) -> Iterable[Finding]:
    """Batch (bounded) mode requires every source to end: stages run to
    completion in topological waves — an unbounded source never lets
    its stage finish."""
    from flink_tpu.api.sources import source_is_bounded

    if _runtime_mode(config) != "batch":
        return
    for sid in plan.sources:
        node = plan.nodes[sid]
        if node.source is not None and not source_is_bounded(node.source):
            yield _f(
                f"source {node.name!r} is unbounded under "
                "execution.runtime-mode=batch — its stage can never "
                "run to completion",
                fix="bound the source (is_bounded=True / finite "
                    "generator) or run in streaming mode",
                node=node.id, node_name=node.name)


@plan_rule("KEYED_OP_WITHOUT_KEYBY", "error",
           fix="insert .key_by(...) before the stateful op")
def keyed_op_without_keyby(plan, config) -> Iterable[Finding]:
    """A keyed stateful op whose input edge never went through a keyBy
    exchange: state would partition on whatever column happens to share
    the key field's name — wrong results or a missing-column crash."""
    for node in plan.nodes.values():
        if node.kind in KEYED_KINDS and not node.keyed_input:
            yield _f(
                f"keyed {node.kind} {node.name!r} is reachable without "
                "a keyBy exchange — its state partitions on an "
                "undeclared key column",
                fix="insert .key_by(column_or_fn) immediately before "
                    "the stateful op",
                node=node.id, node_name=node.name)


@plan_rule("WINDOW_WITHOUT_FIRE_BOUND", "error",
           fix="set a trigger or use a time-bounded assigner")
def window_without_fire_bound(plan, config) -> Iterable[Finding]:
    """A GlobalWindows op with no trigger never fires: every record is
    state forever — unbounded growth and zero output."""
    from flink_tpu.api.windowing import GlobalWindows

    for node in plan.nodes.values():
        wt = node.window_transform
        if wt is None or not isinstance(
                getattr(wt, "assigner", None), GlobalWindows):
            continue
        if getattr(wt, "trigger", None) is None:
            yield _f(
                f"{node.kind} {node.name!r} uses GlobalWindows with no "
                "trigger — it can never fire, and per-key state grows "
                "without bound",
                fix="set a trigger (.trigger(CountTrigger.of(n))) or "
                    "use count_window(n) / a time-bounded assigner",
                node=node.id, node_name=node.name)


@plan_rule("LOG_TOPIC_MULTI_WRITER", "error",
           fix="lease disjoint partitions (owned_partitions + "
               "producer_id), or one LogSink per topic")
def log_topic_multi_writer(plan, config) -> Iterable[Finding]:
    """Multiple LogSinks on one topic directory WITHOUT disjoint
    partition leases: the embedded log serializes appends per
    PARTITION via fenced writer leases (log/bus.py), so N sinks with
    pairwise-disjoint ``owned_partitions`` (distinct producer ids) are
    legal — but two un-leased writers, or two leases overlapping on a
    partition, roll back each other's staged transactions."""
    try:
        from flink_tpu.log.connectors import LogSink
    except Exception:  # log subsystem not importable: nothing to check
        return
    by_topic = {}
    for node in plan.nodes.values():
        if node.kind == "sink" and isinstance(node.sink, LogSink):
            topic = os.path.realpath(str(node.sink.path))
            by_topic.setdefault(topic, []).append(node)
    for topic, nodes in by_topic.items():
        if len(nodes) < 2:
            continue
        appenders = [n.sink._appender for n in nodes]
        leased = all(a.writer_id for a in appenders)
        owners = {}
        overlap = set()
        for n, a in zip(nodes, appenders):
            for p in a.owned:
                if p in owners:
                    overlap.add(p)
                owners[p] = n
        distinct_ids = len({a.writer_id for a in appenders}) == len(
            appenders)
        if leased and distinct_ids and not overlap:
            continue  # disjoint leased partitions: legal multi-writer
        names = ", ".join(f"{n.id} ({n.name!r})" for n in nodes)
        if leased and overlap:
            why = (f"their leased partition sets overlap on "
                   f"{sorted(overlap)} — a partition has ONE writer; "
                   "the lease fence will depose one of them mid-run")
        elif leased:
            why = ("they share a producer_id — writer-scoped markers "
                   "and leases would collide")
        else:
            why = ("they hold no partition leases — un-leased "
                   "concurrent appenders roll back each other's "
                   "staged transactions")
        for node in nodes:
            yield _f(
                f"log topic {topic!r} has {len(nodes)} writers in "
                f"this plan (sink nodes {names}) and {why}",
                fix="give each sink disjoint owned_partitions with a "
                    "distinct producer_id (fenced leases), or give "
                    "each its own topic / union the streams into ONE "
                    "LogSink",
                node=node.id, node_name=node.name)


@config_rule("STORAGE_LOCAL_LOCKS_ON_REMOTE", "warn",
             fix="keep high-availability.dir and log.dir on local "
                 "(file://) paths or a conditional-put scheme "
                 "(objstore://), or accept the documented "
                 "degradation: read-check-write acquisition races are "
                 "then bounded only by epoch fencing at the next "
                 "verify, not prevented")
def storage_local_locks_on_remote(plan, config) -> Iterable[Finding]:
    """Lock-dependent storage on a non-``file`` scheme WITHOUT
    conditional writes: the O_EXCL + rename-first lock discipline (HA
    leader-election leases, the log tier's writer-lease acquisition
    locks and maintenance locks) is LOCAL-filesystem-only —
    ``os.open(O_CREAT|O_EXCL)`` has no remote equivalent here. A
    scheme whose registered driver advertises ``conditional_put``
    (``fs.cas_capable`` — the objstore driver's ``put_if`` CAS) is
    QUIET: every lock-dependent path ports onto compare-and-swap
    there, which PREVENTS the race rather than bounding it. On any
    other remote scheme acquisition degrades to read-check-write
    (PR 9/11 honest residue): two racing acquirers can both believe
    they won until the next epoch verify rejects one. Flag the intent
    early, at submit, instead of as a once-a-month double-leader
    incident. Driver-aware: probes the scheme's REGISTERED filesystem,
    so an out-of-tree driver that grows CAS silences this rule by
    declaring it."""
    from flink_tpu.config import HighAvailabilityOptions, LogOptions
    from flink_tpu.fs import cas_capable, get_filesystem

    checks = (
        ("high-availability.dir",
         str(config.get(HighAvailabilityOptions.HA_DIR)),
         "leader-election lease steals + the durable session registry"),
        ("log.dir", str(config.get(LogOptions.DIR)),
         "per-partition writer-lease acquisition locks and topic "
         "maintenance locks"),
    )
    for key, value, what in checks:
        v = value.strip()
        scheme, sep, _ = v.partition("://")
        if not sep or scheme == "file":
            continue
        try:
            if cas_capable(get_filesystem(v)):
                continue  # CAS replaces the lock: race PREVENTED
        except ValueError:
            pass  # unregistered scheme: fails later, warn here too
        yield _f(
            f"{key}={v!r} resolves to scheme {scheme!r}, whose driver "
            f"offers no conditional-put: the O_EXCL + rename-first "
            f"lock discipline protecting {what} is local-filesystem-"
            "only — on this scheme acquisition degrades to "
            "read-check-write, fenced only after the fact by lease "
            "epochs",
            fix="move the directory to a shared LOCAL filesystem "
                "(file:// / bare path) or a conditional-put scheme "
                "(objstore://), or accept the degradation knowingly "
                "(single-acquirer operational discipline)")


@config_rule("LOG_RETENTION_UNSAFE", "warn",
             fix="set log.retention.ms >= "
                 "execution.checkpointing.interval (or disable one)")
def log_retention_unsafe(plan, config) -> Iterable[Finding]:
    """A retention window shorter than the checkpoint interval under
    checkpointing: consumer-group offsets only advance at checkpoint
    complete, so the dynamic safety floor pins every segment a group
    still needs — but a retention pass between a consumer's start and
    its FIRST completed checkpoint sees no group floor to respect for
    groups that have not committed yet, and a window below the
    checkpoint cadence guarantees the topic is perpetually at the
    floor (retention that can never drop anything, or drops history a
    brand-new group expected to backfill from)."""
    from flink_tpu.config import CheckpointingOptions, LogOptions

    retention_ms = int(config.get(LogOptions.RETENTION_MS))
    interval = int(config.get(CheckpointingOptions.INTERVAL))
    if retention_ms <= 0 or interval <= 0:
        return
    if retention_ms < interval:
        yield _f(
            f"log.retention.ms={retention_ms} is shorter than "
            f"execution.checkpointing.interval={interval}: group "
            "committed offsets (the retention safety floor) only "
            "advance at checkpoint complete, so retention this "
            "aggressive either never drops anything (floor-pinned) or "
            "expires history a new consumer generation expected to "
            "bootstrap from",
            fix=f"raise log.retention.ms to >= {interval}, lower the "
                "checkpoint interval, or disable time retention")


@config_rule("CLEANER_DISABLED_WITH_RETENTION", "warn",
             fix="set log.cleaner.enabled=true (the driver then runs "
                 "compaction + retention at log.cleaner.interval-ms "
                 "under the fenced cleaner lease), or schedule "
                 "explicit `log TOPIC_DIR --retain` passes")
def cleaner_disabled_with_retention(plan, config) -> Iterable[Finding]:
    """A retention policy with no executor: ``log.retention.ms`` /
    ``log.retention.bytes`` describe WHAT to drop, but nothing in the
    runtime drops it unless the background cleaner is enabled
    (``log.cleaner.enabled``) or an operator runs explicit
    maintenance passes. A topic configured this way grows without
    bound while its owner believes retention is active — the classic
    silently-ignored-config failure, surfaced at submit instead of at
    the disk-full incident. Fires only when the plan actually
    PRODUCES into a topic (a LogSink node): a consume-only job
    inherits the producer's maintenance regime."""
    from flink_tpu.config import LogOptions

    if bool(config.get(LogOptions.CLEANER_ENABLED)):
        return
    retention_ms = int(config.get(LogOptions.RETENTION_MS))
    retention_bytes = int(config.get(LogOptions.RETENTION_BYTES))
    if retention_ms <= 0 and retention_bytes <= 0:
        return
    if not _has_log_sink(plan):
        return
    configured = ", ".join(
        f"{k}={v}" for k, v in (("log.retention.ms", retention_ms),
                                ("log.retention.bytes", retention_bytes))
        if v > 0)
    yield _f(
        f"{configured} configured but log.cleaner.enabled=false: "
        "retention policy has NO executor — nothing in the runtime "
        "applies it, so the topic grows without bound unless explicit "
        "maintenance passes run out of band",
        fix="enable log.cleaner.enabled (leased background "
            "compaction + retention per producing topic), or drop the "
            "retention keys if out-of-band `log --retain` passes are "
            "the plan")


def _has_log_sink(plan) -> bool:
    from flink_tpu.log.connectors import LogSink

    if plan is None:
        # config-only analysis (analyze_config / `analyze --conf`):
        # no plan to inspect — retention keys alone signal log-tier
        # intent, so warn conservatively
        return True
    return any(n.kind == "sink" and isinstance(n.sink, LogSink)
               for n in plan.nodes.values())


@config_rule("LOG_PREFETCH_INVALID", "warn",
             fix="log.prefetch-segments >= 0, log.read-batch-records "
                 ">= 0, log.fsync-mode in {group, segment}; set "
                 "log.prefetch-segments=0 when auditing a savepoint "
                 "rewind")
def log_prefetch_invalid(plan, config) -> Iterable[Finding]:
    """A misconfigured perf-grade log read/write path: a negative
    prefetch depth or coalescing target would only fail at LogSource
    construction deep inside the job build, an unknown fsync-mode at
    the first stage — and prefetch combined with an EXPLICIT replay
    rewind (a configured restore path on a consumer-group job) makes
    a rewind audit's batch boundaries nondeterministic (the readahead
    re-reads rows past the frozen barrier; positions stay exact, but a
    side-by-side diff of delivered batches won't line up run to run)."""
    from flink_tpu.config import CheckpointingOptions, LogOptions

    prefetch = int(config.get(LogOptions.PREFETCH_SEGMENTS))
    batch_records = int(config.get(LogOptions.READ_BATCH_RECORDS))
    fsync_mode = str(config.get(LogOptions.FSYNC_MODE))
    if prefetch < 0:
        yield _f(
            f"log.prefetch-segments={prefetch} is negative: LogSource "
            "rejects it at construction, deep inside the job build — "
            "0 disables readahead, >= 1 sets the decode-ahead depth",
            fix="set log.prefetch-segments >= 0")
    if batch_records < 0:
        yield _f(
            f"log.read-batch-records={batch_records} is negative: "
            "LogSource rejects it at construction — 0 reads per "
            "on-disk block, >= 1 coalesces blocks to that many rows",
            fix="set log.read-batch-records >= 0")
    if fsync_mode not in ("group", "segment"):
        yield _f(
            f"log.fsync-mode={fsync_mode!r} is not a known mode: the "
            "sink rejects it at construction, deep inside the job "
            "build",
            fix="use 'group' (batched pre-marker fsync pass) or "
                "'segment' (legacy fsync-per-file)")
    restore = str(config.get(CheckpointingOptions.RESTORE) or "").strip()
    group = str(config.get(LogOptions.GROUP_NAME) or "").strip()
    if (prefetch > 0 and group and restore
            and restore not in ("", "latest")):
        yield _f(
            f"log.prefetch-segments={prefetch} with an explicit "
            f"replay rewind (execution.checkpointing.restore="
            f"{restore!r}) on consumer group {group!r}: the rewound "
            "position is authoritative and re-delivers rows below the "
            "group's committed offset, and readahead makes the "
            "re-delivered batch boundaries nondeterministic run to "
            "run — exactly-once is unaffected, but a rewind AUDIT "
            "(diffing delivered batches) should read inline",
            fix="set log.prefetch-segments=0 for the audit run, or "
                "drop the explicit restore path")


@config_rule("FAULT_POINT_UNKNOWN", "error",
             fix="match a faults.KNOWN_FAULT_POINTS entry")
def fault_point_unknown(plan, config) -> Iterable[Finding]:
    """A faults.inject rule whose point glob matches no registered
    fault point injects NOTHING — a chaos conf that silently does
    nothing is worse than no chaos at all."""
    from flink_tpu.faults import FAULT_INJECT, FAULT_SEED, FaultPlan
    from flink_tpu.faults import KNOWN_FAULT_POINTS

    spec = str(config.get(FAULT_INJECT) or "").strip()
    if not spec:
        return
    try:
        fplan = FaultPlan.from_spec(spec, seed=int(config.get(FAULT_SEED)))
    except ValueError as e:
        yield _f(f"faults.inject does not parse: {e}",
                 fix="grammar: 'point=kind [@prob] [xCOUNT] [+AFTER] "
                     "[~DELAY_MS]', rules ';'-separated")
        return
    for r in fplan.rules:
        if not any(fnmatch.fnmatchcase(p, r.point)
                   for p in KNOWN_FAULT_POINTS):
            close = difflib.get_close_matches(
                r.point, sorted(KNOWN_FAULT_POINTS), n=1)
            hint = (f"did you mean {close[0]!r}? " if close else "")
            yield _f(
                f"faults.inject rule {r.point!r} matches no registered "
                "fault point — it will never inject",
                fix=hint + "see flink_tpu.faults.KNOWN_FAULT_POINTS "
                    "for the registry")


@config_rule("CONFIG_KEY_UNKNOWN", "warn",
             fix="fix the typo or declare the ConfigOption")
def config_key_unknown(plan, config) -> Iterable[Finding]:
    """A set key outside the declared option grammar is almost always a
    typo — the job silently runs with the default of the key you meant."""
    from flink_tpu.config import all_options, is_declared_key

    load_option_grammar()
    known = sorted(all_options())
    for key in config.keys():
        if not is_declared_key(key):
            close = difflib.get_close_matches(key, known, n=1)
            yield _f(
                f"config key {key!r} is not in the declared option "
                "grammar — the job ignores it",
                fix=(f"did you mean {close[0]!r}?" if close else
                     "declare it as a ConfigOption (or under a dynamic "
                     "prefix, config.declare_dynamic_prefix)"))


@config_rule("HOST_PARALLELISM_INVALID", "warn",
             fix="set 1 <= host.parallelism <= os.cpu_count()")
def host_parallelism_invalid(plan, config) -> Iterable[Finding]:
    """host.parallelism outside [1, os.cpu_count()]: below 1 the driver
    cannot size the shared host pool and rejects the job at build;
    above the core count the workers contend for cores instead of
    scaling (the §9.4 contract sizes pools FROM os.cpu_count())."""
    from flink_tpu.config import HostOptions

    try:
        w = int(config.get(HostOptions.PARALLELISM))
    except (TypeError, ValueError):
        yield _f(
            "host.parallelism does not parse as an integer",
            fix="set an integer >= 1 (1 = serial path; default "
                "min(4, os.cpu_count()))")
        return
    ncpu = os.cpu_count() or 1
    if w < 1:
        yield _f(
            f"host.parallelism={w} is below 1 — the shared host worker "
            "pool cannot be sized and the driver rejects the job at "
            "build",
            fix="set host.parallelism >= 1 (1 = the exact serial path)")
    elif w > ncpu:
        yield _f(
            f"host.parallelism={w} exceeds os.cpu_count()={ncpu} — "
            "oversubscribed workers contend for cores instead of "
            "scaling the host operator paths",
            fix=f"set host.parallelism <= {ncpu} (default "
                f"min(4, os.cpu_count()) = {min(4, ncpu)})")


@config_rule("SESSION_QUOTA_INVALID", "error",
             fix="set 1 <= session.slots-per-job <= "
                 "session.runner-slots, and session.max-jobs >= 1")
def session_quota_invalid(plan, config) -> Iterable[Finding]:
    """A session-cluster quota the dispatcher can never satisfy: a
    slots-per-job or max-jobs or runner-slots below 1 (admission
    rejects the submission / the dispatcher refuses to start), or a
    per-job slot quota above one runner's slot capacity — no runner in
    the fleet could ever host the job, so it would be rejected at
    submit (runtime/session.py enforces the same bounds)."""
    from flink_tpu.config import SessionOptions

    def _get(opt, label):
        try:
            return int(config.get(opt)), None
        except (TypeError, ValueError):
            return None, _f(
                f"{label} does not parse as an integer",
                fix=f"set an integer >= 1 for {label}")

    spj, err = _get(SessionOptions.SLOTS_PER_JOB, "session.slots-per-job")
    if err is not None:
        yield err
        return
    rs, err = _get(SessionOptions.RUNNER_SLOTS, "session.runner-slots")
    if err is not None:
        yield err
        return
    mj, err = _get(SessionOptions.MAX_JOBS, "session.max-jobs")
    if err is not None:
        yield err
        return
    if spj < 1:
        yield _f(
            f"session.slots-per-job={spj} is below 1 — the dispatcher "
            "rejects the submission at admission",
            fix="set session.slots-per-job >= 1 (1 = the default "
                "single-slot share)")
    if mj < 1:
        yield _f(
            f"session.max-jobs={mj} is below 1 — the session cluster "
            "could never run a job and refuses to start",
            fix="set session.max-jobs >= 1")
    if rs < 1:
        yield _f(
            f"session.runner-slots={rs} is below 1 — runners would "
            "contribute no slot capacity and the cluster refuses to "
            "start",
            fix="set session.runner-slots >= 1")
    elif spj > rs:
        yield _f(
            f"session.slots-per-job={spj} exceeds "
            f"session.runner-slots={rs} — the quota is above every "
            "runner's slot capacity, so no fleet of any size could "
            "ever place the job (admission rejects it)",
            fix=f"lower session.slots-per-job to <= {rs}, or raise "
                "session.runner-slots")


@config_rule("SESSION_HA_UNSAFE", "warn",
             fix="set high-availability.dir to a shared directory and "
                 "run a standby contender (`session start --standby`)")
def session_ha_unsafe(plan, config) -> Iterable[Finding]:
    """A session cluster running CHECKPOINTING jobs without
    ``high-availability.dir``: every tenant's state is individually
    durable (checkpoints + transactional sinks survive a crash), but
    one dispatcher SIGKILL strands ALL of them — queued, running, and
    admitted-but-undeployed jobs evaporate with the in-memory registry
    even though each could have recovered. The durable session
    registry + standby takeover (runtime/session.py serve_session)
    exists exactly for this; a cluster that bothered to checkpoint
    should bother to survive its control plane."""
    from flink_tpu.config import (
        CheckpointingOptions,
        HighAvailabilityOptions,
    )

    quota_keys = ("session.runner-slots", "session.max-jobs",
                  "session.slots-per-job")
    present = [k for k in quota_keys if k in set(config.keys())]
    if not present:
        return  # no session-cluster intent in this config
    if int(config.get(CheckpointingOptions.INTERVAL)) <= 0:
        return  # nothing durable to strand: re-submission is recovery
    if str(config.get(HighAvailabilityOptions.HA_DIR)).strip():
        return
    yield _f(
        f"session-cluster config ({', '.join(present)}) runs "
        "checkpointing jobs with no high-availability.dir: a "
        "dispatcher crash strands every tenant's queued and running "
        "jobs even though their checkpoints would survive it — no "
        "durable session registry, no standby takeover, no leader "
        "epoch fencing",
        fix="set high-availability.dir to a directory every contender "
            "and runner shares, and start a hot standby with "
            "`session start --standby --ha-dir <dir>`")


@config_rule("DCN_OVERLAP_UNSAFE", "warn",
             fix="leave cluster.dcn-overlap-drain true (the default), "
                 "or disable checkpointing / overlap")
def dcn_overlap_unsafe(plan, config) -> Iterable[Finding]:
    """Step-overlapped cross-host exchange with checkpointing but the
    barrier drain DISABLED: the snapshot's source positions include
    the one in-flight exchange step, whose records are still on the
    wire — a restore from that checkpoint skips past them (at-most-
    once for that step). The drain exists exactly so the cut covers
    every routed record; turning it off is a loss-tolerant perf trade
    that must be a visible decision, not a silent config."""
    from flink_tpu.config import CheckpointingOptions, ClusterOptions

    if int(config.get(ClusterOptions.NUM_PROCESSES)) <= 1:
        return  # no cross-host exchange in this job
    if int(config.get(CheckpointingOptions.INTERVAL)) <= 0:
        return  # nothing snapshots: nothing to miss the cut
    if not bool(config.get(ClusterOptions.DCN_OVERLAP)):
        return  # lockstep loop: the barrier IS the dispatch
    if bool(config.get(ClusterOptions.DCN_OVERLAP_DRAIN)):
        return  # drained at the barrier: the cut is complete
    yield _f(
        "cluster.dcn-overlap is on with checkpointing but "
        "cluster.dcn-overlap-drain is false: the in-flight overlapped "
        "exchange step is NOT drained at the checkpoint barrier, so "
        "its records are in the snapshot's source positions but in "
        "nobody's state — a restore from that checkpoint loses them "
        "(at-most-once for that step)",
        fix="leave cluster.dcn-overlap-drain true (the default; one "
            "extra consume per checkpoint), or disable "
            "cluster.dcn-overlap / checkpointing if the pipeline "
            "tolerates loss")


@config_rule("CHECKPOINT_IN_BATCH", "error",
             fix="drop checkpointing config or run in streaming mode")
def checkpoint_in_batch(plan, config) -> Iterable[Finding]:
    """Bounded-mode recovery is re-execution: nothing checkpoints, so a
    checkpoint interval or an explicit restore path is a config
    contradiction (the driver rejects it at run; this catches it at
    submit)."""
    from flink_tpu.config import CheckpointingOptions

    if _runtime_mode(config) != "batch":
        return
    if config.get(CheckpointingOptions.INTERVAL) > 0:
        yield _f(
            "execution.checkpointing.interval is incompatible with "
            "execution.runtime-mode=batch (bounded-mode recovery is "
            "re-execution; 2PC sinks commit once at end of input)",
            fix="drop the interval, or run in streaming mode")
    restore = str(config.get(CheckpointingOptions.RESTORE)).strip()
    if restore and restore != "latest":
        # restore=latest is injected by supervisor redeploys and the
        # driver degrades it to a fresh run; an explicit path cannot work
        yield _f(
            f"execution.checkpointing.restore={restore!r} is "
            "incompatible with execution.runtime-mode=batch (nothing "
            "checkpoints in batch mode — re-run the job)",
            fix="drop the restore path, or run in streaming mode")


@config_rule("RESCALE_INVALID", "error",
             fix="make the rescale.* config self-consistent")
def rescale_invalid(plan, config) -> Iterable[Finding]:
    """Rescale config that can never work (error) or that will thrash
    (warn), caught at submit instead of at the first arm:

    - reactive mode without checkpointing is an ERROR: the handshake is
      savepoint-based (stop-with-savepoint → key-group repartition →
      redeploy), so the controller would arm rescales whose savepoints
      the runner rejects, forever.
    - device bounds that violate the key-group discipline are an
      ERROR: the per-process shard share must stay divisible by every
      width the controller may pick, and an empty [min, max] range can
      pick none.
    - an inverted pressure band (low >= high) is an ERROR: the
      hysteresis dead zone is empty, so one sample can sit on both
      sides and the controller flaps by construction.

    The thrash-but-legal shapes warn instead (RESCALE_COOLDOWN_THRASH
    below)."""
    from flink_tpu.config import CheckpointingOptions, RescaleOptions

    mode = str(config.get(RescaleOptions.MODE)).strip().lower()
    if mode not in ("off", "reactive"):
        yield _f(
            f"rescale.mode={mode!r} is not a known mode",
            fix="use 'off' (manual RPC/CLI only) or 'reactive'")
        return
    if mode != "reactive":
        return
    interval = int(config.get(CheckpointingOptions.INTERVAL))
    if interval <= 0:
        yield _f(
            "rescale.mode=reactive without checkpointing: the rescale "
            "handshake is savepoint-based, so every controller-armed "
            "rescale would dispatch a stop-with-savepoint the runner "
            "rejects (no checkpoint storage) and disarm — an arm/"
            "disarm loop that never rescales",
            fix="set execution.checkpointing.interval (and .dir), or "
                "rescale.mode=off")
    hi = float(config.get(RescaleOptions.TARGET_PRESSURE_HIGH))
    lo = float(config.get(RescaleOptions.TARGET_PRESSURE_LOW))
    if lo >= hi:
        yield _f(
            f"rescale.target-pressure-low={lo:g} >= "
            f"rescale.target-pressure-high={hi:g}: the hysteresis dead "
            "zone is empty, so the controller classifies one pressure "
            "sample as both scale-out and scale-in and flaps",
            fix="keep low strictly below high (defaults 20/70)")
    try:
        shards = int(config.get_raw("state.num-key-shards", 128) or 128)
    except (TypeError, ValueError):
        shards = 128
    nproc = max(1, int(config.get_raw("cluster.num-processes", 1) or 1))
    share = shards // nproc if shards % nproc == 0 else 0
    mn = int(config.get(RescaleOptions.MIN_DEVICES))
    mx = int(config.get(RescaleOptions.MAX_DEVICES))
    if mn < 1:
        yield _f(
            f"rescale.min-devices={mn} is below 1",
            fix="set rescale.min-devices >= 1")
    elif mx and mx < mn:
        yield _f(
            f"rescale.max-devices={mx} < rescale.min-devices={mn}: "
            "the legal width range is empty — the controller can "
            "never pick a target",
            fix="widen the range (0 max = current fleet capacity)")
    if share:
        for opt, v in (("rescale.min-devices", mn),
                       ("rescale.max-devices", mx)):
            if v > 0 and share % v != 0:
                yield _f(
                    f"{opt}={v} does not divide the per-process shard "
                    f"share ({shards} shards / {nproc} processes = "
                    f"{share}): the key-group discipline (contiguous "
                    "equal ranges per device) is unsatisfiable at that "
                    "width, so the controller would clamp against a "
                    "bound it can never reach",
                    fix=f"pick a divisor of {share} (powers of two "
                        "divide the default 128)")


@config_rule("RESCALE_COOLDOWN_THRASH", "warn",
             fix="keep rescale.cooldown above "
                 "execution.checkpointing.interval")
def rescale_cooldown_thrash(plan, config) -> Iterable[Finding]:
    """A reactive rescale cooldown below the checkpoint interval: the
    controller can re-arm before the first post-rescale checkpoint
    publishes, so every rescale restores from the previous rescale's
    savepoint floor instead of fresh progress — legal (exactly-once
    holds), but under sustained pressure the job spends its life
    savepointing and restoring rather than processing. Warn, not
    error: a one-shot burst workload may want an aggressive cooldown
    and accept the tax."""
    from flink_tpu.config import CheckpointingOptions, RescaleOptions

    mode = str(config.get(RescaleOptions.MODE)).strip().lower()
    if mode != "reactive":
        return
    interval = int(config.get(CheckpointingOptions.INTERVAL))
    if interval <= 0:
        return  # RESCALE_INVALID owns the no-checkpointing error
    cooldown = int(config.get(RescaleOptions.COOLDOWN))
    if cooldown < interval:
        yield _f(
            f"rescale.cooldown={cooldown}ms is below "
            f"execution.checkpointing.interval={interval}ms: the "
            "controller can re-arm before the first post-rescale "
            "checkpoint publishes, so back-to-back rescales keep "
            "restoring the previous savepoint floor — the job "
            "thrashes between savepoint and restore under sustained "
            "pressure",
            fix=f"set rescale.cooldown >= {interval}ms (and ideally "
                "several checkpoint intervals)")


@config_rule("STATE_BUDGET_INVALID", "error",
             fix="make the state.* backend config self-consistent")
def state_budget_invalid(plan, config) -> Iterable[Finding]:
    """State-backend config that can never work (error) or that does
    nothing (warn), caught at submit:

    - an unknown ``state.backend`` is an ERROR: the driver rejects the
      job at build (runtime/driver.py validates against hbm/spill/lsm).
    - an lsm memory budget below ``state.lsm.run-floor-bytes`` is an
      ERROR: the delta would seal a degenerate run on nearly every
      batch, turning every absorb into an fsync — the disk tier
      becomes a write amplifier instead of a spill tier.
    - ``state.lsm.compact-min-runs`` below 2 is an ERROR: a compaction
      of fewer than two runs merges nothing, and the store would arm
      it after every seal.

    The does-nothing shape warns instead (STATE_BUDGET_IGNORED
    below)."""
    from flink_tpu.config import StateOptions

    backend = str(config.get(StateOptions.BACKEND)).strip().lower()
    if backend not in ("hbm", "spill", "lsm"):
        yield _f(
            f"state.backend={backend!r} is not a known backend — the "
            "driver rejects the job at build",
            fix="use 'hbm' (dense device panes), 'spill' (RAM host "
                "offload) or 'lsm' (disk tier)")
        return
    if backend != "lsm":
        return
    try:
        budget = int(config.get(StateOptions.MEMORY_BUDGET_BYTES))
        floor = int(config.get(StateOptions.LSM_RUN_FLOOR_BYTES))
    except (TypeError, ValueError):
        yield _f(
            "state.memory-budget-bytes / state.lsm.run-floor-bytes do "
            "not parse as integers",
            fix="set byte counts (default budget 64 MiB, floor 64 KiB)")
        return
    if budget < floor:
        yield _f(
            f"state.memory-budget-bytes={budget} is below "
            f"state.lsm.run-floor-bytes={floor}: the lsm delta would "
            "seal a degenerate run on nearly every batch — every "
            "absorb becomes an fsync and the disk tier amplifies "
            "writes instead of spilling them",
            fix=f"raise the budget to >= {floor} bytes (or lower the "
                "floor if tiny runs are intended, e.g. crash tests)")
    try:
        cmin = int(config.get(StateOptions.LSM_COMPACT_MIN_RUNS))
    except (TypeError, ValueError):
        yield _f(
            "state.lsm.compact-min-runs does not parse as an integer",
            fix="set an integer >= 2 (default 4)")
        return
    if cmin < 2:
        yield _f(
            f"state.lsm.compact-min-runs={cmin} is below 2 — a "
            "compaction of fewer than two runs merges nothing, and "
            "the store would arm one after every seal",
            fix="set state.lsm.compact-min-runs >= 2 (default 4)")


@config_rule("STATE_BUDGET_IGNORED", "warn",
             fix="set state.backend=lsm, or drop the key")
def state_budget_ignored(plan, config) -> Iterable[Finding]:
    """``state.memory-budget-bytes`` explicitly set while the backend
    is not 'lsm': hbm/spill hold all state resident and ignore the
    key, so the bound the operator thinks they configured does not
    exist — the job OOMs exactly as if the key were absent."""
    from flink_tpu.config import StateOptions

    backend = str(config.get(StateOptions.BACKEND)).strip().lower()
    if backend == "lsm" or backend not in ("hbm", "spill"):
        return  # STATE_BUDGET_INVALID owns the unknown-backend error
    if "state.memory-budget-bytes" in config.keys():
        yield _f(
            "state.memory-budget-bytes is set but "
            f"state.backend={backend!r} ignores it — only the 'lsm' "
            "backend bounds its in-memory delta; this job holds all "
            "state resident",
            fix="set state.backend=lsm to enable the disk tier, or "
                "drop the key")


def load_option_grammar() -> None:
    """Import every module that declares ConfigOptions so the registry
    is complete before a key-validity check (options register at module
    import; a job that never touches metrics would otherwise see
    ``metrics.port`` as unknown)."""
    import flink_tpu.config  # noqa: F401
    import flink_tpu.faults  # noqa: F401
    import flink_tpu.obs.metrics  # noqa: F401
