"""Typed configuration system.

Reproduces the capability of the reference's Configuration stack
(ref: flink-core/.../configuration/Configuration.java, ConfigOption.java,
ConfigOptions.java, GlobalConfiguration.java): typed options with defaults
and doc strings, addressable as dotted ``a.b.c`` keys, layered resolution
(defaults < file < env < explicit overrides).

TPU-first deltas: no YAML dependency required (plain ``key: value`` /
JSON files both parse); options that shape compiled programs (microbatch
size, key shards, pane ring length) are surfaced here because they become
*static* shapes under jit.
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Callable, Dict, Generic, Iterator, Mapping, Optional, TypeVar

T = TypeVar("T")

_REGISTRY: Dict[str, "ConfigOption[Any]"] = {}


@dataclasses.dataclass(frozen=True)
class ConfigOption(Generic[T]):
    """A typed option constant (ref: ConfigOption.java).

    ``parse`` converts a string (env/file) representation to ``T``.
    """

    key: str
    default: T
    description: str = ""
    parse: Optional[Callable[[str], T]] = None

    def __post_init__(self) -> None:
        _REGISTRY[self.key] = self

    def _coerce(self, raw: Any) -> T:
        if isinstance(raw, str) and self.parse is not None:
            return self.parse(raw)
        if isinstance(raw, str) and isinstance(self.default, bool):
            return raw.strip().lower() in ("1", "true", "yes", "on")  # type: ignore[return-value]
        if isinstance(raw, str) and isinstance(self.default, int):
            return int(raw)  # type: ignore[return-value]
        if isinstance(raw, str) and isinstance(self.default, float):
            return float(raw)  # type: ignore[return-value]
        return raw


def all_options() -> Mapping[str, ConfigOption[Any]]:
    """Registry of every declared option — the docs-generation seam
    (ref: flink-docs/ config option reference generator)."""
    return dict(_REGISTRY)


# Namespaces whose keys are legal without a per-key declaration — the
# plan analyzer's CONFIG_KEY_UNKNOWN rule and the repo lints treat any
# key under a declared prefix as grammatical. Use sparingly: a dynamic
# prefix trades per-key validation away for open-ended parameters.
_DYNAMIC_PREFIXES: Dict[str, str] = {}


def declare_dynamic_prefix(prefix: str, description: str = "") -> str:
    if not prefix.endswith("."):
        raise ValueError(f"dynamic prefix must end with '.': {prefix!r}")
    _DYNAMIC_PREFIXES[prefix] = description
    return prefix


def dynamic_prefixes() -> Mapping[str, str]:
    return dict(_DYNAMIC_PREFIXES)


def is_declared_key(key: str) -> bool:
    """True when ``key`` is part of the config grammar: a registered
    option or under a declared dynamic prefix."""
    return key in _REGISTRY or any(
        key.startswith(p) for p in _DYNAMIC_PREFIXES)


# test.* carries per-job parameters of the deployable test jobs
# (tests/runner_job*.py) through the submitted Configuration — the
# job-jar argument channel of the test harness.
declare_dynamic_prefix(
    "test.", "test-harness job parameters (tests/runner_job*.py)")


class Configuration:
    """Layered key→value store (ref: Configuration.java).

    Resolution order, lowest to highest precedence:
    option defaults < loaded file < ``FLINK_TPU_*`` env vars < ``set()``.
    """

    ENV_PREFIX = "FLINK_TPU_"

    def __init__(self, values: Optional[Mapping[str, Any]] = None) -> None:
        self._file: Dict[str, Any] = {}
        self._explicit: Dict[str, Any] = dict(values or {})

    # -- loading ---------------------------------------------------------
    @classmethod
    def from_file(cls, path: str) -> "Configuration":
        """Load ``key: value`` lines or a JSON object
        (ref: GlobalConfiguration.loadConfiguration)."""
        conf = cls()
        with open(path, "r", encoding="utf-8") as f:
            text = f.read()
        stripped = text.lstrip()
        if stripped.startswith("{"):
            conf._file.update(json.loads(text))
            return conf
        for line in text.splitlines():
            line = line.strip()
            if not line or line.startswith("#"):
                continue
            if ":" in line:
                k, _, v = line.partition(":")
            elif "=" in line:
                k, _, v = line.partition("=")
            else:
                continue
            conf._file[k.strip()] = v.strip()
        return conf

    def _env_lookup(self, key: str) -> Optional[str]:
        env_key = self.ENV_PREFIX + key.upper().replace(".", "_").replace("-", "_")
        return os.environ.get(env_key)

    # -- access ----------------------------------------------------------
    def get(self, option: ConfigOption[T]) -> T:
        if option.key in self._explicit:
            return option._coerce(self._explicit[option.key])
        env = self._env_lookup(option.key)
        if env is not None:
            return option._coerce(env)
        if option.key in self._file:
            return option._coerce(self._file[option.key])
        return option.default

    def get_raw(self, key: str, default: Any = None) -> Any:
        if key in self._explicit:
            return self._explicit[key]
        env = self._env_lookup(key)
        if env is not None:
            return env
        return self._file.get(key, default)

    def set(self, option: "ConfigOption[T] | str", value: Any) -> "Configuration":
        key = option.key if isinstance(option, ConfigOption) else option
        self._explicit[key] = value
        return self

    def merged_with(self, other: "Configuration") -> "Configuration":
        out = Configuration()
        out._file = {**self._file, **other._file}
        out._explicit = {**self._explicit, **other._explicit}
        return out

    def keys(self) -> Iterator[str]:
        seen = set(self._file) | set(self._explicit)
        return iter(sorted(seen))

    def to_dict(self) -> Dict[str, Any]:
        out: Dict[str, Any] = dict(self._file)
        out.update(self._explicit)
        return out

    def __repr__(self) -> str:  # pragma: no cover
        return f"Configuration({self.to_dict()!r})"


def _parse_duration_ms(raw: str) -> int:
    """Parse '10 s', '500ms', '1 min' style durations to milliseconds
    (ref: flink-core/.../configuration/TimeUtils.java)."""
    raw = raw.strip().lower()
    units = [
        ("ms", 1),
        ("milliseconds", 1),
        ("s", 1000),
        ("sec", 1000),
        ("seconds", 1000),
        ("min", 60_000),
        ("minutes", 60_000),
        ("h", 3_600_000),
        ("hours", 3_600_000),
        ("d", 86_400_000),
        ("days", 86_400_000),
    ]
    # longest suffix match wins so "ms" is not parsed as "s"
    for suffix, mult in sorted(units, key=lambda u: -len(u[0])):
        if raw.endswith(suffix):
            num = raw[: -len(suffix)].strip()
            return int(float(num) * mult)
    return int(float(raw))


def duration_option(key: str, default_ms: int, description: str = "") -> ConfigOption[int]:
    return ConfigOption(key, default_ms, description, parse=_parse_duration_ms)


# ---------------------------------------------------------------------------
# Core option catalog (ref: TaskManagerOptions / CheckpointingOptions /
# ExecutionOptions catalogs in flink-core/.../configuration/).
# ---------------------------------------------------------------------------

class PipelineOptions:
    MICROBATCH_SIZE = ConfigOption(
        "pipeline.microbatch-size", 8192,
        "Records per device per step. Static shape under jit; the latency/"
        "throughput knob (the BufferDebloater analogue tunes it at runtime).")
    AUTO_WATERMARK_INTERVAL = duration_option(
        "pipeline.auto-watermark-interval", 200,
        "How often the host watermark clock advances (ref: "
        "pipeline.auto-watermark-interval).")
    OBJECT_REUSE = ConfigOption(
        "pipeline.object-reuse", True,
        "Reuse ingest buffers between steps (always safe here: device "
        "owns data after dispatch).")
    MAX_INFLIGHT_STEPS = ConfigOption(
        "pipeline.max-inflight-steps", 3,
        "Microbatch dispatches allowed in flight before ingest blocks on "
        "the oldest — bounds the transport/device queue so emit polls "
        "and checkpoints wait on at most this much backlog (the "
        "credit-based flow-control analogue: SPMD backpressure = step "
        "time; this is the credit count).")
    SOURCE_PREFETCH = ConfigOption(
        "pipeline.source-prefetch", 2,
        "Batches each source split pulls ahead on a feeder thread, so "
        "record generation/decode overlaps the loop's keying + transfer "
        "+ dispatch work (ref: the SourceReader split-fetcher thread "
        "model). 0 disables.")
    EXCHANGE_CAPACITY = ConfigOption(
        "pipeline.exchange-capacity", 0,
        "Per-(source, destination) bucket capacity of the keyBy "
        "all_to_all exchange, in records. Bounds the exchange buffer to "
        "devices x capacity per device. 0 = auto (per-device block "
        "size: can never overflow). When set, batches are SPLIT on the "
        "host so no bucket can exceed it — skewed keys cost extra "
        "steps, never data (ref: credit-based flow control's no-loss "
        "property, SURVEY §3.6).")
    EMIT_DEFER_MS = duration_option(
        "pipeline.emit-defer", -1,
        "An age floor for a fired batch: how long the emit drain thread "
        "lets it age before it waits for the rows' device→host copy "
        "(issued at dispatch) to land and reads it (ref role: "
        "BufferDebloater's in-flight target). -1 = auto: none, on every "
        "backend: the drain waits for the landing itself, under no "
        "lock, so a fired row leaves when its copy is there. A "
        "checkpoint barrier or end-of-input flush ends either wait "
        "immediately.")
    TARGET_LATENCY = duration_option(
        "pipeline.target-latency", 0,
        "Adaptive microbatch debloater (ref: BufferDebloater — auto-"
        "size in-flight buffers to hit a latency target): when > 0, the "
        "driver re-chunks source batches at ingest, halving the chunk "
        "while recent emit p99 exceeds the target and growing it back "
        "toward the source batch size while p99 sits under half the "
        "target. 0 = off (source batch size rules, maximum throughput).")
    PROFILE_DIR = ConfigOption(
        "pipeline.profile-dir", "",
        "When set, the driver wraps pipeline.profile-steps WARM logical "
        "batches (after pipeline.profile-skip) of the streaming run in "
        "jax.profiler.trace(dir) and writes a per-op device-time "
        "summary to <dir>/profile_summary.json (flink_tpu/obs/"
        "profiling.py; the summary also rides JobResult.metrics under "
        "'profile.trace_summary'). The first-class seam for naming "
        "per-op device costs that black-box bisection cannot. Empty = "
        "off (zero overhead).")
    PROFILE_STEPS = ConfigOption(
        "pipeline.profile-steps", 8,
        "Logical batches captured inside the jax.profiler.trace window "
        "when pipeline.profile-dir is set.")
    PROFILE_SKIP = ConfigOption(
        "pipeline.profile-skip", 4,
        "Warm-up logical batches to run BEFORE the profiler trace "
        "starts (compile + cache warm-up must not pollute the per-op "
        "summary) when pipeline.profile-dir is set.")


class ExecutionOptions:
    RUNTIME_MODE = ConfigOption(
        "execution.runtime-mode", "streaming",
        "'streaming' (default): one pipelined region, per-microbatch "
        "watermark advance, continuous window fires. 'batch': bounded "
        "execution (ref: execution.runtime-mode=BATCH, SURVEY §3.7) — "
        "requires every source to report bounded=True; the compiler "
        "marks stage-boundary edges BLOCKING, stages run in topological "
        "waves (runtime/scheduler.py), each upstream stage materializes "
        "its full output to columnar partition files "
        "(exchange/blocking.py + formats_columnar.py), and stateful "
        "operators fire exactly once at end-of-input (no per-step fire "
        "scans). Recovery is re-execution: checkpointing/restore are "
        "rejected in this mode. Honest scope: no sort-merge spill, no "
        "speculative execution (SURVEY §3.7 SPMD rationale).")
    BATCH_SHUFFLE_DIR = ConfigOption(
        "execution.batch.shuffle-dir", "/tmp/flink-tpu-shuffle",
        "Root directory for blocking-shuffle partition files of batch "
        "(bounded-mode) jobs. Node-local scratch space — the analogue "
        "of io.tmp.dirs for BoundedBlockingSubpartition spill files; "
        "each run spools under a unique subdirectory.")
    BATCH_SHUFFLE_PARTITIONS = ConfigOption(
        "execution.batch.shuffle-partitions", 1,
        "Partition files per KEYED blocking edge: records hash-route "
        "by key (the same hash as the runtime exchange) so each file "
        "holds a disjoint key range, preserving per-key record order. "
        "Non-keyed edges always spool to a single file.")
    BATCH_SHUFFLE_CLEANUP = ConfigOption(
        "execution.batch.shuffle-cleanup", True,
        "Delete the run's shuffle spool directory when the job ends "
        "(success or failure). Set false to keep partition files for "
        "inspection.")


class LogOptions:
    DIR = ConfigOption(
        "log.dir", "/tmp/flink-tpu-log",
        "Root directory for embedded durable-log topics (flink_tpu/log/"
        "— the job-chaining exchange plane, the Kafka role without a "
        "broker process). LogSink.from_config resolves a topic name "
        "under this root; any registered FileSystem scheme works. Jobs "
        "chained through one topic must share this filesystem.")
    PARTITIONS = ConfigOption(
        "log.partitions", 1,
        "Default partition count for topics created by "
        "LogSink.from_config. Partitions are the source-split unit of "
        "LogSource (one replayable split per partition); records "
        "hash-route by the sink's key_field, so each partition holds a "
        "disjoint key range and per-key order is preserved. Fixed at "
        "topic creation — reopening with a different count fails "
        "loudly (offsets are per-partition).")
    SEGMENT_RECORDS = ConfigOption(
        "log.segment-records", 65536,
        "Records per appended log segment before the appender rolls to "
        "a new file within one transaction. Every segment is written "
        "sealed (columnar footer + fsync) at pre-commit, so this is "
        "also the recovery/replay granularity of a topic partition.")
    FSYNC_MODE = ConfigOption(
        "log.fsync-mode", "group",
        "Segment durability discipline at transaction pre-commit: "
        "'group' (default) writes every staged segment first and runs "
        "ONE group-commit fsync pass over all of them strictly before "
        "the pre-commit marker publishes (fsyncs overlap through the "
        "host pool on multi-partition topics); 'segment' is the legacy "
        "fsync-per-file-at-write discipline. The 2PC crash-window "
        "semantics are identical: the marker rename — the point after "
        "which a transaction is recoverable — always strictly follows "
        "every segment fsync.")
    ZERO_COPY = ConfigOption(
        "log.zero-copy", True,
        "LogSource decode mode: true mmaps sealed local-fs segments "
        "and returns fixed-width columns as read-only np.frombuffer "
        "views (no read() image copy, no per-column decode copy; "
        "block CRCs still verified, corruption/truncation exactly as "
        "loud). false is the legacy copying decode. Non-local schemes "
        "and big-endian hosts degrade to copying automatically.")
    READ_BATCH_RECORDS = ConfigOption(
        "log.read-batch-records", 262_144,
        "LogSource read-batch coalescing target: on-disk blocks merge "
        "until a batch holds at least this many rows before entering "
        "the pipeline — small sealed blocks otherwise starve the "
        "device path with tiny dispatches (the backfill bench's "
        "dominant cost on a CPU container). Replay "
        "positions advance at merged-batch boundaries and stay "
        "checkpoint-exact. 0 = per-block reads (the legacy "
        "granularity).")
    PREFETCH_SEGMENTS = ConfigOption(
        "log.prefetch-segments", 1,
        "Merged read batches the LogSource decodes ahead on a feeder "
        "thread while the pipeline consumes the current one (the "
        "cluster.dcn-overlap shape at the segment-read seam; 1 = "
        "double-buffered). 0 disables — reads run inline on the "
        "consuming thread. Positions stay checkpoint-exact: only "
        "consumed batches advance them, a restore re-reads from the "
        "frozen offset.")
    COMPACTION_KEY_FIELD = ConfigOption(
        "log.compaction.key-field", "",
        "Key column for latest-wins key compaction (log/bus.py "
        "Compactor): sealed committed segments below the safety floor "
        "are rewritten keeping only the latest committed row per key, "
        "original offsets preserved. Empty = the key_field recorded in "
        "the topic's meta.json at creation (the sink's routing key).")
    COMPACTION_MIN_SEGMENTS = ConfigOption(
        "log.compaction.min-segments", 2,
        "Only compact a partition when at least this many sealed "
        "committed segments sit wholly below the safety floor — a "
        "single segment gains nothing from a rewrite; raising it "
        "amortizes rewrite I/O over more input (the Kafka "
        "min.cleanable.dirty.ratio role, count-based).")
    RETENTION_MS = ConfigOption(
        "log.retention.ms", 0,
        "Retention window: whole sealed segments whose newest row is "
        "older than this (by the topic's ts column) are dropped, but "
        "NEVER above the safety floor (lowest consumer-group committed "
        "offset / open pre-commit marker). 0 = keep forever.")
    RETENTION_TS_FIELD = ConfigOption(
        "log.retention.ts-field", "",
        "Event-time column used by log.retention.ms: a segment's age "
        "is now minus its newest row's value in this column. Required "
        "whenever log.retention.ms > 0 — a time-retention pass "
        "without it fails loudly (size-only retention leaves both "
        "unset).")
    RETENTION_BYTES = ConfigOption(
        "log.retention.bytes", 0,
        "Per-partition size budget: oldest whole sealed segments are "
        "dropped until the partition fits, subject to the same safety "
        "floor as log.retention.ms. 0 = unbounded.")
    LEASE_TTL_MS = ConfigOption(
        "log.lease.ttl-ms", 30_000,
        "Per-partition writer-lease time-to-live (log/bus.py "
        "LeaseManager). A producer renews its leases at every stage/"
        "commit; a lease this stale is expired and another producer "
        "may take the partition over with a bumped fencing epoch — "
        "the deposed holder's late writes are rejected by epoch.")
    GROUP_NAME = ConfigOption(
        "log.group.name", "",
        "Consumer-group name for LogSource.from_config: members share "
        "a topic with per-partition committed offsets published at "
        "checkpoint complete (the compaction/retention safety floor "
        "and the cross-generation resume point). Empty = no group "
        "(anonymous reader, offsets live only in the job checkpoint).")
    GROUP_MEMBER = ConfigOption(
        "log.group.member", 0,
        "This reader's member index within log.group.members: static "
        "partition assignment p % members == member (disjoint, "
        "deterministic — no broker to rebalance).")
    GROUP_MEMBERS = ConfigOption(
        "log.group.members", 1,
        "Total members in the consumer group; together with "
        "log.group.member this fixes the partition assignment. All "
        "members of one group must agree on this count.")
    GROUP_MEMBER_ID = ConfigOption(
        "log.group.member-id", "",
        "DYNAMIC membership: a non-empty id makes LogSource.from_config "
        "join the group's durable membership manifest at open "
        "(idempotent re-join on restart) and derive its partition "
        "assignment from the manifest's sorted member list — the "
        "generation-fenced rebalance protocol, instead of the static "
        "log.group.member/members pair. Offset commits are keyed by "
        "the joined generation; a member deposed by a rebalance it "
        "missed has its late commit rejected at the fence. Members "
        "leave explicitly (ConsumerGroups.leave / the log CLI), not on "
        "close — a restart must keep its seat.")
    CLEANER_ENABLED = ConfigOption(
        "log.cleaner.enabled", False,
        "Run the driver-owned background cleaner service "
        "(log/cleaner.py): one maintenance thread per log topic the "
        "job writes, executing compaction + retention per the "
        "log.compaction.*/log.retention.* grammar at "
        "log.cleaner.interval-ms cadence under a fenced cleaner lease "
        "and the per-topic maintenance lock. False (default) keeps "
        "maintenance an explicit CLI invocation (`log TOPIC --compact/"
        "--retain`).")
    CLEANER_INTERVAL_MS = ConfigOption(
        "log.cleaner.interval-ms", 30_000,
        "Cadence of the background cleaner's maintenance passes per "
        "topic (the Kafka log.cleaner backoff role). Each pass runs "
        "compaction then retention below the safety floor; readers "
        "and leased producers race it freely — the manifest-swap "
        "discipline keeps their reads byte-identical.")
    CLEANER_LEASE_TTL_MS = ConfigOption(
        "log.cleaner.lease-ttl-ms", 60_000,
        "Time-to-live of the fenced cleaner lease (cleaner.lease in "
        "the topic dir): exactly one cleaner service owns a topic's "
        "maintenance at a time, a crashed cleaner's lease expires "
        "after this, and a deposed cleaner's late pass dies at its "
        "next lease verify (the writer-lease epoch discipline).")


class CoreOptions:
    PLUGINS = ConfigOption(
        "plugins.modules", "",
        "Comma-separated module names loaded at environment creation; "
        "each must expose register(registry) extending the FileSystem "
        "scheme registry (ref: core/plugin/PluginManager + "
        "FileSystemFactory SPI; see flink_tpu/fs.py).")


class StateOptions:
    NUM_KEY_SHARDS = ConfigOption(
        "state.num-key-shards", 128,
        "Fixed hash space decoupling logical keys from devices — the "
        "maxParallelism / key-group analogue (ref: "
        "runtime/state/KeyGroupRangeAssignment.java, default 128). Must be "
        "a multiple of the mesh device count.")
    SLOTS_PER_SHARD = ConfigOption(
        "state.slots-per-shard", 4096,
        "Distinct keys a shard can hold before spill/eviction. "
        "slots*shards bounds resident key cardinality in HBM.")
    BACKEND = ConfigOption(
        "state.backend", "hbm",
        "Keyed state backend: 'hbm' (dense pane tensors, the "
        "HeapKeyedStateBackend analogue), 'spill' (RAM-resident host "
        "offload, the RocksDB analogue) or 'lsm' (disk-backed spill "
        "tier: memtable delta bounded by state.memory-budget-bytes, "
        "sealed into CRC'd columnar runs with changelog checkpoints — "
        "the RocksDB + flink-dstl analogue, flink_tpu/state/lsm.py).")
    MEMORY_BUDGET_BYTES = ConfigOption(
        "state.memory-budget-bytes", 64 * 1024 * 1024,
        "RAM ceiling for the in-memory delta (memtable) of the 'lsm' "
        "backend, per windowed operator; when the delta's pane tables "
        "exceed it, the delta is sealed into a sorted on-disk run. "
        "Ignored by 'hbm' and 'spill' (those hold all state resident). "
        "Must be at least state.lsm.run-floor-bytes.")
    LSM_DIR = ConfigOption(
        "state.lsm.dir", "/tmp/flink-tpu-state",
        "Root directory for 'lsm' backend run files; each operator "
        "instance gets a unique store subdirectory. Local filesystem "
        "only (runs are mmap'd for zero-copy scans).")
    LSM_COMPACT_MIN_RUNS = ConfigOption(
        "state.lsm.compact-min-runs", 4,
        "Sealed-run count that triggers a leveled compaction pass "
        "(k-way monoid merge of all live runs into one higher-level "
        "run, under the store's maintenance lock). Minimum 2.")
    LSM_RUN_FLOOR_BYTES = ConfigOption(
        "state.lsm.run-floor-bytes", 65536,
        "Smallest useful sealed-run size; a memory budget below this "
        "floor would seal degenerate runs on nearly every batch and is "
        "rejected at analysis time (STATE_BUDGET_INVALID).")
    ALLOW_DROPS = ConfigOption(
        "state.allow-drops", False,
        "When a key-directory shard fills under state.backend='hbm', "
        "the DEFAULT is to FAIL the job loudly (the reference degrades "
        "but never drops — RocksDB's role, SURVEY §3.4). Set true to "
        "instead drop overflow keys' records with accounting "
        "(records_dropped_full), or use state.backend='spill' for "
        "exact host-side degradation.")


class StorageOptions:
    """The durable-storage degradation grammar (flink_tpu/fs.py): how
    the FileSystem seam behaves when the disk itself fails under a
    write — the crash-consistency plane's runtime half."""

    ENOSPC_POLICY = ConfigOption(
        "storage.enospc-policy", "retry",
        "How a durable write seam (checkpoint persist, log segment "
        "stage, sink part write — everything routed through "
        "fs.write_atomic/enospc_retry) handles OSError(ENOSPC): "
        "'retry' (default) re-attempts the whole-file write with "
        "bounded backoff (retention/rotation may free space between "
        "attempts; every re-attempt counts on the "
        "storage.enospc_retries metric, exhausted budgets count toward "
        "execution.checkpointing.tolerable-failures like any persist "
        "failure) or 'fail' (propagate immediately). Either way the "
        "tmp+fsync+rename discipline guarantees no torn file at a "
        "final name.")
    ENOSPC_RETRIES = ConfigOption(
        "storage.enospc-retries", 4,
        "Bounded retry budget per whole-file write under "
        "storage.enospc-policy=retry (0 behaves like 'fail').")
    ENOSPC_BACKOFF_MS = ConfigOption(
        "storage.enospc-backoff-ms", 50.0,
        "First retry delay in ms under storage.enospc-policy=retry; "
        "doubles per attempt.")


class CheckpointingOptions:
    INTERVAL = duration_option(
        "execution.checkpointing.interval", 0,
        "Checkpoint period in ms; 0 disables (ref: "
        "execution.checkpointing.interval).")
    DIRECTORY = ConfigOption(
        "execution.checkpointing.dir", "/tmp/flink-tpu-checkpoints",
        "Checkpoint storage root (ref: state.checkpoints.dir).")
    RETAINED = ConfigOption(
        "execution.checkpointing.num-retained", 3,
        "Completed checkpoints kept (ref: state.checkpoints.num-retained).")
    INCREMENTAL = ConfigOption(
        "execution.checkpointing.incremental", True,
        "Reuse (hardlink) the previous checkpoint's blob for operators "
        "whose state_version is unchanged — the RocksDB shared-SST "
        "analogue (checkpoint/storage.py format v2). False forces full "
        "re-serialization every checkpoint.")
    COMPRESSION = ConfigOption(
        "execution.checkpointing.compression", "none",
        "Compress checkpoint payload files: 'none' or 'zlib' (ref: "
        "execution.checkpointing.snapshot-compression). Applied on the "
        "background checkpoint executor, never the ingest loop; "
        "recorded in the manifest so restore self-describes.")
    RESTORE = ConfigOption(
        "execution.checkpointing.restore", "",
        "'' (fresh start), 'latest' (resume from newest complete "
        "checkpoint), or a checkpoint/savepoint directory path (ref: "
        "execution.savepoint.path).")
    TOLERABLE_FAILURES = ConfigOption(
        "execution.checkpointing.tolerable-failures", 0,
        "Consecutive PERIODIC checkpoint persist/commit failures the "
        "job rides out before failing over (ref: execution.checkpointing"
        ".tolerable-failed-checkpoints, default 0 = any failure fails "
        "the job). A tolerated epoch stays staged in its 2PC sinks and "
        "commits with the next successful checkpoint — exactly-once is "
        "unaffected. Savepoints and the final end-of-input checkpoint "
        "are never tolerated. Single-process driver only: the "
        "cross-host (DCN) step loop treats any checkpoint failure as "
        "an attempt failure — its rendezvous-consensus cut has no "
        "per-process skip, so recovery goes through restore.")


class ClusterOptions:
    MESH_DEVICES = ConfigOption(
        "cluster.mesh-devices", "",
        "Operator parallelism over a 1-D jax.sharding.Mesh: '' = "
        "single-device local execution, 'all' = every visible device, "
        "an integer N = the first N devices. Each device owns "
        "num-key-shards/N contiguous key shards (the key-group range of "
        "its 'subtask'); keyed exchanges ride XLA all_to_all over the "
        "mesh axis (ref: parallelism.default + slot assignment, "
        "KeyGroupRangeAssignment).")
    NUM_PROCESSES = ConfigOption(
        "cluster.num-processes", 1,
        "Host-process count of ONE job (the cross-host data plane, ref "
        "SURVEY §3.6): each process owns num-key-shards/N contiguous "
        "key shards; keyed records route to their owner through the "
        "per-step DCN all-to-all (exchange/dcn.py), whose rendezvous "
        "also carries the global watermark, termination, and "
        "checkpoint-alignment consensus.")
    PROCESS_ID = ConfigOption(
        "cluster.process-id", 0,
        "This process's index in [0, cluster.num-processes).")
    DCN_PEERS = ConfigOption(
        "cluster.dcn-peers", "",
        "Comma-separated host:port of every process's exchange "
        "listener, indexed by process id (the coordinator fills this "
        "at deploy via the dcn rendezvous; tests set it directly).")
    DCN_PORT = ConfigOption(
        "cluster.dcn-port", 0,
        "This process's exchange listen port (0 = ephemeral).")
    DCN_SECRET = ConfigOption(
        "cluster.dcn-secret", "",
        "Per-job shared secret authenticating the DCN exchange "
        "handshake (HMAC over the hello; exchange/dcn.py). The "
        "coordinator mints one per attempt and ships it in the deploy "
        "config; static cluster.dcn-peers deployments set it "
        "themselves. Empty = unauthenticated (single-host loopback "
        "only).")
    DCN_OVERLAP = ConfigOption(
        "cluster.dcn-overlap", True,
        "Step-overlapped cross-host exchange (exchange/dcn.py "
        "exchange_async): the driver dispatches step N+1's frames and "
        "consumes step N's at the NEXT iteration, so the N-way "
        "rendezvous overlaps the device compute and the host "
        "ingest/route work of the following step instead of "
        "serializing with them. Committed output is identical — the "
        "barrier moves, the per-step consensus (watermark/termination/"
        "checkpoint) does not. False = consume at dispatch (the v0 "
        "lockstep loop; one step of extra exchange latency saved per "
        "barrier, useful when bisecting the exchange itself).")
    DCN_OVERLAP_DRAIN = ConfigOption(
        "cluster.dcn-overlap-drain", True,
        "Drain the ONE in-flight overlapped exchange step before "
        "snapshotting at a checkpoint barrier (the default, and the "
        "exactly-once contract: the cut covers every routed record). "
        "False skips the drain — the snapshot's source positions then "
        "include a step whose records are still on the wire, so a "
        "restore from that checkpoint LOSES them (at-most-once for "
        "that step). Only for pipelines that tolerate loss; the plan "
        "analyzer flags it (DCN_OVERLAP_UNSAFE).")
    DCN_IO_THREADS = ConfigOption(
        "cluster.dcn-io-threads", 0,
        "Sender-worker threads of the parallel DCN I/O plane. 0 = "
        "auto (one per peer — all N-1 sends overlap). A positive "
        "value caps the workers; peers are assigned round-robin and "
        "stick to one worker so per-peer frame order stays FIFO. "
        "Receive threads are always per-peer (each blocks on its own "
        "socket; they are the step barrier).")
    DCN_BUFFER_BYTES = ConfigOption(
        "cluster.dcn-buffer-bytes", 0,
        "SO_SNDBUF/SO_RCVBUF for every DCN exchange socket, in bytes. "
        "0 = OS default. Raise it (e.g. 4-16 MB) on high-bandwidth-"
        "delay cross-rack links so one step's frames fit in the "
        "kernel buffers and the sender workers never stall mid-step.")
    DCN_BIND = ConfigOption(
        "cluster.dcn-bind", "auto",
        "Address the exchange listener binds. 'auto' (default) stays "
        "on 127.0.0.1 unless the configured peers (cluster.dcn-peers / "
        "cluster.dcn-host) are off-host, then widens to 0.0.0.0; set "
        "an explicit address to override.")
    EXCHANGE_IMPL = ConfigOption(
        "exchange.impl", "all-to-all",
        "Keyed-exchange collective pattern (the Shuffle SPI seam, ref: "
        "runtime/shuffle ShuffleMaster/ShuffleEnvironment): "
        "'all-to-all' = one fused lax.all_to_all (bandwidth-optimal on "
        "a fully-connected ICI axis); 'ring' = N-1 lax.ppermute "
        "neighbor hops (ring-only topologies / per-hop overlap). "
        "Third-party implementations register via "
        "exchange.spi.register_shuffle.")
    HEARTBEAT_INTERVAL = duration_option(
        "heartbeat.interval", 10_000,
        "Runner→coordinator heartbeat period (ref: heartbeat.interval=10s).")
    HEARTBEAT_TIMEOUT = duration_option(
        "heartbeat.timeout", 50_000,
        "Declare a runner dead after this silence (ref: heartbeat.timeout=50s).")
    # -- deploy-injected identity keys (the TaskDeploymentDescriptor
    # analogue): the coordinator/runner stamp these into the attempt's
    # config at deploy; user configs normally never set them.
    ATTEMPT = ConfigOption(
        "cluster.attempt", 0,
        "This attempt's fencing epoch, minted by the coordinator on "
        "every (re)deploy. Qualifies in-progress artifacts — "
        "chk-<id>.e<epoch> checkpoints, part-file and log-segment "
        "names — so a deposed attempt can never clobber a successor.")
    COORDINATOR = ConfigOption(
        "cluster.coordinator", "",
        "HOST:PORT of the job coordinator's RPC server, injected by the "
        "runner at deploy (split enumeration, savepoint reporting).")
    JOB_ID = ConfigOption(
        "cluster.job-id", "",
        "Submitted job id, injected by the runner at deploy.")
    RUNNER_ID = ConfigOption(
        "cluster.runner-id", "",
        "This runner's id, injected at deploy (coordinator-side split "
        "enumeration keys on it).")
    DCN_HOST = ConfigOption(
        "cluster.dcn-host", "",
        "Advertised host of this process's DCN exchange listener "
        "(coordinator-brokered rendezvous; defaults to the RPC-visible "
        "address when empty).")
    DCN_RENDEZVOUS = ConfigOption(
        "cluster.dcn-rendezvous", "",
        "'coordinator' lets a multi-process job discover DCN peers "
        "through the coordinator instead of a static cluster.dcn-peers "
        "list; stamped into the attempt config at deploy.")
    RESCALE_FROM = ConfigOption(
        "cluster.rescale-from", "",
        "Deploy-injected by the coordinator after a process-level "
        "rescale: the savepoint path (p0's, for multi-process "
        "savepoints) the new topology was restored from. When a later "
        "attempt restores with execution.checkpointing.restore=latest "
        "and finds NO checkpoint newer than this savepoint (or none at "
        "all — the crash landed before the first post-rescale "
        "checkpoint published), the driver falls back to this path so "
        "recovery never resurrects a pre-rescale checkpoint written "
        "for the OLD key-group ownership, and never replays from "
        "scratch duplicating committed output. User configs never set "
        "it.")
    RESTART_STRATEGY = ConfigOption(
        "restart-strategy.type", "exponential-delay",
        "fixed-delay | exponential-delay | failure-rate | none (ref: "
        "runtime/executiongraph/failover restart strategies).")
    RESTART_ATTEMPTS = ConfigOption(
        "restart-strategy.fixed-delay.attempts", 3,
        "Max restarts for fixed-delay strategy.")
    RESTART_DELAY = duration_option(
        "restart-strategy.fixed-delay.delay", 1_000,
        "Delay between restarts for fixed-delay strategy.")


class HostOptions:
    PARALLELISM = ConfigOption(
        "host.parallelism", min(4, os.cpu_count() or 1),
        "Worker threads of the driver's shared host pool "
        "(flink_tpu/parallel/hostpool.py) running the host-resident "
        "operator paths: the key-sharded session span registry, the "
        "pane-partitioned spill store, and the chunked windowAll fold. "
        "The count-only window lane's native key scan "
        "(ingest_fused_scan) takes its width from the same number: a "
        "batch of 2 x 65,536 records or more is scanned as up to that "
        "many contiguous record ranges on native threads of its own "
        "and merged in range order, byte for byte the serial result. "
        "1 = the exact serial path (no pool threads, no scan threads; "
        "keeps single-core benchmark numbers reproducible). Default "
        "min(4, os.cpu_count()); the plan analyzer warns on values < 1 "
        "or beyond os.cpu_count() (HOST_PARALLELISM_INVALID).")
    FOLD_CHUNK_RECORDS = ConfigOption(
        "host.fold-chunk-records", 1 << 18,
        "Batch-size floor (and chunk size) of the host spill store's "
        "tree-reduction fold: batches below it absorb in one pass "
        "(pool dispatch overhead would exceed the fold); at or above "
        "it the batch splits into chunks of this "
        "many records whose pane partials combine in chunk order. The "
        "chunk size is independent of host.parallelism, so the "
        "reduction tree — and the output bytes — do not change with "
        "the worker count.")


class SessionOptions:
    """Session-cluster runtime mode (runtime/session.py, PAPER §3.4
    dispatcher / ResourceManager / slot pool + §4 session deployment):
    a long-lived SessionDispatcher multiplexes N submitted jobs onto a
    shared runner fleet through logical slot quotas, with fair drain
    scheduling and per-job isolation of checkpoints/faults/metrics."""

    SLOTS_PER_JOB = ConfigOption(
        "session.slots-per-job", 1,
        "Logical slots ONE job occupies on its runner (the slot-sharing "
        "group size, ref: taskmanager slot model). A job may raise it "
        "in its own submitted config to claim a bigger share; admission "
        "rejects values < 1 or above session.runner-slots (a quota no "
        "single runner can ever satisfy — SESSION_QUOTA_INVALID flags "
        "both at analyze time).")
    RUNNER_SLOTS = ConfigOption(
        "session.runner-slots", 4,
        "Logical slot capacity each registered runner contributes to "
        "the session slot pool (ref: taskmanager.numberOfTaskSlots). "
        "Per RUNNER HOST, not per device: the session plane shares one "
        "chip/host among jobs — device-exclusive placement stays the "
        "per-job (non-session) submit path.")
    MAX_JOBS = ConfigOption(
        "session.max-jobs", 8,
        "Maximum jobs RUNNING concurrently across the session cluster; "
        "submissions beyond it queue FIFO and deploy as running jobs "
        "finish (the Dispatcher submission queue). Queued depth feeds "
        "the autoscaler.")
    FAIR_DRAIN = ConfigOption(
        "session.fair-drain", False,
        "Serialize co-resident jobs' emit-ring drain fetches through a "
        "round-robin turnstile (runtime/session.py FairDrainGate) so "
        "one job's fire/drain burst cannot starve another's emit ring "
        "on the shared device→host link. The dispatcher stamps this "
        "true into every session deploy; single-job (non-session) runs "
        "default off and pay zero overhead.")
    CONCURRENT_JOBS = ConfigOption(
        "session.concurrent-jobs", 1,
        "Deploy-injected by the SessionDispatcher: the job's STATIC "
        "slot-proportional share denominator — how many jobs of its "
        "quota fit one runner (session.runner-slots // session.slots-"
        "per-job, clamped by session.max-jobs). The driver divides "
        "its host-pool worker count and in-flight step credit by it, "
        "so K co-resident tenants can never oversubscribe the host "
        "K-fold regardless of deploy order (the reference's per-slot "
        "managed-memory split discipline). User configs normally "
        "never set it.")
    SCOPED_FAULTS = ConfigOption(
        "session.scoped-faults", False,
        "Deploy-injected by the SessionDispatcher when a session job "
        "carries a faults.* plan: the runner installs it as a JOB-"
        "SCOPED plan (faults.install_scoped) instead of the process-"
        "global one, so one tenant's chaos schedule can never inject "
        "into a co-resident job (the per-job fault-plan isolation of "
        "the session contract).")
    AUTOSCALE = ConfigOption(
        "session.autoscale", True,
        "Run the dispatcher's autoscaler loop: submission-queue depth "
        "and aggregate slot pressure push scale-OUT demand through the "
        "provisioner seam (runtime/provisioner.py request_capacity); "
        "runners idle past session.scale-down-idle above session.min-"
        "runners drain (stop-with-savepoint redeploy) and are released "
        "(release_capacity). False = fixed fleet.")
    AUTOSCALE_INTERVAL = duration_option(
        "session.autoscale-interval", 2_000,
        "Autoscaler evaluation period.")
    MIN_RUNNERS = ConfigOption(
        "session.min-runners", 1,
        "Floor the autoscaler never drains below.")
    MAX_RUNNERS = ConfigOption(
        "session.max-runners", 8,
        "Ceiling on the runner fleet the autoscaler will request "
        "capacity for (scale-out demand is clamped here, mirroring the "
        "provisioner's own max_replicas guard).")
    SCALE_DOWN_IDLE = duration_option(
        "session.scale-down-idle", 30_000,
        "A runner holding zero session slots for this long (with the "
        "fleet above session.min-runners) is drained and released by "
        "the autoscaler.")
    HA_STANDBY = ConfigOption(
        "session.ha.standby", False,
        "Start this `session start` process as a hot-standby contender "
        "(the --standby flag sets it): it contends for the leadership "
        "lease in high-availability.dir and serves only once granted — "
        "on takeover it re-hydrates the durable session registry, "
        "re-queues undeployed jobs in original FIFO order, and waits "
        "for runners to re-attach their live executions. Requires "
        "high-availability.dir.")
    HA_REATTACH_GRACE = duration_option(
        "session.ha.reattach-grace", 10_000,
        "How long a new leader waits for a recovered RUNNING job's "
        "runner to re-register carrying it before falling back to a "
        "blind redeploy with restore:latest. A stored runner that "
        "re-registers WITHOUT the job collapses the window early (the "
        "execution died there); a runner that re-attaches it ends the "
        "wait with an in-place re-adoption (no redeploy, exactly-once "
        "preserved). Lower it when runners re-resolve the leader fast "
        "(small heartbeat.interval); raise it on congested fleets "
        "where a blind double-deploy is costlier than a slow failover.")


class RescaleOptions:
    """Reactive elastic rescaling (runtime/coordinator.py, ref: the
    AdaptiveScheduler / reactive mode, FLIP-159/160): the coordinator
    watches the heartbeat-carried backpressure/drain gauges and, when
    pressure stays outside the configured band for a sustained window,
    arms the SAME stop-with-savepoint → repartition → redeploy
    handshake `rescale JOB --devices N` drives manually. Key-group
    discipline (state.num-key-shards at a fixed max-parallelism) makes
    the N→M state move legal; cooldown + the two-sided band give
    hysteresis, so the controller cannot flap by construction."""

    MODE = ConfigOption(
        "rescale.mode", "off",
        "'off' (default) = rescale only via the manual RPC/CLI; "
        "'reactive' = the coordinator's policy loop arms rescales "
        "automatically from observed pressure. Reactive mode requires "
        "checkpointing (the handshake is savepoint-based) — the plan "
        "analyzer rejects it otherwise (RESCALE_INVALID).")
    TARGET_PRESSURE_HIGH = ConfigOption(
        "rescale.target-pressure-high", 70,
        "Upper bound of the target pressure band, in percent of the "
        "job's max(backpressure_pct, drain_busy_pct) heartbeat gauge. "
        "Pressure sustained ABOVE it arms a scale-OUT to the next "
        "legal width (divisibility-preserving doubling, clamped by "
        "rescale.max-devices).")
    TARGET_PRESSURE_LOW = ConfigOption(
        "rescale.target-pressure-low", 20,
        "Lower bound of the band: pressure sustained BELOW it arms a "
        "scale-IN to the previous legal width (halving, floored at "
        "rescale.min-devices). The gap between low and high is the "
        "hysteresis dead zone — a signal oscillating inside it never "
        "triggers.")
    SUSTAINED_WINDOW = duration_option(
        "rescale.sustained-window", 30_000,
        "How long pressure must stay continuously outside the band "
        "before the controller arms a rescale. One in-band sample "
        "resets the clock, so transient spikes (a slow checkpoint, a "
        "GC pause) never rescale the job.")
    COOLDOWN = duration_option(
        "rescale.cooldown", 120_000,
        "Minimum time between controller-armed rescales of one job, "
        "measured from the last rescale COMPLETING (redeploy at the "
        "new width). Keep it above the checkpoint interval — a "
        "cooldown shorter than execution.checkpointing.interval "
        "re-arms before the first post-rescale checkpoint publishes "
        "(RESCALE_INVALID warns).")
    MIN_DEVICES = ConfigOption(
        "rescale.min-devices", 1,
        "Floor the reactive controller never scales below.")
    MAX_DEVICES = ConfigOption(
        "rescale.max-devices", 0,
        "Ceiling the reactive controller never scales above. 0 = the "
        "job's current fleet capacity (largest registered runner).")


class AnalysisOptions:
    FAIL_ON = ConfigOption(
        "analysis.fail-on", "error",
        "Compile-time plan analysis at submit (flink_tpu/analysis/): "
        "'error' (default) fails the job when any error-severity "
        "finding fires (misconfigurations that WILL break at runtime: "
        "unbounded source in batch mode, two log writers on one topic, "
        "fault rules matching no registered point); 'warn' also fails "
        "on warn-severity findings (correctness smells: event-time "
        "windows without a watermark strategy, non-transactional sinks "
        "under checkpointing, unknown config keys); 'off' skips "
        "analysis entirely. Findings below the threshold are kept on "
        "the driver (driver.analysis_findings) without failing the "
        "job. `python -m flink_tpu analyze` runs the same rules "
        "standalone.")
    MAX_STATE_BYTES_PER_KEY = ConfigOption(
        "analysis.max-state-bytes-per-key", 0,
        "Per-key state budget in BYTES for the analyzer's dataflow "
        "plane (analysis/dataflow.py): when > 0, any stateful operator "
        "whose statically-estimated per-key state footprint (lane "
        "accumulators x live panes, from the window/lateness geometry) "
        "exceeds it raises a STATE_BYTES_EXCEEDED warning at submit — "
        "the admission-control seam for multi-tenant budgeting (the "
        "same estimate `analyze --explain` prints per node). 0 = off. "
        "Estimates cover the dense lane layouts; element-buffer "
        "operators (evictors, CEP partial matches) are data-dependent "
        "and never flagged.")


class SourceOptions:
    ENUMERATION = ConfigOption(
        "source.enumeration", "local",
        "Split ownership: 'local' = this process reads every split "
        "(single-runner execution); 'coordinator' = ask the job "
        "coordinator's split enumerator for this runner's share, so "
        "multiple runners of one job divide the source without overlap "
        "(ref: FLIP-27 SplitEnumerator on the JobMaster / "
        "SourceCoordinator). Requires cluster.coordinator/job-id/"
        "runner-id, which the runner injects on deploy.")


class MemoryOptions:
    HBM_BUDGET = ConfigOption(
        "memory.hbm-budget", 0,
        "Plan-time PER-DEVICE HBM budget in BYTES for device-resident "
        "operator state (pane tensors, emit rings). HBM is a per-chip "
        "resource and state shards one block per device, so the check "
        "is per-device and independent of mesh width. Dense static "
        "layouts make the footprint computable before the first step — "
        "a job that cannot fit fails at build with a per-operator "
        "breakdown instead of an XLA allocator error mid-run (ref: "
        "MemoryManager managed-memory budgeting). 0 = unlimited.")


class HighAvailabilityOptions:
    HA_DIR = ConfigOption(
        "high-availability.dir", "",
        "Shared directory for leader election + the job graph store. "
        "Empty = HA off. A standby coordinator pointed at the same dir "
        "takes leadership when the incumbent's lease lapses and "
        "recovers every non-terminal job from the store (ref: "
        "runtime/highavailability HighAvailabilityServices + "
        "JobGraphStore + leader election via ZooKeeper/K8s; here the "
        "shared filesystem is the consensus substrate).")
    LEASE_TIMEOUT = duration_option(
        "high-availability.lease-timeout", 10_000,
        "Leadership lease: the leader renews within this period; a "
        "contender may claim a lease older than this (ref: ZooKeeper "
        "session timeout role).")
