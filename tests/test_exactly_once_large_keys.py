"""Exactly-once checkpoints of keys that come and go: NEXmark Q5 on the
generator's own auction ids (the benchmark's ``nexmark_q5_large_keys``
at a small size: ~530 auctions arrive and ~530 leave with every batch of
8,192 bids) through ``env.execute`` with ``execution.checkpointing
.interval`` set, held to that configuration's plain reference (numpy
only, nothing taken from the program):

- periodic checkpoints complete while keys are released and reused, and
  the committed rows are the reference's, on one device and a mesh of 4;
- a checkpoint write that fails in mid-churn, a restore from the newest
  durable one, a replay: the committed rows are the uncrashed reference's,
  none missing, none twice, ``state.slots_returned_early`` 0 on both
  sides of the restore;
- the read-back of ``benchmark/probes/exactly_once_readback.py`` (every
  cell of the newest mid-stream checkpoint against the reference at its
  source position) and its two controls: one count altered in the
  written blob, a reference one batch off the recorded position;
- a freeze that meets fires in flight; two jobs of one process and their
  checkpoint directories; every new leaf and counter, and the loop's
  leaves still a flat partition of its wall time; the blob written from
  the arrays' own buffers against the encoder it replaced.

No test asserts on wall time. A source stands still at three batches
(``GATES``) until the checkpoint in flight is durable, so the loop
begins one of its own at each of those boundaries, however slow the
machine: every run completes at least four, and a fault armed AT a gate
fails a write that every run reaches, with a durable checkpoint from
the gate before it to restore.
"""
import json
import os
import struct
import time

import jax
import numpy as np
import pytest

import flink_tpu  # noqa: F401 — x64 before other jax users
from benchmark.configs import nexmark_q5_exactly_once as exactly_once
from benchmark.configs import nexmark_q5_large_keys as large
from benchmark.probes import exactly_once_readback as readback
from benchmark.traffic_kinds.constant_rate import Schedule
from flink_tpu import faults
from flink_tpu.api.environment import StreamExecutionEnvironment
from flink_tpu.api.sinks import TransactionalCollectSink
from flink_tpu.api.sources import Source
from flink_tpu.checkpoint import blobformat
from flink_tpu.checkpoint.storage import FsCheckpointStorage
from flink_tpu.config import Configuration
from flink_tpu.ops.window import WindowOperator
from flink_tpu.runtime.driver import (
    CHECKPOINT_COUNTERS, CHECKPOINT_FREEZE_LEAVES, CHECKPOINT_PERSIST_LEAVES)
from flink_tpu.runtime.supervisor import run_with_recovery

BATCH = 8192
SEED = 2**31 + 53
SHARDS = 8
N_BATCHES = 60
GATES = (20, 30, 40)
SCHED = Schedule({"events_per_ms": 2})
# nexmark_q5_large_keys.json's params
PARAMS = {
    "window_ms": 10000, "slide_ms": 2000, "out_of_orderness_ms": 4000,
    "person_proportion": 1, "auction_proportion": 3, "bid_proportion": 46,
    "num_in_flight_auctions": 100, "hot_auction_ratio": 2,
    "num_active_people": 1000, "hot_bidders_ratio": 4, "pool_batches": 4}


class Stream(Source):
    """Batches ``[start_pos, n)`` of the configuration's stream. At each
    batch of ``GATES`` it stands still until the checkpoint in flight, if
    any, is durable: the loop then completes it at that batch's boundary
    and begins the next."""

    def __init__(self, n=N_BATCHES, on_gate=None):
        self.pool = large.make_pool(SEED, BATCH, PARAMS)
        self.n = n
        self.on_gate = on_gate
        self.env = None
        self.start_pos = None

    def declared_schema(self):
        return dict(large.SCHEMA)

    def open_split(self, split, start_pos=0):
        self.start_pos = start_pos
        for i in range(start_pos, self.n):
            if i in GATES:
                pending = getattr(self.env._driver, "_ckpt_pending", None)
                while pending is not None and not pending.done():
                    time.sleep(0.001)
                if self.on_gate is not None:
                    self.on_gate(i)
            yield self.pool[i], SCHED.batch_ts(i, BATCH)


def stream(n=N_BATCHES):
    pool = large.make_pool(SEED, BATCH, PARAMS)
    return [(pool[i], SCHED.batch_ts(i, BATCH)) for i in range(n)]


def conf_of(directory, mesh=None, **extra):
    settings = {
        "pipeline.microbatch-size": BATCH, "state.num-key-shards": SHARDS,
        "state.slots-per-shard": 2048, "analysis.fail-on": "off",
        "pipeline.source-prefetch": 0,
        "execution.checkpointing.interval": 1,
        "execution.checkpointing.dir": str(directory), **extra}
    if mesh:
        settings["cluster.mesh-devices"] = mesh
    return Configuration(settings)


def build_into(sink, envs=None, on_gate=None):
    """``build_env(conf)`` for ``run_with_recovery``: a fresh job of the
    configuration over a fresh ``Stream``; the environments it made are
    appended to ``envs``."""
    def build_env(conf):
        env = StreamExecutionEnvironment(conf)
        source = Stream(on_gate=on_gate)
        source.env = env
        env.stream = source
        large.build(env, source, sink, PARAMS)
        if envs is not None:
            envs.append(env)
        return env
    return build_env


def window_op(env):
    (op,) = [o for o in env._driver._ops.values()
             if isinstance(o, WindowOperator)]
    return op


def committed_batch(sink):
    return [{f: np.asarray([r[f] for r in sink.committed], np.int64)
             for f in ("window_end", "auction", "bid_count")}]


def assert_reference_rows(sink, n=N_BATCHES):
    batches = stream(n)
    cmp_ = large.check(iter(batches), int(batches[-1][1][-1]),
                       committed_batch(sink), PARAMS)
    assert cmp_["rows_expected"] > 0
    assert (cmp_["rows_missing"], cmp_["rows_not_in_reference"],
            cmp_["rows_duplicated"]) == (0, 0, 0), cmp_


def need_devices(mesh):
    if mesh and len(jax.devices()) < mesh:
        pytest.skip(f"needs {mesh} devices")


@pytest.fixture(scope="module", params=[None, 4], ids=["one", "mesh4"])
def checkpointed(request, tmp_path_factory):
    """One uninterrupted job a device layout: (metrics, sink, checkpoint
    root, the environment)."""
    need_devices(request.param)
    root = tmp_path_factory.mktemp("eo")
    sink, envs = TransactionalCollectSink(), []
    res = build_into(sink, envs)(conf_of(root, request.param)).execute("q5-eo")
    return res.metrics, sink, str(root), envs[0]


# -- checkpoints while keys come and go ------------------------------------

def test_checkpoints_complete_while_keys_are_released_and_reused(
        checkpointed):
    m, sink, _root, _env = checkpointed
    assert_reference_rows(sink)
    assert m["records_in"] == N_BATCHES * BATCH
    assert m["records_dropped_full"] == 0 and m["late_records"] == 0
    # three gates, each followed by a checkpoint of its own, and the
    # job's last
    assert m["checkpoint.completed"] >= 4
    assert m["checkpoint.triggered"] == m["checkpoint.completed"]
    assert m["checkpoint.failed"] == 0 and m["checkpoint.aborted"] == 0
    assert 0 < m["checkpoint.bytes_last"] <= m["checkpoint.bytes_total"]
    # the churn went on under them
    assert m["state.slots_reused"] > 1000
    assert m["state.slots_returned_early"] == 0
    assert m["state.live_keys"] == 0


def test_every_new_leaf_and_counter_and_the_loop_still_a_flat_partition(
        checkpointed):
    m, _sink, _root, _env = checkpointed
    for k in CHECKPOINT_COUNTERS + ("checkpoint.freeze_s",
                                    "checkpoint.persist_s",
                                    "checkpoint.loop_share",
                                    "state.pane_rows", "state.ring_columns"):
        assert k in m, k
    pre = "profile.phase."
    for leaf in CHECKPOINT_FREEZE_LEAVES + CHECKPOINT_PERSIST_LEAVES + (
            "ingest.checkpoint_wait",):
        assert m[pre + leaf] > 0 and m[pre + leaf + ".n"] >= 1, leaf
    # one clone, one directory copy, one fetch and one write a checkpoint
    for leaf in ("state.snapshot_clone", "state.snapshot_directory",
                 "ingest.checkpoint_flush", "persist.fetch",
                 "persist.write"):
        assert m[pre + leaf + ".n"] == m["checkpoint.completed"], leaf
    assert m["checkpoint.freeze_s"] == pytest.approx(sum(
        m[pre + leaf] for leaf in CHECKPOINT_FREEZE_LEAVES), abs=1e-4)
    assert m["checkpoint.persist_s"] == pytest.approx(sum(
        m[pre + leaf] for leaf in CHECKPOINT_PERSIST_LEAVES), abs=1e-4)
    wall = m[pre + "loop_wall_s"]
    assert m["checkpoint.loop_share"] == pytest.approx(
        m["checkpoint.freeze_s"] / wall, rel=1e-3)
    # the loop thread's leaves (the drain's and the checkpoint
    # executor's are other threads') still sum to its wall time, the
    # job's last checkpoint included
    loop = sum(v for k, v in m.items() if k.startswith(pre)
               and k[len(pre):].startswith(("ingest.", "window.", "wm.",
                                            "state."))
               and not k.endswith(".n"))
    assert abs(loop - wall) <= 0.03 * wall, (loop, wall)


def test_the_clone_is_a_program_of_its_own_in_buffers_of_its_own():
    from flink_tpu.ops.window import _JIT_SNAPSHOT_CLONE
    from flink_tpu.state.keyed import PaneStateLayout, init_state

    state = init_state(PaneStateLayout(slots=64, ring=12, sum_width=1,
                                       max_width=0, min_width=0))
    clone = _JIT_SNAPSHOT_CLONE(state)
    assert clone.maxs is None and clone.mins is None
    for a, b in ((state.counts, clone.counts), (state.sums, clone.sums)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
        assert a.unsafe_buffer_pointer() != b.unsafe_buffer_pointer()
    assert "snapshot_clone_kernel" in _JIT_SNAPSHOT_CLONE.lower(
        state).as_text()


# -- a fault, a restore, a replay ------------------------------------------

@pytest.mark.parametrize("mesh", [None, 4], ids=["one", "mesh4"])
def test_a_failed_checkpoint_in_mid_churn_restores_and_replays(
        tmp_path, mesh):
    need_devices(mesh)
    sink, envs, armed = TransactionalCollectSink(), [], []
    conf = conf_of(tmp_path / "chaos", mesh, **{
        "restart-strategy.type": "fixed-delay",
        "restart-strategy.fixed-delay.attempts": 3,
        "restart-strategy.fixed-delay.delay": 1})
    # armed at the second gate: the checkpoint begun at the first gate's
    # boundary (position 21) is durable by then, and the next write,
    # which the loop begins at this gate's boundary, fails
    plan = faults.FaultPlan(seed=5).rule(
        "checkpoint.storage.write", "raise", count=1)

    def arm(gate):
        if gate == GATES[1] and not armed:
            armed.append(plan.activate())
            armed[0].__enter__()

    try:
        res = run_with_recovery(build_into(sink, envs, arm), conf,
                                job_name="q5-eo-chaos")
    finally:
        for cm in armed:
            cm.__exit__(None, None, None)
    assert len(plan.log) == 1, "the checkpoint fault never fired"
    assert len(envs) == 2
    # committed rows: the uncrashed reference's, none missing, none twice
    assert_reference_rows(sink)
    crashed, restored = (window_op(e) for e in envs)
    assert crashed.state_counters()["state.slots_returned_early"] == 0
    assert res.metrics["state.slots_returned_early"] == 0
    assert res.metrics["records_dropped_full"] == 0
    assert res.metrics["late_records"] == 0
    # the restore took up in mid-stream, in mid-churn, and the churn
    # went on; the count of records read is part of the snapshot, so
    # over both attempts every record counts once
    assert GATES[0] < envs[1].stream.start_pos <= GATES[1] + 1
    assert res.metrics["records_in"] == N_BATCHES * BATCH
    assert crashed.state_counters()["state.slots_reused"] > 0
    assert restored.state_counters()["state.slots_reused"] > 0
    assert envs[0]._driver.metrics["checkpoint.failed"] == 1


# -- the read-back and its controls ----------------------------------------

def read_back(root, **kw):
    return readback.read_back(
        root, large, large.make_pool(SEED, BATCH, PARAMS), SCHED, BATCH,
        PARAMS, wanted=3, **kw)


def test_the_read_back_finds_every_cell_where_the_reference_has_it(
        checkpointed):
    m, _sink, root, _env = checkpointed
    back = read_back(root)
    assert back["short"] == 0
    assert back["completed_in_window"] == m["checkpoint.completed"] - 1
    assert back["cells_differing"] == 0 and back["position_mismatches"] == 0
    # it read a snapshot cut in mid-churn, not an empty one
    assert 0 < back["position"] <= back["last_position"] == N_BATCHES
    assert back["keys_in_directory"] > 1000
    assert len(back["checkpoints_on_disk"]) == 3    # num-retained


@pytest.mark.parametrize("shift", [1, -1], ids=["a-batch-on", "a-batch-back"])
def test_a_reference_one_batch_off_the_position_is_refused(
        checkpointed, shift):
    _m, _sink, root, _env = checkpointed
    back = read_back(root, shift=shift)
    assert back["cells_differing"] > 0
    assert back["position_mismatches"] > 0


def test_one_count_altered_in_the_written_blob_is_refused(tmp_path):
    sink = TransactionalCollectSink()
    build_into(sink)(conf_of(tmp_path)).execute("q5-eo")
    assert read_back(str(tmp_path))["cells_differing"] == 0
    _cid, d, manifest = readback.list_checkpoints(str(tmp_path))[-2]
    (blob,) = [e["file"] for e in manifest["ops"].values()
               if "panes" in readback.read_blob(os.path.join(d, e["file"]))]
    cells = readback.read_blob(
        os.path.join(d, blob), "r+")["panes"]["counts"]
    row, col = np.argwhere(np.asarray(cells) > 0)[0]
    cells[row, col] += 1
    cells.flush()
    assert read_back(str(tmp_path))["cells_differing"] == 1


def test_fewer_checkpoints_than_asked_for_is_refused(checkpointed):
    m, _sink, root, _env = checkpointed
    back = readback.read_back(
        root, large, large.make_pool(SEED, BATCH, PARAMS), SCHED, BATCH,
        PARAMS, wanted=int(m["checkpoint.completed"]) + 2)
    assert back["short"] == 3


# -- a freeze that meets fires in flight -----------------------------------

def test_a_freeze_that_meets_fires_in_flight_waits_for_their_rows(tmp_path):
    """Every batch spans two slides, so every advance fires, and the
    drain defers each fetch by half a second: a checkpoint that begins
    at the same boundary finds the fire's rows undelivered. Its barrier
    cancels the deferral and the epoch it stages holds them: every row
    is committed once, by the checkpoint that followed its fire."""
    sink = TransactionalCollectSink()
    res = build_into(sink)(conf_of(tmp_path, **{
        "pipeline.emit-defer": "500ms"})).execute("q5-eo-defer")
    m = res.metrics
    assert_reference_rows(sink)
    assert m["checkpoint.completed"] >= 4 and m["checkpoint.failed"] == 0
    assert m["profile.phase.ingest.checkpoint_flush.n"] == \
        m["checkpoint.completed"]
    # the barrier did the drain's work on the loop's time: it fetched
    assert m["profile.phase.ingest.checkpoint_flush"] > 0
    assert m["state.slots_returned_early"] == 0


# -- two jobs of one process -----------------------------------------------

def small_job(build, conf, n=12):
    env = StreamExecutionEnvironment(conf)
    source = env.stream = Stream(n)
    source.env = env
    build(env, source, TransactionalCollectSink(), dict(
        PARAMS, checkpoint_interval=1))
    return env, env.execute("q5-eo-twice").metrics


def test_two_jobs_of_one_process_never_read_each_others_checkpoints(
        tmp_path):
    """The benchmark's configuration gives every job it builds a fresh
    directory, so a job that asks for ``restore: latest`` under the
    same name still starts from nothing; under ONE directory and one
    name it resumes where the other ended, which is what the option
    means. A job that does not ask never reads what it finds there, and
    numbers its own checkpoints past it."""
    base = {"pipeline.microbatch-size": BATCH,
            "state.num-key-shards": SHARDS, "state.slots-per-shard": 2048,
            "analysis.fail-on": "off", "pipeline.source-prefetch": 0}
    restoring = dict(base, **{"execution.checkpointing.restore": "latest"})
    env1, m1 = small_job(exactly_once.build, Configuration(base))
    dir1 = env1.config.get_raw("execution.checkpointing.dir")
    assert m1["checkpoint.completed"] >= 1 and os.listdir(dir1)
    env2, m2 = small_job(exactly_once.build, Configuration(restoring))
    dir2 = env2.config.get_raw("execution.checkpointing.dir")
    assert dir2 != dir1 and m2["records_in"] == 12 * BATCH
    # the directory of the job before goes when the next is built
    assert not os.path.exists(dir1) and os.path.isdir(dir2)
    assert exactly_once.CHECKPOINT_DIRS == [dir2]
    exactly_once.remove_checkpoints()
    assert not os.path.exists(dir2) and exactly_once.CHECKPOINT_DIRS == []

    shared = {"execution.checkpointing.dir": str(tmp_path),
              "execution.checkpointing.interval": 1}
    _env, first = small_job(large.build, Configuration({**base, **shared}))
    storage = FsCheckpointStorage(str(tmp_path), "q5-eo-twice")
    newest = storage.latest().checkpoint_id
    assert newest == first["checkpoint.completed"]
    # no restore asked: the whole stream again, and its checkpoints are
    # numbered past the ones it found, so retention retires THOSE
    _env, again = small_job(large.build, Configuration({**base, **shared}))
    assert again["records_in"] == 12 * BATCH
    ids = [h.checkpoint_id for h in storage.list_complete()]
    assert ids == sorted(ids) and ids[-1] == newest + again[
        "checkpoint.completed"]
    # restore asked, same directory and name: nothing is left to read
    env, _resumed = small_job(
        large.build, Configuration({**restoring, **shared}))
    assert env.stream.start_pos == 12


# -- the blob, written from the arrays' own buffers -------------------------

def old_encode(payload) -> bytes:
    """``blobformat.encode`` as it was before the arrays went to the file
    from their own buffers: one zero-filled buffer, every array copied
    into it."""
    e = blobformat._Encoder()
    tree = e.enc(payload)
    offsets, pos = [], 0
    for a in e.arrays:
        pos = (pos + 63) // 64 * 64
        offsets.append(pos)
        pos += a.nbytes
    header = json.dumps({
        "tree": tree,
        "arrays": [{"dtype": str(a.dtype), "shape": list(a.shape),
                    "offset": off, "nbytes": a.nbytes}
                   for a, off in zip(e.arrays, offsets)],
        "pickle_escapes": e.pickle_escapes}).encode()
    out = bytearray(blobformat.MAGIC + struct.pack("<I", len(header))
                    + header)
    base = len(out)
    out += b"\0" * (pos if e.arrays else 0)
    for a, off in zip(e.arrays, offsets):
        out[base + off:base + off + a.nbytes] = a.tobytes()
    return bytes(out)


def sample(dtype):
    rng = np.random.default_rng(3)
    if dtype == "empty":
        return {"a": np.zeros(0, np.int64), "b": np.zeros((0, 12), np.int32),
                "c": np.arange(5, dtype=np.int8), "d": np.zeros(0, bool)}
    if dtype == "none":
        return {"watermark": 17, "refire": [1, 2], "name": "x"}
    a = (rng.random(1000) * 100).astype(dtype)
    return {"panes": a.reshape(50, 20), "odd": a[:7], "scalar": a[:1][0],
            "zero_d": np.array(a[3]), "strided": a[::3],
            # what a fetch of a TPU's pane tensor hands back: column-major
            "column_major": np.asfortranarray(a.reshape(125, 8)),
            "nested": {"t": (a[:3], 2), 5: a[5:9]}}


@pytest.mark.parametrize("dtype", ["int8", "int32", "int64", "float32",
                                   "float64", "bool", "uint16", "empty",
                                   "none"])
def test_the_file_written_is_the_old_encoders_byte_for_byte(
        tmp_path, dtype, monkeypatch):
    # pieces and turned blocks far smaller than the arrays, so that their
    # seams are crossed
    monkeypatch.setattr(blobformat, "_PIECE_BYTES", 96)
    monkeypatch.setattr(blobformat, "_TURN_ROWS", 5)
    payload = sample(dtype)
    want = old_encode(payload)
    lazy = blobformat.encode_lazy(payload)
    assert blobformat.encode(payload) == want == lazy.tobytes()
    assert lazy.nbytes == len(want)
    path = tmp_path / "op.blob"
    with open(path, "wb") as f:
        lazy.write_to(f)
    assert path.read_bytes() == want
    # and the probe's own reader reads what the program's decoder reads
    mine, theirs = readback.read_blob(str(path)), blobformat.decode(want)

    def leaves(tree):
        if isinstance(tree, dict):
            return [x for k in sorted(tree, key=str) for x in leaves(tree[k])]
        if isinstance(tree, (list, tuple)):
            return [x for v in tree for x in leaves(v)]
        return [tree]

    for got, old in zip(leaves(mine), leaves(theirs)):
        assert np.array_equal(got, old)
        assert np.shape(got) == np.shape(old)


# -- the drain's poll when a fire outlasts its deferral ---------------------

class _Version:
    """A stand-in for an announced emit-ring version: one that has not
    ``landed`` says so once and lands while it is waited for."""

    def __init__(self, rows):
        self.rows, self.landed, self.asked = rows, False, 0

    def is_ready(self):
        self.asked += 1
        return self.landed or self.asked > 1

    @property
    def waited(self):
        return not self.landed and self.asked > 1

    def __array__(self, dtype=None, copy=None):
        return np.asarray(self.rows)


def test_a_poll_passes_over_a_version_it_has_read_and_waits_for_the_next():
    """The fire over a large state is still running when the drain
    polls: the only version that has landed is the one the poll before
    read. Reading it again would hand over nothing and leave the fired
    rows to the next poll, a slide later; the poll waits for the
    version it has not read."""
    from flink_tpu.api.windowing import SlidingEventTimeWindows
    from flink_tpu.ops.aggregates import count

    op = WindowOperator(SlidingEventTimeWindows.of(10000, 2000), count(),
                        num_shards=8, slots_per_shard=64, top_n=("count", 1))
    old, new = _Version([[1]]), _Version([[2]])
    old.landed = True
    op.emit_ring.versions.extend([(6, old), (7, new)])
    op.emit_ring.read_no = 6
    arr, no, _t_ready = op.emit_ring.fetch_version(0, opportunistic=True)
    assert (no, arr.tolist(), new.waited) == (7, [[2]], True)
    assert op.emit_ring.read_no == 7
    # nothing it has not read: nothing to fetch, as before
    assert op.emit_ring.fetch_version(0, opportunistic=True) == (None, None, None)
    # of several it has not read, the newest that has landed
    newer, newest = _Version([[3]]), _Version([[4]])
    newer.landed = True
    op.emit_ring.versions.extend([(8, newer), (9, newest)])
    arr, no, _t_ready = op.emit_ring.fetch_version(0, opportunistic=True)
    assert (no, arr.tolist(), newest.waited) == (8, [[3]], False)
    # a barrier names its version and gets it, read before or not
    arr, no, _t_ready = op.emit_ring.fetch_version(7, opportunistic=False)
    assert no in (8, 9)


# -- a large pane tensor stays on its device until its blob is written ------

@pytest.mark.parametrize("shape,dtype", [
    ((4097, 12), "int32"), ((2 * 4096, 3), "float32"),
    ((3 * 4096 + 1, 5), "int64"), ((7, 1), "int32"), ((4096, 2), "int8")])
@pytest.mark.parametrize("piece", [1000, 1 << 30])
def test_a_leaf_that_stays_on_the_device_writes_the_fetched_ones_bytes(
        shape, dtype, piece, monkeypatch):
    """``coordinator.DeviceRows``: laid out row-major on the device,
    block by block, and fetched in pieces as its blob is written (the
    last piece starts early, its head dropped): byte for byte what the
    whole fetch gives the old encoder, and it decodes to the array."""
    import jax.numpy as jnp

    from flink_tpu.checkpoint import coordinator as C
    from flink_tpu.obs.tracing import PhaseClock

    monkeypatch.setattr(C, "_ROW_MAJOR_BLOCK_ROWS", 4096)
    monkeypatch.setattr(C, "_FETCH_PIECE_BYTES", piece)
    a = (np.arange(int(np.prod(shape))).reshape(shape) * 37 % 1001
         ).astype(dtype)
    rows = C.DeviceRows(jnp.asarray(a))
    rows.phases = PhaseClock()
    assert (rows.shape, rows.dtype, rows.nbytes) == (
        a.shape, a.dtype, a.nbytes)
    assert b"".join(bytes(p) for p in rows.raw_pieces()) == a.tobytes()
    # a wait for a piece is a persist.fetch interval of the writer
    assert rows.phases.snapshot()["persist.fetch"]["count"] == max(
        1, -(-a.nbytes // max(1, piece // a.dtype.itemsize * a.dtype.itemsize)))
    tree = {"panes": (rows,), "ring": 12, "directory": {"k": a[:, 0]}}
    plain = {"panes": (a,), "ring": 12, "directory": {"k": a[:, 0]}}
    blob = blobformat.encode_lazy(tree)
    assert blob.tobytes() == old_encode(plain)
    assert blob.nbytes == len(old_encode(plain))
    got = blobformat.decode(blob.tobytes())
    assert np.array_equal(got["panes"][0], a)
    assert got["panes"][0].dtype == a.dtype


def test_materialize_leaves_only_large_single_device_leaves_of_two_axes(
        monkeypatch):
    import jax.numpy as jnp

    from flink_tpu.checkpoint import coordinator as C

    big = jnp.ones((512, 12), jnp.int32)
    tree = {"panes": (big, jnp.ones((4,), jnp.int32)),
            "cube": jnp.ones((8, 8, 400), jnp.int32), "n": 3}
    whole = C.materialize_snapshot(tree)
    assert isinstance(whole["panes"][0], np.ndarray)    # no list given
    monkeypatch.setattr(C, "ROW_MAJOR_ON_DEVICE_MIN_BYTES", big.nbytes)
    waiting = []
    got = C.materialize_snapshot(tree, waiting)
    assert waiting == [got["panes"][0]]
    assert isinstance(got["panes"][1], np.ndarray)      # one axis
    assert isinstance(got["cube"], np.ndarray)          # three
    assert got["n"] == 3
    if len(jax.devices()) > 1:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec

        mesh = Mesh(np.array(jax.devices()[:2]), ("d",))
        x = jax.device_put(big, NamedSharding(mesh, PartitionSpec("d")))
        assert isinstance(C.materialize_snapshot(x, []), np.ndarray)


def test_a_checkpoint_written_from_the_device_in_pieces_reads_back(
        tmp_path, monkeypatch):
    """The whole path with the pane tensor left on the device and
    fetched in pieces (at the cell's size it is; here the thresholds are
    lowered): the job commits the reference's rows, what it wrote reads
    back cell for cell, and a job restored from it goes on to the
    reference's rows."""
    from flink_tpu.checkpoint import coordinator as C

    made = []
    init = C.DeviceRows.__init__
    monkeypatch.setattr(C, "ROW_MAJOR_ON_DEVICE_MIN_BYTES", 0)
    monkeypatch.setattr(C, "_FETCH_PIECE_BYTES", 100_000)
    monkeypatch.setattr(C.DeviceRows, "__init__", lambda self, x: (
        init(self, x), made.append(self))[0])
    sink = TransactionalCollectSink()
    res = build_into(sink)(conf_of(tmp_path)).execute("q5-eo")
    assert_reference_rows(sink)
    assert len(made) == res.metrics["checkpoint.completed"] >= 4
    pre = "profile.phase."
    assert res.metrics[pre + "persist.fetch.n"] > len(made)
    back = read_back(str(tmp_path))
    assert (back["short"], back["cells_differing"],
            back["position_mismatches"]) == (0, 0, 0), back
    # restored from the newest (the end of input): nothing left to read,
    # nothing committed twice
    env = build_into(sink)(conf_of(tmp_path, **{
        "execution.checkpointing.restore": "latest"}))
    env.execute("q5-eo")
    assert env.stream.start_pos == N_BATCHES
    assert_reference_rows(sink)


def test_a_directory_snapshot_copied_in_ranges_is_the_serial_one(
        monkeypatch):
    from concurrent.futures import ThreadPoolExecutor

    from flink_tpu.state import keyed
    from flink_tpu.state.keyed import KeyDirectory

    d = KeyDirectory(8, 4096)
    d.track_panes()
    k = np.arange(1, 9001, dtype=np.int64) * 7919
    d.note_panes(d.assign(k), k % 5, np.ones(len(k), bool))
    monkeypatch.setattr(keyed, "_SNAPSHOT_RANGE_MIN", 1000)
    ran = []

    def side_by_side(fns):
        ran.append(len(fns))
        with ThreadPoolExecutor(4) as ex:
            return [f.result() for f in [ex.submit(fn) for fn in fns]]

    serial, ranged = d.snapshot(), d.snapshot(side_by_side)
    assert ran == [12]      # three slot-sized arrays, four ranges each
    assert serial.keys() == ranged.keys()
    for name in serial:
        assert np.array_equal(serial[name], ranged[name]), name
        assert ranged[name] is not getattr(d, "_" + name, None)
    again = KeyDirectory.restore(8, 4096, ranged)
    assert np.array_equal(again.assign(k), d.assign(k))
