"""Keys that leave: a key's slot is released when its last pane has been
purged, and handed out again only when no fired row that names it can
still be read (``ops/window.py`` ``_release_dead_keys``: the reuse rule).

On NEXmark Q5's records WITHOUT the fold onto a few ids (the benchmark's
``nexmark_q5_large_keys`` at a small size: ~530 auctions arrive and ~530
leave with every batch of 8,192 bids), held to that configuration's
plain reference, which is numpy only and takes nothing from the program:

- the directory against a dict model, the native and the numpy table in
  parity, probes bounded after 10^5 deletes;
- THE HAZARD: rows leave the device as row numbers and become keys at
  drain time; with the drain held back behind several fires, purges and
  allocations every row still names the right key, and with the rule
  switched off the reference catches a wrong one;
- through ``env.execute``, on one device and on a mesh of four; a
  snapshot in mid-churn restored into a fresh operator; and recurring
  keys, which release nothing while they live;
- THE RELEASE'S PLACE (PR 40): a purging advance returns with its
  release pending; the driver queues the fired cohort first and runs it
  outside its push lock, and a snapshot, ``quiesce``, the next batch
  and the next advance each run it before they look at the directory.
"""
import threading
import time

import jax
import numpy as np
import pytest

import flink_tpu  # noqa: F401 — x64 before other jax users
from benchmark.configs import nexmark_q5, nexmark_q5_large_keys as large
from benchmark.loadgen import BenchSource, RecordingSink
from benchmark.traffic_kinds.constant_rate import Schedule
from flink_tpu import native_codec
from flink_tpu.api.environment import StreamExecutionEnvironment
from flink_tpu.api.windowing import SlidingEventTimeWindows
from flink_tpu.config import Configuration
from flink_tpu.ops.aggregates import count
from flink_tpu.ops.window import WindowOperator
from flink_tpu.parallel.mesh import make_mesh_plan
from flink_tpu.records import hash_keys_numpy
from flink_tpu.state.keyed import KeyDirectory, _NumpyHashTable

BATCH = 8192
SEED = 2**31 + 29
RATE = 2                    # bids per ms of event time
SHARDS = 8
# nexmark_q5_large_keys.json's params
PARAMS = {
    "window_ms": 10000, "slide_ms": 2000, "out_of_orderness_ms": 4000,
    "person_proportion": 1, "auction_proportion": 3, "bid_proportion": 46,
    "num_in_flight_auctions": 100, "hot_auction_ratio": 2,
    "num_active_people": 1000, "hot_bidders_ratio": 4, "pool_batches": 4}
SCHED = Schedule({"events_per_ms": RATE})


def stream(n_batches, module=large, params=PARAMS):
    pool = module.make_pool(SEED, BATCH, params)
    return [(pool[i % len(pool)], SCHED.batch_ts(i, BATCH))
            for i in range(n_batches)]


def distinct_keys(batches):
    return len(np.unique(np.concatenate([d["auction"] for d, _ in batches])))


def q5_operator(slots_per_shard, mesh=None):
    return WindowOperator(
        SlidingEventTimeWindows.of(PARAMS["window_ms"], PARAMS["slide_ms"]),
        count(), num_shards=SHARDS, slots_per_shard=slots_per_shard,
        max_out_of_orderness_ms=PARAMS["out_of_orderness_ms"],
        top_n=("count", 1),
        mesh_plan=(make_mesh_plan(SHARDS, slots_per_shard,
                                  jax.devices()[:mesh]) if mesh else None))


def as_sink_batch(fired):
    """A fired batch under the names the job's sink sees."""
    return {"window_end": np.asarray(fired["window_end"]),
            "auction": np.asarray(fired["key"]),
            "bid_count": np.asarray(fired["count"])}


def drive(op, batches, hold=0, after_advance=None, release_at="queued"):
    """Feed ``batches``, advancing the watermark after each; the fired
    batches are materialised ``hold`` advances late (the drain held
    back), then the end-of-input flush. ``release_at``: the release a
    purging advance left pending runs once its fired batch is
    ``"queued"``, as the driver has it, or is left to whatever needs the
    directory next (``"next_batch"``). -> sink batches."""
    held, out = [], []
    for i, (data, ts) in enumerate(batches):
        op.process_batch(data["auction"], ts, {})
        held.append(op.advance_watermark(
            int(ts[-1]) - PARAMS["out_of_orderness_ms"]))
        if release_at == "queued":
            op.run_pending_release()
        while len(held) > hold:
            out.append(as_sink_batch(held.pop(0)))
        if after_advance is not None:
            after_advance(i)
    held.append(op.advance_watermark(op.final_watermark()))
    op.run_pending_release()
    out.extend(as_sink_batch(f) for f in held)
    return out


def verdict(batches, sink_batches, module=large, params=PARAMS):
    return module.check(iter(batches), int(batches[-1][1][-1]),
                        sink_batches, params)


def assert_equal_to_reference(cmp_):
    assert cmp_["rows_expected"] > 0
    assert (cmp_["rows_missing"], cmp_["rows_not_in_reference"],
            cmp_["rows_duplicated"]) == (0, 0, 0), cmp_


# -- (a) the directory and its tables --------------------------------------

def test_the_used_mask_made_on_the_device_is_the_directorys():
    """PR 45: the fire's used-rows mask is made on the device from the
    shards' free pointers (``used_mask_kernel``); the directory's own
    mask, which a mesh still uploads, is the reference: equal slot for
    slot, the dump row behind them False, and made anew only when a
    never-used slot was taken."""
    op = q5_operator(256)
    for data, ts in stream(3):
        op.process_batch(data["auction"], ts, {})
        mask = op._used_mask_device()
        got = np.asarray(mask)
        assert got.shape == (op.layout.rows,) and got.dtype == bool
        assert (got[:-1] == op.directory.ever_used_mask()).all()
        assert got[:-1].sum() == op.directory.slots_ever_used() > 0
        assert not got[-1]
        assert op._used_mask_device() is mask       # nothing new: kept


def tables():
    native = native_codec.NativeHashTable.create(16)
    return [_NumpyHashTable(16)] + ([native] if native is not None else [])


def test_tables_delete_in_parity_with_a_dict_and_probes_stay_short():
    """10^5 deletes through both tables against a dict; afterwards no run
    of occupied buckets is longer than a table that never held the
    deleted keys would have (backward shift leaves no tombstones)."""
    rng = np.random.default_rng(1)
    tabs, model = tables(), {}
    deleted = 0
    next_key = 0
    while deleted < 100_000:
        fresh = np.arange(next_key, next_key + 1500, dtype=np.int64) * 7919
        next_key += 1500
        for t in tabs:
            t.insert_batch(fresh, hash_keys_numpy(fresh), fresh + 1)
        model.update((int(k), int(k) + 1) for k in fresh)
        live = np.fromiter(model, np.int64, len(model))
        # two thirds of what is there goes, and some keys that are not
        gone = rng.choice(live, size=2 * len(live) // 3, replace=False)
        ask = np.concatenate([gone, np.asarray([-5, -6], np.int64)])
        for t in tabs:
            assert t.delete_batch(ask) == len(gone)
        for k in gone.tolist():
            del model[k]
        deleted += len(gone)
        probe = np.concatenate([live, fresh[:50] + 1])
        want_found = np.asarray([int(k) in model for k in probe])
        for t in tabs:
            vals, found = t.lookup_keys(probe)
            assert np.array_equal(found, want_found)
            assert np.array_equal(vals[found], probe[found] + 1)
            assert t._count == len(model)
    for t in tabs:
        rebuilt = type(t)(16) if isinstance(t, _NumpyHashTable) \
            else native_codec.NativeHashTable.create(16)
        live = np.fromiter(model, np.int64, len(model))
        rebuilt.insert_batch(live, hash_keys_numpy(live), live)
        # the table that deleted is the larger (it never shrinks), so
        # its runs are no longer than the rebuilt one's bound: a probe
        # walks a handful of buckets, not a trail of tombstones
        assert t.longest_run() <= max(rebuilt.longest_run(), 8)


@pytest.mark.parametrize("table", ["native", "numpy"])
def test_directory_insert_release_reinsert_against_a_dict(table):
    """Random insert / release / re-insert: a key keeps its slot while
    it is registered, no two registered keys share one, a released slot
    comes back only through ``reclaim``, and a reclaimed slot is taken
    before a never-used one."""
    if table == "native" and not native_codec.native_available():
        pytest.skip("no native codec")
    rng = np.random.default_rng(4)
    d = KeyDirectory(8, 48)
    if table == "numpy":
        d._table = _NumpyHashTable()
    d.track_panes()
    model, waiting = {}, []
    for step in range(400):
        keys = rng.integers(step * 15, step * 15 + 60, 80)
        slots = d.assign(keys)
        assert (slots >= 0).all(), step
        d.note_panes(slots, np.full(len(keys), step), rng.random(80) < 0.9)
        for k, s in zip(keys.tolist(), slots.tolist()):
            assert model.setdefault(k, s) == s
        assert len(set(model.values())) == len(model) == d.num_keys()
        held = {int(s) for sl in waiting for s in sl}
        assert not held & set(model.values())      # not handed out yet
        released = d.release_below(step - rng.integers(0, 3))
        for k in d.key_of_slots(released).tolist():
            del model[k]
        assert np.array_equal(np.flatnonzero(d.used_mask()),
                              np.sort(list(model.values())))
        waiting.append(released)
        if step % 3 == 0:                          # the drain catches up
            for sl in waiting:
                d.reclaim(sl)
            waiting = []
    assert d.slots_released > 1000 and d.slots_reused > 1000
    assert d.slots_allocated == d.slots_released + d.num_keys()
    # reuse comes first: the space ever touched is far under what a
    # directory that only inserts would have needed
    assert d.slots_ever_used() < d.slots_allocated // 10
    # and a snapshot carries all of it
    twin = KeyDirectory.restore(8, 48, d.snapshot())
    assert np.array_equal(np.sort(twin.free_slots()), np.sort(d.free_slots()))
    more = np.arange(10**6, 10**6 + 40)
    assert np.array_equal(twin.assign(more), d.assign(more))


# -- (b) the hazard --------------------------------------------------------

HOLD = 5          # advances the drain lags behind
N_BATCHES = 40


@pytest.mark.parametrize("release_at", ["queued", "next_batch"])
def test_rows_name_the_right_key_with_the_drain_held_back(release_at):
    """A slot budget under the keys offered, the drain ``HOLD`` advances
    (each a fire, a purge, a release and an allocation) behind: reuse
    waits for it, and every committed row names the right auction,
    wherever between its purge and the next batch the release runs."""
    batches = stream(N_BATCHES)
    op = q5_operator(slots_per_shard=1024)
    assert distinct_keys(batches) > 2 * op.directory.local_slots
    peak_free_rows = []

    def free_rows_are_identities(i):
        # the device rows of every slot that holds no key count nothing,
        # while thousands of keys are alive
        counts = np.asarray(op.state.counts)[:op.directory.local_slots]
        assert not counts[~op.directory.used_mask()].any()
        peak_free_rows.append(op.directory.num_keys())

    rows = drive(op, batches, hold=HOLD,
                 after_advance=free_rows_are_identities,
                 release_at=release_at)
    assert_equal_to_reference(verdict(batches, rows))
    c = op.state_counters()
    assert max(peak_free_rows) > 1000
    assert c["state.slots_reused"] > 2 * op.directory.local_slots
    assert c["state.slots_returned_early"] == 0
    assert c["state.slots_waiting_peak"] >= 500 * (HOLD - 1)
    # one release a purge; none of these cohorts was stamped t_queued
    # (no driver), so none counts as run after it
    assert c["state.releases"] == N_BATCHES + 1
    assert c["state.releases_after_queue"] == 0
    assert op.records_dropped_full == 0 and op.late_records == 0


@pytest.mark.parametrize("release_at", ["queued", "next_batch"])
def test_the_control_without_the_rule_a_wrong_key_is_caught(
        monkeypatch, release_at):
    """The same run with the waiting switched off (a released slot goes
    straight back to the allocator): rows decode to the auction that now
    holds the slot, the reference refuses them, and the program's own
    tripwire counts the slots that went back early."""
    monkeypatch.setattr(WindowOperator, "_drained_through",
                        lambda self: 1 << 62)
    batches = stream(N_BATCHES)
    op = q5_operator(slots_per_shard=1024)
    cmp_ = verdict(batches, drive(op, batches, hold=HOLD,
                                  release_at=release_at))
    assert cmp_["rows_not_in_reference"] > 0 and cmp_["rows_missing"] > 0
    assert op.state_counters()["state.slots_returned_early"] > 0


# -- (b2) the release's place ------------------------------------------------

def purged_with_release_pending(n_batches=12):
    """An operator whose last advance fired, purged ~500 dead keys'
    panes and returned: the keys are still in the directory."""
    op = q5_operator(slots_per_shard=1024)
    batches = stream(n_batches + 1)
    for data, ts in batches[:n_batches]:
        op.process_batch(data["auction"], ts, {})
        fired = op.advance_watermark(
            int(ts[-1]) - PARAMS["out_of_orderness_ms"])
    assert op._release_pending and fired.cohort is not None
    return op, batches[n_batches]


def next_batch(op, batch):
    op.process_batch(batch[0]["auction"], batch[1], {})


def next_advance(op, batch):
    op.advance_watermark(op.watermark + PARAMS["slide_ms"])
    op.run_pending_release()        # the one this advance left


@pytest.mark.parametrize("needs_the_directory", [
    lambda op, batch: op.snapshot_state(),
    lambda op, batch: op.quiesce(),
    next_batch, next_advance, lambda op, batch: op.run_pending_release()],
    ids=["snapshot", "quiesce", "next_batch", "next_advance", "driver"])
def test_whatever_needs_the_directory_runs_the_pending_release_first(
        needs_the_directory):
    op, batch = purged_with_release_pending()
    released, keys, done = (op.directory.slots_released,
                            op.directory.num_keys(), op.releases)
    needs_the_directory(op, batch)
    assert not op._release_pending and op._release_cohort is None
    assert op.releases >= done + 1
    assert op.directory.slots_released > released + 300
    assert op.directory.num_keys() < keys + BATCH // 8
    # stamped with the fires dispatched when it ran: never earlier
    assert all(stamp <= op._fires_so_far() for stamp, _ in op._waiting)


def test_the_snapshot_after_the_end_of_input_flush_holds_no_key():
    """The end-of-input checkpoint: the flush purges every pane, and the
    snapshot that follows it runs the release the flush left pending."""
    batches = stream(12)
    op = q5_operator(slots_per_shard=1024)
    for data, ts in batches:
        op.process_batch(data["auction"], ts, {})
    op.quiesce()
    dict(op.advance_watermark(op.final_watermark()))
    assert op._release_pending and op.directory.num_keys() > 1000
    snap = op.snapshot_state()
    assert not snap["directory"]["rev_used"].any()
    assert not np.asarray(snap["panes"].counts).any()
    assert op.directory.num_keys() == 0


def test_the_driver_queues_the_cohort_then_releases_outside_its_push_lock(
        monkeypatch):
    """Through ``env.execute``: every purging advance's cohort carries
    ``t_queued`` before its release starts, and while the release runs
    another thread gets the driver's ``_push_lock`` (the drain could
    deliver)."""
    from flink_tpu.runtime.driver import Driver

    drivers, starts, lock_free = [], [], []
    emit = Driver._emit_fired
    release = WindowOperator._release_dead_keys
    release_below = KeyDirectory.release_below

    def spy_emit(self, nid, fired):
        drivers.append(self)
        return emit(self, nid, fired)

    def spy_release(self):
        starts.append((self._release_cohort, time.perf_counter()))
        return release(self)

    def spy_release_below(self, dead):
        got = []
        t = threading.Thread(target=lambda: got.append(
            drivers[-1]._push_lock.acquire(timeout=10)))
        t.start()
        t.join()
        if got[0]:
            drivers[-1]._push_lock.release()
        lock_free.append(got[0])
        return release_below(self, dead)

    monkeypatch.setattr(Driver, "_emit_fired", spy_emit)
    monkeypatch.setattr(WindowOperator, "_release_dead_keys", spy_release)
    monkeypatch.setattr(KeyDirectory, "release_below", spy_release_below)
    n = 30
    res, sink, op = run_job(large, PARAMS, n,
                            **{"state.slots-per-shard": 2048})
    assert_equal_to_reference(verdict(stream(n), sink.batches))
    with_cohort = [(c, t) for c, t in starts if c is not None]
    assert len(with_cohort) >= n - 4 and len(lock_free) == len(starts)
    assert all(c["t_fire"] < c["t_queued"] < t for c, t in with_cohort)
    assert all(lock_free)
    m = res.metrics
    assert m["state.releases"] == len(starts)
    assert m["state.releases_after_queue"] == len(with_cohort)
    assert m["state.slots_returned_early"] == 0 and m["state.live_keys"] == 0


# -- (c) (e) through env.execute, one device and a mesh of four ------------

def run_job(module, params, n_batches, **conf):
    settings = {"pipeline.microbatch-size": BATCH,
                "state.num-key-shards": SHARDS, "analysis.fail-on": "off",
                **conf}
    env = StreamExecutionEnvironment(Configuration(settings))
    source = BenchSource(module.make_pool(SEED, BATCH, params), SCHED, BATCH,
                         schema=module.SCHEMA, max_batches=n_batches)
    sink = RecordingSink()
    module.build(env, source, sink.sink, params)
    res = env.execute("q5-keys-that-leave")
    op = [o for o in env._driver._ops.values()
          if isinstance(o, WindowOperator)][0]
    return res, sink, op


@pytest.mark.parametrize("mesh", [None, 4])
def test_q5_on_unwrapped_ids_equals_the_reference(mesh):
    if mesh and len(jax.devices()) < mesh:
        pytest.skip("needs 4 devices")
    n = 60
    conf = {"state.slots-per-shard": 2048}
    if mesh:
        conf["cluster.mesh-devices"] = mesh
    res, sink, op = run_job(large, PARAMS, n, **conf)
    batches = stream(n)
    assert_equal_to_reference(verdict(batches, sink.batches))
    m = res.metrics
    assert distinct_keys(batches) > SHARDS * 2048
    assert m["records_in"] == n * BATCH
    assert m["records_dropped_full"] == 0 and m["late_records"] == 0
    assert m["state.slots_reused"] > 0
    assert m["state.slots_returned_early"] == 0
    assert 0 < m["state.reuse_share"] < 1
    assert m["state.slots_allocated"] == distinct_keys(batches)
    assert m["state.live_keys_peak"] < SHARDS * 2048
    assert m["profile.phase.state.release"] > 0
    assert m["profile.phase.state.reclaim"] > 0
    # the end-of-input flush purged every pane: every key has left, and
    # every row of the state counts nothing again
    assert m["state.live_keys"] == 0
    assert not np.asarray(op.state.counts)[
        op._row_of_slots(np.arange(op.directory.local_slots))].any()


# -- (d) a snapshot in mid-churn -------------------------------------------

def test_restore_in_mid_churn_gives_the_uninterrupted_rows():
    batches = stream(36)
    cut = 20
    whole = drive(q5_operator(1024), batches, hold=2)

    first = q5_operator(1024)
    head, held = [], []
    for data, ts in batches[:cut]:
        first.process_batch(data["auction"], ts, {})
        held.append(first.advance_watermark(
            int(ts[-1]) - PARAMS["out_of_orderness_ms"]))
        first.run_pending_release()
        if len(held) > 2:
            head.append(as_sink_batch(held.pop(0)))
    # a checkpoint flushes the emits, then freezes
    head.extend(as_sink_batch(f) for f in held)
    assert first._waiting            # slots are waiting on the rule
    assert first.directory.free_slots().size or first._n_waiting
    snap = first.snapshot_state()
    waited = first._n_waiting + first.directory.free_slots().size
    second = q5_operator(1024)
    second.restore_state(snap)
    # what waited went into the snapshot as free: nothing is lost
    assert second.directory.free_slots().size == waited
    assert second.directory.num_keys() == first.directory.num_keys()
    tail = drive(second, batches[cut:], hold=2)
    assert_equal_to_reference(verdict(batches, head + tail))
    assert verdict(batches, whole) == verdict(batches, head + tail)
    # and the churn goes on in the restored operator
    assert second.state_counters()["state.slots_reused"] > 1000
    assert second.state_counters()["state.slots_returned_early"] == 0


# -- (f) keys that recur ---------------------------------------------------

def test_recurring_keys_release_nothing_while_they_live():
    """The accepted cells' key space (ids folded onto 400): every key
    recurs in every pane, so no purge finds a key without a live pane;
    the rows are the dense reference's."""
    params = dict(PARAMS, auction_id_wrap=400)
    batches = stream(24, nexmark_q5, params)
    op = q5_operator(slots_per_shard=128)
    seen = []

    def nothing_released(i):
        seen.append((op.directory.slots_released, op.directory.num_keys()))

    rows = drive(op, batches, after_advance=nothing_released)
    assert all(r == 0 for r, _ in seen) and seen[-1][1] == 400
    assert_equal_to_reference(verdict(batches, rows, nexmark_q5, params))
    assert op.directory.slots_reused == 0
    assert op.state_counters()["state.slots_waiting_peak"] in (0, 400)
