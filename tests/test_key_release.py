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
  keys, which release nothing while they live.
"""
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


def drive(op, batches, hold=0, after_advance=None):
    """Feed ``batches``, advancing the watermark after each; the fired
    batches are materialised ``hold`` advances late (the drain held
    back), then the end-of-input flush. -> sink batches."""
    held, out = [], []
    for i, (data, ts) in enumerate(batches):
        op.process_batch(data["auction"], ts, {})
        held.append(op.advance_watermark(
            int(ts[-1]) - PARAMS["out_of_orderness_ms"]))
        while len(held) > hold:
            out.append(as_sink_batch(held.pop(0)))
        if after_advance is not None:
            after_advance(i)
    held.append(op.advance_watermark(op.final_watermark()))
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


def test_rows_name_the_right_key_with_the_drain_held_back():
    """A slot budget under the keys offered, the drain ``HOLD`` advances
    (each a fire, a purge, a release and an allocation) behind: reuse
    waits for it, and every committed row names the right auction."""
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
                 after_advance=free_rows_are_identities)
    assert_equal_to_reference(verdict(batches, rows))
    c = op.state_counters()
    assert max(peak_free_rows) > 1000
    assert c["state.slots_reused"] > 2 * op.directory.local_slots
    assert c["state.slots_returned_early"] == 0
    assert c["state.slots_waiting_peak"] >= 500 * (HOLD - 1)
    assert op.records_dropped_full == 0 and op.late_records == 0


def test_the_control_without_the_rule_a_wrong_key_is_caught(monkeypatch):
    """The same run with the waiting switched off (a released slot goes
    straight back to the allocator): rows decode to the auction that now
    holds the slot, the reference refuses them, and the program's own
    tripwire counts the slots that went back early."""
    monkeypatch.setattr(WindowOperator, "_drained_through",
                        lambda self: 1 << 62)
    batches = stream(N_BATCHES)
    op = q5_operator(slots_per_shard=1024)
    cmp_ = verdict(batches, drive(op, batches, hold=HOLD))
    assert cmp_["rows_not_in_reference"] > 0 and cmp_["rows_missing"] > 0
    assert op.state_counters()["state.slots_returned_early"] > 0


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
