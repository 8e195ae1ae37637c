"""``KeyDirectory.assign`` on the native table: one call that does per
record a memo hit and a store, and everything else once per distinct key
(``native/codec.cc`` ``ht_assign``).

Its contract is the slots of the path it replaced, element for element,
whatever the keys and whatever was released, reclaimed, restored or
doubled in between. That path lives on here as the reference
(``TwoStepDirectory``: ``lookup_claim``, then numpy ``_register``, then
the placeholders resolved in numpy), and a directory on the numpy table
is held to the same, call for call.

The memo's RULE is held by its counters, never by time: keys without
locality read no hit and lose the memo within one stretch, for the next
``MEMO_REST``; locality that comes back has the memo back; the suite's
own order is served nine records in ten.
"""
import numpy as np
import pytest

import flink_tpu  # noqa: F401 — x64 before other jax users
from benchmark.configs import nexmark_q5_large_keys as large
from benchmark.traffic_kinds.constant_rate import Schedule
from flink_tpu import native_codec
from flink_tpu.native_codec import MEMO_REST, MEMO_STRETCH
from flink_tpu.state.keyed import KeyDirectory, _NumpyHashTable

pytestmark = pytest.mark.skipif(
    not native_codec.native_available(),
    reason="the native assign needs the C codec")

MEMO_SIZE = 1024            # codec.cc's: a key's entry is key % MEMO_SIZE
BATCH = 8192
SEED = 2**31 + 35
# nexmark_q5_large_keys.json's params, the cell's rehearsal size
PARAMS = {
    "window_ms": 10000, "slide_ms": 2000, "out_of_orderness_ms": 4000,
    "person_proportion": 1, "auction_proportion": 3, "bid_proportion": 46,
    "num_in_flight_auctions": 100, "hot_auction_ratio": 2,
    "num_active_people": 1000, "hot_bidders_ratio": 4, "pool_batches": 4}
PANE_MS = 2000
SCHED = Schedule({"events_per_ms": 2})


class TwoStepDirectory(KeyDirectory):
    """``assign`` as it was before ``ht_assign``: the reference."""

    def assign(self, keys):
        keys = np.asarray(keys, dtype=np.int64)
        slots, uniq = self._table.lookup_claim(keys)
        if len(uniq):
            order = np.argsort(uniq)
            alloc = np.empty(len(uniq), np.int64)
            alloc[order] = self._register(uniq[order])
            pend = np.flatnonzero(slots <= self._table.PENDING)
            slots[pend] = alloc[self._table.PENDING - slots[pend]]
        return slots


class NumpyDirectory(KeyDirectory):
    """The directory of a process without the codec library."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self._table = _NumpyHashTable()


def assert_same_state(new, other):
    np.testing.assert_array_equal(new._rev_keys, other._rev_keys)
    np.testing.assert_array_equal(new._rev_used, other._rev_used)
    np.testing.assert_array_equal(new._next_free, other._next_free)
    np.testing.assert_array_equal(new._n_free, other._n_free)
    np.testing.assert_array_equal(new.free_slots(), other.free_slots())
    assert len(new._fresh) == len(other._fresh)
    for a, b in zip(new._fresh, other._fresh):
        np.testing.assert_array_equal(a, b)
    for name in ("slots_allocated", "slots_reused", "slots_released",
                 "keys_peak"):
        assert getattr(new, name) == getattr(other, name), name
    assert new.num_keys() == other.num_keys()
    assert new._table._count == other._table._count
    if isinstance(other, TwoStepDirectory):    # same table, same growth
        assert new._table.longest_run() == other._table.longest_run()


class Trio:
    """One sequence of calls made on the new assign, the two-step
    reference and the numpy table, compared after every call."""

    def __init__(self, num_shards, slots_per_shard, shard_range=None,
                 restore_from=None):
        def make(cls):
            if restore_from is None:
                return cls(num_shards, slots_per_shard, shard_range)
            return cls.restore(num_shards, slots_per_shard, restore_from,
                               shard_range)

        self.new = make(KeyDirectory)
        self.others = [make(TwoStepDirectory), make(NumpyDirectory)]
        assert hasattr(self.new._table, "assign")
        assert not hasattr(self.others[1]._table, "assign")

    def each(self, call):
        got = call(self.new)
        for other in self.others:
            np.testing.assert_array_equal(got, call(other))
            assert_same_state(self.new, other)
        return got

    def assign(self, keys):
        return self.each(lambda d: d.assign(keys))


def suite_batches(n_batches, n=BATCH):
    pool = large.make_pool(SEED, n, PARAMS)
    return [(pool[i]["auction"], SCHED.batch_ts(i, n))
            for i in range(n_batches)]


def churn(trio, batches, hold=1):
    """The window operator's use of a directory over the suite's
    stream: assign, note the panes, release what the purge killed, give
    the slots back ``hold`` batches later."""
    trio.each(lambda d: d.track_panes())
    waiting = []
    for keys, ts in batches:
        slots = trio.assign(keys)
        assert (slots >= 0).all()
        panes = ts // PANE_MS
        valid = np.ones(len(keys), bool)
        trio.each(lambda d: d.note_panes(slots, panes, valid))
        dead = int(panes[-1]) - 2
        waiting.append(trio.each(lambda d: d.release_below(dead)))
        if len(waiting) > hold:
            back = waiting.pop(0)
            trio.each(lambda d: d.reclaim(back))


def case_suite_stream_with_release_and_reclaim():
    trio = Trio(8, 512)
    churn(trio, suite_batches(12))
    assert trio.new.slots_reused > 0 and trio.new.slots_released > 0
    # the stream never runs out of slots only because they come back
    assert trio.new.slots_allocated > trio.new.local_slots


def case_all_distinct_shuffled():
    rng = np.random.default_rng(2)
    keys = rng.permutation(np.arange(-20_000, 20_000, dtype=np.int64) * 7919)
    trio = Trio(16, 4096)
    trio.assign(keys[:30_000])
    trio.assign(keys[10_000:])          # 20,000 known, 10,000 new, mixed


def case_one_key_many_times():
    trio = Trio(8, 16)
    slots = trio.assign(np.full(1 << 16, 12345, np.int64))
    assert len(set(slots.tolist())) == 1 and slots[0] >= 0
    assert trio.new.assign_memo_hits == (1 << 16) - 1


def case_keys_that_collide_in_the_memo():
    """k, k + MEMO_SIZE, k + 2 MEMO_SIZE share one entry of the memo and
    evict each other at every record."""
    rng = np.random.default_rng(4)
    base = rng.integers(0, MEMO_SIZE, 50)
    keys = (base[:, None] + MEMO_SIZE * np.arange(3)[None, :]).reshape(-1)
    keys = keys[rng.integers(0, len(keys), 3 * MEMO_STRETCH)].astype(np.int64)
    trio = Trio(8, 64)
    slots = trio.assign(keys)
    assert (slots >= 0).all()
    assert 0 < trio.new.assign_memo_hits < len(keys) // 2
    trio.assign(keys[::-1].copy())


def case_a_batch_across_table_doublings():
    """A fresh table holds 1,024 keys before it doubles: this batch takes
    it through four doublings, with known keys and repeats between."""
    rng = np.random.default_rng(5)
    trio = Trio(16, 2048)
    first = rng.integers(0, 1 << 40, 900)
    trio.assign(first)
    keys = np.concatenate([first, rng.integers(0, 1 << 40, 12_000)])
    keys = keys[rng.integers(0, len(keys), 40_000)]
    trio.assign(keys)
    assert trio.new._table._count > 8 * 1024
    # one more, whose doubling falls due on its LAST new key
    room = 16 * 1024 - trio.new._table._count
    trio.assign(np.arange(room, dtype=np.int64) + (1 << 41))
    trio.assign(np.asarray([1 << 42], np.int64))


def case_full_shard_and_foreign_shards():
    """4 slots a shard: most keys read FULL; the directory owns shards
    [1, 2) of 4, so three keys in four read -1. Released slots come back
    to the keys that ask next, a refused key stays refused."""
    rng = np.random.default_rng(6)
    trio = Trio(4, 4, shard_range=(1, 2))
    keys = rng.integers(0, 1 << 30, 400)
    slots = trio.assign(keys)
    assert set(np.unique(slots).tolist()) == {-2, -1, 0, 1, 2, 3}
    trio.each(lambda d: d.track_panes())
    trio.each(lambda d: d.note_panes(
        slots, np.zeros(len(keys), np.int64), np.ones(len(keys), bool)))
    back = trio.each(lambda d: d.release_below(1))
    assert len(back) == 4
    trio.each(lambda d: d.reclaim(back[:3]))
    again = trio.assign(rng.integers(1 << 30, 1 << 31, 400))
    assert np.count_nonzero(np.unique(again) >= 0) == 3
    np.testing.assert_array_equal(trio.assign(keys)[slots < 0],
                                  slots[slots < 0])

    whole = Trio(4, 4)                  # every shard its own, all full
    assert (np.unique(whole.assign(keys)) >= -2).all()
    assert whole.new.num_keys() == 16


def case_empty_batch_and_a_batch_of_one():
    trio = Trio(8, 16)
    assert len(trio.assign(np.zeros(0, np.int64))) == 0
    one = trio.assign(np.asarray([-5], np.int64))
    assert len(trio.assign(np.zeros(0, np.int64))) == 0
    np.testing.assert_array_equal(trio.assign(np.asarray([-5])), one)
    assert trio.new.num_keys() == 1


def case_after_restore():
    """A snapshot in mid-churn restored into each of the three, which
    then go on through the stream."""
    batches = suite_batches(10)
    before = KeyDirectory(8, 512)
    before.track_panes()
    for keys, ts in batches[:5]:
        slots = before.assign(keys)
        before.note_panes(slots, ts // PANE_MS, np.ones(len(keys), bool))
        before.reclaim(before.release_below(int(ts[-1] // PANE_MS) - 2))
    assert len(before.free_slots())
    trio = Trio(8, 512, restore_from=before.snapshot())
    assert trio.new.num_keys() == before.num_keys() > 0
    churn(trio, batches[5:])


CASES = [v for k, v in sorted(globals().items()) if k.startswith("case_")]


@pytest.mark.parametrize("case", CASES, ids=lambda c: c.__name__[5:])
def test_assign_gives_the_two_step_paths_slots(case):
    case()


# -- the memo's rule, by its counters ---------------------------------------

def memo_counts(d):
    return d.assign_records, d.assign_memo_looks, d.assign_memo_hits


def test_the_suites_order_is_served_by_the_memo():
    d = KeyDirectory(8, 4096)
    keys = np.concatenate([k for k, _ in suite_batches(4)])
    d.assign(keys)
    records, looks, hits = memo_counts(d)
    assert records == looks == len(keys)
    assert hits > 0.9 * records
    assert hits <= records - len(np.unique(keys))


def test_keys_without_locality_lose_the_memo_within_a_stretch():
    """... and pay its compare for one stretch in ``MEMO_REST + 1``."""
    rng = np.random.default_rng(8)
    keys = rng.permutation(1 << 16).astype(np.int64)
    d = KeyDirectory(8, 1 << 14)
    d.assign(keys)
    stretches = len(keys) // MEMO_STRETCH
    looked = -(-stretches // (MEMO_REST + 1)) * MEMO_STRETCH
    assert looked == 2 * MEMO_STRETCH
    assert memo_counts(d) == (len(keys), looked, 0)
    # known keys, no locality: the same
    d.assign(keys[::-1].copy())
    assert memo_counts(d) == (2 * len(keys), 2 * looked, 0)


@pytest.mark.parametrize("rests", [1, 3])
def test_the_memo_steps_aside_for_a_rest_and_not_for_the_call(rests):
    """Locality that ends mid-batch and comes back (a batch whose first
    rows were held back from seconds ago, ISSUE 50): the memo serves the
    first part, leaves at the end of the first stretch without it, looks
    again every ``MEMO_REST + 1`` stretches, and serves the last part
    from the first stretch on that it looks at; a new call starts with
    it."""
    rng = np.random.default_rng(9)
    local = np.repeat(np.arange(64, dtype=np.int64), 2 * MEMO_STRETCH // 64)
    # whole rests: the stretch after the last one is the first of
    # ``local`` again
    scattered = rng.permutation(
        rests * (MEMO_REST + 1) * MEMO_STRETCH).astype(np.int64) + 1000
    d = KeyDirectory(8, 1 << 15)
    d.assign(np.concatenate([local, scattered, local]))
    records, looks, hits = memo_counts(d)
    assert records == 4 * MEMO_STRETCH + len(scattered)
    assert looks == (2 + rests + 2) * MEMO_STRETCH
    # what it held before the rest is still true: the second part's
    # first records hit too, but where a scattered key took the entry
    assert 2 * len(local) - 128 <= hits <= 2 * len(local) - 64
    d.assign(local)
    assert memo_counts(d) == (records + len(local), looks + len(local),
                              hits + len(local) - 64)


def test_held_back_rows_first_leave_the_memo_to_the_rest_of_the_batch():
    """A tenth of a batch held back 0-3 s, offered first
    (``nexmark_q5_delayed`` at the rehearsal's density): the rows after
    them are served as the suite's own order is."""
    from benchmark.configs import nexmark_q5_delayed as delayed

    p = {**PARAMS, "events_per_ms": 16, "prob_delayed": 0.1,
         "occasional_delay_ms": 3000, "delay_seed": 3}
    n = 1 << 17
    pool = delayed.make_pool(SEED, n, p)
    d = KeyDirectory(8, 1 << 15)
    keys = pool[pool.arrivals.steady_from + 2]["auction"]
    d.assign(keys)
    records, looks, hits = memo_counts(d)
    assert records == n
    assert looks > 0.85 * n and hits > 0.75 * n
