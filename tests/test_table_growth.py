"""The key directory's hash table counts its own doublings: how many, the
seconds they took, the buckets it has now (``codec.cc`` ``ht_grow`` /
``ht_growth``; ``_NumpyHashTable._grow``), whichever call's entry was due
one, and ``WindowOperator.state_counters`` hands them on as
``state.table_grows``, ``state.table_grow_s`` and ``state.table_buckets``.

Both tables start at 2,048 buckets and double before the entry that would
pass a load of one half, so the key count implies the other two numbers
(at a load of exactly one half the native table doubles at its next probe,
hit or not, the numpy one at its next entry: no case sits on that edge).
Nothing here asserts how long a doubling took, only that one was timed.
"""
import numpy as np
import pytest

import flink_tpu  # noqa: F401 — x64 before other jax users
from flink_tpu import native_codec
from flink_tpu.api.windowing import SlidingEventTimeWindows
from flink_tpu.ops.aggregates import count
from flink_tpu.ops.window import WindowOperator
from flink_tpu.state.keyed import KeyDirectory, _NumpyHashTable

native = pytest.mark.skipif(
    not native_codec.native_available(),
    reason="the native table needs the C codec")


class NumpyDirectory(KeyDirectory):
    """The directory of a process without the codec library."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self._table = _NumpyHashTable()


TABLES = [pytest.param(KeyDirectory, marks=native, id="native"),
          pytest.param(NumpyDirectory, id="numpy")]


def implied(n_keys):
    """(doublings, buckets) of a table that has held ``n_keys`` at once."""
    buckets = 2048
    while 2 * n_keys > buckets:
        buckets *= 2
    return buckets.bit_length() - (2048).bit_length(), buckets


@pytest.mark.parametrize("directory", TABLES)
@pytest.mark.parametrize("n_keys", [1000, 1023, 1025, 5000, 70_000])
def test_growth_is_what_the_key_count_implies(directory, n_keys):
    d = directory(8, 1 << 14)
    assert d.table_growth() == (0, 0.0, 2048)
    keys = np.random.default_rng(n_keys).permutation(10 * n_keys)[:n_keys]
    # in three batches, each key twice: a hit grows nothing
    for part in np.array_split(keys.astype(np.int64), 3):
        assert (d.assign(np.concatenate([part, part])) >= 0).all()
    grows, grow_s, buckets = d.table_growth()
    assert (grows, buckets) == implied(n_keys)
    assert (grow_s > 0) == (grows > 0)
    assert (d.assign(keys) >= 0).all()
    assert d.table_growth() == (grows, grow_s, buckets)


@pytest.mark.parametrize("directory", TABLES)
def test_released_keys_make_room_and_the_table_never_shrinks(directory):
    """The load is the keys registered NOW: 3,000 keys that come and go
    2,000 at a time stay under a table that 3,000 at once would double."""
    d = directory(8, 1 << 12)
    d.track_panes()
    ones = np.ones(1000, bool)
    for i, pane in enumerate(range(3)):
        keys = np.arange(i * 1000, (i + 1) * 1000, dtype=np.int64)
        d.note_panes(d.assign(keys), np.full(1000, pane), ones)
        d.release_below(pane)       # the batch before last leaves
        assert d.num_keys() <= 2000
    assert d.table_growth()[::2] == implied(2000) == (1, 4096)
    assert implied(3000) == (2, 8192)


@native
def test_the_fused_scans_registrations_grow_the_same_table():
    """``register_misses`` (what the fused scan's probe missed) enters
    keys by ``ht_insert``: counted by the table, not by the caller."""
    d = KeyDirectory(8, 1 << 12)
    d.register_misses(np.arange(3000, dtype=np.int64))
    assert d.table_growth()[::2] == implied(3000)
    d.assign(np.arange(3000, 9000, dtype=np.int64))
    assert d.table_growth()[::2] == implied(9000)


@pytest.mark.parametrize("numpy_table", [
    pytest.param(False, marks=native, id="native"),
    pytest.param(True, id="numpy")])
def test_the_operator_hands_the_counters_on(numpy_table):
    op = WindowOperator(SlidingEventTimeWindows.of(10_000, 2_000), count(),
                        num_shards=8, slots_per_shard=1 << 11)
    if numpy_table:
        op.directory._table = _NumpyHashTable()
    before = op.state_counters()
    assert (before["state.table_grows"], before["state.table_grow_s"],
            before["state.table_buckets"]) == (0, 0.0, 2048)
    rng = np.random.default_rng(7)
    for i in range(4):
        keys = rng.integers(0, 6000, 4096).astype(np.int64)
        ts = np.sort(i * 500 + rng.integers(0, 500, 4096)).astype(np.int64)
        op.process_batch(keys, ts, {})
    after = op.state_counters()
    grows, buckets = implied(after["state.live_keys_peak"])
    assert grows >= 2
    assert after["state.table_grows"] == grows
    assert after["state.table_buckets"] == buckets
    assert after["state.table_grow_s"] > 0
