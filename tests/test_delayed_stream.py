"""A stream that is NOT in order, inside its watermark's bound (ISSUE 50,
ROADMAP R2): a tenth of the records is handed over up to 3 s after it
happened, with the timestamp it happened at, under a watermark that
trails the newest timestamp by 4 s. Nothing is late, so the window
operator on the general lane, with release on, must commit exactly the
rows the same records give in timestamp order, under both loop orders
(``lead_advance`` taken and refused), and exactly what counting the
records without the engine gives.

Also here: the edge of the gate that lets an advance lead (the oldest
record at ``wm + 1`` and at ``wm``, for the window operator and for the
session operator's ``> wm + 1``); a held-back record that names a key
whose newest pane lies above the record's, and one that names a key
released one purge earlier; and what the disorder counters
(``WindowOperator._count_disorder``, ``refire_ends``, the gate's two)
read on streams built to known counts.
"""
import collections

import numpy as np
import pytest

import flink_tpu  # noqa: F401 — x64 before other jax users
from flink_tpu.api.windowing import SlidingEventTimeWindows
from flink_tpu.ops.aggregates import count, multi, sum_of
from flink_tpu.ops.session_device import DeviceSessionOperator
from flink_tpu.ops.window import WindowOperator
from test_advance_first import BIG, SHARDS, drive, rows_of

BATCH = 512
WINDOW, SLIDE = 10_000, 2_000
DELAY = 4_000           # the watermark's bound
HELD = 3_000            # a held-back record's delay lies under this
PER_MS = 1              # records a millisecond: a batch spans ~0.5 s
N_RECORDS = 60 * BATCH  # 30 s of event time


def records(seed=11):
    """The stream as it HAPPENS: timestamps in order, and keys that come
    and go with time as NEXmark's auctions do (a key is named for ~0.4 s
    and then no more), so panes are purged and slots released."""
    rng = np.random.default_rng(seed)
    ts = (np.arange(N_RECORDS) // PER_MS).astype(np.int64)
    keys = (ts // 4 + rng.integers(0, 100, N_RECORDS)).astype(np.int64)
    return keys, ts, rng.integers(1, 9, N_RECORDS).astype(np.float32)


def batches_of(keys, ts, v, order):
    return [(keys[o], ts[o], {"v": v[o]})
            for o in np.split(order, len(order) // BATCH)]


def in_order(seed=11):
    keys, ts, v = records(seed)
    return batches_of(keys, ts, v, np.arange(N_RECORDS))


def held_back(seed=11, share=0.1):
    """The same records, a tenth of them offered 0-3 s after they
    happened: sorted by due time, cut by count (the construction of
    ``benchmark/traffic_kinds/constant_rate_delayed.py``)."""
    keys, ts, v = records(seed)
    rng = np.random.default_rng(seed + 1)
    held = rng.random(N_RECORDS) < share
    due = ts + np.where(held, rng.integers(0, HELD, N_RECORDS), 0)
    return batches_of(keys, ts, v, np.argsort(due, kind="stable"))


def counted(batches):
    """Engine-free: ``{(window_end, key): count}`` of every window a
    record lies in."""
    out = collections.Counter()
    for keys, ts, _ in batches:
        for k, t in zip(keys.tolist(), ts.tolist()):
            first_end = (t // SLIDE + 1) * SLIDE
            for end in range(first_end, first_end + WINDOW, SLIDE):
                out[(end, k)] += 1
    return out


def topn():
    """Q5's operator at a size the fused step cannot address: every
    batch takes the general lane; keys are released."""
    return WindowOperator(
        SlidingEventTimeWindows.of(WINDOW, SLIDE), count(),
        num_shards=SHARDS, slots_per_shard=BIG, allowed_lateness_ms=0,
        max_out_of_orderness_ms=DELAY, top_n=("count", 1))


def pack(lateness=0):
    return WindowOperator(
        SlidingEventTimeWindows.of(WINDOW, SLIDE),
        multi(count(), sum_of("v")), num_shards=SHARDS, slots_per_shard=2048,
        allowed_lateness_ms=lateness, max_out_of_orderness_ms=DELAY)


def committed(fired):
    """``{(window_end, key): count}`` of what an operator fired."""
    out = {}
    for f in fired:
        if len(f.get("window_end", ())):
            for e, k, c in zip(np.asarray(f["window_end"]).tolist(),
                               np.asarray(f["key"]).tolist(),
                               np.asarray(f["count"]).tolist()):
                assert (e, k) not in out, "a window end fired twice"
                out[(e, k)] = c
    return out


def best(counts):
    """Per window end the key(s) with the most records, ties kept."""
    top = collections.defaultdict(int)
    for (e, _), c in counts.items():
        top[e] = max(top[e], c)
    return {ek: c for ek, c in counts.items() if c == top[ek[0]]}


@pytest.mark.parametrize("lead", [True, False], ids=["led", "not_led"])
@pytest.mark.parametrize("kind", ["topn", "pack"])
def test_a_held_back_stream_commits_the_in_order_rows(kind, lead):
    make = {"topn": topn, "pack": pack}[kind]
    stream = held_back()
    op, ref = make(), make()
    fired, led, _, _ = drive(op, stream, lead, delay=DELAY)
    fired_ref, _, _, _ = drive(ref, in_order(), False, delay=DELAY)
    assert rows_of(fired) == rows_of(fired_ref) != []
    want = counted(stream)
    assert committed(fired) == (best(want) if kind == "topn" else want)
    for o in (op, ref):
        assert o.late_records == o.records_dropped_full == 0
        assert o.refire_ends == o.slots_returned_early == 0
    # keys came and went, in both: the release ran on a disordered stream
    assert op.state_counters()["state.slots_released"] > 1_000
    # the gate: every advance with something to do led (a batch's oldest
    # record is ~3.5 s behind its newest, the watermark 4 s), or none was
    # asked to
    assert op.prof["advances_led"] == len(led)
    if lead:
        assert len(led) == op.prof["advances_with_work"] >= 10
    else:
        assert op.prof["advances_with_work"] == 0


def test_the_disorder_counters_read_what_the_stream_was_built_with():
    stream = held_back()
    op = pack()
    drive(op, stream, True, delay=DELAY)
    behind = farthest = panes = 0
    seen = None
    for _, ts, _ in stream:
        if seen is not None:
            behind += int((ts < seen).sum())
            farthest = max(farthest, seen - int(ts.min()))
        seen = max(seen or 0, int(ts.max()))
        panes += int(ts.max()) // SLIDE - int(ts.min()) // SLIDE + 1
    assert op.prof["disorder_records"] == behind
    # a tenth is held back; of those, the few whose delay ran out inside
    # the batch they would have been in anyway are not behind anything
    assert 0.085 * N_RECORDS < behind < 0.105 * N_RECORDS
    assert op.prof["disorder_max_ms"] == farthest
    assert HELD - 300 < farthest < HELD + 2 * BATCH // PER_MS
    assert op.prof["batch_panes"] == panes
    assert 2 * len(stream) <= panes <= 3 * len(stream)
    # inside the bound no record reaches a window end that has fired
    assert op.prof["refire_probe_records"] == 0 == op.refire_ends

    # the same records in order: nothing behind, a pane or two a batch
    op = pack()
    drive(op, in_order(), True, delay=DELAY)
    assert op.prof["disorder_records"] == op.prof["disorder_max_ms"] == 0
    assert len(stream) <= op.prof["batch_panes"] <= 2 * len(stream)


def test_a_record_behind_a_fired_window_end_is_counted_and_refires():
    """Beyond the bound but within an allowed lateness: one record a
    batch, from the fortieth on, stamped 7 s back. Its windows have
    fired; they fire again, and both counters say so."""
    stream = in_order()
    late_from = 40
    for i in range(late_from, len(stream)):
        keys, ts, data = stream[i]
        ts = ts.copy()
        ts[0] -= 7_000
        stream[i] = (keys, ts, data)
    op = pack(lateness=8_000)
    drive(op, stream, True, delay=DELAY)
    assert op.late_records == 0
    assert op.prof["refire_probe_records"] == len(stream) - late_from
    assert op.refire_ends >= len(stream) - late_from
    assert op.prof["disorder_records"] == len(stream) - late_from


# -- the gate's edge ---------------------------------------------------------

def sessions():
    return DeviceSessionOperator(
        3_000, count(), num_shards=SHARDS, slots_per_shard=2048,
        max_out_of_orderness_ms=DELAY)


def edge_stream(behind):
    """In-order batches of one stamp each, 500 ms apart, and in every
    batch from the tenth on one record ``behind`` ms before the batch's
    stamp: with ``behind`` = DELAY the oldest record lies at ``wm + 1``
    (the watermark a batch implies is its newest stamp - DELAY - 1), with
    DELAY + 1 at ``wm``."""
    out = []
    for i in range(40):
        rng = np.random.default_rng(500 + i)
        ts = np.full(64, 500 * (i + 1), np.int64)
        if i >= 10:
            ts[0] -= behind
        out.append((rng.integers(0, 50, 64).astype(np.int64), ts,
                    {"v": np.ones(64, np.float32)}))
    return out


@pytest.mark.parametrize("kind,behind,leads", [
    ("window", DELAY, True),         # oldest at wm + 1: above the watermark
    ("window", DELAY + 1, False),    # oldest at wm: late, or in a dead pane
    ("session", DELAY - 1, True),    # oldest at wm + 2
    ("session", DELAY, False),       # at wm + 1 a session may still grow
    ("session", DELAY + 1, False),
])
def test_the_gate_at_its_edge(kind, behind, leads):
    make = pack if kind == "window" else sessions
    stream = edge_stream(behind)
    a, b = make(), make()
    fired_a, led, _, _ = drive(a, stream, True, delay=DELAY)
    fired_b, _, _, _ = drive(b, stream, False, delay=DELAY)
    assert rows_of(fired_a) == rows_of(fired_b) != []
    # (a record AT the watermark is dropped as late only where its pane
    # or its session is gone: in either order alike)
    assert a.late_records == b.late_records
    tail = [i for i in led if i >= 10]
    assert bool(tail) == leads
    if kind == "window":
        # asked for every advance with work, led for those before the
        # old record appears and, above the watermark, for the rest too
        assert a.prof["advances_led"] == len(led)
        assert a.prof["advances_with_work"] > len(led) or leads


# -- a held-back record and the key it names ---------------------------------

def one_batch(keys_ts):
    keys, ts = (np.asarray(x, np.int64) for x in zip(*keys_ts))
    return keys, ts, {"v": np.ones(len(ts), np.float32)}


@pytest.mark.parametrize("lead", [True, False], ids=["led", "not_led"])
def test_a_held_back_record_for_a_key_with_a_newer_pane(lead):
    """Key 7 is seen in pane 5 (10.5 s) and then, held back, in pane 4
    (9 s): its newest pane stays 5 (``note_panes`` keeps the larger), so
    the purge that takes pane 4 leaves the key alone
    (``release_below``), and its record of pane 5 is still in its slot
    for the windows that follow."""
    stream = [one_batch([(7, 10_500), (8, 10_600)]),
              one_batch([(7, 9_000), (9, 11_000)]),
              # pane 4's last window ends at 18 s: dead at wm 17,999
              one_batch([(10, 21_990)]),
              one_batch([(11, 22_000)]),
              one_batch([(7, 23_000)])]
    op = pack()
    fired, _, _, _ = drive(op, stream, lead, delay=DELAY)
    assert committed(fired) == counted(stream)
    d = op.directory
    # one life: the key was never released between its records
    assert d.slots_allocated == 5 and op.late_records == 0
    assert op.prof["disorder_records"] == 1


@pytest.mark.parametrize("lead", [True, False], ids=["led", "not_led"])
def test_a_held_back_record_for_a_key_released_one_purge_earlier(lead):
    """Key 7 is seen in pane 0 alone. The purge that takes pane 0 (its
    last window ends at 10 s) releases it; the next batch holds a record
    of key 7 that happened 3 s before the batch's newest, in a pane that
    is alive: the key is inserted anew, and the rows are those of the
    count."""
    stream = [one_batch([(7, 500), (8, 1_000)]),
              one_batch([(9, 14_100)]),       # wm 10,099: pane 0 is dead
              one_batch([(10, 17_000), (7, 14_050)]),
              one_batch([(11, 30_000)])]
    op = pack()
    fired, _, _, _ = drive(op, stream, lead, delay=DELAY)
    assert committed(fired) == counted(stream)
    c = op.state_counters()
    # 7 twice: released with pane 0 and inserted anew
    assert op.directory.slots_allocated == 6
    assert c["state.slots_released"] >= 2 and op.late_records == 0
    assert c["state.slots_returned_early"] == 0
