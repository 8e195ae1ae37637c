import time

import numpy as np
import pytest

from benchmark.loadgen import BenchSource, Heartbeat, RecordingSink
from benchmark.run import load_module

Schedule = load_module("traffic_kinds", "constant_rate").Schedule


def rate(events_per_ms):
    return Schedule({"events_per_ms": events_per_ms})


@pytest.mark.parametrize("r,n", [(100, 1 << 12), (45_000, 1 << 14), (7, 50)])
def test_constant_rate_stamps_event_i_with_i_over_rate(r, n):
    s = rate(r)
    assert s.events_per_ms == r
    for index in (0, 1, 2, 977):
        ids = index * n + np.arange(n, dtype=np.int64)
        ts = s.batch_ts(index, n)
        assert ts.dtype == np.int64 and np.array_equal(ts, ids // r)


@pytest.mark.parametrize("bad", [0, -3, 2.5])
def test_constant_rate_refuses_a_rate_that_is_not_a_positive_whole(bad):
    with pytest.raises(ValueError):
        rate(bad)


def test_batches_carry_pool_fields_in_turn_and_consecutive_event_ids():
    pool = [{"k": np.full(4, j)} for j in range(3)]
    src = BenchSource(pool, rate(2), 4, max_batches=5)
    got = list(src.open_split("0"))
    assert [int(d["k"][0]) for d, _ in got] == [0, 1, 2, 0, 1]
    assert np.concatenate([ts for _, ts in got]).tolist() == [
        i // 2 for i in range(20)]
    assert src.batches == 5 and src.max_ts == 9
    assert src.late_s == [] and len(src.gen_s) == 5


def test_paced_source_releases_each_batch_when_its_last_event_is_due():
    # 50 events per batch at 1 event/ms: batch i's last event is stamped
    # 50 i + 49 and is due that many ms after the split was opened
    src = BenchSource([{"k": np.zeros(50)}], rate(1), 50,
                      paced=True, seconds=0.42)
    got = list(src.open_split("0"))
    # batches whose last event is due inside 0.42 s: 49, 99, ..., 399
    assert len(got) == 8 == src.batches
    due = [src.t_open + (50 * i + 49) / 1e3 for i in range(8)]
    assert all(r >= d for r, d in zip(src.release_s, due))
    assert src.late_s == pytest.approx(
        [r - d for r, d in zip(src.release_s, due)])
    # on an idle machine the release is within a few ms of due; a loaded
    # one may be later, never earlier
    assert min(src.late_s) >= 0.0 and min(src.late_s) < 0.05


def test_a_slow_consumer_shows_as_lateness_not_as_a_slower_schedule():
    src = BenchSource([{"k": np.zeros(10)}], rate(1), 10,
                      paced=True, seconds=0.2)
    it = src.open_split("0")
    next(it)
    time.sleep(0.08)            # the job stalls for 80 ms
    next(it), next(it)
    # batches 1, 2 were due at 19 and 29 ms: handed over ~60, ~50 ms late
    assert src.late_s[1] > 0.04 and src.late_s[2] > 0.03
    assert np.asarray(next(it)[1]).tolist() == list(range(30, 40))


def test_unpaced_source_stops_offering_at_the_deadline():
    src = BenchSource([{"k": np.zeros(8)}], rate(1), 8,
                      seconds=0.05)
    n = 0
    for _ in src.open_split("0"):
        n += 1
        time.sleep(0.01)
    assert 3 <= n <= 7 and src.batches == n


def test_source_does_not_resume():
    src = BenchSource([{"k": np.zeros(1)}], rate(1), 1)
    with pytest.raises(ValueError):
        next(src.open_split("0", start_pos=3))


def test_recording_sink_keeps_copies_and_first_arrivals():
    rec = RecordingSink()
    a = {"window_end": np.array([1000, 1000, 2000]), "n": np.array([1, 2, 3])}
    rec.sink.write(a)
    a["n"][0] = 99              # the job reuses its buffers
    rec.sink.write({"window_end": np.array([2000, 3000]),
                    "n": np.array([4, 5])})
    assert rec.batches[0]["n"].tolist() == [1, 2, 3]
    first = rec.first_arrival_by("window_end")
    assert sorted(first) == [1000, 2000, 3000]
    assert first[1000] == first[2000] == rec.arrival_s[0]
    assert first[3000] == rec.arrival_s[1] == rec.last_arrival()


def test_heartbeat_sees_a_process_that_stood_still_and_only_that():
    import sys

    old = sys.getswitchinterval()
    sys.setswitchinterval(10.0)      # a thread that will not let go
    try:
        with Heartbeat() as hb:
            time.sleep(0.05)
            t0 = time.perf_counter()
            while time.perf_counter() - t0 < 0.15:
                pass                 # holds the interpreter lock
            time.sleep(0.05)
    finally:
        sys.setswitchinterval(old)
    assert 0.1 < hb.stall_s() < 0.5
    top = hb.longest(0.0)
    assert top and top[0][1] >= 100.0 and len(top[0]) == 4
    with Heartbeat() as quiet:
        time.sleep(0.1)
    assert quiet.stall_s() < 0.05
