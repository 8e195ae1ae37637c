"""The count-only lane's key scan under ``host.parallelism``: same job.

``WindowOperator._process_batch_fused`` lets the native scan's first pass
run over as many record ranges at once as the driver's host pool has
workers (``native/codec.cc`` ``ingest_fused_scan_split``), when the batch
is long enough to keep every range over the floor. Whatever the pool's
size, the job is the job: the same rows, and the same bytes in every
buffer handed to ``_upload_and_step``, in the same order. What differs is
the counter ``profile.opN.scan_ranges``: the pool's parallelism a batch
where the scan was split, 1 a batch where it was not.

No assertion here is about time.
"""
import numpy as np
import pytest

import flink_tpu  # noqa: F401 — x64 before other jax users
from flink_tpu import native_codec
from flink_tpu.api.environment import StreamExecutionEnvironment
from flink_tpu.api.sinks import FnSink
from flink_tpu.api.sources import GeneratorSource
from flink_tpu.config import Configuration
from flink_tpu.nexmark.queries import q5_hot_items
from flink_tpu.ops.window import WindowOperator

pytestmark = pytest.mark.skipif(
    not native_codec.native_available(),
    reason="the fused scan needs the C codec")

N = 1 << 20                  # records a batch: the cells' own
N_BATCHES = 5
# the first batch meets an empty directory: the scan's pair cap, sized
# from the keys known, overflows and the batch goes the general lane
N_SCANNED = N_BATCHES - 1
SLIDE_MS = 2_000
BATCH_MS = 900               # event time a batch: every third one straddles
KEYS_AT_START, KEYS_LATER = 300, 500    # auctions 300.. first bid in batch 2


def make_stream(n=N, n_batches=N_BATCHES):
    """In order; half of each batch's bids on one hot auction (so the
    largest pair count passes 65,535 and every batch is applied at once,
    through ``_upload_and_step``), the rest over the auctions in flight.
    Batches 2 and 4 straddle a pane boundary; from batch 2 on, 200
    auctions bid that the directory has not seen."""
    out = []
    for i in range(n_batches):
        rng = np.random.default_rng(100 + i)
        live = KEYS_AT_START if i < 2 else KEYS_LATER
        keys = np.where(rng.integers(0, 2, n) > 0, 7,
                        rng.integers(0, live, n)).astype(np.int64)
        ts = (i * BATCH_MS + (np.arange(n, dtype=np.int64) * BATCH_MS) // n)
        out.append((keys, ts))
    return out


@pytest.fixture
def uploads(monkeypatch):
    """Every buffer handed to ``_upload_and_step``, in order."""
    seen = []
    upload = WindowOperator._upload_and_step

    def spy(op, step, buf):
        seen.append((buf.dtype.str, buf.tobytes()))
        return upload(op, step, buf)

    monkeypatch.setattr(WindowOperator, "_upload_and_step", spy)
    return seen


def run_q5(stream, parallelism, uploads, batch=N):
    """-> (metrics, sorted rows, this run's upload buffers in order)."""
    before = len(uploads)
    env = StreamExecutionEnvironment(Configuration({
        "pipeline.microbatch-size": batch, "state.num-key-shards": 8,
        "state.slots-per-shard": 128, "host.parallelism": parallelism,
        "analysis.fail-on": "off"}))
    batches = []
    source = GeneratorSource(
        lambda split, i: ({"auction": stream[i][0]}, stream[i][1])
        if i < len(stream) else None)
    q5_hot_items(env, source, FnSink(batches.append), window_ms=10_000,
                 slide_ms=SLIDE_MS, out_of_orderness_ms=4_000)
    res = env.execute(f"scan-ranges-{parallelism}")
    rows = sorted(
        (int(w), int(a), int(c)) for b in batches
        for w, a, c in zip(b["window_end"], b["auction"], b["bid_count"]))
    return res.metrics, rows, uploads[before:]


def op_counter(metrics, name):
    (value,) = [v for k, v in metrics.items()
                if k.startswith("profile.op") and k.endswith("." + name)]
    return value


def test_rows_and_upload_buffers_do_not_depend_on_host_parallelism(uploads):
    stream = make_stream()
    assert len({int(t.min()) // SLIDE_MS for _, t in stream}) > 1
    assert any(t.min() // SLIDE_MS != t.max() // SLIDE_MS for _, t in stream)
    m1, rows1, up1 = run_q5(stream, 1, uploads)
    m4, rows4, up4 = run_q5(stream, 4, uploads)
    assert rows1 and rows4 == rows1
    assert len(up1) == N_BATCHES        # every batch went this way
    assert up4 == up1                   # dtype, length, every byte, in order
    for m in (m1, m4):
        assert op_counter(m, "preagg_batches") == N_BATCHES
        assert m["records_in"] == N * N_BATCHES
        assert m.get("late_records", 0) == 0
    assert op_counter(m1, "scan_ranges") == N_SCANNED * 1
    assert op_counter(m4, "scan_ranges") == N_SCANNED * 4
    assert m4["profile.phase.scan_ranges"] == N_SCANNED * 4
    # the job's first batch went the general lane, through the
    # directory's native assign; its ~300 keys fit the memo
    for m in (m1, m4):
        assert op_counter(m, "assign_records") == N
        assert N - 2 * KEYS_AT_START < op_counter(m, "assign_memo_hits") < N
        assert m["profile.phase.assign_records"] == N
        assert (m["profile.phase.assign_memo_hits"]
                == op_counter(m, "assign_memo_hits"))
    # a range that starts inside a pane seeks once more; the serial
    # cursor moves once a pane of the batch
    assert op_counter(m1, "scan_pane_moves") < 3 * N_SCANNED
    assert (op_counter(m1, "scan_pane_moves")
            <= op_counter(m4, "scan_pane_moves")
            <= op_counter(m1, "scan_pane_moves") + 3 * N_SCANNED)


def test_a_batch_under_the_floor_is_scanned_in_one_range(uploads):
    """Two ranges' worth of records is the least a split takes: a job
    whose batches are shorter makes the serial call at any
    ``host.parallelism``."""
    n = 2 * native_codec.SCAN_RANGE_MIN_RECORDS - 2
    stream = make_stream(n=n, n_batches=3)
    m, rows, sent = run_q5(stream, 4, uploads, batch=n)
    assert rows and len(sent) == 3
    assert op_counter(m, "preagg_batches") == 3
    assert op_counter(m, "scan_ranges") == 2      # all but the first batch
