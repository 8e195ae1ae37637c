"""Socket ingest source: C line-framed reader + Python fallback parity,
end-to-end windowed pipeline fed over TCP (SURVEY §3.10 item 3)."""
import socket
import threading
import time

import numpy as np
import pytest

from flink_tpu import native_codec as nc
from flink_tpu.config import Configuration
from flink_tpu.connectors import SocketSource, _PySocketReader
from flink_tpu.formats import CsvFormat


def _feed(port, payload: bytes, chunk=7, delay=0.0):
    """Background producer writing payload in awkward chunk sizes (to
    exercise the partial-line carry), then disconnecting."""
    def run():
        s = socket.create_connection(("127.0.0.1", port))
        try:
            for lo in range(0, len(payload), chunk):
                s.sendall(payload[lo:lo + chunk])
                if delay:
                    time.sleep(delay)
        except (ConnectionResetError, BrokenPipeError):
            pass    # the reader under test hung up first (it may: an
            # oversized line makes it raise and close mid-payload)
        finally:
            s.close()

    t = threading.Thread(target=run, daemon=True)
    t.start()
    return t


def _drain(reader, payload, cap=64):
    """Producer in the background, consume blocks until EOF."""
    t = _feed(reader.port, payload)
    deadline = time.time() + 30
    while reader.accept(100) == 0:
        assert time.time() < deadline, "producer never connected"
    got = b""
    while True:
        b = reader.read_block(cap, timeout_ms=200)
        if b is None:
            break
        got += b
        # block invariant: always ends at a newline
        assert b == b"" or b.endswith(b"\n")
        assert time.time() < deadline, "reader never saw EOF"
    t.join()
    reader.close()
    return got


class TestReaders:
    PAYLOAD = b"".join(f"{i},{i*3}\n".encode() for i in range(100))

    def test_native_reader_reassembles_lines(self):
        r = nc.NativeSocketReader.create()
        if r is None:
            pytest.skip("codec library unavailable")
        assert _drain(r, self.PAYLOAD) == self.PAYLOAD

    def test_python_reader_parity(self):
        assert _drain(_PySocketReader(), self.PAYLOAD) == self.PAYLOAD

    def test_unterminated_tail_discarded(self):
        r = _PySocketReader()
        _feed(r.port, b"1,2\n3,4")  # second record never terminated
        while r.accept(1000) == 0:
            pass
        got = b""
        while True:
            b = r.read_block(64, timeout_ms=200)
            if b is None:
                break
            got += b
        r.close()
        assert got == b"1,2\n"


class TestSocketPipeline:
    def test_windowed_count_over_tcp(self):
        from flink_tpu.api.environment import StreamExecutionEnvironment
        from flink_tpu.api.sinks import CollectSink
        from flink_tpu.api.windowing import TumblingEventTimeWindows
        from flink_tpu.time.watermarks import WatermarkStrategy

        rng = np.random.default_rng(0)
        n = 4000
        keys = rng.integers(0, 6, n)
        ts = np.sort(rng.integers(0, 8000, n))
        payload = b"".join(f"{k},{t}\n".encode()
                           for k, t in zip(keys, ts))

        src = SocketSource(format=CsvFormat([("k", "i64"), ("ts", "i64")]),
                           ts_field="ts", poll_ms=50)
        env = StreamExecutionEnvironment(Configuration({
            "state.num-key-shards": 8, "state.slots-per-shard": 16}))
        sink = CollectSink()
        (env.from_source(src,
                         WatermarkStrategy.for_bounded_out_of_orderness(0))
         .key_by("k").window(TumblingEventTimeWindows.of(1000)).count()
         .add_sink(sink))
        _feed(src.port, payload, chunk=1024)
        env.execute("socket-count")

        golden = {}
        for k, t in zip(keys, ts):
            kk = (int(k), (int(t) // 1000 + 1) * 1000)
            golden[kk] = golden.get(kk, 0) + 1
        got = {(int(r["key"]), int(r["window_end"])): int(r["count"])
               for r in sink.rows}
        assert got == golden


class TestReviewRegressions:
    def test_oversized_line_raises_in_both_readers(self):
        r = _PySocketReader()
        _feed(r.port, b"x" * 500 + b"\n")
        while r.accept(1000) == 0:
            pass
        with pytest.raises(IOError, match="exceeded"):
            while True:
                b = r.read_block(64, timeout_ms=200)
                if b is None:
                    break

    def test_accept_wait_yields_typed_empty_batches(self):
        src = SocketSource(format=CsvFormat([("k", "i64"), ("ts", "i64")]),
                           ts_field="ts", poll_ms=20)
        it = src.open_split("socket")
        data, ts = next(it)  # nobody connected: typed empty batch
        assert set(data) == {"k", "ts"}
        assert len(ts) == 0 and data["k"].dtype == np.int64
        src._reader.close()

    def test_finished_runners_reset_on_restart(self):
        from flink_tpu.runtime.coordinator import JobCoordinator

        coord = JobCoordinator(Configuration({}))
        try:
            for r in ("a", "b"):
                coord.rpc_register_runner(r, "h", 1)
            coord.rpc_submit_job("j", runners=["a", "b"])
            coord.rpc_finish_job("j", runner_id="a")
            assert coord.rpc_job_status("j")["state"] == "RUNNING"
            coord.rpc_report_failure("j", "b crashed")
            assert coord.jobs["j"].finished_runners == []
            # attempt 2: BOTH must finish again
            coord.jobs["j"].state = "RUNNING"
            coord.rpc_finish_job("j", runner_id="b")
            assert coord.rpc_job_status("j")["state"] == "RUNNING"
            coord.rpc_finish_job("j", runner_id="a")
            assert coord.rpc_job_status("j")["state"] == "FINISHED"
        finally:
            coord.close()
