"""Device-chained generator source (DeviceGeneratorSource +
devgen_step_kernel): the source is synthesized INSIDE the window
operator's step program — the operator-chaining principle (ref:
StreamingJobGraphGenerator chaining elides serialization between
chained operators; flink-connector-datagen as the embedded source)
taken to its TPU conclusion. These tests pin the contract:
bit-exactness of the device and host streams, golden equality of the
chained path against the host-materialized path, miss repair (batch 0
registers every key through the repair loop), and checkpoint/restore
mid-stream."""
import numpy as np
import pytest

from flink_tpu.api.environment import StreamExecutionEnvironment
from flink_tpu.api.sinks import FnSink
from flink_tpu.config import Configuration
from flink_tpu.native_codec import native_available
from flink_tpu.nexmark.generator import (
    NexmarkConfig, bid_stream, bid_stream_device)
from flink_tpu.nexmark.queries import q5_hot_items

pytestmark = pytest.mark.skipif(
    not native_available(), reason="needs the C codec (miss repair)")


def _cfg(n_batches=6, batch=4096):
    return NexmarkConfig(
        batch_size=batch, n_batches=n_batches, events_per_ms=4,
        num_active_auctions=500, hot_ratio=4)


def _env(batch):
    return StreamExecutionEnvironment(Configuration({
        "state.num-key-shards": 8, "state.slots-per-shard": 128,
        "pipeline.microbatch-size": batch,
    }))


def _rows(sink_rows):
    out = []
    for b in sink_rows:
        for i in range(len(b["window_end"])):
            out.append((int(b["window_end"][i]), int(b["auction"][i]),
                        int(b["bid_count"][i])))
    return sorted(out)


def _run_q5(src_fn, cfg):
    env = _env(cfg.batch_size)
    rows = []
    q5_hot_items(env, src_fn(cfg), FnSink(rows.append),
                 window_ms=4_000, slide_ms=1_000,
                 out_of_orderness_ms=500)
    res = env.execute("q5-devgen")
    return _rows(rows), res


class TestBitExactness:
    def test_device_stream_matches_host_stream(self):
        import jax
        cfg = _cfg()
        src = bid_stream_device(cfg)
        for i in (0, 3, 17):
            dk, dts = jax.jit(src.device_keys_ts)(np.int64(i))
            hk, hts = src.keys_ts_host(i)
            np.testing.assert_array_equal(np.asarray(dk), hk)
            np.testing.assert_array_equal(np.asarray(dts), hts)
            tmin, tmax = src.ts_bounds(i)
            assert tmin == int(hts.min()) and tmax == int(hts.max())

    def test_host_gen_field_superset(self):
        # the materializing fallback produces the same auction/ts lanes
        cfg = _cfg()
        src = bid_stream_device(cfg)
        data, ts = src.gen("0", 2)
        hk, hts = src.keys_ts_host(2)
        np.testing.assert_array_equal(data["auction"], hk)
        np.testing.assert_array_equal(ts, hts)


class TestGoldenEquality:
    def test_q5_device_chain_matches_host_path(self):
        cfg = _cfg()
        got_dev, res_dev = _run_q5(bid_stream_device, cfg)
        got_host, res_host = _run_q5(bid_stream, cfg)
        assert got_dev == got_host
        assert len(got_dev) > 0
        # every record was accounted: the chained path counts the same
        # records_in as the materializing path
        assert (res_dev.metrics["records_in"]
                == res_host.metrics["records_in"])

    def test_q5_device_chain_covers_miss_repair(self):
        # batch 0 arrives with an EMPTY device key table: every record
        # misses, the repair loop re-synthesizes host-side, registers
        # all keys, and the stream still matches the host-path golden
        cfg = _cfg(n_batches=2)
        got_dev, _ = _run_q5(bid_stream_device, cfg)
        got_host, _ = _run_q5(bid_stream, cfg)
        assert got_dev == got_host and len(got_dev) > 0


class TestPlaneCounters:
    """JobResult.metrics says which plane a device-generator source's
    batches took — no fallback to the host hides the device."""

    def test_chained_job_counts_every_batch_through_the_chain(self):
        cfg = _cfg()
        _, res = _run_q5(bid_stream_device, cfg)
        assert res.metrics["device_chain_attached"] == 1
        assert res.metrics["device_chain_batches"] == cfg.n_batches
        assert res.metrics["device_chain_fallback_batches"] == 0
        _, host = _run_q5(bid_stream, cfg)
        assert host.metrics["device_chain_attached"] == 0
        assert host.metrics["device_chain_batches"] == 0

    def test_gate_closed_batches_are_counted_as_fallbacks(self, monkeypatch):
        from flink_tpu.ops.window import WindowOperator

        cfg = _cfg()
        golden, _ = _run_q5(bid_stream, cfg)
        real = WindowOperator.process_batch_device

        def every_other(self, batch_index):
            # a devgen gate that closes on odd batches
            return batch_index % 2 == 0 and real(self, batch_index)

        monkeypatch.setattr(WindowOperator, "process_batch_device",
                            every_other)
        got, res = _run_q5(bid_stream_device, cfg)
        assert got == golden
        assert res.metrics["device_chain_attached"] == 1
        assert res.metrics["device_chain_batches"] == cfg.n_batches // 2
        assert (res.metrics["device_chain_fallback_batches"]
                == cfg.n_batches // 2)
        assert res.metrics["batches"] >= cfg.n_batches

    def test_refused_attach_is_visible(self, monkeypatch):
        from flink_tpu.ops.window import WindowOperator

        monkeypatch.setattr(WindowOperator, "attach_device_source",
                            lambda self, spec: False)
        cfg = _cfg(n_batches=2)
        got, res = _run_q5(bid_stream_device, cfg)
        assert got == _run_q5(bid_stream, cfg)[0]
        assert res.metrics["device_chain_attached"] == 0
        assert res.metrics["device_chain_batches"] == 0
        assert res.metrics["device_chain_fallback_batches"] == 0


class TestAttachGate:
    def test_domain_larger_than_registered_prefix_refused(self):
        # a restored directory holding only an identity PREFIX of the
        # requested domain must refuse the device chain: slots beyond
        # num_keys would be device-writable yet unregistered
        from flink_tpu.api.windowing import SlidingEventTimeWindows
        from flink_tpu.ops.aggregates import count
        from flink_tpu.ops.window import WindowOperator

        src_small = bid_stream_device(_cfg())          # domain 500
        cfg_big = NexmarkConfig(
            batch_size=4096, n_batches=2, events_per_ms=4,
            num_active_auctions=1000, hot_ratio=4)
        src_big = bid_stream_device(cfg_big)           # domain 1000
        op = WindowOperator(
            SlidingEventTimeWindows.of(4_000, 1_000), count(),
            num_shards=8, slots_per_shard=256,
            max_out_of_orderness_ms=500, top_n=("count", 1))
        assert op.attach_device_source(src_small)      # registers 500
        op2 = WindowOperator(
            SlidingEventTimeWindows.of(4_000, 1_000), count(),
            num_shards=8, slots_per_shard=256,
            max_out_of_orderness_ms=500, top_n=("count", 1))
        op2.restore_state(op.snapshot_state())
        assert not op2.attach_device_source(src_big)   # prefix only
        assert op2.attach_device_source(src_small)     # exact domain ok

    def test_multi_split_device_source_refused(self):
        with pytest.raises(ValueError, match="n_splits"):
            bid_stream_device(NexmarkConfig(
                batch_size=1024, n_batches=2, n_splits=2))


class TestCheckpointRestore:
    def test_restore_continues_identically(self, tmp_path):
        cfg = _cfg(n_batches=8)
        golden, _ = _run_q5(bid_stream_device, cfg)

        ckpt = str(tmp_path / "ck")
        base = {
            "state.num-key-shards": 8, "state.slots-per-shard": 128,
            "pipeline.microbatch-size": cfg.batch_size,
            "state.checkpoints.dir": ckpt,
        }

        class Boom(Exception):
            pass

        # crash mid-stream via a poisoned sink once enough rows flowed
        # (count rows, not deliveries — the deferred drain coalesces
        # fires into arbitrarily few sink batches)
        limit = max(len(golden) // 3, 1)
        rows = []
        n_ok = [0]

        def poison(b):
            rows.append(b)
            n_ok[0] += len(b["window_end"])
            if n_ok[0] >= limit:
                raise Boom()

        env2 = StreamExecutionEnvironment(Configuration({
            **base, "execution.checkpointing.interval": "1ms"}))
        q5_hot_items(env2, bid_stream_device(cfg), FnSink(poison),
                     window_ms=4_000, slide_ms=1_000,
                     out_of_orderness_ms=500)
        with pytest.raises(Exception):
            env2.execute("q5-crash")

        # resume from the latest checkpoint; dedupe on window_end since
        # replay re-emits windows fired after the checkpoint
        rows2 = []
        env3 = StreamExecutionEnvironment(Configuration({
            **base, "execution.checkpointing.restore": "latest"}))
        q5_hot_items(env3, bid_stream_device(cfg), FnSink(rows2.append),
                     window_ms=4_000, slide_ms=1_000,
                     out_of_orderness_ms=500)
        env3.execute("q5-resume")

        merged = {}
        for we, a, c in _rows(rows) + _rows(rows2):
            merged[(we, a)] = max(merged.get((we, a), 0), c)
        want = {(we, a): c for we, a, c in golden}
        assert merged == want
