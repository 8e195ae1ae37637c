"""Cross-host data plane (tier-5): one job spanning MULTIPLE runner
processes through the per-step DCN all-to-all (exchange/dcn.py), with
checkpoint/restore. ref: SURVEY §3.6 data network stack (the
TaskManager-to-TaskManager plane) + §5.4 MiniCluster ITCases."""
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from flink_tpu.checkpoint import blobformat
from flink_tpu.exchange import frames
from flink_tpu.exchange.dcn import DcnExchange

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _hello(sender, attempt, codec=1, auth=0, secret=None):
    """A v2 wire hello (magic + sender + attempt + codec + auth flag,
    optionally MAC'd) — what a well-formed dialer sends."""
    import hmac
    import struct

    h = (b"D2" + bytes([sender]) + struct.pack(">I", attempt)
         + bytes([codec, auth]))
    if secret is not None:
        h += hmac.new(secret, h, "sha256").digest()
    return h


class TestExchange:
    def test_three_process_rendezvous(self):
        """In-process smoke of the N-way exchange: 3 endpoints in
        threads, each routes a share to each peer and all metas
        propagate."""
        import threading

        n = 3
        exs = [DcnExchange(i, n) for i in range(n)]
        peers = [f"127.0.0.1:{e.port}" for e in exs]
        results = [None] * n

        def run(i):
            exs[i].connect(peers)
            shares = {j: {"data": {"v": np.array([i * 10 + j])},
                          "ts": np.array([j])} for j in range(n)}
            payloads, metas = exs[i].exchange(shares, {"wm": 100 + i})
            results[i] = (payloads, metas)

        ths = [threading.Thread(target=run, args=(i,)) for i in range(n)]
        for t in ths:
            t.start()
        for t in ths:
            t.join(timeout=30)
        for i in range(n):
            payloads, metas = results[i]
            # process i received j*10+i from every j
            got = sorted(int(p["data"]["v"][0]) for p in payloads)
            assert got == sorted(j * 10 + i for j in range(n))
            assert sorted(m["wm"] for m in metas) == [100, 101, 102]
        for e in exs:
            e.close()


WORKER = r"""
import os, sys, json
import jax
jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, {repo!r})
import numpy as np
from flink_tpu.api.environment import StreamExecutionEnvironment
from flink_tpu.api.sources import GeneratorSource
from flink_tpu.api.windowing import SlidingEventTimeWindows
from flink_tpu.config import Configuration
from flink_tpu.connectors import FileSink
from flink_tpu.formats import CsvFormat
from flink_tpu.time.watermarks import WatermarkStrategy

pid = int(sys.argv[1]); n = int(sys.argv[2])
peers = sys.argv[3]; my_port = int(sys.argv[4])
out_path = sys.argv[5]
crash_at = int(sys.argv[6]) if len(sys.argv) > 6 else -1
restore = len(sys.argv) > 7 and sys.argv[7] == "restore"

N_BATCHES = 24
B = 512

def gen(split, i):
    if i >= N_BATCHES:
        return None
    rng = np.random.default_rng(1000 * int(split) + i)
    base = i * 1000
    keys = rng.integers(0, 64, B).astype(np.int64)
    ts = base + rng.integers(0, 1000, B).astype(np.int64)
    return ({{"auction": keys}}, ts)

# durable exactly-once sink: committed part files survive the crash
# (the in-memory sink pattern only works for in-process attempts)
sink = FileSink(out_path + f"/sink-p{{pid}}",
                CsvFormat([("key", "i64"), ("window_end", "i64"),
                           ("count", "i64")]))

conf = {{
    "state.num-key-shards": 8, "state.slots-per-shard": 32,
    "pipeline.microbatch-size": B,
    "cluster.num-processes": n, "cluster.process-id": pid,
    "cluster.dcn-peers": peers, "cluster.dcn-port": my_port,
    "execution.checkpointing.interval": 1,
    "execution.checkpointing.dir": out_path + "/ckpt",
}}
mesh = os.environ.get("FLINK_TPU_MESH_DEVICES", "")
if mesh:
    conf["cluster.mesh-devices"] = mesh
if restore:
    conf["execution.checkpointing.restore"] = "latest"
if crash_at >= 0:
    # crash injection: die after N source batches via a poisoned source
    real_gen = gen
    def gen(split, i, _g=real_gen):
        if i == crash_at:
            os._exit(43)
        return _g(split, i)

env = StreamExecutionEnvironment(Configuration(conf))
src = GeneratorSource(gen, n_splits=2)
(env.from_source(src,
                 WatermarkStrategy.for_bounded_out_of_orderness(1000))
 .key_by("auction")
 .window(SlidingEventTimeWindows.of(4000, 2000))
 .count()
 .add_sink(sink))
env.execute("dcnq5")
print("WORKER_DONE", flush=True)
"""


def _spawn(tmp, pid, n, peers, port, crash_at=-1, restore=False,
           mesh_devices=0):
    script = tmp / f"worker-{pid}.py"
    script.write_text(WORKER.format(repo=REPO))
    args = [sys.executable, str(script), str(pid), str(n), peers,
            str(port), str(tmp), str(crash_at)]
    if restore:
        args.append("restore")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    if mesh_devices:
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                            f" --xla_force_host_platform_device_count="
                            f"{mesh_devices}").strip()
        env["FLINK_TPU_MESH_DEVICES"] = str(mesh_devices)
    return subprocess.Popen(args, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, env=env)


def _golden(tmp):
    """Single-process run of the same job → expected rows."""
    import jax

    from flink_tpu.api.environment import StreamExecutionEnvironment
    from flink_tpu.api.sinks import FnSink
    from flink_tpu.api.sources import GeneratorSource
    from flink_tpu.api.windowing import SlidingEventTimeWindows
    from flink_tpu.config import Configuration
    from flink_tpu.time.watermarks import WatermarkStrategy

    N_BATCHES, B = 24, 512

    def gen(split, i):
        if i >= N_BATCHES:
            return None
        rng = np.random.default_rng(1000 * int(split) + i)
        base = i * 1000
        keys = rng.integers(0, 64, B).astype(np.int64)
        ts = base + rng.integers(0, 1000, B).astype(np.int64)
        return ({"auction": keys}, ts)

    rows = []

    def sink(b):
        if b:
            for k, w, c in zip(np.asarray(b["key"]),
                               np.asarray(b["window_end"]),
                               np.asarray(b["count"])):
                rows.append((int(k), int(w), int(c)))

    env = StreamExecutionEnvironment(Configuration({
        "state.num-key-shards": 8, "state.slots-per-shard": 32,
        "pipeline.microbatch-size": 512}))
    (env.from_source(GeneratorSource(gen, n_splits=2),
                     WatermarkStrategy.for_bounded_out_of_orderness(1000))
     .key_by("auction")
     .window(SlidingEventTimeWindows.of(4000, 2000))
     .count()
     .add_sink(FnSink(sink)))
    env.execute("golden")
    return sorted(rows)


def _free_ports(n):
    import socket

    socks = []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
    ports = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()
    return ports


def _collect(tmp, n):
    rows = []
    for pid in range(n):
        cd = tmp / f"sink-p{pid}" / "committed"
        assert cd.exists(), f"process {pid} committed nothing"
        for part in sorted(os.listdir(cd)):
            for line in (cd / part).read_text().splitlines():
                k, w, c = line.split(",")
                rows.append((int(k), int(w), int(c)))
    return sorted(rows)


class TestAttemptFencing:
    def test_stale_attempt_peer_rejected(self):
        """A process from a PREVIOUS attempt dialing a new attempt's
        listener must be fenced out at the handshake (the static
        cluster.dcn-peers mode has no coordinator rendezvous key to
        protect it — the attempt epoch in the hello is the fence)."""
        import socket as _socket
        import struct as _struct
        import threading

        n = 2
        fresh = [DcnExchange(i, n, attempt=2) for i in range(n)]
        peers = [f"127.0.0.1:{e.port}" for e in fresh]

        # stale dialer (attempt 1) connects first and must NOT occupy
        # peer slot 1
        stale = _socket.create_connection(("127.0.0.1", fresh[0].port))
        stale.sendall(_hello(1, 1))
        time.sleep(0.1)

        done = []

        def run(i):
            fresh[i].connect(peers, timeout_s=10)
            payloads, metas = fresh[i].exchange(
                {}, {"from": i, "attempt": 2})
            done.append((i, [m.get("from") for m in metas]))

        ths = [threading.Thread(target=run, args=(i,))
               for i in range(n)]
        for t in ths:
            t.start()
        for t in ths:
            t.join(timeout=20)
        assert len(done) == 2
        for i, froms in sorted(done):
            assert froms == [0, 1]  # the REAL peers, not the stale one
        # the stale connection was closed by the fence
        stale.settimeout(2)
        assert stale.recv(1) == b""
        for e in fresh:
            e.close()
        stale.close()

    def test_same_attempt_connects(self):
        import threading

        n = 2
        exs = [DcnExchange(i, n, attempt=7) for i in range(n)]
        peers = [f"127.0.0.1:{e.port}" for e in exs]
        out = []

        def run(i):
            exs[i].connect(peers, timeout_s=10)
            p, m = exs[i].exchange({}, {"pid": i})
            out.append([mm.get("pid") for mm in m])

        ths = [threading.Thread(target=run, args=(i,)) for i in range(n)]
        for t in ths:
            t.start()
        for t in ths:
            t.join(timeout=20)
        assert out == [[0, 1], [0, 1]]
        for e in exs:
            e.close()


@pytest.mark.shard_map
class TestTier5TwoProcessQ5:
    def test_two_process_q5_matches_single_process(self, tmp_path):
        """Q5-shaped job over 2 processes: the union of both processes'
        emitted rows must equal the single-process run exactly (each
        key fires on exactly one process — its shard owner)."""
        golden = _golden(tmp_path / "g")
        ports = _free_ports(2)
        peers = ",".join(f"127.0.0.1:{p}" for p in ports)
        ps = [_spawn(tmp_path, i, 2, peers, ports[i]) for i in range(2)]
        outs = [p.communicate(timeout=300)[0].decode() for p in ps]
        for i, p in enumerate(ps):
            assert p.returncode == 0, f"p{i} failed:\n{outs[i][-3000:]}"
        assert _collect(tmp_path, 2) == golden

    def test_two_process_crash_restore_exactly_once(self, tmp_path):
        """One process crashes mid-run; BOTH restart with
        restore=latest (negotiated common checkpoint id) and the final
        output union still equals the golden run exactly — the
        step-rendezvous checkpoint cut is globally consistent."""
        golden = _golden(tmp_path / "g")
        ports = _free_ports(2)
        peers = ",".join(f"127.0.0.1:{p}" for p in ports)
        # attempt 1: p1 crashes after 10 source batches; p0 dies on the
        # broken exchange
        ps = [_spawn(tmp_path, 0, 2, peers, ports[0]),
              _spawn(tmp_path, 1, 2, peers, ports[1], crash_at=10)]
        for p in ps:
            p.communicate(timeout=300)
        assert ps[1].returncode == 43
        assert ps[0].returncode != 0
        # attempt 2: fresh ports, negotiated restore
        ports2 = _free_ports(2)
        peers2 = ",".join(f"127.0.0.1:{p}" for p in ports2)
        ps = [_spawn(tmp_path, i, 2, peers2, ports2[i], restore=True)
              for i in range(2)]
        outs = [p.communicate(timeout=300)[0].decode() for p in ps]
        for i, p in enumerate(ps):
            assert p.returncode == 0, f"p{i} failed:\n{outs[i][-3000:]}"
        assert _collect(tmp_path, 2) == golden


    def test_two_process_local_mesh_q5(self, tmp_path):
        """The full tier-5 shape: 2 runner processes x 4 virtual
        devices each — records cross PROCESSES via the DCN exchange and
        cross each process's local DEVICES via the in-step keyBy
        all_to_all; output still equals the single-process run."""
        golden = _golden(tmp_path / "g")
        ports = _free_ports(2)
        peers = ",".join(f"127.0.0.1:{p}" for p in ports)
        ps = [_spawn(tmp_path, i, 2, peers, ports[i], mesh_devices=4)
              for i in range(2)]
        outs = [p.communicate(timeout=600)[0].decode() for p in ps]
        for i, p in enumerate(ps):
            assert p.returncode == 0, f"p{i} failed:\n{outs[i][-3000:]}"
        assert _collect(tmp_path, 2) == golden


class TestDcnOverlap:
    """Cross-host contract of cluster.dcn-overlap on/off (the barrier
    moves, the consensus does not)."""

    N_BATCHES = 8
    B = 64

    def _gen(self):
        n_batches, b = self.N_BATCHES, self.B

        def gen(split, i):
            if i >= n_batches:
                return None
            rng = np.random.default_rng(500 * int(split) + i)
            keys = rng.integers(0, 32, b).astype(np.int64)
            ts = i * 1000 + rng.integers(0, 1000, b).astype(np.int64)
            return {"k": keys}, ts
        return gen

    def _golden(self):
        from flink_tpu.api.environment import StreamExecutionEnvironment
        from flink_tpu.api.sinks import FnSink
        from flink_tpu.api.sources import GeneratorSource
        from flink_tpu.api.windowing import TumblingEventTimeWindows
        from flink_tpu.config import Configuration
        from flink_tpu.time.watermarks import WatermarkStrategy

        rows = []
        env = StreamExecutionEnvironment(Configuration({
            "state.num-key-shards": 8, "state.slots-per-shard": 64,
            "pipeline.microbatch-size": self.B}))
        (env.from_source(GeneratorSource(self._gen(), n_splits=2),
                         WatermarkStrategy.for_bounded_out_of_orderness(
                             1000))
         .key_by("k")
         .window(TumblingEventTimeWindows.of(1000))
         .count()
         .add_sink(FnSink(lambda b: rows.extend(
             zip(np.asarray(b["key"]).tolist(),
                 np.asarray(b["window_end"]).tolist(),
                 np.asarray(b["count"]).tolist())) if b else None)))
        env.execute("golden")
        return sorted(rows)

    def _two_proc(self, extra_conf):
        import threading

        from flink_tpu.api.environment import StreamExecutionEnvironment
        from flink_tpu.api.sinks import FnSink
        from flink_tpu.api.sources import GeneratorSource
        from flink_tpu.api.windowing import TumblingEventTimeWindows
        from flink_tpu.config import Configuration
        from flink_tpu.time.watermarks import WatermarkStrategy

        ports = _free_ports(2)
        peers = ",".join(f"127.0.0.1:{p}" for p in ports)
        per_pid = [[], []]
        errs = [None, None]

        def run(pid):
            rows = per_pid[pid]
            conf = {
                "state.num-key-shards": 8, "state.slots-per-shard": 64,
                "pipeline.microbatch-size": self.B,
                "cluster.num-processes": 2, "cluster.process-id": pid,
                "cluster.dcn-peers": peers,
                "cluster.dcn-port": ports[pid],
            }
            conf.update(extra_conf)
            env = StreamExecutionEnvironment(Configuration(conf))
            (env.from_source(GeneratorSource(self._gen(), n_splits=2),
                             WatermarkStrategy
                             .for_bounded_out_of_orderness(1000))
             .key_by("k")
             .window(TumblingEventTimeWindows.of(1000))
             .count()
             .add_sink(FnSink(lambda b: rows.extend(
                 zip(np.asarray(b["key"]).tolist(),
                     np.asarray(b["window_end"]).tolist(),
                     np.asarray(b["count"]).tolist())) if b else None)))
            try:
                env.execute(f"overlap-p{pid}")
            except BaseException as e:  # surfaced by the caller
                errs[pid] = e

        ths = [threading.Thread(target=run, args=(i,)) for i in range(2)]
        for t in ths:
            t.start()
        for t in ths:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in ths), "2-proc run hung"
        for pid, e in enumerate(errs):
            assert e is None, f"p{pid} failed: {e!r}"
        return [sorted(r) for r in per_pid]

    def test_overlap_without_drain_completes_and_matches(self, tmp_path):
        """The analyzer-warned loss mode (overlap on, barrier drain
        off) under checkpointing, with NO faults: nothing is in flight
        at end-of-input, so output still matches — and the undrained
        step's STALE ckpt flag is absorbed exactly once (it rode
        behind the snapshot), so the fleet stays in lockstep instead
        of double-checkpointing every interval."""
        rows = self._two_proc({
            "cluster.dcn-overlap-drain": False,
            "execution.checkpointing.interval": 25,
            "execution.checkpointing.dir": str(tmp_path / "ckpt")})
        assert sorted(rows[0] + rows[1]) == self._golden()

    def test_overlap_off_matches_overlap_on(self):
        """cluster.dcn-overlap moves the barrier, not the semantics:
        lockstep (off) and overlapped (on, the default) runs emit
        identical rows per process."""
        on = self._two_proc({})
        off = self._two_proc({"cluster.dcn-overlap": False})
        assert on == off
        assert sorted(on[0] + on[1]) == self._golden()


class TestExchangeSecurity:
    """ADVICE r5 medium: the exchange port was an unauthenticated RCE
    surface on cross-host (0.0.0.0) deployments — frames decode through
    blobformat, whose __pickle__ escape deserializes attacker pickle.
    Closed two independent ways: an HMAC-over-hello shared secret
    admission check, and a frame decoder that rejects the pickle escape
    outright."""

    def test_unauthenticated_hello_rejected(self):
        """A dialer that knows the wire format but not the secret must
        be dropped at the handshake, while the real (keyed) peers still
        form the mesh."""
        import socket as _socket
        import struct as _struct
        import threading

        n = 2
        exs = [DcnExchange(i, n, attempt=1, secret="job-secret")
               for i in range(n)]
        peers = [f"127.0.0.1:{e.port}" for e in exs]

        # attacker: well-formed keyed hello, garbage MAC
        bad = _socket.create_connection(("127.0.0.1", exs[0].port))
        bad.sendall(_hello(1, 1, auth=1) + b"\x00" * 32)
        time.sleep(0.1)

        out = []

        def run(i):
            exs[i].connect(peers, timeout_s=10)
            p, m = exs[i].exchange({}, {"pid": i})
            out.append([mm.get("pid") for mm in m])

        ths = [threading.Thread(target=run, args=(i,)) for i in range(n)]
        for t in ths:
            t.start()
        for t in ths:
            t.join(timeout=20)
        assert out == [[0, 1], [0, 1]]  # real peers, not the attacker
        bad.settimeout(2)
        assert bad.recv(1) == b"", "unauthenticated hello not dropped"
        bad.close()
        for e in exs:
            e.close()

    def test_secretless_hello_against_keyed_listener_rejected(self):
        """A peer declaring no auth (flag 0) to a keyed listener must
        not be admitted — closed at the handshake, before any frame
        bytes are interpreted."""
        import socket as _socket
        import struct as _struct

        ex = DcnExchange(0, 2, attempt=1, secret="job-secret")
        legacy = _socket.create_connection(("127.0.0.1", ex.port))
        legacy.sendall(_hello(1, 1))
        raw = blobformat.encode({"data": None, "meta": {}})
        legacy.sendall(_struct.pack(">Q", len(raw)) + raw)
        legacy.settimeout(2)
        try:
            got = legacy.recv(1)
        except ConnectionResetError:
            got = b""  # hard reset is rejection too
        assert got == b"", "secretless hello not dropped"
        assert 1 not in ex._in
        legacy.close()
        ex.close()

    def test_keyed_hello_against_unkeyed_listener_rejected(self):
        """The asymmetric rollout in the other direction: a keyed
        dialer hitting an UNKEYED listener is closed cleanly at the
        handshake — its 32 MAC bytes are drained, never parsed as a
        frame length (which would hang or try a huge allocation)."""
        import hmac as _hmac2
        import socket as _socket
        import struct as _struct

        ex = DcnExchange(0, 2, attempt=1)  # no secret
        keyed = _socket.create_connection(("127.0.0.1", ex.port))
        keyed.sendall(_hello(1, 1, auth=1, secret=b"other-secret"))
        keyed.settimeout(2)
        try:
            got = keyed.recv(1)
        except ConnectionResetError:
            got = b""
        assert got == b"", "keyed hello not rejected by unkeyed listener"
        assert 1 not in ex._in
        keyed.close()
        ex.close()

    def test_pickle_escape_frame_rejected(self):
        """A legacy frame smuggling a __pickle__ escape must fail the
        decode loudly instead of deserializing attacker-controlled
        pickle (the legacy codec survives as the benchmark baseline —
        it keeps the rejection)."""
        import socket as _socket
        import struct as _struct

        # an object-dtype array routes through the __pickle__ escape —
        # the exact in-band vector an attacker's crafted frame uses
        evil = np.array([{"x": 1}], dtype=object)
        raw = blobformat.encode({"data": evil, "meta": {}})
        assert b"__pickle__" in raw  # the attack vector exists in-band

        ex = DcnExchange(0, 2, attempt=1, codec="legacy")
        s = _socket.create_connection(("127.0.0.1", ex.port))
        s.sendall(_hello(1, 1, codec=0))  # valid unkeyed legacy hello
        deadline = time.time() + 5
        while 1 not in ex._in and time.time() < deadline:
            time.sleep(0.02)
        assert 1 in ex._in
        s.sendall(_struct.pack(">Q", len(raw)) + raw)
        with pytest.raises(ValueError, match="__pickle__ escape rejected"):
            ex.exchange({}, {})
        s.close()
        ex.close()

    def test_binary_frame_has_no_pickle_vector(self):
        """The binary wire rejects foreign objects AT ENCODE — there is
        no pickle escape for a hostile frame to smuggle through, and a
        corrupt frame fails the CRC, not the keyspace."""
        evil = np.array([{"x": 1}], dtype=object)
        with pytest.raises(frames.FrameError, match="no pickle escape"):
            frames.encode_bytes(0, 0, {}, {"data": evil})

    def test_corrupt_binary_frame_fails_loudly_at_the_barrier(self):
        """Garbage after a valid binary hello must surface as a loud
        FrameError at the exchange barrier — never a silent partial
        decode into operator state."""
        import socket as _socket

        ex = DcnExchange(0, 2, attempt=1)
        s = _socket.create_connection(("127.0.0.1", ex.port))
        s.sendall(_hello(1, 1))
        deadline = time.time() + 5
        while 1 not in ex._in and time.time() < deadline:
            time.sleep(0.02)
        assert 1 in ex._in
        ex._start_io()  # the mesh is "up" for this half-duplex probe
        s.sendall(b"\x00" * frames.HEADER_LEN)
        with pytest.raises(frames.FrameError, match="magic"):
            ex.exchange_async({}, {"wm": 0}).result()
        s.close()
        ex.close()

    def test_legacy_v0_hello_rejected_at_handshake(self):
        """A pre-binary-wire peer (the v0 6-byte hello: no magic) must
        be fenced out AT THE HELLO with a recorded reason — a
        mixed-version fleet fails at admission, never by misparsing a
        foreign frame mid-stream."""
        import socket as _socket
        import struct as _struct

        ex = DcnExchange(0, 2, attempt=1)
        old = _socket.create_connection(("127.0.0.1", ex.port))
        # the exact v0 hello wire shape + enough follow-on bytes that
        # the 9-byte v2 read never blocks on a short hello
        old.sendall(bytes([1]) + _struct.pack(">I", 1) + b"\x00"
                    + b"\x00" * 8)
        old.settimeout(5)
        try:
            got = old.recv(1)
        except (ConnectionResetError, _socket.timeout):
            got = b""
        assert got == b"", "v0 hello not dropped"
        assert 1 not in ex._in
        assert any("wire version" in r for r in ex.hello_rejects), (
            ex.hello_rejects)
        old.close()
        ex.close()

    def test_codec_mismatch_rejected_at_handshake(self):
        """A peer pinned to the LEGACY codec dialing a binary listener
        (or vice versa) is rejected at the hello — a frame-format split
        brain would otherwise corrupt mid-stream."""
        import socket as _socket

        ex = DcnExchange(0, 2, attempt=1)  # binary listener
        peer = _socket.create_connection(("127.0.0.1", ex.port))
        peer.sendall(_hello(1, 1, codec=0))  # legacy dialer
        peer.settimeout(5)
        try:
            got = peer.recv(1)
        except (ConnectionResetError, _socket.timeout):
            got = b""
        assert got == b"", "codec-mismatched hello not dropped"
        assert 1 not in ex._in
        assert any("codec mismatch" in r for r in ex.hello_rejects), (
            ex.hello_rejects)
        peer.close()
        ex.close()

    def test_mixed_codec_fleet_fails_loudly_at_connect(self):
        """Fleet-level interop: one binary and one legacy process can
        never form a mesh — connect() times out with the listener's
        reject recorded, instead of the fleet limping into mid-frame
        garbage."""
        import threading

        a = DcnExchange(0, 2, attempt=1, codec="binary")
        b = DcnExchange(1, 2, attempt=1, codec="legacy")
        peers = [f"127.0.0.1:{a.port}", f"127.0.0.1:{b.port}"]
        errs = {}

        def run(ex, i):
            try:
                ex.connect(peers, timeout_s=3)
            except TimeoutError as e:
                errs[i] = e

        ths = [threading.Thread(target=run, args=(ex, i))
               for i, ex in enumerate((a, b))]
        for t in ths:
            t.start()
        for t in ths:
            t.join(timeout=20)
        assert set(errs) == {0, 1}, "mixed fleet formed a mesh"
        assert any("codec mismatch" in r for r in a.hello_rejects)
        assert any("codec mismatch" in r for r in b.hello_rejects)
        a.close()
        b.close()

    def test_numeric_frames_unaffected_by_pickle_rejection(self):
        """The production payload shape (numeric arrays + scalar meta)
        round-trips identically under allow_pickle=False."""
        payload = {"data": {"k": np.arange(5, dtype=np.int64),
                            "v": np.linspace(0, 1, 5)},
                   "meta": {"wm": 123, "done": False, "persisted": -1}}
        raw = blobformat.encode(payload)
        got = blobformat.decode(raw, allow_pickle=False)
        assert got["meta"] == payload["meta"]
        assert (got["data"]["k"] == payload["data"]["k"]).all()
        assert (got["data"]["v"] == payload["data"]["v"]).all()

    def test_string_columns_cross_without_pickle(self):
        """Text columns (object-dtype string arrays, the socket/file
        source shape) encode via the native __strs__ tag — no pickle
        escape — so they survive the exchange's allow_pickle=False."""
        payload = {"data": {"line": np.array(["a", "bb", "ccc"],
                                             dtype=object),
                            "k": np.arange(3, dtype=np.int64)},
                   "meta": {"wm": 7}}
        raw = blobformat.encode(payload)
        assert b"__pickle__" not in raw
        got = blobformat.decode(raw, allow_pickle=False)
        assert list(got["data"]["line"]) == ["a", "bb", "ccc"]
        assert got["data"]["line"].dtype == object
        assert (got["data"]["k"] == payload["data"]["k"]).all()
