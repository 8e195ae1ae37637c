"""Micro-benchmark suite smoke: every metric runs at toy size and
emits a parseable line (tier-7 analogue, SURVEY §5)."""
import json

import pytest

import bench_micro


def test_bench_dcn_codec_axis_and_artifact(tmp_path):
    """The DCN micro-bench covers BOTH wire codecs and records the
    binary/legacy speedup as a machine-readable artifact line (ISSUE 12
    satellite: the >=5x claim is a recorded number, not a log grep)."""
    import json as _json

    art = tmp_path / "dcn.json"
    rows = bench_micro.bench_dcn(payloads=(0, 4096), procs=(2,),
                                 iters=2, artifact=str(art))
    metrics = {(r["metric"], r.get("codec")) for r in rows}
    for codec in ("legacy", "binary"):
        assert ("dcn_exchange_step_ms", codec) in metrics
        assert ("dcn_exchange_bytes_per_sec", codec) in metrics
    sp = [r for r in rows if r["metric"] == "dcn_codec_speedup"]
    assert sp and all(r["value"] > 0 for r in sp)
    persisted = _json.loads(art.read_text())
    assert persisted["lines"] == rows


def test_bench_dcn_q5_scaling_line_is_always_emitted(tmp_path):
    """dcn_q5_scaling either measures (enough cores) or SKIPs with the
    named hardware constraint — never silently absent (the ROADMAP
    item 2 acceptance line)."""
    import json as _json

    art = tmp_path / "q5.json"
    rows = bench_micro.bench_dcn_q5(n_batches=2, batch=512,
                                    artifact=str(art))
    (line,) = [r for r in rows if r["metric"] == "dcn_q5_scaling"]
    assert ("skipped" in line and "insufficient-cores" in line["skipped"]
            ) or "target_met" in line
    assert _json.loads(art.read_text())["lines"] == rows


def test_bench_columnar_axis_and_artifact(tmp_path):
    """The columnar codec axis (ISSUE 13 satellite) covers encode +
    decode across CRC impl x decode mode and records the
    zero-copy+native vs copy+zlib speedup with a target line at the
    1MB point — a recorded number, not a log grep."""
    import json as _json

    art = tmp_path / "columnar.json"
    rows = bench_micro.bench_columnar(sizes=(1 << 16, 1 << 20),
                                      artifact=str(art))
    metrics = {(r["metric"], r.get("crc"), r.get("mode"))
               for r in rows}
    for crc in ("zlib", "native"):
        if ("columnar_codec_skipped", None, None) in metrics \
                and crc == "native":
            continue  # honest constraint line instead (no compiler)
        assert ("columnar_encode_bytes_per_sec", crc, None) in metrics
        for mode in ("copy", "zero_copy"):
            assert ("columnar_decode_bytes_per_sec", crc,
                    mode) in metrics
    sp = [r for r in rows if r["metric"] == "columnar_decode_speedup"]
    if sp:  # present whenever the native cells ran
        assert all(r["value"] > 0 for r in sp)
        at_1mb = [r for r in sp if "target_met" in r]
        assert len(at_1mb) == 1, "exactly one target line (1MB)"
    persisted = _json.loads(art.read_text())
    assert persisted["lines"] == rows


@pytest.mark.shard_map
def test_all_micro_benchmarks_emit(capsys):
    bench_micro.bench_state_update(batch=1 << 12, iters=2)
    bench_micro.bench_all_to_all(iters=2)
    bench_micro.bench_codec(mb=1)
    bench_micro.bench_fire_flush(iters=2)
    bench_micro.bench_checkpoint()
    lines = [json.loads(ln) for ln in
             capsys.readouterr().out.strip().splitlines()]
    metrics = {ln["metric"] for ln in lines}
    assert {"state_update_ops_per_sec", "keyby_exchange_gbps",
            "ingest_codec_mb_per_sec", "window_fire_flush_ms",
            "checkpoint_bytes_per_sec",
            "checkpoint_resume_ms"} <= metrics
    for ln in lines:
        assert "value" in ln and "unit" in ln
