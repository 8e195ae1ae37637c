"""The readers of the program's own spans and fire records
(``readers/trace_host.py``, ``idle_unnamed.py``, ``fire_stages.py``), on a
trace built by hand (``xspace.py``) whose numbers can be worked out on
paper, and on the recorded file beside this test: 0.3 s of the traced
span of a ``q5_hostfed_replay`` run on a v5e WITH the spans (PR 24), cut
by ``tools/cut_trace.py``."""
import json
import os

import pytest

from benchmark import run
from benchmark import trace_reduce as tr
from xspace import xspace

HERE = os.path.dirname(os.path.abspath(__file__))
WITH_SPANS = os.path.join(HERE, "chip_q5_hostfed_replay_spans.xplane.pb")
WITHOUT_SPANS = os.path.join(HERE, "chip_q5_hostfed_replay.xplane.pb")

# times in ns. The device runs one step per batch (200..220, 600..620).
# The loop thread's phases tile 0..800 with JAX's own events nested inside
# them; the drain thread fetches beside the loop's key scan. Two batches.
PLANES = [
    ("/device:TPU:0", [
        ("XLA Modules", [("jit_step(7)", 200, 20), ("jit_step(7)", 600, 20)]),
        ("XLA Ops", [("fusion.1", 200, 20), ("fusion.1", 600, 20)]),
    ]),
    ("/host:CPU", [
        ("loop", [
            ("ingest.source_wait", 0, 10), ("ingest.route", 10, 20),
            ("window.key_scan", 30, 130), ("window.pack", 160, 10),
            ("window.h2d", 170, 15), ("shard_args", 172, 10),
            ("window.step_dispatch", 185, 15),
            ("PjitFunction(step)", 186, 12),
            ("ingest.bookkeeping", 200, 10), ("wm.advance", 210, 5),
            ("window.fire_dispatch", 215, 5), ("ingest.source_wait", 220, 190),
            ("ingest.route", 410, 20), ("window.key_scan", 430, 130),
            ("window.pack", 560, 10), ("window.h2d", 570, 15),
            ("window.step_dispatch", 585, 15), ("ingest.bookkeeping", 600, 10),
            # 620..780: nothing the host did has a name
            ("ingest.source_wait", 780, 20), ("$python_noise", 0, 800),
        ]),
        ("drain", [("drain.fetch", 100, 30), ("drain.deliver", 130, 5)]),
    ]),
]


def ctx_of(planes, **over):
    from jax.profiler import ProfileData

    trace = tr.reduce_profile(ProfileData.from_serialized_xspace(
        xspace(planes)))
    return {"trace": trace, "trace_batches": 2, "job_metrics": {}, **over}


def reader(name):
    return run.load_module("readers", name).read


def test_trace_host_sums_the_matching_spans_over_all_threads():
    ctx = ctx_of(PLANES)
    read = reader("trace_host")
    assert read(ctx, match=r"^window\.key_scan$") == pytest.approx(260e-9)
    assert read(ctx, match=r"^window\.key_scan$", per="batches",
                scale=1000.0) == pytest.approx(130e-6)
    # several leaves in one metric; JAX's own nested events are not in it
    assert read(ctx, match=r"^window\.(pack|h2d)$", per="batches",
                scale=1e9) == pytest.approx(25.0)
    assert read(ctx, match=r"^ingest\.(route|bookkeeping)$",
                scale=1e9) == pytest.approx(60.0)
    # the drain's spans are beside the loop's, on a thread of their own
    assert read(ctx, match=r"^drain\.", scale=1e9) == pytest.approx(35.0)


def test_trace_host_finds_nothing_without_spans_batches_or_a_device():
    read = reader("trace_host")
    assert read(ctx_of(PLANES), match=r"^no\.such\.span$") is None
    assert read(ctx_of(PLANES, trace_batches=0), match=r"^window\.",
                per="batches") is None
    assert read({"trace": None, "trace_batches": 2}, match=".") is None
    # a CPU run's trace has a host plane and no device plane: no chip time
    assert read(ctx_of(PLANES[1:]), match=r"^window\.key_scan$") is None


def test_gaps_take_the_names_of_the_programs_phases_not_of_what_they_hold():
    trace = ctx_of(PLANES)["trace"]
    got = dict(trace.labelled_gaps(trace.devices[0]))
    # 0..200: the key scan covers 130 of it (the drain's fetch 30, JAX's
    # own events 10 and 12); 220..600: waiting for the source covers 190,
    # the second key scan 130; 620..800: the last 20 are too little
    assert got == {"window.key_scan": pytest.approx(200e-9),
                   "ingest.source_wait": pytest.approx(380e-9),
                   "host.untraced": pytest.approx(180e-9)}


def running_on(planes, until):
    """The same trace with the device still recorded until ``until`` (a
    last op of 10 ns): the profiler stops its host tracer first."""
    name, (modules, ops) = planes[0]
    dev = (name, [
        (modules[0], modules[1] + [("jit_tail(9)", until - 10, 10)]),
        (ops[0], ops[1] + [("copy.9", until - 10, 10)])])
    return [dev] + planes[1:]


def test_per_batch_counts_the_batches_the_host_tracer_saw():
    read = reader("trace_host")
    # the host tracer recorded 800 of 1000 ns: 1.6 of the 2 batches
    ctx = ctx_of(running_on(PLANES, 1000))
    assert ctx["trace"].window == (0.0, 1000.0)
    assert read(ctx, match=r"^window\.key_scan$", per="batches",
                scale=1e9) == pytest.approx(260 / 1.6)
    # ... and what the device did after it stopped is nobody's to name
    assert dict(ctx["trace"].labelled_gaps(ctx["trace"].devices[0]))[
        "host.untraced"] == pytest.approx(370e-9)
    assert reader("idle_unnamed")(ctx) == pytest.approx(100.0 * 180 / 760)


def test_idle_unnamed_is_the_share_of_idle_time_no_host_event_names():
    read = reader("idle_unnamed")
    assert read(ctx_of(PLANES)) == pytest.approx(100.0 * 180 / 760)
    # the same trace with the program's spans taken out: JAX's own two
    # events cover too little of any gap to name it
    bare = [PLANES[0], ("/host:CPU", [("loop", [
        ("PjitFunction(f)", 0, 5), ("shard_args", 172, 10),
        ("PjitFunction(step)", 186, 12), ("PjitFunction(f)", 795, 5)])])]
    assert read(ctx_of(bare)) == pytest.approx(100.0)
    assert read({"trace": None}) is None
    assert read(ctx_of(PLANES[1:])) is None     # no device plane
    assert read(ctx_of(PLANES[:1])) is None     # no host plane


FIRES = [
    {"op": 1, "window_end": 2000, "t_input": 10.000, "t_fire": 10.018,
     "t_fetch0": 10.029, "t_fetch1": 10.0295, "t_sink": 10.030},
    {"op": 1, "window_end": 4000, "t_input": 12.000, "t_fire": 12.017,
     "t_fetch0": 12.027, "t_fetch1": 12.0275, "t_sink": 12.029},
    {"op": 1, "window_end": 6000, "t_input": 14.000, "t_fire": 14.020,
     "t_fetch0": 14.032, "t_fetch1": 14.0325, "t_sink": 14.033},
    # a fire whose rows were never fetched (the run was cut): left out of
    # the stages it did not reach
    {"op": 1, "window_end": 8000, "t_input": 16.000, "t_fire": 16.500,
     "t_fetch0": None, "t_fetch1": None, "t_sink": None},
]


def test_fire_stages_is_the_median_difference_of_two_stamps_in_ms():
    read = reader("fire_stages")
    ctx = {"job_metrics": {"trace.fires": FIRES}}
    # 18, 17, 20 and 500 ms: the median of four lies between 18 and 20
    assert read(ctx, start="t_input", end="t_fire") == pytest.approx(19.0)
    assert read(ctx, start="t_fire", end="t_fetch0") == pytest.approx(11.0)
    assert read(ctx, start="t_fetch0", end="t_sink") == pytest.approx(1.0)
    # the records survive the result line's JSON as they are
    again = {"job_metrics": {"trace.fires": json.loads(json.dumps(FIRES))}}
    assert read(again, start="t_fire", end="t_sink") == pytest.approx(12.0)


def test_fire_stages_finds_nothing_in_a_program_without_fire_records():
    read = reader("fire_stages")
    assert read({"job_metrics": {}}, start="t_fire", end="t_sink") is None
    assert read({"job_metrics": {"trace.fires": []}}, start="t_fire",
                end="t_sink") is None
    assert read({"job_metrics": {"trace.fires": FIRES[3:]}},
                start="t_fire", end="t_sink") is None


def test_every_new_metric_names_a_reader_that_is_there():
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    for m in bench["per_layer"]:
        spec = run.load_json(run.HERE, "layer_metrics", m["name"] + ".json")
        assert callable(reader(spec["reader"])), m["name"]


def ctx_of_file(path):
    trace = tr.reduce_file(path)
    # one step program a batch (and, in PR 24's, two one-element programs)
    steps, _ = trace.busiest().seconds(tr.MODULES_LINE,
                                       "apply_preagg|fused_step")
    return {"trace": trace, "trace_batches": steps, "job_metrics": {}}



def test_the_recorded_chip_trace_names_its_idle_time():
    """0.3 s of a v5e run of the program with its spans: 16 batches, each
    a native key scan of ~15 ms beside ~0.14 ms of device time."""
    ctx = ctx_of_file(WITH_SPANS)
    trace = ctx["trace"]
    assert ctx["trace_batches"] == 16
    read = reader("trace_host")
    hostkey = read(ctx, match=r"^window\.key_scan$", per="batches",
                   scale=1000.0)
    assert 12.0 < hostkey < 18.0
    parts = {name: read(ctx, match=rx, per="batches", scale=1000.0)
             for name, rx in (
                 ("route", r"^ingest\.(route|bookkeeping)$"),
                 ("h2d", r"^window\.(pack|h2d)$"),
                 ("enqueue", r"^window\.(step|fire)_dispatch$"),
                 ("rest", r"^(ingest\.(link_wait|throttle|source_wait)"
                          r"|wm\.advance)$"))}
    assert all(0.0 < v < 3.0 for v in parts.values()), parts
    # the loop thread's phases tile its time: they add up to the cut
    assert hostkey + sum(parts.values()) == pytest.approx(
        1e3 * trace.window_s / 16, rel=0.1)
    gaps = trace.labelled_gaps(trace.busiest())
    assert gaps[0][0] == "window.key_scan" and gaps[0][1] > 0.9 * sum(
        s for _n, s in gaps)
    assert reader("idle_unnamed")(ctx) < 5.0
    # the drain thread's spans are there too
    assert read(ctx, match=r"^drain\.deliver$") > 0


def test_the_recorded_chip_trace_without_spans_names_none_of_it():
    ctx = ctx_of_file(WITHOUT_SPANS)     # PR 23's: the program had none
    assert reader("trace_host")(ctx, match=r"^window\.key_scan$") is None
    assert reader("trace_host")(ctx, match=r"^(ingest|window|wm|drain)\."
                                ) is None
    assert reader("idle_unnamed")(ctx) > 80.0
