"""The reduction from a profiler trace to busy/idle, time per program and
labelled gaps, on a trace built by hand (``xspace.py``) so every number
below can be worked out on paper, and on the recorded file beside this
test: the first 0.3 s of the traced span of a ``q5_hostfed_replay`` run on a
v5e (PR 23), its device plane's ``XLA Modules`` and ``XLA Ops`` lines whole
and the host events of 0.2 ms and more, re-encoded with ``xspace.py``."""
import glob
import os

import pytest

from benchmark import trace_reduce as tr
from xspace import xspace

HERE = os.path.dirname(os.path.abspath(__file__))

# times in ns. Device 0: two step programs (100..150, 300..350) whose ops
# overlap inside them; device 1 is busier. The host dispatches in 0..90,
# re-lays a buffer out in 160..280 and copies rows back in 360..400.
PLANES = [
    ("/device:TPU:0", [
        ("XLA Modules", [("jit_step(11)", 100, 50), ("jit_step(11)", 300, 50),
                         ("jit_drain(12)", 352, 8)]),
        ("XLA Ops", [("fusion.1", 100, 20), ("sort.2", 110, 40),
                     ("fusion.1", 300, 50), ("copy.3", 352, 8)]),
        ("Steps", [("0", 0, 400)]),
    ]),
    ("/device:TPU:1", [
        ("XLA Modules", [("jit_step(11)", 50, 300)]),
        ("XLA Ops", [("fusion.1", 50, 300), ("all-to-all.15", 60, 10)]),
    ]),
    ("/host:CPU", [
        ("main", [("PjitFunction(step)", 0, 90), ("$python_noise", 0, 400)]),
        ("drain", [("ArrayImpl.copy_to_host_async", 360, 40),
                   ("PjitFunction(f)", 155, 10), ("XlaLinearize", 160, 120)]),
    ]),
    ("/host:metadata", []),
]


@pytest.fixture(scope="module")
def trace():
    from jax.profiler import ProfileData

    return tr.reduce_profile(ProfileData.from_serialized_xspace(
        xspace(PLANES)))


def test_merged_intervals_and_gaps():
    assert tr.merged([(5, 7), (1, 3), (2, 4), (7, 8)]) == [(1, 4), (5, 8)]
    assert tr.gaps([(1, 4), (5, 8)], (0, 10)) == [(0, 1), (4, 5), (8, 10)]
    assert tr.gaps([], (2, 5)) == [(2, 5)]
    assert tr.gaps([(0, 10)], (2, 5)) == []


def test_window_is_the_span_of_all_planes(trace):
    assert trace.window == (0.0, 400.0)
    assert trace.window_s == pytest.approx(400e-9)
    assert [d.name for d in trace.devices] == ["/device:TPU:0",
                                               "/device:TPU:1"]


def test_busy_is_the_union_of_op_intervals_not_their_sum(trace):
    d0, d1 = trace.devices
    # ops 100..120 and 110..150 overlap: 50, not 60; + 50 + 8
    assert d0.busy == [(100.0, 150.0), (300.0, 350.0), (352.0, 360.0)]
    assert d0.busy_s == pytest.approx(108e-9)
    assert d1.busy_s == pytest.approx(300e-9)        # the collective is inside
    assert trace.busiest() is d1
    assert trace.mean_busy_s() == pytest.approx(204e-9)
    assert trace.idle_share(d0) == pytest.approx(1 - 108 / 400)
    assert trace.idle_share(d1) == pytest.approx(0.25)


def test_time_per_program_and_per_op(trace):
    d0, d1 = trace.devices
    assert d0.module_totals == {"jit_step": (2, pytest.approx(100e-9)),
                                "jit_drain": (1, pytest.approx(8e-9))}
    assert d0.seconds(tr.MODULES_LINE, "step") == (2, pytest.approx(100e-9))
    assert d0.seconds(tr.MODULES_LINE, ".") == (3, pytest.approx(108e-9))
    assert d0.seconds(tr.OPS_LINE, "^fusion") == (2, pytest.approx(70e-9))
    assert d1.seconds(tr.OPS_LINE, "all[-_]to[-_]all") == (
        1, pytest.approx(10e-9))
    assert d0.seconds(tr.OPS_LINE, "all[-_]to[-_]all") == (0, 0)


def test_idle_gaps_take_the_name_of_what_the_host_was_doing(trace):
    got = dict(trace.labelled_gaps(trace.devices[0]))
    # gaps on device 0: 0..100 (a dispatch covers 90 of it), 150..300
    # (XlaLinearize covers 120; PjitFunction(f)'s 10 would not reach a
    # fifth), 350..352 (nothing), 360..400 (the copy to the host)
    assert got == {"PjitFunction": pytest.approx(100e-9),
                   "XlaLinearize": pytest.approx(150e-9),
                   "host.untraced": pytest.approx(2e-9),
                   "ArrayImpl.copy_to_host_async": pytest.approx(40e-9)}
    # profiler noise ('$...') never labels a gap
    assert not any(k.startswith("$") for k in got)


def test_breakdown_lists_top_ops_and_gaps_of_the_busiest_device(trace):
    b = trace.breakdown()
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(300e-9)]
    assert b["idle_gaps"] and len(b["device_ops"]) <= 10


def test_a_trace_without_a_device_plane_reduces_to_nothing_to_read():
    from jax.profiler import ProfileData

    t = tr.reduce_profile(ProfileData.from_serialized_xspace(
        xspace([("/host:CPU", [("main", [("PjitFunction(f)", 0, 10)])])])))
    assert t.devices == [] and t.busiest() is None
    assert t.mean_busy_s() == 0.0
    assert t.breakdown() == {"device_ops": [], "idle_gaps": []}


RECORDED = sorted(glob.glob(os.path.join(HERE, "*.xplane.pb")))


def test_recorded_chip_trace_reduces():
    t = tr.reduce_file(RECORDED[0])
    assert [d.name for d in t.devices] == ["/device:TPU:0"]
    dev = t.busiest()
    # the host-fed Q5 job runs one program per batch, ~2.3 ms each, and
    # leaves the chip idle for nine tenths of the span
    assert set(dev.module_totals) == {"jit_fused_step_kernel"}
    steps, secs = dev.seconds(tr.MODULES_LINE, "fused_step")
    assert steps >= 10 and 1.5e-3 < secs / steps < 3.5e-3
    assert 0 < dev.busy_s <= t.window_s and 0.8 < t.idle_share(dev) < 0.95
    assert dev.seconds(tr.OPS_LINE, "^sort")[1] > 0
    assert t.breakdown()["device_ops"][0][0] == "conditional.1"
    assert sum(s for _n, s in t.labelled_gaps(t.busiest())) == pytest.approx(
        t.window_s - t.busiest().busy_s)
