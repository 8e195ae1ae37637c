"""Which programs a host-fed Q5 batch and its fire run through, pinned.

A count-only batch that passes the fused scan's gate reaches pane state
as pre-aggregated (slot, ring column) pairs in one of three encodings,
chosen by the LARGEST count any one pair of the batch holds
(``WindowOperator._process_batch_fused``):

- under 4,095: packed u32 pairs, stashed — the next watermark advance
  applies them, fires and clears in ONE program (``fused_step_kernel``,
  the gated fire), whether or not a window ends there;
- 4,095 to 65,535: u16 counts, applied at once — a window that ends at
  the next advance fires through ``_fire_ends`` (the chunked fire) and
  the purge is its own launch;
- over 65,535: i32 counts, otherwise as u16;
- on a mesh every such batch crosses the keyed exchange as i32 pairs
  (``apply_pairs_shard``) and every fire is the sharded ``_fire_ends``.

So whether a window fires fused or chunked follows from a count. These
cases hold each side of both thresholds, with and without a window end
at the batch's advance, on one device and on a mesh of four, to the
plain numpy answer — the guard under which the encodings and the two
fire programs can be collapsed.
"""
import threading

import jax
import numpy as np
import pytest

import flink_tpu  # noqa: F401 — x64 before other jax users
from flink_tpu.api.environment import StreamExecutionEnvironment
from flink_tpu.api.sinks import FnSink
from flink_tpu.api.sources import GeneratorSource
from flink_tpu.config import Configuration
from flink_tpu.native_codec import native_available
from flink_tpu.nexmark.queries import q5_hot_items
from flink_tpu.ops import window as window_mod
from flink_tpu.ops.window import WindowOperator

pytestmark = pytest.mark.skipif(
    not native_available(), reason="the fused scan needs the C codec")

WINDOW_MS, SLIDE_MS, OOO_MS = 10_000, 2_000, 4_000   # Q5 as the cells run it
PANES_PER_WINDOW = WINDOW_MS // SLIDE_MS
HOT, N_FILL = 0, 64          # the hot auction; the others are 1..N_FILL
N = 1 << 17                  # records of the batch under test
N_LEAD = 4096                # records of each batch before it
UNDER_TEST = 7               # its index in the stream


def _batch(rng, n, t_lo, t_hi, hot):
    """``n`` bids stamped in [t_lo, t_hi], in order: ``hot`` of them on
    the hot auction, the rest spread evenly over the other auctions."""
    fill = 1 + (np.arange(n - hot) % N_FILL)
    keys = np.concatenate([np.full(hot, HOT), fill]).astype(np.int64)
    rng.shuffle(keys)
    ts = np.linspace(t_lo, t_hi, n).astype(np.int64)
    return keys, ts


def make_stream(hot_count: int, window_end: bool):
    """Nine batches; one pane is 2,000 ms. Batches 0-5 lie in panes
    0-5 and batch 6 in the first third of pane 6. Batch 7, under test,
    puts exactly ``hot_count`` bids of the hot auction into the rest of
    pane 6 — the largest count of any (auction, pane) pair of it — and
    either stays there (its watermark, 4 s behind, passes no window
    end) or runs 900 ms into pane 7 with 100 more hot bids (its
    watermark passes 10,000: the window of panes 0-4 fires). Batch 8
    follows in pane 7, so what batch 7 ran ends where batch 8 arrives
    and the end-of-input flush stays out of it."""
    rng = np.random.default_rng(hot_count * 2 + window_end)
    out = [_batch(rng, N_LEAD, i * SLIDE_MS + 100, i * SLIDE_MS + 1_900,
                  N_LEAD // 2) for i in range(6)]
    out.append(_batch(rng, N_LEAD, 12_100, 12_700, N_LEAD // 2))
    if not window_end:
        out.append(_batch(rng, N, 12_800, 13_900, hot_count))
    else:
        k1, t1 = _batch(rng, N - 4096, 12_800, 13_990, hot_count)
        k2, t2 = _batch(rng, 4096, 14_000, 14_900, 100)
        out.append((np.concatenate([k1, k2]), np.concatenate([t1, t2])))
    out.append(_batch(rng, N_LEAD, 14_950, 15_900, N_LEAD // 2))
    return out


def reference_rows(stream):
    """Q5's answer in plain numpy: per window the auction(s) with the
    most bids, ties kept — sorted (window_end, auction, bid_count)."""
    keys = np.concatenate([k for k, _ in stream])
    pane = np.concatenate([t for _, t in stream]) // SLIDE_MS
    n_panes = int(pane.max()) + 1
    per_pane = np.zeros((n_panes, N_FILL + 1), np.int64)
    np.add.at(per_pane, (pane, keys), 1)
    rows = []
    for end in range(1, n_panes + PANES_PER_WINDOW):
        cnt = per_pane[max(end - PANES_PER_WINDOW, 0):end].sum(axis=0)
        if cnt.max() > 0:
            rows += [(end * SLIDE_MS, int(a), int(cnt[a]))
                     for a in np.flatnonzero(cnt == cnt.max())]
    return sorted(rows)


class Spy:
    """Records, in order, the batches the operator takes and the
    programs and fire paths it runs: those of the calling thread, which
    is the job's loop (``env.execute`` runs it on its caller). The
    patches are class- and module-wide, so a job that another thread of
    the process still runs would otherwise number its batches here too
    (one whole tier-1 run of PR 34 failed four cases so)."""

    def __init__(self, monkeypatch):
        self.events = []
        self._thread = threading.get_ident()
        for name, label in (("_JIT_PREAGG_U16", "apply_u16"),
                            ("_JIT_PREAGG_U32", "apply_u32"),
                            ("_JIT_PREAGG_I32", "apply_i32"),
                            ("_JIT_FUSED_STEP", "fused_step")):
            monkeypatch.setattr(
                window_mod, name,
                self._noting(getattr(window_mod, name), label))
        monkeypatch.setattr(WindowOperator, "_exchange_pairs", self._noting(
            WindowOperator._exchange_pairs, "pairs_over_mesh"))
        n_batches = iter(range(10**6))
        monkeypatch.setattr(WindowOperator, "process_batch", self._noting(
            WindowOperator.process_batch,
            lambda *a, **kw: ("batch", next(n_batches))))
        monkeypatch.setattr(WindowOperator, "_fire_ends", self._noting(
            WindowOperator._fire_ends,
            lambda op, ends: ("chunked_fire", len(ends)) if ends else None))
        fused = WindowOperator._advance_fused

        def advance_fused(op, wm, ends):
            out = fused(op, wm, ends)
            if out is not None and threading.get_ident() == self._thread:
                self.events.append(("fused_fire", len(ends)))
            return out

        monkeypatch.setattr(WindowOperator, "_advance_fused", advance_fused)

    def _noting(self, fn, label):
        def wrapped(*args, **kwargs):
            if threading.get_ident() == self._thread:
                event = (label(*args, **kwargs) if callable(label)
                         else label)
                if event is not None:
                    self.events.append(event)
            return fn(*args, **kwargs)
        return wrapped

    def of_batch(self, i):
        """What ran from batch ``i``'s arrival to the next batch's."""
        start = self.events.index(("batch", i)) + 1
        rest = self.events[start:]
        stop = (rest.index(("batch", i + 1))
                if ("batch", i + 1) in rest else len(rest))
        return rest[:stop]


def expected_events(hot_count, window_end, devices):
    if devices > 1:
        return ["pairs_over_mesh"] + (
            [("chunked_fire", 1)] if window_end else [])
    if hot_count < 0xFFF:
        # stashed at the batch, applied by the advance's one program
        return ["fused_step", ("fused_fire", int(window_end))]
    apply = "apply_u16" if hot_count <= 0xFFFF else "apply_i32"
    return [apply] + ([("chunked_fire", 1)] if window_end else [])


def run_q5(stream, devices):
    """The stream through Q5 as ``env.execute()`` runs it.
    -> (JobResult, sorted rows, env)."""
    conf = {"pipeline.microbatch-size": N, "state.num-key-shards": 8,
            "state.slots-per-shard": 64, "analysis.fail-on": "off"}
    if devices > 1:
        conf["cluster.mesh-devices"] = devices
    env = StreamExecutionEnvironment(Configuration(conf))
    batches = []
    source = GeneratorSource(
        lambda split, i: ({"auction": stream[i][0]}, stream[i][1])
        if i < len(stream) else None)
    q5_hot_items(env, source, FnSink(batches.append),
                 window_ms=WINDOW_MS, slide_ms=SLIDE_MS,
                 out_of_orderness_ms=OOO_MS)
    res = env.execute(f"fire-paths-{devices}")
    rows = sorted(
        (int(w), int(a), int(c)) for b in batches
        for w, a, c in zip(b["window_end"], b["auction"], b["bid_count"]))
    return res, rows, env


def test_job_metrics_name_no_plane_but_the_host_fed():
    """``JobResult.metrics`` has no counter of a device-chained plane
    any more; a reader that holds such a name at 0 with
    ``metrics.get(name, 0)``, as the benchmark's configuration does,
    reads 0."""
    res, rows, _ = run_q5(make_stream(4_094, True), 1)
    assert rows
    assert not [k for k in res.metrics if "device_chain" in k]
    assert all(int(res.metrics.get(k, 0)) == 0 for k in (
        "device_chain_attached", "device_chain_batches",
        "device_chain_fallback_batches"))


@pytest.mark.parametrize("devices", [1, 4])
@pytest.mark.parametrize("window_end", [False, True],
                         ids=["no_window_end", "window_end"])
@pytest.mark.parametrize("hot_count", [4_094, 4_095, 65_535, 65_536])
def test_pair_encoding_and_fire_path(hot_count, window_end, devices,
                                     monkeypatch):
    if len(jax.devices()) < devices:
        pytest.skip(f"needs {devices} devices")
    stream = make_stream(hot_count, window_end)
    keys, ts = stream[UNDER_TEST]
    pair_counts = np.bincount((ts // SLIDE_MS) * (N_FILL + 1) + keys)
    assert pair_counts.max() == hot_count   # the hot pair is the largest
    assert (len(np.unique(ts // SLIDE_MS)) == 2) == window_end

    spy = Spy(monkeypatch)
    res, rows, env = run_q5(stream, devices)
    assert rows == reference_rows(stream)
    assert res.metrics["records_in"] == sum(len(t) for _, t in stream)
    assert res.metrics.get("late_records", 0) == 0
    (op,) = [op for op in env._driver._ops.values()
             if isinstance(op, WindowOperator)]
    assert op.plan.ring <= 64
    # every batch took the fused scan, and the one under test ran
    # exactly the programs its hottest pair's count prescribes
    assert op.prof["preagg_batches"] == len(stream)
    assert spy.of_batch(UNDER_TEST) == expected_events(
        hot_count, window_end, devices), spy.events
