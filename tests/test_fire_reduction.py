"""What runs in a top-n fire BEFORE its compaction: the window counts
and the top-n threshold (``ops/window.py`` ``fire_kernel``'s count
lane, ``top_values``). A fire of one window end makes its counts by one
masked reduction over the ring axis, the window's live columns alone
passing the mask; a wider fire keeps the roll and the prefix sums; a top
1 is a max. The expressions they replaced live on here as the reference,
to the letter: the roll of the whole ring, its prefix sum and two
columns of it (``roll_prefix_counts``), and ``lax.top_k`` over every
candidate (``top_k_values``). The counts, the thresholds, the emit
ring's rows and both head words (appended total, rows truncated) are
equal element for element, through ``ring_append_topn_kernel``, the
fused step and, on a mesh of four, ``topn_shard``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

import flink_tpu  # noqa: F401 — x64 before other jax users
from flink_tpu.ops import window as W
from flink_tpu.ops.aggregates import count
from flink_tpu.parallel.mesh import make_mesh_plan
from flink_tpu.state.keyed import PaneState, PaneStateLayout

PPW = 5             # Q5: a 10 s window of 2 s panes
RING = 12           # the large-keys cells'
SENTINEL = int(W._END_SENTINEL)


def roll_prefix_counts(counts, end_panes, w_valid, pane_lo, pane_hi, *,
                       panes_per_window, ring):
    """``fire_kernel``'s count path before PR 44, to the letter."""
    ppw = panes_per_window
    roll_amt = (pane_lo % ring).astype(jnp.int32)
    rolled = jnp.roll(counts, -roll_amt, axis=1)
    cs = jnp.cumsum(rolled, axis=1)
    e_hi = jnp.clip(end_panes - 1 - pane_lo, -1, ring - 1).astype(jnp.int32)
    e_lo = jnp.clip(end_panes - ppw - 1 - pane_lo, -1,
                    ring - 1).astype(jnp.int32)
    hiv = jnp.where(e_hi[None, :] >= 0,
                    jnp.take(cs, jnp.clip(e_hi, 0, ring - 1), axis=1), 0)
    lov = jnp.where(e_lo[None, :] >= 0,
                    jnp.take(cs, jnp.clip(e_lo, 0, ring - 1), axis=1), 0)
    return jnp.where(w_valid[None, :], hiv - lov, 0)


_fire_kernel = W.fire_kernel


def roll_prefix_fire_kernel(state, end_panes, w_valid, pane_lo, pane_hi, **kw):
    """``fire_kernel`` with the count path it had before PR 44."""
    sums, maxs, mins, _ = _fire_kernel(
        state, end_panes, w_valid, pane_lo, pane_hi, **kw)
    return sums, maxs, mins, roll_prefix_counts(
        state.counts, end_panes, w_valid, pane_lo, pane_hi, **kw)


def top_k_values(v, k):
    """The threshold's source before PR 44, to the letter."""
    return lax.top_k(v.T, k)[0]


def pane_counts(rows, ring, lo, hi, seed, dead_rows=0.3):
    """(rows, ring) counts as the operator keeps them: a column holds the
    one pane of [lo, hi] it belongs to and is zero where it belongs to
    none (purged panes are cleared, unwritten ones never touched); some
    rows hold nothing; the last is the dump row."""
    rng = np.random.default_rng(seed)
    c = np.zeros((rows, ring), np.int32)
    for p in range(lo, hi + 1):
        c[:, p % ring] = rng.integers(0, 40, rows)
    c[rng.random(rows) < dead_rows] = 0
    c[rows - 1] = 0
    return c


def numpy_counts(c, ring, lo, hi, ends):
    """The plain meaning: a window's panes inside [lo, hi], summed."""
    out = np.zeros((c.shape[0], len(ends)), np.int64)
    for w, e in enumerate(ends):
        if e == SENTINEL:
            continue
        for p in range(e - PPW, e):
            if lo <= p <= hi:
                out[:, w] += c[:, p % ring]
    return out


def end_sets(lo, hi, width):
    """Window-end lists of one static width: a window partly below
    ``lo`` (with a full ring its purged panes' columns hold panes up to
    ``hi``), one partly above ``hi``, one inside, one not valid."""
    below, above, inside = lo + 2, hi + 3, lo + PPW
    if width == 1:
        return [[below], [above], [inside], [SENTINEL]]
    if width == 2:
        return [[below, above], [inside, SENTINEL], [SENTINEL, below]]
    return [[below, inside, above, SENTINEL], [inside, inside + 1,
                                               inside + 2, inside + 3]]


@pytest.mark.parametrize("width", [1, 2, 4])
@pytest.mark.parametrize("roll", range(RING))
def test_counts_equal_the_roll_and_prefix_sum_they_replaced(
        roll, width, monkeypatch):
    prefix_calls = []
    real = W.prefix_sum_counts
    monkeypatch.setattr(
        W, "prefix_sum_counts",
        lambda *a, **k: (prefix_calls.append(1), real(*a, **k))[1])
    lo = 10 * RING + roll                       # pane_lo % ring == roll
    for span in (RING, RING - 3, 1):            # full ring, part of it, one pane
        hi = lo + span - 1
        c = pane_counts(33, RING, lo, hi, seed=roll * 7 + span)
        for ends in end_sets(lo, hi, width):
            e = jnp.asarray(ends, jnp.int64)
            args = (e, e > SENTINEL // 2, jnp.int64(lo), jnp.int64(hi))
            kw = dict(panes_per_window=PPW, ring=RING)
            got = np.asarray(W.fire_kernel(
                PaneState(None, None, None, jnp.asarray(c)), *args, **kw)[3])
            want = np.asarray(roll_prefix_counts(jnp.asarray(c), *args, **kw))
            assert got.dtype == want.dtype == np.int32
            assert np.array_equal(got, want)
            assert np.array_equal(got, numpy_counts(c, RING, lo, hi, ends))
    # the form is read off the static width, and nothing else: one end
    # reads its live columns, a wider fire takes the prefix sums
    assert bool(prefix_calls) == (width > 1)
    assert W.reads_live_columns(width) == (width == 1)


# name -> ((candidates, W) ranking values, k)
NEG = -np.inf
THRESH_CASES = {
    "distinct": (np.array([[3., 9.], [7., 1.], [5., 4.], [NEG, NEG]]), 1),
    "ties_at_the_top": (np.array([[7., 2.], [7., 2.], [1., 2.], [7., NEG]]), 1),
    "a_window_without_candidates": (np.array([[NEG, 6.], [NEG, 8.],
                                              [NEG, NEG]]), 1),
    "no_candidate_at_all": (np.full((5, 3), NEG), 1),
    "one_row": (np.array([[4., NEG, 0.]]), 1),
    "counts_past_float32s_integers": (np.array([[2.0 ** 24], [2.0 ** 24 + 2],
                                                [2.0 ** 24 + 2]]), 1),
    "top_2_keeps_top_k": (np.array([[3., 9.], [7., 1.], [5., NEG],
                                    [7., NEG]]), 2),
    "top_3_of_two_candidates": (np.array([[3., NEG], [7., NEG], [NEG, NEG]]), 3),
}


@pytest.mark.parametrize("case", list(THRESH_CASES))
def test_top_1_by_max_equals_top_k(case, monkeypatch):
    grid, k = THRESH_CASES[case]
    v = jnp.asarray(grid, jnp.float32)
    called = []
    real = lax.top_k
    monkeypatch.setattr(lax, "top_k",
                        lambda *a, **kw: (called.append(1), real(*a, **kw))[1])
    got = np.asarray(W.top_values(v, k))
    assert bool(called) == (k > 1)              # a top 1 sorts nothing
    want = np.asarray(real(v.T, k)[0])
    assert got.shape == want.shape == (grid.shape[1], k)
    assert np.array_equal(got, want)            # -inf == -inf
    # -inf where a window has fewer than k candidates: selects all its rows
    few = (grid > NEG).sum(axis=0) < k
    assert np.array_equal(np.isneginf(got[:, k - 1]), few)


# name -> (ring, span of written panes, window ends (offsets from
# pane_lo; None: padding), rows that tie at the top of window 0,
# candidates at all)
FIRES = {
    "one_end": (12, 9, [5], 1, True),
    "one_end_full_ring_partly_purged": (12, 12, [2], 1, True),
    "two_ends": (12, 9, [5, 6], 1, True),
    "ties": (12, 9, [5], 3, True),
    "no_candidates": (12, 9, [5], 1, False),
    "padding_end_first": (12, 9, [None, 7], 1, True),
    "small_ring_one_end": (8, 7, [5], 2, True),
    "four_ends_keep_the_prefix_form": (8, 7, [5, 6, 7, None], 1, True),
}
ROW_CAP = 300
N_DEV = 4
SLOTS = 16 * N_DEV          # a device's block on the mesh, the grid locally
LO = 131                    # 131 % 12 == 11, 131 % 8 == 3: a real roll


def fire_inputs(case):
    ring, span, offs, ties, any_rows = FIRES[case]
    hi = LO + span - 1
    ends = [SENTINEL if o is None else LO + o for o in offs]
    return ring, hi, ends, ties, any_rows


def tied_counts(c, ring, ends, ties, any_rows, rows_used):
    """Make ``rows_used`` the winners of the first real window, level
    with each other (one pane holds all they have); or empty the grid."""
    if not any_rows:
        return np.zeros_like(c)
    e = next(x for x in ends if x != SENTINEL)
    c[rows_used] = 0
    c[rows_used, (e - 1) % ring] = 1000
    return c


def local_fire(case, fused):
    ring, hi, ends, ties, any_rows = fire_inputs(case)
    rows = SLOTS + 1
    c = tied_counts(pane_counts(rows, ring, LO, hi, seed=len(case)), ring,
                    ends, ties, any_rows, np.arange(3, 3 + ties))
    state = PaneState(None, None, None, jnp.asarray(c))
    used = jnp.ones(rows, bool).at[rows - 1].set(False)
    emit = jnp.zeros((ROW_CAP + 2, 3), jnp.int32).at[0, 0].set(5)
    kw = dict(agg=count(), panes_per_window=PPW, ring=ring, sel_cap=256,
              by="count", topn=1, fire_pad=len(ends))
    if not fused:
        params = jnp.asarray([LO, hi, LO] + ends + [SENTINEL] * (
            W.MIN_FIRE_PAD - len(ends)), jnp.int64)
        return c, lambda: W.ring_append_topn_kernel(
            state, emit, params, used, **kw)
    buf = np.full(W.FUSED_HDR + 8, -1, np.int32)        # pairs: padding
    buf[:W.FUSED_HDR] = 0
    buf[:6] = np.array([LO, hi, LO], np.int64).view(np.int32)
    deltas = np.full(W.MIN_FIRE_PAD, W._DELTA_SENTINEL, np.int64)
    for i, e in enumerate(ends):
        if e != SENTINEL:
            deltas[i] = e - LO
    buf[8:8 + W.MIN_FIRE_PAD] = deltas.astype(np.int32)
    return c, lambda: W.fused_step_kernel(
        state, emit, jnp.asarray(buf), used, dump_row=SLOTS, **kw)[1]


def mesh_fire(case):
    ring, hi, ends, ties, any_rows = fire_inputs(case)
    mp = make_mesh_plan(N_DEV, SLOTS // N_DEV, jax.devices()[:N_DEV])
    layout = PaneStateLayout(mp.slots_per_device, ring, 0, 0, 0)
    rows = N_DEV * layout.rows
    # the winners on two devices: the threshold is the mesh's, not a block's
    winners = np.array([3, 2 * layout.rows + 1, layout.rows + 4])[:ties]
    c = pane_counts(rows, ring, LO, hi, seed=len(case))
    c[layout.rows - 1::layout.rows] = 0                 # each block's dump row
    c = tied_counts(c, ring, ends, ties, any_rows, winners)
    used = np.ones(rows, bool)
    used[layout.rows - 1::layout.rows] = False
    sh = mp.row_sharding()
    state = PaneState(None, None, None, jax.device_put(c, sh))
    emit = jax.device_put(np.zeros((N_DEV * (ROW_CAP + 2), 3), np.int32), sh)
    params = jnp.asarray([LO, hi, LO] + ends + [SENTINEL] * (
        W.MIN_FIRE_PAD - len(ends)), jnp.int64)

    def run():
        # built anew each time: the programs trace what the module holds NOW
        k = W._sharded_kernels.__wrapped__(
            mp, count(), layout, PPW, None, "all-to-all", ("count", 1))
        return k.ring_topn(state, emit, params, jax.device_put(used, sh),
                           sel_cap=256)
    return c, run


KERNELS = {"ring_append_topn_kernel": lambda case: local_fire(case, False),
           "fused_step_kernel": lambda case: local_fire(case, True),
           "topn_shard": mesh_fire}


@pytest.mark.parametrize("case", list(FIRES))
@pytest.mark.parametrize("kernel", list(KERNELS))
def test_fired_rows_equal_through_every_top_n_kernel(kernel, case,
                                                     monkeypatch):
    ring, hi, ends, ties, any_rows = fire_inputs(case)
    c, run = KERNELS[kernel](case)
    got = np.asarray(run())
    monkeypatch.setattr(W, "fire_kernel", roll_prefix_fire_kernel)
    monkeypatch.setattr(W, "top_values", top_k_values)
    want = np.asarray(run())
    assert np.array_equal(got, want)
    # the winners by the plain meaning: per real window the rows level
    # with its largest count, none where it has no row
    wc = numpy_counts(c, ring, LO, hi, ends)
    n = sum(int((wc[:, w] == wc[:, w].max()).sum())
            for w in range(len(ends)) if wc[:, w].max() > 0)
    real = [w for w, e in enumerate(ends) if e != SENTINEL]
    assert n >= len(real) if any_rows else n == 0
    assert int((wc[:, real[0]] == 1000).sum()) == (ties if any_rows else 0)
    blocks = got.reshape(N_DEV if kernel == "topn_shard" else 1, -1, 3)
    start = 0 if kernel == "topn_shard" else 5
    # head words: appended total, rows truncated by the cap
    assert blocks[:, 0, 0].sum() == start * len(blocks) + n
    assert not blocks[:, 0, 1].any()
    body = np.concatenate([b[1 + start:1 + b[0, 0]] for b in blocks])
    want_rows = sorted(
        (r, ends[w] - LO, wc[r, w]) for w in real
        for r in np.nonzero((wc[:, w] == wc[:, w].max()) & (wc[:, w] > 0))[0])
    assert sorted(map(tuple, body.tolist())) == want_rows


# name -> (slots per shard of 8, records a batch, watermarks to advance to
# in turn, the static width of each advance's fire)
COUNTED = {
    # 65,536 slots x 64 ends would not fit: the real ends' pow2 bucket
    "large_grid_one_end": (8192, 600, [2_000], [1]),
    "large_grid_catch_up": (8192, 600, [2_000, 10_000], [1, 4]),
    # a small grid fires all MIN_FIRE_PAD ends of its params: one program
    # (a pane's hot count past 4,095 keeps the batch off the fused step)
    "small_grid": (64, 30_000, [2_000, 4_000], [64, 64]),
    # the fused step's fire is as wide as the real ends' bucket
    "fused_step": (64, 2048, [2_000, 4_000, 12_000], [1, 1, 4]),
}


@pytest.mark.parametrize("case", list(COUNTED))
def test_the_operator_counts_the_fires_that_read_their_own_columns(case):
    from flink_tpu.api.windowing import SlidingEventTimeWindows
    from flink_tpu.native_codec import native_available
    from flink_tpu.ops.window import WindowOperator

    sps, n, watermarks, widths = COUNTED[case]
    if case == "fused_step" and not native_available():
        pytest.skip("the fused scan needs the C codec")
    op = WindowOperator(
        SlidingEventTimeWindows.of(10_000, 2_000), count(), num_shards=8,
        slots_per_shard=sps, max_out_of_orderness_ms=4_000,
        top_n=("count", 1))
    rng = np.random.default_rng(len(case))
    fired, hot_ts = {}, []
    t0 = 0
    for wm in watermarks:
        # in order, up to the watermark's own delay past it
        ts = np.sort(rng.integers(t0, wm + 4_000, n)).astype(np.int64)
        keys = rng.integers(0, 9, n).astype(np.int64)
        keys[: n // 2] = 3                      # the hot key
        hot_ts.append(ts[keys == 3])
        op.process_batch(keys, ts, {})
        for k, col in dict(op.advance_watermark(wm)).items():
            fired.setdefault(k, []).append(np.asarray(col))
        op.run_pending_release()
        t0 = wm + 4_000
    assert op.plan.panes_per_window == PPW
    assert op.prof["fires"] == len(watermarks)
    assert op.prof["fires_direct"] == widths.count(1)
    # both forms ran somewhere in these cases, and the rows are the hot
    # key's with its count
    ends = np.concatenate(fired["window_end"])
    assert list(ends) == list(range(2_000, watermarks[-1] + 1, 2_000))
    assert set(np.concatenate(fired["key"])) == {3}
    hot = np.concatenate(hot_ts)
    assert list(np.concatenate(fired["count"])) == [
        int(((hot >= e - 10_000) & (hot < e)).sum()) for e in ends]
