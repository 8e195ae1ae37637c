"""The fire's compaction: which candidates of the rows x W grid were
selected, in row-major order, padded to a fixed shape
(``ops/window.py`` ``first_true_indices``: a prefix sum and a binary
search where few of many candidates can win, one sort of the masked
positions where as many can win as there are). The expression it
replaced, a stable argsort of the negated mask over every candidate,
lives on here as the reference: the indices, the emit ring's rows and
both head words (appended total, rows truncated) are equal element for
element, in ``_topn_select_append`` (every top-n fire: local, mesh,
fused) and in ``fire_pack_kernel``."""
import jax.numpy as jnp
import numpy as np
import pytest

import flink_tpu  # noqa: F401 — x64 before other jax users
from flink_tpu.ops import window as W
from flink_tpu.ops.aggregates import count
from flink_tpu.state.keyed import PaneState


def argsort_compaction(flat, cap):
    """The fire's compaction before PR 40, to the letter."""
    k = flat.shape[0]
    m = min(k, cap)
    idx = jnp.argsort(~flat, stable=True)[:m]
    idx = jnp.where(flat[idx], idx, k)
    if m < cap:  # tiny grids: pad to the fixed selection shape
        idx = jnp.concatenate([idx, jnp.full(cap - m, k, idx.dtype)])
    return idx


# name -> (rows, W, cap, density (None: exactly one candidate), the form
# ``first_true_indices`` takes at these static shapes)
CASES = {
    "empty": (64, 4, 32, 0.0, "sort"),
    "one": (1024, 4, 32, None, "search"),
    "sparse": (1024, 4, 64, 0.005, "search"),
    "dense": (64, 4, 256, 0.5, "sort"),
    "all": (16, 4, 64, 1.0, "sort"),
    "more_winners_than_cap": (256, 4, 32, 0.5, "search"),
    "grid_smaller_than_cap": (3, 2, 16, 0.5, "sort"),
    "empty_searched": (1024, 4, 32, 0.0, "search"),
    "all_searched": (1024, 4, 32, 1.0, "search"),
}


def mask_of(rows, w, density, seed):
    if density is None:
        m = np.zeros((rows, w), bool)
        m[rows // 3, w - 1] = True
        return m
    return np.random.default_rng(seed).random((rows, w)) < density


def topn_tail(mask):
    """``_topn_select_append`` on a grid whose selection IS ``mask``
    (every candidate at its window's threshold of -inf), appended to a
    ring that already holds 5 rows."""
    rows, w = mask.shape
    counts = jnp.asarray(np.where(mask, 7 + np.arange(rows)[:, None], 0),
                         jnp.int32)
    none = jnp.zeros((rows, w, 0), jnp.float32)
    nz = jnp.asarray(mask)
    v = jnp.where(nz, counts.astype(jnp.float32), -jnp.inf)
    ring = jnp.zeros((300 + 2, 3), jnp.int32).at[0, 0].set(5)

    def run(cap):
        return W._topn_select_append(
            ring, none, none, none, counts, nz, v,
            jnp.full(w, -jnp.inf), jnp.arange(1, w + 1, dtype=jnp.int64),
            jnp.int64(0), agg=count(), sel_cap=cap, row_offset=jnp.int32(3))
    return run


def pack(mask):
    """``fire_pack_kernel`` over a state whose window ``w`` is ring column
    ``w`` alone, so that its non-empty grid IS ``mask``."""
    rows, w = mask.shape
    counts = jnp.asarray(np.where(mask, 7 + np.arange(rows)[:, None], 0),
                         jnp.int32)
    state = PaneState(None, None, None, counts)
    params = jnp.asarray([0, w - 1, 0] + list(range(1, w + 1)), jnp.int64)
    used = jnp.ones(rows, bool)

    def run(cap):
        return W.fire_pack_kernel(state, params, used, agg=count(),
                                  panes_per_window=1, ring=w, out_cap=cap)
    return run


@pytest.mark.parametrize("case", list(CASES))
@pytest.mark.parametrize("kernel", [topn_tail, pack])
def test_compaction_equals_the_argsort_it_replaced(kernel, case, monkeypatch):
    rows, w, cap, density, form = CASES[case]
    mask = mask_of(rows, w, density, seed=len(case))
    flat = jnp.asarray(mask.reshape(-1))
    # few winners of many candidates are searched for, the rest sorted
    assert form == ("search" if 2 * cap * (rows * w).bit_length() <= rows * w
                    else "sort")
    want_idx = np.asarray(argsort_compaction(flat, cap))
    got_idx = np.asarray(W.first_true_indices(flat, cap))
    assert got_idx.shape == (cap,)
    assert np.array_equal(got_idx, want_idx)
    # what the case is there for
    n = int(mask.sum())
    assert {"empty": n == 0, "empty_searched": n == 0, "one": n == 1,
            "all": n == rows * w, "all_searched": n == rows * w,
            "more_winners_than_cap": n > cap,
            "grid_smaller_than_cap": rows * w < cap}.get(case, 0 < n <= cap)
    run = kernel(mask)
    got = np.asarray(run(cap))
    monkeypatch.setattr(W, "first_true_indices", argsort_compaction)
    want = np.asarray(run(cap))
    assert np.array_equal(got, want)
    if kernel is topn_tail:
        # head words: appended total, rows truncated by the cap
        assert got[0, 0] == 5 + min(n, cap) and got[0, 1] == max(n - cap, 0)
        assert np.array_equal(got[6:6 + min(n, cap), 0],
                              want_idx[:min(n, cap)] // w + 3)
    else:
        assert got[0, 0] == n and got[0, 1] == 0
