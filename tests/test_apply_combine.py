"""The per-record apply combines a batch per (row, ring column) on the
device and updates pane state once per distinct cell
(``ops/window.py`` ``_scatter_panes``: ``combine_cells`` then
``apply_cells``). Held here to what a scatter of the records makes:
``np.add.at`` / ``np.maximum.at`` / ``np.minimum.at`` over the same
records, through both uploads (``apply_kernel``'s packed int32,
``apply_kernel_split``'s three bytes)."""
import math
import re

import jax.numpy as jnp
import numpy as np
import pytest

from flink_tpu.api.windowing import SlidingEventTimeWindows
from flink_tpu.ops import window as W
from flink_tpu.ops.aggregates import count, max_of, min_of, multi, sum_of
from flink_tpu.ops.window import WindowOperator
from flink_tpu.state.keyed import PaneStateLayout, init_state

SLOTS, RING = 20_000, 12        # rows fit the split upload's uint16
ALL = multi(count(), sum_of("v"), max_of("v"), min_of("v"))


# -- what a batch holds: (rows, cols, valid), by name ----------------------

def one_cell(rng, b):
    return np.full(b, 4321), np.full(b, 7), np.ones(b, bool)


def all_distinct(rng, b):
    # the first b cells column by column, shuffled: whole columns of
    # SLOTS cells, more than a chunk each once b passes 8 x 1024
    cell = rng.permutation(b)
    return cell % SLOTS, cell // SLOTS, np.ones(b, bool)


def recurring(rng, b):
    return (rng.integers(0, max(1, b // 15), b), rng.integers(3, 5, b),
            np.ones(b, bool))


def some_invalid(rng, b):
    rows, cols, _ = recurring(rng, b)
    return rows, cols, rng.random(b) < 0.7


def none_valid(rng, b):
    rows, cols, _ = recurring(rng, b)
    return rows, cols, np.zeros(b, bool)


def beside_the_dump_row(rng, b):
    # the last real row and the last ring column: the cells next to the
    # dump row (row SLOTS), which takes nothing
    rows = np.where(rng.random(b) < 0.5, SLOTS - 1, rng.integers(0, SLOTS, b))
    cols = np.where(rng.random(b) < 0.5, RING - 1, rng.integers(0, RING, b))
    return rows, cols, rng.random(b) < 0.9


BATCHES = [one_cell, all_distinct, recurring, some_invalid, none_valid,
           beside_the_dump_row]


def reference(rows, cols, valid, v):
    r, c = rows[valid], cols[valid]
    counts = np.zeros((SLOTS + 1, RING), np.int64)
    np.add.at(counts, (r, c), 1)
    sums = np.zeros((SLOTS + 1, RING), np.float64)
    np.add.at(sums, (r, c), v[valid].astype(np.float64))
    maxs = np.full((SLOTS + 1, RING), -np.inf, np.float32)
    np.maximum.at(maxs, (r, c), v[valid])
    mins = np.full((SLOTS + 1, RING), np.inf, np.float32)
    np.minimum.at(mins, (r, c), v[valid])
    return counts, sums, maxs, mins


def run_apply(upload, agg, rows, cols, valid, v):
    layout = PaneStateLayout(slots=SLOTS, ring=RING, sum_width=agg.sum_width,
                             max_width=agg.max_width, min_width=agg.min_width)
    data = {"v": jnp.asarray(v)} if agg is ALL else {}
    if upload == "packed":
        packed = np.where(valid, rows * RING + cols, -1).astype(np.int32)
        return W._JIT_APPLY(init_state(layout), jnp.asarray(packed), data,
                            agg=agg, ring=RING, dump_row=SLOTS)
    sc = W.split_encode(rows, cols.astype(np.uint8), valid)
    return W._JIT_APPLY_SPLIT(init_state(layout), jnp.asarray(sc), data,
                              agg=agg, dump_row=SLOTS)


@pytest.mark.parametrize("b", [1, 1000, 1 << 17])
@pytest.mark.parametrize("lanes", ["count", "all"])
@pytest.mark.parametrize("upload", ["packed", "split"])
@pytest.mark.parametrize("batch", BATCHES, ids=lambda f: f.__name__)
def test_apply_equals_a_scatter_of_the_records(batch, upload, lanes, b):
    rng = np.random.default_rng(b + len(batch.__name__))
    rows, cols, valid = batch(rng, b)
    # prices as the suite's: floats; in every other case whole numbers
    # whose sums stay under 2^24, where float32 is exact in any order
    whole = batch in (one_cell, some_invalid, beside_the_dump_row)
    v = (rng.integers(1, 100, b) if whole
         else rng.random(b) * 1000).astype(np.float32)
    agg = ALL if lanes == "all" else count()
    state, report = run_apply(upload, agg, rows, cols, valid, v)
    counts, sums, maxs, mins = reference(rows, cols, valid, v)
    np.testing.assert_array_equal(np.asarray(state.counts), counts)
    assert tuple(np.asarray(report)) == (
        len(np.unique(rows[valid] * RING + cols[valid])), valid.sum())
    if lanes == "count":
        assert state.sums is state.maxs is state.mins is None
        return
    np.testing.assert_array_equal(np.asarray(state.maxs)[..., 0], maxs)
    np.testing.assert_array_equal(np.asarray(state.mins)[..., 0], mins)
    got = np.asarray(state.sums)[..., 0]
    if whole:
        np.testing.assert_array_equal(got, sums)
    else:
        np.testing.assert_allclose(got, sums, rtol=1e-6)


@pytest.mark.parametrize("b, batch", [
    (1, one_cell), (1000, all_distinct), (1000, recurring),
    (1 << 14, all_distinct), (1 << 17, all_distinct), (1 << 17, recurring),
    (1 << 17, none_valid)], ids=lambda x: getattr(x, "__name__", str(x)))
def test_trips_are_chunks_per_ring_column(b, batch):
    """The chunk loop makes, over the ring columns the batch names,
    ceil(the column's distinct cells / apply_chunk(b)) trips: 13 for
    2^17 distinct cells (six whole columns of 20,000 at two trips, one
    of 11,072), none for a batch without a valid record."""
    rows, cols, valid = batch(np.random.default_rng(b), b)
    chunk = W.apply_chunk(b)
    assert chunk == {1: 1, 1000: 1000, 1 << 14: 2048, 1 << 17: 1 << 14}[b]
    state = init_state(PaneStateLayout(SLOTS, RING, 0, 0, 0))
    cells, starts, scans, n_cells, n_records = W.combine_cells(
        SLOTS + 1, jnp.asarray(rows, jnp.int32), jnp.asarray(cols, jnp.int32),
        jnp.asarray(valid), {})
    state, trips = W.apply_cells(state, cells, starts, scans, n_cells)
    distinct = np.unique(np.stack([cols[valid], rows[valid]]), axis=1)
    per_column = np.bincount(distinct[0], minlength=RING)
    assert int(n_cells) == distinct.shape[1] and int(n_records) == valid.sum()
    assert int(trips) == sum(math.ceil(n / chunk) for n in per_column)
    if batch is all_distinct and b == 1 << 17:
        assert int(trips) == 13 and distinct.shape[1] > chunk
    np.testing.assert_array_equal(np.asarray(state.counts),
                                  reference(rows, cols, valid,
                                            np.zeros(b, np.float32))[0])


@pytest.mark.parametrize("mesh", [None, 4])
def test_the_operator_counts_cells_and_records(mesh):
    """``profile.opN.apply_cells`` / ``apply_records`` are job totals of
    what the applies report, read from retired steps: after a job of
    several batches they equal the distinct (key, pane) pairs and the
    records offered, on one chip and summed over a mesh's devices."""
    import jax

    from flink_tpu.parallel.mesh import make_mesh_plan

    kw = dict(max_out_of_orderness_ms=4000)
    if mesh is None:
        kw.update(num_shards=8, slots_per_shard=512)
    else:
        kw.update(mesh_plan=make_mesh_plan(8, 512, jax.devices()[:mesh]))
    op = WindowOperator(SlidingEventTimeWindows.of(10_000, 2_000), ALL, **kw)
    rng = np.random.default_rng(3)
    cells = records = 0
    for i in range(5):
        n = 3000
        keys = rng.integers(0, 2500, n)     # too many pairs to pre-add
        ts = rng.integers(i * 1000, i * 1000 + 9000, n)
        op.process_batch(keys, ts, {"v": rng.random(n).astype(np.float32)})
        cells += len(np.unique(np.stack([keys, ts // 2000]), axis=1).T)
        records += n
    assert op.prof["preagg_batches"] == 0
    # nothing is read from a step in flight: the newest reports wait
    assert 0 < len(op._step_reports) <= op.max_inflight_steps
    assert op.prof["apply_records"] < records
    op.quiesce()
    assert not op._step_reports
    assert op.prof["apply_cells"] == cells
    assert op.prof["apply_records"] == records


# -- the cell's own shape, compiled for the chip (nothing runs) ------------

@pytest.fixture(scope="module")
def one_chip():
    import os

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_the_large_keys_apply_makes_no_pass_over_the_state(one_chip):
    """At ``q5_large_keys_replay``'s shapes (16,777,217 rows x 12 ring
    columns, 2^20 packed ids) the chip's compiler gives the apply
    program temporaries of a few ring columns (67 MB each) and no flat
    copy of the pane state: a scatter into the whole tensor compiled to
    two copies of all 201,326,604 cells a batch and 0.93 GB of
    temporary (PERF.md, PR 32)."""
    import jax

    from flink_tpu.state.keyed import PaneState

    rows, batch = 128 * 131072 + 1, 1 << 20
    state = PaneState(None, None, None, jax.ShapeDtypeStruct(
        (rows, RING), jnp.int32, sharding=one_chip))
    packed = jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=one_chip)
    compiled = W._JIT_APPLY.lower(state, packed, {}, agg=count(), ring=RING,
                                  dump_row=rows - 1).compile()
    text = compiled.as_text()
    assert f"s32[{rows * RING}]" not in text
    assert "sort(" in text and f"s32[{rows}]" in text
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 4 * 4 * rows
    assert memory.alias_size_in_bytes >= 4 * rows * RING   # donated, in place


def test_the_upsert_apply_updates_its_state_in_place(one_chip):
    """``groupagg_apply_kernel`` at ``q17_upserts_paced``'s 33,554,432
    slots (a small batch and a one-lane aggregate: the compile takes
    seconds, and what could copy the state, the dense blocks' loop, the
    conditional around the small gather trip and the gather loop, is
    there whatever the batch): the donated accumulators are aliased to
    the result and the temporaries are those of the batch, not a copy
    of the state (PERF.md, PR 49)."""
    import jax

    from flink_tpu.ops import aggregates, groupagg_device as G

    agg, slots, batch = aggregates.int_max_of("price"), 128 * 262144, 4096

    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.int32, sharding=one_chip)

    words = G.state_words(agg)
    compiled = G._JIT_GROUPAGG_APPLY.lower(
        shape(words, slots), shape(batch), {"price": shape(batch)},
        shape(2, 256), agg=agg, slots=slots, cap=1024).compile()
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= 4 * words * slots
    assert memory.temp_size_in_bytes < 4 * slots // 8
    text = compiled.as_text()
    assert "conditional(" in text and text.count(" while(") >= 4
    assert f"s32[{words},{slots}]" in text
    assert not re.search(rf"= s32\[{words},{slots}\]\S* copy\(", text)
