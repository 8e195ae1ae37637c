"""Differential test of the native key scan against a plain reference.

``ingest_fused_scan`` (``native/codec.cc``) keeps the pane of the record
before, probes keys a block ahead of the histogram and defers its
statistics; ``ingest_combine`` shares the pane cursor. Whatever the loop
does inside, its outputs are those of the record-by-record semantics
written out below in Python: pane = floor((ts - offset) / pane_ms), the
probe before lateness, pairs in first-touch order, statistics per record.
Every case compares ALL outputs bit for bit: pairs and their order,
counts, the statistics, the refire bitmap, the miss list, and on
overflow ``None`` with the workspace zeroed again.

Every case also runs with its first pass split by record range over
2, 3, 4 and 7 threads (``ingest_fused_scan_split``: private workspaces,
merged in range order): the same outputs, element for element, but the
ninth statistic, which counts one more cursor move for a range that
starts inside a pane.
"""
import numpy as np
import pytest

from flink_tpu import native_codec as nc
from flink_tpu.state.keyed import KeyDirectory

pytestmark = pytest.mark.skipif(
    not nc.native_available(), reason="native codec library unavailable")

BLOCK = 512          # codec.cc SCAN_BLOCK: the cases sit on its edges
THREADS = (1, 2, 3, 4, 7)   # 1 = the serial entry; no case's n divides by all
FLOOR = nc.SCAN_RANGE_MIN_RECORDS


@pytest.fixture(autouse=True)
def split_any_batch(monkeypatch):
    """The cases are short: lift the floor under which a batch stays
    serial (``test_a_short_batch_stays_serial`` puts it back)."""
    monkeypatch.setattr(nc, "SCAN_RANGE_MIN_RECORDS", 1)


def range_starts(n):
    """First record of every later range, over all of THREADS."""
    return sorted({n * j // t for t in THREADS for j in range(1, t)})

PANE_MS = 2000
RING = 12
SLOTS = 64           # slot domain of the cases' workspace
I64 = np.iinfo(np.int64)


# -- the reference: one record at a time ------------------------------------

class RefScan:
    """State of one fused ingest (a first call and its ``cont`` calls)."""

    def __init__(self, bitmap_bits):
        self.pairs, self.hist = [], {}
        self.n_valid = self.n_late = self.n_bad = self.n_refire = 0
        self.pmin, self.pmax = I64.max, I64.min
        self.moves = 0
        self.bitmap = np.zeros(max((bitmap_bits + 7) // 8, 1), np.uint8)

    def stats(self, n_miss):
        cmax = max(self.hist.values(), default=0)
        return [self.n_valid, self.n_late, self.n_bad, self.pmin, self.pmax,
                self.n_refire, n_miss, cmax, self.moves]


def ref_scan(st, keys, ts, table, offset_ms, dead, refire_below, cap,
             miss_cap):
    """One call. Returns the miss list, or -1 / -2 as the C code does."""
    miss, prev_pane = [], None
    for i, (k, t) in enumerate(zip(keys.tolist(), ts.tolist())):
        if k not in table:      # probe first: a late unknown key is a miss
            if len(miss) >= miss_cap:
                return -2
            miss.append(i)
            continue
        pane = (t - offset_ms) // PANE_MS        # floored, also below zero
        st.moves += pane != prev_pane
        prev_pane = pane
        if pane < dead:
            st.n_late += 1
            continue
        if table[k] < 0:
            st.n_bad += 1
            continue
        st.n_valid += 1
        st.pmin, st.pmax = min(st.pmin, pane), max(st.pmax, pane)
        if pane < refire_below and 0 <= pane - dead < 8 * len(st.bitmap):
            st.bitmap[(pane - dead) >> 3] |= 1 << ((pane - dead) & 7)
            st.n_refire += 1
        p = table[k] * RING + pane % RING
        if p not in st.hist:
            if len(st.pairs) >= cap:
                return -1
            st.pairs.append(p)
            st.hist[p] = 0
        st.hist[p] += 1
    return miss


# -- cases ------------------------------------------------------------------

def _table(keys_to_slots):
    t = nc.NativeHashTable.create()
    k = np.fromiter(keys_to_slots, np.int64, len(keys_to_slots))
    v = np.fromiter(keys_to_slots.values(), np.int64, len(keys_to_slots))
    t.insert_batch(k, None, v)
    return t


def _keys(rng, n, known=40):
    """Half on one hot key, half over ``known`` registered keys."""
    cold = 1000 + 3 * rng.integers(0, known, n)
    return np.where(rng.integers(0, 2, n) > 0, 1000, cold).astype(np.int64)


def _known(known=40):
    return {1000 + 3 * j: (7 * j) % SLOTS for j in range(known)}


def _ordered(n, start_ms, span_ms):
    return start_ms + (np.arange(n, dtype=np.int64) * span_ms) // max(n, 1)


def make(n=3 * BLOCK + 17, ts=None, keys=None, table=None, offset_ms=0,
         dead=I64.min, refire_below=I64.min, bits=0, cap=1 << 12,
         miss_cap=None, seed=0):
    """One call's inputs; ``ts`` / ``keys`` are ``f(rng, n)`` or None for
    an in-order batch inside one pane over the known keys."""
    rng = np.random.default_rng(seed)
    return dict(
        keys=_keys(rng, n) if keys is None else keys(rng, n),
        ts=_ordered(n, 100 * PANE_MS + 5, 23) if ts is None else ts(rng, n),
        table=_known() if table is None else table, offset_ms=offset_ms,
        dead=dead, refire_below=refire_below, bits=bits, cap=cap,
        miss_cap=miss_cap)


def case(name, **kw):
    return pytest.param(make(**kw), id=name)


def _pane_change_at(i):
    """In order; record ``i`` is the first of the next pane."""
    return lambda rng, n: np.where(
        np.arange(n) < i, 101 * PANE_MS - 1, 101 * PANE_MS).astype(np.int64)


def _alternating(rng, n):
    return (50 + np.arange(n, dtype=np.int64) % 5) * PANE_MS + 7


def _with_unknown(rng, n):
    k = _keys(rng, n)
    k[rng.integers(0, n, max(n // 9, 1))] = 77_000 + rng.integers(0, 5)
    return k


def _put(a, at, value):
    a[np.asarray(at, np.int64)] = value
    return a


def _unknown_at(indices):
    """Known keys, but the records at ``indices(n)``: one unknown key."""
    return lambda rng, n: _put(_keys(rng, n), indices(n), 88_001)


def _late_at(indices):
    """In order inside pane 100, but the records at ``indices(n)``, which
    lie in pane 98."""
    return lambda rng, n: _put(
        _ordered(n, 100 * PANE_MS + 5, 23), indices(n), 98 * PANE_MS + 1)


def _spread(panes):
    return lambda rng, n: (
        (100 + rng.integers(0, panes, n)) * PANE_MS
        + rng.integers(0, PANE_MS, n)).astype(np.int64)


SIZES = [case(f"n_{label}", n=n) for label, n in (
    ("0", 0), ("1", 1), ("block_minus_1", BLOCK - 1), ("block", BLOCK),
    ("block_plus_1", BLOCK + 1))] + [case(
        "n_2p16_plus_3", n=(1 << 16) + 3,
        keys=lambda rng, n: _keys(rng, n, known=640),
        table={1000 + 3 * j: (7 * j) % SLOTS for j in range(640)},
        ts=lambda rng, n: _ordered(n, 100 * PANE_MS - 40, 90))]

FUSED_CASES = [
    case("in_order_one_pane"),
    case("pane_change_inside_block", ts=_pane_change_at(BLOCK + 200)),
    case("pane_change_on_block_edge", ts=_pane_change_at(2 * BLOCK)),
    *SIZES,
    case("alternating_over_5_panes", ts=_alternating),
    case("negative_times_nonzero_offset", offset_ms=777,
         ts=lambda rng, n: _ordered(n, -3 * PANE_MS - 40, 2 * PANE_MS + 90)),
    case("dead_records_and_a_late_unknown_key", ts=_spread(6), dead=103,
         keys=_with_unknown),
    case("refire_candidates", ts=_spread(8), dead=101, refire_below=105,
         bits=4),
    case("refire_span_wider_than_the_bitmap", ts=_spread(12), dead=101,
         refire_below=111, bits=3),
    case("full_sentinel_slots", ts=_spread(3), dead=101,
         table={**_known(), 1015: KeyDirectory.FULL, 1027: -1}),
    case("shuffled_over_5_panes", ts=_spread(5), seed=3),
    # what a split by record range could get wrong
    case("unknown_key_in_the_last_range_only",
         keys=_unknown_at(lambda n: [n - 3])),
    case("pane_boundary_inside_range_2_of_4",
         ts=_pane_change_at((3 * BLOCK + 17) // 4 + 100)),
    case("late_record_first_in_every_range", ts=_late_at(range_starts),
         dead=100),
    case("full_slot_first_in_every_range", table={**_known(), 555: -1},
         keys=lambda rng, n: _put(_keys(rng, n), range_starts(n), 555)),
    case("unknown_key_first_in_every_range", keys=_unknown_at(range_starts)),
    case("refire_pane_only_in_the_last_range", dead=99, refire_below=103,
         bits=4, ts=lambda rng, n: np.where(
             np.arange(n) < n - 40, 104 * PANE_MS + 3, 101 * PANE_MS + 9)),
]

CONT_CASES = [
    case("misses_then_cont", keys=_with_unknown, ts=_spread(2)),
    case("misses_then_cont_late_and_refire", keys=_with_unknown,
         ts=_spread(6), dead=102, refire_below=104, bits=2),
]


def _run_native(c, table, ws, cont=None, keys=None, ts=None, miss_cap=None,
                threads=1):
    keys = c["keys"] if keys is None else keys
    ts = c["ts"] if ts is None else ts
    if miss_cap is None:
        miss_cap = len(ts) if c["miss_cap"] is None else c["miss_cap"]
    return nc.ingest_fused_scan_native(
        keys, ts, table, PANE_MS, c["offset_ms"], RING, ws, c["cap"],
        c["dead"], c["refire_below"], c["bits"], cont=cont,
        miss_cap=miss_cap, threads=threads)


def _check_stats(res, want, n, threads):
    """All nine as the reference has them; of a split scan the ninth may
    count one more move for each later range (it seeks its own first
    pane, which the serial cursor may have held already)."""
    assert res.ranges == max(1, min(threads, n))
    assert res.stats[:8].tolist() == want[:8]
    assert want[8] <= res.stats[8] <= want[8] + res.ranges - 1
    if res.ranges == 1:
        assert res.stats[8] == want[8]


def _check(res, miss, st, want_miss, ws, finalize, n, threads=1):
    assert miss.tolist() == want_miss
    _check_stats(res, st.stats(len(want_miss)), n, threads)
    assert res.npairs == len(st.pairs)
    assert res.out_pairs[:res.npairs].tolist() == st.pairs   # and order
    assert res.bitmap.tolist() == st.bitmap.tolist()
    counts = [st.hist[p] for p in st.pairs]
    if finalize == "pairs":
        pairs, got = nc.ingest_fused_finalize_pairs_native(res, ws)
        assert (pairs.tolist(), got.tolist()) == (st.pairs, counts)
    else:
        hdr, cap_out = 3, max(len(st.pairs), 1) + 5
        buf = nc.ingest_fused_finalize_u32_native(res, ws, hdr, cap_out)
        want = np.full(hdr + cap_out, -1, np.int64)
        want[hdr:hdr + len(counts)] = [
            (p << 12) | c for p, c in zip(st.pairs, counts)]
        assert buf.view(np.uint32).tolist() == (
            want.astype(np.uint32).tolist())
    assert not ws.hist.any()            # every touched entry reset
    assert not ws.range_hist.any()      # in the ranges' own histograms too


@pytest.mark.parametrize("threads", THREADS)
@pytest.mark.parametrize("finalize", ["pairs", "u32"])
@pytest.mark.parametrize("c", FUSED_CASES)
def test_fused_scan_equals_reference(c, finalize, threads):
    ws = nc.PreaggWorkspace(SLOTS * RING, 0)
    st = RefScan(c["bits"])
    want_miss = ref_scan(st, c["keys"], c["ts"], c["table"], c["offset_ms"],
                         c["dead"], c["refire_below"], c["cap"],
                         len(c["ts"]))
    res, miss = _run_native(c, _table(c["table"]), ws, threads=threads)
    _check(res, miss, st, want_miss, ws, finalize, len(c["ts"]), threads)


@pytest.mark.parametrize("threads", THREADS)
@pytest.mark.parametrize("finalize", ["pairs", "u32"])
@pytest.mark.parametrize("c", CONT_CASES)
def test_cont_call_after_register_misses(c, finalize, threads):
    """The operator's second pass: statistics accumulate, pairs continue,
    ``cmax`` is over both calls. Only the first pass is ever split."""
    ws = nc.PreaggWorkspace(SLOTS * RING, 0)
    st = RefScan(c["bits"])
    table, native = dict(c["table"]), _table(c["table"])
    args = (c["offset_ms"], c["dead"], c["refire_below"], c["cap"])
    miss1 = ref_scan(st, c["keys"], c["ts"], table, *args, len(c["ts"]))
    res, miss = _run_native(c, native, ws, threads=threads)
    assert miss.tolist() == miss1 and miss1
    _check_stats(res, st.stats(len(miss1)), len(c["ts"]), threads)
    new = np.unique(c["keys"][miss])
    slots = (np.arange(len(new)) * 5 + 1) % SLOTS
    native.insert_batch(new, None, slots)
    table.update(zip(new.tolist(), slots.tolist()))
    k2, t2 = c["keys"][miss], c["ts"][miss]
    assert ref_scan(st, k2, t2, table, *args, 1) == []
    res, miss = _run_native(c, native, ws, cont=res, keys=k2, ts=t2,
                            miss_cap=1, threads=threads)
    _check(res, miss, st, [], ws, finalize, len(c["ts"]), threads)


def test_pairs_over_the_global_domain_of_a_mesh_equal_preagg_combine():
    """Under a mesh the directory's slots are GLOBAL (four device blocks
    of ``SLOTS`` here), so the workspace spans ``4 * SLOTS * RING`` and
    a pair id passes one block's domain. The scan's pairs and counts
    are those of the numpy lane's ``preagg_combine`` over the same
    slots, which is what crossed the exchange per record before."""
    from flink_tpu.ops.window import preagg_combine

    devices = 4
    rng = np.random.default_rng(11)
    known = {1000 + 3 * j: int(s) for j, s in enumerate(
        rng.permutation(devices * SLOTS)[:150])}
    assert max(known.values()) >= 3 * SLOTS      # the last block is used
    c = make(n=5 * BLOCK + 3, table=known, ts=_spread(3),
             keys=lambda rng, n: 1000 + 3 * rng.integers(0, 150, n))
    ws = nc.PreaggWorkspace(devices * SLOTS * RING, 0)
    res, miss = _run_native(c, _table(known), ws)
    assert len(miss) == 0
    pairs, counts = nc.ingest_fused_finalize_pairs_native(res, ws)
    slots = np.array([known[k] for k in c["keys"].tolist()], np.int64)
    panes = c["ts"] // PANE_MS
    want_pairs, want_counts, _ = preagg_combine(
        slots, panes % RING, np.ones(len(slots), bool), {}, (),
        ring=RING, domain=ws.domain)
    order = np.argsort(pairs)
    assert pairs[order].tolist() == want_pairs.tolist()
    assert counts[order].tolist() == want_counts.tolist()
    assert pairs.max() >= SLOTS * RING > 0       # past one block's domain
    assert not ws.hist.any()


def test_pane_moves_count_the_mechanism():
    """In order the cursor moves once a pane; alternating panes move it on
    every record (and cost what the division always did)."""
    n = 4 * BLOCK
    c = dict(keys=_keys(np.random.default_rng(1), n), offset_ms=0,
             dead=I64.min, refire_below=I64.min, bits=0, cap=1 << 12,
             miss_cap=None)
    for ts, want in ((_pane_change_at(n // 2)(None, n), 2),
                     (_alternating(None, n), n)):
        ws = nc.PreaggWorkspace(SLOTS * RING, 0)
        res, _ = _run_native({**c, "ts": ts}, _table(_known()), ws)
        assert res.stats[8] == want
        nc.ingest_fused_finalize_pairs_native(res, ws)


def _keys_in_blocks(rng, n):
    """Record i holds known key ``i * 40 // n``: 40 distinct pairs in all,
    a contiguous share of them in any record range."""
    return 1000 + 3 * (np.arange(n, dtype=np.int64) * 40 // n)


@pytest.mark.parametrize("threads", THREADS)
@pytest.mark.parametrize("which,kw", [
    ("pair_cap", dict(cap=8)),
    ("miss_cap", dict(keys=_with_unknown, miss_cap=3)),
    ("pair_cap_in_a_later_block", dict(cap=45, ts=_pane_change_at(
        2 * BLOCK + 9))),
    # every range of 2 or more holds 21 pairs or fewer, their union 40
    ("pair_cap_reached_only_by_the_merge", dict(cap=30,
                                                keys=_keys_in_blocks)),
    # the unknown keys lie in the middle range of three
    ("miss_cap_in_a_middle_range", dict(miss_cap=3, keys=_unknown_at(
        lambda n: range(n // 2 - 3, n // 2 + 3)))),
])
def test_overflow_returns_none_and_rezeroes(which, kw, threads):
    c = make(**kw)
    st = RefScan(0)
    rc = ref_scan(st, c["keys"], c["ts"], c["table"], 0, c["dead"],
                  c["refire_below"], c["cap"],
                  len(c["ts"]) if c["miss_cap"] is None else c["miss_cap"])
    assert rc == (-2 if which.startswith("miss_cap") else -1)
    ws = nc.PreaggWorkspace(SLOTS * RING, 0)
    assert _run_native(c, _table(c["table"]), ws, threads=threads) is None
    assert not ws.hist.any() and not ws.range_hist.any()
    # the workspace serves the next batch as if nothing had happened
    ok = make(n=BLOCK + 3)
    st = RefScan(0)
    want_miss = ref_scan(st, ok["keys"], ok["ts"], ok["table"], 0,
                         ok["dead"], ok["refire_below"], ok["cap"], BLOCK + 3)
    res, miss = _run_native(ok, _table(ok["table"]), ws, threads=threads)
    _check(res, miss, st, want_miss, ws, "pairs", BLOCK + 3, threads)


@pytest.mark.parametrize("threads", THREADS)
def test_split_scan_at_the_global_domain_of_the_mesh_cell(threads):
    """``nexmark_q5_mesh4``'s workspace: 4 devices x 8,192 slots x ring
    12 = 393,216 pair ids, each later range with a histogram of that
    size to itself. Slots up to the last device's block, three panes."""
    slots, n_keys = 4 * 8192, 3000
    rng = np.random.default_rng(5)
    known = {1000 + 3 * j: int(s) for j, s in enumerate(
        rng.choice(slots, n_keys, replace=False))}
    c = make(n=40 * BLOCK + 11, table=known, ts=_spread(3), cap=1 << 14,
             keys=lambda rng, n: 1000 + 3 * rng.integers(0, n_keys, n))
    ws = nc.PreaggWorkspace(slots * RING, 0)
    assert ws.domain == 393_216
    st = RefScan(0)
    assert ref_scan(st, c["keys"], c["ts"], known, 0, c["dead"],
                    c["refire_below"], c["cap"], 0) == []
    res, miss = _run_native(c, _table(known), ws, threads=threads)
    assert max(st.pairs) >= 3 * 8192 * RING      # the last device's block
    _check(res, miss, st, [], ws, "pairs", len(c["ts"]), threads)
    assert ws.range_hist.shape == (threads - 1, 393_216)


@pytest.mark.parametrize("n,threads,want", [
    (2 * FLOOR - 1, 4, 1),        # not two ranges' worth: the serial call
    (2 * FLOOR + 1, 4, 2),        # as many ranges as keep the floor
    (4 * FLOOR + 3, 4, 4),
    (4 * FLOOR + 3, 1, 1),        # host.parallelism = 1
], ids=["under_the_floor", "two_ranges_fit", "four_ranges_fit",
        "one_thread"])
def test_a_short_batch_stays_serial(monkeypatch, n, threads, want):
    """The floor the operator runs under (``SCAN_RANGE_MIN_RECORDS`` a
    range): a batch that cannot fill two ranges makes the serial call,
    which is what ``scan_ranges`` = 1 a batch reads."""
    monkeypatch.setattr(nc, "SCAN_RANGE_MIN_RECORDS", FLOOR)
    c = make(n=n, ts=lambda rng, n: _ordered(n, 100 * PANE_MS - 40, 90))
    ws = nc.PreaggWorkspace(SLOTS * RING, 0)
    res, _ = _run_native(c, _table(c["table"]), ws, threads=threads)
    assert res.ranges == want
    assert ws.range_hist.shape[0] == want - 1    # none made for a serial call
    serial, _ = _run_native(c, _table(c["table"]), nc.PreaggWorkspace(
        SLOTS * RING, 0))
    assert res.stats[:8].tolist() == serial.stats[:8].tolist()
    assert res.out_pairs[:res.npairs].tolist() == (
        serial.out_pairs[:serial.npairs].tolist())


# -- ingest_combine: the same loop for a caller that holds the slots --------

COMBINE_CASES = [c for c in FUSED_CASES if "unknown" not in c.id]


@pytest.mark.parametrize("c", COMBINE_CASES)
def test_ingest_combine_equals_reference(c):
    slots = np.array([c["table"][k] for k in c["keys"].tolist()], np.int64)
    st = RefScan(c["bits"])
    assert ref_scan(st, c["keys"], c["ts"], c["table"], c["offset_ms"],
                    c["dead"], c["refire_below"], c["cap"], 0) == []
    ws = nc.PreaggWorkspace(SLOTS * RING, 0)
    pairs, counts, stats, bitmap = nc.ingest_combine_native(
        c["ts"], slots, PANE_MS, c["offset_ms"], RING, ws, c["cap"],
        c["dead"], c["refire_below"], c["bits"])
    assert pairs.tolist() == st.pairs
    assert counts.tolist() == [st.hist[p] for p in st.pairs]
    assert stats.tolist() == st.stats(0)[:6]
    assert bitmap.tolist() == st.bitmap.tolist()
    assert not ws.hist.any()


def test_ingest_combine_cap_overflow():
    c = make(cap=8)
    slots = np.array([c["table"][k] for k in c["keys"].tolist()], np.int64)
    ws = nc.PreaggWorkspace(SLOTS * RING, 0)
    assert nc.ingest_combine_native(
        c["ts"], slots, PANE_MS, 0, RING, ws, 8, c["dead"],
        c["refire_below"], 0) is None
    assert not ws.hist.any()
