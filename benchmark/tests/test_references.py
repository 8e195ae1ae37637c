"""The plain references on inputs small enough to work out by hand, and
the controls: what has to come out as NOT correct."""
import numpy as np
import pytest

from benchmark.run import load_json, load_module, HERE

Q5 = load_module("configs", "nexmark_q5")
fp = load_module("probes", "float_sum")
SUITE = load_json(HERE, "configs", "nexmark_q5.json")["params"]
# hand-worked streams use ids 1..3: a domain of 4 auction ids
Q5P = {"window_ms": 3000, "slide_ms": 1000, "out_of_orderness_ms": 1000,
       "auction_id_wrap": 4 - Q5.FIRST_AUCTION_ID}


def q5_stream():
    # panes (1 s): 0: a1 a1 a2 | 1: a2 | 2: a3 a3 a3 ; two batches
    return [({"auction": np.array([1, 1, 2, 2])},
             np.array([0, 500, 900, 1500])),
            ({"auction": np.array([3, 3, 3])},
             np.array([2000, 2100, 2999]))]


def q5_sink(rows):
    we, au, ct = zip(*rows)
    return [{"window_end": np.array(we), "auction": np.array(au),
             "bid_count": np.array(ct)}]


Q5_ANSWER = [  # 3 s windows sliding by 1 s; ties kept
    (1000, 1, 2),                  # panes [-2, 1): a1 x2, a2 x1
    (2000, 1, 2), (2000, 2, 2),    # panes [-1, 2): a1 x2, a2 x2
    (3000, 3, 3),                  # panes [0, 3): a3 x3
    (4000, 3, 3),                  # panes [1, 4): a2 x1, a3 x3
    (5000, 3, 3),                  # panes [2, 5)
]


def test_q5_reference_on_a_hand_worked_stream():
    counts = Q5.pane_counts(q5_stream(), 3, Q5P)
    assert counts.tolist() == [[0, 2, 1, 0], [0, 0, 1, 0], [0, 0, 0, 3]]
    assert list(zip(*(c.tolist() for c in Q5.hot_items(counts, Q5P)))) \
        == Q5_ANSWER
    res = Q5.check(q5_stream(), 2999, q5_sink(Q5_ANSWER[::-1]), Q5P)
    assert (res["rows_expected"], res["rows_got"]) == (6, 6)
    assert res["rows_missing"] == res["rows_not_in_reference"] == 0
    assert res["events_without_result"] == res["rows_duplicated"] == 0


@pytest.mark.parametrize("rows,missing,wrong,dup,lost", [
    (Q5_ANSWER[:-1], 1, 0, 0, 3),                               # a window never reached the sink
    (Q5_ANSWER[:3] + [(3000, 3, 2)] + Q5_ANSWER[4:], 1, 1, 0, 3),  # a count altered
    (Q5_ANSWER[:1] + Q5_ANSWER[2:], 1, 0, 0, 2),                # a tie dropped
    (Q5_ANSWER + Q5_ANSWER[:1], 0, 0, 1, 0),                    # a row committed twice
])
def test_q5_check_counts_what_differs(rows, missing, wrong, dup, lost):
    res = Q5.check(q5_stream(), 2999, q5_sink(rows), Q5P)
    assert (res["rows_missing"], res["rows_not_in_reference"],
            res["rows_duplicated"], res["events_without_result"]) == (
        missing, wrong, dup, lost)


def test_control_q5_at_most_once_delivery_is_not_correct():
    """THE CONTROL for the integer cells: the configuration guarantees
    that nothing is dropped. The reference put in the program's place
    with that guarantee broken (one batch of the stream never
    delivered) has to differ from the reference proper."""
    got = Q5.hot_items(Q5.pane_counts(q5_stream()[:1], 3, Q5P), Q5P)
    res = Q5.check(q5_stream(), 2999,
                   q5_sink(list(zip(*(c.tolist() for c in got)))), Q5P)
    assert res["rows_missing"] + res["rows_not_in_reference"] > 0


def test_records_come_from_the_seed_alone():
    big = 2**31 + 12345            # the driver's seeds pass 32 signed bits
    a = Q5.make_pool(big, 64, SUITE)
    b = Q5.make_pool(big, 64, SUITE)
    c = Q5.make_pool(big + 1, 64, SUITE)
    assert len(a) == SUITE["pool_batches"]
    assert set(a[0]) == set(Q5.SCHEMA)
    for f in Q5.SCHEMA:
        assert all(np.array_equal(x[f], y[f]) for x, y in zip(a, b))
        assert a[0][f].dtype == np.dtype(Q5.SCHEMA[f])
    assert not np.array_equal(a[0]["auction"], c[0]["auction"])
    assert not np.array_equal(a[0]["auction"], a[1]["auction"])


def test_bids_follow_the_suites_generator():
    """GeneratorConfig defaults: of 50 event ids 46 are bids and 3 are
    auctions; half of the bids go to the hot auction (the first of the
    newest auction's hundred), the rest uniformly to the ~100 in flight
    and 10 lead ids; three quarters of the bidders are the hot one."""
    n = 1 << 16
    p = dict(SUITE, auction_id_wrap=10**9)          # no wrap: the suite's ids
    bids = Q5.make_pool(7, n, p)[1]                 # bids n .. 2n - 1
    base0 = bids["auction"] - Q5.FIRST_AUCTION_ID
    newest = (n + np.arange(n)) // 46 * 3 + 2
    hot_id = newest // 100 * 100
    is_hot = base0 == hot_id
    assert 0.49 < is_hot.mean() < 0.52
    cold = base0[~is_hot] - newest[~is_hot]
    assert cold.min() == -100 and cold.max() == 10
    # uniform over the 111 ids: each offset holds about 1/111 of them
    share = np.bincount(cold + 100, minlength=111) / len(cold)
    assert share.min() > 0.6 / 111 and share.max() < 1.5 / 111
    people = (n + np.arange(n)) // 46 + 1
    hot_bidder = (people - 1) // 100 * 100 + 1 + Q5.FIRST_PERSON_ID
    assert 0.73 < (bids["bidder"] == hot_bidder).mean() < 0.77
    cold_b = bids["bidder"][bids["bidder"] != hot_bidder] - Q5.FIRST_PERSON_ID
    assert cold_b.min() >= people.min() - 1000 and cold_b.max() < people.max() + 10
    assert bids["price"].min() >= 100 and bids["price"].max() <= 10**8
    # log-uniform: a third of the prices in each pair of decades
    assert 0.30 < (bids["price"] < 10**4).mean() < 0.36
    assert 0.47 < (bids["channel"] < 4).mean() < 0.53
    assert bids["channel"].max() < 4 + 10_000


def test_auction_ids_wrap_into_the_key_directory_and_keep_the_hot_share():
    n = 1 << 16
    bids = Q5.make_pool(3, n, SUITE)[0]
    wrap = SUITE["auction_id_wrap"]
    assert wrap % Q5.HOT_AUCTION_RATIO == 0      # hot ids stay hot after it
    a = bids["auction"]
    assert a.min() >= Q5.FIRST_AUCTION_ID and a.max() < Q5.key_domain(SUITE)
    assert 0.49 < ((a - Q5.FIRST_AUCTION_ID) % 100 == 0).mean() < 0.53


# -- the float-lane probe and its control ------------------------------------

PROBE_P = dict(SUITE, auction_id_wrap=400)
PPW = SUITE["window_ms"] // SUITE["slide_ms"]
SLIDE = SUITE["slide_ms"]


def probe_input(seed, n=1 << 15):
    data = fp.records(Q5, seed, n, PROBE_P)
    return data, np.arange(n, dtype=np.int64) // 3      # ~10.9 s of event time


def probe_rows(win_sums, win_counts):
    e, k = np.nonzero(win_counts > 0)
    return [{"window_end": e * SLIDE, "key": k, "count": win_counts[e, k],
             "sum_price": win_sums[e, k]}]


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 7])
def test_probe_passes_float32_sums_and_fails_its_control(seed):
    """Sound: pane sums held in float32 and added in float32, as the
    program's fire does at Precision.HIGHEST. Control: the same through
    Precision.HIGH (two bfloat16 pieces per operand). The limit has to
    lie between the two with room on both sides."""
    data, ts = probe_input(seed)
    sm, cnt = fp.pane_sums(data, ts, PROBE_P)
    ref_c = fp.sliding(cnt, PPW)
    sound = fp.window_sums_f32(sm, PPW)
    control = fp.lower_precision_sums(sm, PPW)
    ok = fp.check_rows(probe_rows(sound, ref_c), data, ts, PROBE_P)
    bad = fp.check_rows(probe_rows(control, ref_c), data, ts, PROBE_P)
    assert ok["holds"] and ok["sum_max_rel_err"] * 3 < fp.SUM_RTOL
    assert not bad["holds"] and bad["counts_differing"] == 0
    assert bad["sum_max_rel_err"] > 3 * fp.SUM_RTOL
    # and bfloat16 products (the fault PR 21 found) are far outside
    bf16 = fp.window_sums_f32(fp.bf16_round(sm.astype(np.float32)), PPW)
    assert fp.gap(bf16, fp.sliding(sm, PPW)) > 1e-3


def test_bf16_rounding_is_round_to_nearest_even():
    x = np.array([1.0, 1.00390625, 1.005859375, 3.140625, -2.5],
                 np.float32)
    # 1 + 2^-8 is a tie between 1.0 and 1 + 2^-7: to even (1.0)
    assert fp.bf16_round(x).tolist() == [1.0, 1.0, 1.0078125, 3.140625,
                                          -2.5]


def test_probe_check_fails_on_a_wrong_count_a_missing_or_a_double_row():
    data, ts = probe_input(5)
    sm, cnt = fp.pane_sums(data, ts, PROBE_P)
    s, c = fp.window_sums_f32(sm, PPW), fp.sliding(cnt, PPW)
    rows = probe_rows(s, c)
    assert fp.check_rows(rows, data, ts, PROBE_P)["holds"]
    wrong = [dict(rows[0])]
    wrong[0]["count"] = rows[0]["count"].copy()
    wrong[0]["count"][3] += 1
    assert not fp.check_rows(wrong, data, ts, PROBE_P)["holds"]
    short = [{k: v[:-1] for k, v in rows[0].items()}]
    assert not fp.check_rows(short, data, ts, PROBE_P)["holds"]
    twice = [{k: np.concatenate([v, v[:1]]) for k, v in rows[0].items()}]
    assert not fp.check_rows(twice, data, ts, PROBE_P)["holds"]
