import pytest

from benchmark import stats


@pytest.mark.parametrize("values,q,want", [
    ([1, 2, 3, 4, 5], 50, 3.0),
    ([1, 2, 3, 4], 50, 2.5),
    ([10, 20], 95, 19.5),
    ([7], 95, 7.0),
    (list(range(1, 101)), 95, 95.05),
])
def test_percentile_interpolates_between_closest_ranks(values, q, want):
    assert stats.percentile(values, q) == pytest.approx(want)


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_spread_is_the_contracts_quartile_distance_over_median():
    # statistics.quantiles([1..6], n=4) = 1.75, 3.5, 5.25
    assert stats.spread([1, 2, 3, 4, 5, 6]) == pytest.approx(3.5 / 3.5)
    assert stats.spread([100, 100, 100, 100, 100, 101]) < 0.003


def test_fire_latency_runs_from_the_due_time_of_the_releasing_event():
    # window end 5000, 1000 ms of out-of-orderness: the event stamped
    # 6000 lets the watermark pass; it is due 6.000 s after the window
    # opened (t_open = 100.0); the row arrived at 106.080 -> 80 ms
    first = {5000: 106.080, 6000: 107.300, 9000: 111.0}
    got = stats.fire_latencies_ms(first, 100.0, 1000, max_ts_ms=7500)
    # 9000 + 1000 > 7500: fired by the end-of-input flush, not a sample
    assert got == pytest.approx([80.0, 300.0])


def test_a_window_no_event_released_is_not_a_latency_sample():
    assert stats.fire_latencies_ms({5000: 1.0}, 0.0, 1000, 5999) == []
    assert len(stats.fire_latencies_ms({5000: 7.0}, 0.0, 1000, 6000)) == 1


def test_lag_slope_is_zero_for_a_sustained_rate_and_positive_when_behind():
    rel = [0.0, 1.0, 2.0, 3.0]
    assert stats.lag_slope_ms_per_s(rel, [0.002] * 4) == pytest.approx(0.0)
    assert stats.lag_slope_ms_per_s(rel, [0.0, 0.1, 0.2, 0.3]) == \
        pytest.approx(100.0)
    assert stats.lag_slope_ms_per_s(rel[:2], [0.0, 0.1]) is None
