import math

import pytest

from benchmark import stats


def test_percentile_nearest_rank():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 95) == 95
    assert stats.percentile(xs, 50) == 50
    assert stats.percentile([5.0], 95) == 5.0
    assert stats.percentile([3, 1, 2], 100) == 3


def test_missing_request_is_worse_than_any_latency():
    xs = [10.0] * 18 + [None, None]  # 10% failed: the p95 is a miss
    assert stats.percentile(xs, 95) == math.inf
    assert stats.percentile(xs, 90) == 10.0


def test_whole_steps_over_elapsed_time_not_the_nominal_window():
    # steps of 0.4 s from t0 = 100; the window is 1.0 s: the third step
    # straddles the deadline, counts whole, and the time is what elapsed
    done = [100.4, 100.8, 101.2, 101.6]
    rate, n, elapsed = stats.whole_step_rate(done, 100.0, 8192, 101.0)
    assert n == 3 and elapsed == pytest.approx(1.2)
    assert rate == pytest.approx(3 * 8192 / 1.2)
    # the same steps against a window of 1.15 s or 0.85 s: the same rate
    assert stats.whole_step_rate(done, 100.0, 8192, 101.15)[0] == pytest.approx(rate)
    assert stats.whole_step_rate(done[:3], 100.0, 8192, 100.85)[0] == pytest.approx(rate)


def test_a_host_pause_moves_one_step_not_the_rate_of_the_rest():
    done = [0.4, 0.8, 1.5, 1.9, 2.3]  # one step 0.3 s late
    rate, n, elapsed = stats.whole_step_rate(done, 0.0, 100, 2.0)
    assert (n, elapsed) == (5, 2.3) and rate == pytest.approx(500 / 2.3)


def test_whole_step_rate_needs_a_step():
    with pytest.raises(ValueError):
        stats.whole_step_rate([], 0.0, 1, 1.0)


def test_spread_is_the_quartile_distance_over_the_median():
    xs = [100, 101, 102, 103, 104, 105]
    import statistics

    q1, _, q3 = statistics.quantiles(xs, n=4)
    assert stats.spread(xs) == pytest.approx((q3 - q1) / 102.5)
