"""The arithmetic of the end-to-end metrics, on series made by hand."""
import math

import pytest

from lib import stats


def test_one_stall_lowers_the_window_rate_and_stall_share_says_by_how_much():
    durations = [1.0] * 9 + [2.0]           # one stalled segment in ten
    window_rate, median_rate, stall = stats.segment_rate(durations, 100.0)
    assert window_rate == pytest.approx(1000.0 / 11.0)  # all work, all time
    assert median_rate == pytest.approx(100.0)          # the pace between
    assert stall == pytest.approx(1.0 - 10.0 / 11.0)    # 9.09 %
    assert window_rate == pytest.approx(median_rate * (1.0 - stall))


def test_a_shift_moves_the_median_and_leaves_no_stall():
    slow = [1.01] * 10                       # every segment 1 % slower
    window_rate, median_rate, stall = stats.segment_rate(slow, 100.0)
    assert window_rate == pytest.approx(100.0 / 1.01)
    assert median_rate == pytest.approx(window_rate)
    assert stall == pytest.approx(0.0, abs=1e-12)


def test_stall_share_is_never_negative_and_needs_real_segments():
    assert stats.segment_rate([1.0, 0.5, 1.0], 1.0)[2] == 0.0
    with pytest.raises(ValueError):
        stats.segment_rate([1.0], 1.0)
    with pytest.raises(ValueError):
        stats.segment_rate([1.0, 0.0], 1.0)


def test_quantile_interpolates_between_order_statistics():
    xs = [10.0, 20.0, 30.0, 40.0, 50.0]
    assert stats.quantile(xs, 0.5) == 30.0
    assert stats.quantile(xs, 0.9) == pytest.approx(46.0)
    assert stats.quantile([7.0], 0.9) == 7.0


def test_failed_requests_count_as_the_worst():
    ok = [100.0] * 8
    # two of ten failed: the 90th percentile now sits among the failures
    with_failures = ok + [None, None]
    assert stats.tail_with_failures(ok + [100.0, 100.0], 5000.0, 0.9) == 100.0
    assert stats.tail_with_failures(with_failures, 5000.0, 0.9) == 5000.0
    # a median is untouched by two failures in ten, a tail is not
    assert stats.tail_with_failures(with_failures, 5000.0, 0.5) == 100.0
    with pytest.raises(ValueError):
        stats.tail_with_failures([], 1.0, 0.9)


def test_spread_is_the_contracts_interquartile_share():
    import statistics
    values = [100.0, 101.0, 99.0, 100.5, 99.5, 100.0]
    q1, _, q3 = statistics.quantiles(values, n=4)
    assert stats.spread(values) == pytest.approx((q3 - q1) / 100.0)
    assert not math.isnan(stats.spread(values))
