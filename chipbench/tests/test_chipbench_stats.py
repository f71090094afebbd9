"""Percentiles count failures as infinite; the window runs from slice end
to slice end; the spread is the quartile distance over the median."""
import math
import statistics

import pytest

from chipbench import stats


def test_percentile_nearest_rank():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 95) == 95
    assert stats.percentile(xs, 50) == 50
    assert stats.percentile([7.0], 95) == 7.0
    assert math.isnan(stats.percentile([], 95))


@pytest.mark.parametrize("n_failed, want", [(4, 95.0), (5, 95.0),
                                            (6, math.inf)])
def test_failures_rank_above_everything(n_failed, want):
    xs = [float(i) for i in range(1, 101 - n_failed)] + [math.inf] * n_failed
    assert stats.percentile(xs, 95) == want


def test_window_from_slice_ends():
    ends = [0.5, 1.2, 3.0, 4.4, 6.1, 9.9]
    assert stats.window(ends, 1.0, 7.0) == (1.2, 6.1)
    with pytest.raises(ValueError):
        stats.window(ends, 6.5, 9.0)


def test_window_rate_counts_whole_slices():
    slices = [(1.2, 100), (3.0, 40), (4.4, 60), (6.1, 80), (9.9, 999)]
    a, b = stats.window([t for t, _ in slices], 1.0, 7.0)
    # the slice ending at the open is outside; those ending by the close in
    assert stats.window_rate(slices, a, b) == pytest.approx(180 / 4.9)


def test_spread():
    v = [10.0, 11.0, 9.0, 10.5, 9.5, 10.0]
    q1, med, q3 = statistics.quantiles(v, n=4)
    assert stats.spread(v) == pytest.approx((q3 - q1) / med)
