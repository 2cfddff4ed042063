"""Percentile, spread and interval arithmetic of the benchmark."""

import statistics
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench.stats import clip_intervals, gaps, percentile, spread, union_length  # noqa: E402


def test_percentile_is_the_nearest_rank():
    xs = list(range(1, 101))
    assert percentile(xs, 90) == 90
    assert percentile(xs, 95) == 95
    assert percentile(xs, 100) == 100
    assert percentile([5.0], 90) == 5.0
    assert percentile([3, 1, 2], 50) == 2
    # 10 values: the 90th percentile is the 9th smallest.
    assert percentile([10, 9, 8, 7, 6, 5, 4, 3, 2, 1], 90) == 9


def test_percentile_of_nothing_raises():
    with pytest.raises(ValueError):
        percentile([], 90)


def test_spread_is_the_quartile_distance_over_the_median():
    xs = [10.0, 10.5, 11.0, 9.5, 10.2, 10.1]
    q1, q2, q3 = statistics.quantiles(xs, n=4)
    assert spread(xs) == pytest.approx((q3 - q1) / q2)


def test_union_of_overlapping_intervals():
    assert union_length([(0, 1), (0.5, 2), (3, 4)]) == pytest.approx(3.0)
    assert union_length([(0, 4), (1, 2)]) == pytest.approx(4.0)
    assert union_length([]) == 0.0


def test_clip_and_gaps():
    ivs = [(0.0, 1.0), (2.0, 3.0), (2.5, 5.0)]
    assert clip_intervals(ivs, 0.5, 2.7) == [(0.5, 1.0), (2.0, 2.7), (2.5, 2.7)]
    assert gaps(ivs, -1.0, 6.0) == [(-1.0, 0.0), (1.0, 2.0), (5.0, 6.0)]
    assert gaps([], 0.0, 1.0) == [(0.0, 1.0)]
