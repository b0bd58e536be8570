"""The arithmetic from completion stamps to the end-to-end metrics, on lists
worked by hand."""

import pytest

from harness import stats


def test_percentile_interpolates_between_closest_ranks():
    xs = [10.0, 20.0, 30.0, 40.0, 50.0]
    assert stats.percentile(xs, 0) == 10.0
    assert stats.percentile(xs, 50) == 30.0
    assert stats.percentile(xs, 90) == pytest.approx(46.0)   # rank 3.6
    assert stats.percentile(xs, 100) == 50.0
    assert stats.percentile([7.0], 90) == 7.0
    assert stats.percentile([3.0, 1.0, 2.0], 50) == 2.0      # sorts a copy


def test_percentile_of_nothing_raises():
    with pytest.raises(ValueError):
        stats.percentile([], 90)


def test_intervals_one_per_completed_step():
    assert stats.intervals(100.0, [100.5, 100.75, 101.75]) == \
        [0.5, 0.25, 1.0]


def test_rate_is_all_samples_over_all_the_window():
    # 4 steps of 256 samples completed 2 s after the window began, 4 chips
    stamps = [10.5, 11.0, 11.5, 12.0]
    assert stats.samples_per_s_per_chip(10.0, stamps, 256, 4) == \
        pytest.approx(4 * 256 / 2.0 / 4)
    with pytest.raises(ValueError):
        stats.samples_per_s_per_chip(10.0, [], 256, 1)


def test_cycle_means_take_whole_cycles_only():
    assert stats.cycle_means([5.0], 2) == []
    assert stats.cycle_means([5.0, 3.0, 9.0, 2.0, 1.0], 2) == [4.0, 5.5]
