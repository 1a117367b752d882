import math

import pytest

from bench import stats


def test_job_s_is_window_over_jobs():
    assert stats.job_s(31.5, 10) == pytest.approx(3.15)
    with pytest.raises(ValueError):
        stats.job_s(3.0, 0)


def test_p95_counts_failures_as_misses():
    lat = [float(i) for i in range(1, 101)]           # 1..100 ms
    assert stats.percentile_with_misses(lat, 95) == 95.0
    # five misses take the top five ranks: the 95th is still a latency
    assert stats.percentile_with_misses(lat[:95] + [None] * 5, 95) == 95.0
    # six misses reach into the 95th percentile
    assert math.isinf(stats.percentile_with_misses(lat[:94] + [None] * 6,
                                                   95))
    with pytest.raises(ValueError):
        stats.percentile_with_misses([], 95)


def test_completed_rate_counts_only_correct_completions():
    assert stats.completed_rate(380, 20.0) == 19.0
    with pytest.raises(ValueError):
        stats.completed_rate(1, 0.0)
