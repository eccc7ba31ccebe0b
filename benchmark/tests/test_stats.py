import math

from benchmark.harness import stats


def test_percentile_is_nearest_rank_over_all_values():
    v = list(range(1, 101))
    assert stats.percentile(v, 90) == 90
    assert stats.percentile(v, 100) == 100
    assert stats.percentile([5], 90) == 5
    assert stats.percentile([], 90) is None
    assert stats.median([3, 1, 2]) == 2 and stats.median([1, 2, 3, 4]) == 2.5


def test_a_stall_in_the_window_lowers_the_rate_and_raises_the_tail():
    # 100 steps of 0.1 s, or the same with one 5 s stall: the rate is over
    # ALL the time of the window, so the stall shows (a median of chunk
    # rates would not move)
    steady = [0.1] * 100
    stalled = [0.1] * 99 + [5.0]
    r0 = stats.rate(100 * 8192, sum(steady))
    r1 = stats.rate(100 * 8192, sum(stalled))
    assert r1 < 0.7 * r0
    assert stats.median(steady) == stats.median(stalled)
    # latencies: requests that waited behind a stall raise the tail
    lat = [100.0] * 88 + [3000.0] * 12
    assert stats.percentile(lat, 90) == 3000.0
    assert stats.percentile([100.0] * 100, 90) == 100.0


def test_a_request_that_never_answers_counts_as_missing_the_tail():
    assert stats.tail_with_misses([1.0] * 95, 5, 90) == 1.0
    assert math.isinf(stats.tail_with_misses([1.0] * 85, 15, 90))
