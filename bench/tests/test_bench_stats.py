"""Percentile, rate and failure arithmetic of the end-to-end metrics."""
import pytest

from lib.stats import latencies, percentile, rate


def test_percentile_interpolates_between_ranks():
    xs = list(range(1, 101))                       # 1..100
    assert percentile(xs, 50) == pytest.approx(50.5)
    assert percentile(xs, 95) == pytest.approx(95.05)
    assert percentile([3.0], 95) == 3.0
    with pytest.raises(ValueError):
        percentile([], 50)


def test_rate_over_a_window_that_ends_on_a_cycle_boundary():
    # 7 whole cycles of 2 members x 2048 tokens; the window closes at the
    # first cycle boundary after 1.0 s (cycles of 0.15 s end at 1.05 s)
    cycles, tokens = 7, 2 * 2048
    t0, t1 = 10.0, 10.0 + 7 * 0.15
    assert rate(cycles * tokens, t0, t1) == pytest.approx(
        7 * 4096 / 1.05)
    with pytest.raises(ValueError):
        rate(1, 2.0, 2.0)


def test_late_or_missing_request_counts_as_failed():
    due = [0.0, 1.0, 2.0, 3.0]
    done = [0.5, None, 2.25, 99.0]                  # one lost, one late
    lat, failed = latencies(due, done, deadline=60.0)
    assert failed == 2
    assert lat == [0.5, 0.25]

