"""Metric arithmetic: rates over the whole window, tails over all samples."""
import math

import pytest

import stats


def _log():
    lg = stats.TokenLog()
    lg.add(0, 1, 0.5)  # before the window
    lg.add(0, 1, 1.5)
    lg.add(0, 2, 2.0)
    lg.add(1, 1, 2.5)
    lg.add(1, 1, 4.5)  # after the window
    lg.due.update({0: 0.2, 1: 1.0, 2: 1.8})
    return lg


def test_rate_counts_tokens_in_the_window_only():
    lg = _log()
    assert lg.tokens_in(1.0, 3.0) == 4
    assert lg.tokens_in(1.0, 3.0) / 2.0 == 2.0


def test_gaps_end_in_the_window_and_are_per_token():
    assert sorted(_log().gaps_in(1.0, 3.0)) == [0.0, 0.5, 1.0]


def test_ttft_from_due_time_and_missing_requests():
    got, missing = _log().ttfts(1.0, 3.0)
    assert got == [1.5] and missing == 1


def test_percentile_is_over_every_sample():
    vals = list(range(1, 101))
    assert stats.percentile(vals, 95) == pytest.approx(95.05)
    assert math.isinf(stats.percentile([1.0] * 10 + [math.inf], 95))
    with pytest.raises(ValueError):
        stats.percentile([], 95)
