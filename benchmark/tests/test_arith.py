import pytest

from arith import percentile, token_rate


def test_percentile_interpolates():
    v = [10.0, 20.0, 30.0, 40.0, 50.0]
    assert percentile(v, 50) == 30.0
    assert percentile(v, 90) == pytest.approx(46.0)
    assert percentile(v, 0) == 10.0 and percentile(v, 100) == 50.0
    assert percentile([7.0], 90) == 7.0
    assert percentile([], 50) is None


def test_token_rate_counts_by_arrival_not_by_finish():
    t0, seconds = 100.0, 10.0
    requests = [
        # wholly inside: 4 frames, 40 tokens
        ([101.0, 102.0, 103.0, 104.0], 40),
        # started before the window: 2 of its 4 frames arrive inside
        ([98.0, 99.5, 100.0, 100.5], 40),
        # ends after the window: 1 of 4 frames inside (109.9), 110.0 is outside
        ([109.9, 110.0, 111.0, 112.0], 40),
        # never finished (token count unknown): leaves nothing
        ([105.0], None),
        # wholly outside
        ([120.0, 121.0], 32),
    ]
    assert token_rate(requests, t0, seconds) == pytest.approx((40 + 20 + 10) / 10.0)
