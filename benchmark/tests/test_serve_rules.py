"""The serving runner's own arithmetic, on hand-worked records: the judged
time per token over bursts, which requests count, and when a request still
running at the cutoff has failed."""

import pytest

from benchmark.lib import stats
from benchmark.runners import serve


def rec(arrivals, max_new=100, error=None, tags=None):
    tokens = [1] * sum(n for _, n in arrivals)
    return {"arrivals": arrivals, "tokens": tokens, "error": error,
            "first": arrivals[0][0] if arrivals else None,
            "last": arrivals[-1][0] if arrivals else None,
            "request": {"max_new": max_new, "tags": tags or {}}}


def test_time_per_token_counts_the_bursts_after_the_first():
    # Bursts of 8 tokens every second: 8 tokens were waited 1 s for, twice.
    a = rec([(10.0, 8), (11.0, 8), (12.0, 8)])
    assert stats.time_per_token_s([a], 0.0, 50.0) == pytest.approx(2.0 / 16)
    # A second request with one burst in the window adds nothing; one that
    # failed is left out; bursts outside the window are not seen.
    b = rec([(49.5, 8), (50.5, 8)])
    c = rec([(1.0, 1), (31.0, 1)], error="DeadlineExceeded")
    assert stats.time_per_token_s([a, b, c], 0.0, 50.0) == \
        pytest.approx(2.0 / 16)
    d = rec([(20.0, 1), (20.5, 4), (22.0, 4)])
    assert stats.time_per_token_s([a, d], 0.0, 50.0) == \
        pytest.approx((2.0 + 2.0) / (16 + 8))
    assert stats.time_per_token_s([b], 0.0, 50.0) is None


def test_the_ramp_is_not_counted_among_the_requests():
    run = {"records": [rec([(1.0, 1)], tags={"ramp": True}),
                       rec([(2.0, 1)])]}
    assert len(stats.counted(run)) == 1


@pytest.mark.parametrize("arrivals, failed", [
    ([], False),                                      # still queued
    ([(40.0 + 1.2 * i, 8) for i in range(17)], False),  # in step
    ([(40.0, 8), (41.0, 8), (42.0, 8)], True),        # stalled for 18 s
    ([(30.0, 1), (59.0, 1)], True),                   # crawls: 2 in 30 s
    ([(57.0, 8)], False),                             # only just started
])
def test_a_request_cut_at_the_cutoff(arrivals, failed):
    assert serve.cut_request_failed(rec(arrivals), 60.0, 0.15) is failed
