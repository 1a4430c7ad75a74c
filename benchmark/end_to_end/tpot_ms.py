"""Time per output token at the client, in milliseconds, over every token
handed to any request inside the window (``lib/stats.py:time_per_token_s``):
all the work and all the time, not a statistic over requests, so it does not
depend on which requests a window happens to hold."""

from benchmark.lib import stats


def read(run):
    w = run["window"]
    value = stats.time_per_token_s(run["records"], w["t0"], w["t1"])
    return None if value is None else 1e3 * value
