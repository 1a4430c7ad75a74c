"""Time from when a request was due to its first token at the client, median
over every request due in the window; a request with no token by the cutoff
counts as the worst (the window plus the drain).  Recorded and not judged:
prefill is served first come first served, and between two orders of the
same requests this median differs by a third (PERF.md section 2)."""

from benchmark.lib import stats


def read(run):
    values = stats.ttfts(run)
    return 1e3 * stats.percentile(values, 0.5) if values else None
