"""As ``client.ttft_p50_ms`` but the 95th percentile: with some tens of
requests in a window this is nearly the largest; recorded, not judged."""

from benchmark.lib import stats


def read(run):
    values = stats.ttfts(run)
    return 1e3 * stats.percentile(values, 0.95) if values else None
