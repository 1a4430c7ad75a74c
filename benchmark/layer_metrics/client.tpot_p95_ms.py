"""As ``client.tpot_p50_ms`` but the 95th percentile: recorded, not judged."""

from benchmark.lib import stats


def read(run):
    values = stats.tpots(run)
    return 1e3 * stats.percentile(values, 0.95) if values else None
