"""Per request, (last token time - first token time) / (tokens - 1) at the
client over the tokens observed up to the cutoff, median over the window's
requests that got 8 tokens or more: what one user sees, beside the judged
time per token over all of them."""

from benchmark.lib import stats


def read(run):
    values = stats.tpots(run)
    return 1e3 * stats.percentile(values, 0.5) if values else None
