"""Mean time from slot claim to first token over the window's requests
that resumed a cached prefix (``stats()`` ``prefill_span_hit_s_sum`` /
``first_tokens_hit``, as deltas): what is left of a prefill when the
document's pages are shared."""

from benchmark.lib import window


def read(run):
    mean = window.mean_per(run, "prefill_span_hit_s_sum", "first_tokens_hit")
    return None if mean is None else 1e3 * mean
