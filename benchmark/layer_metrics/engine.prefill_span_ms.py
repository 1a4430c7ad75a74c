"""Mean time from slot claim to first token (a request's whole prefill:
its chunks, one a round, and the rounds between them) over the first tokens
delivered inside the window (``stats()`` ``prefill_span_s_sum`` /
``first_tokens``, as deltas)."""

from benchmark.lib import window


def read(run):
    mean = window.mean_per(run, "prefill_span_s_sum", "first_tokens")
    return None if mean is None else 1e3 * mean
