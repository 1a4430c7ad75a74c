"""Mean time a request waited in the engine's queue, submit to slot claim,
over the requests admitted inside the window (``stats()``
``queue_wait_s_sum`` / ``admitted``, as deltas).  With
``engine.prefill_span_ms`` it splits the time to a first token that the
client clock sees whole."""

from benchmark.lib import window


def read(run):
    mean = window.mean_per(run, "queue_wait_s_sum", "admitted")
    return None if mean is None else 1e3 * mean
