"""Wall time of the window per decode step the engine ran in it (``stats()``
``steps`` as a delta over the window): the step itself, the prefill chunks
and the host's work between steps, and any time in which nothing decoded."""

from benchmark.lib import stats


def read(run):
    steps = stats.delta(run, "steps")
    return 1e3 * run["window"]["seconds"] / steps if steps else None
