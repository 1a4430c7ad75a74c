"""The engine's cumulative counters read over the window: ``stats()`` is
snapshotted when the window opens (``before``) and closes (``at_close``),
and a window's figure is the difference of the two.  Every function returns
None where the program keeps no such counter (a commit from before PR 24),
so that a reader built on it leaves its metric out there."""


def grown(run, name):
    """How much the cumulative counter ``name`` grew over the window."""
    c = run["counters"]
    if name not in c["before"] or name not in c["at_close"]:
        return None
    return c["at_close"][name] - c["before"][name]


def mean_per(run, total, count):
    """Growth of ``total`` per unit of growth of ``count`` (seconds summed
    over requests / requests): the window's mean."""
    sum_, n = grown(run, total), grown(run, count)
    return sum_ / n if sum_ is not None and n else None


def share_of_window(run, name):
    """Growth of a counter of seconds over the window's length."""
    seconds = grown(run, name)
    return None if seconds is None else seconds / run["window"]["seconds"]
