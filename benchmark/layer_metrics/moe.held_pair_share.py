"""Share of the window's (row, choice) pairs of the decode steps that fell
on a routed expert THIS chip holds (the rest chose a zero-compute expert or
an expert of another chip, whose part is left out), in percent: the engine's
``pairs_held`` over ``pairs_held + pairs_zero + pairs_absent``
(``lib/pairs.py``).  None where the program keeps no such counters or counted
no pair."""

from benchmark.lib import pairs


def read(run):
    return pairs.share(run, "pairs_held")
