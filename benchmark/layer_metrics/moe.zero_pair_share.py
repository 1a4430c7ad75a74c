"""Share of the window's (row, choice) pairs of the decode steps that chose
a zero-compute expert (a weighted copy of the row, no weights), in percent:
the engine's ``pairs_zero`` over ``pairs_held + pairs_zero + pairs_absent``
(``lib/pairs.py``).  None where the program keeps no such counters or counted
no pair."""

from benchmark.lib import pairs


def read(run):
    return pairs.share(run, "pairs_zero")
