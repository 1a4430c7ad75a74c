"""Where the (row, choice) pairs of a window's decode steps fell: the engine's
``pairs_held`` (a routed expert this chip holds), ``pairs_zero`` (an expert
that needs no weights) and ``pairs_absent`` (an expert of another chip), the
device's own counts as ``stats()`` deltas over the window (``lib/window.py``).
"""

from . import window

PAIRS = ("pairs_held", "pairs_zero", "pairs_absent")


def share(run, name):
    """The pairs under ``name`` over all three, in percent; None where the
    program keeps no such counters or counted no pair."""
    grown = [window.grown(run, key) for key in PAIRS]
    if None in grown or not sum(grown):
        return None
    return 100.0 * grown[PAIRS.index(name)] / sum(grown)
