"""Share of the drafts of the model's own multi-token-prediction module that
the window's decode steps took (the step then yielded two tokens), in
percent: the engine's ``mtp_accepted`` over ``mtp_drafted`` as ``stats()``
deltas over the window (``lib/window.py``).  None where the program keeps no
such counters (a commit before the module) or verified no draft."""

from benchmark.lib import window


def read(run):
    drafted = window.grown(run, "mtp_drafted")
    accepted = window.grown(run, "mtp_accepted")
    if not drafted or accepted is None:
        return None
    return 100.0 * accepted / drafted
