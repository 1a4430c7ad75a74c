"""Share of the window's wall time in which the engine's loop thread did
nothing but wait for the chip (``stats()`` ``loop_round_wait_s`` as a delta
over the window's seconds), in percent.  What is missing to 100 is the
host's own work per round: as device steps get shorter this falls, and the
other ``loop_*_s`` sums say where the time went."""

from benchmark.lib import window


def read(run):
    share = window.share_of_window(run, "loop_round_wait_s")
    return None if share is None else 100.0 * share
