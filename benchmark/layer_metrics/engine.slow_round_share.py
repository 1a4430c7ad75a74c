"""Share of the window's seconds that slow iterations of the engine's loop
took over an ordinary one, in percent: ``stats()`` ``slow_round_s_sum`` as
a delta over the window's length (PR 39).  The loop keeps a running mean
of an iteration's wall time (``wait_work`` left out; of the iterations
that waited for the device); one over 8 times it adds its excess over the
mean here, 1 to ``slow_rounds``, and one
``logging`` warning (at most one a second) with the iteration's own time
in each of the eleven phases, so that a round that held the loop 2 s among
rounds of 5 ms names its phase in the run it happens in.  0 in a run
without one.  A wide round after many narrow ones (8 steps after a mean of
1) can pass the factor by the device's own time: the warning's
``round_wait`` and ``steps`` say so.  None where the program keeps no such
counter."""

from benchmark.lib import window


def read(run):
    share = window.share_of_window(run, "slow_round_s_sum")
    return None if share is None else 100.0 * share
