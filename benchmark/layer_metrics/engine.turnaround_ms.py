"""Mean turnaround between two rounds over the window, in milliseconds:
``stats()`` ``turnaround_s_sum`` / ``turnarounds`` as deltas (PR 39).  A
turnaround runs from the moment a round's FIRST result is on the host (the
end of ``round_wait``'s own stretch: the earliest the loop can know the
device is done) to the return of the next call that hands the device work
(a round's, a verify window's or a prefill chunk's program, whichever
comes first).  In between the synchronous loop leaves the device with
nothing queued, so this is the host's critical path of a round, read by
the program in every run and over the whole window, where the profiler
sees 5 s of one run.  It holds the round's other reads
(``engine.round_read_ms``), the drain to the clients, the accounting and
the next iteration's admission, chunk and round set-up; a turnaround that
the loop closed by going idle (nothing queued, no live slot) is not
counted.  The dispatching annotation carries the same reading as the fact
``since_ready_us``, which ``device.idle_blocked_share`` sums beside the
device's gaps.  None where the program keeps no such counter (before PR
39) or closed no turnaround in the window."""

from benchmark.lib import window


def read(run):
    mean = window.mean_per(run, "turnaround_s_sum", "turnarounds")
    return None if mean is None else 1e3 * mean
