"""Share of the loop thread's unblocked wall time in which it ran on a
core, in percent: ``stats()`` ``loop_cpu_s`` (``time.thread_time()`` of the
loop thread, stored once an iteration) over the nine ``loop_*_s`` phases
in which the thread waits for nothing (all but ``wait_work``, an idle
engine, and ``round_wait``, the host blocked on the device), as deltas
over the window (PR 39).  Near 100 the host work of a round RAN and only
less work makes it shorter; well under 100 the thread stood ready and
waited, for the interpreter lock (the client streams' threads, the
benchmark's sampler) or for a core of a machine that shares them: the
number that tells a slow run's "more work" from "a slower host".  The
thread's few microseconds of CPU inside the two blocked phases count
above the line and not below it, so a reading can pass 100 by a little.
None where the program keeps no such counter or the phases did not grow.
"""

from benchmark.lib import window

UNBLOCKED = ("admit", "housekeeping", "prefill_dispatch", "round_prepare",
             "round_dispatch", "overlap", "round_read", "drain", "account")


def read(run):
    cpu = window.grown(run, "loop_cpu_s")
    wall = [window.grown(run, f"loop_{phase}_s") for phase in UNBLOCKED]
    if cpu is None or None in wall or not sum(wall):
        return None
    return 100.0 * cpu / sum(wall)
