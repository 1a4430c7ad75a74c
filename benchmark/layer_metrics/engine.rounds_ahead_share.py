"""Share of the window's loop iterations whose decode round was dispatched
while the round before it was still unread, in percent: ``stats()``
``rounds_ahead`` over ``loop_rounds``, as deltas over the window (PR 49).
The engine's loop keeps ONE round in flight where another follows: it
dispatches round N+1 (and the chunk before it) and only then waits for
round N's results, so the read, the drain to the clients, the accounting
and the next admission run beside a round on the device and not after one.
A round counts here when the loop got that far ahead of it; the rounds it
did not get ahead of (the first after an idle stretch, one after which no
slot stayed live, a stopping engine's) are the ones whose first result
opens a turnaround (``engine.turnaround_ms`` is their mean), and an
iteration that only admitted or prefilled a chunk is in the denominator
too.  Near 100 in a closed loop that keeps its slots busy (docqa 89: its
cold documents prefill with no slot live), and high in an open loop under
its knee too (chat 93): an idle engine sits in ONE iteration's
``wait_work``, and within a request every round but the first is ahead.
None where the program keeps no such counter (before PR 49) or ran no
iteration in the window."""

from benchmark.lib import window


def read(run):
    share = window.mean_per(run, "rounds_ahead", "loop_rounds")
    return None if share is None else 100.0 * share
