"""What a round reads after its first result has landed, in milliseconds a
round: ``stats()`` ``loop_round_read_s`` over ``fused_rounds``, as deltas
over the window (PR 39).  ``round_read`` is the phase nested in
``round_wait`` that begins when the read the loop makes first (the round's
tokens) has returned: the other reads to the host (the counts, the steps
run, the experts touched, the pairs), the attended positions summed over
the snapshot and the counters taken under the lock.  Until PR 39 this
time read as ``round_wait``, "the host blocked on the device", so
``engine.device_wait_share`` reads lower by this metric's share of a round
from PR 39 on.  It is the part of ``engine.turnaround_ms`` that one
``device_get`` of all results, or copies started at dispatch, could take
away.  A verify round's and a prefill's first-token read count into the
sum and not into the rounds: the cells run neither speculation nor a read
after a chunk's token.  None where the program keeps no such phase or ran
no round in the window."""

from benchmark.lib import window


def read(run):
    mean = window.mean_per(run, "loop_round_read_s", "fused_rounds")
    return None if mean is None else 1e3 * mean
