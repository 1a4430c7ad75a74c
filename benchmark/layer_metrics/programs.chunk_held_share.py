"""Share of the view positions the window's prefill chunks gathered and
scored that a chunk's last real row could see (``stats()``
prefill_positions_held / prefill_positions_scored, as deltas), in
percent: 100 where the chunk program visits only what the slot holds,
held length over table length where it passes over the whole view.  A
program without the counters (before PR 38) gives nothing."""

from benchmark.lib import stats


def read(run):
    if "prefill_positions_scored" not in run["counters"]["before"]:
        return None
    scored = stats.delta(run, "prefill_positions_scored")
    if not scored:
        return None
    return 100.0 * stats.delta(run, "prefill_positions_held") / scored
