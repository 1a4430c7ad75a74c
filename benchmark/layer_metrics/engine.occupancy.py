"""Mean share of slots that held a live sequence per decode step in the
window (``stats()`` occupancy_sum / steps, as deltas), in percent."""

from benchmark.lib import stats


def read(run):
    steps = stats.delta(run, "steps")
    if not steps:
        return None
    c = run["counters"]
    occ = (c["at_close"]["mean_occupancy"] * c["at_close"]["steps"]
           - c["before"]["mean_occupancy"] * c["before"]["steps"]) / steps
    return 100.0 * occ / c["at_close"]["slots"]
