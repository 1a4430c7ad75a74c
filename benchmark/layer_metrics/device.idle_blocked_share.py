"""Of the traced part's device idle time after the engine loop's first
annotation, the share that falls in ``round_wait``'s OWN stretch, in
percent (PR 39): the chip is done and the host has not been told yet,
i.e. the runtime's wake-up of the blocked thread and the first result's
transfer.  Idle gaps and their owners as ``device.idle_attributed_share``
takes them (``trace_reduce.idle_gaps`` over the busiest plane,
``trace_spans.attribute_gaps``: an inner annotation owns its stretch, so
``round_read`` and a ``round_wait`` inside ``drain`` each get theirs).
With the idle seconds, ``engine.round_read_ms`` and
``engine.turnaround_ms`` it splits a round's idle tail into wake-up and
first transfer / the other reads / drain to the next dispatch.

Logs the idle time of every phase in milliseconds a round, and the ONE
CLOCK check: over the turnarounds that lie whole inside the trace, the
program's own reading (the fact ``since_ready_us`` on the dispatching
annotation: first result on the host to the dispatching call's return)
beside the device's (its idle time from that ``round_read``'s start to
its next operation).  The two differ by the tail of the dispatching
call after it has launched the operation and by the skew of the two
clocks, which moves idle time between the two sides of a first result and
so moves this metric.  The same turnarounds bound the skew, and the
reader logs the bounds: an operation cannot start before the annotation
of its dispatch does, and its result cannot be on the host before it ends.

None for an untraced run and for a program whose ``round_wait`` still
holds the reads (``stats()`` without ``loop_round_read_s``: before PR 39);
0 where the trace holds idle time and none of it in ``round_wait``."""

import bisect

from benchmark.lib import trace_reduce, trace_spans

HANDS_WORK = ("round_dispatch", "prefill_dispatch")
# A turnaround's gap is milliseconds long; shorter ones lie between two
# operations of one program, and where the skew puts the next program's
# start before the first result they would be taken for the turnaround's.
LEAST_GAP_NS = 100_000


def turnarounds(phases):
    """[(ready_ns, the dispatching annotation's start_ns and end_ns,
    since_ready_us)] of the turnarounds the program counted: from the
    first ``round_read`` since the device was last handed work to the
    annotation that states ``since_ready_us``."""
    out, ready = [], None
    for phase, start, dur, facts in phases:
        if phase == "round_read" and ready is None:
            ready = start
        elif phase in HANDS_WORK:
            if "since_ready_us" in facts and ready is not None:
                out.append((ready, start, start + dur,
                            facts["since_ready_us"]))
            if phase == "round_dispatch" or facts.get("chunks"):
                ready = None
    return out


def gap_around(gaps, ready, until):
    """(start_ns, end_ns) of the idle gap a turnaround lies in: the first
    one that ends after ``ready`` (``gaps`` [(start_ns, duration_ns, ...)]
    in time order); None where that gap begins after ``until``."""
    ends = [start + dur for start, dur, *_ in gaps]
    i = bisect.bisect_right(ends, ready)
    if i == len(gaps) or gaps[i][0] >= until:
        return None
    return gaps[i][0], ends[i]


def read(run):
    spans = trace_spans.of_run(run)
    if not spans or not spans["phases"] \
            or "loop_round_read_s" not in run["counters"]["at_close"]:
        return None
    trace, phases = run["trace"], spans["phases"]
    first = min(start for _, start, _, _ in phases)
    plane = max(trace["planes"].values(), key=lambda p: p["busy_s"])
    gaps = trace_reduce.idle_gaps(plane["ops"], first, trace["t1"])
    owned, unattributed = trace_spans.attribute_gaps(gaps, phases)
    idle = sum(owned.values()) + unattributed
    rounds = sum(1 for p in phases if p[0] == "round_dispatch")
    print(f"idle by phase, ms a round over {rounds} rounds: " + ", ".join(
        f"{phase} {1e3 * seconds / max(1, rounds):.3f}" for phase, seconds
        in sorted(owned.items(), key=lambda kv: -kv[1])), flush=True)
    in_order = sorted(g for g in gaps if g[1] >= LEAST_GAP_NS)
    counted = turnarounds(phases)
    program = sum(us for *_, us in counted) / 1e6
    device, behind = 0, [float("-inf"), float("inf")]
    for ready, dispatch, until, _ in counted:
        gap = gap_around(in_order, ready, until)
        if gap is not None:
            device += gap[1] - max(ready, gap[0])
            behind[0] = max(behind[0], dispatch - gap[1])
            if gap[0] > first:  # not cut by where the gaps are read from
                behind[1] = min(behind[1], ready - gap[0])
    print(f"one clock: {len(counted)} turnarounds in the trace, the "
          f"program's since_ready_us {program:.4f} s, the device idle from "
          f"the first result to its next operation {device / 1e9:.4f} s; "
          f"the device's clock reads between {behind[0] / 1e6:.3f} and "
          f"{behind[1] / 1e6:.3f} ms behind the host's", flush=True)
    return 100.0 * owned.get("round_wait", 0.0) / idle if idle else None
