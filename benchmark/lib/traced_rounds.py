"""The fused decode rounds that a traced run holds WHOLE, each with what the
program said about it.

One call of the ``decode_rounds`` program is one ``XLA Modules`` event on the
device; the engine's loop dispatches it inside a ``kft.engine.round_dispatch``
annotation (facts ``width``: the steps it may run, ``live``) and reads its
results inside the ``kft.engine.round_wait`` annotation of the same ``round``
(facts ``steps``: the steps the device ran, and ``attended``: the cache
positions those steps read, summed over slots and steps; both from the
device's own counts, so a slot that an EOS stopped early is not counted on).
A call lies between the start of
its dispatch and the end of its wait, and the loop's rounds do not overlap,
so each call finds its round by time: the round its MIDDLE falls in (on a
v5e the device's clock read 1-2 ms behind the host's, PR 26: a call seemed
to start before its own dispatch, and a step is 65 ms).

A reader that multiplies the WINDOW's mean steps per call by the TRACED
calls' mean time mixes two populations and can read over 100 % (it did:
``PERF.md`` section 6, PR 27); these are the traced calls' own steps.  Calls
cut by the trace's edge are dropped, and so is one whose round the trace did
not catch (an annotation is recorded only if it BEGAN while the trace ran).
Returns nothing where the program states no ``steps`` (a commit before PR 26).

``roofline_share`` is the one computation behind every metric that holds the
fused decode program against its roofline, dense stack or looped.
"""

from . import counts_looped, peaks, trace_spans

MODULE = "jit_decode_rounds"


def whole_calls(run, module=MODULE):
    """[{"seconds", "steps", "attended"}] or None."""
    spans = trace_spans.of_run(run)
    if not spans:
        return None
    trace = run["trace"]
    plane = max(trace["planes"].values(), key=lambda p: p["busy_s"])
    rounds = {}
    for phase, start, dur, facts in spans["phases"]:
        if phase == "round_dispatch" and "width" in facts:
            rounds.setdefault(facts.get("round"), {}).update(start=start)
        elif phase == "round_wait" and "steps" in facts:
            rounds.setdefault(facts.get("round"), {}).update(
                steps=facts["steps"], attended=facts.get("attended"),
                end=start + dur)
    rounds = sorted((r for r in rounds.values()
                     if "start" in r and "end" in r and r["steps"] > 0),
                    key=lambda r: r["start"])
    out = []
    for name, start, dur in plane["modules"]:
        if not name.startswith(module + "(") and name != module:
            continue
        if start <= trace["t0"] or start + dur >= trace["t1"]:
            continue  # cut by the trace's edge
        middle = start + dur // 2
        for r in rounds:
            if r["start"] <= middle <= r["end"]:
                out.append({"seconds": dur / 1e9, "steps": r["steps"],
                            "attended": r.get("attended")})
                break
    return out or None


def roofline_share(run):
    """The traced whole calls' least time over their device time, in
    percent, or None where the trace holds no whole call or the program
    states no ``attended``.  A call's least time
    (``counts_looped.decode_round_seconds``; with one loop step its counts
    are ``counts.py``'s to the parameter): its steps times every matmul
    weight once per loop step and the head once, plus the keys and values of
    the positions it attended in every plane, against the FLOPs of one
    sequence a step, at the chip's published peaks.  Steps, positions and
    time are the same calls', and the positions are live sequences' only
    (cached pages nobody reads are no traffic), so this reads over 100 %
    only where the counts are wrong.  No counter of the window enters it."""
    calls = whole_calls(run)
    if not calls or any(c["attended"] is None for c in calls):
        return None
    kind = run["device"]["kind"]
    flops = peaks.peak(kind, "bf16_flops_per_s")
    bytes_per_s = peaks.peak(kind, "hbm_bytes_per_s")
    least = sum(counts_looped.decode_round_seconds(
        run["config"], c["steps"], c["attended"], flops, bytes_per_s)[0]
        for c in calls)
    return 100.0 * least / sum(c["seconds"] for c in calls)
