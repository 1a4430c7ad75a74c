"""``lib/traced_rounds.py``'s whole calls for a stack with sparse experts:
each traced ``decode_rounds`` call with its own ``steps``, ``attended`` AND
``experts_touched`` (the distinct experts that got a row, summed over the
sparse layers and the call's steps: the device's own count, which the
engine writes on the round's ``round_wait`` annotation beside the other
two).  The matching is that module's, word for word (a call belongs to the
round its middle falls in; calls cut by the trace's edge and rounds the
trace did not catch are dropped); it is repeated here because that module
hands on two facts only and is an accepted file (PERF.md section 7 (a1)).
Returns nothing where the program states no ``experts_touched``.
"""

from . import counts_lfm2, peaks, trace_spans, traced_rounds

FACTS = ("steps", "attended", "experts_touched")


def whole_calls(run, module=traced_rounds.MODULE):
    """[{"seconds", "steps", "attended", "experts_touched"}] or None."""
    spans = trace_spans.of_run(run)
    if not spans:
        return None
    trace = run["trace"]
    plane = max(trace["planes"].values(), key=lambda p: p["busy_s"])
    rounds = {}
    for phase, start, dur, facts in spans["phases"]:
        if phase == "round_dispatch" and "width" in facts:
            rounds.setdefault(facts.get("round"), {}).update(start=start)
        elif phase == "round_wait" and all(f in facts for f in FACTS):
            rounds.setdefault(facts.get("round"), {}).update(
                end=start + dur, **{f: facts[f] for f in FACTS})
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
                out.append({"seconds": dur / 1e9,
                            **{f: r[f] for f in FACTS}})
                break
    return out or None


def roofline_share(run):
    """The traced whole calls' least time
    (``counts_lfm2.decode_round_seconds``) over their device time, in
    percent, or None where the trace holds no such call.  Steps, positions,
    experts and time are the same calls', and an expert is counted only
    if the device gave it a row: this reads over 100 % only where the
    counts are wrong."""
    calls = whole_calls(run)
    if not calls:
        return None
    kind = run["device"]["kind"]
    flops = peaks.peak(kind, "bf16_flops_per_s")
    bytes_per_s = peaks.peak(kind, "hbm_bytes_per_s")
    least = sum(counts_lfm2.decode_round_seconds(
        run["config"], c["steps"], c["attended"], c["experts_touched"],
        flops, bytes_per_s)[0] for c in calls)
    return 100.0 * least / sum(c["seconds"] for c in calls)
