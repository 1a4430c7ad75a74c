"""The fused decode rounds a traced run holds WHOLE, each with its place in
time, for readers that need the operations INSIDE a call
(``lib/traced_rounds.py`` and ``lib/traced_moe_rounds.py`` hand on a call's
seconds and facts only, and are accepted files).  The matching is theirs: a
call of ``jit_decode_rounds`` belongs to the round whose dispatch-to-wait
span its middle falls in; calls cut by the trace's edge and rounds the trace
did not catch are dropped.  Returns nothing where the program states none of
the facts asked for (a commit without them).
"""

from . import counts_longcat, peaks, trace_reduce, trace_spans, traced_rounds

FACTS = ("steps", "attended", "experts_touched")
KERNEL = "paged_latent_decode_attention"


def whole_calls(run, facts=FACTS, module=traced_rounds.MODULE):
    """[{"start", "end" (ns), "seconds", <fact>...}] or None."""
    spans = trace_spans.of_run(run)
    if not spans:
        return None
    trace = run["trace"]
    plane = max(trace["planes"].values(), key=lambda p: p["busy_s"])
    rounds = {}
    for phase, start, dur, said in spans["phases"]:
        if phase == "round_dispatch" and "width" in said:
            rounds.setdefault(said.get("round"), {}).update(start=start)
        elif phase == "round_wait" and all(f in said for f in facts):
            rounds.setdefault(said.get("round"), {}).update(
                end=start + dur, **{f: said[f] for f in facts})
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
                out.append({"start": start, "end": start + dur,
                            "seconds": dur / 1e9,
                            **{f: r[f] for f in facts}})
                break
    return out or None


def _peaks(run):
    kind = run["device"]["kind"]
    return (peaks.peak(kind, "bf16_flops_per_s"),
            peaks.peak(kind, "hbm_bytes_per_s"))


def decode_roofline_share(run):
    """The traced whole calls' least time
    (``counts_longcat.decode_round_seconds``) over their device time, in
    percent, or None where the trace holds no such call.  Steps, positions,
    experts and time are the same calls'."""
    calls = whole_calls(run)
    if not calls:
        return None
    least = sum(counts_longcat.decode_round_seconds(
        run["config"], c["steps"], c["attended"], c["experts_touched"],
        *_peaks(run))[0] for c in calls)
    return 100.0 * least / sum(c["seconds"] for c in calls)


def kernel_roofline_share(run, kernel=KERNEL):
    """The latent decode kernel's share of its own roofline, in percent: the
    least time of attending the whole calls' ``attended`` positions in every
    plane (``counts_longcat.latent_attention_seconds``) over the device
    time of the operations named ``kernel`` that BEGAN inside those calls.
    None where the trace holds no whole call or no such operation (a
    program that attends another way, or a pool that is not on a TPU)."""
    calls = whole_calls(run, ("steps", "attended"))
    ops = trace_spans.busiest_ops(run)
    if not calls or ops is None:
        return None
    spent = 0.0
    for name, start, dur, _, _ in ops:
        if trace_reduce.short_name(name).startswith(kernel) and any(
                c["start"] <= start < c["end"] for c in calls):
            spent += dur / 1e9
    if not spent:
        return None
    least = counts_longcat.latent_attention_seconds(
        run["config"], sum(c["attended"] for c in calls), *_peaks(run))[0]
    return 100.0 * least / spent
