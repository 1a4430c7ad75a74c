"""The fused decode rounds a traced run holds whole, read for a stack whose
multi-token-prediction module drafts (``configs/dots.vlm1.inst-l5.json``):
``lib/traced_latent_rounds.py``'s matching of calls to rounds (an accepted
file), asked for this stack's facts, and the reductions behind the ``mtp.*``
metrics.  Every function returns None where the program states none of the
facts (the parent commit) or the trace holds no whole call.

``kft.mtp_draft`` is an OUTER scope: the module's layer keeps the inner
names the main layers have (``kft.mla_q``, ``kft.moe_route``, ...), and
``trace_spans`` keeps an operation's innermost scope alone.  So the share
under the module is read from the operations' whole ``tf_op`` paths, which
``trace_spans.op_names`` gives by (program id, instruction text).
"""

from . import (
    counts_dotsvlm,
    trace_reduce,
    trace_spans,
    traced_latent_rounds,
)

FACTS = ("steps", "attended", "experts_touched", "mtp_drafted")
MODULE = "jit_decode_rounds"
DRAFT_SCOPE = "kft.mtp_draft"
KERNEL = "paged_latent_decode_attention"


def whole_calls(run):
    return traced_latent_rounds.whole_calls(run, FACTS)


def decode_roofline_share(run):
    """The traced whole calls' least time
    (``counts_dotsvlm.decode_round_seconds``) over their device time, in
    percent.  Steps, positions, experts and time are the same calls'."""
    calls = whole_calls(run)
    if not calls:
        return None
    peaks = traced_latent_rounds._peaks(run)
    least = sum(counts_dotsvlm.decode_round_seconds(
        run["config"], c["steps"], c["attended"], c["experts_touched"],
        *peaks)[0] for c in calls)
    return 100.0 * least / sum(c["seconds"] for c in calls)


def kernel_roofline_share(run):
    """The latent decode kernel as a drafting step calls it (two query
    positions a slot, six planes) against its own roofline, in percent
    (``counts_dotsvlm.latent_attention_seconds`` over the device time of the
    kernel's operations that began inside the whole calls)."""
    calls = whole_calls(run)
    ops = trace_spans.busiest_ops(run)
    if not calls or ops is None:
        return None
    spent = sum(dur / 1e9 for name, start, dur, _, _ in ops
                if trace_reduce.short_name(name).startswith(KERNEL)
                and any(c["start"] <= start < c["end"] for c in calls))
    if not spent:
        return None
    least = counts_dotsvlm.latent_attention_seconds(
        run["config"], sum(c["attended"] for c in calls),
        *traced_latent_rounds._peaks(run))[0]
    return 100.0 * least / spent


def _paths(run):
    """{instruction text: tf_op} of the decode program on the busiest
    device plane, or None."""
    spans = trace_spans.of_run(run)
    if not spans or not any(spans["ops"].values()):
        return None
    plane = max(spans["ops"], key=lambda p: sum(
        op[2] for op in spans["ops"][p]))
    modules = run["trace"]["planes"][plane]["modules"]
    programs = set()
    for name, _, _ in modules:
        if trace_reduce.module_name(name) == MODULE:
            tail = name[len(MODULE):].strip("()")
            if tail.isdigit():
                programs.add(int(tail))
    table = trace_spans.op_names(trace_spans.newest_xplane()).get(plane, {})
    return {text: path for (program, text), path in table.items()
            if program in programs}


def draft_share(run):
    """Share of the traced whole decode calls' own device time spent under
    ``kft.mtp_draft`` (the module's forward of a decode step: embedding,
    projection, its attention and expert layer, its head and the argmax),
    in percent."""
    calls = whole_calls(run)
    ops = trace_spans.busiest_ops(run)
    paths = _paths(run)
    if not calls or ops is None or not paths:
        return None
    inside = [op for op in ops if op[3] == MODULE and any(
        c["start"] <= op[1] < c["end"] for c in calls)]
    under = total = 0.0
    for (text, _, _), seconds in trace_spans.own_times(inside).items():
        total += seconds
        if DRAFT_SCOPE in (paths.get(text) or "").split("/"):
            under += seconds
    return 100.0 * under / total if total and under else None
