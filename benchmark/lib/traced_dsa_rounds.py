"""The fused decode rounds a traced run holds whole, read for a stack whose
full layers choose their positions and whose window layers read a window
(``configs/dots3-note-prev-l5.json``): ``lib/traced_latent_rounds.py``'s
matching of calls to rounds (an accepted file), asked for this stack's facts,
and the reductions behind the ``dsa.*`` and ``window.*`` metrics.  Every
function returns None where the program states none of the facts (the parent
commit) or the trace holds no whole call.
"""

from . import counts_dots3, trace_spans, traced_latent_rounds

FACTS = ("steps", "experts_touched", "index_scored", "index_chosen",
         "window_read")
MODULE = "jit_decode_rounds"


def whole_calls(run):
    return traced_latent_rounds.whole_calls(run, FACTS)


def decode_roofline_share(run):
    """The traced whole calls' least time
    (``counts_dots3.decode_round_seconds``) over their device time, in
    percent.  Steps, positions, experts and time are the same calls'."""
    calls = whole_calls(run)
    if not calls:
        return None
    peaks = traced_latent_rounds._peaks(run)
    least = sum(counts_dots3.decode_round_seconds(
        run["config"], *(c[f] for f in FACTS), *peaks)[0] for c in calls)
    return 100.0 * least / sum(c["seconds"] for c in calls)


def scope_seconds(run, scopes, calls):
    """Own device seconds of ``jit_decode_rounds``'s operations under
    ``scopes`` that BEGAN inside ``calls``, and of all its operations there:
    ``(under, total)``."""
    ops = trace_spans.busiest_ops(run)
    if ops is None:
        return None
    inside = [op for op in ops if op[3] == MODULE and any(
        c["start"] <= op[1] < c["end"] for c in calls)]
    under = total = 0.0
    for (_, _, scope), seconds in trace_spans.own_times(inside).items():
        total += seconds
        if scope in scopes:
            under += seconds
    return (under, total) if total else None


def scope_share(run, scopes):
    """Share of the traced whole decode calls' own device time under
    ``scopes``, in percent."""
    calls = whole_calls(run)
    found = calls and scope_seconds(run, scopes, calls)
    return 100.0 * found[0] / found[1] if found else None


def sparse_roofline_share(run, scopes=("kft.mla_sparse",)):
    """The sparse read against its own roofline, in percent: the least time
    of reading the whole calls' chosen rows once
    (``counts_dots3.sparse_attention_seconds``) over the own device time of
    the operations under ``scopes`` inside those calls."""
    calls = whole_calls(run)
    found = calls and scope_seconds(run, scopes, calls)
    if not found or not found[0]:
        return None
    least = counts_dots3.sparse_attention_seconds(
        run["config"], sum(c["index_chosen"] for c in calls),
        *traced_latent_rounds._peaks(run))[0]
    return 100.0 * least / found[0]
