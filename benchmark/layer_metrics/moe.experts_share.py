"""Share of the own device time of the two engine programs
(``decode_rounds``, ``prefill_chunk_into_slot``) spent in the sparse
feed-forward, in percent, over the traced part of the window
(``lib/trace_spans.py``): operations under the scopes ``kft.moe_route``
(scores, choice, the sort by expert and its undoing) and
``kft.moe_experts``, and the grouped products themselves.  Those reach the
trace WITHOUT the scope they were written under: the chip's compiler turns
``jax.lax.ragged_dot`` into a kernel of its own and names it
``ragged-dot-...`` in the scope's place (found on a v5e, PR 33:
``programs.scope_unowned_share`` reads them as unowned), so they are told
by that name."""

from benchmark.lib import trace_reduce, trace_spans

MODULES = ("jit_decode_rounds", "jit_prefill_chunk_into_slot")
SCOPES = ("kft.moe_route", "kft.moe_experts")
KERNEL = "ragged-dot"


def read(run):
    ops = trace_spans.busiest_ops(run)
    if ops is None:
        return None
    total = sparse = 0.0
    scoped = False
    for (name, module, scope), seconds in trace_spans.own_times(ops).items():
        if module not in MODULES:
            continue
        total += seconds
        scoped = scoped or scope in SCOPES
        if scope in SCOPES or trace_reduce.short_name(name).startswith(
                KERNEL):
            sparse += seconds
    # A program without the scopes has no sparse feed-forward to read.
    return 100.0 * sparse / total if total and scoped else None
