"""Share of the own device time of the two engine programs
(``decode_rounds``, ``prefill_chunk_into_slot``) spent in operations under
no ``kft.*`` scope, in percent, over the traced part of the window
(``lib/trace_spans.py``): what the compiler added or moved out of the
scopes.  Logs the unowned operations that took most."""

from benchmark.lib import trace_reduce, trace_spans

MODULES = ("jit_decode_rounds", "jit_prefill_chunk_into_slot")


def read(run):
    share = trace_spans.scope_share(run, MODULES, (None,))
    if share is not None:
        total = {}
        for (name, module, scope), seconds in trace_spans.own_times(
                trace_spans.busiest_ops(run)).items():
            if scope is None and module in MODULES:
                key = trace_reduce.short_name(name)
                total[key] = total.get(key, 0.0) + seconds
        for key, seconds in sorted(total.items(), key=lambda kv: -kv[1])[:8]:
            print(f"unowned {seconds:.4f} s {key}", flush=True)
    return share
