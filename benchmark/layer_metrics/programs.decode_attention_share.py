"""Share of the fused decode program's own device time spent under the
scopes ``kft.kv_view`` (the gather of every slot's row view of the pool)
and ``kft.attention`` (the products over it), in percent, over the traced
part of the window (``lib/trace_spans.py``)."""

from benchmark.lib import trace_spans


def read(run):
    return trace_spans.scope_share(run, ("jit_decode_rounds",),
                                   ("kft.kv_view", "kft.attention"))
