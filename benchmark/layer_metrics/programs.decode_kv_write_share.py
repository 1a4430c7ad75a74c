"""Share of the fused decode program's own device time spent under the
scope ``kft.kv_write`` (the scatter of the new keys and values into the
pool), in percent, over the traced part of the window
(``lib/trace_spans.py``).  Copies of the pool that the compiler adds round
the layer scan carry no scope: they read under
``programs.scope_unowned_share``."""

from benchmark.lib import trace_spans


def read(run):
    return trace_spans.scope_share(run, ("jit_decode_rounds",),
                                   ("kft.kv_write",))
