"""Share of the own device time of the two engine programs
(``decode_rounds``, ``prefill_chunk_into_slot``) spent under the scopes
``kft.short_conv`` (a convolution layer's projections, gate and taps) and
``kft.conv_state`` (the per-slot state's read and write), in percent, over
the traced part of the window (``lib/trace_spans.py``)."""

from benchmark.lib import trace_spans

MODULES = ("jit_decode_rounds", "jit_prefill_chunk_into_slot")


def read(run):
    return trace_spans.scope_share(run, MODULES,
                                   ("kft.short_conv", "kft.conv_state"))
