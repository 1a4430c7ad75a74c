"""The fused decode program of a stack with sparse experts against its
roofline, in percent: over the ``jit_decode_rounds`` calls the trace holds
whole, the sum of their least times over the sum of their device seconds
(``lib/traced_moe_rounds.roofline_share``).  A call's least time
(``lib/counts_lfm2.decode_round_seconds``): per step every weight that does
not depend on the routing and the head once, the three matrices of each
expert the device counted as touched, the attended keys and values in the
attention layers' planes and one sequence's convolution state, at the
chip's published peaks."""


def read(run):
    from benchmark.lib import traced_moe_rounds

    return traced_moe_rounds.roofline_share(run)
