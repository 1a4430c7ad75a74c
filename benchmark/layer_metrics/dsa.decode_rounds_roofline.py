"""The fused decode program of a stack with an indexer, window layers and a
share of the experts against its roofline, in percent: over the
``jit_decode_rounds`` calls the trace holds whole, the sum of their least
times over the sum of their device seconds
(``lib/traced_dsa_rounds.decode_roofline_share``).  A call's least time
(``lib/counts_dots3.decode_round_seconds``): per step every weight that does
not depend on the routing and the head's slice once, the three matrices of
each held expert the device counted as touched, an index key a position
scored (256 B), a latent row a position chosen (1,152 B), a window row a
position read (2,176 B), at the chip's published peaks."""


def read(run):
    from benchmark.lib import traced_dsa_rounds

    return traced_dsa_rounds.decode_roofline_share(run)
