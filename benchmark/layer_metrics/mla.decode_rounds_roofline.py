"""The fused decode program of a stack with latent attention, double layers
and a share of the experts against its roofline, in percent: over the
``jit_decode_rounds`` calls the trace holds whole, the sum of their least
times over the sum of their device seconds
(``lib/traced_latent_rounds.decode_roofline_share``).  A call's least time
(``lib/counts_longcat.decode_round_seconds``): per step every weight that
does not depend on the routing and the head's slice once, the three matrices
of each held expert the device counted as touched, and the attended
positions' latent rows (576 values a plane) in the 2 planes a double layer
owns, at the chip's published peaks."""


def read(run):
    from benchmark.lib import traced_latent_rounds

    return traced_latent_rounds.decode_roofline_share(run)
