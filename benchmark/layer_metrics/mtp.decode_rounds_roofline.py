"""The fused decode program of a stack whose multi-token-prediction module
drafts against its roofline, in percent: over the ``jit_decode_rounds``
calls the trace holds whole, the sum of their least times over the sum of
their device seconds (``lib/traced_mtp_rounds.decode_roofline_share``).  A
call's least time (``lib/counts_dotsvlm.decode_round_seconds``): per step
every weight that does not depend on the routing once and the head's slice
twice (the module's rows wait for the main rows' token), the three matrices
of each held expert the device counted as touched in the main layers and in
the module's, and each attended position's latent row (1,152 B) once a plane
for both rows of a slot, at the chip's published peaks."""


def read(run):
    from benchmark.lib import traced_mtp_rounds

    return traced_mtp_rounds.decode_roofline_share(run)
