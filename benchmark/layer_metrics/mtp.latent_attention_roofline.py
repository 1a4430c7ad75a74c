"""The latent form of the paged decode kernel as a drafting stack calls it
(two query positions a slot as rows of one call, six planes) against its own
roofline, in percent: the least time of reading each attended position's
latent row once a plane for both positions, or of the absorbed form's
operations at 256 query rows a slot if that is longer
(``lib/counts_dotsvlm.latent_attention_seconds``), over the device time of
the kernel's calls inside the traced whole ``decode_rounds`` calls
(``lib/traced_mtp_rounds.kernel_roofline_share``)."""


def read(run):
    from benchmark.lib import traced_mtp_rounds

    return traced_mtp_rounds.kernel_roofline_share(run)
