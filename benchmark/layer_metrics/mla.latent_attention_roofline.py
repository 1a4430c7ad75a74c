"""The latent form of the paged decode kernel
(``ops/paged_attention.py: paged_latent_decode_attention``) against its own
roofline, in percent: the least time of reading each attended position's
latent row once in every plane, or of the absorbed form's operations if that
is longer (``lib/counts_longcat.latent_attention_seconds``), over the device
time of the kernel's calls inside the traced whole ``decode_rounds`` calls
(``lib/traced_latent_rounds.kernel_roofline_share``).  Positions and time
are the same calls'."""


def read(run):
    from benchmark.lib import traced_latent_rounds

    return traced_latent_rounds.kernel_roofline_share(run)
