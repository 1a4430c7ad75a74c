"""The read of the chosen rows (``kft.mla_sparse``: the gather of
``index_topk`` latent rows a slot and plane by (page, offset), the scores and
the sums over them) against its own roofline, in percent: the least time of
reading each chosen row once, or of the absorbed form's operations if that is
longer (``lib/counts_dots3.sparse_attention_seconds``), over the own device
time of the scope's operations inside the traced whole ``decode_rounds``
calls (``lib/traced_dsa_rounds.sparse_roofline_share``).  Rows and time are
the same calls'."""


def read(run):
    from benchmark.lib import traced_dsa_rounds

    return traced_dsa_rounds.sparse_roofline_share(run)
