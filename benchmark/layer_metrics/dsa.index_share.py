"""Share of the traced whole ``decode_rounds`` calls' own device time spent
choosing positions, in percent: operations under ``kft.dsa_index`` (the
index key's write, the scores over the index keys a slot holds) and
``kft.dsa_select`` (the choice of ``index_topk`` of them)
(``lib/traced_dsa_rounds.scope_share``)."""


def read(run):
    from benchmark.lib import traced_dsa_rounds

    return traced_dsa_rounds.scope_share(
        run, ("kft.dsa_index", "kft.dsa_select"))
