"""Share of the traced whole ``decode_rounds`` calls' own device time spent
in the window layers' attention, in percent: operations under
``kft.mla_window``, the paged kernel's walk from the window's first page
(``lib/traced_dsa_rounds.scope_share``)."""


def read(run):
    from benchmark.lib import traced_dsa_rounds

    return traced_dsa_rounds.scope_share(run, ("kft.mla_window",))
