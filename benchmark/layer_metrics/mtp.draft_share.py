"""Share of the traced whole ``decode_rounds`` calls' own device time spent
in the multi-token-prediction module's forward (operations whose scope path
holds ``kft.mtp_draft``), in percent
(``lib/traced_mtp_rounds.draft_share``).  None where the trace holds no whole
call or the program has no such scope."""


def read(run):
    from benchmark.lib import traced_mtp_rounds

    return traced_mtp_rounds.draft_share(run)
