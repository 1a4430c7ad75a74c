"""A looped model's fused decode program against its roofline, in percent:
the least time the chip could take for the TRACED calls' own steps over
those calls' device time.

Per call (``lib/traced_rounds.py``: calls the trace holds whole, the steps
the device ran, the positions those steps attended) the least time is by
``lib/counts_looped.py``: every layer's matmul weights once per loop step and
the head once, per decode step, plus the keys and values of the attended
positions in every plane, at the chip's published peaks (memory bounds it at
these batch sizes).  Steps and time are the same calls', and the positions
are those of live sequences only (cached pages nobody reads are not
counted), so this cannot read over 100 % unless the counts are wrong."""


def read(run):
    from benchmark.lib import counts_looped, peaks, traced_rounds

    calls = traced_rounds.whole_calls(run)
    if not calls or any(c["attended"] is None for c in calls):
        return None
    kind = run["device"]["kind"]
    least = sum(counts_looped.decode_round_seconds(
        run["config"], c["steps"], c["attended"],
        peaks.peak(kind, "bf16_flops_per_s"),
        peaks.peak(kind, "hbm_bytes_per_s"))[0] for c in calls)
    return 100.0 * least / sum(c["seconds"] for c in calls)
