"""A looped model's fused decode program against its roofline, in percent:
the least time the chip could take for the TRACED calls' own steps and the
positions they attended, over those calls' device time
(``lib/traced_rounds.roofline_share``, which says what is counted: every
layer's matmul weights once per loop step and the head once a decode step,
and the attended positions' keys and values in every plane).
``decode_rounds_roofline`` is the same computation under the dense cells'
name."""


def read(run):
    from benchmark.lib import traced_rounds

    return traced_rounds.roofline_share(run)
