"""The fused decode program's share of its roofline, in percent, in the
dense cells: the least time the chip could take for the TRACED
``decode_rounds`` calls' own steps and the positions they attended, over
those calls' device time (``lib/traced_rounds.roofline_share``, which says
what is counted).  Nothing of the window enters the number: no ``steps`` or
``fused_rounds`` counter and no sample of the pool's pages in use, since the
traced 5 s and the window are different populations of rounds and a page in
use is not a page read.  ``loop.decode_rounds_roofline`` is the same
computation under the looped cell's name."""


def read(run):
    from benchmark.lib import traced_rounds

    return traced_rounds.roofline_share(run)
