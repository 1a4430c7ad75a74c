"""Positions the decode steps ATTENDED in the full planes over the index
keys they SCORED there, in percent, over the window: the engine's own
counters ``index_chosen`` / ``index_scored`` (``lib/window.py``).  2,048 of a
context of 8k-33k reads 6-25 %; 100 % means no context passed
``index_topk``."""


def read(run):
    from benchmark.lib import window

    chosen = window.grown(run, "index_chosen")
    scored = window.grown(run, "index_scored")
    if not chosen or not scored:
        return None
    return 100.0 * chosen / scored
