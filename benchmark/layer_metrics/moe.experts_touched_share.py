"""Share of the experts' weights a decode step read, in percent: the
engine's ``experts_touched`` (the device's own count of distinct experts
with at least one row, summed over sparse layers and decode steps) over
``moe_layers x moe_experts x`` decode steps; ``experts_touched`` and
``steps`` as ``stats()`` deltas over the window (``lib/window.py``).  None
where the program states no such counter or ran no step."""

from benchmark.lib import window


def read(run):
    touched, steps = (window.grown(run, "experts_touched"),
                      window.grown(run, "steps"))
    at_close = run["counters"]["at_close"]
    could = (at_close.get("moe_layers", 0) * at_close.get("moe_experts", 0)
             * (steps or 0))
    if touched is None or not could:
        return None
    return 100.0 * touched / could
