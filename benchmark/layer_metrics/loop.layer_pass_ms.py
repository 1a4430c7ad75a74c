"""What one pass of one layer costs inside the fused decode program, in
milliseconds: the device time of the traced ``decode_rounds`` calls
(``lib/traced_rounds.py``: whole calls only) per step the device ran in
them, divided by the engine's ``kv_planes`` counter (loop steps x layers:
192 for Ouro-2.6B).  Comparable across configurations: a dense model's
planes are its layers."""


def read(run):
    from benchmark.lib import traced_rounds

    planes = run["counters"]["at_close"].get("kv_planes")
    calls = traced_rounds.whole_calls(run)
    if not calls or not planes:
        return None
    steps = sum(c["steps"] for c in calls)
    return 1e3 * sum(c["seconds"] for c in calls) / steps / planes
