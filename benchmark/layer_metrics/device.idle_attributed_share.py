"""Share of the traced part's device idle time that falls inside one of the
engine loop's ``kft.engine.*`` phases, in percent; logs the idle seconds
per phase.  Idle time before the first annotation the trace caught is left
out (and logged): the profiler records an annotation only if it BEGAN while
the trace ran, so the phase that covers the trace's first stretch (up to a
whole round) is not in the file.  The phases tile the loop thread's time,
so what reads under 100 here is a hole between two phases or a loop that
stopped annotating.
"""

from benchmark.lib import trace_reduce, trace_spans


def read(run):
    spans = trace_spans.of_run(run)
    if not spans or not spans["phases"]:
        return None
    trace = run["trace"]
    first = min(start for _, start, _, _ in spans["phases"])
    plane = max(trace["planes"].values(), key=lambda p: p["busy_s"])
    before = trace_reduce.idle_gaps(plane["ops"], trace["t0"], first)
    gaps = trace_reduce.idle_gaps(plane["ops"], first, trace["t1"])
    owned, unattributed = trace_spans.attribute_gaps(gaps, spans["phases"])
    idle = sum(owned.values()) + unattributed
    for phase, seconds in sorted(owned.items(), key=lambda kv: -kv[1]):
        print(f"idle {seconds:.4f} s in {phase}", flush=True)
    print(f"idle {unattributed:.4f} s in no phase; "
          f"{sum(g[1] for g in before) / 1e9:.4f} s before the loop's first "
          f"annotation ({(first - trace['t0']) / 1e9:.3f} s into the trace), "
          "left out", flush=True)
    return 100.0 * sum(owned.values()) / idle if idle else None
