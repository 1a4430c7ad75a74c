"""From a profiler trace to numbers.

``load`` reads an ``.xplane.pb`` with nothing but JAX and returns plain data,
{plane name: {line name: [(event name, start_ns, duration_ns), ...]}}; every
reduction below works on that, so the tests check them on a small recorded
trace kept as JSON.

On a TPU the device planes are named ``/device:TPU:<n>``.  Their line
``XLA Ops`` holds one event per executed operation and ``XLA Modules`` one
per executed program, named ``<jit name>(<fingerprint>)``.
"""

import glob
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path):
    from jax.profiler import ProfileData

    trace = {}
    for plane in ProfileData.from_file(path).planes:
        lines = trace.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (e.name, int(e.start_ns), int(e.duration_ns))
                for e in line.events)
    return trace


def device_planes(trace):
    return {name: lines for name, lines in trace.items()
            if name.startswith(DEVICE_PREFIX)
            and name[len(DEVICE_PREFIX):].isdigit()}


def clip(events, t0, t1):
    """Events cut to [t0, t1); those outside are dropped."""
    out = []
    for name, start, dur in events:
        s, e = max(start, t0), min(start + dur, t1)
        if e > s:
            out.append((name, s, e - s))
    return out


def merged(events):
    """Sorted, non-overlapping [start, end, last event name] intervals."""
    out = []
    for name, start, dur in sorted(events, key=lambda e: e[1]):
        if out and start <= out[-1][1]:
            if start + dur > out[-1][1]:
                out[-1][1], out[-1][2] = start + dur, name
        else:
            out.append([start, start + dur, name])
    return out


def busy_ns(events):
    """Length of the union of the events' intervals."""
    return sum(end - start for start, end, _ in merged(events))


def idle_gaps(events, t0, t1):
    """[(start_ns, duration_ns, name of the event that ended before it)],
    longest first, inside [t0, t1)."""
    gaps, cursor, before = [], t0, "window start"
    for start, end, name in merged(clip(events, t0, t1)):
        if start > cursor:
            gaps.append((cursor, start - cursor, before))
        cursor, before = end, name
    if t1 > cursor:
        gaps.append((cursor, t1 - cursor, before))
    return sorted(gaps, key=lambda g: -g[1])


def module_name(event_name):
    """``jit_decode_rounds(123)`` -> ``jit_decode_rounds``"""
    return event_name.split("(")[0]


def module_times(events):
    """{module: (seconds, calls)} of an ``XLA Modules`` line."""
    out = {}
    for name, _, dur in events:
        key = module_name(name)
        s, n = out.get(key, (0.0, 0))
        out[key] = (s + dur / 1e9, n + 1)
    return out


def short_name(event_name):
    """``%copy-done = bf16[2560,16,8,128]{3,2,...} copy-done(...)`` ->
    ``copy-done bf16[2560,16,8,128]``: an operation's name and the shape it
    produces, without the layout and the operands."""
    name, _, rest = event_name.partition(" = ")
    shape = rest.split("{")[0].split(" ")[0] if rest else ""
    return (name.lstrip("%") + (" " + shape if shape else ""))[:120]


def self_times(events):
    """{event name: seconds of its own}: an operation that contains others
    (a loop and its body both have events on the line) keeps only the time
    its children do not cover."""
    total, stack = {}, []  # stack of [name, end, own_ns]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, _, own = stack.pop()
            total[name] = total.get(name, 0.0) + max(0, own) / 1e9

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] -= min(dur, stack[-1][1] - start)
        stack.append([name, start + dur, dur])
    close(float("inf"))
    return total


def top_ops(events, n=10):
    """[(operation, seconds of its own)], the n that took most time."""
    total = {}
    for name, seconds in self_times(events).items():
        key = short_name(name)
        total[key] = total.get(key, 0.0) + seconds
    return sorted(total.items(), key=lambda kv: -kv[1])[:n]


def window_of(trace, marker):
    """(start_ns, end_ns) of the host annotation ``marker`` (the benchmark
    wraps the traced part of its window in one), or None."""
    for plane, lines in trace.items():
        if plane.startswith(DEVICE_PREFIX):
            continue
        for events in lines.values():
            for name, start, dur in events:
                if name == marker:
                    return start, start + dur
    return None


def device_summary(trace, marker):
    """Busy seconds (mean over device planes) and window seconds, with each
    device plane's op and module events clipped to the window.  The window
    is the host annotation ``marker``, or where the trace has none the
    extent of the device operations."""
    planes = {name: (lines.get(OPS_LINE) or lines.get(MODULES_LINE) or [],
                     lines.get(MODULES_LINE, []))
              for name, lines in sorted(device_planes(trace).items())}
    window = window_of(trace, marker)
    if window is None:
        every = [e for ops, _ in planes.values() for e in ops]
        if not every:
            return None
        window = (min(s for _, s, _ in every),
                  max(s + d for _, s, d in every))
    if not planes:
        return None
    t0, t1 = window
    out = {"window_s": (t1 - t0) / 1e9, "t0": t0, "t1": t1, "planes": {}}
    for name, (ops, modules) in planes.items():
        ops = clip(ops, t0, t1)
        out["planes"][name] = {"ops": ops, "modules": clip(modules, t0, t1),
                               "busy_s": busy_ns(ops) / 1e9}
    busy = [p["busy_s"] for p in out["planes"].values()]
    out["busy_s"] = sum(busy) / len(busy)
    return out
