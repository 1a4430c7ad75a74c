"""Owners for what a traced run shows: the program's own spans and scopes.

The program (PR 24) marks two things in the profiler's trace.  Its serving
loop wraps every phase of an iteration in a host annotation named
``kft.engine.<phase>`` (``wait_work``, ``admit``, ``housekeeping``,
``prefill_dispatch``, ``round_prepare``, ``round_dispatch``, ``overlap``,
``round_wait``, ``drain``, ``account``) with a few facts (``round``,
``width``, ``live``, ...), and its AOT programs put every operation of the
model under a ``jax.named_scope`` named ``kft.<scope>`` (``embed``,
``qkv_proj``, ``kv_write``, ``kv_view``, ``attention``, ``attn_out``,
``mlp``, ``logits``, ``sample``).  ``load`` reads both back from the traced
run's ``.xplane.pb`` and the reductions below turn them into numbers:
``attribute_gaps`` gives each idle gap of the device to the phase the host
was in, ``scope_times`` gives each device operation's own time to its scope.

Where the scope lives (found on a v5e, PR 24): NOT in the event and not in
its name.  A device ``XLA Ops`` event has three statistics
(``device_offset_ps``, ``device_duration_ps``, ``Time Scale Multiplier``)
and the instruction's text as its name, without ``metadata={...}``.  The
scope is a statistic of the event's METADATA entry in the plane's table,
``tf_op`` = ``jit(decode_rounds)/while/body/.../kft.attention/dot_general:``,
beside ``program_id`` (the number in the ``XLA Modules`` event's name),
``hlo_category``, ``flops`` and ``bytes_accessed``.
``jax.profiler.ProfileData`` (JAX 0.9) gives an event's own statistics and
not its metadata's, so the table is read here from the file's bytes
(``op_names``: protobuf's wire format, five message types, no dependency);
times and names still come from ``ProfileData``, on the clock
``trace_reduce`` uses.  A fusion carries one ``tf_op``, its root's.
Operations the compiler adds (copies, the layer scan's slices of the
stacked weights, its loop's own time) carry a ``tf_op`` without a
``kft.`` component or none: they are the unowned share.

A host annotation's facts arrive as the event's statistics; a profiler that
leaves them in the name (``name#k=v,k=v#``) is read too.

The file is the newest under ``.bench_trace/`` and has to hold the
benchmark's window marker at exactly the traced run's ``t0``..``t1``: a
wrong file is an error, not a number.
"""

import bisect
import glob
import os
import pathlib

from benchmark.lib import trace_reduce

ROOT = pathlib.Path(__file__).resolve().parents[2]
TRACE_ROOT = ROOT / ".bench_trace"
MARKER = "bench.trace_window"
PHASE_PREFIX = "kft.engine."
SCOPE_PREFIX = "kft."


def newest_xplane(trace_root=None):
    trace_root = trace_root or TRACE_ROOT
    found = glob.glob(os.path.join(
        str(trace_root), "*", "plugins", "profile", "*", "*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_root}")
    return max(found, key=os.path.getmtime)


def is_device_plane(name):
    """``/device:TPU:<n>``, as ``trace_reduce.device_planes`` picks them."""
    return name.startswith(trace_reduce.DEVICE_PREFIX) \
        and name[len(trace_reduce.DEVICE_PREFIX):].isdigit()


# -- the metadata table, from the file's bytes -------------------------------

def _varint(buf, i):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: an int for a varint, the bytes
    of a length-delimited field; fixed-width fields are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield key >> 3, value
        elif wire == 2:
            size, i = _varint(buf, i)
            yield key >> 3, buf[i:i + size]
            i += size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")


def _map_value(entry):
    for number, value in _fields(entry):
        if number == 2:
            return value
    return b""


def op_names(path):
    """{plane name: {(program id, instruction text): tf_op}} from the
    planes' event-metadata tables (``XSpace.planes`` = 1; ``XPlane.name`` =
    2, ``.event_metadata`` = 4, ``.stat_metadata`` = 5; ``XEventMetadata
    .name`` = 2, ``.stats`` = 5; ``XStat.metadata_id`` = 1, ``.uint64_value``
    = 3, ``.int64_value`` = 4, ``.str_value`` = 5, ``.ref_value`` = 7;
    ``XStatMetadata.id`` = 1, ``.name`` = 2).  Entries without a ``tf_op``
    are left out."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for number, plane in _fields(space):
        if number != 1:
            continue
        name, events, stat_names = "", [], {}
        for number, value in _fields(plane):
            if number == 2:
                name = bytes(value).decode()
            elif number == 4:
                events.append(_map_value(value))
            elif number == 5:
                meta = dict(_fields(_map_value(value)))
                stat_names[meta.get(1, 0)] = bytes(meta.get(2, b"")).decode()
        if not is_device_plane(name):
            continue
        table = out.setdefault(name, {})
        for event in events:
            text, found = "", {}
            for number, value in _fields(event):
                if number == 2:
                    text = bytes(value).decode()
                elif number == 5:
                    stat = dict(_fields(value))
                    key = stat_names.get(stat.get(1))
                    if key == "tf_op":
                        found[key] = bytes(stat[5]).decode() if 5 in stat \
                            else stat_names.get(stat.get(7), "")
                    elif key == "program_id":
                        found[key] = stat.get(3, stat.get(4))
            if found.get("tf_op"):
                table[(found.get("program_id"), text)] = found["tf_op"]
    return out


def scope_of(op_name):
    """``jit(f)/while/body/kft.mlp/kft.x/dot_general:`` -> ``kft.x``: the
    innermost component that starts with ``kft.``, else None."""
    for part in reversed((op_name or "").split("/")):
        if part.startswith(SCOPE_PREFIX):
            return part.split(":")[0]
    return None


# -- host annotations ---------------------------------------------------------

def _number(text):
    for kind in (int, float):
        try:
            return kind(text)
        except (TypeError, ValueError):
            pass
    return text


def split_facts(name, stats=()):
    """``("kft.engine.drain#round=3,live=4#", [])`` or
    ``("kft.engine.drain", [("round", 3), ("live", 4)])`` ->
    ``("kft.engine.drain", {"round": 3, "live": 4})``."""
    bare, _, tail = name.partition("#")
    facts = {}
    for item in tail.rstrip("#").split(","):
        key, eq, value = item.partition("=")
        if eq:
            facts[key] = _number(value)
    for key, value in stats:
        if not str(key).startswith("_"):
            facts[str(key)] = _number(value)
    return bare, facts


# -- loading ------------------------------------------------------------------

def load(path, t0, t1):
    """{"phases": [(phase, start_ns, duration_ns, facts)], sorted by start,
    "ops": {device plane: [(instruction text, start_ns, duration_ns, module,
    scope)]}, clipped to [t0, t1)}.  Raises unless the file holds the window
    marker at exactly [t0, t1)."""
    from jax.profiler import ProfileData

    names = op_names(path)
    phases, markers, ops = [], [], {}
    for plane in ProfileData.from_file(path).planes:
        if not is_device_plane(plane.name):
            for line in plane.lines:
                for e in line.events:
                    if e.name == MARKER:
                        markers.append((int(e.start_ns), int(e.start_ns)
                                        + int(e.duration_ns)))
                    elif e.name.startswith(PHASE_PREFIX):
                        bare, facts = split_facts(e.name, e.stats)
                        phases.append((bare[len(PHASE_PREFIX):],
                                       int(e.start_ns), int(e.duration_ns),
                                       facts))
            continue
        lines = {line.name: [(e.name, int(e.start_ns), int(e.duration_ns))
                             for e in line.events] for line in plane.lines}
        ops[plane.name] = with_owners(
            trace_reduce.clip(lines.get(trace_reduce.OPS_LINE, []), t0, t1),
            lines.get(trace_reduce.MODULES_LINE, []),
            names.get(plane.name, {}))
    if (t0, t1) not in markers:
        raise ValueError(
            f"{path} is not this run's trace: its {MARKER!r} markers are "
            f"{markers}, the run's window is {(t0, t1)}")
    return {"phases": sorted(phases, key=lambda p: (p[1], -p[2])),
            "ops": ops}


def with_owners(ops, modules, table):
    """Each operation with the module whose event it started in and its
    scope from ``table`` ({(program id, instruction text): tf_op})."""
    modules = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in modules]
    out = []
    for name, start, dur in ops:
        i = bisect.bisect_right(starts, start) - 1
        module = program = None
        if i >= 0 and start < modules[i][1] + modules[i][2]:
            module = trace_reduce.module_name(modules[i][0])
            tail = modules[i][0][len(module):].strip("()")
            program = int(tail) if tail.isdigit() else None
        out.append((name, start, dur, module,
                    scope_of(table.get((program, name)))))
    return out


_LOADED = {}


def of_run(run):
    """The traced run's spans and scopes (loaded once a process), or None
    where the run was not traced."""
    trace = run.get("trace")
    if not trace:
        return None
    key = (trace["t0"], trace["t1"])
    if key not in _LOADED:
        _LOADED.clear()
        _LOADED[key] = load(newest_xplane(), *key)
    return _LOADED[key]


# -- reductions ---------------------------------------------------------------

def innermost(phases):
    """Non-overlapping [(start_ns, end_ns, phase)] in time order: where one
    phase lies inside another, the inner one owns its stretch."""
    out, stack = [], []  # stack of [phase, end, cursor]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            phase, end, cursor = stack.pop()
            if end > cursor:
                out.append((cursor, end, phase))
            if stack:
                stack[-1][2] = max(stack[-1][2], end)

    for phase, start, dur, *_ in sorted(phases, key=lambda p: (p[1], -p[2])):
        close(start)
        if stack:
            if start > stack[-1][2]:
                out.append((stack[-1][2], start, stack[-1][0]))
            stack[-1][2] = max(stack[-1][2], start)
        stack.append([phase, start + dur, start])
    close(float("inf"))
    return sorted(out)


def attribute_gaps(idle_gaps, phases):
    """({phase: seconds}, unattributed seconds): each idle gap of the device
    ([(start_ns, duration_ns, ...)], as ``trace_reduce.idle_gaps`` gives
    them) split over the phases the host loop was in meanwhile."""
    segments = innermost(phases)
    starts = [s for s, _, _ in segments]
    owned, unattributed = {}, 0.0
    for start, dur, *_ in idle_gaps:
        end, covered = start + dur, 0
        i = max(0, bisect.bisect_right(starts, start) - 1)
        while i < len(segments) and segments[i][0] < end:
            s, e, phase = segments[i]
            part = min(e, end) - max(s, start)
            if part > 0:
                owned[phase] = owned.get(phase, 0.0) + part / 1e9
                covered += part
            i += 1
        unattributed += (dur - covered) / 1e9
    return owned, unattributed


def own_times(ops):
    """{(instruction text, module, scope): own seconds} of [(name, start_ns,
    duration_ns, module, scope)]: a loop keeps only what its body's
    operations do not cover (``trace_reduce.self_times``)."""
    return trace_reduce.self_times(
        [((name, module, scope), start, dur)
         for name, start, dur, module, scope in ops])


def scope_times(ops):
    """{(module, scope): own seconds}; scope None: under no scope."""
    out = {}
    for (_, module, scope), seconds in own_times(ops).items():
        out[(module, scope)] = out.get((module, scope), 0.0) + seconds
    return out


def busiest_ops(run):
    """The traced run's operations on the device plane that worked most,
    or None where the run was not traced or no operation was found."""
    spans = of_run(run)
    if not spans or not any(spans["ops"].values()):
        return None
    return max(spans["ops"].values(),
               key=lambda ops: sum(dur for _, _, dur, _, _ in ops))


def scope_share(run, modules, scopes):
    """Own time of the operations of ``modules`` under ``scopes`` (None in
    the list: under no scope) over all own time of those modules, in
    percent; None where the trace has no operation of the modules or no
    scope at all (a program that sets none)."""
    ops = busiest_ops(run)
    if ops is None:
        return None
    times = scope_times(ops)
    if not any(scope for _, scope in times):
        return None
    total = sum(s for (m, _), s in times.items() if m in modules)
    if not total:
        return None
    return 100.0 * sum(s for (m, scope), s in times.items()
                       if m in modules and scope in scopes) / total
