"""The largest of the engine's AOT programs by the compiler's own account,
arguments + outputs + temporaries - aliased bytes
(``compiled.memory_analysis()``, kept by the engine as
``compiled_peak_bytes``), in GB.  ``memory_peak_bytes`` of the result line
is the runtime's counter, which leaves program temporaries out."""


def read(run):
    peak = run["counters"]["at_close"].get("compiled_peak_bytes")
    return peak / 1e9 if peak else None
