"""Prompt tokens computed (not served from shared pages) per second of
device time of the chunked-prefill program, over the traced part of the
window.  Tokens come from the engine's chunk count in that part (each call
of the program is one chunk of ``prefill_chunk_tokens`` columns; the last
chunk of a prompt is partly padding, so this reads a little high in tokens
and is named for the program's rate, not the prompts')."""

MODULE = "jit_prefill_chunk_into_slot"


def read(run):
    from benchmark.lib import trace_reduce

    trace = run.get("trace")
    if not trace:
        return None
    plane = max(trace["planes"].values(), key=lambda p: p["busy_s"])
    seconds, calls = trace_reduce.module_times(plane["modules"]).get(
        MODULE, (0.0, 0))
    if not calls or not seconds:
        return None
    width = run["engine"]["prefill_chunk_tokens"]
    return calls * width / seconds
