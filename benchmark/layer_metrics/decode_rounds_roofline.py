"""The fused decode program's share of its roofline, in percent: the least
time the chip could take for the traced decode steps over the device time of
the ``decode_rounds`` program.  A decode step must read every matmul weight
once and the keys and values RESIDENT for the live sequences
(``counts.decode_step_bytes``) and multiply each live sequence's token
through the weights; the larger of the two times at the chip's published
peaks is the roofline (memory, at these batch sizes).  Steps, live sequences
and resident tokens are the engine's counters over the whole window, so this
reads the window's mean step against the traced calls' mean time."""

MODULE = "jit_decode_rounds"


def read(run):
    from benchmark.lib import counts, peaks, stats, trace_reduce

    trace = run.get("trace")
    if not trace:
        return None
    plane = max(trace["planes"].values(), key=lambda p: p["busy_s"])
    seconds, calls = trace_reduce.module_times(plane["modules"]).get(
        MODULE, (0.0, 0))
    rounds = stats.delta(run, "fused_rounds")
    steps = stats.delta(run, "steps")
    if not calls or not seconds or not rounds or not steps:
        return None
    steps_per_call = steps / rounds
    c = run["counters"]["at_close"]
    used = [u for _, u, _, _ in run["samples"]]
    resident = (sum(used) / len(used) if used else 0) * c["kv_block_tokens"]
    live = stats.delta(run, "tokens") / steps  # sequences advanced per step
    kind = run["device"]["kind"]
    least, _ = counts.roofline_seconds(
        live * counts.forward_flops_per_token(
            run["config"], resident / max(live, 1.0)),
        counts.decode_step_bytes(run["config"], resident),
        peaks.peak(kind, "bf16_flops_per_s"),
        peaks.peak(kind, "hbm_bytes_per_s"))
    return 100.0 * steps_per_call * least / (seconds / calls)
