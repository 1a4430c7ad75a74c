"""Share of the own device time of the two engine programs
(``decode_rounds``, ``prefill_chunk_into_slot``) spent in operations whose
RESULT has the shape of one side of the paged pool or of one plane of it,
in percent, over the traced part of the window (``lib/trace_spans.py``).

The pool is ``[kv_planes, blocks, block tokens, kv heads, head dim]``
(``stats()``'s ``kv_planes`` and ``kv_blocks``, the engine's page size, the
configuration's heads).  A program that rides the pool through its layer
scan as xs / ys slices each plane out, restacks it and copies the whole
pool around its loops (``dynamic-slice_bitcast_fusion``,
``bitcast_dynamic-update-slice_fusion``, ``copy``, ``copy-done``, all with
such a result: 78 / 40 / 38 % of the programs in the three cells, PR 27's
ledger lines); one that carries the pool and scatters into it in place
keeps only the scatter of a step's columns under these shapes.  Read from
the operations' names, which hold the result's type: no scope or counter
of the program is needed, so it reads on any commit whose trace names its
modules.  Logs the operations that took most."""

from benchmark.lib import trace_reduce, trace_spans

MODULES = ("jit_decode_rounds", "jit_prefill_chunk_into_slot")


def pool_shapes(run):
    """The dims of one side of the pool, of one plane, and of one plane
    with its leading 1, as an instruction's text writes them; None where
    the run does not say how large its pool is."""
    config, stats = run.get("config") or {}, run["counters"]["at_close"]
    heads = config.get("num_attention_heads")
    planes = stats.get("kv_planes") or (
        config.get("num_hidden_layers", 0)
        * (config.get("total_ut_steps") or 1))
    plane = (stats.get("kv_blocks"),
             (run.get("engine") or {}).get("kv_block_tokens"),
             config.get("num_key_value_heads") or heads,
             config.get("head_dim") or (
                 heads and config.get("hidden_size", 0) // heads))
    if not planes or not all(plane):
        return None
    plane = ",".join(str(int(n)) for n in plane)
    return {f"{int(planes)},{plane}", plane, f"1,{plane}"}


def result_dims(event_name):
    """``%copy.68 = bf16[24,2560,16,8,128]{4,3,...} copy(...)`` ->
    ``24,2560,16,8,128``; None for a tuple or an event without a type."""
    rest = event_name.partition(" = ")[2]
    kind, bracket, tail = rest.split("{")[0].split(" ")[0].partition("[")
    if not bracket or not kind.isalnum() or not tail.endswith("]"):
        return None
    return tail[:-1]


def read(run):
    ops = trace_spans.busiest_ops(run)
    if ops is None:
        return None
    shapes = pool_shapes(run)
    if shapes is None:
        return None
    total, moved = 0.0, {}
    for (name, module, _), seconds in trace_spans.own_times(ops).items():
        if module not in MODULES:
            continue
        total += seconds
        if result_dims(name) in shapes:
            key = trace_reduce.short_name(name)
            moved[key] = moved.get(key, 0.0) + seconds
    if not total:
        return None
    for key, seconds in sorted(moved.items(), key=lambda kv: -kv[1])[:8]:
        print(f"pool-shaped {seconds:.4f} s {key}", flush=True)
    return 100.0 * sum(moved.values()) / total
