"""Pages of the KV pool in use (``stats()`` kv_blocks_used / kv_blocks),
sampled each second of the window, mean, in percent."""


def read(run):
    t0, t1 = run["window"]["t0"], run["window"]["t1"]
    used = [u for t, u, _, _ in run["samples"] if t0 <= t <= t1]
    blocks = run["counters"]["at_close"]["kv_blocks"]
    return 100.0 * sum(used) / len(used) / blocks if used and blocks else None
