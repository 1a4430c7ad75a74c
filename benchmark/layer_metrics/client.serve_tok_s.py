"""Output tokens handed to clients inside the window, over the window,
whichever request a token belongs to; tokens of a request that failed are
not counted.  Recorded and not judged: a window holds a handful of cold
documents, and which of them falls where moves this by a tenth (PERF.md
section 2)."""


def read(run):
    t0, t1 = run["window"]["t0"], run["window"]["t1"]
    tokens = sum(n for r in run["records"] if r["error"] is None
                 for t, n in r["arrivals"] if t0 <= t < t1)
    return tokens / (t1 - t0)
