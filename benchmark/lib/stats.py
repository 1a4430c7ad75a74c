"""Small arithmetic shared by the metric readers."""


def percentile(values, q):
    """Linear interpolation between order statistics, q in [0, 1]."""
    values = sorted(values)
    if not values:
        return None
    pos = q * (len(values) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(values) - 1)
    return values[lo] + (values[hi] - values[lo]) * (pos - lo)


def ttft_s(rec, worst):
    """Due (or, in a closed loop, sent) to first token; a failed request
    counts as the worst."""
    if rec["first"] is None:
        return worst
    return rec["first"] - (rec["due"] if rec["due"] is not None
                           else rec["sent"])


def delta(run, name, upto="at_close"):
    c = run["counters"]
    return c[upto][name] - c["before"][name]


def counted(run):
    """The window's own requests: the ramp's are set-up traffic."""
    return [r for r in run["records"]
            if not r["request"]["tags"].get("ramp")]


def ttfts(run):
    """One per request attempted; one without a token by the cutoff counts
    as the worst."""
    worst = run["window"]["seconds"] + run["drain_s"]
    return [ttft_s(r, worst) for r in counted(run)]


def tpots(run, least_tokens=8):
    return [(r["last"] - r["first"]) / (len(r["tokens"]) - 1)
            for r in counted(run)
            if r["error"] is None and len(r["tokens"]) >= least_tokens]


def time_per_token_s(records, t0, t1):
    """Seconds per output token at the client, over every token that any
    request was handed inside [t0, t1).  Tokens arrive in bursts (one fused
    round hands over several at once), so per request the time runs from its
    first burst in the window to its last, and the tokens counted are those
    of the bursts after the first: each of them was waited for inside the
    window.  The sum of those times over the sum of those tokens."""
    span = tokens = 0
    for r in records:
        bursts = [(t, n) for t, n in r["arrivals"] if t0 <= t < t1]
        if len(bursts) > 1 and r["error"] is None:
            span += bursts[-1][0] - bursts[0][0]
            tokens += sum(n for _, n in bursts[1:])
    return span / tokens if tokens else None
