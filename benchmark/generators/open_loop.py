"""Open loop: independent users, requests sent on a schedule whether or not
earlier ones have finished.

Every seed gets the SAME multiset of prompt lengths, output lengths and gaps
between arrivals (the distribution's quantiles at (i + 0.5) / n), in an order
of its own, and token ids of its own.  So the seed changes which request
meets which, never how much work a window holds.

The schedule starts ``ramp_s`` before the window opens: requests due before
0 are set-up traffic (tagged ``ramp``, sent and followed like the others and
left out of ``attempted``), so that the window opens on an engine that holds
what this traffic keeps in it, not on an empty one.  The ramp is a multiset
of its own, the same for every seed.

Parameters (``traffic/<mix>.json``):
  rate_per_s           requests due per second; n = round(rate * seconds)
  ramp_s               seconds of the same arrivals before the window
  prompt, output       {"median", "sigma", "min", "max"}: log-normal lengths
  arrivals             "poisson" (exponential gaps) or "uniform"
"""

import math
import statistics

import numpy as np

_NORMAL = statistics.NormalDist()


def _quantiles(n):
    return [(i + 0.5) / n for i in range(n)]


def _lognormal_lengths(spec, n):
    out = [spec["median"] * math.exp(spec["sigma"] * _NORMAL.inv_cdf(u))
           for u in _quantiles(n)]
    return np.clip(np.rint(out), spec["min"], spec["max"]).astype(np.int64)


def _stretch(params, rng, span_s, start_s, vocab_size, tags):
    """round(rate * span_s) requests due over [start_s, start_s + span_s)."""
    n = round(params["rate_per_s"] * span_s)
    if n < 1:
        return []
    prompts = rng.permutation(_lognormal_lengths(params["prompt"], n))
    outputs = rng.permutation(_lognormal_lengths(params["output"], n))
    if params.get("arrivals", "poisson") == "poisson":
        gaps = np.array([-math.log(1.0 - u) for u in _quantiles(n)])
    else:
        gaps = np.ones(n)
    gaps = rng.permutation(gaps) * (span_s / gaps.sum())
    due = start_s + np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return [{
        "due_s": float(due[i]),
        # Token 0 is the engine's pad id and is trimmed from a prompt's end.
        "prompt": rng.integers(1, vocab_size, int(prompts[i]),
                               dtype=np.int32),
        "max_new": int(outputs[i]),
        "tags": dict(tags),
    } for i in range(n)]


def plan(params, seed, seconds, vocab_size):
    rng = np.random.default_rng(seed)
    ramp_s = float(params.get("ramp_s", 0.0))
    ramp = _stretch(params, rng, ramp_s, -ramp_s, vocab_size,
                    {"ramp": True}) if ramp_s > 0 else []
    window = _stretch(params, rng, seconds, 0.0, vocab_size, {})
    return {"mode": "open", "requests": ramp + window, "setup": []}
