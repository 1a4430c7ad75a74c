"""Runner for cells that serve a language model through the repo's
continuous-batching engine, in this process.

The engine is built the way ``serving.main`` builds it (``loaders.lm_generate``
-> ``serving.main.batcher_factory`` with ``serving.main``'s own flag defaults),
except that the parameter tree is made on the device from ``--seed`` and no
export is written or restored.  Only what a deployment must state is set here
(the configuration file's ``engine_flags``); every tuning knob stays at the
program's default.

From the program this takes the engine object, ``submit_stream`` and
``stats()``.  Traffic, clocks, the reduction to metrics and the comparison
that decides ``correct`` are the benchmark's.
"""

import gc
import inspect
import json
import os
import subprocess
import sys
import threading
import time
import types

import numpy as np

from benchmark.lib import reference, weights

# Hugging Face key -> TransformerConfig field.
_FIELDS = {"vocab_size": "vocab_size", "hidden_size": "d_model",
           "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
           "num_key_value_heads": "n_kv_heads", "intermediate_size": "d_ff",
           "head_dim": "head_dim", "max_position_embeddings": "max_seq_len",
           "rope_theta": "rope_theta"}
# Every request is followed until this long after the window closes; what
# it gets later is not observed (a request with 512 tokens to come can outlast
# the window itself).  One still running then is "cut".
_DRAIN_S = 10.0
# A cut request has failed if it had its first token and then none in the
# last _STALL_S before the cutoff (a live slot gets tokens every round, and a
# round is under 2 s), or if it got under a third of the tokens that its time
# since the first token makes at the run's own time per token.
_STALL_S = 5.0
_SLOW_FACTOR = 3.0
# Workers of a closed loop that finish in the same round are sent on again
# together, in the order of their numbers: the dispatcher waits this long
# for the round's other finishers (a round hands its tokens to every stream
# within a millisecond; the next round is over 100 ms away).
_SETTLE_S = 0.01

# ``serving.main`` keeps its parser inside ``main``; a child process stops
# ``main`` right after it has parsed and prints the namespace, so that the
# patch of ``parse_args`` lives and dies there.
_DEFAULTS_CHILD = """
import argparse, json, sys
from kubeflow_tpu.serving import main as m
real = argparse.ArgumentParser.parse_args
def capture(self, args=None, namespace=None):
    print(json.dumps(vars(real(self, args, namespace)), default=str))
    sys.stdout.flush()
    raise SystemExit(0)
argparse.ArgumentParser.parse_args = capture
m.main(sys.argv[1:])
"""


def main_defaults(flags, root):
    """``serving.main``'s flags as its parser resolves them, turned into
    ``batcher_factory``'s arguments the way ``main`` does.  Starts the child
    that reads them and returns a function that waits for its answer, so
    that the child's imports run beside the making of the weights."""
    argv = ["--model_name", "bench", "--model_base_path", "unused"]
    for name, value in flags.items():
        argv += [f"--{name}", str(value)]
    child = subprocess.Popen(
        [sys.executable, "-c", _DEFAULTS_CHILD, *argv], cwd=root,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True)

    def result():
        from kubeflow_tpu.serving import main as serving_main

        out, err = child.communicate(timeout=120)
        if child.returncode != 0 or not out.strip():
            raise SystemExit("could not read serving.main's defaults: "
                             + err[-2000:])
        ns = json.loads(out.strip().splitlines()[-1])
        renamed = {"batch_timeout_s": ns["batch_timeout_ms"] / 1e3,
                   "lm_engine": not ns["lm_static_batcher"],
                   "prefix_caching": not ns["no_prefix_cache"]}
        wanted = inspect.signature(serving_main.batcher_factory).parameters
        return {k: renamed.get(k, ns.get(k)) for k in wanted
                if k in renamed or k in ns}

    return result


def build_engine(published, engine, seed, root, dtype="bfloat16"):
    """The engine, on weights made from ``seed``.  ``engine["loader"]`` holds
    further keys of the loader's configuration (the control runs set the
    program's own ``quantize`` or ``kv_cache`` there)."""
    import jax
    import jax.numpy as jnp
    from flax import linen as nn

    from kubeflow_tpu.models.transformer import Transformer
    from kubeflow_tpu.serving import loaders
    from kubeflow_tpu.serving.main import batcher_factory

    defaults = main_defaults(engine["flags"], root)
    overrides = {_FIELDS[k]: v for k, v in published.items() if k in _FIELDS}
    overrides["tied_embeddings"] = bool(published.get("tie_word_embeddings"))
    overrides["dtype"] = dtype
    make_predict = loaders.lm_generate({
        **engine.get("loader", {}),
        "model": overrides, "temperature": 0.0,
        "max_new_tokens": engine["max_new_tokens"]})
    cfg = loaders._model_config(overrides)
    # The program's own tree, as shapes: the benchmark's weights must be
    # that tree, name for name.
    theirs = nn.unbox(jax.eval_shape(
        Transformer(cfg).init, jax.random.key(0),
        jnp.zeros((1, 8), jnp.int32)))["params"]
    theirs = {"/".join(str(getattr(p, "key", p)) for p in path): leaf.shape
              for path, leaf in jax.tree_util.tree_leaves_with_path(theirs)}
    ours = {k: s for k, (s, _) in
            weights.tree_shapes(published, jnp.dtype(dtype)).items()}
    if theirs != ours:
        raise SystemExit(f"the program's parameter tree {theirs} is not the "
                         f"benchmark's {ours}")
    params = weights.make_tree(published, seed, jnp.dtype(dtype))
    predict = make_predict({"params": params})
    del params
    model = types.SimpleNamespace(predict=predict, name="bench", version=1)
    built = batcher_factory(**defaults())(model)
    if built is None or not hasattr(built, "submit_stream"):
        raise SystemExit("serving.main's factory built no decode engine")
    return built


class Client:
    """Sends requests and times every token as it arrives.  ``submit`` is
    called by the one dispatcher thread, in the order the requests are due;
    ``read`` follows one request to its end from a thread of its own."""

    def __init__(self, engine, clock=time.perf_counter):
        self.engine, self.clock = engine, clock
        self.cutoff = float("inf")  # tokens after this are not observed

    def submit(self, request, due):
        rec = {"due": due, "sent": self.clock(), "first": None, "last": None,
               "tokens": [], "arrivals": [], "error": None,
               "request": request, "stream": None}
        try:
            _, rec["stream"] = self.engine.submit_stream({
                "tokens": request["prompt"],
                "max_new_tokens": request["max_new"]})
        except Exception as e:  # shed at the door: a failed request
            rec["error"] = f"{type(e).__name__}: {e}"
        return rec

    def read(self, rec):
        try:
            for chunk in rec.pop("stream") or ():
                now = self.clock()
                if now > self.cutoff:
                    rec["cut"] = True
                    break
                if rec["first"] is None:
                    rec["first"] = now
                rec["last"] = now
                rec["tokens"].extend(chunk)
                rec["arrivals"].append((now, len(chunk)))
        except Exception as e:  # deadline, closed: a failed request
            if self.clock() > self.cutoff:
                rec["cut"] = True  # the engine was closed at the cutoff
            else:
                rec["error"] = f"{type(e).__name__}: {e}"
        rec["done"] = self.clock()
        return rec

    def send(self, request, due=None):
        return self.read(self.submit(request, due))


def drive_open(client, requests, t0, at_t0):
    """Send each request at t0 + due_s from this thread; each is read to its
    end by a thread of its own.  ``at_t0`` runs once, when the window opens
    (requests due before 0 are the ramp)."""
    records, threads, opened = [], [], False
    for request in requests:
        if request["due_s"] >= 0 and not opened:
            time.sleep(max(0.0, t0 - client.clock()))
            at_t0()
            opened = True
        due = t0 + request["due_s"]
        time.sleep(max(0.0, due - client.clock()))
        rec = client.submit(request, due)
        records.append(rec)
        t = threading.Thread(target=client.read, args=(rec,), daemon=True)
        t.start()
        threads.append(t)
    if not opened:
        time.sleep(max(0.0, t0 - client.clock()))
        at_t0()
    return records, threads


def drive_closed(client, streams, t1):
    """Each worker sends its next request when its last is answered, until
    the window closes.  One dispatcher (this thread) does all the sending:
    workers that finish in the same round go on in the order of their
    numbers, and not as their threads happen to wake."""
    records, threads, exhausted = [], [], []
    ready = threading.Condition()
    idle = list(range(len(streams)))
    nexts = [iter(s) for s in streams]

    def follow(worker, rec):
        client.read(rec)
        with ready:
            idle.append(worker)
            ready.notify()

    while True:
        with ready:
            while not idle and client.clock() < t1:
                ready.wait(max(0.0, min(0.05, t1 - client.clock())))
        if client.clock() >= t1:
            return records, threads, exhausted
        time.sleep(_SETTLE_S)
        with ready:
            batch, idle[:] = sorted(idle), []
        for worker in batch:
            request = next(nexts[worker], None)
            if request is None:
                exhausted.append(worker)
                continue
            rec = client.submit(request, None)
            records.append(rec)
            t = threading.Thread(target=follow, args=(worker, rec),
                                 daemon=True)
            t.start()
            threads.append(t)


def join_all(threads, deadline, clock=time.perf_counter):
    for t in threads:
        t.join(max(0.0, deadline - clock()))
    return [t for t in threads if t.is_alive()]


def counters(engine):
    s = engine.stats()
    return {k: v for k, v in s.items() if isinstance(v, (int, float))
            and not isinstance(v, bool)} | {
        "compiled_programs": dict(s["compiled_programs"])}


def warm(plan, client, vocab_size, warm_requests):
    """Set-up traffic: what the mix needs resident, then a few short requests
    at once so that every program and every host path the window drives has
    run (the engine compiles its programs on first use)."""
    records = [client.send(r) for r in plan["setup"]]
    rng = np.random.default_rng(0)
    burst = [client.submit(
        {"prompt": rng.integers(1, vocab_size, n, dtype=np.int32),
         "max_new": m, "tags": {"setup": True}}, None)
        for n, m in warm_requests]
    threads = [threading.Thread(target=client.read, args=(r,), daemon=True)
               for r in burst]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    bad = [r["error"] for r in records + burst if r["error"]
           or len(r["tokens"]) != r["request"]["max_new"]]
    if bad:
        raise SystemExit(f"set-up traffic failed: {bad[:3]}")


def cut_request_failed(rec, cutoff, tpot_s):
    """Whether a request still running at the cutoff has stalled or crawls
    (see _STALL_S).  One without a token yet is queued or in prefill, which
    the client cannot tell from a stall: it is not counted as failed here,
    and counts as the worst in the times to first token."""
    if rec["first"] is None:
        return False
    if cutoff - rec["last"] > _STALL_S:
        return True
    running = cutoff - rec["first"]
    return bool(tpot_s and running > _STALL_S and len(rec["tokens"])
                < running / (_SLOW_FACTOR * tpot_s))


def check_correct(published, seed, records, limits, pad_to, n_sample,
                  quantize=None):
    """Decide ``correct`` for the requests the window served.

    Every finished request has to have got exactly the tokens it asked for,
    and none more than that.  Then a sample of the requests that got tokens
    (those cut at the cutoff too: they ran while the engine was fullest),
    drawn from the seed and with the longest in it, goes through the plain
    reference once each (prompt + served tokens), and each served token's
    reference logit is held against the reference's best at its position.
    ``quantize`` ("int8", "fp8" or both with a comma) also reads the control:
    the token the reference in that lower precision puts first, at the same
    positions.  Returns (ok, [(name, value, limit)], {"served": gaps,
    <control>: gaps}).
    """
    finished = [r for r in records if r["error"] is None and r["tokens"]
                and (r.get("cut")
                     or len(r["tokens"]) == r["request"]["max_new"])]
    wrong_count = sum(1 for r in records if r["error"] is None and (
        len(r["tokens"]) > r["request"]["max_new"] or not r.get("cut")
        and len(r["tokens"]) != r["request"]["max_new"]))
    numbers = [("requests_with_wrong_token_count", float(wrong_count), 0.0)]
    if not finished:
        return False, numbers + [("requests_compared", 0.0, None)], {}
    order = np.random.default_rng(seed).permutation(len(finished))
    longest = max(range(len(finished)), key=lambda i: (
        len(finished[i]["request"]["prompt"]) + len(finished[i]["tokens"])))
    picks = [longest] + [int(i) for i in order if i != longest]
    picks = picks[:n_sample]
    ref = reference.Reference(published, seed, quantize=None)
    lows = {q: reference.Reference(published, seed, quantize=q)
            for q in (quantize.split(",") if quantize else ())}
    gaps, control_gaps = [], {q: [] for q in lows}
    for i in picks:
        r = finished[i]
        prompt, served = r["request"]["prompt"], np.asarray(r["tokens"])
        tokens = np.concatenate([prompt, served])
        start, rows = len(prompt) - 1, len(served)
        # One static number of rows (the most a request may ask for), so
        # the head compiles once.
        rows_pad = limits["max_new_tokens"]
        start_pad = min(start, max(0, pad_to - rows_pad))
        logits = np.asarray(ref.logits(tokens, start_pad, rows_pad, pad_to))
        logits = logits[start - start_pad:start - start_pad + rows]
        gaps.append(reference.served_gaps(logits, served))
        for q, low in lows.items():
            low_logits = np.asarray(
                low.logits(tokens, start_pad, rows_pad, pad_to))
            low_logits = low_logits[start - start_pad:
                                    start - start_pad + rows]
            control_gaps[q].append(reference.served_gaps(
                logits, low_logits.argmax(-1)))
    gaps = np.concatenate(gaps)
    control_gaps = {q: np.concatenate(g) for q, g in control_gaps.items()}
    numbers += [
        ("requests_compared", float(len(picks)), None),
        ("served_tokens_compared", float(gaps.shape[0]), None),
        ("served_logit_gap_max", float(gaps.max()),
         limits["served_logit_gap_max"]),
        ("served_logit_gap_mean", float(gaps.mean()),
         limits["served_logit_gap_mean"]),
        # For the record: how often the served token is not the
        # reference's first.
        ("served_not_first_share", float((gaps > 0).mean()), None),
    ]
    for q, cg in control_gaps.items():
        numbers += [(f"control_{q}_logit_gap_max", float(cg.max()), None),
                    (f"control_{q}_logit_gap_mean", float(cg.mean()), None),
                    (f"control_{q}_not_first_share",
                     float((cg > 0).mean()), None)]
    ok = all(limit is None or value <= limit
             for name, value, limit in numbers
             if not name.startswith("control_"))
    return ok, numbers, {"served": gaps.tolist(), **{
        q: g.tolist() for q, g in control_gaps.items()}}


def free_device(jax):
    """Delete every array the process holds on the device: the program's
    state is done with, and the reference needs the room."""
    gc.collect()
    for a in jax.live_arrays():
        a.delete()
    gc.collect()


def run(ctx):
    import jax

    from benchmark.lib import stats
    from kubeflow_tpu.runtime import bootstrap

    if ctx["args"].rehearse:
        # XLA:CPU warns on every load of its own cached executable.
        jax.config.update("jax_enable_compilation_cache", False)
    else:
        bootstrap.configure_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    config, mix, args = ctx["config"], ctx["mix"], ctx["args"]
    published, engine_sizes = config, ctx["sizes"]["engine"]
    checks = ctx["sizes"]["correct"]
    clock = time.perf_counter
    log = ctx["log"]

    plan = ctx["generator"].plan(mix["params"], args.seed, args.seconds,
                                 published["vocab_size"])
    engine = build_engine(published, engine_sizes, args.seed, ctx["root"],
                          config.get("dtype", "bfloat16"))
    client = Client(engine)
    facts = {"prefill_chunk_tokens": engine.chunk_w, "slots": engine.slots,
             "kv_block_tokens": engine.kv_block_tokens,
             "max_len": engine.max_len}
    opened = {}
    try:
        warm(plan, client, published["vocab_size"],
             engine_sizes["warm_requests"])
        samples, stop_sampling = [], threading.Event()

        def sample_pool():
            while not stop_sampling.wait(1.0):
                s = engine.stats()
                samples.append((clock(), s["kv_blocks_used"],
                                s["active_slots"], s["queue_depth"]))

        sampler = threading.Thread(target=sample_pool, daemon=True)
        tracer = ctx["tracer"]

        def at_t0():
            """The window opens: everything before this is set-up."""
            opened["before"] = counters(engine)
            opened["t0"] = clock()
            sampler.start()
            tracer.start(opened["t0"], args.seconds)

        if plan["mode"] == "open":
            ramp_s = -min([r["due_s"] for r in plan["requests"]] + [0.0])
            t0 = clock() + ramp_s
            records, threads = drive_open(client, plan["requests"], t0,
                                          at_t0)
            time.sleep(max(0.0, opened["t0"] + args.seconds - clock()))
            exhausted = []
        else:
            at_t0()
            records, threads, exhausted = drive_closed(
                client, plan["clients"], opened["t0"] + args.seconds)
        t0, before = opened["t0"], opened["before"]
        setup_s = t0 - ctx["process_start"]
        t1 = clock()
        at_close = counters(engine)
        tracer.stop()
        stop_sampling.set()
        client.cutoff = t1 + _DRAIN_S
        join_all(threads, client.cutoff)
        sampler.join()
        after = counters(engine)
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in jax.local_devices())
    finally:
        # Requests still waiting for a token end here, as "cut".
        engine.close(drain_s=0.0)
    stuck = join_all(threads, clock() + 10.0)
    records = list(records)
    del engine, client
    free_device(jax)

    window = {"t0": t0, "t1": t1, "seconds": t1 - t0}
    counted = [r for r in records if not r["request"]["tags"].get("ramp")]
    attempted = len(counted)
    # Failed: an error, another count of tokens than asked, or a request
    # still running at the cutoff that has stalled or crawls.  The ramp's
    # requests are set-up traffic and not attempted, but one of them that
    # fails is a failure of the run all the same.
    tpot_s = stats.time_per_token_s(records, t0, t1)
    failed_records = [r for r in records if r["error"] is not None or (
        len(r["tokens"]) != r["request"]["max_new"] if not r.get("cut")
        else cut_request_failed(r, t1 + _DRAIN_S, tpot_s))]
    failed = len(failed_records) + len(stuck)
    for r in failed_records[:3]:
        log(f"failed request: {r['error']} cut={bool(r.get('cut'))} "
            f"tokens={len(r['tokens'])} asked={r['request']['max_new']}")

    t_ref = clock()
    limits = dict(checks, max_new_tokens=engine_sizes["max_new_tokens"])
    ok, numbers, gaps = check_correct(
        published, args.seed, records, limits,
        checks["reference_pad_to"], checks["sample"],
        quantize=ctx.get("control"))
    for name, value, limit in numbers:
        log(f"compared {name} = {value!r} limit "
            f"{'none' if limit is None else repr(limit)}")
    cut = [r for r in records if r.get("cut")]
    quarters = [[a + q for t, _, a, q in samples
                 if t0 + i * (t1 - t0) / 4 <= t < t0 + (i + 1) * (t1 - t0) / 4]
                for i in range(4)]
    log(f"setup took {setup_s:.2f} s; reference took {clock() - t_ref:.1f} s;"
        f" {len(records)} requests sent ({len(records) - attempted} of them "
        f"the ramp), {len(cut)} still running at the cutoff "
        f"({sum(1 for r in cut if r['first'] is None)} without a token),"
        f" {len(stuck)} stuck; requests in the engine (mean per quarter of "
        f"the window) {[round(sum(q) / max(1, len(q)), 1) for q in quarters]}"
        f"; programs {after['compiled_programs']}")
    compiled_in_window = before["compiled_programs"] != \
        after["compiled_programs"]
    if compiled_in_window:
        log(f"PROGRAMS COMPILED INSIDE THE WINDOW: "
            f"{before['compiled_programs']} -> {after['compiled_programs']}")
    correct = bool(ok and failed == 0 and not exhausted
                   and not compiled_in_window)
    return {
        "correct": correct, "attempted": attempted, "failed": failed,
        "setup_s": setup_s, "window": window, "drain_s": _DRAIN_S,
        "records": records,
        "numbers": numbers,
        "counters": {"before": before, "at_close": at_close, "after": after},
        "samples": samples, "memory_peak_bytes": int(peak),
        "config": config, "mix": mix, "engine": facts,
        "gaps": gaps,
    }
