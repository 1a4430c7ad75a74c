#!/usr/bin/env python3
"""Drive the main path once on the attached TPU and say whether it worked.

    python chip_smoke.py             # one chip (what the driver runs)
    python chip_smoke.py --chips 4   # the paths across chips, and nothing else
    python chip_smoke.py --rehearse  # tiny sizes on the CPU: control flow only

One chip: train the 188M LM for a few steps through ``tools.train_lm``
(flash attention, remat, a verified checkpoint), restore and export that
checkpoint for ``serving.loaders:lm_generate``, serve the export through
``python -m kubeflow_tpu.serving.main`` with its defaults and answer
``:generate`` requests over HTTP, then start the server a second time to
see the compile cache hit.

Four chips: ``train_lm`` under ``--mesh fsdp=2,tensor=2`` against a
one-device child on the same seed and global batch (losses compared), and
the decode engine under ``tensor=4`` against the one-device engine (last-
position logits and greedy tokens compared, placement asserted).

An accelerator belongs to one process at a time, so this parent never
imports JAX: every phase is a child, one alive at a time, and each child
prints the device it computes on (``KFT_DEVICE``, runtime/bootstrap.py).
Any phase that fails, or any child that is not on a TPU, makes the script
exit non-zero.  ``--rehearse`` pins the children to the CPU and can
therefore never report platform "tpu".

The last stdout line is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``.
Per-phase facts are JSON lines before it; full child logs go under
``chiprun_out/chip_smoke/``.
"""

from __future__ import annotations

import argparse
import http.client
import json
import math
import os
import pathlib
import random
import re
import shutil
import signal
import statistics
import subprocess
import sys
import threading
import time

ROOT = pathlib.Path(__file__).resolve().parent

# The 188M LM at full width: nothing is cut.
FULL = {
    "model": {"vocab_size": 32_000, "d_model": 1024, "n_layers": 12,
              "n_heads": 8, "n_kv_heads": 8, "d_ff": 2816, "head_dim": 128,
              "max_seq_len": 2048},
    "dtype": "bfloat16", "attention": "flash", "batch": 8, "train_steps": 6,
    "max_new": 128, "prefix": 128,
    "lens": [32, 64, 96, 200, 256], "budgets": [16, 32, 64, 128, 48],
}
# --rehearse: same control flow, toy widths.  The Pallas kernel cannot run
# on the CPU backend (ops/flash.py raises), so attention is the XLA path.
TINY = {
    "model": {"vocab_size": 256, "d_model": 64, "n_layers": 2, "n_heads": 4,
              "n_kv_heads": 4, "d_ff": 128, "head_dim": 16,
              "max_seq_len": 128},
    "dtype": "float32", "attention": "dot", "batch": 8, "train_steps": 6,
    "max_new": 24, "prefix": 32,
    "lens": [8, 16, 24, 50, 64], "budgets": [4, 8, 16, 24, 12],
}
# Loss agreement across layouts, and logits across placements: activations
# are bf16 (8 bits of mantissa) and a sharded reduction sums in another
# order.  Set beforehand from the dtype, not from what a run showed.
LOSS_TOL = 0.05
LOGIT_RTOL = 0.05
# The whole run must end inside 1200 s; past this it gives up and fails.
DEADLINE_S = 1100.0
# Shows a child one chip of a four-chip host (libtpu's process bounds).
ONE_CHIP_ENV = {"TPU_VISIBLE_CHIPS": "0", "TPU_VISIBLE_DEVICES": "0",
                "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
                "TPU_PROCESS_BOUNDS": "1,1,1"}


class SmokeFailure(Exception):
    pass


def check(cond, message):
    if not cond:
        raise SmokeFailure(message)


def fact(**fields):
    print(json.dumps(fields), flush=True)


# ---------------------------------------------------------------------------
# Parent side: children, one at a time
# ---------------------------------------------------------------------------

class Child:
    """One child process: output teed to a log file, the bootstrap marker
    lines (KFT_DEVICE / KFT_MEMORY / KFT_SERVING_READY) parsed as they
    arrive.  A device line naming another platform than expected kills the
    child at once — it must not carry on on the wrong hardware."""

    live: "list[Child]" = []

    def __init__(self, name, argv, env, log_dir, expect_platform):
        if Child.live:
            raise SmokeFailure(f"child {name!r} started while "
                               f"{Child.live[0].name!r} is alive")
        self.name = name
        self.expect_platform = expect_platform
        self.device = None
        self.memory = None
        self.ports = None
        self.result = None
        self.lines = []
        self.ready = threading.Event()
        self.wrong_device = None
        self.t0 = time.monotonic()
        self.ready_s = None
        self._log = open(log_dir / f"{name}.log", "w")
        self.proc = subprocess.Popen(
            argv, env=env, cwd=ROOT, text=True, errors="replace",
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            start_new_session=True)
        Child.live.append(self)
        self._reader = threading.Thread(target=self._read, daemon=True)
        self._reader.start()

    def _read(self):
        for line in self.proc.stdout:
            self._log.write(line)
            self._log.flush()
            line = line.rstrip("\n")
            self.lines.append(line)
            if line.startswith("KFT_DEVICE "):
                self.device = json.loads(line.split(" ", 1)[1])
                if self.device["platform"] != self.expect_platform:
                    self.wrong_device = self.device
                    self.kill()
            elif line.startswith("KFT_MEMORY "):
                self.memory = json.loads(line.split(" ", 1)[1])
            elif line.startswith("CHILD_RESULT "):
                self.result = json.loads(line.split(" ", 1)[1])
            else:
                m = re.search(r"KFT_SERVING_READY rest=(\d+)", line)
                if m:
                    self.ports = int(m.group(1))
                    self.ready_s = time.monotonic() - self.t0
                    self.ready.set()
        self.ready.set()  # EOF: wake a waiter so it sees the exit

    def kill(self):
        if self.proc.poll() is None:
            try:
                os.killpg(self.proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass

    def wait(self, timeout_s):
        """Exit code; kills and fails on timeout.  Always reaps."""
        try:
            rc = self.proc.wait(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            self.kill()
            self.proc.wait()
            self._finish()
            raise SmokeFailure(
                f"{self.name}: no exit within {timeout_s:.0f}s\n"
                + self.tail())
        self._finish()
        return rc

    def _finish(self):
        self._reader.join(timeout=10)
        self._log.close()
        if self in Child.live:
            Child.live.remove(self)
        self.wall_s = time.monotonic() - self.t0

    def tail(self, n=40):
        return "\n".join(f"  [{self.name}] {l}" for l in self.lines[-n:])

    def require_device(self, count):
        check(self.wrong_device is None,
              f"{self.name}: runs on {self.wrong_device}, not on "
              f"platform {self.expect_platform!r}")
        check(self.device is not None,
              f"{self.name}: printed no KFT_DEVICE line\n" + self.tail())
        check(self.device["count"] == count,
              f"{self.name}: sees {self.device['count']} device(s), "
              f"expected {count}")

    def require_exit_0(self, timeout_s, count):
        rc = self.wait(timeout_s)
        self.require_device(count)
        check(rc == 0, f"{self.name}: exit code {rc}\n" + self.tail())

    def deprecations(self, most=10):
        seen = []
        for line in self.lines:
            if re.search(r"deprecat", line, re.I) and line[:300] not in seen:
                seen.append(line[:300])
        return seen[:most]


class Run:
    """What one invocation shares between phases: sizes, the work and log
    directories, the children's environment, the overall deadline."""

    def __init__(self, args):
        self.rehearse = args.rehearse
        self.seed = args.seed
        self.size = TINY if args.rehearse else FULL
        self.expect_platform = "cpu" if args.rehearse else "tpu"
        self.out = pathlib.Path(args.out).resolve()
        self.work = self.out / "work"
        shutil.rmtree(self.work, ignore_errors=True)
        self.work.mkdir(parents=True)
        self.deadline = time.monotonic() + DEADLINE_S
        self.cache_dir = pathlib.Path(
            os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or ROOT / ".jax_cache")
        self.devices = []

    def env(self, devices=None, one_chip=False):
        env = dict(os.environ, PYTHONPATH=str(ROOT),
                   PYTHONWARNINGS="default::DeprecationWarning")
        if self.rehearse:
            n = 1 if one_chip else (devices or 1)
            env["JAX_PLATFORMS"] = "cpu"
            env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
        elif one_chip:
            env.update(ONE_CHIP_ENV)
        return env

    def left(self, cap_s):
        left = self.deadline - time.monotonic()
        check(left > 5, "out of time before this phase could start")
        return min(cap_s, left)

    def cache_entries(self):
        if not self.cache_dir.is_dir():
            return 0
        return sum(1 for p in self.cache_dir.rglob("*") if p.is_file())

    def child(self, name, argv, **env_kw):
        return Child(name, argv, self.env(**env_kw), self.out,
                     self.expect_platform)

    def model_flags(self):
        m = self.size["model"]
        return ["--d-model", m["d_model"], "--n-layers", m["n_layers"],
                "--n-heads", m["n_heads"], "--n-kv-heads", m["n_kv_heads"],
                "--d-ff", m["d_ff"], "--head-dim", m["head_dim"],
                "--vocab-size", m["vocab_size"],
                "--seq-len", m["max_seq_len"],
                "--attention", self.size["attention"], "--remat"]

    def noted(self, child):
        self.devices.append(child.device)
        return {"device": child.device, "wall_s": round(child.wall_s, 2),
                "deprecation_warnings": child.deprecations()}


def run_train(run, name, steps, *, per_device, mesh="", ckpt=None,
              devices=1, one_chip=False):
    """One ``tools.train_lm`` child, checked and reported; returns its
    losses, where its params were placed, and the finished child."""
    argv = [sys.executable, "-m", "kubeflow_tpu.tools.train_lm",
            *run.model_flags(), "--batch-size-per-device", per_device,
            "--steps", steps, "--log-every", 1, "--max-restarts", 0]
    if mesh:
        argv += ["--mesh", mesh]
    if ckpt:
        argv += ["--checkpoint-dir", ckpt, "--checkpoint-every", 10_000]
    before = run.cache_entries()
    child = run.child(name, [str(a) for a in argv], devices=devices,
                      one_chip=one_chip)
    child.require_exit_0(run.left(600), 1 if one_chip else devices)
    records = [json.loads(l) for l in child.lines
               if l.startswith("{") and '"train_step"' in l]
    losses = [r["loss"] for r in records]
    check(len(losses) == steps,
          f"{name}: {len(losses)} step records for {steps} steps")
    check(all(math.isfinite(x) for x in losses),
          f"{name}: non-finite loss in {losses}")
    times = [r["step_time_s"] for r in records]
    steady = statistics.median(times[1:])
    placed = None
    for line in child.lines:
        m = re.search(r"train state placed: (\d+) param leaves, each on "
                      r"(\d+)\.\.(\d+) of the mesh's (\d+)", line)
        if m:
            placed = [int(g) for g in m.groups()]
    check(placed is not None, f"{name}: no placement line\n" + child.tail())
    fact(phase=name, ok=True, **run.noted(child), steps=steps,
         steps_after_compile=steps - 1, losses=losses,
         first_step_s=times[0], steady_step_s=steady,
         compile_s=round(times[0] - steady, 3),
         mfu=records[-1].get("mfu"),
         param_leaves=placed[0], leaf_devices=placed[1:3],
         memory=child.memory, cache_dir=str(run.cache_dir),
         cache_entries_written=run.cache_entries() - before)
    return losses, placed, child


def phase_train(run):
    steps = run.size["train_steps"]
    ckpt = run.work / "ckpt"
    losses, _, _ = run_train(run, "train", steps,
                             per_device=run.size["batch"], ckpt=ckpt)
    check(steps - 1 >= 3, "fewer than three steps after the compile")
    ln_v = math.log(run.size["model"]["vocab_size"])
    check(abs(losses[0] - ln_v) < 1.0,
          f"first loss {losses[0]:.3f} not within 1.0 of ln(vocab) "
          f"{ln_v:.3f}")
    return ckpt


def phase_export(run, ckpt):
    export_dir = run.work / "export"
    before = run.cache_entries()
    child = run.child("export", [
        sys.executable, str(ROOT / "chip_smoke.py"), "--child", "export",
        "--spec", json.dumps({
            "size": run.size, "ckpt": str(ckpt), "export": str(export_dir),
            "want_step": run.size["train_steps"] - 1})])
    child.require_exit_0(run.left(300), 1)
    check(child.result is not None, "export: no CHILD_RESULT\n"
          + child.tail())
    fact(phase="export", ok=True, **run.noted(child), **child.result,
         cache_entries_written=run.cache_entries() - before)
    return export_dir


def generate(port, tokens, budget, timeout_s=600):
    """One streamed :generate request -> (status, new tokens, done line,
    [t_start, t_end])."""
    t0 = time.monotonic()
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout_s)
    try:
        conn.request("POST", "/model/lm:generate",
                     json.dumps({"tokens": tokens,
                                 "max_new_tokens": budget}).encode(),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            return resp.status, [], resp.read().decode(), (t0, t0)
        got, last = [], None
        # Read to the end of the chunked body, past the done line: closing
        # on an unread terminator resets the server's connection.
        for line in iter(resp.readline, b""):
            if line.strip():
                last = json.loads(line)
                got += last.get("tokens", [])
        return 200, got, last, (t0, time.monotonic())
    finally:
        conn.close()


def http_get(port, path):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        body = resp.read().decode()
        check(resp.status == 200, f"GET {path}: {resp.status} {body[:200]}")
        return body
    finally:
        conn.close()


def requests_for(run):
    """The seeded request set: (label, prompt tokens, budget).  ``A`` and
    ``B`` share their first ``prefix`` tokens."""
    rng = random.Random(run.seed)
    vocab = run.size["model"]["vocab_size"]

    def toks(n):
        return [rng.randrange(1, vocab) for _ in range(n)]

    prefix = toks(run.size["prefix"])
    quarter = run.size["prefix"] // 4
    a = ("A", prefix + toks(quarter), run.size["budgets"][0])
    b = ("B", prefix + toks(quarter + quarter // 4),
         run.size["budgets"][1])
    wave = [(f"len{n}", toks(n), k)
            for n, k in zip(run.size["lens"], run.size["budgets"])] + [b]
    return a, wave


def start_server(run, name, export_dir):
    child = run.child(name, [
        sys.executable, "-m", "kubeflow_tpu.serving.main",
        "--model_name", "lm", "--model_base_path", str(export_dir),
        "--port", "0", "--grpc_port", "0"])
    child.ready.wait(run.left(300))
    if child.ports is None:
        child.kill()
        child.wait(30)
        child.require_device(1)
        raise SmokeFailure(f"{name}: never became ready\n" + child.tail())
    child.require_device(1)
    return child


def stop_server(run, child):
    """SIGTERM, and the drain must exit 0."""
    child.proc.send_signal(signal.SIGTERM)
    rc = child.wait(run.left(120))
    check(rc == 0, f"{child.name}: drain exited {rc}\n" + child.tail())


def answered(label, prompt, budget, reply):
    status, got, last, _ = reply
    check(status == 200, f"request {label}: HTTP {status} {last}")
    check(last == {"done": True, "tokens_emitted": budget}
          and len(got) == budget,
          f"request {label}: asked {budget} tokens, got {len(got)}, "
          f"last line {last}")
    return got


def phase_serve(run, export_dir):
    a, wave = requests_for(run)
    before = run.cache_entries()
    server = start_server(run, "serve", export_dir)
    try:
        port = server.ports
        t0 = time.monotonic()
        first_a = answered(*a, generate(port, a[1], a[2]))
        first_request_s = time.monotonic() - t0
        replies = [None] * len(wave)

        def client(i):
            replies[i] = generate(port, wave[i][1], wave[i][2])

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(wave))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        returned = {}
        for req, reply in zip(wave, replies):
            check(reply is not None, f"request {req[0]}: no reply")
            returned[req[0]] = len(answered(*req, reply))
        spans = [r[3] for r in replies]
        in_flight = max(sum(1 for s, e in spans if s <= t < e)
                        for t, _ in spans)
        check(in_flight >= 4,
              f"only {in_flight} requests were in flight at once")
        again = answered(*a, generate(port, a[1], a[2]))
        check(again == first_a,
              "a repeated greedy request returned other tokens")
        hits = sum(float(l.rsplit(" ", 1)[1])
                   for l in http_get(port, "/metrics").splitlines()
                   if l.startswith("kft_engine_prefix_hits_total"))
        check(hits >= 1, "/metrics shows no prefix hit")
        stats = json.loads(http_get(port, "/model/lm:stats"))["batcher"]
        programs = stats["compiled_programs"]
        check(programs["chunked_prefill"] == 1
              and programs["decode_rounds"] == 1,
              f"engine programs not compiled once each: {programs}")
        # No hidden fallback: on the chip every decode step goes through
        # ops/paged_attention.py; off it (the rehearsal) none does.
        kernel_steps = stats["steps"] if run.expect_platform == "tpu" else 0
        check(stats["steps"] > 0
              and stats["decode_kernel_steps"] == kernel_steps,
              f"decode_kernel_steps {stats['decode_kernel_steps']} of "
              f"{stats['steps']} steps on {run.expect_platform}")
        stop_server(run, server)
    finally:
        server.kill()
    returned["A"] = len(first_a)
    fact(phase="serve", ok=True, **run.noted(server),
         ready_s=round(server.ready_s, 2),
         first_request_s=round(first_request_s, 2),
         requests=len(wave) + 2, tokens_returned=returned,
         max_in_flight=in_flight, prefix_hits=hits,
         compiled_programs=programs,
         steps=stats["steps"],
         decode_kernel_steps=stats["decode_kernel_steps"],
         # The engine's largest program by the compiler's account beside
         # both sides of its paged pool: a program that copies the pool
         # holds a second one among its temporaries.
         compiled_peak_bytes=stats["compiled_peak_bytes"],
         kv_pool_bytes=stats["kv_blocks"] * stats["kv_block_tokens"]
         * stats["kv_bytes_per_token"],
         mean_occupancy=stats.get("mean_occupancy"),
         memory=server.memory,
         cache_entries_written=run.cache_entries() - before)

    # The same server a second time: what the compile cache buys.
    before = run.cache_entries()
    server2 = start_server(run, "serve_again", export_dir)
    try:
        t0 = time.monotonic()
        second_a = answered(*a, generate(server2.ports, a[1], a[2]))
        second_request_s = time.monotonic() - t0
        check(second_a == first_a,
              "the restarted server returned other greedy tokens")
        stop_server(run, server2)
    finally:
        server2.kill()
    fact(phase="serve_again", ok=True, **run.noted(server2),
         ready_s=round(server2.ready_s, 2),
         first_start_ready_s=round(server.ready_s, 2),
         first_request_s=round(second_request_s, 2),
         first_start_first_request_s=round(first_request_s, 2),
         cache_entries_written=run.cache_entries() - before)


def phases_four_chips(run):
    n = 4
    probe = run.child("probe_one_chip", [
        sys.executable, str(ROOT / "chip_smoke.py"), "--child", "probe"],
        one_chip=True)
    probe.require_exit_0(run.left(120), 1)
    fact(phase="probe_one_chip", ok=True, **run.noted(probe))
    run.devices.clear()  # the last line's device is the four-chip one

    steps, batch = 3, run.size["batch"]
    ref, _, _ = run_train(run, "train_one_device", steps,
                          per_device=batch, one_chip=True)
    run.devices.clear()
    got, placed, child = run_train(
        run, "train_fsdp2_tensor2", steps, per_device=batch // n,
        mesh="fsdp=2,tensor=2", devices=n)
    diffs = [abs(x - y) for x, y in zip(ref, got)]
    check(placed[1:] == [n, n, n],
          f"params are not on all {n} devices: leaves on "
          f"{placed[1]}..{placed[2]} of {placed[3]}")
    if not run.rehearse:
        check(child.memory and len(child.memory) == n and all(
            (d["peak_bytes_in_use"] or 0) > 0 for d in child.memory),
            f"not every device held bytes: {child.memory}")
    check(max(diffs) <= LOSS_TOL,
          f"losses differ by {max(diffs):.4f} > {LOSS_TOL}: one device "
          f"{ref}, fsdp=2,tensor=2 {got}")
    fact(phase="train_compare", ok=True, one_device=ref,
         fsdp2_tensor2=got, abs_diff=diffs, tolerance=LOSS_TOL)

    a, wave = requests_for(run)
    before = run.cache_entries()
    child = run.child("serve_tensor4", [
        sys.executable, str(ROOT / "chip_smoke.py"), "--child", "serve4",
        "--spec", json.dumps({
            "size": run.size, "seed": run.seed, "work": str(run.work),
            "requests": [a] + wave, "check_memory": not run.rehearse})],
        devices=n)
    child.require_exit_0(run.left(600), n)
    check(child.result is not None, "serve_tensor4: no CHILD_RESULT\n"
          + child.tail())
    fact(phase="serve_tensor4", ok=True, **run.noted(child), **child.result,
         cache_entries_written=run.cache_entries() - before)


def parent(args):
    run = Run(args)
    error = None
    try:
        if args.chips == 4:
            phases_four_chips(run)
        else:
            ckpt = phase_train(run)
            export_dir = phase_export(run, ckpt)
            phase_serve(run, export_dir)
        first = run.devices[0]
        check(all(d == first for d in run.devices),
              f"children disagree on the device: {run.devices}")
        check(first["platform"] == run.expect_platform
              and first["count"] == args.chips,
              f"ran on {first}, wanted {args.chips} x "
              f"{run.expect_platform}")
    except SmokeFailure as e:
        error = str(e)
    finally:
        while Child.live:
            child = Child.live.pop()
            child.kill()
            child.proc.wait()
        # Checkpoints and exports are gigabytes; the logs are what a
        # reader of the output directory wants.
        shutil.rmtree(run.work, ignore_errors=True)
    assert "jax" not in sys.modules, "the parent imported jax"
    last = {"ok": error is None,
            "device": run.devices[0] if run.devices else None}
    if run.rehearse:
        last["rehearsal"] = True
    if error:
        print(error, file=sys.stderr, flush=True)
        last["error"] = error.splitlines()[0][:300]
    print(json.dumps(last), flush=True)
    return 0 if error is None else 1


# ---------------------------------------------------------------------------
# Child side: the phases that have no entrypoint of their own.  Only these
# functions import JAX.
# ---------------------------------------------------------------------------

def child_start():
    from kubeflow_tpu.runtime import bootstrap

    bootstrap.configure_compile_cache()
    return bootstrap.report_devices()


def child_probe(_spec):
    child_start()


def child_export(spec):
    """Restore the trainer's checkpoint the way a user would (the Trainer's
    own resume path, tests/test_real_data_full_loop.py) and export it."""
    child_start()
    import jax
    import numpy as np
    import optax

    from kubeflow_tpu.models.transformer import lm_task
    from kubeflow_tpu.parallel import MeshSpec
    from kubeflow_tpu.runtime.checkpoint import CheckpointManager
    from kubeflow_tpu.runtime.metrics import MetricsLogger
    from kubeflow_tpu.runtime.train import Trainer
    from kubeflow_tpu.serving.export import export
    from kubeflow_tpu.serving.loaders import _model_config

    size = spec["size"]
    cfg = _model_config(dict(size["model"], attention=size["attention"],
                             remat=True))
    mesh = MeshSpec().build()
    init_fn, loss_fn = lm_task(cfg, mesh=mesh)
    with CheckpointManager(spec["ckpt"]) as mgr, \
            open(os.devnull, "w") as devnull:
        verified = mgr.latest_verified_step()
        check(verified == spec["want_step"],
              f"latest verified checkpoint step is {verified}, wanted "
              f"{spec['want_step']}")
        trainer = Trainer(init_fn=init_fn, loss_fn=loss_fn,
                          tx=optax.adamw(3e-4), mesh=mesh,
                          metrics=MetricsLogger(stream=devnull))
        state, resumed = mgr.restore_or_init(trainer.create_state())
    check(resumed == spec["want_step"] + 1,
          f"restore resumed at step {resumed}")
    params = jax.tree_util.tree_map(np.asarray, state.params)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    check(all(np.isfinite(x).all()
              for x in jax.tree_util.tree_leaves(params)),
          "restored params are not finite")
    export(spec["export"], 1, {"params": params},
           loader="kubeflow_tpu.serving.loaders:lm_generate",
           config={"model": dict(size["model"], dtype=size["dtype"]),
                   "max_new_tokens": size["max_new"], "temperature": 0.0})
    print("CHILD_RESULT " + json.dumps({
        "verified_step": verified, "params": int(n_params),
        "export": spec["export"]}), flush=True)


def child_serve4(spec):
    """The decode engine under tensor=4 against the one-device engine, in
    ONE process that holds all four chips: same export, same factory the
    ``--mesh`` flag of serving.main feeds, same greedy requests."""
    found = child_start()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubeflow_tpu.models.generate import (
        _forward_with_cache,
        init_cache,
    )
    from kubeflow_tpu.models.transformer import Transformer
    from kubeflow_tpu.runtime import bootstrap
    from kubeflow_tpu.serving.export import export
    from kubeflow_tpu.serving.loaders import _model_config
    from kubeflow_tpu.serving.main import batcher_factory
    from kubeflow_tpu.serving.model_server import ModelServer

    n = 4
    size = spec["size"]
    overrides = dict(size["model"], dtype=size["dtype"])
    cfg = _model_config(overrides)
    variables = Transformer(cfg).init(
        jax.random.key(spec["seed"]), np.zeros((1, 8), np.int32))
    base = pathlib.Path(spec["work"]) / "export4"
    export(base, 1, variables,
           loader="kubeflow_tpu.serving.loaders:lm_generate",
           config={"model": overrides, "max_new_tokens": size["max_new"],
                   "temperature": 0.0})
    del variables
    server = ModelServer()
    server.add_model("lm", str(base))
    model = server.get("lm")

    def engine(mesh):
        # serving.main's defaults, as its flags hand them to the factory.
        return batcher_factory(micro_batch_size=0, batch_timeout_s=0.005,
                               decode_rounds=8, mesh=mesh)(model)

    requests = [(label, np.asarray(toks, np.int32)[None], budget)
                for label, toks, budget in spec["requests"]]

    def answers(eng):
        out = [None] * len(requests)

        def client(i):
            _, toks, budget = requests[i]
            out[i] = np.asarray(eng.submit(
                {"tokens": toks, "max_new_tokens": budget})["tokens"])[0]

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(requests))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for (label, toks, budget), row in zip(requests, out):
            check(row is not None and row.shape[0] == toks.shape[1] + budget,
                  f"request {label}: wrong number of tokens back")
        return out

    one, four = engine(""), engine(f"tensor={n}")
    try:
        leaves = jax.tree_util.tree_leaves(four.params)
        spans = sorted({len(x.sharding.device_set) for x in leaves})
        check(spans == [n], f"tensor={n} params sit on {spans} devices")
        sharded = sum(1 for x in leaves if not x.sharding.is_fully_replicated)
        rows_one, rows_four = answers(one), answers(four)
        memory = bootstrap.report_memory()
        if spec["check_memory"]:
            check(all((m["bytes_in_use"] or 0) > 0 for m in memory),
                  f"not every device holds bytes: {memory}")

        # Logits of one forward over [prompt + reference continuation],
        # right-padded to one static length (causal: a position never
        # sees the pad after it), under each placement's own params.
        width = max(r.shape[0] for r in rows_one)

        @jax.jit
        def logits_of(params, tokens):
            out, _ = _forward_with_cache(
                cfg, params, tokens, init_cache(cfg, 1, width), 0)
            return out[0]

        compared = []
        for (label, toks, budget), r1, r4 in zip(requests, rows_one,
                                                 rows_four):
            padded = np.zeros((1, width), np.int32)
            padded[0, :r1.shape[0]] = r1
            l1 = np.asarray(logits_of(one.params, jnp.asarray(padded)))
            l4 = np.asarray(logits_of(four.params, jnp.asarray(padded)))
            last = toks.shape[1] - 1
            scale = float(np.abs(l1[last]).max())
            diff = float(np.abs(l1[last] - l4[last]).max())
            check(np.isfinite(l1[last]).all() and np.isfinite(l4[last]).all(),
                  f"request {label}: non-finite logits")
            check(diff <= LOGIT_RTOL * max(scale, 1.0),
                  f"request {label}: last-position logits differ by "
                  f"{diff:.4f} at scale {scale:.3f}")
            row = {"request": label, "prompt_len": int(toks.shape[1]),
                   "budget": budget, "logit_max_abs_diff": round(diff, 5),
                   "logit_scale": round(scale, 4)}
            differs = np.nonzero(r1 != r4)[0]
            row["tokens_equal"] = differs.size == 0
            if differs.size:
                # Position p's token was chosen from the logits at p-1.
                p = int(differs[0])
                top = np.sort(l1[p - 1])[::-1]
                gap = float(top[0] - top[1])
                row.update(first_divergence=p - int(toks.shape[1]),
                           one_device_token=int(r1[p]),
                           tensor4_token=int(r4[p]),
                           top2_logit_gap=round(gap, 5))
                # A flip is a near-tie or it is a fault: the gap between
                # the two candidates must sit inside the placements' own
                # logit disagreement.
                pair = abs(float(l1[p - 1][r1[p]] - l1[p - 1][r4[p]]))
                check(pair <= 2 * LOGIT_RTOL * max(scale, 1.0),
                      f"request {label}: greedy tokens diverge at "
                      f"{p} with a logit gap of {pair:.4f} — not a "
                      "near-tie")
            compared.append(row)
        programs = {"one_device": one.compiled_programs(),
                    f"tensor{n}": four.compiled_programs()}
    finally:
        one.close()
        four.close()
        server.stop()
    print("CHILD_RESULT " + json.dumps({
        "devices": found["count"], "param_leaves": len(leaves),
        "leaves_sharded": sharded, "leaf_device_span": spans,
        "memory": memory, "compiled_programs": programs,
        "logit_rtol": LOGIT_RTOL, "requests": compared,
        "token_divergences": sum(not r["tokens_equal"]
                                 for r in compared)}), flush=True)


CHILDREN = {"probe": child_probe, "export": child_export,
            "serve4": child_serve4}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = only the paths across chips (builder-run)")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on (virtual) CPU devices; proves "
                         "control flow, never prints platform tpu")
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds the request prompts and the random weights "
                         "of the four-chip serving comparison")
    ap.add_argument("--out", default=str(ROOT / "chiprun_out" / "chip_smoke"),
                    help="logs, checkpoints and exports go here")
    ap.add_argument("--child", choices=sorted(CHILDREN),
                    help=argparse.SUPPRESS)
    ap.add_argument("--spec", default="{}", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.child:
        try:
            CHILDREN[args.child](json.loads(args.spec))
        except SmokeFailure as e:
            print(f"{args.child}: {e}", file=sys.stderr, flush=True)
            return 1
        return 0
    return parent(args)


if __name__ == "__main__":
    sys.exit(main())
