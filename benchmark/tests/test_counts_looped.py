"""What the looped configuration (``configs/ouro-2.6b.json``) brings to the
benchmark: its counts against sizes worked out by hand, its reference against
the dense one and against itself with a part left out, the traced rounds'
reduction on rounds set by hand, a run held against a reference that
leaves the output norms out, which has to come out as not correct, and the
looped runner's exits, which leave no process behind."""

import importlib.util
import json
import pathlib
import sys

import numpy as np
import pytest

from benchmark.lib import (counts, counts_looped, reference,
                           reference_looped, trace_spans, traced_rounds,
                           weights, weights_looped)

ROOT = pathlib.Path(__file__).resolve().parents[2]
OURO = json.loads((ROOT / "benchmark" / "configs" / "ouro-2.6b.json")
                  .read_text())
READERS = ROOT / "benchmark" / "layer_metrics"

# By hand.  One layer: q 2048*16*128 = 4,194,304; k and v 2 * 4,194,304;
# o 4,194,304: attention 16,777,216.  MLP 3 * 2048*5632 = 34,603,008.  Four
# norms 8,192.  Layer 51,388,416; 48 layers 2,466,643,968.  Embedding and
# head 2 * 49,152*2048 = 201,326,592; final norm 2,048; gate 2,049.
# Keys and values a token: 2 * (4*48) * 16 * 128 * 2 B.  A decode step reads
# the layers' matmul weights (51,388,416 - 8,192 = 51,380,224 a layer) 4
# times and the head once, in bf16.
HAND = dict(layer=51_388_416, total=2_667_974_657, kv=1_572_864,
            step_bytes=(4 * 48 * 51_380_224 + 2048 * 49_152) * 2)


def test_counts_against_hand_worked_ouro():
    c = OURO
    assert counts_looped.layer_params(c) == HAND["layer"]
    assert counts_looped.total_params(c) == HAND["total"]
    assert counts_looped.weight_bytes(c) == 2 * HAND["total"]
    assert counts_looped.kv_planes(c) == 192
    assert counts_looped.kv_bytes_per_token(c) == HAND["kv"]
    assert counts_looped.decode_step_bytes(c, 0) == HAND["step_bytes"]
    assert round(HAND["step_bytes"] / 1e9, 1) == 19.9
    assert counts_looped.decode_step_bytes(c, 1000) == \
        HAND["step_bytes"] + 1000 * HAND["kv"]
    # One step of one sequence at 819 GB/s: memory bounds it, 24.3 ms.
    least, bound = counts_looped.decode_round_seconds(
        c, 1, 0, 197e12, 819e9)
    assert bound == "memory" and round(least * 1e3, 1) == 24.3
    three, _ = counts_looped.decode_round_seconds(c, 3, 900, 197e12, 819e9)
    assert three == pytest.approx(
        (3 * HAND["step_bytes"] + 900 * HAND["kv"]) / 819e9)


def test_counts_of_an_unlooped_configuration_are_the_dense_counts():
    c = json.loads((ROOT / "benchmark" / "configs" / "internlm2-1.8b.json")
                   .read_text())
    assert counts_looped.total_params(c) == counts.total_params(c)
    assert counts_looped.kv_bytes_per_token(c) == counts.kv_bytes_per_token(c)
    assert counts_looped.decode_step_bytes(c, 77.0) == \
        counts.decode_step_bytes(c, 77.0)
    assert counts_looped.forward_flops_per_token(c, 9.0) == \
        counts.forward_flops_per_token(c, 9.0)


SMALL = dict(hidden_size=128, num_hidden_layers=3, num_attention_heads=4,
             num_key_value_heads=4, head_dim=32, intermediate_size=256,
             vocab_size=1024, rope_theta=1e6, rms_norm_eps=1e-6,
             tie_word_embeddings=False, total_ut_steps=3, sandwich_norm=True)


class NormAtTheEndOnly(reference_looped.Reference):
    """What a program that forgot the norm BETWEEN loop steps computes:
    the final norm after the last step alone."""

    def logits(self, *args):
        real, left = self._final_norm, [self.c.get("total_ut_steps", 1)]

        def last_only(key, x):
            left[0] -= 1
            return x if left[0] else real(key, x)

        self._final_norm = last_only
        try:
            return super().logits(*args)
        finally:
            self._final_norm = real


def _logits(module, c, seed, quantize=None, n=64, cls=None):
    tokens = np.random.default_rng(seed).integers(1, c["vocab_size"], n,
                                                  dtype=np.int32)
    return np.asarray((cls or module.Reference)(
        c, seed, quantize=quantize).logits(tokens, 0, n, n))


def test_one_step_without_output_norms_is_the_dense_reference():
    c = dict(SMALL, total_ut_steps=1, sandwich_norm=False)
    assert weights_looped.tree_shapes(c) == weights.tree_shapes(c)
    np.testing.assert_array_equal(_logits(reference_looped, c, 5),
                                  _logits(reference, c, 5))


@pytest.mark.parametrize("broken", [
    {"total_ut_steps": 2}, {"sandwich_norm": False},
    {"cls": NormAtTheEndOnly}], ids=lambda b: next(iter(b)))
def test_the_seeded_norm_scales_hide_no_left_out_part(broken):
    """A program that drops a loop step, an output norm or the norm between
    steps picks tokens far below the reference's best, also with output
    norms seeded small (a loop step then moves the stream least): the limits
    of ``correct`` (a mean gap of 0.02 at most) cannot pass it."""
    sound = _logits(reference_looped, SMALL, 7)
    cls = broken.pop("cls", None)
    wrong = _logits(reference_looped, dict(SMALL, **broken), 7, cls=cls)
    gaps = reference_looped.served_gaps(sound, wrong.argmax(-1))
    assert gaps.mean() > 0.05


@pytest.mark.parametrize("seed", [1, 2**31 + 3])
def test_control_in_lower_precision_reads_worse(seed):
    sound = _logits(reference_looped, SMALL, seed)
    low = _logits(reference_looped, SMALL, seed, quantize="fp8")
    control = reference_looped.served_gaps(sound, low.argmax(-1))
    assert reference_looped.served_gaps(sound, sound.argmax(-1)).max() == 0
    assert control.mean() > 0.001 and (control > 0).mean() > 0.03


# -- traced rounds ------------------------------------------------------------

def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"), READERS / f"{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _traced_run(monkeypatch, phases, modules, t0=1_000, t1=1_000_000_000):
    run = {"trace": {"t0": t0, "t1": t1, "planes": {"/device:TPU:0": {
        "busy_s": 1.0, "modules": modules, "ops": []}}},
        "config": OURO, "device": {"kind": "TPU v5 lite"},
        "counters": {"at_close": {"kv_planes": 192}}}
    monkeypatch.setattr(trace_spans, "_LOADED",
                        {(t0, t1): {"phases": phases, "ops": {}}})
    return run


MS = 1_000_000
# Three rounds by hand.  Round 7: 3 steps of a width of 8 in 300 ms.  Round
# 8: 2 steps in 220 ms.  Round 9's call is cut by the trace's end.  The call
# at the trace's start belongs to a round the trace did not catch.
PHASES = [
    ("round_dispatch", 100 * MS, 1 * MS,
     {"round": 7, "width": 8, "live": 2}),
    ("round_wait", 102 * MS, 305 * MS,
     {"round": 7, "steps": 3, "attended": 900}),
    ("round_wait", 410 * MS, 1 * MS, {"round": 7}),  # a drain's own wait
    ("round_dispatch", 420 * MS, 1 * MS,
     {"round": 8, "width": 2, "live": 1}),
    ("round_wait", 422 * MS, 230 * MS,
     {"round": 8, "steps": 2, "attended": 401}),
    ("round_dispatch", 700 * MS, 1 * MS,
     {"round": 9, "width": 8, "live": 1}),
]
MODULES = [
    ("jit_decode_rounds(5)", 1_000, 50 * MS),  # starts with the trace
    # On the device's clock this call begins before its own dispatch.
    ("jit_decode_rounds(5)", 99 * MS, 300 * MS),
    ("jit_prefill_chunk_into_slot(6)", 411 * MS, 5 * MS),
    ("jit_decode_rounds(5)", 421 * MS, 220 * MS),
    ("jit_decode_rounds(5)", 701 * MS, 1_000_000_000 - 701 * MS),
]


def test_whole_calls_are_matched_to_their_rounds(monkeypatch):
    run = _traced_run(monkeypatch, PHASES, MODULES)
    calls = traced_rounds.whole_calls(run)
    assert [(c["seconds"], c["steps"], c["attended"])
            for c in calls] == [(0.3, 3, 900), (0.22, 2, 401)]
    assert _reader("loop.layer_pass_ms").read(run) == pytest.approx(
        1e3 * 0.52 / 5 / 192)
    least = (5 * HAND["step_bytes"] + 1301 * HAND["kv"]) / 819e9
    share = _reader("loop.decode_rounds_roofline").read(run)
    assert share == pytest.approx(100 * least / 0.52)
    assert 20 < share < 30


def test_readers_leave_the_metric_out_where_nothing_is_stated(monkeypatch):
    """A program from before PR 26 states no ``steps``: no number, no
    error.  Nor from an untraced run."""
    bare = [(p, s, d, {k: v for k, v in f.items()
                       if k not in ("steps", "attended")})
            for p, s, d, f in PHASES]
    run = _traced_run(monkeypatch, bare, MODULES)
    assert traced_rounds.whole_calls(run) is None
    assert _reader("loop.layer_pass_ms").read(run) is None
    assert _reader("loop.decode_rounds_roofline").read(run) is None
    untraced = {"trace": None, "counters": {"at_close": {}}}
    assert _reader("loop.layer_pass_ms").read(untraced) is None
    assert _reader("loop.decode_rounds_roofline").read(untraced) is None


def test_the_roofline_counts_the_steps_that_ran_not_the_width(monkeypatch):
    """Had the reader taken the window's mean steps a call, or the round's
    width, a short round would read over 100 %: 8 steps' least time is
    195 ms, the round took 80 ms for the 3 it ran."""
    phases = [("round_dispatch", 100 * MS, MS,
               {"round": 1, "width": 8, "live": 1}),
              ("round_wait", 101 * MS, 90 * MS,
               {"round": 1, "steps": 3, "attended": 300})]
    modules = [("jit_decode_rounds(5)", 101 * MS, 80 * MS)]
    run = _traced_run(monkeypatch, phases, modules)
    share = _reader("loop.decode_rounds_roofline").read(run)
    assert share == pytest.approx(
        100 * (3 * HAND["step_bytes"] + 300 * HAND["kv"]) / 819e9 / 0.08)
    assert share < 100


# -- a broken program through the whole run -----------------------------------

def test_a_run_held_against_another_computation_is_not_correct(
        capsys, monkeypatch):
    """The program and a reference that leaves the output norms out have
    the same tree, so only the comparison of what was served can tell them
    apart."""
    real = reference_looped._layer
    monkeypatch.setattr(reference_looped, "_layer", lambda c, x, w: real(
        {**c, "sandwich_norm": False}, x, w))
    spec = importlib.util.spec_from_file_location(
        "bench_run_py_looped", ROOT / "benchmark" / "run.py")
    run_py = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run_py)
    capsys.readouterr()
    assert run_py.main(["--workload", "ouro-2.6b.reason", "--seed", "11",
                        "--seconds", "3", "--trace", "0", "--rehearse"]) == 0
    out = capsys.readouterr().out
    line = json.loads(out.strip().splitlines()[-1])
    assert line["rehearsal"]["correct"] is False and line["failed"] == 0
    mean = [float(text.split(" = ")[1].split(" limit ")[0])
            for text in out.splitlines()
            if text.startswith("compared served_logit_gap_mean")]
    assert mean and mean[0] > 0.1  # the rehearsal's limit is 0.02


# -- the looped runner leaves no process behind -------------------------------

def _looped_runner():
    spec = importlib.util.spec_from_file_location(
        "bench_serve_looped_under_test",
        ROOT / "benchmark" / "runners" / "serve_looped.py")
    runner = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(runner)
    return runner


def test_a_program_that_cannot_loop_is_refused_before_anything_starts(
        monkeypatch):
    """A program without the looped model's fields (the parent commit) has
    to fail at once and start no child: ``serve.py`` would start the child
    that reads ``serving.main``'s defaults and fail before it waits for it."""
    runner = _looped_runner()
    serve = runner._serve()
    serve._FIELDS = {**serve._FIELDS, "a_key": "a_field_no_program_has"}
    serve.run = lambda ctx: pytest.fail("the run was started")
    monkeypatch.setattr(runner, "_serve", lambda: serve)
    with pytest.raises(SystemExit, match="a_field_no_program_has"):
        runner.run({})


def test_a_run_that_fails_leaves_no_child_running(monkeypatch):
    runner = _looped_runner()
    serve = runner._serve()
    started = []

    def failing_run(ctx):
        started.append(serve.subprocess.Popen(
            [sys.executable, "-c", "import time; time.sleep(600)"],
            stdout=serve.subprocess.PIPE))
        raise SystemExit("the program's parameter tree is not the "
                         "benchmark's")

    serve.run = failing_run
    monkeypatch.setattr(runner, "_serve", lambda: serve)
    with pytest.raises(SystemExit, match="parameter tree"):
        runner.run({})
    assert started and started[0].poll() is not None
