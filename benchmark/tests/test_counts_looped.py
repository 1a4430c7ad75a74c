"""What the looped configuration (``configs/ouro-2.6b.json``) brings to the
benchmark: its counts against sizes worked out by hand, its reference against
the dense one and against itself with a part left out, the traced rounds'
reduction on rounds set by hand (under both names the decode program's
roofline has, on a looped stack and on a dense one), a run held against a
reference that
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
# Both rooflines of the fused decode program, the looped cell's and the dense
# cells', are one computation (``traced_rounds.roofline_share``): every case
# below runs under both metric names, each on a stack of its kind.

MISTRAL = json.loads((ROOT / "benchmark" / "configs"
                      / "mistral-7b-v0.3-l16.json").read_text())
# Mistral-7B at 16 layers, by hand (``test_counts.py``): 3,623,878,656
# matmul parameters read once a step in bf16; keys and values a token
# 2 * 16 * 8 * 128 * 2 B.
DENSE = dict(step_bytes=2 * 3_623_878_656, kv=65_536)
ROOFLINES = [
    pytest.param("loop.decode_rounds_roofline", OURO, HAND, id="looped"),
    pytest.param("decode_rounds_roofline", MISTRAL, DENSE, id="dense")]


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"), READERS / f"{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _traced_run(monkeypatch, phases, modules, t0=1_000, t1=1_000_000_000,
                config=OURO, **more):
    run = {"trace": {"t0": t0, "t1": t1, "planes": {"/device:TPU:0": {
        "busy_s": 1.0, "modules": modules, "ops": []}}},
        "config": config, "device": {"kind": "TPU v5 lite"},
        "counters": {"at_close": {"kv_planes": 192}}, **more}
    monkeypatch.setattr(trace_spans, "_LOADED",
                        {(t0, t1): {"phases": phases, "ops": {}}})
    return run


def _least(hand, steps, attended):
    """Memory bounds a decode round at these sizes (819 GB/s)."""
    return (steps * hand["step_bytes"] + attended * hand["kv"]) / 819e9


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


@pytest.mark.parametrize("name, config, hand", ROOFLINES)
def test_whole_calls_are_matched_to_their_rounds(monkeypatch, name, config,
                                                 hand):
    run = _traced_run(monkeypatch, PHASES, MODULES, config=config)
    calls = traced_rounds.whole_calls(run)
    assert [(c["seconds"], c["steps"], c["attended"])
            for c in calls] == [(0.3, 3, 900), (0.22, 2, 401)]
    assert _reader("loop.layer_pass_ms").read(run) == pytest.approx(
        1e3 * 0.52 / 5 / 192)
    share = _reader(name).read(run)
    assert share == pytest.approx(100 * _least(hand, 5, 1301) / 0.52)
    # 19.9 GB a step (looped) and 7.25 (dense): 23.9 % and 8.5 % of 0.52 s.
    assert 8 < share < 30


@pytest.mark.parametrize("name", ["loop.decode_rounds_roofline",
                                  "decode_rounds_roofline"])
def test_readers_leave_the_metric_out_where_nothing_is_stated(monkeypatch,
                                                              name):
    """A program from before PR 26 states no ``steps``: no number, no
    error.  Nor one that states ``steps`` and no ``attended``, nor an
    untraced run."""
    bare = [(p, s, d, {k: v for k, v in f.items()
                       if k not in ("steps", "attended")})
            for p, s, d, f in PHASES]
    run = _traced_run(monkeypatch, bare, MODULES)
    assert traced_rounds.whole_calls(run) is None
    assert _reader("loop.layer_pass_ms").read(run) is None
    assert _reader(name).read(run) is None
    no_attended = [(p, s, d, {k: v for k, v in f.items() if k != "attended"})
                   for p, s, d, f in PHASES]
    run = _traced_run(monkeypatch, no_attended, MODULES)
    assert len(traced_rounds.whole_calls(run)) == 2
    assert _reader(name).read(run) is None
    untraced = {"trace": None, "counters": {"at_close": {}}}
    assert _reader("loop.layer_pass_ms").read(untraced) is None
    assert _reader(name).read(untraced) is None


@pytest.mark.parametrize("name, config, hand", ROOFLINES)
def test_the_roofline_counts_the_steps_that_ran_not_the_width(
        monkeypatch, name, config, hand):
    """A round of a width of 8 that ran 3 steps in 1.1 times their least
    time reads 90.9 %; held to its width it would read 242 %."""
    least = _least(hand, 3, 300)
    dur = round(1.1 * least * 1e9)
    phases = [("round_dispatch", 100 * MS, MS,
               {"round": 1, "width": 8, "live": 1}),
              ("round_wait", 101 * MS, dur + 10 * MS,
               {"round": 1, "steps": 3, "attended": 300})]
    modules = [("jit_decode_rounds(5)", 101 * MS, dur)]
    run = _traced_run(monkeypatch, phases, modules, config=config)
    share = _reader(name).read(run)
    assert share == pytest.approx(100 * least / (dur / 1e9))
    assert 90 < share < 91 and 8 / 3 * share > 100


def _window_mean_share(run):
    """What ``decode_rounds_roofline`` computed until PR 28, kept here as
    the fault the reader must not have again: the WINDOW's mean steps a
    call (``steps`` / ``fused_rounds``) times one step's least time, with
    every page the pool had in use counted as read, over the mean time of
    the TRACED calls."""
    from benchmark.lib import peaks, stats, trace_reduce

    plane = run["trace"]["planes"]["/device:TPU:0"]
    seconds, calls = trace_reduce.module_times(plane["modules"])[
        "jit_decode_rounds"]
    steps = stats.delta(run, "steps")
    used = [u for _, u, _, _ in run["samples"]]
    resident = sum(used) / len(used) * \
        run["counters"]["at_close"]["kv_block_tokens"]
    live = stats.delta(run, "tokens") / steps
    least, _ = counts.roofline_seconds(
        live * counts.forward_flops_per_token(run["config"],
                                              resident / max(live, 1.0)),
        counts.decode_step_bytes(run["config"], resident),
        peaks.peak("TPU v5 lite", "bf16_flops_per_s"),
        peaks.peak("TPU v5 lite", "hbm_bytes_per_s"))
    return 100.0 * steps / stats.delta(run, "fused_rounds") * least \
        / (seconds / calls)


def _one_step_calls(hand, n, attended, slack=3.0):
    """``n`` rounds of one step each (an admission waits), every call
    ``slack`` times its least time long."""
    dur = round(slack * _least(hand, 1, attended) * 1e9)
    phases, modules = [], []
    for i in range(n):
        at = (100 + 100 * i) * MS
        phases += [("round_dispatch", at, MS,
                    {"round": i, "width": 1, "live": 1}),
                   ("round_wait", at + MS, dur + 5 * MS,
                    {"round": i, "steps": 1, "attended": attended})]
        modules.append(("jit_decode_rounds(5)", at + MS, dur))
    return phases, modules, dur / 1e9


def _window(steps, rounds, tokens, pages_in_use, pages=2400):
    before = dict(steps=1000, fused_rounds=100, tokens=5000)
    at_close = dict(steps=1000 + steps, fused_rounds=100 + rounds,
                    tokens=5000 + tokens, kv_block_tokens=16, kv_planes=16)
    return dict(counters={"before": before, "at_close": at_close},
                samples=[(float(t), pages_in_use, pages, 0)
                         for t in range(50)])


def test_a_wide_window_over_a_narrow_trace_stays_under_100(monkeypatch):
    """The window's mean round is 6 steps wide; the traced 5 s hold only
    rounds of one step, each three times its least time long.  The window's
    mean times the trace's mean reads 200 %; the calls' own steps read the
    33 % that is there."""
    phases, modules, seconds = _one_step_calls(DENSE, 4, 0)
    run = _traced_run(monkeypatch, phases, modules, config=MISTRAL,
                      **_window(steps=600, rounds=100, tokens=600,
                                pages_in_use=0))
    assert _window_mean_share(run) == pytest.approx(200.0, rel=1e-6)
    share = _reader("decode_rounds_roofline").read(run)
    assert share == pytest.approx(100 * _least(DENSE, 1, 0) / seconds)
    assert share == pytest.approx(100 / 3, rel=1e-6)
    # A prefix cache that keeps the pool 90 % in use (2,160 pages of 16
    # positions: 2.26 GB a step on top of 7.25 GB of weights) adds a third
    # to that formula and nothing to the reader.
    cached = dict(run, **_window(steps=600, rounds=100, tokens=600,
                                 pages_in_use=2160))
    assert _window_mean_share(cached) > 260
    assert _reader("decode_rounds_roofline").read(cached) == share


@pytest.mark.parametrize("name, config, hand", ROOFLINES)
def test_the_roofline_follows_the_attended_positions_not_the_pool(
        monkeypatch, name, config, hand):
    """A prefix cache keeps the pool 90 % in use (2,160 pages of 16
    positions) while the live sequences attend 300 positions: the reading is
    by the 300, moves with them, and is the same whatever the pool's
    samples and the window's counters say, or without any."""
    phases, modules, seconds = _one_step_calls(hand, 3, 300)
    full = _window(steps=3, rounds=3, tokens=3, pages_in_use=2160)
    empty = _window(steps=900, rounds=7, tokens=4000, pages_in_use=0)
    reads = [_reader(name).read(_traced_run(
        monkeypatch, phases, modules, config=config, **more))
        for more in (full, empty, {})]
    assert reads[0] == reads[1] == reads[2] == pytest.approx(
        100 * _least(hand, 1, 300) / seconds)
    more_attended, _, _ = _one_step_calls(hand, 3, 600)
    moved = _reader(name).read(_traced_run(
        monkeypatch, more_attended, modules, config=config, **full))
    assert moved == pytest.approx(100 * _least(hand, 1, 600) / seconds)
    assert moved > reads[0]


def test_both_metric_names_read_one_number(monkeypatch):
    """To the last digit, on a looped run and on a dense one."""
    for config in (OURO, MISTRAL):
        run = _traced_run(monkeypatch, PHASES, MODULES, config=config)
        assert _reader("decode_rounds_roofline").read(run) == \
            _reader("loop.decode_rounds_roofline").read(run) == \
            traced_rounds.roofline_share(run)


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
