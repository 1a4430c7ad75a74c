"""What the LFM2-MoE configuration (``configs/lfm2-24b-a2b-l10.json``) brings
to the benchmark: its counts against sizes worked out by hand (the cut and
the published depth), a decode round's least time, which has to fall and
rise with the experts the device touched, the traced rounds' reduction on
rounds set by hand, the readers that leave their metric out where the program
states nothing, the reference against the tests' one and against itself in a
lower precision and with a part left out, and the runner's exits, which leave
no process behind."""

import importlib.util
import json
import pathlib
import sys

import numpy as np
import pytest

from benchmark.lib import (counts_lfm2, reference_lfm2, trace_spans,
                           traced_moe_rounds, weights_lfm2)

ROOT = pathlib.Path(__file__).resolve().parents[2]
LFM2 = json.loads((ROOT / "benchmark" / "configs" / "lfm2-24b-a2b-l10.json")
                  .read_text())
PUBLISHED_DEPTH = dict(LFM2, **LFM2["reduced_from"])
READERS = ROOT / "benchmark" / "layer_metrics"

# By hand (ISSUE 33), hidden 2048.  A conv operator: W_in 2048*6144 +
# W_out 2048*2048 + 3 taps * 2048 = 16,783,360.  An attention operator:
# q 2048*32*64 + k, v 2 * 2048*8*64 + o 2048*2048 = 10,485,760, + 2 * 64 of
# q / k norm scales.  Dense SwiGLU 3 * 2048*11776 = 72,351,744.  One expert
# 3 * 2048*1536 = 9,437,184; 64 of them 603,979,776; router 2048*64 + 64.
# Two norms a layer 4,096.  Embedding (tied) 65,536*2048 = 134,217,728;
# final norm 2,048.
HAND = dict(conv=16_783_360, attn=10_485_888, dense=72_351_744,
            expert=9_437_184, router=131_136, table=134_217_728)
HAND["sparse"] = 64 * HAND["expert"] + HAND["router"]
# Layers 0-9: 8 conv + 2 attention operators, 2 dense + 8 sparse.
HAND["cut"] = (HAND["table"] + 2048 + 10 * 4096 + 8 * HAND["conv"]
               + 2 * HAND["attn"] + 2 * HAND["dense"] + 8 * HAND["sparse"])
# All 40: 30 conv + 10 attention, 2 dense + 38 sparse.
HAND["published"] = (HAND["table"] + 2048 + 40 * 4096 + 30 * HAND["conv"]
                     + 10 * HAND["attn"] + 2 * HAND["dense"]
                     + 38 * HAND["sparse"])
# What every decode step reads, in matmul parameters: the projections of
# 8 conv (4 * 2048^2) and 2 attention operators, 2 dense SwiGLUs, 8 routers
# and the head.
HAND["step"] = (8 * 4 * 2048 * 2048 + 2 * 10_485_760 + 2 * HAND["dense"]
                + 8 * 2048 * 64 + HAND["table"])


def test_counts_against_hand_worked_lfm2():
    c = LFM2
    assert counts_lfm2.conv_operator_params(c) == HAND["conv"]
    assert counts_lfm2.attention_operator_params(c) == HAND["attn"]
    assert counts_lfm2.dense_ff_params(c) == HAND["dense"]
    assert counts_lfm2.expert_params(c) == HAND["expert"]
    assert counts_lfm2.router_params(c) == HAND["router"]
    assert counts_lfm2.total_params(c) == HAND["cut"] == 5_267_090_176
    assert round(counts_lfm2.weight_bytes(c) / 1e9, 2) == 10.53
    assert counts_lfm2.total_params(PUBLISHED_DEPTH) == HAND["published"]
    assert round(HAND["published"] / 1e9, 2) == 23.84
    # About 2.3e9 parameters meet a token at the published depth.
    assert round(counts_lfm2.active_matmul_params(PUBLISHED_DEPTH) / 1e9,
                 1) == 2.3
    assert counts_lfm2.kv_planes(c) == 2
    assert counts_lfm2.kv_bytes_per_token(c) == 4096
    assert counts_lfm2.conv_state_bytes_per_sequence(c) == 65_536
    assert counts_lfm2.step_matmul_params(c) == HAND["step"]


def test_the_program_holds_what_the_counts_count():
    """The served tree's shapes (``weights_lfm2.specs``, which the runner
    holds against the program's own init) add up to the count."""
    for c in (LFM2, PUBLISHED_DEPTH):
        held = sum(int(np.prod(shape))
                   for shape, _ in weights_lfm2.specs(c).values())
        assert held == counts_lfm2.total_params(c)


def _least_ms(steps, attended, touched):
    seconds, bound = counts_lfm2.decode_round_seconds(
        LFM2, steps, attended, touched, 197e12, 819e9)
    assert bound == "memory"
    return 1e3 * seconds


def test_the_least_time_of_a_round_follows_the_experts_touched():
    # ISSUE 33: 16 rows touch ~41 of 64 experts in each of 8 layers: 8.7 ms
    # a step; every expert touched: 12.9 ms; 4 a layer (one row): 1.8 ms.
    assert round(_least_ms(1, 16 * 400, 8 * 41), 1) == 8.7
    assert round(_least_ms(1, 16 * 400, 8 * 64), 1) == 12.9
    assert round(_least_ms(1, 400, 8 * 4), 1) == 1.8
    by_hand = (3 * (2 * HAND["step"] + 2 * 65_536) + 700 * 2 * HAND["expert"]
               + 9000 * 4096) / 819e9
    assert _least_ms(3, 9000, 700) == pytest.approx(1e3 * by_hand)
    # It falls and rises with what was touched, by an expert's bytes each.
    one = 1e3 * 2 * HAND["expert"] / 819e9
    assert _least_ms(3, 9000, 701) - _least_ms(3, 9000, 700) == \
        pytest.approx(one)
    assert _least_ms(3, 9000, 600) < _least_ms(3, 9000, 700) < \
        _least_ms(3, 9000, 800)


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
        "config": LFM2, "device": {"kind": "TPU v5 lite"},
        "counters": {"at_close": {}}}
    monkeypatch.setattr(trace_spans, "_LOADED",
                        {(t0, t1): {"phases": phases, "ops": {}}})
    return run


MS = 1_000_000
# Round 7: 3 steps in 60 ms.  Round 8: 2 steps in 30 ms.  Round 9's call is
# cut by the trace's end; the first call belongs to a round the trace missed.
PHASES = [
    ("round_dispatch", 100 * MS, 1 * MS, {"round": 7, "width": 8, "live": 9}),
    ("round_wait", 102 * MS, 65 * MS,
     {"round": 7, "steps": 3, "attended": 9000, "experts_touched": 700}),
    ("round_wait", 170 * MS, 1 * MS, {"round": 7}),  # a drain's own wait
    ("round_dispatch", 420 * MS, 1 * MS, {"round": 8, "width": 2, "live": 4}),
    ("round_wait", 422 * MS, 40 * MS,
     {"round": 8, "steps": 2, "attended": 2000, "experts_touched": 230}),
    ("round_dispatch", 700 * MS, 1 * MS, {"round": 9, "width": 8, "live": 1}),
]
MODULES = [
    ("jit_decode_rounds(5)", 1_000, 50 * MS),
    ("jit_decode_rounds(5)", 99 * MS, 60 * MS),
    ("jit_prefill_chunk_into_slot(6)", 200 * MS, 15 * MS),
    ("jit_decode_rounds(5)", 421 * MS, 30 * MS),
    ("jit_decode_rounds(5)", 701 * MS, 1_000_000_000 - 701 * MS),
]


def test_whole_calls_carry_the_experts_the_device_touched(monkeypatch):
    run = _traced_run(monkeypatch, PHASES, MODULES)
    calls = traced_moe_rounds.whole_calls(run)
    assert [(c["seconds"], c["steps"], c["attended"], c["experts_touched"])
            for c in calls] == [(0.06, 3, 9000, 700), (0.03, 2, 2000, 230)]
    share = _reader("moe.decode_rounds_roofline").read(run)
    least = _least_ms(3, 9000, 700) + _least_ms(2, 2000, 230)
    assert share == pytest.approx(100 * least / 90)
    assert 20 < share < 100
    # Every expert of every layer and step touched, in the least time that
    # takes: 100 %, never over.
    full = [(p, s, d, dict(f, experts_touched=f["steps"] * 8 * 64)
             if "experts_touched" in f else f) for p, s, d, f in PHASES]
    ns = [round(_least_ms(3, 9000, 3 * 512) * MS),
          round(_least_ms(2, 2000, 2 * 512) * MS)]
    modules = [MODULES[0], ("jit_decode_rounds(5)", 99 * MS, ns[0]),
               ("jit_decode_rounds(5)", 421 * MS, ns[1]), MODULES[-1]]
    run = _traced_run(monkeypatch, full, modules)
    assert _reader("moe.decode_rounds_roofline").read(run) == \
        pytest.approx(100.0, rel=1e-6)


def test_readers_leave_the_metric_out_where_nothing_is_stated(monkeypatch):
    """A program that states no ``experts_touched`` (a dense stack, a
    commit before this configuration): no number, no error; nor an
    untraced run."""
    bare = [(p, s, d, {k: v for k, v in f.items() if k != "experts_touched"})
            for p, s, d, f in PHASES]
    run = _traced_run(monkeypatch, bare, MODULES)
    assert traced_moe_rounds.whole_calls(run) is None
    assert _reader("moe.decode_rounds_roofline").read(run) is None
    untraced = {"trace": None,
                "counters": {"before": {"steps": 0}, "at_close": {
                    "steps": 10}}, "window": {"seconds": 1.0}}
    for name in ("moe.decode_rounds_roofline", "moe.experts_share",
                 "conv.operator_share", "moe.experts_touched_share"):
        assert _reader(name).read(untraced) is None


def test_experts_touched_share_is_over_what_the_steps_could_touch():
    counters = {"before": {"steps": 100, "experts_touched": 30_000},
                "at_close": {"steps": 300, "experts_touched": 95_536,
                             "moe_layers": 8, "moe_experts": 64}}
    run = {"counters": counters, "window": {"seconds": 50.0}}
    assert _reader("moe.experts_touched_share").read(run) == pytest.approx(
        100 * 65_536 / (8 * 64 * 200))
    counters["at_close"]["steps"] = 100  # no step ran in the window
    assert _reader("moe.experts_touched_share").read(run) is None


def test_experts_share_counts_the_compilers_kernel_by_its_name(monkeypatch):
    """The grouped products carry the compiler's ``ragged-dot-...`` where
    the scope stood; a program with no sparse scope at all reads nothing."""
    ops = [
        ("%fusion.1 = f32[16,64]{1,0} fusion(%a)", 0, 10 * MS,
         "jit_decode_rounds", "kft.moe_route"),
        ("%ragged-dot-none.8 = bf16[64,3072]{1,0} custom-call(%b)", 10 * MS,
         50 * MS, "jit_decode_rounds", None),
        ("%fusion.2 = bf16[16,2048]{1,0} fusion(%c)", 60 * MS, 20 * MS,
         "jit_decode_rounds", "kft.short_conv"),
        ("%fusion.3 = bf16[16,2,2048]{2,1,0} fusion(%d)", 80 * MS, 5 * MS,
         "jit_prefill_chunk_into_slot", "kft.conv_state"),
        ("%fusion.4 = bf16[16,2048]{1,0} fusion(%e)", 85 * MS, 15 * MS,
         "jit_decode_rounds", "kft.mlp"),
        ("%ragged-dot-none.8 = bf16[64,3072]{1,0} custom-call(%b)",
         100 * MS, 50 * MS, "jit_something_else", None),
    ]
    run = _traced_run(monkeypatch, [], [])
    monkeypatch.setattr(trace_spans, "_LOADED", {(1_000, 1_000_000_000): {
        "phases": [], "ops": {"/device:TPU:0": ops}}})
    assert _reader("moe.experts_share").read(run) == pytest.approx(60.0)
    assert _reader("conv.operator_share").read(run) == pytest.approx(25.0)
    dense = [op[:4] + ("kft.mlp",) for op in ops[2:5]]
    monkeypatch.setattr(trace_spans, "_LOADED", {(1_000, 1_000_000_000): {
        "phases": [], "ops": {"/device:TPU:0": dense}}})
    assert _reader("moe.experts_share").read(run) is None
    assert _reader("conv.operator_share").read(run) == 0.0


# -- the reference ------------------------------------------------------------

SMALL = dict(hidden_size=64, num_hidden_layers=5, num_attention_heads=4,
             num_key_value_heads=2, head_dim=16, intermediate_size=128,
             moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2,
             num_dense_layers=1, vocab_size=512, conv_L_cache=3,
             layer_types=["conv", "conv", "full_attention", "conv",
                          "full_attention"],
             norm_eps=1e-5, rope_theta=1e6, routed_scaling_factor=1,
             tie_word_embeddings=True)


def _logits(c, seed, quantize=None, n=48, leaves=None):
    tokens = np.random.default_rng(seed).integers(1, c["vocab_size"], n,
                                                  dtype=np.int32)
    ref = reference_lfm2.Reference(c, seed, quantize=quantize)
    return tokens, np.asarray(ref.logits(tokens, 0, n, n))


def test_the_copy_is_the_tests_reference():
    """``lib/reference_lfm2.py`` computes a layer and an expert at a time;
    on one tree it gives what ``tests/reference_lfm2.py`` gives."""
    import jax.numpy as jnp

    sys.path.insert(0, str(ROOT / "tests"))
    try:
        import reference_lfm2 as plain
    finally:
        sys.path.remove(str(ROOT / "tests"))
    tokens, got = _logits(SMALL, 11)
    tree = weights_lfm2.make_tree(SMALL, 11, jnp.bfloat16)
    want = np.asarray(plain.forward(SMALL, tree, tokens))
    assert np.ptp(want) > 1.0
    np.testing.assert_allclose(got, want, atol=2e-5)
    # Padding behind a position changes nothing before it.
    ref = reference_lfm2.Reference(SMALL, 11)
    padded = np.asarray(ref.logits(tokens, 8, 16, 64))
    np.testing.assert_allclose(padded, want[8:24], atol=2e-5)


def test_the_seeded_bias_is_not_zero_and_changes_the_choice():
    import jax.numpy as jnp

    leaves = weights_lfm2.layer_leaves(
        SMALL, weights_lfm2.weights.seed_key(3), 1, jnp.bfloat16)
    bias = np.asarray(leaves["moe/bias"])
    assert bias.dtype == np.float32 and np.abs(bias).min() > 0
    assert 0.03 < bias.std() < 0.3


@pytest.mark.parametrize("seed", [1, 2**31 + 3])
def test_control_in_lower_precision_reads_worse(seed):
    _, sound = _logits(SMALL, seed)
    _, low = _logits(SMALL, seed, quantize="fp8")
    control = reference_lfm2.served_gaps(sound, low.argmax(-1))
    assert reference_lfm2.served_gaps(sound, sound.argmax(-1)).max() == 0
    assert control.mean() > 0.001 and (control > 0).mean() > 0.03


@pytest.mark.parametrize("broken", [
    {"conv_L_cache": 2}, {"num_experts_per_tok": 1}, {"num_dense_layers": 0},
    {"layer_types": ["conv", "conv", "conv", "conv", "full_attention"]}],
    ids=lambda b: next(iter(b)))
def test_a_part_left_out_reads_far_from_the_reference(broken):
    """Another model under the same seed (a tap, an expert a token, the
    leading dense layer or an attention layer fewer) picks tokens far
    below the reference's best: the limits of ``correct`` cannot pass it."""
    _, sound = _logits(SMALL, 7)
    _, wrong = _logits(dict(SMALL, **broken), 7)
    gaps = reference_lfm2.served_gaps(sound, wrong.argmax(-1))
    assert gaps.mean() > 0.05


# -- the runner's exits -------------------------------------------------------

def _runner():
    spec = importlib.util.spec_from_file_location(
        "bench_serve_lfm2_under_test",
        ROOT / "benchmark" / "runners" / "serve_lfm2.py")
    runner = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(runner)
    return runner


def test_the_key_map_names_fields_the_program_has():
    import dataclasses

    from kubeflow_tpu.models.transformer import TransformerConfig

    runner = _runner()
    known = {f.name for f in dataclasses.fields(TransformerConfig)}
    assert set(runner._serve()._FIELDS.values()) <= known
    assert set(runner._FIELDS) <= set(LFM2)


def test_a_program_without_the_fields_is_refused_before_anything_starts(
        monkeypatch):
    """The parent commit's ``TransformerConfig`` has no ``layer_types``: the
    run has to fail at once, with a message, and start no child."""
    runner = _runner()
    serve = runner._serve()
    serve._FIELDS = {**serve._FIELDS, "a_key": "a_field_no_program_has"}
    serve.run = lambda ctx: pytest.fail("the run was started")
    monkeypatch.setattr(runner, "_serve", lambda: serve)
    with pytest.raises(SystemExit, match="a_field_no_program_has"):
        runner.run({"config": LFM2})


def test_another_router_than_the_programs_is_refused(monkeypatch):
    runner = _runner()
    serve = runner._serve()
    serve.run = lambda ctx: pytest.fail("the run was started")
    monkeypatch.setattr(runner, "_serve", lambda: serve)
    with pytest.raises(SystemExit, match="routed_scaling_factor"):
        runner.run({"config": dict(LFM2, routed_scaling_factor=2.5)})


def test_a_run_that_fails_leaves_no_child_running(monkeypatch):
    runner = _runner()
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
        runner.run({"config": LFM2})
    assert started and started[0].poll() is not None
