"""What the LongCat-Flash configuration
(``configs/longcat-flash-omni-l4.json``) brings to the benchmark: its counts
against sizes worked out by hand (the cut and the published model), a decode
round's least time, which has to follow the experts the device touched and
the positions attended, the traced rounds' reduction and the kernel's
roofline on rounds and operations set by hand (never over 100 %), the pair
shares, the readers that leave their metric out where the program states
nothing, the reference against the tests' one and against itself in a lower
precision and with a part left out, and the runner's exits, which leave no
process behind."""

import importlib.util
import json
import pathlib
import sys

import numpy as np
import pytest

from benchmark.lib import (counts_longcat, reference_longcat, trace_spans,
                           traced_latent_rounds, weights_longcat)

ROOT = pathlib.Path(__file__).resolve().parents[2]
LONGCAT = json.loads(
    (ROOT / "benchmark" / "configs" / "longcat-flash-omni-l4.json")
    .read_text())
READERS = ROOT / "benchmark" / "layer_metrics"

# By hand (ISSUE 37), hidden 6144, 64 heads.  One latent attention sublayer:
# W_qa 6144*1536 = 9,437,184 + its norm 1,536 + W_qb 1536*64*192 =
# 18,874,368 + W_kva 6144*576 = 3,538,944 + its norm 512 + W_kvb 512*64*256 =
# 8,388,608 + W_o 8192*6144 = 50,331,648: 90,572,800.  A dense SwiGLU
# 3 * 6144*12288 = 226,492,416.  Four norms 24,576.  The router 6144*768 =
# 4,718,592 and its bias 768.  A double layer beside its experts:
# 2 * 90,572,800 + 2 * 226,492,416 + 24,576 + 4,719,360 = 638,874,368.  One
# expert 3 * 6144*2048 = 37,748,736.
HAND = dict(attn=90_572_800, dense=226_492_416, router=4_719_360,
            double=638_874_368, expert=37_748_736)
HAND["cut"] = (4 * (HAND["double"] + 16 * HAND["expert"])
               + 2 * 16_384 * 6_144 + 6_144)
HAND["published"] = (28 * (HAND["double"] + 512 * HAND["expert"])
                     + 2 * 131_072 * 6_144 + 6_144)
# What every decode step reads, in matmul parameters: 8 sublayers without
# their two low-rank norms, 8 dense SwiGLUs, 4 routers, the head's slice.
HAND["step"] = (8 * (HAND["attn"] - 2_048) + 8 * HAND["dense"]
                + 4 * 6_144 * 768 + 6_144 * 16_384)


def test_counts_against_hand_worked_longcat():
    c = LONGCAT
    assert counts_longcat.attention_params(c) == HAND["attn"]
    assert counts_longcat.dense_ff_params(c) == HAND["dense"]
    assert counts_longcat.router_params(c) == HAND["router"]
    assert counts_longcat.double_layer_params(c) == HAND["double"]
    assert counts_longcat.expert_params(c) == HAND["expert"]
    assert counts_longcat.total_params(c) == HAND["cut"] == 5_172_749_312
    assert round(counts_longcat.weight_bytes(c) / 1e9, 2) == 10.35
    assert counts_longcat.published_params(c) == HAND["published"]
    assert round(HAND["published"] / 1e9, 2) == 560.66
    # "560B-A27B": of 12 choices 8 fall on routed experts on average.
    assert round(counts_longcat.active_params(c) / 1e9, 2) == 27.95
    assert counts_longcat.kv_planes(c) == 8
    assert counts_longcat.latent_values_per_token(c) == 576
    assert counts_longcat.latent_bytes_per_token(c) == 9_216
    # 139 kFLOP a query row and attended position: at half the ridge.
    assert counts_longcat.attention_flops_per_position(c) == 139_264
    assert counts_longcat.step_matmul_params(c) == HAND["step"]


def test_the_file_states_the_catalog_row_and_its_cut():
    """Every number of the catalog's ``config`` under its key, but for the
    three in ``reduced``, whose published values stand beside them."""
    row = {"attention_bias": False, "vocab_size": 131072,
           "hidden_size": 6144, "ffn_hidden_size": 12288,
           "expert_ffn_hidden_size": 2048, "num_layers": 28,
           "num_attention_heads": 64, "kv_lora_rank": 512,
           "q_lora_rank": 1536, "qk_rope_head_dim": 64, "v_head_dim": 128,
           "qk_nope_head_dim": 128, "mla_scale_q_lora": True,
           "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
           "n_routed_experts": 512, "max_position_embeddings": 131072,
           "rms_norm_eps": 1e-05, "rope_theta": 10000000,
           "attention_method": "MLA", "zero_expert_num": 256,
           "zero_expert_type": "identity", "moe_topk": 12}
    differs = {k for k, v in row.items() if LONGCAT.get(k) != v}
    assert differs == set(LONGCAT["reduced"]) == {
        "num_layers", "n_routed_experts", "vocab_size"}
    assert LONGCAT["reduced_from"] == {k: row[k] for k in differs}
    assert LONGCAT["n_routed_experts_published"] == row["n_routed_experts"]
    # The guide's floors: 4 layers, 8 experts, an eighth of the vocabulary.
    assert LONGCAT["num_layers"] >= 4 and LONGCAT["n_routed_experts"] >= 8
    assert 8 * LONGCAT["vocab_size"] >= row["vocab_size"]


def test_the_program_holds_what_the_counts_count():
    """The served tree's shapes (``weights_longcat.specs``, which the runner
    holds against the program's own init) add up to the count."""
    held = sum(int(np.prod(shape))
               for shape, _ in weights_longcat.specs(LONGCAT).values())
    assert held == counts_longcat.total_params(LONGCAT)


def _least_ms(steps, attended, touched):
    seconds, bound = counts_longcat.decode_round_seconds(
        LONGCAT, steps, attended, touched, 197e12, 819e9)
    assert bound == "memory"
    return 1e3 * seconds


def test_the_least_time_of_a_round_follows_experts_and_positions():
    # ISSUE 37: a step reads 5.31 GB of weights beside the experts (6.5 ms);
    # ten of 16 experts a layer add 3.0 GB; 64 rows at 4,000 positions add
    # 2.4 GB of latent rows.
    assert round(_least_ms(1, 0, 0), 1) == 6.5
    assert round(_least_ms(1, 0, 4 * 10) - _least_ms(1, 0, 0), 1) == 3.7
    assert round(_least_ms(1, 64 * 4000, 0) - _least_ms(1, 0, 0), 1) == 2.9
    by_hand = (3 * 2 * HAND["step"] + 100 * 2 * HAND["expert"]
               + 500_000 * 9_216) / 819e9
    assert _least_ms(3, 500_000, 100) == pytest.approx(1e3 * by_hand)
    assert _least_ms(3, 500_000, 99) < _least_ms(3, 500_000, 100) < \
        _least_ms(3, 500_001, 100)


def test_the_kernels_least_time_is_the_rows_read_once():
    seconds, bound = counts_longcat.latent_attention_seconds(
        LONGCAT, 250_000, 197e12, 819e9)
    assert bound == "memory"
    assert seconds == pytest.approx(250_000 * 9_216 / 819e9)
    # The absorbed form's operations take half of that: half the ridge.
    assert 250_000 * 8 * 139_264 / 197e12 / seconds == pytest.approx(
        0.503, abs=0.001)


# -- traced rounds ------------------------------------------------------------

def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"), READERS / f"{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _traced_run(monkeypatch, phases, modules, ops=(), t0=1_000,
                t1=1_000_000_000):
    run = {"trace": {"t0": t0, "t1": t1, "planes": {"/device:TPU:0": {
        "busy_s": 1.0, "modules": modules, "ops": []}}},
        "config": LONGCAT, "device": {"kind": "TPU v5 lite"},
        "counters": {"at_close": {}}}
    monkeypatch.setattr(trace_spans, "_LOADED", {(t0, t1): {
        "phases": phases, "ops": {"/device:TPU:0": list(ops)}}})
    return run


MS = 1_000_000
# Round 7: 3 steps in 90 ms.  Round 8: 2 steps in 50 ms.  Round 9's call is
# cut by the trace's end; the first call belongs to a round the trace missed.
PHASES = [
    ("round_dispatch", 100 * MS, 1 * MS, {"round": 7, "width": 8, "live": 60}),
    ("round_wait", 102 * MS, 95 * MS,
     {"round": 7, "steps": 3, "attended": 700_000, "experts_touched": 120,
      "pairs_held": 40}),
    ("round_dispatch", 420 * MS, 1 * MS, {"round": 8, "width": 2, "live": 50}),
    ("round_wait", 422 * MS, 60 * MS,
     {"round": 8, "steps": 2, "attended": 400_000, "experts_touched": 75}),
    ("round_dispatch", 700 * MS, 1 * MS, {"round": 9, "width": 8, "live": 1}),
]
MODULES = [
    ("jit_decode_rounds(5)", 1_000, 50 * MS),
    ("jit_decode_rounds(5)", 99 * MS, 90 * MS),
    ("jit_prefill_chunk_into_slot(6)", 200 * MS, 25 * MS),
    ("jit_decode_rounds(5)", 421 * MS, 50 * MS),
    ("jit_decode_rounds(5)", 701 * MS, 1_000_000_000 - 701 * MS),
]
KERNEL = "%paged_latent_decode_attention.64 = bf16[64,64,512]{2,1,0} " \
    "custom-call(%a, %b)"


def _kernel_ops(per_call_ms):
    """Eight kernel calls a step in the two whole calls (24 and 16), one in
    the call the trace missed and one in a prefill chunk's time."""
    ops = [(KERNEL, 10 * MS, 5 * MS, "jit_decode_rounds", "kft.mla_decode")]
    for first, n in ((100 * MS, 24), (422 * MS, 16)):
        ops += [(KERNEL, first + i * 3 * MS, round(per_call_ms * MS),
                 "jit_decode_rounds", "kft.mla_decode") for i in range(n)]
    ops.append(("%fusion.9 = bf16[64,6144]{1,0} fusion(%c)", 130 * MS,
                4 * MS, "jit_decode_rounds", "kft.mlp"))
    return ops


def test_whole_calls_and_the_decode_roofline(monkeypatch):
    run = _traced_run(monkeypatch, PHASES, MODULES)
    calls = traced_latent_rounds.whole_calls(run)
    assert [(c["start"], c["seconds"], c["steps"], c["attended"],
             c["experts_touched"]) for c in calls] == [
        (99 * MS, 0.09, 3, 700_000, 120), (421 * MS, 0.05, 2, 400_000, 75)]
    share = _reader("mla.decode_rounds_roofline").read(run)
    least = _least_ms(3, 700_000, 120) + _least_ms(2, 400_000, 75)
    assert share == pytest.approx(100 * least / 140)
    assert 20 < share < 100
    # The same calls in exactly their least time: 100 %, never over.
    ns = [round(_least_ms(3, 700_000, 120) * MS),
          round(_least_ms(2, 400_000, 75) * MS)]
    modules = [MODULES[0], ("jit_decode_rounds(5)", 99 * MS, ns[0]),
               ("jit_decode_rounds(5)", 421 * MS, ns[1]), MODULES[-1]]
    run = _traced_run(monkeypatch, PHASES, modules)
    assert _reader("mla.decode_rounds_roofline").read(run) == \
        pytest.approx(100.0, rel=1e-6)


def test_the_kernels_roofline_is_over_its_own_calls_in_the_whole_rounds(
        monkeypatch):
    run = _traced_run(monkeypatch, PHASES, MODULES, _kernel_ops(1.0))
    least = 1_100_000 * 9_216 / 819e9
    assert _reader("mla.latent_attention_roofline").read(run) == \
        pytest.approx(100 * least / 0.040)
    # Forty calls that take exactly the least time between them: 100 %.
    run = _traced_run(monkeypatch, PHASES, MODULES,
                      _kernel_ops(1e3 * least / 40))
    assert _reader("mla.latent_attention_roofline").read(run) == \
        pytest.approx(100.0, rel=1e-4)
    # A program that attends another way: no such operation, no number.
    other = [op for op in _kernel_ops(1.0) if "latent" not in op[0]]
    run = _traced_run(monkeypatch, PHASES, MODULES, other)
    assert _reader("mla.latent_attention_roofline").read(run) is None


def test_pair_shares_are_over_every_pair_the_window_counted():
    counters = {"before": {"pairs_held": 100, "pairs_zero": 1_000,
                           "pairs_absent": 2_000},
                "at_close": {"pairs_held": 300, "pairs_zero": 4_300,
                             "pairs_absent": 8_500}}
    run = {"counters": counters, "window": {"seconds": 50.0}}
    assert _reader("moe.held_pair_share").read(run) == pytest.approx(2.0)
    assert _reader("moe.zero_pair_share").read(run) == pytest.approx(33.0)
    counters["at_close"] = dict(counters["before"])   # no pair counted
    assert _reader("moe.zero_pair_share").read(run) is None


def test_readers_leave_the_metric_out_where_nothing_is_stated(monkeypatch):
    """A program that states no ``experts_touched`` or no pairs (the parent
    commit, another stack): no number, no error; nor an untraced run."""
    bare = [(p, s, d, {k: v for k, v in f.items() if k != "experts_touched"})
            for p, s, d, f in PHASES]
    run = _traced_run(monkeypatch, bare, MODULES, _kernel_ops(1.0))
    assert traced_latent_rounds.whole_calls(run) is None
    assert _reader("mla.decode_rounds_roofline").read(run) is None
    untraced = {"trace": None,
                "counters": {"before": {"steps": 0}, "at_close": {
                    "steps": 10}}, "window": {"seconds": 1.0}}
    for name in ("mla.decode_rounds_roofline",
                 "mla.latent_attention_roofline", "moe.zero_pair_share",
                 "moe.held_pair_share"):
        assert _reader(name).read(untraced) is None


# -- the reference ------------------------------------------------------------

SMALL = dict(hidden_size=64, num_layers=2, num_attention_heads=4,
             ffn_hidden_size=128, expert_ffn_hidden_size=32, q_lora_rank=32,
             kv_lora_rank=48, qk_nope_head_dim=16, qk_rope_head_dim=8,
             v_head_dim=16, n_routed_experts=4, n_routed_experts_published=8,
             experts_offset=2, zero_expert_num=4, moe_topk=3,
             routed_scaling_factor=6, vocab_size=512, rms_norm_eps=1e-5,
             rope_theta=1e7, mla_scale_q_lora=True, mla_scale_kv_lora=True,
             tie_word_embeddings=False)


def _logits(c, seed, quantize=None, n=48):
    tokens = np.random.default_rng(seed).integers(1, c["vocab_size"], n,
                                                  dtype=np.int32)
    ref = reference_longcat.Reference(c, seed, quantize=quantize)
    return tokens, np.asarray(ref.logits(tokens, 0, n, n))


def test_the_copy_is_the_tests_reference():
    """``lib/reference_longcat.py`` computes a layer and an expert at a time
    and attention in blocks; on one tree, with the same share of the
    experts, it gives what ``tests/reference_longcat.py`` gives."""
    import jax.numpy as jnp

    sys.path.insert(0, str(ROOT / "tests"))
    try:
        import reference_longcat as plain
    finally:
        sys.path.remove(str(ROOT / "tests"))
    tokens, got = _logits(SMALL, 11)
    tree = weights_longcat.make_tree(SMALL, 11, jnp.bfloat16)
    want = np.asarray(plain.forward(SMALL, tree, tokens, experts_held=4,
                                    experts_offset=2))
    assert np.ptp(want) > 1.0
    np.testing.assert_allclose(got, want, atol=2e-5)
    # Padding behind a position changes nothing before it.
    ref = reference_longcat.Reference(SMALL, 11)
    padded = np.asarray(ref.logits(tokens, 8, 16, 64))
    np.testing.assert_allclose(padded, want[8:24], atol=2e-5)
    # The uncut layer is another model: the share is a real cut.
    whole = dict(SMALL, n_routed_experts=8, experts_offset=0)
    assert np.abs(_logits(whole, 11)[1] - got).max() > 1e-2


def test_the_seeded_bias_is_not_zero_and_the_router_keeps_every_output():
    import jax.numpy as jnp

    leaves = weights_longcat.layer_leaves(
        LONGCAT | {"hidden_size": 64, "ffn_hidden_size": 128,
                   "expert_ffn_hidden_size": 32},
        weights_longcat.weights.seed_key(3), 1, jnp.bfloat16)
    bias = np.asarray(leaves["moe/bias"])
    assert bias.shape == (768,) and bias.dtype == np.float32
    assert np.abs(bias).min() > 0 and 0.0005 < bias.std() < 0.002
    assert leaves["moe/router"].shape == (64, 768)
    assert leaves["moe/wi"].shape == (16, 64, 64)
    # The two halves of a double layer hold different matrices.
    assert not np.array_equal(np.asarray(leaves["half_0/attn/wq_a"]),
                              np.asarray(leaves["half_1/attn/wq_a"]))


@pytest.mark.parametrize("seed", [1, 2**31 + 3])
def test_control_in_lower_precision_reads_worse(seed):
    _, sound = _logits(SMALL, seed)
    _, low = _logits(SMALL, seed, quantize="fp8")
    control = reference_longcat.served_gaps(sound, low.argmax(-1))
    assert reference_longcat.served_gaps(sound, sound.argmax(-1)).max() == 0
    assert control.mean() > 0.001 and (control > 0).mean() > 0.03


@pytest.mark.parametrize("broken", [
    {"routed_scaling_factor": 1}, {"zero_expert_num": 0},
    {"experts_offset": 0}, {"num_layers": 1}, {"moe_topk": 1}],
    ids=lambda b: next(iter(b)))
def test_a_part_left_out_reads_far_from_the_reference(broken):
    """Another model under the same seed (the scale 6, the zero-compute
    experts, another chip's share, a double layer or choices a token fewer)
    picks tokens far below the reference's best: the limits of ``correct``
    cannot pass it."""
    _, sound = _logits(SMALL, 7)
    _, wrong = _logits(dict(SMALL, **broken), 7)
    gaps = reference_longcat.served_gaps(sound, wrong.argmax(-1))
    assert gaps.mean() > 0.02


# -- the runner's exits -------------------------------------------------------

def _runner():
    spec = importlib.util.spec_from_file_location(
        "bench_serve_longcat_under_test",
        ROOT / "benchmark" / "runners" / "serve_longcat.py")
    runner = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(runner)
    return runner


def test_the_key_map_names_fields_the_program_has():
    import dataclasses

    from kubeflow_tpu.models.transformer import TransformerConfig

    runner = _runner()
    known = {f.name for f in dataclasses.fields(TransformerConfig)}
    assert set(runner._serve()._FIELDS.values()) <= known
    assert set(runner._FIELDS) <= set(LONGCAT)
    assert set(runner._ONE_FORM) <= set(LONGCAT)


def test_a_program_without_the_fields_is_refused_before_anything_starts(
        monkeypatch):
    """The parent commit's ``TransformerConfig`` has no latent sizes: the
    run has to fail at once, with a message, and start no child."""
    runner = _runner()
    serve = runner._serve()
    serve._FIELDS = {**serve._FIELDS, "a_key": "a_field_no_program_has"}
    serve.run = lambda ctx: pytest.fail("the run was started")
    monkeypatch.setattr(runner, "_serve", lambda: serve)
    with pytest.raises(SystemExit, match="a_field_no_program_has"):
        runner.run({"config": LONGCAT})


@pytest.mark.parametrize("other", [
    {"zero_expert_type": "constant"}, {"attention_bias": True},
    {"mla_scale_kv_lora": False}], ids=lambda b: next(iter(b)))
def test_a_configuration_of_another_form_is_refused(monkeypatch, other):
    runner = _runner()
    serve = runner._serve()
    serve.run = lambda ctx: pytest.fail("the run was started")
    monkeypatch.setattr(runner, "_serve", lambda: serve)
    with pytest.raises(SystemExit, match=next(iter(other))):
        runner.run({"config": dict(LONGCAT, **other)})
