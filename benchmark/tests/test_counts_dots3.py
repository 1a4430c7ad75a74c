"""What the dots3-note-prev configuration
(``configs/dots3-note-prev-l5.json``) brings to the benchmark: its counts
against sizes worked out by hand (the cut, and the published 46 layers against
"288B-A17B"), the file against the catalog's row, a decode round's least
time, which has to follow the experts touched and the positions scored,
chosen and read through a window, the traced rounds' reductions on rounds and
operations set by hand (never over 100 %), the readers that leave their
metric out where the program states nothing, the reference against the
tests' one and against itself in a lower precision and with a part left out,
and the runner's exits."""

import importlib.util
import json
import pathlib
import sys

import numpy as np
import pytest

from benchmark.lib import (counts_dots3, reference_dots3, trace_spans,
                           traced_dsa_rounds, weights_dots3)

ROOT = pathlib.Path(__file__).resolve().parents[2]
DOTS3 = json.loads(
    (ROOT / "benchmark" / "configs" / "dots3-note-prev-l5.json").read_text())
READERS = ROOT / "benchmark" / "layer_metrics"
FULL, WINDOW = "full_attention", "sliding_attention"

# By hand (ISSUE 44), hidden 5120.  Full attention, 128 heads: W_qa
# 5120*1024 = 5,242,880 + its norm 1,024 + W_qb 1024*128*192 = 25,165,824 +
# W_kva 5120*576 = 2,949,120 + its norm 512 + W_kvb 512*128*256 = 16,777,216
# + W_o 16384*5120 = 83,886,080 + the gate 5120*128 = 655,360: 134,678,016.
# The indexer: W_qI 1024*64*128 = 8,388,608 + W_kI 5120*128 = 655,360 + the
# LayerNorm 256 + W_w 5120*64 = 327,680: 9,371,904.  Window attention, 64
# heads: 5,242,880 + 1,024 + 1024*64*256 = 16,777,216 + 5120*1088 =
# 5,570,560 + 1,024 + 1024*64*320 = 20,971,520 + 8192*5120 = 41,943,040 +
# 5120*64 = 327,680: 90,834,944.  A dense SwiGLU 3*5120*13824, an expert
# 3*5120*1536, the router 5120*256 + 256.
HAND = dict(full=134_678_016, indexer=9_371_904, window=90_834_944,
            dense=212_336_640, expert=23_592_960, router=1_310_976)
HAND["layer0"] = HAND["full"] + HAND["indexer"] + 10_240 + HAND["dense"]
HAND["layer1"] = (HAND["full"] + HAND["indexer"] + 10_240 + HAND["router"]
                  + 33 * HAND["expert"])
HAND["layer2"] = HAND["window"] + 10_240 + HAND["router"] \
    + 33 * HAND["expert"]
HAND["cut"] = (HAND["layer0"] + HAND["layer1"] + 3 * HAND["layer2"]
               + 2 * 19_008 * 5_120 + 5_120)


def test_counts_against_hand_worked_dots3():
    c = DOTS3
    assert counts_dots3.attention_params(c, FULL) == HAND["full"]
    assert counts_dots3.indexer_params(c) == HAND["indexer"]
    assert counts_dots3.attention_params(c, WINDOW) == HAND["window"]
    assert counts_dots3.dense_ff_params(c) == HAND["dense"]
    assert counts_dots3.expert_params(c) == HAND["expert"]
    assert counts_dots3.router_params(c) == HAND["router"]
    assert [counts_dots3.layer_params(c, i) for i in range(5)] == [
        HAND["layer0"], HAND["layer1"]] + 3 * [HAND["layer2"]]
    assert (HAND["layer0"], HAND["layer1"], HAND["layer2"]) == (
        356_396_800, 923_938_816, 870_723_840)
    assert counts_dots3.total_params(c) == HAND["cut"] == 4_087_154_176
    assert round(counts_dots3.weight_bytes(c) / 1e9, 2) == 8.17
    # 2 full planes of 576 + 128 values, 3 window planes of 1,088.
    assert counts_dots3.cache_bytes_per_token(c) == 9_344
    assert counts_dots3.attention_flops_per_row(c, FULL) == 2 * 128 * 1_088
    assert counts_dots3.attention_flops_per_row(c, WINDOW) == 2 * 64 * 2_112
    assert counts_dots3.index_flops_per_key(c) == 16_384


def test_the_published_46_layers_against_288b_a17b():
    """13 full and 33 window layers, 256 experts and one shared, the whole
    vocabulary: 279.6e9 parameters, of which 16.3e9 meet a token.  The
    catalog says "288B-A17B" of the model with its towers: what is left
    (~8e9 held, ~1e9 a token) is the vision and audio towers and the MTP
    module, which are no keys of the row."""
    whole = counts_dots3._published(DOTS3)
    assert whole["layer_types"].count(FULL) == 13
    assert whole["layer_types"].count(WINDOW) == 33
    assert whole["layer_types"][:6] == [FULL, FULL, WINDOW, WINDOW, WINDOW,
                                        FULL]
    assert whole["layer_types"][:5] == DOTS3["layer_types"]
    by_hand = (13 * (HAND["full"] + HAND["indexer"]) + 33 * HAND["window"]
               + 46 * 10_240 + HAND["dense"] + 45 * (
                   HAND["router"] + 257 * HAND["expert"])
               + 2 * 152_064 * 5_120 + 5_120)
    assert counts_dots3.published_params(DOTS3) == by_hand
    assert round(by_hand / 1e9, 1) == 279.6
    assert round(counts_dots3.active_params(DOTS3) / 1e9, 1) == 16.3
    assert 0.95 < by_hand / 288e9 < 1.0
    assert 0.94 < counts_dots3.active_params(DOTS3) / 17e9 < 1.0


def test_the_file_states_the_catalog_row_and_its_cut():
    """Every key of the catalog's ``config`` under its name, but for the
    four in ``reduced``, whose published values stand beside them."""
    rows = pathlib.Path(
        "/opt/skills/guides/model-configs/architectures.jsonl")
    if not rows.exists():
        pytest.skip("no catalog beside the guide here")
    row = next(json.loads(line) for line in rows.read_text().splitlines()
               if '"dots3-note-prev"' in line)
    assert DOTS3["source"] == row["source_url"]
    assert DOTS3["reduced"] == ["num_hidden_layers", "layer_types",
                                "n_routed_experts", "vocab_size"]
    for key, value in row["config"].items():
        if key in DOTS3["reduced"]:
            continue
        assert DOTS3[key] == value, key
    assert DOTS3["reduced_from"]["num_hidden_layers"] == 46
    assert DOTS3["reduced_from"]["n_routed_experts"] == 256 \
        == DOTS3["n_routed_experts_published"]
    assert DOTS3["reduced_from"]["vocab_size"] == 152_064 == 8 * 19_008
    assert DOTS3["layer_types"] == row["config"]["layer_types"][:5]
    assert DOTS3["moe_shared_d_ff"] == DOTS3["n_shared_experts"] \
        * DOTS3["moe_intermediate_size"]
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(e for e in manifest["configs"]
                 if e["name"] == "dots3-note-prev-l5")
    assert entry["source"] == DOTS3["source"] and len(entry["source"]) < 200
    assert entry["reduced"] == DOTS3["reduced"]


def test_the_program_holds_what_the_counts_count():
    """The served tree's shapes (``weights_dots3.specs``, which the runner
    holds against the program's own init) add up to the count, and are the
    program's tree."""
    held = sum(int(np.prod(shape))
               for shape, _ in weights_dots3.specs(DOTS3).values())
    assert held == counts_dots3.total_params(DOTS3)
    import dataclasses

    import jax

    from kubeflow_tpu.models.transformer import (TransformerConfig,
                                                 layer_tree_shapes)

    runner = _runner()
    fields = runner._serve()._FIELDS
    known = {f.name for f in dataclasses.fields(TransformerConfig)}
    cfg = TransformerConfig(
        tied_embeddings=False,
        **{fields[k]: v for k, v in DOTS3.items()
           if k in fields and fields[k] in known})
    theirs = {"/".join(str(p.key) for p in path): tuple(shape)
              for path, shape in jax.tree_util.tree_leaves_with_path(
                  layer_tree_shapes(cfg),
                  is_leaf=lambda x: isinstance(x, tuple))}
    ours = {name: shape for name, (shape, _) in
            weights_dots3.specs(DOTS3).items()}
    assert theirs == ours
    assert (cfg.latent_row, cfg.index_dim, cfg.window_row) == (640, 128,
                                                               1152)


def _least_ms(steps, touched, scored, chosen, window):
    seconds, bound = counts_dots3.decode_round_seconds(
        DOTS3, steps, touched, scored, chosen, window, 197e12, 819e9)
    assert bound == "memory"
    return 1e3 * seconds


def test_the_least_time_of_a_round_follows_what_it_read():
    step = counts_dots3.step_matmul_params(DOTS3)
    by_hand = (2 * (HAND["full"] - 1_536 + HAND["indexer"] - 256)
               + 3 * (HAND["window"] - 2_048) + HAND["dense"]
               + 4 * (5_120 * 256 + HAND["expert"]) + 5_120 * 19_008)
    assert step == by_hand
    # A step reads 1.94 GB of weights whatever it routes (2.4 ms); 13 of 32
    # experts in each of 4 layers add 2.45 GB; 16 rows at 20,000 positions
    # score 164 MB of index keys in 2 planes, attend 75 MB of chosen rows
    # and read 54 MB through 3 windows.
    assert round(_least_ms(1, 0, 0, 0, 0), 1) == 2.4
    assert round(_least_ms(1, 52, 0, 0, 0) - _least_ms(1, 0, 0, 0, 0),
                 1) == 3.0
    scored, chosen, window = 2 * 16 * 20_000, 2 * 16 * 2_048, 3 * 16 * 513
    assert counts_dots3.sparse_read_bytes(DOTS3, scored, 0, 0) == 163_840_000
    assert counts_dots3.sparse_read_bytes(DOTS3, 0, chosen, 0) == 75_497_472
    assert counts_dots3.sparse_read_bytes(DOTS3, 0, 0, window) == 53_581_824
    whole = (3 * 2 * step + 100 * 2 * HAND["expert"] + 163_840_000
             + 75_497_472 + 53_581_824) / 819e9
    assert _least_ms(3, 100, scored, chosen, window) == pytest.approx(
        1e3 * whole)
    assert _least_ms(3, 100, scored, chosen, window) < _least_ms(
        3, 100, scored + 1, chosen, window)
    seconds, bound = counts_dots3.sparse_attention_seconds(
        DOTS3, chosen, 197e12, 819e9)
    assert bound == "compute"     # 278 kFLOP a row of 1,152 B: over the ridge
    assert seconds == pytest.approx(chosen * 2 * 128 * 1_088 / 197e12)


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
        "config": DOTS3, "device": {"kind": "TPU v5 lite"},
        "counters": {"at_close": {}}}
    monkeypatch.setattr(trace_spans, "_LOADED", {(t0, t1): {
        "phases": phases, "ops": {"/device:TPU:0": list(ops)}}})
    return run


MS = 1_000_000
FACTS_7 = {"steps": 3, "attended": 900_000, "experts_touched": 150,
           "index_scored": 1_800_000, "index_chosen": 196_608,
           "window_read": 73_872}
FACTS_8 = {"steps": 2, "attended": 600_000, "experts_touched": 90,
           "index_scored": 1_200_000, "index_chosen": 131_072,
           "window_read": 49_248}
PHASES = [
    ("round_dispatch", 100 * MS, 1 * MS, {"round": 7, "width": 8, "live": 16}),
    ("round_wait", 102 * MS, 95 * MS, {"round": 7, **FACTS_7}),
    ("round_dispatch", 420 * MS, 1 * MS, {"round": 8, "width": 2, "live": 16}),
    ("round_wait", 422 * MS, 60 * MS, {"round": 8, **FACTS_8}),
    ("round_dispatch", 700 * MS, 1 * MS, {"round": 9, "width": 8, "live": 1}),
]
MODULES = [
    ("jit_decode_rounds(5)", 1_000, 50 * MS),
    ("jit_decode_rounds(5)", 99 * MS, 90 * MS),
    ("jit_prefill_chunk_into_slot(6)", 200 * MS, 25 * MS),
    ("jit_decode_rounds(5)", 421 * MS, 50 * MS),
    ("jit_decode_rounds(5)", 701 * MS, 1_000_000_000 - 701 * MS),
]


def _ops():
    """In the two whole calls: 8 ms under the sparse read, 6 under the
    index, 2 under the choice, 4 through windows, 20 elsewhere; and
    operations in a call the trace missed and in a chunk's time."""
    def op(scope, start, ms, module="jit_decode_rounds"):
        return (f"%fusion.{start} = bf16[16,5120]{{1,0}} fusion(%c)",
                start, ms * MS, module, scope)

    return [op("kft.mla_sparse", 10 * MS, 30),
            op("kft.mla_sparse", 100 * MS, 5), op("kft.dsa_index", 106 * MS,
                                                  4),
            op("kft.dsa_select", 111 * MS, 2), op("kft.mla_window",
                                                  114 * MS, 3),
            op("kft.mlp", 118 * MS, 12),
            op("kft.mla_sparse", 205 * MS, 9, "jit_prefill_chunk_into_slot"),
            op("kft.mla_sparse", 422 * MS, 3), op("kft.dsa_index", 426 * MS,
                                                  2),
            op("kft.mla_window", 429 * MS, 1), op("kft.mlp", 431 * MS, 8)]


def test_whole_calls_and_the_decode_roofline(monkeypatch):
    run = _traced_run(monkeypatch, PHASES, MODULES)
    calls = traced_dsa_rounds.whole_calls(run)
    assert [(c["start"], c["seconds"], c["steps"], c["index_chosen"])
            for c in calls] == [(99 * MS, 0.09, 3, 196_608),
                                (421 * MS, 0.05, 2, 131_072)]
    share = _reader("dsa.decode_rounds_roofline").read(run)
    facts = [[f[k] for k in traced_dsa_rounds.FACTS[1:]]
             for f in (FACTS_7, FACTS_8)]
    least = _least_ms(3, *facts[0]) + _least_ms(2, *facts[1])
    assert share == pytest.approx(100 * least / 140)
    assert 5 < share < 100
    # The same calls in exactly their least time: 100 %, never over.
    ns = [round(_least_ms(3, *facts[0]) * MS),
          round(_least_ms(2, *facts[1]) * MS)]
    modules = [MODULES[0], ("jit_decode_rounds(5)", 99 * MS, ns[0]),
               ("jit_decode_rounds(5)", 421 * MS, ns[1]), MODULES[-1]]
    run = _traced_run(monkeypatch, PHASES, modules)
    assert _reader("dsa.decode_rounds_roofline").read(run) == \
        pytest.approx(100.0, rel=1e-6)


def test_the_shares_and_the_sparse_roofline_are_over_the_whole_calls_own_ops(
        monkeypatch):
    run = _traced_run(monkeypatch, PHASES, MODULES, _ops())
    assert _reader("dsa.index_share").read(run) == pytest.approx(
        100 * 8 / 40)
    assert _reader("window.attention_share").read(run) == pytest.approx(
        100 * 4 / 40)
    least = counts_dots3.sparse_attention_seconds(
        DOTS3, 196_608 + 131_072, 197e12, 819e9)[0]
    assert _reader("dsa.sparse_attention_roofline").read(run) == \
        pytest.approx(100 * least / 0.008)
    assert 0 < 100 * least / 0.008 < 100


def test_the_chosen_share_is_over_the_windows_counters():
    run = {"counters": {
        "before": {"index_scored": 1_000, "index_chosen": 900},
        "at_close": {"index_scored": 401_000, "index_chosen": 40_900}}}
    assert _reader("dsa.chosen_share").read(run) == pytest.approx(10.0)


def test_readers_leave_the_metric_out_where_nothing_is_stated(monkeypatch):
    """A program that states no ``index_scored`` (the parent commit,
    another stack): no number, no error; nor an untraced run."""
    bare = [(p, s, d, {k: v for k, v in f.items() if k != "index_scored"})
            for p, s, d, f in PHASES]
    run = _traced_run(monkeypatch, bare, MODULES, _ops())
    assert traced_dsa_rounds.whole_calls(run) is None
    untraced = {"trace": None,
                "counters": {"before": {"steps": 0}, "at_close": {
                    "steps": 10}}, "window": {"seconds": 1.0}}
    for name in ("dsa.decode_rounds_roofline", "dsa.index_share",
                 "dsa.sparse_attention_roofline", "window.attention_share"):
        assert _reader(name).read(run) is None
        assert _reader(name).read(untraced) is None
    assert _reader("dsa.chosen_share").read(untraced) is None


# -- the reference ------------------------------------------------------------

SMALL = dict(
    hidden_size=64, num_hidden_layers=3,
    layer_types=[FULL, FULL, WINDOW], num_attention_heads=4,
    intermediate_size=128, moe_intermediate_size=32,
    first_k_dense_replace=1, q_lora_rank=32, kv_lora_rank=48,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, rope_theta=8e7,
    index_n_heads=3, index_head_dim=16, index_topk=12,
    attention_gate_type="headwise", swa_attention_gate_type="headwise",
    sliding_window_size=7, swa_num_attention_heads=2, swa_q_lora_rank=32,
    swa_kv_lora_rank=64, swa_qk_nope_head_dim=24, swa_qk_rope_head_dim=8,
    swa_v_head_dim=16, swa_rope_theta=5e4, n_routed_experts=4,
    n_routed_experts_published=8, experts_offset=2, n_shared_experts=1,
    num_experts_per_tok=3, norm_topk_prob=True, routed_scaling_factor=1,
    vocab_size=512, rms_norm_eps=1e-5, tie_word_embeddings=False)


def _logits(c, seed, quantize=None, n=48):
    tokens = np.random.default_rng(seed).integers(1, c["vocab_size"], n,
                                                  dtype=np.int32)
    ref = reference_dots3.Reference(c, seed, quantize=quantize)
    return tokens, np.asarray(ref.logits(tokens, 0, n, n))


def test_the_copy_is_the_tests_reference(monkeypatch):
    """``lib/reference_dots3.py`` computes a layer, an expert, a group of
    heads and a block of query rows at a time; on one tree, with the same
    share of the experts, it gives what ``tests/reference_dots3.py``
    gives."""
    import jax.numpy as jnp

    sys.path.insert(0, str(ROOT / "tests"))
    try:
        import reference_dots3 as plain
    finally:
        sys.path.remove(str(ROOT / "tests"))
    tokens, got = _logits(SMALL, 11)
    tree = weights_dots3.make_tree(SMALL, 11, jnp.bfloat16)
    want = np.asarray(plain.forward(SMALL, tree, tokens, experts_held=4,
                                    experts_offset=2))
    assert np.ptp(want) > 1.0
    np.testing.assert_allclose(got, want, atol=2e-5)
    # Padding behind a position changes nothing before it.
    ref = reference_dots3.Reference(SMALL, 11)
    padded = np.asarray(ref.logits(tokens, 8, 16, 64))
    np.testing.assert_allclose(padded, want[8:24], atol=2e-5)
    # Several blocks of query rows, the last of them padding alone: the
    # blocks that hold tokens are computed, and read what they read whole.
    monkeypatch.setattr(reference_dots3, "_Q_BLOCK", 16)
    blocks = reference_dots3.Reference(SMALL, 11)
    np.testing.assert_allclose(
        np.asarray(blocks.logits(tokens, 0, 48, 64)), want, atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(blocks.logits(tokens[:20], 4, 16, 64)),
        np.asarray(plain.forward(SMALL, tree, tokens[:20], experts_held=4,
                                 experts_offset=2))[4:20], atol=2e-5)
    # The uncut layer is another model: the share is a real cut.
    whole = dict(SMALL, n_routed_experts=8, experts_offset=0)
    assert np.abs(_logits(whole, 11)[1] - got).max() > 1e-2


def test_the_seeded_biases_are_not_zero():
    import jax.numpy as jnp

    leaves = weights_dots3.layer_leaves(
        DOTS3 | {"hidden_size": 64, "intermediate_size": 128,
                 "moe_intermediate_size": 32},
        weights_dots3.weights.seed_key(3), 1, jnp.bfloat16)
    bias = np.asarray(leaves["moe/bias"])
    assert bias.shape == (256,) and bias.dtype == np.float32
    assert np.abs(bias).min() > 0 and 0.005 < bias.std() < 0.02
    norm_bias = np.asarray(leaves["attn/k_idx_norm/bias"])
    assert norm_bias.shape == (128,) and 0.01 < norm_bias.std() < 0.04
    assert leaves["attn/w_idx"].dtype == np.float32
    assert leaves["moe/router"].shape == (64, 256)
    assert leaves["moe/wi"].shape == (32, 64, 64)
    assert leaves["moe/shared/wi"].shape == (2, 64, 32)
    # Layers alike share a program and hold different matrices.
    assert weights_dots3.same_leaves(DOTS3, 4) == 2
    other = weights_dots3.layer_leaves(
        DOTS3 | {"hidden_size": 64, "intermediate_size": 128,
                 "moe_intermediate_size": 32},
        weights_dots3.weights.seed_key(3), 0, jnp.bfloat16)
    assert not np.array_equal(np.asarray(leaves["attn/wq_a"]),
                              np.asarray(other["attn/wq_a"]))


@pytest.mark.parametrize("seed", [1, 2**31 + 3])
def test_control_in_lower_precision_reads_worse(seed):
    _, sound = _logits(SMALL, seed)
    _, low = _logits(SMALL, seed, quantize="fp8")
    control = reference_dots3.served_gaps(sound, low.argmax(-1))
    assert reference_dots3.served_gaps(sound, sound.argmax(-1)).max() == 0
    assert control.mean() > 0.001 and (control > 0).mean() > 0.03


@pytest.mark.parametrize("broken", [
    {"attention_gate_type": "none"}, {"swa_attention_gate_type": "none"},
    {"sliding_window_size": 3}, {"index_topk": 4}, {"experts_offset": 0},
    {"num_hidden_layers": 2}, {"num_experts_per_tok": 1},
    {"norm_topk_prob": False}], ids=lambda b: next(iter(b)))
def test_a_part_left_out_reads_far_from_the_reference(broken):
    """Another model under the same seed (a gate left out, a shorter
    window, fewer positions chosen, another chip's share, a layer or
    choices a token fewer) picks tokens far below the reference's best: the
    limits of ``correct`` cannot pass it."""
    _, sound = _logits(SMALL, 7)
    _, wrong = _logits(dict(SMALL, **broken), 7)
    gaps = reference_dots3.served_gaps(sound, wrong.argmax(-1))
    assert gaps.mean() > 0.02


# -- the runner's exits -------------------------------------------------------

def _runner():
    spec = importlib.util.spec_from_file_location(
        "bench_serve_dots3_under_test",
        ROOT / "benchmark" / "runners" / "serve_dots3.py")
    runner = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(runner)
    return runner


def test_the_key_map_names_fields_the_program_has():
    import dataclasses

    from kubeflow_tpu.models.transformer import TransformerConfig

    runner = _runner()
    known = {f.name for f in dataclasses.fields(TransformerConfig)}
    assert set(runner._serve()._FIELDS.values()) <= known
    assert set(runner._FIELDS) <= set(DOTS3)
    assert set(runner._ONE_FORM) <= set(DOTS3)


def test_a_program_without_the_fields_is_refused_before_anything_starts(
        monkeypatch):
    """The parent commit's ``TransformerConfig`` has no indexer and no
    window: the run has to fail at once, with a message, and start no
    child."""
    runner = _runner()
    serve = runner._serve()
    serve._FIELDS = {**serve._FIELDS, "a_key": "a_field_no_program_has"}
    serve.run = lambda ctx: pytest.fail("the run was started")
    monkeypatch.setattr(runner, "_serve", lambda: serve)
    with pytest.raises(SystemExit, match="a_field_no_program_has"):
        runner.run({"config": DOTS3})


@pytest.mark.parametrize("other", [
    {"attention_gate_type": "elementwise"}, {"n_shared_experts": 2},
    {"apply_mla_qkv_lora_rescale": False}, {"topk_method": "group_limited"}],
    ids=lambda b: next(iter(b)))
def test_a_configuration_of_another_form_is_refused(monkeypatch, other):
    runner = _runner()
    serve = runner._serve()
    serve.run = lambda ctx: pytest.fail("the run was started")
    monkeypatch.setattr(runner, "_serve", lambda: serve)
    with pytest.raises(SystemExit, match=next(iter(other))):
        runner.run({"config": dict(DOTS3, **other)})
