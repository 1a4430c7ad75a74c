"""What the dots.vlm1.inst configuration
(``configs/dots.vlm1.inst-l5.json``) brings to the benchmark: its counts
against sizes worked out by hand (ISSUE 47's: the cut and the published
model), the seeded tree's size, a drafting decode round's least time, which
has to follow the experts the device touched and the positions attended and
reads the head twice, the kernel's roofline (never over what one read of the
rows allows), the readers that leave their metric out where the program
states nothing, YaRN's numbers as the runner hands them on, the drafts a
record holds, the reference against the tests' one (main and module) and
against itself in a lower precision, the check of the drafts on records made
by hand, and the catalog's keys held unchanged."""

import importlib.util
import json
import pathlib
import sys

import numpy as np
import pytest

from benchmark.lib import (counts_dotsvlm, reference_dotsvlm,
                           traced_mtp_rounds, weights_dotsvlm)

ROOT = pathlib.Path(__file__).resolve().parents[2]
DOTS = json.loads(
    (ROOT / "benchmark" / "configs" / "dots.vlm1.inst-l5.json").read_text())
READERS = ROOT / "benchmark" / "layer_metrics"
CATALOG = pathlib.Path(
    "/opt/skills/guides/model-configs/architectures.jsonl")

# By hand (ISSUE 47), hidden 7168, 128 heads.  One latent attention: W_qa
# 7168*1536 = 11,010,048 + its norm 1,536 + W_qb 1536*128*192 = 37,748,736 +
# W_kva 7168*576 = 4,128,768 + its norm 512 + W_kvb 512*128*256 = 16,777,216
# + W_o 16384*7168 = 117,440,512: 187,107,328.  The dense SwiGLU 3 *
# 7168*18432 = 396,361,728; with its attention and two norms a dense layer is
# 583,483,392.  One expert 3 * 7168*2048 = 44,040,192.  An expert layer
# beside its routed experts: attention + two norms 14,336 + the router
# 7168*256 = 1,835,008 and its bias 256 + the shared expert: 232,997,120.
# The module: three norms 21,504 + eh_proj 2*7168*7168 = 102,760,448 + an
# expert layer with 16 experts: 1,040,422,144.
HAND = dict(attn=187_107_328, dense_layer=583_483_392, expert=44_040_192,
            expert_layer=232_997_120, module=1_040_422_144,
            tables=231_676_928)
HAND["cut"] = (HAND["tables"] + HAND["dense_layer"]
               + 4 * (HAND["expert_layer"] + 16 * HAND["expert"])
               + HAND["module"])
HAND["published"] = (2 * 129_280 * 7_168 + 7_168 + 3 * HAND["dense_layer"]
                     + 58 * (HAND["expert_layer"] + 256 * HAND["expert"]))


def test_counts_against_hand_worked_sizes():
    c = DOTS
    assert counts_dotsvlm.attention_params(c) == HAND["attn"]
    assert counts_dotsvlm.dense_layer_params(c) == HAND["dense_layer"]
    assert counts_dotsvlm.expert_params(c) == HAND["expert"]
    assert counts_dotsvlm.expert_layer_params(c) == HAND["expert_layer"]
    assert counts_dotsvlm.module_params(c) + 16 * HAND["expert"] \
        == HAND["module"]
    assert 2 * c["hidden_size"] * c["vocab_size"] + c["hidden_size"] \
        == HAND["tables"]
    assert counts_dotsvlm.total_params(c) == HAND["cut"] == 5_606_143_232
    assert round(counts_dotsvlm.weight_bytes(c) / 1e9, 2) == 11.21
    assert counts_dotsvlm.published_params(c) == HAND["published"]
    assert round(HAND["published"] / 1e9, 2) == 671.03
    assert counts_dotsvlm.kv_planes(c) == 6
    assert counts_dotsvlm.latent_bytes_per_token(c) == 6 * 1_152


def test_the_seeded_tree_is_that_size_and_the_programs_names():
    shapes = weights_dotsvlm.tree_shapes(DOTS)
    assert sum(int(np.prod(s)) for s, _ in shapes.values()) == HAND["cut"]
    assert shapes["mtp/eh_proj"][0] == (14_336, 7_168)
    assert shapes["layers/0/mlp/wi"][0] == (2, 7_168, 18_432)
    assert shapes["mtp/layer/moe/wi"][0] == (16, 7_168, 4_096)
    assert str(shapes["layers/3/moe/router"][1]) == "float32"
    assert str(shapes["mtp/layer/attn/wq_b"][1]) == "bfloat16"
    assert "layers/0/moe/router" not in shapes
    assert weights_dotsvlm.same_leaves(DOTS, 4) == 1
    assert weights_dotsvlm.same_leaves(DOTS, 5) == 1   # the module's layer


def test_the_file_holds_the_catalogs_keys_unchanged():
    if not CATALOG.exists():
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
               if r["name"] == "dots.vlm1.inst")
    assert DOTS["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if DOTS.get(k) != v)
    assert differs == sorted(DOTS["reduced"])
    assert {k: row["config"][k] for k in differs} == DOTS["reduced_from"]
    assert DOTS["n_routed_experts_published"] == 256
    for said in ("block", "mla", "rope_scaling", "experts", "module",
                 "drafting"):
        assert said in DOTS["assumed"]
    assert "16 v5e chips" in DOTS["deployment"]


def test_a_round_reads_the_head_twice_and_follows_what_the_device_counted():
    c = DOTS
    step = counts_dotsvlm.step_matmul_params(c)
    # Six attentions without their low-rank norms, the dense SwiGLU, five
    # routers and shared experts, the projection, the head's slice twice.
    assert step == (6 * (HAND["attn"] - 2_048) + 396_361_728
                    + 5 * (1_835_008 + HAND["expert"]) + 102_760_448
                    + 2 * 7_168 * 16_160)
    base = counts_dotsvlm.decode_round_bytes(c, 8, 0, 0)
    assert base == 8 * step * 2
    assert counts_dotsvlm.decode_round_bytes(c, 8, 0, 80) - base \
        == 80 * HAND["expert"] * 2
    assert counts_dotsvlm.decode_round_bytes(c, 8, 1000, 0) - base \
        == 1000 * 6 * 1_152
    # Every held expert touched in all five expert layers, 32 slots at
    # 5,000 positions: ISSUE 47's "11.2 GB of weights a step, >= 13.7 ms".
    seconds, bound = counts_dotsvlm.decode_round_seconds(
        c, 1, 32 * 5_000, 80, 197e12, 819e9)
    assert bound == "memory" and 0.0137 < seconds < 0.0160
    # One slot a step is counted for the operations: far under the bytes.
    assert counts_dotsvlm.step_flops(c, 5_000) / 197e12 < seconds / 10


def test_the_kernels_least_time_is_one_read_of_the_rows_for_both_positions():
    c = DOTS
    seconds, bound = counts_dotsvlm.latent_attention_seconds(
        c, 160_000, 197e12, 819e9)
    # 2 positions x 128 heads x (576 + 512) x 2 operations a row and plane
    # against 1,152 B: 557,056 / 197e12 = 2.83 ns against 1.41 ns.
    assert bound == "compute"
    assert abs(seconds - 160_000 * 6 * 557_056 / 197e12) < 1e-9
    assert counts_dotsvlm.latent_attention_seconds(
        dict(c, num_attention_heads=32), 160_000, 197e12, 819e9)[1] \
        == "memory"


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "bench_reader_" + name.replace(".", "_"), READERS / f"{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


@pytest.mark.parametrize("name", [
    "mtp.accepted_share", "mtp.draft_share", "mtp.decode_rounds_roofline",
    "mtp.latent_attention_roofline"])
def test_readers_leave_their_metric_out_where_the_program_states_nothing(
        name):
    """An untraced run, and the parent's counters (no ``mtp_*`` key)."""
    run = {"trace": None, "config": DOTS, "device": {"kind": "TPU v5 lite"},
           "window": {"seconds": 50.0},
           "counters": {"before": {"steps": 1}, "at_close": {"steps": 9},
                        "after": {"steps": 9}}}
    assert _reader(name)(run) is None


def test_accepted_share_reads_the_windows_counters():
    run = {"counters": {
        "before": {"mtp_drafted": 100, "mtp_accepted": 10},
        "at_close": {"mtp_drafted": 1100, "mtp_accepted": 15}}}
    assert _reader("mtp.accepted_share")(run) == 0.5
    run["counters"]["at_close"]["mtp_drafted"] = 100
    assert _reader("mtp.accepted_share")(run) is None


def test_roofline_shares_of_rounds_set_by_hand(monkeypatch):
    """Two whole calls at exactly their least time read 100 %, at twice
    that 50 %; the kernel's operations inside them likewise."""
    peaks = (197e12, 819e9)
    facts = [dict(steps=8, attended=8 * 32 * 4_000, experts_touched=500,
                  mtp_drafted=256),
             dict(steps=4, attended=4 * 20 * 6_000, experts_touched=200,
                  mtp_drafted=80)]
    calls, at = [], 1_000
    for f in facts:
        least = counts_dotsvlm.decode_round_seconds(
            DOTS, f["steps"], f["attended"], f["experts_touched"],
            *peaks)[0]
        calls.append(dict(f, start=at, end=at + int(2 * least * 1e9),
                          seconds=2 * least))
        at = calls[-1]["end"] + 1_000
    monkeypatch.setattr(traced_mtp_rounds.traced_latent_rounds,
                        "whole_calls", lambda run, facts: calls)
    run = {"config": DOTS, "device": {"kind": "TPU v5 lite"}}
    assert abs(traced_mtp_rounds.decode_roofline_share(run) - 50.0) < 1e-9
    least = counts_dotsvlm.latent_attention_seconds(
        DOTS, sum(c["attended"] for c in calls), *peaks)[0]
    ops = [("%paged_latent_decode_attention.1 = bf16[32,256,512]{2,1,0} "
            "custom-call(...)", calls[0]["start"] + 10,
            int(least * 1e9 * 4), "jit_decode_rounds", "kft.mla_decode"),
           ("%fusion.1 = f32[8]{0} fusion(...)", calls[0]["start"] + 5, 50,
            "jit_decode_rounds", "kft.mlp"),
           # Outside the whole calls: not the kernel's time inside them.
           ("%paged_latent_decode_attention.2 = bf16[32,256,512]{2,1,0} "
            "custom-call(...)", 10, 10 ** 9, "jit_decode_rounds", None)]
    monkeypatch.setattr(traced_mtp_rounds.trace_spans, "busiest_ops",
                        lambda run: ops)
    assert abs(traced_mtp_rounds.kernel_roofline_share(run) - 25.0) < 1e-6
    monkeypatch.setattr(traced_mtp_rounds.trace_spans, "busiest_ops",
                        lambda run: ops[1:2])
    assert traced_mtp_rounds.kernel_roofline_share(run) is None


def test_draft_share_reads_the_whole_scope_path(monkeypatch):
    """An operation under ``kft.mtp_draft/kft.mla_q`` counts; one under the
    main layers' ``kft.mla_q`` does not."""
    calls = [dict(start=0, end=1_000, seconds=1e-6, steps=1, attended=1,
                  experts_touched=1, mtp_drafted=1)]
    ops = [("%a = f32[1]{0} fusion()", 10, 100, "jit_decode_rounds",
            "kft.mla_q"),
           ("%b = f32[1]{0} fusion()", 200, 300, "jit_decode_rounds",
            "kft.mla_q"),
           ("%c = f32[1]{0} fusion()", 600, 100, "jit_prefill", "kft.mlp")]
    paths = {"%a = f32[1]{0} fusion()":
             "jit(decode_rounds)/while/body/kft.mla_q/dot_general",
             "%b = f32[1]{0} fusion()":
             "jit(decode_rounds)/while/body/kft.mtp_draft/kft.mla_q/dot"}
    monkeypatch.setattr(traced_mtp_rounds.traced_latent_rounds,
                        "whole_calls", lambda run, facts: calls)
    monkeypatch.setattr(traced_mtp_rounds.trace_spans, "busiest_ops",
                        lambda run: ops)
    monkeypatch.setattr(traced_mtp_rounds, "_paths", lambda run: paths)
    assert abs(traced_mtp_rounds.draft_share({}) - 75.0) < 1e-9
    monkeypatch.setattr(traced_mtp_rounds, "_paths", lambda run: {
        k: v.replace("kft.mtp_draft/", "") for k, v in paths.items()})
    assert traced_mtp_rounds.draft_share({}) is None


def _runner():
    spec = importlib.util.spec_from_file_location(
        "bench_runner_dotsvlm",
        ROOT / "benchmark" / "runners" / "serve_dotsvlm.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_the_runner_hands_on_yarn_as_the_program_names_it():
    runner = _runner()
    fields = runner.yarn_fields(DOTS["rope_scaling"])
    assert fields["yarn_factor"] == 40.0
    assert fields["yarn_original_len"] == 4096
    assert (fields["yarn_beta_fast"], fields["yarn_beta_slow"]) == (32.0, 1.0)
    assert abs(fields["mla_softmax_mult"] - 1.87385) < 1e-5
    assert abs(reference_dotsvlm.softmax_scale(DOTS)
               - 192 ** -0.5 * fields["mla_softmax_mult"]) < 1e-12
    freqs = reference_dotsvlm.yarn_frequencies(DOTS)
    plain = 10_000.0 ** (-2 * np.arange(32) / 64)
    assert np.allclose(freqs[:11], plain[:11], rtol=1e-14)
    assert np.allclose(freqs[23:], plain[23:] / 40, rtol=1e-14)
    with pytest.raises(SystemExit):
        runner.yarn_fields(dict(DOTS["rope_scaling"], mscale=0.7))
    assert set(runner._FIELDS.values()) >= {
        "mtp_layers", "moe_groups", "moe_groups_kept", "mla_rescale"}


def test_the_runner_refuses_a_program_without_the_fields(monkeypatch):
    """The parent commit: an exit before anything is started."""
    import dataclasses

    from kubeflow_tpu.models import transformer

    @dataclasses.dataclass(frozen=True)
    class Older:
        vocab_size: int = 1

    monkeypatch.setattr(transformer, "TransformerConfig", Older)
    with pytest.raises(SystemExit, match="mtp_layers"):
        _runner().run({"config": DOTS})


def test_drafts_of_a_record_are_those_of_the_tokens_the_client_saw():
    runner = _runner()
    rec = {"tokens": [5, 6, 7, 8, 9], "mtp_drafts": [
        (1, np.asarray([3, 4, -1])), (4, np.asarray([2, 1]))]}
    assert runner.drafts_of(rec) == [(1, 3), (2, 4), (4, 2)]
    assert runner.drafts_of({"tokens": [1]}) == []


TINY = {"vocab_size": 64, "hidden_size": 32, "num_hidden_layers": 3,
        "num_attention_heads": 4, "intermediate_size": 64,
        "moe_intermediate_size": 24, "q_lora_rank": 16, "kv_lora_rank": 24,
        "qk_nope_head_dim": 8, "qk_rope_head_dim": 16, "v_head_dim": 8,
        "first_k_dense_replace": 1, "n_routed_experts_published": 16,
        "n_routed_experts": 4, "experts_offset": 4, "n_group": 4,
        "topk_group": 2, "num_experts_per_tok": 3, "norm_topk_prob": True,
        "routed_scaling_factor": 2.5, "rms_norm_eps": 1e-6,
        "rope_theta": 10000, "n_shared_experts": 1,
        "rope_scaling": {"type": "yarn", "factor": 8,
                         "original_max_position_embeddings": 64,
                         "beta_fast": 4, "beta_slow": 0.25, "mscale": 1,
                         "mscale_all_dim": 1},
        "num_nextn_predict_layers": 1}


@pytest.fixture(scope="module")
def tiny():
    import jax.numpy as jnp

    sys.path.insert(0, str(ROOT / "tests"))
    import reference_dotsvlm as tests_reference

    tokens = np.random.default_rng(0).integers(1, 64, 50, dtype=np.int32)
    ref = reference_dotsvlm.Reference(TINY, 5, dtype=jnp.float32)
    main = np.asarray(ref.logits(tokens, 10, 30, 64))
    module = reference_dotsvlm.MODULE_ROWS[(None, tokens.tobytes())]
    tree = weights_dotsvlm.make_tree(TINY, 5, jnp.float32)
    return tokens, main, module, tree, tests_reference


def test_reference_is_the_tests_reference_main_and_module(tiny):
    tokens, main, module, tree, tests_reference = tiny
    want_main, want_module = tests_reference.forward(
        TINY, tree, tokens, experts_held=4, experts_offset=4)
    assert np.abs(main - np.asarray(want_main)[10:40]).max() < 1e-4
    assert np.abs(module[:-1] - np.asarray(want_module)[10:39]).max() < 1e-4
    assert np.abs(module).max() > 1.0


@pytest.mark.parametrize("left_out", ["moe/bias", "moe/shared/wo",
                                      "eh_proj"])
def test_a_part_left_out_of_the_weights_shows(tiny, left_out, monkeypatch):
    import jax.numpy as jnp

    tokens, main, module, _, _ = tiny
    real = weights_dotsvlm.leaf

    def without(key, name, *args, **kw):
        out = real(key, name, *args, **kw)
        return out * 0 if name == left_out else out

    monkeypatch.setattr(weights_dotsvlm, "leaf", without)
    ref = reference_dotsvlm.Reference(TINY, 5, dtype=jnp.float32)
    other = np.asarray(ref.logits(tokens, 10, 30, 64))
    other_module = reference_dotsvlm.MODULE_ROWS[(None, tokens.tobytes())]
    assert np.abs(other_module - module).max() > 1e-2
    if left_out != "eh_proj":
        assert np.abs(other - main).max() > 1e-2


def test_control_in_lower_precision_reads_worse_main_and_module(tiny):
    import jax.numpy as jnp

    tokens, main, module, _, _ = tiny
    low = reference_dotsvlm.Reference(TINY, 5, dtype=jnp.float32,
                                      quantize="fp8")
    low_main = np.asarray(low.logits(tokens, 10, 30, 64))
    low_module = reference_dotsvlm.MODULE_ROWS[("fp8", tokens.tobytes())]
    for sound, control in ((main, low_main), (module, low_module)):
        assert reference_dotsvlm.served_gaps(
            sound, sound.argmax(-1)).max() == 0.0
        assert np.abs(control - sound).max() > 1e-3


def test_the_check_holds_the_drafts_of_the_sampled_requests(tiny,
                                                            monkeypatch):
    """Records made by hand on the tiny configuration: served tokens and
    drafts that are the reference's own first choices pass; one draft
    moved to another token fails by the drafts' limit alone; a request
    without drafts is not compared."""
    import jax.numpy as jnp

    tokens, _, _, _, _ = tiny
    real = reference_dotsvlm.Reference
    monkeypatch.setattr(
        reference_dotsvlm, "Reference",
        lambda published, seed, quantize=None: real(
            published, seed, dtype=jnp.float32, quantize=quantize))
    module = _runner()._serve()
    prompt = tokens[:40]
    ref = real(TINY, 5, dtype=jnp.float32)
    served = []
    for _ in range(6):
        row = np.asarray(ref.logits(np.concatenate(
            [prompt, np.asarray(served, np.int32)]), 39 + len(served), 1,
            64))
        served.append(int(row[0].argmax()))
    ref.logits(np.concatenate([prompt, served]).astype(np.int32), 39, 8, 64)
    rows = reference_dotsvlm.MODULE_ROWS[
        (None, np.concatenate([prompt, served]).astype(np.int32).tobytes())]
    drafts = [int(rows[j - 1].argmax()) for j in range(1, 6)]
    limits = {"max_new_tokens": 8, "served_logit_gap_max": 0.01,
              "served_logit_gap_mean": 0.01, "draft_logit_gap_max": 0.01,
              "draft_logit_gap_mean": 0.01}

    def record(held):
        return {"error": None, "tokens": list(served), "cut": False,
                "request": {"prompt": prompt, "max_new": 6},
                "mtp_drafts": [(1, np.asarray(held[:2])),
                               (3, np.asarray(held[2:]))]}

    ok, numbers, gaps = module.check_correct(
        TINY, 5, [record(drafts)], limits, 64, 8)
    said = {name: value for name, value, _ in numbers}
    assert ok and said["drafts_compared"] == 5
    assert said["draft_logit_gap_max"] == 0.0 and len(gaps["drafts"]) == 5
    wrong = list(drafts)
    wrong[3] = (wrong[3] + 1) % 64
    ok, numbers, _ = module.check_correct(
        TINY, 5, [record(wrong)], limits, 64, 8, quantize="fp8")
    said = {name: value for name, value, _ in numbers}
    assert not ok and said["draft_logit_gap_max"] > 0.01
    assert said["served_logit_gap_max"] == 0.0
    assert "control_fp8_draft_gap_mean" in said
    bare = dict(record(drafts), mtp_drafts=None)
    ok, numbers, _ = module.check_correct(TINY, 5, [bare], limits, 64, 8)
    assert not ok and ("drafts_compared", 0.0, None) in numbers
