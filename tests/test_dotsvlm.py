"""dots.vlm1.inst's language model (DeepSeek-V3's block: latent attention
without the low-rank factor, YaRN rotary frequencies and a factor on the
softmax scale, sigmoid-routed experts chosen inside kept GROUPS beside one
shared expert) with its multi-token-prediction module DRAFTING, against
the plain reference ``tests/reference_dotsvlm.py``: chunked prefill (which
also fills the draft layer's plane) and ``decode_rounds`` (a step runs two
rows a slot, takes the draft or not, and drafts again), down to the engine
with prefix reuse on.  Logits are compared, never tokens, except where the
claim IS about tokens: a drafting stack serves what the same stack without
its module serves.

Tolerances as ``tests/test_longcat.py``: float32 on both sides in another
order of operations reads 2e-6 to 2e-5; ``TOL`` = 2e-4.  Norm scales are
drawn from 1 +- 0.3 and the router's bias at 0.05, so that each left out
shows.  The vocabulary holds 16 tokens, so that a draft of seeded weights
is right about once in 16 and both branches of a step are taken.
"""

import dataclasses
import time

import numpy as np
import pytest

import reference_dotsvlm

test_lfm2 = pytest.importorskip("test_lfm2")
Served, SLOTS, BLOCK, TABLE, CHUNK = (
    test_lfm2.Served, test_lfm2.SLOTS, test_lfm2.BLOCK, test_lfm2.TABLE,
    test_lfm2.CHUNK)

TOL = 2e-4
VOCAB, SEED = 16, 20261003
YARN = {"type": "yarn", "factor": 8, "original_max_position_embeddings": 64,
        "beta_fast": 4, "beta_slow": 0.25, "mscale": 1, "mscale_all_dim": 1}
# Hugging Face keys, as the reference reads them.
PUBLISHED = {
    "vocab_size": VOCAB, "hidden_size": 32, "num_hidden_layers": 3,
    "num_attention_heads": 4, "intermediate_size": 64,
    "moe_intermediate_size": 24, "q_lora_rank": 16, "kv_lora_rank": 24,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 16, "v_head_dim": 8,
    "first_k_dense_replace": 1, "n_routed_experts_published": 16,
    "n_group": 4, "topk_group": 2, "num_experts_per_tok": 3,
    "norm_topk_prob": True, "routed_scaling_factor": 2.5,
    "rms_norm_eps": 1e-6, "rope_theta": 10000, "rope_scaling": YARN,
    "num_nextn_predict_layers": 1,
}
FIELDS = {"vocab_size": "vocab_size", "hidden_size": "d_model",
          "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
          "intermediate_size": "d_ff", "moe_intermediate_size": "moe_d_ff",
          "q_lora_rank": "mla_q_rank", "kv_lora_rank": "mla_kv_rank",
          "qk_nope_head_dim": "mla_nope_dim",
          "qk_rope_head_dim": "mla_rope_dim", "v_head_dim": "mla_v_dim",
          "first_k_dense_replace": "moe_dense_layers",
          "n_routed_experts_published": "moe_experts",
          "n_group": "moe_groups", "topk_group": "moe_groups_kept",
          "num_experts_per_tok": "moe_top_k",
          "norm_topk_prob": "moe_normalize",
          "routed_scaling_factor": "moe_scale", "rms_norm_eps": "norm_eps",
          "rope_theta": "rope_theta",
          "num_nextn_predict_layers": "mtp_layers"}
# The contracted axes of each matmul weight: its fan-in keeps activations
# O(1).
CONTRACTED = {"attn/wq_a": (0,), "attn/wq_b": (0,), "attn/wkv_a": (0,),
              "attn/wk_b": (2,), "attn/wv_b": (0,), "attn/wo": (0, 1),
              "mlp/wi": (1,), "mlp/wo": (0,), "moe/router": (0,),
              "moe/wi": (1,), "moe/wo": (1,), "shared/wi": (1,),
              "shared/wo": (0,), "mtp/eh_proj": (0,), "w_out": (0,)}


def _config(published=PUBLISHED, **kw):
    from kubeflow_tpu.models.transformer import yarn_softmax_mult
    from kubeflow_tpu.serving.loaders import _model_config

    fields = {FIELDS[k]: v for k, v in published.items() if k in FIELDS}
    yarn = published["rope_scaling"]
    return _model_config({
        **fields, "n_kv_heads": fields["n_heads"],
        "layer_types": ["full_attention"] * fields["n_layers"],
        "attention_kind": "latent", "mla_rescale": False,
        "moe_shared_d_ff": published["moe_intermediate_size"],
        "moe_norm_eps": 1e-20, "yarn_factor": yarn["factor"],
        "yarn_original_len": yarn["original_max_position_embeddings"],
        "yarn_beta_fast": yarn["beta_fast"],
        "yarn_beta_slow": yarn["beta_slow"],
        "mla_softmax_mult": yarn_softmax_mult(yarn["factor"],
                                              yarn["mscale_all_dim"]),
        "max_seq_len": 256, "tied_embeddings": False, "dtype": "float32",
        **kw})


def _params(cfg, seed=SEED):
    """The program's own tree (names and shapes from ``Transformer.init``)
    filled with seeded values."""
    import jax
    import jax.numpy as jnp
    from flax import linen as nn

    from kubeflow_tpu.models.transformer import Transformer

    shapes = nn.unbox(jax.eval_shape(
        Transformer(cfg).init, jax.random.key(0),
        jnp.zeros((1, 8), jnp.int32)))["params"]
    rng = np.random.default_rng(seed)

    def fill(path, leaf):
        name = "/".join(str(p.key) for p in path)
        if name.endswith("scale"):
            return jnp.asarray(rng.uniform(0.7, 1.3, leaf.shape), jnp.float32)
        if name.endswith("moe/bias"):
            return jnp.asarray(rng.normal(0, 0.05, leaf.shape), jnp.float32)
        short = "/".join(name.split("/")[-2:])
        fan_in = int(np.prod([leaf.shape[a] for a in CONTRACTED.get(
            short, CONTRACTED.get(name, ()))]))
        return jnp.asarray(rng.normal(0, fan_in ** -0.5, leaf.shape),
                           jnp.float32)

    return jax.tree_util.tree_map_with_path(fill, shapes)


def _reference(params, tokens, published=PUBLISHED, **share):
    main, module = reference_dotsvlm.forward(published, params, tokens,
                                             **share)
    return np.asarray(main), None if module is None else np.asarray(module)


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(1, VOCAB, n, dtype=np.int32)


@pytest.fixture(scope="module")
def dots():
    cfg = _config()
    return cfg, _params(cfg)


def _without_module(cfg, params):
    """The same stack, its module left out."""
    return dataclasses.replace(cfg, mtp_layers=0), {
        k: v for k, v in params.items() if k != "mtp"}


class Drafting(Served):
    """``test_lfm2.Served`` for a drafting stack: a chunk says what ran
    before it, a round hands its drafts over, and ``drafts[slot]`` keeps
    (index of the served token, the draft it was held against)."""

    def __init__(self, cfg, params, new=4, kernel=False):
        super().__init__(cfg, params, new,
                         reference=lambda p, t, c: _reference(p, t)[0])
        self.kernel, self.drafts, self.taken = kernel, {}, 0

    def chunk(self, slot, prompt, start, new=4, after_hit=False):
        chunk = np.zeros((1, CHUNK), np.int32)
        seg = prompt[start:start + CHUNK]
        chunk[0, :len(seg)] = seg
        self.state, first = self.g.prefill_chunk_into_slot(
            self.cfg, self.params, self.state, self.decode, chunk,
            np.int32(start), np.int32(len(prompt)), np.int32(new),
            np.int32(slot), np.int32(7), self.tables[slot][None], None,
            np.int32(prompt[start - 1] if after_hit else -1),
            grouped_kernel=self.grouped_kernel)
        if start + CHUNK >= len(prompt):
            self.served[slot] = [int(first[0])]
            self.drafts[slot] = []

    def rounds(self, steps, k=4):
        self.state, toks, counts, ran, drafts = self.g.decode_rounds(
            self.cfg, self.params, self.state, self.decode, k, self.tables,
            np.int32(steps), paged_kernel=self.kernel,
            grouped_kernel=self.grouped_kernel)
        self.taken += int(self.state["mtp_counts"][1])
        for slot in self.served:
            n, at = int(counts[slot]), len(self.served[slot])
            self.served[slot] += [int(t) for t in toks[slot, :n]]
            self.drafts[slot] += [(at + j, int(d)) for j, d in
                                  enumerate(drafts[slot, :n]) if d >= 0]
        return int(ran)

    def next_logits(self):
        """(main logits of the NEXT position, the module's logits for the
        one after it had the stack's first choice come next), of every
        slot, through the pool as the programs left it."""
        import jax.numpy as jnp

        s, g = self.state, self.g
        cache = tuple(s[side] for side in g.pool_sides(s))
        tables = jnp.asarray(self.tables)
        hidden, cache, _, _ = g.forward_layer_types(
            self.cfg, self.params, s["last_token"][:, None], cache,
            s["lengths"], tables=tables, hidden=True)
        main = g._head(self.cfg, self.params, hidden)
        module = g.mtp_logits(
            self.cfg, self.params, hidden, jnp.argmax(main, -1), cache,
            s["lengths"] + 1, s["lengths"][:, None], tables=tables)[0]
        return np.asarray(main)[:, 0], np.asarray(module)[:, 0]

    def worst(self, slot, prompt, params=None):
        """Largest difference from the reference over what ``slot``
        served: each served token's and each draft's gap under its row's
        best, the next position's main row, and the module's row after
        it."""
        served = self.served[slot]
        main, module = self.next_logits()
        first = int(main[slot].argmax())
        tokens = np.concatenate([prompt, served, [first]])
        want, want_module = _reference(
            self.params if params is None else params, tokens)
        p = len(prompt)
        rows = want[p - 1:p - 1 + len(served)]
        gaps = [(rows.max(-1) - rows[np.arange(len(served)), served]).max(),
                np.abs(main[slot] - want[-2]).max(),
                np.abs(module[slot] - want_module[-1]).max()]
        # The draft of served token j came from the module's row at
        # position p + j - 2.
        gaps += [want_module[p + j - 2].max() - want_module[p + j - 2, d]
                 for j, d in self.drafts[slot]]
        return max(gaps)


def _serve_one(cfg, params, prompt_len, new=8, kernel=False):
    run = Drafting(cfg, params, new, kernel)
    prompt = _tokens(prompt_len, seed=3)
    run.prefill(1, prompt, new)
    while len(run.served[1]) < new:
        run.rounds(2)
    assert len(run.served[1]) == new
    return run, prompt


# -- the tree, the state and what is refused ----------------------------------

def test_tree_holds_the_module_and_the_pool_a_plane_for_it(dots):
    cfg, params = dots
    from kubeflow_tpu.models.generate import init_paged_state

    assert sorted(params["mtp"]) == ["eh_proj", "enorm", "hnorm", "layer",
                                     "norm"]
    assert params["mtp"]["eh_proj"].shape == (64, 32)
    assert sorted(params["mtp"]["layer"]) == sorted(params["layers"]["2"])
    assert "shared" in params["layers"]["1"]["moe"]
    assert "mlp" in params["layers"]["0"]
    state = init_paged_state(cfg, SLOTS, SLOTS * TABLE, BLOCK)
    # Three layers and the draft layer; 24 + 16 values a row, in whole
    # 128-lane rows.
    assert cfg.kv_planes == 4
    assert state["cache_latent"].shape == (4, SLOTS * TABLE, BLOCK, 256)
    assert state["mtp_draft"].shape == (SLOTS,)
    assert state["mtp_hidden"].shape == (SLOTS, 32)


@pytest.mark.parametrize("bad", [
    dict(mtp_layers=2), dict(attention_kind="gqa", mla_rescale=True,
                             yarn_factor=1.0, mla_softmax_mult=1.0),
    dict(index_heads=2, index_dim=16, index_topk=8, yarn_factor=1.0,
         mla_softmax_mult=1.0),
    dict(layer_types=["full_attention", "full_attention",
                      "sliding_attention"], window=4, window_heads=2,
         window_q_rank=8, window_kv_rank=8, window_nope_dim=4,
         window_rope_dim=4, window_v_dim=4, yarn_factor=1.0,
         mla_softmax_mult=1.0),
    dict(layer_types=["shortcut_double"] * 3, moe_dense_layers=0,
         moe_shared_d_ff=0, yarn_factor=1.0, mla_softmax_mult=1.0),
    dict(layer_types=["full_attention", "conv", "full_attention"]),
    dict(attn_gate=True), dict(moe_experts=0, moe_groups=0,
                               moe_groups_kept=0, moe_shared_d_ff=0),
    # YaRN where it is not built, and numbers it cannot take.
    dict(mtp_layers=0, index_heads=2, index_dim=16, index_topk=8),
    dict(yarn_original_len=0), dict(yarn_beta_slow=8.0),
    dict(yarn_factor=0.5),
    # Groups that do not divide the experts, hold under two, keep none,
    # or hold fewer experts than a token takes.
    dict(moe_groups=3), dict(moe_groups=16), dict(moe_groups_kept=0),
    dict(moe_groups_kept=5), dict(moe_groups=8, moe_groups_kept=1),
    dict(moe_zero_experts=2),
])
def test_config_refuses_what_is_not_built(bad):
    with pytest.raises(ValueError, match="not built|mtp_layers|moe_groups|"
                       "YaRN|mla_rescale|layer_types"):
        _config(**bad)


def test_the_older_stacks_keep_their_forms():
    """LongCat's and dots3's tiny configurations, as their own tests
    build them: the factor on, no groups, no YaRN, no module."""
    cfg = pytest.importorskip("test_longcat")._config()
    assert (cfg.mla_rescale, cfg.moe_groups, cfg.yarn_factor,
            cfg.mla_softmax_mult, cfg.mtp_layers, cfg.moe_norm_eps) == (
                True, 0, 1.0, 1.0, 0, 1e-6)
    assert cfg.kv_planes == 4 and cfg.latent_sizes().yarn is None


# -- (f) YaRN -----------------------------------------------------------------

def test_yarn_at_the_published_numbers():
    """factor 40, original 4096, beta_fast 32, beta_slow 1, theta 10000
    over 32 pairs: pairs 0-10 keep their frequency, pairs 23-31 turn 40
    times slower, a ramp between; the scale's factor is 1.87385."""
    from kubeflow_tpu.models.transformer import (
        yarn_frequencies,
        yarn_softmax_mult,
    )

    got = yarn_frequencies(64, 10000.0, (40.0, 4096, 32.0, 1.0))
    plain = np.asarray([10000.0 ** (-2 * i / 64) for i in range(32)])
    assert got.dtype == np.float64
    assert np.allclose(got[:11], plain[:11], rtol=1e-14)
    assert not np.allclose(got[11], plain[11], rtol=1e-3)
    assert np.allclose(got[23:], plain[23:] / 40, rtol=1e-15)
    ramp = (np.arange(11, 23) - 10) / 13
    assert np.allclose(got[11:23], plain[11:23] * (1 - ramp)
                       + plain[11:23] / 40 * ramp, rtol=1e-14)
    published = dict(PUBLISHED, qk_rope_head_dim=64, rope_scaling=dict(
        YARN, factor=40, original_max_position_embeddings=4096,
        beta_fast=32, beta_slow=1))
    assert np.allclose(got, reference_dotsvlm.yarn_frequencies(published),
                       rtol=1e-15)
    assert abs(yarn_softmax_mult(40, 1) - 1.87385) < 1e-5
    assert abs(reference_dotsvlm.softmax_scale(dict(
        published, qk_nope_head_dim=128)) - 192 ** -0.5 * 1.87385) < 1e-6
    assert yarn_softmax_mult(1, 1) == 1.0


def test_the_programs_rotary_table_is_the_float64_one(dots):
    """``_rope_pairs`` turns pair i of a row at position p by p * f'_i."""
    import jax.numpy as jnp

    from kubeflow_tpu.models.generate import _rope_pairs

    cfg, _ = dots
    z = cfg.latent_sizes()
    x = jnp.asarray(np.random.default_rng(1).normal(0, 1, (1, 5, 2, 16)),
                    jnp.float32)
    positions = jnp.asarray([[0, 1, 7, 100, 200]])
    got = np.asarray(_rope_pairs(x, positions, z.rope_theta, z.yarn))
    freqs = reference_dotsvlm.yarn_frequencies(PUBLISHED)
    assert freqs[0] == 1.0 and abs(freqs[-1] * 8 - 10000 ** -0.875) < 1e-12
    ang = np.asarray(positions, np.float64)[0][:, None] * freqs
    x64 = np.asarray(x, np.float64)[0]
    x1, x2 = x64[..., 0::2], x64[..., 1::2]
    cos, sin = np.cos(ang)[:, None], np.sin(ang)[:, None]
    want = np.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                    -1).reshape(x64.shape)
    assert np.abs(got[0] - want).max() < 2e-5
    plain = np.asarray(_rope_pairs(x, positions, z.rope_theta))
    assert np.abs(plain[0] - want).max() > 0.1


# -- (a) the forward, the chunks and the rounds against the reference ---------

@pytest.mark.parametrize("n", [1, 7, 40])
def test_flax_forward_matches_the_reference(dots, n):
    import jax.numpy as jnp

    from kubeflow_tpu.models.transformer import Transformer

    cfg, params = dots
    tokens = _tokens(n, seed=n)
    got = Transformer(cfg).apply({"params": params},
                                 jnp.asarray(tokens)[None])
    assert np.abs(np.asarray(got)[0] - _reference(params, tokens)[0]
                  ).max() < TOL


def test_the_module_over_a_whole_sequence_matches_the_reference(dots):
    """``mtp_logits`` without a pool, over the rows (h_i, t_{i+1}) of one
    sequence."""
    import jax.numpy as jnp

    from kubeflow_tpu.models import generate as g

    cfg, params = dots
    tokens = _tokens(40, seed=5)
    hidden = g.forward_layer_types(cfg, params, jnp.asarray(tokens)[None],
                                   hidden=True)[0]
    got = g.mtp_logits(cfg, params, hidden[:, :-1],
                       jnp.asarray(tokens)[None, 1:], None, 0,
                       jnp.arange(39)[None])[0]
    assert np.abs(np.asarray(got)[0] - _reference(params, tokens)[1]
                  ).max() < TOL


# A final chunk of 1, 2, 63 and 64 real tokens, over 1, 2 and 3 chunks.
@pytest.mark.parametrize("prompt_len", [1, 2, 63, 64, 65, 127, 128, 129])
def test_chunked_prefill_then_decode_rounds_matches_the_reference(
        dots, prompt_len):
    """Main logits, the module's logits and every draft, through the
    pool's four planes."""
    cfg, params = dots
    run, prompt = _serve_one(cfg, params, prompt_len)
    assert len(run.drafts[1]) >= 4
    assert run.worst(1, prompt) < TOL


def _spoil(params, change):
    import jax

    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: change("/".join(str(p.key) for p in path), leaf),
        params)


def _with(params, name, value):
    return _spoil(params, lambda n, leaf: value(leaf) if n.endswith(name)
                  else leaf)


@pytest.mark.parametrize("name", [
    "mtp/enorm/scale", "mtp/hnorm/scale", "mtp/norm/scale",
    "mtp/layer/attn_norm/scale", "mtp/layer/moe/bias", "layers/1/moe/bias",
    "mtp/layer/attn/q_norm/scale", "layers/2/attn/kv_norm/scale"])
def test_a_scale_or_a_bias_left_out_fails_the_comparison(dots, name):
    """The program run on a tree with one norm scale at ones (or one
    router bias at zero), held to the reference on the true tree."""
    cfg, params = dots
    flat = _with(params, name,
                 lambda a: a * 0 + (0.0 if name.endswith("bias") else 1.0))
    run, prompt = _serve_one(cfg, flat, 65)
    assert run.worst(1, prompt, params) > 20 * TOL


@pytest.mark.parametrize("change", [
    dict(mla_rescale=True), dict(mla_softmax_mult=1.0),
    dict(yarn_factor=1.0, mla_softmax_mult=1.0), dict(moe_groups=0,
                                                      moe_groups_kept=0),
    dict(moe_groups_kept=3), dict(moe_scale=1.0),
    dict(moe_shared_d_ff=0)])
def test_another_form_of_the_block_fails_the_comparison(dots, change):
    cfg, params = dots
    other = dataclasses.replace(cfg, **change)
    if "moe_shared_d_ff" in change:
        for layer in ("1", "2"):
            moe = dict(params["layers"][layer]["moe"])
            moe.pop("shared")
            params = dict(params, layers=dict(params["layers"], **{
                layer: dict(params["layers"][layer], moe=moe)}))
        mtp_moe = dict(params["mtp"]["layer"]["moe"])
        mtp_moe.pop("shared")
        params = dict(params, mtp=dict(params["mtp"], layer=dict(
            params["mtp"]["layer"], moe=mtp_moe)))
    run, prompt = _serve_one(other, params, 65)
    assert run.worst(1, prompt, dots[1]) > 20 * TOL


def test_the_module_fed_the_wrong_half_first_fails(dots):
    """[N_h(h); N_e(Emb)] in place of [N_e(Emb); N_h(h)]: the projection's
    halves swapped."""
    import jax.numpy as jnp

    cfg, params = dots
    swapped = _with(params, "mtp/eh_proj",
                    lambda w: jnp.concatenate([w[32:], w[:32]]))
    run, prompt = _serve_one(cfg, swapped, 65)
    assert run.worst(1, prompt, params) > 20 * TOL


def test_a_bfloat16_program_fails_the_tolerance(dots):
    import jax.numpy as jnp

    cfg, params = dots
    low = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    run, prompt = _serve_one(low, params, 65)
    assert run.worst(1, prompt) > 20 * TOL


def test_a_slot_in_mid_prefill_while_the_others_decode(dots):
    """Slot 0 decodes while slot 2's prompt arrives chunk by chunk: each
    keeps its own carried stream and its own rows."""
    cfg, params = dots
    run = Drafting(cfg, params, new=10)
    first, second = _tokens(70, seed=21), _tokens(150, seed=22)
    run.prefill(0, first, 10)
    for start in range(0, 150, CHUNK):
        run.chunk(2, second, start, 10)
        run.rounds(1)
    while len(run.served[2]) < 10:
        run.rounds(2)
    assert len(run.served[0]) == 10
    assert run.worst(2, second) < TOL
    run.served[0] = run.served[0][:10]
    assert max(d for _, d in run.drafts[0]) < VOCAB


# -- (b) a drafting stack serves what the stack without its module serves -----

def _undrafted(cfg, params, prompt, new):
    plain_cfg, plain = _without_module(cfg, params)
    run = Served(plain_cfg, plain, new,
                 reference=lambda p, t, c: _reference(p, t)[0])
    run.prefill(1, prompt, new)
    while len(run.served[1]) < new:
        run.rounds(4)
    return run.served[1]


def test_both_branches_are_taken_and_the_tokens_are_the_undrafted_stacks(
        dots):
    """Three requests of 40 tokens: a draft of seeded weights is right
    about once in 16 tokens of vocabulary, and either way the tokens are
    what the stack without its module decodes."""
    cfg, params = dots
    taken = steps = 0
    for seed, prompt_len in ((31, 20), (32, 70), (33, 130)):
        prompt = _tokens(prompt_len, seed=seed)
        run = Drafting(cfg, params, new=40)
        run.prefill(1, prompt, 40)
        while len(run.served[1]) < 40:
            steps += run.rounds(3)
        assert run.served[1] == _undrafted(cfg, params, prompt, 40)
        assert run.worst(1, prompt) < TOL
        # A token after a taken draft was held against none.
        assert len(run.drafts[1]) == 39 - run.taken
        taken += run.taken
    assert 0 < taken < steps


def test_every_draft_right_yields_two_tokens_a_step(dots):
    """The slot's draft set to the undrafted stack's next token before
    every step: each step takes it, emits two tokens and moves its
    frontier by two over rows that both stay; the odd budget's last step
    emits one."""
    import jax.numpy as jnp

    cfg, params = dots
    prompt = _tokens(70, seed=41)
    want = _undrafted(cfg, params, prompt, 11)
    run = Drafting(cfg, params, new=11)
    run.prefill(1, prompt, 11)
    steps = 0
    while len(run.served[1]) < 11:
        at = len(run.served[1])
        run.state = dict(run.state, mtp_draft=jnp.asarray(
            [0, want[at], 0], jnp.int32))
        lengths = int(run.state["lengths"][1])
        assert run.rounds(1) == 1
        steps += 1
        assert int(run.state["lengths"][1]) == lengths + 2
    assert steps == 5 and run.taken == 5 and run.served[1] == want
    assert bool(run.state["done"][1])
    run.drafts[1] = []          # the test's, not the module's
    assert run.worst(1, prompt) < TOL


def test_a_wrong_draft_every_step_yields_one(dots):
    import jax.numpy as jnp

    cfg, params = dots
    prompt = _tokens(70, seed=42)
    want = _undrafted(cfg, params, prompt, 6)
    run = Drafting(cfg, params, new=6)
    run.prefill(1, prompt, 6)
    while len(run.served[1]) < 6:
        at = len(run.served[1])
        run.state = dict(run.state, mtp_draft=jnp.asarray(
            [0, (want[at] + 1) % VOCAB, 0], jnp.int32))
        assert run.rounds(1) == 1 and len(run.served[1]) == at + 1
    assert run.taken == 0 and run.served[1] == want
    run.drafts[1] = []          # the test's, not the module's
    assert run.worst(1, prompt) < TOL


# -- the two-position form of the paged kernel --------------------------------

@pytest.mark.parametrize("lengths", [(3, 17, 33), (40, 0, 200), (224, 3, 0)])
@pytest.mark.parametrize("first", [0, 1])
def test_latent_kernel_with_two_positions_matches_plain_attention(lengths,
                                                                  first):
    """``paged_latent_decode_attention`` with ``queries=2`` in interpret
    mode: rows [0, h) see one position fewer than rows [h, 2h), positions
    below ``first`` are seen by nobody, pages out of order and shared, a
    slot that attends nothing."""
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.ops.paged_attention import (
        paged_latent_decode_attention,
    )

    rng = np.random.default_rng(13)
    planes, nb, bt, row, latent, h = 3, 48, 16, 256, 128, 4
    pool = jnp.asarray(rng.normal(0, 1, (planes, nb, bt, row)), jnp.float32)
    q = jnp.asarray(rng.normal(0, 1, (3, 2 * h, row)), jnp.float32)
    tables = rng.permutation(nb)[:3 * 14].reshape(3, 14).astype(np.int32)
    tables[2, :2] = tables[0, :2]          # a shared prefix
    n = jnp.asarray(lengths, jnp.int32)
    got = paged_latent_decode_attention(
        q, pool, jnp.int32(1), jnp.asarray(tables), n, latent, 0.25,
        queries=2, first=first, pages_per_block=4, interpret=True)
    view = pool[1][tables].reshape(3, 14 * bt, row)
    sees = n[:, None] - 1 + jnp.arange(2 * h)[None, :] // h       # [S, 2h]
    k_pos = jnp.arange(14 * bt)
    with jax.default_matmul_precision("highest"):
        sc = jnp.einsum("shr,skr->shk", q, view) * 0.25
        keep = (k_pos[None, None, :] < sees[..., None]) \
            & (k_pos[None, None, :] >= first)
        want = jnp.einsum(
            "shk,skc->shc", jax.nn.softmax(
                jnp.where(keep, sc, -jnp.inf), -1), view[..., :latent])
    for slot, length in enumerate(lengths):
        if length == 0:
            assert np.array_equal(np.asarray(got[slot]),
                                  np.zeros((2 * h, latent), np.float32))
        else:
            assert np.abs(np.asarray(got[slot] - want[slot])).max() < 2e-5


def test_decode_rounds_through_the_latent_kernel_matches_the_reference(
        dots, monkeypatch):
    """``decode_rounds`` with ``paged_kernel=True`` (what the engine passes
    when its pool lives on a TPU), the kernel in interpret mode: one call
    a plane and step for both rows, the draft plane's from index 1 on."""
    from kubeflow_tpu.ops import paged_attention

    cfg, params = dots
    calls = []
    real = paged_attention.paged_latent_decode_attention

    def interpreted(*args, **kw):
        calls.append((kw["queries"], kw["first"]))
        return real(*args, interpret=True, **kw)

    monkeypatch.setattr(paged_attention, "paged_latent_decode_attention",
                        interpreted)
    run = Drafting(cfg, params, new=9, kernel=True)
    prompt = _tokens(70, seed=14)
    run.prefill(1, prompt, 9)
    assert run.rounds(4) == 4
    assert calls == [(2, 0)] * 3 + [(2, 1)]
    assert run.worst(1, prompt) < TOL


# -- (e) the groups and the shares --------------------------------------------

def _rows(n=24, seed=9):
    import jax.numpy as jnp

    return jnp.asarray(np.random.default_rng(seed).normal(0, 1, (n, 32)),
                       jnp.float32)


def _expert_layer(cfg, moe, y, live=None):
    """(program's routed ``Experts(y)`` without the shared expert, its
    counts)."""
    from kubeflow_tpu.models.generate import _experts

    out, counts = _experts(cfg, moe, y, live)
    return np.asarray(out), {k: int(v) for k, v in counts.items()}


def _reference_experts(moe, y, **share):
    import jax

    with jax.default_matmul_precision("highest"):
        return np.asarray(reference_dotsvlm.experts(PUBLISHED, y, moe,
                                                    **share))


def test_the_choice_falls_inside_the_kept_groups(dots):
    """Rows whose unrestricted three largest lie in three groups: two
    groups are kept, so the restricted choice differs, and the program's
    layer is the reference's."""
    import jax
    import jax.numpy as jnp

    cfg, params = dots
    moe, y = params["layers"]["1"]["moe"], _rows()
    s, mask = reference_dotsvlm.chosen_experts(PUBLISHED, y, moe)
    e = np.asarray(s + moe["bias"])
    free = np.zeros_like(e)
    np.put_along_axis(free, np.argsort(-e, -1)[:, :3], 1.0, -1)
    differ = (np.asarray(mask) != free).any(-1)
    assert 3 < differ.sum() < 24
    groups = np.asarray(mask).reshape(24, 4, 4).any(-1).sum(-1)
    assert groups.max() <= 2 and (np.asarray(mask).sum(-1) == 3).all()
    got, counts = _expert_layer(cfg, moe, y)
    assert np.abs(got - _reference_experts(moe, y, shared_part=False)
                  ).max() < TOL
    assert counts["held"] == 72
    ungrouped = dataclasses.replace(cfg, moe_groups=0, moe_groups_kept=0)
    assert np.abs(_expert_layer(ungrouped, moe, y)[0] - got).max() \
        > 100 * TOL
    # A tie between two groups' scores keeps the lower group, as a tie
    # between two experts keeps the lower expert.
    tied = dict(moe, router=jnp.zeros_like(moe["router"]),
                bias=jnp.zeros_like(moe["bias"]))
    got, _ = _expert_layer(cfg, tied, y)
    with jax.default_matmul_precision("highest"):
        _, mask = reference_dotsvlm.chosen_experts(PUBLISHED, y, tied)
    assert np.array_equal(np.nonzero(np.asarray(mask)[0])[0], [0, 1, 2])
    assert np.abs(got - _reference_experts(tied, y, shared_part=False)
                  ).max() < TOL


def test_the_shares_add_up_to_the_whole_layer(dots):
    """Four chips with four routed experts each, one group a chip (the
    cell's chip holds half a group: two chips a group there): the parts
    their expert layers give, the shared expert counted once, add up to
    what the uncut reference gives."""
    from kubeflow_tpu.models.generate import _sparse_ff

    cfg, params = dots
    layer, y = params["layers"]["2"], _rows(seed=11)
    moe = layer["moe"]
    shared = _reference_experts(moe, y, experts_held=0)
    total, pairs = np.zeros((24, 32), np.float32), 0
    for k in range(4):
        share = dataclasses.replace(cfg, moe_experts_held=4,
                                    moe_experts_offset=4 * k)
        held = dict(moe, wi=moe["wi"][4 * k:4 * k + 4],
                    wo=moe["wo"][4 * k:4 * k + 4])
        # The block's own feed-forward: routed share + the shared expert.
        x, counts = _sparse_ff(share, dict(layer, moe=held), y[None])
        normed = reference_dotsvlm.rms_norm(
            y, layer["mlp_norm"]["scale"], 1e-6)
        part = np.asarray(x[0] - y)
        want = _reference_experts(held, normed, experts_held=4,
                                  experts_offset=4 * k)
        assert np.abs(part - want).max() < TOL
        total += part
        pairs += int(counts["held"])
        assert int(counts["held"]) + int(counts["absent"]) == 72
    normed_shared = _reference_experts(moe, normed, experts_held=0)
    whole = _reference_experts(moe, normed)
    assert np.abs(total - 3 * normed_shared - whole).max() < TOL
    assert np.abs(shared).max() > 100 * TOL and pairs == 72


# -- ops/grouped_matmul.py in the grouped products' place ----------------------

@pytest.mark.parametrize("live", sorted(test_lfm2.LIVE))
@pytest.mark.parametrize("where", ["stack", "module"])
def test_the_grouped_kernel_is_the_expert_layers_ragged_dot(
        dots, interpreted_grouped_kernel, where, live):
    """An expert layer of the main stack and the draft module's, under
    the expert groups' choice."""
    cfg, params = dots
    layer = next(i for i in range(cfg.n_layers) if cfg.layer_is_sparse(i))
    moe = params["mtp"]["layer"]["moe"] if where == "module" \
        else params["layers"][str(layer)]["moe"]
    test_lfm2.kernel_against_ragged_dot(
        cfg, moe, interpreted_grouped_kernel, test_lfm2.LIVE[live])


def test_both_programs_serve_the_same_through_the_grouped_kernel(
        dots, interpreted_grouped_kernel):
    """Verified pairs, drafts and the draft plane's fill: every expert
    layer of both programs, the module's among them."""
    cfg, params = dots
    test_lfm2.kernel_serves_what_ragged_dot_serves(
        lambda: Drafting(cfg, params, new=6), _tokens(70, seed=21), 6)
    assert len(interpreted_grouped_kernel) >= 2 * 2 * (
        1 + sum(cfg.layer_is_sparse(i) for i in range(cfg.n_layers)))


# -- the engine: (b) again, (c) a hit is a cold request, (d) stops, (g) -------

def _engine(cfg, params, new=8, eos=-1, **kw):
    from kubeflow_tpu.models.generate import DecodeConfig
    from kubeflow_tpu.serving.engine import DecodeEngine

    kw.setdefault("temperature", 0.0)
    return DecodeEngine(
        cfg, params, DecodeConfig(max_new_tokens=new, eos_token=eos,
                                  temperature=kw.pop("temperature")),
        slots=3, prefill_len=160, max_len=160 + new,
        prefill_chunk_tokens=64, decode_rounds=4, name="dotsvlm-test", **kw)


def _stream(engine, prompt, new):
    """(tokens, drafts as (index of the served token, draft))."""
    meta, stream = engine.submit_stream({"tokens": prompt,
                                         "max_new_tokens": new})
    tokens = [t for chunk in stream for t in chunk]
    return tokens, [(at + j, int(d)) for at, held in meta["mtp_drafts"]
                    for j, d in enumerate(held) if d >= 0]


@pytest.mark.parametrize("flag, value", [
    ("host_spill_blocks", 4), ("temperature", 0.7)])
def test_engine_refuses_at_construction_by_name(dots, flag, value):
    cfg, params = dots
    with pytest.raises(ValueError, match=flag):
        _engine(cfg, params, **{flag: value})


def test_engine_refuses_a_mesh_and_adapters_by_name(dots):
    cfg, params = dots
    for flag in ("mesh", "adapters"):
        with pytest.raises(ValueError, match=flag):
            _engine(cfg, params, **{flag: object()})


def test_engine_serves_the_undrafted_engines_tokens_and_counts_its_drafts(
        dots):
    """Three requests at once on three slots, 40 tokens each: rounds
    whose slots advance by unequal amounts."""
    cfg, params = dots
    prompts = [_tokens(n, seed=50 + n) for n in (20, 70, 130)]
    outs = []
    for c, p in ((cfg, params), _without_module(cfg, params)):
        engine = _engine(c, p, new=40)
        try:
            import concurrent.futures as cf

            with cf.ThreadPoolExecutor(3) as pool:
                outs.append(list(pool.map(
                    lambda prompt: np.asarray(engine.submit(
                        {"tokens": prompt})["tokens"])[0], prompts)))
            stats = engine.stats()
        finally:
            engine.close(drain_s=0.0)
        if c.mtp_layers:
            drafting = stats
    for a, b, prompt in zip(*outs, prompts):
        assert a.shape == (len(prompt) + 40,) and np.array_equal(a, b)
    assert stats["mtp_drafted"] == stats["mtp_steps"] == 0
    s = drafting
    assert 0 < s["mtp_accepted"] < s["mtp_drafted"]
    assert s["mtp_steps"] == s["steps"] <= s["mtp_drafted"]
    # A token a step, and one more where a draft was taken.
    assert s["tokens"] - 3 <= s["mtp_drafted"] + s["mtp_accepted"] \
        <= s["tokens"]
    assert s["kv_planes"] == 4


def test_a_request_after_a_prefix_hit_is_the_request_served_cold(dots):
    """Two prompts that share 70 tokens: the second aliases the first's
    four whole pages in all four planes (the draft layer's rows lie at
    the index of the token they embed, so a page is a function of its
    tokens and those before), recomputes the stream at position 63
    without writing, and gets the tokens AND the drafts of the same
    request on a fresh engine; both as the reference computes them."""
    cfg, params = dots
    document = _tokens(70, seed=11)
    prompts = [np.concatenate([document, _tokens(n, seed=20 + n)])
               for n in (5, 9)]
    engine = _engine(cfg, params, new=12)
    try:
        served = [_stream(engine, p, 12) for p in prompts]
        stats = engine.stats()
    finally:
        engine.close(drain_s=0.0)
    assert stats["prefix_hits"] == 1 and stats["cached_prompt_tokens"] == 64
    engine = _engine(cfg, params, new=12)
    try:
        cold = _stream(engine, prompts[1], 12)
        assert engine.stats()["prefix_hits"] == 0
    finally:
        engine.close(drain_s=0.0)
    assert served[1] == cold and len(cold[1]) >= 8
    for prompt, (tokens, drafts) in zip(prompts, served):
        main, module = _reference(params, np.concatenate([prompt, tokens]))
        p = len(prompt)
        rows = main[p - 1:p + 11]
        assert (rows.max(-1) - rows[np.arange(12), tokens]).max() < TOL
        assert max(module[p + j - 2].max() - module[p + j - 2, d]
                   for j, d in drafts) < TOL


@pytest.mark.parametrize("after_hit", [True, False])
def test_the_first_chunk_after_a_hit_recomputes_one_position_unwritten(
        dots, after_hit):
    """Slot 2's table aliases the four whole pages slot 0 filled, and its
    first chunk starts at 64: told so, it recomputes the stream at
    position 63 over the shared pages, writes none of their rows, and the
    module's rows are the reference's; told that something ran before it
    (the sabotage), it reads the slot's stale stream and row 64 of the
    draft plane, which every later row attends, is another."""
    cfg, params = dots
    document = _tokens(70, seed=11)
    first = np.concatenate([document, _tokens(5, seed=25)])
    second = np.concatenate([document, _tokens(9, seed=29)])
    run = Drafting(cfg, params, new=8)
    run.prefill(0, first, 8)
    run.tables[2][:4] = run.tables[0][:4]
    shared = np.asarray(run.state["cache_latent"][:, run.tables[0][:4]])
    run.chunk(2, second, 64, 8, after_hit=after_hit)
    assert np.array_equal(shared, np.asarray(
        run.state["cache_latent"][:, run.tables[0][:4]]))
    while len(run.served[2]) < 8:
        run.rounds(2)
    worst = run.worst(2, second)
    assert worst < TOL if after_hit else worst > 20 * TOL
    assert run.worst(0, first) < TOL


def _cycle(params, shift):
    """A tree whose stack decodes t + 1 after t whatever came before
    (every branch's output projection at zero, one-hot embeddings, a head
    that reads token t as t + 1), and whose module predicts the token
    ``shift`` after the one it embeds: 1 is always right, 2 never."""
    import jax.numpy as jnp

    eye = np.eye(32, dtype=np.float32)

    def change(name, leaf):
        if name.endswith(("attn/wo", "mlp/wo", "moe/wo", "shared/wo")):
            return leaf * 0
        if name.endswith("scale"):
            return leaf * 0 + 1
        if name == "embed":
            return jnp.asarray(eye[:VOCAB])
        if name == "w_out":          # [e, v]: column v reads token v - 1
            return jnp.asarray(np.roll(eye[:VOCAB], 1, axis=0).T * 4)
        if name == "mtp/eh_proj":    # the embedding's half, shifted on
            return jnp.asarray(np.concatenate(
                [np.roll(eye, shift - 1, axis=1)[:, :32], 0 * eye]))
        return leaf

    return _spoil(params, change)


@pytest.mark.parametrize("shift, new, eos", [
    (1, 9, -1), (1, 8, -1), (1, 2, -1), (1, 1, -1), (2, 9, -1),
    # 3 -> 4 5 6 7 ...: with every draft taken the steps emit (5, 6),
    # (7, 8), ...: an EOS of 7 is the first of its pair, 6 the second.
    (1, 9, 7), (1, 9, 6), (1, 9, 5), (2, 9, 7)])
def test_a_budget_or_an_eos_cuts_a_pair(dots, shift, new, eos):
    """Every draft right (or none), so that every step is a pair (or
    none is): a budget or an EOS met by the first token of a pair cuts
    the second, and the tokens are the undrafted engine's."""
    cfg, params = dots
    cyc = _cycle(params, shift)
    prompt = np.asarray([9, 1, 2, 3], np.int32)
    outs = []
    for c, p in ((cfg, cyc), _without_module(cfg, cyc)):
        engine = _engine(c, p, new=9, eos=eos)
        try:
            outs.append(np.asarray(engine.submit(
                {"tokens": prompt, "max_new_tokens": new})["tokens"])[0])
            stats = engine.stats()
        finally:
            engine.close(drain_s=0.0)
        if c.mtp_layers:
            drafting = stats
    want = list(range(4, 4 + new))
    if eos >= 0:
        want = want[:want.index(eos) + 1]
    assert outs[0][4:].tolist() == want == outs[1][4:].tolist()
    s = drafting
    assert s["mtp_accepted"] == (s["mtp_drafted"] if shift == 1 else 0)
    # One token from the prefill, then pairs, the last cut or not.
    assert s["mtp_steps"] == (len(want) // 2 if shift == 1
                              else len(want) - 1)


def _drafting_and_undrafted(cfg, params, new, serve):
    """What ``serve(engine)`` returns on the drafting engine and on the
    same stack with its module left out, each engine closed after."""
    out = []
    for c, p in ((cfg, params), _without_module(cfg, params)):
        engine = _engine(c, p, new=new)
        try:
            out.append(serve(engine))
        finally:
            engine.close(drain_s=0.0)
    return out


def _settled_stats(engine):
    time.sleep(0.05)  # the last round's accounting runs beside the reply
    return engine.stats()


@pytest.mark.parametrize("cut", [1, 2, 3, 4])
def test_a_stream_cut_inside_a_pair_resumes_as_the_undrafted_engines(
        dots, cut):
    """Every draft right, so after the prefill's token every step is a
    pair: 3 -> 4 (5 6) (7 8) ...  A client that has read ``cut`` tokens
    of a stream (2 and 4 fall between the two tokens of a pair) and
    resumes from them gets, streamed and whole, the undrafted engine's
    tokens, and the resumed steps are pairs again."""
    cfg, params = dots
    prompt = np.asarray([9, 1, 2, 3], np.int32)
    want = list(range(4, 13))

    def serve(engine):
        _, stream = engine.submit_stream(
            {"tokens": prompt, "max_new_tokens": 9})
        streamed = [t for chunk in stream for t in chunk]
        before = _settled_stats(engine)
        resumed = np.asarray(engine.submit(
            {"tokens": prompt, "resume_tokens": streamed[:cut],
             "max_new_tokens": 9})["tokens"])[0, 4:].tolist()
        after = _settled_stats(engine)
        return streamed, resumed, [after[k] - before[k]
                                   for k in ("mtp_steps", "mtp_accepted")]

    drafting, plain = _drafting_and_undrafted(
        cfg, _cycle(params, 1), 9, serve)
    assert drafting[:2] == plain[:2] == (want, want)
    # One token from the resumed prefill, then pairs, the last cut or
    # not: no draft was refused for having been resumed.
    assert drafting[2] == [(9 - cut) // 2] * 2 and plain[2] == [0, 0]


@pytest.mark.parametrize("shift", [1, 2])
def test_a_drafting_engine_runs_a_round_ahead_and_reuses_its_slots(
        dots, shift):
    """Five requests through three slots, every draft right (a slot
    advances by two a step, and the round ahead covers the unread
    one's worst case) or none: the loop keeps a round in flight, a slot
    freed by a pair is claimed again, and the tokens are the undrafted
    engine's."""
    import concurrent.futures as cf

    cfg, params = dots
    prompts = [np.asarray([9, 7, 8, last], np.int32)
               for last in (1, 2, 3, 1, 2)]
    # The budget of the tests above, so that their programs serve.
    news = [9, 8, 6, 9, 5]

    def serve(engine):
        with cf.ThreadPoolExecutor(5) as pool:
            outs = list(pool.map(
                lambda i: np.asarray(engine.submit(
                    {"tokens": prompts[i], "max_new_tokens": news[i]}
                )["tokens"])[0, 4:].tolist(), range(5)))
        return outs, _settled_stats(engine)

    (outs, s), (plain, _) = _drafting_and_undrafted(
        cfg, _cycle(params, shift), 9, serve)
    assert outs == plain == [list(range(int(p[3]) + 1, int(p[3]) + 1 + n))
                             for p, n in zip(prompts, news)]
    assert s["requests"] == 5 and s["active_slots"] == 0
    assert s["rounds_ahead"] > 0
    assert s["mtp_accepted"] == (s["mtp_drafted"] if shift == 1 else 0)
    assert s["tokens"] == sum(news)
