"""dots3-note-prev's language model (full layers whose learned indexer picks
``index_topk`` positions before latent attention, window layers with a
latent of their own, a headwise gate, sigmoid-routed experts of which a chip
holds a share beside ONE shared expert) against the plain reference
``tests/reference_dots3.py``: the flax forward (dense scores, masks), and the
serving path (prefill in chunks of 64, then ``decode_rounds``, over THREE
pools on one block table: the full planes' latent rows, their index keys, the
window planes' wider rows), down to the engine with a prefix hit.  Contexts
run past the tiny ``index_topk`` (24) and the tiny window (9), so both bite.
Logits are compared, never tokens.

Tolerances.  Program and reference both compute in float32 on the CPU, in
another order of operations (three paged pools, the absorbed form, rows
gathered by the chosen positions, a window's pages alone, rows sorted by
expert).  Their logits differ by 2e-6 to 2e-5 at a logit spread over 1:
``TOL`` = 2e-4 leaves that an order of room and is two orders under what the
same program in bfloat16 reads (3e-2 and more), so a bfloat16 program fails
it, and so does every sabotage below (1e-2 to 1).  A choice at the 24th place
falls alike on both sides: the index scores are float32 sums of a few dozen
products, and the seeded scores lie ~1e-2 apart where rounding moves them by
~1e-6.  Weights are seeded normals at 1/sqrt(fan-in), norm scales are drawn
from 1 +- 0.3, the indexer's LayerNorm bias and the router's bias at 0.03.
"""

import collections
import dataclasses
import functools
import json

import numpy as np
import pytest

import reference_dots3

test_lfm2 = pytest.importorskip("test_lfm2")
Served, SLOTS, BLOCK, TABLE, CHUNK = (
    test_lfm2.Served, test_lfm2.SLOTS, test_lfm2.BLOCK, test_lfm2.TABLE,
    test_lfm2.CHUNK)

TOL = 2e-4
VOCAB, SEED = 96, 20261002
TOPK, WINDOW = 24, 9
# Hugging Face keys, as the reference reads them.
PUBLISHED = {
    "vocab_size": VOCAB, "hidden_size": 32, "num_hidden_layers": 4,
    "layer_types": ["full_attention", "full_attention", "sliding_attention",
                    "sliding_attention"],
    "num_attention_heads": 4, "intermediate_size": 64,
    "moe_intermediate_size": 24, "first_k_dense_replace": 1,
    "q_lora_rank": 16, "kv_lora_rank": 24, "qk_nope_head_dim": 8,
    "qk_rope_head_dim": 4, "v_head_dim": 8, "rope_theta": 8e7,
    "index_n_heads": 3, "index_head_dim": 8, "index_topk": TOPK,
    "attention_gate_type": "headwise", "swa_attention_gate_type": "headwise",
    "sliding_window_size": WINDOW, "swa_num_attention_heads": 2,
    "swa_q_lora_rank": 16, "swa_kv_lora_rank": 40,
    "swa_qk_nope_head_dim": 12, "swa_qk_rope_head_dim": 4,
    "swa_v_head_dim": 8, "swa_rope_theta": 5e4,
    "n_routed_experts_published": 8, "num_experts_per_tok": 3,
    "norm_topk_prob": True, "routed_scaling_factor": 1,
    "rms_norm_eps": 1e-5,
}
FIELDS = {"vocab_size": "vocab_size", "hidden_size": "d_model",
          "num_hidden_layers": "n_layers", "layer_types": "layer_types",
          "num_attention_heads": "n_heads", "intermediate_size": "d_ff",
          "moe_intermediate_size": "moe_d_ff",
          "first_k_dense_replace": "moe_dense_layers",
          "q_lora_rank": "mla_q_rank", "kv_lora_rank": "mla_kv_rank",
          "qk_nope_head_dim": "mla_nope_dim",
          "qk_rope_head_dim": "mla_rope_dim", "v_head_dim": "mla_v_dim",
          "rope_theta": "rope_theta", "index_n_heads": "index_heads",
          "index_head_dim": "index_dim", "index_topk": "index_topk",
          "sliding_window_size": "window",
          "swa_num_attention_heads": "window_heads",
          "swa_q_lora_rank": "window_q_rank",
          "swa_kv_lora_rank": "window_kv_rank",
          "swa_qk_nope_head_dim": "window_nope_dim",
          "swa_qk_rope_head_dim": "window_rope_dim",
          "swa_v_head_dim": "window_v_dim",
          "swa_rope_theta": "window_rope_theta",
          "n_routed_experts_published": "moe_experts",
          "num_experts_per_tok": "moe_top_k",
          "norm_topk_prob": "moe_normalize",
          "routed_scaling_factor": "moe_scale", "rms_norm_eps": "norm_eps"}
# The contracted axes of each matmul weight: its fan-in keeps activations
# O(1).
CONTRACTED = {"attn/wq_a": (0,), "attn/wq_b": (0,), "attn/wkv_a": (0,),
              "attn/wk_b": (2,), "attn/wv_b": (0,), "attn/wo": (0, 1),
              "attn/wg": (0,), "attn/wq_idx": (0,), "attn/wk_idx": (0,),
              "attn/w_idx": (0,), "mlp/wi": (1,), "mlp/wo": (0,),
              "shared/wi": (1,), "shared/wo": (0,), "moe/router": (0,),
              "moe/wi": (1,), "moe/wo": (1,), "w_out": (0,)}


def _config(published=PUBLISHED, **kw):
    from kubeflow_tpu.serving.loaders import _model_config

    fields = {FIELDS[k]: v for k, v in published.items() if k in FIELDS}
    return _model_config({
        **fields, "n_kv_heads": fields["n_heads"],
        "attention_kind": "latent", "moe_score": "sigmoid",
        "attn_gate": True, "moe_shared_d_ff": fields["moe_d_ff"],
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
        if name.endswith("bias"):
            return jnp.asarray(rng.normal(0, 0.03, leaf.shape), jnp.float32)
        short = "/".join(name.split("/")[-2:])
        fan_in = int(np.prod([leaf.shape[a] for a in CONTRACTED.get(
            short, CONTRACTED.get(name, ()))]))
        return jnp.asarray(rng.normal(0, fan_in ** -0.5, leaf.shape),
                           jnp.float32)

    return jax.tree_util.tree_map_with_path(fill, shapes)


@functools.lru_cache(maxsize=None)
def _jitted_reference(published, share):
    """The reference as ONE program a length (op by op it compiles for
    seconds a length; the arithmetic is the same)."""
    import jax

    return jax.jit(functools.partial(
        reference_dots3.forward, json.loads(published), **dict(share)))


def _reference(params, tokens, published=PUBLISHED, **share):
    return np.asarray(_jitted_reference(
        json.dumps(published), tuple(sorted(share.items())))(
            params, np.asarray(tokens)))


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(1, VOCAB, n, dtype=np.int32)


@pytest.fixture(scope="module")
def dots3():
    cfg = _config()
    return cfg, _params(cfg)


@pytest.fixture(scope="module")
def dots3_wide_keys():
    """Index keys of 128, which the walk kernel can copy (the 8 of
    ``dots3`` cannot, and keep the key tiles under ``paged_kernel``)."""
    cfg = _config(dict(PUBLISHED, index_head_dim=128))
    return cfg, _params(cfg)


def _served(cfg, params, new=4, published=PUBLISHED, **share):
    def reference(params, tokens, _):
        return _reference(params, tokens, published, **share)

    return Served(cfg, params, new, reference=reference)


def _serve_one(cfg, params, prompt_len, new=4, ref_params=None,
               published=PUBLISHED, **share):
    run = _served(cfg, params, new, published, **share)
    prompt = _tokens(prompt_len, seed=3)
    run.prefill(1, prompt, new)
    assert run.rounds(new - 1) == new - 1
    assert len(run.served[1]) == new
    return run.worst(1, prompt, run.next_logits(), params=ref_params)


# -- the tree and the state ---------------------------------------------------

def test_tree_and_the_three_pools_on_one_table(dots3):
    import jax

    from kubeflow_tpu.models.generate import init_paged_state, pool_sides

    cfg, params = dots3
    names = {"/".join(str(p.key) for p in path): leaf.shape for path, leaf
             in jax.tree_util.tree_leaves_with_path(params)}
    full, window = "layers/1/attn/", "layers/2/attn/"
    assert names[full + "wq_b"] == (16, 4, 12)
    assert names[full + "wkv_a"] == (32, 28)
    assert names[full + "wg"] == (32, 4)
    assert names[full + "wq_idx"] == (16, 3, 8)
    assert names[full + "wk_idx"] == (32, 8)
    assert names[full + "k_idx_norm/bias"] == (8,)
    assert names[full + "w_idx"] == (32, 3)
    assert names[window + "wq_b"] == (16, 2, 16)
    assert names[window + "wkv_a"] == (32, 44)
    assert names[window + "wk_b"] == (2, 12, 40)
    assert names[window + "wg"] == (32, 2)
    assert window + "wq_idx" not in names
    assert names["layers/0/mlp/wi"] == (2, 32, 64) \
        and "layers/0/moe/wi" not in names
    assert names["layers/1/moe/wi"] == (8, 32, 48)
    assert names["layers/1/moe/shared/wi"] == (2, 32, 24)
    assert names["layers/3/moe/shared/wo"] == (24, 32)
    assert (cfg.kv_planes, cfg.window_planes, cfg.latent_row,
            cfg.window_row) == (4, 2, 256, 256)
    state = init_paged_state(cfg, SLOTS, SLOTS * TABLE, BLOCK)
    assert pool_sides(state) == ("cache_latent", "cache_index",
                                 "cache_window")
    blocks = SLOTS * TABLE
    assert state["cache_latent"].shape == (2, blocks, BLOCK, 256)
    assert state["cache_index"].shape == (2, blocks, BLOCK, 8)
    assert state["cache_window"].shape == (2, blocks, BLOCK, 256)


@pytest.mark.parametrize("bad", [
    {"index_heads": 0},                     # an indexer without heads
    {"index_dim": 2},                       # narrower than the rotary part
    {"attention_kind": "gqa"},              # a gate, an indexer: latent only
    {"window": 0},                          # a sliding layer without sizes
    {"window_rope_dim": 3},
    {"layer_types": ["shortcut_double"] * 4, "moe_dense_layers": 0},
])
def test_config_refuses_what_is_not_built(bad):
    with pytest.raises(ValueError):
        _config(**bad)


# -- the forward without a cache ----------------------------------------------

@pytest.mark.parametrize("n", [1, 2, 9, 10, 24, 25, 70])
def test_flax_forward_matches_the_reference(dots3, n):
    import jax

    from kubeflow_tpu.models.transformer import Transformer

    cfg, params = dots3
    tokens = _tokens(n, seed=1)
    got = np.asarray(jax.jit(Transformer(cfg).apply)(
        {"params": params}, tokens[None]))[0]
    want = _reference(params, tokens)
    assert n < 70 or np.ptp(want) > 1.0   # the logits are worth comparing
    assert np.abs(got - want).max() < TOL


# -- the serving path ---------------------------------------------------------

# A final chunk of 1, 2, 63 and 64 real tokens, over 1, 2 and 3 chunks;
# every context from 25 on is past the tiny index_topk, from 10 on past the
# tiny window.
@pytest.mark.parametrize("prompt_len", [1, 2, 8, 23, 24, 63, 64, 65, 127,
                                        128, 129, 192])
def test_chunked_prefill_then_decode_rounds_matches_the_reference(
        dots3, prompt_len):
    cfg, params = dots3
    assert _serve_one(cfg, params, prompt_len) < TOL


def test_a_share_of_the_experts_is_served_as_the_reference_cuts_it(dots3):
    """Experts [4, 6) of 8 held: program and reference leave out what the
    other six would add, and keep the shared expert."""
    cfg, params = dots3
    share = dataclasses.replace(cfg, moe_experts_held=2,
                                moe_experts_offset=4)
    cut = _spoil(params, lambda name, leaf: leaf[4:6] if name.endswith(
        ("moe/wi", "moe/wo")) else leaf)
    assert _serve_one(share, cut, 70, experts_held=2,
                      experts_offset=4) < TOL
    # Against the UNCUT reference the share is far off: the cut is real.
    assert _serve_one(share, cut, 70) > 100 * TOL


def _spoil(params, change):
    """``params`` with ``change(path, leaf)`` applied to every leaf."""
    import jax

    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: change("/".join(str(p.key) for p in path), leaf),
        params)


def test_the_shares_add_up(dots3):
    """The 8 shares' routed parts plus the shared expert ONCE equal the
    uncut reference's expert layer: the chip that owns the token adds the
    shared expert, the others hold none."""
    import jax.numpy as jnp

    from kubeflow_tpu.models import generate

    cfg, params = dots3
    layer = params["layers"]["2"]
    x = jnp.asarray(np.random.default_rng(5).normal(0, 1, (1, 19, 32)),
                    jnp.float32)
    total = 0
    for k in range(8):
        share = dataclasses.replace(
            cfg, moe_experts_held=1, moe_experts_offset=k,
            moe_shared_d_ff=cfg.moe_shared_d_ff if k == 0 else 0)
        moe = dict(layer["moe"], wi=layer["moe"]["wi"][k:k + 1],
                   wo=layer["moe"]["wo"][k:k + 1])
        y, counts = generate._sparse_ff(share, dict(layer, moe=moe), x)
        total = total + (y - x)
        assert int(counts["held"] + counts["absent"]) == 19 * 3
    import jax

    with jax.default_matmul_precision("highest"):
        m = reference_dots3.rms_norm(x[0], layer["mlp_norm"]["scale"], 1e-5)
        want = reference_dots3.experts(PUBLISHED, m, layer["moe"])
        once = reference_dots3.experts(PUBLISHED, m, layer["moe"],
                                       shared_part=False)
    assert np.abs(np.asarray(total[0] - want)).max() < TOL
    # The shared expert counted in every share would be off by 7 of them.
    assert np.abs(np.asarray(want - once)).max() > 100 * TOL


# -- sabotages ----------------------------------------------------------------

@pytest.mark.parametrize("published, why", [
    (dict(PUBLISHED, attention_gate_type="none",
          swa_attention_gate_type="none"), "the gate left out"),
    (dict(PUBLISHED, attention_gate_type="none"), "the full layers' gate"),
    (dict(PUBLISHED, sliding_window_size=WINDOW + 1),
     "the window one position off"),
    (dict(PUBLISHED, sliding_window_size=WINDOW - 1),
     "the window one position short"),
    (dict(PUBLISHED, index_topk=TOPK - 1), "one position fewer chosen"),
    (dict(PUBLISHED, index_topk=0), "no indexer: every position attended"),
    (dict(PUBLISHED, apply_mla_qkv_lora_rescale=False),
     "the rescale left out"),
    (dict(PUBLISHED, norm_topk_prob=False), "the weights not normalised"),
    (dict(PUBLISHED, rope_theta=5e4), "the window's rotary base everywhere"),
])
def test_a_reference_that_states_something_else_disagrees(dots3, published,
                                                          why):
    cfg, params = dots3
    assert _serve_one(cfg, params, 70, published=published) > 50 * TOL, why


def _without_relu(products, weights):
    import jax.numpy as jnp

    return jnp.einsum("ths,th->ts", products, weights)


def _without_weights(products, weights):
    return reference_dots3.jax.nn.relu(products).sum(1)


@pytest.mark.parametrize("name, change, why", [
    ("index_combine", _without_relu, "the indexer's relu left out"),
    ("index_combine", _without_weights, "the indexer's weights left out"),
    ("gate_values", lambda g, bias: g + bias, "the bias put into the weights"),
])
def test_a_reference_with_another_indexer_or_router_disagrees(
        dots3, monkeypatch, name, change, why):
    cfg, params = dots3
    run = _served(cfg, params)
    prompt = _tokens(70, seed=3)
    run.prefill(1, prompt)
    run.rounds(3)
    monkeypatch.setattr(reference_dots3, name, change)
    want = np.asarray(reference_dots3.forward(
        PUBLISHED, params, np.concatenate([prompt, run.served[1]])))
    assert np.abs(run.next_logits()[1] - want[-1]).max() > 50 * TOL, why


def test_the_shared_expert_left_out_disagrees(dots3):
    cfg, params = dots3
    assert _serve_one(cfg, params, 70, shared_part=False) > 50 * TOL


def test_the_bias_left_out_of_the_choice_disagrees(dots3):
    cfg, params = dots3
    spoiled = _spoil(params, lambda name, leaf: 0 * leaf
                     if name.endswith("moe/bias") else leaf)
    assert _serve_one(cfg, spoiled, 70, ref_params=params) > 50 * TOL


def test_the_other_rotary_pairing_fails(dots3, monkeypatch):
    from kubeflow_tpu.models import generate, transformer

    cfg, params = dots3
    monkeypatch.setattr(generate, "_rope_pairs", transformer.rope)
    assert _serve_one(dataclasses.replace(cfg, max_seq_len=255), params,
                      66) > 50 * TOL


def test_a_bfloat16_program_fails_the_tolerance(dots3):
    import jax.numpy as jnp

    cfg, params = dots3
    low = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    assert _serve_one(low, params, 66) > 10 * TOL


# -- slots beside each other --------------------------------------------------

def test_a_slot_reused_after_another_request_reads_none_of_it(dots3):
    cfg, params = dots3
    run = _served(cfg, params)
    first, second = _tokens(70, seed=4), _tokens(5, seed=5)
    run.prefill(1, first)
    run.rounds(3)
    run.prefill(1, second)     # the same slot and pages, a shorter prompt
    run.rounds(3)
    assert run.worst(1, second, run.next_logits()) < TOL


def test_slots_of_three_lengths_decode_beside_each_other(dots3):
    """One round over a slot under the window and the choice (5), one past
    the window (20) and one past both (150), and a slot in mid-prefill."""
    cfg, params = dots3
    run = _served(cfg, params, new=6)
    prompts = {0: _tokens(5, seed=6), 1: _tokens(150, seed=7)}
    for slot, prompt in prompts.items():
        run.prefill(slot, prompt, new=6)
    late = _tokens(100, seed=8)
    run.chunk(2, late, 0, new=6)
    assert run.rounds(2) == 2
    run.chunk(2, late, CHUNK, new=6)
    assert run.rounds(3) == 3
    last = run.next_logits()
    for slot, prompt in {**prompts, 2: late}.items():
        assert run.worst(slot, prompt, last) < TOL


def test_a_window_planes_step_reads_no_page_below_its_window(dots3):
    """Pages of the window pool that lie wholly below a slot's window are
    never gathered: filled with NaN they change nothing (a weight of zero
    times NaN would).  The cost of a window plane follows the window."""
    import jax.numpy as jnp

    cfg, params = dots3
    run = _served(cfg, params)
    prompt = _tokens(150, seed=9)
    run.prefill(1, prompt)
    run.rounds(2)
    before = run.next_logits()[1]
    # Positions [0, 150 + 2 - 9] are below the window of the next query
    # (position 152): pages 0..7 hold positions 0..127.
    below = jnp.asarray(run.tables[1][:8])
    run.state = dict(run.state, cache_window=run.state[
        "cache_window"].at[:, below].set(jnp.nan))
    after = run.next_logits()[1]
    assert np.isfinite(after).all()
    assert np.abs(after - before).max() == 0.0


def test_a_full_planes_step_reads_the_chosen_rows_and_no_others(dots3):
    """A slot of 152 positions attends 24 of them a full plane: every
    latent row the indexer did not choose may hold NaN."""
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.models import generate

    cfg, params = dots3
    run = _served(cfg, params)
    prompt = _tokens(150, seed=9)
    run.prefill(1, prompt)
    run.rounds(2)
    before = run.next_logits()[1]
    chosen = []
    real = generate._choose

    def spy(scores, q_pos, topk):
        out = real(scores, q_pos, topk)
        chosen.append(np.asarray(out[0])[1, 0])
        return out

    generate._choose = spy
    try:
        with jax.disable_jit():
            run.next_logits()
    finally:
        generate._choose = real
    assert len(chosen) == 2 and all(len(set(c)) == TOPK for c in chosen)
    pool = run.state["cache_latent"]
    for plane, picks in enumerate(chosen):
        spare = np.setdiff1d(np.arange(153), picks)
        pool = pool.at[plane, run.tables[1][spare // BLOCK],
                       spare % BLOCK].set(jnp.nan)
    run.state = dict(run.state, cache_latent=pool)
    after = run.next_logits()[1]
    assert np.isfinite(after).all()
    assert np.abs(after - before).max() < TOL


# -- the kernel's window form -------------------------------------------------

@pytest.mark.parametrize("pages_per_block", [1, 2, 8])
def test_the_kernels_window_form_is_plain_attention_over_the_window(
        pages_per_block):
    """``paged_latent_decode_attention(window=9)`` in interpret mode over
    slots under the window, past it, far past it and retired, against plain
    softmax attention over the last 9 positions; pages below a slot's
    window hold NaN, so a copy of one would show."""
    import jax.numpy as jnp

    from kubeflow_tpu.ops import paged_attention

    rng = np.random.default_rng(21)
    slots, h, row, latent, bt, mb = 5, 4, 256, 128, 4, 12
    lengths = np.array([3, 9, 10, 41, 0], np.int32)
    pool = rng.normal(0, 1, (2, slots * mb, bt, row)).astype(np.float32)
    tables = rng.permutation(slots * mb).reshape(slots, mb).astype(np.int32)
    q = rng.normal(0, 1, (slots, h, row)).astype(np.float32)
    want = np.zeros((slots, h, latent), np.float32)
    for s, n in enumerate(lengths):
        if n == 0:
            continue
        rows = pool[1, tables[s]].reshape(mb * bt, row)[:n]
        seen = rows[max(n - WINDOW, 0):]
        sc = q[s] @ seen.T * 0.3
        w = np.exp(sc - sc.max(-1, keepdims=True))
        want[s] = (w / w.sum(-1, keepdims=True)) @ seen[:, :latent]
        first_page = max(n - WINDOW, 0) // bt
        pool[1, tables[s][:first_page]] = np.nan
    got = paged_attention.paged_latent_decode_attention(
        jnp.asarray(q), jnp.asarray(pool), jnp.int32(1), jnp.asarray(tables),
        jnp.asarray(lengths), latent, 0.3, window=WINDOW,
        pages_per_block=pages_per_block, interpret=True)
    assert np.abs(np.asarray(got) - want).max() < 2e-5


def test_decode_rounds_through_the_kernel_matches_the_reference(
        dots3, monkeypatch):
    """``decode_rounds`` with ``paged_kernel=True`` (what the engine passes
    when its pools live on a TPU), the kernel in interpret mode: the window
    planes go through its window form, the full planes through the gather
    of the chosen rows."""
    from kubeflow_tpu.ops import paged_attention

    cfg, params = dots3
    calls = []
    real = paged_attention.paged_latent_decode_attention

    def interpreted(*args, **kw):
        calls.append(kw.get("window"))
        return real(*args, interpret=True, **kw)

    monkeypatch.setattr(paged_attention, "paged_latent_decode_attention",
                        interpreted)
    run = _served(cfg, params, new=5)
    prompt = _tokens(70, seed=14)
    run.prefill(1, prompt, new=5)
    run.state, toks, counts, ran = run.g.decode_rounds(
        cfg, params, run.state, run.decode, 4, run.tables, np.int32(4),
        paged_kernel=True)
    assert int(ran) == 4 and calls == [WINDOW] * cfg.window_planes
    run.served[1] += [int(t) for t in toks[1, :int(counts[1])]]
    assert run.worst(1, prompt, run.next_logits()) < TOL


def test_decode_rounds_scores_index_keys_through_the_walk_kernel(
        dots3_wide_keys, monkeypatch):
    """``decode_rounds`` with the kernels interpreted against the tile
    form, float32 on both sides.  Two slots of unequal lengths and a
    third retired; the same positions chosen a step and full plane, the
    same tokens."""
    import jax

    from kubeflow_tpu.models import generate
    from kubeflow_tpu.ops import paged_attention

    cfg, params = dots3_wide_keys
    calls = collections.Counter()
    for name in ("paged_latent_decode_attention", "paged_index_scores"):
        def interpreted(*args, _real=getattr(paged_attention, name),
                        _name=name, **kw):
            calls[_name] += 1
            return _real(*args, interpret=True, **kw)

        monkeypatch.setattr(paged_attention, name, interpreted)
    choose, seen = generate._choose, []

    def recorded(scores, q_pos, topk):
        chosen, real = choose(scores, q_pos, topk)
        jax.debug.callback(
            lambda *a: seen.append([np.asarray(x) for x in a]), scores,
            chosen, real, ordered=True)
        return chosen, real

    monkeypatch.setattr(generate, "_choose", recorded)
    prompts = {0: _tokens(70, seed=14), 2: _tokens(33, seed=15)}
    served = {}
    for kernel in (False, True):
        run = _served(cfg, params, new=5)
        for slot, prompt in prompts.items():
            run.prefill(slot, prompt, new=5)
        del seen[:]                      # the chunks' choices
        run.state, toks, counts, ran = run.g.decode_rounds(
            cfg, params, run.state, run.decode, 4, run.tables, np.int32(4),
            paged_kernel=kernel)
        jax.effects_barrier()
        assert int(ran) == 4 and len(seen) == 4 * 2
        served[kernel] = (np.asarray(toks), np.asarray(counts), list(seen))
    assert calls == {"paged_index_scores": 2,
                     "paged_latent_decode_attention": cfg.window_planes}
    (toks, counts, tiles), (k_toks, k_counts, walked) = \
        served[False], served[True]
    assert counts.tolist() == [4, 0, 4]
    np.testing.assert_array_equal(k_counts, counts)
    np.testing.assert_array_equal(k_toks, toks)
    live = [0, 2]
    for (sc, chosen, real), (k_sc, k_chosen, k_real) in zip(tiles, walked):
        # The table's own length, not whole key tiles of it.
        assert k_sc.shape[-1] == TABLE * BLOCK <= sc.shape[-1]
        assert np.isneginf(k_sc[1]).all()
        np.testing.assert_allclose(k_sc[live], sc[live, :, :TABLE * BLOCK],
                                   atol=1e-5)
        np.testing.assert_array_equal(k_real[live], real[live])
        assert real[live].sum() == 2 * TOPK
        np.testing.assert_array_equal(k_chosen[live][k_real[live]],
                                      chosen[live][real[live]])


# -- ops/grouped_matmul.py in the grouped products' place ----------------------

@pytest.mark.parametrize("live", sorted(test_lfm2.LIVE))
def test_the_grouped_kernel_is_the_expert_layers_ragged_dot(
        dots3, interpreted_grouped_kernel, live):
    cfg, params = dots3
    layer = next(i for i in range(cfg.n_layers) if cfg.layer_is_sparse(i))
    test_lfm2.kernel_against_ragged_dot(
        cfg, params["layers"][str(layer)]["moe"],
        interpreted_grouped_kernel, test_lfm2.LIVE[live])


def test_both_programs_serve_the_same_through_the_grouped_kernel(
        dots3, interpreted_grouped_kernel):
    cfg, params = dots3
    test_lfm2.kernel_serves_what_ragged_dot_serves(
        lambda: _served(cfg, params, new=6), _tokens(70, seed=21), 6)
    sparse = sum(cfg.layer_is_sparse(i) for i in range(cfg.n_layers))
    assert len(interpreted_grouped_kernel) == 2 * 2 * sparse


# -- the engine ---------------------------------------------------------------

def _engine(cfg, params, **kw):
    from kubeflow_tpu.models.generate import DecodeConfig
    from kubeflow_tpu.serving.engine import DecodeEngine

    return DecodeEngine(
        cfg, params, DecodeConfig(max_new_tokens=8, temperature=0.0),
        slots=3, prefill_len=160, max_len=176, prefill_chunk_tokens=64,
        name="dots3-test", **kw)


@pytest.mark.parametrize("flag", ["host_spill_blocks", "adapters", "mesh"])
def test_engine_refuses_at_construction_by_name(dots3, flag):
    cfg, params = dots3
    with pytest.raises(ValueError, match=flag):
        _engine(cfg, params, **{flag: 4})


def test_engine_reuses_a_prefix_on_three_pools_and_says_what_it_read(dots3):
    """Two prompts that share 70 tokens: the second aliases the first's
    four whole pages (64 tokens) in all three pools through the ONE table,
    resumes in the middle of a chunk and of the fifth page, and both are
    served as the reference computes them."""
    cfg, params = dots3
    share = dataclasses.replace(cfg, moe_experts_held=4)
    cut = _spoil(params, lambda name, leaf: leaf[:4] if name.endswith(
        ("moe/wi", "moe/wo")) else leaf)
    engine = _engine(share, cut)
    try:
        document = _tokens(70, seed=11)
        prompts = [np.concatenate([document, _tokens(n, seed=20 + n)])
                   for n in (5, 9)]
        outs = [engine.submit({"tokens": p, "max_new_tokens": 5})
                for p in prompts]
        stats = engine.stats()
    finally:
        engine.close(drain_s=0.0)
    for prompt, out in zip(prompts, outs):
        tokens = np.asarray(out["tokens"])[0]
        assert tokens.shape == (len(prompt) + 5,)
        want = _reference(cut, tokens, experts_held=4)
        rows = want[len(prompt) - 1:len(prompt) + 4]
        assert (rows.max(-1) - rows[np.arange(5), tokens[len(prompt):]]
                ).max() < TOL
    assert stats["prefix_reuse"] == "on"
    assert stats["prefix_hits"] == 1 and stats["cached_prompt_tokens"] == 64
    assert stats["kv_planes"] == 4
    # float32 rows: 2 planes of 256 + 8 values, 2 window planes of 256.
    assert stats["kv_bytes_per_token"] == (2 * (256 + 8) + 2 * 256) * 4
    # Each request: 4 decode steps after the prompt's own first token, at
    # lengths 75..78 and 79..82, in 2 full and 2 window planes.
    held = [np.arange(len(p) + 1, len(p) + 5) for p in prompts]
    assert stats["index_scored"] == 2 * sum(h.sum() for h in held)
    assert stats["index_chosen"] == 2 * TOPK * 8
    assert stats["window_read"] == 2 * WINDOW * 8
    assert stats["pairs_held"] > 0 and stats["pairs_absent"] > 0


@pytest.mark.parametrize("form", ["walk", "tiles"])
def test_engine_counts_the_index_keys_its_program_reads(
        dots3_wide_keys, monkeypatch, form):
    """``index_read``: with the walk kernel (an engine told its pools live
    on a TPU, index keys 128 wide; the kernels interpreted) a step's l
    rounded up to whole pages; with the key tiles, what
    ``index_positions_scored`` says of the round's longest slot, for
    every row of the call.  ``index_scored`` is the l keys a step needs
    either way."""
    from kubeflow_tpu.models import generate
    from kubeflow_tpu.ops import paged_attention
    from kubeflow_tpu.serving import engine as engine_mod

    cfg, params = dots3_wide_keys
    if form == "walk":
        monkeypatch.setattr(engine_mod, "_plain_pool_platform",
                            lambda pool: "tpu")
        for name in ("paged_latent_decode_attention", "paged_index_scores"):
            monkeypatch.setattr(paged_attention, name, functools.partial(
                getattr(paged_attention, name), interpret=True))
    engine = _engine(cfg, params)
    try:
        prompts = [_tokens(n, seed=30 + n) for n in (75, 33)]
        for p in prompts:
            engine.submit({"tokens": p, "max_new_tokens": 5})
        stats = engine.stats()
    finally:
        engine.close(drain_s=0.0)
    # Each request: 4 decode steps after the prompt's own first token, a
    # round a request, in 2 full planes; tables of 11 pages of 16.
    held = [np.arange(len(p) + 1, len(p) + 5) for p in prompts]
    assert stats["steps"] == 8
    assert stats["index_scored"] == 2 * sum(h.sum() for h in held)
    assert stats["index_chosen"] == 2 * TOPK * 8
    if form == "walk":
        assert stats["decode_kernel_steps"] == 8
        assert stats["index_read"] == 2 * sum(
            (-(-h // 16) * 16).sum() for h in held) == 2 * (4 * 80 + 4 * 48)
    else:
        assert stats["decode_kernel_steps"] == 0
        # One key tile holds the whole table: 176 keys for each of the
        # call's 3 rows, whatever the one live slot holds.
        assert generate.index_positions_scored(11, 16, 3 * 3, 80) == 176
        assert stats["index_read"] == 2 * 3 * 176 * 8
    # A round of 3 steps over two unequal slots, one stopping after its
    # first step, with key tiles of 2 pages: from the loop's own lengths.
    monkeypatch.setattr(generate, "_INDEX_KEY_TILE", 32)
    reads = engine._sparse_reads([(40, 3), (75, 1)], 3)
    assert reads["index_scored"] == 2 * (40 + 41 + 42 + 75)
    assert reads["index_read"] == (
        2 * (3 * 48 + 80) if form == "walk"
        # Tiles up to the longest slot (75, then 76 where it stopped).
        else 2 * 3 * (96 + 96 + 96))


def test_engine_stats_of_another_stack_count_no_sparse_reads():
    lfm2_cfg = test_lfm2._config()
    engine = test_lfm2._engine(lfm2_cfg, test_lfm2._params(lfm2_cfg))
    try:
        engine.submit({"tokens": _tokens(20, seed=2), "max_new_tokens": 3})
        stats = engine.stats()
    finally:
        engine.close(drain_s=0.0)
    assert (stats["index_scored"], stats["index_chosen"],
            stats["window_read"]) == (0, 0, 0)
