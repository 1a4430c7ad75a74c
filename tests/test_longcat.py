"""LongCat-Flash's language model (latent attention over ONE paged pool of
latent rows, double layers with a shortcut-connected expert layer,
softmax-routed experts of which a chip holds a share beside zero-compute
experts) against the plain reference ``tests/reference_longcat.py``: the
flax forward (which EXPANDS keys and values from the latent), and the
serving path (prefill in chunks of 64, then ``decode_rounds``, whose
attention ABSORBS the expansion into the query and reads the latent rows of
the pool), down to the engine with prefix reuse on.  Logits are compared, never tokens.

Tolerances.  Program and reference both compute in float32 on the CPU, in
another order of operations (a cache of latent rows, the absorbed form,
per-row scatters, rows sorted by expert and multiplied by groups where the
reference loops over a dense mask): their logits differ by 2e-6 to 1e-5 at
a logit spread over 1.  ``TOL`` = 2e-4 leaves that an order of room and is
two orders under what the same program in bfloat16 reads (3e-2 and more),
so a bfloat16 program fails it; so does every sabotage below (the bias
left out of the choice or put into the weights, the weights normalised,
the scale left out, a zero-compute expert that returns nothing, the two
low-rank scale factors, the other rotary pairing: 1e-2 to 1).  Weights are
seeded normals at 1/sqrt(fan-in), norm scales are drawn from 1 +- 0.3, and
the router's bias at 0.03, about the distance between the largest softmax
scores of 12 outputs, so that it changes about half of the choices.
"""

import dataclasses
import functools

import numpy as np
import pytest

import reference_longcat

test_lfm2 = pytest.importorskip("test_lfm2")
Served, SLOTS, BLOCK, TABLE, CHUNK = (
    test_lfm2.Served, test_lfm2.SLOTS, test_lfm2.BLOCK, test_lfm2.TABLE,
    test_lfm2.CHUNK)

TOL = 2e-4
VOCAB, SEED = 96, 20260930
# Hugging Face keys, as the reference reads them.
PUBLISHED = {
    "vocab_size": VOCAB, "hidden_size": 32, "num_layers": 2,
    "num_attention_heads": 4, "ffn_hidden_size": 64,
    "expert_ffn_hidden_size": 24, "q_lora_rank": 16, "kv_lora_rank": 24,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "v_head_dim": 8,
    "n_routed_experts_published": 8, "zero_expert_num": 4, "moe_topk": 3,
    "routed_scaling_factor": 6, "rms_norm_eps": 1e-5, "rope_theta": 1e7,
    "mla_scale_q_lora": True, "mla_scale_kv_lora": True,
}
FIELDS = {"vocab_size": "vocab_size", "hidden_size": "d_model",
          "num_layers": "n_layers", "num_attention_heads": "n_heads",
          "ffn_hidden_size": "d_ff", "expert_ffn_hidden_size": "moe_d_ff",
          "q_lora_rank": "mla_q_rank", "kv_lora_rank": "mla_kv_rank",
          "qk_nope_head_dim": "mla_nope_dim",
          "qk_rope_head_dim": "mla_rope_dim", "v_head_dim": "mla_v_dim",
          "n_routed_experts_published": "moe_experts",
          "zero_expert_num": "moe_zero_experts", "moe_topk": "moe_top_k",
          "routed_scaling_factor": "moe_scale", "rms_norm_eps": "norm_eps",
          "rope_theta": "rope_theta"}
# The contracted axes of each matmul weight: its fan-in keeps activations
# O(1).
CONTRACTED = {"attn/wq_a": (0,), "attn/wq_b": (0,), "attn/wkv_a": (0,),
              "attn/wk_b": (2,), "attn/wv_b": (0,), "attn/wo": (0, 1),
              "mlp/wi": (1,), "mlp/wo": (0,), "moe/router": (0,),
              "moe/wi": (1,), "moe/wo": (1,), "w_out": (0,)}


def _config(published=PUBLISHED, **kw):
    from kubeflow_tpu.serving.loaders import _model_config

    fields = {FIELDS[k]: v for k, v in published.items() if k in FIELDS}
    return _model_config({
        **fields, "n_kv_heads": fields["n_heads"],
        "layer_types": ["shortcut_double"] * fields["n_layers"],
        "attention_kind": "latent", "moe_score": "softmax",
        "moe_normalize": False, "max_seq_len": 256,
        "tied_embeddings": False, "dtype": "float32", **kw})


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
            return jnp.asarray(rng.normal(0, 0.03, leaf.shape), jnp.float32)
        short = "/".join(name.split("/")[-2:])
        fan_in = int(np.prod([leaf.shape[a] for a in CONTRACTED.get(
            short, CONTRACTED.get(name, ()))]))
        return jnp.asarray(rng.normal(0, fan_in ** -0.5, leaf.shape),
                           jnp.float32)

    return jax.tree_util.tree_map_with_path(fill, shapes)


def _reference(params, tokens, published=PUBLISHED, **share):
    return np.asarray(reference_longcat.forward(published, params, tokens,
                                                **share))


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(1, VOCAB, n, dtype=np.int32)


@pytest.fixture(scope="module")
def longcat():
    cfg = _config()
    return cfg, _params(cfg)


def _served(cfg, params, new=4, **share):
    def reference(params, tokens, published):
        return _reference(params, tokens, **share)

    return Served(cfg, params, new, reference=reference)


# -- the tree and the state ---------------------------------------------------

def test_tree_has_two_halves_a_layer_and_the_state_one_latent_pool(longcat):
    import jax

    from kubeflow_tpu.models.generate import init_paged_state, pool_sides

    cfg, params = longcat
    names = {"/".join(str(p.key) for p in path): leaf.shape for path, leaf
             in jax.tree_util.tree_leaves_with_path(params)}
    for half in ("half_0", "half_1"):
        at = f"layers/1/{half}/"
        assert names[at + "attn/wq_a"] == (32, 16)
        assert names[at + "attn/q_norm/scale"] == (16,)
        assert names[at + "attn/wq_b"] == (16, 4, 12)
        assert names[at + "attn/wkv_a"] == (32, 28)
        assert names[at + "attn/kv_norm/scale"] == (24,)
        assert names[at + "attn/wk_b"] == (4, 8, 24)
        assert names[at + "attn/wv_b"] == (24, 4, 8)
        assert names[at + "attn/wo"] == (4, 8, 32)
        assert names[at + "mlp/wi"] == (2, 32, 64)
    assert names["layers/1/moe/router"] == (32, 12)     # 8 routed + 4 zero
    assert names["layers/1/moe/bias"] == (12,)
    assert names["layers/1/moe/wi"] == (8, 32, 48)
    assert names["w_out"] == (32, VOCAB)
    assert cfg.kv_planes == 4 and cfg.conv_planes == 0
    # 24 + 4 values a token, each part padded to a whole 128-lane row.
    assert cfg.latent_row == 256
    state = init_paged_state(cfg, SLOTS, SLOTS * TABLE, BLOCK)
    assert pool_sides(state) == ("cache_latent",)
    assert "cache_k" not in state and "cache_v" not in state
    assert state["cache_latent"].shape == (4, SLOTS * TABLE, BLOCK, 256)
    assert state["moe_touched"].shape == ()
    assert state["moe_pairs"].shape == (3,)
    # A share of the experts: the router keeps its width.
    share = dataclasses.replace(cfg, moe_experts_held=2,
                                moe_experts_offset=4)
    from kubeflow_tpu.models.transformer import layer_tree_shapes

    moe = layer_tree_shapes(share)["layers"]["0"]["moe"]
    assert moe == {"router": (32, 12), "bias": (12,), "wi": (2, 32, 48),
                   "wo": (2, 24, 32)}


@pytest.mark.parametrize("bad", [
    {"attention_kind": "window"},
    {"moe_score": "tanh"},
    {"attention_kind": "latent", "layer_types": ["full_attention"] * 2},
    {"attention_kind": "latent", "mla_q_rank": 8, "mla_kv_rank": 8,
     "mla_nope_dim": 8, "mla_rope_dim": 4, "mla_v_dim": 8},
    {"layer_types": ["shortcut_double"] * 2},                 # no experts
    {"layer_types": ["shortcut_double"] * 2, "moe_experts": 8,
     "moe_dense_layers": 1},
    {"layer_types": ["full_attention"] * 2, "moe_experts": 8,
     "moe_experts_held": 4, "moe_experts_offset": 6},
    {"moe_experts": 8, "moe_zero_experts": 2},        # no layer_types
])
def test_config_refuses_what_is_not_built(bad):
    from kubeflow_tpu.models.transformer import TransformerConfig

    with pytest.raises(ValueError):
        TransformerConfig(n_layers=2, **bad)


def test_the_older_stacks_keep_their_router_and_their_pool():
    from kubeflow_tpu.models.generate import init_paged_state, pool_sides
    from kubeflow_tpu.models.transformer import TransformerConfig

    cfg = TransformerConfig(n_layers=2, layer_types=["full_attention"] * 2,
                            moe_experts=8, moe_d_ff=16)
    assert (cfg.moe_score, cfg.moe_normalize, cfg.moe_scale,
            cfg.moe_zero_experts, cfg.moe_held, cfg.moe_partial,
            cfg.latent) == ("sigmoid", True, 1.0, 0, 8, False, False)
    state = init_paged_state(cfg, 2, 8, 4)
    assert pool_sides(state) == ("cache_k", "cache_v")
    assert "moe_pairs" not in state and "cache_latent" not in state


def test_what_is_not_built_for_a_latent_pool_is_refused_by_name(longcat):
    import jax.numpy as jnp

    from kubeflow_tpu.models import generate
    from kubeflow_tpu.serving import loaders

    cfg, params = longcat
    with pytest.raises(ValueError, match="int8 latent pool"):
        generate.init_paged_state(cfg, 2, 8, 4, kv_cache_dtype="int8")
    with pytest.raises(ValueError, match="layer_types"):
        generate.generate(cfg, params, jnp.ones((1, 4), jnp.int32))
    with pytest.raises(ValueError, match="quantize"):
        loaders.lm_generate({"model": dataclasses.asdict(cfg),
                             "quantize": "int8"})


# -- the forward without a cache ----------------------------------------------

@pytest.mark.parametrize("n", [1, 2, 3, 40])
def test_flax_forward_matches_the_reference(longcat, n):
    from kubeflow_tpu.models.transformer import Transformer

    cfg, params = longcat
    tokens = _tokens(n, seed=1)
    got = np.asarray(Transformer(cfg).apply({"params": params},
                                            tokens[None]))[0]
    want = _reference(params, tokens)
    assert n < 40 or np.ptp(want) > 1.0   # the logits are worth comparing
    assert np.abs(got - want).max() < TOL


# -- the serving path ---------------------------------------------------------

def _serve_one(cfg, params, prompt_len, new=4, ref_params=None,
               published=PUBLISHED, **share):
    run = _served(cfg, params, new, **share)
    prompt = _tokens(prompt_len, seed=3)
    run.prefill(1, prompt, new)
    assert run.rounds(new - 1) == new - 1
    assert len(run.served[1]) == new
    return run.worst(1, prompt, run.next_logits(), params=ref_params)


# A final chunk of 1, 2, 63 and 64 real tokens, over 1, 2 and 3 chunks.
@pytest.mark.parametrize("prompt_len", [1, 2, 63, 64, 65, 127, 128, 129,
                                        192])
def test_chunked_prefill_then_decode_rounds_matches_the_reference(
        longcat, prompt_len):
    cfg, params = longcat
    assert _serve_one(cfg, params, prompt_len) < TOL


def test_a_share_of_the_experts_is_served_as_the_reference_cuts_it(longcat):
    """Experts [4, 6) of 8 held: program and reference leave out what the
    other six would add, and keep the zero-compute experts' part."""
    import jax

    cfg, params = longcat
    share = dataclasses.replace(cfg, moe_experts_held=2,
                                moe_experts_offset=4)
    cut = jax.tree_util.tree_map(lambda a: a, params)
    for lp in cut["layers"].values():
        lp["moe"] = dict(lp["moe"], wi=lp["moe"]["wi"][4:6],
                         wo=lp["moe"]["wo"][4:6])
    assert _serve_one(share, cut, 70, experts_held=2,
                      experts_offset=4) < TOL
    # Against the UNCUT reference the share is far off: the cut is real.
    run = _served(share, cut)
    prompt = _tokens(70, seed=3)
    run.prefill(1, prompt)
    run.rounds(3)
    whole = _reference(params, np.concatenate([prompt, run.served[1]]))
    assert np.abs(run.next_logits()[1] - whole[-1]).max() > 100 * TOL


def test_the_absorbed_forms_agree_with_the_expanded_form(longcat):
    """The next position's logits of a slot with resident latent pages,
    once as a decode step (one query a row, per-row lengths), once as a
    chunk of two columns at the slot's frontier (both absorb the key
    expansion into the query and attend the latent rows of the slot's
    view), and once as the forward of the whole sequence without a cache,
    which expands keys and values from the latent."""
    import jax.numpy as jnp

    from kubeflow_tpu.models.transformer import Transformer

    cfg, params = longcat
    run = _served(cfg, params)
    prompt = _tokens(77, seed=12)
    run.prefill(2, prompt)
    run.rounds(2)
    step = run.next_logits()[2]
    s = run.state
    column = jnp.stack([s["last_token"][2], jnp.int32(5)])[None]
    chunk = run.g.forward_layer_types(
        cfg, params, column, (s["cache_latent"],), int(s["lengths"][2]),
        tables=jnp.asarray(run.tables[2][None]))[0]
    assert np.abs(step - np.asarray(chunk)[0, 0]).max() < TOL
    whole = np.concatenate([prompt, run.served[2]])
    expanded = Transformer(cfg).apply({"params": params}, whole[None])
    assert np.abs(step - np.asarray(expanded)[0, -1]).max() < TOL


def test_the_views_attention_in_tiles_is_the_untiled_one():
    """A chunk of 128 columns attends in two tiles of 64 query rows; every
    head scores the same rows, and the values are their first lanes."""
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.models.generate import _latent_view_attention

    rng = np.random.default_rng(15)
    q = jnp.asarray(rng.normal(0, 1, (1, 128, 4, 32)), jnp.float32)
    view = jnp.asarray(rng.normal(0, 1, (1, 224, 32)), jnp.float32)
    tiled = _latent_view_attention(q, view, 24, jnp.int32(40), 0.25)
    sc = jnp.einsum("bqhr,bkr->bhqk", q, view) * 0.25
    keep = jnp.arange(224)[None, :] <= 40 + jnp.arange(128)[:, None]
    want = jnp.einsum("bhqk,bkc->bqhc", jax.nn.softmax(
        jnp.where(keep, sc, -jnp.inf), -1), view[..., :24])
    assert tiled.shape == (1, 128, 4, 24)
    assert np.abs(np.asarray(tiled - want)).max() < 1e-5


@pytest.mark.parametrize("start", [0, 159 - 127, 160 - 127, 161 - 127,
                                   160, 2 * 160 - 128, 1280 - 128])
def test_the_views_attention_over_held_tiles_is_the_whole_views(start):
    """A chunk of 128 columns over a table of 1,280 positions visits key
    tiles of 160 and stops after the one that holds its last position (in
    the first tile, on both sides of an edge, in the table's last chunk):
    the result is ONE pass's over the whole gathered view, and the pages
    past the visited tiles, which hold NaN here, are never read."""
    import jax.numpy as jnp

    from kubeflow_tpu.models import generate

    view, t, h, row, lanes, plane = 1280, 128, 4, 32, 24, 1
    rng = np.random.default_rng(15 + start)
    q = jnp.asarray(rng.normal(0, 1, (1, t, h, row)), jnp.float32)
    pool = jnp.asarray(rng.normal(0, 1, (2, 90, 16, row)), jnp.float32)
    tables = jnp.asarray(rng.permutation(90)[None, :view // 16], jnp.int32)
    want = generate._latent_view_attention(
        q, pool[plane, tables].reshape(1, view, row), lanes,
        jnp.int32(start), 0.25)
    tile, visited, pages_of = generate._held_key_tiles(
        tables, 16, t, jnp.int32(start))
    scored = generate.view_positions_scored(view // 16, 16, t, start + t)
    assert (tile, int(visited) * tile) == (160, scored)
    assert start + t <= scored < start + t + 160
    spoiled = pool.at[:, tables[0, scored // 16:]].set(jnp.nan)
    got = generate._tiled_latent_view_attention(
        q, lambda i: spoiled[plane, pages_of(i)].reshape(1, tile, row),
        lanes, jnp.int32(start), 0.25, tile, visited, view)
    assert got.shape == want.shape == (1, t, h, lanes)
    assert np.abs(np.asarray(got - want)).max() < 1e-5


@pytest.mark.parametrize("start", [0, 160 - 128, 161 - 128, 700,
                                   1280 - 128])
def test_a_chunk_over_a_long_table_is_the_one_pass_chunk(longcat, start,
                                                         monkeypatch):
    """``forward_layer_types`` of a 128-column chunk at ``start`` against
    a slot whose table holds 1,280 positions (the latent block takes the
    key-tile loop) and the same call made to pass over the whole view
    (a key tile as long as the table): the same logits and the same
    rows written, on a pool that holds other rows everywhere."""
    import jax.numpy as jnp

    from kubeflow_tpu.models import generate

    cfg, params = longcat
    rng = np.random.default_rng(start)
    state = generate.init_paged_state(cfg, 2, 2 * 80, 16)
    pool = jnp.asarray(rng.normal(
        0, 1, state["cache_latent"].shape), jnp.float32)
    tables = jnp.asarray(rng.permutation(160)[None, :80], jnp.int32)
    chunk = jnp.asarray(_tokens(128, seed=start)[None])

    def forward():
        logits, cache, _, _ = generate.forward_layer_types(
            cfg, params, chunk, (pool,), jnp.int32(start), tables=tables)
        return np.asarray(logits), np.asarray(cache[0])

    assert generate.view_key_tiles(80, 16, 128) == (160, 8)
    tiled = forward()
    monkeypatch.setattr(generate, "_VIEW_KEY_TILE", 1280)
    assert generate.view_key_tiles(80, 16, 128) == (1280, 1)
    whole = forward()
    assert np.abs(tiled[0] - whole[0]).max() < TOL
    assert np.ptp(whole[0]) > 1.0
    np.testing.assert_array_equal(tiled[1][0], whole[1][0])
    assert np.abs(tiled[1] - whole[1]).max() < TOL


def _spoil(params, change):
    """``params`` with ``change(path, leaf)`` applied to every leaf."""
    import jax

    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: change("/".join(str(p.key) for p in path), leaf),
        params)


@pytest.mark.parametrize("published, why", [
    (dict(PUBLISHED, routed_scaling_factor=1), "the scale 6 left out"),
    (dict(PUBLISHED, mla_scale_q_lora=False), "sqrt(hidden / q rank)"),
    (dict(PUBLISHED, mla_scale_kv_lora=False), "sqrt(hidden / kv rank)"),
    (dict(PUBLISHED, rope_theta=1e4), "another rotary base"),
])
def test_a_reference_that_states_something_else_disagrees(longcat,
                                                          published, why):
    cfg, params = longcat
    run = _served(cfg, params)
    prompt = _tokens(66, seed=3)
    run.prefill(1, prompt)
    run.rounds(3)
    want = np.asarray(reference_longcat.forward(
        published, params, np.concatenate([prompt, run.served[1]])))
    assert np.abs(run.next_logits()[1] - want[-1]).max() > 50 * TOL, why


def test_the_other_rotary_pairing_fails(longcat, monkeypatch):
    from kubeflow_tpu.models import generate, transformer

    cfg, params = longcat
    monkeypatch.setattr(generate, "_rope_pairs", transformer.rope)
    assert _serve_one(cfg, params, 66) > 50 * TOL


def test_a_bfloat16_program_fails_the_tolerance(longcat):
    import jax.numpy as jnp

    cfg, params = longcat
    low = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    assert _serve_one(low, params, 66) > 10 * TOL


def test_a_slot_reused_after_another_request_reads_none_of_it(longcat):
    cfg, params = longcat
    run = _served(cfg, params)
    first, second = _tokens(70, seed=4), _tokens(5, seed=5)
    run.prefill(1, first)
    run.rounds(3)
    run.prefill(1, second)     # the same slot and pages, a shorter prompt
    run.rounds(3)
    assert run.worst(1, second, run.next_logits()) < TOL


def test_a_slot_in_mid_prefill_while_the_others_decode(longcat):
    cfg, params = longcat
    run = _served(cfg, params, new=6)
    early, late = _tokens(30, seed=6), _tokens(100, seed=7)
    run.prefill(0, early, new=6)
    run.chunk(2, late, 0, new=6)
    assert run.rounds(2) == 2
    run.chunk(2, late, CHUNK, new=6)
    assert run.rounds(3) == 3
    assert len(run.served[0]) == 6 and len(run.served[2]) == 4
    assert run.worst(2, late, run.next_logits()) < TOL


def test_only_live_rows_choose_and_the_pairs_are_counted_where_they_fell(
        longcat):
    """One live slot of three: every step and expert layer has top_k
    pairs, each held, zero-compute or absent; the counts are the LAST
    round's."""
    cfg, params = longcat
    share = dataclasses.replace(cfg, moe_experts_held=4)
    cut = _spoil(params, lambda name, leaf: leaf[:4] if name.endswith(
        ("moe/wi", "moe/wo")) else leaf)
    run = _served(share, cut, new=8)
    run.prefill(1, _tokens(9, seed=8), new=8)
    run.rounds(3)
    held, zero, absent = (int(n) for n in run.state["moe_pairs"])
    assert held + zero + absent == 3 * 2 * cfg.moe_top_k
    assert min(held, zero, absent) > 0
    assert int(run.state["moe_touched"]) <= held
    run.rounds(1)
    assert int(run.state["moe_pairs"].sum()) == 2 * cfg.moe_top_k


# -- the expert layer alone ---------------------------------------------------

def _rows(n=12, seed=9):
    import jax.numpy as jnp

    return jnp.asarray(np.random.default_rng(seed).normal(0, 1, (n, 32)),
                       jnp.float32)


def _expert_layer(cfg, moe, y, bias=None, live=None):
    """(program's ``Experts(y)``, its counts) with ``bias``."""
    import jax.numpy as jnp

    from kubeflow_tpu.models.generate import _experts

    if bias is not None:
        moe = dict(moe, bias=jnp.asarray(bias, jnp.float32))
    out, counts = _experts(cfg, moe, y, live)
    return np.asarray(out), {k: int(v) for k, v in counts.items()}


def _reference_experts(moe, y, bias=None, published=PUBLISHED, **share):
    import jax
    import jax.numpy as jnp

    if bias is not None:
        moe = dict(moe, bias=jnp.asarray(bias, jnp.float32))
    with jax.default_matmul_precision("highest"):
        return np.asarray(reference_longcat.experts(published, y, moe,
                                                    **share))


def test_the_expert_layer_matches_the_reference(longcat):
    cfg, params = longcat
    moe, y = params["layers"]["0"]["moe"], _rows()
    got, counts = _expert_layer(cfg, moe, y)
    assert np.abs(got - _reference_experts(moe, y)).max() < TOL
    assert counts["held"] + counts["zero"] == 12 * 3
    assert counts["absent"] == 0 and 0 < counts["zero"] < 36


def test_a_chosen_set_of_zero_compute_experts_only_returns_the_row(longcat):
    """Every row chooses outputs 8, 9, 10: no expert is touched, no
    weight is read, and the result is the row times 6 x the three
    softmax scores, unnormalised."""
    import jax

    cfg, params = longcat
    moe, y = params["layers"]["0"]["moe"], _rows()
    bias = np.zeros(12, np.float32)
    bias[[8, 9, 10]] = 10.0
    got, counts = _expert_layer(cfg, moe, y, bias)
    assert counts == {"touched": 0, "held": 0, "zero": 36, "absent": 0}
    g = np.asarray(jax.nn.softmax(y @ moe["router"], axis=-1))
    by_hand = 6 * g[:, 8:11].sum(-1, keepdims=True) * np.asarray(y)
    assert np.abs(got - by_hand).max() < TOL
    assert np.abs(got - _reference_experts(moe, y, bias)).max() < TOL
    # A zero-compute expert that returned nothing would read zeros.
    assert np.abs(by_hand).max() > 100 * TOL


def test_a_chosen_set_without_zero_compute_experts(longcat):
    cfg, params = longcat
    moe, y = params["layers"]["0"]["moe"], _rows()
    bias = np.zeros(12, np.float32)
    bias[[1, 4, 6]] = 10.0
    got, counts = _expert_layer(cfg, moe, y, bias)
    assert counts == {"touched": 3, "held": 36, "zero": 0, "absent": 0}
    assert np.abs(got - _reference_experts(moe, y, bias)).max() < TOL


def test_a_row_whose_chosen_experts_are_all_absent_adds_nothing(longcat):
    """This chip holds experts [0, 2); every row chooses 3, 5 and 7."""
    cfg, params = longcat
    share = dataclasses.replace(cfg, moe_experts_held=2)
    moe = params["layers"]["0"]["moe"]
    moe = dict(moe, wi=moe["wi"][:2], wo=moe["wo"][:2])
    bias = np.zeros(12, np.float32)
    bias[[3, 5, 7]] = 10.0
    got, counts = _expert_layer(share, moe, _rows(), bias)
    assert counts == {"touched": 0, "held": 0, "zero": 0, "absent": 36}
    assert np.array_equal(got, np.zeros_like(got))


def test_the_bias_selects_and_does_not_weigh(longcat):
    import jax

    cfg, params = longcat
    moe, y = params["layers"]["0"]["moe"], _rows()
    bias = np.zeros(12, np.float32)
    bias[[2, 5, 9]] = 10.0       # two routed experts and a zero-compute one
    got, counts = _expert_layer(cfg, moe, y, bias)
    assert (counts["touched"], counts["held"], counts["zero"]) == (2, 24, 12)
    # By hand: 6 x the experts' OWN softmax scores, not normalised over
    # the chosen, and the bias nowhere in them.
    g = np.asarray(jax.nn.softmax(y @ moe["router"], axis=-1))
    by_hand = 6 * g[:, 9:10] * np.asarray(y) + sum(
        6 * g[:, e:e + 1] * np.asarray(reference_longcat.swiglu(
            y, moe["wi"][e, :, :24], moe["wi"][e, :, 24:], moe["wo"][e]))
        for e in (2, 5))
    assert np.abs(got - by_hand).max() < TOL
    # Another bias, another choice, another output.
    unbiased, _ = _expert_layer(cfg, moe, y, np.zeros(12, np.float32))
    assert np.abs(got - unbiased).max() > 100 * TOL


@pytest.mark.parametrize("other", [
    {"moe_normalize": True}, {"moe_scale": 1.0}, {"moe_score": "sigmoid"}])
def test_another_router_fails_the_comparison(longcat, other):
    cfg, params = longcat
    moe, y = params["layers"]["0"]["moe"], _rows()
    got, _ = _expert_layer(dataclasses.replace(cfg, **other), moe, y)
    assert np.abs(got - _reference_experts(moe, y)).max() > 100 * TOL


def test_rows_that_are_no_tokens_choose_nothing(longcat):
    import jax.numpy as jnp

    cfg, params = longcat
    moe, y = params["layers"]["0"]["moe"], _rows(3, seed=10)
    got, counts = _expert_layer(cfg, moe, y,
                                live=jnp.asarray([True, False, True]))
    alone, _ = _expert_layer(cfg, moe, y[:1])
    assert np.array_equal(got[1], np.zeros(32, np.float32))
    assert np.abs(got[0] - alone[0]).max() < 1e-6
    assert counts["held"] + counts["zero"] == 2 * 3


def test_the_shares_add_up_to_the_whole_layer(longcat):
    """Four chips with two routed experts each: the parts their expert
    layers give, with the zero-compute experts' part (which every chip
    computes alike for a token it owns) counted once, add up to what the
    uncut reference gives for ``Experts(m)``."""
    cfg, params = longcat
    moe, y = params["layers"]["1"]["moe"], _rows(seed=11)
    total, pairs = np.zeros((12, 32), np.float32), 0
    for k in range(4):
        share = dataclasses.replace(cfg, moe_experts_held=2,
                                    moe_experts_offset=2 * k)
        held = dict(moe, wi=moe["wi"][2 * k:2 * k + 2],
                    wo=moe["wo"][2 * k:2 * k + 2])
        part, counts = _expert_layer(share, held, y)
        # The same share from the reference, with and without the part
        # that needs no weights.
        assert np.abs(part - _reference_experts(
            held, y, experts_held=2, experts_offset=2 * k)).max() < TOL
        zero_part = _reference_experts(held, y, experts_held=0)
        total += part - (zero_part if k else 0)
        pairs += counts["held"]
        assert counts["held"] + counts["zero"] + counts["absent"] == 36
    whole = _reference_experts(moe, y)
    assert np.abs(total - whole).max() < TOL
    assert np.abs(whole - zero_part).max() > 100 * TOL
    assert pairs + counts["zero"] == 36   # every pair fell somewhere, once


# -- the latent form of the paged kernel --------------------------------------

@pytest.mark.parametrize("lengths", [(1, 16, 17), (40, 0, 200), (224, 3, 0)])
def test_latent_kernel_matches_plain_attention(lengths):
    """``paged_latent_decode_attention`` in interpret mode against plain
    ``jax.numpy`` over each slot's gathered view: pages out of order and
    shared between slots, a slot that attends nothing, a frontier at a
    page's first and last row."""
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.ops.paged_attention import (
        paged_latent_decode_attention,
    )

    rng = np.random.default_rng(13)
    planes, nb, bt, row, latent, h = 3, 48, 16, 256, 128, 8
    pool = jnp.asarray(rng.normal(0, 1, (planes, nb, bt, row)), jnp.float32)
    q = jnp.asarray(rng.normal(0, 1, (3, h, row)), jnp.float32)
    tables = rng.permutation(nb)[:3 * 14].reshape(3, 14).astype(np.int32)
    tables[2, :2] = tables[0, :2]          # a shared prefix
    n = jnp.asarray(lengths, jnp.int32)
    got = paged_latent_decode_attention(
        q, pool, jnp.int32(1), jnp.asarray(tables), n, latent, 0.25,
        pages_per_block=4, interpret=True)
    view = pool[1][tables].reshape(3, 14 * bt, row)
    with jax.default_matmul_precision("highest"):
        sc = jnp.einsum("shr,skr->shk", q, view) * 0.25
        sc = jnp.where(jnp.arange(14 * bt)[None, None, :] < n[:, None, None],
                       sc, -jnp.inf)
        want = jnp.einsum("shk,skc->shc", jax.nn.softmax(sc, -1),
                          view[..., :latent])
    for slot, length in enumerate(lengths):
        if length == 0:
            assert np.array_equal(np.asarray(got[slot]),
                                  np.zeros((h, latent), np.float32))
        else:
            assert np.abs(np.asarray(got[slot] - want[slot])).max() < 2e-5


def test_decode_rounds_through_the_latent_kernel_matches_the_reference(
        longcat, monkeypatch):
    """``decode_rounds`` with ``paged_kernel=True`` (what the engine passes
    when its pool lives on a TPU), the kernel in interpret mode."""
    from kubeflow_tpu.ops import paged_attention

    cfg, params = longcat
    calls = []
    real = paged_attention.paged_latent_decode_attention

    def interpreted(*args, **kw):
        calls.append(1)
        return real(*args, interpret=True, **kw)

    monkeypatch.setattr(paged_attention, "paged_latent_decode_attention",
                        interpreted)
    run = _served(cfg, params, new=5)
    prompt = _tokens(70, seed=14)
    run.prefill(1, prompt, new=5)
    run.state, toks, counts, ran = run.g.decode_rounds(
        cfg, params, run.state, run.decode, 4, run.tables, np.int32(4),
        paged_kernel=True)
    assert int(ran) == 4 and len(calls) == cfg.kv_planes
    run.served[1] += [int(t) for t in toks[1, :int(counts[1])]]
    assert run.worst(1, prompt, run.next_logits()) < TOL


# -- ops/grouped_matmul.py in the grouped products' place ----------------------

@pytest.mark.parametrize("live", sorted(test_lfm2.LIVE))
def test_the_grouped_kernel_is_the_expert_layers_ragged_dot(
        longcat, interpreted_grouped_kernel, live):
    """With zero-compute experts beside the held ones: their pairs are in
    no group, and counted where they fell all the same."""
    cfg, params = longcat
    counts = test_lfm2.kernel_against_ragged_dot(
        cfg, params["layers"]["0"]["moe"], interpreted_grouped_kernel,
        test_lfm2.LIVE[live])
    if live != "none":
        assert int(counts["zero"]) > 0 and int(counts["held"]) > 0


def test_the_grouped_kernel_over_a_share_of_the_experts(
        longcat, interpreted_grouped_kernel):
    """Two of the eight routed experts held: most pairs are another
    chip's, sorted past every group, and the kernel visits none of them."""
    cfg, params = longcat
    share = dataclasses.replace(cfg, moe_experts_held=2,
                                moe_experts_offset=4)
    moe = params["layers"]["0"]["moe"]
    moe = dict(moe, wi=moe["wi"][4:6], wo=moe["wo"][4:6])
    counts = test_lfm2.kernel_against_ragged_dot(
        share, moe, interpreted_grouped_kernel, None)
    assert int(counts["absent"]) > int(counts["held"]) > 0


def test_both_programs_serve_the_same_through_the_grouped_kernel(
        longcat, interpreted_grouped_kernel):
    cfg, params = longcat
    test_lfm2.kernel_serves_what_ragged_dot_serves(
        lambda: _served(cfg, params, new=6), _tokens(70, seed=21), 6)
    assert len(interpreted_grouped_kernel) == 2 * 2 * len(cfg.layer_types)


# -- the engine ---------------------------------------------------------------

def _engine(cfg, params, **kw):
    from kubeflow_tpu.models.generate import DecodeConfig
    from kubeflow_tpu.serving.engine import DecodeEngine

    return DecodeEngine(
        cfg, params, DecodeConfig(max_new_tokens=8, temperature=0.0),
        slots=3, prefill_len=160, max_len=176, prefill_chunk_tokens=64,
        name="longcat-test", **kw)


@pytest.mark.parametrize("flag", ["host_spill_blocks", "adapters", "mesh"])
def test_engine_refuses_at_construction_by_name(longcat, flag):
    cfg, params = longcat
    with pytest.raises(ValueError, match=flag):
        _engine(cfg, params, **{flag: 4})


def test_engine_reuses_a_prefix_on_latent_pages_and_says_what_it_holds(
        longcat):
    """Two prompts that share 70 tokens: the second aliases the first's
    four whole pages (64 tokens), resumes in the middle of a chunk and of
    the fifth page, and both are served as the reference computes them;
    what moves pages is refused by name."""
    cfg, params = longcat
    share = dataclasses.replace(cfg, moe_experts_held=4)
    cut = _spoil(params, lambda name, leaf: leaf[:4] if name.endswith(
        ("moe/wi", "moe/wo")) else leaf)
    engine = _engine(share, cut)
    try:
        document = _tokens(70, seed=11)
        prompts = [np.concatenate([document, _tokens(n, seed=20 + n)])
                   for n in (5, 9)]
        for key in ("park_kv", "kv_handoff", "kv_export"):
            with pytest.raises(ValueError, match=key):
                engine.submit({"tokens": prompts[0], key: True})
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
    assert stats["kv_planes"] == 4 and stats["conv_planes"] == 0
    # One row of 256 values a token and plane, float32, key and value.
    assert stats["kv_bytes_per_token"] == 4 * 256 * 4
    assert stats["latent_bytes_per_token"] == stats["kv_bytes_per_token"]
    # ``moe_experts``: what a step can touch, the experts held.
    assert (stats["moe_layers"], stats["moe_experts"], stats["moe_top_k"],
            stats["moe_experts_held"], stats["moe_routed_experts"],
            stats["moe_zero_experts"]) == (2, 4, 3, 4, 8, 4)
    steps = stats["steps"]
    pairs = [stats[k] for k in ("pairs_held", "pairs_zero", "pairs_absent")]
    # Every live row of every step and expert layer chose three outputs.
    assert 2 * 3 * steps <= sum(pairs) <= 2 * 3 * 3 * steps
    assert min(pairs) > 0
    assert 0 < stats["experts_touched"] <= stats["pairs_held"]


def test_engine_stats_of_a_stack_with_every_expert_held_count_no_pairs(
        longcat):
    lfm2_cfg = test_lfm2._config()
    engine = test_lfm2._engine(lfm2_cfg, test_lfm2._params(lfm2_cfg))
    try:
        engine.submit({"tokens": _tokens(20, seed=2), "max_new_tokens": 3})
        stats = engine.stats()
    finally:
        engine.close(drain_s=0.0)
    assert (stats["latent_bytes_per_token"], stats["moe_zero_experts"],
            stats["moe_experts_held"], stats["pairs_held"],
            stats["pairs_zero"], stats["pairs_absent"]) == (0, 0, 8, 0, 0, 0)
    assert stats["experts_touched"] > 0
