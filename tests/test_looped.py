"""A looped stack (``TransformerConfig.loop_steps`` > 1, sandwich norms,
the final norm between loop steps) against the plain reference
``tests/reference_looped.py``: the training forward, and the serving path
(prefill in chunks, then ``decode_rounds``, through the paged pool of
``loop_steps * n_layers`` planes).  Logits are compared, never tokens.

Tolerances.  Program and reference both compute in float32 on the CPU, in
another order of operations (a cache, a scan, per-row scatters): their
logits differ by 1.3e-6 to 1.6e-6 at a logit spread over 1.  ``TOL`` = 2e-4
leaves that two orders of room and is two orders under what the same program
in bfloat16 reads (2.2e-2), so a bfloat16 program fails it; so does every
sabotage below (a dropped loop step, planes shared between steps, a left-out
output norm, no norm between steps: they read 2.2 to 3.2).  Weights are
seeded normals at 1/sqrt(fan-in) and every norm scale is drawn from 1 +- 0.3,
so that a norm left out, or applied with another layer's scale, cannot hide
behind ones.
"""

import dataclasses

import numpy as np
import pytest

import reference_looped

TOL = 2e-4
VOCAB, SEED = 96, 20260928
SLOTS, BLOCK, TABLE = 3, 4, 8          # 32 positions a slot
CHUNK = 8


def _config(**kw):
    from kubeflow_tpu.serving.loaders import _model_config

    return _model_config({
        "vocab_size": VOCAB, "d_model": 32, "n_layers": 2, "n_heads": 4,
        "n_kv_heads": 2, "d_ff": 64, "head_dim": 8, "max_seq_len": 64,
        "rope_theta": 1e6, "norm_eps": 1e-6, "tied_embeddings": False,
        "dtype": "float32", "loop_steps": 3, "sandwich_norm": True, **kw})


def _params(cfg, seed=SEED):
    """The program's own tree (names and shapes from ``Transformer.init``)
    filled with seeded values; norm scales away from 1."""
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
        stacked = name.startswith("layers/")
        shape = leaf.shape[1:] if stacked else leaf.shape
        # The contracted axes of each matmul weight: its fan-in keeps the
        # activations O(1).  The embedding and the gate's bias contract
        # nothing.
        contracted = {"attn/wq": (0,), "attn/wkv": (1,), "attn/wo": (0, 1),
                      "mlp/wi": (1,), "mlp/wo": (0,), "w_out": (0,),
                      "exit_gate_w": (0,)}.get(
                          name.removeprefix("layers/"), ())
        fan_in = int(np.prod([shape[a] for a in contracted]))
        return jnp.asarray(rng.normal(0, fan_in ** -0.5, leaf.shape),
                           jnp.float32)

    return jax.tree_util.tree_map_with_path(fill, shapes)


def _reference(cfg, params, tokens, **kw):
    facts = {"n_layers": cfg.n_layers, "loop_steps": cfg.loop_steps,
             "eps": cfg.norm_eps, "theta": cfg.rope_theta,
             "sandwich": cfg.sandwich_norm, **kw}
    return np.asarray(reference_looped.logits(params, tokens, **facts))


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(1, VOCAB, n, dtype=np.int32)


@pytest.fixture(scope="module")
def looped():
    cfg = _config()
    return cfg, _params(cfg)


# -- the tree -----------------------------------------------------------------

def test_tree_has_the_new_leaves_and_the_cache_the_planes(looped):
    import jax

    from kubeflow_tpu.models.generate import init_cache, init_paged_state

    cfg, params = looped
    names = {"/".join(str(p.key) for p in path): leaf.shape for path, leaf
             in jax.tree_util.tree_leaves_with_path(params)}
    assert names["layers/attn_out_norm/scale"] == (2, 32)
    assert names["layers/mlp_out_norm/scale"] == (2, 32)
    assert names["exit_gate_w"] == (32, 1) and names["exit_gate_b"] == (1,)
    assert names["layers/attn/wq"][0] == cfg.n_layers  # weights: ONE stack
    assert cfg.kv_planes == 6
    assert init_cache(cfg, 2, 16)[0].shape == (6, 2, 16, 2, 8)
    state = init_paged_state(cfg, SLOTS, SLOTS * TABLE, BLOCK)
    assert state["cache_k"].shape == (6, SLOTS * TABLE, BLOCK, 2, 8)


def test_one_loop_step_without_output_norms_is_todays_model():
    """The defaults reproduce the block that was there: the same leaves,
    no plane beyond the layers', and both forward passes on the dense
    reference.  (Bit for bit against the commit before: CHANGES.md, PR 26.)"""
    import jax

    from kubeflow_tpu.models.generate import _forward_with_cache, init_cache
    from kubeflow_tpu.models.transformer import Transformer

    cfg = _config(loop_steps=1, sandwich_norm=False)
    params = _params(cfg)
    names = {"/".join(str(p.key) for p in path) for path, _
             in jax.tree_util.tree_leaves_with_path(params)}
    assert names == {
        "embed", "w_out", "final_norm/scale", "layers/attn_norm/scale",
        "layers/attn/wq", "layers/attn/wkv", "layers/attn/wo",
        "layers/mlp_norm/scale", "layers/mlp/wi", "layers/mlp/wo"}
    assert cfg.kv_planes == cfg.n_layers
    tokens = _tokens(12)[None]
    want = _reference(cfg, params, tokens[0])
    for got in (Transformer(cfg).apply({"params": params}, tokens),
                _forward_with_cache(cfg, params, tokens,
                                    init_cache(cfg, 1, 16), 0)[0]):
        assert np.abs(np.asarray(got)[0] - want).max() < TOL


# -- the training forward -----------------------------------------------------

CASES = {
    "looped_sandwich": {},
    "looped_plain_block": {"sandwich_norm": False},
    "three_steps_grouped_heads": {"loop_steps": 3, "n_kv_heads": 1},
    "tied_four_steps": {"tied_embeddings": True, "loop_steps": 4},
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_transformer_forward_matches_the_reference(case):
    from kubeflow_tpu.models.transformer import Transformer

    cfg = _config(**CASES[case])
    params = _params(cfg)
    tokens = _tokens(14, seed=1)
    got = np.asarray(Transformer(cfg).apply(
        {"params": params}, tokens[None]))[0]
    want = _reference(cfg, params, tokens)
    assert np.ptp(want) > 1.0  # the logits are worth comparing
    assert np.abs(got - want).max() < TOL


def test_loss_and_gradients_flow_through_every_loop_step(looped):
    """The training loss is the ordinary one; the gradient of a layer's
    weights sums what every loop step sent back to it."""
    import jax

    from kubeflow_tpu.models.transformer import lm_task

    cfg, params = looped
    _, loss_fn = lm_task(cfg)
    batch = {"tokens": _tokens(2 * 10, seed=2).reshape(2, 10)}

    def loss(p):
        return loss_fn(p, {}, batch, jax.random.key(0))[0]

    value, grads = jax.value_and_grad(loss)(params)
    want = _reference(cfg, params, batch["tokens"][0])
    logp = want - np.log(np.exp(want).sum(-1, keepdims=True))
    ce0 = -logp[np.arange(9), batch["tokens"][0, 1:]].mean()
    want1 = _reference(cfg, params, batch["tokens"][1])
    logp1 = want1 - np.log(np.exp(want1).sum(-1, keepdims=True))
    ce1 = -logp1[np.arange(9), batch["tokens"][1, 1:]].mean()
    assert float(value) == pytest.approx((ce0 + ce1) / 2, abs=TOL)
    for leaf in jax.tree_util.tree_leaves(
            {k: v for k, v in grads.items() if "exit_gate" not in k}):
        assert np.abs(np.asarray(leaf)).max() > 0
    assert np.abs(np.asarray(grads["exit_gate_w"])).max() == 0  # unread


# -- the serving path ---------------------------------------------------------

def _serve(cfg, params, prompt, new, mutate=None):
    """Prefill ``prompt`` in CHUNK-wide chunks into slot 1 of a paged pool,
    run ``decode_rounds`` for ``new - 1`` steps, then ask the pool for the
    NEXT position's logits.  Returns (served tokens, those logits):
    the logits read every plane that the chunk program and the round
    program wrote.  ``mutate(state)`` may spoil the state in between."""
    import jax.numpy as jnp

    from kubeflow_tpu.models import generate as g

    decode = g.DecodeConfig(max_new_tokens=new, temperature=0.0)
    state = g.init_paged_state(cfg, SLOTS, SLOTS * TABLE, BLOCK)
    slot = 1
    tables = np.full((SLOTS, TABLE), SLOTS * TABLE, np.int32)
    # Pages out of order, so that a table is really read.
    tables[slot] = np.arange(SLOTS * TABLE - 1, -1, -1)[5:5 + TABLE]
    n = len(prompt)
    for start in range(0, n, CHUNK):
        chunk = np.zeros((1, CHUNK), np.int32)
        seg = prompt[start:start + CHUNK]
        chunk[0, :len(seg)] = seg
        state, first = g.prefill_chunk_into_slot(
            cfg, params, state, decode, chunk, np.int32(start), np.int32(n),
            np.int32(new), np.int32(slot), np.int32(7), tables[slot][None])
    if mutate is not None:
        state = mutate(state)
    state, toks, counts, steps = g.decode_rounds(
        cfg, params, state, decode, 4, tables, np.int32(new - 1))
    assert int(steps) == new - 1 and int(counts[slot]) == new - 1
    served = [int(first[0])] + [int(t) for t in toks[slot, :new - 1]]
    logits, _ = g._forward_with_cache(
        cfg, params, state["last_token"][:, None],
        (state["cache_k"], state["cache_v"]), state["lengths"],
        tables=jnp.asarray(tables))
    return served, np.asarray(logits)[slot, 0]


def _served_against_reference(cfg, params, ref_cfg=None, mutate=None,
                              prompt_len=19, new=4, **ref_kw):
    """Largest logit difference over the served positions: the next
    position's full row, and each served token's own logit and the row's
    best at its position."""
    prompt = _tokens(prompt_len, seed=3)
    served, last = _serve(cfg, params, prompt, new, mutate)
    want = _reference(ref_cfg or cfg, params,
                      np.concatenate([prompt, served]), **ref_kw)
    worst = np.abs(last - want[-1]).max()
    # The engine hands out tokens: each must be the reference's best, or
    # within the tolerance of it.
    rows = want[prompt_len - 1:prompt_len - 1 + new]
    gaps = rows.max(-1) - rows[np.arange(new), served]
    return max(worst, gaps.max())


@pytest.mark.parametrize("prompt_len", [5, 16, 19])  # 1 chunk, 2 whole, 3
def test_chunked_prefill_then_decode_rounds_matches_the_reference(
        looped, prompt_len):
    cfg, params = looped
    assert _served_against_reference(cfg, params,
                                     prompt_len=prompt_len) < TOL


def test_a_bfloat16_program_fails_the_tolerance(looped):
    import jax.numpy as jnp

    cfg, params = looped
    low = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    assert _served_against_reference(low, params, ref_cfg=cfg) > 10 * TOL


def _share_planes(state):
    """Loop step 2 reads what step 1 wrote: planes shared between steps."""
    state = dict(state)
    for side in ("cache_k", "cache_v"):
        pool = state[side]
        state[side] = pool.at[2:4].set(pool[0:2])
    return state


SABOTAGE = {
    # (the program's configuration, what is done to its state, what the
    # reference leaves out in the program's place)
    "a_loop_step_dropped": ({"loop_steps": 2}, None, {}),
    "planes_shared_between_steps": ({}, _share_planes, {}),
    "an_output_norm_left_out": ({"sandwich_norm": False}, None, {}),
    # The program has no switch for it: the reference computes what a
    # program without the norm between steps would.
    "no_norm_between_steps": ({}, None, {"norm_between_steps": False}),
}


@pytest.mark.parametrize("what", sorted(SABOTAGE))
def test_sabotage_fails_the_comparison(looped, what):
    cfg, params = looped
    over, mutate, ref_kw = SABOTAGE[what]
    broken = dataclasses.replace(cfg, **over)
    assert _served_against_reference(
        broken, params, ref_cfg=cfg, mutate=mutate, **ref_kw) > 100 * TOL
    if not over.get("loop_steps"):  # the training forward has no planes
        return
    from kubeflow_tpu.models.transformer import Transformer

    tokens = _tokens(14, seed=1)
    got = np.asarray(Transformer(broken).apply(
        {"params": params}, tokens[None]))[0]
    assert np.abs(got - _reference(cfg, params, tokens)).max() > 100 * TOL


def test_generate_runs_the_loop(looped):
    """``generate`` (contiguous cache) goes through the same scan:
    greedy tokens are the reference's best at every position, within
    the tolerance."""
    from kubeflow_tpu.models import generate as g

    cfg, params = looped
    prompt = _tokens(9, seed=4)
    decode = g.DecodeConfig(max_new_tokens=5, temperature=0.0)
    out, last = g.generate(cfg, params, prompt[None], decode)
    out = np.asarray(out)[0]
    want = _reference(cfg, params, out)
    rows = want[8:13]
    assert (rows.max(-1) - rows[np.arange(5), out[9:]]).max() < TOL
    assert np.abs(np.asarray(last)[0] - want[-1]).max() < TOL


def test_kv_pages_round_trip_carries_every_plane(looped):
    """``gather_kv_pages`` then ``import_kv_pages`` into an empty pool:
    the pages land in all loop_steps * n_layers planes, bit for bit."""
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.models import generate as g

    cfg, _ = looped
    state = g.init_paged_state(cfg, SLOTS, SLOTS * TABLE, BLOCK)
    shape = state["cache_k"].shape
    keys = jax.random.split(jax.random.key(5))
    state["cache_k"] = jax.random.normal(keys[0], shape, jnp.float32)
    state["cache_v"] = jax.random.normal(keys[1], shape, jnp.float32)
    ids = np.asarray([7, 2, 19], np.int32)
    (k, _), (v, _) = g.gather_kv_pages(state, ids)
    assert k.shape == (cfg.kv_planes, 3, BLOCK, 2, 8)
    empty = g.init_paged_state(cfg, SLOTS, SLOTS * TABLE, BLOCK)
    to = np.asarray([4, SLOTS * TABLE, 11, 0], np.int32)  # one is padding
    pad = np.zeros((cfg.kv_planes, 1) + k.shape[2:], np.float32)
    filled = g.import_kv_pages(
        empty, np.concatenate([k[:, :1], pad, k[:, 1:]], 1),
        np.concatenate([v[:, :1], pad, v[:, 1:]], 1), to)
    for side, pages in (("cache_k", k), ("cache_v", v)):
        pool = np.asarray(filled[side])
        np.testing.assert_array_equal(pool[:, [4, 11, 0]], pages)
        assert np.count_nonzero(pool) == np.count_nonzero(pages)
