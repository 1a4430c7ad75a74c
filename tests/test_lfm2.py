"""A stack that states its ``layer_types`` (LFM2-MoE: gated short
convolutions beside attention layers with q / k norms, two leading dense
SwiGLUs, then sigmoid-routed sparse experts that drop nothing) against the
plain reference ``tests/reference_lfm2.py``: the flax forward, and the
serving path (prefill in chunks of 64, then ``decode_rounds``, through the
paged pool of the attention layers' planes AND the per-slot convolution
state), down to the engine.  Logits are compared, never tokens.

Tolerances.  Program and reference both compute in float32 on the CPU, in
another order of operations (a cache, per-row scatters, rows sorted by
expert and multiplied by groups where the reference loops over a dense
mask): their logits differ by 2e-6 to 6e-6 at a logit spread over 1.
``TOL`` = 2e-4 leaves that over an order of room and is two orders under
what the same program in bfloat16 reads (3e-2 and more), so a bfloat16
program fails it; so does every sabotage below (the expert bias zeroed, a
convolution tap dropped, a stale convolution state: 2e-2 to 1).  Weights
are seeded normals at 1/sqrt(fan-in), norm scales are drawn from 1 +- 0.3 so
that a norm left out cannot hide behind ones, and the expert bias is drawn
at 0.2, the spread of the sigmoid scores, so that it changes about half of
the choices.
"""

import dataclasses

import numpy as np
import pytest

import reference_lfm2

TOL = 2e-4
VOCAB, SEED = 96, 20260929
SLOTS, BLOCK, TABLE = 3, 16, 14        # 224 positions a slot
CHUNK = 64
# Hugging Face keys, as the reference reads them.
PUBLISHED = {
    "vocab_size": VOCAB, "hidden_size": 32, "num_hidden_layers": 5,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
    "intermediate_size": 64, "moe_intermediate_size": 24,
    "num_experts": 8, "num_experts_per_tok": 2, "num_dense_layers": 1,
    "layer_types": ["conv", "conv", "full_attention", "conv",
                    "full_attention"],
    "conv_L_cache": 3, "norm_eps": 1e-5, "rope_theta": 1e6,
    "routed_scaling_factor": 1,
}
FIELDS = {"vocab_size": "vocab_size", "hidden_size": "d_model",
          "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
          "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
          "intermediate_size": "d_ff", "moe_intermediate_size": "moe_d_ff",
          "num_experts": "moe_experts", "num_experts_per_tok": "moe_top_k",
          "num_dense_layers": "moe_dense_layers",
          "layer_types": "layer_types", "conv_L_cache": "conv_kernel",
          "norm_eps": "norm_eps", "rope_theta": "rope_theta"}
# The contracted axes of each matmul weight: its fan-in keeps activations
# O(1).
CONTRACTED = {"conv/w_in": (0,), "conv/w_out": (0,), "attn/wq": (0,),
              "attn/wkv": (1,), "attn/wo": (0, 1), "mlp/wi": (1,),
              "mlp/wo": (0,), "moe/router": (0,), "moe/wi": (1,),
              "moe/wo": (1,)}


def _config(published=PUBLISHED, **kw):
    from kubeflow_tpu.serving.loaders import _model_config

    return _model_config({
        **{FIELDS[k]: v for k, v in published.items() if k in FIELDS},
        "max_seq_len": 256, "tied_embeddings": True, "qk_norm": True,
        "dtype": "float32", **kw})


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
            return jnp.asarray(rng.normal(0, 0.2, leaf.shape), jnp.float32)
        if name.endswith("conv/w_conv"):
            return jnp.asarray(rng.normal(0, 0.6, leaf.shape), jnp.float32)
        short = "/".join(name.split("/")[-2:])
        fan_in = int(np.prod([leaf.shape[a]
                              for a in CONTRACTED.get(short, ())]))
        return jnp.asarray(rng.normal(0, fan_in ** -0.5, leaf.shape),
                           jnp.float32)

    return jax.tree_util.tree_map_with_path(fill, shapes)


def _reference(params, tokens, published=PUBLISHED):
    return np.asarray(reference_lfm2.forward(published, params, tokens))


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(1, VOCAB, n, dtype=np.int32)


@pytest.fixture(scope="module")
def lfm2():
    cfg = _config()
    return cfg, _params(cfg)


# -- the tree and the state ---------------------------------------------------

def test_tree_has_a_layer_each_and_the_state_both_kinds(lfm2):
    import jax

    from kubeflow_tpu.models.generate import init_paged_state

    cfg, params = lfm2
    names = {"/".join(str(p.key) for p in path): leaf.shape for path, leaf
             in jax.tree_util.tree_leaves_with_path(params)}
    assert names["layers/0/conv/w_in"] == (32, 3, 32)
    assert names["layers/0/conv/w_conv"] == (3, 32)
    assert names["layers/0/mlp/wi"] == (2, 32, 64)      # leading dense
    assert "layers/1/mlp/wi" not in names
    assert names["layers/1/moe/wi"] == (8, 32, 48)      # gate | up
    assert names["layers/1/moe/wo"] == (8, 24, 32)
    assert names["layers/1/moe/router"] == (32, 8)
    assert names["layers/1/moe/bias"] == (8,)
    assert names["layers/2/attn/q_norm/scale"] == (8,)
    assert "layers/2/conv/w_in" not in names
    assert cfg.kv_planes == 2 and cfg.conv_planes == 3
    state = init_paged_state(cfg, SLOTS, SLOTS * TABLE, BLOCK)
    assert state["cache_k"].shape == (2, SLOTS * TABLE, BLOCK, 2, 8)
    assert state["conv"].shape == (3, SLOTS, 2, 32)
    assert state["moe_touched"].shape == ()


def test_a_stack_without_layer_types_keeps_its_state_and_tree():
    from kubeflow_tpu.models.generate import init_paged_state
    from kubeflow_tpu.models.transformer import TransformerConfig

    cfg = TransformerConfig(vocab_size=VOCAB, d_model=32, n_layers=2,
                            n_heads=4, n_kv_heads=2, d_ff=64, head_dim=8)
    assert cfg.kv_planes == 2 and cfg.conv_planes == 0
    assert sorted(init_paged_state(cfg, 2, 8, 4)) == [
        "adapter_ids", "cache_k", "cache_v", "done", "keys", "last_token",
        "lengths", "stop_len"]


@pytest.mark.parametrize("bad", [
    {"layer_types": ["conv", "window"]},
    {"layer_types": ["conv"]},
    {"layer_types": ["conv", "conv"], "loop_steps": 2},
    {"layer_types": ["conv", "conv"], "conv_kernel": 1},
])
def test_config_refuses_what_is_not_built(bad):
    from kubeflow_tpu.models.transformer import TransformerConfig

    with pytest.raises(ValueError):
        TransformerConfig(n_layers=2, **bad)


# -- the forward without a cache ----------------------------------------------

@pytest.mark.parametrize("n", [1, 2, 3, 40])
def test_flax_forward_matches_the_reference(lfm2, n):
    from kubeflow_tpu.models.transformer import Transformer

    cfg, params = lfm2
    tokens = _tokens(n, seed=1)
    got = np.asarray(Transformer(cfg).apply({"params": params},
                                            tokens[None]))[0]
    want = _reference(params, tokens)
    assert n < 40 or np.ptp(want) > 1.0   # the logits are worth comparing
    assert np.abs(got - want).max() < TOL


# -- the serving path ---------------------------------------------------------

class Served:
    """A paged state with SLOTS slots, driven as the engine drives it
    (``grouped_kernel``: what the engine would hand both programs)."""

    grouped_kernel = False

    def __init__(self, cfg, params, new=4, reference=None):
        from kubeflow_tpu.models import generate as g

        self.g, self.cfg, self.params = g, cfg, params
        # (params, tokens, published) -> the plain reference's logits.
        self.reference = reference or _reference
        self.decode = g.DecodeConfig(max_new_tokens=new, temperature=0.0)
        self.state = g.init_paged_state(cfg, SLOTS, SLOTS * TABLE, BLOCK)
        self.tables = np.full((SLOTS, TABLE), SLOTS * TABLE, np.int32)
        pages = np.arange(SLOTS * TABLE - 1, -1, -1)  # out of order
        for slot in range(SLOTS):
            self.tables[slot] = pages[slot * TABLE:(slot + 1) * TABLE]
        self.served = {}

    def chunk(self, slot, prompt, start, new=4):
        """One chunk of ``prompt`` into ``slot``."""
        chunk = np.zeros((1, CHUNK), np.int32)
        seg = prompt[start:start + CHUNK]
        chunk[0, :len(seg)] = seg
        self.state, first = self.g.prefill_chunk_into_slot(
            self.cfg, self.params, self.state, self.decode, chunk,
            np.int32(start), np.int32(len(prompt)), np.int32(new),
            np.int32(slot), np.int32(7), self.tables[slot][None],
            grouped_kernel=self.grouped_kernel)
        if start + CHUNK >= len(prompt):
            self.served[slot] = [int(first[0])]

    def prefill(self, slot, prompt, new=4):
        for start in range(0, len(prompt), CHUNK):
            self.chunk(slot, prompt, start, new)

    def rounds(self, steps):
        self.state, toks, counts, ran = self.g.decode_rounds(
            self.cfg, self.params, self.state, self.decode, 4, self.tables,
            np.int32(steps), grouped_kernel=self.grouped_kernel)
        for slot in self.served:
            self.served[slot] += [int(t) for t in
                                  toks[slot, :int(counts[slot])]]
        return int(ran)

    def next_logits(self):
        """The NEXT position's logits of every slot, through both kinds of
        state as the programs left them."""
        import jax.numpy as jnp

        s = self.state
        logits = self.g.forward_layer_types(
            self.cfg, self.params, s["last_token"][:, None],
            tuple(s[side] for side in self.g.pool_sides(s)), s["lengths"],
            tables=jnp.asarray(self.tables), conv=s.get("conv"),
            n_new=jnp.ones((SLOTS,), jnp.int32))[0]
        return np.asarray(logits)[:, 0]

    def worst(self, slot, prompt, last, published=PUBLISHED, params=None):
        """Largest logit difference over what ``slot`` served: the next
        position's full row, and how far each served token's reference
        logit lies under the row's best."""
        served = self.served[slot]
        want = self.reference(self.params if params is None else params,
                              np.concatenate([prompt, served]), published)
        rows = want[len(prompt) - 1:len(prompt) - 1 + len(served)]
        gaps = rows.max(-1) - rows[np.arange(len(served)), served]
        return max(np.abs(last[slot] - want[-1]).max(), gaps.max())


def _serve_one(cfg, params, prompt_len, new=4, ref_params=None):
    run = Served(cfg, params, new)
    prompt = _tokens(prompt_len, seed=3)
    run.prefill(1, prompt, new)
    assert run.rounds(new - 1) == new - 1
    assert len(run.served[1]) == new
    return run.worst(1, prompt, run.next_logits(), params=ref_params)


# A final chunk of 1, 2, 63 and 64 real tokens, over 1, 2 and 3 chunks.
@pytest.mark.parametrize("prompt_len", [1, 2, 63, 64, 65, 66, 127, 128, 129,
                                        191, 192])
def test_chunked_prefill_then_decode_rounds_matches_the_reference(
        lfm2, prompt_len):
    cfg, params = lfm2
    assert _serve_one(cfg, params, prompt_len) < TOL


def _zero_bias(params):
    layers = {i: (dict(lp, moe=dict(lp["moe"], bias=0 * lp["moe"]["bias"]))
                  if "moe" in lp else lp)
              for i, lp in params["layers"].items()}
    return dict(params, layers=layers)


def _drop_a_tap(params):
    layers = {i: (dict(lp, conv=dict(
        lp["conv"], w_conv=lp["conv"]["w_conv"].at[0].set(0.0)))
        if "conv" in lp else lp) for i, lp in params["layers"].items()}
    return dict(params, layers=layers)


@pytest.mark.parametrize("prompt_len", [2, 65, 129])
@pytest.mark.parametrize("spoil", [_zero_bias, _drop_a_tap])
def test_without_the_bias_or_a_tap_the_comparison_fails(lfm2, spoil,
                                                        prompt_len):
    cfg, params = lfm2
    assert _serve_one(cfg, spoil(params), prompt_len,
                      ref_params=params) > 100 * TOL


def test_a_bfloat16_program_fails_the_tolerance(lfm2):
    import jax.numpy as jnp

    cfg, params = lfm2
    low = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    assert _serve_one(low, params, 66) > 10 * TOL


def test_a_slot_reused_after_another_request_starts_from_zeros(lfm2):
    cfg, params = lfm2
    run = Served(cfg, params)
    first, second = _tokens(70, seed=4), _tokens(5, seed=5)
    run.prefill(1, first)
    run.rounds(3)
    conv_after_first = np.asarray(run.state["conv"][:, 1])
    assert np.abs(conv_after_first).max() > 0
    run.prefill(1, second)     # the same slot, a shorter prompt
    run.rounds(3)
    assert run.worst(1, second, run.next_logits()) < TOL


def test_a_slot_in_mid_prefill_while_the_others_decode(lfm2):
    """Slot 0 decodes while slot 2 has one of its two chunks: the round
    leaves slot 2's convolution state (and slot 1's, which is free) as it
    was, and both come out on the reference."""
    cfg, params = lfm2
    run = Served(cfg, params, new=6)
    early, late = _tokens(30, seed=6), _tokens(100, seed=7)
    run.prefill(0, early, new=6)
    run.chunk(2, late, 0, new=6)
    before = np.asarray(run.state["conv"])
    assert run.rounds(2) == 2
    after = np.asarray(run.state["conv"])
    assert np.array_equal(after[:, 1:], before[:, 1:])
    assert not np.array_equal(after[:, 0], before[:, 0])
    run.chunk(2, late, CHUNK, new=6)
    assert run.rounds(3) == 3
    assert len(run.served[0]) == 6 and len(run.served[2]) == 4
    last = run.next_logits()
    assert run.worst(2, late, last) < TOL
    # Slot 0 is done (6 of 6): its served tokens are the reference's.
    want = _reference(params, np.concatenate([early, run.served[0]]))
    rows = want[len(early) - 1:len(early) + 5]
    assert (rows.max(-1) - rows[np.arange(6), run.served[0]]).max() < TOL


def test_only_live_rows_choose_experts(lfm2):
    """One live slot of three: a step touches at most top_k experts a
    sparse layer, and ``moe_touched`` is the LAST round's count."""
    cfg, params = lfm2
    run = Served(cfg, params, new=8)
    run.prefill(1, _tokens(9, seed=8), new=8)
    run.rounds(3)
    touched = int(run.state["moe_touched"])
    sparse = sum(cfg.layer_is_sparse(i) for i in range(cfg.n_layers))
    assert sparse == 4
    assert 3 * sparse <= touched <= 3 * sparse * cfg.moe_top_k
    run.rounds(1)
    assert sparse <= int(run.state["moe_touched"]) <= sparse * cfg.moe_top_k


# -- the sparse feed-forward alone --------------------------------------------

def _sparse_layer(cfg, params, bias):
    """(program's output, reference's output, experts touched) of sparse
    layer 1's feed-forward branch on seeded rows, with ``bias``."""
    import jax.numpy as jnp

    from kubeflow_tpu.models.generate import _sparse_ff

    lp = dict(params["layers"]["1"])
    lp["moe"] = dict(lp["moe"], bias=jnp.asarray(bias, jnp.float32))
    x = jnp.asarray(np.random.default_rng(9).normal(0, 1, (2, 6, 32)),
                    jnp.float32)
    out, counts = _sparse_ff(cfg, lp, x)
    touched = counts["touched"]
    y = reference_lfm2.rms_norm(x.reshape(12, 32), lp["mlp_norm"]["scale"],
                                cfg.norm_eps)
    published = dict(PUBLISHED, num_experts_per_tok=cfg.moe_top_k)
    want = reference_lfm2.sparse_ff(published, y, lp["moe"])
    return (np.asarray(out - x).reshape(12, 32), np.asarray(want),
            int(touched))


def test_the_bias_changes_the_choice_and_not_the_weights(lfm2):
    import jax

    cfg, params = lfm2
    moe = params["layers"]["1"]["moe"]
    bias = np.zeros(8, np.float32)
    bias[[2, 5]] = 10.0          # every row chooses experts 2 and 5
    got, want, touched = _sparse_layer(cfg, params, bias)
    assert np.abs(got - want).max() < TOL and touched == 2
    # By hand: the weights are the two experts' own scores, normalised;
    # the bias is nowhere in them.
    x = np.random.default_rng(9).normal(0, 1, (12, 32)).astype(np.float32)
    y = reference_lfm2.rms_norm(
        x, params["layers"]["1"]["mlp_norm"]["scale"], cfg.norm_eps)
    s = np.asarray(jax.nn.sigmoid(y @ moe["router"]))[:, [2, 5]]
    g = s / (s.sum(-1, keepdims=True) + 1e-6)
    by_hand = sum(g[:, j:j + 1] * np.asarray(reference_lfm2.swiglu(
        y, moe["wi"][e, :, :24], moe["wi"][e, :, 24:], moe["wo"][e]))
        for j, e in enumerate((2, 5)))
    assert np.abs(got - by_hand).max() < TOL
    # Another bias, another choice, another output.
    unbiased, _, _ = _sparse_layer(cfg, params, np.zeros(8, np.float32))
    assert np.abs(got - unbiased).max() > 100 * TOL


def test_every_row_sent_to_one_expert(lfm2):
    cfg, params = lfm2
    one = dataclasses.replace(cfg, moe_top_k=1)
    bias = np.zeros(8, np.float32)
    bias[6] = 10.0
    got, want, touched = _sparse_layer(one, params, bias)
    assert touched == 1
    assert np.abs(got - want).max() < TOL


def test_rows_that_are_no_tokens_choose_nothing(lfm2):
    import jax.numpy as jnp

    from kubeflow_tpu.models.generate import _sparse_ff

    cfg, params = lfm2
    lp = params["layers"]["1"]
    x = jnp.asarray(np.random.default_rng(10).normal(0, 1, (3, 1, 32)),
                    jnp.float32)
    live = jnp.asarray([[True], [False], [True]])
    out, counts = _sparse_ff(cfg, lp, x, live)
    touched = counts["touched"]
    alone, _ = _sparse_ff(cfg, lp, x[:1])
    assert np.array_equal(np.asarray(out[1]), np.asarray(x[1]))
    assert np.abs(np.asarray(out[0] - alone[0])).max() < 1e-6
    assert 2 <= int(touched) <= 4


# -- ops/grouped_matmul.py in the grouped products' place ----------------------

# Which of an expert layer's rows are tokens: all, all but a parked slot
# and a final chunk's padding, none.
LIVE = {"all": None,
        "some": [True, False, True, True, False, False, True, False, True,
                 True, False, False],
        "none": [False] * 12}


def kernel_against_ragged_dot(cfg, moe, calls, live, tol=1e-5):
    """``_experts`` over 12 seeded rows with its grouped products through
    the kernel (interpreted: ``calls`` is the fixture's) against the same
    through ``jax.lax.ragged_dot``: two calls a layer, the same output,
    the same counts, and exact zeros for a row that is no token (the
    kernel does not write it: the select covers what the buffer held)."""
    import jax.numpy as jnp

    from kubeflow_tpu.models.generate import _experts

    y = jnp.asarray(np.random.default_rng(11).normal(0, 1, (12, cfg.d_model)),
                    jnp.float32)
    live = None if live is None else jnp.asarray(live)
    plain, counted = _experts(cfg, moe, y, live)
    before = len(calls)
    got, counts = _experts(cfg, moe, y, live, grouped_kernel=True)
    assert len(calls) == before + 2
    assert {k: int(v) for k, v in counts.items()} \
        == {k: int(v) for k, v in counted.items()}
    got, plain = np.asarray(got), np.asarray(plain)
    assert np.isfinite(got).all()
    assert np.abs(got - plain).max() < tol
    if live is not None:
        parked = ~np.asarray(live)
        assert np.array_equal(got[parked], np.zeros_like(got[parked]))
        if parked.all():
            assert int(counts["touched"]) == 0
    return counts


def kernel_serves_what_ragged_dot_serves(make, prompt, new, tol=TOL):
    """Slot 1 of a ``Served`` that ``make`` builds, through BOTH programs
    with ``grouped_kernel`` (a final chunk of padding, then rounds beside
    two parked slots), against the same without: the same tokens, the
    same counts of the last call, the next position's logits."""
    import jax

    runs = []
    for kernel in (False, True):
        run = make()
        run.grouped_kernel = kernel
        run.prefill(1, prompt, new)
        while len(run.served[1]) < new:
            run.rounds(2)
        runs.append(run)
    plain, kernel = runs
    assert kernel.served == plain.served and len(kernel.served[1]) == new
    for key in ("moe_touched", "moe_pairs"):
        if key in plain.state:
            assert np.array_equal(np.asarray(kernel.state[key]),
                                  np.asarray(plain.state[key])), key
    for got, want in zip(jax.tree_util.tree_leaves(kernel.next_logits()),
                         jax.tree_util.tree_leaves(plain.next_logits())):
        assert np.abs(got - want).max() < tol


@pytest.mark.parametrize("live", sorted(LIVE))
def test_the_grouped_kernel_is_the_expert_layers_ragged_dot(
        lfm2, interpreted_grouped_kernel, live):
    cfg, params = lfm2
    counts = kernel_against_ragged_dot(
        cfg, params["layers"]["1"]["moe"], interpreted_grouped_kernel,
        LIVE[live])
    assert int(counts["absent"]) == int(counts["zero"]) == 0


def test_both_programs_serve_the_same_through_the_grouped_kernel(
        lfm2, interpreted_grouped_kernel):
    cfg, params = lfm2
    kernel_serves_what_ragged_dot_serves(
        lambda: Served(cfg, params, new=6), _tokens(70, seed=21), 6)
    # Both programs were traced with it: 4 sparse layers, two products.
    assert len(interpreted_grouped_kernel) == 2 * 2 * 4


# -- the engine ---------------------------------------------------------------

def _engine(cfg, params, **kw):
    from kubeflow_tpu.models.generate import DecodeConfig
    from kubeflow_tpu.serving.engine import DecodeEngine

    return DecodeEngine(
        cfg, params, DecodeConfig(max_new_tokens=8, temperature=0.0),
        slots=3, prefill_len=160, max_len=176, name="lfm2-test", **kw)


@pytest.mark.parametrize("flag", ["host_spill_blocks", "adapters", "mesh"])
def test_engine_refuses_at_construction_by_name(lfm2, flag):
    cfg, params = lfm2
    with pytest.raises(ValueError, match=flag):
        _engine(cfg, params, **{flag: 4})


def test_engine_serves_whole_prefills_and_refuses_page_features(lfm2):
    cfg, params = lfm2
    engine = _engine(cfg, params)
    try:
        prompt = _tokens(70, seed=11)
        for key in ("park_kv", "kv_handoff", "kv_export"):
            with pytest.raises(ValueError, match=key):
                engine.submit({"tokens": prompt, key: True})
        # The same prompt twice: no page of the first is reused.
        outs = [engine.submit({"tokens": prompt, "max_new_tokens": 5})
                for _ in range(2)]
        stats = engine.stats()
    finally:
        engine.close(drain_s=0.0)
    for out in outs:
        tokens = np.asarray(out["tokens"])[0]
        assert tokens.shape == (75,)
        want = _reference(params, tokens)
        rows = want[69:74]
        assert (rows.max(-1) - rows[np.arange(5), tokens[70:]]).max() < TOL
    assert stats["prefix_hits"] == 0 and stats["cached_prompt_tokens"] == 0
    assert stats["prefix_reuse"].startswith("off: a page alias")
    assert stats["kv_planes"] == 2 and stats["conv_planes"] == 3
    # Keys and values over 2 planes, 2 kv heads of 8, float32.
    assert stats["kv_bytes_per_token"] == 2 * 2 * 2 * 8 * 4
    assert stats["conv_state_bytes"] == 3 * 3 * 2 * 32 * 4
    assert (stats["moe_layers"], stats["moe_experts"],
            stats["moe_top_k"]) == (4, 8, 2)
    steps = stats["steps"]
    assert 4 * steps <= stats["experts_touched"] <= 4 * 2 * 3 * steps


def test_engine_hands_both_programs_the_grouped_kernel_and_counts_it(
        lfm2, monkeypatch, interpreted_grouped_kernel):
    """Where the expert matrices are what ops/grouped_matmul.py reads
    (here: said so for the CPU's float32 ones, the kernel interpreted),
    the engine decides ONCE, both programs hold the kernel, and
    ``grouped_kernel_steps`` / ``grouped_kernel_chunks`` count every
    decode step and chunk; the tokens are the plain engine's."""
    from kubeflow_tpu.serving import engine as engine_mod

    cfg, params = lfm2
    prompt = _tokens(70, seed=12)
    served = {}
    for kernel in (False, True):
        if kernel:
            monkeypatch.setattr(engine_mod, "_expert_matrices_platform",
                                lambda cfg, params: "tpu")
        engine = _engine(cfg, params)
        try:
            out = engine.submit({"tokens": prompt, "max_new_tokens": 5})
            served[kernel] = (np.asarray(out["tokens"])[0], engine.stats())
        finally:
            engine.close(drain_s=0.0)
    (plain, off), (tokens, on) = served[False], served[True]
    assert np.array_equal(tokens, plain) and tokens.shape == (75,)
    assert off["grouped_kernel_steps"] == off["grouped_kernel_chunks"] == 0
    assert on["steps"] > 0 and on["grouped_kernel_steps"] == on["steps"]
    assert on["grouped_kernel_chunks"] == on["prefill_chunks"] > 0
    # Two programs, 4 sparse layers, two products a layer.
    assert len(interpreted_grouped_kernel) == 2 * 4 * 2
    assert on["experts_touched"] == off["experts_touched"]


def test_the_kernel_is_for_plain_bfloat16_expert_matrices(lfm2):
    """What ``DecodeEngine`` decides from: the platform of the expert
    matrices' device, and None for what the kernel does not read where
    it lies (another dtype than the model's bfloat16, a quantised leaf,
    columns that fill no whole 128-lane tile, a stack without experts)."""
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.ops.quantize import QTensor
    from kubeflow_tpu.serving.engine import _expert_matrices_platform

    cfg, params = lfm2
    assert _expert_matrices_platform(cfg, params) is None   # float32
    low = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    # Narrow: 32 and 48 columns are no whole lane tile.
    cast = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), params)
    assert _expert_matrices_platform(low, cast) is None
    moe = {"router": jnp.zeros((128, 4)), "bias": jnp.zeros((4,)),
           "wi": jnp.zeros((4, 128, 256), jnp.bfloat16),
           "wo": jnp.zeros((4, 128, 128), jnp.bfloat16),
           "shared": {"wi": jnp.zeros((2, 128, 64), jnp.float32)}}
    tree = {"layers": {"0": {"mlp": {"wi": jnp.zeros((2, 8, 8))}},
                       "1": {"moe": moe}}}
    assert _expert_matrices_platform(low, tree) == "cpu"
    assert _expert_matrices_platform(cfg, tree) is None     # a cast copies
    assert _expert_matrices_platform(low, {"layers": {"0": {}}}) is None
    wide = dict(moe, wo=jnp.zeros((4, 128, 128), jnp.float32))
    assert _expert_matrices_platform(
        low, {"layers": {"1": {"moe": wide}}}) is None
    quantised = dict(moe, wi=QTensor(
        jnp.zeros((4, 128, 256), jnp.int8), jnp.ones((4, 1, 256))))
    assert _expert_matrices_platform(
        low, {"layers": {"1": {"moe": quantised}}}) is None


def test_engine_stats_of_a_dense_stack_say_so():
    from kubeflow_tpu.models.generate import DecodeConfig
    from kubeflow_tpu.models.transformer import TransformerConfig
    from kubeflow_tpu.serving.engine import DecodeEngine
    import jax.numpy as jnp

    cfg = TransformerConfig(vocab_size=VOCAB, d_model=32, n_layers=2,
                            n_heads=4, n_kv_heads=2, d_ff=64, head_dim=8,
                            max_seq_len=64, dtype=jnp.float32)
    test_looped = pytest.importorskip("test_looped")
    params = test_looped._params(cfg)
    engine = DecodeEngine(cfg, params, DecodeConfig(max_new_tokens=4),
                          slots=2, prefill_len=16, name="dense-test")
    try:
        stats = engine.stats()
    finally:
        engine.close(drain_s=0.0)
    assert stats["prefix_reuse"] == "on"
    assert (stats["conv_planes"], stats["conv_state_bytes"],
            stats["moe_layers"], stats["moe_experts"],
            stats["experts_touched"]) == (0, 0, 0, 0, 0)
