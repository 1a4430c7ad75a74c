"""Seeded weights of dots.vlm1.inst's language model with its
multi-token-prediction module (``configs/dots.vlm1.inst-l5.json``), made on
the device: a tree with ONE ENTRY A LAYER (``layers/<i>/...``) and the module
under ``mtp`` (two norms, the projection ``eh_proj``, one more layer of the
expert layers' leaves, a norm; embedding and head are the main model's).
Names and shapes follow the program's tree (``models/transformer.py
layer_tree_shapes``; the runner checks them against ``jax.eval_shape`` of the
program's own init before anything is made).

As in ``weights.py`` one function, ``leaf``, defines every value from (seed,
leaf name, layer), so the plain reference regenerates a layer without taking
anything the program has touched; the module's layer counts as layer
``num_hidden_layers``.  Scales are 1/sqrt(true fan-in): this configuration
puts NO factor on its low-rank norms, so the matrices behind them
(``wq_b``, ``wk_b``, ``wv_b``) have the rank as fan-in, and queries, keys
and attention scores are O(1); ``eh_proj`` contracts two normed streams.
RMSNorm scales are ones.  The router's selection bias is SEEDED where a
checkpoint starts it at zero (std 0.01, about the distance between the 8th
and the 9th largest sigmoid score of the 128 outputs of four groups), so
that leaving it out, or weighing with it, shows.  The expert layers hold the
routed experts ``[experts_offset, experts_offset + n_routed_experts)`` of
``n_routed_experts_published``; the router and its bias keep every output.
Matmul weights are made in the served type; the router and its bias stay
float32, as the program serves them (``ops/quantize.py CONTRACTIONS``).
"""

import jax
import jax.numpy as jnp

from . import weights

# After weights_dots3._LEAF_IDS.
_LEAF_IDS = {"embed": 0, "attn/wo": 3, "mlp/wi": 4, "mlp/wo": 5, "w_out": 6,
             "moe/router": 14, "moe/bias": 15, "moe/wi": 16, "moe/wo": 17,
             "attn/wq_a": 21, "attn/wq_b": 22, "attn/wkv_a": 23,
             "attn/wk_b": 24, "attn/wv_b": 25, "moe/shared/wi": 36,
             "moe/shared/wo": 37, "eh_proj": 41}
_FLOAT32 = ("moe/router", "moe/bias")
_BIAS_STD = 0.01


def layer_specs(c, layer: int):
    """{short leaf name: (shape, std or None for ones)} of layer ``layer``;
    ``num_hidden_layers`` is the module's layer (an expert layer)."""
    d, h = c["hidden_size"], c["num_attention_heads"]
    rq, rkv = c["q_lora_rank"], c["kv_lora_rank"]
    dn, dr, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                  c["v_head_dim"])
    out = {
        "attn_norm/scale": ((d,), None),
        "attn/wq_a": ((d, rq), d ** -0.5),
        "attn/q_norm/scale": ((rq,), None),
        "attn/wq_b": ((rq, h, dn + dr), rq ** -0.5),
        "attn/wkv_a": ((d, rkv + dr), d ** -0.5),
        "attn/kv_norm/scale": ((rkv,), None),
        "attn/wk_b": ((h, dn, rkv), rkv ** -0.5),
        "attn/wv_b": ((rkv, h, dv), rkv ** -0.5),
        "attn/wo": ((h, dv, d), (h * dv) ** -0.5),
        "mlp_norm/scale": ((d,), None)}
    if layer < c["first_k_dense_replace"]:
        f = c["intermediate_size"]
        out.update({"mlp/wi": ((2, d, f), d ** -0.5),
                    "mlp/wo": ((f, d), f ** -0.5)})
    else:
        f, held = c["moe_intermediate_size"], c["n_routed_experts"]
        n = c["n_routed_experts_published"]
        out.update({"moe/router": ((d, n), d ** -0.5),
                    "moe/bias": ((n,), _BIAS_STD),
                    "moe/wi": ((held, d, 2 * f), d ** -0.5),
                    "moe/wo": ((held, f, d), f ** -0.5),
                    "moe/shared/wi": ((2, d, f), d ** -0.5),
                    "moe/shared/wo": ((f, d), f ** -0.5)})
    return out


def module_specs(c):
    """The module's own leaves beside its layer."""
    d = c["hidden_size"]
    return {"enorm/scale": ((d,), None), "hnorm/scale": ((d,), None),
            "eh_proj": ((2 * d, d), (2 * d) ** -0.5),
            "norm/scale": ((d,), None)}


def specs(c):
    """{leaf name: (shape, std or None for ones)} of the whole tree."""
    d, v, n = c["hidden_size"], c["vocab_size"], c["num_hidden_layers"]
    out = {"embed": ((v, d), 0.02), "final_norm/scale": ((d,), None),
           "w_out": ((d, v), d ** -0.5)}
    for i in range(n):
        out.update({f"layers/{i}/{name}": spec
                    for name, spec in layer_specs(c, i).items()})
    if c.get("num_nextn_predict_layers"):
        out.update({f"mtp/{name}": spec
                    for name, spec in module_specs(c).items()})
        out.update({f"mtp/layer/{name}": spec
                    for name, spec in layer_specs(c, n).items()})
    return out


def leaf(key, name, layer, shape, std, dtype, offset=0):
    """One weight: ``name`` the short name (``attn/wq_a``), ``layer`` the
    layer's index (traced or not; ignored outside the stack), ``offset`` the
    first expert held (it seeds the routed experts' matrices, so that two
    shares do not hold the same experts under two numbers)."""
    if std is None:
        return jnp.ones(shape, jnp.float32)
    k = jax.random.fold_in(key, _LEAF_IDS[name])
    if name not in ("embed", "w_out", "eh_proj"):
        k = jax.random.fold_in(k, layer)
    if name in ("moe/wi", "moe/wo"):
        k = jax.random.fold_in(k, offset)
    out = jax.random.normal(k, shape, jnp.float32) * std
    return out if name in _FLOAT32 else out.astype(dtype)


def layer_leaves(c, key, layer, dtype, like=None):
    """The leaves of layer ``layer`` (traced or not), {short name: array};
    ``like``: a layer (static) that holds the same leaves, where ``layer``
    is traced."""
    return {name: leaf(key, name, layer, shape, std, dtype,
                       c.get("experts_offset", 0))
            for name, (shape, std) in layer_specs(
                c, layer if like is None else like).items()}


def module_leaves(c, key, dtype):
    return {name: leaf(key, name, 0, shape, std, dtype)
            for name, (shape, std) in module_specs(c).items()}


def same_leaves(c, layer: int) -> int:
    """The first layer that holds the leaves ``layer`` holds: layers alike
    share one program."""
    return next(i for i in range(layer + 1)
                if layer_specs(c, i) == layer_specs(c, layer))


def make_tree(c, seed: int, dtype=jnp.bfloat16):
    """The whole served tree, a jitted call a layer (the float32
    temporaries are one layer's)."""
    key = weights.seed_key(seed)
    top, n = specs(c), c["num_hidden_layers"]
    flat = {name: jax.jit(lambda k, name=name: leaf(
        k, name, 0, *top[name], dtype))(key) for name in ("embed", "w_out")}
    flat["final_norm/scale"] = jnp.ones((c["hidden_size"],), jnp.float32)
    program = jax.jit(
        lambda k, layer, like: layer_leaves(c, k, layer, dtype, like),
        static_argnums=2)
    for i in range(n):
        made = program(key, jnp.int32(i), same_leaves(c, i))
        flat.update({f"layers/{i}/{name}": a for name, a in made.items()})
    if c.get("num_nextn_predict_layers"):
        made = program(key, jnp.int32(n), same_leaves(c, n))
        flat.update({f"mtp/layer/{name}": a for name, a in made.items()})
        made = jax.jit(lambda k: module_leaves(c, k, dtype))(key)
        flat.update({f"mtp/{name}": a for name, a in made.items()})
    return weights.unflatten(flat)


def tree_shapes(c, dtype=jnp.bfloat16):
    """{leaf name: (shape, dtype)} as the served tree has them."""
    return {name: (shape, jnp.dtype(
        jnp.float32 if std is None or name.endswith(_FLOAT32) else dtype))
        for name, (shape, std) in specs(c).items()}
