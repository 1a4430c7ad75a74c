"""Seeded weights of LongCat-Flash's language model
(``configs/longcat-flash-omni-l4.json``), made on the device: a tree with ONE
ENTRY A DOUBLE LAYER (``layers/<i>/half_0|half_1|moe/...``).  Names and shapes
follow the program's tree (``models/transformer.py layer_tree_shapes``; the
runner checks them against ``jax.eval_shape`` of the program's own init
before anything is made).

As in ``weights.py`` one function, ``leaf``, defines every value from (seed,
leaf name, layer), so the plain reference regenerates a layer without taking
anything the program has touched.  Scales are 1/sqrt(true fan-in), where the
fan-in of the three matrices behind a low-rank norm (``wq_b``, ``wk_b``,
``wv_b``) counts the factor the configuration puts on that norm's output
(``mla_scale_q_lora`` / ``mla_scale_kv_lora``: sqrt(hidden / rank)), so that
queries, keys and scores are O(1) as the factor is there to make them; norm
scales are ones, as the other configurations'.  The expert layer holds the
routed experts ``[experts_offset, experts_offset + n_routed_experts)`` of
``n_routed_experts_published``; the router and its bias keep every output
(routed and zero-compute).  The bias is SEEDED at 0.001 and not zero (about
the distance between the largest softmax scores of 768 outputs: it changes
two of a row's twelve choices), so that a bias left out of the choice, or let
into the weights, shows.  Matmul weights are made in the served type; the
router and the bias stay float32, as the program serves them
(``ops/quantize.py CONTRACTIONS``).
"""

import jax
import jax.numpy as jnp

from . import weights

# After weights._LEAF_IDS, weights_looped's and weights_lfm2's.
_LEAF_IDS = {"embed": 0, "attn/wo": 3, "mlp/wi": 4, "mlp/wo": 5, "w_out": 6,
             "moe/router": 14, "moe/bias": 15, "moe/wi": 16, "moe/wo": 17,
             "attn/wq_a": 21, "attn/wq_b": 22, "attn/wkv_a": 23,
             "attn/wk_b": 24, "attn/wv_b": 25}
_FLOAT32 = ("moe/router", "moe/bias")
_BIAS_STD = 0.001
HALVES = ("half_0", "half_1")


def outputs(c):
    """The router's outputs: routed experts, then zero-compute experts."""
    return c["n_routed_experts_published"] + c["zero_expert_num"]


def layer_specs(c):
    """{short leaf name: (shape, std or None for ones)} of a double layer."""
    d, h = c["hidden_size"], c["num_attention_heads"]
    rq, rkv = c["q_lora_rank"], c["kv_lora_rank"]
    dn, dr, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                  c["v_head_dim"])
    f, fe, held = (c["ffn_hidden_size"], c["expert_ffn_hidden_size"],
                   c["n_routed_experts"])
    # Behind a norm whose output is scaled by sqrt(d / rank): fan-in d.
    q_in = d if c["mla_scale_q_lora"] else rq
    kv_in = d if c["mla_scale_kv_lora"] else rkv
    half = {
        "attn_norm/scale": ((d,), None),
        "attn/wq_a": ((d, rq), d ** -0.5),
        "attn/q_norm/scale": ((rq,), None),
        "attn/wq_b": ((rq, h, dn + dr), q_in ** -0.5),
        "attn/wkv_a": ((d, rkv + dr), d ** -0.5),
        "attn/kv_norm/scale": ((rkv,), None),
        "attn/wk_b": ((h, dn, rkv), kv_in ** -0.5),
        "attn/wv_b": ((rkv, h, dv), kv_in ** -0.5),
        "attn/wo": ((h, dv, d), (h * dv) ** -0.5),
        "mlp_norm/scale": ((d,), None),
        "mlp/wi": ((2, d, f), d ** -0.5),
        "mlp/wo": ((f, d), f ** -0.5)}
    out = {f"{name}/{leaf}": spec for name in HALVES
           for leaf, spec in half.items()}
    out.update({"moe/router": ((d, outputs(c)), d ** -0.5),
                "moe/bias": ((outputs(c),), _BIAS_STD),
                "moe/wi": ((held, d, 2 * fe), d ** -0.5),
                "moe/wo": ((held, fe, d), fe ** -0.5)})
    return out


def specs(c):
    """{leaf name: (shape, std or None for ones)} of the whole tree."""
    d, v = c["hidden_size"], c["vocab_size"]
    out = {"embed": ((v, d), 0.02), "final_norm/scale": ((d,), None),
           "w_out": ((d, v), d ** -0.5)}
    for i in range(c["num_layers"]):
        out.update({f"layers/{i}/{name}": spec
                    for name, spec in layer_specs(c).items()})
    return out


def leaf(key, name, layer, shape, std, dtype, offset=0):
    """One weight: ``name`` the short name (``half_1/attn/wq_a``), ``layer``
    the double layer's index (traced or not; ignored outside the stack),
    ``offset`` the first expert held (it seeds the experts' matrices, so
    that two shares do not hold the same experts under two numbers)."""
    if std is None:
        return jnp.ones(shape, jnp.float32)
    half, _, rest = name.partition("/")
    short, which = (rest, HALVES.index(half)) if half in HALVES \
        else (name, 0)
    k = jax.random.fold_in(key, _LEAF_IDS[short])
    if short not in ("embed", "w_out"):
        k = jax.random.fold_in(k, 2 * layer + which)
    if short in ("moe/wi", "moe/wo"):
        k = jax.random.fold_in(k, offset)
    out = jax.random.normal(k, shape, jnp.float32) * std
    return out if short in _FLOAT32 else out.astype(dtype)


def layer_leaves(c, key, layer, dtype):
    """The leaves of double layer ``layer`` (traced or not), {short name:
    array}."""
    return {name: leaf(key, name, layer, shape, std, dtype,
                       c.get("experts_offset", 0))
            for name, (shape, std) in layer_specs(c).items()}


def make_tree(c, seed: int, dtype=jnp.bfloat16):
    """The whole served tree, a jitted call a layer (the float32
    temporaries are one layer's; the layers share the program)."""
    key = weights.seed_key(seed)
    top = specs(c)
    flat = {name: jax.jit(lambda k, name=name: leaf(
        k, name, 0, *top[name], dtype))(key) for name in ("embed", "w_out")}
    flat["final_norm/scale"] = jnp.ones((c["hidden_size"],), jnp.float32)
    program = jax.jit(lambda k, layer: layer_leaves(c, k, layer, dtype))
    for i in range(c["num_layers"]):
        flat.update({f"layers/{i}/{name}": a for name, a in
                     program(key, jnp.int32(i)).items()})
    return weights.unflatten(flat)


def tree_shapes(c, dtype=jnp.bfloat16):
    """{leaf name: (shape, dtype)} as the served tree has them."""
    return {name: (shape, jnp.dtype(
        jnp.float32 if std is None or name.endswith(_FLOAT32) else dtype))
        for name, (shape, std) in specs(c).items()}
