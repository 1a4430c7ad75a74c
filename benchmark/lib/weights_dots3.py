"""Seeded weights of dots3-note-prev's language model
(``configs/dots3-note-prev-l5.json``), made on the device: a tree with ONE
ENTRY A LAYER (``layers/<i>/...``), since a full layer (latent attention with
an indexer), a window layer (a latent of other sizes) and the leading dense
layer hold different leaves.  Names and shapes follow the program's tree
(``models/transformer.py layer_tree_shapes``; the runner checks them against
``jax.eval_shape`` of the program's own init before anything is made).

As in ``weights.py`` one function, ``leaf``, defines every value from (seed,
leaf name, layer), so the plain reference regenerates a layer without taking
anything the program has touched.  Scales are 1/sqrt(true fan-in), where the
fan-in of the matrices behind a low-rank norm (``wq_b``, ``wq_idx``,
``wk_b``, ``wv_b``) counts the factor the configuration puts on that norm's
output (``apply_mla_qkv_lora_rescale``: sqrt(hidden / rank)), so that
queries, keys, index scores and attention scores are O(1); RMSNorm scales and
the index key's LayerNorm scale are ones.  Two leaves are SEEDED where a
checkpoint starts them at zero, so that leaving them out shows: the router's
selection bias (std 0.01, about the distance between the 8th and the 9th
largest sigmoid score of 256 outputs: it changes about one of a row's eight
choices) and the LayerNorm's bias (std 0.02).  The expert layer holds the
routed experts ``[experts_offset, experts_offset + n_routed_experts)`` of
``n_routed_experts_published``; the router and its bias keep every output.
Matmul weights are made in the served type; the router, its bias and the
indexer's per-head weights ``w_idx`` stay float32, as the program serves them
(``ops/quantize.py CONTRACTIONS``).
"""

import jax
import jax.numpy as jnp

from . import weights

# After weights._LEAF_IDS, weights_looped's, weights_lfm2's and
# weights_longcat's.
_LEAF_IDS = {"embed": 0, "attn/wo": 3, "mlp/wi": 4, "mlp/wo": 5, "w_out": 6,
             "moe/router": 14, "moe/bias": 15, "moe/wi": 16, "moe/wo": 17,
             "attn/wq_a": 21, "attn/wq_b": 22, "attn/wkv_a": 23,
             "attn/wk_b": 24, "attn/wv_b": 25, "attn/wg": 31,
             "attn/wq_idx": 32, "attn/wk_idx": 33, "attn/w_idx": 34,
             "attn/k_idx_norm/bias": 35, "moe/shared/wi": 36,
             "moe/shared/wo": 37}
_FLOAT32 = ("moe/router", "moe/bias", "attn/w_idx", "attn/k_idx_norm/bias")
_BIAS_STD = 0.01
_NORM_BIAS_STD = 0.02


def sizes(c, kind):
    """(heads, q rank, kv rank, nope, rope, v) of a layer of ``kind``."""
    at = "swa_" if kind == "sliding_attention" else ""
    return (c[at + "num_attention_heads"], c[at + "q_lora_rank"],
            c[at + "kv_lora_rank"], c[at + "qk_nope_head_dim"],
            c[at + "qk_rope_head_dim"], c[at + "v_head_dim"])


def layer_specs(c, layer: int):
    """{short leaf name: (shape, std or None for ones)} of layer ``layer``."""
    d, kind = c["hidden_size"], c["layer_types"][layer]
    h, rq, rkv, dn, dr, dv = sizes(c, kind)
    out = {
        "attn_norm/scale": ((d,), None),
        "attn/wq_a": ((d, rq), d ** -0.5),
        "attn/q_norm/scale": ((rq,), None),
        # Behind a norm whose output is scaled by sqrt(d / rank): fan-in d.
        "attn/wq_b": ((rq, h, dn + dr), d ** -0.5),
        "attn/wkv_a": ((d, rkv + dr), d ** -0.5),
        "attn/kv_norm/scale": ((rkv,), None),
        "attn/wk_b": ((h, dn, rkv), d ** -0.5),
        "attn/wv_b": ((rkv, h, dv), d ** -0.5),
        "attn/wo": ((h, dv, d), (h * dv) ** -0.5),
        "attn/wg": ((d, h), d ** -0.5),
        "mlp_norm/scale": ((d,), None)}
    if kind == "full_attention":
        hi, di = c["index_n_heads"], c["index_head_dim"]
        out.update({
            "attn/wq_idx": ((rq, hi, di), d ** -0.5),
            "attn/wk_idx": ((d, di), d ** -0.5),
            "attn/k_idx_norm/scale": ((di,), None),
            "attn/k_idx_norm/bias": ((di,), _NORM_BIAS_STD),
            "attn/w_idx": ((d, hi), d ** -0.5)})
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


def specs(c):
    """{leaf name: (shape, std or None for ones)} of the whole tree."""
    d, v = c["hidden_size"], c["vocab_size"]
    out = {"embed": ((v, d), 0.02), "final_norm/scale": ((d,), None),
           "w_out": ((d, v), d ** -0.5)}
    for i in range(c["num_hidden_layers"]):
        out.update({f"layers/{i}/{name}": spec
                    for name, spec in layer_specs(c, i).items()})
    return out


def leaf(key, name, layer, shape, std, dtype, offset=0):
    """One weight: ``name`` the short name (``attn/wq_a``), ``layer`` the
    layer's index (traced or not; ignored outside the stack), ``offset`` the
    first expert held (it seeds the routed experts' matrices, so that two
    shares do not hold the same experts under two numbers)."""
    if std is None:
        return jnp.ones(shape, jnp.float32)
    k = jax.random.fold_in(key, _LEAF_IDS[name])
    if name not in ("embed", "w_out"):
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


def same_leaves(c, layer: int) -> int:
    """The first layer that holds the leaves ``layer`` holds: layers alike
    share one program."""
    return next(i for i in range(layer + 1)
                if layer_specs(c, i) == layer_specs(c, layer))


def make_tree(c, seed: int, dtype=jnp.bfloat16):
    """The whole served tree, a jitted call a layer (the float32
    temporaries are one layer's)."""
    key = weights.seed_key(seed)
    top = specs(c)
    flat = {name: jax.jit(lambda k, name=name: leaf(
        k, name, 0, *top[name], dtype))(key) for name in ("embed", "w_out")}
    flat["final_norm/scale"] = jnp.ones((c["hidden_size"],), jnp.float32)
    program = jax.jit(
        lambda k, layer, like: layer_leaves(c, k, layer, dtype, like),
        static_argnums=2)
    for i in range(c["num_hidden_layers"]):
        made = program(key, jnp.int32(i), same_leaves(c, i))
        flat.update({f"layers/{i}/{name}": a for name, a in made.items()})
    return weights.unflatten(flat)


def tree_shapes(c, dtype=jnp.bfloat16):
    """{leaf name: (shape, dtype)} as the served tree has them."""
    return {name: (shape, jnp.dtype(
        jnp.float32 if std is None or name.endswith(_FLOAT32) else dtype))
        for name, (shape, std) in specs(c).items()}
