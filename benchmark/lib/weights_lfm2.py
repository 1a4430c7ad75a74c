"""Seeded weights of an LFM2-MoE decoder (``configs/lfm2-24b-a2b-l10.json``),
made on the device: a tree with ONE ENTRY A LAYER (``layers/<i>/...``), for
no two layers need hold the same leaves: a gated short convolution or an
attention operator with q / k norms, a dense SwiGLU or a router, its bias
and the experts.  Names and shapes follow the program's tree
(``models/transformer.py layer_tree_shapes``; the runner checks them against
``jax.eval_shape`` of the program's own init before anything is made).

As in ``weights.py`` one function, ``leaf``, defines every value from (seed,
leaf name, layer), so the plain reference regenerates a layer without taking
anything the program has touched.  Scales are 1/sqrt(true fan-in); norm
scales are ones, as the other configurations'; the convolution's taps are
drawn at conv_L_cache ** -0.5; the expert bias is SEEDED at 0.1 and not zero
(about the distance between the largest sigmoid scores of 64 experts), so
that a bias left out of the choice, or let into the weights, shows.  Matmul
weights are made in the served type; the taps, the router and the bias stay
float32, as the program serves them (``ops/quantize.py CONTRACTIONS``).
"""

import jax
import jax.numpy as jnp

from . import weights

# After weights._LEAF_IDS and weights_looped's.
_LEAF_IDS = {"embed": 0, "attn/wq": 1, "attn/wkv": 2, "attn/wo": 3,
             "mlp/wi": 4, "mlp/wo": 5, "conv/w_in": 11, "conv/w_conv": 12,
             "conv/w_out": 13, "moe/router": 14, "moe/bias": 15,
             "moe/wi": 16, "moe/wo": 17}
_FLOAT32 = ("conv/w_conv", "moe/router", "moe/bias")
_BIAS_STD = 0.1


def layer_specs(c, i):
    """{short leaf name: (shape, std or None for ones)} of layer ``i``."""
    d, h, kv = c["hidden_size"], c["num_attention_heads"], \
        c["num_key_value_heads"]
    hd, taps = c["head_dim"], c["conv_L_cache"]
    out = {"mlp_norm/scale": ((d,), None)}
    if c["layer_types"][i] == "conv":
        out.update({
            "conv_norm/scale": ((d,), None),
            "conv/w_in": ((d, 3, d), d ** -0.5),
            "conv/w_conv": ((taps, d), taps ** -0.5),
            "conv/w_out": ((d, d), d ** -0.5)})
    else:
        out.update({
            "attn_norm/scale": ((d,), None),
            "attn/wq": ((d, h, hd), d ** -0.5),
            "attn/wkv": ((2, d, kv, hd), d ** -0.5),
            "attn/wo": ((h, hd, d), (h * hd) ** -0.5),
            "attn/q_norm/scale": ((hd,), None),
            "attn/k_norm/scale": ((hd,), None)})
    if i < c["num_dense_layers"]:
        f = c["intermediate_size"]
        out.update({"mlp/wi": ((2, d, f), d ** -0.5),
                    "mlp/wo": ((f, d), f ** -0.5)})
    else:
        n, f = c["num_experts"], c["moe_intermediate_size"]
        out.update({"moe/router": ((d, n), d ** -0.5),
                    "moe/bias": ((n,), _BIAS_STD),
                    "moe/wi": ((n, d, 2 * f), d ** -0.5),
                    "moe/wo": ((n, f, d), f ** -0.5)})
    return out


def specs(c):
    """{leaf name: (shape, std or None for ones)} of the whole tree."""
    d, v = c["hidden_size"], c["vocab_size"]
    out = {"embed": ((v, d), 0.02), "final_norm/scale": ((d,), None)}
    for i in range(c["num_hidden_layers"]):
        out.update({f"layers/{i}/{name}": spec
                    for name, spec in layer_specs(c, i).items()})
    return out


def leaf(key, name, layer, shape, std, dtype):
    """One weight: ``name`` the short name, ``layer`` the layer's index
    (traced or not; ignored for ``embed``)."""
    if std is None:
        return jnp.ones(shape, jnp.float32)
    k = jax.random.fold_in(key, _LEAF_IDS[name])
    if name != "embed":
        k = jax.random.fold_in(k, layer)
    out = jax.random.normal(k, shape, jnp.float32) * std
    return out if name in _FLOAT32 else out.astype(dtype)


def layer_leaves(c, key, i, dtype, layer=None):
    """The leaves of layer ``i`` (a Python int: it says what the layer
    holds), {short name: array}; ``layer`` may be the same index traced, so
    that layers of one kind share a compiled program."""
    layer = i if layer is None else layer
    return {name: leaf(key, name, layer, shape, std, dtype)
            for name, (shape, std) in layer_specs(c, i).items()}


def _kind(c, i):
    return c["layer_types"][i], i < c["num_dense_layers"]


def make_tree(c, seed: int, dtype=jnp.bfloat16):
    """The whole served tree, a jitted call a layer (the float32
    temporaries are one layer's; layers of one kind share the program)."""
    key = weights.seed_key(seed)
    programs = {}
    flat = {"embed": jax.jit(lambda k: leaf(
        k, "embed", 0, *specs(c)["embed"], dtype))(key),
            "final_norm/scale": jnp.ones((c["hidden_size"],), jnp.float32)}
    for i in range(c["num_hidden_layers"]):
        if _kind(c, i) not in programs:
            programs[_kind(c, i)] = jax.jit(
                lambda k, layer, i=i: layer_leaves(c, k, i, dtype, layer))
        made = programs[_kind(c, i)](key, jnp.int32(i))
        flat.update({f"layers/{i}/{name}": a for name, a in made.items()})
    return weights.unflatten(flat)


def tree_shapes(c, dtype=jnp.bfloat16):
    """{leaf name: (shape, dtype)} as the served tree has them."""
    return {name: (shape, jnp.dtype(
        jnp.float32 if std is None or name.split("/", 2)[-1] in _FLOAT32
        else dtype)) for name, (shape, std) in specs(c).items()}
