"""Seeded weights of a dense grouped-query decoder, made on the device.

One function, ``leaf``, defines every weight from (seed, leaf name, layer).
The served tree is that function mapped over the layers inside one jitted
call, in the type the model is served in; the plain reference calls the same
function for one layer at a time, so it holds the same values without taking
anything the program has touched.

Names and shapes follow the layout the repo's ``Transformer`` uses (layers
stacked on a leading axis); ``run`` checks them against the program's own
``jax.eval_shape`` before anything is made.  Scales are 1/sqrt(true fan-in)
so that attention scores and logits are O(1), not the program's initialiser.
"""

import jax
import jax.numpy as jnp

_LEAF_IDS = {"embed": 0, "attn/wq": 1, "attn/wkv": 2, "attn/wo": 3,
             "mlp/wi": 4, "mlp/wo": 5, "w_out": 6}


def specs(c):
    """{leaf name: (shape without the layer axis, std or None for ones)}"""
    d, h, kv = c["hidden_size"], c["num_attention_heads"], \
        c["num_key_value_heads"]
    hd, f, v = c["head_dim"], c["intermediate_size"], c["vocab_size"]
    out = {
        "embed": ((v, d), 0.02),
        "layers/attn_norm/scale": ((d,), None),
        "layers/attn/wq": ((d, h, hd), d ** -0.5),
        "layers/attn/wkv": ((2, d, kv, hd), d ** -0.5),
        "layers/attn/wo": ((h, hd, d), (h * hd) ** -0.5),
        "layers/mlp_norm/scale": ((d,), None),
        "layers/mlp/wi": ((2, d, f), d ** -0.5),
        "layers/mlp/wo": ((f, d), f ** -0.5),
        "final_norm/scale": ((d,), None),
    }
    if not c.get("tie_word_embeddings"):
        out["w_out"] = ((d, v), d ** -0.5)
    return out


def seed_key(seed: int):
    """A key from any non-negative whole number (the driver's seeds pass
    2**31; ``jax.random.key`` alone keeps only the low 32 bits)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def leaf(key, name: str, layer, shape, std, dtype):
    """One weight: ``layer`` is the layer index (traced or not), ignored
    for leaves outside the stack."""
    if std is None:
        return jnp.ones(shape, jnp.float32)
    short = name.removeprefix("layers/")
    k = jax.random.fold_in(key, _LEAF_IDS[short])
    if name.startswith("layers/"):
        k = jax.random.fold_in(k, layer)
    return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)


def layer_leaves(c, key, layer, dtype):
    """The leaves of one layer, {short name: array}."""
    return {name.removeprefix("layers/"): leaf(key, name, layer, shape, std,
                                               dtype)
            for name, (shape, std) in specs(c).items()
            if name.startswith("layers/")}


def make_tree(c, seed: int, dtype=jnp.bfloat16):
    """The whole served tree in one jitted call, layer by layer inside it
    so that the float32 temporaries are one layer's."""
    n = c["num_hidden_layers"]

    @jax.jit
    def build(key):
        flat = {name: leaf(key, name, 0, shape, std, dtype)
                for name, (shape, std) in specs(c).items()
                if not name.startswith("layers/")}
        stacked = jax.lax.map(
            lambda i: layer_leaves(c, key, i, dtype), jnp.arange(n))
        flat.update({"layers/" + k: v for k, v in stacked.items()})
        return flat

    return unflatten(build(seed_key(seed)))


def unflatten(flat):
    tree = {}
    for name, value in flat.items():
        node = tree
        *parents, last = name.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = value
    return tree


def tree_shapes(c, dtype=jnp.bfloat16):
    """{leaf name: (stacked shape, dtype)} as the served tree has them."""
    n = c["num_hidden_layers"]
    out = {}
    for name, (shape, std) in specs(c).items():
        full = ((n,) + shape) if name.startswith("layers/") else shape
        out[name] = (full, jnp.dtype(jnp.float32 if std is None else dtype))
    return out
