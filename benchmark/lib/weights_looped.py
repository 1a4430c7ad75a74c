"""Seeded weights of a looped decoder (Ouro): ``weights.py``'s leaves, one
stack of ``num_hidden_layers`` layers whatever ``total_ut_steps`` says, plus
what the looped block adds to the program's tree: the two branch-output
norms of a sandwich block and the exit gate.

Every value still comes from (seed, leaf name, layer), so the plain
reference regenerates a layer without taking anything the program has
touched.  The norms ``weights.py`` has keep scales of one.  The two
branch-output norms are seeded about ``(total_ut_steps *
num_hidden_layers) ** -0.5``: each of the 192 layer passes then adds a small
branch to the stream, as a trained sandwich model's do, where scales of one
add a branch as large as the stream itself 96 times a loop step and the
stack amplifies bfloat16's rounding until half the served tokens are not the
reference's first choice (PERF.md section 6, PR 26).  Seeded, not constant,
so that a norm read with the other norm's or another layer's scale shows;
a norm LEFT OUT shows whatever the scales (``tests/test_counts_looped.py``).
The gate is held and never read (the configuration's ``departures``); its
weight is seeded like any projection and its bias is zero.
"""

import jax
import jax.numpy as jnp

from . import weights

# After weights._LEAF_IDS.
_GATE_ID = 7
_SEEDED_NORM_IDS = {"layers/attn_out_norm/scale": 8,
                    "layers/mlp_out_norm/scale": 9, "final_norm/scale": 10}


def specs(c):
    """{leaf name: (shape without the layer axis, std or None for ones)}"""
    d = c["hidden_size"]
    out = weights.specs(c)
    if c.get("sandwich_norm"):
        small = (c.get("total_ut_steps", 1) * c["num_hidden_layers"]) ** -0.5
        # In the place of std: the value the scales are seeded about.
        out["layers/attn_out_norm/scale"] = ((d,), small)
        out["layers/mlp_out_norm/scale"] = ((d,), small)
    if c.get("total_ut_steps", 1) > 1:
        out["final_norm/scale"] = ((d,), 1.0)
        out["exit_gate_w"] = ((d, 1), d ** -0.5)
        out["exit_gate_b"] = ((1,), 0.0)
    return out


def leaf(key, name, layer, shape, std, dtype):
    if std is not None and name in _SEEDED_NORM_IDS:
        k = jax.random.fold_in(key, _SEEDED_NORM_IDS[name])
        if name.startswith("layers/"):
            k = jax.random.fold_in(k, layer)
        return std * (1.0 + 0.25 * jax.random.normal(k, shape, jnp.float32))
    if name == "exit_gate_w":
        k = jax.random.fold_in(key, _GATE_ID)
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)
    if name == "exit_gate_b":
        return jnp.zeros(shape, jnp.float32)
    return weights.leaf(key, name, layer, shape, std, dtype)


def layer_leaves(c, key, layer, dtype):
    """The leaves of one layer, {short name: array}."""
    return {name.removeprefix("layers/"): leaf(key, name, layer, shape, std,
                                               dtype)
            for name, (shape, std) in specs(c).items()
            if name.startswith("layers/")}


def make_tree(c, seed: int, dtype=jnp.bfloat16):
    """The whole served tree in one jitted call, layer by layer inside it."""
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

    return weights.unflatten(build(weights.seed_key(seed)))


def tree_shapes(c, dtype=jnp.bfloat16):
    """{leaf name: (stacked shape, dtype)} as the served tree has them."""
    n = c["num_hidden_layers"]
    out = {}
    for name, (shape, std) in specs(c).items():
        full = ((n,) + shape) if name.startswith("layers/") else shape
        plain = std is None or name == "exit_gate_b" \
            or name in _SEEDED_NORM_IDS
        out[name] = (full, jnp.dtype(jnp.float32 if plain else dtype))
    return out
