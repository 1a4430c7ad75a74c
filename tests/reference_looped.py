"""The plain reference of a looped decoder (Ouro, arXiv:2510.25741): ONE
stack of layers run ``loop_steps`` times a token over the same weights,
sandwich norms, the final norm after every loop step.

Straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: no cache, no scan, no kernel, one
sequence at a time, and nothing imported from the program.  It reads the
program's parameter TREE (plain arrays under the program's names, layers
stacked on a leading axis) and nothing else of it.

    x = embed[tokens]
    for t in range(loop_steps):              # the same weights every time
      for l in range(n_layers):
        a = N(x; attn_norm_l);  q, k, v = a Wq_l, a Wk_l, a Wv_l
        q, k = rope(q), rope(k)              # rotate-half
        o = softmax(q k^T / sqrt(d), causal) v
        x = x + N(o Wo_l; attn_out_norm_l)   # sandwich: the OUTPUT is normed too
        m = N(x; mlp_norm_l)
        x = x + N((silu(m Wg_l) * (m Wu_l)) Wd_l; mlp_out_norm_l)
      x = N(x; final_norm)                   # after EVERY step; feeds step t + 1
    logits = x W_out

No cache means every (step, layer) pair attends the keys and values that
this very pass of this very step made: a program that shares a cache plane
between steps, drops a step or leaves a norm out computes something else.
The exit gate is not read (threshold 1: every token takes every step).
"""

import jax
import jax.numpy as jnp
import numpy as np


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, theta):
    """x [t, heads, d]; row i is position i."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v):
    """Causal; q [t, h, d], k and v [t, kv, d] with h a multiple of kv."""
    t, h, d = q.shape
    k = jnp.repeat(k, h // k.shape[1], axis=1)
    v = jnp.repeat(v, h // v.shape[1], axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(d)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -jnp.inf)
    return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)


def layer(x, w, eps, theta, sandwich):
    """One block; ``w`` holds one layer's leaves."""
    a = rms_norm(x, w["attn_norm"]["scale"], eps)
    q = rope(jnp.einsum("te,ehd->thd", a, w["attn"]["wq"]), theta)
    k = rope(jnp.einsum("te,ehd->thd", a, w["attn"]["wkv"][0]), theta)
    v = jnp.einsum("te,ehd->thd", a, w["attn"]["wkv"][1])
    o = jnp.einsum("thd,hde->te", attention(q, k, v), w["attn"]["wo"])
    if sandwich:
        o = rms_norm(o, w["attn_out_norm"]["scale"], eps)
    x = x + o
    m = rms_norm(x, w["mlp_norm"]["scale"], eps)
    f = jax.nn.silu(m @ w["mlp"]["wi"][0]) * (m @ w["mlp"]["wi"][1])
    f = f @ w["mlp"]["wo"]
    if sandwich:
        f = rms_norm(f, w["mlp_out_norm"]["scale"], eps)
    return x + f


def logits(params, tokens, *, n_layers, loop_steps, eps, theta, sandwich,
           norm_between_steps=True):
    """float32 logits [t, vocab] of one sequence ``tokens`` [t].
    ``norm_between_steps=False`` is what a program that forgot the norm
    between loop steps would compute (the tests' sabotage case)."""
    w = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        x = w["embed"][jnp.asarray(tokens)]
        for step in range(loop_steps):
            for i in range(n_layers):
                one = jax.tree_util.tree_map(lambda a: a[i], w["layers"])
                x = layer(x, one, eps, theta, sandwich)
            if norm_between_steps or step == loop_steps - 1:
                x = rms_norm(x, w["final_norm"]["scale"], eps)
        out = w["w_out"] if "w_out" in w else w["embed"].T
        return x @ out
