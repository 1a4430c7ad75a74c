"""The plain reference of a looped decoder (Ouro, arXiv:2510.25741): the
dense block of ``reference.py`` (its norm, rotary positions, blocked causal
attention and the int8 / fp8 controls are taken from there), with what the
looped model changes written out here:

    x = embed[tokens]
    for t in range(total_ut_steps):          # the SAME layers' weights
      for l in range(num_hidden_layers):
        a = N(x);  q, k, v = a Wq_l, a Wk_l, a Wv_l;  q, k = rope(q), rope(k)
        x = x + N(attention(q, k, v) Wo_l)   # sandwich_norm: the branch's
        m = N(x)                             # OUTPUT is normed as well
        x = x + N((silu(m Wg_l) * (m Wu_l)) Wd_l)
      x = N_final(x)                         # after EVERY step; it feeds
    logits = x W_out                         # step t + 1

float32, ``default_matmul_precision("highest")``, no cache: every (loop step,
layer) pair attends the keys and values this very pass made, which is what
192 cache planes of their own have to reproduce.  The exit gate is not read
(``early_exit_threshold`` 1: every token takes every step).  It imports
nothing of the program; the weights come from ``weights_looped.leaf`` by the
run's seed, a layer at a time.  With ``total_ut_steps`` 1 and no
``sandwich_norm`` this is ``reference.py``'s decoder, number for number
(``tests/test_counts_looped.py``).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import weights_looped
from .reference import (_Q_BLOCK, _attention, _prepare, _rms_norm, _rope,
                        served_gaps)

__all__ = ["Reference", "served_gaps"]


def _layer(c, x, w):
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    y = _rms_norm(x, w["attn_norm/scale"], eps)
    q = _rope(jnp.einsum("te,ehd->thd", y, w["attn/wq"]), theta)
    k = _rope(jnp.einsum("te,ehd->thd", y, w["attn/wkv"][0]), theta)
    v = jnp.einsum("te,ehd->thd", y, w["attn/wkv"][1])
    y = jnp.einsum("thd,hde->te", _attention(q, k, v), w["attn/wo"])
    if c.get("sandwich_norm"):
        y = _rms_norm(y, w["attn_out_norm/scale"], eps)
    x = x + y
    y = _rms_norm(x, w["mlp_norm/scale"], eps)
    gate = jnp.einsum("te,ef->tf", y, w["mlp/wi"][0])
    up = jnp.einsum("te,ef->tf", y, w["mlp/wi"][1])
    y = jnp.einsum("tf,fe->te", jax.nn.silu(gate) * up, w["mlp/wo"])
    if c.get("sandwich_norm"):
        y = _rms_norm(y, w["mlp_out_norm/scale"], eps)
    return x + y


class Reference:
    """Logits of one configuration on one seed's weights."""

    def __init__(self, published, seed, dtype=jnp.bfloat16, quantize=None):
        self.c = dict(published)
        self.key = weights_looped.weights.seed_key(seed)
        c, spec = self.c, weights_looped.specs(published)

        def leaf(key, name):
            shape, std = spec[name]
            return weights_looped.leaf(key, name, 0, shape, std, dtype)

        @jax.jit
        def embed_rows(key, tokens):
            return leaf(key, "embed")[tokens].astype(jnp.float32)

        @jax.jit
        def layer(key, i, x):
            with jax.default_matmul_precision("highest"):
                w = {n: _prepare(n, a, quantize) for n, a in
                     weights_looped.layer_leaves(c, key, i, dtype).items()}
                return _layer(c, x, w)

        @jax.jit
        def final_norm(key, x):
            return _rms_norm(x, leaf(key, "final_norm/scale"),
                             c["rms_norm_eps"])

        @functools.partial(jax.jit, static_argnames=("rows",))
        def head(key, x, start, rows):
            with jax.default_matmul_precision("highest"):
                x = jax.lax.dynamic_slice_in_dim(x, start, rows, 0)
                out = leaf(key, "embed").T if c.get("tie_word_embeddings") \
                    else leaf(key, "w_out")
                return x @ _prepare("w_out", out, quantize)

        self._embed, self._layer = embed_rows, layer
        self._final_norm, self._head = final_norm, head

    def logits(self, tokens, start, rows, pad_to):
        """float32 logits [rows, vocab] of positions start..start+rows-1 of
        ``tokens`` (1-D), computed at the static length ``pad_to`` (causal:
        a position never sees the padding behind it)."""
        tokens = np.asarray(tokens, np.int32)
        if pad_to > _Q_BLOCK:  # whole blocks of query rows
            pad_to = -(-pad_to // _Q_BLOCK) * _Q_BLOCK
        padded = np.zeros((pad_to,), np.int32)
        padded[:tokens.shape[0]] = tokens
        x = self._embed(self.key, jnp.asarray(padded))
        for _ in range(self.c.get("total_ut_steps", 1)):
            for i in range(self.c["num_hidden_layers"]):
                x = self._layer(self.key, jnp.int32(i), x)
            x = self._final_norm(self.key, x)
        return self._head(self.key, x, jnp.int32(start), rows)
