"""The plain reference: a dense grouped-query decoder (RMSNorm, rotary
positions in the rotate-half form, SwiGLU, no biases) in straightforward
``jax.numpy`` and float32, no cache, no kernels, no batching.

It imports nothing of the program and takes nothing the program made: its
weights come from ``weights.leaf`` with the run's seed, one layer at a time,
upcast to float32.  Matrix multiplications run under
``default_matmul_precision("highest")``; attention is computed in blocks of
query rows so that a 6k context fits beside nothing else.

``quantize`` is the control of the correctness check, not a way to serve:
each matmul weight is rounded to a lower precision with one scale per output
channel and multiplied back, everything else as above.  ``"int8"`` is
symmetric int8 (the scheme the program's own ``quantize: int8`` uses),
``"fp8"`` float8_e4m3.

Departures from the published models, both noted in the configuration
files: InternLM2 stores q, k, v as one packed matrix (layout only), and the
program's RMSNorm eps is a constant 1e-6 where the reference uses the
published value.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import weights

_Q_BLOCK = 512


def _fake_int8(w, contract_axes):
    """Round to int8 with a scale per output channel (every axis that is
    not contracted), and multiply back."""
    amax = jnp.max(jnp.abs(w), axis=contract_axes, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


def _fake_fp8(w, contract_axes):
    """Scale each output channel to float8_e4m3's range, round, and
    multiply back."""
    amax = jnp.max(jnp.abs(w), axis=contract_axes, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x [t, heads, d]; position i is row i."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v):
    """Causal, grouped: q [t, h, d], k/v [t, kv, d] -> [t, h, d]."""
    t, h, d = q.shape
    q_block = min(_Q_BLOCK, t)
    group = h // k.shape[1]
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    cols = jnp.arange(t)

    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, q_block, 0)
        s = jnp.einsum("qhd,khd->hqk", qb, k) / np.sqrt(d)
        rows = start + jnp.arange(q_block)
        s = jnp.where(cols[None, None, :] <= rows[None, :, None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)

    starts = jnp.arange(0, t, q_block)
    return jax.lax.map(block, starts).reshape(t, h, d)


def _layer(c, x, w):
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    y = _rms_norm(x, w["attn_norm/scale"], eps)
    q = _rope(jnp.einsum("te,ehd->thd", y, w["attn/wq"]), theta)
    k = _rope(jnp.einsum("te,ehd->thd", y, w["attn/wkv"][0]), theta)
    v = jnp.einsum("te,ehd->thd", y, w["attn/wkv"][1])
    x = x + jnp.einsum("thd,hde->te", _attention(q, k, v), w["attn/wo"])
    y = _rms_norm(x, w["mlp_norm/scale"], eps)
    gate = jnp.einsum("te,ef->tf", y, w["mlp/wi"][0])
    up = jnp.einsum("te,ef->tf", y, w["mlp/wi"][1])
    return x + jnp.einsum("tf,fe->te", jax.nn.silu(gate) * up, w["mlp/wo"])


# Which axes each matmul weight contracts over (for the int8 control).
_CONTRACT = {"attn/wq": (0,), "attn/wkv": (1,), "attn/wo": (0, 1),
             "mlp/wi": (1,), "mlp/wo": (0,), "w_out": (0,)}


def _prepare(name, w, quantize):
    w = w.astype(jnp.float32)
    if quantize is not None and name in _CONTRACT:
        fake = {"int8": _fake_int8, "fp8": _fake_fp8}[quantize]
        w = fake(w, _CONTRACT[name])
    return w


class Reference:
    """Logits of one configuration on one seed's weights."""

    def __init__(self, published, seed, dtype=jnp.bfloat16, quantize=None):
        self.c = dict(published)
        self.key = weights.seed_key(seed)
        self.dtype = dtype
        self.quantize = quantize
        c, spec = self.c, weights.specs(published)

        @jax.jit
        def embed_rows(key, tokens):
            shape, std = spec["embed"]
            table = weights.leaf(key, "embed", 0, shape, std, dtype)
            return table[tokens].astype(jnp.float32)

        @jax.jit
        def layer(key, i, x):
            with jax.default_matmul_precision("highest"):
                w = {n: _prepare(n, a, quantize) for n, a in
                     weights.layer_leaves(c, key, i, dtype).items()}
                return _layer(c, x, w)

        @functools.partial(jax.jit, static_argnames=("rows",))
        def head(key, x, start, rows):
            with jax.default_matmul_precision("highest"):
                x = jax.lax.dynamic_slice_in_dim(x, start, rows, 0)
                shape, std = spec["final_norm/scale"]
                x = _rms_norm(
                    x, weights.leaf(key, "final_norm/scale", 0, shape, std,
                                    dtype), c["rms_norm_eps"])
                if c.get("tie_word_embeddings"):
                    shape, std = spec["embed"]
                    out = weights.leaf(key, "embed", 0, shape, std, dtype).T
                else:
                    shape, std = spec["w_out"]
                    out = weights.leaf(key, "w_out", 0, shape, std, dtype)
                return x @ _prepare("w_out", out, quantize)

        self._embed, self._layer, self._head = embed_rows, layer, head

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
        for i in range(self.c["num_hidden_layers"]):
            x = self._layer(self.key, jnp.int32(i), x)
        return self._head(self.key, x, jnp.int32(start), rows)


def served_gaps(ref_logits, served):
    """For each served token, how far its reference logit lies below the
    reference's best at that position (0 where the served token IS the
    reference's best)."""
    ref_logits = np.asarray(ref_logits, np.float32)
    served = np.asarray(served)
    picked = ref_logits[np.arange(served.shape[0]), served]
    return ref_logits.max(-1) - picked
