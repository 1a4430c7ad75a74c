"""The plain reference of an LFM2-MoE decoder (``model_type: lfm2_moe``,
``configs/lfm2-24b-a2b-l10.json``): ``tests/reference_lfm2.py``'s equations,
computed a layer at a time and an expert at a time so that the published
widths fit beside nothing else.  float32,
``default_matmul_precision("highest")``, one sequence, no cache, no batching,
no sorting: the experts are a loop over a dense mask.

With ``N`` an RMSNorm (eps ``norm_eps``, a scale) and ``x`` the stream:

    every layer:  x = x + Op(N_op(x));  x = x + FF(N_ff(x))
    after the last layer N_final, then the head (the embedding, tied)

    Op of a "full_attention" layer:
      q, k, v = y Wq, y Wk, y Wv;  q, k = N_q(q), N_k(k) per head;
      rotary positions (half-split form) on q and k; causal grouped
      attention at head_dim ** -0.5;  Wo
    Op of a "conv" layer:
      [B, C, h] = y W_in;  u = B * h;
      c_t = sum_i w_i u_{t - (K - 1) + i}   (per channel, u zero before the
      sequence, K = conv_L_cache taps, no bias);  Op = (C * c) W_out
    FF of the first num_dense_layers layers: W_2 (silu(W_1 y) * W_3 y)
    FF of the others: s = sigmoid(y W_r); the num_experts_per_tok experts
      with the largest s + b are chosen (b selects and does not weigh);
      g_i = s_i / (sum of the chosen s + 1e-6) * routed_scaling_factor;
      FF = sum_i g_i E_i(y), each E_i a SwiGLU at moe_intermediate_size

It imports nothing of the program and takes nothing the program made: the
weights come from ``weights_lfm2.leaf`` by the run's seed, a layer at a time.

``quantize`` is the control of the correctness check (``reference.py``):
every matmul weight rounded to int8 or float8_e4m3 with a scale per output
channel.  The router, the bias and the convolution's taps are left as they
are: a deployment in a lower precision keeps them, and the control is then
the harder one to tell from a sound run.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import weights_lfm2
from .reference import _fake_fp8, _fake_int8, _rms_norm, _rope, served_gaps

__all__ = ["Reference", "served_gaps"]

# Which axes each matmul weight contracts over (for the controls).
_CONTRACT = {"attn/wq": (0,), "attn/wkv": (1,), "attn/wo": (0, 1),
             "mlp/wi": (1,), "mlp/wo": (0,), "conv/w_in": (0,),
             "conv/w_out": (0,), "moe/wi": (1,), "moe/wo": (1,),
             "w_out": (0,)}


def _prepare(name, w, quantize):
    w = w.astype(jnp.float32)
    if quantize is not None and name in _CONTRACT:
        fake = {"int8": _fake_int8, "fp8": _fake_fp8}[quantize]
        w = fake(w, _CONTRACT[name])
    return w


def _attention(q, k, v):
    """Causal, grouped: q [t, h, d], k / v [t, kv, d] -> [t, h, d]."""
    t, h, d = q.shape
    group = h // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(d)
    s = jnp.where(jnp.arange(t)[None, :] <= jnp.arange(t)[:, None], s,
                  -jnp.inf)
    return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)


def _attention_operator(c, y, w):
    eps, theta = c["norm_eps"], c["rope_theta"]
    q = jnp.einsum("te,ehd->thd", y, w["attn/wq"])
    k = jnp.einsum("te,ehd->thd", y, w["attn/wkv"][0])
    v = jnp.einsum("te,ehd->thd", y, w["attn/wkv"][1])
    q = _rope(_rms_norm(q, w["attn/q_norm/scale"], eps), theta)
    k = _rope(_rms_norm(k, w["attn/k_norm/scale"], eps), theta)
    return jnp.einsum("thd,hde->te", _attention(q, k, v), w["attn/wo"])


def _conv_operator(c, y, w):
    taps, t = c["conv_L_cache"], y.shape[0]
    b, gate, h = (y @ w["conv/w_in"][:, i] for i in range(3))
    u = jnp.concatenate([jnp.zeros((taps - 1, y.shape[1])), b * h])
    conv = sum(w["conv/w_conv"][i] * u[i:i + t] for i in range(taps))
    return (gate * conv) @ w["conv/w_out"]


def _swiglu(y, w_gate, w_up, w_down):
    return (jax.nn.silu(y @ w_gate) * (y @ w_up)) @ w_down


def _sparse_ff(c, y, w):
    k, f = c["num_experts_per_tok"], c["moe_intermediate_size"]
    s = jax.nn.sigmoid(y @ w["moe/router"])
    chosen = jnp.argsort(-(s + w["moe/bias"]), axis=-1)[:, :k]
    mask = jnp.zeros_like(s).at[jnp.arange(y.shape[0])[:, None],
                                chosen].set(1.0)
    g = mask * s
    g = g / (g.sum(-1, keepdims=True) + 1e-6) * c["routed_scaling_factor"]

    def one(out, expert):  # an expert at a time, over every row
        wi, wo, gate = expert
        return out + gate[:, None] * _swiglu(y, wi[:, :f], wi[:, f:],
                                             wo), None

    return jax.lax.scan(one, jnp.zeros_like(y),
                        (w["moe/wi"], w["moe/wo"], g.T))[0]


def _layer(c, i, x, w):
    eps = c["norm_eps"]
    if c["layer_types"][i] == "conv":
        x = x + _conv_operator(
            c, _rms_norm(x, w["conv_norm/scale"], eps), w)
    else:
        x = x + _attention_operator(
            c, _rms_norm(x, w["attn_norm/scale"], eps), w)
    y = _rms_norm(x, w["mlp_norm/scale"], eps)
    if i < c["num_dense_layers"]:
        return x + _swiglu(y, w["mlp/wi"][0], w["mlp/wi"][1], w["mlp/wo"])
    return x + _sparse_ff(c, y, w)


class Reference:
    """Logits of one configuration on one seed's weights."""

    def __init__(self, published, seed, dtype=jnp.bfloat16, quantize=None):
        self.c = c = dict(published)
        self.key = weights_lfm2.weights.seed_key(seed)
        embed_spec = weights_lfm2.specs(c)["embed"]

        def embed(key):
            return weights_lfm2.leaf(key, "embed", 0, *embed_spec, dtype)

        @jax.jit
        def embed_rows(key, tokens):
            return embed(key)[tokens].astype(jnp.float32)

        @functools.cache
        def layer_program(i):
            # Layers of one kind share a program: ``i``, the first of the
            # kind, says what the layer holds; the traced ``layer`` seeds
            # its weights.
            @jax.jit
            def run(key, layer, x):
                with jax.default_matmul_precision("highest"):
                    w = {n: _prepare(n, a, quantize) for n, a in
                         weights_lfm2.layer_leaves(c, key, i, dtype,
                                                   layer).items()}
                    return _layer(c, i, x, w)

            return run

        @functools.partial(jax.jit, static_argnames=("rows",))
        def head(key, x, start, rows):
            with jax.default_matmul_precision("highest"):
                x = jax.lax.dynamic_slice_in_dim(x, start, rows, 0)
                x = _rms_norm(x, jnp.ones((c["hidden_size"],)),
                              c["norm_eps"])
                return x @ _prepare("w_out", embed(key).T, quantize)

        self._embed, self._head = embed_rows, head
        first_of = {}
        for i in range(c["num_hidden_layers"]):
            first_of.setdefault(weights_lfm2._kind(c, i), i)
        self._layer = lambda i: layer_program(
            first_of[weights_lfm2._kind(c, i)])

    def logits(self, tokens, start, rows, pad_to):
        """float32 logits [rows, vocab] of positions start..start+rows-1 of
        ``tokens`` (1-D), computed at the static length ``pad_to`` (causal:
        a position never sees the padding behind it)."""
        tokens = np.asarray(tokens, np.int32)
        padded = np.zeros((pad_to,), np.int32)
        padded[:tokens.shape[0]] = tokens
        x = self._embed(self.key, jnp.asarray(padded))
        for i in range(self.c["num_hidden_layers"]):
            x = self._layer(i)(self.key, jnp.int32(i), x)
        return self._head(self.key, x, jnp.int32(start), rows)
