"""The plain reference of an LFM2-MoE decoder (``model_type: lfm2_moe``),
written from the layer equations of ISSUE 33 and not from the program:
straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``, one sequence, no cache, no
batching, no sorting (the experts are a loop over a dense mask).

With ``N`` an RMSNorm (eps ``norm_eps``, a scale) and ``x`` the stream:

    every layer:  x = x + Op(N_op(x));  x = x + FF(N_ff(x))
    after the last layer N_final, then the head (the embedding, tied)

    Op of a "full_attention" layer:
      q, k, v = y Wq, y Wk, y Wv;  q, k = N_q(q), N_k(k) per head;
      rotary positions (theta, half-split form) on q and k; causal grouped
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

``c`` holds the configuration under its Hugging Face keys; ``tree`` is the
parameter tree in the layout the program serves
(``models/transformer.py layer_tree_shapes``): ``conv/w_in`` is
[hidden, 3, hidden] in the order B, C, h; ``moe/wi`` is [experts, hidden,
2 x moe_intermediate_size], W_1 (gate) then W_3 (up) along the last axis;
``mlp/wi`` and ``attn/wkv`` are pairs on their first axis.
"""

import jax
import jax.numpy as jnp
import numpy as np


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, theta):
    """x [t, heads, d]; position i is row i."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v):
    """Causal, grouped: q [t, h, d], k / v [t, kv, d] -> [t, h, d]."""
    t, h, d = q.shape
    group = h // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(d)
    s = jnp.where(jnp.arange(t)[None, :] <= jnp.arange(t)[:, None], s,
                  -jnp.inf)
    return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)


def attention_operator(c, y, w):
    eps, theta = c["norm_eps"], c["rope_theta"]
    q = jnp.einsum("te,ehd->thd", y, w["wq"])
    k = jnp.einsum("te,ehd->thd", y, w["wkv"][0])
    v = jnp.einsum("te,ehd->thd", y, w["wkv"][1])
    q = rope(rms_norm(q, w["q_norm"]["scale"], eps), theta)
    k = rope(rms_norm(k, w["k_norm"]["scale"], eps), theta)
    return jnp.einsum("thd,hde->te", attention(q, k, v), w["wo"])


def conv_operator(c, y, w):
    taps, t = c["conv_L_cache"], y.shape[0]
    b, gate, h = (jnp.einsum("te,ef->tf", y, w["w_in"][:, i])
                  for i in range(3))
    u = jnp.concatenate([jnp.zeros((taps - 1, y.shape[1])), b * h])
    conv = sum(w["w_conv"][i] * u[i:i + t] for i in range(taps))
    return (gate * conv) @ w["w_out"]


def swiglu(y, w_gate, w_up, w_down):
    return (jax.nn.silu(y @ w_gate) * (y @ w_up)) @ w_down


def sparse_ff(c, y, w):
    n, k, f = (c["num_experts"], c["num_experts_per_tok"],
               c["moe_intermediate_size"])
    s = jax.nn.sigmoid(y @ w["router"])
    chosen = jnp.argsort(-(s + w["bias"]), axis=-1)[:, :k]
    mask = jnp.zeros_like(s).at[jnp.arange(y.shape[0])[:, None],
                                chosen].set(1.0)
    g = mask * s
    g = g / (g.sum(-1, keepdims=True) + 1e-6) * c["routed_scaling_factor"]
    out = jnp.zeros_like(y)
    for i in range(n):
        out = out + g[:, i:i + 1] * swiglu(
            y, w["wi"][i, :, :f], w["wi"][i, :, f:], w["wo"][i])
    return out


def layer(c, i, x, w):
    eps = c["norm_eps"]
    if c["layer_types"][i] == "conv":
        x = x + conv_operator(
            c, rms_norm(x, w["conv_norm"]["scale"], eps), w["conv"])
    else:
        x = x + attention_operator(
            c, rms_norm(x, w["attn_norm"]["scale"], eps), w["attn"])
    y = rms_norm(x, w["mlp_norm"]["scale"], eps)
    if i < c["num_dense_layers"]:
        return x + swiglu(y, w["mlp"]["wi"][0], w["mlp"]["wi"][1],
                          w["mlp"]["wo"])
    return x + sparse_ff(c, y, w["moe"])


def forward(c, tree, tokens):
    """tokens [t] -> float32 logits [t, vocab]."""
    with jax.default_matmul_precision("highest"):
        tree = jax.tree_util.tree_map(
            lambda a: jnp.asarray(a, jnp.float32), tree)
        x = tree["embed"][jnp.asarray(tokens)]
        for i in range(c["num_hidden_layers"]):
            x = layer(c, i, x, tree["layers"][str(i)])
        x = rms_norm(x, tree["final_norm"]["scale"], c["norm_eps"])
        return x @ tree["embed"].T
