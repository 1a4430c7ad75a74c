"""The plain reference of LongCat-Flash's language model (the decoder that
``LongCat-Flash-Omni``'s ``config.json`` states), written from the layer
equations of ISSUE 37 and not from the program: straightforward
``jax.numpy`` in float32 under ``default_matmul_precision("highest")``, one
sequence, no cache, no batching, no sorting (the experts are a loop over a
dense mask), keys and values expanded from the latent for every position.

With ``N`` an RMSNorm (eps ``rms_norm_eps``, a scale) and ``x`` the stream,
a double layer is

    a = x + MLA_0(N_0(x));   m = N'_0(a);   s = Experts(m)
    b = a + Dense_0(m);      c = b + MLA_1(N_1(b))
    y = c + Dense_1(N'_1(c)) + s

``Dense(u) = (silu(u W_g) * (u W_u)) W_d`` at ``ffn_hidden_size``; after the
last layer ``N_final``, then an untied head.  ``MLA(u)``, no bias:

    q = (N_q(u W_qa) * sqrt(hidden / q_lora_rank)) W_qb  -> per head
        (q_nope [qk_nope_head_dim], q_rope [qk_rope_head_dim])
    (l, k_r) = split(u W_kva, [kv_lora_rank, qk_rope_head_dim])
    c = N_kv(l) * sqrt(hidden / kv_lora_rank)
    rotary positions on q_rope and k_r over interleaved pairs (2i, 2i + 1);
        k_r is ONE head that every query head shares
    k_nope_j = c W_uk_j,  v_j = c W_uv_j
    score_j(t, s) = (q_nope_j . k_nope_j(s) + q_rope_j . k_r(s))
        / sqrt(qk_nope_head_dim + qk_rope_head_dim),  s <= t;  softmax
    out = concat_j(sum_s p_j(t, s) v_j(s)) W_o

``Experts(m)``, over ``n_routed_experts_published`` routed experts and
``zero_expert_num`` zero-compute experts:

    g = softmax(m W_r) over all outputs
    chosen = the moe_topk largest of g + bias (bias selects, does not weigh)
    w_i = routed_scaling_factor * g_i  for i chosen   (no normalisation)
    E_i(m) = SwiGLU_i(m) at expert_ffn_hidden_size, i < routed;  E_i(m) = m
    s = sum over the chosen of w_i E_i(m)

``experts_held`` / ``experts_offset`` cut ``s`` to one chip's share: the
routed experts ``[offset, offset + held)`` whose weights ``tree`` holds,
plus the zero-compute experts' part where ``zero_part`` (it belongs to the
chip that owns the token: in a sum over shares it is counted once).  What
the other experts would add is left out.

``c`` holds the configuration under its Hugging Face keys; ``tree`` is the
parameter tree in the layout the program serves (``models/transformer.py
layer_tree_shapes``): a double layer is ``half_0`` / ``half_1`` / ``moe``;
``attn/wk_b`` and ``attn/wv_b`` are ``W_uk`` and ``W_uv`` as leaves of their
own, [heads, 128, kv_lora_rank] and [kv_lora_rank, heads, 128]; ``moe/wi``
is [held, hidden, 2 x expert_ffn_hidden_size], gate then up; ``mlp/wi`` is a
pair on its first axis.
"""

import jax
import jax.numpy as jnp
import numpy as np


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope_pairs(x, theta):
    """x [t, heads, d]; position i is row i; pairs (2i, 2i + 1)."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.reshape(x.shape)


def latent_attention(c, u, w):
    e, eps, theta = c["hidden_size"], c["rms_norm_eps"], c["rope_theta"]
    rq, rkv = c["q_lora_rank"], c["kv_lora_rank"]
    dn, dr = c["qk_nope_head_dim"], c["qk_rope_head_dim"]
    up_q = np.sqrt(e / rq) if c.get("mla_scale_q_lora", True) else 1.0
    up_kv = np.sqrt(e / rkv) if c.get("mla_scale_kv_lora", True) else 1.0
    t = u.shape[0]
    q = jnp.einsum("tr,rhd->thd", rms_norm(
        u @ w["wq_a"], w["q_norm"]["scale"], eps) * up_q, w["wq_b"])
    q_nope, q_rope = q[..., :dn], rope_pairs(q[..., dn:], theta)
    kva = u @ w["wkv_a"]
    lat = rms_norm(kva[:, :rkv], w["kv_norm"]["scale"], eps) * up_kv
    k_r = rope_pairs(kva[:, None, rkv:], theta)[:, 0]
    k_nope = jnp.einsum("sc,hdc->shd", lat, w["wk_b"])
    v = jnp.einsum("sc,chd->shd", lat, w["wv_b"])
    s = (jnp.einsum("thd,shd->hts", q_nope, k_nope)
         + jnp.einsum("thd,sd->hts", q_rope, k_r)) / np.sqrt(dn + dr)
    s = jnp.where(jnp.arange(t)[None, :] <= jnp.arange(t)[:, None], s,
                  -jnp.inf)
    out = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), v)
    return jnp.einsum("thd,hde->te", out, w["wo"])


def swiglu(y, w_gate, w_up, w_down):
    return (jax.nn.silu(y @ w_gate) * (y @ w_up)) @ w_down


def dense(y, w):
    return swiglu(y, w["wi"][0], w["wi"][1], w["wo"])


def experts(c, m, w, experts_held=None, experts_offset=0, zero_part=True):
    n, f = c["n_routed_experts_published"], c["expert_ffn_hidden_size"]
    held = n if experts_held is None else experts_held
    g = jax.nn.softmax(m @ w["router"], axis=-1)
    chosen = jnp.argsort(-(g + w["bias"]), axis=-1)[:, :c["moe_topk"]]
    mask = jnp.zeros_like(g).at[jnp.arange(m.shape[0])[:, None],
                                chosen].set(1.0)
    weight = mask * g * c["routed_scaling_factor"]
    out = jnp.zeros_like(m)
    for i in range(held):
        out = out + weight[:, experts_offset + i, None] * swiglu(
            m, w["wi"][i, :, :f], w["wi"][i, :, f:], w["wo"][i])
    if zero_part:
        out = out + weight[:, n:].sum(-1, keepdims=True) * m
    return out


def double_layer(c, x, w, **share):
    eps = c["rms_norm_eps"]
    first, second = w["half_0"], w["half_1"]
    a = x + latent_attention(
        c, rms_norm(x, first["attn_norm"]["scale"], eps), first["attn"])
    m = rms_norm(a, first["mlp_norm"]["scale"], eps)
    s = experts(c, m, w["moe"], **share)
    b = a + dense(m, first["mlp"])
    cc = b + latent_attention(
        c, rms_norm(b, second["attn_norm"]["scale"], eps), second["attn"])
    return cc + dense(rms_norm(cc, second["mlp_norm"]["scale"], eps),
                      second["mlp"]) + s


def forward(c, tree, tokens, **share):
    """tokens [t] -> float32 logits [t, vocab]."""
    with jax.default_matmul_precision("highest"):
        tree = jax.tree_util.tree_map(
            lambda a: jnp.asarray(a, jnp.float32), tree)
        x = tree["embed"][jnp.asarray(tokens)]
        for i in range(c["num_layers"]):
            x = double_layer(c, x, tree["layers"][str(i)], **share)
        x = rms_norm(x, tree["final_norm"]["scale"], c["rms_norm_eps"])
        return x @ tree["w_out"]
