"""The plain reference of dots3-note-prev's language model (the decoder that
its ``config.json`` states; the towers and the MTP module are no keys of it),
written from the layer equations of ISSUE 44 and not from the program:
straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``, one sequence, no cache, no batching,
no sorting (the experts are a loop over a dense mask), keys and values
expanded from the latent for every position, the indexer a dense ``[t, s]``
score matrix and ``top_k``, the window a mask.

With ``N`` an RMSNorm (eps ``rms_norm_eps``, a scale) and ``x`` the stream, a
layer is ``a = x + Attn(N(x)); y = a + FF(N'(a))``; after the last layer
``N_final``, then an untied head.  ``layer_types`` names each layer's
attention.

``full_attention`` (``num_attention_heads`` heads; ``q_lora_rank``,
``kv_lora_rank``, ``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``,
``rope_theta``), ``u`` the normed input:

    qa = N_q(u W_qa) * sqrt(hidden / q_lora_rank)
        (both factors: ``apply_mla_qkv_lora_rescale``)
    q = qa W_qb  -> per head (q_nope, q_rope)
    (l, k_r) = split(u W_kva, [kv_lora_rank, qk_rope_head_dim])
    c = N_kv(l) * sqrt(hidden / kv_lora_rank)
    rotary positions on q_rope and k_r over interleaved pairs (2i, 2i + 1);
        k_r is ONE head that every query head shares
    k_j = [c W_uk_j, k_r],  v_j = c W_uv_j
    score_j(t, s) = q_j(t) . k_j(s) / sqrt(qk_nope_head_dim +
        qk_rope_head_dim),  s in S_t;  softmax over S_t
    out = concat_j(sigmoid(u W_g)_j * sum_s p_j(t, s) v_j(s)) W_o

    the indexer (``index_n_heads``, ``index_head_dim``, ``index_topk``):
    qI = qa W_qI  (heads of index_head_dim),  kI = LayerNorm(u W_kI)  (ONE)
    rotary pairs on the first qk_rope_head_dim values of both
    w = u W_w * index_n_heads^-0.5 * index_head_dim^-0.5
    I(t, s) = sum_h w_h(t) relu(qI_h(t) . kI(s)),  s <= t
    S_t = the index_topk positions of largest I(t, .): every s <= t while
        t < index_topk; ties to the lower position

``sliding_attention``: the same at the ``swa_*`` sizes, no indexer,
``S_t = (t - sliding_window_size, t]``.

``FF``: layers before ``first_k_dense_replace`` a SwiGLU of
``intermediate_size``; the others

    g = sigmoid(m W_r) over n_routed_experts_published outputs
    chosen = the num_experts_per_tok largest of g + bias (the bias selects
        and does not weigh)
    w_i = routed_scaling_factor * g_i / (sum of the chosen g + 1e-6)
    out = sum over the chosen of w_i SwiGLU_i(m)  +  Shared(m)

with ONE shared SwiGLU of ``moe_intermediate_size`` added unweighted.
``experts_held`` / ``experts_offset`` cut the routed sum to one chip's share
(the experts whose weights ``tree`` holds); ``shared_part`` says whether the
shared expert is counted (it belongs to the chip that owns the token: in a
sum over shares it is counted once).

``c`` holds the configuration under its Hugging Face keys; ``tree`` is the
parameter tree in the layout the program serves (``models/transformer.py
layer_tree_shapes``).
"""

import jax
import jax.numpy as jnp
import numpy as np


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def layer_norm(x, scale, bias, eps):
    x = x - jnp.mean(x, -1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale + bias


def rope_pairs(x, theta):
    """x [t, heads, d]; position i is row i; pairs (2i, 2i + 1)."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.reshape(x.shape)


def sizes(c, kind):
    """(heads, q rank, kv rank, nope, rope, v, theta, window) of a layer."""
    if kind == "sliding_attention":
        return (c["swa_num_attention_heads"], c["swa_q_lora_rank"],
                c["swa_kv_lora_rank"], c["swa_qk_nope_head_dim"],
                c["swa_qk_rope_head_dim"], c["swa_v_head_dim"],
                c["swa_rope_theta"], c["sliding_window_size"])
    return (c["num_attention_heads"], c["q_lora_rank"], c["kv_lora_rank"],
            c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"],
            c["rope_theta"], 0)


def index_combine(products, weights):
    """products [t, heads, s] = qI_h(t) . kI(s), weights [t, heads] ->
    I(t, s)."""
    return jnp.einsum("ths,th->ts", jax.nn.relu(products), weights)


def gate_values(g, bias):
    """What weighs a chosen expert: its score; the bias only selects."""
    del bias
    return g


def index_scores(c, u, qa, w, theta, dr):
    """I(t, s) [t, t], -inf where s > t."""
    t = u.shape[0]
    hi, di = c["index_n_heads"], c["index_head_dim"]
    q = jnp.einsum("tr,rhd->thd", qa, w["wq_idx"])
    k = layer_norm(u @ w["wk_idx"], w["k_idx_norm"]["scale"],
                   w["k_idx_norm"]["bias"], c["rms_norm_eps"])[:, None]
    q = jnp.concatenate([rope_pairs(q[..., :dr], theta), q[..., dr:]], -1)
    k = jnp.concatenate([rope_pairs(k[..., :dr], theta), k[..., dr:]],
                        -1)[:, 0]
    weights = (u @ w["w_idx"]) * hi ** -0.5 * di ** -0.5
    scores = index_combine(jnp.einsum("thd,sd->ths", q, k), weights)
    return jnp.where(jnp.arange(t)[None, :] <= jnp.arange(t)[:, None],
                     scores, -jnp.inf)


def chosen_set(scores, topk):
    """[t, t] bool: row t's ``topk`` largest (ties to the lower position),
    of the positions it may see."""
    t = scores.shape[0]
    _, idx = jax.lax.top_k(scores, min(topk, t))
    picked = jnp.zeros((t, t), bool).at[jnp.arange(t)[:, None], idx].set(True)
    return picked & (scores > -jnp.inf)


def attention(c, u, w, kind):
    e, eps = c["hidden_size"], c["rms_norm_eps"]
    heads, rq, rkv, dn, dr, dv, theta, window = sizes(c, kind)
    t = u.shape[0]
    rescale = c.get("apply_mla_qkv_lora_rescale", True)
    qa = rms_norm(u @ w["wq_a"], w["q_norm"]["scale"], eps) \
        * (np.sqrt(e / rq) if rescale else 1.0)
    q = jnp.einsum("tr,rhd->thd", qa, w["wq_b"])
    q_nope, q_rope = q[..., :dn], rope_pairs(q[..., dn:], theta)
    kva = u @ w["wkv_a"]
    lat = rms_norm(kva[:, :rkv], w["kv_norm"]["scale"], eps) \
        * (np.sqrt(e / rkv) if rescale else 1.0)
    k_r = rope_pairs(kva[:, None, rkv:], theta)[:, 0]
    k_nope = jnp.einsum("sc,hdc->shd", lat, w["wk_b"])
    v = jnp.einsum("sc,chd->shd", lat, w["wv_b"])
    s = (jnp.einsum("thd,shd->hts", q_nope, k_nope)
         + jnp.einsum("thd,sd->hts", q_rope, k_r)) / np.sqrt(dn + dr)
    rows, cols = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    keep = cols <= rows
    if window:
        keep = keep & (cols > rows - window)
    elif c.get("index_topk"):
        keep = keep & chosen_set(
            index_scores(c, u, qa, w, theta, dr), c["index_topk"])
    out = jnp.einsum("hts,shd->thd",
                     jax.nn.softmax(jnp.where(keep, s, -jnp.inf), -1), v)
    gate_type = c["swa_attention_gate_type" if window
                  else "attention_gate_type"]
    if gate_type == "headwise":
        out = out * jax.nn.sigmoid(u @ w["wg"])[..., None]
    return jnp.einsum("thd,hde->te", out, w["wo"])


def swiglu(y, w_gate, w_up, w_down):
    return (jax.nn.silu(y @ w_gate) * (y @ w_up)) @ w_down


def dense(y, w):
    return swiglu(y, w["wi"][0], w["wi"][1], w["wo"])


def experts(c, m, w, experts_held=None, experts_offset=0, shared_part=True):
    n, f = c["n_routed_experts_published"], c["moe_intermediate_size"]
    held = n if experts_held is None else experts_held
    g = jax.nn.sigmoid(m @ w["router"])
    chosen = jnp.argsort(-(g + w["bias"]), axis=-1)[
        :, :c["num_experts_per_tok"]]
    mask = jnp.zeros_like(g).at[jnp.arange(m.shape[0])[:, None],
                                chosen].set(1.0)
    weight = mask * gate_values(g, w["bias"])
    if c["norm_topk_prob"]:
        weight = weight / (weight.sum(-1, keepdims=True) + 1e-6)
    weight = weight * c["routed_scaling_factor"]
    out = jnp.zeros_like(m)
    for i in range(held):
        out = out + weight[:, experts_offset + i, None] * swiglu(
            m, w["wi"][i, :, :f], w["wi"][i, :, f:], w["wo"][i])
    if shared_part:
        out = out + dense(m, w["shared"])
    return out


def layer(c, i, x, w, **share):
    eps = c["rms_norm_eps"]
    a = x + attention(c, rms_norm(x, w["attn_norm"]["scale"], eps),
                      w["attn"], c["layer_types"][i])
    m = rms_norm(a, w["mlp_norm"]["scale"], eps)
    if i < c["first_k_dense_replace"]:
        return a + dense(m, w["mlp"])
    return a + experts(c, m, w["moe"], **share)


def forward(c, tree, tokens, **share):
    """tokens [t] -> float32 logits [t, vocab]."""
    with jax.default_matmul_precision("highest"):
        tree = jax.tree_util.tree_map(
            lambda a: jnp.asarray(a, jnp.float32), tree)
        x = tree["embed"][jnp.asarray(tokens)]
        for i in range(c["num_hidden_layers"]):
            x = layer(c, i, x, tree["layers"][str(i)], **share)
        x = rms_norm(x, tree["final_norm"]["scale"], c["rms_norm_eps"])
        return x @ tree["w_out"]
