"""The plain reference of dots.vlm1.inst's language model (DeepSeek-V3's
block, as the catalog row's keys state it) WITH its multi-token-prediction
module, written from the layer equations of ISSUE 47 and not from the
program: straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``, one sequence, no cache, no
batching, no sorting (the experts are a loop over a dense mask), keys and
values expanded from the latent for every position.

With ``N`` an RMSNorm (eps ``rms_norm_eps``, a scale) and ``x`` the stream,
a block is ``a = x + MLA(N(x)); y = a + FF(N'(a))``; layers before
``first_k_dense_replace`` have a SwiGLU of ``intermediate_size``, the others
experts; after the last layer ``h = N_final(x)``, then an untied head.

``MLA(u)``, no bias, NO factor on the low-rank norms:

    q = N_q(u W_qa) W_qb  -> per head (q_nope, q_rope)
    (l, k_r) = split(u W_kva, [kv_lora_rank, qk_rope_head_dim]); c = N_kv(l)
    rotary positions on q_rope and k_r over interleaved pairs (2i, 2i + 1)
        at YaRN's frequencies (``yarn_frequencies``); k_r is ONE head
    k_nope_j = c W_uk_j,  v_j = c W_uv_j
    score_j(t, s) = (q_nope_j . k_nope_j(s) + q_rope_j . k_r(s)) * scale,
        s <= t;  scale = (qk_nope_head_dim + qk_rope_head_dim)^-0.5 * m^2,
        m = 0.1 * mscale_all_dim * ln(factor) + 1;  softmax
    out = concat_j(sum_s p_j(t, s) v_j(s)) W_o

``Experts(m)`` over ``n_routed_experts_published`` outputs in ``n_group``
runs of consecutive experts:

    s = sigmoid(m W_r);  e = s + bias  (the bias selects, does not weigh)
    a group's score: the sum of its 2 largest e;  the ``topk_group`` groups
        of largest score are kept (ties to the lower group)
    chosen: the ``num_experts_per_tok`` largest e inside kept groups (ties
        to the lower expert)
    w_i = routed_scaling_factor * s_i / (sum of the chosen s + 1e-20)
    out = sum_i w_i E_i(m) + Shared(m)

``experts_held`` / ``experts_offset`` cut the routed sum to one chip's
share, the experts ``[offset, offset + held)`` whose weights ``tree``
holds; ``shared_part`` False leaves the shared expert out (it belongs to
the chip that owns the token: in a sum over shares it is counted once).

The multi-token-prediction module, for position i with the main stack's
``h_i`` and the NEXT token ``t_{i+1}``:

    z_i = [N_e(Emb(t_{i+1})); N_h(h_i)] W_eh
    z' = Block(z)  (an expert block as above over rows 0..i, row i at
        rotary position i)
    logits_mtp,i = Head(N_s(z'_i)),  which predicts t_{i+2}

``c`` holds the configuration under its Hugging Face keys; ``tree`` is the
parameter tree in the layout the program serves (``models/transformer.py
layer_tree_shapes``; the module under ``mtp``).
"""

import jax
import jax.numpy as jnp
import numpy as np


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def yarn_frequencies(c):
    """float64 [qk_rope_head_dim / 2]."""
    d, theta, y = c["qk_rope_head_dim"], c["rope_theta"], c["rope_scaling"]
    f = np.asarray([theta ** (-2.0 * i / d) for i in range(d // 2)])
    if not y:
        return f

    def cd(n):
        return d * np.log(y["original_max_position_embeddings"]
                          / (2 * np.pi * n)) / (2 * np.log(theta))

    low = max(int(np.floor(cd(y["beta_fast"]))), 0)
    high = min(int(np.ceil(cd(y["beta_slow"]))), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0, 1)
    return f * (1 - ramp) + (f / y["factor"]) * ramp


def softmax_scale(c):
    y = c["rope_scaling"]
    m = 1.0 if not y else \
        0.1 * y["mscale_all_dim"] * np.log(y["factor"]) + 1.0
    return (c["qk_nope_head_dim"] + c["qk_rope_head_dim"]) ** -0.5 * m * m


def rope_pairs(x, freqs):
    """x [t, heads, d]; position i is row i; pairs (2i, 2i + 1)."""
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] \
        * jnp.asarray(freqs, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.reshape(x.shape)


def latent_attention(c, u, w):
    eps, freqs = c["rms_norm_eps"], yarn_frequencies(c)
    rkv, dn = c["kv_lora_rank"], c["qk_nope_head_dim"]
    t = u.shape[0]
    q = jnp.einsum("tr,rhd->thd", rms_norm(
        u @ w["wq_a"], w["q_norm"]["scale"], eps), w["wq_b"])
    q_nope, q_rope = q[..., :dn], rope_pairs(q[..., dn:], freqs)
    kva = u @ w["wkv_a"]
    lat = rms_norm(kva[:, :rkv], w["kv_norm"]["scale"], eps)
    k_r = rope_pairs(kva[:, None, rkv:], freqs)[:, 0]
    k_nope = jnp.einsum("sc,hdc->shd", lat, w["wk_b"])
    v = jnp.einsum("sc,chd->shd", lat, w["wv_b"])
    s = (jnp.einsum("thd,shd->hts", q_nope, k_nope)
         + jnp.einsum("thd,sd->hts", q_rope, k_r)) * softmax_scale(c)
    s = jnp.where(jnp.arange(t)[None, :] <= jnp.arange(t)[:, None], s,
                  -jnp.inf)
    out = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), v)
    return jnp.einsum("thd,hde->te", out, w["wo"])


def swiglu(y, w_gate, w_up, w_down):
    return (jax.nn.silu(y @ w_gate) * (y @ w_up)) @ w_down


def dense(y, w):
    return swiglu(y, w["wi"][0], w["wi"][1], w["wo"])


def chosen_experts(c, m, w):
    """(sigmoid scores [t, n], 0/1 mask of the chosen [t, n])."""
    n, groups = c["n_routed_experts_published"], c["n_group"]
    s = jax.nn.sigmoid(m @ w["router"])
    e = s + w["bias"]
    per = n // groups
    by_group = e.reshape(-1, groups, per)
    score = jnp.sort(by_group, -1)[..., -2:].sum(-1)            # [t, groups]
    kept = jnp.argsort(-score, -1, stable=True)[:, :c["topk_group"]]
    in_kept = jnp.zeros_like(score).at[
        jnp.arange(m.shape[0])[:, None], kept].set(1.0)
    e = jnp.where(jnp.repeat(in_kept, per, -1) > 0, e, -jnp.inf)
    chosen = jnp.argsort(-e, -1, stable=True)[:, :c["num_experts_per_tok"]]
    return s, jnp.zeros_like(s).at[
        jnp.arange(m.shape[0])[:, None], chosen].set(1.0)


def experts(c, m, w, experts_held=None, experts_offset=0, shared_part=True):
    n, f = c["n_routed_experts_published"], c["moe_intermediate_size"]
    held = n if experts_held is None else experts_held
    s, mask = chosen_experts(c, m, w)
    weight = mask * s
    if c["norm_topk_prob"]:
        weight = weight / (weight.sum(-1, keepdims=True) + 1e-20)
    weight = weight * c["routed_scaling_factor"]
    out = jnp.zeros_like(m)
    for i in range(held):
        out = out + weight[:, experts_offset + i, None] * swiglu(
            m, w["wi"][i, :, :f], w["wi"][i, :, f:], w["wo"][i])
    if shared_part:
        out = out + dense(m, w["shared"])
    return out


def block(c, x, w, **share):
    eps = c["rms_norm_eps"]
    a = x + latent_attention(
        c, rms_norm(x, w["attn_norm"]["scale"], eps), w["attn"])
    m = rms_norm(a, w["mlp_norm"]["scale"], eps)
    if "mlp" in w:
        return a + dense(m, w["mlp"])
    return a + experts(c, m, w["moe"], **share)


def forward(c, tree, tokens, **share):
    """tokens [t] -> (float32 main logits [t, vocab], float32 module
    logits [t - 1, vocab]: row i read (h_i, tokens[i + 1]) and predicts
    tokens[i + 2]; None for a tree without the module)."""
    with jax.default_matmul_precision("highest"):
        tree = jax.tree_util.tree_map(
            lambda a: jnp.asarray(a, jnp.float32), tree)
        eps = c["rms_norm_eps"]
        tokens = jnp.asarray(tokens)
        x = tree["embed"][tokens]
        for i in range(c["num_hidden_layers"]):
            x = block(c, x, tree["layers"][str(i)], **share)
        h = rms_norm(x, tree["final_norm"]["scale"], eps)
        logits = h @ tree["w_out"]
        if "mtp" not in tree or tokens.shape[0] < 2:
            return logits, None
        mtp = tree["mtp"]
        z = jnp.concatenate([
            rms_norm(tree["embed"][tokens[1:]], mtp["enorm"]["scale"], eps),
            rms_norm(h[:-1], mtp["hnorm"]["scale"], eps)], -1) \
            @ mtp["eh_proj"]
        z = block(c, z, mtp["layer"], **share)
        return logits, rms_norm(z, mtp["norm"]["scale"], eps) @ tree["w_out"]
