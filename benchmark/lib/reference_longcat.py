"""The plain reference of LongCat-Flash's language model
(``configs/longcat-flash-omni-l4.json``): ``tests/reference_longcat.py``'s
equations, computed a double layer at a time, an expert at a time and
attention in blocks of query rows, so that the published widths and a 6k
context fit beside nothing else.  float32,
``default_matmul_precision("highest")``, one sequence, no cache, no batching,
no sorting, keys and values expanded from the latent for every position (the
program's decode step never forms them).

With ``N`` an RMSNorm (eps ``rms_norm_eps``, a scale) and ``x`` the stream, a
double layer is

    a = x + MLA_0(N_0(x));   m = N'_0(a);   s = Experts(m)
    b = a + Dense_0(m);      c = b + MLA_1(N_1(b))
    y = c + Dense_1(N'_1(c)) + s

``Dense(u) = (silu(u W_g) * (u W_u)) W_d`` at ``ffn_hidden_size``; after the
last layer ``N_final``, then an untied head over the vocabulary's slice.
``MLA(u)``, no bias:

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

``Experts(m)``: g = softmax(m W_r) over the ``n_routed_experts_published`` +
``zero_expert_num`` outputs; the ``moe_topk`` largest of g + bias are chosen
(the bias selects and does not weigh); w_i = ``routed_scaling_factor`` * g_i,
not normalised; a routed expert is a SwiGLU at ``expert_ffn_hidden_size``, a
zero-compute expert returns its input.  THIS CHIP'S SHARE: the sum runs over
the chosen experts among ``[experts_offset, experts_offset +
n_routed_experts)``, whose weights the tree holds, plus the zero-compute
experts' part ``(sum of their chosen w_i) m``; what the absent experts would
add is left out, as the program leaves it out.

It imports nothing of the program and takes nothing the program made: the
weights come from ``weights_longcat.leaf`` by the run's seed, a layer at a
time.  ``quantize`` is the control of the correctness check
(``reference.py``): every matmul weight rounded to int8 or float8_e4m3 with a
scale per output channel.  The router, its bias and the norms are left as
they are: a deployment in a lower precision keeps them, and the control is
then the harder one to tell from a sound run.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import weights_longcat
from .reference import _fake_fp8, _fake_int8, _rms_norm, served_gaps

__all__ = ["Reference", "served_gaps"]

_Q_BLOCK = 512
# Which axes each matmul weight contracts over (for the controls).
_CONTRACT = {"attn/wq_a": (0,), "attn/wq_b": (0,), "attn/wkv_a": (0,),
             "attn/wk_b": (2,), "attn/wv_b": (0,), "attn/wo": (0, 1),
             "mlp/wi": (1,), "mlp/wo": (0,), "moe/wi": (1,), "moe/wo": (1,),
             "w_out": (0,)}


def _prepare(name, w, quantize):
    w = w.astype(jnp.float32)
    short = name.partition("/")[2] if name.startswith("half_") else name
    if quantize is not None and short in _CONTRACT:
        fake = {"int8": _fake_int8, "fp8": _fake_fp8}[quantize]
        w = fake(w, _CONTRACT[short])
    return w


def _rope_pairs(x, theta):
    """x [t, heads, d]; position i is row i; pairs (2i, 2i + 1)."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


def _attention(q_nope, q_rope, k_nope, k_r, v, scale):
    """Causal: q_* [t, h, .], k_nope / v [t, h, .], k_r [t, dr] (one head
    for all) -> [t, h, dv], in blocks of query rows."""
    t, h, _ = q_nope.shape
    q_block = min(_Q_BLOCK, t)
    cols = jnp.arange(t)

    def block(start):
        qn = jax.lax.dynamic_slice_in_dim(q_nope, start, q_block, 0)
        qr = jax.lax.dynamic_slice_in_dim(q_rope, start, q_block, 0)
        s = (jnp.einsum("qhd,khd->hqk", qn, k_nope)
             + jnp.einsum("qhd,kd->hqk", qr, k_r)) * scale
        rows = start + jnp.arange(q_block)
        s = jnp.where(cols[None, None, :] <= rows[None, :, None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)

    out = jax.lax.map(block, jnp.arange(0, t, q_block))
    return out.reshape(t, h, v.shape[-1])


def _latent_attention(c, u, w, at):
    e, eps, theta = c["hidden_size"], c["rms_norm_eps"], c["rope_theta"]
    rq, rkv = c["q_lora_rank"], c["kv_lora_rank"]
    dn, dr = c["qk_nope_head_dim"], c["qk_rope_head_dim"]
    up_q = np.sqrt(e / rq) if c["mla_scale_q_lora"] else 1.0
    up_kv = np.sqrt(e / rkv) if c["mla_scale_kv_lora"] else 1.0
    q = jnp.einsum("tr,rhd->thd", _rms_norm(
        u @ w[at + "attn/wq_a"], w[at + "attn/q_norm/scale"], eps) * up_q,
        w[at + "attn/wq_b"])
    kva = u @ w[at + "attn/wkv_a"]
    lat = _rms_norm(kva[:, :rkv], w[at + "attn/kv_norm/scale"], eps) * up_kv
    out = _attention(
        q[..., :dn], _rope_pairs(q[..., dn:], theta),
        jnp.einsum("sc,hdc->shd", lat, w[at + "attn/wk_b"]),
        _rope_pairs(kva[:, None, rkv:], theta)[:, 0],
        jnp.einsum("sc,chd->shd", lat, w[at + "attn/wv_b"]),
        1.0 / np.sqrt(dn + dr))
    return jnp.einsum("thd,hde->te", out, w[at + "attn/wo"])


def _swiglu(y, w_gate, w_up, w_down):
    return (jax.nn.silu(y @ w_gate) * (y @ w_up)) @ w_down


def _dense(y, w, at):
    return _swiglu(y, w[at + "mlp/wi"][0], w[at + "mlp/wi"][1],
                   w[at + "mlp/wo"])


def _experts(c, m, w):
    n, f = c["n_routed_experts_published"], c["expert_ffn_hidden_size"]
    first, held = c.get("experts_offset", 0), c["n_routed_experts"]
    g = jax.nn.softmax(m @ w["moe/router"], axis=-1)
    chosen = jnp.argsort(-(g + w["moe/bias"]), axis=-1)[:, :c["moe_topk"]]
    mask = jnp.zeros_like(g).at[jnp.arange(m.shape[0])[:, None],
                                chosen].set(1.0)
    weight = mask * g * c["routed_scaling_factor"]

    def one(out, expert):  # an expert at a time, over every row
        wi, wo, gate = expert
        return out + gate[:, None] * _swiglu(m, wi[:, :f], wi[:, f:],
                                             wo), None

    out = jax.lax.scan(one, jnp.zeros_like(m), (
        w["moe/wi"], w["moe/wo"], weight[:, first:first + held].T))[0]
    # A zero-compute expert returns its input.
    return out + weight[:, n:].sum(-1, keepdims=True) * m


def _layer(c, x, w):
    eps = c["rms_norm_eps"]
    a = x + _latent_attention(
        c, _rms_norm(x, w["half_0/attn_norm/scale"], eps), w, "half_0/")
    m = _rms_norm(a, w["half_0/mlp_norm/scale"], eps)
    s = _experts(c, m, w)
    b = a + _dense(m, w, "half_0/")
    cc = b + _latent_attention(
        c, _rms_norm(b, w["half_1/attn_norm/scale"], eps), w, "half_1/")
    return cc + _dense(_rms_norm(cc, w["half_1/mlp_norm/scale"], eps), w,
                       "half_1/") + s


class Reference:
    """Logits of one configuration on one seed's weights."""

    def __init__(self, published, seed, dtype=jnp.bfloat16, quantize=None):
        self.c = c = dict(published)
        self.key = weights_longcat.weights.seed_key(seed)
        top = weights_longcat.specs(c)

        @jax.jit
        def embed_rows(key, tokens):
            table = weights_longcat.leaf(key, "embed", 0, *top["embed"],
                                         dtype)
            return table[tokens].astype(jnp.float32)

        @jax.jit
        def layer(key, i, x):
            with jax.default_matmul_precision("highest"):
                w = {n: _prepare(n, a, quantize) for n, a in
                     weights_longcat.layer_leaves(c, key, i, dtype).items()}
                return _layer(c, x, w)

        @functools.partial(jax.jit, static_argnames=("rows",))
        def head(key, x, start, rows):
            with jax.default_matmul_precision("highest"):
                x = jax.lax.dynamic_slice_in_dim(x, start, rows, 0)
                x = _rms_norm(x, jnp.ones((c["hidden_size"],)),
                              c["rms_norm_eps"])
                return x @ _prepare("w_out", weights_longcat.leaf(
                    key, "w_out", 0, *top["w_out"], dtype), quantize)

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
        for i in range(self.c["num_layers"]):
            x = self._layer(self.key, jnp.int32(i), x)
        return self._head(self.key, x, jnp.int32(start), rows)
