"""The plain reference of dots3-note-prev's language model
(``configs/dots3-note-prev-l5.json``): ``tests/reference_dots3.py``'s
equations, computed a layer at a time, an expert at a time, and attention in
groups of heads and blocks of query rows, so that the published widths and a
33k context fit beside nothing else.  float32,
``default_matmul_precision("highest")``, one sequence, no cache, no batching,
no sorting, keys and values expanded from the latent for every position (the
program's decode step never forms them), the indexer as dense scores
``[query block, every position]`` and ``top_k``, the window as a mask.

With ``N`` an RMSNorm (eps ``rms_norm_eps``, a scale) and ``x`` the stream, a
layer is ``a = x + Attn(N(x)); y = a + FF(N'(a))``; after the last layer
``N_final``, then an untied head over the vocabulary's slice.

``full_attention``, ``u`` the normed input, no bias:

    qa = N_q(u W_qa) * sqrt(hidden / q_lora_rank)
    q = qa W_qb  -> per head (q_nope [qk_nope_head_dim], q_rope)
    (l, k_r) = split(u W_kva, [kv_lora_rank, qk_rope_head_dim])
    c = N_kv(l) * sqrt(hidden / kv_lora_rank)
    rotary positions on q_rope and k_r over interleaved pairs (2i, 2i + 1);
        k_r is ONE head that every query head shares
    k_j = [c W_uk_j, k_r],  v_j = c W_uv_j
    score_j(t, s) = q_j(t) . k_j(s) / sqrt(qk_nope_head_dim +
        qk_rope_head_dim),  s in S_t;  softmax over S_t
    out = concat_j(sigmoid(u W_g)_j * sum_s p_j(t, s) v_j(s)) W_o
  the indexer:
    qI = qa W_qI (index_n_heads of index_head_dim),  kI = LayerNorm(u W_kI)
    rotary pairs on the first qk_rope_head_dim values of both
    w = u W_w * index_n_heads^-0.5 * index_head_dim^-0.5
    I(t, s) = sum_h w_h(t) relu(qI_h(t) . kI(s)),  s <= t
    S_t = the index_topk positions of largest I(t, .) (every s <= t while
        t < index_topk; ties to the lower position)

``sliding_attention``: the same at the ``swa_*`` sizes, no indexer,
``S_t = (t - sliding_window_size, t]``.

``FF``: layers before ``first_k_dense_replace`` a SwiGLU of
``intermediate_size``; the others ``g = sigmoid(m W_r)`` over the
``n_routed_experts_published`` outputs, the ``num_experts_per_tok`` largest
of g + bias chosen (the bias selects and does not weigh), ``w_i =
routed_scaling_factor * g_i / (sum of the chosen g + 1e-6)``, a routed expert
a SwiGLU of ``moe_intermediate_size``, plus ONE shared SwiGLU of that width
added unweighted.  THIS CHIP'S SHARE: the routed sum runs over the chosen
experts among ``[experts_offset, experts_offset + n_routed_experts)``, whose
weights the tree holds; what the absent experts would add is left out, as
the program leaves it out; the shared expert is whole here.

It imports nothing of the program and takes nothing the program made: the
weights come from ``weights_dots3.leaf`` by the run's seed, a layer at a
time.  ``quantize`` is the control of the correctness check
(``reference.py``): every matmul weight rounded to int8 or float8_e4m3 with a
scale per output channel; or ``index_bf16``, which rounds nothing but the
index queries and keys (``INDEX_BF16``).  The router, its bias, the indexer's per-head
weights and the norms are left as they are: a deployment in a lower precision
keeps them, and the control is then the harder one to tell from a sound run.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import weights_dots3
from .reference import _fake_fp8, _fake_int8, _rms_norm, served_gaps
from .reference_longcat import _rope_pairs, _swiglu

__all__ = ["Reference", "served_gaps"]

_Q_BLOCK = 512
_HEAD_GROUP = 16
# Which axes each matmul weight contracts over (for the controls).
_CONTRACT = {"attn/wq_a": (0,), "attn/wq_b": (0,), "attn/wkv_a": (0,),
             "attn/wk_b": (2,), "attn/wv_b": (0,), "attn/wo": (0, 1),
             "attn/wg": (0,), "attn/wq_idx": (0,), "attn/wk_idx": (0,),
             "mlp/wi": (1,), "mlp/wo": (0,), "moe/wi": (1,), "moe/wo": (1,),
             "moe/shared/wi": (1,), "moe/shared/wo": (0,), "w_out": (0,)}


# A control that is no lower precision of the weights: the index queries and
# keys rounded to bfloat16 before their product, as a program that computes
# in bfloat16 holds them.  What it reads is what a choice at the
# ``index_topk``-th place moves when it falls otherwise.
INDEX_BF16 = "index_bf16"


def _prepare(name, w, quantize):
    w = w.astype(jnp.float32)
    if quantize in ("int8", "fp8") and name in _CONTRACT:
        fake = {"int8": _fake_int8, "fp8": _fake_fp8}[quantize]
        w = fake(w, _CONTRACT[name])
    return w


def _layer_norm(x, scale, bias, eps):
    x = x - jnp.mean(x, -1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale + bias


def _rope_leading(x, theta, dr):
    return jnp.concatenate([_rope_pairs(x[..., :dr], theta), x[..., dr:]],
                           -1)


def _blocks(t):
    q_block = min(_Q_BLOCK, t)
    return q_block, jnp.arange(0, t, q_block)


def _over_blocks(block, starts, live):
    """``block(start)`` for the first ``live`` (traced) of ``starts``,
    stacked; zeros for the blocks after them, which hold padding alone."""
    one = jax.eval_shape(block, starts[0])
    return jax.lax.fori_loop(
        0, live, lambda i, out: out.at[i].set(block(starts[i])),
        jnp.zeros((starts.shape[0],) + one.shape, one.dtype))


def _chosen(c, u, qa, w, theta, dr, live, rounded=False):
    """[t, t] bool: the positions each query's indexer chooses, a block of
    query rows at a time (the first ``live`` blocks); ``rounded``: the
    ``INDEX_BF16`` control."""
    t = u.shape[0]
    hi, di, topk = c["index_n_heads"], c["index_head_dim"], c["index_topk"]
    q = _rope_leading(jnp.einsum("tr,rhd->thd", qa, w["attn/wq_idx"]),
                      theta, dr)
    k = _rope_leading(_layer_norm(
        u @ w["attn/wk_idx"], w["attn/k_idx_norm/scale"],
        w["attn/k_idx_norm/bias"], c["rms_norm_eps"])[:, None], theta,
        dr)[:, 0]
    weights = (u @ w["attn/w_idx"]) * hi ** -0.5 * di ** -0.5
    if rounded:
        q, k = (a.astype(jnp.bfloat16).astype(jnp.float32) for a in (q, k))
    q_block, starts = _blocks(t)
    cols = jnp.arange(t)

    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, q_block, 0)
        wb = jax.lax.dynamic_slice_in_dim(weights, start, q_block, 0)
        scores = jnp.einsum("qhs,qh->qs", jax.nn.relu(
            jnp.einsum("qhd,sd->qhs", qb, k)), wb)
        rows = start + jnp.arange(q_block)
        scores = jnp.where(cols[None, :] <= rows[:, None], scores, -jnp.inf)
        _, idx = jax.lax.top_k(scores, min(topk, t))
        picked = jnp.zeros((q_block, t), bool).at[
            jnp.arange(q_block)[:, None], idx].set(True)
        return picked & (scores > -jnp.inf)

    return _over_blocks(block, starts, live).reshape(t, t)


def _attention(c, u, w, kind, live, rounded=False):
    e, eps = c["hidden_size"], c["rms_norm_eps"]
    heads, rq, rkv, dn, dr, dv = weights_dots3.sizes(c, kind)
    window = c["sliding_window_size"] if kind == "sliding_attention" else 0
    theta = c["swa_rope_theta" if window else "rope_theta"]
    t = u.shape[0]
    qa = _rms_norm(u @ w["attn/wq_a"], w["attn/q_norm/scale"], eps) \
        * np.sqrt(e / rq)
    kva = u @ w["attn/wkv_a"]
    lat = _rms_norm(kva[:, :rkv], w["attn/kv_norm/scale"], eps) \
        * np.sqrt(e / rkv)
    k_r = _rope_pairs(kva[:, None, rkv:], theta)[:, 0]
    chosen = None if window else _chosen(c, u, qa, w, theta, dr, live,
                                         rounded)
    q_block, starts = _blocks(t)
    cols = jnp.arange(t)
    scale = 1.0 / np.sqrt(dn + dr)

    def group(first):
        """``_HEAD_GROUP`` heads from ``first`` on, over every query."""
        n = min(_HEAD_GROUP, heads)
        q = jnp.einsum("tr,rhd->thd", qa, jax.lax.dynamic_slice_in_dim(
            w["attn/wq_b"], first, n, 1))
        q_nope, q_rope = q[..., :dn], _rope_pairs(q[..., dn:], theta)
        k_nope = jnp.einsum("sc,hdc->shd", lat, jax.lax.dynamic_slice_in_dim(
            w["attn/wk_b"], first, n, 0))
        v = jnp.einsum("sc,chd->shd", lat, jax.lax.dynamic_slice_in_dim(
            w["attn/wv_b"], first, n, 1))

        def block(start):
            qn = jax.lax.dynamic_slice_in_dim(q_nope, start, q_block, 0)
            qr = jax.lax.dynamic_slice_in_dim(q_rope, start, q_block, 0)
            s = (jnp.einsum("qhd,khd->hqk", qn, k_nope)
                 + jnp.einsum("qhd,kd->hqk", qr, k_r)) * scale
            rows = start + jnp.arange(q_block)
            keep = cols[None, :] <= rows[:, None]
            if window:
                keep = keep & (cols[None, :] > rows[:, None] - window)
            else:
                keep = keep & jax.lax.dynamic_slice_in_dim(
                    chosen, start, q_block, 0)
            s = jnp.where(keep[None], s, -jnp.inf)
            return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)

        return _over_blocks(block, starts, live).reshape(t, n, dv)

    out = jax.lax.map(group, jnp.arange(0, heads, min(_HEAD_GROUP, heads)))
    out = jnp.moveaxis(out, 0, 1).reshape(t, heads, dv)
    gate = c["swa_attention_gate_type" if window else "attention_gate_type"]
    if gate == "headwise":
        out = out * jax.nn.sigmoid(u @ w["attn/wg"])[..., None]
    return jnp.einsum("thd,hde->te", out, w["attn/wo"])


def _experts(c, m, w):
    f = c["moe_intermediate_size"]
    first, held = c.get("experts_offset", 0), c["n_routed_experts"]
    g = jax.nn.sigmoid(m @ w["moe/router"])
    chosen = jnp.argsort(-(g + w["moe/bias"]),
                         axis=-1)[:, :c["num_experts_per_tok"]]
    mask = jnp.zeros_like(g).at[jnp.arange(m.shape[0])[:, None],
                                chosen].set(1.0)
    weight = mask * g
    if c["norm_topk_prob"]:
        weight = weight / (weight.sum(-1, keepdims=True) + 1e-6)
    weight = weight * c["routed_scaling_factor"]

    def one(out, expert):  # an expert at a time, over every row
        wi, wo, gate = expert
        return out + gate[:, None] * _swiglu(m, wi[:, :f], wi[:, f:],
                                             wo), None

    out = jax.lax.scan(one, jnp.zeros_like(m), (
        w["moe/wi"], w["moe/wo"], weight[:, first:first + held].T))[0]
    return out + _swiglu(m, w["moe/shared/wi"][0], w["moe/shared/wi"][1],
                         w["moe/shared/wo"])


def _layer(c, layer, x, w, live, rounded=False):
    """``live``: the blocks of query rows (``_blocks``) that hold tokens;
    attention leaves the rows of the others, padding alone, at zero."""
    eps = c["rms_norm_eps"]
    a = x + _attention(c, _rms_norm(x, w["attn_norm/scale"], eps), w,
                       c["layer_types"][layer], live, rounded)
    m = _rms_norm(a, w["mlp_norm/scale"], eps)
    if layer < c["first_k_dense_replace"]:
        return a + _swiglu(m, w["mlp/wi"][0], w["mlp/wi"][1], w["mlp/wo"])
    return a + _experts(c, m, w)


class Reference:
    """Logits of one configuration on one seed's weights."""

    def __init__(self, published, seed, dtype=jnp.bfloat16, quantize=None):
        self.c = c = dict(published)
        self.key = weights_dots3.weights.seed_key(seed)
        top = weights_dots3.specs(c)

        @jax.jit
        def embed_rows(key, tokens):
            table = weights_dots3.leaf(key, "embed", 0, *top["embed"], dtype)
            return table[tokens].astype(jnp.float32)

        @functools.partial(jax.jit, static_argnames=("like",))
        def layer(key, i, x, live, like):
            with jax.default_matmul_precision("highest"):
                w = {n: _prepare(n, a, quantize) for n, a in
                     weights_dots3.layer_leaves(c, key, i, dtype,
                                                like).items()}
                return _layer(c, like, x, w, live, quantize == INDEX_BF16)

        @functools.partial(jax.jit, static_argnames=("rows",))
        def head(key, x, start, rows):
            with jax.default_matmul_precision("highest"):
                x = jax.lax.dynamic_slice_in_dim(x, start, rows, 0)
                x = _rms_norm(x, jnp.ones((c["hidden_size"],)),
                              c["rms_norm_eps"])
                return x @ _prepare("w_out", weights_dots3.leaf(
                    key, "w_out", 0, *top["w_out"], dtype), quantize)

        self._embed, self._layer, self._head = embed_rows, layer, head

    def logits(self, tokens, start, rows, pad_to):
        """float32 logits [rows, vocab] of positions start..start+rows-1 of
        ``tokens`` (1-D), computed at the static length ``pad_to`` (causal:
        a position never sees the padding behind it), so that one program
        serves every length; attention runs over the blocks of query rows
        that hold tokens and no others, so a request costs what its own
        length does (rows past the tokens read no attention: their logits
        mean nothing)."""
        tokens = np.asarray(tokens, np.int32)
        if pad_to > _Q_BLOCK:  # whole blocks of query rows
            pad_to = -(-pad_to // _Q_BLOCK) * _Q_BLOCK
        padded = np.zeros((pad_to,), np.int32)
        padded[:tokens.shape[0]] = tokens
        live = jnp.int32(-(-tokens.shape[0] // min(_Q_BLOCK, pad_to)))
        x = self._embed(self.key, jnp.asarray(padded))
        for i in range(self.c["num_hidden_layers"]):
            x = self._layer(self.key, jnp.int32(i), x, live,
                            weights_dots3.same_leaves(self.c, i))
        return self._head(self.key, x, jnp.int32(start), rows)
