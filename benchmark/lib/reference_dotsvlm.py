"""The plain reference of dots.vlm1.inst's language model with its
multi-token-prediction module (``configs/dots.vlm1.inst-l5.json``):
``tests/reference_dotsvlm.py``'s equations, computed a layer at a time, an
expert at a time, and attention in groups of heads and blocks of query rows,
so that the published widths and a 9k context fit beside nothing else.
float32, ``default_matmul_precision("highest")``, one sequence, no cache, no
batching, no sorting, no kernel, keys and values expanded from the latent for
every position (the program's decode step never forms them).

With ``N`` an RMSNorm (eps ``rms_norm_eps``, a scale) and ``x`` the stream, a
layer is ``a = x + MLA(N(x)); y = a + FF(N'(a))``; after the last layer
``h = N_final(x)``, then an untied head over the vocabulary's slice.

``MLA(u)``, no bias, NO factor on the low-rank norms:

    q = N_q(u W_qa) W_qb  -> per head (q_nope [qk_nope_head_dim], q_rope)
    (l, k_r) = split(u W_kva, [kv_lora_rank, qk_rope_head_dim]); c = N_kv(l)
    rotary positions on q_rope and k_r over interleaved pairs (2i, 2i + 1)
        at YaRN's frequencies: f_i = theta^(-2i/d); cd(n) = d ln(original /
        (2 pi n)) / (2 ln theta); low = floor(cd(beta_fast)), high =
        ceil(cd(beta_slow)); ramp_i = clip((i - low) / (high - low), 0, 1);
        f'_i = f_i (1 - ramp_i) + (f_i / factor) ramp_i
    k_j = [c W_uk_j, k_r],  v_j = c W_uv_j;  k_r is ONE head for all
    score_j(t, s) = q_j(t) . k_j(s) * (qk_nope_head_dim +
        qk_rope_head_dim)^-0.5 * m^2,  m = 0.1 mscale_all_dim ln(factor) + 1,
        s <= t;  softmax;  out = concat_j(sum_s p_j(t, s) v_j(s)) W_o

``FF``: layers before ``first_k_dense_replace`` a SwiGLU of
``intermediate_size``; the others ``s = sigmoid(m W_r)`` over the
``n_routed_experts_published`` outputs, ``e = s + bias``, ``n_group`` runs of
consecutive experts of which the ``topk_group`` with the largest sum of
their 2 largest e are kept (ties to the lower group), the
``num_experts_per_tok`` largest e inside them chosen (ties to the lower
expert), ``w_i = routed_scaling_factor * s_i / (sum of the chosen s +
1e-20)``, a routed expert a SwiGLU of ``moe_intermediate_size``, plus ONE
shared SwiGLU of that width added unweighted.  THIS CHIP'S SHARE: the routed
sum runs over the chosen experts among ``[experts_offset, experts_offset +
n_routed_experts)``, whose weights the tree holds; what the absent experts
would add is left out, as the program leaves it out.

The module, for position i with the main stack's ``h_i`` and the NEXT token:

    z_i = [N_e(Emb(t_{i+1})); N_h(h_i)] W_eh;  z' = Layer_n(z) over rows
    0..i (row i at rotary position i);  logits_i = Head(N_s(z'_i)),
    which predicts t_{i+2}

It imports nothing of the program and takes nothing the program made: the
weights come from ``weights_dotsvlm.leaf`` by the run's seed, a layer at a
time.  ``quantize`` is the control of the correctness check
(``reference.py``): every matmul weight rounded to int8 or float8_e4m3 with a
scale per output channel; the router, its bias and the norms are left as
they are.  ``logits`` also leaves the module's rows of the same positions in
``MODULE_ROWS`` (the runner's check of the drafts reads them; the main
stack is then computed once a request).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import weights_dotsvlm
from .reference import _fake_fp8, _fake_int8, _rms_norm, served_gaps
from .reference_dots3 import _Q_BLOCK, _blocks, _over_blocks
from .reference_longcat import _swiglu

__all__ = ["Reference", "served_gaps", "MODULE_ROWS"]

_HEAD_GROUP = 16
# Which axes each matmul weight contracts over (for the controls).
_CONTRACT = {"attn/wq_a": (0,), "attn/wq_b": (0,), "attn/wkv_a": (0,),
             "attn/wk_b": (2,), "attn/wv_b": (0,), "attn/wo": (0, 1),
             "mlp/wi": (1,), "mlp/wo": (0,), "moe/wi": (1,), "moe/wo": (1,),
             "moe/shared/wi": (1,), "moe/shared/wo": (0,), "eh_proj": (0,),
             "w_out": (0,)}
# {(quantize, the tokens' bytes): float32 [rows, vocab]}: the module's logits
# of the positions the last ``logits`` calls were asked for.
MODULE_ROWS = {}


def _prepare(name, w, quantize):
    w = w.astype(jnp.float32)
    if quantize in ("int8", "fp8") and name in _CONTRACT:
        fake = {"int8": _fake_int8, "fp8": _fake_fp8}[quantize]
        w = fake(w, _CONTRACT[name])
    return w


def yarn_frequencies(c):
    """float64 [qk_rope_head_dim / 2]."""
    d, theta, y = c["qk_rope_head_dim"], c["rope_theta"], c["rope_scaling"]
    f = np.asarray([float(theta) ** (-2.0 * i / d) for i in range(d // 2)])
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


def _rope_pairs(x, freqs):
    """x [t, heads, d]; position i is row i; pairs (2i, 2i + 1)."""
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] \
        * jnp.asarray(freqs, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


def _attention(c, u, w, live):
    eps, freqs = c["rms_norm_eps"], yarn_frequencies(c)
    heads, rkv = c["num_attention_heads"], c["kv_lora_rank"]
    dn, dv = c["qk_nope_head_dim"], c["v_head_dim"]
    t = u.shape[0]
    qa = _rms_norm(u @ w["attn/wq_a"], w["attn/q_norm/scale"], eps)
    kva = u @ w["attn/wkv_a"]
    lat = _rms_norm(kva[:, :rkv], w["attn/kv_norm/scale"], eps)
    k_r = _rope_pairs(kva[:, None, rkv:], freqs)[:, 0]
    q_block, starts = _blocks(t)
    cols = jnp.arange(t)
    scale = softmax_scale(c)

    def group(first):
        """``_HEAD_GROUP`` heads from ``first`` on, over every query."""
        n = min(_HEAD_GROUP, heads)
        q = jnp.einsum("tr,rhd->thd", qa, jax.lax.dynamic_slice_in_dim(
            w["attn/wq_b"], first, n, 1))
        q_nope, q_rope = q[..., :dn], _rope_pairs(q[..., dn:], freqs)
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
            s = jnp.where((cols[None, :] <= rows[:, None])[None], s,
                          -jnp.inf)
            return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)

        return _over_blocks(block, starts, live).reshape(t, n, dv)

    out = jax.lax.map(group, jnp.arange(0, heads, min(_HEAD_GROUP, heads)))
    out = jnp.moveaxis(out, 0, 1).reshape(t, heads, dv)
    return jnp.einsum("thd,hde->te", out, w["attn/wo"])


def _experts(c, m, w):
    f, n = c["moe_intermediate_size"], c["n_routed_experts_published"]
    first, held = c.get("experts_offset", 0), c["n_routed_experts"]
    groups = c["n_group"]
    s = jax.nn.sigmoid(m @ w["moe/router"])
    e = s + w["moe/bias"]
    of_group = jnp.sort(e.reshape(-1, groups, n // groups),
                        -1)[..., -2:].sum(-1)
    kept = jnp.argsort(-of_group, -1, stable=True)[:, :c["topk_group"]]
    in_kept = jnp.zeros_like(of_group).at[
        jnp.arange(m.shape[0])[:, None], kept].set(1.0)
    e = jnp.where(jnp.repeat(in_kept, n // groups, -1) > 0, e, -jnp.inf)
    chosen = jnp.argsort(-e, -1, stable=True)[:, :c["num_experts_per_tok"]]
    weight = jnp.zeros_like(s).at[jnp.arange(m.shape[0])[:, None],
                                  chosen].set(1.0) * s
    if c["norm_topk_prob"]:
        weight = weight / (weight.sum(-1, keepdims=True) + 1e-20)
    weight = weight * c["routed_scaling_factor"]

    def one(out, expert):  # an expert at a time, over every row
        wi, wo, gate = expert
        return out + gate[:, None] * _swiglu(m, wi[:, :f], wi[:, f:],
                                             wo), None

    out = jax.lax.scan(one, jnp.zeros_like(m), (
        w["moe/wi"], w["moe/wo"], weight[:, first:first + held].T))[0]
    return out + _swiglu(m, w["moe/shared/wi"][0], w["moe/shared/wi"][1],
                         w["moe/shared/wo"])


def _layer(c, x, w, live):
    """``live``: the blocks of query rows (``_blocks``) that hold tokens;
    attention leaves the rows of the others, padding alone, at zero."""
    eps = c["rms_norm_eps"]
    a = x + _attention(c, _rms_norm(x, w["attn_norm/scale"], eps), w, live)
    m = _rms_norm(a, w["mlp_norm/scale"], eps)
    if "mlp/wi" in w:
        return a + _swiglu(m, w["mlp/wi"][0], w["mlp/wi"][1], w["mlp/wo"])
    return a + _experts(c, m, w)


class Reference:
    """Logits of one configuration on one seed's weights."""

    def __init__(self, published, seed, dtype=jnp.bfloat16, quantize=None):
        self.c = c = dict(published)
        self.quantize = quantize
        self.key = weights_dotsvlm.weights.seed_key(seed)
        top = weights_dotsvlm.specs(c)
        d, eps = c["hidden_size"], c["rms_norm_eps"]

        def table(key):
            return weights_dotsvlm.leaf(key, "embed", 0, *top["embed"], dtype)

        @jax.jit
        def embed_rows(key, tokens):
            return table(key)[tokens].astype(jnp.float32)

        @functools.partial(jax.jit, static_argnames=("like",))
        def layer(key, i, x, live, like):
            with jax.default_matmul_precision("highest"):
                w = {n: _prepare(n, a, quantize) for n, a in
                     weights_dotsvlm.layer_leaves(c, key, i, dtype,
                                                  like).items()}
                return _layer(c, x, w, live)

        @jax.jit
        def final_norm(x):
            return _rms_norm(x, jnp.ones((d,)), eps)

        @jax.jit
        def module_input(key, tokens, h):
            """z of every position: row i reads (h_i, tokens[i + 1])."""
            with jax.default_matmul_precision("highest"):
                w = {n: _prepare(n, a, quantize) for n, a in
                     weights_dotsvlm.module_leaves(c, key, dtype).items()}
                after = jnp.roll(tokens, -1)   # the last row reads padding
                emb = table(key)[after].astype(jnp.float32)
                return jnp.concatenate([
                    _rms_norm(emb, w["enorm/scale"], eps),
                    _rms_norm(h, w["hnorm/scale"], eps)], -1) @ w["eh_proj"]

        @functools.partial(jax.jit, static_argnames=("rows",))
        def head(key, x, start, rows):
            """The head over already-normed rows."""
            with jax.default_matmul_precision("highest"):
                x = jax.lax.dynamic_slice_in_dim(x, start, rows, 0)
                return x @ _prepare("w_out", weights_dotsvlm.leaf(
                    key, "w_out", 0, *top["w_out"], dtype), quantize)

        self._embed, self._layer, self._head = embed_rows, layer, head
        self._final_norm, self._module_input = final_norm, module_input

    def logits(self, tokens, start, rows, pad_to):
        """float32 logits [rows, vocab] of positions start..start+rows-1 of
        ``tokens`` (1-D), computed at the static length ``pad_to`` (causal:
        a position never sees the padding behind it), so that one program
        serves every length; attention runs over the blocks of query rows
        that hold tokens and no others.  Leaves the MODULE's logits of the
        same positions in ``MODULE_ROWS`` (row r read (h, the token after)
        at position start + r and predicts the token two on)."""
        tokens = np.asarray(tokens, np.int32)
        if pad_to > _Q_BLOCK:  # whole blocks of query rows
            pad_to = -(-pad_to // _Q_BLOCK) * _Q_BLOCK
        padded = np.zeros((pad_to,), np.int32)
        padded[:tokens.shape[0]] = tokens
        padded = jnp.asarray(padded)
        live = jnp.int32(-(-tokens.shape[0] // min(_Q_BLOCK, pad_to)))
        c, n = self.c, self.c["num_hidden_layers"]
        x = self._embed(self.key, padded)
        for i in range(n):
            x = self._layer(self.key, jnp.int32(i), x, live,
                            weights_dotsvlm.same_leaves(c, i))
        h = self._final_norm(x)
        del x
        out = self._head(self.key, h, jnp.int32(start), rows)
        if c.get("num_nextn_predict_layers"):
            z = self._module_input(self.key, padded, h)
            z = self._layer(self.key, jnp.int32(n), z, live,
                            weights_dotsvlm.same_leaves(c, n))
            MODULE_ROWS[(self.quantize, tokens.tobytes())] = np.asarray(
                self._head(self.key, self._final_norm(z), jnp.int32(start),
                           rows))
        return out
