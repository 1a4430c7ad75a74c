"""Weight-only int8 quantization for serving.

TPU rationale: autoregressive decode is HBM-bandwidth-bound on the
*weights* — every generated token re-reads the full parameter set while
the activations are a single token's worth.  Storing weights as int8
halves the bytes vs bf16, which is an upper bound of 2x on decode
throughput at small batch.  The scheme is chosen so the matmuls stay on
the MXU's fast path with nothing extra materialized in HBM:

  - **symmetric, per-output-channel scales**: for every weight the scale
    axis set is exactly the matmul's *contraction* axes, so
    ``einsum(x, W)`` equals ``einsum(x, W_int8) * scale`` with the scale
    broadcast over the einsum OUTPUT.  The dequantizing multiply commutes
    out of the dot — the int8->bf16 convert is the only producer fused
    into the matmul operand and the full-precision weight tensor never
    exists in memory;
  - the embedding table additionally supports row gather (decode's token
    lookup): gather int8 rows, then scale — the table is dequantized one
    token at a time, never wholesale;
  - 1D parameters (norm scales) stay in their original dtype: they are
    noise in the byte budget and precision-critical.

The reference's serving plane had no quantization story (its C++
``tensorflow_model_server`` served float SavedModels,
kubeflow/tf-serving/tf-serving.libsonnet:118-132); this is new,
TPU-first capability.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QTensor:
    """int8 values + broadcastable per-output-channel scale.

    ``scale``'s shape is ``values``'s with the contraction axes removed,
    so it broadcasts against the trailing dims of the matmul output.
    Indexing (``q[i]``) narrows both in step — the layer-stacked leaves
    in a scanned transformer slice transparently (lax.scan slices pytree
    leaves, and QTensor is a pytree).
    """

    values: jax.Array   # int8
    scale: jax.Array    # float32, shape = values' minus the axes below
    axes: Tuple[int, ...] = ()   # contraction axes, negative (static)

    def tree_flatten(self):
        return (self.values, self.scale), self.axes

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, axes=aux)

    def __getitem__(self, idx):
        # Leading-axis narrowing (k/v stack slice, scan layer slice);
        # negative contraction axes are unaffected.
        return QTensor(self.values[idx], self.scale[idx], self.axes)

    @property
    def shape(self):
        return self.values.shape

    def astype(self, dtype):
        """Full dequantization — only for callers that cannot keep the
        scale outside their contraction (prefer qeinsum)."""
        scale = jnp.expand_dims(self.scale, self.axes)
        return self.values.astype(dtype) * scale.astype(dtype)


# Per-weight contraction axes, counted from the END so layer-stacked
# leaves ([L, ...]) and unstacked ones share entries.  Matches the
# einsums in models/generate.py / models/transformer.py.
CONTRACTIONS: Dict[Tuple[str, ...], Tuple[int, ...]] = {
    ("embed",): (-1,),             # [v, e] contract e (head); gather rows
    ("w_out",): (-2,),             # [e, v] contract e
    ("attn", "wq"): (-3,),         # [e, h, d] contract e
    ("attn", "wkv"): (-3,),        # [2, e, h, d] contract e
    ("attn", "wo"): (-3, -2),      # [h, d, e] contract h, d
    ("mlp", "wi"): (-2,),          # [2, e, f] contract e
    ("mlp", "wo"): (-2,),          # [f, e] contract f
    # A stack with ``layer_types`` (models/transformer.py).  The
    # convolution's taps and the router are not here: a few thousand
    # numbers a layer, kept in float32.
    ("conv", "w_in"): (-3,),       # [e, 3, e] contract e
    ("conv", "w_out"): (-2,),      # [e, e] contract e
    ("moe", "wi"): (-2,),          # [n, e, 2f] contract e
    ("moe", "wo"): (-2,),          # [n, f, e] contract f
    # Latent attention (TransformerConfig.attention_kind).
    ("attn", "wq_a"): (-2,),       # [e, r_q] contract e
    ("attn", "wq_b"): (-3,),       # [r_q, h, d] contract r_q
    ("attn", "wkv_a"): (-2,),      # [e, r_kv + d_rope] contract e
    ("attn", "wk_b"): (-1,),       # [h, d, r_kv] contract r_kv (a chunk)
    ("attn", "wv_b"): (-3,),       # [r_kv, h, d] contract r_kv
    # The indexer (its per-head weights ``w_idx`` stay float32, as the
    # router), the headwise gate, the shared expert.
    ("attn", "wq_idx"): (-3,),     # [r_q, h_i, d_i] contract r_q
    ("attn", "wk_idx"): (-2,),     # [e, d_i] contract e
    ("attn", "wg"): (-2,),         # [e, h] contract e
    ("shared", "wi"): (-2,),       # [2, e, f] contract e
    ("shared", "wo"): (-2,),       # [f, e] contract f
    # The draft module's projection (TransformerConfig.mtp_layers).
    ("mtp", "eh_proj"): (-2,),     # [2e, e] contract 2e
}


def _match(path: Tuple[str, ...]):
    for suffix, axes in CONTRACTIONS.items():
        if path[-len(suffix):] == suffix:
            return axes
    return None


def map_matmul_weights(params: Any, fn) -> Any:
    """Apply ``fn(leaf, contraction_axes)`` to every CONTRACTIONS-table
    weight in the tree; other leaves pass through untouched."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)

    def visit(path, leaf):
        names = tuple(
            p.key for p in path
            if isinstance(p, jax.tree_util.DictKey)
        )
        axes = _match(names)
        return leaf if axes is None else fn(leaf, axes)

    return jax.tree_util.tree_unflatten(
        treedef, [visit(path, leaf) for path, leaf in flat])


def quantize_array(x, axes: Tuple[int, ...], eps: float = 1e-8, xp=jnp):
    """Symmetric int8: (values, scale) with amax/127 scales over ``axes``.

    Pass ``xp=numpy`` to run host-side (weight staging — jnp would route
    the work through the device); the single definition keeps the weight
    path and the KV-cache path on the same scheme.
    """
    x32 = x.astype("float32")
    amax = xp.max(xp.abs(x32), axis=axes, keepdims=True)
    scale = xp.maximum(amax, eps) / 127.0
    vals = xp.clip(xp.round(x32 / scale), -127, 127).astype("int8")
    return vals, xp.squeeze(scale, axis=axes)


def quantize_params(params: Any, bits: int = 8) -> Any:
    """Quantize known matmul weights of an LM param tree to QTensor.

    Runs before device staging, so the reduced byte count also applies
    to the host->device transfer.  Unknown leaves pass through.
    """
    assert bits == 8, "int8 is the only wired width"

    def q(leaf, axes):
        vals, scale = quantize_array(
            np.asarray(leaf, np.float32), axes, eps=1e-12, xp=np)
        return QTensor(jnp.asarray(vals), jnp.asarray(scale), axes)

    return map_matmul_weights(params, q)


def narrow_params(params: Any, dtype) -> Any:
    """Cast the known matmul weights (CONTRACTIONS table) to ``dtype``.

    The staging-precision counterpart of quantize_params: checkpoints
    carry float32 masters, and serving them as-is doubles every HBM
    weight read just to feed casts the matmuls do anyway.  Norm scales
    and anything else off the table keep their checkpoint dtype —
    including the nn.scan-stacked per-layer norm scales, which are 2-D
    and would be miscaught by any rank-based heuristic.
    """
    return map_matmul_weights(params, lambda leaf, _: leaf.astype(dtype))


def qeinsum(eq: str, x: jax.Array, w: Any, dtype) -> jax.Array:
    """einsum with an optionally-quantized second operand.

    For a QTensor the per-output-channel scale is applied AFTER the dot
    (it commutes out of the contraction), so the int8->dtype convert is
    the only op fused into the matmul operand and no dequantized weight
    tensor is materialized.
    """
    if isinstance(w, QTensor):
        y = jnp.einsum(eq, x, w.values.astype(dtype))
        return y * w.scale.astype(dtype)
    return jnp.einsum(eq, x, w.astype(dtype))


def embed_lookup(embed: Any, tokens: jax.Array, dtype) -> jax.Array:
    """Token-row gather from a (possibly int8) embedding table."""
    if isinstance(embed, QTensor):
        rows = embed.values[tokens].astype(dtype)
        return rows * embed.scale[tokens][..., None].astype(dtype)
    return embed.astype(dtype)[tokens]
