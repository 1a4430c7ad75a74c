"""Autoregressive decoding for the Transformer LM.

The reference's serving story was TF-Serving for classifiers; LMs are this
framework's flagship, so decode is first-party.  TPU-shaped choices:

  - the KV cache is a preallocated [layers, 2, b, max_len, h, d] buffer
    carried through ``lax.scan`` — static shapes end to end, one compiled
    program for the whole generation;
  - prefill and decode are the same jitted function: the prompt is
    processed in one batched forward (MXU-efficient), then tokens stream
    one position at a time against the cache;
  - greedy or temperature sampling under ``jax.random``.

Kept outside the Flax module on purpose: the cache is explicit function
state (scan carry), not module state — no mutable-collection plumbing,
and the whole loop jits/shards like any other pure function.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from kubeflow_tpu.models.transformer import (
    Transformer,
    TransformerConfig,
    rope,
    yarn_frequencies,
)
from kubeflow_tpu.ops.attention import dot_product_attention
from kubeflow_tpu.ops.quantize import (
    QTensor,
    embed_lookup,
    qeinsum,
    quantize_array,
)


@dataclasses.dataclass(frozen=True)
class DecodeConfig:
    max_new_tokens: int = 64
    temperature: float = 0.0   # 0 = greedy
    # Sampling filters (applied in this order when temperature > 0):
    # top_k keeps the k highest-logit tokens (0 = off); top_p keeps the
    # smallest set of tokens whose probability mass reaches p (1.0 =
    # off, i.e. nucleus sampling).  Both are static-shape TPU code: a
    # top_k threshold compare and a sorted-cumsum mask — no dynamic
    # vocabulary subsets.
    top_k: int = 0
    top_p: float = 1.0
    eos_token: int = -1        # -1 = never stop early

    def __post_init__(self):
        if not 0.0 < self.top_p <= 1.0:
            raise ValueError(
                f"top_p must be in (0, 1], got {self.top_p} "
                "(1.0 disables nucleus filtering)")
        if self.top_k < 0:
            raise ValueError(f"top_k must be >= 0, got {self.top_k}")
    # "model" = the model compute dtype; "int8" = quantized cache with
    # per-(position, head) scales (halves cache HBM traffic and memory —
    # the binding resource for batched decode; ops/attention.py folds the
    # scales through both matmuls so nothing dequantized materializes).
    kv_cache_dtype: str = "model"


def _lora(x, a, b, spec_a, spec_b):
    """Per-row low-rank delta: contract ``x`` against PER-ROW factor
    slices ``a``/``b`` (leading batch axis — row i's slice is its own
    adapter's, gathered by ``_forward_with_cache`` from the stacked
    [n_adapters, ...] arrays) in two rank-r hops, so the full-rank
    delta matrix never materializes and the cost stays O(r) of the
    base projection.  Row independence is what makes a mixed-adapter
    batch bit-identical to per-adapter sequential runs."""
    mid = jnp.einsum(spec_a, x, a)
    return jnp.einsum(spec_b, mid, b).astype(x.dtype)


def _rms_norm(x, scale, eps, dtype):
    """RMSNorm in float32, cast to ``dtype`` (models/transformer.py
    RMSNorm on plain arrays)."""
    x32 = x.astype(jnp.float32)
    normed = x32 * jax.lax.rsqrt(
        jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (normed * scale).astype(dtype)


# Query rows a tile of the view's attention (``_view_attention``): the
# width the chunk program ran at until PR 36, whose fusions the chip's
# compiler is known to make well.
_VIEW_QUERY_TILE = 64

# Key positions a tile of a slot's view at most (``view_key_tiles``): a
# call of several query tiles visits the view tile by tile and stops after
# the one that holds its last visible position.
_VIEW_KEY_TILE = 512


def view_key_tiles(max_blocks: int, block_tokens: int, t: int):
    """How a call of ``t`` query columns a row visits a slot's view of
    ``max_blocks`` pages of ``block_tokens`` positions: ``(tile, tiles)``,
    the positions of a key tile (whole pages; at most ``_VIEW_KEY_TILE``
    and an eighth of the table) and how many the table holds, the last
    one perhaps short.  ``(view, 1)``, ONE pass over the whole view,
    where the call holds no two tiles of query rows (a decode step, a
    drafting stack's two rows a slot) or the table no more than two key
    tiles: nothing to leave out that is worth a branch."""
    view = max_blocks * block_tokens
    if (t < 2 * _VIEW_QUERY_TILE or t % _VIEW_QUERY_TILE
            or view <= 2 * _VIEW_KEY_TILE):
        return view, 1
    pages = max(1, min(_VIEW_KEY_TILE, view // 8) // block_tokens)
    return pages * block_tokens, -(-max_blocks // pages)


def _tiles_visited(held, tile: int, tiles: int):
    """Key tiles that hold positions ``[0, held)``, of a view's ``tiles``:
    ``held`` a Python int (the engine's counter) or a traced scalar (the
    program's own bound)."""
    lib = jnp if isinstance(held, jax.Array) else np
    return lib.clip((held + tile - 1) // tile, 1, tiles)


def view_positions_scored(max_blocks: int, block_tokens: int, t: int,
                          held: int) -> int:
    """View positions a call of ``t`` columns whose last row sees
    ``held`` positions gathers and scores: ``held`` rounded up to whole
    key tiles, the whole view at most (and always where
    ``view_key_tiles`` says one pass)."""
    tile, tiles = view_key_tiles(max_blocks, block_tokens, t)
    return min(int(_tiles_visited(held, tile, tiles)) * tile,
               max_blocks * block_tokens)


def _held_key_tiles(tables, block_tokens: int, t: int, cache_len):
    """The key tiles a call of ``t`` columns a row visits of its rows'
    views (``tables`` [b, max_blocks]): None where ``view_key_tiles``
    says one pass, else ``(tile, visited, pages_of)``: the positions of a
    tile, the (traced) tiles that hold every position the call's last
    column may see (``cache_len + t`` of them, over the rows), and
    ``pages_of(i)`` [b, tile pages], tile i's entries of the tables.  A
    position past the visited tiles is masked for every query of the
    call, so its weight is exactly 0 in a pass over the whole view too:
    what is left out is its gather, its score and the sums of zeros."""
    max_blocks = tables.shape[1]
    tile, tiles = view_key_tiles(max_blocks, block_tokens, t)
    if tiles == 1:
        return None
    pages = tile // block_tokens
    # The last tile may overhang the table: its entries past it lie past
    # the view's length, which the forms below mask.
    padded = jnp.pad(tables, ((0, 0), (0, tiles * pages - max_blocks)))
    return tile, _tiles_visited(jnp.max(cache_len) + t, tile, tiles), (
        lambda i: jax.lax.dynamic_slice_in_dim(
            padded, i * pages, pages, axis=1))


def _view_attention(q, view_k, view_v, cache_len, pad_amount):
    """Attention of the call's q [b, t, h, d] over a gathered view of the
    pool, in tiles of ``_VIEW_QUERY_TILE`` query rows where t holds
    several.  A row's scores and softmax are its own, so a tile's rows
    come out as they would from one call over all of t; what changes is
    the float32 score array a layer holds at once, ``[h, tile, view]`` in
    place of ``[h, t, view]``: at 256 rows over Mistral's 6,400-long view
    the whole array is 210 MB, and the chip's compiler made the softmax's
    reduction over it 14 ms a layer where a tile's takes 0.1 (PERF.md
    section 6, PR 36)."""
    t = q.shape[1]
    tiles, rest = divmod(t, _VIEW_QUERY_TILE)
    if tiles < 2 or rest:
        return dot_product_attention(
            q, view_k, view_v, causal=True,
            kv_offset=cache_len, kv_valid_start=pad_amount)

    def tile(i):
        first = i * _VIEW_QUERY_TILE
        return dot_product_attention(
            jax.lax.dynamic_slice_in_dim(q, first, _VIEW_QUERY_TILE, axis=1),
            view_k, view_v, causal=True,
            kv_offset=cache_len + first, kv_valid_start=pad_amount)

    out = jax.lax.map(tile, jnp.arange(tiles))      # [tiles, b, tile, h, d]
    return jnp.moveaxis(out, 0, 1).reshape(q.shape)


def _online_softmax(tile_of, scores_of, sums_of, rows, lanes, q_pos, tile,
                    visited, view, first=None):
    """Softmax-weighted sums over ``visited`` (traced) key tiles of
    ``tile`` positions of a view ``view`` long, with a running float32
    max / sum / accumulator of shapes ``rows`` / ``rows`` / ``rows +
    (lanes,)``: ``tile_of(i)`` gathers key tile i, ``scores_of(held)``
    gives its float32 scores ``rows + (tile,)`` and ``sums_of(held, w)``
    the float32 sums ``rows + (lanes,)`` of its values under weights w.
    A row attends the positions up to its own ``q_pos`` (and from
    ``first`` on), both broadcastable to ``rows``.  A masked score is the
    least float32, which is also where the running max starts: once a
    row has met a position it may see, a masked one weighs exp(least -
    max) = 0 exactly, as in one pass over the whole view."""
    least = jnp.finfo(jnp.float32).min

    def body(i, carry):
        m, l, acc = carry
        held = tile_of(i)
        k_pos = i * tile + jnp.arange(tile)
        keep = (k_pos <= q_pos[..., None]) & (k_pos < view)
        if first is not None:
            keep = keep & (k_pos >= first[..., None])
        sc = jnp.where(keep, scores_of(held), least)
        m_new = jnp.maximum(m, sc.max(axis=-1))
        w = jnp.exp(sc - m_new[..., None])
        fade = jnp.exp(m - m_new)
        return (m_new, l * fade + w.sum(axis=-1),
                acc * fade[..., None] + sums_of(held, w))

    m, l, acc = jax.lax.fori_loop(0, visited, body, (
        jnp.full(rows, least, jnp.float32), jnp.zeros(rows, jnp.float32),
        jnp.zeros(rows + (lanes,), jnp.float32)))
    return acc / l[..., None]


def _tiled_view_attention(q, tile_of, hkv, cache_len, pad_amount, tile,
                          visited, view):
    """Attention of q [b, t, h, d] over ``visited`` (traced) key tiles of
    ``tile`` positions of a view ``view`` long: ``tile_of(i)`` gathers
    the keys and values of tile i, each [b, tile, hkv, d] (arrays or int8
    ``QTensor``s, whose scales fold into the scores and the weights as in
    ``dot_product_attention``).  The group's queries share a key head's
    tile as rows of one product: nothing is repeated over the group."""
    b, t, h, d = q.shape
    dt = q.dtype
    grouped = q.reshape(b, t, hkv, h // hkv, d)

    def split(c):
        return (c.values, c.scale) if isinstance(c, QTensor) else (c, None)

    def scores_of(held):
        k, k_scale = split(held[0])
        sc = jnp.einsum("bqngd,bknd->bngqk", grouped, k.astype(dt),
                        preferred_element_type=jnp.float32) * d ** -0.5
        if k_scale is not None:
            sc = sc * k_scale.transpose(0, 2, 1)[:, :, None, None, :]
        return sc

    def sums_of(held, w):
        v, v_scale = split(held[1])
        if v_scale is not None:
            w = w * v_scale.transpose(0, 2, 1)[:, :, None, None, :]
        return jnp.einsum("bngqk,bknd->bngqd", w.astype(dt), v.astype(dt),
                          preferred_element_type=jnp.float32)

    out = _online_softmax(
        tile_of, scores_of, sums_of, (b, hkv, h // hkv, t), d,
        jnp.reshape(cache_len, (-1, 1, 1, 1)) + jnp.arange(t), tile,
        visited, view,
        None if pad_amount is None else pad_amount.reshape(-1, 1, 1, 1))
    return out.transpose(0, 3, 1, 2, 4).reshape(q.shape).astype(dt)


def _page_coordinates(tables, cache_len, write_cols, b, t, nb, bt):
    """Where a call's ``t`` new columns a row land in a paged pool of
    ``nb`` blocks of ``bt`` positions: (physical block [b, t], offset in
    it [b, t], the rows' first column [b] or None where ``cache_len`` is
    one scalar for the batch).  Sentinel table entries (== nb) and
    logical indices past the table both park the write out of the
    pool's range: the scatter drops them."""
    mb = tables.shape[1]
    base = None
    if not isinstance(cache_len, int) and cache_len.ndim == 1:
        base = cache_len if write_cols is None else write_cols
        pos = base[:, None] + jnp.arange(t)[None, :]
    else:
        pos = cache_len + jnp.arange(t)[None, :]
        pos = jnp.broadcast_to(pos, (b, t))
    blk_slot = pos // bt
    blk = jnp.take_along_axis(
        tables, jnp.clip(blk_slot, 0, mb - 1), axis=1)
    blk = jnp.where(blk_slot < mb, blk, nb)
    return blk, pos % bt, base


def _attention_block(cfg: TransformerConfig, layer_params, x, cache_kv,
                     cache_len, positions, pad_amount=None, write_cols=None,
                     tables=None, adapters=None, paged_kernel=False,
                     plane=None, store=True):
    """The attention half of a decoder block against the KV cache: norm,
    projections, cache write, attention, output projection, residual.

    x: [b, t, e] new activations (t = prompt len at prefill, 1 at decode);
    cache_kv: (k, v) each [b, max_len, hkv, d], this layer's own — or,
    when ``tables`` is given, the paged block POOL of EVERY plane,
    stacked [kv_planes, num_blocks, block_tokens, hkv, d] and shared by
    every slot, of which this call writes and reads plane ``plane`` (a
    traced scalar) and hands the whole pool back;
    cache_len: number of valid cache positions before this call — a
    scalar (whole batch at one length, the generate() path) or a [b]
    array (per-row lengths, the slot-based decode_rounds path, always
    with ``tables``; each row writes its t new k/v columns starting at
    its OWN frontier and attends under its own causal mask via the
    per-row kv_offset — t is 1 at decode, 2 where a drafting stack
    holds its draft beside the slot's last token);
    pad_amount: per-row [b] left-pad width (bucketed mixed-length
    prompts) — cache columns before it hold pad-token garbage and are
    masked out of every attention.
    write_cols: per-row [b] cache column for the new k/v when cache_len
    is per-row (defaults to cache_len); rows that must not write this
    step (retired slots) pass an out-of-range column — the scatter
    drops it.
    tables: [b, max_blocks] int32 per-row block tables mapping each
    row's LOGICAL block index (position // block_tokens) to a physical
    pool block.  Fresh k/v scatter straight into the stacked pool at
    their (plane, block, offset) coordinates: t columns a row, never a
    plane sliced out and put back — a logical index past the table
    span, or a table entry holding the sentinel ``num_blocks``
    (unallocated), drops the write — and attention runs over the row's
    own pages of the plane: ONE gather ``pool[plane, tables]`` of the
    [max_blocks * block_tokens] view for a decode step and for a
    table of no more than two key tiles; for a call of several
    query tiles (the prefill chunk) over a longer table, a loop over
    key tiles that stops after the one holding the call's last visible
    position, ``cache_len + t - 1`` (``_held_key_tiles``: pages past it
    are not gathered, scored or summed).  Sentinel entries clamp onto
    an arbitrary block whose columns all sit beyond the causal
    frontier, so the garbage they contribute is masked.
    paged_kernel (static; the serving engine sets it when its pool
    lives on a TPU): a step with ONE query position per row (t == 1:
    decode_rounds) over a plain-array pool gathers no
    view — ops/paged_attention.py is handed the stacked pool and the
    plane and reads each row's resident pages in place (a row whose
    write is parked attends nothing).
    Wider steps (the prefill chunk), an int8
    ``QTensor`` pool and every other backend gather their pages and
    attend them with plain products (``_view_attention``,
    ``_tiled_view_attention``).
    cache_kv None (a forward without a cache, the flax module's): plain
    causal attention over the call's own q, k, v.
    """
    if cfg.latent:
        if pad_amount is not None or adapters is not None:
            raise ValueError(
                "latent attention with left-padded rows or adapters: "
                "not built")
        return _latent_attention_block(
            cfg, layer_params, x, cache_kv, cache_len, positions,
            write_cols=write_cols, tables=tables,
            paged_kernel=paged_kernel, plane=plane, store=store)
    if not store:
        raise ValueError("store=False: a latent layer's (see there)")
    attn = layer_params["attn"]
    dt = cfg.dtype

    def norm(x, scale):
        return _rms_norm(x, scale, cfg.norm_eps, dt)

    with jax.named_scope("kft.qkv_proj"):
        y = norm(x, layer_params["attn_norm"]["scale"])
        # qeinsum keeps int8 serving weights quantized through the dot
        # (per-output-channel scales applied after; ops/quantize.py).
        q = qeinsum("bse,ehd->bshd", y, attn["wq"], dt)
        k = qeinsum("bse,ehd->bshd", y, attn["wkv"][0], dt)
        v = qeinsum("bse,ehd->bshd", y, attn["wkv"][1], dt)
        if adapters is not None:
            # Adapter-array serving (§5.11): each row adds ITS adapter's
            # low-rank delta to every projection, pre-rope so the delta
            # is part of the projection itself.  Row 0 of the stack is
            # the all-zero base delta, so base traffic co-batches with
            # tenant traffic at identical math.
            ad = adapters["attn"]
            q = q + _lora(y, ad["wq_a"], ad["wq_b"],
                          "bse,ber->bsr", "bsr,brhd->bshd")
            k = k + _lora(y, ad["wkv_a"][:, 0], ad["wkv_b"][:, 0],
                          "bse,ber->bsr", "bsr,brhd->bshd")
            v = v + _lora(y, ad["wkv_a"][:, 1], ad["wkv_b"][:, 1],
                          "bse,ber->bsr", "bsr,brhd->bshd")
        if cfg.qk_norm:
            q = norm(q, attn["q_norm"]["scale"])
            k = norm(k, attn["k_norm"]["scale"])
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)

    ck, cv = cache_kv if cache_kv is not None else (None, None)
    t = x.shape[1]
    per_row = not isinstance(cache_len, int) and cache_len.ndim == 1
    if cache_kv is None:
        with jax.named_scope("kft.attention"):
            out = dot_product_attention(q, k, v, causal=True)
    elif tables is not None:
        vals = ck.values if isinstance(ck, QTensor) else ck
        nb, bt = vals.shape[1], vals.shape[2]
        mb = tables.shape[1]

        def store(c, new):  # new: [b, t, hk, d]
            if isinstance(c, QTensor):
                qvals, s = quantize_array(new, (-1,))
                return QTensor(
                    c.values.at[plane, blk, off].set(qvals, mode="drop"),
                    c.scale.at[plane, blk, off].set(s, mode="drop"),
                    c.axes,
                )
            return c.at[plane, blk, off].set(new.astype(c.dtype),
                                             mode="drop")

        with jax.named_scope("kft.kv_write"):
            blk, off, base = _page_coordinates(
                tables, cache_len, write_cols, x.shape[0], t, nb, bt)
            ck = store(ck, k)
            cv = store(cv, v)

        def paged_view(c, pages):
            # Row view of the (just-updated) pool, the row's pages of
            # this plane in ONE gather (p[plane] first would copy the
            # plane): OOB sentinel entries clamp, contributing finite
            # garbage that the kv_offset mask discards.
            def gather(p):
                g = p[plane, pages]
                return g.reshape(
                    (pages.shape[0], pages.shape[1] * bt) + p.shape[3:])

            if isinstance(c, QTensor):
                return QTensor(gather(c.values), gather(c.scale),
                               c.axes)
            return gather(c)

        if (paged_kernel and t == 1 and per_row and pad_amount is None
                and not isinstance(ck, QTensor)):
            from kubeflow_tpu.ops import paged_attention

            with jax.named_scope("kft.attention"):
                # The step's own k/v are in the pool already (above), so
                # a live row attends its cache_len + 1 positions; a
                # parked write marks a retired row, which reads nothing.
                attend = jnp.where(base < mb * bt, cache_len + 1, 0)
                out = paged_attention.paged_decode_attention(
                    q[:, 0], ck, cv, plane, tables, attend)[:, None]
        else:
            held = _held_key_tiles(tables, bt, t, cache_len)
            if held is None:
                with jax.named_scope("kft.kv_view"):
                    view_k = paged_view(ck, tables)
                    view_v = paged_view(cv, tables)
                with jax.named_scope("kft.attention"):
                    out = _view_attention(q, view_k, view_v, cache_len,
                                          pad_amount)
            else:
                tile, visited, pages_of = held

                def tile_of(i):
                    with jax.named_scope("kft.kv_view"):
                        return (paged_view(ck, pages_of(i)),
                                paged_view(cv, pages_of(i)))

                with jax.named_scope("kft.attention"):
                    out = _tiled_view_attention(
                        q, tile_of, vals.shape[3], cache_len, pad_amount,
                        tile, visited, mb * bt)
    elif isinstance(ck, QTensor):
        def store(c, new):
            vals, s = quantize_array(new, (-1,))    # [b, t, hk, d]
            return QTensor(
                jax.lax.dynamic_update_slice_in_dim(
                    c.values, vals, cache_len, axis=1),
                jax.lax.dynamic_update_slice_in_dim(
                    c.scale, s, cache_len, axis=1),
                c.axes,
            )

        with jax.named_scope("kft.kv_write"):
            ck = store(ck, k)
            cv = store(cv, v)
    else:
        with jax.named_scope("kft.kv_write"):
            ck = jax.lax.dynamic_update_slice_in_dim(
                ck, k.astype(ck.dtype), cache_len, axis=1)
            cv = jax.lax.dynamic_update_slice_in_dim(
                cv, v.astype(cv.dtype), cache_len, axis=1)
    # Attend over the whole buffer; positions beyond cache_len + t are
    # masked by the causal rule (their k_pos > any live q_pos... they are
    # zeros at positions >= cache_len+t, masked via kv_offset arithmetic).
    #
    # Prefill of a LONG prompt on a flash-configured model uses the
    # Pallas flash kernel over the fresh q/k/v instead (the cache is
    # empty at prefill, so causal attention over the prompt alone is the
    # whole computation): the dot path materializes the [b, h, t, t]
    # score matrix in HBM — O(t^2) memory that defeats the point of
    # serving a long-context model whose TRAINING path is O(t).
    # Left-padded bucketed batches ride the kernel's forward-only
    # per-row key-start mask (kv_valid_start — pad keys get zero
    # weight), so DEPLOYED bucketed serving flash-prefills too.  Gated
    # off only for quantized caches (the dot path attends against the
    # freshly quantized cache, and serving goldens pin that rounding).
    # cache_len is a static python 0 at prefill and a TRACED scalar in
    # the decode scan — the gate must only ever inspect the static case.
    static_prefill = (cache_kv is not None and tables is None
                      and isinstance(cache_len, int) and cache_len == 0)
    if (cfg.attention == "flash" and t > 1 and static_prefill
            and not isinstance(ck, QTensor)):
        from kubeflow_tpu.ops.flash import flash_attention

        with jax.named_scope("kft.attention"):
            out = flash_attention(
                q, k, v, causal=True,
                block_q=cfg.flash_block_q, block_k=cfg.flash_block_k,
                kv_valid_start=pad_amount,
            )
    elif tables is None and cache_kv is not None:
        with jax.named_scope("kft.attention"):
            out = dot_product_attention(
                q, ck, cv, causal=True, kv_offset=cache_len,
                kv_valid_start=pad_amount,
            )
    with jax.named_scope("kft.attn_out"):
        y = qeinsum("bshd,hde->bse", out, attn["wo"], dt)
        if adapters is not None:
            ad = adapters["attn"]
            y = y + _lora(out, ad["wo_a"], ad["wo_b"],
                          "bshd,bhdr->bsr", "bsr,bre->bse")
        if cfg.sandwich_norm:
            with jax.named_scope("kft.loop_norm"):
                y = norm(y, layer_params["attn_out_norm"]["scale"])
        x = x + y
    return x, (None if cache_kv is None else (ck, cv))


def _rope_pairs(x, positions, theta, yarn=None):
    """Rotary positions over interleaved pairs (2i, 2i + 1) of the last
    axis (``transformer.rope`` pairs i with i + d / 2).  x [b, s, h, d].
    ``yarn`` (``LatentSizes.yarn``): YaRN's frequencies, a constant of the
    program computed in float64 (``transformer.yarn_frequencies``)."""
    d = x.shape[-1]
    if yarn is None:
        freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    else:
        freqs = jnp.asarray(yarn_frequencies(d, theta, yarn), jnp.float32)
    angles = positions[..., None].astype(jnp.float32) * freqs
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (d // 2, 2))
    x1, x2 = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _latent_view_attention(q_row, view, value_lanes, kv_offset, scale,
                           first=0):
    """Attention of absorbed queries q_row [b, t, h, row] over a slot's
    gathered view of the latent pool [b, s, row] -> [b, t, h,
    value_lanes], in the latent space: a position's row is key (whole)
    and value (its first ``value_lanes`` lanes) for every head, so the
    heads fold into the rows of ONE product against the view.
    ``kv_offset`` (a scalar, or [b] per row): position of query column 0
    among the view's; positions below ``first`` (static) are attended by
    nobody.  Softmax in float32; in tiles of
    ``_VIEW_QUERY_TILE`` query rows where t holds several, as
    ``_view_attention`` (the float32 scores are [h, tile, s])."""
    dt = q_row.dtype
    b, t, h, _ = q_row.shape
    values = view[..., :value_lanes]

    def attend(q, offset):
        n = q.shape[1]
        # (query, head) pairs are the rows of one product with the view;
        # row m is query m // h, which sees the view up to its position.
        sc = jnp.einsum("bmr,bkr->bmk", q.reshape(b, n * h, -1), view,
                        preferred_element_type=jnp.float32) * scale
        q_pos = jnp.asarray(offset)[..., None] + jnp.arange(n * h) // h
        k_pos = jnp.arange(view.shape[1])
        keep = (k_pos <= q_pos[..., None]) & (k_pos >= first)
        w = jax.nn.softmax(
            jnp.where(keep, sc, jnp.finfo(jnp.float32).min), axis=-1)
        out = jnp.einsum("bmk,bkc->bmc", w.astype(dt), values,
                         preferred_element_type=jnp.float32)
        return out.astype(dt).reshape(b, n, h, value_lanes)

    tiles, rest = divmod(t, _VIEW_QUERY_TILE)
    if tiles < 2 or rest:
        return attend(q_row, kv_offset)

    def tile(i):
        first = i * _VIEW_QUERY_TILE
        return attend(jax.lax.dynamic_slice_in_dim(
            q_row, first, _VIEW_QUERY_TILE, axis=1), kv_offset + first)

    out = jax.lax.map(tile, jnp.arange(tiles))
    return jnp.moveaxis(out, 0, 1).reshape(b, t, h, value_lanes)


def _tiled_latent_view_attention(q_row, rows_of, value_lanes, kv_offset,
                                 scale, tile, visited, view, first=0):
    """``_latent_view_attention`` over ``visited`` (traced) key tiles of
    ``tile`` positions of a view ``view`` long; ``rows_of(i)`` gathers
    tile i [b, tile, row]."""
    dt = q_row.dtype
    b, t, h, _ = q_row.shape
    q = q_row.reshape(b, t * h, -1)

    def scores_of(held):
        return jnp.einsum("bmr,bkr->bmk", q, held,
                          preferred_element_type=jnp.float32) * scale

    def sums_of(held, w):
        return jnp.einsum("bmk,bkc->bmc", w.astype(dt),
                          held[..., :value_lanes],
                          preferred_element_type=jnp.float32)

    out = _online_softmax(
        rows_of, scores_of, sums_of, (b, t * h), value_lanes,
        jnp.asarray(kv_offset)[..., None] + jnp.arange(t * h) // h, tile,
        visited, view,
        jnp.full((b, t * h), first, jnp.int32) if first else None)
    return out.astype(dt).reshape(b, t, h, value_lanes)


def _layer_norm(x, scale, bias, eps, dtype):
    """LayerNorm (mean and variance, a scale and a bias) in float32."""
    x32 = x.astype(jnp.float32)
    x32 = x32 - jnp.mean(x32, axis=-1, keepdims=True)
    normed = x32 * jax.lax.rsqrt(
        jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (normed * scale + bias).astype(dtype)


def _rope_leading(x, positions, theta, dr):
    """Rotary pairs on the first ``dr`` values of x [b, s, h, d]."""
    return jnp.concatenate(
        [_rope_pairs(x[..., :dr], positions, theta), x[..., dr:]], axis=-1)


# Float32 scores an index key tile may hold at once (``_index_scores``):
# 32 MB over the call's rows and index heads.
_INDEX_TILE_SCORES = 1 << 23

# Key positions an index key tile at most.
_INDEX_KEY_TILE = 2048


def index_key_tiles(max_blocks: int, block_tokens: int, rows: int):
    """How ``_index_scores`` visits a slot's index keys for ``rows``
    (query, index head) pairs a call: ``(pages a tile, tiles)``."""
    positions = min(_INDEX_KEY_TILE, max(1, _INDEX_TILE_SCORES // rows))
    pages = max(1, min(positions // block_tokens, max_blocks))
    return pages, -(-max_blocks // pages)


def index_positions_scored(max_blocks: int, block_tokens: int, rows: int,
                            held: int) -> int:
    """Index keys ``_index_scores`` scores for a call of ``rows`` (query,
    index head) pairs whose last query sees ``held`` positions: ``held``
    rounded up to whole index key tiles."""
    pages, tiles = index_key_tiles(max_blocks, block_tokens, rows)
    tile = pages * block_tokens
    return int(_tiles_visited(held, tile, tiles)) * tile


def index_keys_walked(index_pool) -> bool:
    """Whether a decode step that reads its pools in place
    (``paged_kernel``) also scores its index keys so, page by page
    (ops/paged_attention.py ``paged_index_scores``), from what the index
    pool [planes, num_blocks, block_tokens, index_dim] is: the layout the
    kernel can copy.  Else the key tiles of ``_index_scores``."""
    from kubeflow_tpu.ops import paged_attention

    return paged_attention.supports_index(
        index_pool.shape[3], index_pool.shape[2], index_pool.dtype)


def _index_scores(q_idx, w_idx, keys_of, tile, tiles, visited, q_pos):
    """The indexer's scores ``I(t, s) = sum_h w_h(t) relu(qI_h(t) .
    kI(s))`` in float32, [b, t, tiles * tile]: ``keys_of(i)`` gathers key
    tile i [b, tile, d] of ``tiles``, of which the first ``visited``
    (traced) are scored; a position past a row's own ``q_pos`` [b, t], or
    in a tile not visited, reads -inf."""
    b, t = q_idx.shape[:2]

    def body(i, out):
        keys = keys_of(i)
        sc = jnp.einsum("bthd,bkd->bthk", q_idx, keys,
                        preferred_element_type=jnp.float32)
        sc = jnp.einsum("bthk,bth->btk", jax.nn.relu(sc), w_idx)
        k_pos = i * tile + jnp.arange(tile)
        sc = jnp.where(k_pos <= q_pos[..., None], sc, -jnp.inf)
        return jax.lax.dynamic_update_slice_in_dim(out, sc, i * tile, 2)

    return jax.lax.fori_loop(
        0, visited, body, jnp.full((b, t, tiles * tile), -jnp.inf))


# Positions a block of ``_choose``'s second level: a row of 128 lanes.
_CHOICE_BLOCK = 128


def _choose(scores, q_pos, topk: int):
    """The ``topk`` positions of largest score a row (ties to the lower
    position), in the order of the positions: ``(positions [b, t, k], real
    [b, t, k])``, ``real`` false past the row's last choice where it sees
    fewer than k positions (``scores`` reads -inf past ``q_pos``).

    The set is ``jax.lax.top_k``'s without its sort (which the chip runs
    as a full sort of the row: 4.8 ms for 16 rows of 34,816, PERF.md
    section 6, PR 44), in three passes of plain vector work:
    - the k-th largest score, bit by bit: a float's bits, flipped so that
      they order as the floats do, and 32 counts of the scores at or above
      a candidate;
    - the chosen: every score above it, and of those equal to it the
      first by position that fill the k;
    - their positions, without a scatter: by blocks of ``_CHOICE_BLOCK``
      positions, choice j lies in the block whose running count passes j,
      and is that block's (j - count before it)-th chosen position.
    """
    b, t, n = scores.shape
    k = min(topk, n)
    blocks = -(-n // _CHOICE_BLOCK)
    pad = blocks * _CHOICE_BLOCK - n
    bits = jax.lax.bitcast_convert_type(
        jnp.pad(scores, ((0, 0), (0, 0), (0, pad)),
                constant_values=-jnp.inf), jnp.uint32)
    # Unsigned keys in the floats' order: a negative float's bits all
    # flip, a positive one's sign bit.
    keys = jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))
    want = jnp.minimum(q_pos + 1, k)[..., None]          # [b, t, 1]

    def bit(i, low):
        cand = low | (jnp.uint32(1) << jnp.asarray(31 - i, jnp.uint32))
        enough = jnp.sum(keys >= cand, axis=-1, keepdims=True) >= want
        return jnp.where(enough, cand, low)

    kth = jax.lax.fori_loop(0, 32, bit, jnp.zeros((b, t, 1), jnp.uint32))
    above = keys > kth
    level = keys == kth
    spare = want - jnp.sum(above, axis=-1, keepdims=True)
    picked = above | (level & (jnp.cumsum(level, axis=-1) <= spare))
    picked = picked.reshape(b, t, blocks, _CHOICE_BLOCK)
    ends = jnp.cumsum(jnp.sum(picked, axis=-1), axis=-1)  # [b, t, blocks]
    slot = jnp.arange(k)
    block = jnp.sum(ends[:, :, None, :] <= slot[:, None], axis=-1)
    block = jnp.minimum(block, blocks - 1)                # [b, t, k]
    before = jnp.take_along_axis(
        jnp.pad(ends, ((0, 0), (0, 0), (1, 0))), block, axis=-1)
    lanes = jnp.take_along_axis(picked, block[..., None], axis=2)
    rank = jnp.cumsum(lanes, axis=-1) - 1                 # [b, t, k, lanes]
    lane = jnp.argmax(lanes & (rank == (slot - before)[..., None]), axis=-1)
    return block * _CHOICE_BLOCK + lane, slot < want


def _rows_attention(q_row, rows, keep, value_lanes, scale):
    """Attention of absorbed queries q_row [b, t, h, row] over latent
    rows of the query's own, rows [b, t, k, row] (``keep`` [b, t, k]), or
    that the call's queries share, rows [b, k, row] (``keep`` [b, t, k]
    too) -> [b, t, h, value_lanes] in the latent space.  Softmax in
    float32 over the kept rows only."""
    dt = q_row.dtype
    own = rows.ndim == 4
    sc = jnp.einsum("bthr,btkr->bthk" if own else "bthr,bkr->bthk", q_row,
                    rows, preferred_element_type=jnp.float32) * scale
    w = jax.nn.softmax(jnp.where(keep[:, :, None, :], sc,
                                 jnp.finfo(jnp.float32).min), axis=-1)
    return jnp.einsum("bthk,btkc->bthc" if own else "bthk,bkc->bthc",
                      w.astype(dt), rows[..., :value_lanes],
                      preferred_element_type=jnp.float32).astype(dt)


def _by_query_tiles(attend, q_row, *per_query):
    """``attend(q_row, *per_query)`` in tiles of ``_VIEW_QUERY_TILE``
    query columns where the call holds several (axis 1 of every
    argument)."""
    t = q_row.shape[1]
    tiles, rest = divmod(t, _VIEW_QUERY_TILE)
    if tiles < 2 or rest:
        return attend(q_row, *per_query)

    def tile(i):
        return attend(*(jax.lax.dynamic_slice_in_dim(
            a, i * _VIEW_QUERY_TILE, _VIEW_QUERY_TILE, axis=1)
            for a in (q_row, *per_query)))

    out = jax.lax.map(tile, jnp.arange(tiles))      # [tiles, b, tile, ...]
    return jnp.moveaxis(out, 0, 1).reshape(
        (q_row.shape[0], t) + out.shape[3:])


def latent_sides(cfg: TransformerConfig) -> Tuple[str, ...]:
    """The pools of a latent stack, in the order its programs carry
    them: the full layers' latent rows, their index keys (with an
    indexer), the sliding layers' rows (with such layers)."""
    return ("cache_latent",) + (("cache_index",) if cfg.indexed else ()) \
        + (("cache_window",) if cfg.window_planes else ())


def window_pages(window: int, block_tokens: int, t: int) -> int:
    """Pages that can meet the positions ``t`` neighbouring queries see
    through a window of ``window``: t + window - 1 positions from
    anywhere in the first page."""
    return (t + window - 2 + block_tokens - 1) // block_tokens + 1


def _table_entries(tables, first, n: int):
    """``tables[i, first[i]:first[i] + n]`` a slot, [b, n].

    As one gather of single entries, NOT ``jax.vmap`` of
    ``dynamic_slice``: where the table and the positions are constants of
    the program (a closure, not arguments), the chip's compiler folds that
    form at compile time to the slice's FIRST entry and zeros after it
    (libtpu 0.0.34; tests/test_tpu_compile.py holds this form to the right
    pages there), and every page but one then reads page 0.  The engine
    hands both as arguments, where either form runs right (PERF.md section
    6, PR 44)."""
    return jnp.take_along_axis(
        tables, first[:, None] + jnp.arange(n), axis=1)


def _window_view_attention(q_row, pool, plane, tables, q_pos, window,
                           value_lanes, scale):
    """Attention of absorbed queries q_row [b, t, h, row] at positions
    ``q_pos`` [b, t] (neighbours, a row) over the last ``window``
    positions each sees, read from the pages of ``pool``'s ``plane`` that
    can meet them (``window_pages``) and no others."""
    b, t = q_row.shape[:2]
    bt, width = pool.shape[2:]
    mb = tables.shape[1]
    n_pages = min(mb, window_pages(window, bt, t))
    first = jnp.maximum(q_pos[:, 0] - window + 1, 0) // bt
    first = jnp.minimum(first, mb - n_pages)
    view = pool[plane, _table_entries(tables, first, n_pages)].reshape(
        b, n_pages * bt, width)
    k_pos = (first * bt)[:, None, None] + jnp.arange(n_pages * bt)
    return _rows_attention(
        q_row, view,
        (k_pos <= q_pos[..., None]) & (k_pos > q_pos[..., None] - window),
        value_lanes, scale)


def _latent_attention_block(cfg: TransformerConfig, layer_params, x, cache,
                            cache_len, positions, write_cols=None,
                            tables=None, paged_kernel=False, plane=None,
                            kind="full_attention", first_pos=0, store=True):
    """Latent attention (MLA, TransformerConfig.attention_kind) in the
    attention block's place: norm, projections, the write of the token's
    latent row, attention, output projection, residual.

    ``cache`` holds the stack's pools in ``latent_sides`` order, each
    [its planes, num_blocks, block_tokens, row] (a latent row: the
    normed, scaled latent, then the shared rotary key, then zeros up to
    whole 128-lane rows), with ``tables`` / ``cache_len`` / ``write_cols``
    / ``plane`` / ``paged_kernel`` as in ``_attention_block``; or None (a
    forward without a cache).  ``kind`` names the layer's sizes and its
    pool (``TransformerConfig.latent_sizes``); ``plane`` counts within
    that pool.  Two forms of one arithmetic:

    - against the pool the key expansion is ABSORBED into the query
      (``qt_j = q_nope_j W_uk_j^T``): all heads score the latent rows as
      they lie, the weights sum the rows (``ot_j``), and the result is
      expanded (``ot_j W_uv_j``), so a position is read once for every
      head and both products, and the heads fold into the rows of one
      matmul.  A decode step (ONE query position a row over per-row
      lengths) with ``paged_kernel`` reads the pages in place through
      ops/paged_attention.py; a decode step on any other backend
      attends over the slot's gathered view
      (``_latent_view_attention``), and a prefill chunk over the key
      tiles of it that the slot holds (``_held_key_tiles``,
      ``_tiled_latent_view_attention``; the whole view in one pass
      where the table is short);
    - the forward without a cache EXPANDS keys and values from the latent
      (``c W_uk``, ``c W_uv``) and attends as any other model does, as
      the plain reference does everywhere.

    With an indexer (``cfg.indexed``, full_attention layers) a token
    also writes its index key to the index pool's plane, the call scores
    the index keys its slots hold, chooses ``index_topk`` positions a
    query (``_choose``) and attends the chosen rows and no others,
    gathered a query by (page, offset).  The scores come by key tiles
    gathered for every row up to the call's longest slot
    (``_index_scores``: a prefill chunk, whose 16,384 (query, head) rows
    a tile are bound by the products; every backend but the chip) or,
    in a decode step with ``paged_kernel`` over a pool the kernel can
    copy (``index_keys_walked``), page by page from each slot's OWN pages
    in place (``paged_index_scores``): one arithmetic, chosen by the
    call's shape and the pool's layout.
    A sliding_attention layer gathers (the kernel:
    copies) only the pages that meet its queries' windows
    (``window_pages``), whatever the slot holds below them.  Both read
    what the whole-view forms would read, masked: the cost is what
    differs.  ``cfg.attn_gate`` scales each head's output by the sigmoid
    of a projection of the layer's normed input.

    A decode step may hold TWO query positions a row (t == 2 over per-row
    lengths: a drafting stack's last token and its draft,
    ``TransformerConfig.mtp_layers``), each under its own frontier; the
    kernel then takes both as rows of one call.  ``first_pos`` (static):
    positions below it are attended by nobody (the draft layer's plane
    holds nothing at index 0).  ``store`` False: the call's rows lie in
    the pool already and the pool is not written (a position recomputed
    over a shared page).  The rotary positions are ``positions``, which
    need not be the rows' indices ``cache_len`` + column.
    """
    attn = layer_params["attn"]
    dt = cfg.dtype
    z = cfg.latent_sizes(kind)
    e, rkv, dn, dr, heads = (cfg.d_model, z.kv_rank, z.nope_dim,
                             z.rope_dim, z.heads)
    up_q, up_kv = ((e / z.q_rank) ** 0.5, (e / rkv) ** 0.5) \
        if cfg.mla_rescale else (1.0, 1.0)
    b, t = x.shape[:2]
    scale = (dn + dr) ** -0.5 * cfg.mla_softmax_mult
    wk_b, wv_b = attn["wk_b"], attn["wv_b"]
    # What ``_rope_pairs`` takes after the positions.
    turns = (z.rope_theta,) if z.yarn is None else (z.rope_theta, z.yarn)
    indexed = cfg.indexed and kind == "full_attention"
    if cache is not None:
        cache, sides = list(cache), latent_sides(cfg)
        side = sides.index("cache_window" if z.window else "cache_latent")

    with jax.named_scope("kft.mla_q"):
        y = _rms_norm(x, layer_params["attn_norm"]["scale"], cfg.norm_eps,
                      dt)
        qa = qeinsum("bse,er->bsr", y, attn["wq_a"], dt)
        qa = _rms_norm(qa, attn["q_norm"]["scale"] * up_q, cfg.norm_eps, dt)
        q = qeinsum("bsr,rhd->bshd", qa, attn["wq_b"], dt)
        q_nope = q[..., :dn]
        q_rope = _rope_pairs(q[..., dn:], positions, *turns)
    with jax.named_scope("kft.mla_latent_write"):
        kva = qeinsum("bse,ec->bsc", y, attn["wkv_a"], dt)
        c = _rms_norm(kva[..., :rkv], attn["kv_norm"]["scale"] * up_kv,
                      cfg.norm_eps, dt)
        k_r = _rope_pairs(kva[:, :, None, rkv:], positions,
                          *turns)[:, :, 0]
        if cache is not None:
            pool = cache[side]
            nb, bt, width = pool.shape[1:]
            mb, pad = tables.shape[1], width - rkv - dr
            blk, off, base = _page_coordinates(
                tables, cache_len, write_cols, b, t, nb, bt)
            if store:
                row = jnp.concatenate(
                    [c, k_r, jnp.zeros((b, t, pad), dt)], axis=-1)
                pool = cache[side] = pool.at[plane, blk, off].set(
                    row.astype(pool.dtype), mode="drop")
    if indexed:
        with jax.named_scope("kft.dsa_index"):
            q_idx = _rope_leading(
                qeinsum("bsr,rhd->bshd", qa, attn["wq_idx"], dt),
                positions, z.rope_theta, dr)
            k_idx = _layer_norm(
                qeinsum("bse,ed->bsd", y, attn["wk_idx"], dt),
                attn["k_idx_norm"]["scale"], attn["k_idx_norm"]["bias"],
                cfg.norm_eps, dt)
            k_idx = _rope_leading(k_idx[:, :, None], positions,
                                  z.rope_theta, dr)[:, :, 0]
            # In float32, as the router's scores: a weight that rounding
            # moves changes which positions a query attends.
            w_idx = jnp.einsum(
                "bse,eh->bsh", y.astype(jnp.float32),
                attn["w_idx"].astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST) \
                * (cfg.index_heads * cfg.index_dim) ** -0.5
            if cache is not None:
                at = sides.index("cache_index")
                keys = cache[at] = cache[at].at[plane, blk, off].set(
                    k_idx.astype(cache[at].dtype), mode="drop")

    gate = None
    if cfg.attn_gate:
        with jax.named_scope("kft.attn_gate"):
            gate = jax.nn.sigmoid(qeinsum(
                "bse,eh->bsh", y, attn["wg"], dt).astype(jnp.float32))

    if cache is None:
        rows_q = jnp.arange(t)
        keep = jnp.broadcast_to(rows_q[None, :] <= rows_q[:, None],
                                (b, t, t))
        if z.window:
            keep = keep & (rows_q[None, :] > rows_q[:, None] - z.window)
        if indexed:
            with jax.named_scope("kft.dsa_index"):
                scores = _index_scores(
                    q_idx, w_idx, lambda i: k_idx, t, 1, 1, positions)
            with jax.named_scope("kft.dsa_select"):
                chosen, real = _choose(scores, positions, cfg.index_topk)
                picked = jnp.zeros((b, t, t), jnp.int32).at[
                    jnp.arange(b)[:, None, None],
                    jnp.arange(t)[None, :, None], chosen].add(real)
                keep = keep & (picked > 0)
        with jax.named_scope("kft.mla_prefill"):
            k = jnp.concatenate(
                [qeinsum("bkc,hdc->bkhd", c, wk_b, dt), jnp.broadcast_to(
                    k_r[:, :, None], (b, t, heads, dr))], axis=-1)
            full = jnp.concatenate([q_nope, q_rope], axis=-1)
            if cfg.mla_softmax_mult != 1.0 and not (z.window or indexed):
                # dot_product_attention scales by the head's width alone.
                full = (full * cfg.mla_softmax_mult).astype(dt)
            if not (z.window or indexed):
                out = dot_product_attention(
                    full, k, qeinsum("bkc,chd->bkhd", c, wv_b, dt),
                    causal=True)
            else:
                sc = jnp.einsum("bqhd,bkhd->bhqk", full, k,
                                preferred_element_type=jnp.float32) * scale
                w = jax.nn.softmax(jnp.where(
                    keep[:, None], sc, jnp.finfo(jnp.float32).min), axis=-1)
                out = jnp.einsum(
                    "bhqk,bkhd->bqhd", w.astype(dt),
                    qeinsum("bkc,chd->bkhd", c, wv_b, dt),
                    preferred_element_type=jnp.float32).astype(dt)
    else:
        decode = base is not None and (
            t == 1 or (t == 2 and not indexed and not z.window))
        # Position of the call's query columns among its slots' own.
        q_pos = jnp.reshape(cache_len, (-1, 1)) + jnp.arange(t)[None, :]
        q_pos = jnp.broadcast_to(q_pos, (b, t))
        with jax.named_scope(
                "kft.mla_decode" if decode else "kft.mla_prefill"):
            qt = qeinsum("bshd,hdc->bshc", q_nope, wk_b, dt)
            q_row = jnp.concatenate(
                [qt, q_rope, jnp.zeros((b, t, heads, pad), dt)],
                axis=-1)                                # [b, t, h, width]
        if decode and paged_kernel:
            from kubeflow_tpu.ops import paged_attention

            # The step's own rows (and index key) are in the pool
            # already: its last query sees cache_len + t positions; a
            # parked write marks a retired row, which reads nothing.
            attend = jnp.where(base < mb * bt, cache_len + t, 0)
        if indexed:
            with jax.named_scope("kft.dsa_index"):
                if decode and paged_kernel and index_keys_walked(keys):
                    padded = tables
                    scores = paged_attention.paged_index_scores(
                        q_idx[:, 0], w_idx[:, 0], keys, plane, tables,
                        attend)[:, None]
                else:
                    pages, tiles = index_key_tiles(
                        mb, bt, b * t * cfg.index_heads)
                    padded = jnp.pad(tables,
                                     ((0, 0), (0, tiles * pages - mb)))
                    scores = _index_scores(
                        q_idx, w_idx,
                        lambda i: keys[plane, jax.lax.dynamic_slice_in_dim(
                            padded, i * pages, pages, axis=1)].reshape(
                                b, pages * bt, -1),
                        pages * bt, tiles,
                        _tiles_visited(jnp.max(cache_len) + t, pages * bt,
                                       tiles), q_pos)
            with jax.named_scope("kft.dsa_select"):
                chosen, real = _choose(scores, q_pos, cfg.index_topk)
            with jax.named_scope("kft.mla_sparse"):
                # Row by row through the compiler's gather, on every
                # backend: the chip's compiler refuses a kernel's copy
                # of ONE row out of a tiled page
                # (ops/paged_attention.py).  A parked row's choices lie
                # past its own position: it attends nothing it keeps.
                at_page = jnp.take_along_axis(
                    padded[:, None, :], chosen // bt, axis=2)
                ot = _by_query_tiles(
                    lambda q, pg, ch, ok: _rows_attention(
                        q, pool[plane, pg, ch % bt], ok, rkv, scale),
                    q_row, at_page, chosen, real)
        elif z.window:
            with jax.named_scope("kft.mla_window"):
                if decode and paged_kernel:
                    ot = paged_attention.paged_latent_decode_attention(
                        q_row[:, 0], pool, plane, tables, attend, rkv,
                        scale, window=z.window)[:, None]
                else:
                    ot = _window_view_attention(
                        q_row, pool, plane, tables, q_pos, z.window, rkv,
                        scale)
        else:
            with jax.named_scope(
                    "kft.mla_decode" if decode else "kft.mla_prefill"):
                if decode and paged_kernel:
                    # Both positions of a drafting step as rows of one
                    # call: a page is copied once for the two.
                    ot = paged_attention.paged_latent_decode_attention(
                        q_row.reshape(b, t * heads, width), pool, plane,
                        tables, attend, rkv, scale, queries=t,
                        first=first_pos).reshape(b, t, heads, rkv)
                else:
                    held = _held_key_tiles(tables, bt, t, cache_len)
                    if held is None:
                        ot = _latent_view_attention(
                            q_row,
                            pool[plane, tables].reshape(b, mb * bt, width),
                            rkv, cache_len, scale, first_pos)
                    else:
                        tile, visited, pages_of = held
                        ot = _tiled_latent_view_attention(
                            q_row,
                            lambda i: pool[plane, pages_of(i)].reshape(
                                b, tile, width),
                            rkv, cache_len, scale, tile, visited, mb * bt,
                            first_pos)
        with jax.named_scope(
                "kft.mla_decode" if decode else "kft.mla_prefill"):
            out = qeinsum("bshc,chd->bshd", ot, wv_b, dt)
    if gate is not None:
        with jax.named_scope("kft.attn_gate"):
            out = (out * gate[..., None]).astype(dt)
    with jax.named_scope("kft.attn_out"):
        x = x + qeinsum("bshd,hde->bse", out, attn["wo"], dt)
    return x, (None if cache is None else tuple(cache))


def _dense_mlp(cfg: TransformerConfig, mlp, y, adapters=None):
    """A SwiGLU over the normed stream y [b, s, e] (no norm, no
    residual)."""
    dt = cfg.dtype
    gate = qeinsum("bse,ef->bsf", y, mlp["wi"][0], dt)
    up = qeinsum("bse,ef->bsf", y, mlp["wi"][1], dt)
    if adapters is not None:
        ad = adapters["mlp"]
        gate = gate + _lora(y, ad["wi_a"][:, 0], ad["wi_b"][:, 0],
                            "bse,ber->bsr", "bsr,brf->bsf")
        up = up + _lora(y, ad["wi_a"][:, 1], ad["wi_b"][:, 1],
                        "bse,ber->bsr", "bsr,brf->bsf")
    h = jax.nn.silu(gate) * up
    y = qeinsum("bsf,fe->bse", h, mlp["wo"], dt)
    if adapters is not None:
        ad = adapters["mlp"]
        y = y + _lora(h, ad["wo_a"], ad["wo_b"],
                      "bsf,bfr->bsr", "bsr,bre->bse")
    return y


def _dense_ff(cfg: TransformerConfig, layer_params, x, adapters=None):
    """The block's SwiGLU feed-forward with its norm and residual."""
    dt = cfg.dtype

    def norm(x, scale):
        return _rms_norm(x, scale, cfg.norm_eps, dt)

    with jax.named_scope("kft.mlp"):
        y = norm(x, layer_params["mlp_norm"]["scale"])
        y = _dense_mlp(cfg, layer_params["mlp"], y, adapters)
        if cfg.sandwich_norm:
            with jax.named_scope("kft.loop_norm"):
                y = norm(y, layer_params["mlp_out_norm"]["scale"])
        x = x + y
    return x


def _layer_step(cfg: TransformerConfig, layer_params, x, cache_kv,
                cache_len, positions, pad_amount=None, write_cols=None,
                tables=None, adapters=None, paged_kernel=False, plane=None):
    """One decoder block against the KV cache: ``_attention_block``,
    then ``_dense_ff`` (models/transformer.py Block with explicit cache
    state)."""
    x, cache_kv = _attention_block(
        cfg, layer_params, x, cache_kv, cache_len, positions,
        pad_amount=pad_amount, write_cols=write_cols, tables=tables,
        adapters=adapters, paged_kernel=paged_kernel, plane=plane)
    return _dense_ff(cfg, layer_params, x, adapters), cache_kv


def _conv_block(cfg: TransformerConfig, layer_params, x, state=None,
                rows=None, fresh=None, n_new=None):
    """The gated short convolution of a ``conv`` layer (TransformerConfig.
    layer_types), with its norm and residual, against its per-sequence
    state.

    x: [b, t, e].  state: this layer's [S, K - 1, e], the last K - 1
    columns of u = B * h of every slot's sequence, or None (a forward
    without a cache: every row starts from zeros and nothing is kept).
    rows [b]: the slot of each row of x; None = row i is slot i (S = b).
    fresh [b] bool: the row's sequence starts in this call, from zeros,
    whatever its slot held.  n_new [b]: how many of the row's t columns
    are real; the state kept is that of the last real one, so 0 leaves a
    row's state as it was (a slot that is parked, or in mid-prefill
    while the others decode) and a right-padded final chunk keeps the
    state of the prompt's last token.  Returns (x, state).
    """
    dt, taps = cfg.dtype, cfg.conv_kernel
    conv = layer_params["conv"]
    t = x.shape[1]
    with jax.named_scope("kft.short_conv"):
        y = _rms_norm(x, layer_params["conv_norm"]["scale"], cfg.norm_eps,
                      dt)
        bch = qeinsum("bse,ecf->bscf", y, conv["w_in"], dt)
        u = bch[:, :, 0] * bch[:, :, 2]
    with jax.named_scope("kft.conv_state"):
        if state is None:
            prev = jnp.zeros((x.shape[0], taps - 1, x.shape[2]), dt)
        else:
            prev = state if rows is None else state[rows]
            if fresh is not None:
                prev = jnp.where(fresh[:, None, None], 0, prev)
        ext = jnp.concatenate([prev.astype(dt), u], axis=1)
        if state is not None:
            keep = n_new[:, None] + jnp.arange(taps - 1)[None, :]
            kept = jnp.take_along_axis(
                ext, keep[:, :, None], axis=1).astype(state.dtype)
            state = kept if rows is None else state.at[rows].set(kept)
    with jax.named_scope("kft.short_conv"):
        w = conv["w_conv"].astype(jnp.float32)
        c = sum(w[i] * ext[:, i:i + t].astype(jnp.float32)
                for i in range(taps)).astype(dt)
        x = x + qeinsum("bse,ef->bsf", bch[:, :, 1] * c, conv["w_out"], dt)
    return x, state


def _experts(cfg: TransformerConfig, moe, y, live=None,
             grouped_kernel=False):
    """The expert layer over normed rows y [m, e]: ``sum over the chosen
    i of w_i E_i(y)`` -> ([m, e] float32, counts); nothing is dropped.

    The router (TransformerConfig.moe_score / moe_normalize / moe_scale)
    scores every output in float32, ``moe/bias`` selects and does not
    weigh; with ``moe_groups`` the choice falls inside the
    ``moe_groups_kept`` groups of largest score, whichever chip holds
    their experts.  Every (row, chosen expert) pair whose expert this program
    HOLDS (``moe_experts_offset``, ``moe_held``) is sorted by expert and
    the experts' SwiGLUs run as two grouped products over the stacked
    expert matrices where they lie: a row meets only the experts it chose,
    and an expert no row chose is not read.  ``grouped_kernel`` (static,
    chosen once by the engine from where the expert matrices live):
    ops/grouped_matmul.py, each touched expert's matrix read once at
    HBM's rate whatever the rows in no group; else
    ``jax.lax.ragged_dot``, the same products.  ``moe/wi`` is [held, e,
    2 f], gate then up along the last axis.  A pair whose expert lies on
    another chip is in no group and adds nothing here; a pair that chose
    a zero-compute expert (an output at or past ``moe_experts``) is in no
    group either and adds its weight times the row itself.
    live [m] bool (None: all): rows that are no token (a parked slot, a
    final chunk's padding) choose nothing and come back as zeros.
    counts: int32 scalars over the live rows: ``touched`` (distinct held
    experts with at least one row), and the pairs by where they fell,
    ``held``, ``zero``, ``absent``.
    """
    dt, n, k, f = cfg.dtype, cfg.moe_experts, cfg.moe_top_k, cfg.moe_d_ff
    held, first, zero = cfg.moe_held, cfg.moe_experts_offset, \
        cfg.moe_zero_experts
    m, e = y.shape
    with jax.named_scope("kft.moe_route"):
        # In float32 whatever the model computes in: a score that
        # rounding moves past another changes a whole expert.
        scores = jnp.einsum(
            "me,en->mn", y.astype(jnp.float32),
            moe["router"].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST)
        scores = jax.nn.sigmoid(scores) if cfg.moe_score == "sigmoid" \
            else jax.nn.softmax(scores, axis=-1)
        # The bias selects and does not weigh.
        select = scores + moe["bias"]
        if cfg.moe_groups:
            with jax.named_scope("kft.moe_groups"):
                # A group scores the sum of its 2 largest; the choice
                # falls inside the groups kept (ties to the lower group,
                # as top_k's to the lower index).
                groups = cfg.moe_groups
                of_group = jax.lax.top_k(
                    select.reshape(m, groups, n // groups), 2)[0].sum(-1)
                _, kept = jax.lax.top_k(of_group, cfg.moe_groups_kept)
                kept = jnp.zeros((m, groups), bool).at[
                    jnp.arange(m)[:, None], kept].set(True)
                select = jnp.where(
                    jnp.repeat(kept, n // groups, axis=1), select, -jnp.inf)
        _, chosen = jax.lax.top_k(select, k)
        gates = jnp.take_along_axis(scores, chosen, axis=1)
        if cfg.moe_normalize:
            gates = gates / (gates.sum(axis=1, keepdims=True)
                             + cfg.moe_norm_eps)
        if cfg.moe_scale != 1.0:
            gates = gates * cfg.moe_scale
        pairs = chosen.reshape(-1)
        if held != n or zero:
            # Another chip's expert, or one without weights: past every
            # expert held here.
            pairs = jnp.where((pairs >= first) & (pairs < first + held),
                              pairs - first, held)
        if live is not None:
            # Past every expert: sorted last, in no group.
            pairs = jnp.where(jnp.repeat(live, k), pairs, held)
        order = jnp.argsort(pairs)
        sizes = jnp.zeros((held,), jnp.int32).at[pairs].add(1, mode="drop")
        rows = y[order // k]
    with jax.named_scope("kft.moe_experts"):
        product = jax.lax.ragged_dot
        if grouped_kernel:
            from kubeflow_tpu.ops import grouped_matmul

            product = grouped_matmul.grouped_matmul
        h = product(rows, moe["wi"].astype(dt), sizes)
        h = jax.nn.silu(h[:, :f]) * h[:, f:]
        out = product(h, moe["wo"].astype(dt), sizes)
    with jax.named_scope("kft.moe_route"):
        out = out[jnp.argsort(order)].reshape(m, k, e)
        in_group = (pairs < held).reshape(m, k)
        # A row in no group is not written by the grouped product
        # (ragged_dot leaves zeros, the kernel whatever was there): a
        # select, never a product.
        if held != n or zero:
            out = jnp.where(in_group[:, :, None], out, 0)
        elif live is not None:
            out = jnp.where(live[:, None, None], out, 0)
        out = jnp.sum(out.astype(jnp.float32) * gates[:, :, None], axis=1)
    alive = jnp.ones((m,), bool) if live is None else live
    is_zero = (chosen >= n) & alive[:, None]
    if zero:
        with jax.named_scope("kft.moe_zero"):
            # A weighted copy of the row, no product.
            out = out + jnp.sum(jnp.where(is_zero, gates, 0), axis=1,
                                keepdims=True) * y.astype(jnp.float32)
    n_held = jnp.sum(in_group).astype(jnp.int32)
    n_zero = jnp.sum(is_zero).astype(jnp.int32)
    return out, {
        "touched": jnp.sum(sizes > 0).astype(jnp.int32),
        "held": n_held, "zero": n_zero,
        "absent": jnp.sum(alive).astype(jnp.int32) * k - n_held - n_zero}


def _sparse_ff(cfg: TransformerConfig, layer_params, x, live=None,
               grouped_kernel=False):
    """Sparse experts in the feed-forward's place (TransformerConfig.
    layer_types), with norm and residual: ``_experts`` over the normed
    stream.  live [b, t] bool (None: all): rows that are no token come
    back unchanged.  ``grouped_kernel``: as ``_experts``'s.  Returns (x,
    ``_experts``'s counts)."""
    b, t, e = x.shape
    moe = layer_params["moe"]
    with jax.named_scope("kft.mlp"):
        normed = _rms_norm(x, layer_params["mlp_norm"]["scale"],
                           cfg.norm_eps, cfg.dtype)
        y, counts = _experts(cfg, moe, normed.reshape(b * t, e),
                             None if live is None else live.reshape(-1),
                             grouped_kernel)
        y = y.astype(cfg.dtype).reshape(b, t, e)
        if cfg.moe_shared_d_ff:
            # The shared expert: every token, unweighted, once.
            with jax.named_scope("kft.moe_shared"):
                shared = _dense_mlp(cfg, moe["shared"], normed)
                y = y + (shared if live is None
                         else jnp.where(live[..., None], shared, 0))
        x = x + y
    return x, counts


def _shortcut_double(cfg: TransformerConfig, layer_params, x, cache,
                     cache_len, positions, live=None, write_cols=None,
                     tables=None, paged_kernel=False, plane=None,
                     grouped_kernel=False):
    """A ``shortcut_double`` layer (TransformerConfig.layer_types): two
    attention sublayers on planes ``plane`` and ``plane + 1``, two dense
    SwiGLUs, and the expert layer that reads the first half's normed
    stream and is added at the layer's end.  Returns (x, cache,
    ``_experts``'s counts)."""
    dt = cfg.dtype
    b, t, e = x.shape
    first, second = layer_params["half_0"], layer_params["half_1"]
    x, cache = _attention_block(
        cfg, first, x, cache, cache_len, positions, write_cols=write_cols,
        tables=tables, paged_kernel=paged_kernel, plane=plane)
    with jax.named_scope("kft.mlp"):
        m = _rms_norm(x, first["mlp_norm"]["scale"], cfg.norm_eps, dt)
        s, counts = _experts(
            cfg, layer_params["moe"], m.reshape(b * t, e),
            None if live is None else live.reshape(-1), grouped_kernel)
        x = x + _dense_mlp(cfg, first["mlp"], m)
    x, cache = _attention_block(
        cfg, second, x, cache, cache_len, positions, write_cols=write_cols,
        tables=tables, paged_kernel=paged_kernel, plane=plane + 1)
    x = _dense_ff(cfg, second, x)
    with jax.named_scope("kft.scmoe_shortcut"):
        x = x + s.astype(dt).reshape(b, t, e)
    return x, cache, counts


def _embed_tokens(cfg: TransformerConfig, params, tokens, cache_len,
                  pad_amount=None):
    """tokens [b, t] -> (x [b, t, e], rope positions [b, t]); see
    ``_forward_with_cache`` for ``cache_len`` and ``pad_amount``."""
    with jax.named_scope("kft.embed"):
        x = embed_lookup(params["embed"], tokens, cfg.dtype)  # int8-aware
    per_row = not isinstance(cache_len, int) and cache_len.ndim == 1
    if per_row:
        positions = (cache_len[:, None]
                     + jnp.arange(tokens.shape[1])[None, :])
    else:
        positions = cache_len + jnp.arange(tokens.shape[1])[None, :]
        positions = jnp.broadcast_to(positions, tokens.shape)
    if pad_amount is not None:
        # Left-padded rows: real token i of a row sits at buffer column
        # pad + i but must see rope position i.  Pad columns clamp to 0
        # — their keys are masked from every attention anyway.
        positions = jnp.maximum(positions - pad_amount[:, None], 0)
    return x, positions


def _final_norm(cfg: TransformerConfig, params, x):
    return _rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps,
                     cfg.dtype)


def _head(cfg: TransformerConfig, params, normed):
    """The head over a normed stream -> float32 logits."""
    dt = cfg.dtype
    if cfg.tied_embeddings:
        logits = qeinsum("bse,ve->bsv", normed, params["embed"], dt)
    else:
        logits = qeinsum("bse,ev->bsv", normed, params["w_out"], dt)
    return logits.astype(jnp.float32)


def _logits(cfg: TransformerConfig, params, x):
    with jax.named_scope("kft.logits"):
        return _head(cfg, params, _final_norm(cfg, params, x))


def forward_layer_types(cfg: TransformerConfig, params, tokens, cache=None,
                        cache_len=0, write_cols=None, tables=None,
                        paged_kernel=False, conv=None, rows=None,
                        fresh=None, n_new=None, hidden=False, store=True,
                        grouped_kernel=False):
    """The forward of a stack that states its ``layer_types``
    (TransformerConfig): tokens [b, t] -> (logits [b, t, v], cache,
    conv, the expert layers' counts).  ``hidden`` (static): the stream
    after the final norm [b, t, e] in the logits' place (what a draft
    module reads, and the head's input: ``_head``).  ``store``: as
    ``_latent_attention_block``'s.

    The layers are walked one by one over ``params["layers"][str(i)]``:
    no two need have the same leaves, every matrix is an array of its
    own that its product reads where it lies, and the attention layers
    alone own planes of the paged pool (plane j is the j-th of them; the
    sliding_attention layers count their own, in a pool of their own).
    ``cache`` is the stacked pool, (k, v) or ``latent_sides``', that the serving
    programs carry and donate, ``tables`` their block tables, ``cache_len`` /
    ``write_cols`` / ``paged_kernel`` as in ``_forward_with_cache``,
    ``grouped_kernel`` as in ``_experts``;
    ``conv`` is the [conv layers, slots, K - 1, e] state of the
    convolution layers with ``rows`` / ``fresh`` / ``n_new`` as in
    ``_conv_block``.  Rows with ``n_new`` 0 and columns at or past
    ``n_new`` are no tokens: they choose no expert.  Without ``cache``
    and ``conv`` this is the plain forward of the whole sequence (the
    flax module's).  The last value sums ``_experts``'s counts over the
    sparse layers (None without any).
    """
    from flax import linen as nn

    params = nn.unbox(params)
    x, positions = _embed_tokens(cfg, params, tokens, cache_len)
    live = None if n_new is None else (
        jnp.arange(tokens.shape[1])[None, :] < n_new[:, None])
    plane = plane_c = plane_w = 0
    counts = None

    def count(n):
        return n if counts is None else jax.tree_util.tree_map(
            jnp.add, counts, n)

    for i, kind in enumerate(cfg.layer_types):
        layer_params = params["layers"][str(i)]
        if kind == "shortcut_double":
            x, cache, n = _shortcut_double(
                cfg, layer_params, x, cache, cache_len, positions, live,
                write_cols=write_cols, tables=tables,
                paged_kernel=paged_kernel, plane=plane,
                grouped_kernel=grouped_kernel)
            counts = count(n)
            plane += 2
            continue
        if kind == "conv":
            x, state = _conv_block(
                cfg, layer_params, x,
                None if conv is None else conv[plane_c], rows, fresh, n_new)
            if conv is not None:
                with jax.named_scope("kft.conv_state"):
                    conv = conv.at[plane_c].set(state)
            plane_c += 1
        elif kind == "sliding_attention":
            x, cache = _latent_attention_block(
                cfg, layer_params, x, cache, cache_len, positions,
                write_cols=write_cols, tables=tables,
                paged_kernel=paged_kernel, plane=plane_w, kind=kind)
            plane_w += 1
        else:
            x, cache = _attention_block(
                cfg, layer_params, x, cache, cache_len, positions,
                write_cols=write_cols, tables=tables,
                paged_kernel=paged_kernel, plane=plane, store=store)
            plane += 1
        if cfg.layer_is_sparse(i):
            x, n = _sparse_ff(cfg, layer_params, x, live, grouped_kernel)
            counts = count(n)
        else:
            x = _dense_ff(cfg, layer_params, x)
    if hidden:
        with jax.named_scope("kft.logits"):
            return _final_norm(cfg, params, x), cache, conv, counts
    return _logits(cfg, params, x), cache, conv, counts


def mtp_logits(cfg: TransformerConfig, params, hidden, tokens, cache,
               cache_len, positions, live=None, write_cols=None,
               tables=None, paged_kernel=False, grouped_kernel=False):
    """The multi-token-prediction module (TransformerConfig.mtp_layers)
    over rows ``(h_i, t_{i+1})``: ``hidden`` [b, t, e], the main stack's
    stream after its final norm at positions i, and ``tokens`` [b, t],
    the tokens AFTER them -> (float32 logits [b, t, v] that predict
    t_{i+2}, cache, the expert layer's counts).

        z = [N_e(Emb(t_{i+1})); N_h(h_i)] W_eh
        z' = Block(z)   (latent attention over the rows before, experts)
        logits = Head(N_s(z'))      (embedding and head the main model's)

    The layer's latent rows lie in the LAST plane of ``cache``'s latent
    pool at index i + 1: ``cache_len`` (a scalar, or [b]) is the index of
    column 0, ``positions`` [b, t] its rotary positions (i, not the
    index: the shift is the layout's, not the model's), and index 0 is
    attended by nobody.  ``live`` [b, t] bool: rows that are no token
    choose no expert.  ``cache`` None: rows over the call's own columns
    alone, no pool (the plain forward of a whole sequence)."""
    from flax import linen as nn

    params = nn.unbox(params)
    mtp, dt = params["mtp"], cfg.dtype
    with jax.named_scope("kft.embed"):
        emb = embed_lookup(params["embed"], tokens, dt)
    with jax.named_scope("kft.mtp_proj"):
        z = jnp.concatenate([
            _rms_norm(emb, mtp["enorm"]["scale"], cfg.norm_eps, dt),
            _rms_norm(hidden.astype(dt), mtp["hnorm"]["scale"],
                      cfg.norm_eps, dt)], axis=-1)
        z = qeinsum("bsf,fe->bse", z, mtp["eh_proj"], dt)
    z, cache = _latent_attention_block(
        cfg, mtp["layer"], z, cache, cache_len, positions,
        write_cols=write_cols, tables=tables, paged_kernel=paged_kernel,
        plane=cfg.kv_planes - 1, first_pos=1)
    z, counts = _sparse_ff(cfg, mtp["layer"], z, live, grouped_kernel)
    with jax.named_scope("kft.logits"):
        return _head(cfg, params, _rms_norm(
            z, mtp["norm"]["scale"], cfg.norm_eps, dt)), cache, counts


def _forward_with_cache(cfg: TransformerConfig, params, tokens, cache,
                        cache_len, pad_amount=None, write_cols=None,
                        tables=None, adapter_ids=None, paged_kernel=False):
    """tokens [b, t] -> (logits [b, t, v], new cache).

    cache_len scalar: the whole batch sits at one length (generate()).
    cache_len [b] array: per-row lengths (slot-based decode_rounds)
    — each row ropes its t tokens at its own positions [len, len + t),
    writes its own cache columns (write_cols, defaulting to
    cache_len), and attends under its own causal frontier (t = 1).
    tables: per-row block tables for the paged block-pool cache (the
    serving engine's unified KV store — see _layer_step); None keeps
    the contiguous per-row layout generate() uses.  With tables,
    ``cache`` is the stacked pool [kv_planes, num_blocks, block_tokens,
    hkv, d] a side and the layer scan CARRIES it: each plane's layer
    scatters its t new columns at (plane, block, offset) and reads its
    pages by plane, so a donated pool is updated in place and returned.
    adapter_ids ([b] int32, optional): per-row index into the stacked
    ``params["adapters"]`` low-rank delta arrays (multi-model adapter
    serving, §5.11) — ignored when the params tree carries no adapter
    stack, so the base model's programs are untouched.
    paged_kernel: see _layer_step (static).
    """
    from flax import linen as nn

    if cfg.layer_types:
        raise ValueError(
            "a stack with layer_types runs in the serving engine's "
            "programs (forward_layer_types), not here")
    params = nn.unbox(params)  # accept raw model.init output
    dt = cfg.dtype
    x, positions = _embed_tokens(cfg, params, tokens, cache_len, pad_amount)

    layer_stack = params["layers"]
    adapter_stack = None
    if adapter_ids is not None and "adapters" in params:
        # Per-row adapter gather (§5.11): each row pulls ITS adapter's
        # low-rank factors out of the stacked [n_adapters, layers, ...]
        # arrays (row 0 is the all-zero base delta), then the layer
        # axis moves out front so the scan body indexes the factors by
        # layer beside the base layer stack — one gather per forward,
        # ONE SPMD program for every mix of co-batched variants.
        with jax.named_scope("kft.embed"):  # the rows' other lookup
            adapter_stack = jax.tree_util.tree_map(
                lambda arr: jnp.moveaxis(
                    jnp.asarray(arr, dt)[adapter_ids], 1, 0),
                dict(params["adapters"]))

    def final_norm(x):
        return _final_norm(cfg, params, x)

    # ONE scan over the cfg.kv_planes cache planes, step-major: plane p
    # is layer p % n_layers of loop step p // n_layers, and the body
    # indexes the stacked weights (and adapters) by that layer.  A scan
    # per loop step would slice a quarter of the pool out and write it
    # back on top.
    #
    # A leaf with ONE matrix a layer (attn/wq, attn/wo, mlp/wo) is read
    # by its matmul where it lies in the stack.  A leaf with a PAIR a
    # layer (mlp/wi [L, 2, e, f]: gate, up; attn/wkv [L, 2, e, hkv, d])
    # was not while the body took w[layer] and _layer_step [0] / [1] of
    # it: the compiler copied the pair out once a layer, before the
    # matmuls (Mistral-7B's 235 MB out to HBM and back, smaller pairs
    # into the chip's fast memory: PERF.md section 6, PR 31).  So the
    # body hands over each matrix of a pair as its own read, at
    # 2 * layer + c of the leaf's first two axes taken as one: a bitcast
    # and a slice with one consumer, as for the single-matrix leaves
    # (_layer_step takes [0] / [1] of the tuple as it did of the array).
    # Storage and the dots are unchanged.
    def step(x, cache_kv, plane):
        layer = plane % cfg.n_layers

        def at(w, i):
            return jax.lax.dynamic_index_in_dim(w, i, keepdims=False)

        def pair(w):  # [L, 2, ...] array or QTensor -> (w[l, 0], w[l, 1])
            return tuple(
                jax.tree_util.tree_map(
                    lambda a: at(a.reshape((-1,) + a.shape[2:]),
                                 2 * layer + c), w)
                for c in range(2))

        layer_params, ad = jax.tree_util.tree_map(
            lambda w: at(w, layer), (layer_stack, adapter_stack))
        layer_params = dict(
            layer_params,
            attn=dict(layer_params["attn"],
                      wkv=pair(layer_stack["attn"]["wkv"])),
            mlp=dict(layer_params["mlp"],
                     wi=pair(layer_stack["mlp"]["wi"])))
        x, cache_kv = _layer_step(
            cfg, layer_params, x, cache_kv, cache_len, positions,
            pad_amount=pad_amount, write_cols=write_cols,
            tables=tables, adapters=ad, paged_kernel=paged_kernel,
            plane=plane,
        )
        if cfg.loop_steps > 1:
            # Step t + 1 reads the NORMED output of step t's last
            # layer; the last step's norm is the one before the logits.
            with jax.named_scope("kft.loop_norm"):
                hand_over = (layer == cfg.n_layers - 1) \
                    & (plane < cfg.kv_planes - 1)
                x = jnp.where(hand_over, final_norm(x), x)
        return x, cache_kv

    planes = jnp.arange(cfg.kv_planes)
    if tables is not None:
        # The paged pool is the scan's CARRY, whole: a layer scatters its
        # t columns into the stacked, donated array at (plane, block,
        # offset) and reads its pages by plane, so the pool that comes in
        # is the pool that goes out.  As xs / ys each plane was sliced
        # out, written and restacked, and the step programs' while_loop
        # and donation could not alias through that: two copies of the
        # whole pool a call and a second pool of temporaries (v5e traces,
        # PRs 25-28: 30 of a 1.9B model's 37 ms a token).
        def body(carry, plane):
            x, cache_kv = step(carry[0], carry[1:], plane)
            return (x, *cache_kv), None

        (x, *cache), _ = jax.lax.scan(body, (x, *cache), planes)
    else:
        # generate()'s contiguous cache rides as xs / ys (sliced per
        # plane, re-stacked from the per-plane outputs).  Its layers
        # write and attend WHOLE planes ([b, max_len, h, d]), and as
        # carry with an indexed update the compiler still copies the
        # whole cache around the loops (described-v5e compile of
        # generate(), 32 rows x 1280 positions: temporaries of 6.05 GB
        # as carry against 6.29 GB so, six times one side of the cache;
        # the first such form measured 235 ms/token for a 188M model on
        # v5e): a second form would buy nothing here.
        def body(x, inputs):
            return step(x, inputs[1:], inputs[0])

        x, cache = jax.lax.scan(body, x, (planes, *cache))

    return _logits(cfg, params, x), tuple(cache)


def init_cache(cfg: TransformerConfig, batch: int, max_len: int,
               kv_cache_dtype: str = "model"):
    # One plane per (loop step, layer): cfg.kv_planes on the leading axis.
    shape = (cfg.kv_planes, batch, max_len, cfg.n_kv_heads, cfg.head_dim)
    if kv_cache_dtype == "int8":
        def buf():
            return QTensor(
                jnp.zeros(shape, jnp.int8),
                jnp.zeros(shape[:-1], jnp.float32),
                (-1,),
            )

        return (buf(), buf())
    if kv_cache_dtype != "model":
        raise ValueError(f"unknown kv_cache_dtype {kv_cache_dtype!r}")
    return (jnp.zeros(shape, cfg.dtype), jnp.zeros(shape, cfg.dtype))


def _filter_logits(decode: DecodeConfig, logits: jax.Array) -> jax.Array:
    """Temperature/top_k/top_p-filtered logits ([..., vocab]), shared by
    generate()'s batched sampler and the slot engine's per-slot one.
    Static-shape TPU code: a top_k threshold compare and a sorted-cumsum
    mask — no dynamic vocabulary subsets."""
    logits = logits / decode.temperature
    if decode.top_k > 0:
        # Clamp to the vocabulary: an oversized k means "no filter",
        # not a trace-time lax.top_k error on the first request.
        k = min(decode.top_k, logits.shape[-1])
        kth = jax.lax.top_k(logits, k)[0][..., -1:]
        logits = jnp.where(logits >= kth, logits, -jnp.inf)
    if decode.top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        cum = jnp.cumsum(
            jax.nn.softmax(sorted_logits, axis=-1), axis=-1)
        # Keep every token whose PRECEDING mass is < p (so the
        # boundary token crossing p stays in, matching the
        # standard nucleus definition), then threshold by the
        # smallest kept logit.
        keep = cum - jax.nn.softmax(sorted_logits, axis=-1) \
            < decode.top_p
        cutoff = jnp.min(
            jnp.where(keep, sorted_logits, jnp.inf),
            axis=-1, keepdims=True)
        logits = jnp.where(logits >= cutoff, logits, -jnp.inf)
    return logits


@partial(jax.jit, static_argnums=(0, 3))
def generate(
    cfg: TransformerConfig,
    params,
    prompt: jax.Array,
    decode: DecodeConfig = DecodeConfig(),
    rng: Optional[jax.Array] = None,
    prompt_len: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """prompt [b, t] -> (tokens [b, t+max_new], logits_last [b, vocab]).

    One jitted program: prefill the prompt, then scan max_new_tokens
    single-token steps against the cache.  With ``eos_token >= 0`` the
    step loop exits early once every row is done; tokens are identical
    to the fixed-length run (pads are 0), and logits_last are from the
    exit step rather than after max_new_tokens of pad-forwarding.

    prompt_len ([b] int32, optional): per-row real prompt lengths for
    LEFT-padded prompts — rows shorter than t carry (t - len) pad
    tokens on the left.  Pad keys are masked out of every attention
    and rope positions count from the first real token, so a padded
    row decodes exactly as it would alone at its natural length.
    This is what lets mixed-length requests share one bucketed batch
    (serving/model_server.py BucketedLMBatcher).
    """
    b, t = prompt.shape
    max_len = t + decode.max_new_tokens
    cache = init_cache(cfg, b, max_len, decode.kv_cache_dtype)
    if rng is None:
        rng = jax.random.key(0)
    pad_amount = None if prompt_len is None else t - prompt_len

    logits, cache = _forward_with_cache(cfg, params, prompt, cache, 0,
                                        pad_amount=pad_amount)
    last = logits[:, -1]

    def sample(logits, key):
        if decode.temperature <= 0.0:
            return jnp.argmax(logits, axis=-1)
        return jax.random.categorical(
            key, _filter_logits(decode, logits), axis=-1)

    def step(carry, _):
        cache, last_logits, cache_len, key, done = carry
        key, sub = jax.random.split(key)
        nxt = sample(last_logits, sub)
        nxt = jnp.where(done, jnp.zeros_like(nxt), nxt)
        logits, cache = _forward_with_cache(
            cfg, params, nxt[:, None], cache, cache_len,
            pad_amount=pad_amount)
        done = done | (nxt == decode.eos_token)
        return (cache, logits[:, -1], cache_len + 1, key, done), nxt

    done0 = jnp.zeros((b,), bool)
    if decode.eos_token >= 0:
        # EOS configured: early-exit with lax.while_loop the moment
        # every row is done — completions shorter than max_new_tokens
        # stop paying per-token forwards.  Emitted TOKENS are identical
        # to the fixed-length scan (done rows emit 0s, and the output
        # buffer starts zeroed), so the goldens hold either way; the
        # returned final logits are those of the step the loop exited
        # at (the scan path kept forwarding pad zeros and returned
        # logits after step max_new_tokens — values no caller should
        # score from anyway once every row is done).
        out0 = jnp.zeros((decode.max_new_tokens, b), jnp.int32)

        def cond(state):
            i, carry, _ = state
            done = carry[4]
            return (i < decode.max_new_tokens) & ~jnp.all(done)

        def body(state):
            i, carry, out = state
            carry, nxt = step(carry, None)
            return i + 1, carry, jax.lax.dynamic_update_index_in_dim(
                out, nxt.astype(jnp.int32), i, axis=0)

        _, (_, final_logits, _, _, _), new_tokens = jax.lax.while_loop(
            cond, body, (0, (cache, last, t, rng, done0), out0))
    else:
        (_, final_logits, _, _, _), new_tokens = jax.lax.scan(
            step, (cache, last, t, rng, done0), None,
            length=decode.max_new_tokens)
    tokens = jnp.concatenate([prompt, new_tokens.T], axis=1)
    return tokens, final_logits


# ---------------------------------------------------------------------------
# Continuous-batching slot engine: jitted programs over a PERSISTENT
# PAGED KV block pool (serving/engine.py drives them).
#
# generate() is one program per (batch, bucket) that owns its rows from
# prefill to the last token — a row admitted mid-generation waits for the
# whole program, and every row pays the batch bucket's padded KV span.
# These entry points split that lifecycle so a serving loop can interleave
# admission with decode.  The unified KV store is a device-side BLOCK
# POOL — [planes, num_blocks, block_tokens, hkv, d] (one plane per layer;
# per (loop step, layer) in a looped stack: cfg.kv_planes), fp or int8 QTensor
# alike — and every program takes the current per-slot block tables as
# a plain argument: which pool block backs which logical block of which
# slot is HOST bookkeeping (serving/prefix_cache.py BlockManager), so
# capacity is bounded by TOKENS RESIDENT rather than slots x max_len,
# and sharing a cached prefix between slots is a refcounted table edit
# (zero device copies; no copy program exists).
#
#   prefill_chunk_into_slot  EXTEND a slot's KV by a static chunk width
#                            starting at a traced offset — the serving
#                            loop splits long prompts into chunks and
#                            schedules them BETWEEN decode steps, so an
#                            arriving prompt can never stall in-flight
#                            decode for longer than one chunk.  Also
#                            FREEZES the slot (done=True) until the
#                            final chunk arms it — the engine dispatches
#                            the first chunk at claim time, which is
#                            what makes reusing a deadline-expired
#                            slot safe
#   decode_rounds            ALL live slots advance up to k tokens in
#                            one dispatch, each at its OWN length
#                            (per-row rope position, per-row causal
#                            frontier, per-row block-scatter through
#                            its table), stopping early once every
#                            slot is done
#
# Static shapes throughout: slot count, chunk width, pool geometry
# and the per-slot table span are fixed at engine construction, so the
# whole serving lifetime compiles TWO step programs (chunked prefill,
# decode rounds).  Retirement is a device-side `done` flag (a
# slot that hits its stop length or EOS stops advancing and drops its
# block writes), so freeing + reusing a slot needs no extra program —
# the next admission's first chunk freezes and overwrites it.
# ---------------------------------------------------------------------------


def init_paged_state(cfg: TransformerConfig, slots: int,
                     num_blocks: int, block_tokens: int,
                     kv_cache_dtype: str = "model"):
    """Fresh paged engine state: every slot retired, block pool zeroed.

    The state dict is the carry the jitted entry points thread (and
    donate): the [cfg.kv_planes, num_blocks, block_tokens, hkv, d] KV block
    pool plus per-slot scalars — lengths (valid cache positions),
    stop_len (length at which the slot stops sampling), last_token
    (sampled but not yet in cache), done, a per-slot PRNG key
    (uint32[2]) so temperature sampling is per-REQUEST deterministic
    regardless of co-batched slots, and adapter_ids — each slot's
    index into the stacked adapter-delta array (0 = base; armed by
    prefill_chunk_into_slot, read by every step program, inert when
    the params tree carries no adapter stack).  Block tables are NOT
    device state: the host owns them and passes the current snapshot
    into every program call.

    A stack with ``layer_types`` (TransformerConfig) adds what its
    layers keep per SLOT, of fixed size, beside the pool: ``conv``
    [conv layers, slots, conv_kernel - 1, e], the last columns of each
    convolution layer's gated input (a slot's first chunk starts from
    zeros whatever is there), and, with sparse experts, the scalar
    ``moe_touched``: the distinct experts that got a row, summed over
    the sparse layers and the steps of the LAST decode_rounds call; where
    the experts held are a share, or some need no weights
    (``cfg.moe_partial``), also ``moe_pairs`` [3]: that call's (row,
    choice) pairs that fell on an expert held here, on a zero-compute
    expert, and on an expert of another chip.

    With latent attention (``cfg.latent``) the pool is ONE array,
    ``cache_latent`` [cfg.kv_planes, num_blocks, block_tokens,
    cfg.latent_row], key and value at once, in place of ``cache_k`` and
    ``cache_v`` (``pool_sides`` names what a state holds).  An indexer
    adds ``cache_index`` (an index key a token and full plane) and
    sliding_attention layers ``cache_window`` (their planes, of their own
    row width); ``cache_latent`` then holds the full layers' planes
    only.  Every pool has the same blocks of the same positions: ONE
    block table a slot serves them all, so a page is allocated, aliased
    and freed in all of them at once.

    A drafting stack (``cfg.mtp_layers``) adds ``mtp_draft`` [slots],
    ``mtp_hidden`` [slots, e] and ``mtp_counts`` [3] (see there); its
    draft layer's rows are the last plane of ``cache_latent``.
    """
    if cfg.latent:
        if kv_cache_dtype != "model":
            raise ValueError("an int8 latent pool: not built")
        full = cfg.kv_planes - cfg.window_planes
        widths = {"cache_latent": (full, cfg.latent_row),
                  "cache_index": (full, cfg.index_dim),
                  "cache_window": (cfg.window_planes, cfg.window_row)}
        pool = {side: jnp.zeros(
            (widths[side][0], num_blocks, block_tokens, widths[side][1]),
            cfg.dtype) for side in latent_sides(cfg)}
    else:
        pool = dict(zip(("cache_k", "cache_v"), init_cache(
            cfg, num_blocks, block_tokens, kv_cache_dtype)))
    extra = {}
    if cfg.moe_partial:
        extra["moe_pairs"] = jnp.zeros((3,), jnp.int32)
    if cfg.conv_planes:
        extra["conv"] = jnp.zeros(
            (cfg.conv_planes, slots, cfg.conv_kernel - 1, cfg.d_model),
            cfg.dtype)
    if cfg.layer_types and cfg.moe_experts:
        extra["moe_touched"] = jnp.zeros((), jnp.int32)
    if cfg.mtp_layers:
        # A slot's draft of the token after ``last_token``, the main
        # stack's normed stream at the last position a chunk filled
        # (what the next chunk's first draft row reads), and the LAST
        # decode_rounds call's (drafts verified, drafts taken, positions
        # the later of a step's two rows saw, summed over live slots).
        extra["mtp_draft"] = jnp.zeros((slots,), jnp.int32)
        extra["mtp_hidden"] = jnp.zeros((slots, cfg.d_model), cfg.dtype)
        extra["mtp_counts"] = jnp.zeros((3,), jnp.int32)
    return {
        **extra,
        **pool,
        "lengths": jnp.zeros((slots,), jnp.int32),
        "stop_len": jnp.zeros((slots,), jnp.int32),
        "last_token": jnp.zeros((slots,), jnp.int32),
        "done": jnp.ones((slots,), bool),
        "keys": jnp.zeros((slots, 2), jnp.uint32),
        "adapter_ids": jnp.zeros((slots,), jnp.int32),
    }


def pool_sides(state) -> Tuple[str, ...]:
    """The keys under which a paged state holds its pool: keys and values,
    or a latent stack's pools (``latent_sides``)."""
    latent = tuple(side for side in (
        "cache_latent", "cache_index", "cache_window") if side in state)
    return latent or ("cache_k", "cache_v")


def _pool_block_tokens(state) -> int:
    """Static block width of a paged state's pool ([L, NB, bt, ...])."""
    cache = state[pool_sides(state)[0]]
    vals = cache.values if isinstance(cache, QTensor) else cache
    return vals.shape[2]


@partial(jax.jit, donate_argnums=(0,))
def import_kv_pages(state, pages_k, pages_v, ids):
    """Disaggregated-serving KV handoff, device side: scatter a list
    of transferred block PAGES into this engine's pool at physical
    blocks ``ids`` ([n] int32; entries holding the pool-size sentinel
    are padding and drop).  ``pages_k``/``pages_v`` are
    [planes, n, block_tokens, hkv, d] page stacks (QTensor values +
    scale for int8 pools) — exactly the prefill replica's pool rows,
    so after the scatter the decode replica's pool holds bit-identical
    k/v and the slot resumes through the ordinary cached-prefix path
    (chunked prefill from the covered offset).  ``n`` is static (the
    engine pads to its table span), so one compiled program covers
    every handoff; it runs once per imported request, never in the
    step loop."""
    nb = (state["cache_k"].values if isinstance(state["cache_k"], QTensor)
          else state["cache_k"]).shape[1]
    ids = jnp.where(ids < nb, ids, nb)

    def scatter(pool, pages):
        if isinstance(pool, QTensor):
            return QTensor(
                pool.values.at[:, ids].set(pages.values, mode="drop"),
                pool.scale.at[:, ids].set(pages.scale, mode="drop"),
                pool.axes)
        return pool.at[:, ids].set(pages.astype(pool.dtype),
                                   mode="drop")

    state = dict(state)
    state["cache_k"] = scatter(state["cache_k"], pages_k)
    state["cache_v"] = scatter(state["cache_v"], pages_v)
    return state


def gather_kv_pages(state, ids):
    """The inverse of ``import_kv_pages``, host side: pull physical
    blocks ``ids`` out of the pool as HOST page stacks — one batched
    fancy index per pool side ([planes, n, block_tokens, hkv, d] in a
    single transfer, never a per-block loop).  Returns
    ``((k_vals, k_scale), (v_vals, v_scale))`` as numpy arrays (scale
    is None for fp pools).  Deliberately NOT jitted: ``n`` varies per
    record and a traced gather would mint a new executable per shape,
    breaking the engine's compiled-program guarantee.  Feeds the KV
    export handoff (§5.9) and the host spill tier (§5.10); callers run
    it on the engine loop thread only, between program dispatches,
    because the pool buffers are donated to the step programs."""
    ids = np.asarray(ids, np.int32)

    def gather(pool):
        if isinstance(pool, QTensor):
            return (np.asarray(pool.values[:, ids]),
                    np.asarray(pool.scale[:, ids]))
        return np.asarray(pool[:, ids]), None

    return gather(state["cache_k"]), gather(state["cache_v"])


def _count_experts(state, counts):
    """``state`` with an expert layer's ``counts`` added to what the
    call has counted so far."""
    state = dict(state)
    if "moe_touched" in state:
        state["moe_touched"] = state["moe_touched"] + counts["touched"]
    if "moe_pairs" in state:
        state["moe_pairs"] = state["moe_pairs"] + jnp.stack(
            [counts["held"], counts["zero"], counts["absent"]])
    return state


def _advance_slots_drafting(cfg: TransformerConfig, params,
                            decode: DecodeConfig, tables: jax.Array, park,
                            state, paged_kernel=False, grouped_kernel=False):
    """``_advance_slots`` of a stack whose multi-token-prediction module
    drafts (TransformerConfig.mtp_layers), greedy: one step that
    VERIFIES a draft and makes the next, and yields one token or two.

    A live slot holds its last token t_n (frontier n = ``lengths``) and
    a draft d of t_{n+1}.  The stack runs the rows [t_n, d] at positions
    n, n + 1, each under its own frontier; g = argmax of the first.  If
    g == d the second row stood on the right token: the step emits g
    and g' = argmax of the second and the frontier moves by 2; else it
    emits g and the frontier moves by 1 (row n + 1 of every plane is
    overwritten by the next step before anything attends it: a length
    reset, never a scatter-erase).  A budget or an EOS met by the first
    token of a pair cuts the second.  The module then runs the rows
    (h_n, g) and, if taken, (h_{n+1}, g') at indices n + 1, n + 2 of its
    plane, and the draft the slot keeps is the argmax of the last real
    one.  Nothing goes to the host between verifying and drafting.

    Returns (state, first [S], second [S], emitted [S]): the tokens and
    how many of the two are real (0 for a retired slot)."""
    if decode.temperature > 0.0:
        raise ValueError(
            "a stack whose multi-token-prediction module drafts "
            "(mtp_layers) decodes greedily: a draft is taken where it is "
            "the stack's own first choice (sampling with a draft: not "
            "built)")
    lengths, done = state["lengths"], state["done"]
    sides = pool_sides(state)
    advance = ~done
    write_cols = jnp.where(advance, lengths, park)
    rows = jnp.stack([state["last_token"], state["mtp_draft"]], axis=1)
    hidden, cache, _, counts = forward_layer_types(
        cfg, params, rows, tuple(state[side] for side in sides), lengths,
        write_cols=write_cols, tables=tables, paged_kernel=paged_kernel,
        n_new=2 * advance.astype(jnp.int32), hidden=True,
        grouped_kernel=grouped_kernel)
    state = _count_experts(state, counts)
    with jax.named_scope("kft.logits"):
        logits = _head(cfg, params, hidden)
    with jax.named_scope("kft.mtp_accept"):
        targets = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [S, 2]
        first, second = targets[:, 0], targets[:, 1]
        taken = advance & (first == state["mtp_draft"])
        emit = jnp.minimum(jnp.where(taken, 2, 1),
                           jnp.maximum(state["stop_len"] - lengths, 0))
        stop = jnp.zeros_like(done)
        if decode.eos_token >= 0:
            emit = jnp.where(first == decode.eos_token,
                             jnp.minimum(emit, 1), emit)
            stop = (first == decode.eos_token) | (
                (emit == 2) & (second == decode.eos_token))
        emit = jnp.where(advance, emit, 0)
        pair = emit == 2
        new_lengths = lengths + emit
        new_done = done | (advance & (stop | (
            new_lengths >= state["stop_len"])))
        first = jnp.where(advance, first, 0)
        second = jnp.where(pair, second, 0)
    with jax.named_scope("kft.mtp_draft"):
        # Row (h_i, t_{i+1}) lies at index i + 1 of the draft plane.
        drafts, cache, counts = mtp_logits(
            cfg, params, hidden, jnp.stack([first, second], axis=1), cache,
            lengths + 1, lengths[:, None] + jnp.arange(2)[None, :],
            live=jnp.stack([advance, pair], axis=1),
            write_cols=jnp.where(advance, lengths + 1, park),
            tables=tables, paged_kernel=paged_kernel,
            grouped_kernel=grouped_kernel)
        drafts = jnp.argmax(drafts, axis=-1).astype(jnp.int32)
    state = _count_experts(state, counts)
    state.update(zip(sides, cache))
    state["lengths"] = new_lengths
    state["last_token"] = jnp.where(
        advance, jnp.where(pair, second, first), state["last_token"])
    state["done"] = new_done
    state["mtp_draft"] = jnp.where(pair, drafts[:, 1], drafts[:, 0])
    state["mtp_counts"] = state["mtp_counts"] + jnp.stack(
        [jnp.sum(advance), jnp.sum(taken),
         jnp.sum(jnp.where(advance, lengths + 2, 0))]).astype(jnp.int32)
    return state, first, second, emit


def _advance_slots(cfg: TransformerConfig, params, decode: DecodeConfig,
                   tables: jax.Array, park, state, paged_kernel=False,
                   grouped_kernel=False):
    """One batched decode step over every slot, ``decode_rounds``'s
    loop body: one forward at t=1 in which each slot ropes at its own
    length, attends under its own causal frontier over its pages of
    the pool, and scatters its new k/v to its own (block, offset)
    through ``tables`` ([S, max_blocks] int32, host-owned).  Retired
    slots ride along with dropped writes and zero emissions, so the
    static shape never changes.  Returns (state, nxt [S]) where
    ``nxt`` is the sampled token per slot (0 for frozen slots).
    ``park`` is the column past the table span where retired slots
    aim their dropped cache writes.  ``paged_kernel`` (static, chosen
    once by the engine from the platform its pool lives on):
    attention reads the pool in place through ops/paged_attention.py
    instead of the gathered view.  ``grouped_kernel`` (static, chosen
    the same way from where the expert matrices live): as
    ``_experts``'s."""
    lengths, done = state["lengths"], state["done"]
    sides = pool_sides(state)
    advance = ~done
    # Retired slots park their write past the table span; the
    # block scatter drops it.
    write_cols = jnp.where(advance, lengths, park)
    if cfg.layer_types:
        # Only live rows are tokens: a parked slot, or one in
        # mid-prefill, keeps its convolution state and chooses no expert.
        logits, cache, conv, counts = forward_layer_types(
            cfg, params, state["last_token"][:, None],
            tuple(state[side] for side in sides), lengths,
            write_cols=write_cols, tables=tables,
            paged_kernel=paged_kernel, conv=state.get("conv"),
            n_new=advance.astype(jnp.int32), grouped_kernel=grouped_kernel)
        state = dict(state) if counts is None \
            else _count_experts(state, counts)
        if conv is not None:
            state["conv"] = conv
    else:
        logits, cache = _forward_with_cache(
            cfg, params, state["last_token"][:, None],
            tuple(state[side] for side in sides), lengths,
            write_cols=write_cols, tables=tables,
            adapter_ids=state.get("adapter_ids"),
            paged_kernel=paged_kernel)
    with jax.named_scope("kft.sample"):
        last = logits[:, -1]
        if decode.temperature <= 0.0:
            nxt = jnp.argmax(last, axis=-1)
            keys = state["keys"]
        else:
            # Per-slot keys, split per step: slot r's sample stream
            # depends only on its own seed and step index, never on
            # which other requests happen to share the batch.
            split = jax.vmap(jax.random.split)(state["keys"])
            keys, subs = split[:, 0], split[:, 1]
            nxt = jax.vmap(jax.random.categorical)(
                subs, _filter_logits(decode, last))
        nxt = jnp.where(advance, nxt.astype(jnp.int32), 0)
        new_lengths = lengths + advance.astype(jnp.int32)
        new_done = done | (new_lengths >= state["stop_len"])
        if decode.eos_token >= 0:
            new_done = new_done | (advance & (nxt == decode.eos_token))
    state = dict(state, **dict(zip(sides, cache)))
    state["lengths"] = new_lengths
    state["last_token"] = nxt
    state["done"] = new_done
    state["keys"] = keys
    return state, nxt


@partial(jax.jit, static_argnums=(0, 3, 4),
         static_argnames=("paged_kernel", "grouped_kernel"),
         donate_argnums=(2,))
def decode_rounds(cfg: TransformerConfig, params, state,
                  decode: DecodeConfig, k: int, tables: jax.Array,
                  max_steps: jax.Array, *, paged_kernel: bool = False,
                  grouped_kernel: bool = False):
    """Device-resident multi-step decode: up to ``k`` decode steps in
    ONE dispatch via ``lax.while_loop``, with device-side early exit
    the moment every slot is done (EOS/budget) — the host never pays
    per-step dispatch, and a round that finishes all slots at step 3
    stops at step 3 instead of burning k-3 dead forwards.

    Returns ``(state, toks, counts, steps_run)`` and, for a drafting
    stack (below), ``drafts`` after them:

    - ``toks`` [S, k] int32, slot-major: slot s's tokens for this
      round occupy ``toks[s, :counts[s]]`` contiguously (a live slot
      advances every step from round start until it freezes, so its
      emissions never leave gaps), the engine drain's
      ``(arr, snapshot, counts)`` stream shape.
    - ``counts`` [S] int32: tokens emitted per slot (EOS included).
    - ``steps_run`` scalar int32: loop iterations actually executed.

    ``k`` is static (it sizes the output buffer and is the ceiling one
    compiled program serves); ``max_steps`` is a TRACED operand the
    host clamps per round, so adaptive round width reuses this single
    executable instead of compiling one program per width.  Block
    tables ride in unchanged as the host-owned snapshot — the host
    must pre-cover every slot for the worst case (``k`` new positions)
    before dispatch.  Per-step math is ``_advance_slots``, so greedy
    tokens do not depend on how the steps are cut into rounds.
    ``paged_kernel`` / ``grouped_kernel``: as there.

    A stack whose multi-token-prediction module drafts
    (``cfg.mtp_layers``) takes ``_advance_slots_drafting`` as its step: a
    slot then emits one token or two a step, ``toks`` is [S, 2 k] (slot
    s's tokens still contiguous in ``toks[s, :counts[s]]``), the host
    covers 2 k + 1 positions a slot, ``state["mtp_counts"]`` holds the
    call's drafts verified and taken, and ``drafts`` [S, 2 k] the draft
    each token of ``toks`` was held against (-1 for the second of a
    pair, which was held against none).
    """
    park = tables.shape[1] * _pool_block_tokens(state)
    slots = state["done"].shape[0]
    len0 = state["lengths"]
    if cfg.mtp_layers:
        state = dict(state, mtp_counts=jnp.zeros((3,), jnp.int32))
    if "moe_touched" in state:
        state = dict(state, moe_touched=jnp.zeros((), jnp.int32))
    if "moe_pairs" in state:
        state = dict(state, moe_pairs=jnp.zeros((3,), jnp.int32))
    cap = jnp.minimum(jnp.asarray(max_steps, jnp.int32),
                      jnp.int32(k))

    def cond(carry):
        i, state, _ = carry
        return (i < cap) & ~jnp.all(state["done"])

    def body(carry):
        i, state, out = carry
        if cfg.mtp_layers:
            at = state["lengths"] - len0     # emitted so far, a slot
            held = state["mtp_draft"]
            state, first, second, emit = _advance_slots_drafting(
                cfg, params, decode, tables, park, state, paged_kernel,
                grouped_kernel)
            row = jnp.arange(slots)
            at = jnp.where(emit > 0, at, 2 * k)
            out = out.at[0, row, at].set(first, mode="drop")
            out = out.at[1, row, at].set(held, mode="drop")
            out = out.at[0, row, jnp.where(emit > 1, at + 1, 2 * k)].set(
                second, mode="drop")
            return i + 1, state, out
        state, nxt = _advance_slots(cfg, params, decode, tables, park,
                                    state, paged_kernel, grouped_kernel)
        return i + 1, state, out.at[:, i].set(nxt)

    # A drafting stack's second plane: the draft each token was held
    # against, -1 where none was (the second of a pair).
    out0 = jnp.zeros((slots, k), jnp.int32) if not cfg.mtp_layers \
        else jnp.stack([jnp.zeros((slots, 2 * k), jnp.int32),
                        jnp.full((slots, 2 * k), -1, jnp.int32)])
    steps_run, state, toks = jax.lax.while_loop(
        cond, body, (jnp.zeros((), jnp.int32), state, out0))
    counts = state["lengths"] - len0
    if cfg.mtp_layers:
        return state, toks[0], counts, steps_run, toks[1]
    return state, toks, counts, steps_run


def _drafting_chunk(cfg, params, state, decode, tokens, start, prompt_len,
                    new_tokens, slot, table_row, prev_token,
                    grouped_kernel=False):
    """``prefill_chunk_into_slot`` of a stack whose multi-token-
    prediction module drafts (see there: ``prev_token``), greedy."""
    if decode.temperature > 0.0:
        raise ValueError("mtp_layers with a temperature: not built")
    slots_n = state["done"].shape[0]
    w = tokens.shape[1]
    sides = pool_sides(state)
    cache = tuple(state[side] for side in sides)
    real = jnp.reshape(jnp.clip(prompt_len - start, 0, w), (1,))

    def recomputed():
        return forward_layer_types(
            cfg, params, jnp.reshape(prev_token, (1, 1)), cache, start - 1,
            tables=table_row, hidden=True, store=False,
            grouped_kernel=grouped_kernel)[0]

    before = jax.lax.cond(
        prev_token >= 0, recomputed,
        lambda: state["mtp_hidden"][slot][None, None])
    hidden, cache, _, _ = forward_layer_types(
        cfg, params, tokens, cache, start, tables=table_row, n_new=real,
        hidden=True, grouped_kernel=grouped_kernel)
    with jax.named_scope("kft.sample"):
        idx = jnp.clip(prompt_len - 1 - start, 0, w - 1)
        last = jax.lax.dynamic_slice_in_dim(hidden, idx, 1, axis=1)
        with jax.named_scope("kft.logits"):
            tok = jnp.argmax(_head(cfg, params, last)[:, 0],
                             axis=-1).astype(jnp.int32)          # [1]
    with jax.named_scope("kft.mtp_fill"):
        # Index i of the draft plane: token i, the stream at i - 1.
        index = start + jnp.arange(w)
        _, cache, _ = mtp_logits(
            cfg, params,
            jnp.concatenate([before.astype(hidden.dtype), hidden[:, :-1]],
                            axis=1),
            tokens, cache, start, (index - 1)[None],
            live=((index >= 1) & (index < prompt_len))[None],
            tables=table_row, grouped_kernel=grouped_kernel)
        # The row of (h_{p-1}, first token) at index p, whose argmax is
        # the slot's first draft; its write is past a chunk that is not
        # the last and is overwritten by the one that is.
        drafts, cache, _ = mtp_logits(
            cfg, params, last, tok[:, None], cache, prompt_len,
            jnp.reshape(prompt_len - 1, (1, 1)), tables=table_row,
            grouped_kernel=grouped_kernel)
        draft = jnp.argmax(drafts[0, 0]).astype(jnp.int32)
    with jax.named_scope("kft.sample"):
        is_last = (start + w) >= prompt_len
        final_slot = jnp.where(is_last, slot, slots_n)
        done_final = new_tokens <= 1
        if decode.eos_token >= 0:
            done_final = done_final | (tok[0] == decode.eos_token)
        state = dict(state, **dict(zip(sides, cache)))
        state["mtp_hidden"] = state["mtp_hidden"].at[slot].set(
            hidden[0, -1].astype(state["mtp_hidden"].dtype))
        state["done"] = state["done"].at[slot].set(True)
        for key, value in (
                ("done", done_final), ("lengths", prompt_len),
                ("stop_len", prompt_len + jnp.maximum(new_tokens, 1) - 1),
                ("last_token", tok[0]), ("mtp_draft", draft)):
            state[key] = state[key].at[final_slot].set(value, mode="drop")
    return state, tok


@partial(jax.jit, static_argnums=(0, 3),
         static_argnames=("grouped_kernel",), donate_argnums=(2,))
def prefill_chunk_into_slot(
    cfg: TransformerConfig,
    params,
    state,
    decode: DecodeConfig,
    tokens: jax.Array,
    start: jax.Array,
    prompt_len: jax.Array,
    new_tokens: jax.Array,
    slot: jax.Array,
    seed: jax.Array,
    table_row: jax.Array,
    adapter_id: Optional[jax.Array] = None,
    prev_token: Optional[jax.Array] = None,
    *,
    grouped_kernel: bool = False,
):
    """Extend slot ``slot``'s KV by one static-width chunk of prompt
    starting at traced cache offset ``start``; returns
    (state, first sampled token [1]).

    adapter_id (traced int32 scalar, optional): the request's index
    into the stacked adapter-delta array (§5.11) — applied to THIS
    chunk's forward (prefill k/v must carry the tenant's delta too)
    and written to ``state["adapter_ids"][slot]`` so the step programs
    gather the same delta.  The write is unconditional at ``slot``
    (not gated on the final chunk): the freeze below already parks the
    slot, so an interleaved step reads a harmless id from a frozen
    row.  Omitted/None means base (0) and traces a separate program —
    engines without an adapter stack never pay the operand.

    prev_token (traced int32 scalar; a drafting stack's,
    ``cfg.mtp_layers``): the chunk also fills the draft layer's plane
    (``kft.mtp_fill``): the row at index i embeds token i and reads the
    main stack's normed stream at position i - 1, so the chunk's first
    row needs the stream ONE POSITION BACK, which no pool holds.  A
    slot's chunks hand it on in ``state["mtp_hidden"]``; where nothing
    ran before this chunk in this slot (the first chunk after a prefix
    hit) ``prev_token`` >= 0 is the prompt's token at ``start`` - 1 and
    that one position is recomputed over the slot's pages WITHOUT
    writing a row (a shared page is never written); -1 takes the
    state's.  The final chunk also runs the row (h_{p-1}, first token)
    at index p and arms the slot's first draft with its argmax.

    grouped_kernel (static): as ``decode_rounds``'s.

    tokens [1, chunk_w]: the prompt's tokens [start, start + chunk_w),
    right-padded past ``prompt_len`` on the final chunk.  table_row
    [1, max_blocks]: the slot's block table — fresh k/v scatter into
    the pool through it, and the chunk's queries attend over the
    slot's gathered pool view under the causal frontier ``start`` (the
    same ``cache_len``-gated attention path the decode scan uses with
    a traced offset), so earlier chunks' — or an aliased shared
    prefix's — k/v participate exactly as if the prompt had prefilled
    in one call, and garbage columns at/after start + chunk_w stay
    masked.  A resumed cached prefix needs NO device copy: the engine
    simply places the cached blocks in the table and starts the first
    chunk at the cached offset.  Chunk width is static and fixed per
    engine, so every admission, resumed at any offset, reuses ONE
    compiled program; the serving loop schedules these calls between
    decode steps under a token budget, which is what bounds how long
    an arriving prompt can stall in-flight decode.

    On the final chunk (start + chunk_w >= prompt_len, decided on
    device) the program samples the request's first token from the
    last real prompt position and arms the slot's scalars (lengths /
    stop_len / last_token / done / keys — what decode_rounds needs to
    advance the slot); intermediate chunks leave the slot frozen and
    park the scalar writes out of range.

    The unconditional ``done`` = True FREEZE is load-bearing: a slot
    freed by mid-generation deadline expiry still has ``done`` = False
    on device, so without it an interleaved decode round would keep
    advancing the dead occupant and scatter garbage through the NEW
    request's block table.  The engine therefore dispatches the first
    chunk of every admission at claim time, before any step program
    can run.
    """
    if cfg.mtp_layers:
        return _drafting_chunk(cfg, params, state, decode, tokens, start,
                               prompt_len, new_tokens, slot, table_row,
                               prev_token, grouped_kernel)
    slots_n = state["done"].shape[0]
    w = tokens.shape[1]
    aid = (jnp.zeros((), jnp.int32) if adapter_id is None
           else jnp.reshape(jnp.asarray(adapter_id, jnp.int32), ()))
    conv = None
    sides = pool_sides(state)
    if cfg.layer_types:
        # The slot's first chunk starts its convolution layers from
        # zeros; a later one goes on from the state the last left, and
        # each leaves the state of its last real token.
        first = jnp.reshape(start == 0, (1,))
        real = jnp.reshape(jnp.clip(prompt_len - start, 0, w), (1,))
        logits, cache, conv, _ = forward_layer_types(
            cfg, params, tokens, tuple(state[side] for side in sides),
            start, tables=table_row, conv=state.get("conv"),
            rows=jnp.reshape(slot, (1,)), fresh=first, n_new=real,
            grouped_kernel=grouped_kernel)
    else:
        logits, cache = _forward_with_cache(
            cfg, params, tokens, tuple(state[side] for side in sides),
            start, tables=table_row, adapter_ids=aid[None])
    with jax.named_scope("kft.sample"):
        # First-token sampling from the last REAL prompt position of this
        # chunk (only meaningful on the final chunk; clamped otherwise).
        idx = jnp.clip(prompt_len - 1 - start, 0, w - 1)
        last = jnp.take_along_axis(
            logits, jnp.reshape(idx, (1, 1, 1)), axis=1)[:, 0]  # [1, V]
        useed = jnp.reshape(seed, (1,)).astype(jnp.uint32)
        keys = jnp.stack([jnp.zeros_like(useed), useed], axis=-1)
        split = jax.vmap(jax.random.split)(keys)
        keys, subs = split[:, 0], split[:, 1]
        if decode.temperature <= 0.0:
            tok = jnp.argmax(last, axis=-1)
        else:
            tok = jax.vmap(jax.random.categorical)(
                subs, _filter_logits(decode, last))
        tok = tok.astype(jnp.int32)

        is_last = (start + w) >= prompt_len
        final_slot = jnp.where(is_last, slot, slots_n)  # OOB mid-prefill
        stop = prompt_len + jnp.maximum(new_tokens, 1) - 1
        done_final = new_tokens <= 1
        if decode.eos_token >= 0:
            done_final = done_final | (tok[0] == decode.eos_token)

        state = dict(state, **dict(zip(sides, cache)))
        if conv is not None:
            state["conv"] = conv
        if "adapter_ids" in state:
            state["adapter_ids"] = state["adapter_ids"].at[slot].set(aid)
        state["done"] = state["done"].at[slot].set(True)
        state["done"] = state["done"].at[final_slot].set(
            done_final, mode="drop")
        state["lengths"] = state["lengths"].at[final_slot].set(
            prompt_len, mode="drop")
        state["stop_len"] = state["stop_len"].at[final_slot].set(
            stop, mode="drop")
        state["last_token"] = state["last_token"].at[final_slot].set(
            tok[0], mode="drop")
        state["keys"] = state["keys"].at[final_slot].set(
            keys[0], mode="drop")
    return state, tok
