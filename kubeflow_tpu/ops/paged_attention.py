"""Decode attention over the paged KV pool, read in place (Pallas TPU).

The decode program of the serving engine (``models/generate.py``
``decode_rounds``) attends ONE query position per slot
against a pool that every slot shares through its block table.  The
pool is STACKED, ``[kv_planes, num_blocks, block_tokens, hkv, d]``: one
plane per layer (per loop step and layer in a looped stack), and the
layer scan carries it whole, so a layer hands the kernel the whole pool
and its plane, never a slice.  The plain path gathers each slot's whole
``[max_blocks * block_tokens]`` view, repeats it over the GQA group and
multiplies in float32: tens of gigabytes of HBM traffic a step for a
gigabyte of resident keys and values.  This kernel leaves the pool in
HBM and, per slot, copies only the pages below the slot's frontier into
VMEM, several pages a block, the next block's copies in flight while
this one is computed.

Layout.  The pool is viewed ``[kv_planes * num_blocks, bt * hkv, d]``
(a bitcast: the leading axes merged, and a page's two), so physical
page ``page`` of plane ``plane`` is row ``plane * num_blocks + page`` of
the view, and the plane rides in as a scalar beside the tables.  A page
is ``[bt, hkv, d]``; with its two leading axes merged
it is ``bt * hkv`` rows of ``d``: one row per (position, kv head).  The
kernel treats those rows as the keys of a plain single-query flash
step: ``q [h, d] @ rows^T`` scores every query head against every
(position, kv head) row on the MXU, the mask keeps for query head ``i``
the rows of ITS kv head (``row % hkv == i // g``) below the frontier,
and ``p [h, rows] @ v_rows [rows, d]`` sums exactly those.  So each page
is loaded once for all ``h / hkv`` query heads of a group, nothing is
transposed or repeated, and the MXU does ``hkv`` times the needed work
on a step that memory bounds.

Narrow heads.  The chip copies whole 128-lane rows, so a head of 64
(or 32) lanes cannot be a row of its own.  ``128 // d`` neighbouring kv
heads then share a row (the same bitcast, ``[bt * hkv * d / 128, 128]``),
each query head is laid into the lanes of ITS kv head with zeros in the
others' (the zeros take the neighbours out of the score), the mask keeps
the rows of its head's group, and of the ``[h, 128]`` product with the
values each head keeps its own lanes.  The kernel is the same; only what
it is told about rows and groups differs (``supports`` says which head
sizes can be laid out so).

Latent pages (``paged_latent_decode_attention``).  A model with latent
attention keeps ONE pool, ``[kv_planes, num_blocks, block_tokens, row]``:
a position's row (the normed latent, the rotary key all heads share,
zeros up to whole 128-lane rows) is key and value at once.  The same
kernel walks it with one side in place of two: ``hkv`` is 1, so every
query head (its nope part absorbed into the latent space, then its rotary
part) scores every row, and the value product takes the rows' first
``value_lanes`` lanes (the latent) of the SAME buffer; the output stays in
the latent space for the caller to expand.

Arithmetic: operands in the pool's dtype (bf16 on the chip) with
float32 accumulation, float32 running max / sum / output (online
softmax), weights cast to the pool's dtype before the value product, as
``ops.attention.dot_product_attention`` does: the same products in
another order of summation.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Finite, so that exp(masked - max) is 0 and never inf - inf.
_MASKED = -1e30


def _kernel(tables_ref, ntok_ref, plane_ref, q_ref, *refs, mb, bt, hkv, g,
            pages, nb, scale, value_lanes=None):
    """One slot: walk its resident pages ``pages`` at a time.

    ``refs``: the pool's sides in HBM (keys and values; or, with
    ``value_lanes``, the ONE latent pool, whose rows are keys whole and
    values in their first ``value_lanes`` lanes), the output, a buffer a
    side, the semaphores."""
    sides = (len(refs) - 2) // 2
    hbm, o_ref = refs[:sides], refs[sides]
    bufs, sems = refs[sides + 1:-1], refs[-1]
    s = pl.program_id(0)
    first = plane_ref[0] * nb             # the plane's page 0 in the view
    n = ntok_ref[s]                       # positions to attend (0: none)
    n_pages = (n + bt - 1) // bt
    n_blocks = (n_pages + pages - 1) // pages
    rows = bt * hkv                       # (position, kv head) rows a page

    @pl.when(s == 0)
    def _():
        # A page the walk never copied is multiplied by a zero weight:
        # it has to hold numbers, and fresh VMEM need not.
        for buf in bufs:
            buf[...] = jnp.zeros_like(buf)

    def copies(blk, buf, p):
        # Entries below the frontier are real pages; the clamp only
        # keeps a sentinel (== nb) that a wrong table would hold inside
        # the plane.
        page = first + jnp.minimum(
            tables_ref[s * mb + blk * pages + p], nb - 1)
        dst = pl.ds(p * rows, rows)
        return tuple(
            pltpu.make_async_copy(
                hbm[side].at[page], bufs[side].at[buf, dst],
                sems.at[side, buf])
            for side in range(sides))

    def for_pages(blk, buf, act):
        for p in range(pages):
            @pl.when(blk * pages + p < n_pages)
            def _(p=p):
                for c in copies(blk, buf, p):
                    act(c)

    @pl.when(n_blocks > 0)
    def _():
        for_pages(0, 0, lambda c: c.start())

    q = q_ref[0]                                            # [h, d]
    h = q.shape[0]
    shape = (h, pages * rows)
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    own = (col % hkv) == (jax.lax.broadcasted_iota(jnp.int32, shape, 0)
                          // g)
    pos = col // hkv

    def body(i, carry):
        m, l, acc = carry
        buf = i % 2

        @pl.when(i + 1 < n_blocks)
        def _():
            for_pages(i + 1, 1 - buf, lambda c: c.start())

        for_pages(i, buf, lambda c: c.wait())
        k = bufs[0][buf]                                    # [rows*, d]
        v = bufs[-1][buf]
        if value_lanes is not None:
            v = v[:, :value_lanes]
        sc = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # [h, rows*]
        keep = own & (pos + i * (pages * bt) < n)
        sc = jnp.where(keep, sc, _MASKED)
        m_new = jnp.maximum(m, jnp.max(sc, axis=1, keepdims=True))
        p = jnp.exp(sc - m_new)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
        acc = alpha * acc + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        return m_new, l, acc

    m0 = jnp.full((h, 1), _MASKED, jnp.float32)
    l0 = jnp.zeros((h, 1), jnp.float32)
    acc0 = jnp.zeros((h, value_lanes or q.shape[1]), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, n_blocks, body, (m0, l0, acc0))
    # A slot with nothing to attend (retired) returns zeros.
    o_ref[0] = (acc / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


_LANES = 128


def supports(head_dim: int, n_kv_heads: int) -> bool:
    """Whether a page of ``n_kv_heads`` heads of ``head_dim`` can be read
    as whole 128-lane rows: heads of a multiple of 128, or narrower heads
    that fill a row between them."""
    if head_dim % _LANES == 0:
        return True
    return _LANES % head_dim == 0 and n_kv_heads % (_LANES // head_dim) == 0


@functools.partial(jax.jit, static_argnames=("pages_per_block",
                                             "interpret"))
def paged_decode_attention(q, k_pool, v_pool, plane, tables, n_tokens, *,
                           pages_per_block: int = 16,
                           interpret: bool = False):
    """``q [S, h, d]`` against each slot's resident pages -> ``[S, h, d]``.

    k_pool / v_pool: ``[kv_planes, num_blocks, block_tokens, hkv, d]``,
    the stacked pools, left in HBM.
    plane: int32 scalar (traced in the layer scan): the plane to read.
    tables ``[S, max_blocks]`` int32: slot s's logical page i lives in
    physical page ``tables[s, i]`` of that plane; entries at and above
    the slot's frontier are never read (they may hold the sentinel
    ``num_blocks``).
    n_tokens ``[S]`` int32: how many positions slot s attends, counted
    from 0 and INCLUDING the step's own (already written to the pool);
    0 does no page and returns zeros (a retired slot).  Slots may share
    physical pages (the prefix cache's aliasing).
    """
    S, h, head_dim = q.shape
    planes, nb, bt, hkv, _ = k_pool.shape
    mb = tables.shape[1]
    assert h % hkv == 0, (h, hkv)
    g, scale = h // hkv, head_dim ** -0.5
    # Narrow heads: ``pack`` kv heads a row (module docstring).  A head
    # size that cannot be laid out so keeps a row a head, which only the
    # interpreter takes (``supports`` is what a caller on the chip asks).
    pack = _LANES // head_dim if head_dim < _LANES \
        and supports(head_dim, hkv) else 1
    lane = None
    if pack > 1:
        lane = jax.nn.one_hot((jnp.arange(h) // g) % pack, pack,
                              dtype=q.dtype)[None, :, :, None]
        q = (q[:, :, None, :] * lane).reshape(S, h, pack * head_dim)
    hkv, g, d = hkv // pack, g * pack, pack * head_dim
    pages = max(1, min(pages_per_block, mb))
    rows = bt * hkv
    kernel = functools.partial(
        _kernel, mb=mb, bt=bt, hkv=hkv, g=g, pages=pages, nb=nb,
        scale=scale)
    out = pl.pallas_call(
        kernel,
        name="paged_decode_attention",
        out_shape=jax.ShapeDtypeStruct((S, h, d), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(S,),
            in_specs=[
                pl.BlockSpec((1, h, d), lambda s, *_: (s, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, h, d), lambda s, *_: (s, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, pages * rows, d), k_pool.dtype),
                pltpu.VMEM((2, pages * rows, d), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(tables.reshape(-1).astype(jnp.int32), n_tokens.astype(jnp.int32),
      jnp.reshape(plane, (1,)).astype(jnp.int32), q,
      k_pool.reshape(planes * nb, rows, d),
      v_pool.reshape(planes * nb, rows, d))
    if pack > 1:
        out = jnp.sum(out.reshape(S, h, pack, head_dim) * lane, axis=2)
    return out


@functools.partial(jax.jit, static_argnames=(
    "value_lanes", "scale", "pages_per_block", "interpret"))
def paged_latent_decode_attention(q, pool, plane, tables, n_tokens,
                                  value_lanes: int, scale: float, *,
                                  pages_per_block: int = 32,
                                  interpret: bool = False):
    """The latent (MLA) form: ``q [S, h, row]`` against each slot's
    resident LATENT pages -> ``[S, h, value_lanes]``, in the latent space.

    pool: ``[kv_planes, num_blocks, block_tokens, row]``, the one stacked
    latent pool, left in HBM.  A position's row is key and value at once:
    every query head scores against the whole row (the query absorbed
    into the latent space, then its rotary part; lanes past those hold
    zeros on both sides), and the weights sum the row's first
    ``value_lanes`` lanes (the latent), so a page is copied once for all
    heads and once for both products.  ``row`` is a multiple of 128 (the
    chip copies whole lane rows: ``TransformerConfig.latent_row``).
    plane / tables / n_tokens: as ``paged_decode_attention``; ``scale``
    multiplies the scores (the expanded head's, not the row's).
    """
    S, h, row = q.shape
    planes, nb, bt, _ = pool.shape
    mb = tables.shape[1]
    pages = max(1, min(pages_per_block, mb))
    kernel = functools.partial(
        _kernel, mb=mb, bt=bt, hkv=1, g=h, pages=pages, nb=nb,
        scale=scale, value_lanes=value_lanes)
    return pl.pallas_call(
        kernel,
        name="paged_latent_decode_attention",
        out_shape=jax.ShapeDtypeStruct((S, h, value_lanes), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(S,),
            in_specs=[
                pl.BlockSpec((1, h, row), lambda s, *_: (s, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, h, value_lanes),
                                   lambda s, *_: (s, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, pages * bt, row), pool.dtype),
                pltpu.SemaphoreType.DMA((1, 2)),
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(tables.reshape(-1).astype(jnp.int32), n_tokens.astype(jnp.int32),
      jnp.reshape(plane, (1,)).astype(jnp.int32), q,
      pool.reshape(planes * nb, bt, row))
