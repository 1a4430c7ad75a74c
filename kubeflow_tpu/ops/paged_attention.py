"""Decode attention over the paged KV pool, and an indexer's scores over
its paged index keys, read in place (Pallas TPU).

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
VMEM, about a MiB of pages a block and side (``_BLOCK_BYTES``), into one
of two buffers.

The walk.  The grid is one slot a step, in order, and the copies stay in
flight from the first slot's first block to the last slot's last.  While
a block is computed, the NEXT block's copies run into the other buffer:
the same slot's next block or, under a slot's last block, the first
block of the next slot that attends anything (``n_tokens`` and the
tables are scalar-prefetched, so a slot can read its successor's).  Two
words in SMEM ride from one grid step to the next: the buffer that next
first block went to (a slot of an odd number of blocks flips it), and
the slot it was started for, so that only a call's first live slot
starts its own first block, with nothing to compute meanwhile.  A
retired slot (``n_tokens`` 0) starts and waits for nothing; the search
for the next live slot walks past it.  Every block but a slot's last is
whole: its pages are issued and waited for under ONE condition, eight
in straight-line code a turn of a loop, and only a last block counts
its pages; no page is a branch.  Every start has its wait, a semaphore
a buffer and side, whatever ends the walk.
The compiler's bounds checks on the copies are off (they were two
thirds of the instructions a page's copy cost): the plane and every
table entry are clamped into the pool instead.

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
the latent space for the caller to expand.  With ``window`` a slot's
walk starts at the page that holds the first position of its window and
the mask keeps ``[n_tokens - window, n_tokens)``: a sliding layer copies
the pages that meet its window, whatever the slot holds below them.

Index keys (``paged_index_scores``).  A stack with an indexer keeps a
second pool beside its latent one, ``[planes, num_blocks, block_tokens,
index_dim]``: a position's index key, one for all index heads, a page of
16 keys of 128 bfloat16 exactly one 4 KB tile.  The walk is the same
(``_walk``: one side, the same two buffers, blocks in flight from the
call's first live slot to its last); what is computed on a block is not
attention: ``q_idx [hI, dI] @ keys^T`` in the pool's dtype with float32
accumulation, relu, times the head's float32 weight, summed over the
index heads: one float32 score a position, written to the block's row
of the output.  No softmax and no carry between blocks; a position at or
past the slot's ``n_tokens`` reads -inf, a retired slot -inf everywhere,
which is what ``models/generate.py`` ``_index_scores`` gives and what its
``_choose`` expects.  At 4 KB a descriptor the walk is bound by the
issue of its copies and their waits (~11 ns a page: 335 GB/s alone on
the chip, the same with 8 index heads as with 64; PERF.md section 6,
PR 45), not by HBM; ``supports_index`` says which pools can be laid out
so.

What is NOT here: a form that copies CHOSEN rows (an indexer's
``index_topk`` positions).  A latent row lies in HBM inside a tile of
(8, 128)(2, 1): the chip's compiler refuses a copy of one row of it
("slice shape along dimension 0 must be aligned to tiling (8)"; compiled
for a described v5e, PR 44), and no bitcast of the pool gives a row a
tile of its own.  ``models/generate.py`` gathers the chosen rows with the
compiler's own gather (``pool[plane, page, offset]``) and attends them
with plain products.

Arithmetic: operands in the pool's dtype (bf16 on the chip) with
float32 accumulation, float32 running max / sum / output (online
softmax), weights cast to the pool's dtype before the value product, as
``ops.attention.dot_product_attention`` does: the same products in
another order of summation.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Finite, so that exp(masked - max) is 0 and never inf - inf.
_MASKED = -1e30


def _first_page(ntok_ref, slot, window, bt):
    """The first page of ``slot``'s walk: 0, or the one that holds the
    first position of its window."""
    if window is None:
        return 0
    return jnp.maximum(ntok_ref[slot] - window, 0) // bt


def _walk(tables_ref, ntok_ref, plane_ref, hbm, bufs, sems, ride, *, mb,
          bt, rows, pages, nb, planes, window, idle, visit, carry, finish):
    """One slot's walk (module docstring), whatever is computed on a
    block: its resident pages ``pages`` at a time into ``bufs`` (one a
    side of ``hbm``, ``rows`` rows a page) and, under its last block, the
    first block of the next slot that holds anything.

    ``visit(i, buf, carry) -> carry`` computes block ``i`` of the slot,
    landed in buffer ``buf``; ``finish(carry)`` takes what the last block
    returns; ``idle()`` stands for both in a slot that holds nothing
    (retired), which starts and waits for nothing.  ``ride``: the two
    words that pass from one grid step to the next, the buffer the next
    first block goes to and the slot whose first block is in flight."""
    s, slots = pl.program_id(0), pl.num_programs(0)
    # The plane's page 0 in the view.  This clamp and the one on a
    # table's entry keep every copy inside the pool; the compiler's own
    # checks are off (``_COMPILER_PARAMS``).
    first = jnp.clip(plane_ref[0], 0, planes - 1) * nb

    def first_page(slot):
        return _first_page(ntok_ref, slot, window, bt)

    def pages_of(slot):
        """Pages ``slot``'s walk holds, from its first page on."""
        return (ntok_ref[slot] + bt - 1) // bt - first_page(slot)

    n_pages = pages_of(s)
    n_blocks = (n_pages + pages - 1) // pages

    @pl.when(s == 0)
    def _():
        # A page the walk never copied is multiplied by a zero weight
        # (or masked): it has to hold numbers, and fresh VMEM need not.
        for buf in bufs:
            buf[...] = jnp.zeros_like(buf)
        ride[0] = 0
        ride[1] = -1

    def block(slot, blk, buf, held, act):
        """``act`` (start or wait) on the copies of ``slot``'s block
        ``blk``, of whose pages the slot holds those below ``held``.
        Every block but a slot's last is whole: its pages go in
        straight-line code, ``_UNROLL`` at a time, under ONE condition;
        only a last block counts its pages."""
        def page(p, _=None):
            # Entries below the frontier are real pages; the clamp only
            # keeps a sentinel (== nb) that a wrong table would hold
            # inside the plane.
            at = first + jnp.clip(
                tables_ref[slot * mb + first_page(slot) + blk * pages + p],
                0, nb - 1)
            dst = pl.ds(pl.multiple_of(p * rows, rows), rows)
            for side, buffer in enumerate(bufs):
                act(pltpu.make_async_copy(
                    hbm[side].at[at], buffer.at[buf, dst],
                    sems.at[side, buf]))

        here = held - blk * pages             # of this block's pages
        unroll = math.gcd(pages, _UNROLL)

        def some_pages(i, _):
            for j in range(unroll):
                page(i * unroll + j)

        @pl.when(here >= pages)
        def _():
            jax.lax.fori_loop(0, pages // unroll, some_pages, None)

        @pl.when(here < pages)
        def _():
            jax.lax.fori_loop(0, here, page, None)

    def start(copy):
        copy.start()

    def wait(copy):
        copy.wait()

    def landed(i, buf, state):
        block(s, i, buf, n_pages, wait)
        return visit(i, buf, state)

    @pl.when(n_blocks == 0)
    def _():
        idle()

    @pl.when(n_blocks > 0)
    def _():
        buf0 = ride[0]

        @pl.when(ride[1] != s)
        def _():
            # Nobody fetched this slot's first block: the call's first
            # slot that holds anything.
            block(s, 0, buf0, n_pages, start)

        def body(i, state):
            buf = (buf0 + i) % 2
            block(s, i + 1, 1 - buf, n_pages, start)
            return landed(i, buf, state)

        state = jax.lax.fori_loop(0, n_blocks - 1, body, carry)
        # The last block: the buffer beside it is free, and takes the
        # first block of the next slot that holds anything.
        buf = (buf0 + n_blocks - 1) % 2
        nxt = jax.lax.while_loop(
            lambda j: (j < slots)
            & (ntok_ref[jnp.minimum(j, slots - 1)] == 0),
            lambda j: j + 1, s + 1)

        @pl.when(nxt < slots)
        def _():
            block(nxt, 0, 1 - buf, pages_of(nxt), start)

        ride[0] = 1 - buf
        ride[1] = nxt
        finish(landed(n_blocks - 1, buf, state))


def _kernel(tables_ref, ntok_ref, plane_ref, q_ref, *refs, mb, bt, hkv, g,
            pages, nb, planes, scale, value_lanes=None, window=None,
            queries=1, first=0):
    """One slot's attention over the pages ``_walk`` brings.

    ``refs``: the pool's sides in HBM (keys and values; or, with
    ``value_lanes``, the ONE latent pool, whose rows are keys whole and
    values in their first ``value_lanes`` lanes), the output, a buffer a
    side, the semaphores, and the walk's ``ride``.
    ``window``: a slot attends its last ``window`` positions only, and
    its walk starts at the page that holds the first of them.
    ``queries``: the query rows hold that many neighbouring positions of
    the slot, position j in rows ``[j * h / queries, (j + 1) * h /
    queries)``; ``n_tokens`` is what the LAST of them sees, the one
    before it one position fewer.  ``first``: positions below it are
    masked for every row."""
    sides = (len(refs) - 3) // 2
    hbm, o_ref = refs[:sides], refs[sides]
    bufs, sems, ride = refs[sides + 1:-2], refs[-2], refs[-1]
    s = pl.program_id(0)
    rows = bt * hkv                       # (position, kv head) rows a page
    n = ntok_ref[s]                       # positions to attend (0: none)
    page0 = _first_page(ntok_ref, s, window, bt)

    q = q_ref[0]                                            # [h, d]
    h = q.shape[0]
    shape = (h, pages * rows)
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    own = (col % hkv) == (jax.lax.broadcasted_iota(jnp.int32, shape, 0)
                          // g)
    pos = col // hkv
    # What a query row sees fewer than the slot's last position does.
    behind = (queries - 1) - jax.lax.broadcasted_iota(
        jnp.int32, shape, 0) // (h // queries) if queries > 1 else 0

    def attend(i, buf, carry):
        m, l, acc = carry
        k = bufs[0][buf]                                    # [rows*, d]
        v = bufs[-1][buf]
        if value_lanes is not None:
            v = v[:, :value_lanes]
        sc = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # [h, rows*]
        if window is None:
            at = pos + i * (pages * bt)
            keep = own & (at < n - behind)
            if first:
                keep = keep & (at >= first)
        else:
            at = pos + (page0 + i * pages) * bt
            keep = own & (at < n) & (at >= n - window)
        sc = jnp.where(keep, sc, _MASKED)
        m_new = jnp.maximum(m, jnp.max(sc, axis=1, keepdims=True))
        p = jnp.exp(sc - m_new)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
        acc = alpha * acc + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        return m_new, l, acc

    def idle():
        # A slot with nothing to attend returns zeros.
        o_ref[0] = jnp.zeros_like(o_ref[0])

    def finish(carry):
        _, l, acc = carry
        o_ref[0] = (acc / l).astype(o_ref.dtype)

    h_rows = (h, 1)
    _walk(tables_ref, ntok_ref, plane_ref, hbm, bufs, sems, ride, mb=mb,
          bt=bt, rows=rows, pages=pages, nb=nb, planes=planes,
          window=window, idle=idle, visit=attend, finish=finish, carry=(
              jnp.full(h_rows, _MASKED, jnp.float32),
              jnp.zeros(h_rows, jnp.float32),
              jnp.zeros((h, value_lanes or q.shape[1]), jnp.float32)))


def _index_kernel(tables_ref, ntok_ref, plane_ref, q_ref, w_ref, pool_ref,
                  o_ref, buf_ref, sems, ride, *, mb, bt, pages, nb, planes):
    """One slot's indexer scores over the index key pages ``_walk``
    brings: block i's ``sum_h w_h relu(q_h . k)`` is row i of the
    output, and every position at or past the slot's ``n_tokens`` (all
    of a retired slot's) reads -inf.  Nothing passes from one block to
    the next."""
    n = ntok_ref[pl.program_id(0)]
    q = q_ref[0]                                            # [hI, dI]
    w = w_ref[0]                                            # [hI, 1]
    pos = jax.lax.broadcasted_iota(jnp.int32, (1, pages * bt), 1)
    o_ref[0] = jnp.full(o_ref.shape[1:], -jnp.inf, o_ref.dtype)

    def score(i, buf, _):
        sc = jax.lax.dot_general(
            q, buf_ref[buf], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)             # [hI, keys]
        sc = jnp.sum(jax.nn.relu(sc) * w, axis=0, keepdims=True)
        o_ref[0, pl.ds(i, 1)] = jnp.where(
            pos + i * (pages * bt) < n, sc, -jnp.inf)

    _walk(tables_ref, ntok_ref, plane_ref, (pool_ref,), (buf_ref,), sems,
          ride, mb=mb, bt=bt, rows=bt, pages=pages, nb=nb, planes=planes,
          window=None, idle=lambda: None, visit=score, carry=None,
          finish=lambda _: None)


_LANES = 128

# Pages of a whole block issued between two turns of their loop: the
# loop's own instructions are a tenth of a page's; unrolled whole (64)
# the kernel's text tripled and every program that holds it took three
# times as long to lower (agents' ``setup_s`` +5 s).
_UNROLL = 8

# A block of copies is about a MiB a side: read alone on the chip
# (PERF.md section 5, PR 41), latent pages of 20 KB are fastest 64 a
# block (32: +12 to +19 %, 128: +2 to +7 %, 16: +44 %), and k / v pages
# of 32 KB a side read within 4 % at 16, 32 and 64 a block (8: +7 %);
# index key pages of 4 KB are fastest 256 a block (64: +11 %, 128: +2 %,
# 512: +5 %; PR 45).
_BLOCK_BYTES = 1 << 20

# Each page's copy carried two bounds checks that halt the chip (one on
# the HBM address, one on the VMEM address): 12 of the ~18 instruction
# bundles a page's copy cost, with no vector work beside them, a third
# of a latent block's time.  Every address the kernel forms is clamped
# (the plane, a table's entry) or static.
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("arbitrary",), disable_bounds_checks=True)


def _block_pages(page_bytes: int, table_pages: int) -> int:
    """Pages a block: the fewest, in powers of two, that fill
    ``_BLOCK_BYTES`` a side; never more than a table holds."""
    pages = 1
    while pages * page_bytes < _BLOCK_BYTES:
        pages *= 2
    return min(pages, table_pages)


def _walk_arguments(tables, n_tokens, plane):
    """The kernel's three scalar-prefetched arguments."""
    return (tables.reshape(-1).astype(jnp.int32),
            n_tokens.astype(jnp.int32),
            jnp.reshape(plane, (1,)).astype(jnp.int32))


def _walk_scratch(buffers):
    """Two buffers a side, a semaphore a buffer, and the two words that
    ride from one grid step to the next."""
    return [*buffers, pltpu.SemaphoreType.DMA((len(buffers), 2)),
            pltpu.SMEM((2,), jnp.int32)]


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
                           pages_per_block: int | None = None,
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
    pages_per_block: the walk's block; None takes it from a page's bytes
    (``_block_pages``).
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
    rows = bt * hkv
    pages = min(pages_per_block, mb) if pages_per_block else _block_pages(
        rows * d * k_pool.dtype.itemsize, mb)
    kernel = functools.partial(
        _kernel, mb=mb, bt=bt, hkv=hkv, g=g, pages=pages, nb=nb,
        planes=planes, scale=scale)
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
            scratch_shapes=_walk_scratch([
                pltpu.VMEM((2, pages * rows, d), k_pool.dtype),
                pltpu.VMEM((2, pages * rows, d), v_pool.dtype)]),
        ),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(*_walk_arguments(tables, n_tokens, plane), q,
      k_pool.reshape(planes * nb, rows, d),
      v_pool.reshape(planes * nb, rows, d))
    if pack > 1:
        out = jnp.sum(out.reshape(S, h, pack, head_dim) * lane, axis=2)
    return out


@functools.partial(jax.jit, static_argnames=(
    "value_lanes", "scale", "window", "queries", "first",
    "pages_per_block", "interpret"))
def paged_latent_decode_attention(q, pool, plane, tables, n_tokens,
                                  value_lanes: int, scale: float, *,
                                  window: int | None = None,
                                  queries: int = 1, first: int = 0,
                                  pages_per_block: int | None = None,
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
    window: a slot attends its last ``window`` positions
    ``[n_tokens - window, n_tokens)`` and the walk copies only the pages
    that hold them, whatever lies below: None attends all.
    queries: ``q`` holds that many neighbouring positions a slot (a
    drafting stack's last token and its draft), ``[S, queries * heads,
    row]`` with position j in rows ``[j * heads, (j + 1) * heads)``;
    ``n_tokens`` counts what the LAST position sees and position j sees
    ``queries - 1 - j`` fewer: the pages are copied once for all of
    them.  first: positions below it are attended by no row (without
    ``window``).
    """
    assert window is None or (queries == 1 and not first)
    S, h, row = q.shape
    planes, nb, bt, _ = pool.shape
    mb = tables.shape[1]
    pages = min(pages_per_block, mb) if pages_per_block else _block_pages(
        bt * row * pool.dtype.itemsize, mb)
    kernel = functools.partial(
        _kernel, mb=mb, bt=bt, hkv=1, g=h, pages=pages, nb=nb,
        planes=planes, scale=scale, value_lanes=value_lanes, window=window,
        queries=queries, first=first)
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
            scratch_shapes=_walk_scratch([
                pltpu.VMEM((2, pages * bt, row), pool.dtype)]),
        ),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(*_walk_arguments(tables, n_tokens, plane), q,
      pool.reshape(planes * nb, bt, row))


def supports_index(index_dim: int, block_tokens: int, dtype) -> bool:
    """Whether a page of the index pool, ``[block_tokens, index_dim]`` of
    ``dtype``, can be copied as it lies: keys of whole 128-lane rows, and
    a page of whole tiles of the dtype (8 rows of 32 bits: 16 of
    bfloat16)."""
    return index_dim % _LANES == 0 \
        and block_tokens % (32 // jnp.dtype(dtype).itemsize) == 0


@functools.partial(jax.jit, static_argnames=("pages_per_block",
                                             "interpret"))
def paged_index_scores(q_idx, w_idx, index_pool, plane, tables, n_tokens, *,
                       pages_per_block: int | None = None,
                       interpret: bool = False):
    """An indexer's scores of ONE query position a slot against the
    index keys the slot holds: ``I(s) = sum_h w_h relu(q_h . k(s))`` ->
    float32 ``[S, max_blocks * block_tokens]``, -inf at and past a slot's
    ``n_tokens`` (everywhere for a retired slot), as
    ``models/generate.py`` ``_index_scores`` gives them.

    q_idx ``[S, hI, dI]`` (the pool's dtype on the chip), w_idx ``[S,
    hI]`` float32; index_pool: ``[planes, num_blocks, block_tokens, dI]``,
    the stacked index pool, left in HBM: a position's index key, which
    all index heads share.  plane / tables / n_tokens /
    pages_per_block: as ``paged_decode_attention``.  The products run in
    the pool's dtype with float32 accumulation; relu, the weights and the
    sum over the index heads in float32.
    """
    S, hI, dI = q_idx.shape
    planes, nb, bt, _ = index_pool.shape
    mb = tables.shape[1]
    pages = min(pages_per_block, mb) if pages_per_block else _block_pages(
        bt * dI * index_pool.dtype.itemsize, mb)
    blocks = -(-mb // pages)
    kernel = functools.partial(
        _index_kernel, mb=mb, bt=bt, pages=pages, nb=nb, planes=planes)
    out = pl.pallas_call(
        kernel,
        name="paged_index_scores",
        # A block's scores a row; the last block may overhang the table.
        out_shape=jax.ShapeDtypeStruct((S, blocks, pages * bt),
                                       jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(S,),
            in_specs=[
                pl.BlockSpec((1, hI, dI), lambda s, *_: (s, 0, 0)),
                pl.BlockSpec((1, hI, 1), lambda s, *_: (s, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, blocks, pages * bt),
                                   lambda s, *_: (s, 0, 0)),
            scratch_shapes=_walk_scratch([
                pltpu.VMEM((2, pages * bt, dI), index_pool.dtype)]),
        ),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(*_walk_arguments(tables, n_tokens, plane), q_idx,
      w_idx.astype(jnp.float32)[..., None],
      index_pool.reshape(planes * nb, bt, dI))
    return out.reshape(S, blocks * pages * bt)[:, :mb * bt]
