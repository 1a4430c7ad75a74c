"""The grouped product of an expert layer, weight-stationary (Pallas TPU).

An expert layer of the serving engine (``models/generate.py``
``_experts``) sorts its (row, chosen expert) pairs by expert and runs
every held expert's SwiGLU as two grouped products: ``rows [p, K]``
against ``weights [G, K, N]`` by ``sizes [G]``, where rows
``[sum(sizes[:g]), sum(sizes[:g + 1]))`` meet matrix ``g``.  The rows are
small beside the matrices (a decode step of 64 verified rows x 8 choices:
7 MB of rows against 939 MB of matrices), so the weights are what the
product has to move, and the chip allows no less than a touched expert's
matrix once at HBM's rate.

What is read once.  The grid is ``(column tiles, visits)``.  A visit is a
(group, row tile) pair: a group is visited once for every tile of ``tm``
rows that holds one of its rows, in order, so a tile that several groups
share is visited once a group and a group that spans tiles once a tile.
The weight block of a visit is the WHOLE contraction of one matrix by
``tn`` columns, ``[K, tn]`` of ``weights[g]`` where it lies (a block over
the array's own layout: nothing is transposed, padded or copied), and
its index is ``(group, 0, column tile)``: consecutive visits of one group
keep the index and fetch nothing, so each touched expert's ``[K, tn]`` is
brought from HBM once a call, while the block before it is multiplied,
whatever its group's rows span.  A group with ``sizes == 0`` has no visit
and is never read.  The visits (``_visits``: the group and the row tile of
each, and how many there are) are computed from ``sizes`` beside the
kernel and ride in by scalar prefetch; the grid's second extent is their
NUMBER, so nothing is visited past the last group.  The rows ARE read
again, once a column tile (and a tile's ``[tm, K]`` only when the tile
changes): N / tn times a few megabytes.

Contract.  ``sizes`` is int32 and sums to at most ``p``.  Rows at and past
``sum(sizes)`` are in no group: their OUTPUT IS NOT WRITTEN (whatever the
buffer held, NaN in the interpreter) and the caller masks them with a
select, never with a product (``_experts`` does: ``jnp.where``).  Inside
a shared tile a visit stores only its own group's rows.  With every group
empty the kernel makes ONE visit (the last group's matrix, a block a column
tile: the one case in which an untouched expert is read) that stores no
row, and no row of the output is written.

Arithmetic: the operands as they are (bf16 on the chip), one product over
the whole contraction with float32 accumulation, rounded once to the
operands' dtype: what ``jax.lax.ragged_dot`` computes, row for row.

Block sizes follow from the shapes (``_tiles``): ``tm`` rows of 128 (all
of ``p`` below that: with a few rows a group the matrix unit's time is
the weights' passage through it, the same for 8 rows as for 128), ``tn``
the most columns, in whole 128-lane tiles that divide N, whose ``[K, tn]``
block stays under ``_WEIGHT_BLOCK_BYTES`` (two of them are in the fast
memory at once).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_ROW_TILE = 128
# One [K, tn] block of a matrix; the pipeline holds two.
_WEIGHT_BLOCK_BYTES = 8 << 20


def supports(k: int, n: int) -> bool:
    """Whether ``[G, k, n]`` matrices can be read in blocks of whole
    128-lane tiles (what the chip's compiler takes; the interpreter takes
    any)."""
    return k % _LANES == 0 and n % _LANES == 0


def _tiles(p: int, k: int, n: int, itemsize: int) -> tuple[int, int]:
    """(tm, tn) from the shapes: see the module docstring."""
    tm = min(p, _ROW_TILE)
    if n % _LANES:
        return tm, n
    lanes = n // _LANES
    fits = [c for c in range(1, lanes + 1) if lanes % c == 0
            and k * c * _LANES * itemsize <= _WEIGHT_BLOCK_BYTES]
    return tm, _LANES * max(fits, default=1)


def _visits(sizes, p: int, tm: int):
    """The visits of ``sizes`` over row tiles of ``tm``, in order: (group
    [V], row tile [V], starts [G], ends [G], how many visits to make);
    V = tiles + G - 1 is the most there can be.  Running sums and
    lookups are masked sums over [G, G] and [V, G], which the compiler
    fuses into a handful of operations beside the kernel (a scan or a
    gather is several of its own, a microsecond or two each)."""
    groups, tiles = sizes.shape[0], pl.cdiv(p, tm)
    g = jnp.arange(groups, dtype=jnp.int32)
    upto_g = g[:, None] <= g[None, :]

    def running(x):
        return jnp.sum(jnp.where(upto_g, x[:, None], 0), axis=0)

    ends = running(sizes)
    starts = ends - sizes
    first = starts // tm
    spans = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    upto = running(spans)
    visit = jnp.arange(tiles + groups - 1, dtype=jnp.int32)
    # The group whose visits hold visit v: as many groups end at or
    # before it.  Past the last visit: clamped, and never visited.
    group = jnp.minimum(
        jnp.sum(visit[:, None] >= upto[None, :], axis=1), groups - 1)
    # Its row tile: the group's first, and one more each visit since.
    tile = visit + jnp.sum(jnp.where(
        group[:, None] == g[None, :], (first + spans - upto)[None, :], 0),
        axis=1)
    # At least one: a grid without a step is not a form the chip's
    # compiler is known to take.  With every group empty that visit
    # meets the last group's matrix and stores no row.
    return (group.astype(jnp.int32),
            jnp.clip(tile, 0, tiles - 1).astype(jnp.int32),
            starts, ends, jnp.maximum(upto[-1], 1))


def _kernel(group_ref, tile_ref, start_ref, end_ref, rows_ref, w_ref,
            out_ref, *, tm):
    visit = pl.program_id(1)
    group = group_ref[visit]
    row = tile_ref[visit] * tm + jax.lax.broadcasted_iota(
        jnp.int32, (tm, 1), 0)
    mine = (row >= start_ref[group]) & (row < end_ref[group])
    acc = jnp.dot(rows_ref[...], w_ref[...],
                  preferred_element_type=jnp.float32)
    out_ref[...] = jnp.where(mine, acc.astype(out_ref.dtype), out_ref[...])


@functools.partial(jax.jit, static_argnames=("row_tile", "column_tile",
                                             "interpret"))
def grouped_matmul(rows, weights, sizes, *, row_tile: int | None = None,
                   column_tile: int | None = None, interpret: bool = False):
    """``rows [p, K]`` x ``weights [G, K, N]`` by ``sizes [G]`` -> ``[p, N]``
    in the rows' dtype: row r of group g is ``rows[r] @ weights[g]``; rows
    in no group are NOT written (module docstring).  ``weights`` stays in
    HBM as it lies.  row_tile / column_tile: None takes them from the
    shapes (``_tiles``)."""
    p, k = rows.shape
    groups, _, n = weights.shape
    tm, tn = _tiles(p, k, n, weights.dtype.itemsize)
    tm, tn = row_tile or tm, column_tile or tn
    group, tile, starts, ends, visits = _visits(
        sizes.astype(jnp.int32), p, tm)
    item = rows.dtype.itemsize
    blocks = 2 * (k * tn * weights.dtype.itemsize + tm * k * item
                  + tm * tn * item) + tm * tn * 4
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm),
        name="grouped_matmul",
        out_shape=jax.ShapeDtypeStruct((p, n), rows.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(pl.cdiv(n, tn), visits),
            in_specs=[
                pl.BlockSpec((tm, k), lambda j, v, g, t, *_: (t[v], 0)),
                pl.BlockSpec((None, k, tn),
                             lambda j, v, g, t, *_: (g[v], 0, j)),
            ],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda j, v, g, t, *_: (t[v], j)),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=blocks + (8 << 20)),
        cost_estimate=pl.CostEstimate(
            flops=2 * p * k * n, transcendentals=0,
            bytes_accessed=(groups * k * n * weights.dtype.itemsize
                            + pl.cdiv(n, tn) * p * k * item + p * n * item)),
        interpret=interpret,
    )(group, tile, starts, ends, rows, weights)
