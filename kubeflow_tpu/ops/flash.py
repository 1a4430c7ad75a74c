"""Pallas TPU flash attention — forward AND backward, with online softmax.

Blockwise attention computed entirely in VMEM: for each query block the
forward kernel streams key/value blocks through the MXU, maintaining the
running max / normalizer / weighted-value accumulator of the online-softmax
recurrence.  The [s, s] score matrix never exists in HBM — memory is O(s)
— and every matmul is a [BQ, d] x [d, BK] or [BQ, BK] x [BK, d] MXU tile.

The forward additionally emits the per-row log-sum-exp (lse = m + log l),
which is what makes a blockwise backward possible: given (o, lse) the
attention probabilities of any block can be recomputed exactly as
``p = exp(q k^T * scale - lse)`` without a second online pass.  Backward
runs two Pallas kernels (dq pass with k innermost; dk/dv pass with q
innermost, computed in transposed [BK, BQ] space so no in-kernel
transposes are needed) — training memory is O(s), not O(s^2).  The same
(o, lse) contract is what parallel/ring.py composes over the `sequence`
mesh axis for context parallelism.

Grid layout: (batch*heads, outer, inner) with the streamed dimension
innermost — TPU grids execute sequentially on a core, so VMEM scratch
accumulators legally carry across the innermost iterations.  Causal jobs
skip fully-masked blocks via predication (half the FLOPs back).

Off-TPU the public entrypoint falls back to ops/attention.py so the CPU
fake-slice tests stay hermetic; the kernels themselves are additionally
tested under the Pallas interpreter (tests/test_ops.py).

Heritage: the reference's attention lived inside external TF binaries
(SURVEY.md §2.2); this module is new, TPU-first capability.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kubeflow_tpu.ops.attention import dot_product_attention

NEG_INF = float(jnp.finfo(jnp.float32).min)


def _fit_block(block: int, s: int) -> int:
    """Largest usable block size <= ``block`` that divides ``s``.

    Prefers multiples of 128 (full lane tiles); falls back to gcd so any
    sequence length works rather than asserting.
    """
    b = min(block, s)
    if s % b == 0:
        return b
    for cand in range(b - b % 128, 0, -128):
        if s % cand == 0:
            return cand
    import math

    return math.gcd(s, b)


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------


def _flash_fwd_kernel(
    q_ref, k_ref, v_ref, *refs,
    scale: float, causal: bool, block_q: int, block_k: int,
    masked: bool = False,
):
    # With ``masked`` a fourth input carries the per-(batch*head) first
    # valid key position (left-padded decode prefill: pad keys must get
    # zero weight) — serving-side forward-only path.
    if masked:
        start_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
        # The whole [bh, 1] start table rides in SMEM (a (1, 1)-blocked
        # VMEM input fails the TPU lowering's 8x128-tile rule); each
        # instance reads its own row.
        row_start = start_ref[pl.program_id(0), 0]
    else:
        row_start = None
        o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = ki * block_k
    # Causal: block is live unless every (q, k) pair has k > q.
    live = (not causal) or (q_start + block_q - 1 >= k_start)
    if masked:
        # Blocks entirely before the first valid key are dead.
        live = live & (k_start + block_k - 1 >= row_start)

    @pl.when(live)
    def _compute():
        # Dots take the inputs' native (bf16) dtype — the MXU's fast path —
        # and accumulate f32 via preferred_element_type.  Casting inputs to
        # f32 first would run the MXU in its 4x-slower f32 mode.
        q = q_ref[0]                                # [BQ, d]
        k = k_ref[0]                                # [BK, d]
        v = v_ref[0]                                # [BK, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                   # [BQ, BK] f32
        if causal or masked:
            k_pos = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
        if causal:
            q_pos = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        if masked:
            s = jnp.where(k_pos >= row_start, s, NEG_INF)

        m_prev = m_scr[:, :1]                       # [BQ, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)                      # [BQ, BK] f32
        if masked:
            # A row whose every key so far is masked leaves m_new at the
            # NEG_INF sentinel; exp(s - m_new) is then exp(0) = 1 for
            # the masked entries (sentinel minus sentinel), silently
            # attending to pads.  The causal path never hits this (the
            # k=0 block always gives each live row a real max) but with
            # a key-start mask the EARLY blocks are the masked ones —
            # zero the contributions explicitly.
            p = jnp.where(s > NEG_INF / 2, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)             # [BQ, 1]
        l_new = alpha * l_scr[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == nk - 1)
    def _finish():
        m = m_scr[:, :1]
        l = l_scr[:, :1]
        # Fully-masked rows (possible only with padding) produce l == 0.
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[:] / safe).astype(o_ref.dtype)
        lse_ref[0] = jnp.where(
            l == 0.0, NEG_INF, m + jnp.log(safe)
        )                                           # [BQ, 1]


def _flash_fwd_bhsd(
    q: jax.Array, k: jax.Array, v: jax.Array,
    *, causal: bool, block_q: int, block_k: int, interpret: bool,
    kv_start: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """q: [bh, sq, d], k/v: [bh, sk, d] -> (o [bh, sq, d], lse [bh, sq]).

    kv_start ([bh, 1] int32, optional): first valid key position per
    batch*head row — keys before it get zero weight (left-padded
    prompts).  Forward-only: the backward kernels have no mask support.
    """
    bh, sq, d = q.shape
    sk = k.shape[1]
    block_q = _fit_block(block_q, sq)
    block_k = _fit_block(block_k, sk)
    scale = d ** -0.5
    grid = (bh, sq // block_q, sk // block_k)
    masked = kv_start is not None
    kernel = functools.partial(
        _flash_fwd_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, masked=masked,
    )
    # Propagate the varying-manual-axes type so the kernel is callable
    # inside shard_map (ring attention, make_sharded_flash).
    vma = jax.typeof(q).vma
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, qi, ki: (b, ki, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, qi, ki: (b, ki, 0)),
    ]
    inputs = [q, k, v]
    if masked:
        # Whole table, SMEM: per-row scalars drive block liveness, and
        # a (1, 1) VMEM block violates the TPU 8x128 tiling rule.
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        inputs.append(kv_start.astype(jnp.int32))
    o, lse = pl.pallas_call(
        kernel,
        name="flash_fwd",
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype, vma=vma),
            # lse kept as a trailing-singleton column so every kernel
            # touches it as a native 2D [BQ, 1] tile (1D<->2D reshapes
            # are the thing Mosaic does not guarantee).
            jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32, vma=vma),
        ],
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, qi, ki: (b, qi, 0)),
        ],
        scratch_shapes=[
            # m/l padded to a full 128-lane tile; column 0 is authoritative.
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
    )(*inputs)
    return o, lse[:, :, 0]


# ---------------------------------------------------------------------------
# Two-pass ("splash"-style) causal forward
#
# The single-pass causal kernel pays full BQ x BK MACs on every block
# that straddles the diagonal — at (512, 1024) on seq 2048 that is ~33%
# of all MACs on masked pairs, counted from the block shapes.  Split the
# work by mask structure instead:
#   pass A — only blocks FULLY below the diagonal, at the big
#     (block_q, block_k) tiling, zero masking code;
#   pass B — the diagonal band (everything pass A skipped), retiled at
#     a fine (block_diag, block_diag) granularity so the masked waste
#     shrinks from BQ*BK/2 per diagonal block to BDf^2/2 per fine tile.
# Each pass emits normalized (o, lse); one fused elementwise merge in
# log space (the ring-attention hop merge, parallel/ring.py _merge)
# combines them exactly.  At (512, 1024, 256) on seq 2048 the MAC count
# drops ~24%.
# ---------------------------------------------------------------------------


def _flash_fwd_full_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
    *, scale: float, block_q: int, block_k: int,
):
    """Pass A: k blocks strictly below the diagonal — no mask, ever.
    A q block whose every k block is dead still writes (o=0,
    lse=NEG_INF): the merge treats it as an empty partial."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # Full blocks for this q block: k in [0, q_start // block_k).
    live = ki < (qi * block_q) // block_k

    @pl.when(live)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_scr[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == nk - 1)
    def _finish():
        m = m_scr[:, :1]
        l = l_scr[:, :1]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[:] / safe).astype(o_ref.dtype)
        lse_ref[0] = jnp.where(l == 0.0, NEG_INF, m + jnp.log(safe))


def _flash_fwd_diag_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
    *, scale: float, block_q: int, block_k: int, block_diag: int,
):
    """Pass B: the diagonal band pass A skipped, at fine tiles.

    For the fine q tile starting at qfs (inside coarse block qi), the
    band is k in [((qi*BQ) // BK) * BK, qfs + BDf); fine tiles beyond
    the causal frontier are dead.  The causal mask is applied on every
    live tile (the `where` is cheap; the MAC waste is what the fine
    tiling already shrank)."""
    qf = pl.program_id(1)
    kf = pl.program_id(2)
    nkf = pl.num_programs(2)

    @pl.when(kf == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    qfs = qf * block_diag
    boundary = ((qfs // block_q) * block_q) // block_k * block_k
    k_start = boundary + kf * block_diag
    live = k_start <= qfs + block_diag - 1

    @pl.when(live)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        q_pos = qfs + jax.lax.broadcasted_iota(
            jnp.int32, (block_diag, block_diag), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_diag, block_diag), 1)
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        # The first band tile of row 0 is the row's own diagonal tile,
        # so every live row sees a real max here (k_pos == q_pos is
        # always in range) — no sentinel-minus-sentinel hazard.
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_scr[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(kf == nkf - 1)
    def _finish():
        m = m_scr[:, :1]
        l = l_scr[:, :1]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[:] / safe).astype(o_ref.dtype)
        lse_ref[0] = jnp.where(l == 0.0, NEG_INF, m + jnp.log(safe))


def merge_partials(o_a, lse_a, o_b, lse_b):
    """Exact log-space merge of two normalized attention partials.

    o_*: [..., d]; lse_*: o.shape[:-1] (an empty partial carries
    lse = NEG_INF, o = 0).  The ONE copy of the sentinel-guarded
    online-softmax merge — the two-pass forward uses it directly and
    ring attention's hop merge (parallel/ring.py _merge) wraps it with
    its own lse layout; a numerics change here serves both."""
    m = jnp.maximum(lse_a, lse_b)
    safe_m = jnp.where(m > NEG_INF / 2, m, 0.0)
    wa = jnp.where(lse_a > NEG_INF / 2, jnp.exp(lse_a - safe_m), 0.0)
    wb = jnp.where(lse_b > NEG_INF / 2, jnp.exp(lse_b - safe_m), 0.0)
    l = wa + wb
    safe_l = jnp.maximum(l, 1e-37)
    o = (o_a.astype(jnp.float32) * (wa / safe_l)[..., None]
         + o_b.astype(jnp.float32) * (wb / safe_l)[..., None])
    lse = jnp.where(l > 0.0, safe_m + jnp.log(safe_l), NEG_INF)
    return o.astype(o_a.dtype), lse


def _flash_fwd_two_pass(
    q: jax.Array, k: jax.Array, v: jax.Array,
    *, block_q: int, block_k: int, block_diag: int, interpret: bool,
) -> Tuple[jax.Array, jax.Array]:
    """Causal self-attention forward via full-block + diagonal-band
    passes.  Requires sq == sk (training self-attention)."""
    import math

    bh, sq, d = q.shape
    block_q = _fit_block(block_q, sq)
    block_k = _fit_block(block_k, sq)
    # The band arithmetic (boundary // block_diag, nband) needs the
    # fine tile to divide both coarse blocks: largest divisor of their
    # gcd <= the request (which then divides sq too, via block_q).
    g = math.gcd(block_q, block_k)
    block_diag = next(c for c in range(min(block_diag, g), 0, -1)
                      if g % c == 0)
    scale = d ** -0.5
    vma = jax.typeof(q).vma
    nq, nk = sq // block_q, sq // block_k
    # Widest band, in fine tiles: the k span [boundary, qfs + BDf) is
    # at most (block_q - block_diag) + block_k wide plus the fine tile
    # itself (boundary snaps down by up to BK - 1 relative to the
    # coarse q start).
    nband = min((block_q + block_k) // block_diag, sq // block_diag)
    out_shape = [
        jax.ShapeDtypeStruct((bh, sq, d), q.dtype, vma=vma),
        jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32, vma=vma),
    ]

    n_full_max = ((nq - 1) * block_q) // block_k
    if n_full_max > 0:
        o_a, lse_a = pl.pallas_call(
            functools.partial(
                _flash_fwd_full_kernel, scale=scale,
                block_q=block_q, block_k=block_k,
            ),
            name="flash_fwd_full",
            out_shape=out_shape,
            grid=(bh, nq, n_full_max),
            in_specs=[
                pl.BlockSpec((1, block_q, d),
                             lambda b, qi, ki: (b, qi, 0)),
                # Dead iterations (ki >= this q block's full count)
                # clamp their fetch to the last live block — the DMA
                # then re-reads a hot block instead of streaming a
                # k/v block the kernel will ignore.
                pl.BlockSpec(
                    (1, block_k, d),
                    lambda b, qi, ki: (
                        b,
                        jnp.minimum(
                            ki,
                            jnp.maximum(
                                (qi * block_q) // block_k - 1, 0)),
                        0)),
                pl.BlockSpec(
                    (1, block_k, d),
                    lambda b, qi, ki: (
                        b,
                        jnp.minimum(
                            ki,
                            jnp.maximum(
                                (qi * block_q) // block_k - 1, 0)),
                        0)),
            ],
            out_specs=[
                pl.BlockSpec((1, block_q, d),
                             lambda b, qi, ki: (b, qi, 0)),
                pl.BlockSpec((1, block_q, 1),
                             lambda b, qi, ki: (b, qi, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, 128), jnp.float32),
                pltpu.VMEM((block_q, 128), jnp.float32),
                pltpu.VMEM((block_q, d), jnp.float32),
            ],
            interpret=interpret,
        )(q, k, v)
    else:
        o_a = lse_a = None

    def _band_k_index(b, qf, kf):
        qfs = qf * block_diag
        boundary = ((qfs // block_q) * block_q) // block_k * block_k
        idx = boundary // block_diag + kf
        # Dead band tiles (beyond the causal frontier) re-fetch the
        # frontier tile; also keeps the index in range.
        return (b, jnp.minimum(idx, qfs // block_diag), 0)

    o_b, lse_b = pl.pallas_call(
        functools.partial(
            _flash_fwd_diag_kernel, scale=scale, block_q=block_q,
            block_k=block_k, block_diag=block_diag,
        ),
        name="flash_fwd_diag",
        out_shape=out_shape,
        grid=(bh, sq // block_diag, nband),
        in_specs=[
            pl.BlockSpec((1, block_diag, d),
                         lambda b, qf, kf: (b, qf, 0)),
            pl.BlockSpec((1, block_diag, d), _band_k_index),
            pl.BlockSpec((1, block_diag, d), _band_k_index),
        ],
        out_specs=[
            pl.BlockSpec((1, block_diag, d),
                         lambda b, qf, kf: (b, qf, 0)),
            pl.BlockSpec((1, block_diag, 1),
                         lambda b, qf, kf: (b, qf, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_diag, 128), jnp.float32),
            pltpu.VMEM((block_diag, 128), jnp.float32),
            pltpu.VMEM((block_diag, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)

    if o_a is None:
        return o_b, lse_b[:, :, 0]
    o, lse = merge_partials(
        o_a, lse_a[:, :, 0], o_b, lse_b[:, :, 0])
    return o, lse


# ---------------------------------------------------------------------------
# Backward kernels
#
# dq pass: grid (bh, q_blocks, k_blocks), k innermost, accumulates dq.
# dkv pass: grid (bh, k_blocks, q_blocks), q innermost, accumulates dk/dv
#   entirely in transposed [BK, BQ] space (kq^T instead of qk^T) so the
#   kernel contains zero transposes.
# ---------------------------------------------------------------------------


def _flash_dq_kernel(
    q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, dq_ref, dq_scr,
    *, scale: float, causal: bool, block_q: int, block_k: int,
):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    q_start = pl.program_id(1) * block_q
    k_start = ki * block_k
    live = (not causal) or (q_start + block_q - 1 >= k_start)

    @pl.when(live)
    def _compute():
        q = q_ref[0]                                # [BQ, d] bf16
        k = k_ref[0]                                # [BK, d]
        v = v_ref[0]                                # [BK, d]
        g = g_ref[0]                                # [BQ, d]
        lse = lse_ref[0]                            # [BQ, 1] f32
        delta = delta_ref[0]                        # [BQ, 1] f32
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                   # [BQ, BK] f32
        if causal:
            q_pos = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        finite = lse > NEG_INF / 2                  # [BQ, 1]
        p = jnp.where(
            finite, jnp.exp(s - jnp.where(finite, lse, 0.0)), 0.0
        )                                           # [BQ, BK] f32
        dp = jax.lax.dot_general(
            g, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                           # [BQ, BK] f32
        ds = (p * (dp - delta) * scale).astype(k.dtype)  # [BQ, BK]
        dq_scr[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _flash_dkv_kernel(
    q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_scr, dv_scr,
    *, scale: float, causal: bool, block_q: int, block_k: int,
):
    qi = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    k_start = pl.program_id(1) * block_k
    q_start = qi * block_q
    live = (not causal) or (q_start + block_q - 1 >= k_start)

    @pl.when(live)
    def _compute():
        q = q_ref[0]                                # [BQ, d] bf16
        k = k_ref[0]                                # [BK, d]
        v = v_ref[0]                                # [BK, d]
        g = g_ref[0]                                # [BQ, d]
        lse_row = lse_ref[0]                        # [1, BQ] f32
        delta_row = delta_ref[0]                    # [1, BQ] f32
        s_t = jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                   # [BK, BQ] f32
        if causal:
            k_pos = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 0)
            q_pos = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 1)
            s_t = jnp.where(q_pos >= k_pos, s_t, NEG_INF)
        finite = lse_row > NEG_INF / 2              # [1, BQ]
        p_t = jnp.where(
            finite, jnp.exp(s_t - jnp.where(finite, lse_row, 0.0)), 0.0
        )                                           # [BK, BQ] f32
        dv_scr[:] += jax.lax.dot_general(
            p_t.astype(g.dtype), g, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                           # [BK, d]
        dp_t = jax.lax.dot_general(
            v, g, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                           # [BK, BQ] f32
        ds_t = (p_t * (dp_t - delta_row) * scale).astype(q.dtype)
        dk_scr[:] += jax.lax.dot_general(
            ds_t, q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                           # [BK, d]

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _flash_bwd_bhsd(
    q: jax.Array, k: jax.Array, v: jax.Array,
    g: jax.Array, lse: jax.Array, delta: jax.Array,
    *, causal: bool, block_q: int, block_k: int, interpret: bool,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Blockwise backward.  q/g: [bh, sq, d]; k/v: [bh, sk, d];
    lse/delta: [bh, sq] -> (dq, dk, dv)."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    block_q = _fit_block(block_q, sq)
    block_k = _fit_block(block_k, sk)
    scale = d ** -0.5
    lse_col = lse[:, :, None]                       # [bh, sq, 1]
    delta_col = delta[:, :, None]
    lse_row = lse[:, None, :]                       # [bh, 1, sq]
    delta_row = delta[:, None, :]

    vma = jax.typeof(q).vma
    dq = pl.pallas_call(
        functools.partial(
            _flash_dq_kernel, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k,
        ),
        name="flash_bwd_dq",
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype, vma=vma),
        grid=(bh, sq // block_q, sk // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, qi, ki: (b, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(q, k, v, g, lse_col, delta_col)

    dk, dv = pl.pallas_call(
        functools.partial(
            _flash_dkv_kernel, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k,
        ),
        name="flash_bwd_dkv",
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk, d), k.dtype, vma=vma),
            jax.ShapeDtypeStruct((bh, sk, d), v.dtype, vma=vma),
        ],
        grid=(bh, sk // block_k, sq // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, ki, qi: (b, qi, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, ki, qi: (b, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, ki, qi: (b, ki, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, ki, qi: (b, qi, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, ki, qi: (b, 0, qi)),
            pl.BlockSpec((1, 1, block_q), lambda b, ki, qi: (b, 0, qi)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, ki, qi: (b, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, ki, qi: (b, ki, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, g, lse_row, delta_row)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Differentiable entrypoint ([bh, s, d] layout)
# ---------------------------------------------------------------------------


def _fwd_dispatch(q, k, v, causal, block_q, block_k, interpret,
                  block_diag):
    """Single-pass vs two-pass forward.  Two-pass needs: a request
    (block_diag > 0), a causal self-attention shape (sq == sk), and a
    sequence long enough for full blocks to exist at all."""
    if (block_diag and causal and q.shape[1] == k.shape[1]
            and q.shape[1] > block_k):
        return _flash_fwd_two_pass(
            q, k, v, block_q=block_q, block_k=block_k,
            block_diag=block_diag, interpret=interpret,
        )
    return _flash_fwd_bhsd(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret,
    )


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7)
)
def _flash(q, k, v, causal, block_q, block_k, interpret, block_diag):
    o, _ = _fwd_dispatch(
        q, k, v, causal, block_q, block_k, interpret, block_diag)
    return o


def _flash_vjp_fwd(q, k, v, causal, block_q, block_k, interpret,
                   block_diag):
    o, lse = _fwd_dispatch(
        q, k, v, causal, block_q, block_k, interpret, block_diag)
    # Under jax.checkpoint this fwd rule IS the primal pass, and (o, lse)
    # are the residuals the backward kernels need.  dots_saveable-style
    # policies never match a Pallas custom call, so without these tags a
    # rematted block re-runs the whole forward kernel in the backward
    # just to rebuild them (measured +1 full fwd pass per step on v5e).
    # Naming them lets the model compose save_only_these_names into its
    # policy and keep the residuals instead.
    o = checkpoint_name(o, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return o, (q, k, v, o, lse)


def _flash_vjp_bwd(causal, block_q, block_k, interpret, block_diag,
                   res, g):
    # The merged lse IS the true full-softmax lse, so the backward
    # kernels are identical for both forward schedules.
    del block_diag
    q, k, v, o, lse = res
    delta = jnp.sum(
        g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1
    )                                               # [bh, sq]
    return _flash_bwd_bhsd(
        q, k, v, g, lse, delta,
        causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret,
    )


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


# ---------------------------------------------------------------------------
# Public [b, s, h, d] API + building blocks for ring attention
# ---------------------------------------------------------------------------


def _to_bhsd(x: jax.Array) -> jax.Array:
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _from_bhsd(x: jax.Array, b: int, h: int) -> jax.Array:
    bh, s, d = x.shape
    return x.reshape(b, h, s, d).transpose(0, 2, 1, 3)


def repeat_kv(k: jax.Array, v: jax.Array, h: int):
    """Broadcast kv heads up to the query head count (GQA). Shared by the
    plain flash path and ring attention's per-hop kernel calls."""
    hkv = k.shape[2]
    if hkv != h:
        k = jnp.repeat(k, h // hkv, axis=2)
        v = jnp.repeat(v, h // hkv, axis=2)
    return k, v


def flash_fwd_with_lse(
    q: jax.Array, k: jax.Array, v: jax.Array,
    *, causal: bool, block_q: int = 512, block_k: int = 512,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Non-differentiable forward returning (o [b,s,h,d], lse [b,h,s]).

    The (o, lse) pair is the composable unit of blockwise attention: ring
    attention merges per-hop pairs in log-space (parallel/ring.py) and the
    backward recomputes probabilities from lse.
    """
    b, sq, h, d = q.shape
    k, v = repeat_kv(k, v, h)
    o, lse = _flash_fwd_bhsd(
        _to_bhsd(q), _to_bhsd(k), _to_bhsd(v),
        causal=causal, block_q=block_q, block_k=block_k, interpret=interpret,
    )
    return _from_bhsd(o, b, h), lse.reshape(b, h, sq)


def flash_bwd_block(
    q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
    lse: jax.Array, delta: jax.Array,
    *, causal: bool, block_q: int = 512, block_k: int = 512,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Blockwise backward in [b,s,h,d] layout; lse/delta are [b,h,s].

    GQA note: callers pass kv already repeated to q's head count and fold
    the head-group sum themselves (ring does; see parallel/ring.py).
    """
    b, sq, h, d = q.shape
    dq, dk, dv = _flash_bwd_bhsd(
        _to_bhsd(q), _to_bhsd(k), _to_bhsd(v), _to_bhsd(g),
        lse.reshape(b * h, sq), delta.reshape(b * h, sq),
        causal=causal, block_q=block_q, block_k=block_k, interpret=interpret,
    )
    return (
        _from_bhsd(dq, b, h), _from_bhsd(dk, b, h), _from_bhsd(dv, b, h)
    )


def make_sharded_flash(
    mesh,
    *,
    causal: bool = True,
    block_q: int = 512,
    block_k: int = 512,
    block_diag: int = 0,
):
    """shard_map wrapper: flash per shard, batch over (data, fsdp), heads
    over tensor, sequence resident (use ring attention for sequence
    sharding).  Pallas kernels don't auto-partition under jit, so any
    sharded caller must come through here."""
    from jax.sharding import PartitionSpec

    from kubeflow_tpu.parallel.mesh import DATA, FSDP, TENSOR

    spec = PartitionSpec((DATA, FSDP), None, TENSOR, None)

    @functools.partial(
        jax.shard_map, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec
    )
    def fn(q, k, v):
        return flash_attention(
            q, k, v, causal=causal, block_q=block_q, block_k=block_k,
            block_diag=block_diag,
        )

    return fn


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    segment_ids: Optional[jax.Array] = None,
    block_q: int = 512,
    block_k: int = 512,
    block_diag: int = 0,
    interpret: bool = False,
    kv_valid_start: Optional[jax.Array] = None,
) -> jax.Array:
    """Flash attention with the ops/attention.py [b, s, h, d] signature.

    Differentiable end-to-end through the Pallas forward AND backward
    kernels — long-context training memory is O(s).  GQA is handled by
    repeating kv heads before the kernel (the cotangent sum over the head
    group is what jnp.repeat's autodiff gives back).  Segment masking is
    not yet in the kernel: segmented calls take the XLA path.  Anywhere
    but on a TPU the kernel cannot run: the call raises, naming the
    backend, unless ``interpret=True`` asks for the Pallas interpreter.

    block_diag > 0 selects the two-pass causal forward: full blocks at
    (block_q, block_k) with no masking, the diagonal band at
    (block_diag, block_diag) fine tiles, merged in log space — cuts the
    masked-MAC waste of diagonal-straddling blocks (backward unchanged;
    the merged lse is exact).  0 = classic single pass.

    kv_valid_start ([b] int32, optional): per-row first valid key —
    keys before it get zero weight (left-padded bucketed decode
    prefill, models/generate.py).  FORWARD-ONLY: this path bypasses the
    custom-vjp kernels (inference has no cotangents; differentiating it
    raises).
    """
    if segment_ids is not None:
        return dot_product_attention(
            q, k, v, causal=causal, segment_ids=segment_ids,
            kv_valid_start=kv_valid_start,
        )
    backend = jax.default_backend()
    if backend != "tpu" and not interpret:
        # The caller asked for the kernel by name.  Answering with the
        # XLA path would let a job that landed on the wrong hardware
        # train, print a loss and exit 0.
        raise RuntimeError(
            f"flash_attention needs a TPU backend; this process runs on "
            f"{backend!r}.  Use attention='dot' there, or pass "
            f"interpret=True to run the kernel in the Pallas interpreter")
    b, sq, h, d = q.shape
    k, v = repeat_kv(k, v, h)
    if kv_valid_start is not None:
        start = jnp.repeat(
            kv_valid_start.astype(jnp.int32), h)[:, None]  # [b*h, 1]
        out, _ = _flash_fwd_bhsd(
            _to_bhsd(q), _to_bhsd(k), _to_bhsd(v),
            causal=causal, block_q=block_q, block_k=block_k,
            interpret=interpret, kv_start=start,
        )
        return _from_bhsd(out, b, h)
    out = _flash(
        _to_bhsd(q), _to_bhsd(k), _to_bhsd(v),
        causal, block_q, block_k, interpret, block_diag,
    )
    return _from_bhsd(out, b, h)
