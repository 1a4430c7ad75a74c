"""Ring attention: context parallelism over the `sequence` mesh axis.

The capability SURVEY.md §5 flags as absent from the reference in any form
("no ring attention, no context/sequence parallel") — its era scaled replica
count, not sequence length.  Here long-context is first-class: the sequence
dimension of q/k/v is sharded over the `sequence` mesh axis, each device
keeps its resident query block, and key/value blocks rotate around the ring
via ``ppermute`` — on a TPU slice that permutation compiles to
neighbour-to-neighbour ICI transfers, overlapping each hop with the local
blockwise attention (the Ring Attention schedule of Liu et al. 2023,
per PAPERS.md).

Composition with the Pallas flash kernel (ops/flash.py): each hop computes
its local block with ``flash_fwd_with_lse`` — VMEM-blockwise, O(s_local)
memory — and hops merge in log-sum-exp space, which is exactly the online
softmax recurrence lifted to the ring level.  Causal hops are classified
statically-per-branch (kv strictly behind the resident queries -> unmasked
kernel; the diagonal hop -> causal kernel; kv strictly ahead -> skipped
entirely), so the causal schedule does half the FLOPs and each branch's
kernel has a static mask shape.

Backward is a custom VJP that *re-rotates* the kv ring instead of saving
per-hop residuals: dk/dv partial gradients travel around the ring with
their kv blocks and arrive home after axis_size hops.  Training memory is
therefore O(s_local) = O(s/ring) — the whole point of ring attention —
rather than the O(s) per device a scanned-and-saved forward would keep.

Numerics: partials combine with the standard log-space online-softmax
merge, so the result is exactly softmax attention — verified against the
single-device reference in tests/test_ring.py.
"""

from __future__ import annotations

import functools
import logging
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec

from kubeflow_tpu.parallel.mesh import DATA, FSDP, SEQUENCE, TENSOR

log = logging.getLogger(__name__)

NEG_INF = float(jnp.finfo(jnp.float32).min)


# ---------------------------------------------------------------------------
# Per-hop block attention: (o fp32 [b,s,h,d], lse fp32 [b,h,s])
# ---------------------------------------------------------------------------


def _xla_block_fwd(
    q: jax.Array, k: jax.Array, v: jax.Array, causal: bool
) -> Tuple[jax.Array, jax.Array]:
    """XLA fallback block (equal head counts): one (q-block, kv-block)
    attention with its log-sum-exp.  O(s_local^2) transient — used off-TPU
    where Pallas isn't available; the hermetic CPU tests run through it."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if causal:
        q_pos = jnp.arange(q.shape[1])[:, None]
        k_pos = jnp.arange(k.shape[1])[None, :]
        s = jnp.where((q_pos >= k_pos)[None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1)                            # [b, h, q]
    safe_m = jnp.where(m > NEG_INF / 2, m, 0.0)
    p = jnp.exp(s - safe_m[..., None])
    p = jnp.where((m > NEG_INF / 2)[..., None], p, 0.0)
    l = jnp.sum(p, axis=-1)                            # [b, h, q]
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    o = o / jnp.maximum(l, 1e-37).swapaxes(1, 2)[..., None]
    lse = jnp.where(l > 0.0, safe_m + jnp.log(jnp.maximum(l, 1e-37)), NEG_INF)
    return o, lse


def _xla_block_bwd(
    q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
    lse: jax.Array, delta: jax.Array, causal: bool,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """XLA fallback block backward.  lse/delta: [b, h, s]."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if causal:
        q_pos = jnp.arange(q.shape[1])[:, None]
        k_pos = jnp.arange(k.shape[1])[None, :]
        s = jnp.where((q_pos >= k_pos)[None, None], s, NEG_INF)
    finite = lse > NEG_INF / 2                         # [b, h, q]
    p = jnp.where(
        finite[..., None],
        jnp.exp(s - jnp.where(finite, lse, 0.0)[..., None]),
        0.0,
    )                                                  # [b, h, q, k]
    g32 = g.astype(jnp.float32)
    dv = jnp.einsum("bhqk,bqhd->bkhd", p, g32)
    dp = jnp.einsum("bqhd,bkhd->bhqk", g32, v.astype(jnp.float32))
    ds = p * (dp - delta[..., None]) * scale
    dq = jnp.einsum("bhqk,bkhd->bqhd", ds, k.astype(jnp.float32))
    dk = jnp.einsum("bhqk,bqhd->bkhd", ds, q.astype(jnp.float32))
    return dq, dk, dv


@functools.lru_cache(maxsize=None)
def _flash_by_backend(backend: str) -> bool:
    """The block kernel when the caller named none: the Pallas kernel on
    a TPU, the XLA block elsewhere.  Cached per backend so the choice is
    logged once, not per traced block."""
    use = backend == "tpu"
    log.info("ring attention block kernel on backend %r: %s", backend,
             "Pallas flash" if use else "XLA einsum")
    return use


def _use_flash(use_flash: Optional[bool]) -> bool:
    if use_flash is None:
        return _flash_by_backend(jax.default_backend())
    return use_flash


def _block_fwd(q, k, v, causal, use_flash, block_q, block_k, interpret):
    if _use_flash(use_flash) or interpret:
        from kubeflow_tpu.ops.flash import flash_fwd_with_lse

        o, lse = flash_fwd_with_lse(
            q, k, v, causal=causal, block_q=block_q, block_k=block_k,
            interpret=interpret,
        )
        return o.astype(jnp.float32), lse
    return _xla_block_fwd(q, k, v, causal)


def _block_bwd(q, k, v, g, lse, delta, causal, use_flash, block_q, block_k,
               interpret):
    if _use_flash(use_flash) or interpret:
        from kubeflow_tpu.ops.flash import flash_bwd_block

        return flash_bwd_block(
            q, k, v, g, lse, delta, causal=causal,
            block_q=block_q, block_k=block_k, interpret=interpret,
        )
    return _xla_block_bwd(q, k, v, g, lse, delta, causal)


# ---------------------------------------------------------------------------
# Ring schedule (runs inside shard_map)
# ---------------------------------------------------------------------------


def _merge(o_acc, lse_acc, o_p, lse_p):
    """Log-space online-softmax merge of two normalized partials:
    o [b, s, h, d] with lse [b, h, s].  The sentinel/floor numerics
    live in ONE place — ops/flash.py merge_partials (shared with the
    two-pass forward); this wrapper only adapts the ring's lse layout
    (head-major) to the o-aligned layout the core expects."""
    from kubeflow_tpu.ops.flash import merge_partials

    o_new, lse_aligned = merge_partials(
        o_acc, lse_acc.swapaxes(1, 2), o_p, lse_p.swapaxes(1, 2))
    return o_new, lse_aligned.swapaxes(1, 2)


def _fold_heads(dk, hkv):
    """Transpose of jnp.repeat(axis=2): sum gradient over each head group."""
    b, s, h, d = dk.shape
    if h == hkv:
        return dk
    return dk.reshape(b, s, hkv, h // hkv, d).sum(axis=3)


def _vary_like(x, ref):
    """Give constant x ref's varying-manual-axes type (shard_map requires
    loop carries / switch branches to agree on vma)."""
    return jax.lax.pcast(x, tuple(jax.typeof(ref).vma), to="varying")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _ring(q, k, v, axis_name, causal, use_flash, block_q, block_k, interpret):
    o, _ = _ring_fwd_impl(
        q, k, v, axis_name, causal, use_flash, block_q, block_k, interpret
    )
    return o.astype(q.dtype)


def _ring_fwd_impl(q, k, v, axis_name, causal, use_flash, block_q, block_k,
                   interpret):
    axis_size = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    b, s, h, d = q.shape
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]
    block = functools.partial(
        _block_fwd, use_flash=use_flash, block_q=block_q, block_k=block_k,
        interpret=interpret,
    )

    # GQA kv-head broadcast happens INSIDE each live branch, so skipped
    # hops (and the rotation itself) never materialize the repeated heads.
    from kubeflow_tpu.ops.flash import repeat_kv

    def hop_partial(step_src, k_cur, v_cur):
        if not causal:
            return block(q, *repeat_kv(k_cur, v_cur, h), causal=False)

        def skip(k_cur, v_cur):
            return (
                _vary_like(jnp.zeros((b, s, h, d), jnp.float32), q),
                _vary_like(jnp.full((b, h, s), NEG_INF, jnp.float32), q),
            )

        def full(k_cur, v_cur):
            return block(q, *repeat_kv(k_cur, v_cur, h), causal=False)

        def diag(k_cur, v_cur):
            return block(q, *repeat_kv(k_cur, v_cur, h), causal=True)

        # src > my_idx: kv strictly ahead of every resident query -> dead.
        case = jnp.where(
            step_src == my_idx, 2, jnp.where(step_src < my_idx, 1, 0)
        )
        return jax.lax.switch(case, [skip, full, diag], k_cur, v_cur)

    def body(step, carry):
        o_acc, lse_acc, k_cur, v_cur = carry
        src = (my_idx - step) % axis_size          # whose kv block we hold
        o_p, lse_p = hop_partial(src, k_cur, v_cur)
        # Rotate kv to the next device; overlapped with the merge math.
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        o_new, lse_new = _merge(o_acc, lse_acc, o_p, lse_p)
        return o_new, lse_new, k_nxt, v_nxt

    o0 = _vary_like(jnp.zeros((b, s, h, d), jnp.float32), q)
    lse0 = _vary_like(jnp.full((b, h, s), NEG_INF, jnp.float32), q)
    o, lse, _, _ = jax.lax.fori_loop(0, axis_size, body, (o0, lse0, k, v))
    return o, lse


def _ring_vjp_fwd(q, k, v, axis_name, causal, use_flash, block_q, block_k,
                  interpret):
    o, lse = _ring_fwd_impl(
        q, k, v, axis_name, causal, use_flash, block_q, block_k, interpret
    )
    return o.astype(q.dtype), (q, k, v, o.astype(q.dtype), lse)


def _ring_vjp_bwd(axis_name, causal, use_flash, block_q, block_k, interpret,
                  res, g):
    q, k, v, o, lse = res
    axis_size = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    b, s, h, d = q.shape
    hkv = k.shape[2]
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]
    delta = jnp.sum(
        g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1
    ).swapaxes(1, 2)                                   # [b, h, s]
    block = functools.partial(
        _block_bwd, use_flash=use_flash, block_q=block_q, block_k=block_k,
        interpret=interpret,
    )

    from kubeflow_tpu.ops.flash import repeat_kv

    def hop_grads(step_src, k_cur, v_cur):
        def run(k_cur, v_cur, causal_block):
            kr, vr = repeat_kv(k_cur, v_cur, h)
            dq, dk, dv = block(q, kr, vr, g, lse, delta,
                               causal=causal_block)
            return (dq.astype(jnp.float32), dk.astype(jnp.float32),
                    dv.astype(jnp.float32))

        def zeros(k_cur, v_cur):
            z = _vary_like(jnp.zeros((b, s, h, d), jnp.float32), q)
            return z, z, z

        def full(k_cur, v_cur):
            return run(k_cur, v_cur, False)

        def diag(k_cur, v_cur):
            return run(k_cur, v_cur, True)

        if not causal:
            return full(k_cur, v_cur)
        case = jnp.where(
            step_src == my_idx, 2, jnp.where(step_src < my_idx, 1, 0)
        )
        return jax.lax.switch(case, [zeros, full, diag], k_cur, v_cur)

    def body(step, carry):
        dq_acc, dk_rot, dv_rot, k_cur, v_cur = carry
        src = (my_idx - step) % axis_size
        dq_p, dk_p, dv_p = hop_grads(src, k_cur, v_cur)
        dq_acc = dq_acc + dq_p
        # dk/dv partials travel WITH their kv block: after axis_size
        # rotations both the block and its accumulated gradient are home.
        dk_rot = dk_rot + _fold_heads(dk_p, hkv)
        dv_rot = dv_rot + _fold_heads(dv_p, hkv)
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        dk_nxt = jax.lax.ppermute(dk_rot, axis_name, perm)
        dv_nxt = jax.lax.ppermute(dv_rot, axis_name, perm)
        return dq_acc, dk_nxt, dv_nxt, k_nxt, v_nxt

    dq0 = _vary_like(jnp.zeros((b, s, h, d), jnp.float32), q)
    dkv0 = _vary_like(jnp.zeros((b, s, hkv, d), jnp.float32), q)
    dq, dk, dv, _, _ = jax.lax.fori_loop(
        0, axis_size, body, (dq0, dkv0, dkv0, k, v)
    )
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_ring.defvjp(_ring_vjp_fwd, _ring_vjp_bwd)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str = SEQUENCE,
    causal: bool = True,
    use_flash: Optional[bool] = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Per-shard ring attention body — call inside shard_map.

    q/k/v: the local sequence shard [b, s_local, h_local, d]; GQA welcome
    (kv heads rotate unrepeated — less ICI traffic — and are broadcast to
    the query head count only inside each hop's kernel call).  Requires
    the global sequence be evenly sharded over ``axis_name``.

    use_flash: None = auto (Pallas kernel on TPU, XLA block off-TPU).
    """
    return _ring(
        q, k, v, axis_name, causal, use_flash, block_q, block_k, interpret
    )


def make_ring_attention(
    mesh: Mesh,
    *,
    causal: bool = True,
    axis_name: str = SEQUENCE,
    use_flash: Optional[bool] = None,
    block_q: int = 512,
    block_k: int = 512,
) -> Callable[[jax.Array, jax.Array, jax.Array], jax.Array]:
    """shard_map-wrapped ring attention over a mesh.

    Layout contract (matches DEFAULT_RULES): batch over (data, fsdp),
    sequence over `sequence`, heads over `tensor`.
    """
    spec = PartitionSpec((DATA, FSDP), axis_name, TENSOR, None)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(spec, spec, spec), out_specs=spec,
    )
    def fn(q, k, v):
        return ring_attention(
            q, k, v, axis_name=axis_name, causal=causal,
            use_flash=use_flash, block_q=block_q, block_k=block_k,
        )

    return fn
