"""Attention op tests: XLA reference vs Pallas flash kernel (interpreter)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.ops.attention import dot_product_attention
from kubeflow_tpu.ops.flash import flash_attention


def rand_qkv(rng, b=2, s=64, h=2, hkv=None, d=16, dtype=jnp.float32):
    hkv = hkv or h
    q = jnp.asarray(rng.randn(b, s, h, d), dtype)
    k = jnp.asarray(rng.randn(b, s, hkv, d), dtype)
    v = jnp.asarray(rng.randn(b, s, hkv, d), dtype)
    return q, k, v


class TestDotProductAttention:
    def test_causal_masks_future(self):
        rng = np.random.RandomState(0)
        q, k, v = rand_qkv(rng, s=8)
        out1 = dot_product_attention(q, k, v, causal=True)
        # Perturb the last key/value: outputs at positions < 7 unchanged.
        k2 = k.at[:, -1].set(0.0)
        v2 = v.at[:, -1].set(0.0)
        out2 = dot_product_attention(q, k2, v2, causal=True)
        np.testing.assert_allclose(
            np.asarray(out1[:, :-1]), np.asarray(out2[:, :-1]), atol=1e-6
        )

    def test_matches_manual_softmax(self):
        rng = np.random.RandomState(1)
        q, k, v = rand_qkv(rng, b=1, s=4, h=1, d=8)
        out = dot_product_attention(q, k, v, causal=False)
        scores = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(8)
        w = jax.nn.softmax(jnp.asarray(scores), axis=-1)
        ref = np.einsum("bhqk,bkhd->bqhd", np.asarray(w), v)
        np.testing.assert_allclose(np.asarray(out), ref, atol=1e-5)

    def test_gqa_equals_repeated_kv(self):
        rng = np.random.RandomState(2)
        q, k, v = rand_qkv(rng, h=4, hkv=2)
        out_gqa = dot_product_attention(q, k, v)
        out_rep = dot_product_attention(
            q, jnp.repeat(k, 2, axis=2), jnp.repeat(v, 2, axis=2)
        )
        np.testing.assert_allclose(
            np.asarray(out_gqa), np.asarray(out_rep), atol=1e-6
        )

    def test_segment_mask_blocks_cross_segment(self):
        rng = np.random.RandomState(3)
        q, k, v = rand_qkv(rng, b=1, s=8, h=1, d=8)
        segs = jnp.asarray([[0, 0, 0, 0, 1, 1, 1, 1]])
        out = dot_product_attention(q, k, v, causal=False, segment_ids=segs)
        # Second segment must be independent of first-segment k/v.
        k2 = k.at[:, :4].set(0.0)
        v2 = v.at[:, :4].set(0.0)
        out2 = dot_product_attention(q, k2, v2, causal=False, segment_ids=segs)
        np.testing.assert_allclose(
            np.asarray(out[:, 4:]), np.asarray(out2[:, 4:]), atol=1e-6
        )


class TestFlashKernel:
    """Kernel logic via the Pallas interpreter (no TPU needed)."""

    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("s,block", [(64, 16), (128, 128), (96, 32)])
    def test_matches_reference(self, causal, s, block):
        rng = np.random.RandomState(4)
        q, k, v = rand_qkv(rng, b=1, s=s, h=2, d=32)
        ref = dot_product_attention(q, k, v, causal=causal)
        out = flash_attention(
            q, k, v, causal=causal, block_q=block, block_k=block,
            interpret=True,
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5
        )

    def test_gqa(self):
        rng = np.random.RandomState(5)
        q, k, v = rand_qkv(rng, s=32, h=4, hkv=2, d=16)
        ref = dot_product_attention(q, k, v, causal=True)
        out = flash_attention(q, k, v, causal=True, block_q=16, block_k=16,
                              interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    def test_gradients_flow(self):
        rng = np.random.RandomState(6)
        q, k, v = rand_qkv(rng, b=1, s=16, h=1, d=8)

        def loss_flash(q, k, v):
            return flash_attention(q, k, v, block_q=8, block_k=8,
                                   interpret=True).sum()

        def loss_ref(q, k, v):
            return dot_product_attention(q, k, v).sum()

        g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)

    def test_cpu_without_interpret_raises_naming_the_backend(self):
        """The kernel was asked for by name: on a backend that cannot
        run it the call fails instead of answering with the XLA path (a
        job that landed on the CPU would train, print a loss and exit
        0).  Segmented calls keep their documented XLA path."""
        rng = np.random.RandomState(7)
        q, k, v = rand_qkv(rng, s=16)
        with pytest.raises(RuntimeError, match=r"'cpu'"):
            flash_attention(q, k, v)
        seg = jnp.zeros(q.shape[:2], jnp.int32)
        ref = dot_product_attention(q, k, v, segment_ids=seg)
        out = flash_attention(q, k, v, segment_ids=seg)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


class TestTwoPassFlash:
    """Splash-style two-pass causal forward: full blocks + fine diagonal
    band merged in log space (ops/flash.py _flash_fwd_two_pass)."""

    @pytest.mark.parametrize("s,bq,bk,bd", [
        (128, 32, 64, 16),   # several full blocks + band
        (128, 32, 32, 8),    # bq == bk
        (96, 32, 32, 16),    # non-power-of-two sequence
        (256, 64, 128, 32),  # wide k blocks (the production shape, scaled)
    ])
    def test_matches_reference(self, s, bq, bk, bd):
        rng = np.random.RandomState(11)
        q, k, v = rand_qkv(rng, b=1, s=s, h=2, d=32)
        ref = dot_product_attention(q, k, v, causal=True)
        out = flash_attention(
            q, k, v, causal=True, block_q=bq, block_k=bk, block_diag=bd,
            interpret=True,
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5)

    def test_pure_band_when_no_full_blocks(self):
        """sq <= block_k leaves pass A with zero full blocks; the
        internal two-pass path must degrade to the band-only pass."""
        from kubeflow_tpu.ops.flash import _flash_fwd_two_pass, _to_bhsd

        rng = np.random.RandomState(12)
        q, k, v = rand_qkv(rng, b=1, s=64, h=1, d=16)
        ref = dot_product_attention(q, k, v, causal=True)
        o, lse = _flash_fwd_two_pass(
            _to_bhsd(q), _to_bhsd(k), _to_bhsd(v),
            block_q=64, block_k=64, block_diag=16, interpret=True)
        np.testing.assert_allclose(
            np.asarray(o.reshape(1, 1, 64, 16).transpose(0, 2, 1, 3)),
            np.asarray(ref), atol=2e-5)

    def test_lse_matches_manual(self):
        """The merged lse must be the TRUE full-softmax lse — it feeds
        the unchanged backward kernels."""
        from kubeflow_tpu.ops.flash import _flash_fwd_two_pass, _to_bhsd

        rng = np.random.RandomState(13)
        q, k, v = rand_qkv(rng, b=1, s=128, h=1, d=16)
        _, lse = _flash_fwd_two_pass(
            _to_bhsd(q), _to_bhsd(k), _to_bhsd(v),
            block_q=32, block_k=64, block_diag=16, interpret=True)
        s_full = np.einsum(
            "bqhd,bkhd->bhqk", np.asarray(q, np.float32),
            np.asarray(k, np.float32)) * (16 ** -0.5)
        mask = np.tril(np.ones((128, 128), bool))
        s_full = np.where(mask[None, None], s_full, -np.inf)
        manual = np.log(np.exp(
            s_full - s_full.max(-1, keepdims=True)).sum(-1)) \
            + s_full.max(-1)
        np.testing.assert_allclose(
            np.asarray(lse).reshape(1, 1, 128), manual, atol=2e-5)

    def test_gradients_match_reference(self):
        rng = np.random.RandomState(14)
        q, k, v = rand_qkv(rng, b=1, s=128, h=2, d=16)

        def loss_two_pass(q, k, v):
            return (flash_attention(
                q, k, v, causal=True, block_q=32, block_k=64,
                block_diag=16, interpret=True) ** 2).sum()

        def loss_ref(q, k, v):
            return (dot_product_attention(q, k, v, causal=True) ** 2).sum()

        g1 = jax.grad(loss_two_pass, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=5e-5)

    def test_dispatch_requires_self_attention_shape(self):
        """block_diag on a cross-attention shape (sq != sk) silently
        uses the classic single pass — same result either way."""
        rng = np.random.RandomState(15)
        q, _, _ = rand_qkv(rng, b=1, s=32, h=1, d=16)
        _, k, v = rand_qkv(rng, b=1, s=64, h=1, d=16)
        out = flash_attention(
            q, k, v, causal=False, block_q=16, block_k=16,
            block_diag=8, interpret=True)
        ref = dot_product_attention(q, k, v, causal=False)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5)


class TestFlashBackwardKernels:
    """The Pallas blockwise backward (dq and dkv passes) via interpreter."""

    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("s,block", [(64, 16), (96, 32)])
    def test_grads_match_reference(self, causal, s, block):
        rng = np.random.RandomState(8)
        q, k, v = rand_qkv(rng, b=2, s=s, h=2, d=32)

        def loss_flash(q, k, v):
            out = flash_attention(q, k, v, causal=causal, block_q=block,
                                  block_k=block, interpret=True)
            return (out * out).sum()  # non-uniform cotangent

        def loss_ref(q, k, v):
            out = dot_product_attention(q, k, v, causal=causal)
            return (out * out).sum()

        g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=5e-4, rtol=1e-3
            )

    def test_gqa_grads_fold_head_groups(self):
        rng = np.random.RandomState(9)
        q, k, v = rand_qkv(rng, b=1, s=32, h=4, hkv=2, d=16)

        def loss(fn):
            def inner(q, k, v):
                return fn(q, k, v).sum()
            return inner

        flash = lambda q, k, v: flash_attention(
            q, k, v, causal=True, block_q=16, block_k=16, interpret=True)
        ref = lambda q, k, v: dot_product_attention(q, k, v, causal=True)
        g1 = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss(ref), argnums=(0, 1, 2))(q, k, v)
        assert g1[1].shape == k.shape  # folded back to kv head count
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-4, rtol=1e-3)

    def test_lse_matches_manual(self):
        from kubeflow_tpu.ops.flash import flash_fwd_with_lse

        rng = np.random.RandomState(10)
        q, k, v = rand_qkv(rng, b=1, s=32, h=2, d=16)
        o, lse = flash_fwd_with_lse(q, k, v, causal=False, block_q=16,
                                    block_k=16, interpret=True)
        scores = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(16)
        ref_lse = jax.nn.logsumexp(jnp.asarray(scores), axis=-1)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                                   atol=1e-5)
        ref_o = dot_product_attention(q, k, v, causal=False)
        np.testing.assert_allclose(np.asarray(o), np.asarray(ref_o),
                                   atol=2e-5)


class TestFlashRematResiduals:
    """The flash fwd names its (out, lse) residuals (checkpoint_name) so a
    remat policy can keep them instead of re-running the forward kernel
    inside the backward pass — the policy composition models/transformer.py
    installs when save_attn_residuals is set."""

    def _policy(self):
        return jax.checkpoint_policies.save_from_both_policies(
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            jax.checkpoint_policies.save_only_these_names(
                "flash_out", "flash_lse"),
        )

    def test_grads_identical_with_saved_residuals(self):
        rng = np.random.RandomState(11)
        q, k, v = rand_qkv(rng, b=2, s=64, h=2, d=32)

        def attend(q, k, v):
            out = flash_attention(q, k, v, causal=True, block_q=16,
                                  block_k=16, interpret=True)
            return (out * out).sum()

        plain = jax.grad(attend, argnums=(0, 1, 2))(q, k, v)
        saved = jax.grad(
            jax.checkpoint(attend, policy=self._policy())
        , argnums=(0, 1, 2))(q, k, v)
        recomputed = jax.grad(
            jax.checkpoint(
                attend,
                policy=jax.checkpoint_policies
                .dots_with_no_batch_dims_saveable,
            ), argnums=(0, 1, 2))(q, k, v)
        for a, b, c in zip(plain, saved, recomputed):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-6)
            np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                       atol=1e-6)

    def test_policy_elides_fwd_recompute(self):
        """With the residuals saved, the backward jaxpr must not contain a
        second forward kernel call (the lse-producing pallas call)."""
        rng = np.random.RandomState(12)
        q, k, v = rand_qkv(rng, b=1, s=32, h=2, d=16)

        def attend(q, k, v):
            out = flash_attention(q, k, v, causal=True, block_q=16,
                                  block_k=16, interpret=True)
            return (out * out).sum()

        def n_pallas_calls(policy):
            fn = jax.checkpoint(attend, policy=policy) if policy else attend
            jaxpr = jax.make_jaxpr(
                jax.grad(fn, argnums=(0, 1, 2)))(q, k, v)
            return str(jaxpr).count("pallas_call")

        # Ungated grad: fwd + dq + dkv = 3 kernel launches.  Saving the
        # named residuals keeps it at 3 under remat; dropping them forces
        # a 4th launch (the fwd recompute inside the backward).
        assert n_pallas_calls(None) == 3
        assert n_pallas_calls(self._policy()) == 3
        assert n_pallas_calls(
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable) == 4


class TestFlashKeyStartMask:
    """Forward-only per-row key-start mask (left-padded decode prefill):
    the kernel's early k blocks are the masked ones, which stresses the
    online-softmax sentinel handling (a fully-masked running max must
    not turn exp(sentinel - sentinel) into weight 1)."""

    def _ref(self, q, k, v, start):
        return dot_product_attention(
            q, k, v, causal=True, kv_valid_start=start)

    @pytest.mark.parametrize("block", [32, 64])
    def test_masked_matches_reference(self, block):
        rng = np.random.RandomState(11)
        b, s, h, d = 3, 128, 2, 16
        q, k, v = (jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
                   for _ in range(3))
        # Row 0 unpadded; row 1 pad crosses a block boundary; row 2 pad
        # larger than a whole k block (the sentinel-corruption case).
        start = jnp.asarray([0, block // 2 + 3, block + 7], jnp.int32)
        out = flash_attention(
            q, k, v, causal=True, block_q=block, block_k=block,
            interpret=True, kv_valid_start=start)
        ref = self._ref(q, k, v, start)
        # Pad-row queries (pos < start) are fully masked: the kernel
        # emits zeros there, the reference emits uniform-weight noise —
        # both are garbage no caller reads.  Compare valid rows only.
        for row in range(b):
            s0 = int(start[row])
            np.testing.assert_allclose(
                np.asarray(out[row, s0:]), np.asarray(ref[row, s0:]),
                atol=2e-5, rtol=2e-5)

    def test_fully_masked_rows_are_finite(self):
        rng = np.random.RandomState(12)
        q, k, v = (jnp.asarray(rng.randn(1, 64, 2, 16), jnp.float32)
                   for _ in range(3))
        out = flash_attention(
            q, k, v, causal=True, block_q=32, block_k=32,
            interpret=True, kv_valid_start=jnp.asarray([40], jnp.int32))
        assert np.isfinite(np.asarray(out)).all()
        # Pad-row outputs are exactly zero (l == 0 guard).
        np.testing.assert_array_equal(
            np.asarray(out[0, :32]), np.zeros_like(out[0, :32]))


_PAGE = 8          # block_tokens of the test pools
_TABLE = 5         # pages a slot's table spans


class TestPagedDecodeKernel:
    """ops/paged_attention.py in the Pallas interpreter against
    ``dot_product_attention`` over each slot's gathered view of the pool
    (what the step programs do off the chip)."""

    @staticmethod
    def _reference(q, k_pool, v_pool, tables, n_tokens):
        nb = k_pool.shape[0]
        t = jnp.minimum(tables, nb - 1)      # sentinels clamp, masked
        s, mb = tables.shape

        def view(p):
            return p[t].reshape((s, mb * _PAGE) + p.shape[2:])

        return dot_product_attention(
            q[:, None], view(k_pool), view(v_pool), causal=True,
            kv_offset=n_tokens - 1)[:, 0]

    @pytest.mark.parametrize("group", [1, 2, 4])
    @pytest.mark.parametrize("case", [
        "retired", "one", "page_minus_one", "page", "page_plus_one",
        "full_span", "shuffled", "sentinel_above_frontier", "aliased"])
    def test_matches_gathered_view(self, case, group):
        from kubeflow_tpu.ops.paged_attention import paged_decode_attention

        rng = np.random.RandomState(40 + group)
        slots, hkv, d = 4, 2, 128
        h = hkv * group
        nb = slots * _TABLE + 3
        q = jnp.asarray(rng.randn(slots, h, d), jnp.float32)
        # The engine's stacked pool: three planes, the kernel reads the
        # middle one; its neighbours hold other numbers, so a wrong plane
        # offset cannot pass.
        k_pools = jnp.asarray(rng.randn(3, nb, _PAGE, hkv, d), jnp.float32)
        v_pools = jnp.asarray(rng.randn(3, nb, _PAGE, hkv, d), jnp.float32)
        # Physical pages in no order, other slots at lengths of their own.
        tables = rng.permutation(nb)[:slots * _TABLE].reshape(
            slots, _TABLE).astype(np.int32)
        n = np.array([13, 2 * _PAGE, 29, 5], np.int32)
        first = {"retired": 0, "one": 1, "page_minus_one": _PAGE - 1,
                 "page": _PAGE, "page_plus_one": _PAGE + 1,
                 "full_span": _TABLE * _PAGE}
        if case in first:
            n[0] = first[case]
        elif case == "sentinel_above_frontier":
            # Unallocated logical pages hold the pool size, as the
            # engine's tables do; slot 2's frontier is its page's end.
            n[2] = 2 * _PAGE
            for s in range(slots):
                tables[s, -(-int(n[s]) // _PAGE):] = nb
        elif case == "aliased":
            # Two slots share their first two physical pages (a cached
            # prefix) and differ after them.
            tables[1, :2] = tables[0, :2]
            n[0], n[1] = 2 * _PAGE + 3, 3 * _PAGE + 1
        tables, n = jnp.asarray(tables), jnp.asarray(n)
        out = paged_decode_attention(
            q, k_pools, v_pools, jnp.int32(1), tables, n,
            pages_per_block=2, interpret=True)
        ref = self._reference(q, k_pools[1], v_pools[1], tables, n)
        live = np.asarray(n) > 0
        np.testing.assert_allclose(
            np.asarray(out)[live], np.asarray(ref)[live], atol=2e-5)
        # A slot with nothing to attend reads no page and returns zeros.
        assert not np.asarray(out)[~live].any()

    @pytest.mark.parametrize("d,hkv,group", [
        (64, 2, 1), (64, 8, 4), (64, 4, 2), (32, 4, 2)])
    def test_narrow_heads_share_a_row(self, d, hkv, group):
        """Heads under 128 lanes: ``128 // d`` kv heads a row, each query
        head in its own kv head's lanes (LFM2's 32 heads on 8 of 64)."""
        from kubeflow_tpu.ops.paged_attention import (
            paged_decode_attention,
            supports,
        )

        assert supports(d, hkv) and not supports(64, 3) \
            and not supports(96, 4)
        rng = np.random.RandomState(7 * d + hkv)
        slots, h = 4, hkv * group
        nb = slots * _TABLE + 3
        q = jnp.asarray(rng.randn(slots, h, d), jnp.float32)
        k_pools = jnp.asarray(rng.randn(2, nb, _PAGE, hkv, d), jnp.float32)
        v_pools = jnp.asarray(rng.randn(2, nb, _PAGE, hkv, d), jnp.float32)
        tables = jnp.asarray(rng.permutation(nb)[:slots * _TABLE].reshape(
            slots, _TABLE).astype(np.int32))
        n = jnp.asarray(np.array([13, 2 * _PAGE, 0, _TABLE * _PAGE],
                                 np.int32))
        out = paged_decode_attention(
            q, k_pools, v_pools, jnp.int32(1), tables, n,
            pages_per_block=2, interpret=True)
        ref = self._reference(q, k_pools[1], v_pools[1], tables, n)
        live = np.asarray(n) > 0
        np.testing.assert_allclose(
            np.asarray(out)[live], np.asarray(ref)[live], atol=2e-5)
        assert not np.asarray(out)[~live].any()

    # What the walk across slots can get wrong (PR 41): a slot's first
    # block is fetched under the slot before it, into the buffer that is
    # free, and a whole block's pages are issued in straight-line code.
    # Six slots, tables of 12 pages, blocks of 2 pages (16 positions):
    # positions a slot, and what the case is about.
    _WALK = {
        "retired_between_live": (40, 0, 23, 0, 0, 50),
        "first_and_last_retired": (0, 33, 48, 7, 17, 0),
        "one_live_slot": (0, 0, 0, 37, 0, 0),
        "n_of_one": (1, 1, 40, 1, 0, 1),
        "exact_block_multiples": (16, 32, 48, 64, 96, 16),
        # 3, 2, 1, 4, 5, 2 blocks: the buffer a slot starts in flips or
        # not from one slot to the next.
        "odd_and_even_block_counts": (41, 32, 9, 57, 70, 20),
        "shared_pages": (52, 52, 36, 20, 60, 44),
        "sentinel_past_the_frontier": (16, 5, 96, 31, 0, 24),
    }

    @pytest.mark.parametrize("form", ["k_v", "latent"])
    @pytest.mark.parametrize("case", sorted(_WALK))
    def test_walk_across_slots_matches_gathered_view(self, case, form):
        """Both forms in the TPU interpreter, which a plain
        ``interpret=True`` is not: scratch starts as NaN, a copy lands
        only where it is WAITED for, a semaphore left with a count and a
        read that races a copy are reported."""
        from jax._src.pallas.mosaic.interpret import (
            interpret_pallas_call as tpu_interpreter,
        )
        from jax.experimental.pallas import tpu as pltpu

        from kubeflow_tpu.ops.paged_attention import (
            paged_decode_attention,
            paged_latent_decode_attention,
        )

        rng = np.random.RandomState(41)
        slots, table, pages = 6, 12, 2
        nb = slots * table + 5
        n = np.array(self._WALK[case], np.int32)
        tables = rng.permutation(nb)[:slots * table].reshape(
            slots, table).astype(np.int32)
        if case == "shared_pages":
            # A cached prefix of three pages under slots 0, 1 and 4: a
            # block and a half, so a shared page is one slot's block 1
            # and stays resident while the next slot fetches it again.
            tables[1, :3] = tables[4, :3] = tables[0, :3]
        if case == "sentinel_past_the_frontier":
            for s in range(slots):
                tables[s, -(-int(n[s]) // _PAGE):] = nb
        tables, n_tokens = jnp.asarray(tables), jnp.asarray(n)
        mode = pltpu.InterpretParams(detect_races=True)
        if form == "k_v":
            hkv, d, h = 2, 128, 4
            q = jnp.asarray(rng.randn(slots, h, d), jnp.float32)
            k_pools = jnp.asarray(
                rng.randn(2, nb, _PAGE, hkv, d), jnp.float32)
            v_pools = jnp.asarray(
                rng.randn(2, nb, _PAGE, hkv, d), jnp.float32)
            ref = self._reference(q, k_pools[1], v_pools[1], tables,
                                  n_tokens)
            run = functools.partial(
                paged_decode_attention, q, k_pools, v_pools, jnp.int32(1),
                tables, n_tokens, pages_per_block=pages, interpret=mode)
        else:
            row, latent, h = 256, 128, 8
            q = jnp.asarray(rng.randn(slots, h, row), jnp.float32)
            pools = jnp.asarray(rng.randn(2, nb, _PAGE, row), jnp.float32)
            view = pools[1][jnp.minimum(tables, nb - 1)].reshape(
                slots, table * _PAGE, row)
            with jax.default_matmul_precision("highest"):
                sc = jnp.einsum("shr,skr->shk", q, view) * 0.25
                sc = jnp.where(
                    jnp.arange(table * _PAGE)[None, None, :]
                    < n_tokens[:, None, None], sc, -jnp.inf)
                ref = jnp.einsum(
                    "shk,skc->shc", jax.nn.softmax(sc, -1),
                    view[..., :latent])
            run = functools.partial(
                paged_latent_decode_attention, q, pools, jnp.int32(1),
                tables, n_tokens, latent, 0.25, pages_per_block=pages,
                interpret=mode)
        # The interpreter's callbacks run jax operations of their own and
        # can deadlock against another dispatch: the reference is on the
        # host before the kernel starts, and nothing follows it.
        ref = np.asarray(ref)
        out = np.asarray(run())
        assert not tpu_interpreter.races.races_found
        live = n > 0
        np.testing.assert_allclose(out[live], ref[live], atol=2e-5)
        assert not out[~live].any()

    def test_walk_leaves_no_copy_unwaited(self, capfd):
        """A slot's first block is started by the slot before it: every
        start has its wait, whatever ends the walk (a live last slot,
        retired ones after it).  The interpreter names a semaphore that
        keeps a count at the kernel's end."""
        from jax.experimental.pallas import tpu as pltpu

        from kubeflow_tpu.ops.paged_attention import (
            paged_latent_decode_attention,
        )

        rng = np.random.RandomState(42)
        slots, table, row = 5, 6, 128
        nb = slots * table
        pools = jnp.asarray(rng.randn(1, nb, _PAGE, row), jnp.float32)
        q = jnp.asarray(rng.randn(slots, 8, row), jnp.float32)
        tables = jnp.asarray(rng.permutation(nb).reshape(
            slots, table).astype(np.int32))
        for n in [(20, 0, 48, 0, 0), (0, 9, 0, 33, 17)]:
            out = paged_latent_decode_attention(
                q, pools, jnp.int32(0), tables, jnp.asarray(n, jnp.int32),
                128, 0.25, pages_per_block=2,
                interpret=pltpu.InterpretParams())
            assert np.isfinite(np.asarray(out)).all()
        assert "non-zero count" not in capfd.readouterr().out

    @pytest.mark.parametrize("page_bytes,table,pages", [
        (16 * 640 * 2, 414, 64),        # agents: latent rows of 640 lanes
        (16 * 8 * 128 * 2, 400, 32),    # docqa, chat: 8 kv heads of 128
        (16 * 16 * 128 * 2, 32, 16),    # reason: 16 kv heads of 128
        (16 * 8 * 64 * 2, 44, 44),      # workers: the whole table
        (8 * 128 * 4, 5, 5)])           # these tests' pools
    def test_a_block_is_a_mib_of_pages_a_side(self, page_bytes, table,
                                              pages):
        from kubeflow_tpu.ops.paged_attention import _block_pages

        assert _block_pages(page_bytes, table) == pages


class TestPagedIndexScores:
    """``paged_index_scores`` in the TPU interpreter (scratch starts as
    NaN, a copy lands only where it is waited for) against
    ``generate._index_scores`` over each slot's gathered index keys."""

    # Tables of 12 pages of 8, blocks of 2 pages (16 positions):
    # (positions a slot, the plane read), and what the case is about.
    _CASES = {
        "mixed_lengths": ((41, 9, 70, 20, 57, 3), 1),
        "ends_on_a_page": ((24, 40, 8, 56, 72, 88), 1),
        "ends_on_a_block": ((16, 32, 48, 64, 96, 16), 1),
        "retired_between_live": ((40, 0, 23, 0, 0, 50), 1),
        "first_and_last_retired": ((0, 33, 48, 7, 17, 0), 1),
        "one_live_slot": ((0, 0, 0, 37, 0, 0), 1),
        "one_slot_only": ((45,), 1),
        "first_plane": ((41, 32, 9, 57, 70, 20), 0),
        "last_plane": ((52, 1, 36, 20, 60, 96), 2),
        "sentinel_past_the_frontier": ((16, 5, 96, 31, 0, 24), 1),
        "all_retired": ((0, 0, 0, 0, 0, 0), 1),
    }

    @pytest.mark.parametrize("case", sorted(_CASES))
    def test_matches_index_scores(self, case):
        from jax._src.pallas.mosaic.interpret import (
            interpret_pallas_call as tpu_interpreter,
        )
        from jax.experimental.pallas import tpu as pltpu

        from kubeflow_tpu.models.generate import _choose, _index_scores
        from kubeflow_tpu.ops.paged_attention import (
            paged_index_scores,
            supports_index,
        )

        n, plane = self._CASES[case]
        n = np.array(n, np.int32)
        rng = np.random.RandomState(45)
        slots, table, heads, dim, topk = len(n), 12, 4, 128, 10
        assert supports_index(dim, _PAGE, jnp.float32) \
            and supports_index(dim, 16, jnp.bfloat16) \
            and not supports_index(32, 16, jnp.bfloat16) \
            and not supports_index(dim, 8, jnp.bfloat16)
        nb = slots * table + 5
        tables = rng.permutation(nb)[:slots * table].reshape(
            slots, table).astype(np.int32)
        if case == "sentinel_past_the_frontier":
            for s in range(slots):
                tables[s, -(-int(n[s]) // _PAGE):] = nb
        q = jnp.asarray(rng.randn(slots, heads, dim), jnp.float32)
        w = jnp.asarray(rng.randn(slots, heads), jnp.float32)
        # The planes beside the one read hold other numbers.
        pools = jnp.asarray(rng.randn(3, nb, _PAGE, dim), jnp.float32)
        view = pools[plane][np.minimum(tables, nb - 1)].reshape(
            slots, table * _PAGE, dim)
        q_pos = jnp.asarray(n[:, None] - 1)
        ref = _index_scores(q[:, None], w[:, None], lambda i: view,
                            table * _PAGE, 1, 1, q_pos)
        want = [np.asarray(a) for a in _choose(ref, q_pos, topk)]
        # The interpreter's callbacks can deadlock against another
        # dispatch: the reference is on the host before the kernel
        # starts, and nothing is dispatched until its result is too.
        ref = np.asarray(ref)[:, 0]
        out = np.asarray(paged_index_scores(
            q, w, pools, jnp.int32(plane), jnp.asarray(tables),
            jnp.asarray(n), pages_per_block=2,
            interpret=pltpu.InterpretParams(detect_races=True)))
        assert not tpu_interpreter.races.races_found
        assert out.shape == ref.shape and out.dtype == np.float32
        past = np.arange(table * _PAGE)[None, :] >= n[:, None]
        assert np.isneginf(out[past]).all()
        np.testing.assert_allclose(out[~past], ref[~past], atol=1e-5,
                                   rtol=1e-5)
        got = [np.asarray(a) for a in _choose(
            jnp.asarray(out)[:, None], q_pos, topk)]
        np.testing.assert_array_equal(got[1], want[1])
        np.testing.assert_array_equal(got[0][got[1]], want[0][want[1]])
