"""Decode-path tests: cached incremental decoding must agree with the
full (uncached) forward pass."""

import jax
import jax.numpy as jnp
import numpy as np

from kubeflow_tpu.models.generate import DecodeConfig, generate
from kubeflow_tpu.models.transformer import Transformer, TransformerConfig

CFG = TransformerConfig(
    vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
    d_ff=64, head_dim=8, max_seq_len=64, dtype=jnp.float32,
)


def setup():
    model = Transformer(CFG)
    prompt = jnp.asarray(
        np.random.RandomState(0).randint(1, CFG.vocab_size, (2, 8)),
        jnp.int32)
    variables = model.init(jax.random.key(0), prompt)
    return model, variables["params"], prompt


def test_greedy_decode_consistent_with_full_forward():
    model, params, prompt = setup()
    tokens, _ = generate(CFG, params, prompt,
                         DecodeConfig(max_new_tokens=6))
    assert tokens.shape == (2, 14)
    # Re-run the whole sequence densely: every generated token must be the
    # argmax of the dense logits at its position.
    dense = model.apply({"params": params}, tokens)
    for pos in range(8, 14):
        expected = jnp.argmax(dense[:, pos - 1], axis=-1)
        np.testing.assert_array_equal(
            np.asarray(tokens[:, pos]), np.asarray(expected))


def test_prefill_logits_match_dense():
    model, params, prompt = setup()
    from kubeflow_tpu.models.generate import (
        _forward_with_cache,
        init_cache,
    )

    cache = init_cache(CFG, 2, 8)
    logits, _ = _forward_with_cache(CFG, params, prompt, cache, 0)
    dense = model.apply({"params": params}, prompt)
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(dense), atol=2e-4
    )


def test_eos_stops_sampling():
    model, params, prompt = setup()
    # Force eos = whatever greedy produces first; the following tokens
    # must be 0 (the pad the decode loop emits after done).
    tokens, _ = generate(CFG, params, prompt,
                         DecodeConfig(max_new_tokens=4))
    first = int(tokens[0, 8])
    tokens2, _ = generate(
        CFG, params, prompt,
        DecodeConfig(max_new_tokens=4, eos_token=first))
    assert int(tokens2[0, 9]) == 0


def test_temperature_sampling_runs():
    model, params, prompt = setup()
    tokens, _ = generate(CFG, params, prompt,
                         DecodeConfig(max_new_tokens=3, temperature=1.0),
                         rng=jax.random.key(7))
    assert tokens.shape == (2, 11)


def test_top_k_one_equals_greedy():
    model, params, prompt = setup()
    greedy, _ = generate(CFG, params, prompt,
                         DecodeConfig(max_new_tokens=5))
    topk1, _ = generate(
        CFG, params, prompt,
        DecodeConfig(max_new_tokens=5, temperature=0.7, top_k=1),
        rng=jax.random.key(3))
    np.testing.assert_array_equal(np.asarray(greedy), np.asarray(topk1))


def test_top_k_samples_stay_in_top_set():
    model, params, prompt = setup()
    k = 3
    # One decode step at high temperature: the sampled token must be one
    # of the top-k next-token candidates of the prefill logits.
    _, prefill_logits = generate(
        CFG, params, prompt, DecodeConfig(max_new_tokens=1))
    del prefill_logits  # logits returned are post-sample; recompute:
    model2 = Transformer(CFG)
    full = model2.apply({"params": params}, prompt)
    allowed = np.asarray(
        jax.lax.top_k(full[:, -1], k)[1])           # [b, k] token ids
    for seed in range(5):
        toks, _ = generate(
            CFG, params, prompt,
            DecodeConfig(max_new_tokens=1, temperature=2.0, top_k=k),
            rng=jax.random.key(seed))
        first_new = np.asarray(toks[:, prompt.shape[1]])
        for b in range(prompt.shape[0]):
            assert first_new[b] in allowed[b], (first_new, allowed)


def test_top_p_tiny_equals_greedy():
    # p smaller than any single token's probability keeps only the
    # argmax -> nucleus sampling degenerates to greedy.
    model, params, prompt = setup()
    greedy, _ = generate(CFG, params, prompt,
                         DecodeConfig(max_new_tokens=5))
    nucleus, _ = generate(
        CFG, params, prompt,
        DecodeConfig(max_new_tokens=5, temperature=1.0, top_p=1e-9),
        rng=jax.random.key(11))
    np.testing.assert_array_equal(np.asarray(greedy), np.asarray(nucleus))


def test_top_p_one_matches_plain_sampling():
    model, params, prompt = setup()
    plain, _ = generate(
        CFG, params, prompt,
        DecodeConfig(max_new_tokens=4, temperature=1.0),
        rng=jax.random.key(5))
    nucleus, _ = generate(
        CFG, params, prompt,
        DecodeConfig(max_new_tokens=4, temperature=1.0, top_p=1.0),
        rng=jax.random.key(5))
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(nucleus))


def test_invalid_top_p_rejected():
    import pytest

    with pytest.raises(ValueError, match="top_p"):
        DecodeConfig(top_p=0.0)
    with pytest.raises(ValueError, match="top_k"):
        DecodeConfig(top_k=-1)


def test_top_k_larger_than_vocab_is_no_filter():
    model, params, prompt = setup()
    plain, _ = generate(
        CFG, params, prompt,
        DecodeConfig(max_new_tokens=3, temperature=1.0),
        rng=jax.random.key(9))
    big_k, _ = generate(
        CFG, params, prompt,
        DecodeConfig(max_new_tokens=3, temperature=1.0, top_k=10_000),
        rng=jax.random.key(9))
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(big_k))


class TestLeftPaddedDecode:
    """Bucketed mixed-length decode: a LEFT-padded row with prompt_len
    must produce exactly the tokens it would alone at natural length
    (pad keys masked, rope offset by the pad) — the contract
    serving/model_server.py BucketedLMBatcher depends on."""

    def test_padded_row_matches_unpadded(self):
        _, params, _ = setup()
        rng = np.random.RandomState(3)
        short = jnp.asarray(rng.randint(1, CFG.vocab_size, (1, 5)),
                            jnp.int32)
        long = jnp.asarray(rng.randint(1, CFG.vocab_size, (1, 8)),
                           jnp.int32)
        dc = DecodeConfig(max_new_tokens=6)
        ref_short, _ = generate(CFG, params, short, dc)
        ref_long, _ = generate(CFG, params, long, dc)

        # One bucketed batch of 8: short row left-padded by 3.
        padded_short = jnp.concatenate(
            [jnp.zeros((1, 3), jnp.int32), short], axis=1)
        batch = jnp.concatenate([padded_short, long], axis=0)
        plen = jnp.asarray([5, 8], jnp.int32)
        out, _ = generate(CFG, params, batch, dc, prompt_len=plen)
        # Short row: strip the 3 pad columns, then compare end to end.
        np.testing.assert_array_equal(
            np.asarray(out[0, 3:]), np.asarray(ref_short[0]))
        np.testing.assert_array_equal(
            np.asarray(out[1]), np.asarray(ref_long[0]))

    def test_full_length_prompt_len_is_identity(self):
        _, params, prompt = setup()
        dc = DecodeConfig(max_new_tokens=4)
        ref, _ = generate(CFG, params, prompt, dc)
        out, _ = generate(CFG, params, prompt, dc,
                          prompt_len=jnp.asarray([8, 8], jnp.int32))
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_bucketed_batcher_mixed_lengths_share_batches():
    """Mixed-length prompts coalesce through BucketedLMBatcher and come
    back at their natural shapes with per-length-correct decodes."""
    from kubeflow_tpu.serving.model_server import BucketedLMBatcher

    _, params, _ = setup()
    dc = DecodeConfig(max_new_tokens=4)
    rng = np.random.RandomState(7)
    # Lengths straddle the [8, 16] bucket boundary: dispatch-time
    # promotion pads a batch containing the length-10 prompt to bucket
    # 16, so even cross-bucket mixes share device batches (the
    # submit-time-padding design re-split them and measured ~5x below
    # uniform-length req/s on-chip).
    prompts = [rng.randint(1, CFG.vocab_size, (1, n)).astype(np.int32)
               for n in (3, 5, 10, 8)]
    refs = [np.asarray(generate(CFG, params, jnp.asarray(p), dc)[0])
            for p in prompts]

    def predict(inputs):
        out, _ = generate(
            CFG, params, jnp.asarray(inputs["tokens"], jnp.int32), dc,
            prompt_len=jnp.asarray(inputs["prompt_len"], jnp.int32))
        return {"tokens": out}

    mb = BucketedLMBatcher(
        predict, buckets=[8, 16], max_batch_size=4,
        batch_timeout_s=0.05, allowed_batch_sizes=[1, 2, 4], name="lmb")
    try:
        import concurrent.futures as cf

        with cf.ThreadPoolExecutor(4) as ex:
            outs = list(ex.map(
                lambda p: mb.submit({"tokens": p}), prompts))
        for p, out, ref in zip(prompts, outs, refs):
            assert out["tokens"].shape == (1, p.shape[1] + 4)
            np.testing.assert_array_equal(out["tokens"], ref)
        # One shared queue: with 4 concurrent clients at a generous
        # timeout the mixed-bucket prompts coalesce rather than running
        # batch-1 (the pre-bucketing behavior) or splitting per bucket
        # (the submit-time-padding behavior).
        stats = mb.stats()
        assert stats["mean_batch_size"] > 1.0, stats
    finally:
        mb.close()


def test_bucketed_batcher_promotion_is_bounded():
    """max_promotion_factor (VERDICT r4 item 7): a short prompt must
    never be co-batched into a bucket more than factor x its own — the
    per-decode-step KV span is set by the batch bucket, so unbounded
    promotion makes a 128-token request pay a 4096-token attention span
    per step on a wide length spread."""
    from kubeflow_tpu.serving.model_server import BucketedLMBatcher

    widths = []

    def predict(inputs):
        widths.append(np.asarray(inputs["tokens"]).shape[1])
        return {"tokens": np.asarray(inputs["tokens"])}

    mb = BucketedLMBatcher(
        predict, buckets=[32, 128, 512, 4096], max_batch_size=2,
        batch_timeout_s=0.05, allowed_batch_sizes=[1, 2], name="lmb4")
    try:
        import concurrent.futures as cf

        with cf.ThreadPoolExecutor(2) as ex:
            short = ex.submit(
                mb.submit, {"tokens": np.ones((1, 100), np.int32)})
            long = ex.submit(
                mb.submit, {"tokens": np.ones((1, 3000), np.int32)})
            short, long = short.result(), long.result()
        # Separate bands (128 vs 4096 with factor 4) -> separate
        # dispatches: the short prompt padded to ITS band's bucket.
        assert sorted(widths) == [128, 4096], widths
        assert short["tokens"].shape == (1, 100)
        assert long["tokens"].shape == (1, 3000)
        assert mb.stats()["batches"] == 2
    finally:
        mb.close()


def test_bucketed_batcher_unbounded_promotion_shares_one_queue():
    """max_promotion_factor=None restores the single shared queue: the
    same spread promotes the short prompt to the long one's bucket."""
    from kubeflow_tpu.serving.model_server import BucketedLMBatcher

    widths = []

    def predict(inputs):
        widths.append(np.asarray(inputs["tokens"]).shape[1])
        return {"tokens": np.asarray(inputs["tokens"])}

    mb = BucketedLMBatcher(
        predict, buckets=[32, 128, 512, 4096],
        max_promotion_factor=None, max_batch_size=2,
        batch_timeout_s=0.2, allowed_batch_sizes=[1, 2], name="lmb5")
    try:
        import concurrent.futures as cf

        with cf.ThreadPoolExecutor(2) as ex:
            outs = list(ex.map(
                lambda n: mb.submit({"tokens": np.ones((1, n), np.int32)}),
                [100, 3000]))
        assert widths == [4096], widths  # one co-batched dispatch
        assert outs[0]["tokens"].shape == (1, 100)
    finally:
        mb.close()


def test_bucketed_batcher_oversize_prompt_rejected():
    from kubeflow_tpu.serving.model_server import BucketedLMBatcher

    mb = BucketedLMBatcher(lambda i: i, buckets=[8], name="lmb2")
    try:
        import pytest

        with pytest.raises(ValueError, match="exceeds"):
            mb.submit({"tokens": np.zeros((1, 9), np.int32)})
    finally:
        mb.close()


def test_bucketed_batcher_rejects_multi_row_submit():
    from kubeflow_tpu.serving.model_server import BucketedLMBatcher

    mb = BucketedLMBatcher(lambda i: i, buckets=[8], name="lmb3")
    try:
        import pytest

        with pytest.raises(ValueError, match="one prompt"):
            mb.submit({"tokens": np.zeros((2, 5), np.int32)})
    finally:
        mb.close()


def test_flash_prefill_matches_dot_decode(monkeypatch):
    """A flash-configured model's generate() (flash prefill, cached dot
    decode) must produce the dot-configured model's tokens.  The kernel
    cannot run on this suite's CPU backend (it raises rather than fall
    back), so the test itself steers it into the Pallas interpreter —
    that also proves the gate really reaches the kernel, with the
    per-row key-start mask.  Kernel-vs-dot numerics are pinned with
    tolerances in tests/test_ops.py; here the float32 toy model's
    greedy tokens survive the kernel's blockwise summation order."""
    import functools

    from kubeflow_tpu.ops import flash

    calls = []
    real = flash.flash_attention

    def interpreted(*args, **kwargs):
        calls.append(kwargs.get("kv_valid_start") is not None)
        return real(*args, **{**kwargs, "interpret": True})

    monkeypatch.setattr(flash, "flash_attention",
                        functools.wraps(real)(interpreted))
    _, params, prompt = setup()
    dc = DecodeConfig(max_new_tokens=5)
    ref, _ = generate(CFG, params, prompt, dc)
    cfg_flash = TransformerConfig(
        **{**CFG.__dict__, "attention": "flash"})
    out, _ = generate(cfg_flash, params, prompt, dc)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    # Left-padded rows ride flash prefill via the kernel's per-row
    # key-start mask and must decode identically to the unpadded
    # reference;
    # int8 caches keep the dot path (goldens pin that rounding) and
    # must still decode at the right shape.
    padded = jnp.concatenate(
        [jnp.zeros((2, 3), jnp.int32), prompt], axis=1)
    out_pad, _ = generate(cfg_flash, params, padded, dc,
                          prompt_len=jnp.asarray([8, 8], jnp.int32))
    np.testing.assert_array_equal(np.asarray(out_pad[:, 3:]),
                                  np.asarray(ref))
    out_q, _ = generate(
        TransformerConfig(**{**CFG.__dict__, "attention": "flash"}),
        params, prompt,
        DecodeConfig(max_new_tokens=5, kv_cache_dtype="int8"))
    assert out_q.shape == ref.shape
    # Both fp prefills went through the kernel — the padded one with its
    # key-start mask — and the int8-cache one stayed on the dot path.
    assert calls == [False, True]


def test_eos_while_loop_matches_scan_when_eos_never_fires():
    """eos_token >= 0 switches decode to the early-exit while_loop; when
    no row ever emits EOS it must produce exactly the fixed-length scan's
    tokens (the early exit changes wall time, never content)."""
    _, params, prompt = setup()
    ref, _ = generate(CFG, params, prompt, DecodeConfig(max_new_tokens=6))
    used = set(np.asarray(ref[:, prompt.shape[1]:]).ravel().tolist())
    eos = next(i for i in range(CFG.vocab_size) if i not in used)
    out, _ = generate(CFG, params, prompt,
                      DecodeConfig(max_new_tokens=6, eos_token=eos))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_eos_early_exit_payoff_case_matches_scan_semantics():
    """The case the while_loop exists FOR: every row done well before
    max_new_tokens.  Tokens must equal the fixed-length run truncated at
    EOS (EOS emitted, zeros after), at the full output shape."""
    _, params, prompt = setup()
    row = prompt[:1]  # single row: its first greedy token becomes EOS
    ref, _ = generate(CFG, params, row, DecodeConfig(max_new_tokens=6))
    t = row.shape[1]
    eos = int(ref[0, t])
    out, _ = generate(CFG, params, row,
                      DecodeConfig(max_new_tokens=6, eos_token=eos))
    assert out.shape == ref.shape
    expect = np.asarray(ref).copy()
    expect[0, t + 1:] = 0  # everything after the EOS emission pads to 0
    np.testing.assert_array_equal(np.asarray(out), expect)
