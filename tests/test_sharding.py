"""Multi-chip serving: tensor-parallel engine identity + KV handoff.

The conftest forces an 8-device CPU host platform, so meshes of 2 and
4 build hermetically.  The battery the multichip item demands:

  - the sharded engine (params + paged KV pool placed over a
    ``tensor`` mesh, serving/sharding.py) is BIT-IDENTICAL to the
    single-device engine for greedy decode — across plain prompts,
    prefix-cache hits and int8 KV pools;
  - disaggregated handoff (prefill replica exports finished block
    pages, decode replica imports them) equals local prefill at EVERY
    page-coverage cut, i.e. every chunk boundary the import can land
    on;
  - the partition-rule machinery degrades gracefully (non-divisible
    dims replicate, rank mismatches replicate, bad --mesh specs fail
    fast).
"""

import numpy as np
import pytest

SEED = 20260804
VOCAB, NEW_TOKENS = 96, 10


@pytest.fixture(scope="module")
def lm():
    """Tiny LM whose head/kv-head/mlp/vocab dims divide 4, so mesh 2
    AND mesh 4 shard every rule'd dim; yields (cfg, params, decode,
    reference) with reference(prompt) -> full greedy token list."""
    import jax
    from flax import linen as nn

    from kubeflow_tpu.models.generate import DecodeConfig, generate
    from kubeflow_tpu.models.transformer import Transformer
    from kubeflow_tpu.serving.loaders import _model_config

    cfg = _model_config({
        "vocab_size": VOCAB, "d_model": 32, "n_layers": 2,
        "n_heads": 4, "n_kv_heads": 4, "d_ff": 64, "head_dim": 8,
        "max_seq_len": 64, "dtype": "float32"})
    model = Transformer(cfg)
    params = nn.unbox(model.init(
        jax.random.key(SEED), np.zeros((1, 8), np.int32))["params"])
    decode = DecodeConfig(max_new_tokens=NEW_TOKENS, temperature=0.0)
    cache = {}

    def reference(prompt):
        key = np.asarray(prompt, np.int32).tobytes()
        if key not in cache:
            out, _ = generate(cfg, params,
                              np.asarray(prompt, np.int32)[None],
                              decode)
            cache[key] = np.asarray(out)[0].tolist()
        return cache[key]

    return cfg, params, decode, reference


def _prompts():
    rng = np.random.RandomState(SEED)
    return [rng.randint(1, VOCAB, size=(n,)).astype(np.int32)
            for n in (8, 5, 11, 16)]


def _engine(lm, **kw):
    from kubeflow_tpu.serving.engine import DecodeEngine

    cfg, params, decode, _ = lm
    kw.setdefault("slots", 2)
    kw.setdefault("prefill_len", 16)
    kw.setdefault("prefill_chunk_tokens", 4)
    kw.setdefault("kv_block_tokens", 4)
    return DecodeEngine(cfg, params, decode, **kw)


def _mesh(n):
    from kubeflow_tpu.serving import sharding

    return sharding.build_mesh({"tensor": n})


class TestPartitionRules:
    def test_parse_mesh_flag(self):
        from kubeflow_tpu.serving import sharding

        assert sharding.parse_mesh_flag("") == {}
        assert sharding.parse_mesh_flag("tensor=4") == {"tensor": 4}
        with pytest.raises(ValueError, match="axis=N"):
            sharding.parse_mesh_flag("tensor")
        with pytest.raises(ValueError, match="unknown serving mesh"):
            sharding.parse_mesh_flag("fsdp=2")
        with pytest.raises(ValueError, match="not an integer"):
            sharding.parse_mesh_flag("tensor=x")
        with pytest.raises(ValueError, match=">= 1"):
            sharding.parse_mesh_flag("tensor=0")

    def test_build_mesh_sizes(self):
        from kubeflow_tpu.serving import sharding

        assert sharding.build_mesh({}) is None
        assert sharding.build_mesh({"tensor": 1}) is None
        mesh = sharding.build_mesh({"tensor": 4})
        assert mesh is not None and mesh.devices.size == 4
        assert sharding.mesh_devices(mesh) == 4
        assert sharding.mesh_devices(None) == 1
        with pytest.raises(ValueError, match="exceeds"):
            sharding.build_mesh({"tensor": 999})

    def test_rules_map_param_tree(self, lm):
        from jax.sharding import PartitionSpec

        from kubeflow_tpu.serving import sharding

        cfg, params, _, _ = lm
        specs = sharding.match_partition_rules(
            sharding.LM_PARTITION_RULES, params)
        assert specs["layers"]["attn"]["wq"] \
            == PartitionSpec(None, None, "tensor", None)
        assert specs["layers"]["mlp"]["wo"] \
            == PartitionSpec(None, "tensor", None)
        assert specs["embed"] == PartitionSpec("tensor", None)
        # Norm scales fall through to the replicate catch-all.
        assert specs["final_norm"]["scale"] == PartitionSpec()

    def test_non_divisible_dim_degrades_to_replicated(self, lm):
        import jax

        from kubeflow_tpu.serving import sharding

        cfg, params, _, _ = lm
        mesh = _mesh(4)
        # 3 kv-heads do not divide tensor=4: the wkv rule must
        # replicate that dim instead of crashing construction.
        odd = {"layers": {"attn": {
            "wkv": np.zeros((2, 2, 32, 3, 8), np.float32)}}}
        placed = sharding.shard_params(odd, mesh)
        leaf = placed["layers"]["attn"]["wkv"]
        assert leaf.sharding.spec == jax.sharding.PartitionSpec(
            None, None, None, None, None)

    def test_rank_mismatch_replicates(self):
        from jax.sharding import PartitionSpec

        from kubeflow_tpu.serving import sharding

        # A QTensor scale companion rides its values rule at a lower
        # rank: the guard must replicate, not raise.
        tree = {"layers": {"attn": {"wq": np.zeros((4,), np.float32)}}}
        specs = sharding.match_partition_rules(
            sharding.LM_PARTITION_RULES, tree)
        assert specs["layers"]["attn"]["wq"] == PartitionSpec()


class TestShardedEngineIdentity:
    @pytest.mark.parametrize("tensor", [2, 4])
    def test_greedy_identity_and_prefix_hits(self, lm, tensor):
        """Sharded engine == generate() for mixed-length greedy
        prompts, slot reuse included; then a shared-prefix admission
        aliases cached pages and stays identical."""
        _, _, _, reference = lm
        eng = _engine(lm, mesh=_mesh(tensor), name=f"mesh{tensor}")
        try:
            for p in _prompts():
                got = eng.submit({"tokens": p})["tokens"][0].tolist()
                assert got == reference(p), (
                    f"mesh={tensor} diverged for len {p.shape[0]}")
            # Prefix hit: shares the 8-token (2-page) prefix of the
            # 11-token prompt just published.
            p = _prompts()[2]
            out = eng.submit({"tokens": p, "return_timing": True})
            assert out["tokens"][0].tolist() == reference(p)
            assert out["cached_tokens"] == 8
            stats = eng.stats()
            assert stats["mesh_devices"] == tensor
            assert stats["prefix_hits"] >= 1
        finally:
            eng.close()

    def test_int8_kv_identity(self, lm):
        """Sharded int8 pool == single-device int8 pool, token for
        token (int8 tokens may differ from fp tokens — the comparison
        is sharded-vs-single at the SAME quantization)."""
        import dataclasses

        cfg, params, decode, _ = lm
        decode8 = dataclasses.replace(decode, kv_cache_dtype="int8")
        lm8 = (cfg, params, decode8, None)
        single = _engine(lm8, name="int8-single")
        shard = _engine(lm8, mesh=_mesh(2), name="int8-mesh2")
        try:
            for p in _prompts():
                want = single.submit({"tokens": p})["tokens"][0]
                got = shard.submit({"tokens": p})["tokens"][0]
                assert got.tolist() == want.tolist(), (
                    f"int8 sharded diverged for len {p.shape[0]}")
        finally:
            single.close()
            shard.close()

    def test_mesh_gauge_zeroed_on_close(self, lm):
        from kubeflow_tpu.runtime.prom import (
            REGISTRY,
            parse_metrics,
            sample_value,
        )

        eng = _engine(lm, mesh=_mesh(2), name="gauge-mesh")
        parsed = parse_metrics(REGISTRY.render())
        assert sample_value(parsed, "kft_engine_mesh_devices",
                            engine="gauge-mesh") == 2
        eng.close()
        parsed = parse_metrics(REGISTRY.render())
        assert sample_value(parsed, "kft_engine_mesh_devices",
                            engine="gauge-mesh") == 0


class TestKVHandoff:
    def test_import_identity_at_every_chunk_boundary(self, lm):
        """Export once, then import trimmed to EVERY page-coverage cut
        (1..max pages): each lands the resumed chunk schedule at a
        different boundary, and every one must equal the local run."""
        _, _, _, reference = lm
        pre = _engine(lm, name="ho-pre")
        p = _prompts()[3]  # 16 tokens, bt=4 -> up to 3 full pages
        try:
            out = pre.prefill_export({"tokens": p})
            ho = out["kv_handoff"]
            assert ho["tokens_covered"] == 12
            assert ho["k"].shape[1] == 3
            max_pages = ho["k"].shape[1]
            for n in range(1, max_pages + 1):
                cut = {"block_tokens": ho["block_tokens"],
                       "tokens_covered": n * ho["block_tokens"],
                       "k": ho["k"][:, :n], "v": ho["v"][:, :n]}
                dec = _engine(lm, prefix_caching=False,
                              name=f"ho-dec{n}")
                try:
                    got = dec.submit({"tokens": p, "kv_handoff": cut})
                    assert got["tokens"][0].tolist() == reference(p), (
                        f"handoff diverged at {n}-page coverage")
                    stats = dec.stats()
                    assert stats["handoff_pages_in"] == n
                    assert dec.compiled_programs()["kv_import"] == 1
                finally:
                    dec.close()
            assert pre.stats()["handoff_pages_out"] == max_pages
        finally:
            pre.close()

    def test_import_into_sharded_engine(self, lm):
        """Cross-tier AND cross-layout: a single-device prefill
        replica's pages import into a mesh-2 decode replica."""
        _, _, _, reference = lm
        pre = _engine(lm, name="ho-pre-s")
        dec = _engine(lm, mesh=_mesh(2), name="ho-dec-s")
        p = _prompts()[2]
        try:
            ho = pre.prefill_export({"tokens": p})["kv_handoff"]
            got = dec.submit({"tokens": p, "kv_handoff": ho})
            assert got["tokens"][0].tolist() == reference(p)
        finally:
            pre.close()
            dec.close()

    def test_int8_handoff_roundtrip(self, lm):
        import dataclasses

        cfg, params, decode, _ = lm
        lm8 = (cfg, params,
               dataclasses.replace(decode, kv_cache_dtype="int8"),
               None)
        pre = _engine(lm8, name="ho8-pre")
        dec = _engine(lm8, name="ho8-dec")
        ctl = _engine(lm8, name="ho8-ctl")
        p = _prompts()[2]
        try:
            want = ctl.submit({"tokens": p})["tokens"][0].tolist()
            ho = pre.prefill_export({"tokens": p})["kv_handoff"]
            assert isinstance(ho["k"], dict)  # values + scale
            got = dec.submit({"tokens": p, "kv_handoff": ho})
            assert got["tokens"][0].tolist() == want
        finally:
            pre.close()
            dec.close()
            ctl.close()

    def test_geometry_and_dtype_mismatches_are_typed(self, lm):
        pre = _engine(lm, name="ho-err-pre")
        dec = _engine(lm, kv_block_tokens=8, name="ho-err-dec")
        p = _prompts()[3]
        try:
            ho = pre.prefill_export({"tokens": p})["kv_handoff"]
            with pytest.raises(ValueError, match="block_tokens"):
                dec.submit({"tokens": p, "kv_handoff": ho})
            with pytest.raises(ValueError, match="quantized"):
                pre.submit({"tokens": p, "kv_handoff": {
                    "block_tokens": 4,
                    "k": {"values": np.zeros((2, 1, 4, 4, 8), np.int8),
                          "scale": np.zeros((2, 1, 4, 4), np.float32)},
                    "v": {"values": np.zeros((2, 1, 4, 4, 8), np.int8),
                          "scale": np.zeros((2, 1, 4, 4),
                                            np.float32)}}})
            with pytest.raises(ValueError, match="pages"):
                pre.submit({"tokens": p, "kv_handoff": {
                    "block_tokens": 4,
                    "k": np.zeros((2, 1, 4, 9, 8), np.float32),
                    "v": np.zeros((2, 1, 4, 9, 8), np.float32)}})
        finally:
            pre.close()
            dec.close()

    def test_short_prompt_exports_nothing(self, lm):
        """A prompt under one full page (limit = len - 1) has no
        exportable pages: the payload is absent and the caller falls
        back to the untiered path."""
        pre = _engine(lm, name="ho-short")
        try:
            out = pre.prefill_export(
                {"tokens": np.asarray([3, 5, 9], np.int32)})
            assert "kv_handoff" not in out
        finally:
            pre.close()

    def test_wire_codec_roundtrip(self, lm):
        from kubeflow_tpu.serving.http import (
            decode_kv_handoff,
            encode_kv_handoff,
        )

        pre = _engine(lm, name="ho-wire")
        p = _prompts()[3]
        try:
            ho = pre.prefill_export({"tokens": p})["kv_handoff"]
            wire = encode_kv_handoff(ho)
            assert isinstance(wire["k"]["b64"], str)
            back = decode_kv_handoff(wire)
            np.testing.assert_array_equal(back["k"], ho["k"])
            np.testing.assert_array_equal(back["v"], ho["v"])
            assert back["block_tokens"] == ho["block_tokens"]
            with pytest.raises(ValueError):
                decode_kv_handoff({"block_tokens": 4, "k": "junk",
                                   "v": "junk"})
        finally:
            pre.close()

    def test_handoff_fault_site_fires(self, lm):
        from kubeflow_tpu.testing import faults

        pre = _engine(lm, name="ho-fault")
        p = _prompts()[3]
        try:
            inj = faults.parse("engine.kv_handoff:raise")
            faults.install(inj)
            try:
                with pytest.raises(Exception):
                    pre.prefill_export({"tokens": p})
            finally:
                faults.install(None)
            assert inj.fired("engine.kv_handoff") >= 1
        finally:
            pre.close()
