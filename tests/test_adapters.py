"""Adapter-array multi-model serving (§5.11): stacked per-tenant
deltas, one SPMD program, co-batched variants.

The contract under test, layer by layer:

  - REGISTRY: bounded slots, digest-verified load, LRU eviction of
    IDLE adapters only (in-flight pins are untouchable), a per-adapter
    breaker so a corrupt artifact can't hot-loop the loader while the
    last-good revision keeps serving, typed 404/429 sheds.
  - ENGINE IDENTITY: a mixed-adapter continuous batch is bit-identical
    to per-adapter sequential runs — through plain decode, adapter-
    scoped prefix-cache hits and a tensor mesh —
    while ``compiled_programs()`` never grows a per-adapter entry.
  - WIRE: ``model@adapter`` resolves through ModelServer to the engine
    (predict + streaming), unknown adapters shed 404, and a request
    naming an adapter can never silently fall through to base weights.

Heavy combined sweeps carry ``slow``; every contract keeps a cheap
tier-1 sibling.
"""

import threading

import numpy as np
import pytest

SEED = 20260807
VOCAB, NEW_TOKENS = 96, 10
RANK = 4


# ---------------------------------------------------------------------------
# fixtures


@pytest.fixture(scope="module")
def lm():
    """Tiny LM (dims divide tensor=2) + single-request greedy
    reference for BASE traffic; adapter references come from
    sequential engine runs (generate() has no adapter surface)."""
    import jax
    from flax import linen as nn

    from kubeflow_tpu.models.generate import DecodeConfig, generate
    from kubeflow_tpu.models.transformer import Transformer
    from kubeflow_tpu.serving.loaders import _model_config

    cfg = _model_config({
        "vocab_size": VOCAB, "d_model": 32, "n_layers": 2,
        "n_heads": 4, "n_kv_heads": 2, "d_ff": 64, "head_dim": 8,
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


def _cfg():
    from kubeflow_tpu.serving.loaders import _model_config

    return _model_config({
        "vocab_size": VOCAB, "d_model": 32, "n_layers": 2,
        "n_heads": 4, "n_kv_heads": 2, "d_ff": 64, "head_dim": 8,
        "max_seq_len": 64, "dtype": "float32"})


def _factors(cfg, seed):
    from kubeflow_tpu.serving.adapters import random_adapter_factors

    # scale=0.5: large enough that the delta flips greedy argmax on a
    # 32-dim toy model — a variant that decodes base's exact tokens
    # would make every identity assertion vacuous.
    return random_adapter_factors(cfg, RANK, seed, scale=0.5)


def _registry(cfg, names=("alpha", "beta"), **kw):
    from kubeflow_tpu.serving.adapters import AdapterRegistry

    kw.setdefault("slots", 4)
    kw.setdefault("rank", RANK)
    reg = AdapterRegistry(cfg, **kw)
    for i, name in enumerate(names):
        reg.put(name, _factors(cfg, SEED + 100 + i))
    return reg


def _engine(lm, **kw):
    from kubeflow_tpu.serving.engine import DecodeEngine

    cfg, params, decode, _ = lm
    kw.setdefault("slots", 3)
    kw.setdefault("prefill_len", 16)
    kw.setdefault("prefill_chunk_tokens", 4)
    kw.setdefault("kv_block_tokens", 4)
    return DecodeEngine(cfg, dict(params), decode, **kw)


def _prompts(n=4, seed_off=0):
    rng = np.random.RandomState(SEED + seed_off)
    return [rng.randint(1, VOCAB, size=(k,)).astype(np.int32)
            for k in (8, 5, 11, 16, 3, 9)[:n]]


def _sequential_refs(lm, workload, **engine_kw):
    """Per-adapter sequential goldens: ONE request in flight at a
    time on a fresh engine — the baseline co-batching must match."""
    engine_kw.setdefault("adapters", _registry(lm[0]))
    engine_kw.setdefault("name", "ad-seq-ref")
    eng = _engine(lm, **engine_kw)
    try:
        refs = []
        for adapter, prompt, new in workload:
            req = {"tokens": prompt, "max_new_tokens": new}
            if adapter:
                req["adapter"] = adapter
            refs.append(eng.submit(req)["tokens"][0].tolist())
        return refs
    finally:
        eng.close()


def _counting_proxy(fn, compiles, key):
    class _Proxy:
        def lower(self, *a, **kw):
            compiles[key] += 1
            return fn.lower(*a, **kw)

        def __call__(self, *a, **kw):
            return fn(*a, **kw)

    return _Proxy()


def _mixed_workload(n_each=2):
    prompts = _prompts(6, seed_off=3)
    workload = []
    for i, adapter in enumerate((None, "alpha", "beta") * n_each):
        workload.append((adapter, prompts[i % len(prompts)],
                         3 + (i % 3) * 3))
    return workload


def _run_concurrent(eng, workload):
    outs = [None] * len(workload)

    def client(i):
        adapter, prompt, new = workload[i]
        req = {"tokens": prompt, "max_new_tokens": new}
        if adapter:
            req["adapter"] = adapter
        try:
            outs[i] = eng.submit(req)["tokens"][0].tolist()
        except Exception as exc:  # noqa: BLE001 — surfaced by assert
            outs[i] = exc
    threads = [threading.Thread(target=client, args=(i,))
               for i in range(len(workload))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    return outs


# ---------------------------------------------------------------------------
# host side: registry, artifacts, breaker


class TestAdapterRegistry:
    def test_split_model_adapter(self):
        from kubeflow_tpu.serving.adapters import split_model_adapter

        assert split_model_adapter("lm") == ("lm", None)
        assert split_model_adapter("lm@t1") == ("lm", "t1")
        assert split_model_adapter("lm@") == ("lm", None)

    def test_stack_shapes_base_row_zero(self):
        from kubeflow_tpu.serving.adapters import init_adapter_stack

        cfg = _cfg()
        stack = init_adapter_stack(cfg, rows=3, rank=RANK)
        wq_a = stack["attn"]["wq_a"]
        assert wq_a.shape == (3, cfg.n_layers, cfg.d_model, RANK)
        assert stack["mlp"]["wi_b"].shape == (
            3, cfg.n_layers, 2, RANK, cfg.d_ff)
        reg = _registry(cfg, names=("alpha",))
        stack, version = reg.stack_snapshot()
        assert version >= 1
        for leaves in stack.values():
            for arr in leaves.values():
                assert not np.any(arr[0])      # base row stays zero
        assert any(np.any(arr[1]) for leaves in stack.values()
                   for arr in leaves.values())  # alpha landed in row 1

    def test_save_load_roundtrip_digest_verified(self, tmp_path):
        import json

        from kubeflow_tpu.serving.adapters import (
            factors_digest,
            load_adapter,
            save_adapter,
        )

        cfg = _cfg()
        factors = _factors(cfg, SEED + 1)
        path = str(tmp_path / "t1.npz")
        digest = save_adapter(path, factors)
        assert digest == factors_digest(factors)
        loaded, got = load_adapter(path, cfg, RANK)
        assert got == digest
        np.testing.assert_array_equal(
            loaded["attn"]["wq_a"],
            np.asarray(factors["attn"]["wq_a"], np.float32))
        # Sidecar/content mismatch = torn or tampered artifact: refuse.
        (tmp_path / "t1.npz.json").write_text(
            json.dumps({"digest": "0" * 64}))
        with pytest.raises(ValueError, match="digest mismatch"):
            load_adapter(path, cfg, RANK)
        # Wrong-shaped artifact (e.g. exported at another rank): refuse.
        bad = str(tmp_path / "t2.npz")
        with open(bad, "wb") as f:
            np.savez(f, **{"attn/wq_a": np.zeros((1, 2), np.float32)})
        with pytest.raises(ValueError, match="missing/misshaped"):
            load_adapter(bad, cfg, RANK)

    def test_acquire_pins_release_unpins(self, tmp_path):
        from kubeflow_tpu.serving.adapters import (
            AdapterNotFound,
            AdapterRegistry,
            save_adapter,
        )

        cfg = _cfg()
        save_adapter(str(tmp_path / "a.npz"), _factors(cfg, SEED + 2))
        reg = AdapterRegistry(cfg, slots=2, rank=RANK,
                              directory=str(tmp_path), name="pins")
        idx, digest = reg.acquire("a")
        assert idx == 1 and len(digest) == 64
        assert reg.salt(idx) == bytes.fromhex(digest)
        assert reg.salt(0) == b""
        assert reg.loaded()[0]["pins"] == 1
        idx2, _ = reg.acquire("a")
        assert idx2 == idx
        assert reg.loaded()[0]["pins"] == 2
        reg.release(idx)
        reg.release(idx)
        assert reg.loaded()[0]["pins"] == 0
        assert reg.stats()["adapters_resident"] == 1
        with pytest.raises(AdapterNotFound):
            reg.acquire("ghost")
        # Wire names must not path-traverse out of the directory.
        with pytest.raises(AdapterNotFound):
            reg.acquire("../a")

    def test_lru_evicts_idle_only_all_pinned_sheds(self, tmp_path):
        from kubeflow_tpu.serving.adapters import (
            AdapterRegistry,
            save_adapter,
        )
        from kubeflow_tpu.serving.errors import Overloaded

        cfg = _cfg()
        for i, name in enumerate(("a", "b", "c", "d")):
            save_adapter(str(tmp_path / f"{name}.npz"),
                         _factors(cfg, SEED + 10 + i))
        reg = AdapterRegistry(cfg, slots=2, rank=RANK,
                              directory=str(tmp_path), name="lru")
        ia, _ = reg.acquire("a")            # pinned (in-flight)
        ib, _ = reg.acquire("b")
        reg.release(ib)                     # b idle -> the LRU victim
        ic, _ = reg.acquire("c")
        names = {r["name"] for r in reg.loaded()}
        assert names == {"a", "c"}, (
            "eviction must take the idle adapter, never a pinned one")
        with pytest.raises(Overloaded) as exc:
            reg.acquire("d")                # a and c both pinned
        assert exc.value.retry_after_s > 0
        reg.release(ia)
        reg.release(ic)
        idd, _ = reg.acquire("d")           # idle slot frees up
        assert idd in (ia, ic)

    def test_corrupt_artifact_breaker_last_good_serves(self, tmp_path):
        from kubeflow_tpu.serving.adapters import (
            AdapterRegistry,
            save_adapter,
        )
        from kubeflow_tpu.serving.errors import Overloaded
        from kubeflow_tpu.testing import faults

        cfg = _cfg()
        good = _factors(cfg, SEED + 20)
        save_adapter(str(tmp_path / "a.npz"), good)
        reg = AdapterRegistry(cfg, slots=2, rank=RANK,
                              directory=str(tmp_path), name="breaker")
        with faults.injected("seed=0") as inj:
            idx, digest = reg.acquire("a")
            reg.release(idx)
            assert inj.fired("adapter.load") == 1
            # Corrupt the artifact ON DISK (different bytes -> the
            # registry sees a changed digest and attempts a reload).
            (tmp_path / "a.npz").write_bytes(b"not an npz")
            (tmp_path / "a.npz.json").unlink()
            idx2, digest2 = reg.acquire("a")
            assert (idx2, digest2) == (idx, digest), (
                "last-good revision must keep serving through a "
                "corrupt reload")
            reg.release(idx2)
            assert inj.fired("adapter.load") == 2
            # Breaker open: the next acquire must NOT touch the loader.
            idx3, _ = reg.acquire("a")
            reg.release(idx3)
            assert inj.fired("adapter.load") == 2
            # A never-loaded corrupt adapter sheds typed 429 and the
            # open breaker keeps the loader cold on the retry.
            (tmp_path / "b.npz").write_bytes(b"garbage")
            with pytest.raises(Overloaded):
                reg.acquire("b")
            fired = inj.fired("adapter.load")
            with pytest.raises(Overloaded):
                reg.acquire("b")
            assert inj.fired("adapter.load") == fired
            # Backoff expiry (policy clock) + a repaired artifact:
            # the breaker closes and the load goes through.
            save_adapter(str(tmp_path / "b.npz"),
                         _factors(cfg, SEED + 21))
            inj.advance_clock(600)
            ib, _ = reg.acquire("b")
            reg.release(ib)
            assert {r["name"] for r in reg.loaded()} >= {"b"}

    def test_put_reloads_in_place(self):
        cfg = _cfg()
        reg = _registry(cfg, names=("alpha",))
        idx = reg.put("alpha", _factors(cfg, SEED + 30))
        assert idx == 1                     # same row, new revision
        _, version = reg.stack_snapshot()
        idx2 = reg.put("alpha", _factors(cfg, SEED + 31))
        assert idx2 == idx
        _, version2 = reg.stack_snapshot()
        assert version2 > version


# ---------------------------------------------------------------------------
# device side: co-batched identity, one program set


class TestAdapterEngineIdentity:
    def test_mixed_batch_matches_sequential_no_new_programs(
            self, lm, monkeypatch):
        """Base + alpha + beta co-batched through 3 slots must emit
        exactly the tokens each request gets when it runs ALONE, the
        base rows must equal single-request generate(), the variants
        must genuinely diverge from base — and the whole mixed
        workload compiles the same two programs base-only traffic
        does (the stacked gather is inside them, never beside them)."""
        from kubeflow_tpu.models import generate as gen_mod

        _, _, _, reference = lm
        workload = _mixed_workload()
        want = _sequential_refs(lm, workload)
        # Count compiles only for the co-batched engine under test
        # (the reference engine above did its own, identical, two).
        compiles = {"chunked_prefill": 0, "decode_rounds": 0}
        for attr, key in (("prefill_chunk_into_slot", "chunked_prefill"),
                          ("decode_rounds", "decode_rounds")):
            monkeypatch.setattr(gen_mod, attr, _counting_proxy(
                getattr(gen_mod, attr), compiles, key))
        eng = _engine(lm, adapters=_registry(lm[0]), name="ad-mixed")
        try:
            outs = _run_concurrent(eng, workload)
            for i, (adapter, prompt, new) in enumerate(workload):
                assert outs[i] == want[i], (
                    f"request {i} (adapter={adapter}) diverged from "
                    "its sequential run")
                if adapter is None:
                    assert outs[i] == reference(prompt)[
                        :len(prompt) + new], (
                        "co-batched base row drifted from generate()")
            by_key = {}
            for (adapter, prompt, _), out in zip(workload, outs):
                by_key[(adapter, prompt.tobytes())] = out
            for (adapter, pkey), out in by_key.items():
                if adapter is not None and (None, pkey) in by_key:
                    assert out != by_key[(None, pkey)], (
                        f"adapter {adapter} decoded base's exact "
                        "tokens — the delta never applied")
            stats = eng.stats()
            assert stats["requests"] == len(workload)
            assert stats["adapters"]["adapters_resident"] == 2
        finally:
            eng.close()
        two = {"chunked_prefill": 1, "decode_rounds": 1}
        assert compiles == two
        assert eng.compiled_programs() == two

    def test_prefix_cache_is_adapter_scoped(self, lm):
        """One prompt under base/alpha/beta, twice each, prefix cache
        ON: every rerun must hit ITS OWN adapter's chain and emit the
        cache-off sequential tokens — a cross-adapter alias would
        splice one tenant's KV into another's generation."""
        prompt = _prompts(1, seed_off=9)[0]
        workload = [(a, prompt, NEW_TOKENS)
                    for a in (None, "alpha", "beta")] * 2
        want = _sequential_refs(lm, workload, prefix_caching=False,
                                name="ad-nocache-ref")
        eng = _engine(lm, adapters=_registry(lm[0]),
                      prefix_caching=True, name="ad-scoped")
        try:
            for i, (adapter, _, new) in enumerate(workload):
                req = {"tokens": prompt, "max_new_tokens": new}
                if adapter:
                    req["adapter"] = adapter
                got = eng.submit(req)["tokens"][0].tolist()
                assert got == want[i], (
                    f"round {i} adapter={adapter}: cached pages "
                    "leaked across adapter scopes")
            stats = eng.stats()
            # Round 2 hits each scope's own published chain.
            assert stats["prefix_hits"] >= 3
        finally:
            eng.close()

    def test_mesh2_identity(self, lm):
        """The stacked adapter axis sharded over tensor=2 changes no
        token: mixed traffic equals the unsharded sequential runs."""
        from kubeflow_tpu.serving import sharding

        workload = _mixed_workload(n_each=1)
        want = _sequential_refs(lm, workload, name="ad-mesh-ref")
        eng = _engine(lm, adapters=_registry(lm[0]),
                      mesh=sharding.build_mesh({"tensor": 2}),
                      name="ad-mesh2")
        try:
            outs = _run_concurrent(eng, workload)
            assert outs == want
        finally:
            eng.close()

    @pytest.mark.slow  # ~9s combined sweep; the per-path tests above stay tier-1
    def test_full_sweep_prefix_mesh(self, lm):
        """The heavy combination: prefix cache ON, tensor=2 mesh, 12
        mixed requests over 3 slots with slot reuse and repeated
        prompts — every row equals its sequential twin."""
        from kubeflow_tpu.serving import sharding

        workload = _mixed_workload(n_each=4)
        want = _sequential_refs(lm, workload, name="ad-sweep-ref")
        eng = _engine(lm, adapters=_registry(lm[0]),
                      mesh=sharding.build_mesh({"tensor": 2}),
                      prefix_caching=True, name="ad-sweep")
        try:
            outs = _run_concurrent(eng, workload)
            assert outs == want
        finally:
            eng.close()

    def test_hot_load_evict_under_pinned_traffic(self, lm, tmp_path):
        """Slot pressure with a live pin: loading a third adapter into
        a 2-slot registry must evict the IDLE one, never the pinned
        one, and every accepted request decodes its correct tokens —
        including the re-load of the evicted adapter afterwards."""
        from kubeflow_tpu.serving.adapters import (
            AdapterRegistry,
            save_adapter,
        )

        cfg = lm[0]
        for i, name in enumerate(("alpha", "beta", "gamma")):
            save_adapter(str(tmp_path / f"{name}.npz"),
                         _factors(cfg, SEED + 100 + i))
        prompt = _prompts(1, seed_off=11)[0]
        workload = [(a, prompt, 6)
                    for a in ("alpha", "beta", "gamma", "beta")]
        want = _sequential_refs(
            lm, workload, name="ad-hot-ref",
            adapters=_registry(cfg, names=("alpha", "beta", "gamma")))
        reg = AdapterRegistry(cfg, slots=2, rank=RANK,
                              directory=str(tmp_path), name="ad-hot")
        eng = _engine(lm, adapters=reg, name="ad-hot")
        try:
            assert eng.submit({"tokens": prompt, "max_new_tokens": 6,
                               "adapter": "alpha"}
                              )["tokens"][0].tolist() == want[0]
            assert eng.submit({"tokens": prompt, "max_new_tokens": 6,
                               "adapter": "beta"}
                              )["tokens"][0].tolist() == want[1]
            # Pin alpha (a request mid-generation holds exactly this).
            pin, _ = reg.acquire("alpha")
            assert eng.submit({"tokens": prompt, "max_new_tokens": 6,
                               "adapter": "gamma"}
                              )["tokens"][0].tolist() == want[2]
            assert {r["name"] for r in reg.loaded()} == \
                {"alpha", "gamma"}, "eviction touched the pinned slot"
            reg.release(pin)
            # The evicted adapter hot-reloads on demand, identically.
            assert eng.submit({"tokens": prompt, "max_new_tokens": 6,
                               "adapter": "beta"}
                              )["tokens"][0].tolist() == want[3]
        finally:
            eng.close()

    def test_load_fault_mid_traffic(self, lm, tmp_path):
        """adapter.load raising mid-traffic: the named request sheds
        typed 429, the breaker keeps the loader cold on the retry,
        resident adapters keep serving bit-identically, and after the
        backoff the load goes through."""
        from kubeflow_tpu.serving.adapters import (
            AdapterRegistry,
            save_adapter,
        )
        from kubeflow_tpu.serving.errors import Overloaded
        from kubeflow_tpu.testing import faults

        cfg = lm[0]
        for i, name in enumerate(("alpha", "beta")):
            save_adapter(str(tmp_path / f"{name}.npz"),
                         _factors(cfg, SEED + 100 + i))
        prompt = _prompts(1, seed_off=13)[0]
        workload = [("alpha", prompt, 6), ("beta", prompt, 6)]
        want = _sequential_refs(lm, workload, name="ad-fault-ref")
        reg = AdapterRegistry(cfg, slots=2, rank=RANK,
                              directory=str(tmp_path), name="ad-fault")
        eng = _engine(lm, adapters=reg, name="ad-fault")
        try:
            # Warm alpha before the fault window: the scripted raise
            # must hit beta's cold load, not resident traffic.
            assert eng.submit(
                {"tokens": prompt, "max_new_tokens": 6,
                 "adapter": "alpha"}
            )["tokens"][0].tolist() == want[0]
            with faults.injected("adapter.load:raise*1") as inj:
                with pytest.raises(Overloaded):
                    eng.submit({"tokens": prompt, "max_new_tokens": 6,
                                "adapter": "beta"})
                assert inj.fired("adapter.load") == 1
                # Breaker open: the retry sheds WITHOUT a load attempt.
                with pytest.raises(Overloaded):
                    eng.submit({"tokens": prompt, "max_new_tokens": 6,
                                "adapter": "beta"})
                assert inj.fired("adapter.load") == 1
                # The resident adapter is untouched by the fault.
                assert eng.submit(
                    {"tokens": prompt, "max_new_tokens": 6,
                     "adapter": "alpha"}
                )["tokens"][0].tolist() == want[0]
                inj.advance_clock(600)      # breaker backoff expires
                assert eng.submit(
                    {"tokens": prompt, "max_new_tokens": 6,
                     "adapter": "beta"}
                )["tokens"][0].tolist() == want[1]
        finally:
            eng.close()

    def test_unknown_adapter_and_no_registry_shed_404(self, lm):
        from kubeflow_tpu.serving.adapters import AdapterNotFound

        prompt = _prompts(1)[0]
        bare = _engine(lm, name="ad-bare")
        try:
            with pytest.raises(AdapterNotFound):
                bare.submit({"tokens": prompt, "adapter": "alpha"})
        finally:
            bare.close()
        eng = _engine(lm, adapters=_registry(lm[0]), name="ad-404")
        try:
            with pytest.raises(AdapterNotFound):
                eng.submit({"tokens": prompt, "adapter": "ghost"})
            # The shed left nothing pinned or in flight.
            stats = eng.stats()
            assert stats["in_flight_requests"] == 0
            assert stats["adapters"]["adapters_pinned"] == 0
        finally:
            eng.close()


# ---------------------------------------------------------------------------
# wire: model@adapter through ModelServer


@pytest.fixture(scope="module")
def adapter_server(tmp_path_factory, lm):
    """An exported lm served through the engine batching plane with an
    adapter directory beside it: the full ``model@adapter`` wire."""
    import jax

    from kubeflow_tpu.models.transformer import Transformer
    from kubeflow_tpu.serving.adapters import save_adapter
    from kubeflow_tpu.serving.export import export
    from kubeflow_tpu.serving.main import batcher_factory
    from kubeflow_tpu.serving.model_server import ModelServer

    overrides = {
        "vocab_size": VOCAB, "d_model": 32, "n_layers": 2, "n_heads": 4,
        "n_kv_heads": 2, "d_ff": 64, "head_dim": 8, "max_seq_len": 64,
        "dtype": "float32"}
    model = Transformer(lm[0])
    variables = model.init(
        jax.random.key(SEED), np.zeros((1, 8), np.int32))
    base = tmp_path_factory.mktemp("adapter-models") / "lm"
    export(base, 1, variables,
           loader="kubeflow_tpu.serving.loaders:lm_generate",
           config={"model": overrides,
                   "max_new_tokens": NEW_TOKENS, "temperature": 0.0})
    adir = tmp_path_factory.mktemp("adapters")
    for i, name in enumerate(("alpha", "beta")):
        save_adapter(str(adir / f"{name}.npz"),
                     _factors(lm[0], SEED + 100 + i))
    server = ModelServer()
    server.add_model("lm", str(base))
    server.enable_batching("lm", batcher_factory(
        micro_batch_size=0, batch_timeout_s=0.005, lm_engine=True,
        lm_engine_slots=2, lm_engine_prefill_len=16,
        prefill_chunk_tokens=4, kv_block_tokens=4,
        adapters_dir=str(adir), adapter_slots=4, adapter_rank=RANK))
    yield server
    server.stop()


class TestModelAdapterRouting:
    def test_predict_resolves_adapter_and_matches_engine(
            self, lm, adapter_server):
        prompt = _prompts(1, seed_off=17)[0]
        want = _sequential_refs(
            lm, [("alpha", prompt, NEW_TOKENS),
                 (None, prompt, NEW_TOKENS)], name="ad-wire-ref")
        out = adapter_server.predict(
            "lm@alpha", {"tokens": prompt[None]})
        assert np.asarray(out["tokens"])[0].tolist() == want[0]
        base = adapter_server.predict("lm", {"tokens": prompt[None]})
        assert np.asarray(base["tokens"])[0].tolist() == want[1]
        assert want[0] != want[1]

    def test_unknown_adapter_is_404(self, adapter_server):
        from kubeflow_tpu.serving.adapters import AdapterNotFound

        prompt = _prompts(1)[0]
        with pytest.raises(AdapterNotFound):  # KeyError -> HTTP 404
            adapter_server.predict("lm@ghost",
                                   {"tokens": prompt[None]})
        with pytest.raises(KeyError):
            adapter_server.predict("nope@alpha",
                                   {"tokens": prompt[None]})

    def test_has_model_and_readyz_advertisement(self, adapter_server):
        assert adapter_server.has_model("lm@anything")
        info = adapter_server.adapter_info()
        names = {a["name"] for a in info.get("lm", ())}
        assert "alpha" in names
        digests = {a["digest"] for a in info["lm"]}
        assert all(len(d) == 64 for d in digests)

    def test_generate_stream_carries_adapter(self, lm, adapter_server):
        prompt = _prompts(1, seed_off=19)[0]
        want = _sequential_refs(
            lm, [("beta", prompt, NEW_TOKENS)], name="ad-stream-ref")
        meta, stream = adapter_server.generate_stream(
            "lm@beta", {"tokens": prompt})
        toks = []
        for chunk in stream:
            toks.extend(chunk)
        assert meta["resumable"]
        assert prompt.tolist() + toks == want[0]

    def test_direct_path_never_serves_base_for_adapter(self, lm,
                                                       tmp_path):
        """A model WITHOUT the engine plane must refuse model@adapter
        (404), not silently decode base weights for a tenant."""
        import jax

        from kubeflow_tpu.models.transformer import Transformer
        from kubeflow_tpu.serving.adapters import AdapterNotFound
        from kubeflow_tpu.serving.export import export
        from kubeflow_tpu.serving.model_server import ModelServer

        overrides = {
            "vocab_size": VOCAB, "d_model": 32, "n_layers": 2,
            "n_heads": 4, "n_kv_heads": 2, "d_ff": 64, "head_dim": 8,
            "max_seq_len": 64, "dtype": "float32"}
        model = Transformer(lm[0])
        variables = model.init(
            jax.random.key(SEED), np.zeros((1, 8), np.int32))
        base = tmp_path / "lm"
        export(base, 1, variables,
               loader="kubeflow_tpu.serving.loaders:lm_generate",
               config={"model": overrides, "max_new_tokens": 4,
                       "temperature": 0.0})
        server = ModelServer()
        server.add_model("lm", str(base))
        try:
            prompt = _prompts(1)[0]
            with pytest.raises(AdapterNotFound):
                server.predict("lm@alpha", {"tokens": prompt[None]})
        finally:
            server.stop()
