"""Fused multi-step decode (models/generate.py ``decode_rounds`` +
serving/engine.py ``decode_rounds > 1``, docs §5.2e): the while_loop
round program must be INVISIBLE in the tokens — fused(k=8) ==
unfused(k=1) == single-request generate() across slot reuse, EOS
inside a round, deadline expiry at a round boundary, mid-round
admission, and SPMD meshes — while the
fused engine compiles exactly ONE extra program (and the k=1 path
compiles none)."""

import dataclasses
import threading
import time

import numpy as np
import pytest

from kubeflow_tpu.serving.errors import DeadlineExceeded
from kubeflow_tpu.testing import faults

SEED = 20260730
VOCAB, PROMPT_LEN, NEW_TOKENS = 128, 8, 12


@pytest.fixture(scope="module")
def engine_model():
    """The same tiny LM config test_lm_serving's engines run, built
    directly (no export/ModelServer round trip — the engines take
    cfg/params/decode, and the full-suite jit cache already holds this
    config's generate() programs): yields (spec, None) in the
    engine_spec shape."""
    import jax
    from flax import linen as nn

    from kubeflow_tpu.models.generate import DecodeConfig
    from kubeflow_tpu.models.transformer import Transformer
    from kubeflow_tpu.serving.loaders import _model_config

    cfg = _model_config({
        "vocab_size": VOCAB, "d_model": 32, "n_layers": 2, "n_heads": 4,
        "n_kv_heads": 2, "d_ff": 64, "head_dim": 8, "max_seq_len": 64,
        "dtype": "float32"})
    model = Transformer(cfg)
    params = nn.unbox(model.init(
        jax.random.key(SEED), np.zeros((1, PROMPT_LEN), np.int32))
        ["params"])
    decode = DecodeConfig(max_new_tokens=NEW_TOKENS, temperature=0.0)
    yield {"cfg": cfg, "params": params, "decode": decode}, None


def _counting_proxy(fn, compiles, key):
    """Each .lower() call — exactly one XLA compilation in the
    AOT-disciplined engine — bumps ``compiles[key]``."""
    class _Proxy:
        def lower(self, *a, **kw):
            compiles[key] += 1
            return fn.lower(*a, **kw)

        def __call__(self, *a, **kw):
            return fn(*a, **kw)

    return _Proxy()


def _reference_rows(spec, prompts, news, decode=None):
    """Single-request generate() goldens truncated to each request's
    budget (greedy is prefix-stable)."""
    from kubeflow_tpu.models.generate import generate

    rows = []
    for prompt, new in zip(prompts, news):
        out, _ = generate(spec["cfg"], spec["params"],
                          np.asarray(prompt, np.int32)[None],
                          decode or spec["decode"])
        rows.append(np.asarray(out)[0, :len(prompt) + new].tolist())
    return rows


def _run_engine(spec, prompts, news, *, decode_rounds, slots=3,
                decode=None, name="test-fused", **kw):
    from kubeflow_tpu.serving.engine import DecodeEngine

    engine = DecodeEngine(
        spec["cfg"], spec["params"], decode or spec["decode"],
        slots=slots, prefill_len=16, admit_width=2,
        prefill_chunk_tokens=8, kv_block_tokens=4,
        decode_rounds=decode_rounds,
        name=f"{name}-k{decode_rounds}", **kw)
    try:
        outs = [None] * len(prompts)

        def client(i):
            outs[i] = engine.submit({
                "tokens": np.asarray(prompts[i], np.int32),
                "max_new_tokens": news[i]})

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return outs, engine.stats(), engine.compiled_programs()
    finally:
        engine.close()


class TestFusedDecode:
    def test_fused_matches_generate_slot_reuse_one_extra_program(
            self, engine_model, monkeypatch):
        """The tentpole identity: 9 mixed-length requests through 3
        slots (every slot reused, multi-chunk prefill, mid-round
        admission waves) are token-identical across a round cap of 8,
        a cap of 1, and generate() — and each engine compiles one
        chunked prefill and one round program (whose adaptive widths
        all ride the same executable)."""
        from kubeflow_tpu.models import generate as gen_mod

        compiles = {"chunked_prefill": 0, "decode_rounds": 0}
        for attr, key in (("prefill_chunk_into_slot", "chunked_prefill"),
                          ("decode_rounds", "decode_rounds")):
            monkeypatch.setattr(gen_mod, attr, _counting_proxy(
                getattr(gen_mod, attr), compiles, key))

        spec, _ = engine_model
        rng = np.random.RandomState(SEED)
        lens = [3, 9, 16, 2, 9, 16, 3, 16, 2]
        news = [12, 6, 3, 8, 12, 4, 10, 5, 12]
        prompts = [rng.randint(1, VOCAB, size=(n,)).tolist()
                   for n in lens]
        want = _reference_rows(spec, prompts, news)

        fused_outs, fused_stats, fused_programs = _run_engine(
            spec, prompts, news, decode_rounds=8)
        plain_outs, plain_stats, plain_programs = _run_engine(
            spec, prompts, news, decode_rounds=1)
        for i in range(len(prompts)):
            got_f = np.asarray(fused_outs[i]["tokens"])[0].tolist()
            got_p = np.asarray(plain_outs[i]["tokens"])[0].tolist()
            assert got_f == want[i], (
                f"fused request {i} (len {lens[i]}, budget {news[i]}) "
                "drifted from single-request generate()")
            assert got_p == want[i], (
                f"k=1 request {i} drifted from generate()")

        # Fused rounds really ran, and the round-width accounting
        # surfaced through stats.
        assert fused_stats["decode_rounds"] == 8
        assert fused_stats["fused_rounds"] > 0
        assert fused_stats["steps_per_round_p50"] >= 1
        assert fused_stats["steps_per_round_p99"] \
            >= fused_stats["steps_per_round_p50"]
        assert fused_stats["fused_steps_wasted"] >= 0
        assert fused_stats["tokens"] == sum(news)
        assert fused_stats["active_slots"] == 0
        assert fused_stats["in_flight_requests"] == 0

        # A cap of 1 is the same program run one step a dispatch.
        assert plain_stats["steps_per_round_p99"] == 1
        assert plain_stats["fused_rounds"] == plain_stats["steps"]

        assert compiles == {"chunked_prefill": 2, "decode_rounds": 2}
        assert fused_programs == plain_programs == {
            "chunked_prefill": 1, "decode_rounds": 1}

    def test_eos_inside_round_matches_generate(self, engine_model):
        """A slot whose EOS lands mid-round freezes on device; the
        drain must deliver exactly generate()'s tokens up to and
        including EOS and the slot must come back."""
        from kubeflow_tpu.models.generate import generate
        from kubeflow_tpu.serving.engine import DecodeEngine

        spec, _ = engine_model
        rng = np.random.RandomState(SEED + 1)
        decode = dataclasses.replace(spec["decode"], eos_token=5)
        prompts = [rng.randint(1, VOCAB, size=(n,)).tolist()
                   for n in (3, 9, 16)]
        engine = DecodeEngine(spec["cfg"], spec["params"], decode,
                              slots=2, prefill_len=16, decode_rounds=8,
                              name="fused-eos")
        try:
            for prompt in prompts:
                out = engine.submit(
                    {"tokens": np.asarray(prompt, np.int32)})
                got = np.asarray(out["tokens"])[0, len(prompt):].tolist()
                ref, _ = generate(spec["cfg"], spec["params"],
                                  np.asarray(prompt, np.int32)[None],
                                  decode)
                ref = np.asarray(ref)[0, len(prompt):].tolist()
                if 5 in ref:
                    ref = ref[:ref.index(5) + 1]
                assert got == ref
            assert engine.stats()["active_slots"] == 0
        finally:
            engine.close()

    def test_deadline_expiry_at_round_boundary_frees_slot(
            self, engine_model):
        """Deadline enforcement under fused rounds is round-granular
        (§5.2e): a request expiring while a round is in flight is
        retired at the next boundary — DeadlineExceeded to the client,
        slot reclaimed for a successor whose tokens match generate()."""
        from kubeflow_tpu.serving.engine import DecodeEngine

        spec, _ = engine_model
        rng = np.random.RandomState(SEED)
        prompt_c = rng.randint(1, VOCAB, size=(6,)).tolist()
        prompt_a = rng.randint(1, VOCAB, size=(5,)).tolist()
        prompt_b = rng.randint(1, VOCAB, size=(7,)).tolist()
        # One fused round costs >= 200 ms (the injected step sleep
        # fires once per DISPATCH); A's 100 ms deadline expires during
        # the first round it could ride, so the boundary sweep must
        # retire it — its budget (12 tokens > 8-wide round) guarantees
        # it cannot complete inside one round.
        with faults.injected("seed=1;engine.step:sleep=0.2"):
            engine = DecodeEngine(spec["cfg"], spec["params"],
                                  spec["decode"], slots=2,
                                  prefill_len=16, decode_rounds=8,
                                  name="fused-dl")
            outs: dict = {}

            def client(key, prompt, deadline=None):
                try:
                    outs[key] = engine.submit(
                        {"tokens": np.asarray(prompt, np.int32)},
                        deadline=deadline)
                except Exception as exc:  # noqa: BLE001 — the point
                    outs[key] = exc

            try:
                t_c = threading.Thread(
                    target=client, args=("c", prompt_c))
                t_c.start()
                t_a = threading.Thread(
                    target=client, args=("a", prompt_a,
                                         faults.monotonic() + 0.1))
                t_a.start()
                t_a.join(timeout=60)
                assert isinstance(outs["a"], DeadlineExceeded), outs["a"]
                # B admitted into A's reclaimed slot while C decodes.
                client("b", prompt_b)
                t_c.join(timeout=60)
                stats = engine.stats()
                assert stats["deadline_expired"] == 1
                assert stats["in_flight_requests"] == 0
            finally:
                engine.close()
        want = _reference_rows(spec, [prompt_c, prompt_b],
                               [NEW_TOKENS, NEW_TOKENS])
        for key, ref in (("c", want[0]), ("b", want[1])):
            got = np.asarray(outs[key]["tokens"])[0].tolist()
            assert got == ref, (
                f"request {key!r} drifted after round-boundary expiry")

    def test_mid_round_admission_joins_at_boundary(self, engine_model):
        """A request arriving while a fused round is in flight joins
        at the next boundary and decodes identically to generate()."""
        from kubeflow_tpu.serving.engine import DecodeEngine

        spec, _ = engine_model
        rng = np.random.RandomState(SEED + 3)
        prompt_a = rng.randint(1, VOCAB, size=(9,)).tolist()
        prompt_b = rng.randint(1, VOCAB, size=(4,)).tolist()
        want = _reference_rows(spec, [prompt_a, prompt_b],
                               [NEW_TOKENS, NEW_TOKENS])
        engine = DecodeEngine(spec["cfg"], spec["params"],
                              spec["decode"], slots=2, prefill_len=16,
                              decode_rounds=8, name="fused-admit")
        try:
            outs: dict = {}

            def client(key, prompt):
                outs[key] = engine.submit(
                    {"tokens": np.asarray(prompt, np.int32)})

            t_a = threading.Thread(target=client, args=("a", prompt_a))
            t_a.start()
            time.sleep(0.02)  # A is mid-generation when B arrives
            client("b", prompt_b)
            t_a.join(timeout=60)
            for key, ref in (("a", want[0]), ("b", want[1])):
                got = np.asarray(outs[key]["tokens"])[0].tolist()
                assert got == ref, f"request {key!r} drifted"
        finally:
            engine.close()

    @pytest.mark.parametrize("tensor", [2])
    def test_mesh_fused_identity(self, engine_model, tensor):
        """Decode rounds compile SPMD under the serving mesh:
        greedy identity holds at mesh 2 (the
        conftest forces an 8-device CPU host platform; the mesh-1 /
        single-device fused path is every other test in this file)."""
        from kubeflow_tpu.serving import sharding
        from kubeflow_tpu.serving.engine import DecodeEngine

        spec, _ = engine_model
        rng = np.random.RandomState(SEED + 5)
        prompts = [rng.randint(1, VOCAB, size=(n,)).tolist()
                   for n in (8, 5, 11)]
        want = _reference_rows(spec, prompts, [NEW_TOKENS] * 3)
        mesh = sharding.build_mesh({"tensor": tensor})
        engine = DecodeEngine(spec["cfg"], spec["params"],
                              spec["decode"], slots=2, prefill_len=16,
                              kv_block_tokens=4, decode_rounds=8,
                              mesh=mesh, name=f"fused-mesh{tensor}")
        try:
            for i, prompt in enumerate(prompts):
                got = engine.submit(
                    {"tokens": np.asarray(prompt, np.int32)}
                )["tokens"][0].tolist()
                assert got == want[i], (
                    f"mesh={tensor} fused decode diverged on {i}")
            stats = engine.stats()
            assert stats["mesh_devices"] == max(1, tensor)
            assert stats["fused_rounds"] > 0
            assert engine.compiled_programs()["decode_rounds"] == 1
        finally:
            engine.close()

    def test_fault_inside_fused_round_aborts_cleanly(
            self, engine_model, monkeypatch):
        """A device fault inside a fused round (seeded at the
        engine.step chaos site, which _dispatch_round fires per dispatch)
        must error EVERY waiter — no hung client, no wedged loop."""
        from kubeflow_tpu.models import generate as gen_mod
        from kubeflow_tpu.serving.engine import DecodeEngine

        real = gen_mod.decode_rounds
        calls = {"n": 0}

        class _DiesOnSecondRound:
            def lower(self, *a, **kw):
                lowered = real.lower(*a, **kw)

                class _Compiled:
                    def __init__(self):
                        self.exe = lowered.compile()

                    def __getattr__(self, name):  # memory_analysis
                        return getattr(self.exe, name)

                    def __call__(self, *ra, **rkw):
                        calls["n"] += 1
                        if calls["n"] >= 2:
                            raise RuntimeError("device died")
                        return self.exe(*ra, **rkw)

                class _Lowered:
                    compile = staticmethod(_Compiled)

                return _Lowered()

        monkeypatch.setattr(gen_mod, "decode_rounds",
                            _DiesOnSecondRound())
        spec, _ = engine_model
        engine = DecodeEngine(spec["cfg"], spec["params"],
                              spec["decode"], slots=2, prefill_len=16,
                              decode_rounds=4, name="fused-abort")
        outs: dict = {}

        def client(i, new):
            try:
                outs[i] = engine.submit({
                    "tokens": np.arange(1, 5, dtype=np.int32),
                    "max_new_tokens": new})
            except Exception as exc:  # noqa: BLE001 — the point
                outs[i] = exc

        threads = [threading.Thread(target=client, args=a)
                   for a in ((0, 12), (1, 12))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert not any(t.is_alive() for t in threads), (
            "a client hung after the fused loop died")
        assert len(outs) == 2  # every waiter resolved (result or error)
        assert calls["n"] == 2
        assert [type(outs[i]) for i in (0, 1)] == [RuntimeError] * 2
        engine.close()


def _synchronous(monkeypatch):
    """Every engine built from here on reads a round before it
    dispatches the next: the loop as it was before PR 49, the same code
    with ``_another_round_follows`` answering no."""
    from kubeflow_tpu.serving.engine import DecodeEngine

    monkeypatch.setattr(DecodeEngine, "_another_round_follows",
                        lambda self, stopping: False)


def _sparse_stack(kind):
    """(module of the stack's own tests, cfg, params): ``lfm2`` counts
    the experts its rounds touch, ``longcat`` also where its (row,
    choice) pairs fell, ``dotsvlm`` drafts (two tokens a step)."""
    module = pytest.importorskip(f"test_{kind}")
    cfg = module._config()
    return module, cfg, module._params(cfg)


COUNTED = {
    "lfm2": ("experts_touched",),
    "longcat": ("experts_touched", "pairs_held", "pairs_zero",
                "pairs_absent"),
    "dotsvlm": ("experts_touched", "mtp_drafted", "mtp_accepted",
                "mtp_steps"),
}


class TestOneRoundAhead:
    """PR 49: the loop dispatches round N+1 before it reads round N
    wherever another round follows.  Ordering only: no token, counter or
    waiter may tell."""

    @pytest.mark.parametrize("decode_rounds", [1, 4])
    def test_mixed_run_ahead_is_generates_tokens(self, engine_model,
                                                 decode_rounds):
        """Admissions mid-stream, retirements at dispatch, a prefix hit
        and two slots for nine requests (a freed slot is claimed while
        its last round is still unread): every request gets exactly the
        reference generator's tokens, and most rounds were dispatched
        ahead."""
        from kubeflow_tpu.serving.engine import DecodeEngine

        spec, _ = engine_model
        rng = np.random.RandomState(SEED + 49)
        shared = rng.randint(1, VOCAB, size=(8,)).tolist()
        prompts = [rng.randint(1, VOCAB, size=(n,)).tolist()
                   for n in (3, 9, 16, 2, 11, 5, 7)]
        prompts[2:2] = [shared + [17, 3], shared + [5, 9, 11]]
        news = [12, 3, 7, 9, 12, 2, 10, 5, 12]
        want = _reference_rows(spec, prompts, news)
        engine = DecodeEngine(
            spec["cfg"], spec["params"], spec["decode"], slots=2,
            prefill_len=16, admit_width=2, prefill_chunk_tokens=8,
            kv_block_tokens=4, decode_rounds=decode_rounds,
            name=f"ahead-mixed-{decode_rounds}")
        outs = [None] * len(prompts)

        def client(i):
            outs[i] = engine.submit({
                "tokens": np.asarray(prompts[i], np.int32),
                "max_new_tokens": news[i]})

        try:
            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(len(prompts))]
            for t in threads:
                t.start()
                time.sleep(0.004)  # arrivals spread over the rounds
            for t in threads:
                t.join(timeout=60)
            stats = engine.stats()
        finally:
            engine.close()
        for i, ref in enumerate(want):
            got = np.asarray(outs[i]["tokens"])[0].tolist()
            assert got == ref, f"request {i} drifted from generate()"
        assert stats["tokens"] == sum(news)
        assert stats["prefix_hits"] >= 1
        assert stats["in_flight_requests"] == stats["active_slots"] == 0
        assert 0 < stats["rounds_ahead"] < stats["fused_rounds"] \
            <= stats["loop_rounds"]
        if decode_rounds == 1:
            assert stats["rounds_ahead"] >= stats["fused_rounds"] // 2

    @pytest.mark.parametrize("kind", sorted(COUNTED))
    def test_counts_read_after_the_state_moved_on_add_up(
            self, kind, monkeypatch):
        """An expert stack's and a drafting stack's counts are read out
        of a state that the next call has already been handed: served
        one request after another (so that what a round holds does not
        hang on the clients' threads) the tokens and every counter are
        the synchronous loop's, and three requests at once get the
        synchronous loop's tokens."""
        import concurrent.futures as cf

        from kubeflow_tpu.models.generate import DecodeConfig
        from kubeflow_tpu.serving.engine import DecodeEngine

        module, cfg, params = _sparse_stack(kind)
        prompts = [module._tokens(n, seed=70 + n) for n in (20, 70, 9)]
        served = {}
        for ahead in (True, False):
            if not ahead:
                _synchronous(monkeypatch)
            # Rounds of two steps at most: five or more a request.
            engine = DecodeEngine(
                cfg, params, DecodeConfig(max_new_tokens=12,
                                          temperature=0.0),
                slots=3, prefill_len=160, max_len=176,
                prefill_chunk_tokens=64, decode_rounds=2,
                name=f"ahead-{kind}-{int(ahead)}")
            try:
                alone = [np.asarray(engine.submit(
                    {"tokens": p})["tokens"])[0] for p in prompts]
                time.sleep(0.05)  # the last round's accounting
                counted = engine.stats()
                with cf.ThreadPoolExecutor(3) as pool:
                    at_once = list(pool.map(
                        lambda p: np.asarray(engine.submit(
                            {"tokens": p})["tokens"])[0], prompts))
                served[ahead] = (alone, counted, at_once, engine.stats())
            finally:
                engine.close(drain_s=0.0)
        (alone, counted, at_once, after), (s_alone, s_counted, s_at_once,
                                           s_after) = served[True], \
            served[False]
        for got, ref in zip(alone + at_once, s_alone + s_at_once):
            assert np.array_equal(got, ref)
        for key in COUNTED[kind] + ("tokens", "steps"):
            assert counted[key] == s_counted[key], key
            # (The tests' share of longcat's experts holds them all.)
            assert counted[key] > 0 or key == "pairs_absent", key
        assert after["tokens"] == s_after["tokens"]
        assert counted["rounds_ahead"] >= 3 and after["rounds_ahead"] \
            > counted["rounds_ahead"]
        assert s_after["rounds_ahead"] == 0

    def test_a_deadline_expires_with_rounds_unread(self, engine_model):
        """A request whose deadline passes while the loop is a round
        ahead gets DeadlineExceeded, its neighbour and the successor in
        its slot get generate()'s tokens."""
        from kubeflow_tpu.serving.engine import DecodeEngine

        spec, _ = engine_model
        rng = np.random.RandomState(SEED + 50)
        prompt_c, prompt_a, prompt_b = (
            rng.randint(1, VOCAB, size=(n,)).tolist() for n in (6, 5, 7))
        outs: dict = {}
        with faults.injected("seed=1;engine.step:sleep=0.04"):
            engine = DecodeEngine(spec["cfg"], spec["params"],
                                  spec["decode"], slots=2, prefill_len=16,
                                  decode_rounds=1, name="ahead-deadline")

            def client(key, prompt, deadline=None):
                try:
                    outs[key] = engine.submit(
                        {"tokens": np.asarray(prompt, np.int32)},
                        deadline=deadline)
                except Exception as exc:  # noqa: BLE001 — the point
                    outs[key] = exc

            try:
                t_c = threading.Thread(target=client,
                                       args=("c", prompt_c))
                t_c.start()
                # Four or five one-step rounds of the twelve it asks for.
                client("a", prompt_a, faults.monotonic() + 0.2)
                assert isinstance(outs["a"], DeadlineExceeded), outs["a"]
                client("b", prompt_b)
                t_c.join(timeout=60)
                stats = engine.stats()
            finally:
                engine.close()
        assert stats["deadline_expired"] == 1
        assert stats["in_flight_requests"] == 0
        assert stats["rounds_ahead"] >= 8
        want = _reference_rows(spec, [prompt_c, prompt_b],
                               [NEW_TOKENS, NEW_TOKENS])
        for key, ref in (("c", want[0]), ("b", want[1])):
            assert np.asarray(outs[key]["tokens"])[0].tolist() == ref

    def test_a_read_that_fails_with_two_rounds_unread_fails_every_waiter(
            self, engine_model, monkeypatch):
        """The third round's tokens cannot be read; the loop finds out
        after it has dispatched the fourth.  The request the third or
        fourth round retired at dispatch (in no slot any more) and the
        one still live both get the error."""
        from kubeflow_tpu.models import generate as gen_mod
        from kubeflow_tpu.serving.engine import DecodeEngine

        real = gen_mod.decode_rounds
        calls = {"n": 0}

        class _Unreadable:
            def __array__(self, *a, **kw):
                raise RuntimeError("device died")

        class _ThirdRoundUnreadable:
            def lower(self, *a, **kw):
                exe = real.lower(*a, **kw).compile()

                class _Compiled:
                    def __getattr__(self, name):  # memory_analysis
                        return getattr(exe, name)

                    def __call__(self, *ra, **rkw):
                        calls["n"] += 1
                        state, toks, *rest = exe(*ra, **rkw)
                        if calls["n"] == 3:
                            toks = _Unreadable()
                        return (state, toks, *rest)

                class _Lowered:
                    compile = staticmethod(_Compiled)

                return _Lowered()

        monkeypatch.setattr(gen_mod, "decode_rounds",
                            _ThirdRoundUnreadable())
        spec, _ = engine_model
        engine = DecodeEngine(spec["cfg"], spec["params"],
                              spec["decode"], slots=2, prefill_len=16,
                              decode_rounds=1, name="ahead-abort")
        outs: dict = {}

        def client(i, new):
            try:
                outs[i] = engine.submit({
                    "tokens": np.arange(1, 5, dtype=np.int32),
                    "max_new_tokens": new})
            except Exception as exc:  # noqa: BLE001 — the point
                outs[i] = exc

        threads = [threading.Thread(target=client, args=a)
                   for a in ((0, 4), (1, 12))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert not any(t.is_alive() for t in threads), (
            "a client hung after the loop died a round ahead")
        assert calls["n"] == 4  # the fourth was dispatched before the read
        assert [type(outs[i]) for i in (0, 1)] == [RuntimeError] * 2
        assert engine.stats()["in_flight_requests"] == 0
        engine.close()

    @pytest.mark.parametrize("drain_s", [10.0, 0.0])
    def test_close_with_rounds_unread_resolves_every_waiter(
            self, engine_model, drain_s):
        """``close()`` while the loop is a round ahead: given time it
        drains both unread rounds and every request gets its whole
        answer; given none, every waiter is failed at once."""
        from kubeflow_tpu.serving.engine import DecodeEngine

        spec, _ = engine_model
        rng = np.random.RandomState(SEED + 51)
        prompts = [rng.randint(1, VOCAB, size=(n,)).tolist()
                   for n in (4, 9, 6)]
        want = _reference_rows(spec, prompts, [NEW_TOKENS] * 3)
        outs: dict = {}
        with faults.injected("seed=1;engine.step:sleep=0.03"):
            engine = DecodeEngine(spec["cfg"], spec["params"],
                                  spec["decode"], slots=3, prefill_len=16,
                                  decode_rounds=1, name="ahead-close")

            def client(i):
                try:
                    outs[i] = engine.submit(
                        {"tokens": np.asarray(prompts[i], np.int32)})
                except Exception as exc:  # noqa: BLE001 — the point
                    outs[i] = exc

            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(3)]
            for t in threads:
                t.start()
            while engine.stats()["rounds_ahead"] < 3:
                time.sleep(0.005)
            engine.close(drain_s=drain_s)
            for t in threads:
                t.join(timeout=30)
        assert not any(t.is_alive() for t in threads)
        assert len(outs) == 3
        stats = engine.stats()
        assert stats["in_flight_requests"] == 0
        if drain_s:
            for i, ref in enumerate(want):
                assert np.asarray(outs[i]["tokens"])[0].tolist() == ref
        else:
            assert all(isinstance(out, Exception) for out in outs.values())

    @pytest.mark.parametrize("decode_rounds", [1, 4])
    def test_an_eos_engine_runs_ahead_too(self, engine_model,
                                          decode_rounds):
        """``done`` is the device's: a slot an EOS stopped in round N
        rides in N+1 and emits nothing, its request is resolved at N's
        drain, and every answer is generate()'s up to its EOS."""
        from kubeflow_tpu.serving.engine import DecodeEngine

        spec, _ = engine_model
        rng = np.random.RandomState(SEED + 52)
        prompts = [rng.randint(1, VOCAB, size=(n,)).tolist()
                   for n in (3, 9, 16, 5, 11)]
        plain = _reference_rows(spec, prompts, [NEW_TOKENS] * 5)
        # A token that request 0 emits in the middle of its answer.
        eos = plain[0][len(prompts[0]) + 5]
        decode = dataclasses.replace(spec["decode"], eos_token=eos)
        want = []
        for prompt, row in zip(prompts, plain):
            answer = row[len(prompt):]
            if eos in answer:
                answer = answer[:answer.index(eos) + 1]
            want.append(prompt + answer)
        assert len(want[0]) < len(plain[0])
        outs, stats, _ = _run_engine(
            spec, prompts, [NEW_TOKENS] * 5, decode_rounds=decode_rounds,
            slots=2, decode=decode, name="ahead-eos")
        for i, ref in enumerate(want):
            assert np.asarray(outs[i]["tokens"])[0].tolist() == ref
        assert stats["tokens"] == sum(
            len(w) - len(p) for w, p in zip(want, prompts))
        assert stats["rounds_ahead"] >= 3
        assert stats["in_flight_requests"] == stats["active_slots"] == 0


def _with_config(spec, **over):
    """The tiny LM with some fields of its config changed, and weights
    initialised anew for it."""
    import jax
    from flax import linen as nn

    from kubeflow_tpu.models.transformer import Transformer

    cfg = dataclasses.replace(spec["cfg"], **over)
    params = nn.unbox(Transformer(cfg).init(
        jax.random.key(SEED), np.zeros((1, PROMPT_LEN), np.int32))
        ["params"])
    return {"cfg": cfg, "params": params, "decode": spec["decode"]}


class TestPagedKernelChoice:
    """serving/engine.py decides ONCE, from the platform its pool lives
    on, whether decode_rounds attends through
    ops/paged_attention.py; ``decode_kernel_steps`` over ``steps`` is
    the share of decode steps the kernel served."""

    @staticmethod
    def _lane_wide(spec):
        """The tiny LM with heads of 128: the engine takes the kernel
        only where a page's rows fill whole 128-lane tiles."""
        return _with_config(spec, head_dim=128)

    @pytest.mark.parametrize("decode_rounds", [1, 8])
    def test_kernel_engine_matches_plain_engine_and_counts_its_steps(
            self, engine_model, monkeypatch, interpreted_paged_kernel,
            decode_rounds):
        from kubeflow_tpu.runtime.prom import REGISTRY, parse_metrics, \
            sample_value
        from kubeflow_tpu.serving import engine as engine_mod

        spec = self._lane_wide(engine_model[0])
        rng = np.random.RandomState(SEED + 25)
        lens = [3, 9, 16, 2, 9, 16, 3]
        news = [12, 6, 3, 8, 12, 4, 10]
        prompts = [rng.randint(1, VOCAB, size=(n,)).tolist()
                   for n in lens]
        want = _reference_rows(spec, prompts, news)

        # On the CPU the pool's platform says "cpu": the plain path.
        plain_outs, plain_stats, _ = _run_engine(
            spec, prompts, news, decode_rounds=decode_rounds,
            name="test-plain-path")
        assert plain_stats["steps"] > 0
        assert plain_stats["decode_kernel_steps"] == 0

        # The same engine told its pool lives on a TPU (the kernel in
        # the Pallas interpreter: this process has no chip).
        monkeypatch.setattr(engine_mod, "_plain_pool_platform",
                            lambda pool: "tpu")
        name = "test-kernel-path"
        outs, stats, programs = _run_engine(
            spec, prompts, news, decode_rounds=decode_rounds, name=name)
        assert stats["steps"] > 0
        assert stats["decode_kernel_steps"] == stats["steps"]
        assert sample_value(
            parse_metrics(REGISTRY.render()),
            "kft_engine_decode_kernel_steps_total",
            engine=f"{name}-k{decode_rounds}") == stats["steps"]
        assert programs["chunked_prefill"] == 1
        assert programs["decode_rounds"] == 1
        for i in range(len(prompts)):
            got = np.asarray(outs[i]["tokens"])[0].tolist()
            assert got == np.asarray(
                plain_outs[i]["tokens"])[0].tolist() == want[i], i

    def test_narrow_heads_keep_the_view_on_a_tpu(self, engine_model,
                                                 monkeypatch):
        """Heads of 8 (the suite's tiny LM): the chip's compiler would
        refuse the kernel's page copies, so the engine does not choose
        it, whatever the platform."""
        from kubeflow_tpu.serving import engine as engine_mod

        monkeypatch.setattr(engine_mod, "_plain_pool_platform",
                            lambda pool: "tpu")
        spec, _ = engine_model
        _, stats, _ = _run_engine(spec, [[5, 6, 7]], [4], decode_rounds=8,
                                  name="test-narrow-heads")
        assert stats["steps"] > 0 and stats["decode_kernel_steps"] == 0

    def test_int8_pool_keeps_the_view_on_a_tpu(self):
        """An int8 ``QTensor`` pool is not the kernel's: the engine says
        so itself, whatever the platform."""
        import jax.numpy as jnp

        from kubeflow_tpu.ops.quantize import QTensor
        from kubeflow_tpu.serving import engine as engine_mod

        pool = QTensor(jnp.zeros((4, 4, 2, 8), jnp.int8),
                       jnp.zeros((4, 4, 2), jnp.float32), (-1,))
        assert engine_mod._plain_pool_platform(pool) is None
        assert engine_mod._plain_pool_platform(
            jnp.zeros((4, 4, 2, 8))) == "cpu"


# Greedy tokens of the cases below from the PARENT's programs (commit
# c94825e: the pool rode the layer scan as xs / ys), new tokens only, on
# this suite's CPU backend.  The carry form computes the same products in
# the same order, so the tokens are these, bit for bit.
_PARENT_TOKENS = {
    "int8-k1": [
        [98, 98, 98, 98, 98, 98, 27, 27, 27, 27, 27, 27],
        [16, 16, 16, 16, 16, 16],
        [23, 92, 88],
        [2, 73, 43, 43, 25, 113, 113, 102],
        [99, 16, 16, 16, 46, 46, 46, 30, 30, 102, 102, 65],
        [123, 123, 123, 123],
        [126, 126, 102, 102, 98, 48, 102, 98, 98, 98],
    ],
    "int8-k8": [
        [98, 98, 98, 98, 98, 98, 27, 27, 27, 27, 27, 27],
        [16, 16, 16, 16, 16, 16],
        [23, 92, 88],
        [2, 73, 43, 43, 25, 113, 113, 102],
        [99, 16, 16, 16, 46, 46, 46, 30, 30, 102, 102, 65],
        [123, 123, 123, 123],
        [126, 126, 102, 102, 98, 48, 102, 98, 98, 98],
    ],
    "looped": [
        [36, 36, 102, 36, 102, 27, 27, 36, 36, 36, 36, 36],
        [16, 16, 16, 16, 16, 16],
        [53, 123, 9],
        [2, 80, 53, 74, 71, 53, 74, 71],
        [9, 5, 17, 46, 17, 46, 46, 5, 95, 46, 46, 46],
        [45, 45, 45, 45],
        [57, 57, 35, 57, 26, 57, 57, 26, 57, 26],
    ],
    "mesh4": [
        [39, 39, 63, 63, 63, 63, 46, 46, 42, 42, 42, 42],
        [66, 66, 66, 66, 66, 66],
        [108, 108, 57],
        [15, 19, 19, 90, 90, 90, 105, 105],
        [48, 48, 64, 64, 64, 64, 64, 1, 107, 107, 107, 107],
        [114, 114, 114, 2],
        [50, 47, 47, 47, 47, 47, 42, 42, 42, 42],
    ],
    "plain-k1": [
        [98, 98, 98, 98, 98, 98, 27, 27, 27, 27, 27, 27],
        [16, 16, 16, 16, 16, 16],
        [23, 92, 88],
        [2, 73, 43, 43, 25, 113, 113, 102],
        [99, 16, 16, 16, 46, 46, 46, 30, 30, 102, 102, 65],
        [123, 123, 123, 123],
        [126, 126, 102, 102, 98, 48, 102, 98, 98, 98],
    ],
    "plain-k8": [
        [98, 98, 98, 98, 98, 98, 27, 27, 27, 27, 27, 27],
        [16, 16, 16, 16, 16, 16],
        [23, 92, 88],
        [2, 73, 43, 43, 25, 113, 113, 102],
        [99, 16, 16, 16, 46, 46, 46, 30, 30, 102, 102, 65],
        [123, 123, 123, 123],
        [126, 126, 102, 102, 98, 48, 102, 98, 98, 98],
    ],
}


class TestPoolCarriedInPlace:
    """models/generate.py carries the STACKED paged pool through the
    layer scan: a layer scatters its columns at (plane, block, offset)
    and reads its pages by plane.  Invisible in the tokens, for every
    stack that runs the paged programs: plain and int8 pools, rounds
    of one step and of eight, a looped stack, a mesh."""

    CASES = {
        "plain-k1": {"decode_rounds": 1},
        "plain-k8": {"decode_rounds": 8},
        "int8-k1": {"decode_rounds": 1, "kv": "int8"},
        "int8-k8": {"decode_rounds": 8, "kv": "int8"},
        "looped": {"decode_rounds": 8,
                   "cfg": {"loop_steps": 2, "sandwich_norm": True}},
        "mesh4": {"decode_rounds": 8, "tensor": 4,
                  "cfg": {"n_kv_heads": 4}},
    }

    @classmethod
    def serve(cls, spec, case):
        """(new tokens per request, generate()'s, stats) of one case."""
        from kubeflow_tpu.serving import sharding

        case = dict(cls.CASES[case])
        if "cfg" in case:
            spec = _with_config(spec, **case.pop("cfg"))
        decode = dataclasses.replace(
            spec["decode"], kv_cache_dtype=case.pop("kv", "model"))
        rng = np.random.RandomState(SEED + 29)
        lens = [3, 9, 16, 2, 12, 16, 5]
        news = [12, 6, 3, 8, 12, 4, 10]
        prompts = [rng.randint(1, VOCAB, size=(n,)).tolist() for n in lens]
        tensor = case.pop("tensor", 0)
        if tensor:
            case["mesh"] = sharding.build_mesh({"tensor": tensor})
        want = _reference_rows(spec, prompts, news, decode)
        outs, stats, _ = _run_engine(
            spec, prompts, news, decode=decode, name="test-in-place",
            **case)
        got = [np.asarray(o["tokens"])[0].tolist() for o in outs]
        assert [g[:len(p)] for g, p in zip(got, prompts)] == prompts
        return ([g[len(p):] for g, p in zip(got, prompts)],
                [w[len(p):] for w, p in zip(want, prompts)], stats)

    @pytest.mark.parametrize("case", sorted(CASES))
    def test_tokens_are_generates_and_the_parents(self, engine_model,
                                                  case):
        got, want, stats = self.serve(engine_model[0], case)
        assert got == want, "drifted from single-request generate()"
        assert got == _PARENT_TOKENS[case], "drifted from the parent's"
        assert stats["steps"] > 0
        if case == "looped":
            assert stats["kv_planes"] == 4 and stats["fused_rounds"] > 0
        if case == "mesh4":
            assert stats["mesh_devices"] == 4

    @pytest.mark.parametrize("kv", ["model", "int8"])
    def test_parked_and_sentinel_writes_leave_every_plane_as_it_was(
            self, engine_model, kv):
        """A retired slot parks its write past the table span, an
        unallocated logical page holds the sentinel (== pool size): the
        scatter into the stacked pool drops both, in every plane, in
        every program."""
        import jax
        import jax.numpy as jnp

        from kubeflow_tpu.models import generate as gen

        spec, _ = engine_model
        cfg, params = spec["cfg"], spec["params"]
        decode = dataclasses.replace(spec["decode"], kv_cache_dtype=kv)
        slots, nb, bt, mb = 3, 10, 4, 3
        rng = np.random.RandomState(SEED + 33)

        def noise(leaf):
            return jnp.asarray(
                rng.randint(-100, 100, leaf.shape).astype(leaf.dtype))

        def state(**over):
            s = gen.init_paged_state(cfg, slots, nb, bt, kv)
            for side in ("cache_k", "cache_v"):
                s[side] = jax.tree_util.tree_map(noise, s[side])
            return {**s, **{k: jnp.asarray(v) for k, v in over.items()}}

        def pool(s):
            return [np.asarray(leaf) for side in ("cache_k", "cache_v")
                    for leaf in jax.tree_util.tree_leaves(s[side])]

        def unchanged(before, s):
            after = pool(s)
            assert before[0].shape[0] == cfg.kv_planes
            assert all(np.array_equal(a, b)
                       for a, b in zip(before, after))

        tables = jnp.asarray(
            rng.permutation(nb)[:slots * mb].reshape(slots, mb), jnp.int32)
        # Live slots whose next position falls on a page the table does
        # not hold (the sentinel): rounds of one step and of three, and
        # a round of two in which only slot 0 lives, so that each of
        # its steps also parks the retired slots' writes (a round of
        # retired slots alone runs no step).
        live = {"done": np.zeros(slots, bool),
                "lengths": np.full(slots, bt, np.int32),
                "stop_len": np.full(slots, 2 * bt, np.int32)}
        holes = tables.at[:, 1:].set(nb)
        for alive, cap in ((live, 1), ({**live, "done": np.arange(
                slots) > 0}, 2)):
            s = state(**alive)
            before = pool(s)
            s, _, counts, steps = gen.decode_rounds(
                cfg, params, s, decode, 4, holes, jnp.int32(cap))
            assert int(steps) == cap
            assert counts.tolist() == (~alive["done"] * cap).tolist()
            unchanged(before, s)
        s = state(**live)
        before = pool(s)
        s, _, _, steps = gen.decode_rounds(
            cfg, params, s, decode, 4, holes, jnp.int32(3))
        assert int(steps) == 3
        unchanged(before, s)
        # A chunk whose table row holds no page at all.
        s = state()
        before = pool(s)
        s, _ = gen.prefill_chunk_into_slot(
            cfg, params, s, decode, jnp.ones((1, 8), jnp.int32),
            jnp.int32(0), jnp.int32(8), jnp.int32(4), jnp.int32(1),
            jnp.int32(7), jnp.full((1, mb), nb, jnp.int32))
        unchanged(before, s)
        # The control: the same chunk through a real row writes its 8
        # columns into EVERY plane, and nowhere else.
        s = state()
        before = pool(s)
        s, _ = gen.prefill_chunk_into_slot(
            cfg, params, s, decode, jnp.ones((1, 8), jnp.int32),
            jnp.int32(0), jnp.int32(8), jnp.int32(4), jnp.int32(1),
            jnp.int32(7), tables[1:2])
        row = np.asarray(tables[1, :2])
        for b, a in zip(before, pool(s)):
            changed = np.any(
                (a != b).reshape(a.shape[0], a.shape[1], -1), axis=-1)
            assert changed[:, row].all() and changed.sum() \
                == cfg.kv_planes * 2


# What the PARENT's programs gave for TestStackedPairsReadInPlace (commit
# a2e38cf: the layer scan sliced one layer's ``mlp/wi`` [2, e, f] and
# ``attn/wkv`` [2, e, hkv, d] out of the stack and took [0] / [1] of the
# copy), on this suite's CPU backend.  Reading each matrix of the pair
# where it lies feeds the same dots the same operands.
_PARENT_PAIRS = {
    "int8-weights": {
        "first": [62, 127], "round": [[62, 62, 64, 64], [127, 127, 127, 127]],
        "chunk_logits": [
            -0.09711797, 0.01449957, -0.007245621,
            -0.04465917, -0.0009560251, 0.06132012],
        "step_logits": [
            [-0.08817986, -0.05842119, -0.1803328,
             -0.08834793, 0.07048687, 0.1008183],
            [-0.01559237, 0.02670428, 0.118,
             0.2855819, -0.01710831, 0.04240373]],
    },
    "adapters": {
        "first": [62, 127], "round": [[62, 62, 62, 62], [127, 127, 127, 127]],
        "chunk_logits": [
            -0.09201603, -0.002648324, -0.03161668,
            -0.05473974, 0.01275305, 0.05538537],
        "step_logits": [
            [-0.0824753, -0.06748873, -0.1865508,
             -0.09866641, 0.07318876, 0.09117322],
            [-0.01818722, 0.01004753, 0.1023682,
             0.2816063, -0.01172643, 0.03893422]],
    },
    "looped-3x2": {
        "first": [36, 127], "round": [[36, 36, 36, 36], [127, 127, 42, 42]],
        "chunk_logits": [
            0.2088994, -0.1669596, -0.0337302,
            -0.26163, -0.02251597, -0.04253511],
        "step_logits": [
            [0.1727304, -0.1478264, -0.02805489,
             -0.261128, -0.002452213, -0.01993862],
            [-0.1085904, 0.1173044, 0.08726891,
             0.2103384, 0.002443834, -0.08984765]],
    },
}


class TestStackedPairsReadInPlace:
    """models/generate.py hands a layer's ``mlp/wi`` and ``attn/wkv`` to
    their matmuls as two single-matrix reads of the stacked leaf, at
    ``2 * layer + c`` of its first two axes taken as one.  Invisible in
    tokens and logits where no benchmark cell looks: int8 weights (a
    ``QTensor``'s values and scale are indexed in step), an adapter
    stack (its factors keep their own [:, 0] / [:, 1]), and a looped
    stack whose planes outnumber its layers (the pair's index comes from
    ``plane % n_layers``, never from the plane)."""

    CASES = ("int8-weights", "adapters", "looped-3x2")

    @staticmethod
    def build(spec, case):
        from kubeflow_tpu.ops.quantize import quantize_params
        from kubeflow_tpu.serving import adapters

        if case == "looped-3x2":
            spec = _with_config(spec, n_layers=3, loop_steps=2,
                                sandwich_norm=True)
        cfg, params = spec["cfg"], dict(spec["params"])
        if case == "int8-weights":
            params = quantize_params(params)
        if case == "adapters":
            stack = adapters.init_adapter_stack(cfg, 3, 2)  # row 0: base
            for row in (1, 2):
                factors = adapters.random_adapter_factors(cfg, 2, SEED + row)
                for grp, leaves in factors.items():
                    for k, v in leaves.items():
                        stack[grp][k][row] = v
            params["adapters"] = stack
        return cfg, params, spec["decode"]

    @staticmethod
    def run(cfg, params, decode):
        """Two slots by hand through both engine programs: a chunk of 8
        columns each (adapter rows 1 and 2), then a round of 4 steps;
        the logits of a chunk and of a decode step come from the same
        forward the programs wrap."""
        import jax
        import jax.numpy as jnp

        from kubeflow_tpu.models import generate as gen

        slots, nb, bt = 2, 8, 4
        rng = np.random.RandomState(SEED + 31)
        prompts = jnp.asarray(rng.randint(1, VOCAB, size=(slots, 8)),
                              jnp.int32)
        tables = jnp.arange(nb, dtype=jnp.int32).reshape(slots, -1)
        ids = jnp.arange(1, slots + 1, dtype=jnp.int32)
        forward = jax.jit(gen._forward_with_cache, static_argnums=0)

        state = gen.init_paged_state(cfg, slots, nb, bt)
        chunk_logits, _ = forward(
            cfg, params, prompts[:1], (state["cache_k"], state["cache_v"]),
            jnp.int32(0), tables=tables[:1], adapter_ids=ids[:1])
        first = []
        for slot in range(slots):
            state, tok = gen.prefill_chunk_into_slot(
                cfg, params, state, decode, prompts[slot:slot + 1],
                jnp.int32(0), jnp.int32(8 - 2 * slot), jnp.int32(6),
                jnp.int32(slot), jnp.int32(7), tables[slot:slot + 1],
                ids[slot])
            first.append(int(tok[0]))
        step_logits, _ = forward(
            cfg, params, state["last_token"][:, None],
            (state["cache_k"], state["cache_v"]), state["lengths"],
            write_cols=state["lengths"], tables=tables,
            adapter_ids=state["adapter_ids"])
        state, toks, counts, steps = gen.decode_rounds(
            cfg, params, state, decode, 4, tables, jnp.int32(4))
        assert int(steps) == 4
        return {
            "first": first,
            "round": [np.asarray(toks)[s, :int(counts[s])].tolist()
                      for s in range(slots)],
            "chunk_logits": np.asarray(chunk_logits)[0, -1, :6].tolist(),
            "step_logits": np.asarray(step_logits)[:, 0, :6].tolist(),
        }

    @pytest.mark.parametrize("case", CASES)
    def test_tokens_and_logits_are_the_parents(self, engine_model, case):
        got = self.run(*self.build(engine_model[0], case))
        want = _PARENT_PAIRS[case]
        assert got["first"] == want["first"]
        assert got["round"] == want["round"]
        for key in ("chunk_logits", "step_logits"):
            np.testing.assert_allclose(got[key], want[key], rtol=2e-5,
                                       atol=2e-5, err_msg=key)
