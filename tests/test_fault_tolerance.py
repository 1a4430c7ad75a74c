"""Serving fault-tolerance layer: per-request deadlines, bounded
admission with load shedding, circuit-broken reloads, readiness +
graceful drain, and the typed-error mapping on both wire faces — all
driven deterministically through the fault-injection harness
(kubeflow_tpu/testing/faults.py) instead of wall-clock luck."""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from kubeflow_tpu.serving.errors import (
    BatcherClosed,
    DeadlineExceeded,
    Overloaded,
)
from kubeflow_tpu.serving.model_server import (
    LoadedModel,
    MicroBatcher,
    ModelServer,
    _ReloadBreaker,
)
from kubeflow_tpu.testing import faults

SEED = 20260803
VOCAB, PROMPT_LEN, NEW_TOKENS = 128, 8, 12


class _GatedPredict:
    """predict() that announces entry and blocks until released — the
    deterministic 'wedged device' for queue-behavior tests."""

    def __init__(self):
        self.entered = threading.Event()
        self.release = threading.Event()
        self.calls = 0

    def __call__(self, inputs):
        self.calls += 1
        self.entered.set()
        assert self.release.wait(timeout=30), "test forgot to release"
        return {"y": np.asarray(inputs["x"])}


class TestBatcherDeadlines:
    def test_expired_on_arrival_raises_immediately(self):
        mb = MicroBatcher(lambda i: i, batch_timeout_s=10.0)
        try:
            with pytest.raises(DeadlineExceeded):
                mb.submit({"x": np.zeros((1, 2))},
                          deadline=faults.monotonic() - 0.1)
            assert mb.stats()["deadline_expired"] == 1
        finally:
            mb.close()

    def test_queued_entry_expires_before_batch_window(self):
        """A request deadline preempts the (much longer) batch window:
        the entry is failed at its own deadline, not dispatched 10 s
        later."""
        mb = MicroBatcher(lambda i: {"y": i["x"]}, max_batch_size=4,
                          batch_timeout_s=10.0, name="ft-queue-dl")
        try:
            t0 = time.monotonic()
            with pytest.raises(DeadlineExceeded):
                mb.submit({"x": np.zeros((1, 2))},
                          deadline=faults.monotonic() + 0.1)
            waited = time.monotonic() - t0
            assert waited < 5.0, (
                f"expiry took {waited:.1f}s — the batch window was not "
                "preempted")
            stats = mb.stats()
            assert stats["deadline_expired"] == 1
            assert stats["queue_depth"] == 0
        finally:
            mb.close()

    def test_unexpired_entries_unaffected_by_sweep(self):
        mb = MicroBatcher(lambda i: {"y": np.asarray(i["x"]) * 2},
                          max_batch_size=2, batch_timeout_s=0.02)
        try:
            out = mb.submit({"x": np.ones((1, 2))},
                            deadline=faults.monotonic() + 30.0)
            np.testing.assert_allclose(out["y"], 2 * np.ones((1, 2)))
            assert mb.stats()["deadline_expired"] == 0
        finally:
            mb.close()


class TestBatcherOverload:
    def test_queue_cap_sheds_with_retry_after(self):
        gate = _GatedPredict()
        mb = MicroBatcher(gate, max_batch_size=1, batch_timeout_s=0.001,
                          allowed_batch_sizes=[1], in_flight=1,
                          max_queue_depth=1, overload_retry_after_s=2.5,
                          name="ft-shed")
        results = {}

        def worker(i):
            try:
                results[i] = mb.submit({"x": np.full((1, 1), float(i))})
            except Exception as exc:  # noqa: BLE001 — the point
                results[i] = exc

        try:
            t_a = threading.Thread(target=worker, args=(0,))
            t_a.start()
            assert gate.entered.wait(timeout=10)  # A is IN the device
            t_b = threading.Thread(target=worker, args=(1,))
            t_b.start()
            deadline = time.monotonic() + 10
            while mb.stats()["queue_depth"] < 1:  # B holds the seat
                assert time.monotonic() < deadline
                time.sleep(0.005)
            with pytest.raises(Overloaded) as err:
                mb.submit({"x": np.full((1, 1), 2.0)})
            assert err.value.retry_after_s == 2.5
            gate.release.set()
            t_a.join(timeout=10)
            t_b.join(timeout=10)
            # The accepted requests completed despite the shed.
            assert not isinstance(results[0], Exception)
            assert not isinstance(results[1], Exception)
            assert mb.stats()["shed"] == 1
        finally:
            gate.release.set()
            mb.close()


class TestCloseFailsQueuedEntries:
    """Satellite regression: close() must resolve EVERY queued entry
    with BatcherClosed — including requests already queued when close
    begins — while dispatched batches complete; no path may hang."""

    def test_queued_entries_raise_dispatched_completes(self):
        gate = _GatedPredict()
        mb = MicroBatcher(gate, max_batch_size=1, batch_timeout_s=0.001,
                          allowed_batch_sizes=[1], in_flight=1,
                          name="ft-close")
        results = {}

        def worker(i):
            try:
                results[i] = mb.submit({"x": np.full((1, 1), float(i))})
            except Exception as exc:  # noqa: BLE001 — the point
                results[i] = exc

        threads = [threading.Thread(target=worker, args=(0,))]
        threads[0].start()
        assert gate.entered.wait(timeout=10)  # 0 is mid-dispatch
        for i in (1, 2):
            t = threading.Thread(target=worker, args=(i,))
            t.start()
            threads.append(t)
        deadline = time.monotonic() + 10
        while mb.stats()["queue_depth"] < 2:
            assert time.monotonic() < deadline
            time.sleep(0.005)

        closer = threading.Thread(target=mb.close)
        closer.start()
        # Queued entries resolve promptly — close() must not hold them
        # hostage to the wedged in-flight batch.
        for i in (1, 2):
            threads[i].join(timeout=10)
            assert not threads[i].is_alive(), f"request {i} hung"
            assert isinstance(results[i], BatcherClosed), results[i]
        gate.release.set()
        threads[0].join(timeout=10)
        closer.join(timeout=10)
        assert not closer.is_alive()
        # The dispatched batch kept its result.
        assert not isinstance(results[0], Exception), results[0]

    def test_bucketed_submit_after_close_raises(self):
        from kubeflow_tpu.serving.model_server import BucketedLMBatcher

        bmb = BucketedLMBatcher(lambda i: i, buckets=[8],
                                name="ft-bucket-closed")
        bmb.close()
        with pytest.raises(BatcherClosed):
            bmb.submit({"tokens": np.ones((1, 4), np.int32)})

    def test_closed_batcher_falls_back_through_model_server(self):
        """The ModelServer contract that makes fail-at-close safe: a
        BatcherClosed from a dying batcher retries the replacement (or
        the direct path) — the accepted request is never dropped."""
        served = []

        def predict(inputs):
            served.append(True)
            return {"y": np.asarray(inputs["x"])}

        srv = ModelServer()
        srv._models["m"] = {1: LoadedModel(
            name="m", version=1, predict=predict, meta={})}
        srv._base_paths["m"] = "unused"
        mb = MicroBatcher(predict, batch_timeout_s=0.001, name="ft-dead")
        mb.close()
        srv._batchers["m"] = mb  # stale closed batcher (swap race)
        try:
            out = srv.predict("m", {"x": np.zeros((1, 2))})
            assert out["y"].shape == (1, 2)
            assert served  # direct path picked it up
        finally:
            srv.stop()


@pytest.fixture(scope="module")
def engine_model(tmp_path_factory):
    """Tiny exported lm_generate model; yields (spec, server) exactly
    like tests/test_lm_serving.py's fixture, so engine fault tests and
    the reference generate() share identical staged params."""
    import jax

    from kubeflow_tpu.models.transformer import Transformer
    from kubeflow_tpu.serving.export import export
    from kubeflow_tpu.serving.loaders import _model_config

    overrides = {
        "vocab_size": VOCAB, "d_model": 32, "n_layers": 2, "n_heads": 4,
        "n_kv_heads": 2, "d_ff": 64, "head_dim": 8, "max_seq_len": 64,
        "dtype": "float32",
    }
    model = Transformer(_model_config(overrides))
    variables = model.init(
        jax.random.key(SEED), np.zeros((1, PROMPT_LEN), np.int32))
    base = tmp_path_factory.mktemp("ft-models") / "lm"
    export(base, 1, variables,
           loader="kubeflow_tpu.serving.loaders:lm_generate",
           config={"model": overrides,
                   "max_new_tokens": NEW_TOKENS, "temperature": 0.0})
    server = ModelServer()
    server.add_model("lm", str(base))
    yield server.get("lm").predict.engine_spec, server
    server.stop()


def _reference_row(spec, prompt, new):
    from kubeflow_tpu.models.generate import generate

    out, _ = generate(spec["cfg"], spec["params"],
                      np.asarray(prompt, np.int32)[None], spec["decode"])
    return np.asarray(out)[0, :len(prompt) + new].tolist()


class TestEngineDeadlines:
    def test_expired_on_arrival(self, engine_model):
        from kubeflow_tpu.serving.engine import DecodeEngine

        spec, _ = engine_model
        engine = DecodeEngine(spec["cfg"], spec["params"],
                              spec["decode"], slots=1, prefill_len=16,
                              name="ft-arrival")
        try:
            with pytest.raises(DeadlineExceeded):
                engine.submit({"tokens": np.arange(1, 5, dtype=np.int32)},
                              deadline=faults.monotonic() - 1.0)
            assert engine.stats()["deadline_expired"] == 1
        finally:
            engine.close()

    def test_midgeneration_expiry_reclaims_slot_no_corruption(
            self, engine_model):
        """Satellite: a deadline-expired mid-generation request frees
        its slot for a new admission and never corrupts a co-resident
        slot's tokens — both survivors token-identical to single-
        request generate()."""
        import threading

        from kubeflow_tpu.serving.engine import DecodeEngine

        spec, _ = engine_model
        rng = np.random.RandomState(SEED)
        prompt_c = rng.randint(1, VOCAB, size=(6,)).tolist()
        prompt_a = rng.randint(1, VOCAB, size=(5,)).tolist()
        prompt_b = rng.randint(1, VOCAB, size=(7,)).tolist()
        with faults.injected("seed=1;engine.step:sleep=0.05"):
            # One step a round: the fault sleeps once a DISPATCH, and
            # A's expiry below counts on a sleep before every token.
            engine = DecodeEngine(spec["cfg"], spec["params"],
                                  spec["decode"], slots=2,
                                  prefill_len=16, decode_rounds=1,
                                  name="ft-reclaim")
            outs: dict = {}

            def client(key, prompt, deadline=None):
                try:
                    outs[key] = engine.submit(
                        {"tokens": np.asarray(prompt, np.int32)},
                        deadline=deadline)
                except Exception as exc:  # noqa: BLE001 — the point
                    outs[key] = exc

            try:
                # C: healthy full-budget request in slot 0.
                t_c = threading.Thread(
                    target=client, args=("c", prompt_c))
                t_c.start()
                # A: full budget (12 steps x >=50 ms) but a 150 ms
                # deadline — guaranteed to expire mid-generation.
                t_a = threading.Thread(
                    target=client, args=("a", prompt_a,
                                         faults.monotonic() + 0.15))
                t_a.start()
                t_a.join(timeout=60)
                assert isinstance(outs["a"], DeadlineExceeded), outs["a"]
                # B: admitted into A's reclaimed slot while C decodes.
                client("b", prompt_b)
                t_c.join(timeout=60)
                stats = engine.stats()
                assert stats["deadline_expired"] == 1
                assert stats["in_flight_requests"] == 0
            finally:
                engine.close()
        # Token identity against single-request generate(): neither the
        # survivor nor the reclaimed-slot request saw A's leftovers.
        for key, prompt in (("c", prompt_c), ("b", prompt_b)):
            got = np.asarray(outs[key]["tokens"])[0].tolist()
            assert got == _reference_row(spec, prompt, NEW_TOKENS), (
                f"request {key!r} drifted after mid-generation abort")

    def test_request_retired_at_dispatch_is_delivered_at_its_rounds_end(
            self, engine_model):
        """A request whose remaining budget fits the round is retired
        at that round's DISPATCH (its slot is free while the device
        computes) and its tokens arrive at that round's end: under
        wedged rounds it is not kept waiting on the co-resident
        request's later rounds, and a deadline it holds is met, not
        swept (expiry runs between rounds and finds it delivered)."""
        from kubeflow_tpu.serving.engine import DecodeEngine

        spec, _ = engine_model
        prompt = np.arange(1, 5, dtype=np.int32)
        engine = DecodeEngine(spec["cfg"], spec["params"],
                              spec["decode"], slots=2, prefill_len=16,
                              decode_rounds=2, name="ft-retired")
        outs: dict = {}

        def client(key, new, deadline=None):
            try:
                outs[key] = engine.submit(
                    {"tokens": prompt, "max_new_tokens": new},
                    deadline=deadline)
            except Exception as exc:  # noqa: BLE001 — the point
                outs[key] = exc

        try:
            client("warm", 3)  # compiles both programs, unwedged
            with faults.injected("seed=1;engine.step:sleep=0.1"):
                # B: 11 steps after its first token, two a round, each
                # round wedged 0.1 s.  A: one step after its first
                # token, so the round that runs it retires it.
                t_b = threading.Thread(target=client, args=("b", 12))
                t_b.start()
                t_a = threading.Thread(
                    target=client,
                    args=("a", 2, faults.monotonic() + 30.0))
                t_a.start()
                t_a.join(timeout=60)
                assert t_b.is_alive(), "A waited for B's later rounds"
                t_b.join(timeout=60)
            want = _reference_row(spec, prompt.tolist(), NEW_TOKENS)
            for key, new in (("a", 2), ("b", 12)):
                assert not isinstance(outs[key], Exception), outs[key]
                assert np.asarray(outs[key]["tokens"])[0].tolist() \
                    == want[:len(prompt) + new]
            stats = engine.stats()
            assert stats["deadline_expired"] == 0
            assert stats["in_flight_requests"] == 0
        finally:
            engine.close()

    def test_queued_request_expires_while_slots_busy(self, engine_model):
        from kubeflow_tpu.serving.engine import DecodeEngine

        spec, _ = engine_model
        with faults.injected("seed=1;engine.step:sleep=0.04"):
            # One step a round: a sleep before every token keeps the
            # occupant in its slot well past the queued deadline.
            engine = DecodeEngine(spec["cfg"], spec["params"],
                                  spec["decode"], slots=1,
                                  prefill_len=16, decode_rounds=1,
                                  name="ft-queue-exp")
            holder: dict = {}

            def occupant():
                holder["out"] = engine.submit(
                    {"tokens": np.arange(1, 7, dtype=np.int32)})

            t = threading.Thread(target=occupant)
            try:
                t.start()
                deadline = time.monotonic() + 30
                while engine.stats()["in_flight_requests"] < 1:
                    assert time.monotonic() < deadline
                    time.sleep(0.005)
                with pytest.raises(DeadlineExceeded):
                    engine.submit({"tokens": np.arange(1, 4, dtype=np.int32)},
                                  deadline=faults.monotonic() + 0.1)
                t.join(timeout=60)
                assert "out" in holder  # occupant unaffected
            finally:
                t.join(timeout=60)
                engine.close()


class TestEngineOverload:
    def test_admission_queue_cap_sheds(self, engine_model):
        from kubeflow_tpu.serving.engine import DecodeEngine

        spec, _ = engine_model
        with faults.injected("seed=1;engine.step:sleep=0.04"):
            # One step a round: a sleep before every token holds the
            # slot while the queue fills and the third submit sheds.
            engine = DecodeEngine(spec["cfg"], spec["params"],
                                  spec["decode"], slots=1,
                                  prefill_len=16, max_queue_depth=1,
                                  overload_retry_after_s=3.0,
                                  decode_rounds=1, name="ft-eng-shed")
            results: dict = {}

            def client(i):
                try:
                    results[i] = engine.submit(
                        {"tokens": np.arange(1, 6, dtype=np.int32)})
                except Exception as exc:  # noqa: BLE001 — the point
                    results[i] = exc

            threads = [threading.Thread(target=client, args=(0,))]
            try:
                threads[0].start()
                deadline = time.monotonic() + 30
                while engine.stats()["in_flight_requests"] < 1:
                    assert time.monotonic() < deadline
                    time.sleep(0.005)
                threads.append(threading.Thread(target=client, args=(1,)))
                threads[1].start()
                while engine.stats()["queue_depth"] < 1:
                    assert time.monotonic() < deadline
                    time.sleep(0.005)
                with pytest.raises(Overloaded) as err:
                    engine.submit({"tokens": np.arange(1, 6, dtype=np.int32)})
                assert err.value.retry_after_s == 3.0
                for t in threads:
                    t.join(timeout=60)
                # Accepted work completed despite the shed.
                assert not isinstance(results[0], Exception)
                assert not isinstance(results[1], Exception)
                stats = engine.stats()
                assert stats["shed"] == 1
                assert stats["requests"] == 2
            finally:
                engine.close()

    def test_alloc_block_fault_aborts_cleanly(self, engine_model):
        """The paged-KV allocator's hook site (engine.alloc_block,
        fired when pages are taken from an admission's reservation):
        an injected raise is a device-allocation death — the loop
        aborts, the waiting client gets the error (never a hang), and
        the closed engine refuses new work."""
        from kubeflow_tpu.serving.engine import DecodeEngine
        from kubeflow_tpu.serving.errors import BatcherClosed

        spec, _ = engine_model
        with faults.injected("seed=1;engine.alloc_block:raise") as inj:
            engine = DecodeEngine(spec["cfg"], spec["params"],
                                  spec["decode"], slots=1,
                                  prefill_len=16, name="ft-alloc")
            try:
                with pytest.raises(Exception) as err:
                    engine.submit(
                        {"tokens": np.arange(1, 6, dtype=np.int32)})
                assert "injected fault" in str(err.value)
                assert inj.fired("engine.alloc_block") >= 1
                with pytest.raises(BatcherClosed):
                    engine.submit(
                        {"tokens": np.arange(1, 6, dtype=np.int32)})
            finally:
                engine.close()


class TestServerInflightCap:
    def test_direct_path_bounded_by_max_inflight(self):
        """The un-batched path has no queue to bound it, so the
        ModelServer-level cap must shed there too: one request in
        flight on the direct path, the next sheds with Overloaded."""
        gate = _GatedPredict()
        srv = ModelServer(max_inflight=1, overload_retry_after_s=4.0)
        srv._models["m"] = {1: LoadedModel(
            name="m", version=1, predict=lambda i: gate(i), meta={})}
        srv._base_paths["m"] = "unused"
        holder: dict = {}
        t = threading.Thread(target=lambda: holder.update(
            out=srv.predict("m", {"x": np.zeros((2, 2))})))
        t.start()
        try:
            assert gate.entered.wait(timeout=10)
            with pytest.raises(Overloaded) as err:
                srv.predict("m", {"x": np.zeros((2, 2))})
            assert err.value.retry_after_s == 4.0
            gate.release.set()
            t.join(timeout=10)
            assert "out" in holder  # accepted request unaffected
            # Cap released: the next request is admitted again.
            out = srv.predict("m", {"x": np.zeros((2, 2))})
            assert out["y"].shape == (2, 2)
        finally:
            gate.release.set()
            t.join(timeout=10)
            srv.stop()

    def test_direct_fallthrough_rechecks_deadline(self):
        """A request whose batcher closed under it (drain/swap race)
        must not fall through to an uninterruptible direct-path
        generation once its deadline is spent — 504, not a late 200."""
        ran = []

        class ClosedThenExpired:
            def submit(self, inputs, deadline=None):
                # Simulate the request's budget dying while it was
                # queued here, then the batcher closing (drain).
                faults.active().advance_clock(10)
                raise BatcherClosed("draining")

            def close(self):
                pass

        srv = ModelServer()
        srv._models["m"] = {1: LoadedModel(
            name="m", version=1,
            predict=lambda i: ran.append(True) or {"y": i["x"]},
            meta={})}
        srv._base_paths["m"] = "unused"
        srv._batchers["m"] = ClosedThenExpired()
        try:
            with faults.injected("seed=0"):
                with pytest.raises(DeadlineExceeded):
                    srv.predict("m", {"x": np.zeros((1, 2))},
                                deadline=faults.monotonic() + 1.0)
            assert not ran, "direct path ran a dead request"
        finally:
            srv.stop()


class TestReloadBreaker:
    def _export_lm(self, base, version):
        import jax

        from kubeflow_tpu.models.transformer import Transformer
        from kubeflow_tpu.serving.export import export
        from kubeflow_tpu.serving.loaders import _model_config

        overrides = {
            "vocab_size": 32, "d_model": 8, "n_layers": 1, "n_heads": 2,
            "n_kv_heads": 2, "d_ff": 16, "head_dim": 4,
            "max_seq_len": 16, "dtype": "float32",
        }
        model = Transformer(_model_config(overrides))
        variables = model.init(jax.random.key(0),
                               np.zeros((1, 4), np.int32))
        export(base, version, variables,
               loader="kubeflow_tpu.serving.loaders:lm",
               config=overrides)

    def test_corrupt_version_trips_breaker_last_good_serves(
            self, tmp_path):
        base = tmp_path / "lm"
        self._export_lm(base, 1)
        with faults.injected("seed=0") as inj:
            srv = ModelServer(reload_backoff_s=0.5,
                              reload_backoff_cap_s=8.0)
            srv.add_model("lm", str(base))
            assert srv.get("lm").version == 1
            loads_after_v1 = inj.fired("loader.load")
            # Corrupt version 2 lands in the watch path.
            (base / "2").mkdir()
            (base / "2" / "model.json").write_text("{corrupt")
            with pytest.raises(Exception):
                srv.reload("lm")
            attempts = inj.fired("loader.load")
            assert attempts == loads_after_v1 + 1
            # Breaker OPEN: watcher-style polls skip the loader — no
            # hot-loop on the corrupt artifact.
            for _ in range(8):
                assert srv.reload("lm") is False
            assert inj.fired("loader.load") == attempts
            # Last-good keeps serving.
            out = srv.predict(
                "lm", {"tokens": np.asarray([[1, 2, 3]], np.int32)})
            assert "logits" in out
            assert srv.get("lm").version == 1
            # Backoff elapsed (policy clock) -> HALF-OPEN: one trial.
            inj.advance_clock(60)
            with pytest.raises(Exception):
                srv.reload("lm")
            assert inj.fired("loader.load") == attempts + 1
            # Re-opened with doubled backoff: skipped again.
            assert srv.reload("lm") is False
            assert inj.fired("loader.load") == attempts + 1
            # A NEW good version resets the breaker immediately.
            self._export_lm(base, 3)
            assert srv.reload("lm") is True
            assert srv.get("lm").version == 3
            srv.stop()
        from kubeflow_tpu.runtime.prom import REGISTRY

        rendered = REGISTRY.render()
        line = [ln for ln in rendered.splitlines() if ln.startswith(
            'kft_serving_reload_failures_total{model="lm"}')]
        assert line and float(line[0].rsplit(" ", 1)[1]) >= 2

    def test_half_open_admits_exactly_one_trial(self):
        with faults.injected("seed=0") as inj:
            breaker = _ReloadBreaker(base_s=1.0, cap_s=8.0)
            breaker.record_failure(2)
            assert not breaker.allow(2)  # open
            inj.advance_clock(10)
            assert breaker.allow(2)       # the half-open trial
            assert not breaker.allow(2)   # concurrent poll: refused
            breaker.record_failure(2)     # trial failed -> re-opened
            assert not breaker.allow(2)
            breaker.record_success()
            assert breaker.allow(2)

    def test_new_version_resets_breaker(self):
        breaker = _ReloadBreaker(base_s=100.0)
        breaker.record_failure(2)
        assert not breaker.allow(2)
        assert breaker.allow(3)  # different artifact: try at once


class TestReloadBreakerBackoffBounds:
    """White-box invariants of the breaker's backoff schedule: the
    jittered window must stay inside [B, 1.25*B] for B = min(cap,
    base * 2^(n-1)) — a jitter that can exceed the bound turns the cap
    into a lie, and one that can undershoot re-opens the hot-loop the
    breaker exists to prevent.  Clock-skew driven: no wall sleeps."""

    def test_backoff_window_within_jitter_bounds_per_failure(self):
        import random

        base_s, cap_s = 0.5, 8.0
        with faults.injected("seed=0"):
            breaker = _ReloadBreaker(base_s=base_s, cap_s=cap_s,
                                     rng=random.Random(7))
            for n in range(1, 10):
                before = faults.monotonic()
                breaker.record_failure(2)
                window = breaker.open_until - before
                expected = min(cap_s, base_s * (2 ** (n - 1)))
                # record_failure read the clock a hair after `before`,
                # so `window` can only exceed the nominal bound.
                assert expected <= window <= expected * 1.25 + 1e-6, (
                    n, window, expected)

    def test_jitter_sequences_differ_across_default_breakers(self):
        # OS-seeded default rngs: two replicas watching one model path
        # must not walk identical backoff schedules (lockstep retry).
        with faults.injected("seed=0"):
            windows = []
            for _ in range(2):
                b = _ReloadBreaker(base_s=1.0, cap_s=64.0)
                seq = []
                for _ in range(6):
                    before = faults.monotonic()
                    b.record_failure(2)
                    seq.append(round(b.open_until - before, 9))
                windows.append(seq)
            assert windows[0] != windows[1]

    def test_half_open_single_trial_under_concurrent_clock_skew(self):
        """After the (skewed-past) backoff expires, exactly ONE caller
        may claim the trial slot no matter how many race for it; a
        failed trial re-opens with a doubled window, a successful one
        closes the breaker for everyone."""
        with faults.injected("seed=0") as inj:
            breaker = _ReloadBreaker(base_s=1.0, cap_s=64.0)
            breaker.record_failure(5)
            first_window = breaker.open_until - faults.monotonic()
            inj.advance_clock(2.0)  # backoff spent

            grants = []
            barrier = threading.Barrier(8)

            def racer():
                barrier.wait()
                if breaker.allow(5):
                    grants.append(threading.get_ident())

            threads = [threading.Thread(target=racer)
                       for _ in range(8)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert len(grants) == 1, grants
            # Trial fails -> re-open, doubled (jittered) window; the
            # skewed clock is the only time source consulted.
            before = faults.monotonic()
            breaker.record_failure(5)
            second_window = breaker.open_until - before
            assert second_window >= 2.0 > first_window / 1.25
            assert not breaker.allow(5)
            inj.advance_clock(second_window + 0.001)
            assert breaker.allow(5)      # next half-open trial
            breaker.record_success()
            # Closed: every caller admitted again, immediately.
            assert breaker.allow(5) and breaker.allow(5)


class TestReadinessAndDrain:
    def test_ready_requires_models_and_not_draining(self):
        srv = ModelServer()
        assert not srv.is_ready()  # nothing loaded yet
        srv._models["m"] = {1: LoadedModel(
            name="m", version=1, predict=lambda i: i, meta={})}
        assert srv.is_ready()
        srv.begin_drain()
        assert srv.draining() and not srv.is_ready()

    def test_readyz_flips_healthz_stays(self):
        from kubeflow_tpu.serving.http import make_http_server

        srv = ModelServer()
        srv._models["m"] = {1: LoadedModel(
            name="m", version=1, predict=lambda i: i, meta={})}
        httpd, _ = make_http_server(srv, port=0, host="127.0.0.1")
        port = httpd.server_address[1]
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/readyz", timeout=30) as r:
                assert r.status == 200
                assert json.loads(r.read())["status"] == "ready"
            srv.begin_drain()
            with pytest.raises(urllib.error.HTTPError) as err:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/readyz", timeout=30)
            assert err.value.code == 503
            assert json.loads(err.value.read())["status"] == "draining"
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/healthz", timeout=30) as r:
                assert r.status == 200  # alive while draining
        finally:
            httpd.shutdown()
            srv.stop()

    def test_wait_for_drain_tracks_inflight(self):
        from kubeflow_tpu.serving.main import wait_for_drain

        gate = _GatedPredict()
        srv = ModelServer()
        srv._models["m"] = {1: LoadedModel(
            name="m", version=1,
            predict=lambda i: gate(i), meta={})}
        srv._base_paths["m"] = "unused"
        holder: dict = {}
        t = threading.Thread(target=lambda: holder.update(
            out=srv.predict("m", {"x": np.zeros((2, 2))})))
        t.start()
        try:
            assert gate.entered.wait(timeout=10)
            assert srv.inflight() == 1
            assert not wait_for_drain(srv, deadline_s=0.2)
            gate.release.set()
            t.join(timeout=10)
            assert srv.inflight() == 0
            assert wait_for_drain(srv, deadline_s=5.0)
            assert "out" in holder  # the accepted request completed
        finally:
            gate.release.set()
            t.join(timeout=10)
            srv.stop()


class _Raiser:
    """Stub batcher raising a scripted error from submit()."""

    def __init__(self, exc):
        self.exc = exc

    def submit(self, inputs, deadline=None):
        raise self.exc

    def close(self):
        pass


def _stub_server(exc):
    srv = ModelServer()
    srv._models["m"] = {1: LoadedModel(
        name="m", version=1,
        predict=lambda i: {"y": np.asarray(i["x"])}, meta={})}
    srv._base_paths["m"] = "unused"
    srv._batchers["m"] = _Raiser(exc)
    return srv


class TestHTTPStatusMapping:
    def _post(self, port, body):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/model/m:predict",
            data=json.dumps(body).encode())
        try:
            with urllib.request.urlopen(req, timeout=30) as resp:
                return resp.status, dict(resp.headers), \
                    json.loads(resp.read())
        except urllib.error.HTTPError as e:
            return e.code, dict(e.headers), json.loads(e.read())

    def test_overloaded_maps_to_429_with_retry_after(self):
        from kubeflow_tpu.serving.http import make_http_server

        srv = _stub_server(Overloaded("queue full", retry_after_s=7))
        httpd, _ = make_http_server(srv, port=0, host="127.0.0.1")
        try:
            code, headers, payload = self._post(
                httpd.server_address[1],
                {"instances": [{"x": [1.0]}]})
            assert code == 429
            assert headers.get("Retry-After") == "7"
            assert "queue full" in payload["error"]
        finally:
            httpd.shutdown()
            srv.stop()

    def test_deadline_maps_to_504(self):
        from kubeflow_tpu.serving.http import make_http_server

        srv = _stub_server(DeadlineExceeded("expired mid-generation"))
        httpd, _ = make_http_server(srv, port=0, host="127.0.0.1")
        try:
            code, _, payload = self._post(
                httpd.server_address[1],
                {"instances": [{"x": [1.0]}]})
            assert code == 504
            assert "expired" in payload["error"]
        finally:
            httpd.shutdown()
            srv.stop()

    def test_malformed_deadline_ms_is_400(self):
        from kubeflow_tpu.serving.http import make_http_server

        srv = _stub_server(RuntimeError("unreached"))
        httpd, _ = make_http_server(srv, port=0, host="127.0.0.1")
        try:
            # Non-positive, wrong-typed, and non-finite (NaN would
            # otherwise pass `<= 0` and enforce nothing) all map to
            # the documented 400, never a 500.
            for bad in (0, -5, [500], "soon", float("nan")):
                code, _, payload = self._post(
                    httpd.server_address[1],
                    {"instances": [{"x": [1.0]}],
                     "deadline_ms": bad})
                assert code == 400, (bad, code, payload)
        finally:
            httpd.shutdown()
            srv.stop()


class TestGRPCStatusMapping:
    def test_overloaded_roundtrips_as_typed_error(self):
        from kubeflow_tpu.serving.grpc_server import (
            PredictionClient,
            make_grpc_server,
        )

        srv = _stub_server(Overloaded("engine queue full",
                                      retry_after_s=2))
        server = make_grpc_server(srv, port=0, host="127.0.0.1")
        client = PredictionClient(f"127.0.0.1:{server.bound_port}")
        try:
            with pytest.raises(Overloaded,
                               match="engine queue full") as err:
                client.predict("m", {"x": np.ones((1, 2), np.float32)})
            # The server's Retry-After hint survives the wire — clients
            # backing off via the typed field honor the server's number.
            assert err.value.retry_after_s == 2.0
        finally:
            client.close()
            server.stop(0)
            srv.stop()

    def test_server_deadline_roundtrips_as_typed_error(self):
        from kubeflow_tpu.serving.grpc_server import (
            PredictionClient,
            make_grpc_server,
        )

        srv = _stub_server(DeadlineExceeded("expired in queue"))
        server = make_grpc_server(srv, port=0, host="127.0.0.1")
        client = PredictionClient(f"127.0.0.1:{server.bound_port}")
        try:
            with pytest.raises(DeadlineExceeded):
                client.predict("m", {"x": np.ones((1, 2), np.float32)})
        finally:
            client.close()
            server.stop(0)
            srv.stop()

    def test_transport_timeout_maps_to_deadline_exceeded(self):
        """Satellite: a client-supplied deadline that the transport
        itself enforces (server too slow to answer at all) surfaces as
        the SAME typed error as a server-side expiry."""
        from kubeflow_tpu.serving.grpc_server import (
            PredictionClient,
            make_grpc_server,
        )

        gate = _GatedPredict()
        srv = ModelServer()
        srv._models["m"] = {1: LoadedModel(
            name="m", version=1, predict=lambda i: gate(i), meta={})}
        srv._base_paths["m"] = "unused"
        server = make_grpc_server(srv, port=0, host="127.0.0.1")
        client = PredictionClient(f"127.0.0.1:{server.bound_port}")
        try:
            with pytest.raises(DeadlineExceeded):
                client.predict("m", {"x": np.ones((2, 2), np.float32)},
                               timeout=0.2)
        finally:
            gate.release.set()
            client.close()
            server.stop(0)
            srv.stop()

    def test_client_timeouts_default_to_none(self):
        """Satellite: no more hard-coded 60 s — the client sends no
        deadline unless the caller supplies one."""
        import inspect

        from kubeflow_tpu.serving.grpc_server import PredictionClient

        for method in ("predict", "classify", "metadata"):
            sig = inspect.signature(getattr(PredictionClient, method))
            assert sig.parameters["timeout"].default is None, method


class TestEngineDrainDeadlineSkew:
    def test_drain_deadline_expires_under_skewed_policy_clock(
            self, engine_model):
        """PR-8 satellite: the engine's close() drain deadline rides
        the POLICY clock (faults.monotonic), so a seeded skew expires
        it without waiting out the drain budget.  Each step adds 500 s
        of skew: the step AFTER close() arms the deadline pushes the
        clock past it, the loop aborts the in-flight request, and
        close() returns in wall-milliseconds despite drain_s=60.  On
        the real clock (the pre-migration bug) the request would
        simply complete inside the budget and no abort would fire."""
        from kubeflow_tpu.serving.engine import DecodeEngine

        spec, _ = engine_model
        rng = np.random.RandomState(SEED)
        prompt = rng.randint(1, VOCAB, size=(6,)).tolist()
        with faults.injected(
                "seed=1;engine.step:sleep=0.05;engine.step:skew=500"):
            # One step a round: "the step AFTER close()" must exist,
            # and 12 tokens in rounds of 8 are over in two dispatches.
            engine = DecodeEngine(spec["cfg"], spec["params"],
                                  spec["decode"], slots=1,
                                  prefill_len=16, decode_rounds=1,
                                  name="ft-drain-skew")
            outs: dict = {}

            def client():
                try:
                    outs["r"] = engine.submit(
                        {"tokens": np.asarray(prompt, np.int32)})
                except Exception as exc:  # noqa: BLE001 — the point
                    outs["r"] = exc
            t = threading.Thread(target=client)
            t.start()
            deadline = time.monotonic() + 30
            while not engine.stats()["in_flight_requests"]:
                assert time.monotonic() < deadline, "never admitted"
                time.sleep(0.01)
            t0 = time.monotonic()
            engine.close(drain_s=60.0)
            wall = time.monotonic() - t0
            t.join(timeout=30)
            assert isinstance(outs.get("r"), RuntimeError), outs
            assert "drain deadline" in str(outs["r"])
            # Skew, not wall time, expired the drain: 60 s of budget
            # consumed in well under 30 s of real time.
            assert wall < 30.0, wall
