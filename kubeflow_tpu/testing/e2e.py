"""E2E test drivers — heir of testing/test_deploy.py's argparse
subcommands (deploy_model :160-190, deploy_pytorchjob :219-235,
teardown :520-626), each wrapped into JUnit artifacts.

Two backends: against a real cluster these drive kubectl-applied
manifests; hermetically they drive the FakeKube + reconciler, which is
how CI exercises the full TPUJob lifecycle without hardware (the
improvement SURVEY.md §4 calls for over the reference's rented-VM
strategy).
"""

from __future__ import annotations

import argparse
import sys
import time

from kubeflow_tpu.testing.junit import JUnitSuite


def tpujob_smoke(namespace: str = "kubeflow-test") -> None:
    """Submit a tiny TPUJob to the in-process control plane and drive it
    to completion — the simple_tfjob equivalent
    (testing/workflows/components/workflows.libsonnet:398-411)."""
    from kubeflow_tpu.operator import crd
    from kubeflow_tpu.operator.gang import GangScheduler
    from kubeflow_tpu.operator.kube import RUNNING, SUCCEEDED, FakeKube
    from kubeflow_tpu.operator.reconciler import (
        JOB_RUNNING,
        JOB_SUCCEEDED,
        TPUJobController,
    )

    kube = FakeKube()
    controller = TPUJobController(kube, GangScheduler({"v5e-8": 1}))
    job = crd.TPUJobSpec(name="smoke", namespace=namespace,
                         slice_type="v5e-8")
    kube.create_custom(job.to_custom_resource())
    cr = kube.list_custom()[0]
    controller.reconcile_once(cr)
    for pod in kube.list_pods(namespace):
        kube.set_pod_phase(namespace, pod["metadata"]["name"], RUNNING)
    assert controller.reconcile_once(cr) == JOB_RUNNING
    for pod in kube.list_pods(namespace):
        kube.set_pod_phase(namespace, pod["metadata"]["name"], SUCCEEDED)
    assert controller.reconcile_once(cr) == JOB_SUCCEEDED


def serving_smoke(namespace: str = "kubeflow-test") -> None:
    """Export a tiny model, serve it over HTTP, assert a live predict —
    the inception-golden equivalent (testing/test_tf_serving.py)."""
    import json
    import tempfile
    import urllib.request

    import jax
    import numpy as np

    from kubeflow_tpu.models.resnet import ResNet18
    from kubeflow_tpu.serving.export import export
    from kubeflow_tpu.serving.http import make_http_server
    from kubeflow_tpu.serving.model_server import ModelServer

    with tempfile.TemporaryDirectory() as tmp:
        model = ResNet18(num_classes=4, num_filters=8)
        variables = model.init(
            jax.random.key(0), np.zeros((1, 32, 32, 3), np.float32),
            train=False)
        export(f"{tmp}/m", 1, variables,
               loader="kubeflow_tpu.serving.loaders:classifier",
               config={"family": "resnet18", "num_classes": 4,
                       "num_filters": 8},
               signature={"inputs": ["image"]})
        server = ModelServer()
        server.add_model("m", f"{tmp}/m")
        httpd, _ = make_http_server(server, port=0, host="127.0.0.1")
        try:
            port = httpd.server_address[1]
            body = json.dumps({"instances": [
                {"image": np.zeros((32, 32, 3), np.float32).tolist()}
            ]}).encode()
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/model/m:predict", data=body)
            with urllib.request.urlopen(req, timeout=60) as resp:
                out = json.loads(resp.read())
            assert len(out["predictions"]) == 1
            scores = out["predictions"][0]["scores"]
            assert abs(sum(scores) - 1.0) < 1e-3
        finally:
            httpd.shutdown()


def engine_smoke(namespace: str = "kubeflow-test") -> None:
    """Admit mixed-length LM requests through the HTTP surface against
    the in-process continuous-batching DecodeEngine: all must complete
    (in-flight admission + slot reuse, 3 requests through 2 slots) and
    the engine must report zero occupancy and an empty queue after.
    Then a shared-prefix burst (concurrent clients, one common system
    prompt) must register prefix-cache hits in
    ``kft_engine_prefix_hits_total`` and keep the max inter-token gap
    of in-flight slots under the chunk-budget bound (no full-prefill
    stall spike).  Then a block-exhaustion burst against a
    deliberately tiny ``kv_pool_blocks`` pool: admission must shed
    typed Overloaded (HTTP 429) while the pool is exhausted,
    retirement must free blocks and restore admission (the queued
    request completes), and the
    ``kft_engine_kv_block_evictions_total`` /
    ``kft_engine_kv_shed_no_blocks_total`` counters must move as
    deltas over /metrics.  Finally a fused-decode burst
    (``--decode_rounds 8`` rebuild): the engine must dispatch fused
    while_loop rounds (``kft_engine_fused_rounds_total`` delta > 0),
    report exactly its two compiled programs over :stats (chunked
    prefill, decode rounds — prefix reuse is zero-copy block aliasing,
    no copy program exists), and produce token-IDENTICAL output to a
    ``decode_rounds=1`` control rebuild (the same program, one step a
    dispatch)."""
    import json
    import tempfile
    import threading
    import urllib.error
    import urllib.request

    import jax
    import numpy as np

    from kubeflow_tpu.models.transformer import Transformer
    from kubeflow_tpu.serving.export import export
    from kubeflow_tpu.serving.http import make_http_server
    from kubeflow_tpu.serving.loaders import _model_config
    from kubeflow_tpu.serving.main import batcher_factory
    from kubeflow_tpu.serving.model_server import ModelServer

    overrides = {
        "vocab_size": 128, "d_model": 32, "n_layers": 2, "n_heads": 4,
        "n_kv_heads": 2, "d_ff": 64, "head_dim": 8, "max_seq_len": 64,
        "dtype": "float32",
    }
    max_new = 16
    cfg = _model_config(overrides)
    model = Transformer(cfg)
    variables = model.init(jax.random.key(0), np.zeros((1, 4), np.int32))
    with tempfile.TemporaryDirectory() as tmp:
        export(f"{tmp}/lm", 1, variables,
               loader="kubeflow_tpu.serving.loaders:lm_generate",
               config={"model": overrides, "max_new_tokens": max_new,
                       "temperature": 0.0})
        server = ModelServer()
        server.add_model("lm", f"{tmp}/lm")

        def rebuild(**extra):
            server.enable_batching("lm", batcher_factory(
                micro_batch_size=0, batch_timeout_s=0.005,
                lm_engine=True, lm_engine_slots=2,
                lm_engine_prefill_len=16, prefill_chunk_tokens=8,
                kv_block_tokens=4, **extra))

        rebuild()
        httpd, _ = make_http_server(server, port=0, host="127.0.0.1")
        try:
            port = httpd.server_address[1]
            rng = np.random.RandomState(0)
            prompts = [rng.randint(1, 128, size=(n,)).tolist()
                       for n in (3, 9, 16)]
            outs: dict = {}

            def client(i, prompt):
                body = json.dumps(
                    {"instances": [{"tokens": prompt}]}).encode()
                req = urllib.request.Request(
                    f"http://127.0.0.1:{port}/model/lm:predict",
                    data=body)
                with urllib.request.urlopen(req, timeout=120) as resp:
                    outs[i] = json.loads(resp.read())

            threads = [threading.Thread(target=client, args=(i, p))
                       for i, p in enumerate(prompts)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            for i, prompt in enumerate(prompts):
                tokens = outs[i]["predictions"][0]["tokens"]
                assert tokens[:len(prompt)] == prompt
                assert len(tokens) == len(prompt) + max_new
            # Occupancy must return to zero once the work drains (the
            # :stats route reads the engine's locked snapshot).
            deadline = time.time() + 30
            while True:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/model/lm:stats",
                        timeout=30) as resp:
                    stats = json.loads(resp.read())["batcher"]
                if (stats["active_slots"] == 0
                        and stats["queue_depth"] == 0
                        and stats["in_flight_requests"] == 0):
                    break
                assert time.time() < deadline, (
                    f"engine never drained: {stats}")
                time.sleep(0.05)
            assert stats["requests"] == len(prompts)

            # --- shared-prefix burst: 4 concurrent clients, one
            # common 8-token system prompt + unique suffixes.  The
            # first admission captures the prefix into the donor pool;
            # later ones resume from it.
            shared = rng.randint(1, 128, size=(8,)).tolist()
            burst = [shared + rng.randint(1, 128, size=(4,)).tolist()
                     for _ in range(4)]
            outs.clear()
            threads = [threading.Thread(target=client, args=(i, p))
                       for i, p in enumerate(burst)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            for i, prompt in enumerate(burst):
                tokens = outs[i]["predictions"][0]["tokens"]
                assert tokens[:len(prompt)] == prompt
                assert len(tokens) == len(prompt) + max_new
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/model/lm:stats",
                    timeout=30) as resp:
                stats = json.loads(resp.read())["batcher"]
            assert stats["prefix_hits"] > 0, (
                f"shared-prefix burst produced no cache hits: {stats}")
            assert stats["cached_token_ratio"] > 0
            # Concurrent admission must not have stalled in-flight
            # decode beyond the chunk budget: the worst observed
            # inter-token gap stays within a (generous, CI-noise-proof)
            # multiple of one scheduling turn — one chunk call plus one
            # step — where an unchunked full-prefill storm would spike
            # it by the whole admission wave's prompt length.
            turn_ms = (stats["token_latency_p95_ms"]
                       + stats["prefill_chunk_p95_ms"])
            bound_ms = 500.0 + 25.0 * max(turn_ms, 1.0)
            assert stats["inter_token_gap_max_ms"] <= bound_ms, (
                f"inter-token gap {stats['inter_token_gap_max_ms']} ms "
                f"exceeded the chunk-budget bound {bound_ms:.0f} ms")
            # The prefix-cache counters are on /metrics for operators.
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/metrics",
                    timeout=30) as resp:
                metrics = resp.read().decode()
            from kubeflow_tpu.runtime.prom import (
                parse_metrics,
                sample_value,
            )
            parsed = parse_metrics(metrics)
            hits = sample_value(
                parsed, "kft_engine_prefix_hits_total") or 0
            assert hits > 0, "kft_engine_prefix_hits_total not exported"
            assert sample_value(
                parsed, "kft_serving_cached_token_ratio") is not None

            # --- block-exhaustion burst: a deliberately tiny pool (8
            # pages of 4 tokens against 12-token prompts + 16-token
            # budgets = 7 reserved pages per request, so exactly ONE
            # request fits) and a queue cap of 1.  8 simultaneous
            # clients: one admits, one queues, the rest MUST shed 429
            # Overloaded while the pool is exhausted — and every
            # accepted request must still complete, because
            # retirement frees its pages and re-opens admission for
            # the queued one (tokens-resident admission never
            # deadlocks a mid-flight slot).
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/metrics",
                    timeout=30) as resp:
                parsed = parse_metrics(resp.read().decode())
            shed_before = sample_value(
                parsed, "kft_engine_kv_shed_no_blocks_total",
                engine="lm-v1") or 0
            evict_before = sample_value(
                parsed, "kft_engine_kv_block_evictions_total",
                engine="lm-v1") or 0
            rebuild(kv_pool_blocks=8, max_queue_depth=1)
            burst = [rng.randint(1, 128, size=(12,)).tolist()
                     for _ in range(8)]
            outs.clear()
            codes: dict = {}

            def burst_client(i, prompt):
                try:
                    client(i, prompt)
                    codes[i] = 200
                except urllib.error.HTTPError as err:
                    codes[i] = err.code
                    err.read()

            threads = [threading.Thread(target=burst_client,
                                        args=(i, p))
                       for i, p in enumerate(burst)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            ok = [i for i, c in codes.items() if c == 200]
            shed = [i for i, c in codes.items() if c == 429]
            assert codes and set(codes.values()) <= {200, 429}, codes
            assert ok, f"exhaustion burst completed nothing: {codes}"
            assert shed, (
                f"pool exhaustion shed nothing (want 429s): {codes}")
            for i in ok:
                tokens = outs[i]["predictions"][0]["tokens"]
                assert tokens[:len(burst[i])] == burst[i]
                assert len(tokens) == len(burst[i]) + max_new
            # Admission restored after the burst drains: a fresh
            # request must be served, not shed.
            client("post", burst[0])
            assert len(outs["post"]["predictions"][0]["tokens"]) \
                == len(burst[0]) + max_new
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/model/lm:stats",
                    timeout=30) as resp:
                stats = json.loads(resp.read())["batcher"]
            # Every 429 is a typed shed; the pool-typed counter is
            # racy by design (a thread scheduled after the first
            # request retires can shed queue-full while the freed
            # pages sit unclaimed), so assert it MOVED rather than
            # that it covers every shed.
            assert stats["shed"] >= len(shed), stats
            assert stats["kv_shed_no_blocks"] >= 1, stats
            assert stats["kv_blocks"] == 8
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/metrics",
                    timeout=30) as resp:
                parsed = parse_metrics(resp.read().decode())
            shed_after = sample_value(
                parsed, "kft_engine_kv_shed_no_blocks_total",
                engine="lm-v1") or 0
            evict_after = sample_value(
                parsed, "kft_engine_kv_block_evictions_total",
                engine="lm-v1") or 0
            # The pool gauges are live: capacity == the rebuilt
            # engine's 8 pages, and the published prefix pages of the
            # drained burst are still resident (scrape-visible — the
            # loop refreshes the used gauge, not just close()).
            assert sample_value(parsed, "kft_engine_kv_blocks",
                                engine="lm-v1") == 8
            assert (sample_value(parsed, "kft_engine_kv_blocks_used",
                                 engine="lm-v1") or 0) > 0
            assert shed_after - shed_before >= 1, (
                shed_before, shed_after, codes)
            # Successive distinct prompts through an 8-page pool force
            # LRU eviction of published prefix pages — the eviction
            # counter must move.
            assert evict_after > evict_before, (
                evict_before, evict_after)

            # --- decode-rounds burst: rebuild with decode_rounds=8
            # (docs §5.2e) and drive mixed-length concurrent prompts.
            # The engine must dispatch rounds
            # (kft_engine_fused_rounds_total delta > 0), report the
            # program in compiled_programs, and produce
            # token-IDENTICAL output to a decode_rounds=1 control
            # rebuild (the same program, one step a dispatch).
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/metrics",
                    timeout=30) as resp:
                parsed = parse_metrics(resp.read().decode())
            fused_before = sample_value(
                parsed, "kft_engine_fused_rounds_total",
                engine="lm-v1") or 0
            rebuild(decode_rounds=8)
            fused_prompts = [rng.randint(1, 128, size=(n,)).tolist()
                             for n in (3, 9, 16)]
            outs.clear()
            threads = [threading.Thread(target=client, args=(i, p))
                       for i, p in enumerate(fused_prompts)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            fused_out = {}
            for i, prompt in enumerate(fused_prompts):
                tokens = outs[i]["predictions"][0]["tokens"]
                assert tokens[:len(prompt)] == prompt
                assert len(tokens) == len(prompt) + max_new
                fused_out[i] = tokens
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/model/lm:stats",
                    timeout=30) as resp:
                stats = json.loads(resp.read())["batcher"]
            assert stats["decode_rounds"] == 8, stats
            assert stats["fused_rounds"] > 0, (
                f"fused burst dispatched no fused rounds: {stats}")
            assert stats["steps_per_round_p50"] >= 1, stats
            # Each program compiles exactly once, for every width, and
            # there is no other: prefix reuse is host-side block-table
            # aliasing, not a device program.
            assert stats["compiled_programs"] == {
                "chunked_prefill": 1, "decode_rounds": 1}, stats
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/metrics",
                    timeout=30) as resp:
                parsed = parse_metrics(resp.read().decode())
            fused_after = sample_value(
                parsed, "kft_engine_fused_rounds_total",
                engine="lm-v1") or 0
            assert fused_after - fused_before > 0, (
                fused_before, fused_after)
            # Cap-1 control rebuild: identical tokens.
            # Same concurrent shape as the burst above — greedy decode
            # is order-independent per slot, and the threads halve the
            # control's wall time.
            rebuild(decode_rounds=1)
            outs.clear()
            threads = [threading.Thread(target=client, args=(i, p))
                       for i, p in enumerate(fused_prompts)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            for i, prompt in enumerate(fused_prompts):
                assert outs[i]["predictions"][0]["tokens"] \
                    == fused_out[i], (
                    f"fused decode changed tokens for prompt {i}")
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/model/lm:stats",
                    timeout=30) as resp:
                stats = json.loads(resp.read())["batcher"]
            assert stats["decode_rounds"] == 1, stats
            assert stats["steps_per_round_p99"] == 1, stats
        finally:
            httpd.shutdown()
            server.stop()


def fault_injection_smoke(namespace: str = "kubeflow-test") -> None:
    """Seeded chaos scenario against the whole serving fault layer,
    driven by the KFT_FAULTS harness (kubeflow_tpu/testing/faults.py):

      1. overload shed — slots full + queue full => HTTP 429 with a
         Retry-After header, while accepted requests still complete;
      2. deadline expiry MID-GENERATION (slow steps injected) => HTTP
         504, and the freed slot serves a follow-up request;
      3. loader circuit-break — a corrupt model version trips the
         reload breaker (no loader hot-loop) while the last-good
         version keeps serving; a fixed version recovers;
      4. graceful drain — /readyz flips 503 with a request in flight,
         /healthz stays 200, and the accepted request completes;
      5. every shed/expired/reload-failure is visible in kft_* metrics.

    Override the scenario by exporting KFT_FAULTS (same grammar).
    """
    import json
    import os
    import tempfile
    import threading
    import urllib.error
    import urllib.request

    import jax
    import numpy as np

    from kubeflow_tpu.models.transformer import Transformer
    from kubeflow_tpu.serving.export import export
    from kubeflow_tpu.serving.http import make_http_server
    from kubeflow_tpu.serving.loaders import _model_config
    from kubeflow_tpu.serving.main import batcher_factory, wait_for_drain
    from kubeflow_tpu.serving.model_server import ModelServer
    from kubeflow_tpu.testing import faults

    overrides = {
        "vocab_size": 128, "d_model": 32, "n_layers": 2, "n_heads": 4,
        "n_kv_heads": 2, "d_ff": 64, "head_dim": 8, "max_seq_len": 64,
        "dtype": "float32",
    }
    max_new = 16
    scenario = os.environ.get(faults.ENV) or \
        "seed=20260803;engine.step:sleep=0.03"
    model = Transformer(_model_config(overrides))
    variables = model.init(jax.random.key(0), np.zeros((1, 4), np.int32))

    def predict_req(port, body):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/model/lm:predict",
            data=json.dumps(body).encode())
        try:
            with urllib.request.urlopen(req, timeout=180) as resp:
                return resp.status, dict(resp.headers), \
                    json.loads(resp.read())
        except urllib.error.HTTPError as e:
            return e.code, dict(e.headers), json.loads(e.read())

    def engine_stats(port):
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/model/lm:stats",
                timeout=30) as resp:
            return json.loads(resp.read())["batcher"]

    prompt = list(range(1, 9))
    body_full = {"instances": [{"tokens": prompt}]}
    with faults.injected(scenario) as inj, \
            tempfile.TemporaryDirectory() as tmp:
        export(f"{tmp}/lm", 1, variables,
               loader="kubeflow_tpu.serving.loaders:lm_generate",
               config={"model": overrides, "max_new_tokens": max_new,
                       "temperature": 0.0})
        server = ModelServer(reload_backoff_s=0.5)
        server.add_model("lm", f"{tmp}/lm")
        # One step a round: the scenario sleeps once a DISPATCH, and
        # the expiry in step 2 counts on a sleep before every token.
        server.enable_batching("lm", batcher_factory(
            micro_batch_size=0, batch_timeout_s=0.005,
            lm_engine=True, lm_engine_slots=1,
            lm_engine_prefill_len=16, max_queue_depth=1,
            decode_rounds=1))
        httpd, _ = make_http_server(server, port=0, host="127.0.0.1")
        port = httpd.server_address[1]
        try:
            # -- 1. overload shed ---------------------------------------
            results: dict = {}

            def client(i, body):
                results[i] = predict_req(port, body)

            t0 = threading.Thread(target=client, args=(0, body_full))
            t0.start()
            deadline = time.time() + 120
            while engine_stats(port)["in_flight_requests"] < 1:
                assert time.time() < deadline, "first request never ran"
                time.sleep(0.01)
            # Slot busy (slow steps injected): 4 more arrivals — the
            # single queue seat takes one, the rest shed as 429.
            burst = [threading.Thread(target=client, args=(i, body_full))
                     for i in range(1, 5)]
            for t in burst:
                t.start()
            for t in [t0] + burst:
                t.join(timeout=180)
            codes = sorted(results[i][0] for i in range(5))
            assert codes.count(429) >= 1, codes
            assert codes.count(200) >= 2, codes  # slot + queue seat
            shed_headers = [results[i][1] for i in range(5)
                            if results[i][0] == 429]
            assert all(h.get("Retry-After") for h in shed_headers), (
                "429 responses must carry Retry-After")
            ok = [results[i][2] for i in range(5)
                  if results[i][0] == 200]
            for out in ok:
                tokens = out["predictions"][0]["tokens"]
                assert tokens[:len(prompt)] == prompt
                assert len(tokens) == len(prompt) + max_new
            # -- 2. deadline expiry mid-generation ----------------------
            code, _, payload = predict_req(
                port, {**body_full, "deadline_ms": 120})
            assert code == 504, (code, payload)
            assert "deadline" in payload["error"].lower()
            # The expired request's slot is reclaimed: a follow-up
            # full-budget request completes on the same single slot.
            code, _, payload = predict_req(port, body_full)
            assert code == 200, (code, payload)
            stats = engine_stats(port)
            assert stats["deadline_expired"] >= 1, stats
            assert stats["shed"] >= 1, stats
            # -- 3. loader circuit-break --------------------------------
            os.makedirs(f"{tmp}/lm/2")
            with open(f"{tmp}/lm/2/model.json", "w") as f:
                f.write("{corrupt json")
            raised = False
            try:
                server.reload("lm")
            except Exception:
                raised = True
            assert raised, "corrupt version must raise"
            attempts = inj.fired("loader.load")
            # Breaker open: repeated polls (the watcher loop) skip the
            # loader entirely — no hot-loop on the corrupt artifact.
            for _ in range(5):
                assert server.reload("lm") is False
            assert inj.fired("loader.load") == attempts
            # Last-good version keeps serving through the open breaker.
            code, _, _ = predict_req(port, body_full)
            assert code == 200
            assert server.get("lm").version == 1
            # Half-open after backoff (policy clock skipped forward):
            # the trial load runs, still corrupt, breaker re-opens.
            inj.advance_clock(30)
            raised = False
            try:
                server.reload("lm")
            except Exception:
                raised = True
            assert raised, "still-corrupt version must raise"
            assert inj.fired("loader.load") == attempts + 1
            # A NEW good version resets the breaker and loads at once.
            export(f"{tmp}/lm", 3, variables,
                   loader="kubeflow_tpu.serving.loaders:lm_generate",
                   config={"model": overrides,
                           "max_new_tokens": max_new,
                           "temperature": 0.0})
            assert server.reload("lm") is True
            assert server.get("lm").version == 3
            code, _, _ = predict_req(port, body_full)
            assert code == 200
            # -- 4. graceful drain --------------------------------------
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/readyz", timeout=30) as r:
                assert r.status == 200
            holder: dict = {}
            t = threading.Thread(
                target=lambda: holder.update(
                    {"resp": predict_req(port, body_full)}))
            t.start()
            deadline = time.time() + 120
            while server.inflight() < 1:
                assert time.time() < deadline, "drain request never ran"
                time.sleep(0.01)
            server.begin_drain()
            try:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/readyz", timeout=30)
                raise AssertionError("/readyz must be 503 while draining")
            except urllib.error.HTTPError as e:
                assert e.code == 503
                assert json.loads(e.read())["status"] == "draining"
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/healthz", timeout=30) as r:
                assert r.status == 200  # alive, just not ready
            t.join(timeout=180)
            assert holder["resp"][0] == 200, (
                "request accepted before drain was lost")
            assert wait_for_drain(server, deadline_s=30)
            # -- 5. shed/expired/breaker visible in kft_* metrics -------
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/metrics", timeout=30) as r:
                metrics = r.read().decode()
            for needle in ('kft_serving_shed_total{batcher="lm-v1"}',
                           'kft_serving_deadline_expired_total'
                           '{batcher="lm-v1"}',
                           'kft_serving_reload_failures_total'
                           '{model="lm"}'):
                line = [ln for ln in metrics.splitlines()
                        if ln.startswith(needle)]
                assert line and float(line[0].rsplit(" ", 1)[1]) >= 1, (
                    f"expected a nonzero {needle} series")
        finally:
            httpd.shutdown()
            server.stop()


def fleet_smoke(namespace: str = "kubeflow-test") -> None:
    """Hermetic fleet control-plane scenario: a load-aware router in
    front of THREE in-process serving replicas (each a real ModelServer
    + DecodeEngine + HTTP listener), discovered as label-selected pods
    through testing/fake_apiserver.py over real sockets.

      1. discovery + routing — kube-discovered endpoints, concurrent
         mixed traffic through the router, spread across replicas;
      2. scale-out under open-loop load — the autoscaler reads scraped
         kft_serving_* load off the registry and patches the serving
         Deployment's replicas through the SAME fake apiserver;
      3. replica kill mid-generation -> ejection within one probe
         interval; every request issued after the kill is retried onto
         survivors (failed-before-send policy) and succeeds; clock-
         skewed backoff expiry + restart -> half-open probe recovery;
      4. drain-aware rolling restart under continuous traffic — the
         draining replica gets no NEW work, finishes its in-flight,
         restarts, and ZERO accepted requests are lost end to end;
      5. distributed tracing end to end — a request proxied through
         the router yields ONE trace whose span tree walks
         router.request -> router.forward -> server.predict ->
         engine.admission -> engine.prefill_chunk -> engine.decode
         with a consistent trace_id (W3C traceparent propagation),
         retrievable from /debug/traces on the router AND the
         replica; with the healthy-sample rate at ZERO, a
         deadline-expired request is still always retained (tail
         sampling) while ok traffic is not;
      6. router/autoscaler/trace outcomes visible in kft_router_* /
         kft_autoscaler_* / kft_trace_* metrics.

    All replicas share one process (and thus one prom registry and one
    fault injector): per-endpoint /metrics scrapes stay correct because
    each replica's scrape refreshes its own server's gauges at render
    time.  Override the chaos scenario via KFT_FAULTS (the default
    slows engine steps so in-flight load is observable).
    """
    import json
    import os
    import tempfile
    import threading
    import urllib.error
    import urllib.request

    import jax
    import numpy as np

    from kubeflow_tpu.fleet.autoscaler import Autoscaler
    from kubeflow_tpu.fleet.endpoints import (
        EndpointRegistry,
        KubeEndpoints,
    )
    from kubeflow_tpu.fleet.router import FleetRouter, make_router_server
    from kubeflow_tpu.models.transformer import Transformer
    from kubeflow_tpu.operator.kube_http import HttpKube
    from kubeflow_tpu.runtime import tracing
    from kubeflow_tpu.serving.export import export
    from kubeflow_tpu.serving.http import make_http_server
    from kubeflow_tpu.serving.loaders import _model_config
    from kubeflow_tpu.serving.main import batcher_factory, wait_for_drain
    from kubeflow_tpu.serving.model_server import ModelServer
    from kubeflow_tpu.testing import faults
    from kubeflow_tpu.testing.fake_apiserver import make_fake_apiserver

    overrides = {
        "vocab_size": 128, "d_model": 32, "n_layers": 2, "n_heads": 4,
        "n_kv_heads": 2, "d_ff": 64, "head_dim": 8, "max_seq_len": 64,
        "dtype": "float32",
    }
    max_new = 8
    scenario = os.environ.get(faults.ENV) or \
        "seed=20260803;engine.step:sleep=0.02"
    prompt = list(range(1, 9))

    def make_replica(base, port=0):
        server = ModelServer()
        server.add_model("lm", base)
        # One step a round: the scenario's sleep comes once a
        # DISPATCH, and the in-flight load is observable only while a
        # sleep stands before every token.
        server.enable_batching("lm", batcher_factory(
            micro_batch_size=0, batch_timeout_s=0.005,
            lm_engine=True, lm_engine_slots=2,
            lm_engine_prefill_len=16, max_queue_depth=8,
            decode_rounds=1))
        httpd, _ = make_http_server(server, port=port,
                                    host="127.0.0.1")
        return server, httpd

    def predict_via(port, body, timeout=180):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/model/lm:predict",
            data=json.dumps(body).encode())
        try:
            with urllib.request.urlopen(req, timeout=timeout) as resp:
                return resp.status, json.loads(resp.read())
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read())

    def get_traces(port):
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/debug/traces",
                timeout=30) as resp:
            return json.loads(resp.read())

    model = Transformer(_model_config(overrides))
    variables = model.init(jax.random.key(0), np.zeros((1, 4), np.int32))
    replicas = []
    apiserver = router_httpd = None
    registry = None
    # Tracing ON for the whole scenario: every hop below stamps spans
    # into one shared in-process store (router + replicas share the
    # process here, which is exactly what makes the cross-"process"
    # trace_id consistency assertable end to end).
    tracing.enable(sample_rate=1.0, capacity=256)
    with faults.injected(scenario) as inj, \
            tempfile.TemporaryDirectory() as tmp:
        export(f"{tmp}/lm", 1, variables,
               loader="kubeflow_tpu.serving.loaders:lm_generate",
               config={"model": overrides, "max_new_tokens": max_new,
                       "temperature": 0.0})
        try:
            # -- fleet assembly -------------------------------------------
            replicas = [list(make_replica(f"{tmp}/lm"))
                        for _ in range(3)]
            apiserver, _, store = make_fake_apiserver()
            api_port = apiserver.server_address[1]
            kube = HttpKube(base_url=f"http://127.0.0.1:{api_port}")
            store.create_deployment({
                "metadata": {"namespace": namespace,
                             "name": "tpu-serving"},
                "spec": {"replicas": 1}})
            for i, (_, httpd) in enumerate(replicas):
                store.create_pod({
                    "metadata": {"namespace": namespace,
                                 "name": f"srv-{i}",
                                 "labels": {"app": "tpu-serving"}},
                    "spec": {"containers": [{"ports": [{
                        "name": "http",
                        "containerPort": httpd.server_address[1]}]}]},
                    "status": {"podIP": "127.0.0.1"}})
                store.set_pod_phase(namespace, f"srv-{i}", "Running")
            registry = EndpointRegistry(
                KubeEndpoints(kube, namespace, {"app": "tpu-serving"}),
                probe_interval_s=0.2, eject_threshold=1,
                eject_backoff_s=2.0)
            registry.refresh()
            assert len(registry.routable()) == 3, registry.describe()
            router = FleetRouter(registry, max_tries=3,
                                 try_timeout_s=180.0)
            router_httpd, _ = make_router_server(router, port=0,
                                                 host="127.0.0.1")
            rport = router_httpd.server_address[1]
            body_full = {"instances": [{"tokens": prompt}]}

            # -- 1. routed traffic spreads and completes ------------------
            results: dict = {}

            def client(i, body=body_full):
                results[i] = predict_via(rport, body)

            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(9)]
            for t in threads:
                t.start()
            # -- 2. scale-out under the open-loop burst -------------------
            autoscaler = Autoscaler(
                kube, namespace, "tpu-serving", registry,
                target_inflight_per_replica=1.0, tolerance=0.1,
                min_replicas=1, max_replicas=3,
                scale_up_cooldown_s=0.0, scale_down_cooldown_s=3600.0)
            deadline = time.time() + 120
            scaled = None
            while time.time() < deadline:
                registry.refresh()
                if registry.total_load() >= 2:
                    scaled = autoscaler.reconcile_once()
                    if scaled["applied"]:
                        break
                time.sleep(0.02)
            assert scaled is not None and scaled["applied"], (
                "autoscaler never saw the burst's load")
            dep = kube.get_deployment(namespace, "tpu-serving")
            assert dep["spec"]["replicas"] >= 2, dep
            for t in threads:
                t.join(timeout=180)
            assert sorted(r[0] for r in results.values()) \
                == [200] * 9, results
            for code, payload in results.values():
                tokens = payload["predictions"][0]["tokens"]
                assert tokens[:len(prompt)] == prompt
                assert len(tokens) == len(prompt) + max_new
            served_by = [i for i, (srv, _) in enumerate(replicas)
                         if (srv.batcher_stats("lm") or {}).get(
                             "requests", 0) > 0]
            assert len(served_by) >= 2, (
                f"load not spread: replicas {served_by} served")

            # -- 5a. trace propagation: router hop -> decode step ---------
            # One routed request must yield ONE trace whose span tree
            # carries the whole path with a consistent trace_id: the
            # router injected its forward span's traceparent, the
            # replica's server span continued it, and the engine
            # stamped admission/prefill/decode children at drain time.
            snap = get_traces(rport)
            assert snap["enabled"], snap
            full = None
            for trace in snap["traces"]:
                names = {s["name"] for s in trace["spans"]}
                if {"router.request", "router.forward",
                        "server.predict", "engine.admission",
                        "engine.prefill_chunk",
                        "engine.decode"} <= names:
                    full = trace
                    break
            assert full is not None, (
                f"no trace with the full router->engine span chain in "
                f"{[sorted({s['name'] for s in t['spans']}) for t in snap['traces']]}")
            tid = full["trace_id"]
            assert all(s["trace_id"] == tid for s in full["spans"])
            by_name = {}
            for s in full["spans"]:
                by_name.setdefault(s["name"], s)
            # Parent chain: server span under the forward span, which
            # is under the router root (the W3C header did its job).
            root = by_name["router.request"]
            assert root["parent_id"] is None
            assert by_name["router.forward"]["parent_id"] \
                == root["span_id"]
            assert by_name["server.predict"]["parent_id"] \
                == by_name["router.forward"]["span_id"]
            assert by_name["engine.decode"]["attrs"]["tokens"] \
                == max_new
            # The router root span's id is retrievable from a REPLICA's
            # /debug/traces too (shared store in the hermetic fleet):
            # the trace one port shows is the trace every port shows.
            replica_port = replicas[0][1].server_address[1]
            replica_snap = get_traces(replica_port)
            assert any(t["trace_id"] == tid
                       for t in replica_snap["traces"]), (
                f"trace {tid} not visible on replica "
                f"{replica_port}")

            # -- 3. kill mid-generation -> eject -> recover ---------------
            victim_srv, victim_httpd = replicas[0]
            victim_port = victim_httpd.server_address[1]
            holder: dict = {}
            t = threading.Thread(target=lambda: holder.update(
                {"resp": predict_via(victim_port, body_full,
                                     timeout=30)}))
            t.start()
            deadline = time.time() + 60
            while victim_srv.inflight() < 1:
                assert time.time() < deadline, \
                    "victim request never started"
                time.sleep(0.01)
            victim_httpd.shutdown()   # the kill, mid-generation
            victim_httpd.server_close()
            t.join(timeout=60)
            # One probe interval: a single refresh ejects it
            # (eject_threshold=1).
            registry.refresh()
            states = {s.name: s for s in registry.all()}
            assert states["srv-0"].breaker.open, registry.describe()
            assert len(registry.routable()) == 2
            # Everything issued AFTER the kill lands on survivors.
            results.clear()
            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(6)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=180)
            assert sorted(r[0] for r in results.values()) \
                == [200] * 6, results
            # Recovery: backoff expires on the skewed policy clock, the
            # replica returns on the SAME port (warm engine), and the
            # half-open probe readmits it.
            new_httpd = make_http_server(
                victim_srv, port=victim_port, host="127.0.0.1")[0]
            replicas[0][1] = new_httpd
            inj.advance_clock(10)
            registry.refresh()
            assert states["srv-0"].routable(), registry.describe()

            # -- 4. drain-aware rolling restart, zero loss ----------------
            stop_traffic = threading.Event()
            outcomes: list = []

            def traffic():
                while not stop_traffic.is_set():
                    outcomes.append(predict_via(rport, body_full)[0])

            traffic_threads = [threading.Thread(target=traffic)
                               for _ in range(3)]
            for t in traffic_threads:
                t.start()
            try:
                roll_srv, roll_httpd = replicas[1]
                roll_port = roll_httpd.server_address[1]
                roll_srv.begin_drain()
                registry.refresh()
                states = {s.name: s for s in registry.all()}
                assert not states["srv-1"].routable()
                assert states["srv-1"].state_label() == "draining"
                assert wait_for_drain(roll_srv, deadline_s=120), \
                    "draining replica never quiesced"
                roll_httpd.shutdown()
                roll_httpd.server_close()
                roll_srv.stop()
                # Restarted process: fresh ModelServer, same address.
                new_srv, new_httpd = make_replica(f"{tmp}/lm",
                                                  port=roll_port)
                replicas[1] = [new_srv, new_httpd]
                registry.refresh()
                states = {s.name: s for s in registry.all()}
                deadline = time.time() + 60
                while not states["srv-1"].routable():
                    assert time.time() < deadline, registry.describe()
                    time.sleep(0.05)
                    registry.refresh()
            finally:
                stop_traffic.set()
                for t in traffic_threads:
                    t.join(timeout=180)
            assert outcomes, "traffic generator produced nothing"
            bad = [c for c in outcomes if c != 200]
            assert not bad, (
                f"rolling restart lost {len(bad)}/{len(outcomes)} "
                f"accepted requests: {bad[:5]}")

            # -- 5b. tail sampling: errored requests ALWAYS retained ------
            # Fresh store with the healthy-sample rate at ZERO: ok
            # traffic keeps nothing, a deadline-expired request is
            # still captured (the always-keep tier).
            tracing.enable(sample_rate=0.0, capacity=64)
            assert predict_via(rport, body_full)[0] == 200
            code, payload = predict_via(
                rport, {**body_full, "deadline_ms": 0.001})
            assert code == 504, (code, payload)
            snap = get_traces(rport)
            statuses = [t["status"] for t in snap["traces"]]
            assert "deadline_exceeded" in statuses, snap["traces"]
            kept = [t for t in snap["traces"]
                    if t["status"] == "deadline_exceeded"]
            assert all(t["retained"] == "error" for t in kept)
            assert not any(t["status"] == "ok"
                           for t in snap["traces"]), (
                f"ok traffic retained at sample rate 0: {statuses}")

            # -- 6. control-plane outcomes in kft_* metrics ---------------
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{rport}/metrics",
                    timeout=30) as resp:
                metrics = resp.read().decode()
            from kubeflow_tpu.runtime.prom import (
                parse_metrics,
                sample_value,
            )

            parsed = parse_metrics(metrics)
            assert (sample_value(parsed, "kft_router_ejections_total",
                                 endpoint="srv-0") or 0) >= 1
            ok = sum(v for labels, v in
                     parsed.get("kft_router_requests_total", ())
                     if labels.get("outcome") == "ok")
            assert ok >= 15, parsed.get("kft_router_requests_total")
            assert (sample_value(
                parsed, "kft_autoscaler_desired_replicas") or 0) >= 2
            assert sample_value(parsed, "kft_router_endpoints",
                                state="routable") == 3, parsed.get(
                                    "kft_router_endpoints")
            # Trace-store health on the same scrape: spans recorded,
            # the errored trace retained, occupancy visible.
            assert (sample_value(parsed, "kft_trace_spans_total")
                    or 0) > 0
            assert (sample_value(parsed, "kft_trace_retained_total",
                                 reason="error") or 0) >= 1
            assert sample_value(
                parsed, "kft_trace_store_traces") is not None
        finally:
            tracing.disable()
            if router_httpd is not None:
                router_httpd.shutdown()
            if apiserver is not None:
                apiserver.shutdown()
                apiserver.server_close()
            for srv, httpd in replicas:
                try:
                    httpd.shutdown()
                    httpd.server_close()
                except Exception:
                    pass
                srv.stop()


def survivable_smoke(namespace: str = "kubeflow-test") -> None:
    """Hermetic survivable-inference scenario: a router in front of
    THREE engine replicas under a seeded chaos schedule that kills a
    replica MID-GENERATION and restarts it mid-burst.

      1. control — an uninterrupted streaming :generate run records
         the greedy token sequence (all replicas share one export, so
         greedy is replica-independent);
      2. chaos burst — concurrent streaming clients through the
         router while a deterministic kill schedule fires: the moment
         a client has received its 3rd token, the replica serving it
         is killed (its live sockets severed — the in-process
         equivalent of SIGKILL's socket signature).  EVERY accepted
         greedy request must complete with a token stream
         BIT-IDENTICAL to the control — zero duplicated, missing, or
         reordered tokens, zero 502s — because the router replays
         prompt + delivered tokens as a resume payload on a survivor
         and splices the streams (the engine admits the resume as one
         chunked prefill);
      3. the dead replica is force-ejected immediately (no probe-
         interval wait), then RESTARTED on the same port and readmits
         via the half-open probe on the skewed policy clock, serving
         post-restart traffic;
      4. dedup — a double-submitted :predict with one idempotency key
         executes ONCE and both submissions get the identical
         payload;
      5. kft_router_replays_total{outcome="ok"} > 0,
         kft_router_resume_tokens observations, and
         kft_serving_dedup_hits_total > 0 asserted as /metrics
         deltas, plus the router.replay / engine.resume hook-site
         encounters on the installed injector.
    """
    import json
    import os
    import socket
    import tempfile
    import threading
    import urllib.request
    from http.server import ThreadingHTTPServer

    import jax
    import numpy as np

    from kubeflow_tpu.fleet.endpoints import (
        Endpoint,
        EndpointRegistry,
        StaticEndpoints,
    )
    from kubeflow_tpu.fleet.router import FleetRouter, make_router_server
    from kubeflow_tpu.models.transformer import Transformer
    from kubeflow_tpu.runtime.prom import parse_metrics, sample_value
    from kubeflow_tpu.serving.export import export
    from kubeflow_tpu.serving.http import make_http_server
    from kubeflow_tpu.serving.loaders import _model_config
    from kubeflow_tpu.serving.main import batcher_factory
    from kubeflow_tpu.serving.model_server import ModelServer
    from kubeflow_tpu.testing import faults

    class KillableServer(ThreadingHTTPServer):
        """ThreadingHTTPServer that can sever its LIVE connections:
        shutdown() only stops accepting, while a crashed process also
        resets every established socket — kill() reproduces that
        signature so a mid-generation stream actually breaks."""

        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self._live = set()
            self._live_lock = threading.Lock()

        def process_request(self, request, client_address):
            with self._live_lock:
                self._live.add(request)
            super().process_request(request, client_address)

        def shutdown_request(self, request):
            with self._live_lock:
                self._live.discard(request)
            super().shutdown_request(request)

        def handle_error(self, request, client_address):
            # The severed handler threads die on BrokenPipe by
            # design; their tracebacks are not scenario output.
            pass

        def kill(self):
            # Sever FIRST: shutdown() blocks up to serve_forever's
            # 0.5 s poll, and a kill that waits that long lands after
            # a short generation already finished.
            with self._live_lock:
                live = list(self._live)
                self._live.clear()
            for sock in live:
                try:
                    sock.shutdown(socket.SHUT_RDWR)
                except OSError:
                    pass
                sock.close()
            self.shutdown()
            self.server_close()

    overrides = {
        "vocab_size": 128, "d_model": 32, "n_layers": 2, "n_heads": 4,
        "n_kv_heads": 2, "d_ff": 64, "head_dim": 8, "max_seq_len": 64,
        "dtype": "float32",
    }
    max_new = 12
    prompt = list(range(1, 9))
    # Seeded schedule: the step sleep paces generation so the 3rd-token
    # kill trigger always lands mid-generation, deterministically.
    scenario = os.environ.get(faults.ENV) or \
        "seed=20260804;engine.step:sleep=0.02"

    def make_replica(base, port=0):
        server = ModelServer()
        server.add_model("lm", base)
        # One step a round: the sleep paces generation token by token
        # (it comes once a DISPATCH), so the kill at the 3rd token
        # lands mid-generation on the replica that serves it.
        server.enable_batching("lm", batcher_factory(
            micro_batch_size=0, batch_timeout_s=0.005,
            lm_engine=True, lm_engine_slots=2,
            lm_engine_prefill_len=32, max_queue_depth=16,
            decode_rounds=1))
        httpd, _ = make_http_server(server, port=port, host="127.0.0.1",
                                    server_cls=KillableServer)
        return server, httpd

    def stream_via(port, body, on_tokens=None, timeout=180):
        """POST :generate, read the NDJSON stream; returns
        (meta, tokens, terminal_msg)."""
        import http.client

        conn = http.client.HTTPConnection("127.0.0.1", port,
                                          timeout=timeout)
        conn.request("POST", "/model/lm:generate",
                     json.dumps(body).encode(),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 200, (resp.status, resp.read())
        meta = terminal = None
        tokens = []
        while True:
            line = resp.readline()
            if not line:
                break
            line = line.strip()
            if not line:
                continue
            msg = json.loads(line)
            if "meta" in msg:
                meta = msg["meta"]
            elif "tokens" in msg:
                tokens.extend(msg["tokens"])
                if on_tokens is not None:
                    on_tokens(tokens)
            if "done" in msg or "error" in msg:
                terminal = msg
                break
        conn.close()
        return meta, tokens, terminal

    def predict_via(port, body, headers=None, timeout=180):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/model/lm:predict",
            data=json.dumps(body).encode(),
            headers=headers or {})
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, resp.read()

    def scrape(port):
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=30) as resp:
            return parse_metrics(resp.read().decode())

    model = Transformer(_model_config(overrides))
    variables = model.init(jax.random.key(0), np.zeros((1, 4), np.int32))
    replicas = []
    router_httpd = None
    with faults.injected(scenario) as inj, \
            tempfile.TemporaryDirectory() as tmp:
        export(f"{tmp}/lm", 1, variables,
               loader="kubeflow_tpu.serving.loaders:lm_generate",
               config={"model": overrides, "max_new_tokens": max_new,
                       "temperature": 0.0})
        try:
            replicas = [list(make_replica(f"{tmp}/lm"))
                        for _ in range(3)]
            eps = [Endpoint(name=f"srv-{i}",
                            url=f"http://127.0.0.1:"
                                f"{h.server_address[1]}")
                   for i, (_, h) in enumerate(replicas)]
            registry = EndpointRegistry(
                StaticEndpoints(eps), probe_interval_s=0.2,
                eject_threshold=3, eject_backoff_s=2.0)
            registry.refresh()
            assert len(registry.routable()) == 3, registry.describe()
            router = FleetRouter(registry, max_tries=3, max_replays=2,
                                 try_timeout_s=180.0)
            router_httpd, _ = make_router_server(router, port=0,
                                                 host="127.0.0.1")
            rport = router_httpd.server_address[1]
            body = {"tokens": prompt, "max_new_tokens": max_new}

            # -- 1. uninterrupted control run -------------------------
            meta, control, terminal = stream_via(
                replicas[0][1].server_address[1], body)
            assert meta["resumable"] is True, meta
            assert terminal.get("done") and len(control) == max_new, \
                (control, terminal)

            before = scrape(rport)

            # -- 2. chaos burst: kill the serving replica at token 3 --
            killed: dict = {}
            kill_lock = threading.Lock()

            def maybe_kill(tokens):
                if len(tokens) < 3:
                    return
                with kill_lock:
                    if killed:
                        return
                    for i, (srv, httpd) in enumerate(replicas):
                        stats = srv.batcher_stats("lm") or {}
                        if stats.get("in_flight_requests", 0) > 0:
                            killed["index"] = i
                            killed["port"] = httpd.server_address[1]
                            httpd.kill()
                            return

            results: dict = {}

            def client(i, on_tokens=None):
                results[i] = stream_via(rport, body,
                                        on_tokens=on_tokens)

            threads = [threading.Thread(
                target=client, args=(i, maybe_kill if i == 0 else None))
                for i in range(5)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=180)
            assert killed, "the kill schedule never fired"
            for i, (meta, tokens, terminal) in results.items():
                assert terminal is not None and terminal.get("done"), (
                    f"client {i} stream did not complete: {terminal}")
                assert tokens == control, (
                    f"client {i} stream drifted from the uninterrupted "
                    f"control: {tokens} != {control}")
            assert inj.fired("router.replay") >= 1
            assert inj.fired("engine.resume") >= 1

            # -- 3. immediate ejection, then restart + readmission ----
            victim = {s.name: s for s in registry.all()}[
                f"srv-{killed['index']}"]
            assert victim.breaker.open, registry.describe()
            assert victim.breaker.state() in ("open", "half_open")
            srv = replicas[killed["index"]][0]
            new_httpd = make_http_server(
                srv, port=killed["port"], host="127.0.0.1",
                server_cls=KillableServer)[0]
            replicas[killed["index"]][1] = new_httpd
            inj.advance_clock(30)
            registry.refresh()
            assert victim.routable(), registry.describe()
            _, tokens, terminal = stream_via(rport, body)
            assert terminal.get("done") and tokens == control

            # -- 4. dedup: double submit executes once ----------------
            target_srv, target_httpd = replicas[(killed["index"] + 1)
                                                % 3]
            tport = target_httpd.server_address[1]
            stats0 = target_srv.batcher_stats("lm") or {}
            pbody = {"instances": [{"tokens": prompt}]}
            hdrs = {"x-kft-idempotency-key": "survivable-e2e-1"}
            s1, payload1 = predict_via(tport, pbody, hdrs)
            s2, payload2 = predict_via(tport, pbody, hdrs)
            assert (s1, s2) == (200, 200)
            assert payload1 == payload2, "dedup hit changed the payload"
            stats1 = target_srv.batcher_stats("lm") or {}
            assert stats1.get("requests", 0) \
                == stats0.get("requests", 0) + 1, (
                "double submit executed twice", stats0, stats1)

            # -- 5. /metrics deltas (shared in-process registry) ------
            after = scrape(rport)

            def delta(name, **labels):
                return (sample_value(after, name, **labels) or 0) \
                    - (sample_value(before, name, **labels) or 0)

            assert delta("kft_router_replays_total", outcome="ok") \
                >= 1, after.get("kft_router_replays_total")
            assert delta("kft_serving_dedup_hits_total", model="lm") \
                >= 1, after.get("kft_serving_dedup_hits_total")
            assert delta("kft_router_resume_tokens_count") >= 1, \
                after.get("kft_router_resume_tokens_count")
            # Zero 502/504 THIS scenario (delta — an earlier in-process
            # scenario may have driven deliberate failures).
            prior = {tuple(sorted(labels.items())): v for labels, v in
                     before.get("kft_router_requests_total", ())}
            bad = {tuple(sorted(labels.items())): v for labels, v in
                   after.get("kft_router_requests_total", ())
                   if labels.get("code") in ("502", "504")
                   and v > prior.get(
                       tuple(sorted(labels.items())), 0)}
            assert not bad, bad
        finally:
            if router_httpd is not None:
                router_httpd.shutdown()
            for srv, httpd in replicas:
                try:
                    httpd.shutdown()
                    httpd.server_close()
                except Exception:
                    pass
                srv.stop()


def kv_spill_smoke(namespace: str = "kubeflow-test") -> None:
    """Hermetic hierarchical-KV scenario (§5.10): three engine
    replicas with a TIGHT device pool (12 pages) and a host spill
    tier behind the fleet router.

      1. control — uninterrupted turn-1 and turn-2 greedy streams
         recorded on one replica;
      2. spill under pressure — multi-turn sessions park their KV
         (``park_kv``) on a replica until the parked mass exceeds the
         device pool; the overflow spills to host RAM with ZERO
         sheds and ZERO destructive evictions
         (kft_engine_kv_spill_total{direction="out"} and the host-
         tier gauge move, kv_shed stays flat);
      3. re-import — the first parked session's turn 2 re-imports its
         spilled pages through kv_import (spill_total{direction="in"}
         delta) and streams BIT-IDENTICAL to the uninterrupted
         control;
      4. resume-by-FETCH failover — a session parked on BOTH
         surviving replicas is killed mid-generation on whichever
         replica serves its turn 2; the router's replay fetches the
         session's pages from a surviving peer (:fetch_kv,
         kft_router_kv_fetch_total{outcome="ok"} delta, engine.fetch
         hook-site encounter) and the spliced stream equals the
         control.
    """
    import json
    import os
    import socket
    import tempfile
    import threading
    import urllib.request
    from http.server import ThreadingHTTPServer

    import jax
    import numpy as np

    from kubeflow_tpu.fleet.endpoints import (
        Endpoint,
        EndpointRegistry,
        StaticEndpoints,
    )
    from kubeflow_tpu.fleet.router import FleetRouter, make_router_server
    from kubeflow_tpu.models.transformer import Transformer
    from kubeflow_tpu.runtime.prom import parse_metrics, sample_value
    from kubeflow_tpu.serving.export import export
    from kubeflow_tpu.serving.http import make_http_server
    from kubeflow_tpu.serving.loaders import _model_config
    from kubeflow_tpu.serving.main import batcher_factory
    from kubeflow_tpu.serving.model_server import ModelServer
    from kubeflow_tpu.testing import faults

    class KillableServer(ThreadingHTTPServer):
        """See survivable_smoke: severs live sockets on kill()."""

        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self._live = set()
            self._live_lock = threading.Lock()

        def process_request(self, request, client_address):
            with self._live_lock:
                self._live.add(request)
            super().process_request(request, client_address)

        def shutdown_request(self, request):
            with self._live_lock:
                self._live.discard(request)
            super().shutdown_request(request)

        def handle_error(self, request, client_address):
            pass

        def kill(self):
            with self._live_lock:
                live = list(self._live)
                self._live.clear()
            for sock in live:
                try:
                    sock.shutdown(socket.SHUT_RDWR)
                except OSError:
                    pass
                sock.close()
            self.shutdown()
            self.server_close()

    overrides = {
        "vocab_size": 128, "d_model": 32, "n_layers": 2, "n_heads": 4,
        "n_kv_heads": 2, "d_ff": 64, "head_dim": 8, "max_seq_len": 64,
        "dtype": "float32",
    }
    max_new = 12
    rng = np.random.RandomState(20260807)
    prompts = [rng.randint(1, 120, size=(9 + i,)).tolist()
               for i in range(5)]
    scenario = os.environ.get(faults.ENV) or \
        "seed=20260807;engine.step:sleep=0.02"

    def make_replica(base, port=0):
        server = ModelServer()
        server.add_model("lm", base)
        # One step a round: step 4 kills a replica once 3 tokens have
        # streamed, and the 25-token turn-2 prompt plus what was
        # delivered must still fit the 32-token prefill width for the
        # survivor to take the resume (a round of 8 delivers 9).
        server.enable_batching("lm", batcher_factory(
            micro_batch_size=0, batch_timeout_s=0.005,
            lm_engine=True, lm_engine_slots=2,
            lm_engine_prefill_len=32, max_queue_depth=16,
            kv_block_tokens=4, kv_pool_blocks=12,
            host_spill_blocks=60, decode_rounds=1))
        httpd, _ = make_http_server(server, port=port, host="127.0.0.1",
                                    server_cls=KillableServer)
        return server, httpd

    def stream_via(port, body, on_tokens=None, timeout=180):
        import http.client

        conn = http.client.HTTPConnection("127.0.0.1", port,
                                          timeout=timeout)
        conn.request("POST", "/model/lm:generate",
                     json.dumps(body).encode(),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 200, (resp.status, resp.read())
        meta = terminal = None
        tokens = []
        while True:
            line = resp.readline()
            if not line:
                break
            line = line.strip()
            if not line:
                continue
            msg = json.loads(line)
            if "meta" in msg:
                meta = msg["meta"]
            elif "tokens" in msg:
                tokens.extend(msg["tokens"])
                if on_tokens is not None:
                    on_tokens(tokens)
            if "done" in msg or "error" in msg:
                terminal = msg
                break
        conn.close()
        return meta, tokens, terminal

    def scrape(port):
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=30) as resp:
            return parse_metrics(resp.read().decode())

    model = Transformer(_model_config(overrides))
    variables = model.init(jax.random.key(0), np.zeros((1, 4), np.int32))
    replicas = []
    router_httpd = None
    with faults.injected(scenario) as inj, \
            tempfile.TemporaryDirectory() as tmp:
        export(f"{tmp}/lm", 1, variables,
               loader="kubeflow_tpu.serving.loaders:lm_generate",
               config={"model": overrides, "max_new_tokens": max_new,
                       "temperature": 0.0})
        try:
            replicas = [list(make_replica(f"{tmp}/lm"))
                        for _ in range(3)]
            ports = [h.server_address[1] for _, h in replicas]
            eps = [Endpoint(name=f"srv-{i}",
                            url=f"http://127.0.0.1:{p}")
                   for i, p in enumerate(ports)]
            registry = EndpointRegistry(
                StaticEndpoints(eps), probe_interval_s=0.2,
                eject_threshold=3, eject_backoff_s=2.0)
            registry.refresh()
            assert len(registry.routable()) == 3, registry.describe()
            router = FleetRouter(registry, max_tries=3, max_replays=2,
                                 try_timeout_s=180.0)
            router_httpd, _ = make_router_server(router, port=0,
                                                 host="127.0.0.1")
            rport = router_httpd.server_address[1]

            # -- 1. uninterrupted controls on replica 0 ---------------
            def turn1_body(i, park=False):
                b = {"tokens": prompts[i], "max_new_tokens": max_new}
                if park:
                    b["park_kv"] = True
                return b

            controls = {}
            for i in range(len(prompts)):
                _, toks, term = stream_via(ports[0], turn1_body(i))
                assert term.get("done") and len(toks) == max_new
                controls[i] = toks
            # Turn 2 extends turn 1's full context with 3 user tokens.
            extra = rng.randint(1, 120, size=(3,)).tolist()

            def turn2_body(i):
                return {"tokens": prompts[i] + controls[i] + extra,
                        "max_new_tokens": max_new}

            control2 = {}
            for i in (0, 1):
                _, toks, term = stream_via(ports[0], turn2_body(i))
                assert term.get("done"), term
                control2[i] = toks

            before = scrape(rport)
            spills_before = inj.fired("engine.spill")

            def delta(name, **labels):
                # Deltas, not absolutes: the registry is process-wide
                # and an earlier in-process scenario may have moved
                # the same counters.  Engine-labeled reads must pin
                # engine="lm-v1" (batcher_factory names engines
                # {model}-v{version}): sample_value returns the FIRST
                # matching series, and an earlier test file's engines
                # (default name "engine") register theirs first.
                return (sample_value(scrape(rport), name, **labels)
                        or 0) - (sample_value(before, name, **labels)
                                 or 0)

            # -- 2. parked sessions overflow the pool into host RAM --
            # Replica 1 parks every session (5 contexts x ~5 pages in
            # a 12-page pool => the cold ones MUST spill); replica 2
            # parks session 1 too — the fetch-failover scenario needs
            # the session host-resident on BOTH survivors.
            for i in range(len(prompts)):
                _, toks, term = stream_via(
                    ports[1], turn1_body(i, park=True))
                assert term.get("done") and toks == controls[i], (
                    f"parked session {i} diverged", toks)
            _, toks, _ = stream_via(ports[2], turn1_body(1, park=True))
            assert toks == controls[1]
            assert inj.fired("engine.spill") > spills_before
            assert delta("kft_engine_kv_spill_total",
                         engine="lm-v1", direction="out") > 0
            assert (sample_value(scrape(rport),
                                 "kft_engine_host_tier_blocks",
                                 engine="lm-v1")
                    or 0) > 0
            assert delta("kft_engine_kv_shed_no_blocks_total",
                         engine="lm-v1") == 0, (
                "pool-exhaustion shed while spillable mass existed")
            st1 = replicas[1][0].batcher_stats("lm") or {}
            assert st1.get("shed", 0) == 0, st1
            assert st1.get("parked_sessions") == len(prompts)
            assert st1.get("tokens_addressable") == (12 + 60) * 4
            assert st1.get("kv_spill_ratio", 0) > 0

            # -- 3. turn-2 re-import: bit-identical to the control ----
            _, toks, term = stream_via(ports[1], turn2_body(0))
            assert term.get("done") and toks == control2[0], (
                "re-imported resume diverged from control",
                toks, control2[0])
            assert delta("kft_engine_kv_spill_total",
                         engine="lm-v1", direction="in") > 0, \
                "turn 2 did not re-import spilled pages"

            # -- 4. kill mid-generation; resume by FETCH from a peer --
            killed: dict = {}
            kill_lock = threading.Lock()

            def maybe_kill(tokens):
                if len(tokens) < 3:
                    return
                with kill_lock:
                    if killed:
                        return
                    for i, (srv, httpd) in enumerate(replicas):
                        stats = srv.batcher_stats("lm") or {}
                        if stats.get("in_flight_requests", 0) > 0:
                            killed["index"] = i
                            httpd.kill()
                            return

            meta, toks, term = stream_via(rport, turn2_body(1),
                                          on_tokens=maybe_kill)
            assert killed, "the kill never fired"
            assert term is not None and term.get("done"), term
            assert toks == control2[1], (
                "fetch-resumed stream diverged from control",
                toks, control2[1])
            assert delta("kft_router_kv_fetch_total",
                         outcome="ok") >= 1
            assert delta("kft_router_replays_total",
                         outcome="ok") >= 1
            assert inj.fired("engine.fetch") >= 1
        finally:
            if router_httpd is not None:
                router_httpd.shutdown()
            for srv, httpd in replicas:
                try:
                    httpd.shutdown()
                    httpd.server_close()
                except Exception:
                    pass
                srv.stop()


def multichip_serving_smoke(namespace: str = "kubeflow-test") -> None:
    """Hermetic multi-chip serving scenario (§5.9) over a forced
    multi-device host platform:

      1. topology — a PREFILL-role replica and a DECODE-role replica
         (its engine tensor-parallel over a 2-device mesh,
         serving/sharding.py) behind the fleet router; the registry
         learns both tiers off /readyz;
      2. tiered :generate — streams through the router pipeline
         prefill-then-decode (the prompt's KV pages cross as a
         block-page handoff payload) and every token stream is
         IDENTICAL to a unified single-tier control replica's;
      3. handoff counters — kft_engine_handoff_pages_total
         {direction="export"} on the prefill replica and
         {direction="import"} on the decode replica move as /metrics
         deltas, as do kft_router_tier_requests_total{tier};
      4. decode-pool death mid-handoff — with the only decode
         replica dead, a tiered :generate sheds typed 429 Overloaded
         (Retry-After set) instead of hanging or 502ing.

    Needs >= 4 local devices; when the current process sees fewer
    (standalone CI runs, a one-chip machine), it re-execs itself in a
    subprocess pinned to four VIRTUAL CPU devices
    (``--xla_force_host_platform_device_count=4``, the test conftest's
    trick) and says so: that run proves sharding and control flow, and
    nothing about chips.
    """
    import os
    import sys

    import jax

    if jax.device_count() < 4:
        import subprocess

        print(f"multichip_serving: {jax.device_count()} "
              f"{jax.devices()[0].platform} device(s) here, need 4 — "
              "running the multichip leg on 4 virtual CPU devices in a "
              "child process, NOT on accelerator chips", flush=True)
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        flags = env.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            env["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=4"
            ).strip()
        proc = subprocess.run(
            [sys.executable, "-m", "kubeflow_tpu.testing.e2e",
             "multichip_serving", "--namespace", namespace],
            env=env, timeout=600)
        assert proc.returncode == 0, (
            f"multichip_serving re-exec failed rc={proc.returncode}")
        return

    import json
    import tempfile
    import urllib.request

    import numpy as np

    from kubeflow_tpu.fleet.endpoints import (
        Endpoint,
        EndpointRegistry,
        StaticEndpoints,
    )
    from kubeflow_tpu.fleet.router import FleetRouter, make_router_server
    from kubeflow_tpu.models.transformer import Transformer
    from kubeflow_tpu.runtime.prom import parse_metrics, sample_value
    from kubeflow_tpu.serving.export import export
    from kubeflow_tpu.serving.http import make_http_server
    from kubeflow_tpu.serving.loaders import _model_config
    from kubeflow_tpu.serving.main import batcher_factory
    from kubeflow_tpu.serving.model_server import ModelServer

    overrides = {"vocab_size": 96, "d_model": 32, "n_layers": 2,
                 "n_heads": 4, "n_kv_heads": 4, "d_ff": 64,
                 "head_dim": 8, "max_seq_len": 64, "dtype": "float32"}
    max_new = 10
    rng = np.random.RandomState(20260804)
    prompts = [rng.randint(1, 96, size=(n,)).tolist()
               for n in (9, 12, 16)]

    import socket
    import threading
    from http.server import ThreadingHTTPServer

    class KillableServer(ThreadingHTTPServer):
        """shutdown() only stops accepting; a dead pod also resets
        every ESTABLISHED socket (including the router's pooled
        keep-alive upstreams) — kill() reproduces that signature."""

        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self._live = set()
            self._live_lock = threading.Lock()

        def process_request(self, request, client_address):
            with self._live_lock:
                self._live.add(request)
            super().process_request(request, client_address)

        def shutdown_request(self, request):
            with self._live_lock:
                self._live.discard(request)
            super().shutdown_request(request)

        def kill(self):
            self.shutdown()
            self.server_close()
            with self._live_lock:
                live = list(self._live)
            for sock in live:
                try:
                    sock.shutdown(socket.SHUT_RDWR)
                except OSError:
                    pass
                try:
                    sock.close()
                except OSError:
                    pass

    def make_replica(base, role, mesh=""):
        server = ModelServer(role=role)
        server.add_model("lm", base)
        server.enable_batching("lm", batcher_factory(
            micro_batch_size=0, batch_timeout_s=0.005,
            lm_engine=True, lm_engine_slots=2,
            lm_engine_prefill_len=32, kv_block_tokens=4,
            max_queue_depth=16, mesh=mesh))
        httpd, _ = make_http_server(server, port=0, host="127.0.0.1",
                                    server_cls=KillableServer)
        return server, httpd

    def stream_via(port, body, path="/model/lm:generate",
                   timeout=180):
        import http.client

        conn = http.client.HTTPConnection("127.0.0.1", port,
                                          timeout=timeout)
        conn.request("POST", path, json.dumps(body).encode(),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            payload = resp.read()
            conn.close()
            return resp.status, dict(resp.headers.items()), payload
        tokens = []
        terminal = None
        while True:
            line = resp.readline()
            if not line:
                break
            line = line.strip()
            if not line:
                continue
            msg = json.loads(line)
            if "tokens" in msg:
                tokens.extend(msg["tokens"])
            if "done" in msg or "error" in msg:
                terminal = msg
                break
        conn.close()
        return 200, tokens, terminal

    def scrape(port):
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics",
                timeout=30) as resp:
            return parse_metrics(resp.read().decode())

    model = Transformer(_model_config(overrides))
    variables = model.init(jax.random.key(0),
                           np.zeros((1, 4), np.int32))
    servers = []
    router_httpd = None
    with tempfile.TemporaryDirectory() as tmp:
        export(f"{tmp}/lm", 1, variables,
               loader="kubeflow_tpu.serving.loaders:lm_generate",
               config={"model": overrides, "max_new_tokens": max_new,
                       "temperature": 0.0})
        try:
            pre_srv, pre_httpd = make_replica(f"{tmp}/lm", "prefill")
            dec_srv, dec_httpd = make_replica(f"{tmp}/lm", "decode",
                                              mesh="tensor=2")
            uni_srv, uni_httpd = make_replica(f"{tmp}/lm", "unified")
            servers = [(pre_srv, pre_httpd), (dec_srv, dec_httpd),
                       (uni_srv, uni_httpd)]
            pre_port = pre_httpd.server_address[1]
            dec_port = dec_httpd.server_address[1]
            uni_port = uni_httpd.server_address[1]
            # The fleet is the two TIERS; the unified replica stays
            # outside as the single-tier control.
            registry = EndpointRegistry(StaticEndpoints([
                Endpoint(name="pre-0",
                         url=f"http://127.0.0.1:{pre_port}"),
                Endpoint(name="dec-0",
                         url=f"http://127.0.0.1:{dec_port}"),
            ]), probe_interval_s=0.2, eject_threshold=2)
            registry.refresh()
            tiers = {s.name: s.tier for s in registry.all()}
            assert tiers == {"pre-0": "prefill", "dec-0": "decode"}, (
                f"registry failed to learn tiers: {tiers}")
            router = FleetRouter(registry, max_tries=3,
                                 try_timeout_s=60.0)
            router_httpd, _ = make_router_server(router, port=0,
                                                 host="127.0.0.1")
            rport = router_httpd.server_address[1]

            pre0 = scrape(pre_port)
            dec0 = scrape(dec_port)
            r0 = scrape(rport)

            # --- tiered streams match the unified control exactly ---
            for prompt in prompts:
                body = {"tokens": prompt}
                st, want, wterm = stream_via(uni_port, body)
                assert st == 200 and wterm.get("done"), (st, wterm)
                st, got, gterm = stream_via(rport, body)
                assert st == 200, (st, got)
                assert gterm.get("done"), gterm
                assert got == want, (
                    f"tiered stream diverged from unified control "
                    f"for len {len(prompt)}: {got} != {want}")

            # --- handoff + tier counters moved as deltas ------------
            def delta(before, after, name, **labels):
                b = sample_value(before, name, **labels) or 0
                a = sample_value(after, name, **labels) or 0
                return a - b

            pre1, dec1, r1 = (scrape(pre_port), scrape(dec_port),
                              scrape(rport))
            exported = delta(pre0, pre1,
                             "kft_engine_handoff_pages_total",
                             engine="lm-v1", direction="export")
            imported = delta(dec0, dec1,
                             "kft_engine_handoff_pages_total",
                             engine="lm-v1", direction="import")
            assert exported > 0, "no pages exported by prefill tier"
            assert imported > 0, "no pages imported by decode tier"
            assert delta(r0, r1, "kft_router_tier_requests_total",
                         tier="prefill") == len(prompts)
            assert delta(r0, r1, "kft_router_tier_requests_total",
                         tier="decode") == len(prompts)
            # Per-replica (the three in-process replicas share one
            # prom registry, so the engine-labeled gauge aliases —
            # the :stats route is per-server truth).
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{dec_port}/model/lm:stats",
                    timeout=30) as resp:
                dec_stats = json.loads(resp.read())["batcher"]
            assert dec_stats["mesh_devices"] == 2, dec_stats
            assert dec_stats["handoff_pages_in"] > 0
            assert dec_stats["compiled_programs"]["kv_import"] == 1

            # --- decode-pool death mid-handoff: typed Overloaded ----
            dec_httpd.kill()
            # The registry still lists the decode tier as routable
            # (no probe ran since the kill), so the router commits to
            # the tiered path, the prefill leg succeeds, and the dead
            # decode pool must shed typed 429 — never hang or 502.
            st, headers, payload = stream_via(rport,
                                              {"tokens": prompts[0]})
            assert st == 429, (st, payload)
            assert "Retry-After" in headers, headers
            r2 = scrape(rport)
            assert delta(r1, r2, "kft_router_requests_total",
                         outcome="shed", code="429") >= 1
        finally:
            if router_httpd is not None:
                router_httpd.shutdown()
            for srv, httpd in servers:
                try:
                    httpd.shutdown()
                except Exception:
                    pass
                srv.stop()


def adapter_serving_smoke(namespace: str = "kubeflow-test") -> None:
    """Hermetic adapter-array multi-model serving scenario (§5.11):
    THREE per-tenant adapters over a TWO-replica engine fleet behind
    the router, every variant riding the base model's one compiled
    program set.

      1. hot-load under live traffic — while concurrent base-model
         clients stream through the router, the first requests naming
         ``lm@alpha`` / ``lm@beta`` hot-load their artifacts from the
         adapter directory mid-burst; every request (base and variant)
         returns 200 with tokens IDENTICAL to a sequential per-adapter
         control server's;
      2. co-batched mixed burst — base/alpha/beta concurrently through
         the router: all complete, all token-identical to their
         sequential controls, and each engine still reports only the
         base program set over :stats (no per-adapter executable);
      3. evict-under-pressure — with 2 registry slots per replica, a
         gamma request against a replica holding an IN-FLIGHT alpha
         generation must evict the idle beta, never the pinned alpha:
         the live request completes bit-identical, beta hot-reloads on
         its next request, and kft_engine_adapter_evictions_total
         moves as a /metrics delta;
      4. advertisement + affinity — /readyz advertises loaded adapter
         digests, the registry learns them at the next probe, and
         routed ``lm@alpha`` traffic prefers warm replicas
         (kft_router_adapter_affinity_total{outcome="hit"} delta);
         an unknown adapter sheds typed 404 through the whole stack.

    kft_engine_adapter_loads_total / _requests_total / _evictions_total
    and the router affinity counter are all asserted as /metrics
    deltas.  Override the chaos scenario via KFT_FAULTS (the default
    slows engine steps so the in-flight pin in step 3 is observable).
    """
    import json
    import os
    import tempfile
    import threading
    import urllib.error
    import urllib.request

    import jax
    import numpy as np

    from kubeflow_tpu.fleet.endpoints import (
        Endpoint,
        EndpointRegistry,
        StaticEndpoints,
    )
    from kubeflow_tpu.fleet.router import FleetRouter, make_router_server
    from kubeflow_tpu.models.transformer import Transformer
    from kubeflow_tpu.runtime.prom import parse_metrics, sample_value
    from kubeflow_tpu.serving.adapters import (
        random_adapter_factors,
        save_adapter,
    )
    from kubeflow_tpu.serving.export import export
    from kubeflow_tpu.serving.http import make_http_server
    from kubeflow_tpu.serving.loaders import _model_config
    from kubeflow_tpu.serving.main import batcher_factory
    from kubeflow_tpu.serving.model_server import ModelServer
    from kubeflow_tpu.testing import faults

    overrides = {"vocab_size": 96, "d_model": 32, "n_layers": 2,
                 "n_heads": 4, "n_kv_heads": 2, "d_ff": 64,
                 "head_dim": 8, "max_seq_len": 64, "dtype": "float32"}
    cfg = _model_config(overrides)
    max_new, rank = 8, 4
    scenario = os.environ.get(faults.ENV) or \
        "seed=20260807;engine.step:sleep=0.01"
    rng = np.random.RandomState(20260807)
    prompts = [rng.randint(1, 96, size=(n,)).tolist()
               for n in (8, 5, 11, 9)]
    tenants = ("alpha", "beta", "gamma")

    def make_replica(base, adir):
        server = ModelServer()
        server.add_model("lm", base)
        server.enable_batching("lm", batcher_factory(
            micro_batch_size=0, batch_timeout_s=0.005,
            lm_engine=True, lm_engine_slots=3,
            lm_engine_prefill_len=16, kv_block_tokens=4,
            max_queue_depth=16, adapters_dir=adir,
            adapter_slots=2, adapter_rank=rank,
            # One step a round: a sleep before every token keeps the
            # pinned generation of step 3 in flight long enough to see.
            decode_rounds=1))
        httpd, _ = make_http_server(server, port=0, host="127.0.0.1")
        return server, httpd

    def predict_via(port, name, prompt, timeout=180):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/model/{name}:predict",
            data=json.dumps(
                {"instances": [{"tokens": prompt}]}).encode())
        try:
            with urllib.request.urlopen(req, timeout=timeout) as resp:
                return resp.status, json.loads(resp.read())
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read())

    def scrape(port):
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics",
                timeout=30) as resp:
            return parse_metrics(resp.read().decode())

    def delta(before, after, name, **labels):
        return (sample_value(after, name, **labels) or 0.0) \
            - (sample_value(before, name, **labels) or 0.0)

    model = Transformer(cfg)
    variables = model.init(jax.random.key(0),
                           np.zeros((1, 4), np.int32))
    replicas = []
    router_httpd = None
    with faults.injected(scenario), \
            tempfile.TemporaryDirectory() as tmp:
        export(f"{tmp}/lm", 1, variables,
               loader="kubeflow_tpu.serving.loaders:lm_generate",
               config={"model": overrides, "max_new_tokens": max_new,
                       "temperature": 0.0})
        adir = f"{tmp}/adapters"
        os.makedirs(adir)
        for i, name in enumerate(tenants):
            save_adapter(f"{adir}/{name}.npz", random_adapter_factors(
                cfg, rank, seed=100 + i, scale=0.5))
        control = None
        try:
            # -- sequential per-adapter controls (one request in
            # flight at a time, dedicated server: the co-batched
            # fleet must be bit-identical to THIS).
            control = ModelServer()
            control.add_model("lm", f"{tmp}/lm")
            control.enable_batching("lm", batcher_factory(
                micro_batch_size=0, batch_timeout_s=0.005,
                lm_engine=True, lm_engine_slots=1,
                lm_engine_prefill_len=16, adapters_dir=adir,
                adapter_slots=3, adapter_rank=rank))
            want = {}
            for name in ("lm", "lm@alpha", "lm@beta", "lm@gamma"):
                for p in prompts:
                    out = control.predict(
                        name, {"tokens": np.asarray(p, np.int32)[None]})
                    want[(name, tuple(p))] = \
                        np.asarray(out["tokens"])[0].tolist()
            assert want[("lm@alpha", tuple(prompts[0]))] != \
                want[("lm", tuple(prompts[0]))], (
                "adapter delta too small to move greedy decode — the "
                "identity assertions below would be vacuous")

            # -- fleet assembly --------------------------------------
            replicas = [make_replica(f"{tmp}/lm", adir)
                        for _ in range(2)]
            ports = [h.server_address[1] for _, h in replicas]
            registry = EndpointRegistry(StaticEndpoints([
                Endpoint(name=f"srv-{i}",
                         url=f"http://127.0.0.1:{p}")
                for i, p in enumerate(ports)]),
                probe_interval_s=0.2, eject_threshold=2)
            registry.refresh()
            assert len(registry.routable()) == 2, registry.describe()
            router = FleetRouter(registry, max_tries=3,
                                 try_timeout_s=180.0)
            router_httpd, _ = make_router_server(router, port=0,
                                                 host="127.0.0.1")
            rport = router_httpd.server_address[1]
            m0 = scrape(ports[0])

            # -- 1. hot-load under live base traffic -----------------
            results: dict = {}

            def client(i, name, prompt):
                results[i] = (name, prompt,
                              predict_via(rport, name, prompt))

            base_threads = [
                threading.Thread(target=client,
                                 args=(i, "lm", prompts[i % 2]))
                for i in range(4)]
            for t in base_threads:
                t.start()
            # Mid-burst: the FIRST requests naming the variants land
            # while base traffic is in flight — cold artifact loads
            # under live load.
            hot_threads = [
                threading.Thread(
                    target=client,
                    args=(4 + j, f"lm@{name}", prompts[2 + j % 2]))
                for j, name in enumerate(("alpha", "beta"))]
            for t in hot_threads:
                t.start()
            for t in base_threads + hot_threads:
                t.join(timeout=180)
            assert len(results) == 6
            for name, prompt, (code, payload) in results.values():
                assert code == 200, (name, code, payload)
                got = payload["predictions"][0]["tokens"]
                assert got == want[(name, tuple(prompt))], (
                    f"{name} diverged from its sequential control "
                    f"under the hot-load burst")

            # -- 2. co-batched mixed burst ---------------------------
            results = {}
            mixed = [("lm", prompts[0]), ("lm@alpha", prompts[1]),
                     ("lm@beta", prompts[2]), ("lm@alpha", prompts[3]),
                     ("lm", prompts[2]), ("lm@beta", prompts[0]),
                     ("lm@alpha", prompts[2]), ("lm", prompts[1]),
                     ("lm@beta", prompts[3])]
            threads = [threading.Thread(target=client,
                                        args=(i, name, prompt))
                       for i, (name, prompt) in enumerate(mixed)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=180)
            assert sorted(r[2][0] for r in results.values()) \
                == [200] * len(mixed), results
            for name, prompt, (_, payload) in results.values():
                got = payload["predictions"][0]["tokens"]
                assert got == want[(name, tuple(prompt))], (
                    f"{name} diverged from its sequential control "
                    f"in the co-batched burst")
            # One program set per engine — no per-adapter executable.
            for i, (srv, _) in enumerate(replicas):
                stats = srv.batcher_stats("lm") or {}
                programs = stats.get("compiled_programs") or {}
                assert set(k for k, v in programs.items() if v) <= \
                    {"chunked_prefill", "decode_rounds"}, (
                    f"replica {i} grew extra programs under mixed "
                    f"adapter traffic: {programs}")

            # -- 3. evict-under-pressure with a live pin -------------
            # Direct to replica 0: make alpha + beta resident, hold an
            # alpha generation IN FLIGHT, then demand gamma — its load
            # must evict idle beta, never the pinned alpha.
            srv0, port0 = replicas[0][0], ports[0]
            for name in ("lm@alpha", "lm@beta"):
                code, payload = predict_via(port0, name, prompts[0])
                assert code == 200, (name, code, payload)
            m_before = scrape(port0)
            inflight0 = srv0.inflight()
            holder: dict = {}
            t = threading.Thread(target=lambda: holder.update(
                {"resp": predict_via(port0, "lm@alpha", prompts[3])}))
            t.start()
            deadline = time.time() + 60
            while srv0.inflight() <= inflight0:
                assert time.time() < deadline, (
                    "pinned alpha request never started")
                time.sleep(0.005)
            code, payload = predict_via(port0, "lm@gamma", prompts[1])
            assert code == 200, (code, payload)
            assert payload["predictions"][0]["tokens"] \
                == want[("lm@gamma", tuple(prompts[1]))]
            t.join(timeout=180)
            code, payload = holder["resp"]
            assert code == 200, (
                "the in-flight alpha request was dropped by the "
                "eviction", code, payload)
            assert payload["predictions"][0]["tokens"] \
                == want[("lm@alpha", tuple(prompts[3]))], (
                "the pinned alpha generation was corrupted by the "
                "gamma load")
            resident = {a["name"]
                        for a in srv0.adapter_info().get("lm", ())}
            assert "alpha" in resident and "gamma" in resident, resident
            assert "beta" not in resident, (
                "eviction took the wrong victim", resident)
            m_after = scrape(port0)
            assert delta(m_before, m_after,
                         "kft_engine_adapter_evictions_total",
                         engine="lm-v1") >= 1
            # Evicted beta hot-reloads on demand, identically.
            code, payload = predict_via(port0, "lm@beta", prompts[0])
            assert code == 200
            assert payload["predictions"][0]["tokens"] \
                == want[("lm@beta", tuple(prompts[0]))]

            # -- 4. advertisement + affinity + typed sheds -----------
            # Touch alpha on replica 0 first: the beta reload above may
            # have taken alpha as its LRU victim, and the affinity
            # assertion below needs at least one warm alpha replica.
            code, _ = predict_via(port0, "lm@alpha", prompts[0])
            assert code == 200
            resident = {a["name"]
                        for a in srv0.adapter_info().get("lm", ())}
            assert "alpha" in resident, resident
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port0}/readyz",
                    timeout=30) as resp:
                ready = json.loads(resp.read())
            advertised = {a["name"]: a["digest"]
                          for a in ready.get("adapters", {}).get(
                              "lm", ())}
            assert set(advertised) == resident, ready
            assert all(len(d) == 64 for d in advertised.values())
            registry.refresh()   # the probe learns the advertisement
            r_before = scrape(rport)
            for _ in range(4):
                code, payload = predict_via(rport, "lm@alpha",
                                            prompts[0])
                assert code == 200
                assert payload["predictions"][0]["tokens"] \
                    == want[("lm@alpha", tuple(prompts[0]))]
            r_after = scrape(rport)
            assert delta(r_before, r_after,
                         "kft_router_adapter_affinity_total",
                         outcome="hit") >= 4, (
                "routed lm@alpha traffic never hit the warm subset")
            code, payload = predict_via(rport, "lm@ghost", prompts[0])
            assert code == 404, (
                "unknown adapter must shed typed 404 through the "
                "router", code, payload)

            # -- engine adapter counters moved as /metrics deltas ----
            m1 = scrape(ports[0])
            assert delta(m0, m1, "kft_engine_adapter_loads_total",
                         engine="lm-v1", adapter="alpha") >= 1
            assert delta(m0, m1, "kft_engine_adapter_requests_total",
                         engine="lm-v1", adapter="alpha") >= 1
            total_loads = sum(
                delta(m0, m1, "kft_engine_adapter_loads_total",
                      engine="lm-v1", adapter=name)
                for name in tenants)
            assert total_loads >= 4, (
                "expected initial loads + the beta reload", total_loads)
        finally:
            if router_httpd is not None:
                router_httpd.shutdown()
            if control is not None:
                control.stop()
            for srv, httpd in replicas:
                try:
                    httpd.shutdown()
                except Exception:
                    pass
                srv.stop()


def scheduler_smoke(namespace: str = "kubeflow-test") -> None:
    """Hermetic multi-tenant scheduler scenario: two tenants' TPUJobs
    through the fake apiserver (real sockets, HttpKube) against the
    policy layer (kubeflow_tpu/scheduler/) + gang + reconciler:

      1. quota — a greedy tenant's third job holds at QuotaExceeded
         while a politer tenant admitted later runs;
      2. backfill — a small low-priority job provably jumps a blocked
         large high-priority job (disjoint slice pools) and the large
         job's admission is not delayed;
      3. preemption with resume — a high-priority arrival evicts the
         lowest-priority gang through the Preempting grace window
         (clock-skewed, no wall sleeping); the victim re-queues
         ``resumable`` and, after the preemptor finishes, restarts and
         resumes from its latest CheckpointManager step (> 0, no
         step-0 retraining);
      4. every outcome is scrapeable in kft_scheduler_* metrics.
    """
    import numpy as np

    from kubeflow_tpu.operator import crd
    from kubeflow_tpu.operator.gang import GangScheduler
    from kubeflow_tpu.operator.kube_http import HttpKube
    from kubeflow_tpu.operator.reconciler import (
        JOB_PREEMPTING,
        JOB_SUCCEEDED,
        QUEUED,
        STARTING,
        TPUJobController,
    )
    from kubeflow_tpu.runtime.checkpoint import CheckpointManager
    from kubeflow_tpu.runtime.prom import (
        REGISTRY,
        parse_metrics,
        sample_value,
    )
    from kubeflow_tpu.scheduler import (
        LABEL_PRIORITY,
        LABEL_TENANT,
        ClusterScheduler,
        PreemptionConfig,
        SchedulerConfig,
    )
    from kubeflow_tpu.testing import faults
    from kubeflow_tpu.testing.fake_apiserver import make_fake_apiserver

    def make_cr(name, tenant, priority, slice_type="v5e-8", n=1):
        job = crd.TPUJobSpec(name=name, namespace=namespace,
                             slice_type=slice_type, num_slices=n)
        cr = job.to_custom_resource()
        cr["metadata"]["labels"] = {LABEL_TENANT: tenant,
                                    LABEL_PRIORITY: priority}
        return cr

    import tempfile

    apiserver = None
    with faults.injected("seed=20260804") as inj, \
            tempfile.TemporaryDirectory() as tmp:
        try:
            apiserver, _, store = make_fake_apiserver()
            kube = HttpKube(
                base_url=f"http://127.0.0.1:"
                         f"{apiserver.server_address[1]}")
            gang = GangScheduler({"v5e-8": 4, "v5p-32": 1})
            cluster = ClusterScheduler(gang, SchedulerConfig(
                quotas={"greedy": {"v5e-8": 16}},
                preemption=PreemptionConfig(grace_period_s=30.0)))
            ctl = TPUJobController(kube, gang, cluster)

            def statuses():
                return {c["metadata"]["name"]: (c.get("status") or {})
                        for c in kube.list_custom(namespace)}

            def run_pods(job_name):
                for p in kube.list_pods(
                        namespace,
                        labels={"kubeflow-tpu.org/job-name": job_name}):
                    store.set_pod_phase(namespace,
                                        p["metadata"]["name"],
                                        "Running")

            # -- 1. quota-capped greedy tenant ------------------------
            for i in range(3):
                kube.create_custom(
                    make_cr(f"greedy-{i}", "greedy", "normal"))
            kube.create_custom(make_cr("polite", "polite", "normal"))
            ctl.reconcile_all()
            st = statuses()
            admitted = sorted(n for n in st
                              if st[n].get("phase") == STARTING)
            assert admitted == ["greedy-0", "greedy-1", "polite"], st
            assert st["greedy-2"]["phase"] == QUEUED
            assert st["greedy-2"]["reason"] == "QuotaExceeded", st

            # -- 2. backfill past a blocked large job -----------------
            kube.create_custom(
                make_cr("vp-run", "research", "high",
                        slice_type="v5p-32"))
            ctl.reconcile_all()
            kube.create_custom(
                make_cr("vp-blocked", "research", "high",
                        slice_type="v5p-32"))
            kube.create_custom(make_cr("small-low", "batch", "low"))
            ctl.reconcile_all()
            st = statuses()
            assert st["vp-blocked"]["reason"] == "WaitingForSlices", st
            assert st["small-low"]["phase"] == STARTING, st
            assert cluster.status()["counters"]["backfilled"] >= 1
            # ETA unchanged: vp-run ends, vp-blocked starts at once
            # with the backfilled job still holding its v5e slice.
            run_pods("vp-run")
            ctl.reconcile_all()
            for p in kube.list_pods(
                    namespace,
                    labels={"kubeflow-tpu.org/job-name": "vp-run"}):
                store.set_pod_phase(namespace, p["metadata"]["name"],
                                    "Succeeded")
            ctl.reconcile_all()
            ctl.reconcile_all()
            st = statuses()
            assert st["vp-run"]["phase"] == JOB_SUCCEEDED
            assert st["vp-blocked"]["phase"] == STARTING, st
            assert st["small-low"]["phase"] == STARTING, st

            # -- 3. preemption -> checkpoint grace -> resume ----------
            # The victim gang's trainer has checkpointed through step
            # 4 (what restore_or_init will find on re-admission).
            base = np.arange(8, dtype=np.float32)
            with CheckpointManager(f"{tmp}/victim-ckpt",
                                   save_interval_steps=1) as mgr:
                for step in range(5):
                    mgr.save(step,
                             {"step": np.full((), step, np.int32),
                              "w": base + step})
            kube.create_custom(make_cr("vip", "prod", "high"))
            ctl.reconcile_all()
            st = statuses()
            # v5e-8 was full; the lowest-priority gang is evicted.
            assert st["small-low"]["phase"] == JOB_PREEMPTING, st
            assert st["small-low"]["resumable"] is True
            assert kube.list_pods(
                namespace,
                labels={"kubeflow-tpu.org/job-name": "small-low"}), \
                "pods must survive the checkpoint grace window"
            ctl.reconcile_all()
            assert statuses()["small-low"]["phase"] == JOB_PREEMPTING
            inj.advance_clock(31)   # grace elapses, zero wall waiting
            ctl.reconcile_all()
            st = statuses()
            assert st["small-low"]["phase"] == QUEUED
            assert st["small-low"]["reason"] == "PreemptedRequeued", st
            ctl.reconcile_all()
            st = statuses()
            assert st["vip"]["phase"] == STARTING, st
            run_pods("vip")
            ctl.reconcile_all()
            for p in kube.list_pods(
                    namespace,
                    labels={"kubeflow-tpu.org/job-name": "vip"}):
                store.set_pod_phase(namespace, p["metadata"]["name"],
                                    "Succeeded")
            ctl.reconcile_all()
            ctl.reconcile_all()
            st = statuses()
            assert st["vip"]["phase"] == JOB_SUCCEEDED
            assert st["small-low"]["phase"] == STARTING, st
            # resumable was consumed by the resume admission; the
            # preemption count survives as history.
            assert st["small-low"]["resumable"] is False
            assert int(st["small-low"]["preemptions"]) == 1
            assert int(st["small-low"].get("restarts", 0)) == 0, \
                "preemption must not consume the restart budget"
            # Trainer side of the resume contract: the re-admitted
            # gang restores step 4 and continues at 5 — never step 0.
            fresh = {"step": np.zeros((), np.int32),
                     "w": np.zeros(8, np.float32)}
            with CheckpointManager(f"{tmp}/victim-ckpt") as mgr2:
                restored, start = mgr2.restore_or_init(fresh)
            assert start == 5, f"resume restarted at {start}"
            np.testing.assert_allclose(restored["w"], base + 4)

            # -- 4. outcomes in kft_scheduler_* metrics ---------------
            parsed = parse_metrics(REGISTRY.render())
            assert (sample_value(parsed,
                                 "kft_scheduler_preemptions_total",
                                 tenant="batch") or 0) >= 1, parsed.get(
                "kft_scheduler_preemptions_total")
            assert (sample_value(parsed,
                                 "kft_scheduler_backfills_total",
                                 tenant="batch") or 0) >= 1
            assert (sample_value(parsed,
                                 "kft_scheduler_resumes_total",
                                 tenant="batch") or 0) >= 1
            assert sample_value(parsed, "kft_scheduler_quota_chips",
                                tenant="greedy",
                                slice_type="v5e-8") == 16
            assert sample_value(parsed, "kft_scheduler_queue_depth",
                                tenant="greedy",
                                priority="normal") is not None
            assert "kft_scheduler_queue_wait_seconds" in parsed or \
                "kft_scheduler_queue_wait_seconds_count" in parsed
        finally:
            if apiserver is not None:
                apiserver.shutdown()
                apiserver.server_close()


def train_resilience_smoke(namespace: str = "kubeflow-test") -> None:
    """Hermetic crash-safe training scenario — the whole PR-10 stack:

      1. supervised resume — a tiny LM trains under the
         TrainSupervisor with an injected ``train.step`` raise; the
         supervisor restarts in process, resumes from a VERIFIED
         checkpoint (never step 0), the global step stays monotone,
         and the final params are IDENTICAL to an uninterrupted
         control run of the same seed (loss-identity);
      2. walk-back restore — the latest checkpoint is corrupted on
         disk (truncated leaf file); ``restore_or_init`` skips it and
         resumes from the newest verified predecessor;
      3. bad-node quarantine — a TPUJob over the fake apiserver flaps
         repeatedly on one node; the operator quarantines the node
         (NodeQuarantined event), excludes it from the re-placed
         gang's pods via node anti-affinity, and exports
         ``kft_operator_quarantined_nodes``;
      4. every outcome lands in kft_train_* / kft_checkpoint_*
         metrics (asserted as deltas — the registry is shared).
    """
    import tempfile
    from pathlib import Path

    import jax
    import numpy as np
    import optax

    from kubeflow_tpu.models.transformer import TransformerConfig, lm_task
    from kubeflow_tpu.operator import crd
    from kubeflow_tpu.operator.gang import GangScheduler, NodeQuarantine
    from kubeflow_tpu.operator.kube import FAILED, RUNNING
    from kubeflow_tpu.operator.kube_http import HttpKube
    from kubeflow_tpu.operator.reconciler import TPUJobController
    from kubeflow_tpu.parallel import MeshSpec
    from kubeflow_tpu.runtime.checkpoint import CheckpointManager
    from kubeflow_tpu.runtime.metrics import MetricsLogger
    from kubeflow_tpu.runtime.prom import (
        REGISTRY,
        parse_metrics,
        sample_value,
    )
    from kubeflow_tpu.runtime.supervisor import TrainSupervisor
    from kubeflow_tpu.runtime.train import Trainer
    from kubeflow_tpu.testing import faults
    from kubeflow_tpu.testing.fake_apiserver import make_fake_apiserver

    def metric(parsed, name, **labels):
        return sample_value(parsed, name, **labels) or 0.0

    before = parse_metrics(REGISTRY.render())
    mesh = MeshSpec(data=-1).build()
    cfg = TransformerConfig(
        vocab_size=64, d_model=16, n_layers=1, n_heads=2, n_kv_heads=2,
        d_ff=32, head_dim=8, max_seq_len=16, dtype="float32")
    init_fn, loss_fn = lm_task(cfg, mesh=mesh)
    batch = 2 * jax.device_count()
    steps = 8

    def data_factory():
        rng = np.random.RandomState(0)
        while True:
            yield {"tokens": rng.randint(
                0, cfg.vocab_size, size=(batch, 16)).astype(np.int32)}

    def make_trainer(ckpt_dir):
        return Trainer(
            init_fn=init_fn, loss_fn=loss_fn, tx=optax.adamw(1e-3),
            mesh=mesh,
            checkpoints=CheckpointManager(ckpt_dir, max_to_keep=3),
            checkpoint_every=2,
            metrics=MetricsLogger(stream=open("/dev/null", "w")))

    def leaves(state):
        return [np.asarray(x) for x in
                jax.tree_util.tree_leaves(state.params)]

    with faults.injected("seed=20260804") as inj, \
            tempfile.TemporaryDirectory() as tmp:
        # -- control: one uninterrupted run ---------------------------
        control = make_trainer(f"{tmp}/control")
        control_state = control.run_state = TrainSupervisor(
            control, max_restarts=0).run(
                data_factory, steps, examples_per_step=batch,
                log_every=0)
        control.checkpoints.close()

        # -- 1. supervised resume from a verified step ----------------
        trainer = make_trainer(f"{tmp}/victim")
        sup = TrainSupervisor(trainer, max_restarts=2, backoff_s=5.0)
        sup.run(data_factory, 4, examples_per_step=batch, log_every=0)
        assert trainer.checkpoints.latest_verified_step() == 3
        # Fault the FIRST step of the continuation; the skew entry
        # expires the restart backoff instantly (no wall sleeping).
        inj2 = faults.parse("train.step:raise*1;train.step:skew=60")
        faults.install(inj2)
        try:
            final = sup.run(data_factory, steps,
                            examples_per_step=batch, log_every=0)
        finally:
            faults.install(inj)
        assert sup.restarts == 1, sup.stats()
        # Monotone global step: every call boundary after the restart
        # continues PAST the verified step — never back to 0.
        boundaries = sup.steps_seen
        assert boundaries == sorted(boundaries), boundaries
        assert boundaries[-1] == steps
        assert min(b for b in boundaries if b > 4) == 5, boundaries
        # Loss identity: the supervised run's params equal the
        # uninterrupted control's (same seed, replayed stream).
        for got, want in zip(leaves(final), leaves(control_state)):
            np.testing.assert_allclose(got, want, rtol=0, atol=0)

        # -- 2. corrupt latest -> walk-back restore -------------------
        trainer.checkpoints.wait()
        ckpt_dir = Path(f"{tmp}/victim")
        all_steps = trainer.checkpoints.all_steps()
        latest = all_steps[-1]
        victim_file = max(
            (p for p in (ckpt_dir / str(latest)).rglob("*")
             if p.is_file()), key=lambda p: p.stat().st_size)
        victim_file.write_bytes(victim_file.read_bytes()[:16])
        fresh = trainer.create_state()
        restored, start = trainer.checkpoints.restore_or_init(fresh)
        prev_verified = max(s for s in all_steps if s != latest)
        assert start == prev_verified + 1, (
            f"walk-back resumed at {start}, want {prev_verified + 1}")
        trainer.checkpoints.close()

        # -- 3. node flap -> quarantine + gang re-place ---------------
        apiserver = None
        try:
            apiserver, _, store = make_fake_apiserver()
            kube = HttpKube(base_url=f"http://127.0.0.1:"
                                     f"{apiserver.server_address[1]}")
            ctl = TPUJobController(
                kube, GangScheduler({"v5e-8": 1}),
                quarantine=NodeQuarantine(threshold=3, window_s=600,
                                          cooldown_s=1800))
            kube.create_custom(crd.TPUJobSpec(
                name="flappy", namespace=namespace,
                slice_type="v5e-8").to_custom_resource())
            for _ in range(3):  # three worker failures on one node
                ctl.reconcile_all()
                for p in kube.list_pods(namespace):
                    store.set_pod_node(namespace,
                                       p["metadata"]["name"],
                                       "node-flap")
                    store.set_pod_phase(namespace,
                                        p["metadata"]["name"], RUNNING)
                ctl.reconcile_all()
                pod = kube.list_pods(namespace)[0]
                store.set_pod_phase(namespace, pod["metadata"]["name"],
                                    FAILED)
                ctl.reconcile_all()
            assert ctl.quarantine.quarantined() == ["node-flap"]
            events = [e for e in store.events
                      if e["reason"] == "NodeQuarantined"]
            assert len(events) == 1, events
            # The re-placed gang's pods must EXCLUDE the bad node.
            ctl.reconcile_all()
            pods = kube.list_pods(namespace)
            assert pods, "gang was not re-placed after quarantine"
            for p in pods:
                terms = (p["spec"]["affinity"]["nodeAffinity"]
                         ["requiredDuringSchedulingIgnoredDuring"
                          "Execution"]["nodeSelectorTerms"])
                expr = terms[0]["matchExpressions"][0]
                assert expr["operator"] == "NotIn"
                assert "node-flap" in expr["values"]
        finally:
            if apiserver is not None:
                apiserver.shutdown()
                apiserver.server_close()

        # -- 4. outcomes in kft_* metrics (deltas) --------------------
        parsed = parse_metrics(REGISTRY.render())
        assert metric(parsed, "kft_train_restarts_total",
                      reason="step") \
            - metric(before, "kft_train_restarts_total",
                     reason="step") >= 1
        assert metric(parsed, "kft_checkpoint_saves_total") \
            - metric(before, "kft_checkpoint_saves_total") >= 4
        assert metric(parsed, "kft_checkpoint_verify_failures_total") \
            - metric(before,
                     "kft_checkpoint_verify_failures_total") >= 1
        assert sample_value(
            parsed, "kft_operator_quarantined_nodes") == 1
        assert sample_value(
            parsed, "kft_train_heartbeat_age_seconds") is not None


def train_smoke(namespace: str = "kubeflow-test") -> None:
    """A few real SPMD train steps on whatever devices exist."""
    import subprocess

    proc = subprocess.run(
        [sys.executable, "-m", "kubeflow_tpu.tools.train_cnn",
         "--model", "resnet18", "--steps", "2",
         "--batch-size-per-device", "2", "--image-size", "32",
         "--num-classes", "4"],
        capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]


def hfta_smoke(namespace: str = "kubeflow-test") -> None:
    """Hermetic horizontally-fused-training-array scenario — the whole
    HFTA tier, scheduler fold (scheduler/fuse.py) through fused
    runtime (runtime/hfta.py):

      1. fold at admission — two tenants submit four fusable
         singleton TPUJobs (same family/slice/priority) over the fake
         apiserver; they admit as ONE fused gang claim on one slice,
         each member stamped with its gang and billed its fair SHARE
         of the slice chips (2 of 8), so both tenants fit a 4-chip
         quota that could not admit even one 8-chip singleton;
      2. preemption with per-member resume — a high-priority arrival
         evicts the fused gang through the clock-skewed grace window;
         every member requeues ``resumable`` with its gang stamp
         cleared, and once the preemptor finishes the fold re-forms
         and resumes ALL members (resume counter == member count);
      3. member-level completion — the shared pod gang succeeding
         completes every member CR individually (one
         FusedMemberCompleted event per member);
      4. runtime bit-identity across the same lifecycle — a width-4
         FusedTrainer (two tenants, one member early-stopping masked
         mid-run) is killed after 3 steps and resumed from its
         per-member verified-manifest checkpoints: per-member steps
         stay monotone across the boundary and final params are
         bit-identical to an uninterrupted control run, the
         early-stopped member included;
      5. outcomes are scrapeable: kft_scheduler_fused_gangs/_members
         while the gang runs, kft_train_member_steps_total /
         kft_train_members_active from the fused fit.
    """
    import tempfile

    import jax
    import numpy as np

    from kubeflow_tpu.models.transformer import TransformerConfig, lm_task
    from kubeflow_tpu.operator import crd
    from kubeflow_tpu.operator.gang import GangScheduler
    from kubeflow_tpu.operator.kube_http import HttpKube
    from kubeflow_tpu.operator.reconciler import (
        JOB_PREEMPTING,
        JOB_SUCCEEDED,
        QUEUED,
        STARTING,
        TPUJobController,
    )
    from kubeflow_tpu.parallel import MeshSpec
    from kubeflow_tpu.runtime.hfta import FusedTrainer, MemberSpec
    from kubeflow_tpu.runtime.metrics import MetricsLogger
    from kubeflow_tpu.runtime.prom import (
        REGISTRY,
        parse_metrics,
        sample_value,
    )
    from kubeflow_tpu.scheduler import (
        LABEL_FUSE_FAMILY,
        LABEL_PRIORITY,
        LABEL_TENANT,
        ClusterScheduler,
        PreemptionConfig,
        SchedulerConfig,
    )
    from kubeflow_tpu.testing import faults
    from kubeflow_tpu.testing.fake_apiserver import make_fake_apiserver

    def metric(parsed, name, **labels):
        return sample_value(parsed, name, **labels) or 0.0

    def make_cr(name, tenant, priority="low", family="sweep"):
        job = crd.TPUJobSpec(name=name, namespace=namespace,
                             slice_type="v5e-8", num_slices=1)
        cr = job.to_custom_resource()
        cr["metadata"]["labels"] = {LABEL_TENANT: tenant,
                                    LABEL_PRIORITY: priority,
                                    LABEL_FUSE_FAMILY: family}
        return cr

    before = parse_metrics(REGISTRY.render())
    apiserver = None
    with faults.injected("seed=20260807") as inj, \
            tempfile.TemporaryDirectory() as tmp:
        try:
            apiserver, _, store = make_fake_apiserver()
            kube = HttpKube(
                base_url=f"http://127.0.0.1:"
                         f"{apiserver.server_address[1]}")
            gang = GangScheduler({"v5e-8": 1})
            cluster = ClusterScheduler(gang, SchedulerConfig(
                quotas={"tenant-a": {"v5e-8": 4},
                        "tenant-b": {"v5e-8": 4}},
                preemption=PreemptionConfig(grace_period_s=30.0)))
            ctl = TPUJobController(kube, gang, cluster)

            def statuses():
                return {c["metadata"]["name"]: (c.get("status") or {})
                        for c in kube.list_custom(namespace)}

            # -- 1. two tenants' singletons fold into one gang --------
            for i in range(4):
                kube.create_custom(make_cr(
                    f"m{i}", tenant=f"tenant-{'ab'[i % 2]}"))
            ctl.reconcile_all()
            st = statuses()
            gkey = f"fused:{namespace}/sweep"
            for i in range(4):
                assert st[f"m{i}"]["phase"] == STARTING, st
                assert st[f"m{i}"]["fusedGang"] == gkey, st
            assert gang.admitted(gkey)
            assert kube.list_pods(
                namespace,
                labels={"kubeflow-tpu.org/job-name": "fused-sweep"}), \
                "fused gang must run ONE shared pod gang"
            # Fair share: each tenant is billed its members' slice
            # share (2 x 2 chips), inside a quota an 8-chip singleton
            # would blow on its own.
            quotas = {q["tenant"]: q["used_chips"]
                      for q in cluster.status()["quotas"]}
            assert quotas == {"tenant-a": 4.0, "tenant-b": 4.0}, quotas
            rows = {r["job"]: r for r in cluster.status()["jobs"]}
            assert rows[f"{namespace}/m0"]["members"] == 4
            assert rows[f"{namespace}/m0"]["chips"] == 2.0
            parsed = parse_metrics(REGISTRY.render())
            assert sample_value(
                parsed, "kft_scheduler_fused_gangs") == 1.0
            assert sample_value(
                parsed, "kft_scheduler_fused_members") == 4.0

            # -- 2. preempt the gang; every member resumes ------------
            # vip rides an unquoted tenant — the point is priority
            # eviction, not quota.
            kube.create_custom(make_cr("vip", tenant="prod",
                                       priority="high", family=""))
            ctl.reconcile_all()
            st = statuses()
            for i in range(4):
                assert st[f"m{i}"]["phase"] == JOB_PREEMPTING, st
                assert st[f"m{i}"]["resumable"] is True
            inj.advance_clock(31)   # grace elapses, no wall waiting
            ctl.reconcile_all()
            st = statuses()
            for i in range(4):
                assert st[f"m{i}"]["phase"] == QUEUED, st
                assert st[f"m{i}"]["reason"] == "PreemptedRequeued"
                assert not st[f"m{i}"].get("fusedGang"), st
            assert not gang.admitted(gkey)
            ctl.reconcile_all()
            assert statuses()["vip"]["phase"] == STARTING
            for p in kube.list_pods(
                    namespace,
                    labels={"kubeflow-tpu.org/job-name": "vip"}):
                store.set_pod_phase(namespace, p["metadata"]["name"],
                                    "Succeeded")
            ctl.reconcile_all()
            ctl.reconcile_all()
            st = statuses()
            assert st["vip"]["phase"] == JOB_SUCCEEDED
            for i in range(4):
                assert st[f"m{i}"]["phase"] == STARTING, st
                assert int(st[f"m{i}"]["preemptions"]) == 1
            assert gang.admitted(gkey)
            assert cluster.status()["counters"]["resumed"] == 4

            # -- 3. one pod-gang success completes every member -------
            for p in kube.list_pods(
                    namespace,
                    labels={"kubeflow-tpu.org/job-name": "fused-sweep"}):
                store.set_pod_phase(namespace, p["metadata"]["name"],
                                    "Succeeded")
            ctl.reconcile_all()
            st = statuses()
            for i in range(4):
                assert st[f"m{i}"]["phase"] == JOB_SUCCEEDED, st
            assert not gang.admitted(gkey)
            completed = [e for e in store.events
                         if e["reason"] == "FusedMemberCompleted"]
            assert len(completed) == 4, store.events

            # -- 4. the members' TRAINING side of that lifecycle ------
            mesh = MeshSpec(data=-1).build()
            cfg = TransformerConfig(
                vocab_size=64, d_model=16, n_layers=1, n_heads=2,
                n_kv_heads=2, d_ff=32, head_dim=8, max_seq_len=16,
                dtype="float32")
            init_fn, loss_fn = lm_task(cfg, mesh=mesh)
            batch = 2 * jax.device_count()
            members = [MemberSpec(name=f"m{i}", seed=i,
                                  lr=1e-3 * (i + 1),
                                  tenant=f"tenant-{'ab'[i % 2]}",
                                  stop_step=(2 if i == 1 else None))
                       for i in range(4)]

            def data_factory():
                rng = np.random.RandomState(0)
                while True:
                    yield {"tokens": rng.randint(
                        0, cfg.vocab_size,
                        size=(batch, 16)).astype(np.int32)}

            def fused_trainer(ckpt=None):
                return FusedTrainer(
                    init_fn=init_fn, loss_fn=loss_fn, members=members,
                    mesh=mesh, checkpoint_dir=ckpt, checkpoint_every=1,
                    metrics=MetricsLogger(stream=open("/dev/null",
                                                      "w")))

            def member_leaves(ft, state, i):
                return [np.asarray(x) for x in
                        jax.tree_util.tree_leaves(
                            ft.member_state(state, i).params)]

            control = fused_trainer()
            s_control = control.fit(data_factory(), 6, log_every=0)
            # Kill after 3 steps; m1 froze at its stop_step before the
            # kill, so the resume must re-enter it MASKED.
            victim = fused_trainer(ckpt=f"{tmp}/fused")
            s_victim = victim.fit(data_factory(), 3, log_every=0)
            cut = [int(victim.member_state(s_victim, i).step)
                   for i in range(4)]
            assert cut == [3, 2, 3, 3], cut
            resumed = fused_trainer(ckpt=f"{tmp}/fused")
            s_resumed = resumed.fit(data_factory(), 6, log_every=0)
            steps = [int(resumed.member_state(s_resumed, i).step)
                     for i in range(4)]
            assert steps == [6, 2, 6, 6], steps
            assert all(a >= b for a, b in zip(steps, cut)), (steps, cut)
            for i in range(4):
                got = member_leaves(resumed, s_resumed, i)
                want = member_leaves(control, s_control, i)
                assert len(got) == len(want)
                for g, w in zip(got, want):
                    assert np.array_equal(g, w), \
                        f"member {i} diverged across preempt/resume"

            # -- 5. fused-fit observability ---------------------------
            parsed = parse_metrics(REGISTRY.render())
            assert metric(parsed, "kft_train_member_steps_total",
                          member="m0") \
                - metric(before, "kft_train_member_steps_total",
                         member="m0") >= 6
            # Every member either completed num_steps or early-stopped
            # — the active gauge must read 0 after the final fit.
            assert sample_value(
                parsed, "kft_train_members_active") == 0.0
        finally:
            if apiserver is not None:
                apiserver.shutdown()
                apiserver.server_close()


def colocation_smoke(namespace: str = "kubeflow-test") -> None:
    """Hermetic train/serve colocation scenario (§5.13): ONE chip pool
    under the shared arbiter, driven through the fake apiserver (real
    sockets, HttpKube) by the REAL fleet Autoscaler in claims mode:

      1. trough — zero serving load with min_replicas=0 makes no
         claim; training owns the whole pool;
      2. burst — scraped load spikes, the autoscaler writes a
         2-replica claim CR (never spec.replicas), the arbiter evicts
         the low-priority training gang on the SHORT serving grace
         while prepull pods pin to the victim's exact nodes, and the
         reconciler patches the Deployment only on grant;
      3. the victim checkpoints inside the grace window and — after
         the evening trough releases the claim (CR deleted,
         Deployment zeroed, stale sweep frees the gang claim) — is
         backfilled and resumes bit-identical from its latest
         verified step, restart budget untouched;
      4. the combined-pool snapshot rides the claim status back to
         the ServingClaimClient (the fleet-status footer's data) and
         every transition lands in kft_* metric deltas.
    """
    import tempfile

    import numpy as np

    from kubeflow_tpu.fleet.autoscaler import Autoscaler
    from kubeflow_tpu.operator import crd
    from kubeflow_tpu.operator.gang import GangScheduler
    from kubeflow_tpu.operator.kube_http import HttpKube
    from kubeflow_tpu.operator.reconciler import (
        JOB_PREEMPTING,
        JOB_RUNNING,
        QUEUED,
        STARTING,
        TPUJobController,
    )
    from kubeflow_tpu.runtime.checkpoint import CheckpointManager
    from kubeflow_tpu.runtime.prom import (
        REGISTRY,
        parse_metrics,
        sample_value,
    )
    from kubeflow_tpu.scheduler import (
        LABEL_PRIORITY,
        LABEL_TENANT,
        ClusterScheduler,
        PreemptionConfig,
        SchedulerConfig,
        colocate,
    )
    from kubeflow_tpu.testing import faults
    from kubeflow_tpu.testing.fake_apiserver import make_fake_apiserver

    class ScrapedLoad:
        """Registry stand-in: the diurnal curve the test scripts."""

        def __init__(self):
            self.load = 0.0

        def total_load(self):
            return self.load

        def ready_count(self):
            return 1

    def make_train_cr(name, priority, n=1):
        job = crd.TPUJobSpec(name=name, namespace=namespace,
                             num_slices=n)
        cr = job.to_custom_resource()
        cr["metadata"]["labels"] = {LABEL_TENANT: "research",
                                    LABEL_PRIORITY: priority}
        return cr

    apiserver = None
    with faults.injected("seed=20260807") as inj, \
            tempfile.TemporaryDirectory() as tmp:
        try:
            apiserver, _, store = make_fake_apiserver()
            kube = HttpKube(
                base_url=f"http://127.0.0.1:"
                         f"{apiserver.server_address[1]}")
            gang = GangScheduler({"v5e-8": 4})
            cluster = ClusterScheduler(gang, SchedulerConfig(
                preemption=PreemptionConfig(
                    grace_period_s=30.0,
                    serving_grace_period_s=5.0)))
            ctl = TPUJobController(kube, gang, cluster)
            store.create_deployment({
                "metadata": {"name": "lm", "namespace": namespace},
                "spec": {"replicas": 0}})
            load = ScrapedLoad()
            claims = colocate.ServingClaimClient(kube, namespace, "lm")
            scaler = Autoscaler(
                kube, namespace, "lm", load,
                target_inflight_per_replica=4.0,
                min_replicas=0, max_replicas=4,
                scale_up_cooldown_s=10.0,
                scale_down_cooldown_s=60.0,
                claims=claims)

            def statuses():
                return {c["metadata"]["name"]: (c.get("status") or {})
                        for c in kube.list_custom(namespace)}

            # -- 1. overnight trough: training owns the pool ----------
            out = scaler.reconcile_once()
            assert out["desired"] == 0
            assert out["claim"]["state"] == "released"
            kube.create_custom(make_train_cr("night-batch", "low", n=2))
            kube.create_custom(make_train_cr("steady", "normal", n=2))
            ctl.reconcile_all()
            st = statuses()
            assert st["night-batch"]["phase"] == STARTING, st
            assert st["steady"]["phase"] == STARTING, st
            pool = cluster.pool_status()
            assert pool["free_chips"] == 0
            assert pool["training_chips"] == pool["capacity_chips"]
            # The victim's trainer checkpoints through step 4.
            base = np.arange(8, dtype=np.float32)
            with CheckpointManager(f"{tmp}/night-ckpt",
                                   save_interval_steps=1) as mgr:
                for step in range(5):
                    mgr.save(step,
                             {"step": np.full((), step, np.int32),
                              "w": base + step})
            for i, p in enumerate(kube.list_pods(
                    namespace,
                    labels={"kubeflow-tpu.org/job-name":
                            "night-batch"})):
                store.set_pod_node(namespace, p["metadata"]["name"],
                                   f"node-{i}")

            # -- 2. morning burst: claim steals chips -----------------
            load.load = 8.0   # ceil(8/4) = 2 replicas wanted
            out = scaler.reconcile_once()
            assert out["applied"] and out["desired"] == 2
            assert out["claim"]["state"] == "pending"
            # Desire rode the claim CR; replicas are still 0.
            assert kube.get_deployment(
                namespace, "lm")["spec"]["replicas"] == 0
            ctl.reconcile_all()
            st = statuses()
            # Lowest-priority 2-slice gang drains; high-priority claim
            # outranks it on the shared pool.
            assert st["night-batch"]["phase"] == JOB_PREEMPTING, st
            assert st["night-batch"]["resumable"] is True
            assert st["steady"]["phase"] == STARTING, st
            # Speculative placement: prepull pods pin the EXACT nodes
            # the plan predicts will free, during the drain.
            prepulls = kube.list_pods(
                namespace,
                labels={colocate.LABEL_WORKLOAD:
                        colocate.WORKLOAD_PREPULL})
            assert sorted(
                p["spec"]["nodeName"] for p in prepulls) == \
                ["node-0", "node-1"], prepulls
            # SHORT serving grace: 6 s ends the drain (the 30 s
            # training grace would still be holding it).
            inj.advance_clock(6)
            ctl.reconcile_all()
            st = statuses()
            assert st["night-batch"]["phase"] == QUEUED
            assert st["night-batch"]["reason"] == "PreemptedRequeued"
            ctl.reconcile_all()
            ctl.reconcile_all()
            st = statuses()
            assert st["serving-lm"]["phase"] == JOB_RUNNING, st
            assert st["serving-lm"]["grantedReplicas"] == 2
            # The RECONCILER patched replicas on grant.
            assert kube.get_deployment(
                namespace, "lm")["spec"]["replicas"] == 2
            inj.advance_clock(11)
            out = scaler.reconcile_once()
            assert out["claim"]["state"] == "granted"
            # Combined-pool snapshot rode the claim status back to the
            # client (the `fleet status` footer's data source).
            pool = claims.pool()
            assert pool is not None
            assert pool["serving_chips"] == 16
            assert pool["used_chips"] == pool["capacity_chips"]
            # Prepull warmers retire once the claim is fully granted.
            ctl.reconcile_all()
            assert kube.list_pods(
                namespace,
                labels={colocate.LABEL_WORKLOAD:
                        colocate.WORKLOAD_PREPULL}) == []

            # -- 3. evening trough: release, backfill, resume ---------
            load.load = 0.0
            inj.advance_clock(120)   # past the scale-down cooldown
            out = scaler.reconcile_once()
            assert out["desired"] == 0
            assert out["claim"]["state"] == "released"
            assert kube.get_deployment(
                namespace, "lm")["spec"]["replicas"] == 0
            ctl.reconcile_all()   # stale sweep frees the gang claim
            ctl.reconcile_all()   # backfill re-admits the victim
            st = statuses()
            assert "serving-lm" not in st
            assert st["night-batch"]["phase"] == STARTING, st
            assert st["night-batch"]["resumable"] is False
            assert int(st["night-batch"]["preemptions"]) == 1
            assert int(st["night-batch"].get("restarts", 0)) == 0, \
                "eviction must not consume the restart budget"
            # Bit-identical resume from the verified checkpoint.
            fresh = {"step": np.zeros((), np.int32),
                     "w": np.zeros(8, np.float32)}
            with CheckpointManager(f"{tmp}/night-ckpt") as mgr2:
                restored, start = mgr2.restore_or_init(fresh)
            assert start == 5, f"resume restarted at {start}"
            np.testing.assert_allclose(restored["w"], base + 4)

            # -- 4. every transition is scrapeable --------------------
            parsed = parse_metrics(REGISTRY.render())
            assert (sample_value(
                parsed,
                "kft_scheduler_colocation_preemptions_total") or 0) \
                >= 1
            assert (sample_value(
                parsed, "kft_autoscaler_claim_granted_total",
                deployment="lm") or 0) >= 1
            assert (sample_value(
                parsed, "kft_scheduler_resumes_total",
                tenant="research") or 0) >= 1
            claims.close()
            parsed = parse_metrics(REGISTRY.render())
            assert not any(
                v for _, v in parsed.get(
                    "kft_scheduler_serving_claim_chips", [])), \
                "claim gauge must read 0 after close()"
        finally:
            if apiserver is not None:
                apiserver.shutdown()
                apiserver.server_close()


def _kubectl(args, *, input_text: str = None, timeout: int = 300) -> str:
    import subprocess

    proc = subprocess.run(
        ["kubectl"] + args, input=input_text, text=True,
        capture_output=True, timeout=timeout,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"kubectl {' '.join(args)} failed: {proc.stderr[-2000:]}")
    return proc.stdout


def deploy_real(namespace: str = "kubeflow-test") -> None:
    """Deploy the platform to the CURRENT kubectl context and verify it
    comes up — the reference's center-of-gravity E2E
    (testing/test_deploy.py:160-190 deploy-then-verify; cluster may be
    kind/minikube/GKE, exactly as prow_config.yaml parameterised it).

    Renders the platform through the same registry path a user drives,
    applies it, then waits for every Deployment to roll out within the
    reference's 10-minute readiness budget (test_deploy.py:188-189).
    KFT_E2E_DEPLOY selects the prototypes (comma-separated; default the
    full kubeflow-core — clusters that can only pull a subset of images,
    e.g. kind with locally built ones, set e.g. `tpujob-operator`).
    """
    import os

    import kubeflow_tpu.manifests  # noqa: F401 — registers prototypes
    from kubeflow_tpu.config.registry import App
    from kubeflow_tpu.manifests.base import to_yaml

    app = App()
    prototypes = os.environ.get("KFT_E2E_DEPLOY", "kubeflow-core")
    for i, proto in enumerate(p.strip() for p in prototypes.split(",")):
        app.add(proto, f"c{i}-{proto}", namespace=namespace)
    objects = app.render()
    _kubectl(["create", "namespace", namespace,
              "--dry-run=client", "-o", "yaml"])  # validates kubectl works
    try:
        _kubectl(["create", "namespace", namespace])
    except RuntimeError:
        pass  # already exists
    _kubectl(["apply", "-n", namespace, "-f", "-"],
             input_text=to_yaml(objects))
    deployments = [o["metadata"]["name"] for o in objects
                   if o["kind"] == "Deployment"]
    for name in deployments:
        _kubectl(["rollout", "status", f"deployment/{name}",
                  "-n", namespace, "--timeout=600s"], timeout=650)


def deploy_crds(namespace: str = "kubeflow-test") -> None:
    """Apply only the CRDs (+ namespace) to the current context.

    The control-plane-only footing for clusters that cannot pull the
    platform images (ephemeral kind, ci/run_e2e_kind.sh): the operator
    then runs as a host process against the cluster, so exactly one
    reconciler owns the CRs."""
    import kubeflow_tpu.manifests  # noqa: F401
    from kubeflow_tpu.config.registry import default_registry
    from kubeflow_tpu.manifests.base import to_yaml

    objs = default_registry.generate("tpujob-operator", "op",
                                     namespace=namespace)
    crds = [o for o in objs if o["kind"] == "CustomResourceDefinition"]
    try:
        _kubectl(["create", "namespace", namespace])
    except RuntimeError:
        pass  # already exists
    _kubectl(["apply", "-f", "-"], input_text=to_yaml(crds))


def tpujob_real(namespace: str = "kubeflow-test") -> None:
    """Submit the tpu-job-simple example to the real cluster and poll the
    CR until the operator reports a terminal phase (the simple_tfjob
    check, workflows.libsonnet:398-411, against a live control plane)."""
    import json
    import os

    import kubeflow_tpu.manifests  # noqa: F401
    from kubeflow_tpu.config.registry import default_registry
    from kubeflow_tpu.manifests.base import to_yaml

    objs = default_registry.generate(
        "tpu-job-simple", "e2e-smoke", namespace=namespace,
        slice_type=os.environ.get("KFT_E2E_SLICE", "v5e-1"))
    _kubectl(["apply", "-n", namespace, "-f", "-"],
             input_text=to_yaml(objs))
    deadline = time.time() + 600
    phase = ""
    while time.time() < deadline:
        out = _kubectl(["get", "tpujobs.kubeflow-tpu.org", "e2e-smoke",
                        "-n", namespace, "-o", "json"])
        phase = json.loads(out).get("status", {}).get("phase", "")
        if phase in ("Succeeded", "Failed"):
            break
        time.sleep(5)
    assert phase == "Succeeded", f"TPUJob ended in phase {phase!r}"


def teardown(namespace: str = "kubeflow-test") -> None:
    """Hermetic backend has nothing persistent; real clusters delete the
    test namespace (the reference's teardown subcommand,
    test_deploy.py:520-626)."""
    try:
        _kubectl(["delete", "namespace", namespace, "--ignore-not-found"],
                 timeout=600)
    except (RuntimeError, FileNotFoundError):
        pass  # no cluster in hermetic runs — nothing to tear down


COMMANDS = {
    "tpujob": tpujob_smoke,
    "serving": serving_smoke,
    "engine": engine_smoke,
    "faults": fault_injection_smoke,
    "fleet": fleet_smoke,
    "survivable": survivable_smoke,
    "kv_spill": kv_spill_smoke,
    "multichip_serving": multichip_serving_smoke,
    "adapter_serving": adapter_serving_smoke,
    "scheduler": scheduler_smoke,
    "train": train_smoke,
    "train_resilience": train_resilience_smoke,
    "hfta": hfta_smoke,
    "colocation": colocation_smoke,
    "deploy": deploy_real,
    "deploy-crds": deploy_crds,
    "tpujob-real": tpujob_real,
    "teardown": teardown,
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="kubeflow-tpu-e2e")
    ap.add_argument("command", choices=sorted(COMMANDS))
    ap.add_argument("--namespace", default="kubeflow-test")
    ap.add_argument("--artifacts-dir", default="/tmp/artifacts")
    args = ap.parse_args(argv)

    suite = JUnitSuite(args.command)
    suite.run(args.command, lambda: COMMANDS[args.command](args.namespace))
    path = suite.write(args.artifacts_dir)
    print(f"junit: {path}", file=sys.stderr)
    return 0 if suite.ok else 1


if __name__ == "__main__":
    sys.exit(main())
