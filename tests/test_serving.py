"""Serving plane tests: export/load, version hot-swap, REST contract,
micro-batching.  The REST wire format is checked against the reference
proxy's shapes (instances/predictions, b64, metadata, classify)."""

import base64
import json
import threading
import urllib.request

import jax
import numpy as np
import pytest

from kubeflow_tpu.models.resnet import ResNet18
from kubeflow_tpu.serving.export import export, list_versions, load_version
from kubeflow_tpu.serving.http import (
    ServingAPI,
    decode_b64_if_needed,
    make_http_server,
)
from kubeflow_tpu.serving.model_server import MicroBatcher, ModelServer

CLASSES, IMG = 4, 32


@pytest.fixture(scope="module")
def exported(tmp_path_factory):
    base = tmp_path_factory.mktemp("models") / "tiny"
    model = ResNet18(num_classes=CLASSES, num_filters=8)
    variables = model.init(
        jax.random.key(0), np.zeros((1, IMG, IMG, 3), np.float32),
        train=False,
    )
    export(
        base, 1, variables,
        loader="kubeflow_tpu.serving.loaders:classifier",
        config={"family": "resnet18", "num_classes": CLASSES, "top_k": 2,
                "num_filters": 8},
        signature={"inputs": ["image"],
                   "outputs": ["scores", "top_k_scores", "top_k_classes"]},
    )
    return base, model, variables


# The classifier loader must honor num_filters for the tiny test net.
@pytest.fixture(autouse=True, scope="module")
def _tiny_loader_support():
    yield


class TestExport:
    def test_versions_listed(self, exported):
        base, _, _ = exported
        assert list_versions(base) == [1]

    def test_load_and_predict_matches_direct(self, exported):
        base, model, variables = exported
        predict, meta = load_version(base, 1)
        rng = np.random.RandomState(0)
        img = rng.randn(2, IMG, IMG, 3).astype(np.float32)
        out = predict({"image": img})
        direct = model.apply(variables, img, train=False)
        probs = np.asarray(jax.nn.softmax(direct, axis=-1))
        np.testing.assert_allclose(
            np.asarray(out["scores"]), probs, atol=1e-5
        )
        assert meta["version"] == 1

    def test_duplicate_version_rejected(self, exported):
        base, _, variables = exported
        with pytest.raises(FileExistsError):
            export(base, 1, variables, loader="x:y")


class TestModelServer:
    def test_serves_latest_and_hot_swaps(self, exported, tmp_path):
        src, model, variables = exported
        import shutil

        base = tmp_path / "tiny"
        shutil.copytree(src, base)
        srv = ModelServer()
        srv.add_model("tiny", str(base))
        assert srv.get("tiny").version == 1

        export(
            base, 2, variables,
            loader="kubeflow_tpu.serving.loaders:classifier",
            config={"family": "resnet18", "num_classes": CLASSES,
                    "top_k": 2, "num_filters": 8},
        )
        changed = srv.reload("tiny")
        assert changed and srv.get("tiny").version == 2
        # Old version unloaded (latest-only policy).
        with pytest.raises(KeyError):
            srv.get("tiny", version=1)

    def test_unknown_model(self):
        srv = ModelServer()
        with pytest.raises(KeyError):
            srv.get("nope")


class TestRESTContract:
    @pytest.fixture(scope="class")
    def api(self, exported):
        base, _, _ = exported
        srv = ModelServer()
        srv.add_model("tiny", str(base))
        return ServingAPI(srv)

    def test_predict_instances_to_predictions(self, api):
        rng = np.random.RandomState(1)
        instances = [
            {"image": rng.randn(IMG, IMG, 3).astype(np.float32).tolist()}
            for _ in range(3)
        ]
        out = api.predict("tiny", {"instances": instances})
        assert len(out["predictions"]) == 3
        row = out["predictions"][0]
        assert set(row) == {"scores", "top_k_scores", "top_k_classes"}
        assert len(row["scores"]) == CLASSES

    def test_predict_missing_instances_is_400(self, api):
        with pytest.raises(ValueError, match="instances"):
            api.predict("tiny", {"inputs": []})

    def test_classify_shape(self, api):
        rng = np.random.RandomState(2)
        instances = [
            {"image": rng.randn(IMG, IMG, 3).astype(np.float32).tolist()}
        ]
        out = api.classify("tiny", {"instances": instances})
        pairs = out["result"]["classifications"][0]
        assert len(pairs) == 2  # top_k
        assert isinstance(pairs[0][0], str) and isinstance(pairs[0][1], float)

    def test_metadata(self, api):
        meta = api.metadata("tiny")
        assert meta["model_spec"]["name"] == "tiny"
        assert meta["metadata"]["signature"]["inputs"] == ["image"]

    def test_b64_decode(self):
        raw = np.arange(4, dtype=np.uint8).tobytes()
        decoded = decode_b64_if_needed(
            [{"b64": base64.b64encode(raw).decode()}]
        )
        np.testing.assert_array_equal(decoded[0], np.arange(4, dtype=np.uint8))


class TestWireDtypes:
    """uint8 is shipped to the device as-is (4x fewer wire bytes) and
    scaled to [0,1] on device; integer JSON pixels narrow to uint8."""

    def test_uint8_matches_scaled_float(self, exported):
        base, _, _ = exported
        from kubeflow_tpu.serving.export import load_version

        predict, _ = load_version(base, 1)
        rng = np.random.RandomState(7)
        img_u8 = rng.randint(0, 256, (2, IMG, IMG, 3)).astype(np.uint8)
        out_u8 = predict({"image": img_u8})
        out_f32 = predict(
            {"image": img_u8.astype(np.float32) / 255.0})
        np.testing.assert_allclose(
            np.asarray(out_u8["scores"]), np.asarray(out_f32["scores"]),
            atol=1e-5,
        )

    def test_json_int_pixels_narrow_to_uint8_path(self, exported):
        base, _, _ = exported
        from kubeflow_tpu.serving.export import load_version

        predict, _ = load_version(base, 1)
        rng = np.random.RandomState(8)
        img = rng.randint(0, 256, (1, IMG, IMG, 3))  # int64, JSON-style
        out_int = predict({"image": img})
        out_u8 = predict({"image": img.astype(np.uint8)})
        np.testing.assert_allclose(
            np.asarray(out_int["scores"]), np.asarray(out_u8["scores"]),
            atol=1e-6,
        )

    def test_out_of_range_ints_fall_back_to_float(self, exported):
        base, _, _ = exported
        from kubeflow_tpu.serving.export import load_version

        predict, _ = load_version(base, 1)
        img = np.full((1, IMG, IMG, 3), 1000, dtype=np.int64)
        out = predict({"image": img})  # must not wrap/clip silently
        assert np.asarray(out["scores"]).shape == (1, CLASSES)


class TestHTTPEndToEnd:
    def test_full_http_roundtrip(self, exported):
        base, _, _ = exported
        srv = ModelServer()
        srv.add_model("tiny", str(base))
        httpd, thread = make_http_server(srv, port=0, host="127.0.0.1")
        port = httpd.server_address[1]
        try:
            rng = np.random.RandomState(3)
            body = json.dumps({
                "instances": [
                    {"image": rng.randn(IMG, IMG, 3).astype(
                        np.float32).tolist()}
                ]
            }).encode()
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/model/tiny:predict",
                data=body, headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(req, timeout=30) as resp:
                out = json.loads(resp.read())
            assert len(out["predictions"]) == 1

            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/model/tiny:metadata", timeout=30
            ) as resp:
                meta = json.loads(resp.read())
            assert meta["model_spec"]["version"] == "1"

            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=30
            ) as resp:
                health = json.loads(resp.read())
            assert health["models"] == {"tiny": [1]}

            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=30
            ) as resp:
                metrics = resp.read().decode()
            assert ('kft_serving_requests_total{model="tiny",'
                    'outcome="ok",route="predict"}') in metrics
            assert "kft_serving_request_seconds_bucket" in metrics
        finally:
            httpd.shutdown()


class TestMicroBatcher:
    def test_batches_concurrent_requests(self):
        calls = []

        def predict(inputs):
            calls.append(inputs["x"].shape[0])
            return {"y": inputs["x"] * 2}

        mb = MicroBatcher(predict, max_batch_size=4, batch_timeout_s=0.05,
                          allowed_batch_sizes=[1, 2, 4])
        results = {}

        def worker(i):
            results[i] = mb.submit({"x": np.full((1, 2), float(i))})

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        mb.close()
        for i in range(4):
            np.testing.assert_allclose(
                results[i]["y"], np.full((1, 2), 2.0 * i)
            )
        # Requests were coalesced: fewer device calls than requests.
        assert sum(calls) >= 4 and len(calls) < 4

    def test_cycle_profile_consistent_under_concurrent_runners(self):
        """ADVICE r5 regression: stage timings are accumulated per
        _process locally and folded into self._cycle under the lock —
        with in_flight>1 runners racing a stats() reader, the profile
        must stay internally consistent (every stage present, finite,
        non-negative) instead of showing torn/lost updates."""
        import concurrent.futures as cf

        def predict(inputs):
            return {"y": inputs["x"]}

        mb = MicroBatcher(predict, max_batch_size=4,
                          allowed_batch_sizes=[1, 2, 4],
                          batch_timeout_s=0.002, in_flight=4)
        try:
            snapshots = []
            with cf.ThreadPoolExecutor(9) as ex:
                futures = [
                    ex.submit(mb.submit, {"x": np.full((1, 2), float(i))})
                    for i in range(64)]
                # stats() races the runner threads mid-dispatch.
                for _ in range(16):
                    snapshots.append(mb.stats())
                for f in futures:
                    f.result()
            stats = mb.stats()
        finally:
            mb.close()
        assert stats["requests"] == 64
        assert stats["batches"] == sum(stats["batch_size_hist"].values())
        profile = stats["cycle_profile_ms"]
        assert set(profile) == {"queue_wait", "collate", "pad",
                                "predict", "to_host", "deliver"}
        for stage, ms in profile.items():
            assert np.isfinite(ms) and ms >= 0.0, (stage, ms)
        for snap in snapshots:
            for stage, ms in snap["cycle_profile_ms"].items():
                assert np.isfinite(ms) and ms >= 0.0, (stage, ms)

    def test_error_propagates(self):
        def predict(inputs):
            raise RuntimeError("boom")

        mb = MicroBatcher(predict, batch_timeout_s=0.01)
        with pytest.raises(RuntimeError, match="boom"):
            mb.submit({"x": np.zeros((1,))})
        mb.close()

    def test_pipelined_dispatch_overlaps_slow_predict(self):
        """With a high-latency predict, two executors must keep two
        batches in flight: wall time for two batches' worth of load ~=
        one latency, not two (one runner thread => one batch in flight
        => throughput collapse)."""
        import concurrent.futures as cf
        import time as _t

        latency = 0.15

        def predict(inputs):
            _t.sleep(latency)
            return {"y": inputs["x"]}

        mb = MicroBatcher(predict, max_batch_size=4,
                          allowed_batch_sizes=[1, 2, 4],
                          batch_timeout_s=0.02, in_flight=2)
        try:
            t0 = _t.perf_counter()
            with cf.ThreadPoolExecutor(8) as ex:
                outs = list(ex.map(
                    lambda i: mb.submit({"x": np.full((1,), float(i))}),
                    range(8),
                ))
            wall = _t.perf_counter() - t0
            assert len(outs) == 8
            # 8 requests = 2+ batches of <=4; serialized would be
            # >= 2*latency + collect timeouts; pipelined fits well under.
            assert wall < 2 * latency + 0.1, wall
        finally:
            mb.close()

    def test_stats_batch_size_distribution(self):
        def predict(inputs):
            return {"y": inputs["x"]}

        mb = MicroBatcher(predict, max_batch_size=4,
                          allowed_batch_sizes=[1, 2, 4],
                          batch_timeout_s=0.02, in_flight=2)
        try:
            import concurrent.futures as cf

            with cf.ThreadPoolExecutor(8) as ex:
                list(ex.map(
                    lambda i: mb.submit({"x": np.full((1,), float(i))}),
                    range(8),
                ))
            stats = mb.stats()
            assert stats["requests"] == 8
            assert stats["batches"] >= 2
            assert sum(k * v for k, v in
                       stats["batch_size_hist"].items()) == 8
            assert stats["mean_batch_size"] > 0
        finally:
            mb.close()


class TestGRPC:
    def test_predict_classify_metadata_roundtrip(self, exported):
        import grpc

        from kubeflow_tpu.serving.grpc_server import (
            PredictionClient,
            make_grpc_server,
        )

        base, model, variables = exported
        srv = ModelServer()
        srv.add_model("tiny", str(base))
        server = make_grpc_server(srv, port=0, host="127.0.0.1")
        try:
            client = PredictionClient(f"127.0.0.1:{server.bound_port}")
            rng = np.random.RandomState(9)
            img = rng.randn(2, IMG, IMG, 3).astype(np.float32)
            out = client.predict("tiny", {"image": img})
            assert out["scores"].shape == (2, CLASSES)
            np.testing.assert_allclose(out["scores"].sum(-1), 1.0, atol=1e-3)

            pairs = client.classify("tiny", {"image": img})
            assert len(pairs) == 2 and len(pairs[0]) == 2  # top_k=2 config

            meta = client.metadata("tiny")
            assert meta["version"] == 1

            with pytest.raises(grpc.RpcError) as err:
                client.predict("missing", {"image": img})
            assert err.value.code() == grpc.StatusCode.NOT_FOUND
            client.close()
        finally:
            server.stop(0)

    def test_server_span_continues_client_traceparent(self, exported):
        """The gRPC face reads ``traceparent`` from invocation
        metadata: the server span joins the caller's trace (consistent
        trace_id, parent = the caller's span id) and the admission
        child span hangs under it."""
        import grpc

        from kubeflow_tpu.runtime import tracing
        from kubeflow_tpu.serving import grpc_server as gs

        base, _, _ = exported
        srv = ModelServer()
        srv.add_model("tiny", str(base))
        server = gs.make_grpc_server(srv, port=0, host="127.0.0.1")
        store = tracing.enable(sample_rate=1.0)
        try:
            channel = grpc.insecure_channel(
                f"127.0.0.1:{server.bound_port}")
            method = channel.unary_unary(
                f"/{gs.SERVICE}/Predict",
                request_serializer=(
                    gs.pb.PredictRequest.SerializeToString),
                response_deserializer=gs.pb.PredictResponse.FromString)
            req = gs.pb.PredictRequest()
            req.model_spec.name = "tiny"
            rng = np.random.RandomState(9)
            req.inputs["image"].CopyFrom(gs.numpy_to_tensor(
                rng.randn(1, IMG, IMG, 3).astype(np.float32)))
            trace_id = tracing.new_trace_id()
            parent_id = tracing.new_span_id()
            header = tracing.format_traceparent(trace_id, parent_id)
            method(req, timeout=60,
                   metadata=(("traceparent", header),))
            channel.close()
            traces = [t for t in store.traces()
                      if t["trace_id"] == trace_id]
            assert len(traces) == 1, store.traces()
            spans = {s["name"]: s for s in traces[0]["spans"]}
            assert spans["server.grpc_predict"]["parent_id"] \
                == parent_id
            assert spans["server.admission"]["parent_id"] \
                == spans["server.grpc_predict"]["span_id"]
        finally:
            tracing.disable()
            server.stop(0)

    def test_health_check_mirrors_readyz(self, exported):
        """grpc.health.v1 Check parity with /readyz: SERVING with a
        model loaded, NOT_SERVING once a drain begins — so the fleet
        router can probe gRPC-only replicas (satellite of the fleet
        control plane)."""
        from kubeflow_tpu.serving.grpc_server import (
            PredictionClient,
            check_health,
            make_grpc_server,
        )

        base, _, _ = exported
        srv = ModelServer()
        srv.add_model("tiny", str(base))
        server = make_grpc_server(srv, port=0, host="127.0.0.1")
        try:
            target = f"127.0.0.1:{server.bound_port}"
            assert check_health(target) is True
            client = PredictionClient(target)
            assert client.ready() is True
            srv.begin_drain()  # /readyz flips 503 -> Check NOT_SERVING
            assert client.ready() is False
            assert check_health(target) is False
            client.close()
        finally:
            server.stop(0)
            srv._draining.clear()

    def test_health_check_unreachable_is_false_not_raise(self):
        from kubeflow_tpu.serving.grpc_server import check_health

        # A probe's job is a verdict: no listener -> False.
        assert check_health("127.0.0.1:1", timeout=0.5) is False


class TestRetryCallHonorsServerHint:
    def test_overloaded_waits_server_retry_after(self):
        import random

        from kubeflow_tpu.serving.grpc_server import retry_call
        from kubeflow_tpu.serving.model_server import Overloaded

        sleeps = []
        calls = []

        def fn():
            calls.append(1)
            if len(calls) < 3:
                raise Overloaded("full", retry_after_s=2.0)
            return "ok"

        out = retry_call(fn, retries=3, backoff_s=0.001,
                         backoff_cap_s=10.0, rng=random.Random(0),
                         sleep=sleeps.append)
        assert out == "ok" and len(calls) == 3
        # Both waits came from the server's 2.0s hint (±10% jitter),
        # not the 1ms local schedule.
        assert all(2.0 <= s <= 2.2 + 1e-9 for s in sleeps), sleeps

    def test_hint_capped_and_deadline_never_retried(self):
        import random

        from kubeflow_tpu.serving.errors import DeadlineExceeded
        from kubeflow_tpu.serving.grpc_server import retry_call
        from kubeflow_tpu.serving.model_server import Overloaded

        sleeps = []

        def overloaded():
            raise Overloaded("full", retry_after_s=3600.0)

        with pytest.raises(Overloaded):
            retry_call(overloaded, retries=1, backoff_cap_s=0.05,
                       rng=random.Random(0), sleep=sleeps.append)
        assert sleeps and sleeps[0] <= 0.055 + 1e-9  # capped hint

        calls = []

        def expired():
            calls.append(1)
            raise DeadlineExceeded("spent")

        with pytest.raises(DeadlineExceeded):
            retry_call(expired, retries=5, sleep=sleeps.append)
        assert len(calls) == 1  # the deadline is spent; no retry


class TestLoaderAllowlist:
    """model.json is producer-controlled: loader resolution must not
    import arbitrary modules (ADVICE r1: code-exec via writable model
    path)."""

    def test_unlisted_module_rejected(self):
        from kubeflow_tpu.serving.export import resolve_loader

        with pytest.raises(PermissionError):
            resolve_loader("os:system")

    def test_builtin_loaders_allowed(self):
        from kubeflow_tpu.serving.export import resolve_loader

        fn = resolve_loader("kubeflow_tpu.serving.loaders:classifier")
        assert callable(fn)

    def test_registered_name_wins(self):
        from kubeflow_tpu.serving.export import (
            register_loader,
            resolve_loader,
        )

        sentinel = lambda cfg: None
        register_loader("my-loader", sentinel)
        assert resolve_loader("my-loader") is sentinel

    def test_opt_in_module(self, monkeypatch):
        from kubeflow_tpu.serving.export import resolve_loader

        monkeypatch.setenv("KFT_SERVING_LOADER_MODULES", "json")
        assert callable(resolve_loader("json:loads"))


class TestBatcherPadTable:
    def test_max_batch_clamped_to_pad_table(self):
        """max_batch_size beyond the padding table would produce unpadded
        batches and fresh compiles; the cap is the table max."""
        calls = []

        def predict(inputs):
            calls.append(inputs["x"].shape[0])
            return {"y": inputs["x"]}

        b = MicroBatcher(predict, max_batch_size=8,
                         allowed_batch_sizes=[1, 2, 4],
                         batch_timeout_s=0.01)
        try:
            assert b.max_batch_size == 4
            import concurrent.futures as cf

            with cf.ThreadPoolExecutor(8) as ex:
                outs = list(ex.map(
                    lambda i: b.submit({"x": np.full((1, 2), i)}), range(8)
                ))
            assert len(outs) == 8
            assert all(c in (1, 2, 4) for c in calls)  # never unpadded 8
        finally:
            b.close()


class TestShapeGroupedBatching:
    def test_mixed_shapes_batch_separately_and_all_succeed(self):
        """One odd-shaped request must not poison the batch: rows only
        share a device batch with shape-identical peers (LM prompts come
        in many lengths)."""
        shapes_seen = []

        def predict(inputs):
            shapes_seen.append(inputs["x"].shape)
            return {"y": inputs["x"] * 2}

        mb = MicroBatcher(predict, max_batch_size=8, batch_timeout_s=0.05,
                          allowed_batch_sizes=[1, 2, 4, 8])
        results = {}

        def worker(i):
            width = 2 if i % 2 == 0 else 3   # two shape groups
            results[i] = mb.submit({"x": np.full((1, width), float(i))})

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        mb.close()
        for i in range(8):
            width = 2 if i % 2 == 0 else 3
            np.testing.assert_allclose(
                results[i]["y"], np.full((1, width), 2.0 * i))
        # No device batch ever mixed the two widths.
        assert all(s[1] in (2, 3) for s in shapes_seen)
        assert {s[1] for s in shapes_seen} == {2, 3}

    def test_lm_generate_batches_uniform_prompts(self, tmp_path):
        """Uniform-length decode requests coalesce into one batched
        generate program and every caller gets its own row back."""
        import jax
        import jax.numpy as jnp

        from kubeflow_tpu.models.transformer import (
            Transformer,
            TransformerConfig,
        )
        from kubeflow_tpu.serving.export import export

        cfg = TransformerConfig(
            vocab_size=64, d_model=16, n_layers=1, n_heads=2, n_kv_heads=2,
            d_ff=32, head_dim=8, max_seq_len=32, dtype=jnp.float32)
        model = Transformer(cfg)
        variables = model.init(jax.random.key(0),
                               jnp.zeros((1, 4), jnp.int32))
        export(str(tmp_path / "lm"), 1, variables,
               loader="kubeflow_tpu.serving.loaders:lm_generate",
               config={"model": {
                   "vocab_size": 64, "d_model": 16, "n_layers": 1,
                   "n_heads": 2, "n_kv_heads": 2, "d_ff": 32,
                   "head_dim": 8, "max_seq_len": 32, "dtype": "float32"},
                   "max_new_tokens": 4, "temperature": 0.0})
        server = ModelServer()
        server.add_model("lm", str(tmp_path / "lm"))
        predict = server.get("lm").predict

        prompts = [np.random.RandomState(i).randint(1, 64, (1, 4))
                   .astype(np.int32) for i in range(4)]
        direct = [np.asarray(predict({"tokens": p})["tokens"])
                  for p in prompts]

        mb = MicroBatcher(predict, max_batch_size=4, batch_timeout_s=0.1,
                          allowed_batch_sizes=[1, 2, 4])
        results = {}

        def worker(i):
            results[i] = mb.submit({"tokens": prompts[i]})

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        stats = mb.stats()
        mb.close()
        for i in range(4):
            np.testing.assert_array_equal(
                np.asarray(results[i]["tokens"]), direct[i])
        assert stats["mean_batch_size"] > 1, stats


class TestDispatchFairness:
    """_take_batch_locked liveness: a saturating majority shape must not
    starve an expired minority shape (full groups get no priority over
    older expired heads)."""

    @staticmethod
    def _bare(max_batch_size=2, timeout=10.0):
        # Construct the object without starting runner threads so the
        # dispatch choice is deterministic and directly observable.
        mb = object.__new__(MicroBatcher)
        mb.max_batch_size = max_batch_size
        mb.batch_timeout_s = timeout
        mb._groups = {}
        mb._next_deadline = None
        mb._stopped = False
        mb._pending_total = 0
        return mb

    @staticmethod
    def _entry(t, id_, deadline=None):
        return {"t": t, "id": id_, "deadline": deadline}

    def test_expired_minority_beats_full_majority(self):
        import time as _t

        mb = self._bare(max_batch_size=2, timeout=0.01)
        now = _t.monotonic()
        # Majority shape A: full group, fresh heads (sustained load).
        mb._groups["A"] = [self._entry(now, i) for i in range(2)]
        # Minority shape B: one entry, long expired.
        mb._groups["B"] = [self._entry(now - 1.0, "b")]
        batch = mb._take_batch_locked([])
        assert [e["id"] for e in batch] == ["b"], batch

    def test_full_group_dispatches_before_its_own_timeout(self):
        import time as _t

        mb = self._bare(max_batch_size=2, timeout=10.0)
        now = _t.monotonic()
        mb._groups["A"] = [self._entry(now, 0), self._entry(now, 1)]
        mb._groups["B"] = [self._entry(now, "b")]  # neither full nor old
        batch = mb._take_batch_locked([])
        assert [e["id"] for e in batch] == [0, 1]
        # B stays queued with its own deadline registered.
        assert "B" in mb._groups and mb._next_deadline is not None

    def test_nothing_ready_registers_earliest_deadline(self):
        import time as _t

        mb = self._bare(max_batch_size=4, timeout=10.0)
        now = _t.monotonic()
        mb._groups["A"] = [self._entry(now, 0)]
        mb._groups["B"] = [self._entry(now - 5.0, "b")]  # older, not expired
        batch = mb._take_batch_locked([])
        assert batch is None
        # Earliest deadline is B's (older head).
        assert abs(mb._next_deadline - (now - 5.0 + 10.0)) < 0.5

    def test_request_deadline_swept_before_dispatch(self):
        """A deadline-expired entry is swept into the expired list, not
        dispatched — even when its group is otherwise dispatchable."""
        import time as _t

        mb = self._bare(max_batch_size=2, timeout=0.01)
        now = _t.monotonic()
        mb._pending_total = 2
        mb._groups["A"] = [
            self._entry(now - 1.0, "dead", deadline=now - 0.5),
            self._entry(now - 1.0, "live"),
        ]
        expired = []
        batch = mb._take_batch_locked(expired)
        assert [e["id"] for e in expired] == ["dead"]
        assert [e["id"] for e in batch] == ["live"]
        assert mb._pending_total == 0


class TestDeployedBatching:
    """ModelServer.enable_batching: the deployed predict path (REST via
    http.py and gRPC via grpc_server.py both route through
    ModelServer.predict) coalesces concurrent single-row requests,
    survives hot-swap, and leaves multi-row, pinned-version, and
    over-bucket requests on the direct path."""

    def _counting_factory(self, calls):
        from kubeflow_tpu.serving.model_server import MicroBatcher

        def build(model):
            def predict(inputs):
                calls.append(inputs["image"].shape[0])
                return model.predict(inputs)

            return MicroBatcher(predict, max_batch_size=4,
                                batch_timeout_s=0.25,
                                allowed_batch_sizes=[1, 2, 4],
                                name=f"t-v{model.version}")

        return build

    def test_concurrent_singles_coalesce_and_swap_keeps_batching(
            self, exported, tmp_path):
        base, model, variables = exported
        srv = ModelServer()
        srv.add_model("tiny", str(base))
        calls = []
        srv.enable_batching("tiny", self._counting_factory(calls))
        try:
            img = np.zeros((1, IMG, IMG, 3), np.float32)

            def one(i):
                return srv.predict("tiny", {"image": img + i * 0.01})

            # Warm the predict compile first so the concurrent arrivals
            # are not staggered by it (the generous 250 ms window plus
            # this keeps the coalescing assertion timing-robust).
            one(0)
            calls.clear()

            import concurrent.futures as cf

            with cf.ThreadPoolExecutor(4) as ex:
                outs = list(ex.map(one, range(4)))
            assert all(o["scores"].shape == (1, CLASSES) for o in outs)
            assert len(calls) < 4, "requests were not coalesced"

            # Hot-swap to version 2: batching must keep working through
            # the rebuilt batcher (no restart, no stale predict).
            export(base, 2, variables,
                   loader="kubeflow_tpu.serving.loaders:classifier",
                   config={"family": "resnet18", "num_classes": CLASSES,
                           "top_k": 2, "num_filters": 8})
            assert srv.reload("tiny")
            out = srv.predict("tiny", {"image": img})
            assert out["scores"].shape == (1, CLASSES)

            # Multi-row requests bypass the batcher (an entry maps to
            # exactly one result row); pinned versions bypass too.
            n_calls = len(calls)
            batch = srv.predict("tiny",
                                {"image": np.zeros((3, IMG, IMG, 3),
                                                   np.float32)})
            assert batch["scores"].shape == (3, CLASSES)
            pinned = srv.predict("tiny", {"image": img}, version=2)
            assert pinned["scores"].shape == (1, CLASSES)
        finally:
            srv.stop()


def test_main_batcher_factory_picks_per_loader():
    from kubeflow_tpu.serving.main import batcher_factory
    from kubeflow_tpu.serving.model_server import (
        BucketedLMBatcher,
        LoadedModel,
        MicroBatcher,
    )

    build = batcher_factory(micro_batch_size=8, batch_timeout_s=0.005,
                            lm_buckets="64,128")
    lm = LoadedModel(name="lm", version=1, predict=lambda i: i,
                     meta={"loader":
                           "kubeflow_tpu.serving.loaders:lm_generate"})
    clf = LoadedModel(name="clf", version=1, predict=lambda i: i,
                      meta={"loader":
                            "kubeflow_tpu.serving.loaders:classifier"})
    b_lm, b_clf = build(lm), build(clf)
    try:
        assert isinstance(b_lm, BucketedLMBatcher)
        assert b_lm.buckets == [64, 128]
        assert isinstance(b_clf, MicroBatcher)
        assert b_clf.max_batch_size == 8
    finally:
        b_lm.close()
        b_clf.close()

    # Without buckets even an lm model gets the plain batcher.
    build2 = batcher_factory(micro_batch_size=4, batch_timeout_s=0.005)
    b2 = build2(lm)
    try:
        assert isinstance(b2, MicroBatcher)
    finally:
        b2.close()


class TestBatcherLifecycleRaces:
    def test_submit_after_close_raises_not_hangs(self):
        from kubeflow_tpu.serving.model_server import BatcherClosed

        mb = MicroBatcher(lambda i: i, batch_timeout_s=0.01)
        mb.close()
        with pytest.raises(BatcherClosed):
            mb.submit({"x": np.zeros((1, 2))})

    def test_predict_retries_onto_replacement_batcher(self, exported):
        """A hot-swap can close the batcher between lookup and submit;
        predict must retry against the rebuilt one, not hang or fail."""
        from kubeflow_tpu.serving.model_server import (
            BatcherClosed,
            MicroBatcher,
        )

        base, _, _ = exported
        srv = ModelServer()
        srv.add_model("tiny", str(base))

        model = srv.get("tiny")
        real = MicroBatcher(model.predict, max_batch_size=2,
                            batch_timeout_s=0.01,
                            allowed_batch_sizes=[1, 2], name="real")

        class ClosedOnce:
            calls = 0

            def submit(self, inputs):
                # Simulate reload() winning the race: the replacement is
                # installed, then this stale batcher reports closed.
                ClosedOnce.calls += 1
                srv._batchers["tiny"] = real
                raise BatcherClosed("stale")

            def close(self):
                pass

        srv._batchers["tiny"] = ClosedOnce()
        try:
            out = srv.predict(
                "tiny",
                {"image": np.zeros((1, IMG, IMG, 3), np.float32)})
            assert out["scores"].shape == (1, CLASSES)
            assert ClosedOnce.calls == 1
        finally:
            real.close()
            srv.stop()

    def test_finish_failure_spares_delivered_rows(self):
        """A `finish` hook raising on row i must not poison rows
        0..i-1 of the same batch: their waiters keep their results
        (they may not have woken yet when the error handler runs)."""
        from kubeflow_tpu.serving.model_server import MicroBatcher

        def finish(row, meta):
            if meta:
                raise RuntimeError("finish boom")
            return row

        mb = MicroBatcher(
            lambda inputs: {"x": np.asarray(inputs["x"])},
            max_batch_size=2, batch_timeout_s=0.5,
            allowed_batch_sizes=[1, 2], in_flight=1, name="finfail",
            group_key=lambda inputs: "all",
            collate=lambda rows: (
                {"x": np.concatenate(
                    [np.asarray(r["x"]) for r in rows], axis=0)},
                # Meta truthy (=> finish raises) for every row but the
                # first, so one batch mixes delivered and poisoned rows.
                [i > 0 for i in range(len(rows))]),
            finish=finish,
        )
        try:
            import concurrent.futures as cf

            with cf.ThreadPoolExecutor(2) as ex:
                futs = [ex.submit(
                    mb.submit, {"x": np.full((1, 2), i, np.int32)})
                    for i in range(2)]
                results = []
                for f in futs:
                    try:
                        results.append(("ok", f.result(timeout=10)))
                    except RuntimeError as exc:
                        results.append(("err", str(exc)))
            kinds = sorted(k for k, _ in results)
            # Exactly one row delivered, one poisoned — never both
            # poisoned (the old handler overwrote delivered rows) and
            # never a hang.
            assert kinds == ["err", "ok"], results
        finally:
            mb.close()

    def test_over_bucket_prompt_falls_back_to_direct(self):
        from kubeflow_tpu.serving.model_server import BucketedLMBatcher

        served = []

        def predict(inputs):
            served.append(np.asarray(inputs["tokens"]).shape)
            return {"tokens": np.asarray(inputs["tokens"])}

        srv = ModelServer()
        srv._models["lm"] = {1: __import__(
            "kubeflow_tpu.serving.model_server",
            fromlist=["LoadedModel"]).LoadedModel(
                name="lm", version=1, predict=predict, meta={})}
        srv._base_paths["lm"] = "unused"
        bmb = BucketedLMBatcher(predict, buckets=[8], name="over")
        srv._batchers["lm"] = bmb
        try:
            out = srv.predict("lm", {"tokens": np.zeros((1, 20),
                                                        np.int32)})
            # Served directly at its natural length, unpadded, unerrored.
            assert out["tokens"].shape == (1, 20)
            assert served[-1] == (1, 20)
        finally:
            bmb.close()
            srv.stop()

    def test_per_request_budget_trims_batched_rows(self):
        """A per-request max_new_tokens must be honored on the static
        batcher path: the generate program still decodes the config's
        full budget (it is baked into the program), but each row's
        surplus is trimmed on the way out — same contract as the
        DecodeEngine and the direct path."""
        import concurrent.futures as cf

        from kubeflow_tpu.serving.model_server import BucketedLMBatcher

        config_new = 10

        def predict(inputs):
            toks = np.asarray(inputs["tokens"])
            fill = np.full((toks.shape[0], config_new), 7, toks.dtype)
            return {"tokens": np.concatenate([toks, fill], axis=1)}

        bmb = BucketedLMBatcher(
            predict, buckets=[8], max_batch_size=2, batch_timeout_s=0.2,
            allowed_batch_sizes=[1, 2], name="budget")
        try:
            with cf.ThreadPoolExecutor(2) as ex:
                small = ex.submit(bmb.submit, {
                    "tokens": np.ones((1, 3), np.int32),
                    "max_new_tokens": 2})
                full = ex.submit(bmb.submit, {
                    "tokens": np.ones((1, 8), np.int32)})
                # Row with a budget: prompt 3 + 2 new, pad stripped.
                assert small.result(timeout=30)["tokens"].shape == (1, 5)
                # Row without one keeps the config budget untouched.
                assert full.result(timeout=30)["tokens"].shape \
                    == (1, 8 + config_new)
        finally:
            bmb.close()


class TestIdempotencyDedup:
    """ModelServer's idempotency-key result dedup (PR 14): a retried
    key is answered, never re-executed — the survivable-inference
    contract behind the router's POST replays."""

    def _server(self, predict, **kw):
        from kubeflow_tpu.serving.model_server import LoadedModel

        server = ModelServer(**kw)
        server._models["m"] = {1: LoadedModel(
            name="m", version=1, predict=predict, meta={})}
        return server

    def test_completed_duplicate_answered_from_cache(self):
        calls = []

        def predict(inputs):
            calls.append(1)
            return {"y": np.asarray([len(calls)])}

        server = self._server(predict)
        inp = {"x": np.asarray([[1.0]])}
        r1 = server.predict("m", inp, idem_key="k1")
        r2 = server.predict("m", inp, idem_key="k1")
        assert len(calls) == 1
        # The IDENTICAL payload, not a fresh execution's.
        assert r1 is r2
        # A different key is a different request.
        server.predict("m", inp, idem_key="k2")
        assert len(calls) == 2
        # No key = no dedup (the pre-PR-14 path, unchanged).
        server.predict("m", inp)
        assert len(calls) == 3
        from kubeflow_tpu.runtime.prom import (
            REGISTRY,
            parse_metrics,
            sample_value,
        )

        parsed = parse_metrics(REGISTRY.render())
        assert (sample_value(parsed, "kft_serving_dedup_hits_total",
                             model="m") or 0) >= 1

    def test_concurrent_double_submit_executes_once(self):
        import time as _time

        started = threading.Event()
        release = threading.Event()
        calls = []

        def predict(inputs):
            calls.append(1)
            started.set()
            release.wait(timeout=10)
            return {"y": np.asarray([7])}

        server = self._server(predict)
        inp = {"x": np.asarray([[1.0]])}
        results = {}

        def submit(i):
            results[i] = server.predict("m", inp, idem_key="dup")

        t1 = threading.Thread(target=submit, args=(0,))
        t1.start()
        assert started.wait(timeout=10)
        # The duplicate arrives while the primary is mid-execution:
        # it must ATTACH, not run predict a second time.
        t2 = threading.Thread(target=submit, args=(1,))
        t2.start()
        _time.sleep(0.05)
        release.set()
        t1.join(timeout=10)
        t2.join(timeout=10)
        assert len(calls) == 1, "double submit executed twice"
        assert results[0] is results[1]

    def test_failures_are_not_cached(self):
        calls = []

        def predict(inputs):
            calls.append(1)
            if len(calls) == 1:
                raise RuntimeError("transient")
            return {"y": np.asarray([1])}

        server = self._server(predict)
        inp = {"x": np.asarray([[1.0]])}
        with pytest.raises(RuntimeError):
            server.predict("m", inp, idem_key="k")
        # The key freed with the failure: the retry re-executes.
        out = server.predict("m", inp, idem_key="k")
        assert len(calls) == 2
        assert int(np.asarray(out["y"])[0]) == 1

    def test_ttl_expires_completed_results(self):
        from kubeflow_tpu.testing import faults

        calls = []

        def predict(inputs):
            calls.append(1)
            return {"y": np.asarray([len(calls)])}

        server = self._server(predict, dedup_ttl_s=30.0)
        inp = {"x": np.asarray([[1.0]])}
        with faults.injected("seed=0") as inj:
            server.predict("m", inp, idem_key="k")
            server.predict("m", inp, idem_key="k")
            assert len(calls) == 1
            # Past the TTL (policy clock) the key re-executes: a
            # cached result must not outlive its usefulness window.
            inj.advance_clock(31)
            server.predict("m", inp, idem_key="k")
            assert len(calls) == 2

    def test_capacity_evicts_completed_not_inflight(self):
        from kubeflow_tpu.serving.model_server import _DedupCache

        cache = _DedupCache(capacity=2, ttl_s=0)
        v1, e1 = cache.begin("a")
        cache.finish("a", e1, {"r": 1})
        v2, e2 = cache.begin("b")  # in flight
        v3, e3 = cache.begin("c")  # overflows: evicts completed "a"
        assert (v1, v2, v3) == ("new", "new", "new")
        assert cache.begin("a")[0] == "new"  # evicted
        # The in-flight entry is pinned (waiters hold it).
        assert cache.begin("b")[0] == "inflight"

    def test_grpc_metadata_key_dedups(self, exported):
        """The gRPC face's x-kft-idempotency-key metadata reaches the
        same dedup cache the REST header feeds."""
        from kubeflow_tpu.serving.grpc_server import (
            PredictionClient,
            make_grpc_server,
        )

        base, _, _ = exported
        calls = []
        server = ModelServer()
        server.add_model("resnet", str(base))
        real = server.get("resnet").predict

        def counting(inputs):
            calls.append(1)
            return real(inputs)

        server.get("resnet").predict = counting
        grpc_server = make_grpc_server(server, port=0,
                                       host="127.0.0.1")
        client = PredictionClient(
            f"127.0.0.1:{grpc_server.bound_port}")
        try:
            img = np.zeros((1, 32, 32, 3), np.float32)
            r1 = client.predict("resnet", {"image": img},
                                idem_key="g1")
            r2 = client.predict("resnet", {"image": img},
                                idem_key="g1")
            assert len(calls) == 1
            for k in r1:
                assert np.array_equal(r1[k], r2[k])
        finally:
            client.close()
            grpc_server.stop(grace=0)
            server.stop()

    def test_rest_header_key_dedups(self, exported):
        """The REST x-kft-idempotency-key header reaches the dedup
        cache and the duplicate answers BYTE-identical."""
        from kubeflow_tpu.serving.http import make_http_server

        base, _, _ = exported
        calls = []
        server = ModelServer()
        server.add_model("resnet", str(base))
        real = server.get("resnet").predict

        def counting(inputs):
            calls.append(1)
            return real(inputs)

        server.get("resnet").predict = counting
        httpd = None
        try:
            httpd, _ = make_http_server(server, port=0,
                                        host="127.0.0.1")
            port = httpd.server_address[1]
            body = json.dumps({"instances": [
                {"image": np.zeros((32, 32, 3)).tolist()}]}).encode()

            def post():
                req = urllib.request.Request(
                    f"http://127.0.0.1:{port}/model/resnet:predict",
                    data=body,
                    headers={"X-KFT-Idempotency-Key": "rest-1"})
                with urllib.request.urlopen(req, timeout=60) as resp:
                    return resp.read()

            p1 = post()
            p2 = post()
            assert len(calls) == 1
            assert p1 == p2
        finally:
            if httpd is not None:
                httpd.shutdown()
            server.stop()
