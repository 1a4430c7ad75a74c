"""Deployed-entrypoint test: spawn serving/main.py as a real process and
exercise BOTH wire protocols against it — the gRPC PredictionService
(the reference's primary protocol, tensorflow_model_server :9000,
kubeflow/tf-serving/tf-serving.libsonnet:118-132) and the REST contract
(:176-207) — proving the container entrypoint the manifests deploy
actually serves what the manifests expose."""

import json
import os
import pathlib
import re
import subprocess
import sys
import urllib.request

import jax
import numpy as np
import pytest

from kubeflow_tpu.models.resnet import ResNet18
from kubeflow_tpu.serving.export import export

CLASSES, IMG = 4, 32


@pytest.fixture(scope="module")
def served_process(tmp_path_factory):
    base = tmp_path_factory.mktemp("proc_models") / "tiny"
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
    # PYTHONPATH pinned to the repo so `python -m` finds the package
    # from any cwd; JAX_PLATFORMS keeps the spawned server on the CPU.
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(pathlib.Path(__file__).parents[1]))
    proc = subprocess.Popen(
        [sys.executable, "-m", "kubeflow_tpu.serving.main",
         "--model_name", "tiny", "--model_base_path", str(base),
         "--port", "0", "--grpc_port", "0"],
        stderr=subprocess.PIPE, text=True, env=env,
    )
    # Readiness scan runs on a helper thread so a silently-hung server
    # cannot block the suite forever: the main thread waits on an event
    # with a hard deadline and kills the process on timeout.
    import threading

    found = {}
    ready = threading.Event()

    def scan():
        for line in proc.stderr:
            m = re.search(r"KFT_SERVING_READY rest=(\d+) grpc=(\d+)", line)
            if m:
                found["ports"] = int(m.group(1)), int(m.group(2))
                ready.set()
                return
        ready.set()  # EOF without the marker — process died

    threading.Thread(target=scan, daemon=True).start()
    if not ready.wait(timeout=180) or "ports" not in found:
        proc.kill()
        pytest.fail("serving process never became ready")
    ports = found["ports"]
    yield proc, ports
    proc.terminate()
    proc.wait(timeout=10)


class TestServingProcess:
    def test_rest_predict_and_health(self, served_process):
        _, (rest_port, _) = served_process
        rng = np.random.RandomState(0)
        body = json.dumps({
            "instances": [
                {"image": rng.randn(IMG, IMG, 3).astype(np.float32).tolist()}
            ]
        }).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{rest_port}/model/tiny:predict",
            data=body, headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=60) as resp:
            out = json.loads(resp.read())
        assert len(out["predictions"]) == 1
        assert len(out["predictions"][0]["scores"]) == CLASSES

        with urllib.request.urlopen(
            f"http://127.0.0.1:{rest_port}/healthz", timeout=60
        ) as resp:
            health = json.loads(resp.read())
        assert health["models"] == {"tiny": [1]}

    def test_grpc_predict_and_metadata(self, served_process):
        from kubeflow_tpu.serving.grpc_server import PredictionClient

        _, (_, grpc_port) = served_process
        client = PredictionClient(f"127.0.0.1:{grpc_port}")
        rng = np.random.RandomState(1)
        img = rng.randn(2, IMG, IMG, 3).astype(np.float32)
        out = client.predict("tiny", {"image": img}, timeout=120.0)
        assert out["scores"].shape == (2, CLASSES)
        np.testing.assert_allclose(out["scores"].sum(-1), 1.0, atol=1e-3)
        meta = client.metadata("tiny", timeout=60.0)
        assert meta["version"] == 1
        client.close()

    def test_manifest_deploys_both_protocols(self):
        """The deployed container/Service expose exactly the ports the
        entrypoint binds (the round-2 gap: gRPC tested in-process but
        absent from the deployment)."""
        import kubeflow_tpu.manifests  # noqa: F401 — registers prototypes
        from kubeflow_tpu.config.registry import default_registry

        deploy, svc = default_registry.generate(
            "tpu-serving", "m", model_name="m")[:2]
        container = deploy["spec"]["template"]["spec"]["containers"][0]
        assert "--grpc_port=9000" in container["args"]
        assert {p["containerPort"] for p in container["ports"]} == \
            {8000, 9000}
        assert {p["port"] for p in svc["spec"]["ports"]} == {8000, 9000}
