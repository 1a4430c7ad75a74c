"""Full-loop E2E: the real operator daemon loop and the real-cluster E2E
drivers exercised TOGETHER against one shared control plane.

Round-2 gap (VERDICT #7): `operator/main.py`'s loop and
`testing/e2e.py deploy-crds`/`tpujob-real` were each tested only against
their own isolated stub.  Here one FakeKube plays the cluster for both
sides at once — the reference's deploy-then-submit-then-poll loop
(testing/test_deploy.py:160-190 + the simple_tfjob check) with three
real actors:

  * the TPUJobController reconcile loop (the exact object
    operator/main.py constructs), running on its own thread;
  * a fake kubelet driving created pods Pending -> Running -> Succeeded,
    standing in for the containers a kind/GKE cluster would run —
    docker/kind are unavailable in this build environment, so
    container execution is the one simulated piece;
  * the unmodified e2e.py drivers, whose kubectl shell-outs are routed
    onto the same FakeKube by a translating stub.
"""

import json
import threading
import time

import pytest
import yaml

from kubeflow_tpu.operator.gang import GangScheduler
from kubeflow_tpu.operator.kube import (
    FAILED,
    PENDING,
    RUNNING,
    SUCCEEDED,
    FakeKube,
    NotFound,
)
from kubeflow_tpu.operator.reconciler import TPUJobController
from kubeflow_tpu.testing import e2e


class KubectlStub:
    """Translate the e2e drivers' kubectl invocations onto a FakeKube.

    Only the verbs the drivers use: create namespace, apply -f -, and
    get tpujobs <name> -o json.  Anything else is a test bug."""

    def __init__(self, kube: FakeKube):
        self.kube = kube
        self.applied = []

    def __call__(self, args, *, input_text=None, timeout=300):
        if args[:2] == ["create", "namespace"]:
            return ""
        if args[0] == "apply":
            for doc in yaml.safe_load_all(input_text or ""):
                if not doc:
                    continue
                self.applied.append(doc)
                if doc.get("kind") == "TPUJob":
                    self.kube.create_custom(doc)
            return ""
        if args[0] == "get" and args[1].startswith("tpujobs"):
            name, namespace = args[2], args[args.index("-n") + 1]
            try:
                return json.dumps(self.kube.get_custom(namespace, name))
            except NotFound:
                raise RuntimeError(f"tpujob {name} not found")
        raise AssertionError(f"unexpected kubectl verb: {args}")


@pytest.fixture()
def cluster():
    """Shared FakeKube + operator loop + fake kubelet, started/stopped
    around each test."""
    kube = FakeKube()
    controller = TPUJobController(
        kube, GangScheduler({"v5e-1": 2, "v5e-8": 4}))
    stop = threading.Event()

    def operator_loop():
        # The daemon loop operator/main.py runs, bounded per iteration so
        # the stop flag is honored.
        while not stop.is_set():
            controller.run(poll_interval_s=0.0, max_iterations=1)
            time.sleep(0.02)

    def kubelet_loop():
        # Stand-in for container execution (no docker/kind here): every
        # scheduled pod runs briefly, then exits 0.
        seen = {}
        while not stop.is_set():
            for key, pod in list(kube.pods.items()):
                phase = pod["status"]["phase"]
                ns, name = key
                if phase == PENDING:
                    kube.set_pod_phase(ns, name, RUNNING)
                    seen[key] = time.monotonic()
                elif phase == RUNNING and \
                        time.monotonic() - seen.get(key, 0) > 0.1:
                    kube.set_pod_phase(ns, name, SUCCEEDED)
            time.sleep(0.02)

    threads = [threading.Thread(target=operator_loop, daemon=True),
               threading.Thread(target=kubelet_loop, daemon=True)]
    for t in threads:
        t.start()
    yield kube
    stop.set()
    for t in threads:
        t.join(timeout=5)


class TestFullLoop:
    def test_deploy_crds_then_tpujob_real_succeeds(self, cluster,
                                                   monkeypatch):
        stub = KubectlStub(cluster)
        monkeypatch.setattr(e2e, "_kubectl", stub)
        monkeypatch.setenv("KFT_E2E_SLICE", "v5e-1")

        e2e.deploy_crds(namespace="kubeflow-test")
        assert any(d.get("kind") == "CustomResourceDefinition"
                   for d in stub.applied)

        e2e.tpujob_real(namespace="kubeflow-test")
        cr = cluster.get_custom("kubeflow-test", "e2e-smoke")
        assert cr["status"]["phase"] == "Succeeded"
        # The operator really created gang pods for the job.
        assert any("e2e-smoke" in name
                   for (_, name) in cluster.pods.keys())

    def test_failed_worker_surfaces_failure(self, cluster, monkeypatch):
        """The loop also propagates failure: a pod that exits nonzero
        after max restarts drives the CR to Failed, and tpujob-real's
        assertion trips — the E2E would catch a broken operator."""
        stub = KubectlStub(cluster)
        monkeypatch.setattr(e2e, "_kubectl", stub)
        monkeypatch.setenv("KFT_E2E_SLICE", "v5e-1")

        # Sabotage the kubelet: flip every running pod to Failed.
        def saboteur():
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                for (ns, name), pod in list(cluster.pods.items()):
                    if pod["status"]["phase"] in (PENDING, RUNNING):
                        cluster.set_pod_phase(ns, name, FAILED)
                time.sleep(0.01)

        t = threading.Thread(target=saboteur, daemon=True)
        t.start()
        e2e.deploy_crds(namespace="kubeflow-test")
        # tpujob_real's poll breaks on any terminal phase and asserts
        # Succeeded — a Failed CR trips it without waiting out the
        # 10-minute budget.
        with pytest.raises(AssertionError, match="Failed"):
            e2e.tpujob_real(namespace="kubeflow-test")
