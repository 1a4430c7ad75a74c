"""Tests for the CI harness: JUnit emission, workflow DAG, e2e drivers."""

import xml.etree.ElementTree as ET

import pytest

from kubeflow_tpu.testing.e2e import (
    adapter_serving_smoke,
    colocation_smoke,
    engine_smoke,
    fault_injection_smoke,
    fleet_smoke,
    hfta_smoke,
    kv_spill_smoke,
    multichip_serving_smoke,
    scheduler_smoke,
    serving_smoke,
    survivable_smoke,
    tpujob_smoke,
    train_resilience_smoke,
)
from kubeflow_tpu.testing.junit import JUnitSuite
from kubeflow_tpu.testing.workflow import Step, default_e2e


class TestJUnit:
    def test_pass_fail_error_classification(self, tmp_path):
        suite = JUnitSuite("demo")
        suite.run("ok", lambda: None)
        suite.run("fails", lambda: (_ for _ in ()).throw(AssertionError("x")))
        suite.run("errors", lambda: (_ for _ in ()).throw(RuntimeError("y")))
        path = suite.write(tmp_path)
        root = ET.parse(path).getroot()
        assert root.get("tests") == "3"
        assert root.get("failures") == "1"
        assert root.get("errors") == "1"
        assert not suite.ok

    def test_xml_escaping(self, tmp_path):
        suite = JUnitSuite("esc")
        suite.run("bad<name>", lambda: None)
        root = ET.parse(suite.write(tmp_path)).getroot()
        assert root[0].get("name") == "bad<name>"


class TestWorkflowDAG:
    def test_default_dag_shape(self):
        cr = default_e2e(artifacts_gcs="gs://bucket/artifacts")
        assert cr.to_custom_resource()["kind"] == "Workflow"
        spec = cr.to_custom_resource()["spec"]
        dag = [t for t in spec["templates"] if t["name"] == "main"][0]["dag"]
        by_name = {t["name"]: t for t in dag["tasks"]}
        assert by_name["deploy-kubeflow"]["dependencies"] == ["checkout"]
        assert by_name["tpujob-test"]["dependencies"] == ["deploy-kubeflow"]
        assert spec["onExit"] == "exit-handler"
        exit_tmpl = [t for t in spec["templates"]
                     if t["name"] == "exit-handler"][0]
        names = [s[0]["name"] for s in exit_tmpl["steps"]]
        assert names == ["teardown", "copy-artifacts"]

    def test_custom_step_env(self):
        wf = default_e2e().add_step(
            Step("extra", ["true"], env={"A": "1"}, deps=["checkout"]))
        cr = wf.to_custom_resource()
        tmpl = [t for t in cr["spec"]["templates"] if t["name"] == "extra"][0]
        assert tmpl["container"]["env"] == [{"name": "A", "value": "1"}]


class TestE2EDrivers:
    def test_tpujob_smoke(self):
        tpujob_smoke()

    def test_scheduler_smoke(self):
        # The ci/e2e_config.yaml hermetic `scheduler` step: two
        # tenants over the fake apiserver — quota-capped greedy
        # tenant, backfill past a blocked large job, priority
        # preemption through the checkpoint grace window with a
        # resumed-from-latest-step victim, kft_scheduler_* metrics
        # (see kubeflow_tpu/testing/e2e.py scheduler_smoke).
        scheduler_smoke()

    def test_serving_smoke(self):
        serving_smoke()

    def test_engine_smoke(self):
        # The ci/e2e_config.yaml hermetic `engine` step: mixed-length
        # requests through the HTTP surface against the in-process
        # continuous-batching engine (occupancy drains to zero), a
        # shared-prefix burst (kft_engine_prefix_hits_total > 0,
        # bounded inter-token gap), a block-exhaustion burst and a
        # decode-rounds burst (two compiled programs, token-identical
        # to a decode_rounds=1 control).
        engine_smoke()

    def test_fault_injection_smoke(self):
        # The ci/e2e_config.yaml hermetic `faults` step: the seeded
        # KFT_FAULTS chaos scenario — overload shed (429+Retry-After),
        # mid-generation deadline expiry (504) with slot reuse, loader
        # circuit-break with last-good serving, graceful drain, and
        # kft_* metric visibility of every outcome.
        fault_injection_smoke()

    def test_fleet_smoke(self):
        # The ci/e2e_config.yaml hermetic `fleet` step: router + 3
        # in-process replicas + fake apiserver — scale-out under
        # open-loop load, replica kill -> ejection -> recovery, and a
        # drain-aware rolling restart with zero lost accepted
        # requests (see kubeflow_tpu/testing/e2e.py fleet_smoke).
        fleet_smoke()

    def test_survivable_smoke(self):
        # The ci/e2e_config.yaml hermetic `survivable` step: router +
        # 3 engine replicas under a seeded kill-mid-generation
        # schedule — every accepted greedy :generate stream completes
        # bit-identical to an uninterrupted control (resume-based
        # failover + stream splicing), the dead replica force-ejects
        # and readmits after restart, a double-submitted :predict with
        # one idempotency key executes once, and
        # kft_router_replays_total{outcome="ok"} /
        # kft_serving_dedup_hits_total move as /metrics deltas (see
        # kubeflow_tpu/testing/e2e.py survivable_smoke).
        survivable_smoke()

    def test_kv_spill_smoke(self):
        # The ci/e2e_config.yaml hermetic `kv_spill` step: router + 3
        # engine replicas with a TIGHT 12-page device pool and a host
        # spill tier (user_guide §5.10) — parked multi-turn sessions
        # overflow to host RAM with zero sheds and zero destructive
        # evictions, a resumed session re-imports its spilled pages
        # bit-identical to an uninterrupted control, and a
        # kill-mid-generation failover resumes by FETCHING the
        # session's pages from a surviving peer
        # (kft_router_kv_fetch_total{outcome="ok"} delta; see
        # kubeflow_tpu/testing/e2e.py kv_spill_smoke).
        kv_spill_smoke()

    def test_multichip_serving_smoke(self):
        # The ci/e2e_config.yaml hermetic `multichip_serving` step:
        # prefill + decode tiers behind the router over the forced
        # multi-device host platform (the conftest's 8 fake chips) —
        # tiered :generate streams identical to a unified control,
        # block-page handoff counters moving as /metrics deltas, the
        # decode replica's engine tensor-parallel over a 2-device
        # mesh, and decode-pool death shedding typed 429 (see
        # kubeflow_tpu/testing/e2e.py multichip_serving_smoke).
        multichip_serving_smoke()

    def test_adapter_serving_smoke(self):
        # The ci/e2e_config.yaml hermetic `adapter_serving` step:
        # three per-tenant adapters over a 2-replica engine fleet
        # behind the router (user_guide §5.11) — hot-load under live
        # base traffic, a co-batched mixed burst token-identical to a
        # sequential per-adapter control with the engines reporting
        # only the base program set, evict-under-pressure sparing the
        # pinned in-flight adapter with zero lost accepted requests,
        # /readyz digest advertisement driving router affinity
        # (kft_router_adapter_affinity_total{outcome="hit"} delta),
        # and unknown-adapter typed 404 (see
        # kubeflow_tpu/testing/e2e.py adapter_serving_smoke).
        adapter_serving_smoke()

    def test_train_resilience_smoke(self):
        # The ci/e2e_config.yaml hermetic `train_resilience` step:
        # supervised in-process resume from a VERIFIED checkpoint
        # after an injected train.step fault (params identical to an
        # uninterrupted control run), corrupt-latest walk-back
        # restore, and node-flap -> quarantine + anti-affinity gang
        # re-place over the fake apiserver, with kft_train_* /
        # kft_checkpoint_* metric deltas asserted (see
        # kubeflow_tpu/testing/e2e.py train_resilience_smoke).
        train_resilience_smoke()

    def test_hfta_smoke(self):
        # The ci/e2e_config.yaml hermetic `hfta` step: two tenants'
        # four fusable singleton TPUJobs fold into ONE fused gang
        # (fair-share chip billing inside a quota no singleton could
        # enter), survive a high-priority preemption with every
        # member requeued resumable and resumed, complete per member
        # on pod-gang success; plus the runtime side — a width-4
        # FusedTrainer with one early-stopped masked member killed
        # mid-run resumes from per-member verified checkpoints with
        # steps monotone and params bit-identical to an uninterrupted
        # control (see kubeflow_tpu/testing/e2e.py hfta_smoke).
        hfta_smoke()

    def test_colocation_smoke(self):
        # The ci/e2e_config.yaml hermetic `colocation` step: the real
        # fleet Autoscaler in claims mode over the fake apiserver —
        # a scripted diurnal burst writes a serving claim that evicts
        # low-priority training on the SHORT serving grace (prepull
        # pods pinned to the victim's nodes), the reconciler patches
        # the Deployment only on grant, and the evening trough's
        # released chips backfill the victim, which resumes
        # bit-identical from its verified checkpoint (see
        # kubeflow_tpu/testing/e2e.py colocation_smoke).
        colocation_smoke()


class _FakeKubectl:
    """Records kubectl invocations; scripted stdout per verb."""

    def __init__(self):
        self.calls = []
        self.job_phase = "Succeeded"

    def __call__(self, cmd, input=None, text=None, capture_output=None,
                 timeout=None):
        import types

        assert cmd[0] == "kubectl"
        self.calls.append((cmd[1:], input))
        stdout = ""
        if cmd[1] == "get" and "-o" in cmd:
            stdout = ('{"status": {"phase": "%s"}}' % self.job_phase)
        return types.SimpleNamespace(returncode=0, stdout=stdout,
                                     stderr="")


class TestRealClusterDrivers:
    """The deploy-then-verify path (heir of
    testing/test_deploy.py:160-190) against a scripted kubectl — the
    real code path short of a live apiserver; ci/run_e2e_kind.sh runs
    the same commands against an actual kind cluster."""

    def test_deploy_applies_and_waits(self, monkeypatch):
        import subprocess

        from kubeflow_tpu.testing import e2e

        fake = _FakeKubectl()
        monkeypatch.setattr(subprocess, "run", fake)
        e2e.deploy_real("kf-e2e")
        verbs = [c[0][0] for c in fake.calls]
        assert "apply" in verbs
        applied = [c for c in fake.calls if c[0][0] == "apply"][0]
        assert "kind: Deployment" in applied[1]
        # Every rendered Deployment gets a rollout wait (readiness
        # budget, test_deploy.py:188-189).
        rollouts = [c[0] for c in fake.calls if c[0][0] == "rollout"]
        assert len(rollouts) >= 3
        assert all("--timeout=600s" in r for r in rollouts)

    def test_tpujob_real_polls_to_success(self, monkeypatch):
        import subprocess

        from kubeflow_tpu.testing import e2e

        fake = _FakeKubectl()
        monkeypatch.setattr(subprocess, "run", fake)
        e2e.tpujob_real("kf-e2e")
        applied = [c for c in fake.calls if c[0][0] == "apply"][0]
        assert "TPUJob" in applied[1]
        assert any(c[0][0] == "get" for c in fake.calls)

    def test_tpujob_real_fails_on_failed_phase(self, monkeypatch):
        import subprocess

        import pytest

        from kubeflow_tpu.testing import e2e

        fake = _FakeKubectl()
        fake.job_phase = "Failed"
        monkeypatch.setattr(subprocess, "run", fake)
        with pytest.raises(AssertionError, match="Failed"):
            e2e.tpujob_real("kf-e2e")
