"""What makes the main path honest about the hardware it runs on: a
compile cache that can be placed from outside, entrypoints that name their
device, no quiet fallback off the chip, one process per accelerator, and
``chip_smoke.py`` failing fast where there is no TPU.  (That the kernels
and programs compile for the chip is tests/test_tpu_compile.py; that they
run on it is ``python chip_smoke.py`` on a machine that has one.)
"""

import json
import os
import pathlib
import subprocess
import sys
import types

import jax
import pytest

from kubeflow_tpu.runtime import bootstrap, metrics

REPO = pathlib.Path(__file__).parents[1]


def _env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO))
    env.update(extra)
    return env


class TestCompileCache:
    METADATA_IN_KEY = ("jax_compilation_cache_include_metadata_in_key", True)

    def test_placed_from_outside_the_code_sets_no_directory(
            self, monkeypatch):
        """JAX reads JAX_COMPILATION_CACHE_DIR itself; the helper must not
        set any directory on top of it.  What it does set, here too: the
        programs' metadata (named scopes, source lines) is part of the key,
        so that no other commit's executable is served with that commit's
        scopes to a profile of this one."""
        updates = []
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        monkeypatch.setattr(jax.config, "update",
                            lambda *a: updates.append(a))
        assert bootstrap.configure_compile_cache() == "/some/dir"
        assert updates == [self.METADATA_IN_KEY]

    def test_default_is_the_checkout_and_never_moves(self, monkeypatch):
        """Unset: <checkout>/.jax_cache — a fixed path, the same across
        calls and across processes (the directory is part of the cache
        key; one that moved would never hit)."""
        want = str(REPO / ".jax_cache")
        updates = []
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setattr(jax.config, "update",
                            lambda *a: updates.append(a))
        assert bootstrap.configure_compile_cache() == want
        assert bootstrap.configure_compile_cache() == want
        assert updates == [self.METADATA_IN_KEY,
                           ("jax_compilation_cache_dir", want)] * 2

        env = _env()
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        script = ("import jax; from kubeflow_tpu.runtime import bootstrap; "
                  "bootstrap.configure_compile_cache(); "
                  "print(jax.config.jax_compilation_cache_dir)")
        procs = [subprocess.Popen([sys.executable, "-c", script], env=env,
                                  cwd=cwd, stdout=subprocess.PIPE, text=True)
                 for cwd in (REPO, "/")]
        seen = [p.communicate(timeout=120)[0].strip() for p in procs]
        assert seen == [want, want]


def test_entrypoints_name_their_device_and_memory(capsys):
    found = bootstrap.report_devices()
    assert found == {"platform": "cpu", "kind": jax.devices()[0].device_kind,
                     "count": len(jax.devices())}
    memory = bootstrap.report_memory()
    assert [m["id"] for m in memory] == [d.id for d in jax.local_devices()]
    err = capsys.readouterr().err.splitlines()
    assert json.loads(err[0].removeprefix("KFT_DEVICE ")) == found
    assert json.loads(err[1].removeprefix("KFT_MEMORY ")) == memory


class TestNoQuietFallback:
    """(The kernel's own refusal to run off-TPU is
    tests/test_ops.py::test_cpu_without_interpret_raises_naming_the_backend.)"""

    def test_peak_table_knows_the_v5e_and_no_mfu_off_tpu(self):
        v5e = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
        assert metrics.peak_flops(v5e) == 197e12
        assert metrics.peak_flops(jax.devices()[0]) is None
        rec = metrics.MetricsLogger(stream=open(os.devnull, "w")).step(
            0, 0.1, 8, flops_per_step=1e9,
            peak_flops_per_chip=metrics.peak_flops(jax.devices()[0]))
        assert "mfu" not in rec

    def test_peak_table_refuses_a_tpu_it_does_not_know(self):
        unknown = types.SimpleNamespace(platform="tpu",
                                        device_kind="TPU v9 mega")
        with pytest.raises(ValueError, match="TPU v9 mega"):
            metrics.peak_flops(unknown)


def test_launcher_command_form_parent_stays_off_jax():
    """The worker command is a child that needs the chip, so the
    launcher parent must not touch JAX — not even for a multi-process
    job, where initialize() would be jax.distributed.initialize.  It
    reads the env contract and hands it on."""
    script = (
        "import sys\n"
        "from kubeflow_tpu.tools import launcher\n"
        "rc = launcher.main(['--', sys.executable, '-c', "
        "'import os; print(\"child\", os.environ[\"KFT_PROCESS_ID\"], "
        "os.environ[\"KFT_COORDINATOR_ADDRESS\"])'])\n"
        "assert 'jax' not in sys.modules, 'launcher parent imported jax'\n"
        "sys.exit(rc)\n")
    proc = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        timeout=120, cwd=REPO,
        env=_env(KFT_NUM_PROCESSES="2", KFT_PROCESS_ID="1",
                 KFT_COORDINATOR_ADDRESS="no-such-host.invalid:1234"))
    assert proc.returncode == 0, proc.stderr[-1500:]
    assert "child 1 no-such-host.invalid:1234" in proc.stdout


class TestChipSmoke:
    def test_without_a_tpu_it_fails_at_once_and_says_so(self, tmp_path):
        """``JAX_PLATFORMS=cpu python chip_smoke.py`` must not carry on on
        the CPU: the first child names its device, the parent kills it
        and exits non-zero with ok false."""
        proc = subprocess.run(
            [sys.executable, str(REPO / "chip_smoke.py"),
             "--out", str(tmp_path)],
            capture_output=True, text=True, timeout=240, env=_env())
        assert proc.returncode != 0
        last = json.loads(proc.stdout.strip().splitlines()[-1])
        assert last["ok"] is False and last["device"] is None
        assert "'cpu'" in last["error"] and "tpu" in last["error"]

    def test_children_run_one_at_a_time(self, tmp_path):
        """(That the parent never imports jax is asserted by the script
        itself before its last line — the test above runs through it.)"""
        import chip_smoke

        first = chip_smoke.Child(
            "first", [sys.executable, "-c", "import time; time.sleep(30)"],
            _env(), tmp_path, "cpu")
        try:
            with pytest.raises(chip_smoke.SmokeFailure, match="is alive"):
                chip_smoke.Child("second", [sys.executable, "-c", "pass"],
                                 _env(), tmp_path, "cpu")
        finally:
            first.kill()
            first.wait(30)
        assert chip_smoke.Child.live == []
