"""Container entrypoints executed for real: launcher (heir of the
reference's tf-cnn launcher.py), the LM training entrypoint, and the
profiling helpers — the last modules that had no direct test."""

import os
import pathlib
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).parents[1]


def _env():
    # CPU fake slice for the spawned entrypoints, package importable
    # from any cwd.
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(REPO),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    return env


class TestLauncher:
    def test_exec_command_propagates_exit_code(self):
        ok = subprocess.run(
            [sys.executable, "-m", "kubeflow_tpu.tools.launcher",
             "--no-distributed", "--",
             sys.executable, "-c", "print('worker ran')"],
            capture_output=True, text=True, timeout=240, env=_env(),
        )
        assert ok.returncode == 0, ok.stderr[-1500:]
        assert "worker ran" in ok.stdout

        fail = subprocess.run(
            [sys.executable, "-m", "kubeflow_tpu.tools.launcher",
             "--no-distributed", "--",
             sys.executable, "-c", "raise SystemExit(3)"],
            capture_output=True, text=True, timeout=240, env=_env(),
        )
        # The reference's launcher slept forever to mask failure
        # (tf-cnn/launcher.py:86-90); this one propagates it.
        assert fail.returncode == 3

    def test_nothing_to_run_is_an_error(self):
        proc = subprocess.run(
            [sys.executable, "-m", "kubeflow_tpu.tools.launcher",
             "--no-distributed"],
            capture_output=True, text=True, timeout=240, env=_env(),
        )
        assert proc.returncode == 2


class TestTrainLM:
    def test_few_steps_on_fake_slice(self):
        proc = subprocess.run(
            [sys.executable, "-m", "kubeflow_tpu.tools.train_lm",
             "--d-model", "32", "--n-layers", "2", "--n-heads", "4",
             "--n-kv-heads", "4", "--d-ff", "64", "--head-dim", "8",
             "--vocab-size", "64", "--seq-len", "16",
             "--batch-size-per-device", "2", "--steps", "4", "--ce-dtype", "compute",
             "--log-every", "2", "--mesh", "fsdp=2"],
            capture_output=True, text=True, timeout=280, env=_env(),
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert '"event": "train_step"' in proc.stderr

    def test_pipeline_parallel_on_fake_slice(self):
        """The container entrypoint trains the real LM through GPipe:
        --mesh pipeline=2 + --pipeline-microbatches, end to end."""
        proc = subprocess.run(
            [sys.executable, "-m", "kubeflow_tpu.tools.train_lm",
             "--d-model", "32", "--n-layers", "2", "--n-heads", "4",
             "--n-kv-heads", "4", "--d-ff", "64", "--head-dim", "8",
             "--vocab-size", "64", "--seq-len", "16",
             "--batch-size-per-device", "1", "--steps", "2",
             "--pipeline-microbatches", "4",
             "--log-every", "1", "--mesh", "data=2,pipeline=2"],
            capture_output=True, text=True, timeout=280, env=_env(),
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert '"event": "train_step"' in proc.stderr


class TestProfiling:
    def test_trace_writes_xplane(self, tmp_path):
        import jax
        import jax.numpy as jnp

        from kubeflow_tpu.runtime import profiling

        with profiling.trace(str(tmp_path)):
            jax.block_until_ready(jnp.ones((8, 8)) @ jnp.ones((8, 8)))
        files = list(tmp_path.rglob("*.xplane.pb"))
        assert files, list(tmp_path.rglob("*"))

    def test_schedule_captures_configured_window(self, tmp_path):
        import jax
        import jax.numpy as jnp

        from kubeflow_tpu.runtime.profiling import ProfileSchedule

        sched = ProfileSchedule(str(tmp_path), start=1, count=2)
        for step in range(4):
            sched.before_step(step)
            jax.block_until_ready(jnp.ones((4, 4)) * step)
            sched.after_step(step)
        sched.close()
        assert list(tmp_path.rglob("*.xplane.pb")), \
            list(tmp_path.rglob("*"))
