"""Real two-process rendezvous through runtime/bootstrap.py.

Heir of the reference's `simple_tfjob` E2E — the only test there that
actually ran a multi-pod job through the TF_CONFIG contract
(/root/reference/testing/workflows/components/workflows.libsonnet:398-411).
Here two REAL OS processes run the worker bootstrap (env parse, DNS wait,
``jax.distributed.initialize`` against a localhost coordinator), then
execute one cross-process collective — the seam every previous round
covered only up to, never through.
"""

import os
import socket
import subprocess
import sys

from kubeflow_tpu.runtime import bootstrap

_WORKER = r"""
import os, sys
import jax

from kubeflow_tpu.runtime import bootstrap

env = bootstrap.worker_env()
env = bootstrap.initialize(env, wait_coordinator_timeout_s=60.0)

assert jax.process_count() == 2, jax.process_count()
assert jax.process_index() == env.process_id

import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

devs = jax.devices()
assert len(devs) == 2 * jax.local_device_count(), devs
mesh = Mesh(np.array(devs), ("data",))
# Each process contributes its own shard; the jitted sum is a real
# cross-process collective over the distributed backend.
local = np.array([float(env.process_id + 1)], dtype=np.float32)
arr = jax.make_array_from_process_local_data(
    NamedSharding(mesh, P("data")), local)
total = jax.jit(jax.numpy.sum,
                out_shardings=NamedSharding(mesh, P()))(arr)
print(f"RENDEZVOUS process={env.process_id} sum={float(total)}", flush=True)
"""


_TRAIN_WORKER = r"""
import os, sys
import jax

from kubeflow_tpu.runtime import bootstrap

env = bootstrap.initialize(bootstrap.worker_env(),
                           wait_coordinator_timeout_s=60.0)
assert jax.process_count() == 2

import numpy as np
import optax

from kubeflow_tpu.models.transformer import TransformerConfig, lm_task
from kubeflow_tpu.parallel import MeshSpec
from kubeflow_tpu.runtime.metrics import MetricsLogger
from kubeflow_tpu.runtime.train import Trainer

cfg = TransformerConfig(
    vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=4,
    d_ff=64, head_dim=8, max_seq_len=16, dtype=jax.numpy.float32,
)
mesh = MeshSpec(data=2).build()  # one device per process -> data=2
init_fn, loss_fn = lm_task(cfg, mesh=mesh)
trainer = Trainer(
    init_fn=init_fn, loss_fn=loss_fn, tx=optax.adam(1e-2), mesh=mesh,
    metrics=MetricsLogger(stream=open(os.devnull, "w")),
)

# Each process feeds ONLY its local rows (global batch 4 = 2 x 2);
# Trainer.shard_batch assembles the global array from process-local
# data — no host ever holds the full batch.
rng = np.random.RandomState(env.process_id)


def data():
    while True:
        yield {"tokens": rng.randint(0, 64, size=(2, 16)).astype(np.int32)}


state = trainer.fit(data(), num_steps=3, examples_per_step=4, log_every=0)
# The loss/params are replicated state: both processes must agree
# bit-for-bit (same compiled SPMD program, collectives included).
print(f"TRAIN process={env.process_id} "
      f"loss={trainer.last_metrics['loss']:.6f} "
      f"step={int(state.step)}", flush=True)
"""


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]

def _run_two_workers(worker_src: str, job_name: str, timeout_s: float,
                     devices_per_process: int = 1):
    """Spawn two worker processes against one localhost coordinator and
    return [(rc, stdout, stderr)], asserting both exited cleanly."""
    port = _free_port()
    env_base = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        # devices_per_process=1: the 2-process world has 2 global
        # devices and every collective is cross-process.  >1 models a
        # multi-host slice — an intra-process axis (ICI-like) crossed
        # with the process-spanning axis (DCN-like).
        "XLA_FLAGS": "--xla_force_host_platform_device_count="
                     f"{devices_per_process}",
        bootstrap.ENV_COORDINATOR: f"127.0.0.1:{port}",
        bootstrap.ENV_NUM_PROCESSES: "2",
        bootstrap.ENV_JOB_NAME: job_name,
    }
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", worker_src],
            env={**env_base, bootstrap.ENV_PROCESS_ID: str(pid)},
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            cwd=os.path.dirname(os.path.dirname(__file__)),
        )
        for pid in (0, 1)
    ]
    outs = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=timeout_s)
            outs.append((p.returncode, out, err))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for rc, out, err in outs:
        assert rc == 0, f"worker failed rc={rc}\nstdout:{out}\nstderr:{err}"
    return outs


_SHARDED_TRAIN_WORKER = r"""
import os, sys
import jax

from kubeflow_tpu.runtime import bootstrap

env = bootstrap.initialize(bootstrap.worker_env(),
                           wait_coordinator_timeout_s=60.0)
assert jax.process_count() == 2
assert jax.local_device_count() == 2
assert jax.device_count() == 4

import numpy as np
import optax

from kubeflow_tpu.models.transformer import TransformerConfig, lm_task
from kubeflow_tpu.parallel import MeshSpec
from kubeflow_tpu.runtime.metrics import MetricsLogger
from kubeflow_tpu.runtime.train import Trainer

cfg = TransformerConfig(
    vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=4,
    d_ff=64, head_dim=8, max_seq_len=16, dtype=jax.numpy.float32,
)
# data=2 x fsdp=2 over 4 devices, 2 per process: jax.devices() is
# process-major, so the DATA axis spans the process boundary (the DCN
# hop of a multi-host slice) while FSDP weight sharding stays
# intra-process (the ICI hop) — the actual topology of a multi-host
# TPU job, and the configuration the suite previously never modeled.
mesh = MeshSpec(data=2, fsdp=2).build()
for row in mesh.devices.reshape(2, 2):  # rows: data idx, cols: fsdp
    assert len({d.process_index for d in row}) == 1, (
        "fsdp row must be intra-process", mesh.devices)
assert {d.process_index for d in mesh.devices.reshape(2, 2)[:, 0]} \
    == {0, 1}, "data axis must span the process boundary"

init_fn, loss_fn = lm_task(cfg, mesh=mesh)
trainer = Trainer(
    init_fn=init_fn, loss_fn=loss_fn, tx=optax.adam(1e-2), mesh=mesh,
    metrics=MetricsLogger(stream=open(os.devnull, "w")),
)
state = trainer.create_state(seed=0)
# FSDP actually shards the weights: each param's embed dim is split
# over the fsdp axis, so every train step all-gathers weights inside
# each process while grads cross processes over the data axis.
wq = state.params["layers"]["attn"]["wq"]
assert "fsdp" in tuple(str(a) for a in wq.sharding.spec), wq.sharding.spec

# Global batch 8 = 2 processes x 4 local rows; each process feeds only
# its local shard (batch axis = data axis = process axis).
rng = np.random.RandomState(env.process_id)


def data():
    while True:
        yield {"tokens": rng.randint(0, 64, size=(4, 16)).astype(np.int32)}


state = trainer.fit(data(), num_steps=3, state=state,
                    examples_per_step=8, log_every=0)
print(f"SHARDED process={env.process_id} "
      f"loss={trainer.last_metrics['loss']:.6f} "
      f"step={int(state.step)}", flush=True)
"""


def test_two_process_rendezvous_and_psum():
    outs = _run_two_workers(_WORKER, "rendezvous-test", 150)
    # 1.0 + 2.0 over the two processes.
    assert "RENDEZVOUS process=0 sum=3.0" in outs[0][1], outs[0]
    assert "RENDEZVOUS process=1 sum=3.0" in outs[1][1], outs[1]


def test_two_process_training_through_trainer():
    """REAL multi-host SPMD training in CI: two OS processes, the
    shipped Trainer.fit, each feeding only its process-local batch shard
    (make_array_from_process_local_data), gradients averaged by compiled
    collectives over the distributed backend.  Both processes must end
    at the identical replicated loss — the multi-worker contract the
    reference could only check on rented clusters (SURVEY.md §4)."""
    outs = _run_two_workers(_TRAIN_WORKER, "train-rendezvous", 240)
    lines = [next(ln for ln in out.splitlines() if ln.startswith("TRAIN"))
             for _, out, _ in outs]
    # Same replicated state on both processes, steps advanced.
    loss0 = lines[0].split("loss=")[1].split()[0]
    loss1 = lines[1].split("loss=")[1].split()[0]
    assert loss0 == loss1, lines
    assert "step=3" in lines[0], lines


def test_two_process_two_device_sharded_training():
    """Multi-process x multi-device mesh in CI (VERDICT r4 item 6): two
    OS processes x two CPU devices each, a data x fsdp mesh whose DATA
    axis spans the process boundary and whose FSDP axis shards weights
    intra-process — the topology of a real multi-host slice — through
    the shipped Trainer.fit to the identical replicated loss."""
    outs = _run_two_workers(
        _SHARDED_TRAIN_WORKER, "sharded-rendezvous", 300,
        devices_per_process=2)
    lines = [next(ln for ln in out.splitlines()
                  if ln.startswith("SHARDED"))
             for _, out, _ in outs]
    loss0 = lines[0].split("loss=")[1].split()[0]
    loss1 = lines[1].split("loss=")[1].split()[0]
    assert loss0 == loss1, lines
    assert "step=3" in lines[0], lines
