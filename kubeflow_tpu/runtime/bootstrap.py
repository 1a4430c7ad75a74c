"""Worker process bootstrap: from pod environment to an initialized JAX world.

Heir of the reference's rendezvous machinery, with the daemons deleted:

- TF_CONFIG JSON -> CLI flags translation
  (tf-controller-examples/tf-cnn/launcher.py:64-76) becomes a typed
  ``WorkerEnv`` parsed from env vars the operator injects.
- The openmpi hostfile trick — stable DNS names ``{name}-worker-{i}`` from a
  headless Service (kubeflow/openmpi/assets.libsonnet:30-35,
  service.libsonnet:29 ``clusterIP: None``) — is kept: the coordinator
  address is ``{job}-worker-0.{job}.{ns}:{port}`` and each worker derives its
  process index from its own pod ordinal.  What is deleted: sshd, mpiexec
  probing, mca-params, SIGCONT/SIGTERM file signalling
  (kubeflow/openmpi/assets/init.sh:13-41) — ``jax.distributed.initialize``
  plus the TPU runtime's own topology discovery replace all of it.
- The PS process fallback (grpc_tensorflow_server.py at
  kubeflow/core/tf-job-operator.libsonnet:194) has no equivalent: SPMD has
  no parameter servers.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
import pathlib
import re
import socket
import sys
import time
from typing import Optional

log = logging.getLogger(__name__)

# The checkout this package was imported from: the compile cache lives
# beside it when nobody placed it from outside.
_CHECKOUT = pathlib.Path(__file__).resolve().parents[2]

# Env contract injected by the operator (manifests/tpujob.py) into every
# worker pod.  Names are the framework's own — TF_CONFIG is not emulated.
ENV_COORDINATOR = "KFT_COORDINATOR_ADDRESS"
ENV_NUM_PROCESSES = "KFT_NUM_PROCESSES"
ENV_PROCESS_ID = "KFT_PROCESS_ID"
ENV_JOB_NAME = "KFT_JOB_NAME"
ENV_SLICE_TYPE = "KFT_SLICE_TYPE"
ENV_MEGASCALE_SLICES = "MEGASCALE_NUM_SLICES"

_ORDINAL_RE = re.compile(r"-(\d+)$")


@dataclasses.dataclass(frozen=True)
class WorkerEnv:
    """Resolved distributed identity of this worker process."""

    coordinator_address: Optional[str]
    num_processes: int
    process_id: int
    job_name: str = ""
    slice_type: str = ""

    @property
    def is_distributed(self) -> bool:
        return self.num_processes > 1

    @property
    def is_coordinator(self) -> bool:
        return self.process_id == 0


def pod_ordinal(hostname: Optional[str] = None) -> int:
    """Derive the process index from the pod's StatefulSet ordinal.

    ``myjob-worker-3`` -> 3.  This is the same naming scheme the reference's
    generated hostfile relied on (kubeflow/openmpi/assets.libsonnet:30-35),
    reused as the process-id source so the operator never has to template a
    per-pod env value.
    """
    name = hostname if hostname is not None else socket.gethostname()
    m = _ORDINAL_RE.search(name)
    return int(m.group(1)) if m else 0


def worker_env(environ: Optional[dict] = None) -> WorkerEnv:
    """Parse the distributed contract from the environment.

    Precedence: explicit KFT_PROCESS_ID beats the hostname ordinal, so
    non-StatefulSet deployments (bare pods, local runs) still work.
    """
    env = os.environ if environ is None else environ
    num = int(env.get(ENV_NUM_PROCESSES, "1"))
    pid_raw = env.get(ENV_PROCESS_ID)
    pid = int(pid_raw) if pid_raw is not None else pod_ordinal()
    coord = env.get(ENV_COORDINATOR)
    if coord is None and num > 1:
        raise RuntimeError(
            f"{ENV_NUM_PROCESSES}={num} but {ENV_COORDINATOR} unset; the "
            "operator must inject the headless-Service coordinator address"
        )
    if not 0 <= pid < num:
        raise RuntimeError(f"process_id {pid} out of range for {num} processes")
    return WorkerEnv(
        coordinator_address=coord,
        num_processes=num,
        process_id=pid,
        job_name=env.get(ENV_JOB_NAME, ""),
        slice_type=env.get(ENV_SLICE_TYPE, ""),
    )


def initialize(
    env: Optional[WorkerEnv] = None,
    *,
    wait_coordinator_timeout_s: float = 300.0,
) -> WorkerEnv:
    """Initialize the JAX distributed runtime for this worker.

    Single-process jobs are a no-op (``jax.devices()`` already sees the
    whole local slice).  Multi-process jobs resolve the coordinator's DNS
    name first — pods of a gang come up in any order and the headless
    Service record for worker-0 may not exist yet; the 300 s default equals
    the reference's MPI ``initTimeout``
    (kubeflow/openmpi/prototypes/openmpi.jsonnet:21).
    """
    env = env or worker_env()
    if not env.is_distributed:
        log.info("single-process job; skipping jax.distributed")
        return env
    host = env.coordinator_address.rsplit(":", 1)[0]
    _wait_dns(host, wait_coordinator_timeout_s)
    import jax

    jax.distributed.initialize(
        coordinator_address=env.coordinator_address,
        num_processes=env.num_processes,
        process_id=env.process_id,
    )
    log.info(
        "jax.distributed up: process %d/%d, %d global devices",
        env.process_id, env.num_processes, jax.device_count(),
    )
    return env


def configure_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns the directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and
    this places nothing.  Otherwise the cache goes to
    ``<checkout>/.jax_cache`` — a fixed path, because the directory is
    part of the cache key: one that moved between two processes would
    never hit.  Call before the first compile; every entrypoint does so
    first thing.

    Either way the key takes the programs' METADATA in (source lines,
    ``jax.named_scope`` names).  By default JAX leaves it out, and an
    executable compiled by another commit is then served with that
    commit's metadata: a profile of this commit's ``kft.*`` scopes showed
    none, because the parent commit had filled the cache (found on the
    chip, PR 24).  The price is a cold compile per checkout and per
    change to a traced source line.
    """
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    path = str(_CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def report_devices() -> dict:
    """Name the devices this process computes on, on one parseable
    stderr line (``KFT_DEVICE {json}``), so a caller that started the
    process can check it landed on the hardware it was meant for."""
    import jax

    devices = jax.devices()
    found = {"platform": devices[0].platform,
             "kind": devices[0].device_kind, "count": len(devices)}
    print(f"KFT_DEVICE {json.dumps(found)}", file=sys.stderr, flush=True)
    return found


def report_memory() -> list:
    """Bytes in use and at peak on each local device, on one parseable
    stderr line (``KFT_MEMORY [json]``) — only the process that holds a
    chip can read these, so entrypoints print them on their way out.
    Backends that keep no such statistics (CPU) report nulls."""
    import jax

    found = []
    for device in jax.local_devices():
        stats = device.memory_stats() or {}
        found.append({"id": device.id,
                      "bytes_in_use": stats.get("bytes_in_use"),
                      "peak_bytes_in_use": stats.get("peak_bytes_in_use")})
    print(f"KFT_MEMORY {json.dumps(found)}", file=sys.stderr, flush=True)
    return found


def _wait_dns(host: str, timeout_s: float, poll_s: float = 2.0) -> None:
    """Busy-wait for the coordinator hostname to resolve.

    Functional heir of the reference master's ``mpiexec … echo ready`` probe
    loop (kubeflow/openmpi/assets/init.sh:13-26), reduced to the one thing
    that actually gated readiness there: DNS for the gang's stable names.
    """
    deadline = time.monotonic() + timeout_s
    while True:
        try:
            socket.getaddrinfo(host, None)
            return
        except socket.gaierror:
            if time.monotonic() >= deadline:
                raise TimeoutError(
                    f"coordinator {host!r} did not resolve within {timeout_s}s"
                ) from None
            time.sleep(poll_s)
