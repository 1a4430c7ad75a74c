"""Worker container launcher.

Heir of tf-controller-examples/tf-cnn/launcher.py: where that script
translated operator-injected TF_CONFIG JSON into tf_cnn_benchmarks flags
and streamed the subprocess (launcher.py:29-90), this one consumes the
KFT_* env contract (runtime/bootstrap.py) and then either imports a python
entrypoint in-process — after initializing jax.distributed, so the
initialized JAX runtime is shared — or runs the user command as a child.
In the command form the launcher itself never touches JAX: an accelerator
belongs to one process at a time, so the child, which inherits the env
contract, does its own ``bootstrap.initialize()`` (the train entrypoints
do) and gets the chip.

Deliberately absent: the reference's sleep-forever-on-success hack
(launcher.py:86-90) — gang restart policy lives in the operator, pods use
restartPolicy Never, so finishing is just exiting 0.
"""

from __future__ import annotations

import argparse
import importlib
import logging
import os
import subprocess
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="kubeflow-tpu-launch")
    ap.add_argument("--entrypoint",
                    help="python entrypoint 'module:function' run in-process "
                         "after jax.distributed init")
    ap.add_argument("--no-distributed", action="store_true",
                    help="--entrypoint form: skip jax.distributed "
                         "(single-process debug)")
    ap.add_argument("command", nargs=argparse.REMAINDER,
                    help="command to exec (after '--')")
    args = ap.parse_args(argv)

    logging.basicConfig(
        level=logging.INFO, stream=sys.stderr,
        format="%(asctime)s launcher %(levelname)s %(message)s",
    )
    from kubeflow_tpu.runtime import bootstrap

    env = bootstrap.worker_env()
    logging.info(
        "worker %d/%d (job=%s slice=%s coordinator=%s)",
        env.process_id, env.num_processes, env.job_name or "-",
        env.slice_type or "-", env.coordinator_address or "-",
    )
    if args.entrypoint:
        if not args.no_distributed:
            bootstrap.initialize(env)
        mod_name, _, fn_name = args.entrypoint.partition(":")
        fn = getattr(importlib.import_module(mod_name), fn_name or "main")
        result = fn()
        return int(result or 0)

    command = args.command
    if command and command[0] == "--":
        command = command[1:]
    if not command:
        logging.error("nothing to run: give --entrypoint or a command")
        return 2
    # Stream the child's output; propagate its exit code unchanged so the
    # operator sees real success/failure (no restart-policy games).
    proc = subprocess.run(command, env=os.environ)
    return proc.returncode


if __name__ == "__main__":
    sys.exit(main())
