"""In-container CNN training entrypoint — heir of tf_cnn_benchmarks as
driven by the reference's prototypes (kubeflow/tf-job/prototypes/
tf-cnn-benchmarks.jsonnet:40-62) and launcher
(tf-controller-examples/tf-cnn/launcher.py).

Where the reference translated TF_CONFIG into --ps_hosts/--worker_hosts
PS-mode flags, this entrypoint reads the KFT_* env (runtime/bootstrap.py),
joins the gang via jax.distributed, and runs the SPMD data-parallel
trainer.  Synthetic data by default (as tf_cnn_benchmarks offered); real
input via --data-dir of KFTR shards through the data/ pipeline's C++
prefetch core, sharded per process (each host feeds only its own rows —
the multi-host contract of Trainer.shard_batch).
"""

from __future__ import annotations

import argparse
import glob
import logging
import os
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="kubeflow-tpu-train-cnn")
    ap.add_argument("--model", default="resnet50")
    ap.add_argument("--batch-size-per-device", type=int, default=128)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--image-size", type=int, default=224)
    ap.add_argument("--num-classes", type=int, default=1000)
    ap.add_argument("--dtype", default="bfloat16",
                    choices=["bfloat16", "float32"])
    ap.add_argument("--data-dir", default="",
                    help="directory of KFTR shards with image/label "
                         "examples; synthetic data when unset")
    ap.add_argument("--shuffle-buffer", type=int, default=4096)
    ap.add_argument("--data-threads", type=int, default=4)
    ap.add_argument("--checkpoint-dir", default="")
    ap.add_argument("--checkpoint-every", type=int, default=100)
    ap.add_argument("--learning-rate", type=float, default=0.1)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--max-restarts", type=int, default=3,
                    help="in-process supervised restarts from the last "
                         "verified checkpoint (0 = fail on the first "
                         "fault)")
    ap.add_argument("--stall-factor", type=float, default=10.0,
                    help="flag a stall when the current dispatch age "
                         "exceeds this multiple of the rolling median "
                         "step time")
    ap.add_argument("--heartbeat-s", type=float, default=10.0,
                    help="stall-watchdog poll period (also the "
                         "kft_train_heartbeat_age_seconds refresh)")
    args = ap.parse_args(argv)

    logging.basicConfig(level=logging.INFO, stream=sys.stderr)
    from kubeflow_tpu.runtime import bootstrap
    from kubeflow_tpu.testing import faults

    # Honor KFT_FAULTS like serving/main.py: the same scripted chaos
    # (train.step/checkpoint.*/data.next) drives a deployed training
    # container, the e2e harness, and in-process tests.
    faults.install_from_env()
    bootstrap.configure_compile_cache()
    env = bootstrap.initialize()
    bootstrap.report_devices()

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from kubeflow_tpu.models.classification import classification_task
    from kubeflow_tpu.models.resnet import ResNetConfig
    from kubeflow_tpu.parallel import MeshSpec
    from kubeflow_tpu.runtime.checkpoint import CheckpointManager
    from kubeflow_tpu.runtime.metrics import MetricsLogger, peak_flops
    from kubeflow_tpu.runtime.train import Trainer
    from kubeflow_tpu.runtime.topology import parse_slice_type

    n = jax.device_count()
    global_batch = args.batch_size_per_device * n
    # Each process feeds only its own shard of the global batch
    # (Trainer.shard_batch assembles the global array across hosts).
    host_batch = args.batch_size_per_device * jax.local_device_count()
    size = args.image_size
    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    cfg = ResNetConfig(name=args.model, num_classes=args.num_classes,
                       dtype=dtype)
    init_fn, loss_fn = classification_task(
        cfg.build(), (1, size, size, 3))
    mesh = MeshSpec(data=n).build()
    peak = (parse_slice_type(env.slice_type).bf16_tflops_per_chip * 1e12
            if env.slice_type else peak_flops(jax.devices()[0]))
    ckpt = (CheckpointManager(args.checkpoint_dir)
            if args.checkpoint_dir else None)
    trainer = Trainer(
        init_fn=init_fn, loss_fn=loss_fn,
        tx=optax.sgd(args.learning_rate, momentum=0.9), mesh=mesh,
        checkpoints=ckpt, checkpoint_every=args.checkpoint_every,
        metrics=MetricsLogger(static={"job": env.job_name,
                                      "process": env.process_id}),
        flops_per_example=cfg.fwd_flops_per_image * (size / 224) ** 2,
        peak_flops_per_chip=peak,
    )

    if args.data_dir:
        from kubeflow_tpu.data.loader import RecordDataset, tensor_batches

        files = sorted(glob.glob(os.path.join(args.data_dir, "*.kftr")))
        if not files:
            logging.error("no *.kftr shards under %s", args.data_dir)
            return 1

        def data_factory():
            ds = RecordDataset(
                files, num_threads=args.data_threads,
                shuffle_buffer=args.shuffle_buffer, seed=env.process_id,
                repeat=-1,  # cycle forever; steps bound the run
            )
            if env.num_processes > 1:
                ds = ds.shard(env.process_id, env.num_processes)
            return tensor_batches(ds, host_batch)
    else:
        def data_factory():
            # Fresh RNG per attempt: a supervised restart replays the
            # SAME stream, and fit's resume drain re-aligns it.
            rng = np.random.RandomState(env.process_id)
            while True:
                yield {
                    "image": rng.randn(host_batch, size, size, 3).astype(
                        np.float32),
                    "label": rng.randint(0, args.num_classes,
                                         size=(host_batch,)),
                }

    from kubeflow_tpu.runtime.supervisor import TrainSupervisor

    supervisor = TrainSupervisor(
        trainer, max_restarts=args.max_restarts,
        stall_factor=args.stall_factor, heartbeat_s=args.heartbeat_s)
    supervisor.run(data_factory, args.steps,
                   examples_per_step=global_batch,
                   log_every=args.log_every)
    logging.info("training done: %s", trainer._last_metrics)
    bootstrap.report_memory()
    return 0


if __name__ == "__main__":
    sys.exit(main())
