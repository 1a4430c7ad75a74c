"""In-container LM training entrypoint — the flagship Transformer under
the full parallelism surface (dp/fsdp/sp/tp/ep via --mesh axes).

No reference counterpart (its era had no LM workload); this is the
entrypoint TPUJob LM prototypes launch.
"""

from __future__ import annotations

import argparse
import logging
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="kubeflow-tpu-train-lm")
    ap.add_argument("--d-model", type=int, default=512)
    ap.add_argument("--n-layers", type=int, default=8)
    ap.add_argument("--n-heads", type=int, default=8)
    ap.add_argument("--n-kv-heads", type=int, default=8)
    ap.add_argument("--d-ff", type=int, default=1408)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--vocab-size", type=int, default=32_000)
    ap.add_argument("--seq-len", type=int, default=1024)
    ap.add_argument("--moe-experts", type=int, default=0)
    ap.add_argument("--pipeline-microbatches", type=int, default=0,
                    help="GPipe microbatches; takes effect when --mesh "
                         "includes pipeline=N>1 (the layer stack then "
                         "runs N_layers/N per stage)")
    ap.add_argument("--attention", default="dot",
                    choices=["dot", "flash", "ring"])
    ap.add_argument("--remat", action="store_true")
    ap.add_argument("--ce-dtype", default="f32",
                    choices=["f32", "compute"],
                    help="cross-entropy input precision (see "
                         "TransformerConfig.ce_dtype)")
    ap.add_argument("--batch-size-per-device", type=int, default=8)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--steps-per-call", type=int, default=1,
                    help="fused train steps per device dispatch "
                         "(Trainer.fit host-loop fusion)")
    ap.add_argument("--learning-rate", type=float, default=3e-4)
    ap.add_argument("--warmup-steps", type=int, default=0,
                    help=">0 = linear warmup to --learning-rate then "
                         "cosine decay over --steps")
    ap.add_argument("--optimizer", default="adamw",
                    choices=["adamw", "adafactor"])
    ap.add_argument("--moe-capacity-factor", type=float, default=1.25)
    ap.add_argument("--metrics-out", default="",
                    help="write the final metrics history as JSON "
                         "(loss-curve artifact)")
    ap.add_argument("--mesh", default="",
                    help="axis sizes, e.g. 'tensor=4,sequence=2' "
                         "(data absorbs the rest)")
    ap.add_argument("--data-files", nargs="*", default=[],
                    help="KFTR shards with {'tokens': [s]} examples "
                         "(synthetic stream if empty)")
    ap.add_argument("--checkpoint-dir", default="")
    ap.add_argument("--checkpoint-every", type=int, default=100)
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
    import numpy as np
    import optax

    from kubeflow_tpu.models.transformer import TransformerConfig, lm_task
    from kubeflow_tpu.parallel import MeshSpec
    from kubeflow_tpu.runtime.checkpoint import CheckpointManager
    from kubeflow_tpu.runtime.metrics import MetricsLogger, peak_flops
    from kubeflow_tpu.runtime.topology import parse_slice_type
    from kubeflow_tpu.runtime.train import Trainer

    mesh_axes = {}
    if args.mesh:
        for pair in args.mesh.split(","):
            k, _, v = pair.partition("=")
            k = k.strip()
            if k == "model":
                # The TPUJob CRD spells the tensor axis "model"
                # (operator/crd.py MeshSpec); accept either spelling so
                # an admitted spec.mesh can be mirrored into worker args
                # verbatim.
                k = "tensor"
            if k in mesh_axes:
                # Matches crd.MeshSpec.from_dict: declaring an axis
                # twice (incl. via its alias) fails loudly instead of
                # silently last-wins.
                ap.error(f"--mesh declares axis {k!r} twice "
                         "(note 'model' aliases 'tensor')")
            mesh_axes[k] = int(v)
    if mesh_axes.get("pipeline", 1) > 1 and not args.pipeline_microbatches:
        # Without microbatches the model runs the plain sequential scan
        # while the layer stack stays sharded over the pipeline axis —
        # every device all-gathers the other stages' params each step,
        # pure overhead that LOOKS like working PP.  Fail loudly.
        ap.error("--mesh pipeline>1 requires --pipeline-microbatches>0 "
                 "(otherwise the pipeline axis is pure overhead: the "
                 "layer stack is sharded over it but the GPipe schedule "
                 "never runs)")
    mesh = MeshSpec(**mesh_axes).build()

    cfg = TransformerConfig(
        vocab_size=args.vocab_size, d_model=args.d_model,
        n_layers=args.n_layers, n_heads=args.n_heads,
        n_kv_heads=args.n_kv_heads, d_ff=args.d_ff,
        head_dim=args.head_dim, max_seq_len=args.seq_len,
        moe_experts=args.moe_experts,
        moe_capacity_factor=args.moe_capacity_factor,
        attention=args.attention,
        remat=args.remat, ce_dtype=args.ce_dtype,
        pipeline_microbatches=args.pipeline_microbatches,
    )
    init_fn, loss_fn = lm_task(cfg, mesh=mesh)
    batch = args.batch_size_per_device * jax.device_count()
    peak = (parse_slice_type(env.slice_type).bf16_tflops_per_chip * 1e12
            if env.slice_type else peak_flops(jax.devices()[0]))
    if args.warmup_steps > 0:
        lr = optax.warmup_cosine_decay_schedule(
            init_value=0.0, peak_value=args.learning_rate,
            warmup_steps=args.warmup_steps, decay_steps=args.steps,
            end_value=args.learning_rate * 0.1)
    else:
        lr = args.learning_rate
    tx = (optax.adafactor(lr) if args.optimizer == "adafactor"
          else optax.adamw(lr))
    trainer = Trainer(
        init_fn=init_fn, loss_fn=loss_fn,
        tx=tx, mesh=mesh,
        checkpoints=(CheckpointManager(args.checkpoint_dir)
                     if args.checkpoint_dir else None),
        checkpoint_every=args.checkpoint_every,
        metrics=MetricsLogger(static={"job": env.job_name,
                                      "process": env.process_id}),
        flops_per_example=cfg.flops_per_token() * args.seq_len,
        peak_flops_per_chip=peak,
    )

    if args.data_files:
        from kubeflow_tpu.data import RecordDataset, tensor_batches

        def data_factory():
            ds = RecordDataset(
                args.data_files, shuffle_buffer=1024, repeat=-1,
            ).shard(env.process_id, max(env.num_processes, 1))
            return tensor_batches(ds, batch)
    else:
        def data_factory():
            # Fresh RNG per attempt: a supervised restart replays the
            # SAME stream, and fit's resume drain re-aligns it.
            rng = np.random.RandomState(env.process_id)
            while True:
                yield {"tokens": rng.randint(
                    0, args.vocab_size,
                    size=(batch, args.seq_len)).astype(np.int32)}

    from kubeflow_tpu.runtime.supervisor import TrainSupervisor

    supervisor = TrainSupervisor(
        trainer, max_restarts=args.max_restarts,
        stall_factor=args.stall_factor, heartbeat_s=args.heartbeat_s)
    supervisor.run(data_factory, args.steps, examples_per_step=batch,
                   log_every=args.log_every,
                   steps_per_call=args.steps_per_call)
    logging.info("training done: %s", trainer._last_metrics)
    bootstrap.report_memory()
    if args.metrics_out:
        import json as _json

        with open(args.metrics_out, "w") as f:
            _json.dump({
                "config": {k: v for k, v in vars(args).items()
                           if isinstance(v, (int, float, str, bool))},
                "history": trainer.metrics.history,
            }, f, indent=1, default=float)
            f.write("\n")
        logging.info("metrics history -> %s", args.metrics_out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
