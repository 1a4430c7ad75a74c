"""A runnable tour of every parallelism family on one model.

The reference expressed parallelism as replica counts wired by TF_CONFIG
or MPI hostfiles (SURVEY.md §2.3); here each family is a mesh shape, and
the SAME flagship Transformer trains through all of them — this script
runs the whole ladder on a virtual 8-device CPU slice in a few minutes:

    JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/parallelism.py

On a real slice, drop the env vars and scale the sizes; a TPUJob
declares the same axes in `spec.mesh` (docs/user_guide.md §7).
Executed in CI by tests/test_examples.py.
"""

from __future__ import annotations

import os
import sys


def main() -> int:
    # Same opt-in gate as quickstart.py: pin the virtual CPU slice
    # unless the user explicitly asks for real hardware.
    if not os.environ.get("KFT_PARALLELISM_TPU"):
        os.environ["JAX_PLATFORMS"] = "cpu"
        if "xla_force_host_platform_device_count" not in \
                os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=8").strip()
    import jax

    import jax.numpy as jnp
    import numpy as np
    import optax

    from kubeflow_tpu.models.transformer import TransformerConfig, lm_task
    from kubeflow_tpu.parallel import MeshSpec
    from kubeflow_tpu.runtime.metrics import MetricsLogger
    from kubeflow_tpu.runtime.train import Trainer

    base = dict(
        vocab_size=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, head_dim=16, max_seq_len=64, dtype=jnp.bfloat16,
    )
    # (name, mesh, config overrides) — one row per family.  Sizes are
    # sized for 8 devices; each mesh trains 2 steps of the real model.
    ladder = [
        ("data-parallel", MeshSpec(data=8), {}),
        ("fsdp (ZeRO-3)", MeshSpec(data=2, fsdp=4), {}),
        ("tensor-parallel", MeshSpec(data=4, tensor=2), {}),
        ("sequence-parallel (ring attention)",
         MeshSpec(data=4, sequence=2), {"attention": "ring"}),
        ("expert-parallel (MoE)",
         MeshSpec(data=4, expert=2), {"moe_experts": 4}),
        ("pipeline-parallel (GPipe)",
         MeshSpec(data=4, pipeline=2),
         {"pipeline_microbatches": 4, "attention": "dot"}),
        # The composed finale: ring attention + MoE + GPipe in ONE
        # program over a pipeline x sequence x expert mesh — the
        # combinations a >1-slice MoE long-context job wants (the r4
        # composition walls, lifted in r5).
        ("pp x sp x ep composed (ring + MoE through GPipe)",
         MeshSpec(pipeline=2, sequence=2, expert=2),
         {"pipeline_microbatches": 2, "attention": "ring",
          "moe_experts": 2}),
    ]
    rng = np.random.RandomState(0)
    devnull = open(os.devnull, "w")
    for name, spec, overrides in ladder:
        mesh = spec.build()
        cfg = TransformerConfig(**{**base, **overrides})
        init_fn, loss_fn = lm_task(cfg, mesh=mesh)
        trainer = Trainer(
            init_fn=init_fn, loss_fn=loss_fn, tx=optax.adamw(1e-3),
            mesh=mesh,
            metrics=MetricsLogger(stream=devnull),
        )
        batch = max(8, mesh.shape["data"] * mesh.shape["fsdp"] * 2)
        tokens = rng.randint(0, cfg.vocab_size,
                             size=(batch, 32)).astype(np.int32)

        def data(tokens=tokens):
            while True:
                yield {"tokens": tokens}

        trainer.fit(data(), num_steps=2, examples_per_step=batch,
                    log_every=0)
        loss = trainer.last_metrics["loss"]
        axes = {a: s for a, s in mesh.shape.items() if s > 1}
        print(f"{name:40s} mesh={axes}  loss={loss:.3f}")
    devnull.close()
    print("parallelism tour complete: every family trained the real "
          "Transformer")
    return 0


if __name__ == "__main__":
    sys.exit(main())
