"""Compile the main path's kernels and two engine programs for a v5e chip
that is described, not attached (the TPU compiler ships with jaxlib).

Interpret-mode tests (tests/test_ops.py) prove the kernels' arithmetic;
they cannot see what the chip's compiler refuses — a slice off the tiling,
too much VMEM, a program that does not fit 16 GB.  These compiles can, at
the 188M LM's real widths, for no chip time.  Nothing runs: a pass here
is a compile, not a chip run.

The kernels are compiled through ``ops.flash._flash`` /
``_flash_fwd_bhsd`` directly: the public ``flash_attention`` asks
``jax.default_backend()``, which is the CPU in this suite.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from kubeflow_tpu.ops import flash

# The 188M LM (chip_smoke.py, bench.py's lm preset).
LM = {"vocab_size": 32_000, "d_model": 1024, "n_layers": 12, "n_heads": 8,
      "n_kv_heads": 8, "d_ff": 2816, "head_dim": 128, "max_seq_len": 2048,
      "dtype": "bfloat16"}


@pytest.fixture(scope="module")
def chip():
    """One device of a described v5e 2x2; skips where it cannot be
    described (no TPU compiler in this jaxlib)."""
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described device is written to the persistent
    cache but cannot be read back without the chip: the next one would
    warn and recompile.  Off around these tests."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _qkv(chip, bh, s, d):
    x = jax.ShapeDtypeStruct((bh, s, d), jnp.bfloat16, sharding=chip)
    return x, x, x


def _has_kernel(compiled):
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("bh,s,d,block_k", [
    (64, 2048, 128, 1024),   # train: batch 8 x 8 heads, seq 2048
    (32, 1024, 64, 512),     # train_lm's default widths
])
def test_flash_forward_and_backward_compile(chip, bh, s, d, block_k):
    def loss(q, k, v):
        return flash._flash(q, k, v, True, 512, block_k, False,
                            0).astype(jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        *_qkv(chip, bh, s, d)).compile()
    # forward, dq and dkv kernels
    assert compiled.as_text().count("tpu_custom_call") >= 3


def test_flash_forward_long_sequence_compiles(chip):
    compiled = jax.jit(
        lambda q, k, v: flash._flash(q, k, v, True, 512, 1024, False, 0)
    ).lower(*_qkv(chip, 8, 16384, 128)).compile()
    assert _has_kernel(compiled)


def test_flash_two_pass_forward_compiles(chip):
    compiled = jax.jit(
        lambda q, k, v: flash._flash(q, k, v, True, 512, 1024, False, 128)
    ).lower(*_qkv(chip, 16, 4096, 128)).compile()
    assert _has_kernel(compiled)


@pytest.mark.parametrize("s", [96, 256, 2048])
def test_flash_kv_start_forward_compiles(chip, s):
    """The static batcher's left-padded prefill (models/generate.py)."""
    bh = 64
    start = jax.ShapeDtypeStruct((bh, 1), jnp.int32, sharding=chip)
    compiled = jax.jit(
        lambda q, k, v, st: flash._flash_fwd_bhsd(
            q, k, v, causal=True, block_q=512, block_k=512,
            interpret=False, kv_start=st)
    ).lower(*_qkv(chip, bh, s, 128), start).compile()
    assert _has_kernel(compiled)


def _engine_shapes(chip, widths, slots, max_len, block=16,
                   max_new_tokens=128):
    """Abstract params and paged state of an engine on ``chip``."""
    from flax import linen as nn

    from kubeflow_tpu.models import generate
    from kubeflow_tpu.models.transformer import Transformer
    from kubeflow_tpu.ops.quantize import narrow_params
    from kubeflow_tpu.serving.loaders import _model_config

    cfg = _model_config(widths)
    table_blocks = -(-max_len // block)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
            tree)

    params = on_chip(jax.eval_shape(lambda: narrow_params(
        nn.unbox(Transformer(cfg).init(
            jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]),
        cfg.dtype)))
    state = on_chip(jax.eval_shape(lambda: generate.init_paged_state(
        cfg, slots, slots * table_blocks, block)))

    def arg(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)

    return {"cfg": cfg, "params": params, "state": state, "arg": arg,
            "decode": generate.DecodeConfig(max_new_tokens=max_new_tokens),
            "slots": slots, "table_blocks": table_blocks}


@pytest.fixture(scope="module")
def engine_shapes(chip):
    """The 188M engine: 16 slots, chunk 64, 16-token pages, 256-token
    prompts + 128 new."""
    return _engine_shapes(chip, LM, 16, 256 + 128)


def _fits(compiled, gib=16):
    m = compiled.memory_analysis()
    live = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    return live < gib * 2 ** 30


def test_engine_decode_step_compiles(engine_shapes):
    from kubeflow_tpu.models.generate import decode_step

    e = engine_shapes
    compiled = decode_step.lower(
        e["cfg"], e["params"], e["state"], e["decode"], 1,
        e["arg"](e["slots"], e["table_blocks"])).compile()
    assert _fits(compiled)


def test_engine_prefill_chunk_compiles(engine_shapes):
    from kubeflow_tpu.models.generate import prefill_chunk_into_slot

    e = engine_shapes
    scalar = e["arg"]()
    compiled = prefill_chunk_into_slot.lower(
        e["cfg"], e["params"], e["state"], e["decode"], e["arg"](1, 64),
        scalar, scalar, scalar, scalar, scalar,
        e["arg"](1, e["table_blocks"])).compile()
    assert _fits(compiled)


# The serving cells' widths (benchmark/configs/, benchmark/cells/): the
# engine's decode program has to come out WITH the paged attention kernel
# when its pool lives on a TPU, and without the gathered float32 view.
CELLS = {
    "internlm2-1.8b": (
        {"vocab_size": 92_544, "d_model": 2048, "n_layers": 24,
         "n_heads": 16, "n_kv_heads": 8, "d_ff": 8192, "head_dim": 128,
         "max_seq_len": 32_768, "dtype": "bfloat16"}, 16, 2560),
    "mistral-7b-v0.3-l16": (
        {"vocab_size": 32_768, "d_model": 4096, "n_layers": 16,
         "n_heads": 32, "n_kv_heads": 8, "d_ff": 14_336, "head_dim": 128,
         "max_seq_len": 32_768, "dtype": "bfloat16"}, 6, 6400),
    # A looped stack: 192 planes over 48 layers' weights, kv heads = heads
    # (a page is 256 rows of the kernel's block, twice the others').
    "ouro-2.6b": (
        {"vocab_size": 49_152, "d_model": 2048, "n_layers": 48,
         "n_heads": 16, "n_kv_heads": 16, "d_ff": 5632, "head_dim": 128,
         "max_seq_len": 65_536, "rope_theta": 1e6, "tied_embeddings": False,
         "loop_steps": 4, "sandwich_norm": True, "dtype": "bfloat16"},
        5, 512),
}


@pytest.mark.parametrize("name", sorted(CELLS))
def test_engine_decode_rounds_compiles_with_paged_kernel(chip, name):
    import re

    from kubeflow_tpu.models.generate import decode_rounds
    from kubeflow_tpu.serving.engine import _plain_pool_platform

    widths, slots, max_len = CELLS[name]
    e = _engine_shapes(chip, widths, slots, max_len)
    # What DecodeEngine decides from: the platform of the pool's device.
    assert _plain_pool_platform(e["state"]["cache_k"]) == "tpu"
    compiled = decode_rounds.lower(
        e["cfg"], e["params"], e["state"], e["decode"], 8,
        e["arg"](slots, e["table_blocks"]), e["arg"](),
        paged_kernel=True).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "paged_decode_attention" in text
    # No float32 array of a slot's whole view, repeated over the group
    # or not, and no float32 copy of a layer's pool.
    view = slots * max_len * widths["n_kv_heads"] * widths["head_dim"]
    sizes = [int(np.prod([int(n) for n in dims.split(",")]))
             for dims in re.findall(r"f32\[([\d,]+)\]", text)]
    assert max(sizes) < view, max(sizes)
    assert _fits(compiled)
