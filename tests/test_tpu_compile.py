"""Compile the main path's kernels and the engine's programs for a v5e chip
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

# The 188M LM (chip_smoke.py).
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
    """The 188M engine: 16 slots, 16-token pages, 256-token prompts (one
    chunk each) + 128 new."""
    return _engine_shapes(chip, LM, 16, 256 + 128)


def _fits(compiled, gib=16):
    m = compiled.memory_analysis()
    live = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    return live < gib * 2 ** 30


def _compile_chunk(e, max_len, **static):
    """``prefill_chunk_into_slot`` of the engine ``e`` describes, as wide
    as ``DecodeEngine`` makes it where nobody states a width: its
    constant, clamped to ``prefill_len``; ``static``: what the engine
    would decide for it (``grouped_kernel``)."""
    from kubeflow_tpu.models.generate import prefill_chunk_into_slot
    from kubeflow_tpu.serving.engine import PREFILL_CHUNK_TOKENS

    width = min(PREFILL_CHUNK_TOKENS, max_len - e["decode"].max_new_tokens)
    scalar = e["arg"]()
    return prefill_chunk_into_slot.lower(
        e["cfg"], e["params"], e["state"], e["decode"], e["arg"](1, width),
        scalar, scalar, scalar, scalar, scalar,
        e["arg"](1, e["table_blocks"]), **static).compile()


def _grouped_calls(text):
    """The lines of ops/grouped_matmul.py's custom calls in a program's
    text (what ``DecodeEngine`` hands both programs of a stack whose
    expert matrices are plain bfloat16 arrays on a TPU)."""
    return [line for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line
            and "%grouped_matmul" in line.split(" = ")[0]]


def _holds_the_grouped_kernel(text, layers):
    """Two calls an expert layer, each under ``kft.moe_experts``, and
    nothing left of the compiler's ``ragged-dot``."""
    calls = _grouped_calls(text)
    return (len(calls) == 2 * layers and "ragged-dot" not in text
            and all("kft.moe_experts/" in line for line in calls))


def test_engine_prefill_chunk_compiles(engine_shapes):
    assert _fits(_compile_chunk(engine_shapes, 256 + 128))


# The serving cells' widths (benchmark/configs/, benchmark/cells/): the
# engine's decode program has to come out WITH the paged attention kernel
# when its pool lives on a TPU, and without the gathered float32 view.
# ``(widths, slots, max_len, max_new_tokens)``: ``prefill_len`` is the
# difference of the last two (2048, 6272, 128).
CELLS = {
    "internlm2-1.8b": (
        {"vocab_size": 92_544, "d_model": 2048, "n_layers": 24,
         "n_heads": 16, "n_kv_heads": 8, "d_ff": 8192, "head_dim": 128,
         "max_seq_len": 32_768, "tied_embeddings": False,
         "dtype": "bfloat16"}, 16, 2560, 512),
    "mistral-7b-v0.3-l16": (
        {"vocab_size": 32_768, "d_model": 4096, "n_layers": 16,
         "n_heads": 32, "n_kv_heads": 8, "d_ff": 14_336, "head_dim": 128,
         "max_seq_len": 32_768, "tied_embeddings": False,
         "dtype": "bfloat16"}, 6, 6400, 128),
    # A looped stack: 192 planes over 48 layers' weights, kv heads = heads
    # (a page is 256 rows of the kernel's block, twice the others').
    "ouro-2.6b": (
        {"vocab_size": 49_152, "d_model": 2048, "n_layers": 48,
         "n_heads": 16, "n_kv_heads": 16, "d_ff": 5632, "head_dim": 128,
         "max_seq_len": 65_536, "rope_theta": 1e6, "tied_embeddings": False,
         "loop_steps": 4, "sandwich_norm": True, "dtype": "bfloat16"},
        5, 512, 384),
}


@pytest.fixture(scope="module")
def cell_program(chip):
    """``(engine shapes, compiled program)`` of a cell's engine, each
    program compiled once for the tests below: ``decode_rounds`` (8 steps
    wide, with the paged kernel) or ``prefill_chunk_into_slot`` (as wide
    as the engine makes it for the cell: 256 columns, 128 in
    ``ouro-2.6b``)."""
    import functools

    from kubeflow_tpu.models import generate

    @functools.cache
    def compiled(name, program):
        widths, slots, max_len, new = CELLS[name]
        e = _engine_shapes(chip, widths, slots, max_len, max_new_tokens=new)
        if program == "decode_rounds":
            return e, generate.decode_rounds.lower(
                e["cfg"], e["params"], e["state"], e["decode"], 8,
                e["arg"](slots, e["table_blocks"]), e["arg"](),
                paged_kernel=True).compile()
        return e, _compile_chunk(e, max_len)

    return compiled


@pytest.mark.parametrize("name", sorted(CELLS))
def test_engine_decode_rounds_compiles_with_paged_kernel(cell_program, name):
    import re

    from kubeflow_tpu.serving.engine import _plain_pool_platform

    widths, slots, max_len, _ = CELLS[name]
    e, compiled = cell_program(name, "decode_rounds")
    # What DecodeEngine decides from: the platform of the pool's device.
    assert _plain_pool_platform(e["state"]["cache_k"]) == "tpu"
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "paged_decode_attention" in text
    # No float32 array of a slot's whole view, repeated over the group
    # or not, and no float32 copy of a layer's pool.
    view = slots * max_len * widths["n_kv_heads"] * widths["head_dim"]
    sizes = [int(np.prod([int(n) for n in dims.split(",")]))
             for dims in re.findall(r"f32\[([\d,]+)\]", text)]
    assert max(sizes) < view, max(sizes)
    assert _fits(compiled)


def _pool_moves(text, pool):
    """Instructions of an HLO module that COPY the paged pool: a ``copy``,
    ``copy-start`` / ``copy-done``, ``dynamic-slice`` or
    ``dynamic-update-slice`` (or a fusion the compiler named for one)
    whose result has the shape of one side of the pool or of one plane of
    it.  A scatter into the pool and the loops' own tuples have that shape
    too and move nothing."""
    import re

    whole = ",".join(str(n) for n in pool)
    plane = ",".join(str(n) for n in pool[1:])
    shaped = re.compile(
        rf"%(\S+) = \(?\w+\[(?:{whole}|1,{plane}|{plane})\].*? ([\w-]+)\(")
    moving = ("copy", "dynamic-slice", "dynamic-update-slice")
    return [m.group(1) for m in map(shaped.search, text.splitlines())
            if m and any(w in m.group(1) or m.group(2).startswith(w)
                         for w in moving)]


def test_pool_moves_finds_the_scans_slices_and_copies():
    """The texts are the parent's (PR 28's traces and compiles)."""
    pool = (24, 2560, 16, 8, 128)
    whole, plane = "bf16[24,2560,16,8,128]{4,3,2,1,0}", "[2560,16,8,128]"
    text = f"""
  %copy.68 = {whole} copy(%gte.1)
  %bitcast_dynamic-update-slice_fusion.7 = {whole} fusion(%a, %b), kind=kLoop
  %dynamic-slice_bitcast_fusion.25 = bf16{plane}{{3,2,1,0}} fusion(%a, %i)
  %copy-start.1 = ({whole}, {whole}, u32[]) copy-start(%p)
  %slice.3 = bf16[1,2560,16,8,128]{{4,3,2,1,0}} dynamic-slice(%p, %i, %z)
  ROOT %scatter.26 = {whole} scatter(%p, %i, %u)
  %get-tuple-element.9 = {whole} get-tuple-element(%while.3), index=2
  %copy.14 = bf16[24,2,2048,8,128]{{4,2,3,1,0}} copy(%wkv)
"""
    assert _pool_moves(text, pool) == [
        "copy.68", "bitcast_dynamic-update-slice_fusion.7",
        "dynamic-slice_bitcast_fusion.25", "copy-start.1", "slice.3"]


@pytest.mark.parametrize("program", ["decode_rounds",
                                     "prefill_chunk_into_slot"])
@pytest.mark.parametrize("name", sorted(CELLS))
def test_engine_programs_update_the_pool_in_place(cell_program, name,
                                                  program):
    """The layer scan carries the stacked pool: neither engine program
    slices a plane out, restacks it or copies the pool, and the
    temporaries of a call are smaller than ONE side of the pool (with the
    pool as the scan's xs / ys they were larger than both: 5.2-5.8 GB)."""
    e, compiled = cell_program(name, program)
    pool = e["state"]["cache_k"]
    assert _pool_moves(compiled.as_text(), pool.shape) == []
    m = compiled.memory_analysis()
    side = int(np.prod(pool.shape)) * pool.dtype.itemsize
    assert m.temp_size_in_bytes < side, (m.temp_size_in_bytes, side)
    # Donated and aliased: the pool that comes in is the pool that goes out.
    assert m.alias_size_in_bytes >= 2 * side
    assert _fits(compiled)


def _fusions_given_up(text):
    """Fusions of a compiled module for which the chip's compiler found no
    cost (``estimated_cycles`` at the largest int64) after retrying: the
    fallback it then emits ran 90 times slower than the same fusion a
    width below (the softmax's reduction over ``f32[32,256,6400]`` of a
    256-wide chunk, 14 ms a layer; PERF.md section 6, PR 36)."""
    import re

    return re.findall(
        r"%(\S+) = [^\n]*\"estimated_cycles\":\"9223372036854775807\"", text)


def test_fusions_given_up_finds_the_untiled_softmax():
    """The line is the 256-wide chunk's, compiled without query tiles
    (PR 36, operands and the window's bounds shortened)."""
    scores = "f32[32,256,6400]{2,1,0:T(8,128)}"
    text = f"""
  %fusion.180 = {scores} fusion(%copy.44, %copy.43), kind=kOutput, \
backend_config={{"estimated_cycles":"120934","retry_count":"0"}}
  %fusion.181 = (f32[32,256]{{1,0:T(8,128)S(1)}}, {scores}) \
fusion(%fusion.180), kind=kOutput, backend_config={{"window_config":\
{{"estimated_cycles":"9223372036854775807"}},"retry_count":"6"}}
"""
    assert _fusions_given_up(text) == ["fusion.181"]


def _program_of(request, name, program):
    """``(engine shapes, compiled program)`` of any of the five cells'
    configurations, from the fixture that compiles it."""
    if name in CELLS:
        return request.getfixturevalue("cell_program")(name, program)
    return request.getfixturevalue(
        {"lfm2-24b-a2b-l10": "lfm2_program",
         "longcat-flash-omni-l4": "longcat_program"}[name])(program)


FIVE = sorted(CELLS) + ["lfm2-24b-a2b-l10", "longcat-flash-omni-l4"]


@pytest.mark.parametrize("program", ["decode_rounds",
                                     "prefill_chunk_into_slot"])
@pytest.mark.parametrize("name", FIVE)
def test_engine_programs_hold_no_fusion_the_compiler_gave_up_on(
        request, name, program):
    _, compiled = _program_of(request, name, program)
    assert _fusions_given_up(compiled.as_text()) == []


# The chunk programs whose table is long enough to be visited by key
# tiles (generate.view_key_tiles): (query heads, table positions, key
# tile).  Reason's 512 and workers' 704 positions run one pass.
TILED = {"internlm2-1.8b": (16, 2560, 320),
         "mistral-7b-v0.3-l16": (32, 6400, 512),
         "longcat-flash-omni-l4": (64, 6624, 512)}


@pytest.mark.parametrize("name", FIVE)
def test_chunk_programs_score_a_key_tile_at_a_time(request, name):
    """A chunk's attention loops over key tiles up to the slot's held
    length (PR 38): the program holds no float32 array as long as the
    table in any axis and none as large as ONE query tile's scores over
    the whole view (``[h, 64, view]`` / ``[64 x h, view]``, which the
    one pass held four times a layer); the two short tables keep the
    one pass."""
    import re

    from kubeflow_tpu.models import generate
    from kubeflow_tpu.serving.engine import PREFILL_CHUNK_TOKENS

    e, compiled = _program_of(request, name, "prefill_chunk_into_slot")
    text = compiled.as_text()
    view = e["table_blocks"] * 16
    width = min(PREFILL_CHUNK_TOKENS, {"ouro-2.6b": 128}.get(name, 256))
    tile, tiles = generate.view_key_tiles(e["table_blocks"], 16, width)
    shapes = [[int(n) for n in dims.split(",")]
              for dims in re.findall(r"f32\[([\d,]+)\]", text)]
    if name not in TILED:
        assert (tile, tiles) == (view, 1)
        assert any(view in dims for dims in shapes)
        return
    heads, positions, key_tile = TILED[name]
    assert (view, tile) == (positions, key_tile)
    assert tiles == -(-view // tile) > 2
    assert not [dims for dims in shapes if view in dims]
    assert max(int(np.prod(dims)) for dims in shapes) < heads * 64 * view
    assert any(tile in dims for dims in shapes)


def _layer_pair_shapes(widths):
    """One layer's ``mlp/wi`` and ``attn/wkv``, whole and one of the pair."""
    e, f = widths["d_model"], widths["d_ff"]
    hkv, d = widths["n_kv_heads"], widths["head_dim"]
    return [(2, e, f), (e, f), (2, e, hkv, d), (e, hkv, d)]


def _weight_moves(text, shapes):
    """Instructions of an HLO module, outside its fused computations,
    whose result has one of ``shapes`` (in any layout and memory space):
    an array of a layer's weight that the program PRODUCES, in HBM or in
    the chip's fast memory (``S(1)``), before a matmul reads it.  The
    loops' own tuple elements, parameters and bitcasts move nothing."""
    import re

    dims = "|".join(",".join(str(n) for n in s) for s in shapes)
    shaped = re.compile(
        rf"%(\S+) = \(?\w+\[(?:{dims})\](?:\{{[^}}]*\}})? ([\w-]+)\(")
    found, fused = [], False
    for line in text.splitlines():
        if line.startswith("%fused_computation"):
            fused = True
        elif line.startswith("}"):
            fused = False
        elif not fused:
            m = shaped.search(line)
            if m and m.group(2) not in ("get-tuple-element", "parameter",
                                        "bitcast"):
                found.append(m.group(1))
    return found


def test_weight_moves_finds_the_scans_slices_of_a_pair():
    """The texts are the parent's (PR 30's programs, compiled for the
    described v5e; operands shortened): the slice of Mistral's ``mlp/wi``
    went to HBM, those of InternLM2's and of every ``attn/wkv`` to the fast
    memory; a matmul's ``[slots, f]`` result, the relayout of a whole
    stack and what a fusion holds inside are not such moves."""
    tile = "T(8,128)(2,1)"
    hbm, fast = f"{{2,1,0:{tile}}}", f"{{2,1,0:{tile}S(1)}}"
    text = f"""
%fused_computation.4.clone (p0: bf16[16,2,4096,14336]) -> bf16[2,4096,14336] {{
  %dynamic_slice.88 = bf16[1,2,4096,14336]{{3,2,1,0:{tile}}} dynamic-slice(%p0, %p1)
  ROOT %bitcast.178 = bf16[2,4096,14336]{hbm} bitcast(%dynamic_slice.88)
}}

%wide.region_3.28.clone (arg: (s32[], bf16[6,1,4096])) -> (s32[], bf16[6,1,4096]) {{
  %get-tuple-element.9 = bf16[2,4096,14336]{hbm} get-tuple-element(%w), index=2
  %dynamic-slice_bitcast_fusion.18 = bf16[2,4096,8,128]{{3,1,2,0:{tile}S(1)}} fusion(
  %fusion.138 = bf16[6,14336]{{1,0:{tile}S(1)}} fusion(%get-tuple-element.1392, %i)
  %dynamic-slice_bitcast_fusion.19 = bf16[2,4096,14336]{hbm} fusion(%gte.1392, %i)
  %fusion.137 = bf16[6,14336]{{1,0:{tile}S(1)}} fusion(%dynamic-slice_bitcast_fusion.19)
  %copy.16 = bf16[16,2,4096,8,128]{{4,2,3,1,0:{tile}}} copy(%wkv)
  %slice_bitcast_fusion.9 = bf16[4096,14336]{{1,0:{tile}}} fusion(%fusion.19)
}}
"""
    assert _weight_moves(text, _layer_pair_shapes(
        CELLS["mistral-7b-v0.3-l16"][0])) == [
        "dynamic-slice_bitcast_fusion.18", "dynamic-slice_bitcast_fusion.19",
        "slice_bitcast_fusion.9"]
    text = f"""
  %dynamic-slice_bitcast_fusion.19 = bf16[2,2048,8192]{fast} fusion(%gte.1410, %i)
  %fusion.136 = bf16[16,8192]{{1,0:{tile}S(1)}} fusion(%fusion.19, %bitcast.9)
"""
    assert _weight_moves(text, _layer_pair_shapes(
        CELLS["internlm2-1.8b"][0])) == ["dynamic-slice_bitcast_fusion.19"]


@pytest.mark.parametrize("program", ["decode_rounds",
                                     "prefill_chunk_into_slot"])
@pytest.mark.parametrize("name", sorted(CELLS))
def test_engine_programs_read_stacked_weights_in_place(cell_program, name,
                                                       program):
    """A stacked leaf that holds a PAIR a layer (``mlp/wi``: gate, up;
    ``attn/wkv``: keys, values) reaches its matmuls as their own operand:
    neither engine program produces an array of the shape of one layer's
    pair or of one matrix of it.  Sliced out as ``w[layer]`` first, the
    pair was copied once a layer: Mistral's 235 MB to HBM and back, the
    smaller ones into ``S(1)`` (``PERF.md`` section 6, PR 31)."""
    widths = CELLS[name][0]
    _, compiled = cell_program(name, program)
    assert _weight_moves(compiled.as_text(),
                         _layer_pair_shapes(widths)) == []
    if (name, program) == ("mistral-7b-v0.3-l16", "decode_rounds"):
        # What stays is the relayout of the stacked wq / wkv once a call
        # (0.537 + 0.268 GB); the parent's 1.041 GB held the slice too.
        assert compiled.memory_analysis().temp_size_in_bytes < 0.85e9


@pytest.mark.parametrize("program", ["decode_rounds",
                                     "prefill_chunk_into_slot"])
@pytest.mark.parametrize("name", sorted(CELLS))
def test_dense_programs_hold_no_grouped_product(cell_program, name, program):
    """The three configurations without ``layer_types`` never trace
    ``_experts``: neither ops/grouped_matmul.py's call nor the compiler's
    ``ragged-dot`` is in their programs, whatever an engine decides for
    the stacks that have experts."""
    _, compiled = cell_program(name, program)
    text = compiled.as_text()
    assert _grouped_calls(text) == []
    assert "grouped_matmul" not in text and "ragged-dot" not in text
    assert "kft.moe_experts" not in text


# A stack with layer_types at the cell's sizes
# (benchmark/configs/lfm2-24b-a2b-l10.json, cells/lfm2-24b-a2b-l10.workers):
# 8 convolution layers with a per-slot state, 2 attention layers that own
# the pool's 2 planes (heads of 64: two kv heads a 128-lane row), 2 dense
# feed-forwards and 8 x 64 experts walked layer by layer.
LFM2 = ({"vocab_size": 65_536, "d_model": 2048, "n_layers": 10,
         "n_heads": 32, "n_kv_heads": 8, "d_ff": 11_776, "head_dim": 64,
         "max_seq_len": 128_000, "rope_theta": 1e6, "tied_embeddings": True,
         "norm_eps": 1e-5, "qk_norm": True, "conv_kernel": 3,
         "layer_types": ["conv", "conv", "full_attention", "conv", "conv",
                         "conv", "full_attention", "conv", "conv", "conv"],
         "moe_experts": 64, "moe_top_k": 4, "moe_dense_layers": 2,
         "moe_d_ff": 1536, "dtype": "bfloat16"}, 16, 704, 384)


@pytest.fixture(scope="module")
def lfm2_program(chip):
    import functools

    from kubeflow_tpu.models import generate

    widths, slots, max_len, new = LFM2
    e = _engine_shapes(chip, widths, slots, max_len, max_new_tokens=new)

    @functools.cache
    def compiled(program):
        if program == "decode_rounds":
            return e, generate.decode_rounds.lower(
                e["cfg"], e["params"], e["state"], e["decode"], 8,
                e["arg"](slots, e["table_blocks"]), e["arg"](),
                paged_kernel=True, grouped_kernel=True).compile()
        return e, _compile_chunk(e, max_len, grouped_kernel=True)

    return compiled


@pytest.mark.parametrize("program", ["decode_rounds",
                                     "prefill_chunk_into_slot"])
def test_layer_types_programs_hold_both_states_and_the_experts_in_place(
        lfm2_program, program):
    """Both engine programs of the LFM2 cut: the pool AND the convolution
    state come in donated and go out aliased, no array of a layer's
    experts (1.2 GB) is produced outside a fusion, the grouped products
    are ops/grouped_matmul.py's calls under ``kft.moe_experts`` (two an
    expert layer), and a call's temporaries stay under 0.5 GB
    beside 10.53 GB of weights.  (``_pool_moves`` is not held to nothing
    here: a side of this pool is 23 MB, and the compiler parks it in the
    chip's fast memory between a step's scatters and brings it back for
    the kernel: 5 % of the programs' time on the chip, PERF.md section 5.)"""
    e, compiled = lfm2_program(program)
    text = compiled.as_text()
    pool, conv = e["state"]["cache_k"], e["state"]["conv"]
    assert pool.shape == (2, 16 * 44, 16, 8, 64)
    assert conv.shape == (8, 16, 2, 2048)
    assert _weight_moves(text, [(64, 2048, 3072), (64, 1536, 2048),
                                (2048, 3072), (1536, 2048),
                                (2, 2048, 11_776)]) == []
    assert _holds_the_grouped_kernel(text, 8)
    assert _fusions_given_up(text) == []
    m = compiled.memory_analysis()
    held = 2 * int(np.prod(pool.shape)) * 2 + int(np.prod(conv.shape)) * 2
    assert m.alias_size_in_bytes >= held
    assert m.temp_size_in_bytes < 0.5e9, m.temp_size_in_bytes
    assert 10.53e9 < m.argument_size_in_bytes < 10.7e9
    assert _fits(compiled, gib=11.65)  # 12.5 GB


def test_layer_types_decode_rounds_keeps_the_paged_kernel(lfm2_program):
    """Heads of 64 lanes: the decode program still attends through
    ``ops/paged_attention.py`` (two kv heads a row), once a plane."""
    from kubeflow_tpu.ops.paged_attention import supports

    assert supports(64, 8)
    _, compiled = lfm2_program("decode_rounds")
    assert "paged_decode_attention" in compiled.as_text()
    _, chunk = lfm2_program("prefill_chunk_into_slot")
    assert "paged_decode_attention" not in chunk.as_text()


# LongCat-Flash's language model at the cell's sizes
# (benchmark/configs/longcat-flash-omni-l4.json,
# cells/longcat-flash-omni-l4.agents): 4 double layers, each two latent
# attention sublayers (8 planes of ONE latent pool, a row of 512 + 64
# values padded to 640 lanes), two dense SwiGLUs of 12,288 and 16 of 512
# routed experts beside 256 zero-compute ones, walked layer by layer; 64
# slots of 6,240 + 384 positions.
LONGCAT = ({"vocab_size": 16_384, "d_model": 6144, "n_layers": 4,
            "n_heads": 64, "n_kv_heads": 64, "d_ff": 12_288,
            "max_seq_len": 131_072, "rope_theta": 1e7,
            "tied_embeddings": False, "norm_eps": 1e-5,
            "layer_types": ["shortcut_double"] * 4,
            "attention_kind": "latent", "mla_q_rank": 1536,
            "mla_kv_rank": 512, "mla_nope_dim": 128, "mla_rope_dim": 64,
            "mla_v_dim": 128, "moe_experts": 512, "moe_experts_held": 16,
            "moe_zero_experts": 256, "moe_top_k": 12, "moe_d_ff": 2048,
            "moe_score": "softmax", "moe_normalize": False,
            "moe_scale": 6.0, "dtype": "bfloat16"}, 64, 6624, 384)


@pytest.fixture(scope="module")
def longcat_program(chip):
    import functools

    from kubeflow_tpu.models import generate

    widths, slots, max_len, new = LONGCAT
    e = _engine_shapes(chip, widths, slots, max_len, max_new_tokens=new)

    @functools.cache
    def compiled(program):
        if program == "decode_rounds":
            return e, generate.decode_rounds.lower(
                e["cfg"], e["params"], e["state"], e["decode"], 8,
                e["arg"](slots, e["table_blocks"]), e["arg"](),
                paged_kernel=True, grouped_kernel=True).compile()
        return e, _compile_chunk(e, max_len, grouped_kernel=True)

    return compiled


@pytest.mark.parametrize("program", ["decode_rounds",
                                     "prefill_chunk_into_slot"])
def test_latent_programs_hold_the_pool_and_the_weights_in_place(
        longcat_program, program):
    """Both engine programs of the LongCat-Flash cut at the cell's sizes: the
    ONE latent pool comes in donated and goes out aliased with no copy,
    slice or restack of it or of a plane (PR 29's guard); no array of a
    double layer's experts, of one expert's matrix or of a dense SwiGLU's
    pair is produced outside a fusion (PR 31's), and what the program does
    produce of the shape of an attention matrix is the compiler's own
    sliced prefetch into the fast memory (``ConcatBitcast`` of
    ``slice-done``s, ``copy-done`` of the cross-program prefetch) and one
    relayout a sublayer and CALL, named below; the grouped products are
    ops/grouped_matmul.py's calls, two a layer; no fusion the compiler
    gave up on (PR 36's); and the whole fits
    under 15.2 GB, which is where ISSUE 37's fallback to 48 slots would
    have been taken (it is not: 14.81 and 14.97 GB)."""
    e, compiled = longcat_program(program)
    text = compiled.as_text()
    pool = e["state"]["cache_latent"]
    assert pool.shape == (8, 64 * 414, 16, 640)
    assert "cache_k" not in e["state"]
    assert _pool_moves(text, pool.shape) == []
    assert _weight_moves(text, [
        (16, 6144, 4096), (16, 2048, 6144), (6144, 4096), (2048, 6144),
        (2, 6144, 12_288), (6144, 12_288), (12_288, 6144)]) == []
    prefetched = _weight_moves(text, [
        (6144, 1536), (1536, 64, 192), (6144, 576), (64, 128, 512),
        (512, 64, 128), (64, 128, 6144), (6144, 768)])
    relaid = [name for name in prefetched
              if not name.startswith(("custom-call", "copy-done"))]
    # What stays: decode_rounds relays each sublayer's ``wv_b`` (8.4 MB,
    # head-major for the product that leaves the latent space) ONCE A
    # CALL, before its loop of steps; the chunk program nothing.
    assert len(relaid) == (8 if program == "decode_rounds" else 0), relaid
    assert all(name.startswith("copy.") for name in relaid)
    assert _holds_the_grouped_kernel(text, 4)
    assert _fusions_given_up(text) == []
    m = compiled.memory_analysis()
    side = int(np.prod(pool.shape)) * 2
    assert side == 4_341_104_640
    assert m.alias_size_in_bytes >= side
    # The chunk's 0.249 GB fell to 0.148 with the key-tile loop (PR 38:
    # no float32 scores of a query tile over the whole view).
    assert m.temp_size_in_bytes < (0.2e9 if program.startswith("prefill")
                                   else 0.15e9), m.temp_size_in_bytes
    # Weights 10.38 GB (the routers in float32) beside the pool.
    assert 14.70e9 < m.argument_size_in_bytes < 14.75e9
    live = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert live < 15.2e9, live


def test_latent_decode_rounds_attends_through_the_latent_kernel(
        longcat_program):
    """The decode program reads the latent pages in place, once a plane
    (8 kernel calls a step); the chunk program attends the slot's
    gathered view and holds no kernel."""
    _, compiled = longcat_program("decode_rounds")
    text = compiled.as_text()
    assert text.count(
        "paged_latent_decode_attention/pallas_call\" ") >= 1
    assert len([line for line in text.splitlines()
                if "custom_call_target=\"tpu_custom_call\"" in line
                and "%paged_latent_decode_attention" in
                line.split(" = ")[0]]) == 8
    assert "paged_decode_attention" not in text.replace(
        "paged_latent_decode_attention", "")
    _, chunk = longcat_program("prefill_chunk_into_slot")
    assert "paged_latent_decode_attention" not in chunk.as_text()


# ``decode_rounds``'s live bytes (arguments + outputs + temporaries -
# aliased) at the five cells' sizes before PR 41 changed the kernel's page
# walk.  What the walk added rides in VMEM and SMEM scratch, and its tables
# are clamped inside the kernel: the compiled peak does not rise with it.
_DECODE_PEAK_BEFORE_THE_WALK = {
    "internlm2-1.8b": 8_208_373_760,
    "mistral-7b-v0.3-l16": 10_839_322_624,
    "ouro-2.6b": 10_571_918_336,
    "lfm2-24b-a2b-l10": 10_693_747_200,
    "longcat-flash-omni-l4": 14_813_549_056,
}


@pytest.mark.parametrize("name", sorted(_DECODE_PEAK_BEFORE_THE_WALK))
def test_decode_rounds_peak_is_no_higher_than_before_the_walk(
        request, name):
    """Both forms of the kernel, in every cell's decode program: the
    kernel by its name in the text (the benchmark's readers find it so),
    the pool aliased, the peak where it was."""
    if name in CELLS:
        e, compiled = request.getfixturevalue("cell_program")(
            name, "decode_rounds")
    else:
        e, compiled = request.getfixturevalue(
            "lfm2_program" if name.startswith("lfm2")
            else "longcat_program")("decode_rounds")
    latent = "cache_latent" in e["state"]
    kernel = "paged_latent_decode_attention" if latent \
        else "paged_decode_attention"
    assert f"%{kernel}" in compiled.as_text()
    m = compiled.memory_analysis()
    sides = [e["state"][k] for k in (
        ["cache_latent"] if latent else ["cache_k", "cache_v"])]
    assert m.alias_size_in_bytes >= sum(
        int(np.prod(p.shape)) * p.dtype.itemsize for p in sides)
    live = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert live <= _DECODE_PEAK_BEFORE_THE_WALK[name], live


# dots3-note-prev's language model at the cell's sizes
# (benchmark/configs/dots3-note-prev-l5.json,
# cells/dots3-note-prev-l5.longctx): layers 0-4 (full, full, sliding x 3),
# an indexer of 64 heads of 128 choosing 2,048 positions in the full
# layers, window layers of 64 heads over rows of 1,088 values, 32 of 256
# routed experts beside one shared; THREE pools on one table, 16 slots of
# 32,864 + 384 positions.
DOTS3 = ({"vocab_size": 19_008, "d_model": 5120, "n_layers": 5,
          "n_heads": 128, "n_kv_heads": 128, "d_ff": 13_824,
          "max_seq_len": 524_288, "rope_theta": 8e7,
          "tied_embeddings": False, "norm_eps": 1e-5,
          "layer_types": ["full_attention"] * 2
          + ["sliding_attention"] * 3,
          "attention_kind": "latent", "mla_q_rank": 1024,
          "mla_kv_rank": 512, "mla_nope_dim": 128, "mla_rope_dim": 64,
          "mla_v_dim": 128, "index_heads": 64, "index_dim": 128,
          "index_topk": 2048, "window": 513, "window_heads": 64,
          "window_q_rank": 1024, "window_kv_rank": 1024,
          "window_nope_dim": 192, "window_rope_dim": 64,
          "window_v_dim": 128, "window_rope_theta": 5e4,
          "attn_gate": True, "moe_experts": 256, "moe_experts_held": 32,
          "moe_top_k": 8, "moe_d_ff": 1536, "moe_dense_layers": 1,
          "moe_shared_d_ff": 1536, "dtype": "bfloat16"}, 16, 33_248, 384)


@pytest.fixture(scope="module")
def dots3_program(chip):
    import functools

    from kubeflow_tpu.models import generate

    widths, slots, max_len, new = DOTS3
    e = _engine_shapes(chip, widths, slots, max_len, max_new_tokens=new)

    @functools.cache
    def compiled(program):
        if program == "decode_rounds":
            return e, generate.decode_rounds.lower(
                e["cfg"], e["params"], e["state"], e["decode"], 8,
                e["arg"](slots, e["table_blocks"]), e["arg"](),
                paged_kernel=True, grouped_kernel=True).compile()
        return e, _compile_chunk(e, max_len, grouped_kernel=True)

    return compiled


@pytest.mark.parametrize("program", ["decode_rounds",
                                     "prefill_chunk_into_slot"])
def test_three_pools_come_in_donated_and_go_out_aliased(dots3_program,
                                                        program):
    """Both engine programs of the dots3-note-prev cut at the cell's sizes:
    the three pools (full planes' latent rows, their index keys, window
    planes' rows) come in donated and go out aliased with no copy, slice
    or restack of one or of a plane; no array of a layer's experts is
    produced outside a fusion; the grouped products are the chip's own
    kernel, once a sparse layer; and the whole fits the chip."""
    e, compiled = dots3_program(program)
    text = compiled.as_text()
    blocks = 16 * 2078
    shapes = {"cache_latent": (2, blocks, 16, 640),
              "cache_index": (2, blocks, 16, 128),
              "cache_window": (3, blocks, 16, 1152)}
    assert {k: e["state"][k].shape for k in shapes} == shapes
    for shape in shapes.values():
        assert _pool_moves(text, shape) == []
    assert _weight_moves(text, [
        (32, 5120, 3072), (32, 1536, 5120), (5120, 3072),
        (2, 5120, 13_824), (5120, 13_824), (13_824, 5120)]) == []
    # The shared expert's matrices are leaves of their own (its down
    # matrix has the shape of a routed expert's): what is produced of
    # their shapes is the compiler's prefetch of a leaf into the fast
    # memory.
    assert all(name.startswith(("copy-done", "custom-call"))
               for name in _weight_moves(text, [
                   (1536, 5120), (2, 5120, 1536), (5120, 1536)]))
    assert _holds_the_grouped_kernel(text, 4)
    m = compiled.memory_analysis()
    print(program, "temp", m.temp_size_in_bytes, "args",
          m.argument_size_in_bytes)
    pools = sum(int(np.prod(s)) * 2 for s in shapes.values())
    assert pools == 16 * 33_248 * 9_984 == 5_311_168_512
    assert m.alias_size_in_bytes >= pools
    # Weights 8.18 GB (routers and index weights in float32) beside them.
    assert 13.45e9 < m.argument_size_in_bytes < 13.55e9, \
        m.argument_size_in_bytes
    live = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert live < 15.5e9, (live, m.temp_size_in_bytes)


def test_window_planes_decode_through_the_kernel_from_the_windows_first_page(
        dots3_program):
    """The decode program reads a window plane's pages in place through the
    latent kernel's window form (3 calls a step); a full plane's chosen
    rows come through the compiler's gather, and no program gathers a
    slot's whole view of a latent pool."""
    e, compiled = dots3_program("decode_rounds")
    text = compiled.as_text()
    assert len([line for line in text.splitlines()
                if "custom_call_target=\"tpu_custom_call\"" in line
                and "%paged_latent_decode_attention" in
                line.split(" = ")[0]]) == 3
    view = 2078 * 16
    for program in ("decode_rounds", "prefill_chunk_into_slot"):
        text = dots3_program(program)[1].as_text()
        for rows, width in ((view, 640), (view, 1152)):
            assert f"bf16[16,{rows},{width}]" not in text
            assert f"bf16[1,{rows},{width}]" not in text


def test_full_planes_score_their_index_keys_through_the_walk_kernel(
        dots3_program):
    """The decode program scores a full plane's index keys page by page in
    place: ``paged_index_scores`` under ``kft.dsa_index``, once a full
    plane, and no gather of a key tile of 2,048 positions (128 pages) for
    the call's 16 rows; the chunk program (256 query columns a tile,
    compute-bound) keeps the key tiles and holds no such kernel."""
    _, compiled = dots3_program("decode_rounds")
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if "custom_call_target=\"tpu_custom_call\"" in line
             and "%paged_index_scores" in line.split(" = ")[0]]
    assert len(calls) == 2
    assert all("/kft.dsa_index/" in line.split("op_name=\"")[1].split(
        "\"")[0] for line in calls)
    # The stacked index pool, viewed a page a row, left where it is.
    assert all("bf16[66496,16,128]" in line for line in calls)
    for tile in ("bf16[2048,16,128]", "bf16[16,2048,128]",
                 "bf16[16,128,16,128]", "f32[16,1,64,2048]",
                 "f32[16,64,2048]"):
        assert tile not in text, tile
    # Its 16,384 (query, head) rows a call make a key tile 512 positions.
    chunk = dots3_program("prefill_chunk_into_slot")[1].as_text()
    assert "paged_index_scores" not in chunk
    assert "bf16[32,16,128]" in chunk and "f32[1,256,64,512]" in chunk


def _folded_table_entries(chip, entries, n):
    """``entries(tables, first, n)`` over a table and positions that are
    CONSTANTS of the program, compiled for the chip: (the s32 constants of
    the result's size that the compiler folded it to, the right entries)."""
    import re

    from jax._src.lib import xla_client

    slots, table_blocks = 4, 2078
    rng = np.random.default_rng(0)
    tables = rng.permutation(slots * table_blocks).reshape(
        slots, table_blocks).astype(np.int32)
    first = np.asarray([1798, 1467, 1272, 898], np.int32)
    compiled = jax.jit(lambda x: entries(
        jnp.asarray(tables), jnp.asarray(first), n) + x).lower(
            jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)).compile()
    options = xla_client._xla.HloPrintOptions()
    options.print_large_constants = True
    text = compiled.runtime_executable().hlo_modules()[0].to_string(options)
    folded = [np.asarray(re.findall(r"-?\d+", values), np.int64)
              for values in re.findall(
                  r"= s32\[[0-9,]*\]\S* constant\(\{([^\n]*)\}\)", text)]
    want = np.stack([tables[i, p:p + n] for i, p in enumerate(first)])
    return [c for c in folded if c.size == want.size], want


def test_a_windows_pages_of_a_constant_table_are_the_right_pages(chip):
    """PR 44's fault, found in a scratch script and not in the engine: a
    slot's window pages taken as ``jax.vmap`` of ``dynamic_slice`` over a
    table that is a constant of the program fold, in this compiler, to the
    slice's first entry and zeros, and the window then reads page 0 for
    every page but one (0.37-0.41 off at an output scale of 0.25 on the
    chip).  ``generate._table_entries`` is one gather of single entries,
    which the compiler leaves to run time (or folds right).  The first
    half holds the program's form; the second says when the other form
    may come back."""
    from kubeflow_tpu.models import generate

    folded, want = _folded_table_entries(chip, generate._table_entries, 33)
    assert all((c == want.reshape(-1)).all() for c in folded)
    folded, want = _folded_table_entries(
        chip, lambda tables, first, n: jax.vmap(
            lambda row, p: jax.lax.dynamic_slice_in_dim(row, p, n))(
                tables, first), 33)
    if all((c == want.reshape(-1)).all() for c in folded):
        return  # this compiler folds the sliced form right
    wrong, = folded
    assert (wrong.reshape(4, 33)[:, 0] == want[:, 0]).all()
    assert (wrong.reshape(4, 33)[:, 1:] == 0).all()


# dots.vlm1.inst's language model with its multi-token-prediction module
# drafting, at the cell's sizes (benchmark/configs/dots.vlm1.inst-l5.json,
# cells/dots.vlm1.inst-l5.longform): a dense layer and four expert layers
# of 16 of 256 experts in 8 groups, the module's layer with 16 more, 128
# heads, YaRN; 6 planes of ONE latent pool (the draft layer's the last);
# 32 slots of 8,288 + 768 positions and the draft plane's one more.
DOTSVLM = ({"vocab_size": 16_160, "d_model": 7168, "n_layers": 5,
            "n_heads": 128, "n_kv_heads": 128, "d_ff": 18_432,
            "max_seq_len": 163_840, "rope_theta": 10_000.0,
            "tied_embeddings": False, "norm_eps": 1e-6,
            "layer_types": ["full_attention"] * 5,
            "attention_kind": "latent", "mla_q_rank": 1536,
            "mla_kv_rank": 512, "mla_nope_dim": 128, "mla_rope_dim": 64,
            "mla_v_dim": 128, "mla_rescale": False, "yarn_factor": 40.0,
            "yarn_original_len": 4096, "yarn_beta_fast": 32.0,
            "yarn_beta_slow": 1.0, "mla_softmax_mult": 1.8738526,
            "moe_experts": 256, "moe_experts_held": 16, "moe_top_k": 8,
            "moe_d_ff": 2048, "moe_dense_layers": 1, "moe_scale": 2.5,
            "moe_groups": 8, "moe_groups_kept": 4, "moe_norm_eps": 1e-20,
            "moe_shared_d_ff": 2048, "mtp_layers": 1,
            "dtype": "bfloat16"}, 32, 8288 + 768 + 1, 768)


@pytest.fixture(scope="module")
def dotsvlm_program(chip):
    import functools

    from kubeflow_tpu.models import generate

    widths, slots, max_len, new = DOTSVLM
    e = _engine_shapes(chip, widths, slots, max_len, max_new_tokens=new)

    @functools.cache
    def compiled(program):
        if program == "decode_rounds":
            return e, generate.decode_rounds.lower(
                e["cfg"], e["params"], e["state"], e["decode"], 8,
                e["arg"](slots, e["table_blocks"]), e["arg"](),
                paged_kernel=True, grouped_kernel=True).compile()
        scalar = e["arg"]()
        return e, generate.prefill_chunk_into_slot.lower(
            e["cfg"], e["params"], e["state"], e["decode"],
            e["arg"](1, 256), scalar, scalar, scalar, scalar, scalar,
            e["arg"](1, e["table_blocks"]), None, scalar,
            grouped_kernel=True).compile()

    return compiled


@pytest.mark.parametrize("program", ["decode_rounds",
                                     "prefill_chunk_into_slot"])
def test_drafting_programs_hold_six_planes_in_place_under_the_chips_memory(
        dotsvlm_program, program):
    """Both engine programs of the drafting stack at the cell's sizes:
    5,606,143,232 parameters (ISSUE 47's count), the six-plane pool
    donated and aliased with nothing of its shape copied (the chunk's ONE
    row at the prompt's length is a dynamic-update-slice in place, named
    below), the position a chunk recomputes after a hit under a
    conditional that writes no plane, no fusion the compiler gave up on,
    and 32 slots fit: 14.04 / 13.96 GB live."""
    e, compiled = dotsvlm_program(program)
    text = compiled.as_text()
    pool = e["state"]["cache_latent"]
    assert pool.shape == (6, 32 * 567, 16, 640)
    assert sum(int(np.prod(a.shape)) for a in
               jax.tree_util.tree_leaves(e["params"])) == 5_606_143_232
    moves = _pool_moves(text, pool.shape)
    lines = {name: line for line in text.splitlines()
             for name in moves if f"%{name} = " in line}
    assert all("kft.mtp_fill/kft.mla_latent_write/scatter" in line
               and "dynamic-update-slice(" in line
               for line in lines.values()), moves
    assert len(moves) == (0 if program == "decode_rounds" else 1)
    assert _fusions_given_up(text) == []
    # (An expert's [2048, 7168] is also the shape of a chunk's sorted
    # rows and of the shared expert's prefetch: not asked here.)
    assert _weight_moves(text, [
        (16, 7168, 4096), (16, 2048, 7168), (7168, 4096),
        (2, 7168, 18_432), (7168, 18_432), (18_432, 7168),
        (14_336, 7168)]) == []
    m = compiled.memory_analysis()
    side = int(np.prod(pool.shape)) * 2
    assert side == 2_229_534_720 and m.alias_size_in_bytes >= side
    assert m.temp_size_in_bytes < 0.65e9, m.temp_size_in_bytes
    live = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert live < 14.3e9, live


def test_drafting_decode_rounds_reads_each_plane_once_for_both_rows(
        dotsvlm_program):
    """A step's two rows a slot go through the latent kernel as rows of
    ONE call a plane (256 query rows of 640 lanes a slot): six calls a
    step, the draft plane's among them; the chunk holds no such kernel;
    the grouped products are ops/grouped_matmul.py's calls, two an expert
    layer (the module's among the five)."""
    _, compiled = dotsvlm_program("decode_rounds")
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if "custom_call_target=\"tpu_custom_call\"" in line
             and "%paged_latent_decode_attention" in line.split(" = ")[0]]
    assert len(calls) == 6
    assert all("bf16[32,256,640]" in line for line in calls)
    assert _holds_the_grouped_kernel(text, 5)
    for scope in ("kft.mtp_draft", "kft.mtp_accept", "kft.moe_groups"):
        assert scope in text
    _, chunk = dotsvlm_program("prefill_chunk_into_slot")
    assert "paged_latent_decode_attention" not in chunk.as_text()
    assert "kft.mtp_fill" in chunk.as_text()
    # The chunk: four expert layers, the same four for the position it
    # recomputes after a hit, and the module's over the row that arms
    # the first draft (8 sorted rows).  The module's pass over the
    # chunk's rows only fills its plane: its experts feed nothing and the
    # compiler drops them.
    assert _holds_the_grouped_kernel(chunk.as_text(), 9)
    assert sum("bf16[8,4096]" in line
               for line in _grouped_calls(chunk.as_text())) == 1 + 4
