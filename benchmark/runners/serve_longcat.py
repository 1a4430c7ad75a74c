"""Runner for cells that serve LongCat-Flash's language model (latent
attention over one paged pool of latent rows, double layers with a
shortcut-connected expert layer, softmax-routed experts of which this chip
holds a share beside zero-compute experts:
``configs/longcat-flash-omni-l4.json``) through the repo's
continuous-batching engine.

As ``runners/serve_lfm2.py``: everything a run does is ``runners/serve.py``'s
(the engine built as ``serving.main`` builds it, the traffic, the clocks,
``failed`` and the comparison that decides ``correct``), loaded as a private
copy with three names rebound: ``lib/reference_longcat.py``,
``lib/weights_longcat.py`` and the key map below, which hands the program the
latent sizes, the router and the share of the experts under its own field
names.  What the program has ONE form of (interleaved rotary pairs, silu, no
bias, the factor sqrt(hidden / rank) on both low-rank norms, zero-compute
experts that return their input) is checked here and not handed on.  A program without
the fields (the parent commit) is refused before anything is started, and
whatever child a failed run leaves is stopped on the way out
(``serve_looped._Children``).
"""

import dataclasses
import importlib.util
import pathlib

from benchmark.lib import reference_longcat, weights_longcat
from benchmark.runners import serve_looped

_HERE = pathlib.Path(__file__).resolve().parent
_FIELDS = {"num_layers": "n_layers", "ffn_hidden_size": "d_ff",
           "expert_ffn_hidden_size": "moe_d_ff", "rms_norm_eps": "norm_eps",
           "q_lora_rank": "mla_q_rank", "kv_lora_rank": "mla_kv_rank",
           "qk_nope_head_dim": "mla_nope_dim",
           "qk_rope_head_dim": "mla_rope_dim", "v_head_dim": "mla_v_dim",
           "n_routed_experts_published": "moe_experts",
           "n_routed_experts": "moe_experts_held",
           "experts_offset": "moe_experts_offset",
           "zero_expert_num": "moe_zero_experts", "moe_topk": "moe_top_k",
           "routed_scaling_factor": "moe_scale",
           # Stated in the file under the program's own names.
           "layer_types": "layer_types", "attention_kind": "attention_kind",
           "moe_score": "moe_score", "moe_normalize": "moe_normalize"}
# What the program's latent attention and expert layer are, under the
# configuration's keys.
_ONE_FORM = {"attention_method": "MLA", "attention_bias": False,
             "mla_scale_q_lora": True, "mla_scale_kv_lora": True,
             "zero_expert_type": "identity", "tie_word_embeddings": False}


def _serve():
    spec = importlib.util.spec_from_file_location(
        "bench_serve_for_longcat", _HERE / "serve.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    module.reference, module.weights = reference_longcat, weights_longcat
    module._FIELDS = {**module._FIELDS, **_FIELDS}
    return module


def run(ctx):
    from kubeflow_tpu.models.transformer import TransformerConfig

    module = _serve()
    known = {f.name for f in dataclasses.fields(TransformerConfig)}
    missing = sorted(set(module._FIELDS.values()) - known)
    if missing:
        raise SystemExit(f"this program's TransformerConfig has no {missing}: "
                         "it cannot run latent attention, a double layer or "
                         "a share of the experts")
    config = ctx["config"]
    other = {k: config.get(k) for k, v in _ONE_FORM.items()
             if config.get(k) != v}
    if other:
        raise SystemExit(f"the program's latent block has {_ONE_FORM}; the "
                         f"configuration states {other}")
    children = module.subprocess = serve_looped._Children()
    try:
        return module.run(ctx)
    finally:
        for child in children.started:
            if child.poll() is None:
                child.kill()
                child.communicate()
