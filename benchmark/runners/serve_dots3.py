"""Runner for cells that serve dots3-note-prev's language model (full layers
whose learned indexer chooses ``index_topk`` positions before latent
attention, window layers with a latent of their own, a headwise gate,
sigmoid-routed experts of which this chip holds a share beside one shared
expert: ``configs/dots3-note-prev-l5.json``) through the repo's
continuous-batching engine.

As ``runners/serve_longcat.py``: everything a run does is
``runners/serve.py``'s (the engine built as ``serving.main`` builds it, the
traffic, the clocks, ``failed`` and the comparison that decides ``correct``),
loaded as a private copy with three names rebound:
``lib/reference_dots3.py``, ``lib/weights_dots3.py`` and the key map below,
which hands the program the two sets of latent sizes, the indexer, the window,
the router and the share of the experts under its own field names.  What the
program has ONE form of (interleaved rotary pairs, silu, no bias, the factor
sqrt(hidden / rank) on both low-rank norms, a headwise gate on both kinds of
layer, one shared expert, no expert groups) is checked here and not handed
on.  A program without the fields (the parent commit) is refused before
anything is started, and whatever child a failed run leaves is stopped on
the way out (``serve_looped._Children``).
"""

import dataclasses
import importlib.util
import pathlib

from benchmark.lib import reference_dots3, weights_dots3
from benchmark.runners import serve_looped

_HERE = pathlib.Path(__file__).resolve().parent
_FIELDS = {"rms_norm_eps": "norm_eps", "layer_types": "layer_types",
           "q_lora_rank": "mla_q_rank", "kv_lora_rank": "mla_kv_rank",
           "qk_nope_head_dim": "mla_nope_dim",
           "qk_rope_head_dim": "mla_rope_dim", "v_head_dim": "mla_v_dim",
           "index_n_heads": "index_heads", "index_head_dim": "index_dim",
           "index_topk": "index_topk", "sliding_window_size": "window",
           "swa_num_attention_heads": "window_heads",
           "swa_q_lora_rank": "window_q_rank",
           "swa_kv_lora_rank": "window_kv_rank",
           "swa_qk_nope_head_dim": "window_nope_dim",
           "swa_qk_rope_head_dim": "window_rope_dim",
           "swa_v_head_dim": "window_v_dim",
           "swa_rope_theta": "window_rope_theta",
           "first_k_dense_replace": "moe_dense_layers",
           "moe_intermediate_size": "moe_d_ff",
           "n_routed_experts_published": "moe_experts",
           "n_routed_experts": "moe_experts_held",
           "experts_offset": "moe_experts_offset",
           "num_experts_per_tok": "moe_top_k",
           "norm_topk_prob": "moe_normalize",
           "routed_scaling_factor": "moe_scale",
           "scoring_func": "moe_score",
           # Stated in the file under the program's own names.
           "attention_kind": "attention_kind", "attn_gate": "attn_gate",
           "moe_shared_d_ff": "moe_shared_d_ff"}
# What the program's latent block and expert layer are, under the
# configuration's keys.
_ONE_FORM = {"apply_mla_qkv_lora_rescale": True, "attention_bias": False,
             "attention_gate_type": "headwise",
             "swa_attention_gate_type": "headwise", "hidden_act": "silu",
             "n_shared_experts": 1, "moe_layer_freq": 1,
             "topk_method": "noaux_tc", "rope_scaling": None,
             "tie_word_embeddings": False}


def _serve():
    spec = importlib.util.spec_from_file_location(
        "bench_serve_for_dots3", _HERE / "serve.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    module.reference, module.weights = reference_dots3, weights_dots3
    module._FIELDS = {**module._FIELDS, **_FIELDS}
    return module


def run(ctx):
    from kubeflow_tpu.models.transformer import TransformerConfig

    module = _serve()
    known = {f.name for f in dataclasses.fields(TransformerConfig)}
    missing = sorted(set(module._FIELDS.values()) - known)
    if missing:
        raise SystemExit(f"this program's TransformerConfig has no {missing}: "
                         "it cannot run an indexer, a window layer, a gate "
                         "or a shared expert")
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
