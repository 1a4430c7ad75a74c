"""Runner for cells that serve an LFM2-MoE decoder (a stack that states its
``layer_types``: gated short convolutions beside attention layers, leading
dense feed-forwards, then sigmoid-routed sparse experts:
``configs/lfm2-24b-a2b-l10.json``) through the repo's continuous-batching
engine.

As ``runners/serve_looped.py``: everything a run does is ``runners/serve.py``'s
(the engine built as ``serving.main`` builds it, the traffic, the clocks,
``failed`` and the comparison that decides ``correct``), loaded as a private
copy with three names rebound: ``lib/reference_lfm2.py``,
``lib/weights_lfm2.py`` and the key map below, which hands the program what
each layer is.  The program has ONE router for such a stack (sigmoid scores,
a bias that selects and does not weigh, the chosen scores normalised, scale
1), so the three keys that would say otherwise are checked here and not
handed on.  A program without the fields (the parent commit) is refused
before anything is started, and whatever child a failed run leaves is
stopped on the way out (``serve_looped._Children``).
"""

import dataclasses
import importlib.util
import pathlib

from benchmark.lib import reference_lfm2, weights_lfm2
from benchmark.runners import serve_looped

_HERE = pathlib.Path(__file__).resolve().parent
_FIELDS = {"layer_types": "layer_types", "conv_L_cache": "conv_kernel",
           "qk_norm": "qk_norm", "norm_eps": "norm_eps",
           "num_experts": "moe_experts", "num_experts_per_tok": "moe_top_k",
           "num_dense_layers": "moe_dense_layers",
           "moe_intermediate_size": "moe_d_ff"}
# What the program's router is, under the configuration's keys.
_ROUTER = {"use_expert_bias": True, "norm_topk_prob": True,
           "routed_scaling_factor": 1, "conv_bias": False}


def _serve():
    spec = importlib.util.spec_from_file_location(
        "bench_serve_for_lfm2", _HERE / "serve.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    module.reference, module.weights = reference_lfm2, weights_lfm2
    module._FIELDS = {**module._FIELDS, **_FIELDS}
    return module


def run(ctx):
    from kubeflow_tpu.models.transformer import TransformerConfig

    module = _serve()
    known = {f.name for f in dataclasses.fields(TransformerConfig)}
    missing = sorted(set(module._FIELDS.values()) - known)
    if missing:
        raise SystemExit(f"this program's TransformerConfig has no {missing}: "
                         "it cannot run a stack with layer_types")
    other = {k: ctx["config"].get(k) for k, v in _ROUTER.items()
             if ctx["config"].get(k) != v}
    if other:
        raise SystemExit(f"the program's block for layer_types has {_ROUTER}; "
                         f"the configuration states {other}")
    children = module.subprocess = serve_looped._Children()
    try:
        return module.run(ctx)
    finally:
        for child in children.started:
            if child.poll() is None:
                child.kill()
                child.communicate()
