"""Runner for cells that serve dots.vlm1.inst's language model (DeepSeek-V3's
block: latent attention without a factor on its low-rank norms, YaRN rotary
frequencies and a factor on the softmax scale, sigmoid-routed experts chosen
inside kept groups, of which this chip holds a share beside one shared
expert) with its multi-token-prediction module DRAFTING every decode step
(``configs/dots.vlm1.inst-l5.json``) through the repo's continuous-batching
engine.

As ``runners/serve_dots3.py``: everything a run does is ``runners/serve.py``'s
(the engine built as ``serving.main`` builds it, the traffic, the clocks,
``failed`` and the comparison of the served tokens), loaded as a private
copy with these names rebound: ``lib/reference_dotsvlm.py``,
``lib/weights_dotsvlm.py``, the key map below (YaRN's numbers and the
scale's factor are worked out here from ``rope_scaling`` and handed on under
the program's field names), ``Client`` (which keeps what ``submit_stream``
says the module drafted for a request) and ``check_correct``, which after
``serve.py``'s own holds every draft of the sampled requests against the
reference's module: the draft's logit in the reference's module head, fed
the same (h_i, t_{i+1}) history, against that head's best at its position
(``draft_logit_gap_max`` / ``_mean``, limits of their own in the cell's
file).  Without it nothing on the chip tells a wrong module from a right
one: with seeded weights either is refused at almost every step.

What the program has ONE form of (interleaved rotary pairs, silu, no bias,
one shared expert, an expert layer every layer after the dense ones, a
rotary table without a factor of its own) is checked here and not handed on.
A program without the fields (the parent commit) is refused before anything
is started, and whatever child a failed run leaves is stopped on the way out
(``serve_looped._Children``).
"""

import dataclasses
import importlib.util
import math
import pathlib

import numpy as np

from benchmark.lib import reference_dotsvlm, weights_dotsvlm
from benchmark.runners import serve_looped

_HERE = pathlib.Path(__file__).resolve().parent
_FIELDS = {"rms_norm_eps": "norm_eps", "layer_types": "layer_types",
           "q_lora_rank": "mla_q_rank", "kv_lora_rank": "mla_kv_rank",
           "qk_nope_head_dim": "mla_nope_dim",
           "qk_rope_head_dim": "mla_rope_dim", "v_head_dim": "mla_v_dim",
           "first_k_dense_replace": "moe_dense_layers",
           "moe_intermediate_size": "moe_d_ff",
           "n_routed_experts_published": "moe_experts",
           "n_routed_experts": "moe_experts_held",
           "experts_offset": "moe_experts_offset",
           "num_experts_per_tok": "moe_top_k",
           "norm_topk_prob": "moe_normalize",
           "routed_scaling_factor": "moe_scale",
           "scoring_func": "moe_score", "n_group": "moe_groups",
           "topk_group": "moe_groups_kept",
           "num_nextn_predict_layers": "mtp_layers",
           # Stated in the file under the program's own names.
           "attention_kind": "attention_kind",
           "moe_shared_d_ff": "moe_shared_d_ff",
           "mla_rescale": "mla_rescale", "moe_norm_eps": "moe_norm_eps",
           # Worked out below from ``rope_scaling``.
           "yarn_factor": "yarn_factor",
           "yarn_original_len": "yarn_original_len",
           "yarn_beta_fast": "yarn_beta_fast",
           "yarn_beta_slow": "yarn_beta_slow",
           "mla_softmax_mult": "mla_softmax_mult"}
# What the program's block is, under the configuration's keys.
_ONE_FORM = {"attention_bias": False, "hidden_act": "silu",
             "n_shared_experts": 1, "moe_layer_freq": 1,
             "topk_method": "noaux_tc", "tie_word_embeddings": False}


def yarn_fields(rope_scaling):
    """The program's fields of ``rope_scaling`` (type yarn)."""
    y = rope_scaling
    if y.get("type") != "yarn" or y["mscale"] != y["mscale_all_dim"]:
        raise SystemExit(
            "the program builds YaRN with a rotary table whose own factor "
            f"m(mscale) / m(mscale_all_dim) is 1; the configuration states "
            f"{y}")
    m = 0.1 * y["mscale_all_dim"] * math.log(y["factor"]) + 1.0
    return {"yarn_factor": float(y["factor"]),
            "yarn_original_len": y["original_max_position_embeddings"],
            "yarn_beta_fast": float(y["beta_fast"]),
            "yarn_beta_slow": float(y["beta_slow"]),
            "mla_softmax_mult": m * m}


def drafts_of(rec):
    """[(index of the served token, the draft it was held against)] of a
    request's record, over the tokens the client saw."""
    seen = len(rec["tokens"])
    return [(at + j, int(d)) for at, held in rec.get("mtp_drafts") or ()
            for j, d in enumerate(held) if d >= 0 and at + j < seen]


def _serve():
    spec = importlib.util.spec_from_file_location(
        "bench_serve_for_dotsvlm", _HERE / "serve.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    module.reference, module.weights = reference_dotsvlm, weights_dotsvlm
    module._FIELDS = {**module._FIELDS, **_FIELDS}

    class Client(module.Client):
        """Keeps the drafts ``submit_stream`` reports beside the stream."""

        def submit(self, request, due):
            rec = {"due": due, "sent": self.clock(), "first": None,
                   "last": None, "tokens": [], "arrivals": [], "error": None,
                   "request": request, "stream": None}
            try:
                meta, rec["stream"] = self.engine.submit_stream({
                    "tokens": request["prompt"],
                    "max_new_tokens": request["max_new"]})
                rec["mtp_drafts"] = meta.get("mtp_drafts")
            except Exception as e:  # shed at the door: a failed request
                rec["error"] = f"{type(e).__name__}: {e}"
            return rec

    served_check = module.check_correct

    def check_correct(published, seed, records, limits, pad_to, n_sample,
                      quantize=None):
        """``serve.py``'s comparison of the served tokens, then the drafts
        of the same requests against the reference's module."""
        reference_dotsvlm.MODULE_ROWS.clear()
        ok, numbers, gaps = served_check(
            published, seed, records, limits, pad_to, n_sample, quantize)
        rows_of = reference_dotsvlm.MODULE_ROWS
        lows = quantize.split(",") if quantize else []
        drafts, control = [], {q: [] for q in lows}
        for r in records:
            if r["error"] is not None or not r["tokens"]:
                continue
            tokens = np.concatenate(
                [r["request"]["prompt"], np.asarray(r["tokens"])]).astype(
                    np.int32).tobytes()
            rows = rows_of.get((None, tokens))
            if rows is None:
                continue          # not of the sample
            # ``logits`` began at the prompt's last position (or later, for
            # a request longer than the reference's window): the draft of
            # served token j came from the module's row at p + j - 2.
            p = len(r["request"]["prompt"])
            first = min(p - 1, max(0, pad_to - limits["max_new_tokens"]))
            found = [(p + j - 2 - first, d) for j, d in drafts_of(r)]
            found = [(at, d) for at, d in found if 0 <= at < rows.shape[0]]
            if not found:
                continue
            at, d = map(np.asarray, zip(*found))
            drafts.append(rows[at].max(-1) - rows[at, d])
            for q in lows:
                low = rows_of[(q, tokens)]
                control[q].append(rows[at].max(-1)
                                  - rows[at, low[at].argmax(-1)])
        if not drafts:
            return False, numbers + [("drafts_compared", 0.0, None)], gaps
        drafts = np.concatenate(drafts)
        more = [("drafts_compared", float(drafts.shape[0]), None),
                ("draft_logit_gap_max", float(drafts.max()),
                 limits["draft_logit_gap_max"]),
                ("draft_logit_gap_mean", float(drafts.mean()),
                 limits["draft_logit_gap_mean"]),
                ("draft_not_first_share", float((drafts > 0).mean()), None)]
        for q, found in control.items():
            found = np.concatenate(found)
            more += [(f"control_{q}_draft_gap_max", float(found.max()), None),
                     (f"control_{q}_draft_gap_mean", float(found.mean()),
                      None)]
        ok = ok and all(limit is None or value <= limit
                        for _, value, limit in more)
        return ok, numbers + more, dict(gaps, drafts=drafts.tolist())

    module.Client, module.check_correct = Client, check_correct
    return module


def run(ctx):
    from kubeflow_tpu.models.transformer import TransformerConfig

    module = _serve()
    known = {f.name for f in dataclasses.fields(TransformerConfig)}
    missing = sorted(set(module._FIELDS.values()) - known)
    if missing:
        raise SystemExit(f"this program's TransformerConfig has no {missing}: "
                         "it cannot run expert groups, YaRN or a drafting "
                         "multi-token-prediction module")
    config = ctx["config"]
    other = {k: config.get(k) for k, v in _ONE_FORM.items()
             if config.get(k) != v}
    if other:
        raise SystemExit(f"the program's block has {_ONE_FORM}; the "
                         f"configuration states {other}")
    ctx = dict(ctx, config=dict(config,
                                **yarn_fields(config["rope_scaling"])))
    children = module.subprocess = serve_looped._Children()
    try:
        return module.run(ctx)
    finally:
        for child in children.started:
            if child.poll() is None:
                child.kill()
                child.communicate()
