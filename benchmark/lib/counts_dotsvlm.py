"""Parameters, bytes and operations of dots.vlm1.inst's language model with
its multi-token-prediction module drafting
(``configs/dots.vlm1.inst-l5.json``), from the configuration's keys.  As in
``counts.py`` every function counts the LEAST the algorithm needs, so a share
of a peak worked out from these can only read low.

What differs from the other stacks.  A decode step runs the main stack over
TWO rows a live slot (the last token and the module's draft of the next) and
then the module over one row or two: the weights are read once for all of a
step's rows, and a position's latent row (``kv_lora_rank`` +
``qk_rope_head_dim`` values, 1,152 B in bfloat16, whatever the pool pads a
row to) once a plane for BOTH rows of a slot.  The order of the data forces
the head's slice to be read TWICE a step: the module's rows embed the tokens
the main rows' logits choose.  The module is two norms, the projection of
[embedding; stream] (``2 d x d``), one more attention + expert layer with a
plane of its own, and a norm; embedding and head are the main model's.  An
expert layer routes over ``n_routed_experts_published`` outputs and holds
``n_routed_experts`` of them (this chip's share); an expert's three matrices
are read if at least one of the step's rows chose it AND this chip holds it:
how many were touched, in the main layers and in the module's, is the
device's own count (the fact ``experts_touched`` of a round's ``round_wait``
annotation).  ``attended`` is the device's own too: over live slots and
steps, the positions the LATER of a step's two rows saw.
"""

from . import counts


def attention_params(c) -> int:
    """One latent attention: W_qa, its norm, W_qb, W_kva, its norm,
    W_uk | W_uv, W_o."""
    d, h = c["hidden_size"], c["num_attention_heads"]
    rq, rkv = c["q_lora_rank"], c["kv_lora_rank"]
    dn, dr, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                  c["v_head_dim"])
    return (d * rq + rq + rq * h * (dn + dr) + d * (rkv + dr) + rkv
            + rkv * h * (dn + dv) + h * dv * d)


def attention_matmul_params(c) -> int:
    return attention_params(c) - c["q_lora_rank"] - c["kv_lora_rank"]


def dense_ff_params(c) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def expert_params(c) -> int:
    """One routed expert's three matrices; the shared expert's too."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def router_params(c) -> int:
    """The router over every output and the bias that selects."""
    return (c["hidden_size"] + 1) * c["n_routed_experts_published"]


def dense_layer_params(c) -> int:
    return attention_params(c) + dense_ff_params(c) + 2 * c["hidden_size"]


def expert_layer_params(c) -> int:
    """An expert layer BESIDE its routed experts: attention, two norms, the
    router, the shared expert."""
    return (attention_params(c) + 2 * c["hidden_size"] + router_params(c)
            + c["n_shared_experts"] * expert_params(c))


def module_params(c) -> int:
    """The module beside its layer's routed experts: three norms, the
    projection, an expert layer."""
    d = c["hidden_size"]
    return 3 * d + 2 * d * d + expert_layer_params(c)


def expert_layers(c) -> int:
    """Of the main stack."""
    return c["num_hidden_layers"] - c["first_k_dense_replace"]


def total_params(c) -> int:
    """What the tree holds: both tables, the final norm, the layers with the
    experts held here, the module with its."""
    d, held = c["hidden_size"], c["n_routed_experts"] * expert_params(c)
    return (2 * d * c["vocab_size"] + d
            + c["first_k_dense_replace"] * dense_layer_params(c)
            + expert_layers(c) * (expert_layer_params(c) + held)
            + c["num_nextn_predict_layers"] * (module_params(c) + held))


def published_params(c) -> int:
    """The language model the configuration was cut from: every layer, every
    routed expert, the whole vocabulary (``reduced_from``), without the
    module."""
    whole = dict(c, **c["reduced_from"], num_nextn_predict_layers=0)
    return total_params(whole)


def weight_bytes(c, bytes_per_param: int = 2) -> int:
    return total_params(c) * bytes_per_param


def kv_planes(c) -> int:
    """The main layers' and the module's."""
    return c["num_hidden_layers"] + c["num_nextn_predict_layers"]


def latent_values_per_token(c) -> int:
    return c["kv_lora_rank"] + c["qk_rope_head_dim"]


def latent_bytes_per_token(c, bytes_per_value: int = 2) -> int:
    """Over every plane; 6,912 B for the cut's 6."""
    return kv_planes(c) * latent_values_per_token(c) * bytes_per_value


def attention_flops_per_position(c) -> float:
    """One query position against ONE attended position in one plane, in the
    absorbed form."""
    return 2.0 * c["num_attention_heads"] * (
        latent_values_per_token(c) + c["kv_lora_rank"])


def step_matmul_params(c) -> int:
    """Matmul parameters EVERY drafting decode step reads whatever it
    routes: attention, the dense feed-forward, the routers, the shared
    experts and the module's projection once, the head's slice TWICE (the
    embedding is a row lookup)."""
    d, n = c["hidden_size"], c["n_routed_experts_published"]
    layer = attention_matmul_params(c) + d * n \
        + c["n_shared_experts"] * expert_params(c)
    return (c["first_k_dense_replace"] * (attention_matmul_params(c)
                                          + dense_ff_params(c))
            + expert_layers(c) * layer
            + c["num_nextn_predict_layers"] * (layer + 2 * d * d)
            + (1 + c["num_nextn_predict_layers"]) * d * c["vocab_size"])


def step_flops(c, context: float) -> float:
    """Forward FLOPs of ONE slot's drafting step that attends ``context``
    positions a plane and meets NO routed expert (the fewest): two rows
    through the main stack and the head, one through the module and the
    head."""
    d, n, v = (c["hidden_size"], c["n_routed_experts_published"],
               c["vocab_size"])
    layer = attention_matmul_params(c) + d * n \
        + c["n_shared_experts"] * expert_params(c)
    main = (c["first_k_dense_replace"] * (attention_matmul_params(c)
                                          + dense_ff_params(c))
            + expert_layers(c) * layer + d * v)
    module = c["num_nextn_predict_layers"] * (layer + 2 * d * d + d * v)
    rows_attending = 2 * c["num_hidden_layers"] \
        + c["num_nextn_predict_layers"]
    return (2.0 * (2 * main + module)
            + rows_attending * attention_flops_per_position(c) * context)


def decode_round_bytes(c, steps: int, attended: float,
                       experts_touched: float, bytes_per_param: int = 2,
                       bytes_per_value: int = 2) -> float:
    """Least bytes a fused round of ``steps`` drafting steps must move: per
    step ``step_matmul_params``; the three matrices of each of the
    ``experts_touched``; the latent rows of the ``attended`` positions in
    every plane, once for both rows of a slot."""
    return (steps * step_matmul_params(c) * bytes_per_param
            + experts_touched * expert_params(c) * bytes_per_param
            + attended * latent_bytes_per_token(c, bytes_per_value))


def decode_round_seconds(c, steps: int, attended: float,
                         experts_touched: float, peak_flops: float,
                         peak_bytes_per_s: float):
    """Least seconds of such a round: (seconds, which bound).  FLOPs are
    counted for one slot a step."""
    return counts.roofline_seconds(
        steps * step_flops(c, attended / steps),
        decode_round_bytes(c, steps, attended, experts_touched),
        peak_flops, peak_bytes_per_s)


def latent_attention_seconds(c, attended: float, peak_flops: float,
                             peak_bytes_per_s: float,
                             bytes_per_value: int = 2):
    """Least seconds of the latent decode attention as a drafting step
    calls it, over ``attended`` positions (summed over live slots and
    steps) in EVERY plane: each position's row read once for all heads,
    both products and BOTH query positions, against the absorbed form's
    operations at two query positions a slot (256 query rows of 128
    heads)."""
    return counts.roofline_seconds(
        2 * attended * kv_planes(c) * attention_flops_per_position(c),
        attended * latent_bytes_per_token(c, bytes_per_value),
        peak_flops, peak_bytes_per_s)
