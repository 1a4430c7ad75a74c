"""Parameters, bytes and operations of LongCat-Flash's language model
(``configs/longcat-flash-omni-l4.json``), from the configuration's keys.  As
in ``counts.py`` every function counts the LEAST the algorithm needs, so a
share of a peak worked out from these can only read low.

What differs from the other stacks.  A layer is DOUBLE: two latent
attention sublayers and two dense SwiGLUs at ``ffn_hidden_size``, and one
expert layer whose result joins at the layer's end.  The cache of a token
and attention sublayer is ONE row, the normed latent (``kv_lora_rank``) and
the shared rotary key (``qk_rope_head_dim``), key and value at once: 576
values, 1,152 B in bfloat16, whatever the pool pads a row to.  A decode step
that absorbs the key expansion into the query does, a head and attended
position, ``2 x (kv_lora_rank + qk_rope_head_dim)`` operations for the score
and ``2 x kv_lora_rank`` for the sum.  The expert layer routes over
``n_routed_experts_published`` + ``zero_expert_num`` outputs and holds
``n_routed_experts`` of the routed experts (this chip's share); a
zero-compute expert has no weights.  So the least bytes of a decode step
depend on the routing: an expert's three matrices are read if at least one of
the step's rows chose it AND this chip holds it.  How many were touched is
the device's own count (``stats()["experts_touched"]``, and the fact
``experts_touched`` of a round's ``round_wait`` annotation).
"""

from . import counts


def attention_params(c) -> int:
    """One latent attention sublayer: W_qa, its norm, W_qb, W_kva, its norm,
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
    return 3 * c["hidden_size"] * c["ffn_hidden_size"]


def expert_params(c) -> int:
    """One routed expert's three matrices."""
    return 3 * c["hidden_size"] * c["expert_ffn_hidden_size"]


def router_outputs(c) -> int:
    return c["n_routed_experts_published"] + c["zero_expert_num"]


def router_params(c) -> int:
    """The router over every output and the bias that selects."""
    return (c["hidden_size"] + 1) * router_outputs(c)


def double_layer_params(c) -> int:
    """A double layer BESIDE its experts: two attention sublayers, two dense
    SwiGLUs, four norms, the router."""
    return (2 * attention_params(c) + 2 * dense_ff_params(c)
            + 4 * c["hidden_size"] + router_params(c))


def total_params(c) -> int:
    """What the tree holds: both tables, the final norm, and per double
    layer everything beside the experts and the experts held here."""
    d = c["hidden_size"]
    return (2 * d * c["vocab_size"] + d + c["num_layers"] * (
        double_layer_params(c) + c["n_routed_experts"] * expert_params(c)))


def published_params(c) -> int:
    """The model the configuration was cut from: every layer, every routed
    expert, the whole vocabulary (``reduced_from``)."""
    return total_params(dict(c, **c["reduced_from"]))


def active_params(c) -> float:
    """Parameters a token meets in the whole model: of its ``moe_topk``
    choices the expected share falls on routed experts (the rest need no
    weights) at a router that treats its outputs alike."""
    whole = dict(c, **c["reduced_from"])
    routed = (whole["moe_topk"] * c["n_routed_experts_published"]
              / router_outputs(c))
    return (2 * whole["hidden_size"] * whole["vocab_size"]
            + whole["num_layers"] * (double_layer_params(whole)
                                     + routed * expert_params(whole)))


def weight_bytes(c, bytes_per_param: int = 2) -> int:
    return total_params(c) * bytes_per_param


def kv_planes(c) -> int:
    return 2 * c["num_layers"]


def latent_values_per_token(c) -> int:
    """What one attention sublayer keeps of a token."""
    return c["kv_lora_rank"] + c["qk_rope_head_dim"]


def latent_bytes_per_token(c, bytes_per_value: int = 2) -> int:
    """Over every plane; 9,216 B for the cut's 8."""
    return kv_planes(c) * latent_values_per_token(c) * bytes_per_value


def attention_flops_per_position(c) -> float:
    """One query position against ONE attended position in one plane, in the
    absorbed form: every head scores the latent row and the rotary key, and
    sums the latent."""
    return 2.0 * c["num_attention_heads"] * (
        latent_values_per_token(c) + c["kv_lora_rank"])


def step_matmul_params(c) -> int:
    """Matmul parameters EVERY decode step reads whatever it routes: the
    attention sublayers' matrices, the dense feed-forwards, the routers and
    the head's slice (the input embedding is a row lookup)."""
    d = c["hidden_size"]
    return (c["num_layers"] * (2 * attention_matmul_params(c)
                               + 2 * dense_ff_params(c)
                               + d * router_outputs(c))
            + d * c["vocab_size"])


def forward_flops_per_token(c, context: float) -> float:
    """Forward FLOPs of one decode token that attends ``context`` positions
    in every plane and meets NO routed expert (the fewest: all twelve
    choices may need no weights or lie on other chips)."""
    return (2.0 * step_matmul_params(c)
            + kv_planes(c) * attention_flops_per_position(c) * context)


def decode_round_bytes(c, steps: int, attended: float,
                       experts_touched: float, bytes_per_param: int = 2,
                       bytes_per_value: int = 2) -> float:
    """Least bytes a fused round of ``steps`` decode steps must move: per
    step every weight that does not depend on the routing once; the three
    matrices of each of the ``experts_touched`` (held experts with a row,
    summed over layers and steps); the latent rows of the ``attended``
    positions (summed over sequences and steps) in every plane."""
    return (steps * step_matmul_params(c) * bytes_per_param
            + experts_touched * expert_params(c) * bytes_per_param
            + attended * latent_bytes_per_token(c, bytes_per_value))


def decode_round_seconds(c, steps: int, attended: float,
                         experts_touched: float, peak_flops: float,
                         peak_bytes_per_s: float):
    """Least seconds of such a round: (seconds, which bound).  FLOPs are
    counted for one sequence a step."""
    return counts.roofline_seconds(
        steps * forward_flops_per_token(c, attended / steps),
        decode_round_bytes(c, steps, attended, experts_touched),
        peak_flops, peak_bytes_per_s)


def latent_attention_seconds(c, attended: float, peak_flops: float,
                             peak_bytes_per_s: float,
                             bytes_per_value: int = 2):
    """Least seconds of the latent decode attention over ``attended``
    positions (summed over sequences, steps), in EVERY plane, whatever
    implements it: each position's row read once for all heads and both
    products, against the absorbed form's operations.  The queries and the
    outputs (a few KB a sequence) are left out: the count may only read
    low."""
    return counts.roofline_seconds(
        attended * kv_planes(c) * attention_flops_per_position(c),
        attended * latent_bytes_per_token(c, bytes_per_value),
        peak_flops, peak_bytes_per_s)
