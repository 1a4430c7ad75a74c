"""Parameters, bytes and operations of an LFM2-MoE decoder
(``configs/lfm2-24b-a2b-l10.json``), from the configuration's keys.  As in
``counts.py`` every function counts the LEAST the algorithm needs, so a share
of a peak worked out from these can only read low.

What differs from a dense stack: only the ``full_attention`` layers own keys
and values (2 of the cut's 10 layers: 4,096 B a token); a ``conv`` layer
keeps ``conv_L_cache - 1`` columns of its gated input per sequence instead;
the first ``num_dense_layers`` feed-forwards are a SwiGLU at
``intermediate_size`` and the others ``num_experts`` SwiGLUs at
``moe_intermediate_size``, of which a token meets ``num_experts_per_tok``.
So the least bytes of a decode step depend on the routing: an expert's three
matrices are read if at least one of the step's rows chose it, and not
otherwise.  How many were touched is the device's own count
(``stats()["experts_touched"]``, and the fact ``experts_touched`` of a
round's ``round_wait`` annotation): distinct experts with at least one row,
summed over the sparse layers and the decode steps.
"""

from . import counts


def _kinds(c):
    types = c["layer_types"]
    assert len(types) == c["num_hidden_layers"], (len(types), c)
    conv = sum(1 for t in types if t == "conv")
    dense = min(c["num_dense_layers"], len(types))
    return conv, len(types) - conv, dense, len(types) - dense


def conv_operator_params(c) -> int:
    """W_in (hidden -> 3 x hidden), W_out and the taps."""
    d = c["hidden_size"]
    return 3 * d * d + d * d + c["conv_L_cache"] * d


def attention_operator_params(c) -> int:
    """Wq, Wk, Wv, Wo and the two per-head norm scales."""
    d, h, kv, hd = (c["hidden_size"], c["num_attention_heads"],
                    c["num_key_value_heads"], c["head_dim"])
    return d * hd * (h + 2 * kv) + h * hd * d + 2 * hd


def dense_ff_params(c) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def expert_params(c) -> int:
    """One expert's three matrices."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def router_params(c) -> int:
    """The router and the bias that selects."""
    return c["hidden_size"] * c["num_experts"] + c["num_experts"]


def total_params(c) -> int:
    """What the tree holds: the tied table, the final norm, and per layer
    its operator, its two norms and its feed-forward."""
    d = c["hidden_size"]
    conv, attn, dense, sparse = _kinds(c)
    tables = d * c["vocab_size"] * (1 if c.get("tie_word_embeddings") else 2)
    return (tables + d + 2 * d * (conv + attn)
            + conv * conv_operator_params(c)
            + attn * attention_operator_params(c)
            + dense * dense_ff_params(c)
            + sparse * (c["num_experts"] * expert_params(c)
                        + router_params(c)))


def weight_bytes(c, bytes_per_param: int = 2) -> int:
    return total_params(c) * bytes_per_param


def kv_planes(c) -> int:
    return _kinds(c)[1]


def kv_bytes_per_token(c, bytes_per_value: int = 2) -> int:
    return (2 * kv_planes(c) * c["num_key_value_heads"] * c["head_dim"]
            * bytes_per_value)


def conv_state_bytes_per_sequence(c, bytes_per_value: int = 2) -> int:
    return (_kinds(c)[0] * (c["conv_L_cache"] - 1) * c["hidden_size"]
            * bytes_per_value)


def step_matmul_params(c) -> int:
    """Matmul parameters EVERY decode step reads whatever it routes: the
    operators' projections, the dense feed-forwards, the routers and the
    head (the input embedding is a row lookup)."""
    d = c["hidden_size"]
    conv, attn, dense, sparse = _kinds(c)
    return (conv * 4 * d * d
            + attn * (attention_operator_params(c) - 2 * c["head_dim"])
            + dense * dense_ff_params(c)
            + sparse * d * c["num_experts"]
            + d * c["vocab_size"])


def active_matmul_params(c) -> int:
    """Matmul parameters one token is multiplied through."""
    return (step_matmul_params(c) + _kinds(c)[3]
            * c["num_experts_per_tok"] * expert_params(c))


def forward_flops_per_token(c, context: float) -> float:
    """Forward FLOPs of one token that attends ``context`` keys in every
    attention layer (the convolution's taps are a few thousand and left
    out: the count may only read low)."""
    return (2.0 * active_matmul_params(c) + 4.0 * kv_planes(c)
            * c["num_attention_heads"] * c["head_dim"] * context)


def decode_round_bytes(c, steps: int, attended: float,
                       experts_touched: float, bytes_per_param: int = 2,
                       bytes_per_value: int = 2) -> float:
    """Least bytes a fused round of ``steps`` decode steps must move: per
    step every weight that does not depend on the routing once and one
    sequence's convolution state read and written (the fewest a step that
    ran can have had); the three matrices of each of the
    ``experts_touched`` (summed over sparse layers and steps); the keys
    and values of the ``attended`` positions (summed over sequences and
    steps) in the attention layers' planes."""
    return (steps * (step_matmul_params(c) * bytes_per_param
                     + 2 * conv_state_bytes_per_sequence(c, bytes_per_value))
            + experts_touched * expert_params(c) * bytes_per_param
            + attended * kv_bytes_per_token(c, bytes_per_value))


def decode_round_seconds(c, steps: int, attended: float,
                         experts_touched: float, peak_flops: float,
                         peak_bytes_per_s: float):
    """Least seconds of such a round: (seconds, which bound).  FLOPs are
    counted for one sequence a step."""
    return counts.roofline_seconds(
        steps * forward_flops_per_token(c, attended / steps),
        decode_round_bytes(c, steps, attended, experts_touched),
        peak_flops, peak_bytes_per_s)
