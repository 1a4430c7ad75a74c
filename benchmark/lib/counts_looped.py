"""Operations and bytes a LOOPED decoder needs (Ouro: one stack of
``num_hidden_layers`` layers run ``total_ut_steps`` times a token), from a
configuration's sizes.  As in ``counts.py`` every function counts the LEAST
the algorithm needs, so a share of a peak worked out from these can only read
low.

Why a decode step counts the layers' weights ``total_ut_steps`` times though
there is one set of them: loop step t + 1's first layer needs loop step t's
last layer's output, so a step walks the whole stack 4 times in order, and
the stack (4.93 GB in bfloat16 for Ouro-2.6B) is some forty times what the
chip can keep on itself between two walks.  Every walk reads it from HBM
again: 4 x 4.93 + 0.20 (the head) = 19.9 GB a step at any batch.  Keys and
values: every (loop step, layer) pair has a cache plane of its own, 192 for
Ouro-2.6B, and a token costs 2 x 192 x 16 x 128 x 2 B = 1,572,864 B.
"""

from . import counts


def _loops(c):
    return c.get("total_ut_steps", 1)


def layer_params(c) -> int:
    """One layer: the projections, the MLP and its norms (four in a
    sandwich block, else two)."""
    extra = 2 * c["hidden_size"] if c.get("sandwich_norm") else 0
    return counts.layer_params(c) + extra


def layer_matmul_params(c) -> int:
    return counts.layer_params(c) - 2 * c["hidden_size"]


def matmul_params_walked(c) -> int:
    """Parameters one token is multiplied through: every layer once per
    loop step, and the output head."""
    return (_loops(c) * c["num_hidden_layers"] * layer_matmul_params(c)
            + c["hidden_size"] * c["vocab_size"])


def total_params(c) -> int:
    """What the tree holds: ONE stack, both tables, the final norm, and
    the exit gate (hidden_size + 1) where the model loops."""
    d, v = c["hidden_size"], c["vocab_size"]
    tables = d * v if c.get("tie_word_embeddings") else 2 * d * v
    gate = d + 1 if _loops(c) > 1 else 0
    return c["num_hidden_layers"] * layer_params(c) + tables + d + gate


def weight_bytes(c, bytes_per_param: int = 2) -> int:
    return total_params(c) * bytes_per_param


def kv_planes(c) -> int:
    return _loops(c) * c["num_hidden_layers"]


def kv_bytes_per_token(c, bytes_per_value: int = 2) -> int:
    return (2 * kv_planes(c) * c["num_key_value_heads"] * c["head_dim"]
            * bytes_per_value)


def forward_flops_per_token(c, context: float) -> float:
    """Forward FLOPs for one token that attends ``context`` keys in every
    plane."""
    return (2.0 * matmul_params_walked(c) + 4.0 * kv_planes(c)
            * c["num_attention_heads"] * c["head_dim"] * context)


def decode_step_bytes(c, attended_tokens: float, bytes_per_param: int = 2,
                      bytes_per_value: int = 2) -> float:
    """Least bytes one decode step must read: the layers' matmul weights
    once per loop step, the head once, and the keys and values of the
    ``attended_tokens`` positions the step's sequences attend (summed over
    the sequences), in every plane."""
    return (matmul_params_walked(c) * bytes_per_param
            + attended_tokens * kv_bytes_per_token(c, bytes_per_value))


def decode_round_seconds(c, steps: int, attended: float, peak_flops: float,
                         peak_bytes_per_s: float):
    """Least seconds of one fused round of ``steps`` decode steps whose
    sequences attend ``attended`` positions in all (summed over sequences
    and steps): (seconds, which bound).  FLOPs are counted for one sequence
    a step, the fewest a step that ran can have had."""
    per_step = attended / steps
    return counts.roofline_seconds(
        steps * forward_flops_per_token(c, per_step),
        steps * decode_step_bytes(c, per_step), peak_flops,
        peak_bytes_per_s)
