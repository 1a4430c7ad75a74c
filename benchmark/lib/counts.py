"""Operations and bytes the algorithm needs, from a configuration's sizes.

Every function counts the LEAST the algorithm needs (causal attention
halved, no recomputation, the embedding a lookup and not a matmul), so a
share of a peak worked out from these can only read low, never over 100%.
``c`` is a configuration file's ``published`` group (Hugging Face key names).
"""


def _dims(c):
    d, h, kv = c["hidden_size"], c["num_attention_heads"], \
        c["num_key_value_heads"]
    return (d, h, kv, c["head_dim"], c["intermediate_size"],
            c["vocab_size"], c["num_hidden_layers"])


def layer_params(c) -> int:
    d, h, kv, hd, f, _, _ = _dims(c)
    return d * hd * (h + 2 * kv) + h * hd * d + 3 * d * f + 2 * d


def matmul_params(c) -> int:
    """Parameters every token is multiplied through: the blocks and the
    output head.  The input embedding is a row lookup."""
    d, _, _, _, _, v, n = _dims(c)
    return n * (layer_params(c) - 2 * d) + d * v


def total_params(c) -> int:
    d, _, _, _, _, v, n = _dims(c)
    tables = d * v if c.get("tie_word_embeddings") else 2 * d * v
    return n * layer_params(c) + tables + d


def weight_bytes(c, bytes_per_param: int = 2) -> int:
    return total_params(c) * bytes_per_param


def kv_bytes_per_token(c, bytes_per_value: int = 2) -> int:
    _, _, kv, hd, _, _, n = _dims(c)
    return 2 * n * kv * hd * bytes_per_value


def forward_flops_per_token(c, context: float) -> float:
    """Forward FLOPs for one token that attends to ``context`` keys
    (for a whole causal sequence of length s the mean context is s / 2)."""
    _, h, _, hd, _, _, n = _dims(c)
    return 2.0 * matmul_params(c) + 4.0 * n * h * hd * context


def train_flops_per_token(c, seq_len: int) -> float:
    """Forward and backward (2x forward), causal, nothing recomputed."""
    return 3.0 * forward_flops_per_token(c, seq_len / 2.0)


def decode_step_bytes(c, attended_tokens: float,
                      bytes_per_param: int = 2,
                      bytes_per_value: int = 2) -> float:
    """Least bytes one decode step must read: every matmul weight once
    (the embedding only a row per sequence) and the keys and values of the
    ``attended_tokens`` positions the step's sequences attend, summed over
    the sequences.  Pages a prefix cache keeps for nobody in the batch are
    not read, and counting them would break this file's promise."""
    w = matmul_params(c) * bytes_per_param
    return w + attended_tokens * kv_bytes_per_token(c, bytes_per_value)


def roofline_seconds(flops: float, bytes_: float, peak_flops: float,
                     peak_bytes_per_s: float):
    """(least seconds, which bound)"""
    tf, tb = flops / peak_flops, bytes_ / peak_bytes_per_s
    return (tf, "compute") if tf >= tb else (tb, "memory")
