"""Parameters, bytes and operations of dots3-note-prev's language model
(``configs/dots3-note-prev-l5.json``), from the configuration's keys.  As in
``counts.py`` every function counts the LEAST the algorithm needs, so a share
of a peak worked out from these can only read low.

What differs from the other stacks.  ``layer_types`` names two kinds of
latent attention.  A ``full_attention`` layer keeps of a token one latent row
(``kv_lora_rank`` + ``qk_rope_head_dim``: 576 values) and one index key
(``index_head_dim``: 128 values); a decode step SCORES every index key its
sequence holds (``2 x index_n_heads x index_head_dim`` operations a key, 256
B read) and then ATTENDS ``index_topk`` latent rows at most (1,152 B a row),
whatever the context holds.  A ``sliding_attention`` layer has head count,
ranks and head widths of its own (``swa_*``; a row of 1,088 values) and
attends the last ``sliding_window_size`` positions.  Every latent layer
holds a headwise gate.  Layers from ``first_k_dense_replace`` on route over
``n_routed_experts_published`` outputs and hold ``n_routed_experts`` of the
routed experts (this chip's share) beside ONE shared expert that every token
meets; an expert's three matrices are read if at least one of a step's rows
chose it AND this chip holds it (``stats()["experts_touched"]``, the fact
``experts_touched`` of a round's ``round_wait`` annotation).  The positions
a round's steps scored, chose and read through a window are the program's
own facts too (``index_scored``, ``index_chosen``, ``window_read``: summed
over slots, steps and planes).
"""

from . import counts

FULL, WINDOW = "full_attention", "sliding_attention"


def _sizes(c, kind):
    at = "swa_" if kind == WINDOW else ""
    return (c[at + "num_attention_heads"], c[at + "q_lora_rank"],
            c[at + "kv_lora_rank"], c[at + "qk_nope_head_dim"],
            c[at + "qk_rope_head_dim"], c[at + "v_head_dim"])


def latent_norm_params(c, kind) -> int:
    _, rq, rkv, *_ = _sizes(c, kind)
    return rq + rkv


def attention_params(c, kind) -> int:
    """One latent attention: W_qa, its norm, W_qb, W_kva, its norm,
    W_uk | W_uv, W_o, the gate; WITHOUT the indexer."""
    d = c["hidden_size"]
    h, rq, rkv, dn, dr, dv = _sizes(c, kind)
    return (d * rq + rq + rq * h * (dn + dr) + d * (rkv + dr) + rkv
            + rkv * h * (dn + dv) + h * dv * d + d * h)


def indexer_params(c) -> int:
    """W_qI, W_kI, the LayerNorm's scale and bias, W_w."""
    d, hi, di = c["hidden_size"], c["index_n_heads"], c["index_head_dim"]
    return c["q_lora_rank"] * hi * di + d * di + 2 * di + d * hi


def dense_ff_params(c) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def expert_params(c) -> int:
    """One expert's three matrices (routed or shared)."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def router_params(c) -> int:
    """The router over every output and the bias that selects."""
    return (c["hidden_size"] + 1) * c["n_routed_experts_published"]


def layer_params(c, layer: int, held=None) -> int:
    """What the tree holds of layer ``layer``: attention (with the indexer
    in a full layer), two norms, and the dense SwiGLU or the router, the
    routed experts held (``held``: None = the file's) and the shared one."""
    kind = c["layer_types"][layer]
    out = attention_params(c, kind) + 2 * c["hidden_size"]
    if kind == FULL:
        out += indexer_params(c)
    if layer < c["first_k_dense_replace"]:
        return out + dense_ff_params(c)
    held = c["n_routed_experts"] if held is None else held
    return out + router_params(c) + (
        held + c["n_shared_experts"]) * expert_params(c)


def total_params(c) -> int:
    """What the tree holds: both tables, the final norm, every layer."""
    d = c["hidden_size"]
    return 2 * d * c["vocab_size"] + d + sum(
        layer_params(c, i) for i in range(c["num_hidden_layers"]))


def _published(c):
    whole = dict(c, **{k: v for k, v in c["reduced_from"].items()
                       if k != "layer_types"})
    n = whole["num_hidden_layers"]
    # Layers 0 and 1 full, then the period (full, sliding x 3) from layer 1.
    whole["layer_types"] = [FULL if i == 0 or i % 4 == 1 else WINDOW
                            for i in range(n)]
    return whole


def published_params(c) -> int:
    """The language model the configuration was cut from: every layer,
    every routed expert, the whole vocabulary (``reduced_from``)."""
    return total_params(_published(c))


def active_params(c) -> int:
    """Parameters a token meets in the whole language model: of the routed
    experts its ``num_experts_per_tok`` choices."""
    whole = _published(c)
    d = whole["hidden_size"]
    return 2 * d * whole["vocab_size"] + d + sum(
        layer_params(whole, i, held=whole["num_experts_per_tok"])
        for i in range(whole["num_hidden_layers"]))


def weight_bytes(c, bytes_per_param: int = 2) -> int:
    return total_params(c) * bytes_per_param


def planes(c, kind) -> int:
    return c["layer_types"].count(kind)


def row_values(c, kind) -> int:
    """What one latent layer of ``kind`` keeps of a token to attend it."""
    _, _, rkv, _, dr, _ = _sizes(c, kind)
    return rkv + dr


def cache_bytes_per_token(c, bytes_per_value: int = 2) -> int:
    """Over every plane: latent rows and index keys; 9,344 B for the cut."""
    return bytes_per_value * (
        planes(c, FULL) * (row_values(c, FULL) + c["index_head_dim"])
        + planes(c, WINDOW) * row_values(c, WINDOW))


def attention_flops_per_row(c, kind) -> float:
    """One query position against ONE attended row of one plane, in the
    absorbed form: every head scores the row and sums its latent."""
    h, _, rkv, *_ = _sizes(c, kind)
    return 2.0 * h * (row_values(c, kind) + rkv)


def index_flops_per_key(c) -> float:
    """One query position against ONE index key: every index head's
    product; the relu and the weighted sum are left out."""
    return 2.0 * c["index_n_heads"] * c["index_head_dim"]


def step_matmul_params(c) -> int:
    """Matmul parameters EVERY decode step reads whatever it routes: the
    attention layers' matrices and gates, the indexers, layer 0's SwiGLU,
    the routers, the shared experts and the head's slice (the input
    embedding is a row lookup)."""
    d = c["hidden_size"]
    out = d * c["vocab_size"]
    for i, kind in enumerate(c["layer_types"]):
        out += attention_params(c, kind) - latent_norm_params(c, kind)
        if kind == FULL:
            out += indexer_params(c) - 2 * c["index_head_dim"]
        if i < c["first_k_dense_replace"]:
            out += dense_ff_params(c)
        else:
            out += d * c["n_routed_experts_published"] \
                + c["n_shared_experts"] * expert_params(c)
    return out


def sparse_read_bytes(c, index_scored: float, index_chosen: float,
                      window_read: float, bytes_per_value: int = 2):
    """Least bytes of what a round's steps read of the pools: an index key
    a position scored, a latent row a position chosen, a window row a
    position read (each already summed over planes)."""
    return bytes_per_value * (
        index_scored * c["index_head_dim"]
        + index_chosen * row_values(c, FULL)
        + window_read * row_values(c, WINDOW))


def sparse_read_flops(c, index_scored: float, index_chosen: float,
                      window_read: float) -> float:
    return (index_scored * index_flops_per_key(c)
            + index_chosen * attention_flops_per_row(c, FULL)
            + window_read * attention_flops_per_row(c, WINDOW))


def decode_round_seconds(c, steps: int, experts_touched: float,
                         index_scored: float, index_chosen: float,
                         window_read: float, peak_flops: float,
                         peak_bytes_per_s: float, bytes_per_param: int = 2):
    """Least seconds of a fused round of ``steps`` decode steps: (seconds,
    which bound).  Bytes: per step every weight that does not depend on the
    routing once; the three matrices of each of the ``experts_touched``
    (held routed experts with a row, summed over layers and steps); the index
    keys scored, the rows chosen and the window rows read.  FLOPs are
    counted for one sequence a step and NO routed expert (the fewest)."""
    return counts.roofline_seconds(
        2.0 * steps * step_matmul_params(c) + sparse_read_flops(
            c, index_scored, index_chosen, window_read),
        (steps * step_matmul_params(c)
         + experts_touched * expert_params(c)) * bytes_per_param
        + sparse_read_bytes(c, index_scored, index_chosen, window_read),
        peak_flops, peak_bytes_per_s)


def sparse_attention_seconds(c, index_chosen: float, peak_flops: float,
                             peak_bytes_per_s: float,
                             bytes_per_value: int = 2):
    """Least seconds of attending ``index_chosen`` chosen rows (summed over
    sequences, steps and full planes), whatever implements it: each row read
    once for all heads and both products, against the absorbed form's
    operations."""
    return counts.roofline_seconds(
        index_chosen * attention_flops_per_row(c, FULL),
        index_chosen * row_values(c, FULL) * bytes_per_value,
        peak_flops, peak_bytes_per_s)
