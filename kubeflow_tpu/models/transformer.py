"""Decoder-only Transformer LM — the framework's flagship model.

New capability relative to the reference (its model zoo was whatever TF
image you mounted; SURVEY.md §2.2), designed TPU-first:

  - bfloat16 activations, fp32 params; every matmul MXU-shaped
    (d_model/d_ff/head_dim multiples of 128 in real configs);
  - logical-axis annotations on every kernel (nn.with_logical_partitioning)
    so the parallel/mesh.py rule table alone decides dp/fsdp/tp/sp layout;
  - layers stacked with ``nn.scan``: one compiled block body regardless of
    depth (compile time O(1) in n_layers), with selective rematerialisation
    via ``nn.remat`` to trade FLOPs for HBM;
  - RoPE positions, RMSNorm, SwiGLU MLP, grouped-query attention —
    the contemporary LLM block;
  - attention dispatches to ops/ (XLA now, Pallas flash / ring attention
    over the `sequence` axis for long context).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from kubeflow_tpu.ops.attention import dot_product_attention

Dtype = Any

init = nn.initializers
kernel_init = init.lecun_normal()
embed_init = init.normal(stddev=0.02)


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32_000
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: int = 8
    d_ff: int = 1408
    head_dim: int = 64
    max_seq_len: int = 2048
    rope_theta: float = 10_000.0
    dropout_rate: float = 0.0
    dtype: Dtype = jnp.bfloat16
    remat: bool = False
    # Checkpoint policy under remat: "nobatch" saves only dots without
    # batch dims (minimum memory); "dots" saves every matmul output so
    # backward recomputes only elementwise/norms.  Measured equal on
    # v5e at the bench config (231 vs 233 ms/step — the flash kernel
    # recomputes its own internals either way), so the default is the
    # memory-minimal policy.
    remat_policy: str = "nobatch"
    # Save the flash kernel's (out, lse) residuals across the remat
    # boundary.  The Pallas custom call is invisible to dots_saveable, so
    # without this every rematted block re-runs the forward flash kernel
    # inside the backward pass just to rebuild the residuals its backward
    # kernels need — one full extra fwd attention pass per step (measured
    # ~13 ms/step at the v5e bench config, 231 -> 218 ms/step when saved).
    # Costs O(b*s*d) bf16 per layer of extra live memory; disable only
    # when that doesn't fit.
    save_attn_residuals: bool = True
    # Tie input embedding and output projection (small models benefit).
    tied_embeddings: bool = True
    # Attention backend: "dot" (XLA einsum), "flash" (Pallas kernel, heads
    # TP-sharded via shard_map when a mesh is given), "ring" (context
    # parallel over the `sequence` mesh axis; requires a mesh).
    attention: str = "dot"
    # On-chip sweep (v5e, seq 2048, head_dim 128, 188M LM, before PR 21):
    # k-block 1024 runs 4.8% faster than the old 512 default (231 vs
    # 242 ms/step); 2048 gives it back (234), larger q-blocks lose.
    # _fit_block clamps both to the actual sequence length.
    flash_block_q: int = 512
    flash_block_k: int = 1024
    # >0 = two-pass causal forward (ops/flash.py): full blocks at
    # (block_q, block_k) mask-free + the diagonal band at this fine
    # tiling, merged in log space — shrinks the masked-MAC waste of
    # diagonal-straddling blocks.  0 = classic single pass.
    flash_block_diag: int = 0
    # Mixture-of-Experts: 0 = dense MLP; >0 replaces every block's MLP
    # with a MoE layer of that many experts (expert-parallel over the
    # `expert` mesh axis; models/moe.py).
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_coef: float = 0.01
    # GShard routing group (tokens); dispatch-einsum cost per token is
    # proportional to it, capacity granularity inversely.  0 = the
    # measured per-impl optimum (einsum 128, gather 256 — each impl's
    # best from the on-chip sweeps; pinning one shared default would
    # silently pair the other impl with its worst config).  Sweep
    # history at the bench config (4 experts, ms/step): round-3
    # G-major einsums 128 -> 516, 256 -> 471, 512 -> 495,
    # 1024 -> 528; after the round-4 E-major rank-3 rework 64 -> 423,
    # 128 -> 421, 256 -> 427 — see models/moe.py for why the optimum
    # moved.
    moe_group_size: int = 0
    # MoE dispatch/combine implementation: "einsum" (GShard one-hot
    # contractions — the measured on-chip winner, MXU-bound) or
    # "gather" (slot-index scatter + row gathers, no O(g) contraction,
    # but XLA's dynamic-gather lowering loses ~12% end to end).  See
    # models/moe.py MoEMLP.impl for the sweep numbers.
    moe_impl: str = "einsum"
    # Cross-entropy input precision.  "f32" materializes the full
    # [b, s, vocab] logits tensor in float32 before the loss (simple,
    # maximally precise).  "compute" keeps logits in the compute dtype
    # and evaluates a fused max/logsumexp/gather loss with f32
    # accumulation — on a bf16 model the 4-byte logits copy (2.1 GB at
    # the bench config) never exists in HBM, and the loss cotangent is
    # half the bytes.  Loss differs only in bf16 rounding of individual
    # logits (reductions still accumulate f32).
    ce_dtype: str = "f32"
    # Sequence-chunked cross-entropy: >0 unembeds and evaluates the
    # loss `ce_chunk` positions at a time under a rematerialized
    # lax.scan, so no [b, s, vocab] logits tensor ever exists in HBM
    # (peak extra memory is O(b * chunk * vocab)).  The long-context
    # loss lever above ce_dtype: at seq 128k even bf16 logits are
    # 8.4 GB.  The effective chunk is the largest divisor of s <= this
    # (any s works); numerics follow ce_dtype within each chunk.
    # 0 = unchunked.
    ce_chunk: int = 0
    # Pipeline parallelism: >0 streams this many microbatches through the
    # layer stack under the GPipe schedule (parallel/pipeline.py) whenever
    # the model's mesh has a `pipeline` axis > 1.  The nn.scan param stack
    # [L, ...] is sharded L/S layers per stage via the ("layers", PIPELINE)
    # rule; embed / final norm / logits stay replicated across stages.
    # 0 (or a pipeline-less mesh) runs the plain sequential scan.
    pipeline_microbatches: int = 0
    # Epsilon of every RMSNorm, in training and in the serving programs.
    norm_eps: float = 1e-6
    # Looped stack (Ouro's ``total_ut_steps``): the n_layers blocks run
    # loop_steps times a token over ONE set of weights.  Every (step,
    # layer) pair attends keys and values of its own, so a KV cache has
    # loop_steps * n_layers planes (``kv_planes``) where the weights have
    # n_layers.  With more than one step the tree also holds the exit
    # gate (``exit_gate_w`` / ``exit_gate_b``), which no program reads:
    # every token takes every step, the published early-exit threshold
    # of 1.  The final norm also runs BETWEEN loop steps: step t+1 reads
    # the normed output of step t.
    loop_steps: int = 1
    # Sandwich norms: a second RMSNorm on the OUTPUT of each branch
    # (``attn_out_norm``, ``mlp_out_norm``), before the residual add.
    sandwich_norm: bool = False
    # A stack whose layers differ (LFM2's ``layer_types``): one entry a
    # layer, "full_attention" or "conv"; empty = every layer is the
    # attention block above.  A stack that states its layer types is
    # walked layer by layer over a tree with one entry a layer
    # (``params["layers"][str(i)]``, shapes in
    # ``layer_tree_shapes`` below) by the serving programs,
    # and only by them: training and generate() do not take it.
    #   "conv": a gated short convolution in the attention's place,
    #     [B, C, h] = y W_in; u = B * h; c_t = sum_i w_i u_{t-K+1+i}
    #     (depthwise, causal, ``conv_kernel`` = K taps, no bias);
    #     out = (C * c) W_out.  Its cache is the last K - 1 columns of u
    #     per sequence, of fixed size beside the paged KV pool, and only
    #     the attention layers own a KV plane (``kv_planes``).
    #   The feed-forward of such a stack: layers before
    #     ``moe_dense_layers`` keep the SwiGLU of ``d_ff``; with
    #     ``moe_experts`` > 0 the others hold that many SwiGLU experts of
    #     ``moe_d_ff`` and send each token to ``moe_top_k`` of them,
    #     nothing dropped: scores s = sigmoid(y W_r) in float32, the
    #     choice by s + bias (``moe/bias`` selects and does not weigh),
    #     weights s_i / (sum of the chosen s + 1e-6).
    #   ``qk_norm``: an RMSNorm over each head of q and of k, scales of
    #     their own, before the rotary positions.
    #   "shortcut_double" (LongCat-Flash's double layer): TWO attention
    #     sublayers and TWO dense SwiGLUs of ``d_ff`` a layer, and one
    #     expert layer that reads the first half's normed stream and is
    #     added at the layer's end (the shortcut):
    #       a = x + Attn_0(N_0(x));  m = N'_0(a);  s = Experts(m)
    #       b = a + Dense_0(m);      c = b + Attn_1(N_1(b))
    #       y = c + Dense_1(N'_1(c)) + s
    #     It owns two planes of the pool.  Every such layer holds
    #     experts (``moe_dense_layers`` must be 0).
    #   "sliding_attention": see ``window`` below.
    layer_types: Tuple[str, ...] = ()
    conv_kernel: int = 3
    qk_norm: bool = False
    moe_dense_layers: int = 0
    moe_d_ff: int = 0
    # The router of a stack with ``layer_types``, as fields (the
    # defaults are LFM2's): scores ``sigmoid`` or ``softmax`` of y W_r
    # in float32; the chosen scores divided by their sum or not; a
    # factor on the weights.  ``moe/bias`` always selects and never
    # weighs.
    moe_score: str = "sigmoid"
    moe_normalize: bool = True
    moe_scale: float = 1.0
    # Experts that need no weights: the router has ``moe_experts`` +
    # ``moe_zero_experts`` outputs, and a chosen output at or past
    # ``moe_experts`` returns its input (times its weight).
    moe_zero_experts: int = 0
    # The chip's share of the experts (expert parallelism without its
    # exchange): this program holds the weights of experts
    # [moe_experts_offset, moe_experts_offset + moe_experts_held) of
    # ``moe_experts`` (0 held = all of them), routes over every output,
    # and computes the part of the result that its own experts give,
    # plus the zero-compute experts' part, which belongs to the chip
    # that owns the token.  What an absent expert would add is left out.
    moe_experts_held: int = 0
    moe_experts_offset: int = 0
    # Attention of every attending layer: "gqa" (the block above) or
    # "latent" (MLA): queries through a low-rank pair with a norm
    # between (``mla_q_rank``), keys and values expanded from ONE
    # normed latent of ``mla_kv_rank`` a token, beside a rotary key of
    # ``mla_rope_dim`` that all heads share; a query / key head is
    # ``mla_nope_dim`` + ``mla_rope_dim`` wide, a value head
    # ``mla_v_dim``; rotary pairs are interleaved (2i, 2i + 1); the two
    # low-rank activations are scaled by sqrt(d_model / rank) after
    # their norms (``mla_rescale``).  The cache of a token and plane is
    # ``(latent, rotary key)``, key and value at once: ONE pool
    # ``cache_latent`` [planes, blocks, block_tokens, latent_row] in
    # place of a k and a v pool (``latent_row``: the two parts, padded
    # to whole 128-lane rows).  ``n_kv_heads`` / ``head_dim`` are unread.
    attention_kind: str = "gqa"
    mla_q_rank: int = 0
    mla_kv_rank: int = 0
    mla_nope_dim: int = 0
    mla_rope_dim: int = 0
    mla_v_dim: int = 0
    # A learned choice of the positions a "full_attention" layer of a
    # latent stack attends (DeepSeek-V3.2's indexer): ``index_topk`` > 0
    # gives every such layer ``index_heads`` index queries of
    # ``index_dim`` from the query's low-rank activation, ONE index key
    # of ``index_dim`` a token (a LayerNorm with scale and bias, cached
    # per token in a pool of its own, ``cache_index``), rotary pairs on
    # the first ``mla_rope_dim`` values of both, and a weight a head
    # ``w = u W_w * index_heads^-0.5 * index_dim^-0.5``:
    #   I(t, s) = sum_h w_h(t) relu(qI_h(t) . kI(s))  in float32, s <= t
    # The softmax of the layer's attention runs over the ``index_topk``
    # positions of largest I(t, .) (all of them while t < index_topk; ties
    # to the lower position) and over no other.
    index_heads: int = 0
    index_dim: int = 0
    index_topk: int = 0
    # "sliding_attention": a latent layer with head count, ranks and head
    # widths of its own (``window_*``; the rows of its planes lie in a
    # third pool, ``cache_window``, ``window_row`` wide) whose query at t
    # sees the positions (t - ``window``, t], its own among them, and
    # carries no indexer.
    window: int = 0
    window_heads: int = 0
    window_q_rank: int = 0
    window_kv_rank: int = 0
    window_nope_dim: int = 0
    window_rope_dim: int = 0
    window_v_dim: int = 0
    window_rope_theta: float = 10_000.0
    # A headwise gate on every latent layer's attention, before the
    # output projection: out_j = sigmoid(u W_g)_j * o_j, u the layer's
    # normed input, W_g [d_model, heads] (``attn/wg``), no bias.
    attn_gate: bool = False
    # ONE shared SwiGLU of this width beside the routed experts of every
    # sparse layer (``moe/shared``), added unweighted; it belongs to the
    # chip that owns the token (in a sum over shares it counts once).
    moe_shared_d_ff: int = 0
    # The factor sqrt(d_model / rank) on both low-rank activations of a
    # latent layer, after their norms (LongCat-Flash, dots3); False:
    # the norms alone (DeepSeek-V3).
    mla_rescale: bool = True
    # YaRN on a latent layer's rotary frequencies (``yarn_factor`` > 1):
    # pair i of ``mla_rope_dim`` / 2 turns at f_i = rope_theta^(-2i/d)
    # below ``low`` and at f_i / yarn_factor above ``high``, a linear
    # ramp between them, where low / high are the floor / ceiling of
    # d ln(yarn_original_len / (2 pi beta)) / (2 ln rope_theta) at
    # beta = yarn_beta_fast / yarn_beta_slow (``yarn_frequencies``).
    # ``mla_softmax_mult`` multiplies the softmax scale of a latent
    # layer (YaRN's (0.1 mscale_all_dim ln(factor) + 1)^2).
    yarn_factor: float = 1.0
    yarn_original_len: int = 0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    mla_softmax_mult: float = 1.0
    # Expert groups (DeepSeek-V3's ``n_group`` / ``topk_group``): the
    # router's ``moe_experts`` outputs are ``moe_groups`` runs of
    # consecutive experts; a group scores the sum of its 2 largest
    # (score + bias), the ``moe_groups_kept`` groups of largest score are
    # kept (ties to the lower group), and the ``moe_top_k`` are chosen
    # inside them.  0: no groups.  ``moe_norm_eps`` is what the sum of the
    # chosen scores gains before it divides them (``moe_normalize``).
    moe_groups: int = 0
    moe_groups_kept: int = 0
    moe_norm_eps: float = 1e-6
    # Multi-token-prediction modules (DeepSeek-V3's
    # ``num_nextn_predict_layers``; 0 or 1) that DRAFT in the serving
    # engine: ``mtp`` in the tree holds two norms, a projection of
    # [N_e(Emb(t_{i+1})); N_h(h_i)] (h_i after the final norm), one more
    # latent + expert layer and a norm before the main model's head; its
    # logits at i predict t_{i+2}.  The layer owns the LAST plane of the
    # latent pool, and keeps the row of (h_i, t_{i+1}) at index i + 1,
    # the position of the token it embeds (index 0 holds nothing and is
    # never attended), so that a page of it is a function of the page's
    # tokens and those before, as every other plane's.  A decode step
    # then runs the stack over TWO positions a slot, the last token and
    # the draft, and yields one token or two (models/generate.py).
    mtp_layers: int = 0

    def __post_init__(self):
        # Latent attention reads neither n_kv_heads nor head_dim.
        assert self.latent or self.n_heads % self.n_kv_heads == 0
        # JSON hands a list over; the config is a static (hashed) argument.
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if self.attention_kind not in ("gqa", "latent"):
            raise ValueError(
                f"attention_kind={self.attention_kind!r} not in "
                "('gqa', 'latent')")
        if self.moe_score not in ("sigmoid", "softmax"):
            raise ValueError(
                f"moe_score={self.moe_score!r} not in "
                "('sigmoid', 'softmax')")
        if self.latent:
            sizes = (self.mla_q_rank, self.mla_kv_rank, self.mla_nope_dim,
                     self.mla_rope_dim, self.mla_v_dim)
            if min(sizes) < 1 or self.mla_rope_dim % 2:
                raise ValueError(
                    "attention_kind='latent' needs mla_q_rank, "
                    "mla_kv_rank, mla_nope_dim, mla_rope_dim (even) and "
                    f"mla_v_dim, got {sizes}")
            if not self.layer_types or self.qk_norm:
                raise ValueError(
                    "attention_kind='latent' runs in a stack that states "
                    "its layer_types, without qk_norm (the rest: not "
                    "built)")
        indexer = (self.index_heads, self.index_dim, self.index_topk)
        if any(indexer) and (
                not self.latent or min(indexer) < 1
                or self.index_dim < self.mla_rope_dim):
            raise ValueError(
                "an indexer needs attention_kind='latent' and index_heads, "
                f"index_dim (>= mla_rope_dim) and index_topk, got {indexer}")
        if "sliding_attention" in self.layer_types:
            sizes = (self.window, self.window_heads, self.window_q_rank,
                     self.window_kv_rank, self.window_nope_dim,
                     self.window_rope_dim, self.window_v_dim)
            if not self.latent or min(sizes) < 1 \
                    or self.window_rope_dim % 2:
                raise ValueError(
                    "a sliding_attention layer is a latent layer with "
                    "window, window_heads, window_q_rank, window_kv_rank, "
                    "window_nope_dim, window_rope_dim (even) and "
                    f"window_v_dim, got {sizes}")
        if self.attn_gate and not self.latent:
            raise ValueError("attn_gate gates latent layers: "
                             "attention_kind='latent'")
        if self.moe_shared_d_ff and not self.layer_types:
            raise ValueError("moe_shared_d_ff belongs to the sparse layers "
                             "of a stack that states its layer_types")
        if "shortcut_double" in self.layer_types and (
                self.indexed or self.attn_gate or self.moe_shared_d_ff):
            raise ValueError(
                "a shortcut_double layer with an indexer, a gate or a "
                "shared expert: not built")
        if not self.mla_rescale and not self.latent:
            raise ValueError("mla_rescale belongs to latent layers: "
                             "attention_kind='latent'")
        yarn = self.yarn_factor != 1.0 or self.mla_softmax_mult != 1.0
        if yarn and (
                not self.latent or self.indexed or self.window_planes
                or "shortcut_double" in self.layer_types
                or self.yarn_factor < 1.0 or self.yarn_original_len < 1
                or not 0 < self.yarn_beta_slow < self.yarn_beta_fast):
            raise ValueError(
                "YaRN frequencies (yarn_factor >= 1, yarn_original_len, "
                "yarn_beta_fast > yarn_beta_slow > 0) and mla_softmax_mult "
                "belong to the full_attention layers of a latent stack; "
                "with gqa attention, an indexer, sliding_attention or "
                "shortcut_double layers: not built")
        if self.moe_groups or self.moe_groups_kept:
            groups, kept = self.moe_groups, self.moe_groups_kept
            if not self.layer_types or self.moe_zero_experts \
                    or groups < 1 or not 1 <= kept <= groups \
                    or self.moe_experts % groups \
                    or self.moe_experts // groups < 2 \
                    or kept * (self.moe_experts // groups) < self.moe_top_k:
                raise ValueError(
                    f"moe_groups={groups} with moe_groups_kept={kept}: "
                    f"groups divide the {self.moe_experts} experts of a "
                    "stack with layer_types into runs of 2 or more, the "
                    f"kept ones hold moe_top_k={self.moe_top_k} or more; "
                    "with zero-compute experts: not built")
        if self.mtp_layers:
            other = [name for name, on in (
                ("an indexer", self.indexed),
                ("sliding_attention layers", self.window_planes),
                ("shortcut_double layers",
                 "shortcut_double" in self.layer_types),
                ("a convolution state", "conv" in self.layer_types),
                ("an attention gate", self.attn_gate)) if on]
            if self.mtp_layers != 1 or not self.latent \
                    or not self.moe_experts or other:
                raise ValueError(
                    f"mtp_layers={self.mtp_layers}: ONE multi-token-"
                    "prediction module drafts for a latent stack with "
                    f"experts; with {other or 'anything else'}: not built")
        if self.moe_zero_experts or self.moe_experts_held \
                or self.moe_experts_offset:
            held, first = self.moe_experts_held, self.moe_experts_offset
            if not self.layer_types or min(
                    self.moe_zero_experts, held, first) < 0 \
                    or first + (held or self.moe_experts) \
                    > self.moe_experts:
                raise ValueError(
                    f"experts [{first}, {first} + {held}) of "
                    f"{self.moe_experts} with {self.moe_zero_experts} "
                    "zero-compute experts: not a share of a stack with "
                    "layer_types")
        if self.layer_types:
            kinds = {"full_attention", "conv", "shortcut_double",
                     "sliding_attention"}
            unknown = set(self.layer_types) - kinds
            if unknown or len(self.layer_types) != self.n_layers:
                raise ValueError(
                    f"layer_types must name n_layers={self.n_layers} "
                    f"layers as one of {sorted(kinds)}, got "
                    f"{self.layer_types}")
            if "shortcut_double" in self.layer_types and (
                    not self.moe_experts or self.moe_dense_layers):
                raise ValueError(
                    "a shortcut_double layer holds experts: moe_experts "
                    "> 0 and moe_dense_layers == 0")
            if self.loop_steps > 1 or self.sandwich_norm:
                raise ValueError(
                    "layer_types with loop_steps > 1 or sandwich_norm: "
                    "not built")
            if self.conv_kernel < 2:
                raise ValueError(
                    f"conv_kernel={self.conv_kernel} must be >= 2")
        if self.loop_steps < 1:
            raise ValueError(f"loop_steps={self.loop_steps} must be >= 1")
        if self.loop_steps > 1 and self.pipeline_microbatches:
            raise ValueError(
                "pipeline_microbatches requires loop_steps=1 (a looped "
                "stack would send every microbatch round the stage ring "
                "loop_steps times; not built)")
        if self.ce_dtype not in ("f32", "compute"):
            raise ValueError(
                f"ce_dtype={self.ce_dtype!r} not in ('f32', 'compute')")
        if self.pipeline_microbatches:
            # MoE composes (aux losses ride pipelined_scan's with_aux
            # accumulator) and ring composes (the GPipe shard_map goes
            # manual over {pipeline, sequence} and calls the per-shard
            # ring body directly — see _pipelined_layers).  Dropout is
            # the one documented residual: the functional per-layer
            # body threads no flax rngs, and every shipped config
            # trains at dropout 0 (the contemporary LLM default), so
            # the rng plumbing would be dead weight on the hot path.
            if self.dropout_rate:
                raise ValueError(
                    "pipeline_microbatches requires dropout_rate=0 "
                    "(the GPipe functional body does not thread "
                    "dropout rngs; all shipped configs train "
                    "dropout-free)")

    def resolved_moe_group_size(self) -> int:
        """The routing group actually used: the configured value, or
        each impl's measured on-chip optimum when left at 0 (the
        single source of truth is models/moe.py default_group_size)."""
        if self.moe_group_size:
            return self.moe_group_size
        from kubeflow_tpu.models.moe import default_group_size

        return default_group_size(self.moe_impl)

    @property
    def kv_planes(self) -> int:
        """Leading axis of a KV cache: one plane per (loop step, layer),
        of the layers that attend; the draft layer's (``mtp_layers``) is
        the last."""
        if self.layer_types:
            return self.layer_types.count("full_attention") \
                + 2 * self.layer_types.count("shortcut_double") \
                + self.window_planes + self.mtp_layers
        return self.loop_steps * self.n_layers

    @property
    def window_planes(self) -> int:
        """The planes of ``kv_planes`` that sliding_attention layers own,
        in the pool of their own row width."""
        return self.layer_types.count("sliding_attention")

    @property
    def latent(self) -> bool:
        return self.attention_kind == "latent"

    def latent_sizes(self, kind: str = "full_attention") -> "LatentSizes":
        """The sizes of a latent layer of ``kind``: the model's own, or a
        sliding_attention layer's (``window_*``)."""
        if kind == "sliding_attention":
            return LatentSizes(
                self.window_heads, self.window_q_rank, self.window_kv_rank,
                self.window_nope_dim, self.window_rope_dim,
                self.window_v_dim, self.window_rope_theta, self.window)
        yarn = None if self.yarn_factor == 1.0 else (
            self.yarn_factor, self.yarn_original_len, self.yarn_beta_fast,
            self.yarn_beta_slow)
        return LatentSizes(
            self.n_heads, self.mla_q_rank, self.mla_kv_rank,
            self.mla_nope_dim, self.mla_rope_dim, self.mla_v_dim,
            self.rope_theta, 0, yarn)

    @property
    def latent_row(self) -> int:
        """Values a token and plane of the latent pool holds: the normed
        latent, then the rotary key, each padded to whole 128-lane rows
        (the chip copies whole rows: 512 + 64 -> 512 + 128)."""
        return self.latent_sizes().row

    @property
    def window_row(self) -> int:
        """``latent_row`` of a sliding_attention layer's planes."""
        return self.latent_sizes("sliding_attention").row

    @property
    def indexed(self) -> bool:
        """Whether the full_attention layers choose their positions."""
        return self.index_topk > 0

    @property
    def moe_partial(self) -> bool:
        """Whether an expert layer's result is this chip's part of it, or
        holds pairs that need no weights: its pairs are then counted by
        where they fell (``init_paged_state``'s ``moe_pairs``)."""
        return bool(self.layer_types and self.moe_experts and (
            self.moe_zero_experts or self.moe_held != self.moe_experts))

    @property
    def moe_held(self) -> int:
        """Experts whose weights this program holds."""
        return self.moe_experts_held or self.moe_experts

    @property
    def conv_planes(self) -> int:
        """Layers that keep a convolution state per sequence."""
        return self.layer_types.count("conv")

    def layer_is_sparse(self, layer: int) -> bool:
        """Whether ``layer`` of a stack with ``layer_types`` holds
        experts (else the dense SwiGLU)."""
        return self.moe_experts > 0 and layer >= self.moe_dense_layers

    def flops_per_token(self) -> float:
        """Forward useful FLOPs per token (2*params matmul convention +
        attention term) — the MFU numerator, bwd counted as 2x by caller."""
        p_attn = self.d_model * self.head_dim * (
            self.n_heads + 2 * self.n_kv_heads
        ) + self.n_heads * self.head_dim * self.d_model
        p_mlp = 3 * self.d_model * self.d_ff
        if self.moe_experts > 0:
            # Useful MLP flops per token = the top_k experts it routes to
            # plus the router matmul; idle experts' weights are not work.
            p_mlp = self.moe_top_k * p_mlp \
                + self.d_model * self.moe_experts
        p_embed = self.vocab_size * self.d_model
        matmul = 2 * (self.kv_planes * (p_attn + p_mlp) + p_embed)
        attn = 2 * 2 * self.kv_planes * self.n_heads * self.head_dim \
            * self.max_seq_len  # qk^T + av, causal halving ignored
        return float(matmul + attn)


@dataclasses.dataclass(frozen=True)
class LatentSizes:
    """What ``_latent_attention_block`` reads of a latent layer."""
    heads: int
    q_rank: int
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    rope_theta: float
    window: int        # 0: every position up to the query's
    # None, or YaRN's (factor, original length, beta_fast, beta_slow).
    yarn: Optional[Tuple[float, int, float, float]] = None

    @property
    def row(self) -> int:
        def rows(n):
            return -(-n // 128) * 128

        return rows(self.kv_rank) + rows(self.rope_dim)


def _latent_tree(cfg: TransformerConfig, kind: str):
    """The ``attn`` leaves of a latent layer of ``kind``."""
    e, z = cfg.d_model, cfg.latent_sizes(kind)
    h, rq, rkv = z.heads, z.q_rank, z.kv_rank
    attn = {"wq_a": (e, rq), "q_norm": {"scale": (rq,)},
            "wq_b": (rq, h, z.nope_dim + z.rope_dim),
            "wkv_a": (e, rkv + z.rope_dim),
            "kv_norm": {"scale": (rkv,)},
            "wk_b": (h, z.nope_dim, rkv),
            "wv_b": (rkv, h, z.v_dim),
            "wo": (h, z.v_dim, e)}
    if cfg.attn_gate:
        attn["wg"] = (e, h)
    if cfg.indexed and kind == "full_attention":
        attn.update(wq_idx=(rq, cfg.index_heads, cfg.index_dim),
                    wk_idx=(e, cfg.index_dim),
                    k_idx_norm={"scale": (cfg.index_dim,),
                                "bias": (cfg.index_dim,)},
                    w_idx=(e, cfg.index_heads))
    return attn


def layer_tree_shapes(cfg: TransformerConfig):
    """The parameter tree of a stack with ``layer_types``, as nested
    {name: shape}: one entry a layer under ``layers`` (no two need agree,
    so nothing is stacked), each matrix a leaf of its own."""
    e, d, n = cfg.d_model, cfg.head_dim, cfg.moe_experts
    f = cfg.moe_d_ff or cfg.d_ff
    norm = {"scale": (e,)}
    tree = {"embed": (cfg.vocab_size, e), "final_norm": norm, "layers": {}}
    if not cfg.tied_embeddings:
        tree["w_out"] = (e, cfg.vocab_size)
    dense = {"wi": (2, e, cfg.d_ff), "wo": (cfg.d_ff, e)}
    outputs, held = n + cfg.moe_zero_experts, cfg.moe_held
    experts = {"router": (e, outputs), "bias": (outputs,),
               "wi": (held, e, 2 * f), "wo": (held, f, e)}
    if cfg.moe_shared_d_ff:
        experts["shared"] = {"wi": (2, e, cfg.moe_shared_d_ff),
                             "wo": (cfg.moe_shared_d_ff, e)}
    if cfg.latent:
        # An expanded key and value head lie in leaves of their own
        # (``wk_b``, ``wv_b``): a decode step absorbs the first into the
        # query and applies the second to the latent-space output, and a
        # slice of one leaf would be copied out a layer.  ``wk_b`` lies
        # head-major, [h, d_nope, r_kv]: the absorbing product is a
        # batch over heads that contracts d_nope, and from [r_kv, h,
        # d_nope] the chip's compiler relaid the matrix once a step and
        # sublayer.
        attn = _latent_tree(cfg, "full_attention")
    else:
        attn = {"wq": (e, cfg.n_heads, d),
                "wkv": (2, e, cfg.n_kv_heads, d),
                "wo": (cfg.n_heads, d, e)}
        if cfg.qk_norm:
            attn.update(q_norm={"scale": (d,)}, k_norm={"scale": (d,)})
    for i, kind in enumerate(cfg.layer_types):
        if kind == "shortcut_double":
            half = {"attn_norm": norm, "attn": attn, "mlp_norm": norm,
                    "mlp": dense}
            tree["layers"][str(i)] = {"half_0": half, "half_1": half,
                                      "moe": experts}
            continue
        layer = {"mlp_norm": norm}
        if kind == "conv":
            layer["conv_norm"] = norm
            layer["conv"] = {"w_in": (e, 3, e),
                             "w_conv": (cfg.conv_kernel, e),
                             "w_out": (e, e)}
        else:
            layer["attn_norm"] = norm
            layer["attn"] = _latent_tree(cfg, kind) \
                if kind == "sliding_attention" else attn
        if cfg.layer_is_sparse(i):
            layer["moe"] = experts
        else:
            layer["mlp"] = dense
        tree["layers"][str(i)] = layer
    if cfg.mtp_layers:
        # Embedding and head are the main model's.
        tree["mtp"] = {
            "enorm": norm, "hnorm": norm, "eh_proj": (2 * e, e),
            "layer": {"attn_norm": norm, "attn": attn, "mlp_norm": norm,
                      "moe": experts},
            "norm": norm}
    return tree


def yarn_softmax_mult(factor: float, mscale_all_dim: float) -> float:
    """``TransformerConfig.mla_softmax_mult`` under YaRN: the square of
    0.1 mscale_all_dim ln(factor) + 1 (1 without a factor)."""
    if factor <= 1.0:
        return 1.0
    return float(0.1 * mscale_all_dim * np.log(factor) + 1.0) ** 2


def yarn_frequencies(dim: int, theta: float, yarn) -> np.ndarray:
    """The ``dim`` / 2 rotary frequencies of a latent layer under YaRN
    (``TransformerConfig.yarn_factor``), float64: ``yarn`` is
    ``LatentSizes.yarn``."""
    factor, original, beta_fast, beta_slow = yarn
    freqs = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)

    def correction(turns):
        return dim * np.log(original / (2 * np.pi * turns)) \
            / (2 * np.log(theta))

    low = max(np.floor(correction(beta_fast)), 0)
    high = min(np.ceil(correction(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3),
                   0, 1)
    return freqs * (1 - ramp) + freqs / factor * ramp


def _hold(module: nn.Module, shapes):
    """Declare a nested {name: shape} of parameters on ``module`` (from
    inside its compact call) and hand them back as the same nesting of
    arrays: matrices lecun-normal, ``scale`` ones, ``bias`` zeros."""
    out = {}
    for name, shape in shapes.items():
        if isinstance(shape, tuple):
            make = {"scale": init.ones_init(),
                    "bias": init.zeros_init()}.get(name, kernel_init)
            out[name] = module.param(name, make, shape, jnp.float32)
        else:
            out[name] = _Leaves(shape, name=name)()
    return out


class _Leaves(nn.Module):
    shapes: Any

    @nn.compact
    def __call__(self):
        return _hold(self, self.shapes)


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary position embedding, applied per head. x: [b, s, h, d]."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = positions[..., None].astype(jnp.float32) * freqs  # [b, s, d/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


class RMSNorm(nn.Module):
    dtype: Dtype = jnp.bfloat16
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        scale = self.param(
            "scale",
            nn.with_logical_partitioning(init.ones_init(), ("norm",)),
            (x.shape[-1],),
            jnp.float32,
        )
        x32 = x.astype(jnp.float32)
        norm = x32 * jax.lax.rsqrt(
            jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps
        )
        return (norm * scale).astype(self.dtype)


class Attention(nn.Module):
    cfg: TransformerConfig
    mesh: Optional[jax.sharding.Mesh] = None
    # Inside an enclosing shard_map that is ALREADY manual over the
    # `sequence` axis (the GPipe pipeline path): call the per-shard ring
    # body directly instead of wrapping a second shard_map — nested
    # manual regions over the same mesh do not compose, exposing the
    # body does (parallel/ring.py ring_attention's documented contract).
    ring_manual: bool = False

    def _attend(self, q, k, v, segment_ids):
        cfg = self.cfg
        if cfg.attention == "ring":
            if self.ring_manual:
                from kubeflow_tpu.parallel.ring import ring_attention

                return ring_attention(q, k, v, causal=True)
            if self.mesh is None:
                raise ValueError("attention='ring' requires a mesh")
            from kubeflow_tpu.parallel.ring import make_ring_attention

            return make_ring_attention(self.mesh, causal=True)(q, k, v)
        if cfg.attention == "flash":
            from kubeflow_tpu.ops.flash import (
                flash_attention,
                make_sharded_flash,
            )

            if self.mesh is not None:
                return make_sharded_flash(
                    self.mesh, causal=True,
                    block_q=cfg.flash_block_q, block_k=cfg.flash_block_k,
                    block_diag=cfg.flash_block_diag,
                )(q, k, v)
            return flash_attention(
                q, k, v, causal=True,
                block_q=cfg.flash_block_q, block_k=cfg.flash_block_k,
                block_diag=cfg.flash_block_diag,
            )
        return dot_product_attention(q, k, v, causal=True,
                                     segment_ids=segment_ids)

    @nn.compact
    def __call__(self, x, positions, segment_ids=None):
        cfg = self.cfg
        wq = self.param(
            "wq",
            nn.with_logical_partitioning(kernel_init, ("embed", "heads", "kv")),
            (cfg.d_model, cfg.n_heads, cfg.head_dim),
            jnp.float32,
        )
        wkv = self.param(
            "wkv",
            nn.with_logical_partitioning(kernel_init, (None, "embed", "heads", "kv")),
            (2, cfg.d_model, cfg.n_kv_heads, cfg.head_dim),
            jnp.float32,
        )
        wo = self.param(
            "wo",
            nn.with_logical_partitioning(kernel_init, ("heads", "kv", "embed")),
            (cfg.n_heads, cfg.head_dim, cfg.d_model),
            jnp.float32,
        )
        dt = cfg.dtype
        q = jnp.einsum("bse,ehd->bshd", x, wq.astype(dt))
        k = jnp.einsum("bse,ehd->bshd", x, wkv[0].astype(dt))
        v = jnp.einsum("bse,ehd->bshd", x, wkv[1].astype(dt))
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
        q = nn.with_logical_constraint(q, ("batch", "seq", "heads", "kv"))
        k = nn.with_logical_constraint(k, ("batch", "seq", "heads", "kv"))
        out = self._attend(q, k, v, segment_ids)
        return jnp.einsum("bshd,hde->bse", out, wo.astype(dt))


class MLP(nn.Module):
    """SwiGLU feed-forward, column->row parallel under the rule table."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        wi = self.param(
            "wi",
            nn.with_logical_partitioning(kernel_init, (None, "embed", "mlp")),
            (2, cfg.d_model, cfg.d_ff),
            jnp.float32,
        )
        wo = self.param(
            "wo",
            nn.with_logical_partitioning(kernel_init, ("mlp", "embed")),
            (cfg.d_ff, cfg.d_model),
            jnp.float32,
        )
        dt = cfg.dtype
        gate = jnp.einsum("bse,ef->bsf", x, wi[0].astype(dt))
        up = jnp.einsum("bse,ef->bsf", x, wi[1].astype(dt))
        h = nn.silu(gate) * up
        h = nn.with_logical_constraint(h, ("batch", "seq", "mlp"))
        return jnp.einsum("bsf,fe->bse", h, wo.astype(dt))


class Block(nn.Module):
    """One decoder block in nn.scan carry form: (x, bcast...) -> (x, None)."""

    cfg: TransformerConfig
    deterministic: bool = True
    mesh: Optional[jax.sharding.Mesh] = None
    ring_manual: bool = False

    @nn.compact
    def __call__(self, x, positions, segment_ids):
        cfg = self.cfg

        def norm(name, y):
            return RMSNorm(dtype=cfg.dtype, eps=cfg.norm_eps, name=name)(y)

        y = norm("attn_norm", x)
        y = Attention(cfg, mesh=self.mesh, ring_manual=self.ring_manual,
                      name="attn")(y, positions, segment_ids)
        if cfg.sandwich_norm:
            with jax.named_scope("kft.loop_norm"):
                y = norm("attn_out_norm", y)
        if cfg.dropout_rate:
            y = nn.Dropout(cfg.dropout_rate,
                           deterministic=self.deterministic)(y)
        x = x + y
        y = norm("mlp_norm", x)
        if cfg.moe_experts > 0:
            from kubeflow_tpu.models.moe import MoEMLP

            y = MoEMLP(
                d_model=cfg.d_model, d_ff=cfg.d_ff,
                num_experts=cfg.moe_experts, top_k=cfg.moe_top_k,
                capacity_factor=cfg.moe_capacity_factor,
                group_size=cfg.resolved_moe_group_size(),
                dtype=cfg.dtype,
                impl=cfg.moe_impl,
                name="moe",
            )(y)
        else:
            y = MLP(cfg, name="mlp")(y)
        if cfg.sandwich_norm:
            with jax.named_scope("kft.loop_norm"):
                y = norm("mlp_out_norm", y)
        if cfg.dropout_rate:
            y = nn.Dropout(cfg.dropout_rate,
                           deterministic=self.deterministic)(y)
        x = x + y
        x = nn.with_logical_constraint(x, ("batch", "seq", "act_embed"))
        return x, None


def _remat_policy(cfg: TransformerConfig):
    """Checkpoint policy for one decoder block under remat (shared by the
    sequential nn.scan path and the GPipe per-layer body)."""
    policies = {
        "dots": jax.checkpoint_policies.dots_saveable,
        "nobatch":
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        # Save nothing but the (composed-below) flash residuals —
        # every matmul recomputes in the bwd.  The long-context
        # policy: at seq 32k the nobatch-saved MLP activations alone
        # are 2 x 2.06 GB and the program OOMs a 16 GB v5e; minimal
        # fits.
        "minimal": jax.checkpoint_policies.nothing_saveable,
    }
    if cfg.remat_policy not in policies:
        raise ValueError(
            f"remat_policy={cfg.remat_policy!r} not in "
            f"{sorted(policies)}")
    policy = policies[cfg.remat_policy]
    if cfg.attention == "flash" and cfg.save_attn_residuals:
        policy = jax.checkpoint_policies.save_from_both_policies(
            policy,
            jax.checkpoint_policies.save_only_these_names(
                "flash_out", "flash_lse"),
        )
    return policy


class Transformer(nn.Module):
    """LM: token ids [b, s] -> logits [b, s, vocab].

    With ``return_hidden=True`` the unembed projection is skipped and
    the call returns ``(hidden [b, s, d], unembed [v, d] or [d, v])``
    instead — the chunked-CE contract (lm_task, cfg.ce_chunk > 0).
    """

    cfg: TransformerConfig
    mesh: Optional[jax.sharding.Mesh] = None

    @nn.compact
    def __call__(
        self,
        tokens: jax.Array,
        *,
        positions: Optional[jax.Array] = None,
        segment_ids: Optional[jax.Array] = None,
        deterministic: bool = True,
        return_hidden: bool = False,
    ) -> "jax.Array | Tuple[jax.Array, jax.Array]":
        cfg = self.cfg
        if cfg.layer_types:
            # The tree the serving programs walk, and their forward over
            # the whole sequence without a cache.  Held here so that an
            # export of such a stack initialises and restores like any
            # other; the trainer's block (sharding names, remat, the
            # experts' balance loss) is not built.
            if positions is not None or segment_ids is not None \
                    or return_hidden:
                raise ValueError(
                    "a stack with layer_types takes default positions, "
                    "no segment_ids and no chunked loss")
            from kubeflow_tpu.models.generate import forward_layer_types

            params = _hold(self, layer_tree_shapes(cfg))
            return forward_layer_types(cfg, params, tokens)[0]
        embed = self.param(
            "embed",
            nn.with_logical_partitioning(embed_init, ("vocab", "embed")),
            (cfg.vocab_size, cfg.d_model),
            jnp.float32,
        )
        default_positions = positions is None
        if positions is None:
            positions = jnp.broadcast_to(
                jnp.arange(tokens.shape[1]), tokens.shape
            )
        x = embed.astype(cfg.dtype)[tokens]
        x = nn.with_logical_constraint(x, ("batch", "seq", "act_embed"))

        use_pipeline = (
            cfg.pipeline_microbatches > 0
            and self.mesh is not None
            and self.mesh.shape.get("pipeline", 1) > 1
            and not self.is_initializing()
        )
        final_norm = RMSNorm(dtype=cfg.dtype, eps=cfg.norm_eps,
                             name="final_norm")
        if use_pipeline:
            if not default_positions or segment_ids is not None:
                raise ValueError(
                    "the pipelined layer stack supports only default "
                    "positions and no segment_ids")
            x = self._pipelined_layers(x)
        else:
            block = nn.remat(Block, policy=_remat_policy(cfg)) \
                if cfg.remat else Block
            # One compiled body for all layers; params gain a leading
            # 'layers' dim, sharded over the `pipeline` mesh axis by the
            # rule table (a no-op at pipeline=1).
            layers = nn.scan(
                block,
                variable_axes={"params": 0, "losses": 0},
                split_rngs={"params": True, "dropout": True},
                length=cfg.n_layers,
                metadata_params={nn.PARTITION_NAME: "layers"},
                in_axes=(nn.broadcast, nn.broadcast),
            )(cfg, deterministic, self.mesh, name="layers")
            # A looped stack calls the ONE scanned module loop_steps
            # times: the same parameters every time.
            for step in range(cfg.loop_steps):
                x, _ = layers(x, positions, segment_ids)
                if step < cfg.loop_steps - 1:
                    with jax.named_scope("kft.loop_norm"):
                        x = final_norm(x)

        x = final_norm(x)
        if cfg.loop_steps > 1:
            # Held, not read (TransformerConfig.loop_steps).
            self.param(
                "exit_gate_w",
                nn.with_logical_partitioning(kernel_init, ("embed", None)),
                (cfg.d_model, 1), jnp.float32)
            self.param(
                "exit_gate_b",
                nn.with_logical_partitioning(init.zeros_init(), (None,)),
                (1,), jnp.float32)
        if cfg.tied_embeddings:
            unembed = embed
        else:
            unembed = self.param(
                "w_out",
                nn.with_logical_partitioning(kernel_init, ("embed", "vocab")),
                (cfg.d_model, cfg.vocab_size),
                jnp.float32,
            )
        if return_hidden:
            # Sequence-chunked CE (lm_task, cfg.ce_chunk > 0): the
            # caller unembeds chunk by chunk so the [b, s, vocab]
            # logits never materialize — at seq 128k they are 8.4 GB
            # even in bf16, past what remat can claw back.
            return x, unembed.astype(cfg.dtype)
        spec = "bse,ve->bsv" if cfg.tied_embeddings else "bse,ev->bsv"
        logits = jnp.einsum(spec, x, unembed.astype(cfg.dtype))
        if cfg.ce_dtype == "f32":
            return logits.astype(jnp.float32)
        return logits  # compute dtype; lm_task fuses the f32 reductions

    def _pipelined_layers(self, x: jax.Array) -> jax.Array:
        """GPipe path: parallel/pipeline.py's schedule over the real block.

        The nn.scan param stack [L, ...] (sharded L/S layers per stage over
        the `pipeline` axis by the ("layers", PIPELINE) rule) runs under
        ``pipelined_scan``: microbatches stream through the stage ring via
        ppermute.  shard_map is manual over the pipeline axis (plus the
        sequence axis under ring attention, below) — batch/fsdp/tensor
        stay auto, so XLA still inserts the usual data/tensor collectives
        inside each stage.  Embedding, final norm, and logits run
        replicated across stages (cheap next to the L blocks; the psum at
        the schedule's end hands every stage the full activations).

        Compositions (VERDICT r4 item 3):
          * MoE: each block.apply collects its sown load-balance loss,
            which rides pipelined_scan's ``with_aux`` accumulator
            (bubble steps masked), is averaged over microbatches (the
            sown aux is a token-mean — a model property, not a
            per-microbatch sum), and re-sown at the Transformer level so
            lm_task's existing "losses" plumbing sees it unchanged.
          * Ring attention: ONE shard_map manual over BOTH
            {pipeline, sequence}; each stage calls the per-shard ring
            body (parallel/ring.py ring_attention) directly — nesting a
            second shard_map would not compose.  Activations enter
            sequence-sharded, positions are offset per shard, and the
            block's logical "seq" constraints are re-mapped to None for
            the trace (a constraint naming a manual axis is an error).
        """
        import contextlib
        import functools

        from jax.sharding import PartitionSpec as P

        from kubeflow_tpu.parallel.mesh import PIPELINE, SEQUENCE
        from kubeflow_tpu.parallel.pipeline import (
            microbatch,
            pipelined_scan,
            unmicrobatch,
        )

        cfg = self.cfg
        n_micro = cfg.pipeline_microbatches
        n_stages = self.mesh.shape[PIPELINE]
        if x.shape[0] % n_micro:
            raise ValueError(
                f"batch {x.shape[0]} not divisible by "
                f"pipeline_microbatches={n_micro}")
        if cfg.n_layers % n_stages:
            raise ValueError(
                f"n_layers={cfg.n_layers} not divisible by "
                f"pipeline={n_stages} stages")
        ring = cfg.attention == "ring"
        with_aux = cfg.moe_experts > 0
        seq_ax = self.mesh.shape.get(SEQUENCE, 1)
        if ring and x.shape[1] % seq_ax:
            raise ValueError(
                f"seq {x.shape[1]} not divisible by sequence={seq_ax}")
        stacked = nn.unbox(self.get_variable("params", "layers"))
        block = Block(cfg, deterministic=True, mesh=None,
                      ring_manual=ring)
        if ring:
            # "seq" (and any other SEQUENCE-mapped logical name) must
            # not resolve to the now-manual axis inside the body.
            ring_rules = tuple(
                (name,
                 None if axes == SEQUENCE else
                 tuple(a for a in axes if a != SEQUENCE)
                 if isinstance(axes, tuple) else axes)
                for name, axes in nn.get_logical_axis_rules())

        def body(layer_params, act):
            s_local = act.shape[1]
            offset = (jax.lax.axis_index(SEQUENCE) * s_local if ring
                      else 0)
            pos = jnp.broadcast_to(
                offset + jnp.arange(s_local), act.shape[:2])
            ctx = (nn.logical_axis_rules(list(ring_rules)) if ring
                   else contextlib.nullcontext())
            with ctx:
                if with_aux:
                    (out, _), sown = block.apply(
                        {"params": layer_params}, act, pos, None,
                        mutable=["losses"])
                    aux = sum(jnp.sum(v) for v in
                              jax.tree_util.tree_leaves(sown["losses"]))
                    return out, aux
                out, _ = block.apply(
                    {"params": layer_params}, act, pos, None)
                return out

        if cfg.remat:
            body = jax.checkpoint(body, policy=_remat_policy(cfg))

        pipe_specs = jax.tree_util.tree_map(lambda _: P(PIPELINE), stacked)
        act_spec = P(None, SEQUENCE) if ring else P()

        @functools.partial(
            jax.shard_map, mesh=self.mesh,
            in_specs=(pipe_specs, act_spec),
            out_specs=(act_spec, P()) if with_aux else act_spec,
            axis_names={PIPELINE, SEQUENCE} if ring else {PIPELINE},
        )
        def run(params, act):
            act = act.astype(cfg.dtype)
            res = pipelined_scan(body, params, microbatch(act, n_micro),
                                 with_aux=with_aux)
            if not with_aux:
                return unmicrobatch(res).astype(jnp.float32)
            ys, aux = res
            # The sown aux is a mean over (local) tokens: averaging
            # over microbatches — and over sequence shards under ring —
            # restores the sequential path's scale; summing would
            # multiply the balance penalty by M (x seq shards).
            aux = aux / n_micro
            if ring:
                aux = jax.lax.pmean(aux, SEQUENCE)
            return unmicrobatch(ys).astype(jnp.float32), aux

        # Activations cross the shard_map boundary in f32 (cast back to
        # the compute dtype on each side): the boundary's transpose
        # inserts a psum over the pipeline axis for the activation
        # cotangent, and XLA's partitioner aborts on sub-f32 all-reduce
        # inside a partial-manual region (same bug pipelined_scan works
        # around for its own output psum).
        if with_aux:
            out, aux = run(stacked, x.astype(jnp.float32))
            # Re-sown at this level so lm_task's existing losses
            # plumbing (mutable=["losses"], sum of leaves) is unchanged.
            self.sow("losses", "pipeline_moe_aux", aux)
            return out.astype(cfg.dtype)
        return run(stacked, x.astype(jnp.float32)).astype(cfg.dtype)


def lm_task(cfg: TransformerConfig, mesh=None):
    """(init_fn, loss_fn) pair for Trainer: next-token cross-entropy.

    Batch contract: {"tokens": [b, s] int32}; loss predicts tokens[1:].
    """
    import optax

    model = Transformer(cfg, mesh=mesh)

    def init_fn(rng):
        # Shapes only seed parameter shapes, but sharded attention backends
        # (ring/flash via shard_map) trace with them — keep both batch and
        # seq divisible by the relevant mesh axes.
        b, s = 1, min(cfg.max_seq_len, 16)
        if mesh is not None:
            b = mesh.shape.get("data", 1) * mesh.shape.get("fsdp", 1)
            s_ax = mesh.shape.get("sequence", 1)
            s = max(s, s_ax) // s_ax * s_ax
        toks = jnp.zeros((b, s), jnp.int32)
        variables = model.init(rng, toks)
        return variables["params"], {}

    def ce_per_position(lg, tgt):
        """Per-position CE [*, n] from logits [*, n, v], honoring
        cfg.ce_dtype (shared by the unchunked and chunked paths)."""
        if cfg.ce_dtype == "f32":
            return optax.softmax_cross_entropy_with_integer_labels(
                lg.astype(jnp.float32), tgt)
        # Fused CE on compute-dtype logits: each reduction upcasts
        # per element inside its own fusion, so the only [*, n, v]
        # tensors in HBM are the compute-dtype logits — no 4-byte
        # copy, and the backward's softmax cotangent stays narrow.
        m = jax.lax.stop_gradient(jnp.max(lg, axis=-1, keepdims=True))
        # Subtract in f32 (exact; the casts fuse into the reduce — no
        # [*, n, v] f32 tensor hits HBM): the only precision
        # difference vs the f32 path is the narrow storage of the
        # logits themselves.
        lse = jnp.log(jnp.sum(
            jnp.exp(lg.astype(jnp.float32) - m.astype(jnp.float32)),
            axis=-1,
        )) + m[..., 0].astype(jnp.float32)
        target_logit = jnp.take_along_axis(
            lg, tgt[..., None], axis=-1
        )[..., 0].astype(jnp.float32)
        return lse - target_logit

    def chunked_ce(hidden, unembed, tokens):
        """Mean next-token CE without materializing [b, s, vocab]:
        unembed + loss run `chunk` positions at a time under a
        rematerialized scan (backward recomputes each chunk's logits).
        The final position has no target; a zero weight masks it so
        chunks can tile all s positions regardless of divisibility of
        s - 1 (at seq 128k, s - 1 is prime)."""
        from kubeflow_tpu.models.moe import fit_divisor

        b, s = tokens.shape
        chunk = fit_divisor(
            s, cfg.ce_chunk, "ce_chunk",
            "The chunked CE collapses toward an s-iteration scan of "
            "single-position unembeds (looks like a hang).  Choose a "
            "sequence length with a divisor close to ce_chunk.")
        n = s // chunk
        targets = jnp.concatenate(
            [tokens[:, 1:], jnp.zeros((b, 1), tokens.dtype)], axis=1)
        weights = jnp.concatenate(
            [jnp.ones((b, s - 1), jnp.float32),
             jnp.zeros((b, 1), jnp.float32)], axis=1)
        spec = "bce,ve->bcv" if cfg.tied_embeddings else "bce,ev->bcv"

        def body(total, inp):
            hc, tc, wc = inp
            lg = jnp.einsum(spec, hc, unembed)
            return total + jnp.sum(ce_per_position(lg, tc) * wc), None

        total, _ = jax.lax.scan(
            jax.checkpoint(body),
            jnp.zeros((), jnp.float32),
            (hidden.reshape(b, n, chunk, -1).transpose(1, 0, 2, 3),
             targets.reshape(b, n, chunk).transpose(1, 0, 2),
             weights.reshape(b, n, chunk).transpose(1, 0, 2)),
        )
        return total / (b * (s - 1))

    def loss_fn(params, mutable, batch, rng):
        del mutable
        tokens = batch["tokens"]
        apply_kwargs = dict(
            deterministic=False, rngs={"dropout": rng},
            return_hidden=cfg.ce_chunk > 0,
        )
        if cfg.moe_experts > 0:
            out, sown = model.apply(
                {"params": params}, tokens,
                mutable=["losses"], **apply_kwargs,
            )
        else:
            out = model.apply({"params": params}, tokens, **apply_kwargs)
        if cfg.ce_chunk > 0:
            hidden, unembed = out
            loss = chunked_ce(hidden, unembed, tokens)
        else:
            logits = out
            loss = ce_per_position(
                logits[:, :-1], tokens[:, 1:]).mean()
        metrics = {"perplexity": jnp.exp(loss)}
        if cfg.moe_experts > 0:
            aux = sum(jnp.sum(v) for v in
                      jax.tree_util.tree_leaves(sown["losses"]))
            metrics["moe_aux"] = aux
            loss = loss + cfg.moe_aux_coef * aux
        return loss, (metrics, {})

    return init_fn, loss_fn
