"""Mixture-of-Experts MLP with expert parallelism.

Absent from the reference (SURVEY.md §2.3 "Expert parallel: absent").
GShard-style dense dispatch, shaped for the TPU:

  - routing, dispatch and combine are einsums (MXU work, no gather/scatter
    with dynamic shapes — XLA keeps static tiling);
  - **grouped dispatch**: tokens are routed in fixed-size groups, each
    filling its own per-group expert slots (GShard's group dimension).
    The one-hot dispatch/combine einsums cost O(tokens * E*C * d); with a
    single group E*C grows with top_k * tokens, making dispatch O(N^2 d)
    — measured 675 ms/step at the bench config, dwarfing the experts
    themselves.  Fixed groups make E*C a constant (group * top_k *
    capacity_factor), so dispatch is linear in N;
  - fixed per-group expert capacity C = ceil(group * top_k / E *
    capacity_factor) (slots scale with top_k, the GShard convention —
    otherwise uniform top-2 routing already drops second choices):
    tokens over capacity are dropped (residual connection carries them),
    the standard trade for static shapes;
  - expert weight tensors carry the ("expert", ...) logical axis, so the
    rule table places experts on the `expert` mesh axis and XLA inserts
    the all-to-alls implied by the dispatch einsums;
  - Switch-style load-balancing aux loss over ALL tokens (not per group),
    sown into the "losses" collection (models/transformer.py threads it
    into the train loss).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from flax import linen as nn

kernel_init = nn.initializers.lecun_normal()


def default_group_size(impl: str) -> int:
    """Measured per-impl routing-group optimum (v5e bench config):
    einsum 128 (dispatch one-hot cost is linear in the group), gather
    256 (smaller groups degrade its scatter/gather, 28.1k vs 31.0k
    tok/s).  Single source of truth for the group_size=0 sentinel."""
    return 256 if impl == "gather" else 128


def fit_divisor(n: int, limit: int, label: str, consequence: str) -> int:
    """Largest divisor of ``n`` <= ``limit`` — the trace-time tiling
    fit shared by the MoE routing-group and the chunked-CE scan (a gcd
    shortcut degenerates badly for counts sharing few factors with a
    power-of-two limit: gcd(2046, 256) = 2).

    The scan itself can still degenerate for prime-ish ``n`` (the fit
    collapses toward 1); below limit//4 a trace-time warning names the
    ``label`` and its ``consequence`` so the config is fixed rather
    than silently paid every step."""
    want = min(limit, n)
    got = next(c for c in range(want, 0, -1) if n % c == 0)
    if got < want // 4:
        import warnings

        warnings.warn(
            f"{label} degenerated: {n} has no divisor near {limit} "
            f"(fitted {got}).  {consequence}",
            stacklevel=3,
        )
    return got


class MoEMLP(nn.Module):
    """Drop-in replacement for the dense SwiGLU MLP block."""

    d_model: int
    d_ff: int
    num_experts: int
    top_k: int = 2
    capacity_factor: float = 1.25
    # Routing group size (tokens): dispatch cost per token is
    # proportional to it, capacity granularity (and drop variance)
    # inversely.  The effective size is a divisor of the token count <=
    # this (gcd fallback), so any batch shape works.  0 = each impl's
    # measured optimum (default_group_size above).  Sweep on v5e with
    # the E-major rank-3 einsums: 128 wins (MFU 0.404 vs 0.399 at 256,
    # dispatch one-hot cost halved) and 64 plateaus (0.402) while
    # shrinking per-group statistics.
    group_size: int = 0
    dtype: object = jnp.bfloat16
    # Dispatch/combine implementation:
    #   "einsum" — GShard one-hot einsums: dispatch builds a [g, E, C]
    #     one-hot tensor and contracts over the g tokens, O(g*E*C*d)
    #     MACs each way.  The contraction is pure token MOVEMENT priced
    #     as MXU work — but the MXU is exactly where the TPU is fast.
    #   "gather" — the same routing decisions materialized as indices:
    #     a [E, C] slot->token scatter, a row gather into the expert
    #     batch (O(E*C*d) bytes moved, no MACs), and a per-choice row
    #     gather back out (O(g*top_k*d)).  Identical numerics and drop
    #     semantics; the g-fold reduction dimension disappears.
    # An on-chip sweep before PR 1 (v5e, 4 experts, top-2; its record is
    # gone, so not re-measured since) had einsum ahead of gather, each
    # at its own best group size (the group_size=0 sentinel).  The
    # asymptotic-MAC win loses to XLA's dynamic-gather lowering
    # (vector-unit + HBM bound); the one-hot contractions ride the MXU.
    impl: str = "einsum"

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg_e, d, f = self.num_experts, self.d_model, self.d_ff
        b, s, _ = x.shape
        n_tokens = b * s
        group_size = self.group_size or default_group_size(self.impl)
        g = fit_divisor(
            n_tokens, group_size, "MoE routing group",
            "Per-group capacity clamps to top_k and expert "
            "compute/memory inflates by up to num_experts/top_k x.  "
            "Choose batch*seq with a divisor close to group_size.")
        n_groups = n_tokens // g
        capacity = max(
            self.top_k,
            int(math.ceil(g * self.top_k / cfg_e * self.capacity_factor)),
        )

        wr = self.param(
            "router",
            nn.with_logical_partitioning(kernel_init, ("embed", "expert")),
            (d, cfg_e), jnp.float32,
        )
        wi = self.param(
            "wi",
            nn.with_logical_partitioning(
                kernel_init, ("expert", None, "embed", "mlp")),
            (cfg_e, 2, d, f), jnp.float32,
        )
        wo = self.param(
            "wo",
            nn.with_logical_partitioning(
                kernel_init, ("expert", "mlp", "embed")),
            (cfg_e, f, d), jnp.float32,
        )

        tokens = x.reshape(n_groups, g, d)
        # Routing in fp32 (softmax stability matters more than MXU here).
        logits = jnp.einsum(
            "gnd,de->gne", tokens.astype(jnp.float32), wr)
        probs = jax.nn.softmax(logits, axis=-1)

        # Top-k dispatch with per-group capacity.  Greedy per-choice
        # cumsum positions along the token axis of each group.
        gate_vals, gate_idx = jax.lax.top_k(probs, self.top_k)  # [G, g, k]
        # Renormalise the kept gates.
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)

        # Greedy per-choice routing: slot positions along each group's
        # token axis via cumsum (shared by both implementations).
        route_idx, route_pos, route_keep = [], [], []      # [k] x [G, g]
        counts = jnp.zeros((n_groups, cfg_e), jnp.int32)
        for choice in range(self.top_k):
            idx = gate_idx[..., choice]                    # [G, g]
            onehot = jax.nn.one_hot(idx, cfg_e, dtype=jnp.int32)
            pos = counts[:, None, :] + jnp.cumsum(onehot, axis=1) - 1
            my_pos = jnp.take_along_axis(
                pos, idx[..., None], axis=2)[..., 0]       # [G, g]
            counts = counts + onehot.sum(1)
            route_idx.append(idx)
            route_pos.append(my_pos)
            route_keep.append(my_pos < capacity)

        dt = self.dtype
        if self.impl == "gather":
            # Slot -> source-token index map, built by scatter (a [g]
            # write per choice; dropped tokens write column `capacity`,
            # which is out of bounds and dropped).  Sentinel g points at
            # the zero row appended to the token table, so unfilled
            # slots read zeros exactly as the one-hot contraction gave.
            slot_src = jnp.full((n_groups, cfg_e, capacity), g, jnp.int32)
            token_ids = jnp.broadcast_to(
                jnp.arange(g)[None, :], (n_groups, g))
            for choice in range(self.top_k):
                pos_or_oob = jnp.where(
                    route_keep[choice], route_pos[choice], capacity)
                slot_src = jax.vmap(
                    lambda s, e, p, t: s.at[e, p].set(t, mode="drop")
                )(slot_src, route_idx[choice], pos_or_oob, token_ids)
            tokens_pad = jnp.concatenate(
                [tokens.astype(dt),
                 jnp.zeros((n_groups, 1, d), dt)], axis=1)
            expert_in = jax.vmap(lambda tp, ss: tp[ss])(
                tokens_pad, slot_src)                      # [G, E, C, d]
        else:
            if self.impl != "einsum":
                raise ValueError(f"unknown moe impl {self.impl!r}")
            # One contrib tensor per choice feeds BOTH the dispatch and
            # combine accumulations — the drop/sentinel logic lives in
            # exactly one place.
            dispatch = jnp.zeros(
                (n_groups, g, cfg_e, capacity), jnp.bfloat16)
            combine = jnp.zeros(
                (n_groups, g, cfg_e, capacity), jnp.float32)
            for choice in range(self.top_k):
                onehot = jax.nn.one_hot(
                    route_idx[choice], cfg_e, dtype=jnp.float32)
                pos_onehot = jax.nn.one_hot(
                    jnp.where(route_keep[choice], route_pos[choice],
                              capacity),
                    capacity + 1, dtype=jnp.float32)[..., :capacity]
                contrib = onehot[..., :, None] * pos_onehot[..., None, :]
                dispatch = dispatch + contrib.astype(jnp.bfloat16)
                combine = combine \
                    + contrib * gate_vals[..., choice, None, None]
            # Expert axis LEADING on the dispatch output: the expert
            # einsums batch over E, and producing [G, E, C, d] makes
            # XLA materialize a G<->E transpose between dispatch and
            # the first expert matmul (profiled at ~18 ms/step, ~4% of
            # the MoE step, pure data movement).  E-major feeds them
            # in place.
            expert_in = jnp.einsum(
                "gnec,gnd->egcd", dispatch, tokens.astype(jnp.bfloat16))

        def expert_mlp(x, spec, x_axes, h_axes):
            """Batched SwiGLU over the expert slot tensor; `spec` is the
            up-projection einsum (its transpose is the down-projection),
            `x_axes`/`h_axes` the logical shardings of the input and
            the f-dim activations."""
            x = nn.with_logical_constraint(x, x_axes)
            lhs, out = spec.split("->")
            lhs = lhs.split(",")[0]
            gate = jnp.einsum(spec, x, wi[:, 0].astype(dt))
            up = jnp.einsum(spec, x, wi[:, 1].astype(dt))
            h = nn.with_logical_constraint(nn.silu(gate) * up, h_axes)
            return jnp.einsum(f"{out},efd->{lhs}", h, wo.astype(dt))

        if self.impl == "gather":
            # The slot map is [G, E, C]; vmap over G builds [G, E, C,
            # d], and the combine row-gathers index it per group.
            expert_out = expert_mlp(
                expert_in, "gecd,edf->gecf",
                (None, "expert", None, None),
                (None, "expert", None, "mlp"))
        else:
            # [E, G*C, d] — one big MXU batch, expert axis outermost
            # end to end (dispatch through combine).  The G and C dims
            # are collapsed for the matmuls: rank-3 inputs lower to one
            # clean batched dot per expert, where the rank-4 form kept
            # G as a second batch dim.
            expert_out = expert_mlp(
                expert_in.reshape(cfg_e, n_groups * capacity, d),
                "end,edf->enf", ("expert", None, None),
                ("expert", None, "mlp"),
            ).reshape(cfg_e, n_groups, capacity, d)

        if self.impl == "gather":
            # Each token reads its top_k slots back out: a per-choice
            # row gather weighted by the (renormalized, kept) gates.
            out = jnp.zeros((n_groups, g, d), dt)
            for choice in range(self.top_k):
                rows = jax.vmap(lambda eo, e, p: eo[e, p])(
                    expert_out, route_idx[choice],
                    jnp.clip(route_pos[choice], 0, capacity - 1),
                )                                          # [G, g, d]
                w = (gate_vals[..., choice]
                     * route_keep[choice]).astype(dt)[..., None]
                out = out + rows * w
        else:
            out = jnp.einsum(
                "gnec,egcd->gnd", combine.astype(dt), expert_out)

        # Switch load-balance loss: E * sum_e (fraction of tokens routed
        # to e) * (mean router prob of e); minimised by uniform routing.
        # Global over all tokens — routing balance is a model property,
        # not a per-group one.
        top1 = jax.nn.one_hot(
            gate_idx[..., 0].reshape(n_tokens), cfg_e, dtype=jnp.float32)
        fraction = top1.mean(0)
        mean_prob = probs.reshape(n_tokens, cfg_e).mean(0)
        aux = cfg_e * jnp.sum(fraction * mean_prob)
        self.sow("losses", "moe_aux", aux)

        return out.reshape(b, s, d).astype(self.dtype)
