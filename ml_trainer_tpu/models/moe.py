"""Mixture-of-Experts feed-forward — expert parallelism over the ``expert``
mesh axis.

The reference has no MoE (SURVEY.md §2C: expert parallel "not required");
this fills the reserved ``expert`` axis with the TPU-idiomatic GShard/
Mesh-TensorFlow formulation: experts live as ONE stacked parameter
[E, ...] sharded ``P('expert', ...)``, and routing is dense einsum algebra
over a capacity-bounded one-hot dispatch tensor — no gather/scatter, no
data-dependent shapes, so XLA lowers the whole layer onto the MXU and turns
the expert-axis shardings into the dispatch all-to-alls.

Top-1 routing (Switch-Transformer style) by default; ``num_selected=2``
gives GShard-style top-2 with renormalized gates and priority dispatch
(all first choices claim capacity before any second choice).  Capacity
factor + auxiliary load-balance loss (reported via ``self.sow`` so
trainers can add it) apply to both.
"""

from __future__ import annotations

from typing import Callable

import flax.linen as nn
import jax
import jax.numpy as jnp


class MoEMLP(nn.Module):
    """Drop-in replacement for the dense transformer MLP block.

    x: [B, S, M] -> [B, S, M]; E experts each an (M -> hidden -> M) MLP.
    Tokens route to their top-``num_selected`` experts, bounded by
    ``capacity = floor(capacity_factor * tokens * num_selected / E)``
    (min 1) per expert; overflow tokens fall through the residual
    (output 0 for the MLP branch).  With ``num_selected > 1`` gates renormalize over the
    selected experts (GShard) — at 1 the raw router probability is the
    gate (Switch), so the default reproduces the original behavior
    exactly.
    """

    num_experts: int
    hidden_dim: int
    capacity_factor: float = 1.25
    num_selected: int = 1
    dtype: jnp.dtype = jnp.float32
    activation: Callable = nn.gelu

    @nn.compact
    def __call__(self, x, train: bool = False):
        b, s, m = x.shape
        e = self.num_experts
        kk = self.num_selected
        if not 1 <= kk <= e:
            raise ValueError(
                f"num_selected must be in [1, num_experts={e}], got {kk}"
            )
        tokens = b * s
        capacity = max(int(self.capacity_factor * tokens * kk / e), 1)
        xt = x.reshape(tokens, m)

        # Router (always f32 — small matmul, numerics matter).
        router = nn.Dense(e, dtype=jnp.float32, name="router")
        probs = jax.nn.softmax(router(xt.astype(jnp.float32)), axis=-1)

        topk_probs, topk_idx = jax.lax.top_k(probs, kk)        # [T, K]
        masks = jax.nn.one_hot(topk_idx, e)                    # [T, K, E]
        gates = (
            topk_probs if kk == 1
            else topk_probs
            / jnp.sum(topk_probs, axis=-1, keepdims=True)
        )                                                      # [T, K]

        # Switch/GShard load-balance loss: E * sum(fraction * prob), with
        # the token fraction taken over FIRST choices (both papers').
        fraction = jnp.mean(masks[:, 0], axis=0)
        prob_mean = jnp.mean(probs, axis=0)
        self.sow(
            "losses", "moe_aux_loss",
            e * jnp.sum(fraction * prob_mean),
        )

        # Position of each token within its expert's capacity buffer,
        # priority-ordered: every first choice claims a slot before any
        # second choice (GShard's dispatch order); tokens past capacity
        # are dropped (residual passes them through).  K is static so
        # this unrolls into K cumsums.
        dispatch = jnp.zeros((tokens, e, capacity), jnp.float32)
        combine = jnp.zeros((tokens, e, capacity), jnp.float32)
        claimed = jnp.zeros((e,), jnp.float32)
        for sel in range(kk):
            mask_s = masks[:, sel]                              # [T, E]
            position = (
                jnp.cumsum(mask_s, axis=0) - 1.0 + claimed[None, :]
            ) * mask_s
            keep = (position < capacity) & (mask_s > 0)         # [T, E]
            onehot_pos = jax.nn.one_hot(
                jnp.clip(position, 0, capacity - 1).astype(jnp.int32),
                capacity,
            )                                                   # [T, E, C]
            slot = onehot_pos * keep[..., None]                 # [T, E, C]
            dispatch = dispatch + slot
            combine = combine + slot * gates[:, sel][:, None, None]
            claimed = claimed + jnp.sum(mask_s, axis=0)

        # Stacked expert weights, sharded over the expert mesh axis by the
        # EP_RULES PartitionSpecs (parallel/tp_rules.py).
        wi = self.param(
            "wi", nn.initializers.lecun_normal(batch_axis=(0,)),
            (e, m, self.hidden_dim),
        )
        wo = self.param(
            "wo", nn.initializers.lecun_normal(batch_axis=(0,)),
            (e, self.hidden_dim, m),
        )

        xin = jnp.einsum(
            "tec,tm->ecm", dispatch.astype(self.dtype), xt.astype(self.dtype)
        )                                                       # [E, C, M]
        h = self.activation(
            jnp.einsum("ecm,emh->ech", xin, wi.astype(self.dtype))
        )
        xout = jnp.einsum("ech,ehm->ecm", h, wo.astype(self.dtype))
        out = jnp.einsum(
            "tec,ecm->tm", combine.astype(self.dtype), xout
        )
        return out.reshape(b, s, m).astype(x.dtype)


class GatedMLP(nn.Module):
    """Gated feed-forward without biases: ``down(silu(gate x) * up x)``."""

    hidden_dim: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        dense = lambda n, name: nn.Dense(  # noqa: E731
            n, use_bias=False, dtype=self.dtype, param_dtype=self.dtype,
            name=name,
        )
        return dense(x.shape[-1], "down")(
            nn.silu(dense(self.hidden_dim, "gate")(x))
            * dense(self.hidden_dim, "up")(x)
        )


# Up to this many tokens a call, the routed layer multiplies every token by
# every held expert (see HeldExpertsMoE).  At T tokens that form does T
# operations for each byte of expert weights it reads, and a v5e's product
# is bound by memory up to 240: to about here the form costs what reading
# the experts once costs, whatever the routing.  (On the chip a prefill of
# 256 took 30.5-32.2 ms grouped, by the seed of the weights, of 512
# 39.4-41.5; PERF.md, PR 27.)
EVERY_EXPERT_ROWS = 256


def held_expert_counter_args(counters: dict, rows_in_flight: int,
                             num_selected: int) -> dict:
    """A decode step's ``HeldExpertsMoE`` counters as arguments of its fence
    span (a model's ``step_counter_args``).  ``counters["expert_rows"][0]``:
    ``[expert layers, experts held]``, the rows in flight whose token fell on
    each held expert."""
    per_layer = counters["expert_rows"][0]
    return {
        "expert_rows": float(per_layer.sum(axis=1).mean()),
        "expert_rows_max": float(per_layer.max(axis=1).mean()),
        "routed_rows": float(rows_in_flight * num_selected),
    }


def held_expert_counters_in_flight(counters: dict, in_flight) -> dict:
    """Inside the decode program (a model's ``reduce_step_counters``): the
    per-row ``HeldExpertsMoE`` counts summed over the rows in flight."""
    return jax.tree.map(
        lambda c: jnp.tensordot(in_flight, c, axes=1), counters)


class HeldExpertsMoE(nn.Module):
    """Dropless routed feed-forward over the experts THIS chip holds.

    The deployment divides a layer's ``num_experts`` over chips; this
    module is one chip's share.  The router keeps its published width:
    scores ``sigmoid(x Wr)`` in float32 over all ``num_experts``, the
    ``num_selected`` largest of ``score + bias`` chosen (the bias selects,
    it does not weigh), weights ``routed_scaling * score / sum of the
    chosen scores``.  Of the (token, expert) assignments those whose expert
    lies in ``experts_held = (first, count)`` are computed here.  No
    capacity and no dropped token, whatever the imbalance.  What the absent
    experts would add is left out (on the chips that hold them it is their
    part of the sum).  Each expert is a gated feed-forward of
    ``hidden_dim``; weights are stacked ``[count, ...]`` leaves in
    ``dtype``, the router and its bias float32.

    Two forms of the same sum, chosen by the number of tokens in the call
    (a shape, so each compiled program holds one):

    * up to ``EVERY_EXPERT_ROWS`` tokens (a decode step, a short prompt):
      every token through every held expert, the unchosen weighed 0.  The
      step reads each held expert once and takes the same time whatever the
      routing; the grouped form's time follows the fullest experts' tiles
      (on the chip a decode step swung 9% with the seed of the weights).
    * above it (a prefill): the held assignments sorted by expert, one
      ``jax.lax.ragged_dot`` a projection, so the work follows the
      assignments that land here and not tokens x experts.

    Returns ``(out, rows)``: the routed part ``[B, S, M]`` and, for the
    counters, how many of each row's tokens' assignments fell on each held
    expert, ``int32[B, count]``.
    """

    num_experts: int
    hidden_dim: int
    num_selected: int
    experts_held: tuple = ()  # (first, count); () holds all of them
    routed_scaling: float = 1.0
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        b, s, m = x.shape
        e, kk = self.num_experts, self.num_selected
        first, count = self.experts_held or (0, e)
        if not (0 <= first and first + count <= e and 1 <= kk <= e):
            raise ValueError(
                f"experts_held {self.experts_held} / num_selected {kk} do "
                f"not fit {e} experts"
            )
        tokens = b * s
        xt = x.reshape(tokens, m)
        router = self.param(
            "router", nn.initializers.normal(0.02), (m, e), jnp.float32)
        bias = self.param(
            "router_bias", nn.initializers.zeros, (e,), jnp.float32)
        scores = jax.nn.sigmoid(jnp.matmul(
            xt.astype(jnp.float32), router,
            precision=jax.lax.Precision.HIGHEST))               # [T, E]
        _, chosen = jax.lax.top_k(scores + bias, kk)            # [T, K]
        picked = jnp.take_along_axis(scores, chosen, axis=-1)
        gates = self.routed_scaling * picked / jnp.sum(
            picked, axis=-1, keepdims=True)
        local = chosen - first
        held = (local >= 0) & (local < count)
        local = jnp.where(held, local, count)     # absent experts: past all
        gates = jnp.where(held, gates, 0.0)

        stack = lambda name, shape: self.param(  # noqa: E731
            name, nn.initializers.normal(0.02), (count,) + shape, self.dtype
        ).astype(self.dtype)
        wg = stack("wg", (m, self.hidden_dim))
        wu = stack("wu", (m, self.hidden_dim))
        wd = stack("wd", (self.hidden_dim, m))
        form = (self._every_expert if tokens <= EVERY_EXPERT_ROWS
                else self._grouped)
        out = form(xt.astype(self.dtype), local, gates, wg, wu, wd)
        per_token = jnp.sum(
            jax.nn.one_hot(local, count, dtype=jnp.int32), axis=1)
        rows = per_token.reshape(b, s, count).sum(axis=1)
        return out.reshape(b, s, m).astype(x.dtype), rows

    def _every_expert(self, xt, local, gates, wg, wu, wd):
        """[T, M] through all ``count`` experts; a token's weight on an
        expert it did not choose is 0."""
        count = wg.shape[0]
        weights = jnp.sum(
            gates[:, :, None] * (local[:, :, None] == jnp.arange(count)),
            axis=1)                                             # [T, count]
        hidden = nn.silu(jnp.einsum("tm,emh->teh", xt, wg)) * jnp.einsum(
            "tm,emh->teh", xt, wu)
        return jnp.einsum(
            "teh,ehm->tm", hidden * weights[:, :, None].astype(self.dtype),
            wd, preferred_element_type=jnp.float32)

    def _grouped(self, xt, local, gates, wg, wu, wd):
        """The held assignments as grouped products: rows in order of their
        expert, those of absent experts last and outside every group."""
        tokens, kk = local.shape
        count = wg.shape[0]
        key = local.reshape(-1)                                 # [T*K]
        order = jnp.argsort(key)
        group_sizes = jnp.bincount(key, length=count + 1)[:count].astype(
            jnp.int32)
        rows_in = xt[order // kk]                               # [T*K, M]
        grouped = lambda t, w: jax.lax.ragged_dot(  # noqa: E731
            t, w, group_sizes)
        hidden = nn.silu(grouped(rows_in, wg)) * grouped(rows_in, wu)
        # Rows past the last group are not the product's to define.
        landed = (jnp.arange(tokens * kk) < jnp.sum(group_sizes))[:, None]
        rows_out = jnp.where(landed, grouped(hidden, wd), 0)
        # Back to token order (a gather, not a scatter), weighed and summed.
        back = jnp.argsort(order).reshape(tokens, kk)
        return jnp.einsum(
            "tkm,tk->tm", rows_out[back], gates.astype(self.dtype),
            preferred_element_type=jnp.float32)
