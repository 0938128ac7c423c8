"""``brumby`` decoder (Brumby-14B-Base's ``model_type``): a dense decoder
whose every layer is a POWER RETENTION layer (power attention with a gate,
arXiv:2507.04239, power 2) in a block of grouped heads with a query-key
RMSNorm, rotary positions and a gated feed-forward.

One layer, token ``x`` at position ``t``, pre-norm (``~x = RMSNorm(x)``, ``h
= x + Attn(~x)``, ``x' = h + FFN(~h)``), no bias anywhere:

* ``q = RoPE_t(RMSNorm(Wq ~x))`` (``num_heads`` heads of ``head_dim``), ``k
  = RoPE_t(RMSNorm(Wk ~x))``, ``v = Wv ~x`` (``num_kv_heads`` heads), the
  norms over each head with a learned scale, the rotation ``rotate_half``
  at base ``rope_theta`` (``models/exaone_moe.py``'s).
* ``log g = log sigmoid(wg . ~x)``: one scalar a key-value head a token.
* the retention of ``ops/power_retention.py``: query head ``h`` reads the
  state of key-value head ``h // (num_heads / num_kv_heads)``.
* ``Attn = Wo concat_h(o_h)``; ``FFN = Wd (silu(Wg ~h) * Wu ~h)``.

Served through the slot engine with a per-slot STATE in the ``cache``
collection and no cache of tokens (the engine tells leaves apart by rank
alone): a layer's ``state [slots, kv_heads, head_dim, P]`` and ``norm
[slots, kv_heads, P]``, float32, ``P`` the ``D = head_dim (head_dim + 1) /
2`` entries of ``phi`` in whole rows of ``head_dim`` lanes (8,320 for 8,256
at 128), REPLACED every step whatever the context in ONE pass over the pool
(``ops/kernels/retention_state_step.py``), and its per-row ``cache_index``
(the rotary position).  A slot costs ``kv_heads x P x (head_dim + 1) x 4``
bytes a layer (34.3 MB at the published sizes): the pool is sized by state,
not by positions.  A prompt (``S > 1``) runs the chunked form from a FRESH
state (the contract is an empty cache); a decode step (``S == 1``) the
one-token form.  ``true_len`` (the engine's padded prefill): the state
comes out as it stood at the true length.

The decode step counts, for its fence span: the gates ``g`` and the
divisors ``z . phi(q)`` of every row, layer and head (``step_counters``).

The module takes the tree it is handed in the tree's own precision: with
``dtype=bfloat16`` the matrices are bfloat16 leaves and no float32 copy of
one is made; norm scales are float32 leaves; state, normaliser, gates and
powers are float32.  Nothing here runs at import (``models/registry.py``
imports every family).
"""

from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ml_trainer_tpu.models.exaone_moe import rotate_half
from ml_trainer_tpu.models.moe import GatedMLP
from ml_trainer_tpu.models.registry import register_model
from ml_trainer_tpu.ops.kernels.retention_state_step import (
    retention_state_step,
)
from ml_trainer_tpu.ops.power_retention import (
    phi_padded,
    retention_chunked,
    retention_step,
)


class PowerRetention(nn.Module):
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope_theta: float = 1e6
    eps: float = 1e-6
    dtype: jnp.dtype = jnp.float32
    chunk: int = 2048
    decode: bool = False

    @nn.compact
    def __call__(self, x, true_len=None):
        """Returns the branch and, of a decode step, ``(g [B, G], divisor
        [B, G, R])``, else None."""
        b, s, _ = x.shape
        h, g, d = self.num_heads, self.num_kv_heads, self.head_dim
        if h % g:
            raise ValueError(f"{h} query heads over {g} key-value heads")
        f32 = jnp.float32

        def dense(n, name):
            return nn.Dense(n, use_bias=False, dtype=self.dtype,
                            param_dtype=self.dtype, name=name)

        def heads(name, n):
            return dense(n * d, name)(x).reshape(b, s, n, d).transpose(
                0, 2, 1, 3)

        norm = lambda name: nn.RMSNorm(  # noqa: E731
            epsilon=self.eps, dtype=self.dtype, name=name)
        q, k, v = norm("q_norm")(heads("q", h)), norm("k_norm")(
            heads("k", g)), heads("v", g)                    # [B, H, S, D]
        log_g = jax.nn.log_sigmoid(
            dense(g, "gate")(x).astype(f32)).transpose(0, 2, 1)  # [B, G, S]
        if self.decode:
            state = self.variable(
                "cache", "state",
                lambda: jnp.zeros((b, g, d, phi_padded(d)), f32))
            total = self.variable(
                "cache", "norm", lambda: jnp.zeros((b, g, phi_padded(d)), f32))
            index = self.variable(
                "cache", "cache_index", lambda: jnp.zeros((), jnp.int32))
            idx = index.value
            index.value = idx + s
        counted = None
        if self.decode and s == 1:
            rows = idx if idx.ndim else jnp.full((b,), idx, jnp.int32)
            q, k = (rotate_half(t, rows[:, None], self.rope_theta)
                    for t in (q, k))
            o, state.value, total.value, den = retention_step(
                q.reshape(b, g, h // g, d), k[:, :, 0], v[:, :, 0],
                log_g[:, :, 0], state.value, total.value,
                state_step=retention_state_step)
            o = o[:, :, :, None]                             # [B, G, R, 1, D]
            counted = (jnp.exp(log_g[:, :, 0]), den)
        else:
            if self.decode:
                if idx.ndim:
                    raise ValueError(
                        f"{type(self).__name__} has no verify window over "
                        "the slot cache (speculation serves only the GPT-2 "
                        "family)")
                # The contract is an EMPTY cache (see layers.py): poison the
                # output where it is not, rather than be quietly wrong.
                q = jnp.where(idx == 0, q, jnp.nan)
            q, k = (rotate_half(t, jnp.arange(s)[None], self.rope_theta)
                    for t in (q, k))
            o, after, summed = retention_chunked(
                q.reshape(b, g, h // g, s, d), k, v, log_g,
                true_len=true_len if self.decode else None, chunk=self.chunk)
            if self.decode:
                state.value, total.value = after, summed
        o = o.reshape(b, h, s, d).transpose(0, 2, 1, 3).reshape(b, s, h * d)
        return dense(x.shape[-1], "o")(o.astype(self.dtype)), counted


class BrumbyBlock(nn.Module):
    num_heads: int
    num_kv_heads: int
    head_dim: int
    dense_dim: int
    rope_theta: float
    eps: float
    dtype: jnp.dtype
    chunk: int
    decode: bool

    @nn.compact
    def __call__(self, x, true_len=None):
        norm = lambda name: nn.RMSNorm(  # noqa: E731
            epsilon=self.eps, dtype=self.dtype, name=name)
        a, counted = PowerRetention(
            self.num_heads, self.num_kv_heads, self.head_dim,
            rope_theta=self.rope_theta, eps=self.eps, dtype=self.dtype,
            chunk=self.chunk, decode=self.decode, name="attn",
        )(norm("attn_norm")(x), true_len)
        h = x + a
        f = GatedMLP(self.dense_dim, dtype=self.dtype, name="mlp")(
            norm("mlp_norm")(h))
        return h + f, counted


class BrumbyLM(nn.Module):
    """The causal LM.  Defaults are Brumby-14B-Base's published sizes; a
    chip's share of a stated deployment names ``num_layers`` and
    ``max_len``."""

    vocab_size: int = 151936
    max_len: int = 32768
    embed_dim: int = 5120
    num_layers: int = 40
    num_heads: int = 40
    num_kv_heads: int = 8
    head_dim: int = 128
    dense_dim: int = 17408
    rope_theta: float = 1e6
    eps: float = 1e-6
    dtype: jnp.dtype = jnp.float32
    chunk: int = 2048
    decode: bool = False

    @nn.compact
    def __call__(self, input_ids, train: bool = False,
                 true_len: Optional[jax.Array] = None):
        """``true_len`` (decode-mode prefill only): how many of the
        positions handed in are the prompt's own, the rest being padding to
        a bucket; an input of the program, so one program a bucket."""
        del train  # no dropout; the entry points pass it
        x = nn.Embed(
            self.vocab_size, self.embed_dim, dtype=self.dtype,
            param_dtype=self.dtype, name="tok_embed",
        )(input_ids)
        counted = []
        for i in range(self.num_layers):
            x, stats = BrumbyBlock(
                self.num_heads, self.num_kv_heads, self.head_dim,
                dense_dim=self.dense_dim, rope_theta=self.rope_theta,
                eps=self.eps, dtype=self.dtype, chunk=self.chunk,
                decode=self.decode, name=f"block{i}",
            )(x, true_len)
            if stats is not None:
                counted.append(stats)
        if counted:
            # What the slot engine reads beside a decode step's tokens
            # (``reduce_step_counters``): row axis first.
            b = x.shape[0]
            self.sow("step_counters", "gate", jnp.stack(
                [g for g, _ in counted], axis=1).reshape(b, -1))
            self.sow("step_counters", "divisor", jnp.stack(
                [n for _, n in counted], axis=1).reshape(b, -1))
        x = nn.RMSNorm(
            epsilon=self.eps, dtype=self.dtype, name="final_norm")(x)
        head = self.param(
            "lm_head", nn.initializers.normal(0.02),
            (self.embed_dim, self.vocab_size), self.dtype)
        if true_len is not None:
            # The engine's padded prefill reads the logits of the last true
            # position and of no other: the head on that one row (2,048 x
            # 151,936 logits would be 1.2 GB of float32 and 3.2e12
            # operations an admission), zeros at the others.
            at = jnp.asarray(true_len, jnp.int32) - 1
            x = jax.lax.dynamic_index_in_dim(x, at, axis=1, keepdims=True)
        logits = jnp.matmul(x.astype(self.dtype), head.astype(self.dtype),
                            preferred_element_type=jnp.float32)
        if true_len is not None:
            logits = jnp.where(
                jnp.arange(input_ids.shape[1])[None, :, None] == at,
                logits, 0.0)
        return logits

    def reduce_step_counters(self, counters: dict, in_flight) -> dict:
        """Inside the decode program: the step's counters over the rows in
        flight (a free row's state is whatever its last request left).  The
        gates add up; of the divisors the smallest is kept, which no sum
        over rows gives."""
        gate, divisor = counters["gate"][0], counters["divisor"][0]
        return {
            "gate_sum": jnp.tensordot(
                in_flight.astype(gate.dtype), gate.sum(axis=1), axes=1),
            "norm_min": jnp.min(jnp.where(
                in_flight[:, None] > 0, divisor, jnp.inf)),
        }

    def step_counter_args(self, counters: dict, rows_in_flight: int) -> dict:
        """The decode step's counters as arguments of its fence span:
        ``gate_mean`` (``1 / (1 - g)`` tokens is how far the state
        remembers) and ``norm_min`` (how near the division comes to its
        ``eps``), over the rows in flight, all layers and heads."""
        gates = max(rows_in_flight, 1) * self.num_layers * self.num_kv_heads
        return {"gate_mean": float(counters["gate_sum"]) / gates,
                "norm_min": float(counters["norm_min"])}


@register_model("brumby")
def brumby(**kw) -> BrumbyLM:
    """Brumby-14B-Base as published; a chip's share names ``num_layers``
    and ``max_len``."""
    return BrumbyLM(**kw)


@register_model("brumby_tiny")
def brumby_tiny(**kw) -> BrumbyLM:
    """Test preset: four layers, 10 query heads over 2 key-value heads of
    16 (groups of five, as published; a state of 136 x 16 a head), chunks of
    8."""
    tiny = dict(
        vocab_size=256, max_len=64, embed_dim=64, num_layers=4,
        num_heads=10, num_kv_heads=2, head_dim=16, dense_dim=96, chunk=8,
    )
    return BrumbyLM(**{**tiny, **kw})
