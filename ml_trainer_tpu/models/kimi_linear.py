"""``kimi_linear`` decoder (Kimi-Linear-48B-A3B's ``model_type``): Kimi Delta
Attention layers (a gated delta rule with a decay a channel: a per-head
STATE that does not grow with the context) beside latent attention layers
(MLA without positional rotation: a cache of one LATENT row a token), three
to one, over a routed feed-forward with a shared expert.

One layer, token ``x`` at position ``t``, pre-norm (``~x = RMSNorm(x)``,
``h = x + Attn(~x)``, ``x' = h + FFN(~h)``), no bias anywhere:

* **KDA** (``num_heads`` heads, keys and values of ``head_dim``).
  ``[q~; k~; v~] = [Wq; Wk; Wv] ~x``; a causal depthwise convolution of
  ``conv_size`` taps and SiLU on each channel; a head's ``q`` and ``k``
  divided by their L2 norm.  Decay ``g = -exp(A_log[h]) softplus(Wf2 Wf1 ~x
  + dt_bias)`` a channel of the key, write strength ``beta = sigmoid(Wb
  ~x)`` a head; the recurrence is ``ops/delta_rule.py`` with ``scale =
  head_dim ** -0.5``; the output ``Wo concat_h(RMSNorm(o_h) sigmoid(Wg2
  Wg1 ~x)_h)``, one learned scale of ``head_dim`` shared by the heads.
* **MLA** (``num_heads`` heads).  ``q = Wq ~x`` (``qk_nope_head_dim +
  qk_rope_head_dim`` a head), ``[c; k_r] = Wa ~x`` (``kv_lora_rank +
  qk_rope_head_dim``), ``c^ = RMSNorm(c)``; a head's ``[k_n; v] = Wb_h c^``
  and its key ``[k_n; k_r]``, ``k_r`` shared by the heads and NOT rotated
  (``mla_use_nope``: position enters through the causal mask alone); scores
  over ``q . k / sqrt(nope + rope)``, softmax in float32.
* ``FFN``: a gated feed-forward (the first ``first_k_dense_replace``
  layers), or ``models/moe.py::HeldExpertsMoE`` plus one shared gated
  feed-forward.

Served through the slot engine with three kinds of per-slot state side by
side (the ``cache`` collection; the engine tells them apart by rank alone):

* a KDA layer's ``state [slots, heads, head_dim, head_dim]``, float32,
  REPLACED every step, whatever the context;
* its ``conv_tail [slots, conv_size - 1, 3 heads head_dim]``: the last
  inputs of the three convolutions, ``[q~; k~; v~]``;
* an MLA layer's ``latent [slots, 1, max_len, kv_lora_rank +
  qk_rope_head_dim]``, the row ``[c^; k_r]`` a token, written at the row's
  position, and its per-row ``cache_index``.

A prompt (``S > 1``) runs the chunked form of the recurrence and the
EXPANDED attention (keys and values made from the latent rows); a decode
step (``S == 1``) runs the one-token form and the ABSORBED attention: ``q_n``
is taken through ``Wb``'s key half into the latent space, scored against
the latent rows as they lie, and the weighted latent sum through its value
half, so no key or value is ever expanded over the cache.  ``true_len`` (the
engine's padded prefill): the state comes out as it stood at the true
length and the tail holds the last true inputs; the latent rows past it are
padding that the per-row index masks.

The module takes the tree it is handed in the tree's own precision: with
``dtype=bfloat16`` the matrices are bfloat16 leaves and no float32 copy of
one is made; norm scales, ``A_log``, ``dt_bias``, the router and its bias
are float32 leaves, and the recurrence runs in float32.  Nothing here runs
at import (``models/registry.py`` imports every family).
"""

from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ml_trainer_tpu.models.moe import (
    GatedMLP,
    HeldExpertsMoE,
    held_expert_counter_args,
    held_expert_counters_in_flight,
)
from ml_trainer_tpu.models.registry import register_model
from ml_trainer_tpu.ops.attention import attention
from ml_trainer_tpu.ops.delta_rule import (
    gated_delta_chunked,
    gated_delta_step,
)
from ml_trainer_tpu.ops.kernels.decode_attention import (
    grouped_decode_attention,
)
from ml_trainer_tpu.ops.kernels.slot_cache_write import slot_row_write

KDA_LAYERS = (1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21, 22,
              23, 25, 26)
FULL_ATTN_LAYERS = (4, 8, 12, 16, 20, 24, 27)
L2_EPS = 1e-6  # inside the root of q's and k's norm


def _dense(features: int, dtype, name: str) -> nn.Dense:
    return nn.Dense(features, use_bias=False, dtype=dtype, param_dtype=dtype,
                    name=name)


class KimiDeltaAttention(nn.Module):
    num_heads: int
    head_dim: int
    conv_size: int = 4
    eps: float = 1e-5
    dtype: jnp.dtype = jnp.float32
    chunk: int = 64
    decode: bool = False

    @nn.compact
    def __call__(self, x, true_len=None):
        b, s, _ = x.shape
        h, d, taps = self.num_heads, self.head_dim, self.conv_size
        wide = h * d
        f32 = jnp.float32
        mixed = jnp.concatenate(
            [_dense(wide, self.dtype, n)(x) for n in ("q", "k", "v")],
            axis=-1)                                         # [B, S, 3HD]
        if self.decode:
            state = self.variable(
                "cache", "state", lambda: jnp.zeros((b, h, d, d), f32))
            tail = self.variable(
                "cache", "conv_tail",
                lambda: jnp.zeros((b, taps - 1, 3 * wide), self.dtype))
            before, tail_before = state.value, tail.value
        else:
            before = jnp.zeros((b, h, d, d), f32)
            tail_before = jnp.zeros((b, taps - 1, 3 * wide), self.dtype)

        # The three convolutions as one over [q~; k~; v~]: the inputs the
        # tail kept, then this call's.
        seen = jnp.concatenate([tail_before, mixed.astype(self.dtype)], 1)
        weights = self.param(
            "conv", nn.initializers.normal(0.02), (3 * wide, taps),
            self.dtype).astype(f32)
        conv = sum(weights[:, j] * seen[:, j:j + s].astype(f32)
                   for j in range(taps))
        q, k, v = (
            t.reshape(b, s, h, d).transpose(0, 2, 1, 3)
            for t in jnp.split(nn.silu(conv), 3, axis=-1))   # [B, H, S, D]
        q, k = (t * jax.lax.rsqrt(
            jnp.sum(t * t, axis=-1, keepdims=True) + L2_EPS) for t in (q, k))

        def low(name):
            """A gate of the layer's width through a rank of ``head_dim``."""
            return _dense(wide, self.dtype, name + "_b")(
                _dense(d, self.dtype, name + "_a")(x)).astype(f32)

        rate = jnp.exp(self.param(
            "A_log", nn.initializers.zeros, (h,), f32))
        dt_bias = self.param("dt_bias", nn.initializers.zeros, (wide,), f32)
        g = -rate[:, None] * jax.nn.softplus(
            low("f") + dt_bias).reshape(b, s, h, d)
        g = g.transpose(0, 2, 1, 3)                          # [B, H, S, D]
        beta = jax.nn.sigmoid(
            _dense(h, self.dtype, "b")(x).astype(f32)).transpose(0, 2, 1)
        if s == 1:
            o, after = gated_delta_step(
                q[:, :, 0], k[:, :, 0], v[:, :, 0], g[:, :, 0], beta[:, :, 0],
                before, scale=d ** -0.5)
            o = o[:, :, None]
        else:
            o, after = gated_delta_chunked(
                q, k, v, g, beta, before, scale=d ** -0.5, true_len=true_len,
                chunk=self.chunk)
        if self.decode:
            state.value = after
            # The last inputs that are the prompt's own, not the bucket's.
            tail.value = jax.lax.dynamic_slice_in_dim(
                seen, s if true_len is None else true_len, taps - 1, axis=1)
        o = nn.RMSNorm(epsilon=self.eps, dtype=f32, name="o_norm")(
            o.transpose(0, 2, 1, 3))                         # [B, S, H, D]
        y = o.reshape(b, s, wide) * jax.nn.sigmoid(low("g"))
        return _dense(x.shape[-1], self.dtype, "o")(y.astype(self.dtype))


class LatentAttention(nn.Module):
    num_heads: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    latent_dim: int
    eps: float = 1e-5
    dtype: jnp.dtype = jnp.float32
    attention_impl: str = "auto"
    decode: bool = False
    decode_max_len: int = 0

    @nn.compact
    def __call__(self, x, true_len=None):
        del true_len  # rows past it are padding the per-row index masks
        b, s, _ = x.shape
        h, n, r, dv, c = (self.num_heads, self.nope_dim, self.rope_dim,
                          self.v_dim, self.latent_dim)
        q = _dense(h * (n + r), self.dtype, "q")(x).reshape(
            b, s, h, n + r).transpose(0, 2, 1, 3)            # [B, H, S, n+r]
        down = _dense(c + r, self.dtype, "kv_down")(x)
        # What the cache holds of a token: [c^; k_r].
        row = jnp.concatenate(
            [nn.RMSNorm(epsilon=self.eps, dtype=self.dtype,
                        name="kv_norm")(down[..., :c]), down[..., c:]],
            axis=-1).astype(self.dtype)[:, None]             # [B, 1, S, c+r]
        up = self.param(
            "kv_up", nn.initializers.normal(0.02), (c, h * (n + dv)),
            self.dtype).astype(self.dtype).reshape(c, h, n + dv)
        if self.decode and s == 1:
            out = self._absorbed(q, row, up)
        else:
            if self.decode:
                # The contract is an EMPTY cache (see layers.py): poison
                # the output where it is not, rather than be quietly wrong.
                q = jnp.where(self._keep(row) == 0, q, jnp.nan)
            out = self._expanded(q, row, up)
        out = out.transpose(0, 2, 1, 3).reshape(b, s, h * dv)
        return _dense(x.shape[-1], self.dtype, "o")(out.astype(self.dtype))

    def _expanded(self, q, row, up):
        """A whole sequence against itself, every key and value made from
        its latent row."""
        n, c = self.nope_dim, self.latent_dim
        b, _, s, _ = row.shape
        kv = jnp.einsum("bsc,chd->bhsd", row[:, 0, :, :c], up)
        k = jnp.concatenate(
            [kv[..., :n], jnp.broadcast_to(
                row[:, :, :, c:], (b, self.num_heads, s, self.rope_dim))],
            axis=-1)
        # Values padded to the keys' width: the attention paths take one
        # (and pad it to what the flash kernel takes, ops/attention.py).
        v = jnp.pad(kv[..., n:], ((0, 0),) * 3 + (
            (0, k.shape[-1] - self.v_dim),))
        return attention(q, k, v, causal=True,
                         implementation=self.attention_impl)[..., :self.v_dim]

    def _cache(self, b):
        if self.decode_max_len <= 0:
            raise ValueError("decode=True needs decode_max_len > 0")
        latent = self.variable(
            "cache", "latent", lambda: jnp.zeros(
                (b, 1, self.decode_max_len, self.latent_dim + self.rope_dim),
                self.dtype))
        index = self.variable(
            "cache", "cache_index", lambda: jnp.zeros((), jnp.int32))
        return latent, index

    def _keep(self, row):
        """The prefill of an EMPTY batch-1 cache (the flax cache pattern of
        ``layers.MultiHeadAttention``): the prompt's rows from position 0.
        Returns the index it found."""
        latent, index = self._cache(row.shape[0])
        start = index.value
        if start.ndim:
            raise ValueError(
                f"{type(self).__name__} has no verify window over the slot "
                "cache (speculation serves only the GPT-2 family)")
        latent.value = jax.lax.dynamic_update_slice(
            latent.value, row, (0, 0, start, 0))
        index.value = start + row.shape[2]
        return start

    def _absorbed(self, q, row, up):
        """One token a row: its latent row written at the row's position,
        then the scores and the weighted sum taken IN the latent space."""
        n, c = self.nope_dim, self.latent_dim
        b = row.shape[0]
        latent, index = self._cache(b)
        idx = index.value
        index.value = idx + 1
        rows = idx if idx.ndim else jnp.full((b,), idx, jnp.int32)
        if idx.ndim:
            # One in-place write with every row in flight; a free row's
            # position clamps (ops/kernels/slot_cache_write.py).
            latent.value = slot_row_write(latent.value, row, rows)
        else:
            latent.value = jax.lax.dynamic_update_slice(
                latent.value, row, (0, 0, idx, 0))
        q_latent = jnp.einsum(
            "bhn,chn->bhc", q[:, :, 0, :n], up[:, :, :n],
            preferred_element_type=jnp.float32).astype(self.dtype)
        query = jnp.concatenate([q_latent, q[:, :, 0, n:]], axis=-1)
        valid = jnp.arange(self.decode_max_len)[None, :] <= rows[:, None]
        # The latent cache is the keys AND the values: one key-value head
        # that every query head reads; of the sum the latent part is kept.
        mixed = grouped_decode_attention(
            query[:, :, None, :], latent.value, latent.value, valid,
            scale=(n + self.rope_dim) ** -0.5)[:, :, 0, :c]
        return jnp.einsum(
            "bhc,chd->bhd", mixed, up[:, :, n:],
            preferred_element_type=jnp.float32)[:, :, None, :]


class KimiBlock(nn.Module):
    kda: bool
    sparse: bool
    num_heads: int
    head_dim: int
    conv_size: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    latent_dim: int
    dense_dim: int
    expert_dim: int
    num_experts: int
    experts_held: tuple
    num_experts_per_tok: int
    routed_scaling: float
    num_shared_experts: int
    eps: float
    dtype: jnp.dtype
    attention_impl: str
    chunk: int
    decode: bool
    decode_max_len: int

    @nn.compact
    def __call__(self, x, true_len=None):
        norm = lambda name: nn.RMSNorm(  # noqa: E731
            epsilon=self.eps, dtype=self.dtype, name=name)
        if self.kda:
            attn = KimiDeltaAttention(
                self.num_heads, self.head_dim, conv_size=self.conv_size,
                eps=self.eps, dtype=self.dtype, chunk=self.chunk,
                decode=self.decode, name="attn")
        else:
            attn = LatentAttention(
                self.num_heads, self.nope_dim, self.rope_dim, self.v_dim,
                self.latent_dim, eps=self.eps, dtype=self.dtype,
                attention_impl=self.attention_impl, decode=self.decode,
                decode_max_len=self.decode_max_len, name="attn")
        h = x + attn(norm("attn_norm")(x), true_len)
        inner = norm("mlp_norm")(h)
        rows = None
        if self.sparse:
            f, rows = HeldExpertsMoE(
                self.num_experts, self.expert_dim, self.num_experts_per_tok,
                experts_held=self.experts_held,
                routed_scaling=self.routed_scaling, dtype=self.dtype,
                name="moe",
            )(inner)
            if self.num_shared_experts:
                f = f + GatedMLP(
                    self.expert_dim * self.num_shared_experts,
                    dtype=self.dtype, name="shared")(inner)
        else:
            f = GatedMLP(self.dense_dim, dtype=self.dtype, name="mlp")(inner)
        return h + f, rows


class KimiLinearLM(nn.Module):
    """The causal LM.  Defaults are Kimi-Linear-48B-A3B-Instruct's published
    sizes (``kda_layers`` and ``full_attn_layers`` 1-indexed, as published,
    read up to ``num_layers``); ``vocab_rows`` and ``experts_held`` are this
    chip's share of a stated deployment (a sliced vocabulary is a smaller
    vocabulary: ids, logits and sampling are over the rows held)."""

    vocab_rows: int = 163840
    max_len: int = 1048576
    embed_dim: int = 2304
    num_layers: int = 27
    kda_layers: tuple = KDA_LAYERS
    full_attn_layers: tuple = FULL_ATTN_LAYERS
    num_heads: int = 32
    head_dim: int = 128           # KDA: keys and values of a head
    conv_size: int = 4
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    first_k_dense_replace: int = 1
    dense_dim: int = 9216
    expert_dim: int = 1024
    num_experts: int = 256
    experts_held: tuple = ()      # (first, count); () holds all of them
    num_experts_per_tok: int = 8
    routed_scaling: float = 2.446
    num_shared_experts: int = 1
    eps: float = 1e-5
    dtype: jnp.dtype = jnp.float32
    attention_impl: str = "auto"
    chunk: int = 64
    decode: bool = False

    @property
    def vocab_size(self) -> int:
        return self.vocab_rows

    @nn.compact
    def __call__(self, input_ids, train: bool = False,
                 true_len: Optional[jax.Array] = None):
        """``true_len`` (decode-mode prefill only): how many of the
        positions handed in are the prompt's own, the rest being padding to
        a bucket; an input of the program, so one program a bucket."""
        del train  # no dropout; the entry points pass it
        x = nn.Embed(
            self.vocab_rows, self.embed_dim, dtype=self.dtype,
            param_dtype=self.dtype, name="tok_embed",
        )(input_ids)
        counted = []
        for i in range(1, self.num_layers + 1):
            if (i in self.kda_layers) == (i in self.full_attn_layers):
                raise ValueError(
                    f"layer {i} is in both or neither of kda_layers and "
                    "full_attn_layers")
            x, rows = KimiBlock(
                kda=i in self.kda_layers,
                sparse=i > self.first_k_dense_replace,
                num_heads=self.num_heads, head_dim=self.head_dim,
                conv_size=self.conv_size, nope_dim=self.qk_nope_head_dim,
                rope_dim=self.qk_rope_head_dim, v_dim=self.v_head_dim,
                latent_dim=self.kv_lora_rank, dense_dim=self.dense_dim,
                expert_dim=self.expert_dim, num_experts=self.num_experts,
                experts_held=tuple(self.experts_held),
                num_experts_per_tok=self.num_experts_per_tok,
                routed_scaling=self.routed_scaling,
                num_shared_experts=self.num_shared_experts, eps=self.eps,
                dtype=self.dtype, attention_impl=self.attention_impl,
                chunk=self.chunk, decode=self.decode,
                decode_max_len=self.max_len if self.decode else 0,
                name=f"block{i - 1}",
            )(x, true_len)
            if rows is not None:
                counted.append(rows)
        if counted:
            # What the slot engine reads beside a decode step's tokens
            # (``step_counter_args``): row axis first.
            self.sow("step_counters", "expert_rows",
                     jnp.stack(counted, axis=1))
        x = nn.RMSNorm(
            epsilon=self.eps, dtype=self.dtype, name="final_norm")(x)
        head = self.param(
            "lm_head", nn.initializers.normal(0.02),
            (self.embed_dim, self.vocab_rows), self.dtype)
        return jnp.matmul(x.astype(self.dtype), head.astype(self.dtype),
                          preferred_element_type=jnp.float32)

    def reduce_step_counters(self, counters: dict, in_flight) -> dict:
        """Inside the decode program: the counters over the rows in flight."""
        return held_expert_counters_in_flight(counters, in_flight)

    def step_counter_args(self, counters: dict, rows_in_flight: int) -> dict:
        """The decode step's counters as arguments of its fence span."""
        return held_expert_counter_args(
            counters, rows_in_flight, self.num_experts_per_tok)


def _build(kw: dict) -> KimiLinearLM:
    """Lists from a configuration's JSON become the tuples a module's
    fields (and the compiled-program cache's keys) need."""
    for key in ("kda_layers", "full_attn_layers", "experts_held"):
        if key in kw:
            kw[key] = tuple(kw[key])
    return KimiLinearLM(**kw)


@register_model("kimi_linear")
def kimi_linear(**kw) -> KimiLinearLM:
    """Kimi-Linear-48B-A3B-Instruct as published; a chip's share names
    ``num_layers``, ``experts_held``, ``vocab_rows`` and ``max_len``."""
    return _build(kw)


@register_model("kimi_linear_tiny")
def kimi_linear_tiny(**kw) -> KimiLinearLM:
    """Test preset with every kind of layer: two periods of three KDA
    layers and a latent one, the dense layer leading, 16 experts of which 2
    a token, heads of 16, a latent row of 32 + 8, chunks of 8."""
    tiny = dict(
        vocab_rows=256, max_len=64, embed_dim=64, num_layers=8,
        num_heads=4, head_dim=16, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, dense_dim=96, expert_dim=32,
        num_experts=16, num_experts_per_tok=2, chunk=8,
    )
    return _build({**tiny, **kw})
