"""``exaone_moe`` decoder (K-EXAONE-236B-A23B's ``model_type``): grouped-query
attention with window and full layers side by side, a routed feed-forward
with a shared expert, norms on the branches' OUTPUTS.

One layer, token ``x`` at position ``t``, no bias anywhere:

* ``q = Wq x`` (``num_heads`` heads of ``head_dim``), ``k = Wk x``,
  ``v = Wv x`` (``num_kv_heads`` heads).  RMSNorm with a learned scale over
  each head of ``q`` and ``k``.  A window layer (``sliding_attention``)
  rotates ``q`` and ``k`` (rotate-half, base ``rope_theta``) and attends
  positions ``t - window < j <= t``; a full layer applies no rotation and
  attends every ``j <= t``.  Query head ``h`` reads key-value head
  ``h // (num_heads / num_kv_heads)``.
* ``h = x + RMSNorm(attention)`` and ``x' = h + RMSNorm(f(h))``: a departure
  from the pre-norm habit of this repo's other decoders.
* ``f`` is a gated feed-forward (``dense``), or the routed layer plus one
  shared gated feed-forward (``sparse``; ``models/moe.py::HeldExpertsMoE``).

Served through the slot engine with a cache of each kind: a full layer keeps
``[slots, kv_heads, max_len, head_dim]``; a window layer keeps a RING of
``window`` positions, position ``p`` at ``p mod window``, written after the
rotation so that it needs no re-rotation, read under a mask built from the
row's position.  Decode attention is stated over the key-value heads with
their query heads as a group, so the cache is read once and never repeated
to the query heads.

The module takes the tree it is handed in the tree's own precision: with
``dtype=bfloat16`` the matrices are bfloat16 leaves and no float32 copy of
one is made; norm scales, the router and its bias are float32 leaves.
Nothing here runs at import (``models/registry.py`` imports every family).
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
from ml_trainer_tpu.ops.kernels.decode_attention import (
    decode_attention_append,
    grouped_decode_attention,
)
from ml_trainer_tpu.ops.kernels.slot_cache_write import slot_cache_write

PERIOD = ("sliding_attention",) * 3 + ("full_attention",)


def rotate_half(x, positions, theta: float):
    """Rotary embedding of ``x`` [B, H, S, D] at ``positions`` [B or 1, S]
    (each row at its own position in the slot engine); angles in float32."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[:, None, :, None] * freqs
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1).astype(x.dtype)


class ExaoneAttention(nn.Module):
    num_heads: int
    num_kv_heads: int
    head_dim: int
    window: int = 0  # 0: a full layer (and no rotation)
    rope_theta: float = 1e6
    eps: float = 1e-5
    dtype: jnp.dtype = jnp.float32
    attention_impl: str = "auto"
    decode: bool = False
    decode_max_len: int = 0

    @nn.compact
    def __call__(self, x, true_len=None):
        b, s, _ = x.shape
        h, g, d = self.num_heads, self.num_kv_heads, self.head_dim
        if h % g:
            raise ValueError(f"{h} query heads over {g} key-value heads")

        def heads(name, n):
            t = nn.Dense(n * d, use_bias=False, dtype=self.dtype,
                         param_dtype=self.dtype, name=name)(x)
            return t.reshape(b, s, n, d).transpose(0, 2, 1, 3)

        q = self._norm("q_norm")(heads("q", h))
        k = self._norm("k_norm")(heads("k", g))
        v = heads("v", g)
        if self.decode:
            out = self._cached(q, k, v, true_len)
        else:
            out = self._causal(*self._rotate(q, k, jnp.arange(s)[None]), v)
        out = out.transpose(0, 2, 1, 3).reshape(b, s, h * d)
        return nn.Dense(x.shape[-1], use_bias=False, dtype=self.dtype,
                        param_dtype=self.dtype, name="o")(out)

    def _norm(self, name):
        return nn.RMSNorm(epsilon=self.eps, dtype=self.dtype, name=name)

    def _rotate(self, q, k, positions):
        if not self.window:
            return q, k
        return (rotate_half(q, positions, self.rope_theta),
                rotate_half(k, positions, self.rope_theta))

    def _causal(self, q, k, v, implementation=None):
        """A whole sequence against itself: the band on a window layer (XLA
        path), the flash forward where it applies on a full one."""
        rep = self.num_heads // self.num_kv_heads
        return attention(
            q, jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1),
            causal=True, window=self.window or None,
            implementation=implementation or self.attention_impl,
        )

    def _cached(self, q, k, v, true_len):
        """The flax cache pattern of ``layers.MultiHeadAttention``: S > 1
        with a scalar index is the prefill of an empty batch-1 cache, S == 1
        a decode step, the index a per-row vector in the slot engine."""
        b, _, s, d = q.shape
        g, w = self.num_kv_heads, self.window
        if self.decode_max_len <= 0:
            raise ValueError("decode=True needs decode_max_len > 0")
        length = w or self.decode_max_len
        cached_k = self.variable(
            "cache", "cached_key",
            lambda: jnp.zeros((b, g, length, d), self.dtype))
        cached_v = self.variable(
            "cache", "cached_value",
            lambda: jnp.zeros((b, g, length, d), self.dtype))
        idx_var = self.variable(
            "cache", "cache_index", lambda: jnp.zeros((), jnp.int32))
        idx = idx_var.value
        idx_var.value = idx + s
        k, v = k.astype(self.dtype), v.astype(self.dtype)

        def put(at):  # batch-1 paths: this call's K and V at one position
            for var, new in ((cached_k, k), (cached_v, v)):
                var.value = jax.lax.dynamic_update_slice(
                    var.value, new, (0, 0, at, 0))

        if s > 1:
            if idx.ndim:
                raise ValueError(
                    f"{type(self).__name__} has no verify window over the "
                    "slot cache (speculation serves only the GPT-2 family)")
            q, k = self._rotate(q, k, jnp.arange(s)[None])
            # The contract is an EMPTY cache (see layers.py): poison the
            # output where it is not, rather than be quietly wrong.
            q = jnp.where(idx == 0, q, jnp.nan)
            if w:
                # The ring ends up holding the last ``window`` TRUE
                # positions, whatever the bucket the prompt was padded to:
                # slot i takes the newest position p < true_len with
                # p mod window == i (none yet: masked at the read).
                last = (s if true_len is None else true_len) - 1
                newest = last - jnp.mod(last - jnp.arange(w), w)
                newest = jnp.clip(newest, 0, s - 1)
                cached_k.value = jnp.take(k, newest, axis=2)
                cached_v.value = jnp.take(v, newest, axis=2)
            else:
                put(idx)
            return self._causal(q, k, v, implementation="auto")
        rows = idx if idx.ndim else jnp.full((b,), idx, jnp.int32)
        q, k = self._rotate(q, k, rows[:, None])
        if idx.ndim and not w:
            # A full layer of the slot engine, one call with every row in
            # flight (ops/kernels/decode_attention.py): each row's live
            # blocks and no others, this step's K and V put into the last
            # of them and only their tile written back; a free row's
            # position clamps.
            out, cached_k.value, cached_v.value = decode_attention_append(
                q, k, v, cached_k.value, cached_v.value, rows)
            return out
        at = rows % w if w else rows
        if idx.ndim:
            # A ring is one block, full after ``window`` tokens, so there
            # is nothing to skip: one in-place write a layer
            # (ops/kernels/slot_cache_write.py, the position always inside
            # the ring), then XLA's read of the whole ring.
            cached_k.value, cached_v.value = slot_cache_write(
                cached_k.value, cached_v.value, k, v, at)
        else:
            put(at[0])
        slots = jnp.arange(length)[None, :]
        valid = slots <= rows[:, None]
        if w:
            # Slot i holds position p = t - ((t - i) mod w) > t - w; only
            # p < 0 (the ring not yet full) is nothing to attend.
            valid |= rows[:, None] >= w
        return grouped_decode_attention(
            q, cached_k.value, cached_v.value, valid)


class ExaoneBlock(nn.Module):
    num_heads: int
    num_kv_heads: int
    head_dim: int
    window: int
    sparse: bool
    dense_dim: int
    expert_dim: int
    num_experts: int
    experts_held: tuple
    num_experts_per_tok: int
    routed_scaling: float
    num_shared_experts: int
    rope_theta: float
    eps: float
    dtype: jnp.dtype
    attention_impl: str
    decode: bool
    decode_max_len: int

    @nn.compact
    def __call__(self, x, true_len=None):
        a = ExaoneAttention(
            self.num_heads, self.num_kv_heads, self.head_dim,
            window=self.window, rope_theta=self.rope_theta, eps=self.eps,
            dtype=self.dtype, attention_impl=self.attention_impl,
            decode=self.decode, decode_max_len=self.decode_max_len,
            name="attn",
        )(x, true_len)
        norm = lambda name: nn.RMSNorm(  # noqa: E731
            epsilon=self.eps, dtype=self.dtype, name=name)
        h = x + norm("post_attn_norm")(a)
        rows = None
        if self.sparse:
            f, rows = HeldExpertsMoE(
                self.num_experts, self.expert_dim, self.num_experts_per_tok,
                experts_held=self.experts_held,
                routed_scaling=self.routed_scaling, dtype=self.dtype,
                name="moe",
            )(h)
            if self.num_shared_experts:
                f = f + GatedMLP(
                    self.expert_dim * self.num_shared_experts,
                    dtype=self.dtype, name="shared")(h)
        else:
            f = GatedMLP(self.dense_dim, dtype=self.dtype, name="mlp")(h)
        return h + norm("post_mlp_norm")(f), rows


class ExaoneMoeLM(nn.Module):
    """The causal LM.  Defaults are K-EXAONE-236B-A23B's published sizes;
    ``vocab_rows`` and ``experts_held`` are this chip's share of a stated
    deployment (a sliced vocabulary is a smaller vocabulary: ids, logits and
    sampling are over the rows held)."""

    vocab_rows: int = 153600
    max_len: int = 262144
    embed_dim: int = 6144
    num_heads: int = 64
    num_kv_heads: int = 8
    head_dim: int = 128
    layer_types: tuple = PERIOD * 12
    mlp_layer_types: tuple = ("dense",) + ("sparse",) * 47
    window: int = 128
    dense_dim: int = 18432
    expert_dim: int = 2048
    num_experts: int = 128
    experts_held: tuple = ()  # (first, count); () holds all of them
    num_experts_per_tok: int = 8
    routed_scaling: float = 2.5
    num_shared_experts: int = 1
    rope_theta: float = 1e6
    eps: float = 1e-5
    dtype: jnp.dtype = jnp.float32
    attention_impl: str = "auto"
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
        if len(self.layer_types) != len(self.mlp_layer_types):
            raise ValueError("layer_types and mlp_layer_types differ in length")
        x = nn.Embed(
            self.vocab_rows, self.embed_dim, dtype=self.dtype,
            param_dtype=self.dtype, name="tok_embed",
        )(input_ids)
        counted = []
        for i, (kind, mlp) in enumerate(
                zip(self.layer_types, self.mlp_layer_types)):
            x, rows = ExaoneBlock(
                self.num_heads, self.num_kv_heads, self.head_dim,
                window=self.window if kind == "sliding_attention" else 0,
                sparse=mlp == "sparse", dense_dim=self.dense_dim,
                expert_dim=self.expert_dim, num_experts=self.num_experts,
                experts_held=tuple(self.experts_held),
                num_experts_per_tok=self.num_experts_per_tok,
                routed_scaling=self.routed_scaling,
                num_shared_experts=self.num_shared_experts,
                rope_theta=self.rope_theta, eps=self.eps, dtype=self.dtype,
                attention_impl=self.attention_impl, decode=self.decode,
                decode_max_len=self.max_len if self.decode else 0,
                name=f"block{i}",
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


def _build(kw: dict) -> ExaoneMoeLM:
    """Lists from a configuration's JSON become the tuples a module's
    fields (and the compiled-program cache's keys) need."""
    for key in ("layer_types", "mlp_layer_types", "experts_held"):
        if key in kw:
            kw[key] = tuple(kw[key])
    return ExaoneMoeLM(**kw)


@register_model("exaone_moe")
def exaone_moe(**kw) -> ExaoneMoeLM:
    """K-EXAONE-236B-A23B as published; a chip's share names
    ``layer_types``, ``mlp_layer_types``, ``experts_held``, ``vocab_rows``
    and ``max_len``."""
    return _build(kw)


@register_model("exaone_moe_tiny")
def exaone_moe_tiny(**kw) -> ExaoneMoeLM:
    """Test preset with every kind of layer: two periods, the dense layer
    leading, 16 experts of which 2 a token, a window of 8."""
    tiny = dict(
        vocab_rows=256, max_len=64, embed_dim=64, num_heads=4,
        num_kv_heads=2, head_dim=16, layer_types=PERIOD * 2,
        mlp_layer_types=("dense",) + ("sparse",) * 7, window=8,
        dense_dim=96, expert_dim=32, num_experts=16,
        num_experts_per_tok=2,
    )
    return _build({**tiny, **kw})
