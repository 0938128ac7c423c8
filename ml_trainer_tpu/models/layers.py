"""Shared transformer building blocks (flax), TPU-first.

No analog in the reference (its only model is a 62K-param CNN,
ref: src/model.py) — these exist for the north-star families
(BASELINE.json configs[2..4]).  Design notes:

* all attention flows through ``ops.attention`` so the Pallas flash kernel,
  the XLA path and (via ``parallel.ring``) ring sequence-parallel attention
  are interchangeable behind one module;
* ``dtype`` threads bf16 activation compute through every block (params stay
  f32 — the standard TPU mixed-precision recipe for the ViT config);
* weight layouts keep the contraction dim leading/trailing such that the
  tensor-parallel PartitionSpecs in ``parallel.tp_rules`` shard cleanly
  (qkv/mlp-in column-parallel, proj/mlp-out row-parallel).
"""

from __future__ import annotations

from typing import Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ml_trainer_tpu.ops.attention import attention
from ml_trainer_tpu.ops.kernels.decode_attention import (
    decode_attention_append,
)
from ml_trainer_tpu.ops.kernels.slot_cache_write import (
    slot_cache_write_reference,
)

# Dense targets a LoRA adapter may attach to (docs/serving.md "Batched
# LoRA adapters"): the attention and MLP projections.  Embeddings and
# the tied LM head stay base-only by design.
LORA_TARGETS = ("qkv", "proj", "fc_in", "fc_out")


def lora_delta(mdl: nn.Module, name: str, x, features: int,
               adapter_idx=None):
    """Low-rank delta for Dense target ``name``: added AFTER the base
    projection, so base param paths (and the base program when LoRA is
    off) are untouched.

    Two modes, selected by the owning module's static fields:

    * **Train** (``lora_rank > 0``, ``lora_slots == 0``): one trainable
      adapter — params ``<name>_lora_A`` (init N(0, 0.01²)) and
      ``<name>_lora_B`` (init zeros, so step 0 is the base model
      exactly), delta ``(x @ A @ B) · alpha/rank``.  The base kernel
      stays frozen by the Trainer's optimizer mask, not here.
    * **Serve** (``lora_slots > 0``): a POOL of adapters lives in the
      ``"lora"`` collection — stacks ``A [S, in, rank]`` /
      ``B [S, rank, out]`` owned and uploaded by the serving engine
      (serving/adapter_pool.py) — and every batch row gathers ITS OWN
      adapter by index: ``(x @ A[idx]) @ B[idx]``.  Slot 0 is the trash
      adapter (all-zero), so rows with no adapter compute an exact-zero
      delta and stay bit-identical to the base model.  The alpha/rank
      scale is folded into ``B`` at upload time, so mixed-rank
      adapters (zero-padded to the pool's rank bucket) share this ONE
      program — adapter swap/hot-load never recompiles.
    """
    rank = int(mdl.lora_rank)
    slots = int(mdl.lora_slots)
    in_dim = x.shape[-1]
    if slots:
        A = mdl.variable(
            "lora", f"{name}_lora_A",
            lambda: jnp.zeros((slots, in_dim, rank), mdl.dtype),
        ).value
        B = mdl.variable(
            "lora", f"{name}_lora_B",
            lambda: jnp.zeros((slots, rank, features), mdl.dtype),
        ).value
        if adapter_idx is None:
            # Init trace (no engine-supplied index yet): every row reads
            # the trash adapter — the zero delta.
            adapter_idx = jnp.zeros((x.shape[0],), jnp.int32)
        a = jnp.take(A, adapter_idx, axis=0)         # [B, in, rank]
        b = jnp.take(B, adapter_idx, axis=0)         # [B, rank, out]
        xa = jnp.einsum("bsi,bir->bsr", x.astype(a.dtype), a)
        return jnp.einsum("bsr,bro->bso", xa, b)
    A = mdl.param(
        f"{name}_lora_A", nn.initializers.normal(0.01), (in_dim, rank)
    )
    B = mdl.param(
        f"{name}_lora_B", nn.initializers.zeros, (rank, features)
    )
    scale = float(mdl.lora_alpha) / rank
    x = x.astype(mdl.dtype)
    return (x @ A.astype(mdl.dtype) @ B.astype(mdl.dtype)) * scale


def _quant_dense(mdl: nn.Module, name: str, x, features: int):
    """Int8 weight-quantized replacement for Dense target ``name``.

    Reads ``<name>_w`` (int8 [K, N]) / ``<name>_scale`` (f32 [N]) /
    ``<name>_b`` (f32 [N]) from the ``"quant"`` collection — built
    host-side by ``ops.kernels.quantize_tree`` from the fp32 params, so
    param paths and checkpoints never change and only the decode model
    clone flips the knob.  The fp32 ``kernel``/``bias`` params go unread
    by this program (flax apply tolerates unused collections entries).
    """
    from ml_trainer_tpu.ops.kernels.int8_matmul import int8_matmul

    in_dim = x.shape[-1]
    w = mdl.variable(
        "quant", f"{name}_w",
        lambda: jnp.zeros((in_dim, features), jnp.int8),
    ).value
    s = mdl.variable(
        "quant", f"{name}_scale",
        lambda: jnp.ones((features,), jnp.float32),
    ).value
    b = mdl.variable(
        "quant", f"{name}_b",
        lambda: jnp.zeros((features,), jnp.float32),
    ).value
    y = int8_matmul(x.astype(mdl.dtype), w, s)
    return y + b.astype(y.dtype)


class MultiHeadAttention(nn.Module):
    """Self-attention over [B, S, E] with heads split for ops.attention.

    ``decode=True`` switches to single-token autoregressive mode (flax's
    standard cache pattern): each call consumes x of sequence length 1,
    appends its key/value into a ``cache`` collection ([B, H, L, D] ring
    written at ``cache_index``) and attends the query against every cached
    position so far.  The decode loop then runs as one ``lax.scan`` with
    the cache as carry — no recompilation per step, no growing shapes.
    ``decode_max_len`` fixes the cache length L (static shapes for XLA).
    """

    num_heads: int
    head_dim: Optional[int] = None
    causal: bool = False
    dropout_rate: float = 0.0
    dtype: jnp.dtype = jnp.float32
    attention_impl: str = "auto"
    mesh: Optional[object] = None  # jax Mesh, required for 'ring'
    decode: bool = False
    decode_max_len: int = 0
    # Paged KV cache (serving/kv_pool.py): > 0 switches the decode cache
    # from per-row contiguous [B, H, L, D] blocks to a SHARED pool of
    # fixed-size pages [kv_pages, H, kv_page_size, D] addressed through a
    # per-row page table — rows own pages, not max_len regions, so pool
    # memory tracks live tokens and identical prefixes can share pages.
    kv_page_size: int = 0
    kv_pages: int = 0
    # Pallas paged-attention decode (ops/kernels/paged_attention.py):
    # fuse the page-table gather into the attention kernel on the S == 1
    # step.  'auto' dispatch resolves to the lax reference off-TPU —
    # bitwise the gather path below — so flipping this knob never
    # changes bytes on CPU.
    paged_kernel: bool = False
    # Int8 weight-quantized decode (ops/kernels/int8_matmul.py): the
    # qkv/proj projections read int8 weights + per-column scales from
    # the "quant" collection instead of the fp32 params.
    quant_int8: bool = False
    # LoRA (see lora_delta): rank > 0 adds low-rank deltas on the
    # targeted projections — trainable single-adapter params when
    # lora_slots == 0, the serving engine's per-row-indexed adapter pool
    # when lora_slots > 0.
    lora_rank: int = 0
    lora_alpha: float = 1.0
    lora_slots: int = 0
    lora_targets: tuple = ()

    @nn.compact
    def __call__(self, x, mask=None, train: bool = False, kv_lens=None,
                 adapter_idx=None):
        embed = x.shape[-1]
        head_dim = self.head_dim or embed // self.num_heads
        inner = self.num_heads * head_dim
        # Fused QKV projection: one [E, 3·inner] matmul keeps the MXU busy
        # and gives tensor parallelism a single column-sharded kernel.
        if self.quant_int8:
            qkv = _quant_dense(self, "qkv", x, 3 * inner)
        else:
            qkv = nn.Dense(3 * inner, dtype=self.dtype, name="qkv")(x)
        if self.lora_rank and "qkv" in self.lora_targets:
            qkv = qkv + lora_delta(self, "qkv", x, 3 * inner, adapter_idx)
        q, k, v = jnp.split(qkv, 3, axis=-1)

        def heads(t):  # [B, S, inner] -> [B, H, S, D]
            b, s, _ = t.shape
            return t.reshape(b, s, self.num_heads, head_dim).transpose(0, 2, 1, 3)

        if self.decode:
            if mask is not None or kv_lens is not None:
                raise ValueError(
                    "decode mode attends the cached prefix; mask/kv_lens "
                    "are not supported (an error rather than a silent drop)"
                )
            out = self._decode_step(heads(q), heads(k), heads(v))
        else:
            out = attention(
                heads(q), heads(k), heads(v),
                causal=self.causal, mask=mask, kv_lens=kv_lens,
                implementation=self.attention_impl,
                mesh=self.mesh,
            )
        b, h, s, d = out.shape
        out = out.transpose(0, 2, 1, 3).reshape(b, s, h * d)
        attn_out = out
        if self.quant_int8:
            out = _quant_dense(self, "proj", out, embed)
        else:
            out = nn.Dense(embed, dtype=self.dtype, name="proj")(out)
        if self.lora_rank and "proj" in self.lora_targets:
            out = out + lora_delta(self, "proj", attn_out, embed,
                                   adapter_idx)
        if self.dropout_rate:
            out = nn.Dropout(self.dropout_rate, deterministic=not train)(out)
        return out

    def _decode_step(self, q, k, v):
        """Cached attention step.  S > 1 is the PREFILL call — the whole
        prompt runs one ordinary causal attention while its K/V land in
        the cache (one batched MXU-friendly pass, not P single-token
        steps); S == 1 is the incremental decode step attending the
        cached prefix."""
        b, h, s, d = q.shape
        L = self.decode_max_len
        if L <= 0:
            raise ValueError("decode=True needs decode_max_len > 0")
        if self.kv_page_size:
            return self._paged_decode_step(q, k, v)
        cached_k = self.variable(
            "cache", "cached_key",
            lambda: jnp.zeros((b, h, L, d), self.dtype),
        )
        cached_v = self.variable(
            "cache", "cached_value",
            lambda: jnp.zeros((b, h, L, d), self.dtype),
        )
        idx_var = self.variable(
            "cache", "cache_index", lambda: jnp.zeros((), jnp.int32)
        )
        idx = idx_var.value
        if idx.ndim == 1:
            # Slot-indexed serving mode (serving/engine.py): ``cache_index``
            # is a PER-ROW [B] vector — each batch row (slot) sits at its
            # own sequence position, so rows write K/V at their own index
            # and attend their own valid prefix.  ``s == 1`` is the
            # ordinary decode step, ONE call a layer with every row in
            # flight (ops/kernels/decode_attention.py): it reads each row's
            # live blocks and no others (the masked attention it replaces
            # reads all ``L`` positions of every row), puts this step's K
            # and V into the row's last block while it holds it, and writes
            # back the one tile they land in (XLA runs the scatter it
            # replaces as a sequential loop over the rows; a write kernel of
            # its own moved that tile both ways, 1.5 GB a step of gpt2-large
            # for 5.9 MB of new rows).
            # ``s > 1`` is the speculative VERIFY window (speculative.py): a
            # length-``s`` token window lands at each row's own dynamic
            # offset — one dynamic_update_slice per row, shapes static at
            # fixed ``s``, so a fixed draft length K never recompiles — and
            # query position j attends cached positions <= idx + j (the
            # in-window causal rule).  The window keeps the scatter and the
            # masked attention (the kernels' references): it may cross the
            # edge of the write's tile and has ``s`` lengths a row, which
            # would take second kernels, and no measured traffic runs it.
            # Prefill still runs per request at batch 1 with the ordinary
            # scalar index and is inserted into the slot cache afterwards.
            idx_var.value = idx + s
            k, v = k.astype(self.dtype), v.astype(self.dtype)
            if s == 1:
                out, cached_k.value, cached_v.value = decode_attention_append(
                    q, k, v, cached_k.value, cached_v.value, idx)
                return out
            cached_k.value, cached_v.value = slot_cache_write_reference(
                cached_k.value, cached_v.value, k, v, idx)
            valid = (
                jnp.arange(L)[None, None, :]
                <= idx[:, None, None] + jnp.arange(s)[None, :, None]
            )[:, None, :, :]
            return attention(
                q, cached_k.value, cached_v.value,
                causal=False, mask=valid, implementation="xla",
            )
        cached_k.value = jax.lax.dynamic_update_slice(
            cached_k.value, k.astype(self.dtype), (0, 0, idx, 0)
        )
        cached_v.value = jax.lax.dynamic_update_slice(
            cached_v.value, v.astype(self.dtype), (0, 0, idx, 0)
        )
        idx_var.value = idx + s
        if s > 1:
            # Prefill: plain causal attention over the prompt itself.  The
            # contract is an EMPTY cache (generate() guarantees it) — a
            # warm-cache multi-token call would silently ignore the cached
            # prefix, so poison the output to NaN instead of being quietly
            # wrong (the index is traced; a static assert cannot see it).
            q = jnp.where(idx == 0, q, jnp.nan)
            return attention(q, k, v, causal=True, implementation="auto")
        # Attend over the valid prefix only: one [1, L] masked row — the
        # decode analog of the causal mask.
        valid = (jnp.arange(L) <= idx)[None, None, None, :]
        return attention(
            q, cached_k.value, cached_v.value,
            causal=False, mask=valid, implementation="xla",
        )

    def _paged_decode_step(self, q, k, v):
        """Paged cached attention (serving/kv_pool.py's memory model).

        K/V live in ONE pool of ``kv_pages`` fixed-size pages
        ``[N, H, page, D]`` shared by every batch row; a per-row
        ``page_table`` ``[B, P]`` (P = decode_max_len / page) maps each
        row's logical position ``i`` to page ``table[row, i // page]``
        at offset ``i % page``.  Writes scatter the length-``s`` window
        at each row's own dynamic offset (the PR2 windowed-append
        discipline: shapes static at fixed ``s``, so ragged join/leave
        traffic and the speculative verify window never recompile);
        reads gather ``pool[table]`` back into logical order
        ``[B, H, P·page, D]`` and attend under the same
        ``arange(L) <= idx + j`` validity mask as the contiguous slot
        path — so a paged row computes bit-for-bit the same attention
        as a contiguous row holding the same K/V.

        Safety invariants (owned by the engine/pool, exploited here):
        page 0 is a TRASH page no live row maps to; inactive rows carry
        an all-zero table, so their writes land in trash instead of
        another row's pages, and positions past a row's allocation also
        resolve to trash.  Positions at or past ``max_len`` (a padded
        continuation window hanging over the end of the sequence) route
        to trash EXPLICITLY — clipping them into the last table slot
        would scatter padding garbage over a full row's real tail K/V.
        """
        b, h, s, d = q.shape
        ps = self.kv_page_size
        L = self.decode_max_len
        if L % ps:
            raise ValueError(
                f"decode_max_len ({L}) must be a multiple of kv_page_size "
                f"({ps}) — the gathered logical length must equal the "
                "contiguous path's for byte-identical attention"
            )
        if self.kv_pages < 2:
            raise ValueError(
                f"kv_pages must be >= 2 (page 0 is the trash page), got "
                f"{self.kv_pages}"
            )
        P = L // ps
        pool_k = self.variable(
            "cache", "cached_key",
            lambda: jnp.zeros((self.kv_pages, h, ps, d), self.dtype),
        )
        pool_v = self.variable(
            "cache", "cached_value",
            lambda: jnp.zeros((self.kv_pages, h, ps, d), self.dtype),
        )
        table_var = self.variable(
            "cache", "page_table", lambda: jnp.zeros((b, P), jnp.int32)
        )
        idx_var = self.variable(
            "cache", "cache_index", lambda: jnp.zeros((), jnp.int32)
        )
        idx = idx_var.value
        # Init trace reaches here with the scalar init value; broadcast
        # for the (garbage) init compute, keep the stored shape intact.
        idx_vec = idx if idx.ndim == 1 else jnp.full((b,), idx, jnp.int32)
        table = table_var.value

        # -- write: scatter the window at each row's own offset ----------
        positions = idx_vec[:, None] + jnp.arange(s)[None, :]       # [B, s]
        page_slot = jnp.clip(positions // ps, 0, P - 1)
        offs = positions % ps
        page_ids = jnp.where(
            positions < L,
            jnp.take_along_axis(table, page_slot, axis=1),
            0,
        )                                                           # [B, s]

        def scatter(pool, t):  # t: [B, H, s, D] -> rows [B*s, H, D]
            rows = t.astype(pool.dtype).transpose(0, 2, 1, 3)
            rows = rows.reshape(b * s, h, d)
            return pool.at[
                page_ids.reshape(-1), :, offs.reshape(-1), :
            ].set(rows)

        pool_k.value = scatter(pool_k.value, k)
        pool_v.value = scatter(pool_v.value, v)
        idx_var.value = idx + s

        # -- read ---------------------------------------------------------
        if self.paged_kernel and s == 1:
            # Fused path (ops/kernels/paged_attention.py): the kernel
            # pulls pages straight off the table instead of the XLA
            # gather below materializing [B, H, L, D] twice per step.
            # Same mask semantics: lengths = idx + 1 (this step's token
            # included), and the kernel fetches the very pages the
            # gather would — 'auto' resolves to the lax reference
            # (bitwise this gather path) off-TPU.
            from ml_trainer_tpu.ops.kernels.paged_attention import (
                paged_attention,
            )

            out = paged_attention(
                q[:, :, 0, :], pool_k.value, pool_v.value, table,
                idx_vec + 1,
            )
            return out[:, :, None, :]

        # -- read: gather pages back into logical order ------------------
        def gather(pool):  # [B, P, H, page, D] -> [B, H, L, D]
            g = pool[table]
            return g.transpose(0, 2, 1, 3, 4).reshape(b, h, P * ps, d)

        valid = (
            jnp.arange(L)[None, None, :]
            <= idx_vec[:, None, None] + jnp.arange(s)[None, :, None]
        )[:, None, :, :]
        return attention(
            q, gather(pool_k.value), gather(pool_v.value),
            causal=False, mask=valid, implementation="xla",
        )


class MLP(nn.Module):
    """Transformer feed-forward block."""

    hidden_dim: int
    dropout_rate: float = 0.0
    dtype: jnp.dtype = jnp.float32
    activation: Callable = nn.gelu
    # Int8 weight-quantized projections (see MultiHeadAttention).
    quant_int8: bool = False
    # LoRA (see lora_delta / MultiHeadAttention).
    lora_rank: int = 0
    lora_alpha: float = 1.0
    lora_slots: int = 0
    lora_targets: tuple = ()

    @nn.compact
    def __call__(self, x, train: bool = False, adapter_idx=None):
        embed = x.shape[-1]
        if self.quant_int8:
            h = _quant_dense(self, "fc_in", x, self.hidden_dim)
        else:
            h = nn.Dense(self.hidden_dim, dtype=self.dtype, name="fc_in")(x)
        if self.lora_rank and "fc_in" in self.lora_targets:
            h = h + lora_delta(self, "fc_in", x, self.hidden_dim,
                               adapter_idx)
        h = self.activation(h)
        if self.quant_int8:
            out = _quant_dense(self, "fc_out", h, embed)
        else:
            out = nn.Dense(embed, dtype=self.dtype, name="fc_out")(h)
        if self.lora_rank and "fc_out" in self.lora_targets:
            out = out + lora_delta(self, "fc_out", h, embed, adapter_idx)
        if self.dropout_rate:
            out = nn.Dropout(self.dropout_rate, deterministic=not train)(out)
        return out


def remat_policy(name: str):
    """Map a policy name to a jax.checkpoint saveable-filter (shared by
    every transformer family's ``remat_policy`` knob).

    'none': recompute everything in the backward (max memory savings);
    'dots': keep matmul outputs, recompute only the elementwise chain —
    the standard middle ground on TPU, where matmuls are the expensive
    recompute and layernorm/gelu are nearly free."""
    import jax

    if name == "none":
        return None
    if name == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    raise ValueError(
        f"Unknown remat_policy {name!r}; expected 'none' or 'dots'"
    )


def remat_block(remat: bool, policy_name: str = "none"):
    """The TransformerBlock constructor, wrapped in jax.checkpoint when
    ``remat`` — one definition of the (static_argnums, policy) plumbing
    for the gpt2/vit/bert families."""
    if not remat:
        return TransformerBlock
    return nn.remat(
        TransformerBlock, static_argnums=(3,),
        policy=remat_policy(policy_name),
    )


class TransformerBlock(nn.Module):
    """Pre-LN transformer block (the GPT-2/ViT arrangement; BERT uses
    post-LN via the ``post_norm`` flag)."""

    num_heads: int
    mlp_dim: int
    causal: bool = False
    dropout_rate: float = 0.0
    post_norm: bool = False
    dtype: jnp.dtype = jnp.float32
    attention_impl: str = "auto"
    mesh: Optional[object] = None
    moe_experts: int = 0  # >0: MoE feed-forward (expert parallelism)
    moe_top_k: int = 1    # experts per token (1 = Switch, 2 = GShard)
    decode: bool = False  # KV-cached single-token mode (see MultiHeadAttention)
    decode_max_len: int = 0
    kv_page_size: int = 0  # >0: paged KV pool (see MultiHeadAttention)
    kv_pages: int = 0
    paged_kernel: bool = False  # fused paged-attention decode kernel
    quant_int8: bool = False    # int8 weight-quantized projections
    # LoRA (see lora_delta): threaded to the attention/MLP projections.
    lora_rank: int = 0
    lora_alpha: float = 1.0
    lora_slots: int = 0
    lora_targets: tuple = ()

    @nn.compact
    def __call__(self, x, mask=None, train: bool = False, kv_lens=None,
                 adapter_idx=None):
        lora_kw = dict(
            lora_rank=self.lora_rank, lora_alpha=self.lora_alpha,
            lora_slots=self.lora_slots, lora_targets=self.lora_targets,
        ) if self.lora_rank else {}
        attn = lambda y: MultiHeadAttention(
            self.num_heads, causal=self.causal, dropout_rate=self.dropout_rate,
            dtype=self.dtype, attention_impl=self.attention_impl,
            mesh=self.mesh, decode=self.decode,
            decode_max_len=self.decode_max_len,
            kv_page_size=self.kv_page_size, kv_pages=self.kv_pages,
            paged_kernel=self.paged_kernel, quant_int8=self.quant_int8,
            name="attn", **lora_kw,
        )(y, mask=mask, train=train, kv_lens=kv_lens,
          **({"adapter_idx": adapter_idx} if self.lora_rank else {}))
        if self.moe_experts:
            from ml_trainer_tpu.models.moe import MoEMLP

            mlp = lambda y: MoEMLP(
                self.moe_experts, self.mlp_dim,
                num_selected=self.moe_top_k, dtype=self.dtype, name="mlp",
            )(y, train=train)
        else:
            mlp = lambda y: MLP(
                self.mlp_dim, dropout_rate=self.dropout_rate, dtype=self.dtype,
                quant_int8=self.quant_int8, name="mlp", **lora_kw,
            )(y, train=train,
              **({"adapter_idx": adapter_idx} if self.lora_rank else {}))
        ln1 = nn.LayerNorm(dtype=self.dtype, name="ln1")
        ln2 = nn.LayerNorm(dtype=self.dtype, name="ln2")
        if self.post_norm:  # BERT-style
            x = ln1(x + attn(x))
            x = ln2(x + mlp(x))
        else:  # GPT-2/ViT-style
            x = x + attn(ln1(x))
            x = x + mlp(ln2(x))
        return x
