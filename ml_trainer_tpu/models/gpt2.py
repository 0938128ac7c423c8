"""GPT-2 124M causal LM — the pretrain north-star config
(BASELINE.json configs[4]: grad-accum + checkpoint save/restore) and the
framework's flagship long-context model.

Pre-LN decoder stack with causal attention through ops.attention (so the
Pallas flash kernel and ring sequence parallelism apply), learned position
embeddings, weight-tied LM head (logits = h @ tok_embedᵀ — halves embedding
memory and is the published GPT-2 arrangement).
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from ml_trainer_tpu.models.layers import TransformerBlock, remat_block
from ml_trainer_tpu.models.registry import register_model


def _embed_input(mdl: nn.Module, input_ids, pos_start=None):
    """Shared non-trunk front end for the GPT-2 variants: token embedding +
    learned positions (params ``tok_embed``/``pos_embed`` on ``mdl`` — one
    definition so GPT2, GPT2Pipelined and the decode path cannot drift
    apart).  ``pos_start`` (traced scalar) offsets the position slice for
    KV-cached decoding.  Returns the embedded activations and the embed
    module for head tying."""
    import jax as _jax

    s = input_ids.shape[1]
    tok_embed = nn.Embed(mdl.vocab_size, mdl.embed_dim, name="tok_embed")
    x = tok_embed(input_ids)
    pos = mdl.param(
        "pos_embed", nn.initializers.normal(0.01),
        (1, mdl.max_len, mdl.embed_dim),
    )
    if pos_start is None:
        pos_slice = pos[:, :s]
    elif getattr(pos_start, "ndim", 0) == 1:
        # Per-row positions (serving slots / speculative verify windows:
        # each batch row sits at its own sequence position).  ``s == 1``
        # is the decode step; ``s > 1`` gathers a length-s position
        # window per row (clipped at max_len — out-of-range rows are
        # inactive slots whose outputs nobody reads).
        pos_slice = jnp.take(
            pos[0],
            pos_start[:, None] + jnp.arange(s)[None, :],
            axis=0,
            mode="clip",
        )
    else:
        pos_slice = _jax.lax.dynamic_slice(
            pos, (0, pos_start, 0), (1, s, mdl.embed_dim)
        )
    return (x + pos_slice).astype(mdl.dtype), tok_embed


def _tied_head(mdl: nn.Module, x, tok_embed, targets=None):
    """Shared back end: final LayerNorm + weight-tied LM head (logits =
    h @ tok_embedᵀ — halves embedding memory, the published GPT-2
    arrangement).  With ``targets`` (and ``mdl.loss_chunk`` set) it
    returns the chunked LM loss instead — one LayerNorm definition for
    both paths, so the 'ln_final' parameter cannot diverge."""
    x = nn.LayerNorm(dtype=mdl.dtype, name="ln_final")(x)
    if targets is not None:
        # Model-computed loss: the [B, S, V] logits tensor (the memory
        # hot spot — ~0.8 GB for the 124M config at bs=8) is never
        # materialized; see ops.losses.chunked_lm_cross_entropy.  The
        # Trainer drives this path for models that accept ``targets``
        # (metric must be None — there are no logits to score).
        if not getattr(mdl, "loss_chunk", 0):
            raise ValueError(
                "targets requires loss_chunk > 0 (set loss_chunk to a "
                "divisor of the sequence length to enable the chunked "
                "LM loss)"
            )
        from ml_trainer_tpu.ops.losses import chunked_lm_cross_entropy

        return chunked_lm_cross_entropy(
            x, tok_embed.embedding, targets, mdl.loss_chunk
        )
    return x.astype(jnp.float32) @ tok_embed.embedding.T.astype(jnp.float32)


class GPT2(nn.Module):
    vocab_size: int = 50257
    max_len: int = 1024
    embed_dim: int = 768
    depth: int = 12
    num_heads: int = 12
    dropout_rate: float = 0.0
    dtype: jnp.dtype = jnp.float32
    attention_impl: str = "auto"
    mesh: object = None  # jax Mesh; needed for attention_impl='ring'
    moe_experts: int = 0  # >0: MoE feed-forward in every block (EP axis)
    moe_top_k: int = 1    # experts per token (1 = Switch, 2 = GShard)
    remat: bool = False  # jax.checkpoint each block: O(depth) -> O(1)
    # layer activations live in HBM during backward (long-context lever)
    remat_policy: str = "none"  # what remat may KEEP: 'none' (recompute
    # everything), 'dots' (keep matmul outputs — recompute only the cheap
    # elementwise chain: ~2x less recompute FLOPs for ~the matmul
    # activations' memory back).  Only read when remat=True.
    decode: bool = False  # KV-cached single-token inference (generate())
    loss_chunk: int = 0  # >0: with targets, chunked LM loss (see __call__)
    # Paged KV serving (serving/kv_pool.py): the decode cache becomes a
    # shared page pool + per-row page tables (models/layers.py).
    kv_page_size: int = 0
    kv_pages: int = 0
    # Pallas kernel knobs (ops/kernels/): fused paged-attention decode
    # and int8 weight-quantized projections.  Both resolve to lax
    # references off-TPU, so byte-identity holds on CPU; the engine owns
    # the refusal rules (paged_kernel needs kv_page_size > 0, quant_int8
    # excludes spec_k / adapters).
    paged_kernel: bool = False
    quant_int8: bool = False
    # LoRA (models/layers.py lora_delta; docs/serving.md "Batched LoRA
    # adapters"): rank > 0 adds low-rank deltas on ``lora_targets``.
    # ``lora_slots == 0`` is TRAIN mode (one trainable adapter as
    # params); ``lora_slots > 0`` is SERVE mode — the adapter pool
    # stacks live in the "lora" collection and each batch row gathers
    # its own adapter through the per-row ``adapter_idx`` vector the
    # serving engine supplies in that collection.
    lora_rank: int = 0
    lora_alpha: float = 1.0
    lora_slots: int = 0
    lora_targets: tuple = ()

    @nn.compact
    def __call__(self, input_ids, train: bool = False, targets=None):
        if self.decode:
            # Positions come from a cached counter so the whole decode
            # loop (prefill at S=P, then S=1 steps) runs under one
            # compiled program.
            pos_idx = self.variable(
                "cache", "pos_index", lambda: jnp.zeros((), jnp.int32)
            )
            x, tok_embed = _embed_input(
                self, input_ids, pos_start=pos_idx.value
            )
            pos_idx.value = pos_idx.value + input_ids.shape[1]
        else:
            x, tok_embed = _embed_input(self, input_ids)
        if self.dropout_rate:
            x = nn.Dropout(self.dropout_rate, deterministic=not train)(x)
        adapter_idx = None
        if self.lora_rank and self.lora_slots:
            # Serving pool mode: the per-row adapter index rides the
            # "lora" collection next to the pool stacks (the engine
            # supplies both as ordinary program inputs — swapping which
            # adapter a row reads never recompiles).
            adapter_idx = self.variable(
                "lora", "adapter_idx",
                lambda: jnp.zeros((input_ids.shape[0],), jnp.int32),
            ).value
        # remat: recompute each block's activations in the backward pass
        # instead of keeping them in HBM (jax.checkpoint; train arg static).
        Block = remat_block(self.remat, self.remat_policy)
        for i in range(self.depth):
            block = Block(
                num_heads=self.num_heads, mlp_dim=4 * self.embed_dim,
                causal=True, dropout_rate=self.dropout_rate, dtype=self.dtype,
                attention_impl=self.attention_impl, mesh=self.mesh,
                moe_experts=self.moe_experts, moe_top_k=self.moe_top_k,
                decode=self.decode,
                decode_max_len=self.max_len if self.decode else 0,
                kv_page_size=self.kv_page_size, kv_pages=self.kv_pages,
                paged_kernel=self.paged_kernel, quant_int8=self.quant_int8,
                lora_rank=self.lora_rank, lora_alpha=self.lora_alpha,
                lora_slots=self.lora_slots, lora_targets=self.lora_targets,
                name=f"block{i}",
            )
            if self.lora_rank:
                x = block(x, None, train, None, adapter_idx)
            else:
                x = block(x, None, train)
        return _tied_head(self, x, tok_embed, targets)


@register_model("gpt2")
def gpt2(**kw) -> GPT2:
    """GPT-2 124M: 12 layers, 768 wide, 12 heads, 50257 vocab."""
    return GPT2(**kw)


@register_model("gpt2_medium")
def gpt2_medium(**kw) -> GPT2:
    """GPT-2 355M: 24 layers, 1024 wide, 16 heads."""
    kw.setdefault("embed_dim", 1024)
    kw.setdefault("depth", 24)
    kw.setdefault("num_heads", 16)
    return GPT2(**kw)


@register_model("gpt2_large")
def gpt2_large(**kw) -> GPT2:
    """GPT-2 774M: 36 layers, 1280 wide, 20 heads."""
    kw.setdefault("embed_dim", 1280)
    kw.setdefault("depth", 36)
    kw.setdefault("num_heads", 20)
    return GPT2(**kw)


@register_model("gpt2_tiny")
def gpt2_tiny(**kw) -> GPT2:
    """Small GPT-2 for tests: 2 layers, 128 wide, 1k vocab."""
    kw.setdefault("vocab_size", 1024)
    kw.setdefault("embed_dim", 128)
    kw.setdefault("depth", 2)
    kw.setdefault("num_heads", 4)
    kw.setdefault("max_len", 256)
    return GPT2(**kw)


@register_model("gpt2_moe_tiny")
def gpt2_moe_tiny(**kw) -> GPT2:
    """gpt2_tiny with a 4-expert MoE feed-forward — the expert-parallel
    test/demo config (mesh axis ``expert``, rules_for(..., 'ep'))."""
    kw.setdefault("moe_experts", 4)
    return gpt2_tiny(**kw)


@register_model("gpt2_mini")
def gpt2_mini(**kw) -> GPT2:
    """Mid-size GPT-2 (≈29M params): 4 layers, 512 wide, 8k vocab.

    The speculative-decoding serving demo target: large enough that a
    decode forward is weight-streaming-bound — a K+1-token verify
    window costs ~2x a single-token step, not K+1x — which is the regime
    where drafting pays."""
    kw.setdefault("vocab_size", 8192)
    kw.setdefault("embed_dim", 512)
    kw.setdefault("depth", 4)
    kw.setdefault("num_heads", 8)
    kw.setdefault("max_len", 512)
    return GPT2(**kw)


@register_model("gpt2_nano")
def gpt2_nano(**kw) -> GPT2:
    """Draft-model config paired with ``gpt2_mini``: 1 layer, 128 wide,
    the SAME 8k vocabulary (speculative acceptance compares token ids, so
    vocab identity is the compatibility contract — models/registry.py
    records the pairing)."""
    kw.setdefault("vocab_size", 8192)
    kw.setdefault("embed_dim", 128)
    kw.setdefault("depth", 1)
    kw.setdefault("num_heads", 4)
    kw.setdefault("max_len", 512)
    return GPT2(**kw)


class GPT2Pipelined(nn.Module):
    """Pipeline-parallel GPT-2 trainable through the Trainer.

    The TPU-idiomatic stage split: the REPEATED, equal-width transformer
    blocks form the pipeline trunk — their params live stacked
    ``[n_stages, ...]`` and shard ``P('stage', ...)`` (PP_RULES), executing
    through ``parallel.pipeline.pipeline_apply`` (activations hop stage →
    stage over ICI ppermute inside one lax.scan).  The unequal-width ends —
    token/position embedding and the tied LM head — run OUTSIDE the
    pipeline, replicated: an SPMD pipeline needs shape-homogeneous stages,
    so heterogeneous ends ride outside the trunk (the arrangement used by
    production TPU pipelining; the reference has no PP at all, SURVEY.md
    §2C).

    With ``mesh=None`` the SAME stacked params fold serially via
    ``lax.scan`` — one param structure for both execution modes, which is
    what lets tests assert pipelined == serial trajectories exactly.
    The trunk is dropout-free (GPipe microbatches would need per-stage RNG
    plumbing; the reference parity configs train without dropout anyway).
    """

    vocab_size: int = 50257
    max_len: int = 1024
    embed_dim: int = 768
    n_stages: int = 4
    num_heads: int = 12
    dtype: jnp.dtype = jnp.float32
    mesh: object = None  # jax Mesh with a live 'stage' axis -> pipelined
    n_microbatches: int = 0  # 0 -> one microbatch per stage
    remat: bool = False  # recompute stage bodies in backward (O(1) ticks
    # of activation memory instead of O(S+M-1); math unchanged)
    schedule: str = "gpipe"  # pipeline schedule (parallel.pipeline
    # SCHEDULES: gpipe | 1f1b | interleaved | zb); same math, different
    # WHERE/WHEN — the Trainer's `pipeline_schedule=` knob clones this.
    n_virtual: int = 1  # interleaved only: virtual stages per device;
    # the mesh's stage axis then spans n_stages // n_virtual devices.

    @nn.compact
    def __call__(self, input_ids, train: bool = False):
        import jax

        from ml_trainer_tpu.parallel.pipeline import pipeline_apply

        x, tok_embed = _embed_input(self, input_ids)

        # One block TEMPLATE; its params are created stacked [n_stages, ...]
        # so they shard over the stage mesh axis as a single pytree.
        block = TransformerBlock(
            num_heads=self.num_heads, mlp_dim=4 * self.embed_dim,
            causal=True, dtype=self.dtype,
        )

        def stacked_init(rng):
            dummy = jnp.zeros((1, 1, self.embed_dim), self.dtype)

            def one(r):
                return block.init({"params": r}, dummy, None, False)["params"]

            return jax.vmap(one)(jax.random.split(rng, self.n_stages))

        blocks = self.param("blocks", stacked_init)

        def stage_fn(p, mb):
            return block.apply({"params": p}, mb, None, False)

        if self.mesh is not None and "stage" in getattr(
            self.mesh, "axis_names", ()
        ):
            x = pipeline_apply(
                stage_fn, blocks, x, self.mesh,
                n_microbatches=self.n_microbatches or None,
                remat=self.remat,
                schedule=self.schedule,
                n_virtual=self.n_virtual,
            )
        else:
            body = jax.checkpoint(stage_fn) if self.remat else stage_fn
            x, _ = jax.lax.scan(
                lambda carry, p: (body(p, carry), None), x, blocks
            )
        return _tied_head(self, x, tok_embed)


@register_model("gpt2_pipe")
def gpt2_pipe(**kw) -> GPT2Pipelined:
    """GPT-2 124M with the 12 blocks as pipeline stages."""
    kw.setdefault("n_stages", 12)
    return GPT2Pipelined(**kw)


@register_model("gpt2_pipe_tiny")
def gpt2_pipe_tiny(**kw) -> GPT2Pipelined:
    """Small pipelined GPT-2 for tests: 4 stages of 64-wide blocks."""
    kw.setdefault("vocab_size", 256)
    kw.setdefault("embed_dim", 64)
    kw.setdefault("n_stages", 4)
    kw.setdefault("num_heads", 2)
    kw.setdefault("max_len", 128)
    return GPT2Pipelined(**kw)
