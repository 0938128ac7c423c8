"""Attention ops: fused XLA path + Pallas TPU flash-attention kernel.

The reference has no attention code at all (SURVEY.md §5 long-context:
"entirely absent") — this module exists for the north-star model families
(BERT/ViT/GPT-2, BASELINE.json configs[2..4]) and is designed TPU-first:

* ``dot_product_attention`` — the XLA path.  Plain einsum + softmax; XLA
  fuses the mask/scale/softmax chain and tiles the two matmuls onto the MXU.
  Works on any backend (CPU tests run this).
* ``flash_attention`` — a Pallas kernel computing attention with the online
  softmax recurrence, never materializing the [S, S] score matrix in HBM:
  the query block stays in VMEM while KV blocks stream through, carrying
  running (max, sum, output) accumulators.  Backward is the matching
  FlashAttention-2-style block-recompute kernel pair (dQ / dK+dV) driven by
  the saved per-row logsumexp, so memory is O(S) in both directions.
* ``attention`` — dispatcher: 'auto' picks flash on TPU for tile-aligned
  shapes, XLA otherwise.

Shapes follow the TPU-native convention [batch, heads, seq, head_dim].
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P


def _mask_bias(mask, dtype):
    return jnp.where(mask, 0.0, jnp.finfo(dtype).min).astype(dtype)


def dot_product_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    mask: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """Reference XLA attention.  q,k,v: [B, H, S, D] (k/v may have S_kv).
    ``window`` narrows the causal mask to a band: a query attends the
    ``window`` newest keys up to its own position."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    scores = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    s_q, s_k = scores.shape[-2], scores.shape[-1]
    if causal:
        row = jax.lax.broadcasted_iota(jnp.int32, (s_q, s_k), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (s_q, s_k), 1)
        causal_mask = row + (s_k - s_q) >= col
        if window is not None:
            causal_mask &= row + (s_k - s_q) - col < window
        scores = scores + _mask_bias(causal_mask, scores.dtype)
    if mask is not None:
        scores = scores + _mask_bias(mask, scores.dtype)
    weights = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum(
        "bhqk,bhkd->bhqd", weights.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    ).astype(q.dtype)


# --------------------------------------------------------------------- flash
def _flash_kernel(*refs, block_k: int, causal: bool, scale: float,
                  masked: bool):
    """One (batch·head, q-block, kv-block) grid step of the online-softmax
    recurrence.  KV streams through VMEM one [block_k, D] tile at a time
    (the kv grid axis iterates fastest), with running (o, m, l) accumulators
    in VMEM scratch that persist across kv steps; the final kv step
    normalizes and writes the output block.  With ``masked`` a per-sequence
    valid-key count streams in via SMEM and columns past it are dropped —
    the right-padded (BERT) mask family, fused into the kernel instead of
    falling back to the XLA path."""
    from jax.experimental import pallas as pl

    if masked:
        q_ref, k_ref, v_ref, lens_ref, o_ref, lse_ref, o_scr, m_scr, l_scr = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, o_scr, m_scr, l_scr = refs
        lens_ref = None

    _, block_q, d = q_ref.shape
    kv_idx = pl.program_id(2)
    num_kv = pl.num_programs(2)
    q_start = pl.program_id(1) * block_q
    kv_start = kv_idx * block_k

    @pl.when(kv_idx == 0)
    def _init():
        o_scr[:] = jnp.zeros((block_q, d), jnp.float32)
        m_scr[:] = jnp.full((block_q, 1), jnp.finfo(jnp.float32).min,
                            jnp.float32)
        l_scr[:] = jnp.zeros((block_q, 1), jnp.float32)

    # Under causal masking, blocks fully above the diagonal contribute
    # nothing — skip their matmuls entirely; likewise blocks entirely in
    # the padded key tail.
    kv_len = lens_ref[pl.program_id(0)] if masked else None
    live = (q_start + block_q > kv_start) if causal else True
    if masked:
        live = jnp.logical_and(live, kv_start < kv_len)

    @pl.when(live)
    def _attend():
        q = q_ref[0].astype(jnp.float32) * scale
        kk = k_ref[0].astype(jnp.float32)
        vv = v_ref[0].astype(jnp.float32)
        scores = jax.lax.dot_general(
            q, kk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [block_q, block_k]
        keep = _keep_mask(
            (block_q, block_k), q_start, kv_start, kv_len, causal, masked,
        )
        if keep is not None:
            scores = jnp.where(keep, scores, jnp.finfo(jnp.float32).min)
        m_prev, l_prev = m_scr[:], l_scr[:]
        m_cur = jnp.max(scores, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(scores - m_new)
        alpha = jnp.exp(m_prev - m_new)
        m_scr[:] = m_new
        l_scr[:] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        o_scr[:] = o_scr[:] * alpha + jax.lax.dot_general(
            p, vv, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(kv_idx == num_kv - 1)
    def _finalize():
        o_ref[0] = (o_scr[:] / l_scr[:]).astype(o_ref.dtype)
        # Per-row logsumexp of the scaled scores — the only softmax
        # statistic the flash backward needs (FlashAttention-2 style).
        # Written as a [block_q, 1] column: a trailing singleton dim is
        # exempt from Mosaic's (8, 128) block-tiling rule, whereas a
        # [1, block_q] row block is rejected by the compiled lowering
        # (interpret mode never checks this).
        lse_ref[0] = m_scr[:] + jnp.log(l_scr[:])


def _lens_per_bh(kv_lens, b, h):
    """[B] valid-key counts -> [B*H] int32 (one per grid row)."""
    return jnp.repeat(kv_lens.astype(jnp.int32), h)


def _flash_forward(q, k, v, kv_lens, *, causal, scale, block_q, block_k,
                   interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, s_q, d = q.shape
    s_k = k.shape[2]
    qr = q.reshape(b * h, s_q, d)
    kr = k.reshape(b * h, s_k, d)
    vr = v.reshape(b * h, s_k, d)
    masked = kv_lens is not None
    kernel = functools.partial(
        _flash_kernel, block_k=block_k, causal=causal, scale=scale,
        masked=masked,
    )
    grid = (b * h, pl.cdiv(s_q, block_q), pl.cdiv(s_k, block_k))
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda i, j, kv: (i, j, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_k, d), lambda i, j, kv: (i, kv, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_k, d), lambda i, j, kv: (i, kv, 0),
                     memory_space=pltpu.VMEM),
    ]
    operands = [qr, kr, vr]
    if masked:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        operands.append(_lens_per_bh(kv_lens, b, h))
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j, kv: (i, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, 1), lambda i, j, kv: (i, j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s_q, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, s_q, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(*operands)
    return out.reshape(b, h, s_q, d), lse.reshape(b, h, s_q)


# Per-row statistics (lse, delta) travel through the backward kernels as
# [B*H, S, 1] columns with (1, block, 1) blocks for the same Mosaic
# block-tiling reason documented in _flash_kernel's finalize.


def _keep_mask(p_shape, q_start, kv_start, kv_len, causal, masked):
    """The score-keep mask shared by all three kernels (forward and the
    two backward passes): causal diagonal and/or the padded-key tail —
    one definition so value and gradient masking cannot diverge."""
    row = jax.lax.broadcasted_iota(jnp.int32, p_shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, p_shape, 1)
    keep = None
    if causal:
        keep = (q_start + row) >= (kv_start + col)
    if masked:
        keep_pad = (kv_start + col) < kv_len
        keep = keep_pad if keep is None else jnp.logical_and(keep, keep_pad)
    return keep


def _flash_bwd_dq_kernel(*refs, block_k: int, causal: bool, scale: float,
                         masked: bool):
    """dQ pass: one q-block stays resident while KV blocks stream through
    (kv is the fastest grid axis); dQ accumulates in VMEM scratch and is
    written once on the last kv step.  Recomputes P from (q, k, lse) — the
    block-recompute that keeps backward memory O(S)."""
    from jax.experimental import pallas as pl

    if masked:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, lens_ref,
         dq_ref, dq_scr) = refs
    else:
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr = refs
        lens_ref = None

    _, block_q, d = q_ref.shape
    kv_idx = pl.program_id(2)
    num_kv = pl.num_programs(2)
    q_start = pl.program_id(1) * block_q
    kv_start = kv_idx * block_k

    @pl.when(kv_idx == 0)
    def _init():
        dq_scr[:] = jnp.zeros((block_q, d), jnp.float32)

    kv_len = lens_ref[pl.program_id(0)] if masked else None
    live = (q_start + block_q > kv_start) if causal else True
    if masked:
        live = jnp.logical_and(live, kv_start < kv_len)

    @pl.when(live)
    def _accumulate():
        q = q_ref[0].astype(jnp.float32)
        kk = k_ref[0].astype(jnp.float32)
        vv = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]                   # [block_q, 1]
        delta = delta_ref[0]               # [block_q, 1]
        scores = jax.lax.dot_general(
            q, kk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        p = jnp.exp(scores - lse)          # [block_q, block_k]
        keep = _keep_mask(
            p.shape, q_start, kv_start, kv_len, causal, masked,
        )
        if keep is not None:
            p = jnp.where(keep, p, 0.0)
        dp = jax.lax.dot_general(
            do, vv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta) * scale
        dq_scr[:] = dq_scr[:] + jax.lax.dot_general(
            ds, kk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(kv_idx == num_kv - 1)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(*refs, block_q: int, causal: bool, scale: float,
                          masked: bool):
    """dK/dV pass: one kv-block stays resident while Q blocks stream through
    (q is the fastest grid axis); dK and dV accumulate in VMEM scratch."""
    from jax.experimental import pallas as pl

    if masked:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, lens_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
        lens_ref = None

    _, block_k, d = k_ref.shape
    q_idx = pl.program_id(2)
    num_q = pl.num_programs(2)
    kv_start = pl.program_id(1) * block_k
    q_start = q_idx * block_q

    @pl.when(q_idx == 0)
    def _init():
        dk_scr[:] = jnp.zeros((block_k, d), jnp.float32)
        dv_scr[:] = jnp.zeros((block_k, d), jnp.float32)

    kv_len = lens_ref[pl.program_id(0)] if masked else None
    live = (q_start + block_q > kv_start) if causal else True
    if masked:
        # A kv block entirely in the padded tail gets zero gradient.
        live = jnp.logical_and(live, kv_start < kv_len)

    @pl.when(live)
    def _accumulate():
        q = q_ref[0].astype(jnp.float32)
        kk = k_ref[0].astype(jnp.float32)
        vv = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]                   # [block_q, 1]
        delta = delta_ref[0]               # [block_q, 1]
        scores = jax.lax.dot_general(
            q, kk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        p = jnp.exp(scores - lse)          # [block_q, block_k]
        keep = _keep_mask(
            p.shape, q_start, kv_start, kv_len, causal, masked,
        )
        if keep is not None:
            p = jnp.where(keep, p, 0.0)
        dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, vv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta) * scale
        dk_scr[:] = dk_scr[:] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(q_idx == num_q - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _flash_backward(q, k, v, kv_lens, out, lse, g, *, causal, scale, block_q,
                    block_k, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, s_q, d = q.shape
    s_k = k.shape[2]
    qr = q.reshape(b * h, s_q, d)
    kr = k.reshape(b * h, s_k, d)
    vr = v.reshape(b * h, s_k, d)
    dor = g.reshape(b * h, s_q, d)
    lser = lse.reshape(b * h, s_q, 1)
    # delta_i = rowsum(dO_i * O_i) — a cheap elementwise reduce; let XLA
    # fuse it rather than adding a third kernel pass.
    delta = jnp.sum(
        dor.astype(jnp.float32) * out.reshape(b * h, s_q, d).astype(jnp.float32),
        axis=-1, keepdims=True,
    )
    nq, nkv = pl.cdiv(s_q, block_q), pl.cdiv(s_k, block_k)
    masked = kv_lens is not None
    operands = [qr, kr, vr, dor, lser, delta]
    lens_spec = []
    if masked:
        operands.append(_lens_per_bh(kv_lens, b, h))
        lens_spec = [pl.BlockSpec(memory_space=pltpu.SMEM)]

    qspec = pl.BlockSpec((1, block_q, d), lambda i, j, x: (i, j, 0),
                         memory_space=pltpu.VMEM)
    kvspec_stream = pl.BlockSpec((1, block_k, d), lambda i, j, x: (i, x, 0),
                                 memory_space=pltpu.VMEM)
    rowspec = pl.BlockSpec((1, block_q, 1), lambda i, j, x: (i, j, 0),
                           memory_space=pltpu.VMEM)
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, block_k=block_k,
                          causal=causal, scale=scale, masked=masked),
        grid=(b * h, nq, nkv),
        in_specs=[qspec, kvspec_stream, kvspec_stream, qspec, rowspec,
                  rowspec] + lens_spec,
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((b * h, s_q, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dq",
    )(*operands)

    kvspec = pl.BlockSpec((1, block_k, d), lambda i, j, x: (i, j, 0),
                          memory_space=pltpu.VMEM)
    qspec_stream = pl.BlockSpec((1, block_q, d), lambda i, j, x: (i, x, 0),
                                memory_space=pltpu.VMEM)
    rowspec_stream = pl.BlockSpec((1, block_q, 1), lambda i, j, x: (i, x, 0),
                                  memory_space=pltpu.VMEM)
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, block_q=block_q,
                          causal=causal, scale=scale, masked=masked),
        grid=(b * h, nkv, nq),
        in_specs=[qspec_stream, kvspec, kvspec, qspec_stream, rowspec_stream,
                  rowspec_stream] + lens_spec,
        out_specs=[kvspec, kvspec],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s_k, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, s_k, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(*operands)
    return (
        dq.reshape(b, h, s_q, d),
        dk.reshape(b, h, s_k, d),
        dv.reshape(b, h, s_k, d),
    )


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8)
)
def flash_attention(
    q, k, v,
    kv_lens=None,
    causal: bool = False,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
):
    """Pallas flash attention, [B, H, S, D] -> [B, H, S, D].

    Forward runs the tiled online-softmax kernel and saves only the per-row
    logsumexp; the VJP is the FlashAttention-2-style block-recompute pair of
    Pallas kernels (dQ streaming KV, dK/dV streaming Q), so training memory
    stays O(S) — the [S, S] score matrix is never materialized in either
    direction.  ``interpret=True`` runs the kernels in interpreter mode for
    CPU tests.

    ``kv_lens`` ([B] int, or None) masks the padded key tail per sequence —
    key/value positions >= kv_lens[b] are dropped from the softmax (the
    right-padded BERT mask family, fused into the kernel).  Every length
    must be >= 1.  custom_vjp functions take positional arguments only.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    out, _ = _flash_forward(
        q, k, v, kv_lens, causal=causal, scale=scale,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
    return out


def _flash_fwd(q, k, v, kv_lens, causal, scale, block_q, block_k, interpret):
    if scale is None:
        scale = q.shape[-1] ** -0.5
    out, lse = _flash_forward(
        q, k, v, kv_lens, causal=causal, scale=scale,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
    return out, (q, k, v, kv_lens, out, lse)


def _flash_bwd(causal, scale, block_q, block_k, interpret, res, g):
    q, k, v, kv_lens, out, lse = res
    if scale is None:
        scale = q.shape[-1] ** -0.5
    dq, dk, dv = _flash_backward(
        q, k, v, kv_lens, out, lse, g, causal=causal, scale=scale,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
    dlens = (
        None if kv_lens is None
        else np.zeros(kv_lens.shape, jax.dtypes.float0)
    )
    return dq, dk, dv, dlens


flash_attention.defvjp(_flash_fwd, _flash_bwd)


# The mesh the program being traced will run on, as its owner declares it
# (the Trainer wraps every model.apply in ``kernel_mesh``).  GSPMD cannot
# partition a Mosaic kernel — under a jit over more than one device the
# lowering refuses it ("Mosaic kernels cannot be automatically
# partitioned") — so there the flash call runs inside a shard_map, each
# device on its own block of the batch (and of the heads, under tensor
# parallelism).
_KERNEL_MESH: contextvars.ContextVar = contextvars.ContextVar(
    "kernel_mesh", default=None
)


@contextlib.contextmanager
def kernel_mesh(mesh):
    """Declare ``mesh`` as the device mesh of whatever is traced inside."""
    token = _KERNEL_MESH.set(mesh)
    try:
        yield
    finally:
        _KERNEL_MESH.reset(token)


def _kernel_specs(q):
    """(q/k/v spec, kv_lens spec, ways the batch splits) to shard_map the
    flash call with, or None when it runs as it is: no mesh declared, one
    device, or already inside a shard_map (the sharded train step,
    ring/ulysses, a pipeline stage)."""
    mesh = _KERNEL_MESH.get()
    if (mesh is None or mesh.size == 1
            or jax.sharding.get_abstract_mesh().manual_axes):
        return None
    batch = tuple(
        a for a in ("data", "fsdp")
        if a in mesh.axis_names and mesh.shape[a] > 1
    )
    heads = (
        "tensor" if mesh.shape.get("tensor", 1) > 1
        and q.shape[1] % mesh.shape["tensor"] == 0 else None
    )
    return (
        P(batch or None, heads, None, None), P(batch or None),
        math.prod(mesh.shape[a] for a in batch),
    )


def _off_tile(q, k, block_q, block_k) -> bool:
    return bool(
        q.shape[-2] % block_q or k.shape[-2] % block_k
        or q.shape[-1] % 64  # sublane-friendly head dim (Mosaic pads 64->128)
    )


def _flash_on_mesh(q, k, v, kv_lens, causal, scale, block_q, block_k,
                   interpret=False):
    """The flash kernel (padded to tile shapes where it must be) on the
    declared mesh."""
    fn = _flash_padded if _off_tile(q, k, block_q, block_k) else (
        flash_attention
    )
    specs = _kernel_specs(q)
    if specs is None:
        return fn(q, k, v, kv_lens, causal, scale, block_q, block_k,
                  interpret)
    spec, lens_spec, _ = specs
    if scale is None:
        scale = q.shape[-1] ** -0.5
    lens = () if kv_lens is None else (kv_lens,)
    return jax.shard_map(
        lambda q, k, v, *lens: fn(q, k, v, *(lens or (None,)), causal, scale,
                                  block_q, block_k, interpret),
        mesh=_KERNEL_MESH.get(),
        in_specs=(spec, spec, spec) + (lens_spec,) * len(lens),
        out_specs=spec, check_vma=False,
    )(q, k, v, *lens)


def _flash_supported(q, k) -> bool:
    """What 'auto' needs before it picks the kernel: the TPU, equal
    lengths (the kernel's causal mask is diagonal-aligned), and a batch
    the declared mesh's data axes divide."""
    specs = _kernel_specs(q)
    if specs is not None and q.shape[0] % specs[2]:
        return False
    return jax.default_backend() == "tpu" and q.shape[-2] == k.shape[-2]


# In 'auto' mode the padded-flash path only engages from this sequence
# length up: padding to the next block multiple costs up to
# (ceil(S/128)*128 / S)^2 extra score FLOPs, which at short S can hand
# back more than flash saves, while the XLA path's materialized [S, S]
# scores are still cheap there.  From ~1K tokens the O(S) memory and
# fused-softmax wins dominate.  Explicit implementation='flash' pads at
# any length.
_AUTO_PAD_MIN_SEQ = 1024


def _flash_padded(q, k, v, kv_lens, causal, scale, block_q, block_k,
                  interpret=False):
    """Run the flash kernel on shapes it cannot take directly, by padding.

    * head_dim -> next multiple of 64: zero-padding q and k adds zero
      terms to every score (q·k over the padded lanes), and zero-padding
      v makes the extra output lanes exact zeros — both sliced off, so
      the result is bit-equivalent math, not an approximation.
    * seq -> next multiple of lcm(block_q, block_k): padded KEYS are
      masked via the kernel's fused ``kv_lens`` right-padding (so they
      contribute nothing forward and get zero dK/dV); padded QUERY rows
      compute values that are sliced off, and their output cotangent is
      zero under the slice's VJP, so ds for those rows vanishes and they
      contribute nothing to dQ/dK/dV either.

    Requires s_q == s_k (the kernel's causal mask is diagonal-aligned);
    ``scale`` is resolved against the ORIGINAL head_dim before padding.
    """
    b, h, s, d = q.shape
    if scale is None:
        scale = d ** -0.5
    block = math.lcm(block_q, block_k)
    s_pad = -(-s // block) * block
    d_pad = -(-d // 64) * 64
    pad = ((0, 0), (0, 0), (0, s_pad - s), (0, d_pad - d))
    qp, kp, vp = (jnp.pad(t, pad) for t in (q, k, v))
    if kv_lens is None and s_pad == s:
        # Head-dim-only padding adds no masked keys — keep the unmasked
        # kernel variant (no SMEM lens operand, no per-block keep mask).
        lens = None
    elif kv_lens is None:
        lens = jnp.full((b,), s, jnp.int32)
    else:
        lens = jnp.minimum(kv_lens.astype(jnp.int32), s)
    out = flash_attention(
        qp, kp, vp, lens, causal, scale, block_q, block_k, interpret
    )
    return out[..., :s, :d]


def attention(
    q, k, v,
    *,
    causal: bool = False,
    mask: Optional[jax.Array] = None,
    kv_lens: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    implementation: str = "auto",
    block_q: int = 128,
    block_k: int = 128,
    mesh=None,
    ring_axis: str = "sequence",
    window: Optional[int] = None,
):
    """Dispatch between the Pallas flash kernel, ring sequence parallelism
    and the XLA path.

    ``window`` (with ``causal``) makes the causal mask a band: position t
    attends positions j with ``t - window < j <= t``.  Only the XLA path
    states it, so 'auto' takes that path and the others refuse (the flash
    kernel skipping the blocks outside the band is not written yet).

    ``implementation``: 'auto' | 'xla' | 'flash' | 'ring' | 'ulysses'.
    ARBITRARY masks always take the XLA path (requesting 'flash' with one
    is an error rather than a silent drop), but the right-padded mask
    family — ``kv_lens`` [B] valid-key counts, the BERT padding case — is
    fused into the flash kernel, so padded batches keep the O(S) kernel
    instead of falling back.  When both ``mask`` and ``kv_lens`` are given
    they must describe the same thing (callers pass the boolean mask for
    the XLA fallback and the lengths for the kernel); the flash path uses
    only ``kv_lens``.  Lengths are clamped to >= 1 on BOTH paths (a
    zero-length row would divide by an empty softmax in the kernel and
    produce uniform garbage in the fallback — the clamp makes the two
    backends agree on attending key 0).  The flash kernel also requires
    s_q == s_k — its
    causal mask is aligned to the main diagonal, whereas the XLA path uses
    bottom-right alignment for cross-length decode shapes.

    Off-tile shapes (sequence not divisible by the block sizes, head_dim
    not a multiple of 64) run the kernel through ``_flash_padded`` —
    exact math via zero-padding plus the fused kv_lens mask, at the cost
    of the padded block's extra FLOPs.  'flash' pads whenever needed;
    'auto' pads only from ``_AUTO_PAD_MIN_SEQ`` tokens up, where the
    O(S) memory win dominates, and otherwise falls back to XLA.

    'ring' runs sequence-parallel ring attention (parallel.ring) over
    ``mesh[ring_axis]`` — K/V shards rotate around the ICI ring while each
    device attends its local query shard; requires ``mesh``.  'ulysses'
    is the all-to-all variant (parallel.ulysses): one a2a scatters heads /
    gathers sequence, attention runs dense locally, a second a2a restores
    the layout; requires ``mesh`` and heads divisible by the axis size.
    """
    if window is not None:
        if (not causal or kv_lens is not None
                or implementation not in ("auto", "xla")):
            raise ValueError(
                "a window needs causal=True, no kv_lens and the XLA path "
                f"(got causal={causal}, implementation={implementation!r})"
            )
        return dot_product_attention(
            q, k, v, causal=True, mask=mask, scale=scale, window=window
        )
    if implementation in ("ring", "ulysses"):
        # Shared preconditions for the sequence-parallel strategies.
        if mask is not None or kv_lens is not None:
            raise ValueError(
                f"{implementation} attention supports the causal mask only; "
                "pass implementation='xla' for arbitrary masks"
            )
        if mesh is None or ring_axis not in mesh.axis_names:
            raise ValueError(
                f"implementation='{implementation}' needs a mesh with a "
                f"live '{ring_axis}' axis (got mesh={mesh})"
            )
        if implementation == "ring":
            from ml_trainer_tpu.parallel.ring import ring_attention as sp_fn
        else:
            from ml_trainer_tpu.parallel.ulysses import (
                ulysses_attention as sp_fn,
            )
        return sp_fn(
            q, k, v, mesh, axis_name=ring_axis, causal=causal, scale=scale
        )
    if kv_lens is not None:
        # Contract: every length >= 1 (see docstring); clamp on both
        # backends so they agree instead of NaN-vs-garbage divergence.
        kv_lens = jnp.maximum(kv_lens, 1)
    if implementation == "flash":
        if mask is not None and kv_lens is None:
            raise ValueError(
                "flash attention supports the causal mask and kv_lens "
                "right-padding only; pass implementation='xla' (or 'auto') "
                "for arbitrary masks"
            )
        if q.shape[-2] != k.shape[-2]:
            raise ValueError(
                "flash attention requires equal query/key lengths "
                f"(got {q.shape[-2]} vs {k.shape[-2]}); use the XLA path"
            )
        # Off-tile shapes run through the padding wrapper — exact math
        # (see _flash_padded), slightly more FLOPs.
        return _flash_on_mesh(
            q, k, v, kv_lens, causal, scale, block_q, block_k
        )
    if implementation == "auto" and (mask is None or kv_lens is not None):
        # Long off-tile sequences pad: the O(S) memory win beats the
        # padding overhead (see _AUTO_PAD_MIN_SEQ rationale).
        if _flash_supported(q, k) and (
            q.shape[-2] >= _AUTO_PAD_MIN_SEQ
            or not _off_tile(q, k, block_q, block_k)
        ):
            return _flash_on_mesh(
                q, k, v, kv_lens, causal, scale, block_q, block_k
            )
    if mask is None and kv_lens is not None:
        # XLA fallback must honor the padding the kernel would have fused.
        mask = (
            jnp.arange(k.shape[-2])[None, None, None, :]
            < kv_lens[:, None, None, None]
        )
    return dot_product_attention(q, k, v, causal=causal, mask=mask, scale=scale)
