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
# A grid step of a Pallas kernel costs about 0.35 us on a v5e before it
# computes anything, so the three kernels below do as much in a step as VMEM
# allows.  The grid walks (batch*head, q block, kv block) with blocks chosen
# from the shape (``_flash_blocks``), and INSIDE a step the body sweeps the
# step's keys in sub-blocks of ``sub_k``, so the score tile is
# [block_q, sub_k] however many keys the step holds.  The sweep's trip count
# is the causal (and ``kv_lens``) bound: the dead part of a block the
# diagonal crosses is not computed, and only the sub-blocks the diagonal or
# the padded tail crosses pay for a mask.  A kv block that is dead
# altogether names, in its index map, the block already in VMEM, so the
# pipeline fetches nothing for it.  The products take their operands in the
# dtype they arrive in (bfloat16 into the MXU, float32 out); the softmax
# statistics and every accumulator are float32.
_NEG = float(np.finfo(np.float32).min)
_NT = (((1,), (1,)), ((), ()))   # a @ b.T
_NN = (((1,), (0,)), ((), ()))   # a @ b

# What scripts/flash_tune.py measured on a v5e (PERF.md section 6, PR 28).
_BLOCK_Q = 512                   # rows of the score tile
_SUB_K = 512                     # keys a sweep step takes: its columns
# Of K (and as much of V) a grid step holds: 1,024 keys of 64 in bfloat16.
# Bytes, not keys: a head of 128 gets half the keys a head of 64 gets, and
# float32 half of bfloat16, so the step's VMEM stays what was measured.
_KV_BLOCK_BYTES = 128 * 1024


def _largest_block(s: int, cap: int) -> int:
    """Largest multiple of 128 that divides ``s`` and is <= ``cap``; 128
    where none larger does; ``s`` itself where not even 128 divides it (one
    block: only the interpreter is handed such a shape)."""
    if s % 128:
        return s
    return max(m for m in range(128, max(cap, 128) + 1, 128) if s % m == 0)


def _flash_blocks(s_q: int, s_k: int, d: int, dtype) -> tuple:
    """(block_q, block_k) from the shape and the dtype alone.  ``block_k`` is
    what a grid step holds of K and V, swept in sub-blocks; ``block_q`` is
    the height of the score tile.  On the v5e all three kernels are bound by
    the MXU, half filled by heads of 64, and by a fixed cost a tile, so one
    pair is best for all three: a q block of 256 wastes less above the
    diagonal (1.25x against 1.5x) and is slower for its twice as many
    tiles."""
    return (
        _largest_block(s_q, _BLOCK_Q),
        _largest_block(
            s_k, _KV_BLOCK_BYTES // (d * jnp.dtype(dtype).itemsize)),
    )


def _resolve_blocks(block_q, block_k, q, k) -> tuple:
    """An explicit integer is honoured; ``None`` is the chooser's."""
    bq, bk = _flash_blocks(q.shape[2], k.shape[2], q.shape[3], q.dtype)
    return block_q or bq, block_k or bk


def _sub_block(block_k: int) -> int:
    """Keys one sweep step takes of a kv block of ``block_k``."""
    return _largest_block(block_k, _SUB_K)


def _lanes(stat, n: int):
    """A per-row statistic against a tile ``n`` lanes wide.  The kernels
    keep the running maximum, the sum, ``lse`` and ``delta`` replicated
    across 128 lanes ([block_q, 128]: whole vregs, and a tile takes them
    by repeating the vreg, which costs nothing) where a [block_q, 1] column
    would use one lane of each vreg and pay a lane broadcast at every use
    (2.5 against 1.5 ms for the forward at the training cell's shape, q
    blocks of 256, v5e, PR 28).  Blocks that 128 does not divide (the
    interpreter's) keep the column, which broadcasts by itself."""
    w = stat.shape[-1]
    if w == 1 or n == w:
        return stat
    return jnp.tile(stat, (1, n // w)) if n > w else stat[:, :n]


def _stat_lanes(*blocks: int) -> int:
    """Lanes a per-row statistic is replicated across (see ``_lanes``)."""
    return 1 if any(b % 128 for b in blocks) else 128


def _rows_from_stat(stat):
    """[block_q, w] statistic -> its rows' values with the POSITIONS ON THE
    LANES, [block_q // 128, 128] (or [1, block_q] from a column): what HBM
    keeps without padding.  From the replicated form each 128 x 128 square
    gives up its diagonal (a select and a sum over sublanes): 0.06 ms a call
    at the training cell's shape, where reshaping the column costs 0.64 and
    a transpose 0.12 (v5e, PR 28)."""
    block_q, w = stat.shape
    if w == 1:
        return stat.reshape(1, block_q)
    squares = stat.reshape(block_q // w, w, w)
    diagonal = (jax.lax.broadcasted_iota(jnp.int32, squares.shape, 1)
                == jax.lax.broadcasted_iota(jnp.int32, squares.shape, 2))
    return jnp.sum(jnp.where(diagonal, squares, 0.0), axis=1)


def _stat_from_row(row, w: int):
    """[1, block_q] row, positions on the lanes -> [block_q, w] replicated
    across ``w`` lanes: copy the row down 128 sublanes (cheap) and transpose
    the squares.  0.09 ms a call and statistic at the training cell's shape,
    where reshaping to a column costs 0.2 (v5e, PR 28)."""
    block_q = row.shape[-1]
    if w == 1:
        return row.reshape(block_q, 1)
    return jnp.broadcast_to(row, (w, block_q)).T


def _keep_mask(p_shape, q_start, kv_start, kv_len, causal, masked,
               q_axis=0):
    """The score-keep mask shared by all three kernels (forward and the
    two backward passes): causal diagonal and/or the padded-key tail —
    one definition so value and gradient masking cannot diverge.  The
    queries lie along ``q_axis`` of the tile (dK/dV works on its transpose)."""
    qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, p_shape, q_axis)
    kpos = kv_start + jax.lax.broadcasted_iota(jnp.int32, p_shape, 1 - q_axis)
    keep = None
    if causal:
        keep = qpos >= kpos
    if masked:
        keep_pad = kpos < kv_len
        keep = keep_pad if keep is None else jnp.logical_and(keep, keep_pad)
    return keep


def _kv_sweep(step, *, q_start, block_q, kv_start, block_k, sub_k, kv_len,
              causal):
    """Call ``step(k0, needs_mask)`` for the sub-blocks [k0, k0 + sub_k) of
    one kv block that some query of the q block attends: first those every
    query attends whole (no mask), then those the diagonal or the padded
    tail crosses.  A block that is dead altogether runs nothing."""
    from jax.experimental import pallas as pl

    n_sub = block_k // sub_k

    def subs_below(bound, partly):
        # sub-blocks lying wholly (or, with `partly`, at all) below `bound`
        keys = jnp.maximum(bound - kv_start + (sub_k - 1) * partly, 0)
        return jnp.minimum(keys // sub_k, n_sub)

    def sweep(lo, hi, needs_mask):
        jax.lax.fori_loop(
            lo, hi,
            lambda i, c: step(pl.multiple_of(i * sub_k, sub_k), needs_mask),
            None,
        )

    n_full = n_live = n_sub
    if causal:
        n_full = subs_below(q_start + 1, False)
        n_live = subs_below(q_start + block_q, True)
    if kv_len is not None:
        n_full = jnp.minimum(n_full, subs_below(kv_len, False))
        n_live = jnp.minimum(n_live, subs_below(kv_len, True))
    sweep(0, n_full, False)
    if causal or kv_len is not None:
        sweep(n_full, n_live, True)


def _kv_stream_map(causal: bool, block_q: int, block_k: int):
    """Index map of K and V where they stream past a resident q block
    (forward and dQ; grid (batch*head, q block, kv block)).  Under the
    causal mask a dead kv block names the last one q block ``j`` attends,
    which is in VMEM already: the pipeline fetches nothing for it."""
    def index_map(i, j, kv):
        if causal:
            kv = jnp.minimum(kv, (j * block_q + block_q - 1) // block_k)
        return (i, kv, 0)

    return index_map


def _flash_kernel(*refs, sub_k: int, causal: bool, scale: float,
                  masked: bool):
    """One (batch·head, q-block, kv-block) grid step of the online-softmax
    recurrence.  The step's keys are swept ``sub_k`` at a time
    (``_kv_sweep``) against the resident q block, with running (o, m, l)
    accumulators in VMEM scratch that persist across kv steps; the final kv
    step normalizes and writes the output block.  With ``masked`` a
    per-sequence valid-key count streams in via SMEM and columns past it are
    dropped — the right-padded (BERT) mask family, fused into the kernel
    instead of falling back to the XLA path."""
    from jax.experimental import pallas as pl

    if masked:
        q_ref, k_ref, v_ref, lens_ref, o_ref, lse_ref, o_scr, m_scr, l_scr = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, o_scr, m_scr, l_scr = refs
        lens_ref = None

    _, block_q, d = q_ref.shape
    block_k = k_ref.shape[1]
    kv_idx = pl.program_id(2)
    q_start = pl.program_id(1) * block_q
    kv_start = kv_idx * block_k

    @pl.when(kv_idx == 0)
    def _init():
        o_scr[:] = jnp.zeros(o_scr.shape, jnp.float32)
        m_scr[:] = jnp.full(m_scr.shape, _NEG, jnp.float32)
        l_scr[:] = jnp.zeros(l_scr.shape, jnp.float32)

    kv_len = lens_ref[pl.program_id(0)] if masked else None
    q = q_ref[0]

    def _attend(k0, needs_mask):
        kk = k_ref[0, pl.ds(k0, sub_k), :]
        vv = v_ref[0, pl.ds(k0, sub_k), :]
        scores = jax.lax.dot_general(
            q, kk, _NT, preferred_element_type=jnp.float32,
        ) * scale  # [block_q, sub_k]
        if needs_mask:
            keep = _keep_mask(
                scores.shape, q_start, kv_start + k0, kv_len, causal, masked,
            )
            scores = jnp.where(keep, scores, _NEG)
        m_prev, l_prev = m_scr[:], l_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
        p = jnp.exp(scores - _lanes(m_new, sub_k))
        alpha = jnp.exp(m_prev - m_new)
        m_scr[:] = m_new
        l_scr[:] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        o_scr[:] = o_scr[:] * _lanes(alpha, d) + jax.lax.dot_general(
            p.astype(vv.dtype), vv, _NN, preferred_element_type=jnp.float32,
        )

    _kv_sweep(_attend, q_start=q_start, block_q=block_q, kv_start=kv_start,
              block_k=block_k, sub_k=sub_k, kv_len=kv_len, causal=causal)

    @pl.when(kv_idx == pl.num_programs(2) - 1)
    def _finalize():
        o_ref[0] = (o_scr[:] / _lanes(l_scr[:], d)).astype(o_ref.dtype)
        # Per-row logsumexp of the scaled scores — the only softmax
        # statistic the flash backward needs (FlashAttention-2 style).
        # Written with the positions on the lanes: in HBM a [.., S, 1]
        # array is tiled with its last dimension padded to 128 lanes, 128
        # times the bytes, and the backward would keep that.
        lse_ref[0, 0] = _rows_from_stat(m_scr[:] + jnp.log(l_scr[:]))


def _lens_per_bh(kv_lens, b, h):
    """[B] valid-key counts -> [B*H] int32 (one per grid row)."""
    return jnp.repeat(kv_lens.astype(jnp.int32), h)


def _compiler_params():
    """Heads and q (or kv) blocks are independent; the last grid axis
    accumulates."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


# Both wrappers are jitted so that a model's layers, which call them with the
# same shapes, share ONE trace and ONE lowering to Mosaic of each kernel: a
# step of 12 layers otherwise lowers 36 kernel bodies on every start, compile
# cache or not (as ops/kernels/slot_cache_write.py found for its own call).
_STATIC = ("causal", "scale", "block_q", "block_k", "interpret")


@functools.partial(jax.jit, static_argnames=_STATIC)
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
    sub_k = _sub_block(block_k)
    stat_lanes = _stat_lanes(block_q, sub_k)
    # lse leaves as [.., q blocks, block_q / 128, 128] (or [.., 1, block_q]):
    # row-major that IS [B*H, S].
    lse_block = (block_q // stat_lanes, stat_lanes) if stat_lanes > 1 else (
        1, block_q)
    kernel = functools.partial(
        _flash_kernel, sub_k=sub_k, causal=causal, scale=scale, masked=masked,
    )
    grid = (b * h, pl.cdiv(s_q, block_q), pl.cdiv(s_k, block_k))

    kv_map = _kv_stream_map(causal, block_q, block_k)
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda i, j, kv: (i, j, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_k, d), kv_map, memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_k, d), kv_map, memory_space=pltpu.VMEM),
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
            pl.BlockSpec((1, 1) + lse_block, lambda i, j, kv: (i, j, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s_q, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, grid[1]) + lse_block, jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, stat_lanes), jnp.float32),
            pltpu.VMEM((block_q, stat_lanes), jnp.float32),
        ],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="flash_fwd",
    )(*operands)
    return out.reshape(b, h, s_q, d), lse.reshape(b, h, s_q)


# Per-row statistics (lse, delta) travel between the kernels as [B*H, 1, S]
# rows, positions on the lanes, in (1, 1, block_q) blocks.  dQ, whose score
# tile has the queries on the sublanes, spreads its block over the lanes
# (``_lanes``) once a grid step; dK/dV computes the TRANSPOSED tile (keys on the sublanes,
# queries on the lanes), so a row is what it subtracts, and all four of its
# products are plain ones: nothing is transposed on the way to the MXU.


def _flash_bwd_dq_kernel(*refs, sub_k: int, causal: bool, scale: float,
                         masked: bool):
    """dQ pass: one q-block stays resident while KV blocks stream through
    (kv is the fastest grid axis), each swept ``sub_k`` keys at a time; dQ
    accumulates in VMEM scratch and is written once on the last kv step.
    Recomputes P from (q, k, lse) — the block-recompute that keeps backward
    memory O(S)."""
    from jax.experimental import pallas as pl

    if masked:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, lens_ref,
         dq_ref, dq_scr) = refs
    else:
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr = refs
        lens_ref = None

    _, block_q, d = q_ref.shape
    block_k = k_ref.shape[1]
    kv_idx = pl.program_id(2)
    q_start = pl.program_id(1) * block_q
    kv_start = kv_idx * block_k

    @pl.when(kv_idx == 0)
    def _init():
        dq_scr[:] = jnp.zeros((block_q, d), jnp.float32)

    kv_len = lens_ref[pl.program_id(0)] if masked else None
    q = q_ref[0]
    do = do_ref[0]
    lse = _stat_from_row(lse_ref[0], _stat_lanes(block_q, sub_k))
    delta = _stat_from_row(delta_ref[0], _stat_lanes(block_q, sub_k))

    def _accumulate(k0, needs_mask):
        kk = k_ref[0, pl.ds(k0, sub_k), :]
        vv = v_ref[0, pl.ds(k0, sub_k), :]
        scores = jax.lax.dot_general(
            q, kk, _NT, preferred_element_type=jnp.float32,
        ) * scale
        p = jnp.exp(scores - _lanes(lse, sub_k))   # [block_q, sub_k]
        if needs_mask:
            keep = _keep_mask(
                p.shape, q_start, kv_start + k0, kv_len, causal, masked,
            )
            p = jnp.where(keep, p, 0.0)
        dp = jax.lax.dot_general(
            do, vv, _NT, preferred_element_type=jnp.float32,
        )
        ds = p * (dp - _lanes(delta, sub_k))   # times `scale`: in _finalize
        dq_scr[:] = dq_scr[:] + jax.lax.dot_general(
            ds.astype(kk.dtype), kk, _NN, preferred_element_type=jnp.float32,
        )

    _kv_sweep(_accumulate, q_start=q_start, block_q=block_q,
              kv_start=kv_start, block_k=block_k, sub_k=sub_k, kv_len=kv_len,
              causal=causal)

    @pl.when(kv_idx == pl.num_programs(2) - 1)
    def _finalize():
        dq_ref[0] = (dq_scr[:] * scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(*refs, sub_k: int, causal: bool, scale: float,
                          masked: bool):
    """dK/dV pass: one kv-block stays resident while Q blocks stream through
    (q is the fastest grid axis); dK and dV accumulate in VMEM scratch, a
    sub-block of ``sub_k`` keys at a time.  The tile is the transpose of the
    other two kernels': [sub_k, block_q]."""
    from jax.experimental import pallas as pl

    if masked:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, lens_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
        lens_ref = None

    _, block_k, d = k_ref.shape
    block_q = q_ref.shape[1]
    q_idx = pl.program_id(2)
    kv_start = pl.program_id(1) * block_k
    q_start = q_idx * block_q

    @pl.when(q_idx == 0)
    def _init():
        dk_scr[:] = jnp.zeros((block_k, d), jnp.float32)
        dv_scr[:] = jnp.zeros((block_k, d), jnp.float32)

    # A kv block entirely in the padded tail gets zero gradient.
    kv_len = lens_ref[pl.program_id(0)] if masked else None
    q = q_ref[0]
    do = do_ref[0]
    lse = lse_ref[0]                       # [1, block_q]
    delta = delta_ref[0]

    def _accumulate(k0, needs_mask):
        rows = pl.ds(k0, sub_k)
        kk = k_ref[0, rows, :]
        vv = v_ref[0, rows, :]
        scores = jax.lax.dot_general(
            kk, q, _NT, preferred_element_type=jnp.float32,
        ) * scale
        p = jnp.exp(scores - lse)          # [sub_k, block_q]
        if needs_mask:
            keep = _keep_mask(
                p.shape, q_start, kv_start + k0, kv_len, causal, masked,
                q_axis=1,
            )
            p = jnp.where(keep, p, 0.0)
        dv_scr[rows, :] = dv_scr[rows, :] + jax.lax.dot_general(
            p.astype(do.dtype), do, _NN, preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            vv, do, _NT, preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta)              # times `scale`: in _finalize
        dk_scr[rows, :] = dk_scr[rows, :] + jax.lax.dot_general(
            ds.astype(q.dtype), q, _NN, preferred_element_type=jnp.float32,
        )

    _kv_sweep(_accumulate, q_start=q_start, block_q=block_q,
              kv_start=kv_start, block_k=block_k, sub_k=sub_k, kv_len=kv_len,
              causal=causal)

    @pl.when(q_idx == pl.num_programs(2) - 1)
    def _finalize():
        dk_ref[0] = (dk_scr[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=_STATIC)
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
    lser = lse.reshape(b * h, 1, s_q)
    # delta_i = rowsum(dO_i * O_i) — a cheap elementwise reduce; let XLA
    # fuse it rather than adding a third kernel pass.
    delta = jnp.sum(
        dor.astype(jnp.float32) * out.reshape(b * h, s_q, d).astype(jnp.float32),
        axis=-1,
    ).reshape(b * h, 1, s_q)
    masked = kv_lens is not None
    operands = [qr, kr, vr, dor, lser, delta]
    lens_spec = []
    if masked:
        operands.append(_lens_per_bh(kv_lens, b, h))
        lens_spec = [pl.BlockSpec(memory_space=pltpu.SMEM)]

    def vmem(block, index_map):
        return pl.BlockSpec(block, index_map, memory_space=pltpu.VMEM)

    qspec = vmem((1, block_q, d), lambda i, j, x: (i, j, 0))
    rowspec = vmem((1, 1, block_q), lambda i, j, x: (i, 0, j))
    kvspec = vmem((1, block_k, d), _kv_stream_map(causal, block_q, block_k))
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, sub_k=_sub_block(block_k),
                          causal=causal, scale=scale, masked=masked),
        grid=(b * h, pl.cdiv(s_q, block_q), pl.cdiv(s_k, block_k)),
        in_specs=[qspec, kvspec, kvspec, qspec, rowspec, rowspec] + lens_spec,
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((b * h, s_q, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="flash_bwd_dq",
    )(*operands)

    def q_stream(j, x):
        if causal:  # the q blocks before kv block j's first live one name it
            x = jnp.maximum(x, (j * block_k) // block_q)
        return x

    kvspec = vmem((1, block_k, d), lambda i, j, x: (i, j, 0))
    qspec = vmem((1, block_q, d), lambda i, j, x: (i, q_stream(j, x), 0))
    rowspec = vmem((1, 1, block_q), lambda i, j, x: (i, 0, q_stream(j, x)))
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, sub_k=_sub_block(block_k),
                          causal=causal, scale=scale, masked=masked),
        grid=(b * h, pl.cdiv(s_k, block_k), pl.cdiv(s_q, block_q)),
        in_specs=[qspec, kvspec, kvspec, qspec, rowspec, rowspec] + lens_spec,
        out_specs=[kvspec, kvspec],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s_k, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, s_k, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=_compiler_params(),
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
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: bool = False,
):
    """Pallas flash attention, [B, H, S, D] -> [B, H, S, D].

    Forward runs the tiled online-softmax kernel and saves only the per-row
    logsumexp; the VJP is the FlashAttention-2-style block-recompute pair of
    Pallas kernels (dQ streaming KV, dK/dV streaming Q), so training memory
    stays O(S) — the [S, S] score matrix is never materialized in either
    direction.  ``interpret=True`` runs the kernels in interpreter mode for
    CPU tests.

    ``block_q`` / ``block_k`` left ``None`` are chosen from the shape and
    the dtype (``_flash_blocks``); an integer is taken as given (compiled,
    a multiple of 128 or the whole sequence: the per-row statistics leave
    the forward with the positions on the lanes).

    ``kv_lens`` ([B] int, or None) masks the padded key tail per sequence —
    key/value positions >= kv_lens[b] are dropped from the softmax (the
    right-padded BERT mask family, fused into the kernel).  Every length
    must be >= 1.  custom_vjp functions take positional arguments only.
    """
    return _flash_fwd(
        q, k, v, kv_lens, causal, scale, block_q, block_k, interpret
    )[0]


def _flash_fwd(q, k, v, kv_lens, causal, scale, block_q, block_k, interpret):
    if scale is None:
        scale = q.shape[-1] ** -0.5
    block_q, block_k = _resolve_blocks(block_q, block_k, q, k)
    out, lse = _flash_forward(
        q, k, v, kv_lens, causal=causal, scale=scale,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
    return out, (q, k, v, kv_lens, out, lse)


def _flash_bwd(causal, scale, block_q, block_k, interpret, res, g):
    q, k, v, kv_lens, out, lse = res
    if scale is None:
        scale = q.shape[-1] ** -0.5
    block_q, block_k = _resolve_blocks(block_q, block_k, q, k)
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


def _padded_head(d: int) -> int:
    """The head dim the kernel takes: a multiple of 64 up to the 128 lanes
    (Mosaic pads 64 -> 128), whole lane tiles beyond them (the statistics
    are spread along 128 lanes; a head of 192 is refused by the lowering)."""
    return -(-d // 64) * 64 if d <= 128 else -(-d // 128) * 128


def _off_tile(q, k, block_q=None, block_k=None) -> bool:
    """Whether the kernel needs ``_flash_padded``.  Blocks left to the
    chooser are multiples of 128 that divide the sequence, so 128 is the
    test; which shapes 'auto' hands the kernel does not follow the caps."""
    return bool(
        q.shape[-2] % (block_q or 128) or k.shape[-2] % (block_k or 128)
        or q.shape[-1] != _padded_head(q.shape[-1])
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
# length up: padding to the next multiple of 128 (the blocks are chosen
# AFTER padding, among its divisors) costs up to
# (ceil(S/128)*128 / S)^2 extra score FLOPs, which at short S can hand
# back more than flash saves, while the XLA path's materialized [S, S]
# scores are still cheap there.  From ~1K tokens the O(S) memory and
# fused-softmax wins dominate.  Explicit implementation='flash' pads at
# any length.
_AUTO_PAD_MIN_SEQ = 1024


def _flash_padded(q, k, v, kv_lens, causal, scale, block_q, block_k,
                  interpret=False):
    """Run the flash kernel on shapes it cannot take directly, by padding.

    * head_dim -> ``_padded_head``: zero-padding q and k adds zero
      terms to every score (q·k over the padded lanes), and zero-padding
      v makes the extra output lanes exact zeros — both sliced off, so
      the result is bit-equivalent math, not an approximation.
    * seq -> next multiple of 128, or of lcm(block_q, block_k) where they
      are given (blocks left to the chooser are chosen from the PADDED
      length, so 1,100 pads to 1,152 and runs in blocks of 384, not to a
      multiple of the largest block): padded KEYS are
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
    block = math.lcm(block_q or 128, block_k or 128)
    s_pad = -(-s // block) * block
    d_pad = _padded_head(d)
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
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
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

    ``block_q`` / ``block_k`` left ``None`` are chosen by the kernel from
    the shape it is handed (``_flash_blocks``).  Off-tile shapes (sequence
    not divisible by 128, or by the blocks where they are given; head_dim
    not a multiple of 64, or of 128 beyond 128) run the kernel through
    ``_flash_padded`` — exact math via zero-padding plus the fused kv_lens
    mask, at the cost of the padded block's extra FLOPs.  'flash' pads
    whenever needed; 'auto' pads only from ``_AUTO_PAD_MIN_SEQ`` tokens up, where the
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
