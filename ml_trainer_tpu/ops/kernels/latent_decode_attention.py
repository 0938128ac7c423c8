"""Decode attention over a latent cache: one query position a row, the
row's live blocks fetched once and used as the keys AND the values.

A latent attention layer (``models/kimi_linear.py``, MLA) caches one row
``[c^; k_r]`` of ``c + r`` values a token under ONE key-value head.  Its
decode step takes each query head into the latent space (the absorbed
query, ``[B, H, 1, c + r]``), scores it against the cached rows as they lie,
and keeps the weighted sum of the rows' first ``c`` values.  Stated in XLA
(``grouped_decode_attention`` with the pool as ``k_cache`` and as
``v_cache``) that is two einsums over all ``L`` positions of every row under
a mask: the pool is read whole for the scores and read whole again for the
sum, where the rows' live positions are a third of it on
``kimi-linear-48b-ep8.reason-4k`` (PERF.md).

This kernel visits only the live blocks, on the grid ``decode_attention``
lays out (``_work_list``: the rows' live blocks end to end on one axis of
dynamic length, so a dead block costs no DMA and no grid step), and holds
each block in VMEM for both products: the scores against all ``c + r``
columns, then the weighted sum over the first ``c``.  The ``H`` query heads
share the one key-value head, so both are matrix products on the MXU with
the heads as rows: ``[H, c + r] . [c + r, block]`` and ``[H, block] .
[block, c]``, float32 out.

The pool is read where it lies.  Which of ``L`` and ``c + r`` XLA puts on
the lanes follows from the shape (``_position_on_lanes``, as for the write,
``slot_row_write``): at 4,096 rows of 576 the position is on the lanes, the
view ``[B, 1, c + r, L]`` is a bitcast and a block ``[1, 1, c + r,
block]``; with the position on the sublanes the block is the cache as
written, ``[1, 1, block, c + r]``.  The two bodies differ in which axis of
the block each product contracts, and nothing else.

The arithmetic is the reference's: the query and the pool as the
configuration states them, float32 scores, softmax statistics and
accumulator, the weights rounded to the pool's dtype for the second product.
The softmax runs online across a row's blocks (running maximum, sum and
output, rescaled a block), so parity with the reference is by tolerance.
In a row's last block the scores past its length are masked before the
maximum and the sum, and the value columns past it are zeroed before the
product, so what a freed slot left there, a NaN included, cannot reach the
output.

Contract, pinned by ``tests/test_kernels.py``:

* ``latent_decode_attention_reference`` IS the layer's read before the
  kernel: ``grouped_decode_attention`` over the pool as keys and values
  under the mask ``arange(L) < lengths``, the first ``value_dim`` values of
  each head kept.
* ``lengths`` is clamped to ``[1, L]`` on both paths, as ``decode_attention``
  clamps it: the layer hands ``landing_position(index) + 1``, and a free
  row's index runs past ``L - 1``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ml_trainer_tpu.ops.attention import _NEG
from ml_trainer_tpu.ops.kernels.decode_attention import (
    _decode_block,
    _edges,
    _softmax_step,
    _work_list,
    grouped_decode_attention,
)
from ml_trainer_tpu.ops.kernels.slot_cache_write import (
    _position_on_lanes,
    _use_pallas,
)


def latent_decode_attention_reference(q, pool, lengths, scale, value_dim):
    """The masked read of all ``L`` positions, as the layer stated it before
    the kernel."""
    L = pool.shape[2]
    valid = jnp.arange(L)[None, :] < jnp.clip(lengths, 1, L)[:, None]
    return grouped_decode_attention(
        q, pool, pool, valid, scale=scale)[..., :value_dim]


def _kernel(row_of, block_of, lens, q_ref, kv_ref, o_ref, m_scr, l_scr,
            acc_scr, *, block, scale, value_dim, on_lanes):
    """q: ``[1, H, c + r]``; the block ``[1, 1, c + r, block]`` (position on
    the lanes) or ``[1, 1, block, c + r]``; o: ``[1, H, c]``; the running
    maximum and sum ``[H, 1]``, the output ``[H, c]``, float32."""
    from jax.experimental import pallas as pl

    j, n, last = _edges(row_of, block_of, lens, block)

    @pl.when(j == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Which axis of the block is the position: the scores contract the
    # other with the query's, the weighted sum contracts this one.
    at_axis = 1 if on_lanes else 0

    def attend(last):
        s = jax.lax.dot_general(
            q_ref[0], kv_ref[0, 0], (((1,), (1 - at_axis,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # [H, block]
        v = (kv_ref[0, 0, :value_dim, :] if on_lanes
             else kv_ref[0, 0, :, :value_dim])
        if last:
            at = j * block + jax.lax.broadcasted_iota(
                jnp.int32, (1, block), 1)
            s = jnp.where(at < n, s, _NEG)
            live = j * block + jax.lax.broadcasted_iota(
                jnp.int32, v.shape, at_axis) < n
            v = jnp.where(live, v, jnp.zeros_like(v))
        p, alpha = _softmax_step(s, m_scr, l_scr)
        acc_scr[...] = alpha * acc_scr[...] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (at_axis,)), ((), ())),
            preferred_element_type=jnp.float32)              # [H, c]

    @pl.when(jnp.logical_not(last))
    def _():
        attend(False)

    @pl.when(last)
    def _():
        attend(True)
        o_ref[0] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)


# Jitted so that a model's layers share ONE trace and ONE lowering of the
# kernel (see slot_cache_write).
@functools.partial(jax.jit, static_argnames=(
    "scale", "value_dim", "block", "interpret"))
def _latent_decode_attention_pallas(q, pool, lengths, scale, value_dim,
                                    block, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, _, d = q.shape
    L = pool.shape[2]
    # 0: the chooser's; an explicit block is the sweep's that chose it.
    block = block or _decode_block(1, L, d, pool.dtype)
    lengths = jnp.clip(lengths, 1, L)
    row_of, block_of, steps = _work_list(lengths, L, block)
    on_lanes = _position_on_lanes(L, d)

    def spec(shape, index):
        return pl.BlockSpec(
            shape, lambda t, row, blk, n: index(row[t], blk[t]))

    if on_lanes:
        pool = pool.transpose(0, 1, 3, 2)        # a bitcast of XLA's layout
        kv_spec = spec((1, 1, d, block), lambda row, blk: (row, 0, 0, blk))
    else:
        kv_spec = spec((1, 1, block, d), lambda row, blk: (row, 0, blk, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, block=block, scale=scale,
                          value_dim=value_dim, on_lanes=on_lanes),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(steps,),
            in_specs=[spec((1, h, d), lambda row, blk: (row, 0, 0)), kv_spec],
            out_specs=spec((1, h, value_dim), lambda row, blk: (row, 0, 0)),
            scratch_shapes=[pltpu.VMEM(shape, jnp.float32) for shape in (
                (h, 1), (h, 1), (h, value_dim))],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, value_dim), q.dtype),
        interpret=interpret,
        name="latent_decode_attention",
    )(row_of, block_of, lengths, q[:, :, 0, :], pool)
    return out[:, :, None, :]


def latent_decode_attention(
    q: jax.Array,
    pool: jax.Array,
    lengths: jax.Array,
    *,
    scale: float,
    value_dim: int,
    implementation: str = "auto",
    interpret: bool = False,
):
    """The absorbed query ``q [B, H, 1, c + r]`` against the first
    ``lengths[b]`` rows of row ``b`` of the latent pool ``[B, 1, L, c + r]``,
    scores scaled by ``scale``; returns the weighted sum of the rows' first
    ``value_dim`` (``c``) values, ``[B, H, 1, c]``.  See the module
    docstring.

    implementation: 'auto' (pallas on TPU, reference elsewhere),
    'pallas', or 'reference'.  ``interpret=True`` runs the Pallas kernel
    in interpret mode (the CPU parity harness).
    """
    if pool.ndim != 4 or pool.shape[1] != 1:
        raise ValueError(
            f"pool must be [B, 1, L, c + r] (one key-value head), got "
            f"{pool.shape}")
    b, _, _, d = pool.shape
    if q.ndim != 4 or q.shape[0] != b or q.shape[2:] != (1, d):
        raise ValueError(
            f"q must be [{b}, H, 1, {d}] (one position a row of the pool "
            f"{pool.shape}), got {q.shape}")
    if not 0 < value_dim <= d:
        raise ValueError(
            f"value_dim {value_dim} must lie in [1, {d}], the pool's row")
    if lengths.shape != (b,):
        raise ValueError(
            f"lengths must be [{b}], one a pool row, got {lengths.shape}")
    if not _use_pallas(implementation, "latent_decode_attention"):
        return latent_decode_attention_reference(
            q, pool, lengths, scale, value_dim)
    return _latent_decode_attention_pallas(
        q, pool, jnp.asarray(lengths, jnp.int32), float(scale), value_dim, 0,
        interpret)
