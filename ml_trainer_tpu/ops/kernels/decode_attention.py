"""Decode attention over a slot cache: one query position a row, and of
each row's K and V only the blocks that hold a live position.

The slot engine's decode step (``models/layers.py::_decode_step``, the
per-row ``cache_index`` branch at ``s == 1``) attends ``q [B, H, 1, D]``
against a cache ``[B, G, L, D]`` in which row ``b`` holds ``lengths[b]``
live positions.  Stated in XLA that is a masked softmax over all ``L``
positions of every row: the mask makes the answer right and does not stop
the read, so a pool filled to 42% moves 2.4 times the bytes it needs
(PERF.md, PR 30).  This kernel takes the lengths as a scalar-prefetch
operand and visits only the live blocks.

How nothing dead is fetched, or even stepped over.  A grid step of a
Pallas kernel costs 0.4-0.5 us on a v5e before it computes anything, so a
grid ``(B, L / block)`` with the dead steps skipped would still pay for
them.  The grid here is ONE axis whose length is the number of live blocks
of this call, a traced scalar: ``_work_list`` lays the rows' live blocks
end to end (``row_of[t]``, ``block_of[t]``), the index maps read those two
vectors, and Pallas's own pipeline fetches step ``t + 1`` while step ``t``
computes, across the edge of a row as inside it.  A block past a row's
length has no step, so it costs no DMA and no time.

The cache is read where it lies.  Which two dimensions of the cache XLA
puts on the chip's (sublane, lane) tile follows from the shape alone
(``slot_cache_write._position_on_lanes``, shared, not copied), and each
layout has its body, as in that kernel:

* heads of 64, the position on the lanes: the view ``[B, G, D, L]`` (a
  transpose XLA turns into a bitcast) in blocks ``[1, G, D, block]``.
  Multiply and reduce on the VPU, as XLA's fusion does: as the MXU's
  weights a block of heads of 64 fills half the array for one row of q
  and moves 430 GB/s where the VPU moves 610 (v5e, PERF.md, PR 30);
* heads of 128, the position on the sublanes: the cache as written, blocks
  ``[1, G, block, D]``, both products on the MXU with the ``H / G`` query
  heads of a key-value head as the rows of one product against one read
  of the cache row (``G == H``: one row): the memory's speed, 745 GB/s.

A wrong guess of the layout is never a wrong byte but two copies of the
cache a call (``tests/test_tpu_compile.py`` compiles both for a described
v5e and finds none).

The arithmetic is the reference's: K, V and q as the configuration states
them, float32 scores, float32 softmax statistics and accumulator (the VPU
body widens K and V to float32; the MXU body rounds the weights to V's
dtype for the second product, as the reference does).  The softmax runs
online across a row's blocks (running maximum, sum and output, rescaled a
block), so sums are taken in another order than XLA takes them and parity
with the reference is by tolerance, not bit for bit (docs/kernels.md).  A
grid step is three phases: every head's scores, ONE update of the
statistics over all heads, every head's output; a chain of reductions and
exponentials a head costs a microsecond a step.  In the one block the
length crosses, scores past the length are masked before the maximum and
the sum and the V positions past it are zeroed before the product, so what
a freed slot left there, a NaN included, cannot reach the output.

Contract, pinned by ``tests/test_kernels.py``:

* ``decode_attention_reference`` IS the engine's path before the kernel:
  ``dot_product_attention`` under the mask ``arange(L) < lengths`` for
  ``G == H``, ``grouped_decode_attention`` for ``G < H``.
* ``lengths`` is clamped to ``[1, L]`` on both paths: the engine advances
  every row's index on every step, a free row's too, so a free row's
  length runs past ``L`` (see ``slot_cache_write``), and a row of length 0
  would have nothing to normalise by.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from ml_trainer_tpu.ops.attention import (
    _NEG,
    _largest_block,
    dot_product_attention,
)
from ml_trainer_tpu.ops.kernels.slot_cache_write import (
    LANES,
    _position_on_lanes,
)

# What a grid step holds of K (and as much of V), set by the sweep of
# scripts/decode_attention_tune.py on a v5e (PERF.md section 6, PR 30).
# Bytes, not positions, so that more heads or a wider dtype keep the step's
# VMEM what was measured; a larger block wastes more past each row's length
# (half a block a row), a smaller one pays the step's fixed cost more often:
# 256 positions at both serving cells' shapes (128 reads as fast end to end
# on the lanes and slower on the sublanes; 512 is the memory's speed on 20%
# more bytes).
_BLOCK_BYTES = 640 * 1024


def attended_positions(lengths, L: int, block: int):
    """Positions of each row the kernel fetches at ``block`` positions a
    grid step: the clamped length rounded up to whole blocks.  numpy in,
    numpy out; traced in, traced out."""
    xp = jnp if isinstance(lengths, jax.Array) else np
    live = xp.clip(xp.asarray(lengths), 1, L)
    return (live + block - 1) // block * block


def _decode_block(g: int, L: int, d: int, dtype) -> int:
    """Positions a grid step: the largest multiple of 128 that divides
    ``L`` within ``_BLOCK_BYTES`` of K; ``L`` itself where 128 does not
    divide it (one block: only the interpreter is handed such a shape)."""
    return _largest_block(
        L, _BLOCK_BYTES // (g * d * jnp.dtype(dtype).itemsize))


def grouped_decode_attention(q, k_cache, v_cache, valid, scale=None):
    """One query position a row against a cache that keeps the key-value
    heads only.  q: [B, H, 1, D]; caches [B, G, L, D]; valid: [B, L].  The
    H/G query heads of a key-value head form a group, so each cache row is
    read once (repeating the cache to H heads would move H/G times as
    much).  ``scale`` (default ``D ** -0.5``) is the scores' factor: a
    latent cache's row is wider than the head the scores are scaled by."""
    b, h, _, d = q.shape
    g = k_cache.shape[1]
    qg = q.reshape(b, g, h // g, d)
    scores = jnp.einsum(
        "bgrd,bgld->bgrl", qg, k_cache, preferred_element_type=jnp.float32,
    ) * (d ** -0.5 if scale is None else scale)
    scores = jnp.where(
        valid[:, None, None, :], scores, jnp.finfo(jnp.float32).min)
    weights = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bgrl,bgld->bgrd", weights.astype(v_cache.dtype), v_cache,
        preferred_element_type=jnp.float32,
    )
    return out.astype(q.dtype).reshape(b, h, 1, d)


def decode_attention_reference(q, k_cache, v_cache, lengths):
    """The masked attention over all ``L`` positions, as the engine stated
    it before the kernel."""
    L = k_cache.shape[2]
    valid = jnp.arange(L)[None, :] < jnp.clip(lengths, 1, L)[:, None]
    if k_cache.shape[1] == q.shape[1]:
        return dot_product_attention(
            q, k_cache, v_cache, mask=valid[:, None, None, :])
    return grouped_decode_attention(q, k_cache, v_cache, valid)


def _work_list(lengths, L: int, block: int):
    """The rows' live blocks laid end to end: for grid step ``t`` the row
    and the block of that row it visits, and how many steps there are.
    Steps past the last (never run) name the last row's last block.
    Compares and sums over ``[steps, B]`` only, which XLA fuses into a few
    small operations (2 us a call on a v5e) where a cumulative sum and two
    gathers were a dozen, of a layer that takes a hundred and forty."""
    b = lengths.shape[0]
    n = attended_positions(lengths, L, block) // block
    rows = jnp.arange(b, dtype=jnp.int32)
    ends = jnp.sum(
        jnp.where(rows[None, :] <= rows[:, None], n[None, :], 0), axis=1)
    first = ends - n
    t = jnp.arange(b * (L // block), dtype=jnp.int32)[:, None]
    row_of = jnp.minimum(jnp.sum(t >= ends[None, :], axis=1), b - 1)
    # The last row that starts at or before t: starts do not decrease.
    block_of = t[:, 0] - jnp.max(
        jnp.where(first[None, :] <= t, first[None, :], 0), axis=1)
    block_of = jnp.where(t[:, 0] < ends[b - 1], block_of, n[b - 1] - 1)
    return (row_of.astype(jnp.int32), block_of.astype(jnp.int32),
            ends[b - 1].astype(jnp.int32))


def _edges(row_of, block_of, lens, block):
    """This grid step's block of its row, the row's length, and whether the
    length lies inside the block (its tail is masked) or ends the row."""
    from jax.experimental import pallas as pl

    t = pl.program_id(0)
    j = block_of[t]
    n = lens[row_of[t]]
    return j, n, (j + 1) * block > n, (j + 1) * block >= n


def _softmax_step(s, m_scr, l_scr):
    """One block of scores ``[.., block]`` into the running maximum and sum
    (``[.., w]``, the same value along their last axis); returns the
    block's weights, not yet normalised, and the factor that brings what
    was accumulated before down to the new maximum.  Every head at once:
    one chain of reductions and exponentials a grid step, where a chain a
    head costs a microsecond a step whatever the block (PERF.md, PR 30)."""
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[..., :1])
    l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
    m_scr[...] = m_new
    return p, alpha


def _sublane_kernel(row_of, block_of, lens, q_ref, k_ref, v_ref, o_ref, s_scr,
                    m_scr, l_scr, acc_scr, *, block, scale):
    """The position on the sublanes: both products on the MXU, a key-value
    head's query heads the rows of one product.  q, o: ``[1, G, R, D]``; K,
    V: ``[1, G, block, D]``; scores ``[G, R, block]``, the running maximum
    and sum ``[G, R, 1]``, the output ``[G, R, D]``, float32."""
    from jax.experimental import pallas as pl

    j, n, crossed, last = _edges(row_of, block_of, lens, block)
    groups = q_ref.shape[1]

    @pl.when(j == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def attend(crossed):
        for g in range(groups):
            s_scr[g] = jax.lax.dot_general(
                q_ref[0, g], k_ref[0, g], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale     # q @ K.T
        s = s_scr[...]
        if crossed:
            s = jnp.where(j * block + jax.lax.broadcasted_iota(
                jnp.int32, (1, 1, block), 2) < n, s, _NEG)
            at = j * block + jax.lax.broadcasted_iota(
                jnp.int32, v_ref.shape[2:], 0)
        p, alpha = _softmax_step(s, m_scr, l_scr)
        s_scr[...] = p
        for g in range(groups):
            v = v_ref[0, g]
            if crossed:
                v = jnp.where(at < n, v, jnp.zeros_like(v))
            acc_scr[g] = alpha[g] * acc_scr[g] + jax.lax.dot_general(
                s_scr[g].astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)             # p @ V

    @pl.when(jnp.logical_not(crossed))
    def _():
        attend(False)

    @pl.when(crossed)
    def _():
        attend(True)

    @pl.when(last)
    def _():
        o_ref[0] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)


def _lane_kernel(row_of, block_of, lens, q_ref, k_ref, v_ref, o_ref, q_scr,
                 s_scr, m_scr, l_scr, a_scr, acc_scr, *, block, scale):
    """The position on the lanes: multiply and reduce on the VPU, as XLA's
    own fusion does (as the MXU's weights a tile of K or V of heads of 64
    fills half the array for one row of q: 430 GB/s, PERF.md, PR 30).  q,
    o: ``[1, D, Hl]``, a head's values down one column, heads padded to the
    lanes; K, V: ``[1, G, D, block]``; ``q_scr`` ``[H, D, w]`` a head's
    column spread along ``w`` lanes; scores ``[H, 1, block]``; the running
    maximum and sum and the step's factor ``[H, 1, w]``; the output
    ``[H, D, w]``, summed along the lanes at the row's end.  The two sweeps
    over the heads are loops, not unrolled: twenty bodies traced and
    lowered in every start of a server cost ``setup_s`` 5 s."""
    from jax.experimental import pallas as pl

    j, n, crossed, last = _edges(row_of, block_of, lens, block)
    groups, d = k_ref.shape[1], k_ref.shape[2]
    heads, _, w = q_scr.shape
    rep = heads // groups
    tiles = [slice(i * w, (i + 1) * w) for i in range(block // w)]

    @pl.when(j == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        columns = q_ref[0].astype(jnp.float32) * scale          # [D, Hl]
        for h in range(heads):
            q_scr[h] = jnp.broadcast_to(columns[:, h:h + 1], (d, w))

    def attend(crossed):
        def scores(g, carry):
            k = k_ref[0, g].astype(jnp.float32)                 # [D, block]
            for h in (g * rep + i for i in range(rep)):
                q = q_scr[h]
                for tile in tiles:
                    s_scr[h, :, tile] = jnp.sum(
                        k[:, tile] * q, axis=0, keepdims=True)
            return carry

        jax.lax.fori_loop(0, groups, scores, 0)
        s = s_scr[...]
        if crossed:
            at = j * block + jax.lax.broadcasted_iota(
                jnp.int32, (d, block), 1)
            s = jnp.where(at[:1] < n, s, _NEG)
        p, alpha = _softmax_step(s, m_scr, l_scr)
        s_scr[...] = p
        a_scr[...] = alpha

        def outputs(g, carry):
            v = v_ref[0, g].astype(jnp.float32)
            if crossed:
                v = jnp.where(at < n, v, 0.0)
            for h in (g * rep + i for i in range(rep)):
                acc_scr[h] = a_scr[h] * acc_scr[h] + sum(
                    v[:, tile] * s_scr[h, :, tile] for tile in tiles)
            return carry

        jax.lax.fori_loop(0, groups, outputs, 0)

    @pl.when(jnp.logical_not(crossed))
    def _():
        attend(False)

    @pl.when(crossed)
    def _():
        attend(True)

    @pl.when(last)
    def _():
        lane = jax.lax.broadcasted_iota(jnp.int32, o_ref.shape[1:], 1)
        out = jnp.zeros(o_ref.shape[1:], jnp.float32)
        for h in range(heads):
            column = jnp.sum(
                acc_scr[h], axis=1, keepdims=True) / l_scr[h][:, :1]
            out = jnp.where(lane == h, column, out)
        o_ref[0] = out.astype(o_ref.dtype)


# Jitted so that a model's layers share ONE trace and ONE lowering of the
# kernel (see slot_cache_write: 36 layers would pay it on every start).
@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _decode_attention_pallas(q, k_cache, v_cache, lengths, block, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, _, d = q.shape
    _, g, L, _ = k_cache.shape
    r = h // g
    # 0: the chooser's; an explicit block is the sweep's that chose it.
    block = block or _decode_block(g, L, d, k_cache.dtype)
    lengths = jnp.clip(lengths, 1, L)
    row_of, block_of, steps = _work_list(lengths, L, block)
    on_lanes = _position_on_lanes(L, d)
    if on_lanes:
        w = math.gcd(block, LANES)
        hl = -(-h // LANES) * LANES
        operands = [
            jnp.pad(q[:, :, 0, :].transpose(0, 2, 1),
                    ((0, 0), (0, 0), (0, hl - h))),              # [B, D, Hl]
            k_cache.transpose(0, 1, 3, 2), v_cache.transpose(0, 1, 3, 2)]
        q_spec = pl.BlockSpec(
            (1, d, hl), lambda t, row, blk, n: (row[t], 0, 0))
        cache_spec = pl.BlockSpec(
            (1, g, d, block), lambda t, row, blk, n: (row[t], 0, 0, blk[t]))
        kernel = functools.partial(
            _lane_kernel, block=block, scale=d ** -0.5)
        scratch = [(h, d, w), (h, 1, block), (h, 1, w), (h, 1, w), (h, 1, w),
                   (h, d, w)]
    else:
        operands = [q.reshape(b, g, r, d), k_cache, v_cache]
        q_spec = pl.BlockSpec(
            (1, g, r, d), lambda t, row, blk, n: (row[t], 0, 0, 0))
        cache_spec = pl.BlockSpec(
            (1, g, block, d), lambda t, row, blk, n: (row[t], 0, blk[t], 0))
        kernel = functools.partial(
            _sublane_kernel, block=block, scale=d ** -0.5)
        scratch = [(g, r, block), (g, r, 1), (g, r, 1), (g, r, d)]
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(steps,),
            in_specs=[q_spec, cache_spec, cache_spec],
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM(shape, jnp.float32) for shape in scratch],
        ),
        out_shape=jax.ShapeDtypeStruct(operands[0].shape, q.dtype),
        interpret=interpret,
        name="decode_attention",
    )(row_of, block_of, lengths, *operands)
    if on_lanes:
        return out[:, :, :h].transpose(0, 2, 1)[:, :, None, :]
    return out.reshape(b, h, 1, d)


def decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    lengths: jax.Array,
    *,
    implementation: str = "auto",
    interpret: bool = False,
):
    """``q [B, H, 1, D]`` against the first ``lengths[b]`` positions of row
    ``b`` of the caches ``[B, G, L, D]`` (``H % G == 0``); returns
    ``[B, H, 1, D]``.  See the module docstring.

    implementation: 'auto' (pallas on TPU, reference elsewhere),
    'pallas', or 'reference'.  ``interpret=True`` runs the Pallas kernel
    in interpret mode (the CPU parity harness).
    """
    if k_cache.shape != v_cache.shape or k_cache.dtype != v_cache.dtype:
        raise ValueError(
            f"k_cache/v_cache differ: {k_cache.shape} {k_cache.dtype} vs "
            f"{v_cache.shape} {v_cache.dtype}"
        )
    b, g, L, d = k_cache.shape
    if q.ndim != 4 or q.shape[0] != b or q.shape[2:] != (1, d):
        raise ValueError(
            f"q must be [{b}, H, 1, {d}] (one position a row of the cache "
            f"{k_cache.shape}), got {q.shape}"
        )
    if q.shape[1] % g:
        raise ValueError(
            f"{q.shape[1]} query heads over {g} key-value heads")
    if lengths.shape != (b,):
        raise ValueError(
            f"lengths must be [{b}], one a cache row, got {lengths.shape}")
    if implementation == "auto":
        implementation = (
            "pallas" if jax.default_backend() == "tpu" else "reference"
        )
    if implementation in ("reference", "xla"):
        return decode_attention_reference(q, k_cache, v_cache, lengths)
    if implementation != "pallas":
        raise ValueError(
            f"Unknown decode_attention implementation {implementation!r}; "
            "expected 'auto', 'pallas', or 'reference'"
        )
    return _decode_attention_pallas(
        q, k_cache, v_cache, jnp.asarray(lengths, jnp.int32), 0, interpret)
