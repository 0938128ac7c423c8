"""Decode attention over a slot cache: one query position a row, of each
row's K and V only the blocks that hold a live position, and this step's
own K and V put into the last of them on the way.

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

The append (``decode_attention_append``, PR 35).  The step that reads a
row has first to put its own K and V at the row's position, and a single
position has no address of its own (``slot_cache_write``): a write kernel
before this one read the aligned tile that holds it, replaced one position
and wrote the tile back, 1.5 GB a step of ``gpt2-large.batch-decode`` for
5.9 MB of new rows, a fifth of the chip's busy time.  But the row's LAST
block, which this kernel fetches anyway, holds that tile.  So on the one
grid step a row that is its last, the new row enters the block under an
``iota`` compare (rounded to the cache's dtype first, so that the
attention sees the bits a read-back would), the block is attended in the
read's own order of operations, and the tile alone, 128 positions on the
lanes or ``32 // itemsize`` on the sublanes, goes back through a second
pair of ``out_specs`` whose block index is constant over a row's steps,
aliased onto the caches.  Nothing is read that the read did not read, and
the write is half of what the write kernel moved.

The arithmetic is the reference's: K, V and q as the configuration states
them, float32 scores, float32 softmax statistics and accumulator (the VPU
body widens K and V to float32; the MXU body rounds the weights to V's
dtype for the second product, as the reference does).  The softmax runs
online across a row's blocks (running maximum, sum and output, rescaled a
block), so sums are taken in another order than XLA takes them and parity
with the reference is by tolerance, not bit for bit (docs/kernels.md).  A
grid step is three phases: every head's scores, ONE update of the
statistics over all heads, every head's output; a chain of reductions and
exponentials a head costs a microsecond a step.  In a row's last block,
scores past the length are masked before the maximum and the sum and the
V positions past it are zeroed before the product, so what a freed slot
left there, a NaN included, cannot reach the output.

Contract, pinned by ``tests/test_kernels.py``:

* ``decode_attention_reference`` IS the engine's read before the kernel:
  ``dot_product_attention`` under the mask ``arange(L) < lengths`` for
  ``G == H``, ``grouped_decode_attention`` for ``G < H``.
* ``lengths`` is clamped to ``[1, L]`` on both paths: the engine advances
  every row's index on every step, a free row's too, so a free row's
  length runs past ``L`` (see ``slot_cache_write``), and a row of length 0
  would have nothing to normalise by.
* ``decode_attention_append_reference`` IS the pair the engine made before
  the fused call: ``slot_cache_write_reference`` at ``pos``, then
  ``decode_attention_reference`` over the positions up to and with the one
  written.  ``pos`` is read as the write reads it (``landing_position``: as
  ``dynamic_update_slice`` reads a start, clamped into ``[0, L - 1]``), so
  a free row's index past ``L - 1`` lands on its own last position and
  attends ``L``; for every ``pos >= 0`` the length is the pair's
  ``pos + 1`` clamped.  The caches are bit for bit the scatter's.
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
    _use_pallas,
    landing_position,
    slot_cache_write_reference,
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
    block is the row's last: the one the length lies in, or ends."""
    from jax.experimental import pallas as pl

    t = pl.program_id(0)
    j = block_of[t]
    n = lens[row_of[t]]
    return j, n, (j + 1) * block >= n


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


def _words(x):
    """A block of a dtype narrower than 32 bits as the 32-bit words the
    chip keeps it in (the rows that share a sublane in one word), so that a
    select between two such blocks moves words and widens nothing; a 32-bit
    block as it is."""
    from jax.experimental.pallas import tpu as pltpu

    return x if x.dtype.itemsize == 4 else pltpu.bitcast(x, jnp.uint32)


def _from_words(x, dtype):
    from jax.experimental.pallas import tpu as pltpu

    return x if x.dtype == dtype else pltpu.bitcast(x, dtype)


def _sublane_kernel(row_of, block_of, lens, q_ref, kn_ref, vn_ref, k_ref,
                    v_ref, o_ref, ko_ref, vo_ref, s_scr, m_scr, l_scr,
                    acc_scr, *, block, scale):
    """The position on the sublanes: both products on the MXU, a key-value
    head's query heads the rows of one product.  q, o: ``[1, G, R, D]``; K,
    V: ``[1, G, block, D]``; scores ``[G, R, block]``, the running maximum
    and sum ``[G, R, 1]``, the output ``[G, R, D]``, float32.  Appending
    (``kn_ref`` and ``vn_ref`` ``[1, G, 1, D]``, this step's rows; ``ko_ref``
    and ``vo_ref`` ``[1, G, tile, D]``, the tile of the caches they land
    in): the row's last block holds position ``n - 1``, which takes the
    new row under an ``iota`` compare before it is attended, and the tile
    goes back alone."""
    from jax.experimental import pallas as pl

    j, n, last = _edges(row_of, block_of, lens, block)
    groups = q_ref.shape[1]

    @pl.when(j == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def attend(last):
        append = last and kn_ref is not None
        if last:
            at = j * block + jax.lax.broadcasted_iota(
                jnp.int32, v_ref.shape[2:], 0)
        for g in range(groups):
            k = k_ref[0, g]
            if append:
                k = jnp.where(at == n - 1, kn_ref[0, g], k)
            s_scr[g] = jax.lax.dot_general(
                q_ref[0, g], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale     # q @ K.T
        s = s_scr[...]
        if last:
            s = jnp.where(j * block + jax.lax.broadcasted_iota(
                jnp.int32, (1, 1, block), 2) < n, s, _NEG)
        p, alpha = _softmax_step(s, m_scr, l_scr)
        s_scr[...] = p
        for g in range(groups):
            v = v_ref[0, g]
            if append:
                v = jnp.where(at == n - 1, vn_ref[0, g], v)
            if last:
                v = jnp.where(at < n, v, jnp.zeros_like(v))
            acc_scr[g] = alpha[g] * acc_scr[g] + jax.lax.dot_general(
                s_scr[g].astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)             # p @ V
        if append:
            tile = ko_ref.shape[2]
            offset = n - 1 - j * block
            start = pl.multiple_of(offset // tile * tile, tile)
            hit = jax.lax.broadcasted_iota(
                jnp.int32, ko_ref.shape[2:], 0) == offset % tile
            for new, old, out in ((kn_ref, k_ref, ko_ref),
                                  (vn_ref, v_ref, vo_ref)):
                for g in range(groups):
                    out[0, g] = jnp.where(
                        hit, new[0, g], old[0, g, pl.ds(start, tile), :])

    @pl.when(jnp.logical_not(last))
    def _():
        attend(False)

    @pl.when(last)
    def _():
        attend(True)
        o_ref[0] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)


def _lane_kernel(row_of, block_of, lens, q_ref, kn_ref, vn_ref, k_ref, v_ref,
                 o_ref, ko_ref, vo_ref, q_scr, s_scr, m_scr, l_scr, a_scr,
                 acc_scr, *, block, scale):
    """The position on the lanes: multiply and reduce on the VPU, as XLA's
    own fusion does (as the MXU's weights a tile of K or V of heads of 64
    fills half the array for one row of q: 430 GB/s, PERF.md, PR 30).  q,
    o: ``[1, D, Hl]``, a head's values down one column, heads padded to the
    lanes; K, V: ``[1, G, D, block]``; ``q_scr`` ``[H, D, w]`` a head's
    column spread along ``w`` lanes; scores ``[H, 1, block]``; the running
    maximum and sum and the step's factor ``[H, 1, w]``; the output
    ``[H, D, w]``, summed along the lanes at the row's end.  The two sweeps
    over the heads are loops, not unrolled: twenty bodies traced and
    lowered in every start of a server cost ``setup_s`` 5 s.

    Appending (``kn_ref`` and ``vn_ref`` ``[1, D, Gl]``, this step's rows
    as columns; ``ko_ref`` and ``vo_ref`` ``[1, G, D, w]``, the tile of the
    caches they land in): in the row's last block the tile that holds
    position ``n - 1`` is merged with the new column under an ``iota``
    compare INTO the tile that goes back, and attended from there, so that
    the attention reads the bits a read-back would and pays no select of
    its own; a body a tile of the block, chosen by the position.  The merge
    selects 32-bit words (``_words``) and is unrolled over the heads, a
    slice, a broadcast, a select and a store each: widened to float32 with
    the columns spread into scratch by a loop, as ``q``'s are, it cost the
    call 0.008 ms of 0.19 more (v5e, PERF.md, PR 35)."""
    from jax.experimental import pallas as pl

    j, n, last = _edges(row_of, block_of, lens, block)
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

    def attend(last, fresh=None):
        """``fresh``: the tile of the block this step appended to, read
        from the tile that goes back."""
        def tile_of(ref, out_ref, g, i):
            tile = out_ref[0, g] if i == fresh else ref[0, g, :, tiles[i]]
            return tile.astype(jnp.float32)                     # [D, w]

        def scores(g, carry):
            for i, tile in enumerate(tiles):
                k = tile_of(k_ref, ko_ref, g, i)
                for h in (g * rep + r for r in range(rep)):
                    s_scr[h, :, tile] = jnp.sum(
                        k * q_scr[h], axis=0, keepdims=True)
            return carry

        jax.lax.fori_loop(0, groups, scores, 0)
        s = s_scr[...]
        if last:
            s = jnp.where(j * block + jax.lax.broadcasted_iota(
                jnp.int32, (1, 1, block), 2) < n, s, _NEG)
            lane = jax.lax.broadcasted_iota(jnp.int32, (d, w), 1)
        p, alpha = _softmax_step(s, m_scr, l_scr)
        s_scr[...] = p
        a_scr[...] = alpha

        def outputs(g, carry):
            v = [tile_of(v_ref, vo_ref, g, i) for i in range(len(tiles))]
            if last:
                v = [jnp.where(lane < n - j * block - i * w, x, 0.0)
                     for i, x in enumerate(v)]
            for h in (g * rep + r for r in range(rep)):
                acc_scr[h] = a_scr[h] * acc_scr[h] + sum(
                    x * s_scr[h, :, tile] for x, tile in zip(v, tiles))
            return carry

        jax.lax.fori_loop(0, groups, outputs, 0)

    @pl.when(jnp.logical_not(last))
    def _():
        attend(False)

    def append(i, offset):
        """The new columns into tile ``i`` of the block, in the tiles that
        go back; then the block attended with that tile read from there."""
        for columns_ref, old, out in ((kn_ref, k_ref, ko_ref),
                                      (vn_ref, v_ref, vo_ref)):
            columns = _words(columns_ref[0])                    # [D', Gl]
            hit = jax.lax.broadcasted_iota(
                jnp.int32, (columns.shape[0], w), 1) == offset % w
            for g in range(groups):
                tile = _words(old[0, g, :, tiles[i]])           # [D', w]
                column = jnp.broadcast_to(columns[:, g:g + 1], tile.shape)
                out[0, g] = _from_words(
                    jnp.where(hit, column, tile), out.dtype)
        attend(True, fresh=i)

    @pl.when(last)
    def _():
        if kn_ref is None:
            attend(True)
        else:
            offset = n - 1 - j * block
            for i in range(len(tiles)):
                pl.when(offset // w == i)(
                    functools.partial(append, i, offset))
        lane = jax.lax.broadcasted_iota(jnp.int32, o_ref.shape[1:], 1)
        out = jnp.zeros(o_ref.shape[1:], jnp.float32)
        for h in range(heads):
            column = jnp.sum(
                acc_scr[h], axis=1, keepdims=True) / l_scr[h][:, :1]
            out = jnp.where(lane == h, column, out)
        o_ref[0] = out.astype(o_ref.dtype)


def _without_append(kernel):
    """The kernel of the read alone: no new rows in, no tiles out."""
    def body(row_of, block_of, lens, q_ref, k_ref, v_ref, o_ref, *scratch,
             **static):
        kernel(row_of, block_of, lens, q_ref, None, None, k_ref, v_ref,
               o_ref, None, None, *scratch, **static)

    return body


# Jitted so that a model's layers share ONE trace and ONE lowering of the
# kernel (see slot_cache_write: 36 layers would pay it on every start).
@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _decode_attention_pallas(q, k_cache, v_cache, lengths, block, interpret,
                             news=()):
    """The Pallas call of both entry points.  ``news``: nothing, or this
    step's ``(k_new, v_new)`` (``[B, G, 1, D]`` in the caches' dtype), which
    the call puts at position ``lengths - 1`` of each row before it attends
    the row; it then returns the two caches after the output."""
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

    def spec(shape, index):
        return pl.BlockSpec(shape, lambda t, row, blk, n: index(
            row[t], blk[t], n[row[t]] - 1))

    if on_lanes:
        w = tile = math.gcd(block, LANES)

        def columns(x):     # [B, N, 1, D] -> [B, D, N padded to the lanes]
            return jnp.pad(x[:, :, 0, :].transpose(0, 2, 1),
                           ((0, 0), (0, 0), (0, -x.shape[1] % LANES)))

        rows = [columns(x) for x in (q, *news)]
        caches = [k_cache.transpose(0, 1, 3, 2), v_cache.transpose(0, 1, 3, 2)]
        row_specs = [spec((1,) + x.shape[1:], lambda row, blk, at: (row, 0, 0))
                     for x in rows]
        cache_spec = spec(
            (1, g, d, block), lambda row, blk, at: (row, 0, 0, blk))
        tile_spec = spec(
            (1, g, d, tile), lambda row, blk, at: (row, 0, 0, at // tile))
        kernel = _lane_kernel
        scratch = [(h, d, w), (h, 1, block), (h, 1, w), (h, 1, w), (h, 1, w),
                   (h, d, w)]
    else:
        # As slot_cache_write: the sublanes of one 32-bit tile.
        tile = math.gcd(block, max(8, 32 // k_cache.dtype.itemsize))
        rows = [q.reshape(b, g, r, d), *news]
        caches = [k_cache, v_cache]
        row_specs = [
            spec((1,) + x.shape[1:], lambda row, blk, at: (row, 0, 0, 0))
            for x in rows]
        cache_spec = spec(
            (1, g, block, d), lambda row, blk, at: (row, 0, blk, 0))
        tile_spec = spec(
            (1, g, tile, d), lambda row, blk, at: (row, 0, at // tile, 0))
        kernel = _sublane_kernel
        scratch = [(g, r, block), (g, r, 1), (g, r, 1), (g, r, d)]
    out = pl.pallas_call(
        functools.partial(
            kernel if news else _without_append(kernel),
            block=block, scale=d ** -0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(steps,),
            in_specs=row_specs + [cache_spec] * 2,
            out_specs=[row_specs[0]] + [tile_spec] * len(news),
            scratch_shapes=[
                pltpu.VMEM(shape, jnp.float32) for shape in scratch],
        ),
        out_shape=[jax.ShapeDtypeStruct(rows[0].shape, q.dtype)] + [
            jax.ShapeDtypeStruct(c.shape, c.dtype) for c in caches[:len(news)]],
        # Operands count from the three scalars: after them q and the new
        # rows, then the caches, each written where it lies.
        input_output_aliases={
            3 + len(rows) + i: 1 + i for i in range(len(news))},
        interpret=interpret,
        name="decode_attention_append" if news else "decode_attention",
    )(row_of, block_of, lengths, *rows, *caches)
    if on_lanes:
        attended = out[0][:, :, :h].transpose(0, 2, 1)[:, :, None, :]
        written = [c.transpose(0, 1, 3, 2) for c in out[1:]]
    else:
        attended, written = out[0].reshape(b, h, 1, d), out[1:]
    return (attended, *written) if news else attended


def _check_shapes(q, k_cache, v_cache, per_row, what):
    """What both entry points refuse: caches that differ, a q that is not
    one position a row of them, heads no whole number a key-value head, a
    per-row vector (``what`` names it) of another length than the rows."""
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
    if per_row.shape != (b,):
        raise ValueError(
            f"{what} must be [{b}], one a cache row, got {per_row.shape}")


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
    _check_shapes(q, k_cache, v_cache, lengths, "lengths")
    if not _use_pallas(implementation, "decode_attention"):
        return decode_attention_reference(q, k_cache, v_cache, lengths)
    return _decode_attention_pallas(
        q, k_cache, v_cache, jnp.asarray(lengths, jnp.int32), 0, interpret)


def decode_attention_append_reference(q, k_new, v_new, k_cache, v_cache, pos):
    """The pair the call joins, as the engine stated a decode step before
    it: the scatter at ``pos``, then the masked attention over the
    positions before this step's and the one just written."""
    k_cache, v_cache = slot_cache_write_reference(
        k_cache, v_cache, k_new.astype(k_cache.dtype),
        v_new.astype(v_cache.dtype), pos)
    lengths = landing_position(pos, k_cache.shape[2]) + 1
    return (decode_attention_reference(q, k_cache, v_cache, lengths),
            k_cache, v_cache)


def decode_attention_append(
    q: jax.Array,
    k_new: jax.Array,
    v_new: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    pos: jax.Array,
    *,
    implementation: str = "auto",
    interpret: bool = False,
):
    """A decode step of the slot engine in one call: this step's ``k_new``
    and ``v_new`` (``[B, G, 1, D]``) go to position ``pos[b]`` of row ``b``
    of the caches ``[B, G, L, D]`` as ``slot_cache_write`` puts them, and
    ``q [B, H, 1, D]`` attends the row up to and with that position.
    Returns the output ``[B, H, 1, D]`` and the two caches.  See the module
    docstring.

    implementation: 'auto' (pallas on TPU, reference elsewhere),
    'pallas', or 'reference'.  ``interpret=True`` runs the Pallas kernel
    in interpret mode (the CPU parity harness).
    """
    _check_shapes(q, k_cache, v_cache, pos, "pos")
    b, g, L, d = k_cache.shape
    if k_new.shape != (b, g, 1, d) or v_new.shape != (b, g, 1, d):
        raise ValueError(
            f"k_new/v_new must be {(b, g, 1, d)} (one position a row), got "
            f"{k_new.shape} and {v_new.shape}"
        )
    if not _use_pallas(implementation, "decode_attention_append"):
        return decode_attention_append_reference(
            q, k_new, v_new, k_cache, v_cache, pos)
    news = (k_new.astype(k_cache.dtype), v_new.astype(v_cache.dtype))
    lengths = landing_position(jnp.asarray(pos, jnp.int32), L) + 1
    return _decode_attention_pallas(
        q, k_cache, v_cache, lengths, 0, interpret, news)
