"""Slot-cache write: this step's K and V into each row's own position, in
place and with every row in flight.

A decode step of the slot engine appends one position a row to a
``[B, H, L, D]`` cache, each row at its own index.  Stated in XLA that is a
scatter of ``B`` windows ``[H, 1, D]`` (``jax.vmap`` of a
``dynamic_update_slice``), and the TPU runs a scatter as a sequential
``while`` over its indices: 72 loops of 32 iterations a step of a 36-layer
model, half of the decode step (PERF.md, PR 25).  This kernel is the same
write as one grid over the rows whose DMAs the pipeline overlaps.

Who calls it.  A cache whose read does not write it: the rings of
``models/exaone_moe.py``'s window layers (written at ``position mod
window``, read whole by XLA), the latent cache of ``models/kimi_linear.py``
(``slot_row_write``: one leaf, read by ``latent_decode_attention``), a paged
pool's rows (``rows``, below).  A cache read by ``decode_attention`` is written BY that kernel
since PR 35 (``decode_attention_append``: the read already holds the tile
this kernel would fetch, so only the write-back is left of it):
``models/layers.py`` and ``exaone_moe``'s full layers.  The reference here
is also the verify window's scatter (``s > 1``) and the first half of that
call's reference.

Why it moves a block and not a position.  The chip addresses memory in
tiles of 8 sublanes of 32 bits by 128 lanes, and a DMA moves whole tiles.
Which two dimensions of the cache lie on the tile is XLA's choice, made
from the shape alone so that every program agrees on a buffer: the pair
that pads least.  With heads of 64 the head dimension would fill half of
the 128 lanes, so XLA puts the POSITION on the lanes and the head dimension
on the sublanes (``bf16[32,20,1024,64]{2,3,1,0:T(8,128)(2,1)}``); with heads
of a multiple of 128 it keeps the order as written, head dimension on the
lanes and position on the sublanes, where two bfloat16 positions share one
32-bit sublane.  Either way a single position has no address of its own.
Each grid step therefore reads the aligned block that holds the position,
replaces the position under an ``iota`` compare and writes the block back
through ``input_output_aliases``: the cache never leaves its buffer and
nothing outside the block is touched.  The kernel follows XLA's rule
(``_position_on_lanes``) and hands Mosaic the view whose order as written
IS the order in memory, so that no copy of the cache is made on the way in
or out (a wrong guess costs two such copies, never a wrong byte):

* position on the lanes: the view ``[N, H, D, L]`` (a transpose that XLA
  turns into a bitcast), block ``[1, H, D, 128]``: 320 KB at 20 heads of 64
  in bfloat16.  The new row arrives as ``[B, D, H padded to 128]`` so that a
  head's column can be spread along the lanes without a transpose in the
  kernel;
* position on the sublanes: the cache as written, block
  ``[1, H, 32 // itemsize, D]`` (16 positions of bfloat16).

Contract, pinned bit for bit by ``tests/test_kernels.py``:

* ``slot_cache_write_reference`` IS the write the engine made before the
  kernel (``jax.vmap`` of ``dynamic_update_slice``), so a position outside
  ``[0, L - 1]`` is CLAMPED into it as ``dynamic_update_slice`` clamps
  (a negative one counts from the end first, as JAX reads it).
  The engine advances every row's index on every step, a free row's too,
  so a free row's index runs past ``L - 1`` and lands on its own last
  position.  The kernel clamps before the block index is computed: an
  unclamped DMA would write over another row, or outside the array.
* ``rows`` (default ``arange(B)``) names the cache row each new row goes
  to, so a paged pool ``[N, H, page, D]`` can use the same call with
  ``rows`` the page and ``pos`` the offset in it.  The rows of one call
  must differ: two grid steps on one block race (the second reads the
  block before the first has written it back).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

LANES = 128


def _write_rows(cache, new, pos, rows=None):
    """One cache's scatter: a window ``[B, H, s, D]`` at ``pos[b]`` of row
    ``b`` (or ``rows[b]``)."""
    def write_row(cache_row, new_row, i):
        # [H, L, D] <- [H, s, D] at position i of THIS row only.
        return jax.lax.dynamic_update_slice(cache_row, new_row, (0, i, 0))

    if rows is None:
        return jax.vmap(write_row)(cache, new, pos)
    return cache.at[rows].set(jax.vmap(write_row)(cache[rows], new, pos))


def slot_cache_write_reference(k_cache, v_cache, k_new, v_new, pos,
                               rows=None):
    """The scatter, as the engine stated it before the kernel; a window
    ``[B, H, s, D]`` of any length ``s`` (the speculative verify window
    still takes this path)."""
    return (_write_rows(k_cache, k_new, pos, rows),
            _write_rows(v_cache, v_new, pos, rows))


def landing_position(pos, L: int):
    """Where a write at ``pos`` lands, as ``jax.lax.dynamic_update_slice``
    reads a start: a negative one counts from the end, and the result is
    clamped into the array."""
    return jnp.clip(jnp.where(pos < 0, pos + L, pos), 0, L - 1)


def _position_on_lanes(L: int, d: int) -> bool:
    """XLA's layout of a ``[.., L, d]`` array on the TPU: the dimension
    that pads least to a multiple of 128 goes on the lanes, the order as
    written winning a tie."""
    def padding(n):
        return -(-n // LANES) * LANES / n

    return padding(L) < padding(d)


def _wide(dtype):
    # The select runs on 32-bit lanes on every generation; widening a
    # bfloat16 and rounding it back returns the same bits.
    return jnp.float32 if jnp.dtype(dtype).itemsize < 4 else dtype


def _triples(refs):
    """The kernel's operands after the scalars: the new rows, the caches
    in, the caches out, as many of each as the call has caches."""
    n = len(refs) // 3
    return zip(refs[:n], refs[n:2 * n], refs[2 * n:])


def _sublane_kernel(rows_ref, pos_ref, *refs, tile):
    """Blocks ``[1, H, tile, D]``; the new rows ``[1, H, 1, D]``."""
    from jax.experimental import pallas as pl

    offset = pos_ref[pl.program_id(0)] % tile
    shape = refs[-1].shape[1:]
    hit = jax.lax.broadcasted_iota(jnp.int32, shape, 1) == offset
    wide = _wide(refs[-1].dtype)
    for new, old, out in _triples(refs):
        row = jnp.broadcast_to(new[0].astype(wide), shape)
        out[0] = jnp.where(hit, row, old[0].astype(wide)).astype(out.dtype)


def _lane_kernel(rows_ref, pos_ref, *refs, tile):
    """Blocks ``[1, H, D, tile]``; the new rows ``[1, D, H padded]``, a
    head's values down one column."""
    from jax.experimental import pallas as pl

    offset = pos_ref[pl.program_id(0)] % tile
    _, heads, d, _ = refs[-1].shape
    hit = jax.lax.broadcasted_iota(jnp.int32, (d, tile), 1) == offset
    wide = _wide(refs[-1].dtype)
    for new, old, out in _triples(refs):
        columns = new[0].astype(wide)                           # [D, Hp]
        for h in range(heads):
            row = jnp.broadcast_to(columns[:, h:h + 1], (d, tile))
            out[0, h] = jnp.where(
                hit, row, old[0, h].astype(wide)).astype(out.dtype)


# Jitted so that a model's layers share ONE trace and ONE lowering of the
# kernel: every layer calls it at the same shapes, and lowering a Pallas
# call to Mosaic takes about 0.15 s, which 36 layers would pay on every
# start of a server, compile cache or not.
@functools.partial(jax.jit, static_argnames=("interpret",))
def _slot_cache_write_pallas(caches, news, rows, pos, interpret):
    """``caches``, ``news``: tuples of as many caches of ONE shape and dtype
    (K and V; a latent cache alone) and the row each takes."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    count = len(caches)
    n, h, L, d = caches[0].shape
    b = news[0].shape[0]
    pos = landing_position(pos, L)
    on_lanes = _position_on_lanes(L, d)
    if on_lanes:
        tile = min(LANES, L)
        padded = -(-h // LANES) * LANES

        def arrange(new):                      # [B, H, 1, D] -> [B, D, Hp]
            new = new[:, :, 0, :].transpose(0, 2, 1)
            return jnp.pad(new, ((0, 0), (0, 0), (0, padded - h)))

        caches = [c.transpose(0, 1, 3, 2) for c in caches]
        news = [arrange(new) for new in news]
        new_spec = pl.BlockSpec((1, d, padded), lambda bi, r, p: (bi, 0, 0))
        cache_spec = pl.BlockSpec(
            (1, h, d, tile), lambda bi, r, p: (r[bi], 0, 0, p[bi] // tile))
        kernel = _lane_kernel
    else:
        tile = min(max(8, 32 // caches[0].dtype.itemsize), L)
        new_spec = pl.BlockSpec((1, h, 1, d), lambda bi, r, p: (bi, 0, 0, 0))
        cache_spec = pl.BlockSpec(
            (1, h, tile, d), lambda bi, r, p: (r[bi], 0, p[bi] // tile, 0))
        kernel = _sublane_kernel
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[new_spec] * count + [cache_spec] * count,
        out_specs=[cache_spec] * count,
    )
    shape = jax.ShapeDtypeStruct(caches[0].shape, caches[0].dtype)
    out = pl.pallas_call(
        functools.partial(kernel, tile=tile),
        grid_spec=grid_spec,
        out_shape=[shape] * count,
        # Operands count from the scalars: after the two of them and the
        # new rows come the caches (4 and 5 where there are two).
        input_output_aliases={2 + count + i: i for i in range(count)},
        interpret=interpret,
        name="slot_cache_write",
    )(rows, pos, *news, *caches)
    if on_lanes:
        out = [c.transpose(0, 1, 3, 2) for c in out]
    return tuple(out)


def _use_pallas(implementation: str, name: str) -> bool:
    """'auto' is the kernel on the TPU and the reference elsewhere."""
    if implementation == "auto":
        implementation = (
            "pallas" if jax.default_backend() == "tpu" else "reference"
        )
    if implementation in ("reference", "xla"):
        return False
    if implementation != "pallas":
        raise ValueError(
            f"Unknown {name} implementation {implementation!r}; "
            "expected 'auto', 'pallas', or 'reference'"
        )
    return True


def _write(caches, news, pos, rows, implementation, interpret, name):
    """What both entry points do once their shapes are checked: the
    reference's scatter, or the kernel over as many caches as they hand."""
    news = tuple(n.astype(c.dtype) for n, c in zip(news, caches))
    if not _use_pallas(implementation, name):
        return tuple(_write_rows(c, n, pos, rows)
                     for c, n in zip(caches, news))
    if rows is None:
        rows = jnp.arange(pos.shape[0], dtype=jnp.int32)
    return _slot_cache_write_pallas(
        caches, news, jnp.asarray(rows, jnp.int32),
        jnp.asarray(pos, jnp.int32), interpret,
    )


def slot_cache_write(
    k_cache: jax.Array,
    v_cache: jax.Array,
    k_new: jax.Array,
    v_new: jax.Array,
    pos: jax.Array,
    rows: Optional[jax.Array] = None,
    *,
    implementation: str = "auto",
    interpret: bool = False,
):
    """Write ``k_new[b]``, ``v_new[b]`` (``[B, H, 1, D]``) at position
    ``pos[b]`` of row ``rows[b]`` of the caches (``[N, H, L, D]``) and
    return the two caches.  See the module docstring.

    implementation: 'auto' (pallas on TPU, reference elsewhere),
    'pallas', or 'reference'.  ``interpret=True`` runs the Pallas kernel
    in interpret mode (the CPU parity harness).
    """
    if k_cache.shape != v_cache.shape or k_cache.dtype != v_cache.dtype:
        raise ValueError(
            f"k_cache/v_cache differ: {k_cache.shape} {k_cache.dtype} vs "
            f"{v_cache.shape} {v_cache.dtype}"
        )
    n, h, L, d = k_cache.shape
    b = pos.shape[0]
    if k_new.shape != (b, h, 1, d) or v_new.shape != (b, h, 1, d):
        raise ValueError(
            f"k_new/v_new must be {(b, h, 1, d)} (one position a row), got "
            f"{k_new.shape} and {v_new.shape}"
        )
    if rows is None and b != n:
        raise ValueError(f"{b} positions for {n} cache rows and no `rows`")
    return _write((k_cache, v_cache), (k_new, v_new), pos, rows,
                  implementation, interpret, "slot_cache_write")


def slot_row_write(
    cache: jax.Array,
    new: jax.Array,
    pos: jax.Array,
    *,
    implementation: str = "auto",
    interpret: bool = False,
):
    """The same write for a cache that is ONE leaf (a latent cache: keys and
    values are both read from it): ``new[b]`` (``[B, H, 1, D]``) at position
    ``pos[b]`` of row ``b`` of ``cache`` (``[B, H, L, D]``).  One kernel, one
    contract (the clamp, the layouts): the call above with one cache."""
    b, h, L, d = cache.shape
    if new.shape != (b, h, 1, d) or pos.shape != (b,):
        raise ValueError(
            f"new must be {(b, h, 1, d)} and pos [{b}] (one position a row "
            f"of the cache {cache.shape}), got {new.shape} and {pos.shape}"
        )
    return _write((cache,), (new,), pos, None, implementation, interpret,
                  "slot_row_write")[0]
