"""Paged-attention decode kernel: the page-table gather fused into
attention.

The serving engine's paged decode step
(``models/layers.py::_paged_decode_step``) attends each row's single
query against K/V scattered across a shared page pool.  The XLA path
must first materialize the gather — ``pool[table]`` then a transpose
back to logical order — which copies the FULL [B, H, L, D] cached K and
V through HBM every decode step; at decode batch sizes that copy is the
dominant byte-mover (the attention matmuls then read the same bytes
again).  This kernel removes it: the per-row page list rides in as a
scalar-prefetch operand and the grid's page axis pulls each page
HBM->VMEM directly via its BlockSpec ``index_map`` — the gather IS the
pipeline's fetch, never a separate HBM-resident array.

Parity discipline (pinned by tests/test_kernels.py):

* ``paged_attention_reference`` is bitwise-identical to the pre-kernel
  engine path (gather + ``dot_product_attention`` under the validity
  mask) — it IS that path, minus the engine's mask plumbing.
* the Pallas kernel in ``interpret=True`` mode is bitwise-identical to
  the reference: scores/softmax/output are computed once per (b, h) on
  the full [1, L] row with the exact op chain of
  ``dot_product_attention`` (f32 dots, mask bias ADDED, same softmax),
  and the scratch holds the very pages the reference gathers — trash
  and partially-filled pages included — so masked positions see the
  same bytes on both sides.

Layout contract (owned by serving/kv_pool.py + models/layers.py):
``k_pool``/``v_pool`` are [N, H, page, D] with page 0 the trash page;
``table`` is [B, P] int32; ``lengths`` is [B] int32 with
``lengths[b] >= 1`` (position 0 is always valid — the engine passes
``cache_index + 1``).  Rows past ``lengths`` are masked, so trash-page
rows (all-zero tables) and partial last pages cost nothing but the
masked lanes.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ml_trainer_tpu.ops.attention import _mask_bias, dot_product_attention


def paged_attention_reference(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    table: jax.Array,
    lengths: jax.Array,
    *,
    scale: Optional[float] = None,
) -> jax.Array:
    """The gather + masked dot-product-attention path, verbatim.

    q: [B, H, D] (one query token per row); pools: [N, H, page, D];
    table: [B, P]; lengths: [B].  Returns [B, H, D] in q.dtype.
    """
    b, h, d = q.shape
    _, _, ps, _ = k_pool.shape
    P = table.shape[-1]
    L = P * ps

    def gather(pool):  # [B, P, H, page, D] -> [B, H, L, D]
        return pool[table].transpose(0, 2, 1, 3, 4).reshape(b, h, L, d)

    valid = (jnp.arange(L)[None, :] < lengths[:, None])[:, None, None, :]
    out = dot_product_attention(
        q[:, :, None, :], gather(k_pool), gather(v_pool),
        mask=valid, scale=scale,
    )
    return out[:, :, 0, :]


def _paged_kernel(table_ref, lens_ref, q_ref, k_ref, v_ref, o_ref,
                  k_scr, v_scr, *, pages, page_size, scale):
    """Grid (B, H, P); page p of row b's table lands in k_ref/v_ref (the
    BlockSpec index_map did the gather).  Pages accumulate into VMEM
    scratch; the last page triggers the one [1, L] attention row."""
    from jax.experimental import pallas as pl

    b_i = pl.program_id(0)
    p_i = pl.program_id(2)
    L = pages * page_size
    # A dynamic-start store on the sublane axis: Mosaic needs to know the
    # start is tile-aligned (page_size must be a multiple of the cache
    # dtype's sublane tile — 8 rows of f32, 16 of bf16).
    start = pl.multiple_of(p_i * page_size, page_size)
    k_scr[pl.ds(start, page_size), :] = k_ref[0, 0]
    v_scr[pl.ds(start, page_size), :] = v_ref[0, 0]

    @pl.when(p_i == pages - 1)
    def _finish():
        # The exact dot_product_attention op chain on the [1, L] row:
        # f32 score dot, python-float scale, ADDED mask bias, softmax,
        # weights cast to v.dtype then f32 for the output dot.
        qv = q_ref[0, 0].astype(jnp.float32)                   # [1, D]
        scores = jax.lax.dot_general(
            qv, k_scr[...].astype(jnp.float32),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                              # [1, L]
        pos = jax.lax.broadcasted_iota(jnp.int32, (1, L), 1)
        scores = scores + _mask_bias(pos < lens_ref[b_i], scores.dtype)
        weights = jax.nn.softmax(scores, axis=-1)
        weights = weights.astype(v_scr.dtype).astype(jnp.float32)
        out = jax.lax.dot_general(
            weights, v_scr[...].astype(jnp.float32),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                      # [1, D]
        o_ref[0, 0] = out.astype(o_ref.dtype)


def _paged_attention_pallas(q, k_pool, v_pool, table, lengths, scale,
                            interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, d = q.shape
    _, _, ps, _ = k_pool.shape
    P = table.shape[-1]
    L = P * ps

    # q/out ride as [B, H, 1, D] with (1, 1, 1, D) blocks: a block's last
    # two dims must equal the array's (or be (8, 128)-divisible), and a
    # (1, D) block on [.., H, D] puts a 1 on the H axis — which the TPU
    # lowering rejects and interpret mode never checks.
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, h, P),
        in_specs=[
            pl.BlockSpec(
                (1, 1, 1, d), lambda bi, hi, pi, tbl, lens: (bi, hi, 0, 0)
            ),
            # The fused gather: page p of row b streams in from whatever
            # pool page the prefetched table names for it.
            pl.BlockSpec(
                (1, 1, ps, d),
                lambda bi, hi, pi, tbl, lens: (tbl[bi, pi], hi, 0, 0),
            ),
            pl.BlockSpec(
                (1, 1, ps, d),
                lambda bi, hi, pi, tbl, lens: (tbl[bi, pi], hi, 0, 0),
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, 1, d), lambda bi, hi, pi, tbl, lens: (bi, hi, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((L, d), k_pool.dtype),
            pltpu.VMEM((L, d), v_pool.dtype),
        ],
    )
    kernel = functools.partial(
        _paged_kernel, pages=P, page_size=ps, scale=scale
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, 1, d), q.dtype),
        interpret=interpret,
        name="paged_attention_decode",
    )(table, lengths, q[:, :, None, :], k_pool, v_pool)[:, :, 0, :]


def paged_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    table: jax.Array,
    lengths: jax.Array,
    *,
    scale: Optional[float] = None,
    implementation: str = "auto",
    interpret: bool = False,
) -> jax.Array:
    """Fused paged-attention decode step.  See module docstring.

    implementation: 'auto' (pallas on TPU, reference elsewhere),
    'pallas', or 'reference'.  ``interpret=True`` runs the Pallas kernel
    in interpret mode (the CPU parity harness).
    """
    if q.ndim != 3:
        raise ValueError(f"q must be [B, H, D], got {q.shape}")
    if k_pool.shape != v_pool.shape:
        raise ValueError(
            f"k_pool/v_pool shapes differ: {k_pool.shape} vs {v_pool.shape}"
        )
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if implementation == "auto":
        implementation = (
            "pallas" if jax.default_backend() == "tpu" else "reference"
        )
    if implementation in ("reference", "xla"):
        return paged_attention_reference(
            q, k_pool, v_pool, table, lengths, scale=scale
        )
    if implementation != "pallas":
        raise ValueError(
            f"Unknown paged_attention implementation {implementation!r}; "
            "expected 'auto', 'pallas', or 'reference'"
        )
    return _paged_attention_pallas(
        q, k_pool, v_pool, table, jnp.asarray(lengths, jnp.int32),
        scale, interpret,
    )
