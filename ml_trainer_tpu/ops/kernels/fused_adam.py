"""Fused unscale + clip + Adam update for the ``dp_update='sharded'``
path.

The sharded-dp optimizer tail (``trainer.py::_make_sharded_train_step``)
runs as optax's many small ops over each 1/N dim-0 shard: unscale,
per-leaf squared-norm for the global clip, the clip multiply, two
moment updates, bias corrections, the schedule step, and the param
write — each a separate HBM round-trip over the same bytes.  This
module fuses them into two passes (the global-norm psum between them is
an unavoidable barrier):

* ``unscale_sqsum`` — ``g / denom`` and the f32 sum-of-squares of the
  result in one read of ``g``;
* ``fused_adam_update`` — clip multiply + Adam moment/bias-correction/
  step + schedule scale + ``lr_scale`` + param write in one read of
  (g, p, mu, nu) and one write of (p', mu', nu', u).

Bit-identity contract (pinned by tests/test_kernels.py): the lax
references replicate optax 0.2.3's exact op chain —
``scale_by_adam`` (``mu' = (1-b1)·g + b1·mu``, ``nu' = (1-b2)·g² +
b2·nu``, ``safe_int32_increment`` counts, ``m / (1 - b**count)`` bias
corrections cast to the moment dtype), ``scale_by_schedule``
(``jnp.array(-lr(count), u.dtype) * u``), the trainer's ``u * lr_scale``
and ``optax.apply_updates`` — so the fused path's fp32 trajectory is
bitwise the optax path's, and the rebuilt ``opt_state``
(``EmptyState``, (``ScaleByAdamState``, ``ScaleByScheduleState``))
keeps checkpoints and the NaN-guard's where-select structure unchanged.

The Pallas kernels are elementwise over lane-padded 2-D views, the Adam
kernel row-blocked over a grid so a leaf of any size streams through
VMEM (no cross-element reductions except ``unscale_sqsum``'s whole-leaf
sum, which runs single-block to preserve the reference reduction order —
leaves past the VMEM budget fall back to the reference).  Output
shapes/dtypes come from ``jax.eval_shape`` of the reference, so the
kernels inherit its promotion semantics exactly.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import optax

# optax.adam defaults — the only config the fused path accepts (the
# trainer gates on optimizer='adam' with weight_decay=0).
B1, B2, EPS, EPS_ROOT = 0.9, 0.999, 1e-8, 0.0

# unscale_sqsum runs the whole leaf as one Pallas block (reduction-order
# preservation); leaves above this many elements use the reference.
# 1M f32 elements is 4 MB in + 4 MB out, half the 16 MB a TPU core's
# default scoped-VMEM limit allows one kernel.
_SQSUM_VMEM_ELEMS = 1024 * 1024

_LANES = 128
# Rows of the (-1, 128) view one grid step of the Adam kernel takes: four
# inputs + four outputs, double-buffered, at 512 x 128 f32 = 256 KB each
# is 4 MB of VMEM whatever the leaf's size.
_ADAM_BLOCK_ROWS = 512


def adam_scalars(count, sched_count, lr_schedule):
    """The per-step scalars every leaf shares: incremented counts, the
    two bias corrections, and the schedule step size — each the exact
    optax expression (``safe_int32_increment``, ``1 - b**count_inc``,
    ``-lr(count)`` evaluated at the PRE-increment schedule count)."""
    count_inc = optax.safe_int32_increment(count)
    bc1 = 1 - B1 ** count_inc
    bc2 = 1 - B2 ** count_inc
    if callable(lr_schedule):
        step_size = -1 * lr_schedule(sched_count)
    else:
        step_size = jnp.asarray(-1.0 * lr_schedule, jnp.float32)
    sched_inc = optax.safe_int32_increment(sched_count)
    return count_inc, bc1, bc2, step_size, sched_inc


def _flat2(t):
    """Lane-padded 2-D view for the elementwise kernels (bit-safe: no
    cross-element arithmetic touches the padding)."""
    f = t.reshape(-1)
    pad = (-f.shape[0]) % _LANES
    if pad:
        f = jnp.pad(f, (0, pad))
    return f.reshape(-1, _LANES)


def _unflat(f, shape):
    n = 1
    for s in shape:
        n *= int(s)
    return f.reshape(-1)[:n].reshape(shape)


# --------------------------------------------------------- unscale+sqsum
def _unscale_reference(g, denom, compute_sq):
    g_u = g / denom
    if not compute_sq:
        return g_u, None
    return g_u, jnp.sum(jnp.square(g_u.astype(jnp.float32)))


def _unscale_pallas(g, denom, compute_sq, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    static_denom = isinstance(denom, (int, float))
    ref_out = jax.eval_shape(
        lambda gg, dd: _unscale_reference(gg, dd, True), g,
        denom if static_denom else jnp.asarray(denom),
    )
    out_dtype = ref_out[0].dtype

    def kernel(*refs):
        if static_denom:
            g_ref, o_ref, sq_ref = refs
            g_u = g_ref[...] / denom
        else:
            d_ref, g_ref, o_ref, sq_ref = refs
            g_u = g_ref[...] / d_ref[0, 0]
        o_ref[...] = g_u.astype(o_ref.dtype)
        if compute_sq:
            sq_ref[0, 0] = jnp.sum(jnp.square(g_u.astype(jnp.float32)))
        else:
            sq_ref[0, 0] = 0.0

    # NO lane padding or reshape here: a multi-axis full reduce
    # associates per-axis, so the sqsum only matches the reference if
    # the kernel sees g's original shape (1-d leaves ride as (1, N),
    # which reduces in the same order).
    flat = g if g.ndim >= 2 else g.reshape(1, -1)
    in_specs = [pl.BlockSpec(memory_space=pltpu.VMEM)]
    args = [flat]
    if not static_denom:
        in_specs.insert(0, pl.BlockSpec(memory_space=pltpu.SMEM))
        args.insert(0, jnp.asarray(denom, jnp.float32).reshape(1, 1))
    out, sq = pl.pallas_call(
        kernel,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(flat.shape, out_dtype),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        interpret=interpret,
        name="fused_adam_norm",
    )(*args)
    out = out.reshape(g.shape)
    return (out, sq[0, 0]) if compute_sq else (out, None)


def unscale_sqsum(
    g: jax.Array,
    denom,
    *,
    compute_sq: bool = True,
    implementation: str = "auto",
    interpret: bool = False,
) -> Tuple[jax.Array, Optional[jax.Array]]:
    """``(g / denom, sum(square(f32(g / denom))))`` in one pass.

    ``denom`` is a python float (no loss scaling) or a traced f32 scalar
    (``denom * scale``); the division matches the unfused path bit-for-
    bit either way.  ``compute_sq=False`` skips the norm contribution
    (no clip, no telemetry).

    Caveat (documented VMEM bound): the Pallas path keeps the whole leaf
    in one block so the sum reduction runs in the reference's order;
    ``implementation='auto'`` falls back to the reference for leaves
    past the budget."""
    if implementation == "auto":
        implementation = (
            "pallas"
            if jax.default_backend() == "tpu"
            and g.size <= _SQSUM_VMEM_ELEMS
            else "reference"
        )
    if implementation == "reference":
        return _unscale_reference(g, denom, compute_sq)
    if implementation != "pallas":
        raise ValueError(
            f"Unknown unscale_sqsum implementation {implementation!r}"
        )
    return _unscale_pallas(g, denom, compute_sq, interpret)


# ------------------------------------------------- clip + Adam + write
def _adam_reference(g, p, mu, nu, bc1, bc2, step_size, lr_scale, factor):
    if factor is not None:
        g = g * factor
    mu_n = (1 - B1) * g + B1 * mu
    nu_n = (1 - B2) * (g ** 2) + B2 * nu
    mu_hat = mu_n / bc1.astype(mu_n.dtype)
    nu_hat = nu_n / bc2.astype(nu_n.dtype)
    u = mu_hat / (jnp.sqrt(nu_hat + EPS_ROOT) + EPS)
    u = jnp.array(step_size, u.dtype) * u
    u = u * lr_scale
    p_n = jnp.asarray(p + u).astype(jnp.asarray(p).dtype)
    return p_n, mu_n, nu_n, u


def _adam_kernel(s_ref, g_ref, p_ref, mu_ref, nu_ref,
                 p_out, mu_out, nu_out, u_out, *, has_factor):
    # Scalars arrive as strong-f32 SMEM reads, matching the traced
    # scalars of the unfused path (promotion semantics identical).
    g = g_ref[...]
    if has_factor:
        g = g * s_ref[0, 4]
    mu_n = (1 - B1) * g + B1 * mu_ref[...]
    nu_n = (1 - B2) * (g ** 2) + B2 * nu_ref[...]
    mu_hat = mu_n / s_ref[0, 0].astype(mu_n.dtype)
    nu_hat = nu_n / s_ref[0, 1].astype(nu_n.dtype)
    u = mu_hat / (jnp.sqrt(nu_hat + EPS_ROOT) + EPS)
    u = s_ref[0, 2].astype(u.dtype) * u
    u = u * s_ref[0, 3]
    p_out[...] = (p_ref[...] + u).astype(p_out.dtype)
    mu_out[...] = mu_n.astype(mu_out.dtype)
    nu_out[...] = nu_n.astype(nu_out.dtype)
    u_out[...] = u.astype(u_out.dtype)


def _adam_pallas(g, p, mu, nu, bc1, bc2, step_size, lr_scale, factor,
                 interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    has_factor = factor is not None
    ref_out = jax.eval_shape(
        lambda *a: _adam_reference(*a),
        g, p, mu, nu, jnp.asarray(bc1, jnp.float32),
        jnp.asarray(bc2, jnp.float32),
        jnp.asarray(step_size, jnp.float32),
        jnp.asarray(lr_scale, jnp.float32),
        jnp.asarray(factor, jnp.float32) if has_factor else None,
    )
    scalars = jnp.stack([
        jnp.asarray(bc1, jnp.float32),
        jnp.asarray(bc2, jnp.float32),
        jnp.asarray(step_size, jnp.float32),
        jnp.asarray(lr_scale, jnp.float32),
        jnp.asarray(factor if has_factor else 1.0, jnp.float32),
    ]).reshape(1, 5)
    flats = [_flat2(t) for t in (g, p, mu, nu)]
    # Elementwise over the row-blocked view: a leaf of any size streams
    # through VMEM one block at a time (GPT-2's 50257 x 768 embedding is
    # 154 MB per operand — whole-leaf operands cannot fit).  A short leaf
    # is one block of all its rows; a ragged last block reads padding it
    # never writes back.
    rows = flats[0].shape[0]
    block_rows = min(_ADAM_BLOCK_ROWS, rows)
    block = pl.BlockSpec((block_rows, _LANES), lambda i: (i, 0),
                         memory_space=pltpu.VMEM)
    outs = pl.pallas_call(
        functools.partial(_adam_kernel, has_factor=has_factor),
        grid=(pl.cdiv(rows, block_rows),),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] + [block] * 4,
        out_specs=[block] * 4,
        out_shape=[
            jax.ShapeDtypeStruct(flats[1].shape, ref_out[0].dtype),
            jax.ShapeDtypeStruct(flats[2].shape, ref_out[1].dtype),
            jax.ShapeDtypeStruct(flats[3].shape, ref_out[2].dtype),
            jax.ShapeDtypeStruct(flats[1].shape, ref_out[3].dtype),
        ],
        interpret=interpret,
        name="fused_adam_update",
    )(scalars, *flats)
    return tuple(
        _unflat(o, r.shape) for o, r in zip(outs, ref_out)
    )


def fused_adam_update(
    g: jax.Array,
    p: jax.Array,
    mu: jax.Array,
    nu: jax.Array,
    *,
    bc1,
    bc2,
    step_size,
    lr_scale,
    factor=None,
    implementation: str = "auto",
    interpret: bool = False,
):
    """One fused pass of the post-psum optimizer tail for one leaf
    shard: returns ``(p', mu', nu', u)`` where ``u`` is the applied
    update (the telemetry update-norm input).  ``factor=None`` means no
    clip was configured — the multiply is omitted entirely, matching the
    unfused path's conditional."""
    if implementation == "auto":
        implementation = (
            "pallas" if jax.default_backend() == "tpu" else "reference"
        )
    if implementation == "reference":
        return _adam_reference(
            g, p, mu, nu, bc1, bc2, step_size, lr_scale, factor
        )
    if implementation != "pallas":
        raise ValueError(
            f"Unknown fused_adam_update implementation {implementation!r}"
        )
    return _adam_pallas(
        g, p, mu, nu, bc1, bc2, step_size, lr_scale, factor, interpret
    )
