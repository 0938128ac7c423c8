"""Power retention's decode step over the state pool: ONE read and one write
of the pool, in place.

A decode step of ``models/brumby.py`` needs, for every slot and key-value
head, what the state ``S`` (``[d_v, P]``, float32, ``phi``'s entries on the
lanes: ``ops/power_retention.py``) and the normaliser ``z`` (``[P]``) read
at ``phi(q)`` of the group's query heads AS THEY ARRIVED, and then ``g S +
v phi(k)^T`` and ``g z + phi(k)`` in their place.  Stated in XLA that is two
passes a layer over the pool: a multiply-and-reduce fusion that reads it
(five query heads a group: ten VPU operations an element, 390 GB/s on a v5e)
and a multiply-add fusion that reads and writes it (665 GB/s): 24.2 ms of a
35.2 ms step at 16 slots of eight layers of 8 x 8,256 x 128, 44% of the
10.65 ms that one read and one write take at the memory's speed (PERF.md,
PR 34).  This kernel makes one pass: a grid step holds one slot's one
key-value head (``[d_v, P]``: 4.26 MB at heads of 128), written back through
``input_output_aliases``, so the pool never leaves its buffer.

Inside a grid step:

* ``phi(q)`` (the group's heads on the sublanes, padded to 8) and ``phi(k)``
  are made in VMEM, a row of ``phi`` (``d`` lanes) at a time: ``c_r a
  roll(a, -r)``, one lane rotation each, no gather (the order is
  ``ops/power_retention.py::phi``'s, which the prompt's form wrote the state
  in).  The normaliser's read and update ride along, one vreg a row.
* the state goes by in blocks of 32 value rows.  For each row of ``phi``
  the ``[32, d]`` tile is multiplied by each head's row of ``phi(q)`` (a
  sublane broadcast) into that head's accumulator, and ``g tile + v
  phi(k)`` is written where the tile was; the value column is spread along
  the lanes once a block.  The sum over ``D`` stays lane by lane until the
  block's last tile, then ONE lane reduction a head.
* what the state read comes back as ``[B, G, d_v, 128]``, head ``h`` in lane
  ``h`` (a lane-dense block; XLA takes the first ``R`` lanes), the
  normaliser's as ``[B, G, 8, d]`` lane by lane (XLA sums the lanes): both
  are 1/65 of the state or less.

``implementation='auto'`` is the kernel on a TPU at heads of a multiple of
128 and ``state_step_reference`` everywhere else.  Pinned to the reference by
tolerance, not bit for bit: the sum over ``D`` runs in another order
(``tests/test_kernels.py``, interpret mode).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ml_trainer_tpu.ops.power_retention import (
    phi_padded,
    state_step_reference,
)

SUBLANES = 8        # the group's query heads are padded to a vreg's rows
VALUE_ROWS = 32     # value rows of the state a block of accumulators holds
READ_LANES = 128    # the lanes of the read's output block


def _kernel(q_ref, k_ref, v_ref, g_ref, s_ref, z_ref,
            read_ref, z_read_ref, s_out, z_out, pq_scr, pk_scr, *,
            d: int, heads: int, value_rows: int):
    # Pallas is imported where it is used, as in the package's other
    # kernels: every model family's import reaches this module.
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows = d // 2 + 1
    q, k = q_ref[0, 0], k_ref[0, 0]                # [8, d], k in every row
    g = g_ref[0, 0]                                # [1, d], g in every lane
    lane = jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, d), 1)
    z_read = jnp.zeros((SUBLANES, d), jnp.float32)
    for r in range(rows):
        at = slice(r * d, (r + 1) * d)
        coef = (1.0 if r == 0 else math.sqrt(2.0)) * d ** -0.5

        def row(a):
            turned = a if r == 0 else pltpu.roll(a, shift=d - r, axis=1)
            out = coef * a * turned
            # a pair at distance d / 2 comes twice: its second half is 0
            return jnp.where(lane < d // 2, out, 0.0) if 2 * r == d else out

        pq, pk = row(q), row(k)
        pq_scr[:, at], pk_scr[:, at] = pq, pk
        z = z_ref[0, 0, :, at]                                  # [1, d]
        z_read = z_read + pq * z
        z_out[0, 0, :, at] = g * z + pk[0:1]
    z_read_ref[0, 0] = z_read

    out_lane = jax.lax.broadcasted_iota(
        jnp.int32, (value_rows, READ_LANES), 1)

    def block(i, carry):
        these = pl.ds(pl.multiple_of(i * value_rows, value_rows), value_rows)
        spread = jnp.broadcast_to(v_ref[0, 0, these, :], (value_rows, d))
        acc = [jnp.zeros((value_rows, d), jnp.float32)] * heads
        for r in range(rows):
            at = slice(r * d, (r + 1) * d)
            tile = s_ref[0, 0, these, at]
            acc = [a + tile * pq_scr[h:h + 1, at] for h, a in enumerate(acc)]
            s_out[0, 0, these, at] = g * tile + spread * pk_scr[0:1, at]
        out = jnp.zeros((value_rows, READ_LANES), jnp.float32)
        for h, a in enumerate(acc):
            out = jnp.where(out_lane == h,
                            jnp.sum(a, axis=1, keepdims=True), out)
        read_ref[0, 0, these, :] = out
        return carry

    jax.lax.fori_loop(0, s_ref.shape[2] // value_rows, block, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _retention_state_step_pallas(q, k, v, g, state, norm, interpret=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, n, heads, d = q.shape
    d_v, p = state.shape[2], state.shape[3]
    f32 = jnp.float32
    value_rows = math.gcd(d_v, VALUE_ROWS)
    q8 = jnp.pad(q.astype(f32),
                 ((0, 0), (0, 0), (0, SUBLANES - heads), (0, 0)))
    k8 = jnp.broadcast_to(k.astype(f32)[:, :, None], (b, n, SUBLANES, d))
    g_lanes = jnp.broadcast_to(g.astype(f32)[:, :, None, None], (b, n, 1, d))

    def spec(*block):
        return pl.BlockSpec((1, 1) + block, lambda i, j: (i, j, 0, 0))

    read, z_read, state, norm = pl.pallas_call(
        functools.partial(_kernel, d=d, heads=heads, value_rows=value_rows),
        grid=(b, n),
        in_specs=[spec(SUBLANES, d), spec(SUBLANES, d), spec(d_v, 1),
                  spec(1, d), spec(d_v, p), spec(1, p)],
        out_specs=[spec(d_v, READ_LANES), spec(SUBLANES, d), spec(d_v, p),
                   spec(1, p)],
        out_shape=[
            jax.ShapeDtypeStruct((b, n, d_v, READ_LANES), f32),
            jax.ShapeDtypeStruct((b, n, SUBLANES, d), f32),
            jax.ShapeDtypeStruct(state.shape, f32),
            jax.ShapeDtypeStruct((b, n, 1, p), f32),
        ],
        scratch_shapes=[pltpu.VMEM((SUBLANES, p), f32),
                        pltpu.VMEM((SUBLANES, p), f32)],
        input_output_aliases={4: 2, 5: 3},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            # a head's state in and out, each twice for the pipeline
            vmem_limit_bytes=int(4.5 * d_v * p * 4) + (8 << 20)),
        interpret=interpret,
        name="retention_state_step",
    )(q8, k8, v.astype(f32)[..., None], g_lanes, state,
      norm[:, :, None, :])
    return (jnp.swapaxes(read[..., :heads], 2, 3),
            jnp.sum(z_read[:, :, :heads], axis=-1), state, norm[:, :, 0])


def retention_state_step(q, k, v, g, state, norm, *,
                         implementation: str = "auto",
                         interpret: bool = False):
    """The pass over the pool of one decode step; arguments and results as
    ``ops/power_retention.py::state_step_reference``."""
    d, heads = q.shape[-1], q.shape[2]
    if state.shape[-1] != phi_padded(d):
        raise ValueError(
            f"a state of {state.shape} for heads of {d}: its last axis is "
            f"phi's {phi_padded(d)} entries")
    if implementation == "auto":
        implementation = (
            "pallas" if jax.default_backend() == "tpu" and d % 128 == 0
            and heads <= SUBLANES else "reference")
    if implementation in ("reference", "xla"):
        return state_step_reference(q, k, v, g, state, norm)
    if implementation != "pallas":
        raise ValueError(
            f"Unknown retention_state_step implementation "
            f"{implementation!r}; expected 'auto', 'pallas', or 'reference'")
    if heads > SUBLANES:
        raise ValueError(f"{heads} query heads a group: at most {SUBLANES}")
    return _retention_state_step_pallas(
        q, k, v, g, state, norm, interpret=interpret)
