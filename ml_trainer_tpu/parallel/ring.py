"""Ring attention — sequence/context parallelism over a mesh axis.

The reference "scales sequence length" not at all (SURVEY.md §5
long-context); this module makes it first-class.  Each device holds a
``S/n``-length shard of Q, K and V.  K/V shards rotate around the ring via
``lax.ppermute`` (ICI neighbour hops) while every device folds each visiting
block into its local online-softmax accumulators — full attention over
sequences n× longer than one chip could hold, with O(S/n) local memory and
communication that overlaps compute.

Built on ``shard_map`` so the same module composes with data/tensor
sharding on the other mesh axes, and the inner block math reuses the same
online-softmax recurrence as the Pallas flash kernel (ops/attention.py).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map

from ml_trainer_tpu.parallel.collectives import ppermute_ring
from ml_trainer_tpu.parallel.comm_stats import account as _comm_account
from jax.sharding import Mesh, PartitionSpec as P


def _block_attend(q, k, v, m_prev, l_prev, o_prev, q_offset, k_offset,
                  causal, scale):
    """Fold one visiting K/V block into the online-softmax accumulators.
    q: [B, H, Sq, D]; k, v: [B, H, Sk, D]; offsets are global positions."""
    scores = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if causal:
        sq, sk = scores.shape[-2], scores.shape[-1]
        row = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0) + q_offset
        col = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1) + k_offset
        scores = jnp.where(row >= col, scores, jnp.finfo(jnp.float32).min)
    m_cur = jnp.max(scores, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(scores - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    o_new = o_prev * alpha + jnp.einsum(
        "bhqk,bhkd->bhqd", p, v.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    return m_new, l_new, o_new


def _ring_attention_local(q, k, v, *, axis_name, causal, scale):
    """Runs per-shard inside shard_map.  q/k/v: [B, H, S_local, D]."""
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    s_local = q.shape[-2]
    q32 = q.astype(jnp.float32)
    q_offset = my * s_local

    def step(i, carry):
        m, l, o, kk, vv = carry
        # kk/vv currently hold the block that started on device (my - i) % n.
        src = jnp.mod(my - i, n)
        m, l, o = _block_attend(
            q32, kk.astype(jnp.float32), vv, m, l, o,
            q_offset, src * s_local, causal, scale,
        )
        # Rotate: send our current block to the next device on the ring.
        kk = ppermute_ring(kk, axis_name)
        vv = ppermute_ring(vv, axis_name)
        return m, l, o, kk, vv

    b, h, _, d = q.shape
    init = (
        jnp.full((b, h, s_local, 1), jnp.finfo(jnp.float32).min, jnp.float32),
        jnp.zeros((b, h, s_local, 1), jnp.float32),
        jnp.zeros((b, h, s_local, d), jnp.float32),
        k,
        v,
    )
    # The two ppermute_ring hops in step() trace ONCE inside fori_loop but
    # execute n times each; top the comm accounting up by the remaining
    # n-1 iterations (parallel/comm_stats.py).
    _comm_account("ppermute", (k, v), axis_name, times=n - 1)
    m, l, o, _, _ = lax.fori_loop(0, n, step, init)
    return (o / l).astype(q.dtype)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    *,
    axis_name: str = "sequence",
    causal: bool = False,
    scale: Optional[float] = None,
    batch_axis: Optional[str] = "data",
) -> jax.Array:
    """Sequence-parallel attention over [B, H, S, D] arrays whose S dim is
    (or will be) sharded over ``mesh[axis_name]``.

    ``batch_axis`` names the mesh axis the batch dim is sharded over (so the
    ring composes with data parallelism without an implicit all-gather);
    axes absent from the mesh are ignored."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if batch_axis is not None and batch_axis not in mesh.axis_names:
        batch_axis = None
    spec = P(batch_axis, None, axis_name, None)
    fn = shard_map(
        functools.partial(
            _ring_attention_local, axis_name=axis_name, causal=causal,
            scale=scale,
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)
