"""Ulysses-style sequence parallelism — all-to-all head/sequence exchange.

The second of the two standard long-context strategies (alongside
``parallel/ring.py``; the reference has neither, SURVEY.md §5): with the
sequence dim sharded over ``n`` devices, one ``all_to_all`` re-partitions
[B, H, S/n, D] into [B, H/n, S, D] — every device then holds the FULL
sequence for its slice of heads, runs an ordinary (flash-able) attention
locally with no cross-device math in the softmax, and a second
``all_to_all`` restores the sequence sharding.

Trade-off vs ring: two bulk a2a collectives (ICI-friendly) instead of n
pipelined ppermute hops, and the local attention is an ordinary full-
sequence call — it dispatches through ``ops.attention`` in 'auto' mode, so
on TPU the Pallas flash kernel applies (O(S) local memory) and elsewhere
the XLA path runs.  Requires the sequence-axis size to divide the head
count (``H % n == 0``).

Built on ``shard_map`` like the ring, so it composes with data/tensor
sharding on the other mesh axes.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ml_trainer_tpu.parallel.collectives import all_to_all


def _ulysses_local(q, k, v, *, axis_name, causal, scale, attend):
    """Per-shard body.  q/k/v: [B, H, S_local, D] -> same shape."""
    # Scatter heads, gather sequence: [B, H, S/n, D] -> [B, H/n, S, D].
    def a2a_fwd(x):
        return all_to_all(x, axis_name, split_axis=1, concat_axis=2)

    def a2a_bwd(x):
        return all_to_all(x, axis_name, split_axis=2, concat_axis=1)

    qg, kg, vg = a2a_fwd(q), a2a_fwd(k), a2a_fwd(v)
    # Full sequence present locally: plain causal attention, no offsets.
    out = attend(qg, kg, vg, causal=causal, scale=scale)
    return a2a_bwd(out)


def ulysses_attention(
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
    (or will be) sharded over ``mesh[axis_name]``; same contract as
    ``ring_attention``.  The sequence-axis size must divide the head
    count."""
    from ml_trainer_tpu.ops import attention as attention_ops

    if scale is None:
        scale = q.shape[-1] ** -0.5
    n = mesh.shape[axis_name]
    h = q.shape[1]
    if h % n:
        raise ValueError(
            f"ulysses needs heads % sequence-axis == 0, got H={h}, n={n}"
        )
    if batch_axis is not None and batch_axis not in mesh.axis_names:
        batch_axis = None

    def attend(qg, kg, vg, *, causal, scale):
        # 'auto' picks the Pallas flash kernel on TPU when shapes allow,
        # the XLA path otherwise — the a2a layout makes this an ordinary
        # single-device attention call.
        return attention_ops.attention(
            qg, kg, vg, causal=causal, scale=scale, implementation="auto"
        )

    spec = P(batch_axis, None, axis_name, None)
    fn = shard_map(
        functools.partial(
            _ulysses_local, axis_name=axis_name, causal=causal, scale=scale,
            attend=attend,
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)
