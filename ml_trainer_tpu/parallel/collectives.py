"""Collective primitives over mesh axes.

The reference's collective surface is DDP's implicit bucketed all-reduce
plus a dead manual ``dist.all_reduce(SUM)/world`` loop
(ref: src/trainer.py:98, 152-158).  Here the same operations are XLA
collective primitives bound to named mesh axes — usable inside
``shard_map``-decorated kernels (ring attention, expert dispatch) while
ordinary data parallelism never calls them explicitly (sharding annotations
imply them).

Every wrapper reports its analytic byte count to ``comm_stats.account``
AT TRACE TIME (shapes and axis sizes are static there), so the registry's
``comm_bytes_total{op=...}`` gauges attribute traffic per collective with
zero runtime cost and no change to the compiled program — the
distributed-observability leg of docs/observability.md.
"""

from __future__ import annotations

from typing import Union, Sequence

from jax import lax

from ml_trainer_tpu.parallel.comm_stats import account as _account

AxisName = Union[str, Sequence[str]]


def psum(x, axis: AxisName):
    """Sum across an axis — the ``dist.all_reduce(SUM)`` analog
    (ref: src/trainer.py:157)."""
    _account("psum", x, axis)
    return lax.psum(x, axis)


def pmean(x, axis: AxisName):
    """Mean across an axis — all_reduce(SUM)/world in one op, the exact
    semantics of the reference's ``_average_gradients``
    (ref: src/trainer.py:152-158)."""
    _account("pmean", x, axis)
    return lax.pmean(x, axis)


def all_gather(x, axis: AxisName, *, axis_index: int = 0, tiled: bool = True,
               bucket: str = None):
    """``bucket`` labels this call in the per-bucket comm breakdown
    (``comm_bucket_bytes_total{op=,bucket=}``) — the bucketed weight
    all-gather of the sharded update path tags each bucket's traffic."""
    _account("all_gather", x, axis, bucket=bucket)
    return lax.all_gather(x, axis, axis=axis_index, tiled=tiled)


def reduce_scatter(x, axis: AxisName, *, scatter_axis: int = 0,
                   bucket: str = None):
    """``bucket`` labels this call in the per-bucket comm breakdown — the
    overlapped backward issues one reduce-scatter per gradient bucket."""
    _account("reduce_scatter", x, axis, bucket=bucket)
    return lax.psum_scatter(x, axis, scatter_dimension=scatter_axis, tiled=True)


def ppermute_ring(x, axis: AxisName, shift: int = 1):
    """Send each shard to its ring neighbour over ICI — the building block
    of ring attention (parallel/ring.py rotates K/V through it)."""
    n = lax.axis_size(axis)
    perm = [(i, (i + shift) % n) for i in range(n)]
    _account("ppermute", x, axis)
    return lax.ppermute(x, axis, perm)


def all_to_all(x, axis: AxisName, *, split_axis: int, concat_axis: int):
    """Re-partition one array dim across another — the Ulysses
    head/sequence exchange (parallel/ulysses.py runs a pair of these)."""
    _account("all_to_all", x, axis)
    return lax.all_to_all(
        x, axis, split_axis=split_axis, concat_axis=concat_axis, tiled=True
    )


def axis_index(axis: AxisName):
    return lax.axis_index(axis)


def axis_size(axis: AxisName):
    return lax.axis_size(axis)
