"""Collective-comms accounting — bytes per collective, from static shapes.

A sharding bug usually announces itself as a comms/compute ratio that is
wildly off (MegaScale-style fleet forensics: a layer all-gathering weights
it should have kept sharded doubles the step's ICI traffic long before it
shows up in loss curves).  XLA knows the traffic but buries it in HLO cost
analysis; this module makes the explicit-collective layer self-accounting
instead: every wrapper in ``parallel/collectives.py`` (and the pipeline
schedule's hops) reports its analytic byte count HERE, **at trace time**.

Trace-time discipline (the same one the on-device step stats follow):

* shapes, dtypes and mesh-axis sizes are all static during tracing, so the
  byte math runs in plain host Python exactly once per compiled program —
  zero runtime cost, zero extra compiled programs, the executed HLO is
  byte-identical to the unaccounted call;
* accounting can therefore never desynchronize from the program: a
  retrace (new shapes) re-records automatically;
* the recorded number is *bytes moved per execution* of the traced
  program — for a train step that compiles once and runs every step, that
  IS bytes-per-step.

Per-op analytic formulas (``n`` = collective axis size, ``size`` = bytes
of one participant's input):

=================  ==========================  =============================
op                 bytes per participant       rationale
=================  ==========================  =============================
psum / pmean       ``2 * size * (n-1)/n``      ring all-reduce
                                               (reduce-scatter + all-gather)
all_gather         ``size * (n-1)``            receives every other shard
reduce_scatter     ``size * (n-1)/n``          ring reduce-scatter
ppermute           ``size``                    one neighbour hop
all_to_all         ``size * (n-1)/n``          keeps 1/n locally
=================  ==========================  =============================

Recording never raises: a collective traced outside a mapped context (no
axis size to read) or with exotic leaves simply skips accounting — the
program always comes first.
"""

from __future__ import annotations

import threading
from typing import Dict, Tuple

import numpy as np
from jax import lax

_lock = threading.Lock()
_bytes: Dict[str, float] = {}
_calls: Dict[str, int] = {}
# Per-bucket breakdown for bucketed collectives (the overlapped
# reduce-scatter backward issues one collective per gradient bucket;
# attributing bytes per bucket is how a mis-sized bucket plan shows up
# on /metrics).  Keyed (op, bucket-label); mirrored into the registry as
# ``comm_bucket_bytes_total{op=,bucket=}``.
_bucket_bytes: Dict[Tuple[str, str], float] = {}
# Per-hop breakdown for the pipeline schedules (the same view-not-ledger
# pattern as buckets, one level up): forward activation hops, backward
# cotangent hops, the recompute feed, and the output/input-grad
# broadcasts are separately attributed per schedule, so a schedule that
# moves more bytes than its tick table promises shows up on /metrics.
# Keyed (schedule, hop-label); mirrored as
# ``comm_hop_bytes_total{schedule=,hop=}``.
_hop_bytes: Dict[Tuple[str, str], float] = {}
_hop_calls: Dict[Tuple[str, str], int] = {}

_FACTORS = {
    "psum": lambda size, n: 2.0 * size * (n - 1) / n,
    "pmean": lambda size, n: 2.0 * size * (n - 1) / n,
    "all_gather": lambda size, n: float(size) * (n - 1),
    "reduce_scatter": lambda size, n: float(size) * (n - 1) / n,
    "ppermute": lambda size, n: float(size),
    "all_to_all": lambda size, n: float(size) * (n - 1) / n,
}


def collective_bytes(op: str, size_bytes: int, axis_n: int) -> float:
    """Analytic bytes one participant moves for ``op`` over an axis of
    ``axis_n`` devices, given ``size_bytes`` of local input."""
    if op not in _FACTORS:
        raise ValueError(f"unknown collective op {op!r}")
    if axis_n <= 1:
        return 0.0
    return _FACTORS[op](float(size_bytes), int(axis_n))


def _tree_bytes(x) -> int:
    import jax

    total = 0
    for leaf in jax.tree.leaves(x):
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is None or dtype is None:
            continue
        total += int(np.prod([int(d) for d in shape], initial=1)) * int(
            np.dtype(dtype).itemsize
        )
    return total


def record_collective(op: str, n_bytes: float, calls: int = 1,
                      bucket: str = None) -> None:
    """Accumulate ``n_bytes`` against ``op`` and mirror the running totals
    into the default registry (``comm_bytes_total{op=...}`` /
    ``comm_calls_total{op=...}`` gauges — gauges, not counters, because
    ``reset_comm_stats`` legally zeroes them between runs).  With
    ``bucket`` set the bytes additionally land in the per-bucket
    breakdown (``comm_bucket_bytes_total{op=,bucket=}``) — the op totals
    always include bucketed traffic, so the breakdown is a view, not a
    second ledger."""
    bb = None
    with _lock:
        _bytes[op] = _bytes.get(op, 0.0) + float(n_bytes)
        _calls[op] = _calls.get(op, 0) + int(calls)
        b, c = _bytes[op], _calls[op]
        if bucket is not None:
            key = (op, str(bucket))
            _bucket_bytes[key] = _bucket_bytes.get(key, 0.0) + float(n_bytes)
            bb = _bucket_bytes[key]
    try:
        from ml_trainer_tpu.telemetry.registry import default_registry

        r = default_registry()
        r.gauge(
            "comm_bytes_total",
            "analytic bytes moved by explicit collectives (trace-time)",
            ("op",),
        ).labels(op=op).set(b)
        r.gauge(
            "comm_calls_total",
            "traced explicit-collective call sites",
            ("op",),
        ).labels(op=op).set(c)
        if bb is not None:
            r.gauge(
                "comm_bucket_bytes_total",
                "per-bucket analytic bytes of bucketed collectives "
                "(the overlapped reduce-scatter backward)",
                ("op", "bucket"),
            ).labels(op=op, bucket=str(bucket)).set(bb)
    except Exception:  # registry trouble must never break a trace
        pass


def record_hop(schedule: str, hop: str, n_bytes: float,
               calls: int = 1) -> None:
    """Accumulate ``n_bytes`` against one pipeline hop kind (``fwd`` /
    ``bwd`` / ``fwd_recompute`` / ``output_broadcast`` /
    ``grad_input_broadcast``) for ``schedule``, and mirror the running
    total into the registry as ``comm_hop_bytes_total{schedule=,hop=}``
    (a gauge, like the other comm mirrors, because ``reset_comm_stats``
    legally zeroes it between runs).  The hop breakdown is a VIEW
    beside the per-op totals — pipeline call sites record the same
    bytes into both, so op totals already include hop traffic."""
    key = (str(schedule), str(hop))
    with _lock:
        _hop_bytes[key] = _hop_bytes.get(key, 0.0) + float(n_bytes)
        _hop_calls[key] = _hop_calls.get(key, 0) + int(calls)
        b, c = _hop_bytes[key], _hop_calls[key]
    try:
        from ml_trainer_tpu.telemetry.registry import default_registry

        r = default_registry()
        r.gauge(
            "comm_hop_bytes_total",
            "analytic bytes moved per pipeline-schedule hop kind "
            "(trace-time)",
            ("schedule", "hop"),
        ).labels(schedule=key[0], hop=key[1]).set(b)
        r.gauge(
            "comm_hop_calls_total",
            "executed hop count per pipeline-schedule hop kind",
            ("schedule", "hop"),
        ).labels(schedule=key[0], hop=key[1]).set(c)
    except Exception:  # registry trouble must never break a trace
        pass


def account(op: str, x, axis, times: int = 1, bucket: str = None,
            hop: Tuple[str, str] = None) -> None:
    """Trace-time accounting hook: compute the analytic byte count of one
    ``op`` over ``axis`` for input ``x`` and record it ``times`` times.
    ``times`` exists for collectives traced once inside a ``scan`` /
    ``fori_loop`` body but executed on every iteration — the loop owner
    tops the count up with the static trip count (ring attention rotates
    K/V ``n`` times; the pipeline hops ``S+M-1`` ticks).  ``hop`` is an
    optional ``(schedule, hop_kind)`` pair that additionally lands the
    same bytes in the per-hop pipeline breakdown (``record_hop``).
    Best-effort by design — any failure (untracked axis, abstract
    leaves) is swallowed so the wrapped collective always executes
    unchanged."""
    try:
        if isinstance(axis, (tuple, list)):
            n = 1
            for a in axis:
                n *= int(lax.axis_size(a))
        else:
            n = int(lax.axis_size(axis))
        n_bytes = collective_bytes(op, _tree_bytes(x), n) * int(times)
        record_collective(op, n_bytes, calls=int(times), bucket=bucket)
        if hop is not None:
            record_hop(hop[0], hop[1], n_bytes, calls=int(times))
    except Exception:
        pass


def comm_bytes() -> Dict[str, float]:
    """Per-op cumulative analytic bytes (copy)."""
    with _lock:
        return dict(_bytes)


def comm_calls() -> Dict[str, int]:
    with _lock:
        return dict(_calls)


def comm_bucket_bytes() -> Dict[str, Dict[str, float]]:
    """Per-bucket cumulative analytic bytes, grouped by op:
    ``{op: {bucket: bytes}}`` (copy; empty when nothing bucketed ran)."""
    with _lock:
        out: Dict[str, Dict[str, float]] = {}
        for (op, bucket), b in _bucket_bytes.items():
            out.setdefault(op, {})[bucket] = b
        return out


def comm_hop_bytes() -> Dict[str, Dict[str, float]]:
    """Per-hop cumulative analytic bytes of the pipeline schedules,
    grouped by schedule: ``{schedule: {hop: bytes}}`` (copy; empty when
    no pipeline ran)."""
    with _lock:
        out: Dict[str, Dict[str, float]] = {}
        for (schedule, hop), b in _hop_bytes.items():
            out.setdefault(schedule, {})[hop] = b
        return out


def comm_hop_calls() -> Dict[str, Dict[str, int]]:
    """Executed hop counts, same grouping as :func:`comm_hop_bytes`."""
    with _lock:
        out: Dict[str, Dict[str, int]] = {}
        for (schedule, hop), c in _hop_calls.items():
            out.setdefault(schedule, {})[hop] = c
        return out


def comm_bytes_total() -> float:
    """Total analytic collective bytes across all ops."""
    with _lock:
        return float(sum(_bytes.values()))


def comm_delta(since: Dict[str, float]) -> Dict[str, float]:
    """Per-op bytes recorded since a previous ``comm_bytes()`` snapshot
    (ops with zero delta omitted)."""
    now = comm_bytes()
    out = {}
    for op, b in now.items():
        d = b - since.get(op, 0.0)
        if d > 0:
            out[op] = d
    return out


def reset_comm_stats() -> None:
    """Zero the accumulators (and their registry mirrors) — tests and
    the multichip dryrun reset between runs."""
    with _lock:
        ops: Tuple[str, ...] = tuple(_bytes)
        buckets = tuple(_bucket_bytes)
        hops = tuple(_hop_bytes)
        _bytes.clear()
        _calls.clear()
        _bucket_bytes.clear()
        _hop_bytes.clear()
        _hop_calls.clear()
    try:
        from ml_trainer_tpu.telemetry.registry import default_registry

        r = default_registry()
        for op in ops:
            r.gauge("comm_bytes_total", "", ("op",)).labels(op=op).set(0.0)
            r.gauge("comm_calls_total", "", ("op",)).labels(op=op).set(0.0)
        for op, bucket in buckets:
            r.gauge(
                "comm_bucket_bytes_total", "", ("op", "bucket")
            ).labels(op=op, bucket=bucket).set(0.0)
        for schedule, hop in hops:
            r.gauge(
                "comm_hop_bytes_total", "", ("schedule", "hop")
            ).labels(schedule=schedule, hop=hop).set(0.0)
            r.gauge(
                "comm_hop_calls_total", "", ("schedule", "hop")
            ).labels(schedule=schedule, hop=hop).set(0.0)
    except Exception:
        pass
