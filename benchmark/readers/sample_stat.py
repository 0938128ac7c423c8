"""A statistic of one series of ``ServingMetrics`` samples recorded inside
the measured window (queue wait, prefill or step seconds, occupancy)."""

from benchmark.loadgen import percentile


def read(ctx, series, stat, scale=1.0):
    values = (ctx.get("samples") or {}).get(series) or []
    if not values:
        return None
    if stat == "mean":
        return scale * sum(values) / len(values)
    if stat == "p95":
        return scale * percentile(values, 0.95)
    raise ValueError(f"unknown statistic {stat!r}")
