"""The whole decode step's share of its roofline: the least time the chip
could take for one step's reads and products (the function of the
configuration's work counts that ``work`` names gives the window's mean
operations and bytes a step) over the mean of the engine's own step samples
of the window (what ``decode_step_ms`` reads: dispatch to the host copy of
the tokens).  A step cannot take less than its roofline, so the share is at
most 100 by construction.  Silent where the run has no device trace, as
``span_stat`` is: a host duration from a machine whose "device" is the same
cores is not a number about the system."""

from benchmark import flops


def read(ctx, work, series="step_secs"):
    if not ctx.get("trace_reduced"):
        return None
    values = (ctx.get("samples") or {}).get(series) or []
    if not values:
        return None
    ops, moved = getattr(ctx["work"], work)(ctx)
    if not ops:
        return None
    least, bound = flops.roofline_seconds(ops, moved, ctx["peaks"])
    ctx.setdefault("notes", {})[f"{work}_bound"] = bound
    return 100.0 * least * len(values) / sum(values)
