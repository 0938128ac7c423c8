"""A kernel's share of its roofline: the least time the chip could take for
the work its calls needed over the device time of the operations the pattern
names in the traced window.  ``work`` names the function of the
configuration's work counts (``work/<name>.py``) that gives the operations
and bytes of those calls from the run's shapes.  Silent where the trace
holds no such operation."""

from benchmark import flops, trace_reduce


def read(ctx, pattern, work):
    trace = ctx.get("trace")
    if not trace:
        return None
    seconds, calls = trace_reduce.op_seconds(trace, pattern)
    if calls == 0 or seconds <= 0:
        return None
    ops, moved = getattr(ctx["work"], work)(ctx)
    if ops == 0:
        return None
    least, bound = flops.roofline_seconds(ops, moved, ctx["peaks"])
    ctx.setdefault("notes", {})[f"{work}_bound"] = bound
    return 100.0 * least / seconds
