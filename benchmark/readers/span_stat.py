"""A statistic of the program's own spans (``telemetry/spans.py``) over the
measured window, on the host's clock: the mean or 95th percentile of the
named spans' durations in seconds, or, with ``per``, their summed duration
over the number of ``per`` spans (host seconds a decode step).

A span counts when it starts and ends inside the window: one cut by the
window's edge was not measured whole (the training driver closes its window
with a device fence inside the last dispatch).  Nothing to read where the
program has no ``events_between``, where the ring wrapped inside the window
(the answer would be partial), or where the run has no device trace with
operations: a host duration from a machine whose "device" is the same cores
is not a number about the system.
"""

from benchmark.loadgen import percentile


def read(ctx, names, stat="mean", per=None, scale=1.0):
    if not ctx.get("trace_reduced"):
        return None
    from ml_trainer_tpu.telemetry import spans

    between = getattr(spans, "events_between", None)
    if between is None:
        return None
    t0, t1 = ctx["window"]
    events, wrapped = between(
        t0, t1, names=list(names) + ([per] if per else []))
    if wrapped:
        return None
    end_us = (t1 - spans.clock_payload()["mono_epoch"]) * 1e6
    whole = [e for e in events if e["ts"] + e["dur"] <= end_us]
    secs = [e["dur"] / 1e6 for e in whole if e["name"] in names]
    if not secs:
        return None
    if per is not None:
        count = sum(1 for e in whole if e["name"] == per)
        return scale * sum(secs) / count if count else None
    if stat == "mean":
        return scale * sum(secs) / len(secs)
    if stat == "p95":
        return scale * percentile(secs, 0.95)
    raise ValueError(f"unknown statistic {stat!r}")
