"""A statistic of one named argument of the program's own spans
(``telemetry/spans.py``: ``span(name, **args)``) over the measured window:
the mean of ``arg`` over the named spans that carry it, or, with ``over``,
the sum of ``arg`` over the sum of ``over`` (a share of a count).
``times_size`` multiplies by a size of the configuration (the last value
under that key of ``sizes``).

The arguments are counts the program made, so a CPU run prints them too.
Nothing to read where the program has no ``events_between``, where the ring
wrapped inside the window (the answer would be partial), or where no span of
these names carries the argument (a program older than the counter)."""

import numpy as np


def read(ctx, names, arg, over=None, scale=1.0, times_size=None):
    from ml_trainer_tpu.telemetry import spans

    between = getattr(spans, "events_between", None)
    if between is None:
        return None
    events, wrapped = between(*ctx["window"], names=list(names))
    have = [e["args"] for e in events
            if arg in e.get("args", {}) and (over is None or over in e["args"])]
    if wrapped or not have:
        return None
    if times_size is not None:
        scale = scale * float(np.ravel(ctx["sizes"][times_size])[-1])
    top = sum(float(a[arg]) for a in have)
    if over is None:
        return scale * top / len(have)
    bottom = sum(float(a[over]) for a in have)
    return scale * top / bottom if bottom else None
