"""What admissions cost the host's loop, from the program's span ring over
the measured window (``serving.engine.ADMISSION_SPANS``: ``serve_land``,
``serve_admit``, ``serve_restart``).

``cost_ms``: the summed durations of the three spans per ``serve_admit``,
in milliseconds: how long one admission holds every decoding slot, landing
and restart included.  ``per_turn``: the mean of ``serve_restart``'s
``admitted``, the admissions that share one landing and one restart.

A span counts when it starts and ends inside the window.  Nothing to read
where the program exports no such names (it is older than the spans: a sum
of ``serve_admit`` alone would be a partial number), where the ring wrapped
inside the window, where the window holds none of the spans divided by, or
where the run has no device trace with operations: as ``span_stat``, a
host duration on a machine whose "device" is the same cores is not a number
about the system, and neither is a count of the admissions that queue
behind one landing, which follows the device's pace.
"""

from benchmark.readers.admission import phase_names


def read(ctx, quantity):
    names = phase_names()
    if names is None or not ctx.get("trace_reduced"):
        return None
    from ml_trainer_tpu.telemetry import spans

    t0, t1 = ctx["window"]
    events, wrapped = spans.events_between(t0, t1, names=names)
    end_us = (t1 - spans.clock_payload()["mono_epoch"]) * 1e6
    whole = [e for e in events if e["ts"] + e["dur"] <= end_us]
    if quantity == "cost_ms":
        per = [e for e in whole if e["name"] == names[1]]
        top = sum(e["dur"] for e in whole) / 1e3
    elif quantity == "per_turn":
        per = [e for e in whole if e["name"] == names[2]]
        top = sum(e["args"]["admitted"] for e in per)
    else:
        raise ValueError(f"unknown quantity {quantity!r}")
    if wrapped or not per:
        return None
    return top / len(per)
