"""The device time of the operations a pattern names over the device's busy
time in the traced window, in percent.  Silent where the trace holds no such
operation (a program whose kernels carry no name of their own)."""

from benchmark import trace_reduce


def read(ctx, pattern):
    red, trace = ctx.get("trace_reduced"), ctx.get("trace")
    if not red or not trace or red["busy_s"] <= 0:
        return None
    seconds, calls = trace_reduce.op_seconds(trace, pattern)
    if calls == 0:
        return None
    return 100.0 * seconds / red["busy_s"]
