"""A kernel's share of its roofline: the least time the chip could take for
the work its calls needed (benchmark/flops.py, from shapes) over the device
time of the operations the pattern names in the traced window.  Silent
where the trace holds no such operation."""

from benchmark import flops, trace_reduce


def _train_work(ctx):
    train = ctx["train"]
    o, b = flops.flash_train_work(
        ctx["sizes"], train["batch"], train["seq_len"],
        ctx["bytes_per_value"])
    n = ctx["sizes"]["layers"] * train["traced_steps"]
    return o * n, b * n


WORK = {"flash_train": _train_work}


def read(ctx, pattern, work):
    trace = ctx.get("trace")
    if not trace:
        return None
    seconds, calls = trace_reduce.op_seconds(trace, pattern)
    if calls == 0 or seconds <= 0:
        return None
    ops, moved = WORK[work](ctx)
    if ops == 0:
        return None
    least, bound = flops.roofline_seconds(ops, moved, ctx["peaks"])
    ctx.setdefault("notes", {})[f"{work}_bound"] = bound
    return 100.0 * least / seconds
