"""One minus the union of device operations over the traced window."""


def read(ctx):
    red = ctx.get("trace_reduced")
    if not red or red["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
