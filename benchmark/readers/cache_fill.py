"""Live cache positions over the positions the slot cache reserves, averaged
over the window, in percent.  A request holds ``prompt_len + k`` positions
between its k-th token and the next, as the client received them; the pool
is ``slots`` x ``positions``, reserved whatever the traffic fills."""


def read(ctx):
    t0, t1 = ctx["window"]
    pool = ctx.get("slots", 0) * ctx["sizes"]["positions"]
    held = 0.0
    for r in ctx.get("records") or []:
        times = r["times"]
        for k, (a, b) in enumerate(zip(times, times[1:]), start=1):
            overlap = min(b, t1) - max(a, t0)
            if overlap > 0:
                held += (r["prompt_len"] + k) * overlap
    if not pool or held <= 0:
        return None
    return 100.0 * held / ((t1 - t0) * pool)
