"""What an admission costs the device, phase by phase, from the traced
slice (``ctx["trace"]``, normal form): the device's idle under the program's
admission spans, its busy time inside them, and how far the host's clock can
be trusted against the device's.

The program names the phases of an admission turn in one constant,
``serving.engine.ADMISSION_SPANS``: the landing an admission forces
(``serve_land``), each admission (``serve_admit``), and the first dispatch
after them with nothing ahead (``serve_restart``).  A program without the
constant is older than the spans: nothing to read.

**The bracket.**  The profiler stamps host spans and device operations with
two clocks some 1.5 ms apart, as large as the phases to be split.  Every
admission turn marks both, because the landing empties the device and
nothing is queued behind the prefill or the restart: the busy run (what lies
between two idle gaps of at least ``trace_reduce.MIN_LABELLED_GAP_NS``) that
a prefill's ``serve_prefill`` ... ``serve_prefill.fence`` interval overlaps
most cannot start before ``serve_prefill`` starts nor end after the fence
returns; the run that the landing's ``serve_decode.fence`` overlaps most
(the step in flight) cannot end after that fence returns; the run that the
restart's ``serve_decode.dispatch`` overlaps most cannot start before the
dispatch starts.  With device time = host time + delta, a start gives
delta <= run start - host start, an end delta >= run end - host end.  The
bracket is the largest lower bound and the smallest upper bound over the
slice's marks and devices: the prefills' runs are matched at delta = 0, then
every mark's at the midpoint of the prefills' bracket.  Its width says how
far to trust a split between host and device; a negative width means the
bounds contradict each other, and is reported as it is.

The host spans are shifted by the bracket's midpoint before any cut (not at
all where the slice brackets nothing); the idle is then cut at their edges as
``idle_by_span`` cuts it, which does not shift.

``phase``: ``turn`` (any of the three spans), ``land``, ``admit`` or
``restart``: device idle in labelled gaps under it, percent of the slice; 0
where the slice holds no such span.  ``quantity``: ``device_ms``, device
busy time inside ``serve_admit`` per admission wholly in the slice, or
``bracket_us``, the bracket's width; None where the slice holds no
admission.
"""

from benchmark import trace_reduce
from benchmark.readers.idle_by_span import overlap_ns

PREFILL, FENCE = "serve_prefill", "serve_prefill.fence"
LAND_FENCE, DISPATCH = "serve_decode.fence", "serve_decode.dispatch"


def phase_names():
    """The program's admission spans, or None for a program without them."""
    from ml_trainer_tpu.serving import engine

    return getattr(engine, "ADMISSION_SPANS", None)


def device_intervals(ctx) -> list:
    """Per device: (busy intervals, labelled gaps), computed once a run."""
    if "admission_device" not in ctx:
        trace = ctx["trace"]
        start, end = trace["window"]
        out = []
        for events in trace["devices"].values():
            busy = trace_reduce.busy_intervals(events, start, end)
            gaps = [g for g in trace_reduce.idle_intervals(busy, start, end)
                    if g[1] - g[0] >= trace_reduce.MIN_LABELLED_GAP_NS]
            out.append((busy, gaps))
        ctx["admission_device"] = out
    return ctx["admission_device"]


def busy_runs(gaps: list, start: int, end: int) -> list:
    """What lies between two labelled gaps; a run cut by the slice's edge
    is left out (its first or last operation is not in the slice)."""
    edges = [start] + [x for g in gaps for x in g] + [end]
    return [(lo, hi) for lo, hi in zip(edges[::2], edges[1::2])
            if lo < hi and lo != start and hi != end]


def marks(host: list, names) -> list:
    """(host start, host end, which bounds) of each interval whose device
    run the bracket reads: a prefill gives both, a landing's fence the lower
    one, a restart's dispatch the upper one."""
    land, admit, restart = names
    out = []
    for name, s, d in host:
        if name not in names:
            continue
        kids = {n: (ks, ks + kd) for n, ks, kd in host
                if s <= ks and ks + kd <= s + d}
        if name == admit and PREFILL in kids and FENCE in kids:
            out.append((kids[PREFILL][0], kids[FENCE][1], "both"))
        elif name == land and LAND_FENCE in kids:
            out.append((*kids[LAND_FENCE], "lower"))
        elif name == restart and DISPATCH in kids:
            out.append((*kids[DISPATCH], "upper"))
    return out


def bracket(marks: list, runs_by_device: list, delta: float):
    """(lower, upper) bounds on delta from each mark's run, the runs
    matched with the host intervals shifted by ``delta``; None without a
    bound on each side."""
    lower, upper = [], []
    for lo, hi, bounds in marks:
        for runs in runs_by_device:
            over = [(min(r[1], hi + delta) - max(r[0], lo + delta), r)
                    for r in runs]
            best = max(over, default=(0, None))
            if best[0] <= 0:
                continue
            if bounds != "lower":
                upper.append(best[1][0] - lo)
            if bounds != "upper":
                lower.append(best[1][1] - hi)
    if not lower or not upper:
        return None
    return max(lower), min(upper)


def read(ctx, phase=None, quantity=None):
    names = phase_names()
    trace = ctx.get("trace")
    if names is None or not trace or not ctx.get("trace_reduced"):
        return None
    if (phase is None) == (quantity is None):
        raise ValueError("give one of 'phase' and 'quantity'")
    land, admit, restart = names
    start, end = trace["window"]
    device = device_intervals(ctx)
    runs = [busy_runs(gaps, start, end) for _, gaps in device]
    found = marks(trace["host"], names)
    bounds, delta = None, 0
    # A prefill's interval is long enough to find its run whatever the
    # offset; the fences and dispatches are matched at the prefills' midpoint.
    for used in ([m for m in found if m[2] == "both"], found):
        got = bracket(used, runs, delta)
        if got is None:
            break
        bounds, delta = got, (got[0] + got[1]) // 2
    if quantity == "bracket_us":
        return None if bounds is None else (bounds[1] - bounds[0]) / 1e3
    shifted = [(n, s + delta, d) for n, s, d in trace["host"]]
    if quantity == "device_ms":
        whole = [e for e in shifted if e[0] == admit
                 and e[1] >= start and e[1] + e[2] <= end]
        if not whole:
            return None
        cover = trace_reduce.busy_intervals(whole, start, end)
        busy = sum(overlap_ns(b, cover) for b, _ in device) / len(device)
        return busy / len(whole) / 1e6
    if quantity is not None:
        raise ValueError(f"unknown quantity {quantity!r}")
    spans = {"turn": names, "land": (land,), "admit": (admit,),
             "restart": (restart,)}[phase]
    cover = trace_reduce.busy_intervals(
        [e for e in shifted if e[0] in spans], start, end)
    idle = sum(overlap_ns(gaps, cover) for _, gaps in device)
    return 100.0 * idle / ((end - start) * len(device))
