"""The device's idle time by what the host was doing, cut at the host spans'
edges: the nanoseconds of idle during which a span of one of the given names
is open (``spans``), or during which none of them is (``not_spans``), over
the traced window, in percent.

Idle is what ``device_idle`` reads, less the gaps under
``trace_reduce.MIN_LABELLED_GAP_NS`` (between two operations of one program;
``reduce`` calls them ``between_ops``).  A gap that runs through several
spans is split where they meet, not handed whole to the one that covers most
of it, and an instant under two of the given names counts once.  Metrics
whose ``spans`` are never open together, and the one ``not_spans`` metric
over all of those names, therefore add up to the labelled idle exactly.

``needs`` names spans that a program with these phase spans always records in
a traced slice (default: the given names).  Where the trace holds none of
them the program is older than the spans, and there is nothing to read; where
it does, a span that happens not to occur in the slice (no admission in that
second) reads 0.
"""

from benchmark import trace_reduce


def labelled_gaps(trace: dict) -> list:
    """Each device's idle gaps of at least the labelled length (the costly
    part: a serving trace holds 450,000 device events a second)."""
    start, end = trace["window"]
    return [
        [g for g in trace_reduce.idle_intervals(
            trace_reduce.busy_intervals(events, start, end), start, end)
         if g[1] - g[0] >= trace_reduce.MIN_LABELLED_GAP_NS]
        for events in trace["devices"].values()]


def overlap_ns(gaps: list, cover: list) -> int:
    """Nanoseconds of the sorted, disjoint ``gaps`` that lie inside the
    sorted, disjoint ``cover``."""
    total, j = 0, 0
    for lo, hi in gaps:
        while j < len(cover) and cover[j][1] <= lo:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < hi:
            total += min(hi, cover[k][1]) - max(lo, cover[k][0])
            k += 1
    return total


def read(ctx, spans=None, not_spans=None, needs=None):
    trace = ctx.get("trace")
    if not ctx.get("trace_reduced") or not trace:
        return None
    if (spans is None) == (not_spans is None):
        raise ValueError("give one of 'spans' and 'not_spans'")
    names = spans if spans is not None else not_spans
    recorded = {e[0] for e in trace["host"]}
    if not recorded.intersection(needs or names):
        return None
    gaps = ctx.get("labelled_gaps")
    if gaps is None:
        gaps = ctx["labelled_gaps"] = labelled_gaps(trace)
    start, end = trace["window"]
    # The union of the intervals in which a span of these names is open.
    cover = trace_reduce.busy_intervals(
        [e for e in trace["host"] if e[0] in names], start, end)
    inside = sum(overlap_ns(g, cover) for g in gaps)
    if spans is None:
        inside = sum(hi - lo for g in gaps for lo, hi in g) - inside
    return 100.0 * inside / ((end - start) * len(gaps))
