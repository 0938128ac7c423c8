"""From a profiler trace to numbers: busy union, idle share, per-operation
time, and idle gaps by what the host was doing.

Two steps, so that the arithmetic is testable without a chip: ``load_xplane``
turns the profiler's ``.xplane.pb`` into a plain dictionary of events
(``normal form``, also what ``fixtures/`` holds), and everything else works
on that dictionary alone.

Normal form, all times in nanoseconds on the profiler's one clock::

    {"window": [start, end],
     "devices": {"<plane name>": [[name, start, duration], ...]},
     "host": [[name, start, duration], ...]}

``devices`` holds the leaf operations of each device ("XLA Ops" line);
``host`` holds the program's spans (``jax.profiler.TraceAnnotation``).
"""

from __future__ import annotations

import glob
import os
import re

WINDOW_SPAN = "bench_trace_window"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
# The program names its spans in snake_case (telemetry/spans.py); the
# runtime's own host events carry '::', '(' or a leading '$'.
HOST_SPAN = re.compile(r"^[a-z][a-z0-9_.]*$")


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(
        os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


TARGET = re.compile(r'custom_call_target="([^"]+)"')
INSTANCE = re.compile(r"\.\d+(?=$|\|)")


def short_name(text: str) -> str:
    """The profiler names a device operation by its whole HLO instruction.
    Keep the instruction's own name and, for a custom call, its target
    (``tpu_custom_call`` is a Pallas kernel compiled by Mosaic)."""
    name = text.split(" = ", 1)[0].lstrip("%")
    target = TARGET.search(text)
    return f"{name}|{target.group(1)}" if target else name


def load_xplane(path: str) -> dict:
    """Read the profiler's file with JAX's own reader."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host, window = {}, [], None
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        [short_name(ev.name), int(ev.start_ns),
                         int(ev.duration_ns)]
                        for ev in line.events
                    ]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW_SPAN:
                        window = [int(ev.start_ns),
                                  int(ev.start_ns + ev.duration_ns)]
                    elif HOST_SPAN.match(ev.name):
                        host.append([ev.name, int(ev.start_ns),
                                     int(ev.duration_ns)])
    if window is None:
        edges = [(s, s + d) for evs in devices.values() for _, s, d in evs]
        if edges:
            window = [min(e[0] for e in edges), max(e[1] for e in edges)]
    return {"window": window, "devices": devices, "host": sorted(
        host, key=lambda e: e[1])}


def clip(trace: dict, start: int, end: int) -> dict:
    """The part of a trace inside [start, end): how a fixture is cut."""
    def inside(evs):
        return [e for e in evs if e[1] >= start and e[1] + e[2] <= end]

    return {"window": [start, end],
            "devices": {k: inside(v) for k, v in trace["devices"].items()},
            "host": inside(trace["host"])}


def busy_intervals(events, start: int, end: int) -> list:
    """Union of the events' intervals, clipped to the window: nested and
    overlapping operations count once."""
    spans = sorted(
        (max(s, start), min(s + d, end)) for _, s, d in events
        if s < end and s + d > start and d > 0
    )
    merged = []
    for lo, hi in spans:
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    return merged


def idle_intervals(busy: list, start: int, end: int) -> list:
    gaps, at = [], start
    for lo, hi in busy:
        if lo > at:
            gaps.append([at, lo])
        at = max(at, hi)
    if end > at:
        gaps.append([at, end])
    return gaps


# Gaps shorter than this lie between two operations of one program: the
# host is not in them, and there are hundreds of thousands.
MIN_LABELLED_GAP_NS = 20_000


def _labeller(host):
    """gap -> the host span that covers most of it ('none' if none does;
    'between_ops' for the short gaps inside a program)."""
    import bisect

    starts = [e[1] for e in host]
    longest = max((e[2] for e in host), default=0)

    def label(gap) -> str:
        if gap[1] - gap[0] < MIN_LABELLED_GAP_NS:
            return "between_ops"
        best, name = 0, "none"
        i = bisect.bisect_left(starts, gap[0] - longest)
        for n, s, d in host[i:]:
            if s >= gap[1]:
                break
            over = min(s + d, gap[1]) - max(s, gap[0])
            if over > best:
                best, name = over, n
        return name

    return label


def kind_of(name: str) -> str:
    """``fusion.1932`` -> ``fusion``: the compiler numbers each instance
    (one a layer); the breakdown adds them up by kind."""
    return INSTANCE.sub("", name)


def op_seconds(trace: dict, pattern: str) -> tuple:
    """(seconds, calls) of the device operations whose name matches,
    averaged over the devices.  Children of a matching operation that match
    too (a fusion inside a loop body) would count twice; patterns name
    leaf kernels."""
    rx = re.compile(pattern)
    start, end = trace["window"]
    total, calls = 0, 0
    for events in trace["devices"].values():
        for n, s, d in events:
            if s >= start and s + d <= end and rx.search(n):
                total += d
                calls += 1
    n_dev = max(len(trace["devices"]), 1)
    return total / 1e9 / n_dev, calls // n_dev


def reduce(trace: dict, top: int = 10) -> dict:
    """Busy and idle seconds (averaged over the devices used), the kinds
    of operation that took most time (self time), and idle time by host
    span."""
    if not trace["devices"] or trace["window"] is None:
        raise ValueError("the trace holds no device operations")
    start, end = trace["window"]
    busy_ns, by_op, by_gap = 0, {}, {}
    label = _labeller(trace["host"])
    for events in trace["devices"].values():
        busy = busy_intervals(events, start, end)
        busy_ns += sum(hi - lo for lo, hi in busy)
        # Self time: an operation's duration minus what its children (the
        # operations that start inside it) cover, so a loop and its body
        # are not both counted whole.
        stack = []
        for n, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
            if s < start or s + d > end:
                continue
            while stack and stack[-1][1] <= s:
                stack.pop()
            n = kind_of(n)
            if stack:
                by_op[stack[-1][0]] -= d
            by_op[n] = by_op.get(n, 0) + d
            stack.append((n, s + d))
        for gap in idle_intervals(busy, start, end):
            name = label(gap)
            by_gap[name] = by_gap.get(name, 0) + gap[1] - gap[0]
    n_dev = len(trace["devices"])

    def ranked(d):
        return [[k, v / 1e9 / n_dev] for k, v in sorted(
            d.items(), key=lambda kv: -kv[1])[:top]]

    return {"busy_s": busy_ns / 1e9 / n_dev,
            "window_s": (end - start) / 1e9,
            "device_ops": ranked(by_op),
            "idle_gaps": ranked(by_gap)}
