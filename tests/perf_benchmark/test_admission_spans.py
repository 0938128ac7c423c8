"""The readers of PR 36's admission metrics, on the CPU: the device's idle
under the program's admission spans after the host spans are put on the
device's clock by the bracket the admissions give (``readers/admission.py``),
and the span ring's cost of an admission and admissions a turn
(``readers/admission_ring.py``), each through the parameters its
``layer_metrics/<name>.json`` gives, on hand-built traces with a planted
offset between the two clocks."""

import collections
import gzip
import json
import os
import time

import pytest

from benchmark import harness, trace_reduce
from benchmark.readers import (
    admission,
    admission_ring,
    device_idle,
    idle_by_span,
)
from ml_trainer_tpu.serving import engine

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")
THREE = ["gpt2-large.batch-decode", "k-exaone-236b-ep8.sharegpt-2k",
         "kimi-linear-48b-ep8.reason-4k"]
FOUR = THREE + ["brumby-14b-l8.reason-4k"]
NEW = {  # metric -> (reader, cells, source)
    "idle_admit_turn_pct": ("admission", THREE, "device_trace"),
    "idle_land_pct": ("admission", THREE, "device_trace"),
    "idle_restart_pct": ("admission", THREE, "device_trace"),
    "admit_device_ms": ("admission", THREE, "device_trace"),
    "clock_bracket_us": ("admission", THREE, "device_trace"),
    "admit_cost_ms": ("admission_ring", FOUR, "program_span"),
    "admits_per_turn": ("admission_ring", FOUR, "program_counter"),
}
US = 1000
WINDOW_US = 40_000
ADMIT_PHASE = {"phase": "admit"}                   # the identity's third term


def params(metric):
    return harness.Cell(MANIFEST, NEW[metric][1][0]).layer_metric(
        metric).get("params", {})


@pytest.mark.parametrize("metric", sorted(NEW))
def test_each_new_metric_file_agrees_with_its_manifest_entry(metric):
    reader, cells, source = NEW[metric]
    entry = {m["name"]: m for m in harness.load_json(MANIFEST)[
        "per_layer"]}[metric]
    spec = harness.Cell(MANIFEST, cells[0]).layer_metric(metric)
    assert set(cells) <= set(entry["workloads"])
    assert entry["moves"] == "serve_tokens_per_s"
    assert (spec["layer"], spec["source"]) == (entry["layer"], source)
    assert spec["reader"] == reader and spec["what"]


# ------------------------------------------------------- the planted trace
def _turn_trace(offset_us):
    """One device, 40 ms on its clock.  Steady steps, one 100 us gap under a
    steady ``serve_deliver``; then an admission turn of two admissions: the
    landing, whose fence returns 60 us after the step in flight ends (idle
    1,000 us); two prefills whose busy runs start 150 and 100 us after their
    ``serve_prefill`` and end 150 and 100 us before their fence returns (a
    10 us gap inside the first, between two of its operations); and the
    restart, whose step starts 60 us into its dispatch (idle 2,000 us) and
    runs until 100 us before the slice ends.  The host's spans are stamped
    on a clock ``offset_us`` behind the device's: device time = host time +
    offset."""
    def busy(*intervals):
        return [["fusion.1", lo * US, (hi - lo) * US] for lo, hi in intervals]

    host = [
        ("serve_deliver", 4990, 5200),
        ("serve_land", 9900, 11000),
        ("serve_decode", 9900, 10060),
        ("serve_decode.fence", 9900, 10060),
        ("serve_deliver", 10070, 11000),
        ("serve_admit", 11000, 19000),
        ("serve_prefill", 11300, 11800),
        ("serve_prefill.fence", 11800, 17600),
        ("serve_admit.emit", 17600, 18800),
        ("serve_admit", 19000, 25000),
        ("serve_prefill", 19200, 19500),
        ("serve_prefill.fence", 19500, 24200),
        ("serve_admit.emit", 24200, 24800),
        ("serve_restart", 25000, 28000),
        ("serve_prepare", 25000, 26900),
        ("serve_decode", 26900, 28000),
        ("serve_decode.dispatch", 26940, 28000),
    ]
    return {
        "window": [0, WINDOW_US * US],
        "devices": {"/device:TPU:0": busy(
            (0, 5000), (5100, 10000), (11450, 14000), (14010, 17450),
            (19300, 24100), (27000, WINDOW_US - 100))},
        "host": sorted([[n, (lo - offset_us) * US, (hi - lo) * US]
                        for n, lo, hi in host], key=lambda e: e[1]),
    }


PLANTED_US = {"land": 1000, "admit": 450 + 1550 + 300 + 900,
              "restart": 2000}


def _ctx(trace):
    return {"trace": trace, "trace_reduced": trace_reduce.reduce(trace)}


@pytest.mark.parametrize("offset_us", [1500, -1500, 0])
def test_the_bracket_holds_the_planted_offset_and_the_split_is_exact(
        offset_us):
    ctx = _ctx(_turn_trace(offset_us))
    marks = admission.marks(ctx["trace"]["host"], engine.ADMISSION_SPANS)
    assert [m[2] for m in marks] == ["lower", "both", "both", "upper"]
    start, end = ctx["trace"]["window"]
    runs = [admission.busy_runs(g, start, end)
            for _, g in admission.device_intervals(ctx)]
    # the prefills alone, matched on the host's own stamps, bracket the
    # offset to 100 us each way; at their midpoint the landing's fence and
    # the restart's dispatch find their runs and narrow it to 60
    both = [m for m in marks if m[2] == "both"]
    assert admission.bracket(both, runs, 0) == (
        (offset_us - 100) * US, (offset_us + 100) * US)
    assert admission.bracket(marks, runs, offset_us * US) == (
        (offset_us - 60) * US, (offset_us + 60) * US)
    assert admission.read(ctx, **params("clock_bracket_us")) == 120.0
    window_ns = WINDOW_US * US

    def ns(pct):
        return pct * window_ns / 100.0

    got = {p: admission.read(ctx, phase=p) for p in PLANTED_US}
    for p, want in PLANTED_US.items():
        assert abs(ns(got[p]) - want * US) < 1.0, p   # to the nanosecond
    assert ns(admission.read(ctx, **params("idle_land_pct"))) == (
        pytest.approx(1000 * US, abs=1.0))
    assert ns(admission.read(ctx, **params("idle_restart_pct"))) == (
        pytest.approx(2000 * US, abs=1.0))
    # busy inside serve_admit: 6,000 us less the 10 us gap, and 4,800 us
    assert admission.read(ctx, **params("admit_device_ms")) == (
        pytest.approx((5990 + 4800) / 2 / 1000))


@pytest.mark.parametrize("offset_us", [1500, -1500])
def test_without_the_shift_the_split_would_be_wrong(offset_us):
    """What ``idle_by_span`` reads from the same trace, on the host's own
    stamps: the same labelled idle, handed to the wrong phases."""
    ctx = _ctx(_turn_trace(offset_us))
    unshifted = {p: idle_by_span.read(ctx, spans=["serve_" + p])
                 for p in PLANTED_US}
    shifted = {p: admission.read(ctx, phase=p) for p in PLANTED_US}
    assert unshifted != pytest.approx(shifted, abs=0.1)


@pytest.mark.parametrize("offset_us", [1500, -1500, 0])
def test_the_phases_add_up_to_the_turn_and_stay_under_the_idle(offset_us):
    ctx = _ctx(_turn_trace(offset_us))
    turn = admission.read(ctx, **params("idle_admit_turn_pct"))
    parts = (admission.read(ctx, **params("idle_land_pct"))
             + admission.read(ctx, **ADMIT_PHASE)
             + admission.read(ctx, **params("idle_restart_pct")))
    assert turn == pytest.approx(parts, abs=1e-9)
    assert turn == pytest.approx(100.0 * 6200 / WINDOW_US)
    labelled = 100.0 * (6200 + 200) / WINDOW_US    # and the other gaps
    assert turn < labelled <= device_idle.read(ctx)


def test_nothing_from_an_older_program_and_zero_from_a_quiet_slice(
        monkeypatch):
    trace = _turn_trace(1500)
    quiet = {**trace, "host": [
        e for e in trace["host"] if e[0] not in engine.ADMISSION_SPANS
        and not e[0].startswith(("serve_prefill", "serve_admit"))]}
    ctx = _ctx(quiet)
    for m in ("idle_admit_turn_pct", "idle_land_pct", "idle_restart_pct"):
        assert admission.read(ctx, **params(m)) == 0.0
    assert admission.read(ctx, **ADMIT_PHASE) == 0.0
    assert admission.read(ctx, **params("admit_device_ms")) is None
    assert admission.read(ctx, **params("clock_bracket_us")) is None
    device_metrics = [m for m in NEW if NEW[m][0] == "admission"]
    for m in device_metrics:                       # no device trace
        assert admission.read({"trace": trace}, **params(m)) is None
    with pytest.raises(ValueError):
        admission.read(_ctx(trace))
    monkeypatch.delattr(engine, "ADMISSION_SPANS")
    for m in device_metrics:
        assert admission.read(_ctx(trace), **params(m)) is None


def test_contradicting_bounds_give_a_negative_width():
    """A run longer than its host interval allows (here the second
    prefill's run ends 200 us after its fence returned on the device's
    clock): the width is reported as it is."""
    trace = _turn_trace(0)
    ops = trace["devices"]["/device:TPU:0"]
    ops[4] = ["fusion.1", 19300 * US, (24400 - 19300) * US]
    width = admission.read(_ctx(trace), **params("clock_bracket_us"))
    assert width == pytest.approx(60 - 200)


# ------------------------------------------------------------- the ring
@pytest.fixture
def ring(monkeypatch):
    """The program's ring, small enough to wrap, with two admission turns
    in a ten-second window on the monotonic clock."""
    from ml_trainer_tpu.telemetry import spans

    monkeypatch.setattr(spans, "_events", collections.deque(maxlen=32))
    t0 = time.monotonic() - 100.0

    def add(name, start, dur, **args):
        spans.complete_event(name, t0 + start, t0 + start + dur, **args)

    add("serve_restart", -1.0, 0.003, admitted=5)  # before the window
    add("serve_land", 1.0, 0.004, freed=1)
    add("serve_admit", 1.004, 0.010)
    add("serve_restart", 1.014, 0.003, admitted=1)
    add("serve_land", 4.0, 0.005, freed=2)
    add("serve_admit", 4.005, 0.012)
    add("serve_admit", 4.017, 0.014)
    add("serve_restart", 4.031, 0.002, admitted=2)
    add("serve_decode", 5.0, 0.030)                # no admission span
    add("serve_land", 9.999, 0.004, freed=0)       # ends past the window
    return {"window": (t0, t0 + 10.0), "trace_reduced": {"busy_s": 1.0}}


def test_the_ring_reads_an_admissions_cost_and_the_admissions_a_turn(ring):
    cost = admission_ring.read(ring, **params("admit_cost_ms"))
    assert cost == pytest.approx((4 + 10 + 3 + 5 + 12 + 14 + 2) / 3)
    per_turn = admission_ring.read(ring, **params("admits_per_turn"))
    assert per_turn == pytest.approx((1 + 2) / 2)
    with pytest.raises(ValueError):
        admission_ring.read(ring, quantity="median")


def test_the_ring_reads_nothing_where_it_cannot(ring, monkeypatch):
    from ml_trainer_tpu.telemetry import spans

    t0, t1 = ring["window"]
    for m in ("admit_cost_ms", "admits_per_turn"):
        assert admission_ring.read(                # no device: no number
            {"window": ring["window"]}, **params(m)) is None
        empty = {**ring, "window": (t0 + 6.0, t0 + 9.0)}
        assert admission_ring.read(empty, **params(m)) is None
    for _ in range(32):                            # the ring wraps
        spans.complete_event("filler", t0 + 9.0, t0 + 9.001)
    assert admission_ring.read(ring, **params("admit_cost_ms")) is None
    monkeypatch.delattr(engine, "ADMISSION_SPANS")
    assert admission_ring.read(ring, **params("admits_per_turn")) is None


# ----------------------------------------------------- the CPU rehearsals
SERVING_REHEARSALS = ["tiny.closed", "tiny.open", "tiny.other-closed",
                      "tiny.exaone-closed", "tiny.kimi-closed",
                      "tiny.brumby-closed"]


@pytest.mark.parametrize("name", SERVING_REHEARSALS)
def test_a_cpu_rehearsal_records_the_spans_and_prints_none_of_them(
        tiny, run_tiny, name):
    """A traced run on the CPU goes through admission turns, and its line
    holds none of the new metrics: the device ones have no device to read,
    the ring's no device whose pace they would follow."""
    from ml_trainer_tpu.telemetry import spans

    spans.clear_trace()
    line = run_tiny(tiny, name, seed=2**31 + 36, trace=True)
    assert line["correct"] is True
    assert not set(NEW) & set(line["metrics"])
    names = {e["name"]: e for e in spans.trace_events()
             if e["name"] in engine.ADMISSION_SPANS}
    assert set(names) == set(engine.ADMISSION_SPANS)
    assert names["serve_restart"]["args"]["admitted"] >= 1


# ---------------------------------------------------- the recorded trace
def test_the_recorded_admission_turn_reads_what_the_chip_run_printed():
    """A piece of a traced run of this tree on the chip
    (``fixtures/README_admission.md``): the bracket, the phase split and
    the device time an admission read what the run printed when it cut the
    piece; the phases add up to the turn, which stays under the idle."""
    path = os.path.join(ROOT, "benchmark", "fixtures",
                        "serve_trace_v5e_admission.json.gz")
    with gzip.open(path, "rt") as fp:
        fixture = json.load(fp)
    trace, want = fixture["trace"], fixture["expect"]
    ctx = _ctx(trace)
    assert ctx["trace_reduced"]["busy_s"] == pytest.approx(
        want["busy_s"], rel=1e-9)
    assert set(engine.ADMISSION_SPANS) <= {e[0] for e in trace["host"]}
    got = {f"idle_{p}_pct": admission.read(ctx, phase=p)
           for p in ("turn", "land", "admit", "restart")}
    got["bracket_us"] = admission.read(ctx, **params("clock_bracket_us"))
    got["device_ms"] = admission.read(ctx, **params("admit_device_ms"))
    assert got == {k: pytest.approx(want[k], rel=1e-9) for k in got}
    assert got["idle_turn_pct"] == pytest.approx(
        got["idle_land_pct"] + got["idle_admit_pct"]
        + got["idle_restart_pct"], abs=1e-9)
    assert 0 < got["idle_turn_pct"] <= device_idle.read(ctx)
    assert got["bracket_us"] > 0
