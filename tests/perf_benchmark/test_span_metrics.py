"""The readers of PR 24's per-layer metrics, on the CPU and free of any
clock: device idle cut at the host spans' edges (``idle_by_span``), a
statistic of the program's span ring over a window (``span_stat``), a
kernel's share of the busy time (``op_share``), each through the parameters
its ``layer_metrics/<name>.json`` gives, on hand-built traces and on a piece
of a real trace of the chip (``fixtures/README_spans.md``)."""

import collections
import gzip
import json
import os
import time

import pytest

from benchmark import harness, trace_reduce
from benchmark.readers import idle_by_span, op_share, span_stat

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(ROOT, "benchmark")
SERVE, TRAIN = "gpt2-large.batch-decode", "gpt2-124m.pretrain-1k"
NEW = {  # metric -> (cell, reader)
    "idle_dispatch_pct": (SERVE, "idle_by_span"),
    "idle_fence_pct": (SERVE, "idle_by_span"),
    "idle_admit_pct": (SERVE, "idle_by_span"),
    "idle_host_pct": (SERVE, "idle_by_span"),
    "decode_dispatch_ms": (SERVE, "span_stat"),
    "decode_fence_ms": (SERVE, "span_stat"),
    "decode_host_ms": (SERVE, "span_stat"),
    "admit_stall_ms": (SERVE, "span_stat"),
    "train_dispatch_ms": (TRAIN, "span_stat"),
    "flash_fwd_share_pct": (TRAIN, "op_share"),
    "flash_dq_share_pct": (TRAIN, "op_share"),
    "flash_dkv_share_pct": (TRAIN, "op_share"),
}
IDLE_PARTS = [n for n, (_, r) in NEW.items() if r == "idle_by_span"]
US = 1000


def params(metric):
    return harness.load_json(os.path.join(
        BENCH, "layer_metrics", metric + ".json")).get("params", {})


@pytest.mark.parametrize("metric", sorted(NEW))
def test_each_new_metric_file_agrees_with_its_manifest_entry(metric):
    cell, reader = NEW[metric]
    entry = {m["name"]: m for m in harness.load_json(
        os.path.join(ROOT, "BENCHMARK.json"))["per_layer"]}[metric]
    spec = harness.Cell(os.path.join(ROOT, "BENCHMARK.json"),
                        cell).layer_metric(metric)
    assert entry["workloads"] == [cell]
    assert (spec["layer"], spec["source"]) == (entry["layer"], entry["source"])
    assert spec["reader"] == reader and spec["what"]
    assert entry["source"] == {"idle_by_span": "device_trace",
                               "op_share": "device_trace",
                               "span_stat": "program_span"}[reader]


# --------------------------------------------------------------- idle_by_span
def _serving_trace():
    """Two devices.  The first idles four times at length: under dispatch
    and fence (nested in ``serve_decode``); through deliver, prepare,
    dispatch and fence in one gap; under an admission; under no span at all;
    and once for 10 us, which no host code can have caused.  The second
    idles once."""
    def busy(*intervals):
        return [["fusion.1", lo * US, (hi - lo) * US] for lo, hi in intervals]

    return {
        "window": [0, 1300 * US],
        "devices": {
            "/device:TPU:0": busy((0, 100), (200, 300), (310, 400),
                                  (700, 900), (1000, 1200), (1260, 1300)),
            "/device:TPU:1": busy((0, 100), (200, 1300)),
        },
        "host": sorted([
            ["serve_decode", 90 * US, 170 * US],
            ["serve_decode.dispatch", 100 * US, 50 * US],
            ["serve_decode.fence", 150 * US, 105 * US],
            ["serve_deliver", 380 * US, 70 * US],
            ["serve_prepare", 450 * US, 50 * US],
            ["serve_decode", 500 * US, 300 * US],
            ["serve_decode.dispatch", 500 * US, 120 * US],
            ["serve_decode.fence", 620 * US, 170 * US],
            ["serve_admit", 880 * US, 130 * US],
            ["serve_prefill", 890 * US, 30 * US],
            ["serve_prefill.fence", 920 * US, 85 * US],
        ], key=lambda e: e[1]),
    }


def _ctx(trace):
    return {"trace": trace, "trace_reduced": trace_reduce.reduce(trace)}


@pytest.mark.parametrize("metric,first,second", [
    ("idle_dispatch_pct", 50 + 120, 50),
    ("idle_fence_pct", 50 + 80, 50),
    ("idle_admit_pct", 100, 0),
    ("idle_host_pct", 50 + 50 + 60, 0),
])
def test_idle_is_cut_at_the_spans_edges(metric, first, second):
    ctx = _ctx(_serving_trace())
    got = idle_by_span.read(ctx, **params(metric))
    assert got == pytest.approx(100.0 * (first + second) / (2 * 1300))
    assert "labelled_gaps" in ctx                  # computed once, kept


def test_the_four_idle_parts_add_up_to_the_labelled_idle():
    ctx = _ctx(_serving_trace())
    parts = [idle_by_span.read(ctx, **params(m)) for m in IDLE_PARTS]
    gaps = dict(ctx["trace_reduced"]["idle_gaps"])
    assert gaps.pop("between_ops") == pytest.approx(10e-6 / 2)
    labelled = sum(gaps.values()) / ctx["trace_reduced"]["window_s"]
    assert sum(parts) == pytest.approx(100.0 * labelled)
    assert labelled == pytest.approx((560 + 100) / (2 * 1300))


def test_idle_by_span_reads_nothing_from_an_older_program_or_no_device():
    trace = _serving_trace()
    older = {**trace, "host": [e for e in trace["host"] if "." not in e[0]
                               and e[0] in ("serve_decode", "serve_prefill")]}
    for metric in IDLE_PARTS:
        assert idle_by_span.read(_ctx(older), **params(metric)) is None
        assert idle_by_span.read({"trace": trace}, **params(metric)) is None
    # the spans are there and no admission fell into the slice: 0, not None
    quiet = {**trace, "host": [e for e in trace["host"]
                               if not e[0].startswith(("serve_admit",
                                                       "serve_prefill"))]}
    ctx = _ctx(quiet)
    assert idle_by_span.read(ctx, **params("idle_admit_pct")) == 0.0
    assert idle_by_span.read(ctx, **params("idle_host_pct")) == pytest.approx(
        100.0 * (160 + 100) / (2 * 1300))
    with pytest.raises(ValueError):
        idle_by_span.read(ctx)


# ------------------------------------------------------------------ span_stat
@pytest.fixture
def ring(monkeypatch):
    """A ring of the program's own, small enough to wrap, and a window of
    ten seconds on the monotonic clock with spans laid out in it."""
    from ml_trainer_tpu.telemetry import spans

    monkeypatch.setattr(spans, "_events", collections.deque(maxlen=64))
    t0 = time.monotonic() - 100.0

    def add(name, start, dur):
        spans.complete_event(name, t0 + start, t0 + start + dur)

    add("serve_decode.fence", -0.5, 0.030)         # before the window
    for i, (dispatch, fence) in enumerate([(0.004, 0.036), (0.006, 0.034),
                                           (0.005, 0.035), (0.025, 0.015)]):
        add("serve_prepare", i, 0.001)
        add("serve_decode", i + 0.001, dispatch + fence)
        add("serve_decode.dispatch", i + 0.001, dispatch)
        add("serve_decode.fence", i + 0.001 + dispatch, fence)
        add("serve_deliver", i + 0.05, 0.002)
    add("serve_admit", 4.0, 0.014)
    add("serve_admit", 5.0, 0.018)
    add("train_step_dispatch", 6.0, 0.002)
    add("train_step_dispatch", 9.5, 30.0)          # ends past the window
    return {"window": (t0, t0 + 10.0), "trace_reduced": {"busy_s": 1.0}}


@pytest.mark.parametrize("metric,want", [
    ("decode_dispatch_ms", (4 + 6 + 5 + 25) / 4),
    ("decode_fence_ms", (36 + 34 + 35 + 15) / 4),
    ("decode_host_ms", 4 * (1 + 2) / 4),
    ("admit_stall_ms", 16.0),
    ("train_dispatch_ms", 2.0),                    # the cut span left out
])
def test_span_stat_reads_the_ring_over_the_window(ring, metric, want):
    assert span_stat.read(ring, **params(metric)) == pytest.approx(want)


def test_span_stat_percentile_edges_and_what_gives_nothing(ring):
    from ml_trainer_tpu.telemetry import spans

    p95 = span_stat.read(ring, names=["serve_decode.dispatch"], stat="p95",
                         scale=1000.0)
    assert p95 == pytest.approx(25.0)
    t0, t1 = ring["window"]
    late = {**ring, "window": (t0 + 2.5, t1)}      # the window's edges count
    assert span_stat.read(late, **params("decode_dispatch_ms")) == (
        pytest.approx(25.0))
    assert span_stat.read(ring, names=["no_such_span"]) is None
    assert span_stat.read(ring, names=["serve_prepare"], per="nothing") is None
    with pytest.raises(ValueError):
        span_stat.read(ring, names=["serve_admit"], stat="median")
    assert span_stat.read({"window": ring["window"]},
                          **params("admit_stall_ms")) is None  # no device
    for i in range(64):                            # the ring wraps
        spans.complete_event("filler", t0 + 9.0, t0 + 9.001)
    assert spans.events_between(t0, t1)[1] is True
    assert span_stat.read(ring, **params("admit_stall_ms")) is None


# ------------------------------------------------------------------- op_share
def test_op_share_tells_the_three_flash_kernels_apart():
    def op(name, start, dur):
        return [name + "|tpu_custom_call" if "flash" in name else name,
                start * US, dur * US]

    trace = {"window": [0, 1000 * US], "host": [], "devices": {
        "/device:TPU:0": [op("flash_fwd.1", 0, 100), op("fusion.7", 100, 300),
                          op("flash_bwd_dq.1", 400, 150),
                          op("flash_bwd_dkv.1", 550, 250),
                          op("flash_fwd.2", 800, 100)]}}
    ctx = _ctx(trace)
    assert ctx["trace_reduced"]["busy_s"] == pytest.approx(900e-6)
    got = {m: op_share.read(ctx, **params(m))
           for m in NEW if NEW[m][1] == "op_share"}
    assert got == {"flash_fwd_share_pct": pytest.approx(100 * 200 / 900),
                   "flash_dq_share_pct": pytest.approx(100 * 150 / 900),
                   "flash_dkv_share_pct": pytest.approx(100 * 250 / 900)}
    roofline = harness.load_json(os.path.join(
        BENCH, "layer_metrics", "flash_train_roofline.json"))["params"]
    assert trace_reduce.op_seconds(trace, roofline["pattern"]) == (
        pytest.approx(600e-6), 4)                  # the old pattern: all four
    unnamed = {**trace, "devices": {"/device:TPU:0": [
        ["attn.3|tpu_custom_call", 0, 100 * US]]}}
    assert op_share.read(_ctx(unnamed), **params("flash_fwd_share_pct")) is None
    assert op_share.read({"trace": trace}, **params("flash_fwd_share_pct")) is None


# ---------------------------------------------------- the recorded trace
def test_idle_by_span_on_the_recorded_trace_with_the_phase_spans():
    """A piece of a real trace of the chip with the spans PR 24 added
    (fixtures/README_spans.md): one gap runs from a deliver through an
    admission into the next dispatch.  The four parts add up to the slice's
    labelled idle, and each reads what it read when the piece was cut."""
    path = os.path.join(BENCH, "fixtures", "serve_trace_v5e_spans.json.gz")
    with gzip.open(path, "rt") as fp:
        fixture = json.load(fp)
    trace, want = fixture["trace"], fixture["expect"]
    ctx = _ctx(trace)
    red = ctx["trace_reduced"]
    assert red["busy_s"] == pytest.approx(want["busy_s"], rel=1e-6)
    gaps = dict(red["idle_gaps"])
    assert gaps.pop("between_ops") == pytest.approx(want["between_ops_s"])
    assert sorted(gaps) == want["old_labels"] == ["serve_admit",
                                                  "serve_decode"]
    labelled = sum(gaps.values())
    assert labelled == pytest.approx(want["labelled_idle_s"], rel=1e-6)
    parts = {m: idle_by_span.read(ctx, **params(m)) for m in IDLE_PARTS}
    assert parts == {m: pytest.approx(v, rel=1e-6)
                     for m, v in want["parts_pct"].items()}
    assert sum(parts.values()) == pytest.approx(
        100.0 * labelled / red["window_s"], rel=1e-9)
    assert all(v > 0 for v in parts.values())
    # the admission's idle is no longer handed whole to one span
    assert parts["idle_admit_pct"] < 100.0 * gaps["serve_admit"] / red["window_s"]
