"""PR 30's per-layer metric ``decode_attention_share_pct``, on the CPU: the
``op_share`` reader through the parameters its
``layer_metrics/decode_attention_share_pct.json`` gives, on hand-built
traces of both sides of the change and on the recorded trace of a program
from before it (``fixtures/README_spans.md``).  It stands beside
``test_cache_write_share.py``, which a PR that changes the program may not
edit."""

import gzip
import json
import os
import re

import pytest

from benchmark import harness, trace_reduce
from benchmark.readers import op_share

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(ROOT, "benchmark")
METRIC, CELL = "decode_attention_share_pct", "gpt2-large.batch-decode"
US = 1000


def params():
    return harness.load_json(os.path.join(
        BENCH, "layer_metrics", METRIC + ".json")).get("params", {})


def _ctx(trace):
    return {"trace": trace, "trace_reduced": trace_reduce.reduce(trace)}


def test_the_metric_file_agrees_with_its_manifest_entry():
    manifest = os.path.join(ROOT, "BENCHMARK.json")
    per_layer = harness.load_json(manifest)["per_layer"]
    entry = {m["name"]: m for m in per_layer}[METRIC]
    spec = harness.Cell(manifest, CELL).layer_metric(METRIC)
    assert CELL in entry["workloads"]
    assert (entry["moves"], entry["better"]) == ("serve_tokens_per_s", "lower")
    assert (spec["layer"], spec["source"]) == (entry["layer"], entry["source"])
    assert spec["reader"] == "op_share" and spec["what"]
    assert entry["source"] == "device_trace"
    # the layer is one BENCHMARK.json already names, letter for letter
    assert entry["layer"] in {
        m["layer"] for m in per_layer if m["name"] != METRIC}


def test_decode_attention_share_reads_the_fusions_or_the_kernel():
    """The metric on both sides of its change: the parent's attention is two
    ``multiply_reduce_fusion`` a layer, the change's a Pallas call named on
    the call.  Fusions of other kinds, other kernels, the kernel's work list
    and a trace with neither read nothing."""
    others = [["slot_cache_write.5|tpu_custom_call", 0, 20 * US],
              ["convert_reduce_fusion.7", 20 * US, 30 * US],
              ["fusion.12", 50 * US, 10 * US]]
    parent = {"window": [0, 400 * US], "host": [], "devices": {
        "/device:TPU:0": others + [
            ["multiply_reduce_fusion", 60 * US, 70 * US],
            ["multiply_reduce_fusion.71", 130 * US, 50 * US]]}}
    ctx = _ctx(parent)
    assert ctx["trace_reduced"]["busy_s"] == pytest.approx(180e-6)
    assert op_share.read(ctx, **params()) == pytest.approx(100 * 120 / 180)
    change = {**parent, "devices": {"/device:TPU:0": others + [
        ["cumsum_compare_fusion.3", 60 * US, 2 * US],
        ["decode_attention.35|tpu_custom_call", 62 * US, 40 * US],
        ["decode_attention|tpu_custom_call", 102 * US, 18 * US]]}}
    ctx = _ctx(change)
    assert ctx["trace_reduced"]["busy_s"] == pytest.approx(120e-6)
    assert op_share.read(ctx, **params()) == pytest.approx(100 * 58 / 120)
    neither = {**parent, "devices": {"/device:TPU:0": others + [
        ["multiply_reduce_fusion_2", 60 * US, 10 * US],
        ["exponential_multiply_reduce_fusion.4", 70 * US, 10 * US],
        ["paged_attention_decode.1|tpu_custom_call", 80 * US, 10 * US],
        ["decode_attention.2|other_call", 90 * US, 10 * US]]}}
    assert op_share.read(_ctx(neither), **params()) is None
    assert op_share.read({"trace": None, "trace_reduced": None},
                         **params()) is None


def test_decode_attention_share_on_the_recorded_trace():
    """The recorded steps (fixtures/README_spans.md; a program from before
    PR 25, so the write's loops take half its busy time) hold two decode
    steps of 36 layers, two fusions a layer: 144 operations, 17.05 ms, 8.5
    ms a step, 22.4% of the busy 76.08 ms."""
    path = os.path.join(BENCH, "fixtures", "serve_trace_v5e_spans.json.gz")
    with gzip.open(path, "rt") as fp:
        trace = json.load(fp)["trace"]
    pattern = params()["pattern"]
    seconds, calls = trace_reduce.op_seconds(trace, pattern)
    assert calls == 144
    assert seconds == pytest.approx(17.05e-3, rel=1e-3)
    (events,) = trace["devices"].values()
    names = {n for n, _, _ in events if re.search(pattern, n)}
    assert all(n.startswith("multiply_reduce_fusion") for n in names)
    ctx = _ctx(trace)
    assert op_share.read(ctx, **params()) == pytest.approx(
        100 * 17.05 / 76.08, abs=0.05)
