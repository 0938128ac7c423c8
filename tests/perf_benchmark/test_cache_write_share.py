"""PR 25's per-layer metric ``cache_write_share_pct``, on the CPU: the
``op_share`` reader through the parameters its
``layer_metrics/cache_write_share_pct.json`` gives, on hand-built traces of
both sides of the change and on the recorded trace of the parent
(``fixtures/README_spans.md``).  It stands beside ``test_span_metrics.py``,
which a PR that changes the program may not edit."""

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
METRIC, CELL = "cache_write_share_pct", "gpt2-large.batch-decode"
US = 1000


def params():
    return harness.load_json(os.path.join(
        BENCH, "layer_metrics", METRIC + ".json")).get("params", {})


def _ctx(trace):
    return {"trace": trace, "trace_reduced": trace_reduce.reduce(trace)}


def test_the_metric_file_agrees_with_its_manifest_entry():
    manifest = os.path.join(ROOT, "BENCHMARK.json")
    entry = {m["name"]: m
             for m in harness.load_json(manifest)["per_layer"]}[METRIC]
    spec = harness.Cell(manifest, CELL).layer_metric(METRIC)
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "serve_tokens_per_s"
    assert (spec["layer"], spec["source"]) == (entry["layer"], entry["source"])
    assert spec["reader"] == "op_share" and spec["what"]
    assert entry["source"] == "device_trace"


def test_cache_write_share_reads_the_loops_or_the_kernel_and_no_child():
    """The metric on both sides of its change: the parent's scatter is a
    ``while`` a layer's K or V whose body's operations start inside it and
    must not count again; the change's write is a Pallas call named on the
    call.  Loops of other names, and a trace with neither, read nothing."""
    def loop(at, n):
        body = [[f"dynamic-update-slice.{n}", (at + 1 + 8 * i) * US, 5 * US]
                for i in range(4)]
        return [[f"while.{n}", at * US, 40 * US]] + body

    parent = {"window": [0, 200 * US], "host": [], "devices": {
        "/device:TPU:0": loop(0, 72) + loop(40, 73) + [
            ["multiply_reduce_fusion.3", 80 * US, 80 * US]]}}
    ctx = _ctx(parent)
    assert ctx["trace_reduced"]["busy_s"] == pytest.approx(160e-6)
    assert op_share.read(ctx, **params()) == pytest.approx(100 * 80 / 160)
    change = {**parent, "devices": {"/device:TPU:0": [
        ["slot_cache_write.5|tpu_custom_call", 0, 10 * US],
        ["slot_cache_write|tpu_custom_call", 10 * US, 10 * US],
        ["multiply_reduce_fusion.3", 20 * US, 80 * US]]}}
    assert op_share.read(_ctx(change), **params()) == \
        pytest.approx(100 * 20 / 100)
    neither = {**parent, "devices": {"/device:TPU:0": [
        ["while_body_fusion.2", 0, 10 * US], ["awhile.1", 10 * US, 10 * US],
        ["paged_attention_decode.1|tpu_custom_call", 20 * US, 10 * US]]}}
    assert op_share.read(_ctx(neither), **params()) is None


def test_cache_write_share_on_the_recorded_trace():
    """The recorded steps of the parent (fixtures/README_spans.md) hold two
    decode steps of 72 loops: 144 operations, 39.14 ms, 51.4% of the busy
    76.08 ms.  Their bodies (4,608 ``dynamic-update-slice`` and as many of
    three other kinds) lie inside them and are not counted."""
    path = os.path.join(BENCH, "fixtures", "serve_trace_v5e_spans.json.gz")
    with gzip.open(path, "rt") as fp:
        trace = json.load(fp)["trace"]
    pattern = params()["pattern"]
    seconds, calls = trace_reduce.op_seconds(trace, pattern)
    assert calls == 144
    assert seconds == pytest.approx(39.14e-3, rel=1e-3)
    (events,) = trace["devices"].values()
    names = {n for n, _, _ in events if re.search(pattern, n)}
    assert {"while.72", "while.73"} <= names
    assert all(n.startswith("while") for n in names)
    ctx = _ctx(trace)
    assert ctx["trace_reduced"]["busy_s"] == pytest.approx(76.08e-3, rel=1e-3)
    assert op_share.read(ctx, **params()) == pytest.approx(51.4, abs=0.05)
    # every loop's body starts inside a loop: counting it would pass 100%
    inside = sum(d for n, _, d in events
                 if n.startswith("dynamic-update-slice."))
    assert inside / 1e9 < seconds < ctx["trace_reduced"]["busy_s"]
