"""The per-layer metric ``latent_decode_attention_share_pct``, on the CPU: the
``op_share`` reader through the parameters its
``layer_metrics/latent_decode_attention_share_pct.json`` gives, on
hand-built traces of both sides of the change (the parent reads the latent
pools inside XLA's ``fusion``, so the metric is silent there), and what the
manifest says of it.  The metric names its cell with ``in``, so a later cell
can be appended to its list."""

import json
import os
import re

import pytest

from benchmark import harness, trace_reduce
from benchmark.readers import op_share

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(ROOT, "benchmark")
METRIC, CELL = "latent_decode_attention_share_pct", "kimi-linear-48b-ep8.reason-4k"
LAYER = "latent cache (models/kimi_linear.py)"
US = 1000


def spec(name=METRIC):
    return harness.load_json(os.path.join(
        BENCH, "layer_metrics", name + ".json"))


def _ctx(trace):
    return {"trace": trace, "trace_reduced": trace_reduce.reduce(trace)}


def test_the_metric_file_agrees_with_its_manifest_entry():
    manifest = os.path.join(ROOT, "BENCHMARK.json")
    b = harness.load_json(manifest)
    entry = {m["name"]: m for m in b["per_layer"]}[METRIC]
    assert CELL in entry["workloads"]
    assert CELL in {w["name"] for w in b["workloads"]}
    assert (entry["moves"], entry["better"], entry["unit"]) == (
        "serve_tokens_per_s", "lower", "%")
    assert CELL in {m["name"]: m for m in b["end_to_end"]}[
        entry["moves"]]["workloads"]
    assert (entry["layer"], entry["source"]) == (LAYER, "device_trace")
    layer_metric = harness.Cell(manifest, CELL).layer_metric(METRIC)
    assert (layer_metric["layer"], layer_metric["source"]) == (
        entry["layer"], entry["source"])
    assert layer_metric["reader"] == "op_share" and layer_metric["what"]
    # a device metric: no CPU rehearsal of the cell may print it
    with open(os.path.join(HERE, "tiny", "cells", CELL + ".json")) as fp:
        assert METRIC not in json.load(fp)["cpu_layer_metrics"]


def test_it_reads_the_kernel_and_nothing_else():
    """The change's trace: the kernel's calls, named on the call, over the
    busy time.  The parent's (XLA's two passes inside ``fusion``), the
    other decode kernels, the write of the latent row and a trace with no
    kernel read nothing."""
    others = [["slot_cache_write.5|tpu_custom_call", 0, 10 * US],
              ["multiply_add_fusion.7", 10 * US, 40 * US],
              ["fusion.12", 50 * US, 60 * US]]
    change = {"window": [0, 400 * US], "host": [], "devices": {
        "/device:TPU:0": others + [
            ["compare_fusion.3", 110 * US, 2 * US],
            ["latent_decode_attention.2|tpu_custom_call", 112 * US, 30 * US],
            ["latent_decode_attention|tpu_custom_call", 142 * US, 18 * US]]}}
    ctx = _ctx(change)
    assert ctx["trace_reduced"]["busy_s"] == pytest.approx(160e-6)
    assert op_share.read(ctx, **spec()["params"]) == pytest.approx(
        100 * 48 / 160)
    parent = {**change, "devices": {"/device:TPU:0": others + [
        ["fusion.40", 110 * US, 50 * US],
        ["decode_attention.1|tpu_custom_call", 160 * US, 5 * US],
        ["decode_attention_append|tpu_custom_call", 165 * US, 5 * US],
        ["latent_decode_attention.2|other_call", 170 * US, 5 * US]]}}
    assert op_share.read(_ctx(parent), **spec()["params"]) is None
    assert op_share.read({"trace": None, "trace_reduced": None},
                         **spec()["params"]) is None


def test_the_two_decode_attention_shares_count_apart():
    """``decode_attention_share_pct`` (the slot cache's kernel) does not
    count the latent kernel, nor this metric that one: a kernel's time is
    in one share."""
    latent = re.compile(spec()["params"]["pattern"])
    slot = re.compile(spec("decode_attention_share_pct")["params"]["pattern"])
    for name in ("latent_decode_attention|tpu_custom_call",
                 "latent_decode_attention.3|tpu_custom_call"):
        assert latent.search(name) and not slot.search(name)
    for name in ("decode_attention|tpu_custom_call",
                 "decode_attention_append.4|tpu_custom_call"):
        assert slot.search(name) and not latent.search(name)
