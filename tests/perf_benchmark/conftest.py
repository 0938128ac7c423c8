"""What the benchmark's test files share: the tiny cells, read from data.

``tiny/cells/*.json`` holds one file for each CPU rehearsal of a root cell:
``rehearses`` names the cell of ``BENCHMARK.json`` whose metrics it reports,
``cell``, ``config`` and ``traffic`` the tiny preset under ``tiny/``,
``cpu_layer_metrics`` the per-layer metrics a traced run on the CPU can read
(the others need a device), ``also`` further tiny cells that differ from it
only in the keys they give.  A new root cell, or a new architecture's
rehearsal, is one more file; nothing here lists them.
"""

import glob
import io
import json
import os
import time

import pytest

from benchmark import harness

HERE = os.path.dirname(os.path.abspath(__file__))
MANIFEST = os.path.join(os.path.dirname(os.path.dirname(HERE)),
                        "BENCHMARK.json")


def load_tiny_cells() -> dict:
    """Tiny cell name -> its entry, ``also`` cells flattened in."""
    cells = {}
    for path in sorted(glob.glob(os.path.join(HERE, "tiny", "cells",
                                              "*.json"))):
        with open(path) as fp:
            main = json.load(fp)
        for entry in [main] + [{**main, **a} for a in main.get("also", [])]:
            entry.pop("also", None)
            assert entry["cell"] not in cells, (path, entry["cell"])
            cells[entry["cell"]] = entry
    return cells


TINY_CELLS = load_tiny_cells()


def pytest_generate_tests(metafunc):
    """``tiny_case``: every tiny cell, untraced and traced."""
    if "tiny_case" in metafunc.fixturenames:
        cases = [(name, trace) for name in TINY_CELLS
                 for trace in (False, True)]
        metafunc.parametrize(
            "tiny_case", cases,
            ids=[f"{n}-{'traced' if t else 'untraced'}" for n, t in cases])


@pytest.fixture(scope="session")
def tiny(tmp_path_factory):
    """The root manifest with the tiny presets in the cells' places: each
    metric lists the tiny cells that rehearse the root cells it lists."""
    with open(MANIFEST) as fp:
        b = json.load(fp)
    stand_ins = {}
    for entry in TINY_CELLS.values():
        stand_ins.setdefault(entry["rehearses"], []).append(entry["cell"])
    root_cells = {w["name"] for w in b["workloads"]}
    assert set(stand_ins) == root_cells, (
        "every cell of BENCHMARK.json has a file under tiny/cells/, and every "
        f"file rehearses one: {sorted(set(stand_ins) ^ root_cells)}")
    b["configs"] = [
        {"name": c, "source": "test preset", "reduced": [], "why": "CPU",
         "file": os.path.join(HERE, "tiny", "configs", c + ".json")}
        for c in sorted({e["config"] for e in TINY_CELLS.values()})]
    b["workloads"] = [
        {"name": e["cell"], "config": e["config"], "traffic": e["traffic"],
         "chips": 1, "why": "CPU rehearsal"} for e in TINY_CELLS.values()]
    for m in b["end_to_end"] + b["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [t for w in m["workloads"] for t in stand_ins[w]]
    path = tmp_path_factory.mktemp("tiny") / "BENCHMARK.json"
    path.write_text(json.dumps(b))
    return str(path)


def run_tiny_cell(manifest, name, seed, trace=False, seconds=1.0):
    """One run of a tiny cell through its driver's ``run``, as ``run.py``
    makes it but for the look for a chip; the result line, checked to be
    the last of standard output and to end in ``compared``."""
    cell = harness.Cell(manifest, name)
    if cell.config["program"]["entry"] == "serve":
        from benchmark import serve_driver as drv
    else:
        from benchmark import train_driver as drv
    out, err = io.StringIO(), io.StringIO()
    real = harness.emit

    def quiet(*a, **kw):
        return real(*a, out=out, err=err, **kw)

    harness.emit, drv.harness.emit = quiet, quiet
    try:
        line = drv.run(cell, seed, seconds, trace, time.monotonic(),
                       allow_cpu=True)
    finally:
        harness.emit = drv.harness.emit = real
    assert json.loads(out.getvalue().splitlines()[-1]) == line
    assert err.getvalue().splitlines()[-1] == f"correct {line['correct']}"
    assert list(line)[-1] == "compared"
    return line


@pytest.fixture(scope="session")
def run_tiny():
    return run_tiny_cell


def device_only(metric: dict) -> bool:
    """A per-layer metric that no CPU run may print: read from the device's
    trace or the program's spans, or a share of a peak or a roofline."""
    return (metric["source"] in ("device_trace", "program_span")
            or "mfu" in metric["name"] or "roofline" in metric["name"])


@pytest.fixture(scope="session")
def rehearse(tiny):
    """``rehearse(name, trace, seed)``: one run of a tiny cell, held to what
    every rehearsal has to show, from the manifest's own lists and the
    cell's file; returns the result line."""
    def rehearse(name, trace, seed):
        cell = harness.Cell(tiny, name)
        line = run_tiny_cell(tiny, name, seed, trace=trace)
        assert line["correct"] is True, line["compared"]
        assert line["failed"] == 0 and line["attempted"] > 0
        if trace:
            listed = {m["name"]: m for m in cell.metrics("per_layer")}
            expect = set(TINY_CELLS[name]["cpu_layer_metrics"])
            assert expect <= set(listed)
            assert not any(device_only(listed[n]) for n in line["metrics"])
        else:
            expect = {m["name"] for m in cell.metrics("end_to_end")}
        assert set(line["metrics"]) == expect
        assert line["device"]["platform"] == "cpu"
        assert all(v["value"] > 0 for v in line["metrics"].values())
        return line

    return rehearse
