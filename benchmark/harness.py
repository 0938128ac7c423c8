"""What every cell's run shares: finding the cell's files by name, the device
check, the compile cache, the compile counter, the result line.

Driven by data.  ``BENCHMARK.json`` names cells, configurations and metrics;
each configuration is ``configs/<name>.json``, each traffic mix or job is
``traffic/<name>.json``, each per-layer metric is
``layer_metrics/<name>.json`` naming a reader ``readers/<reader>.py``.  A
configuration's file names its architecture's plain reference
(``"reference"``: ``references/<name>.py``) and work counts (``"work"``:
``work/<name>.py``); nothing here, in the drivers, in ``calibrate.py`` or in
the readers knows an architecture but through those two names.  A new cell,
configuration, mix, metric or architecture is new files and a new entry.
"""

from __future__ import annotations

import functools
import importlib
import importlib.util
import json
import os
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
EXIT_NO_CHIP = 3
EXIT_COMPILED_IN_WINDOW = 4
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class BenchError(Exception):
    """The run cannot give a result; the message goes to standard error."""

    def __init__(self, msg: str, code: int = 1):
        super().__init__(msg)
        self.code = code


def phase(name: str, t_process: float) -> None:
    """Where set-up goes: a line on standard error as each phase ends."""
    print(f"phase {name} +{time.monotonic() - t_process:.2f}s",
          file=sys.stderr, flush=True)


def load_json(path: str) -> dict:
    with open(path) as fp:
        return json.load(fp)


class Cell:
    """One entry of ``workloads`` with the files it names, all loaded."""

    def __init__(self, manifest_path: str, name: str):
        self.root = os.path.dirname(os.path.abspath(manifest_path))
        self.manifest = load_json(manifest_path)
        cells = {w["name"]: w for w in self.manifest["workloads"]}
        if name not in cells:
            raise BenchError(
                f"no workload {name!r} in {manifest_path} "
                f"(known: {sorted(cells)})", 2)
        self.entry = cells[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        configs = {c["name"]: c for c in self.manifest["configs"]}
        config_file = os.path.join(
            self.root, configs[self.entry["config"]]["file"])
        self.config = load_json(config_file)
        # A mix lives beside the configurations' directory, by its name.
        self.bench_dir = os.path.dirname(os.path.dirname(config_file))
        self.traffic = load_json(os.path.join(
            self.bench_dir, "traffic", self.entry["traffic"] + ".json"))
        # No default architecture: the file names both, and both are there.
        self._module_files = {}
        for key, kind in (("reference", "references"), ("work", "work")):
            if not isinstance(self.config.get(key), str):
                raise BenchError(
                    f"{config_file} names no {key!r}: every configuration "
                    f"says which {kind}/<name>.py is its architecture's", 2)
            self._module_files[key] = self._find(
                kind, self.config[key] + ".py")

    def _find(self, kind: str, filename: str) -> str:
        """A file of the cell's own by its name: beside the configurations'
        directory first, then under ``benchmark/``."""
        for base in (self.bench_dir, HERE):
            path = os.path.join(base, kind, filename)
            if os.path.exists(path):
                return path
        raise BenchError(f"no {kind}/{filename} for {self.name}", 2)

    @functools.cached_property
    def reference(self):
        """The architecture's plain reference (PERF.md section 3 states the
        contract it keeps), imported when first asked for."""
        return load_module(self._module_files["reference"])

    @functools.cached_property
    def work(self):
        """The architecture's work counts (same section, same contract)."""
        return load_module(self._module_files["work"])

    def sizes(self) -> dict:
        """What the configuration's own reference reads from its keys."""
        return self.reference.sizes_of(self.config)

    def metrics(self, section: str) -> list:
        """The metrics of ``end_to_end`` or ``per_layer`` that this cell
        reports: those without a ``workloads`` key, and those that list it."""
        return [m for m in self.manifest[section]
                if "workloads" not in m or self.name in m["workloads"]]

    def layer_metric(self, name: str) -> dict:
        return load_json(self._find("layer_metrics", name + ".json"))


def load_module(path: str):
    """A module by its file.  One under ``benchmark/`` is imported by its
    dotted name; one elsewhere (a test's architecture) under a name made of
    its path, once a process."""
    path = os.path.abspath(path)
    if path.startswith(HERE + os.sep):
        return importlib.import_module("benchmark." + os.path.relpath(
            path, HERE)[:-3].replace(os.sep, "."))
    name = "benchmark_found_" + "".join(
        c if c.isalnum() else "_" for c in path[:-3])
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        module = importlib.util.module_from_spec(spec)
        sys.modules[name] = module
        try:
            spec.loader.exec_module(module)
        except BaseException:
            del sys.modules[name]
            raise
    return sys.modules[name]


def read_layer_metrics(cell: Cell, ctx: dict) -> dict:
    """Each per-layer metric through its own reader.  A reader that finds
    nothing to read returns None and the metric is left out of the line."""
    out = {}
    for m in cell.metrics("per_layer"):
        spec = cell.layer_metric(m["name"])
        reader = importlib.import_module(
            f"benchmark.readers.{spec['reader']}")
        value = reader.read(ctx, **spec.get("params", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# ----------------------------------------------------- the system under test
def build_model(config: dict):
    """The program's model as the configuration's file names it."""
    import jax.numpy as jnp

    from ml_trainer_tpu.models import get_model

    opts = dict(config["program"].get("model_options", {}))
    for key, value in opts.items():
        if key == "dtype" or key.endswith("_dtype"):
            opts[key] = getattr(jnp, value)
    return get_model(config["program"]["model"], **opts)


def make_weights(cell: Cell, seed: int):
    """The benchmark's own weights from the seed, by the configuration's own
    reference, in the precision it states."""
    from benchmark import reference

    return cell.reference.make_weights(
        reference.seed_key(seed), **cell.sizes())


def start_trace(log_dir: str) -> None:
    """The profiler on, host spans kept, the Python tracer off (it would
    swamp the trace and slow the host it measures)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)


# ------------------------------------------------------------------ device
def device_facts(chips: int, allow_cpu: bool = False) -> dict:
    """Name the device as JAX reports it; no accelerator, or fewer chips
    than the cell asks for, is an error with no result."""
    import jax

    devices = jax.devices()
    facts = {"platform": devices[0].platform,
             "kind": devices[0].device_kind, "count": len(devices)}
    if allow_cpu:
        return facts
    if facts["platform"] != "tpu" or facts["count"] < chips:
        raise BenchError(
            f"need {chips} TPU chip(s); JAX reports {facts['count']} x "
            f"{facts['platform']} ({facts['kind']}): no accelerator, no "
            "result", EXIT_NO_CHIP)
    return facts


def memory_peak_bytes() -> int:
    """The allocator's peak on the fullest chip (0 where the backend keeps
    no statistics, as on the CPU)."""
    import jax

    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def use_compile_cache() -> str:
    """JAX's persistent cache where ``JAX_COMPILATION_CACHE_DIR`` says, else
    at the checkout's one fixed ignored path (the program's own default,
    ``trainer.COMPILE_CACHE_DIR``).  Every program is kept, the quick ones
    too, so that a second run compiles nothing."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileCounter:
    """Backend compiles (cache retrievals included) as ``jax.monitoring``
    reports them, with the function's name and the time of each."""

    def __init__(self):
        import jax

        self.events = []
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == BACKEND_COMPILE_EVENT:
            with self._lock:
                self.events.append(
                    (time.monotonic(), str(kw.get("fun_name", "?")),
                     float(duration)))

    def between(self, t0: float, t1: float) -> list:
        """Compiles that ENDED in (t0, t1]: the listener fires at the end."""
        with self._lock:
            return [e for e in self.events if t0 < e[0] <= t1]


def forbid_compiles(counter: CompileCounter, t0: float, t1: float) -> None:
    inside = counter.between(t0, t1)
    if inside:
        names = ", ".join(f"{n} ({d:.2f}s)" for _, n, d in inside[:8])
        raise BenchError(
            f"{len(inside)} program(s) compiled inside the measured window: "
            f"{names}; warm-up missed a shape", EXIT_COMPILED_IN_WINDOW)


# ------------------------------------------------------------------ result
def judge(compared: dict) -> bool:
    """``compared``: name -> {"value", "limit"}; correct when every value
    is a number at or under its limit."""
    return bool(compared) and all(
        c["value"] is not None and c["value"] == c["value"]
        and c["value"] <= c["limit"] for c in compared.values())


def finish(cell: Cell, trace: bool, facts: dict, numbers: dict,
           compared: dict, attempted: int, failed: int, peak: int,
           ctx: dict, allow_cpu: bool = False):
    """What both drivers do once the outputs are checked: reduce the trace,
    read the per-layer metrics through their readers, print the result.
    ``ctx`` is what the readers read (the trace in normal form under
    ``trace``); a CPU rehearsal prints no device metric."""
    from benchmark import flops, trace_reduce

    device_extra = {"memory_peak_bytes": peak}
    layer_values, breakdown = {}, None
    if trace:
        ctx.update(cell=cell.name, chips=cell.chips,
                   reference=cell.reference, work=cell.work)
        if ctx.get("trace") and ctx["trace"]["devices"]:
            red = ctx["trace_reduced"] = trace_reduce.reduce(ctx["trace"])
            device_extra.update(busy_s=red["busy_s"],
                                window_s=red["window_s"])
            breakdown = {k: red[k] for k in ("device_ops", "idle_gaps")}
        nan = float("nan")
        ctx["peaks"] = ({"bf16_flops_per_s": nan, "hbm_bytes_per_s": nan}
                        if allow_cpu else flops.peaks_for(facts["kind"]))
        layer_values = read_layer_metrics(cell, ctx)
        if allow_cpu:  # never a device number from a CPU run
            layer_values = {k: v for k, v in layer_values.items()
                            if v["value"] == v["value"]}
    return emit(cell, trace, facts, numbers, layer_values, compared,
                attempted, failed, device_extra, breakdown)


def emit(cell: Cell, trace: bool, facts: dict, numbers: dict,
         layer_values: dict, compared: dict, attempted: int, failed: int,
         device_extra: dict, breakdown=None, out=sys.stdout,
         err=sys.stderr) -> dict:
    """The one result line, last on standard output, and each number
    compared beside its limit, last on standard error."""
    if trace:
        metrics = layer_values
    else:
        metrics = {}
        for m in cell.metrics("end_to_end"):
            if m["name"] not in numbers:
                raise BenchError(
                    f"end-to-end metric {m['name']} was not measured")
            metrics[m["name"]] = {"value": float(numbers[m["name"]]),
                                  "unit": m["unit"]}
    line = {"correct": judge(compared), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics,
            "device": {**facts, **device_extra}}
    if trace and breakdown:
        line["breakdown"] = breakdown
    line["workload"] = cell.name
    line["compared"] = compared
    print("compared (value <= limit):", file=err)
    for name, c in compared.items():
        print(f"  {name} {c['value']!r} limit {c['limit']!r}", file=err)
    print(f"correct {line['correct']}", file=err, flush=True)
    print(json.dumps(line), file=out, flush=True)
    return line
