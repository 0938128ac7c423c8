#!/usr/bin/env python
"""Telemetry smoke leg (scripts/fastlane.sh) — ~30s on CPU.

One tiny end-to-end pass over the telemetry spine's cheap guarantees,
as a standalone script so the fast lane exercises the REAL env-var
plumbing (flight-dir redirect, JSONL sink), not just the programmatic
test hooks:

1. A one-epoch ``Trainer(telemetry=True)`` run with an injected
   ``nan_grad`` + rollback emits train gauges into the default
   registry, writes a ``history.json`` mirror ``load_history`` prefers,
   and dumps a flight record naming the offending step.
2. The registry round-trips through Prometheus text exposition
   (headers + samples parse) and the JSONL sink appends parseable
   lines.
3. The span buffer holds the run's ``data_load`` / ``h2d`` /
   ``ckpt_write`` spans and saves a loadable Perfetto trace.
4. The distributed-observability leg, single-process degenerate case:
   the trainer's cluster aggregation published ``cluster_*{host=0}``
   series and a ``run_report.json``/``.md`` pair, and a sharded dryrun
   step (``shard_map`` + explicit collectives over a 2-virtual-device
   mesh) left ``comm_bytes_total{op=...}`` gauges behind.

Exits non-zero (with a reason) on any violation.
"""

import json
import os
import sys
import tempfile

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# Two virtual CPU devices so the comm-bytes leg has a real axis to
# collect over (the trainer legs keep their single-device mesh).
_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=2"
    ).strip()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    workdir = tempfile.mkdtemp(prefix="telemetry_smoke_")
    os.environ["ML_TRAINER_TPU_FLIGHT_DIR"] = workdir
    os.environ["ML_TRAINER_TPU_METRICS_JSONL"] = os.path.join(
        workdir, "metrics.jsonl"
    )

    from ml_trainer_tpu import Trainer, MLModel, load_history
    from ml_trainer_tpu.data import SyntheticCIFAR10
    from ml_trainer_tpu.resilience import faults
    from ml_trainer_tpu.telemetry import (
        default_registry,
        prometheus_text,
        save_trace,
        trace_events,
    )
    from ml_trainer_tpu.utils.functions import custom_pre_process_function

    def fail(msg):
        print(f"TELEMETRY_SMOKE FAIL: {msg}")
        return 1

    t0 = custom_pre_process_function()
    with faults.injected("nan_grad@step=3"):
        t = Trainer(
            MLModel(),
            datasets=(SyntheticCIFAR10(size=64, seed=0, transform=t0),
                      SyntheticCIFAR10(size=32, seed=1, transform=t0)),
            epochs=1, batch_size=16, model_dir=workdir, metric=None,
            lr=0.01, save_history=True, telemetry=True, log_every_steps=1,
            rollback_bad_steps=1,
        )
        t.fit()

    # 1. Registry gauges + flight dump + history.json.
    snap = default_registry().snapshot()
    if snap.get("train_skipped_steps_total", 0) < 1:
        return fail(f"skipped-step counter not published: {snap}")
    # The real recompile instrument (telemetry/compile_watch.py) replaces
    # the old per-function _cache_size() pin: the train step compiled
    # exactly once, and the labeled counter reached the registry.
    from ml_trainer_tpu.telemetry import compile_watch

    if compile_watch.compile_count("jit(train_step)") != 1:
        return fail(
            f"telemetry caused recompiles: {compile_watch.counts_by_fn()}"
        )
    if snap.get("compile_events_total{fn=jit(train_step)}") != 1:
        return fail("compile_events_total{fn=} counter not published")
    dumps = [f for f in os.listdir(workdir) if f.startswith("flight_")]
    if not dumps:
        return fail("no flight dump after nan_grad rollback")
    payload = json.load(open(os.path.join(workdir, dumps[0])))
    if payload.get("first_bad_step") != 3:
        return fail(f"flight dump does not name step 3: {payload.get('first_bad_step')}")
    # OOM/wedge forensics ride along: the dump attaches the device-memory
    # snapshot and the recent compile events (flight context providers).
    ctx = payload.get("context", {})
    if "live" not in ctx.get("memory", {}):
        return fail(f"flight dump missing memory snapshot: {list(ctx)}")
    if not isinstance(ctx.get("compile_events"), list):
        return fail(f"flight dump missing compile events: {list(ctx)}")
    hist = load_history(workdir)
    if hist.get("rollbacks") != 1 or sum(hist.get("skipped_steps", [])) != 1:
        return fail(f"history.json resilience ledger wrong: {hist}")

    # 2. Prometheus text + JSONL sink.
    text = prometheus_text(default_registry())
    if "# TYPE train_grad_norm gauge" not in text:
        return fail("prometheus exposition missing train gauges")
    for line in text.splitlines():
        if not (line.startswith("#") or " " in line):
            return fail(f"malformed exposition line: {line!r}")
    with open(os.environ["ML_TRAINER_TPU_METRICS_JSONL"]) as fp:
        lines = [json.loads(ln) for ln in fp if ln.strip()]
    if not any(ln.get("kind") == "train_step" for ln in lines):
        return fail("JSONL sink holds no train_step events")

    # 3. Spans: the run's host regions are on the trace.
    names = {e["name"] for e in trace_events()}
    for expected in ("data_load", "h2d", "ckpt_write"):
        if expected not in names:
            return fail(f"span {expected!r} missing from trace ({names})")
    trace_path = save_trace(os.path.join(workdir, "trace.json"))
    loaded = json.load(open(trace_path))
    if not loaded.get("traceEvents"):
        return fail("saved Perfetto trace is empty")

    # 4. Distributed observability, degenerate single-host case.
    for key in ("cluster_last_step{host=0}", "cluster_step_ms_p50{host=0}",
                "cluster_syncs_total"):
        if key not in default_registry().snapshot():
            return fail(f"cluster aggregation missing {key!r}")
    report_path = os.path.join(workdir, "run_report.json")
    if not os.path.exists(report_path):
        return fail("trainer did not write run_report.json")
    report = json.load(open(report_path))
    for section in ("throughput", "hosts", "comm_bytes_by_op", "resilience"):
        if section not in report:
            return fail(f"run report missing section {section!r}")
    if report["resilience"].get("rollbacks") != 1:
        return fail(f"run report missed the rollback: {report['resilience']}")
    if not os.path.exists(os.path.join(workdir, "run_report.md")):
        return fail("run_report.md missing")

    # Comm-bytes gauges after one sharded (shard_map + explicit
    # collective) step over the 2-virtual-device mesh.
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ml_trainer_tpu.parallel import create_mesh
    from ml_trainer_tpu.parallel.collectives import psum
    from jax import shard_map

    if jax.device_count() < 2:
        return fail(f"expected 2 virtual devices, got {jax.device_count()}")
    mesh = create_mesh({"data": 2}, devices=jax.devices()[:2])
    step = jax.jit(shard_map(
        lambda x: psum(x, "data"), mesh=mesh,
        in_specs=P("data"), out_specs=P(),
    ))
    step(jnp.ones((4, 8), jnp.float32)).block_until_ready()
    snap = default_registry().snapshot()
    comm = snap.get("comm_bytes_total{op=psum}", 0)
    # per-shard (2, 8) f32 = 64 bytes; ring all-reduce over 2 devices
    # moves 2 * 64 * 1/2 = 64 bytes per participant.
    if comm < 64:
        return fail(f"comm_bytes_total{{op=psum}} not published: {comm}")

    print(
        "TELEMETRY_SMOKE OK: "
        f"{int(snap['train_steps_total'])} steps telemetered, "
        f"flight dump {dumps[0]} names step 3, "
        f"{len(loaded['traceEvents'])} trace events, "
        f"{len(lines)} JSONL records, "
        f"cluster series + run report present, "
        f"psum comm bytes {int(comm)}"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
