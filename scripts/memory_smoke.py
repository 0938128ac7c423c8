#!/usr/bin/env python
"""Fastlane smoke: memory ledger + goodput + recompile forensics.

A 2-virtual-device dryrun over the third observability pillar
(telemetry/memory.py, goodput.py, compile_watch.py), asserting the
acceptance invariants end to end through the REAL Trainer:

1. **Analytic-vs-measured agreement** (hard, 10%): the formula-driven
   ledger (``plan_train_memory`` — ``jax.eval_shape`` only, no state
   read) prices the state of {pure-DP, ZeRO-1 sharded-dp, 2-stage
   1F1B pipeline} configs within 10% of the MEASURED per-device buffer
   bytes of the live state (``measured_tree_bytes`` — real
   ``addressable_shards``).  ZeRO-1 must show the ÷2 moment shard,
   the pipeline must show the ÷2 stage shard.
2. **Goodput decomposition**: every run publishes a
   ``train_goodput_fraction`` in (0, 1] whose buckets + compute
   remainder reconstruct the wall-clock, with the compile bucket
   non-zero on a fresh process.
3. **Zero post-warmup compiles**: after each trainer's first epoch
   (train + eval programs built) the second epoch compiles NOTHING —
   ``compile_watch.post_warmup_count()`` stays 0 — and the compile
   counter named every program (``compile_events_total{fn=...}``).

Prints one ``MEMORY_SMOKE_RESULT {json}`` line (consumed by
``scripts/bench_gate.py gate_goodput`` and committed as
``docs/memory_goodput_cpu.json``), then ``MEMORY_SMOKE_OK``.  Exits
non-zero with a reason on any violation.  Runs on CPU in ~1 min.
"""

import json
import os
import sys
import tempfile

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=2"
    ).strip()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

TOLERANCE = 0.10
GOODPUT_FLOOR = 0.02  # CPU floor: compiles dominate a tiny dryrun


def main() -> int:
    from ml_trainer_tpu import Trainer, MLModel
    from ml_trainer_tpu.data import SyntheticCIFAR10, SyntheticTokens
    from ml_trainer_tpu.models import get_model
    from ml_trainer_tpu.parallel import rules_for
    from ml_trainer_tpu.telemetry import compile_watch
    from ml_trainer_tpu.telemetry import memory as M
    from ml_trainer_tpu.telemetry.registry import default_registry
    from ml_trainer_tpu.utils.functions import custom_pre_process_function

    def fail(msg):
        print(f"MEMORY_SMOKE FAIL: {msg}")
        return 1

    assert jax.device_count() >= 2, "2-virtual-device mesh not active"
    workdir = tempfile.mkdtemp(prefix="memory_smoke_")
    t0 = custom_pre_process_function()

    def image_sets():
        return (SyntheticCIFAR10(size=64, seed=0, transform=t0),
                SyntheticCIFAR10(size=32, seed=1, transform=t0))

    result = {"configs": [], "backend": jax.default_backend()}

    def state_bytes_measured(trainer):
        measured, _ = M.measured_tree_bytes({
            "params": trainer.state.params,
            "opt_state": trainer.state.opt_state,
            "batch_stats": trainer.state.batch_stats,
        })
        return measured

    def analytic_state_bytes(ledger):
        return sum(
            c.bytes for c in ledger.components
            if c.name in ("params", "opt_state", "batch_stats")
        )

    # ---- leg 1/2: pure-DP and ZeRO-1 sharded-dp over data=2 ------------
    for label, extra in (
        ("pure_dp", {}),
        ("zero1_sharded_dp", {"dp_update": "sharded"}),
    ):
        before = compile_watch.post_warmup_count()
        t = Trainer(
            MLModel(), datasets=image_sets(), epochs=2, batch_size=16,
            model_dir=os.path.join(workdir, label), metric=None, lr=0.01,
            optimizer="adamw", mesh_shape={"data": 2}, telemetry=True,
            log_every_steps=1, **extra,
        )
        t.fit()
        if compile_watch.post_warmup_count() != before:
            return fail(
                f"{label}: {compile_watch.post_warmup_count() - before} "
                f"post-warmup recompile(s): "
                f"{[e.as_dict() for e in compile_watch.events(last=4)]}"
            )
        # Formula planner (no state read) vs the measured live buffers.
        plan = M.plan_train_memory(
            MLModel(), t._batch_geometry, optimizer="adamw",
            mesh_shape={"data": 2},
            dp_update=extra.get("dp_update", "fused"),
        )
        measured = state_bytes_measured(t)
        check = M.cross_check(
            analytic_state_bytes(plan), measured, TOLERANCE
        )
        row = {"config": label, **check}
        result["configs"].append(row)
        if not check["ok"]:
            return fail(f"{label}: analytic vs measured disagree: {check}")
        if label == "zero1_sharded_dp":
            # The ÷2 must be visible: sharded moments cost LESS than the
            # pure-DP replicated ones did.
            rep = next(
                r for r in result["configs"] if r["config"] == "pure_dp"
            )
            if check["measured_bytes"] >= rep["measured_bytes"]:
                return fail(
                    "ZeRO-1 state not smaller than replicated: "
                    f"{check['measured_bytes']} >= {rep['measured_bytes']}"
                )
        print(f"# memory smoke: {label} analytic/measured "
              f"{check['ratio']:.3f} OK")

    # ---- leg 3: 2-stage 1F1B pipeline over a stage mesh ----------------
    before = compile_watch.post_warmup_count()
    ds = SyntheticTokens(size=32, seq_len=32, vocab_size=256, seed=0)
    from ml_trainer_tpu.parallel import create_mesh

    mesh = create_mesh({"stage": 2}, devices=jax.devices()[:2])
    pipe_model = get_model(
        "gpt2_pipe_tiny", n_stages=2, num_heads=2, mesh=mesh,
        n_microbatches=4,
    )
    t_pp = Trainer(
        pipe_model, datasets=(ds, ds),
        model_dir=os.path.join(workdir, "pipeline"),
        epochs=2, batch_size=8, seed=3, lr=0.01, optimizer="adamw",
        metric=None, mesh_shape={"stage": 2},
        sharding_rules=rules_for("gpt2", "pp"),
        pipeline_schedule="1f1b", telemetry=True, log_every_steps=2,
    )
    t_pp.fit()
    if compile_watch.post_warmup_count() != before:
        return fail(
            f"pipeline: {compile_watch.post_warmup_count() - before} "
            "post-warmup recompile(s)"
        )
    plan = M.plan_train_memory(
        get_model("gpt2_pipe_tiny", n_stages=2, num_heads=2,
                  n_microbatches=4),
        t_pp._batch_geometry, optimizer="adamw",
        mesh_shape={"stage": 2}, sharding_rules=rules_for("gpt2", "pp"),
    )
    measured = state_bytes_measured(t_pp)
    check = M.cross_check(analytic_state_bytes(plan), measured, TOLERANCE)
    result["configs"].append({"config": "pipeline_1f1b_s2", **check})
    if not check["ok"]:
        return fail(f"pipeline: analytic vs measured disagree: {check}")
    # The trainer's own ledger priced the pipeline stash.
    stash = t_pp._memory_ledger.component("pipeline_stash")
    if stash is None or stash.bytes <= 0:
        return fail("trainer ledger missing the pipeline_stash component")
    result["pipeline_stash_bytes"] = int(stash.bytes)
    print(f"# memory smoke: pipeline_1f1b_s2 analytic/measured "
          f"{check['ratio']:.3f}, stash {int(stash.bytes)} bytes OK")

    # ---- goodput decomposition ----------------------------------------
    gp = t_pp._telemetry.goodput.last
    if gp is None:
        return fail("goodput meter never reported")
    recon = gp["compute_secs"] + sum(gp["buckets_secs"].values())
    if abs(recon - gp["wall_secs"]) > max(
        gp["overshoot_secs"] + 1e-6, 0.01 * gp["wall_secs"]
    ):
        return fail(
            f"goodput buckets do not reconstruct the wall clock: "
            f"{recon} vs {gp['wall_secs']}"
        )
    snap = default_registry().snapshot()
    frac = snap.get("train_goodput_fraction", 0.0)
    if not (GOODPUT_FLOOR <= frac <= 1.0):
        return fail(f"goodput fraction {frac} outside "
                    f"[{GOODPUT_FLOOR}, 1.0]")
    if snap.get(
        "train_goodput_seconds_total{bucket=compile}", 0.0
    ) <= 0.0:
        return fail("compile bucket empty on a fresh process")
    result["goodput"] = {
        "fraction": round(frac, 4),
        "buckets_secs": {
            b: round(v, 3) for b, v in gp["buckets_secs"].items()
        },
        "compute_secs": round(gp["compute_secs"], 3),
        "wall_secs": round(gp["wall_secs"], 3),
    }

    # ---- compile forensics --------------------------------------------
    by_fn = compile_watch.counts_by_fn()
    train_compiles = sum(
        v for k, v in by_fn.items() if "train_step" in k
    )
    if train_compiles < 2:  # the per-batch step of >= 2 of the trainers
        return fail(f"compile counter missed the train steps: {by_fn}")
    result["compiles"] = {
        "total": compile_watch.compile_count(),
        "post_warmup": compile_watch.post_warmup_count(),
        "train_step": train_compiles,
    }
    # Live-vs-analytic exposition both landed in the registry.
    for key in ("mem_analytic_resident_bytes", "mem_live_bytes{device=0}"):
        if key not in snap:
            return fail(f"registry missing {key!r}")

    print("MEMORY_SMOKE_RESULT " + json.dumps(result))
    print(
        "MEMORY_SMOKE_OK: "
        f"{len(result['configs'])} configs within {TOLERANCE:.0%}, "
        f"goodput {result['goodput']['fraction']}, "
        f"{result['compiles']['total']} compiles "
        f"({result['compiles']['post_warmup']} post-warmup)"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
