"""Mixed precision (precision.py) — policy resolution, dynamic loss
scaling composed with the non-finite guard and gradient accumulation, and
the gradient-bucket planner behind the sharded DP update.

The distributed trajectory-equality pins for dp_update='sharded' live in
tests/test_parallel.py (slow tier); this module is the fast lane:
single-device Trainer runs, pure-host units, and what the
{precision} x {dp_update} matrix must hold on the virtual data mesh
(finite loss, one program, the per-bucket comm ledger).
"""

import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ml_trainer_tpu import Trainer, MLModel
from ml_trainer_tpu.data import SyntheticCIFAR10
from ml_trainer_tpu.precision import (
    LossScaleConfig,
    Precision,
    cast_floating,
    resolve_loss_scale,
    resolve_precision,
)
from ml_trainer_tpu.resilience import faults
from ml_trainer_tpu.utils.functions import custom_pre_process_function


def make_trainer(model_dir, **kw):
    t = custom_pre_process_function()  # float batches: NaN-poisonable
    kw.setdefault("epochs", 1)
    kw.setdefault("batch_size", 16)
    kw.setdefault("lr", 0.01)
    kw.setdefault("metric", None)
    return Trainer(
        MLModel(),
        datasets=(SyntheticCIFAR10(size=32, seed=0, transform=t),
                  SyntheticCIFAR10(size=16, seed=1, transform=t)),
        model_dir=str(model_dir), **kw,
    )


# ------------------------------------------------------------------ units
def test_precision_policy_resolution():
    assert not resolve_precision(None).active
    assert not resolve_precision("fp32").active
    p = resolve_precision("bf16")
    assert p.active and jnp.dtype(p.compute) == jnp.dtype(jnp.bfloat16)
    assert jnp.dtype(p.params) == jnp.dtype(jnp.float32)
    assert p.label() == "bfloat16"
    # Instances pass through; a non-fp32 master is rejected (the master
    # copy IS the TrainState — changing it would change every checkpoint).
    assert resolve_precision(p) is p
    with pytest.raises(ValueError, match="params"):
        resolve_precision(Precision(params=jnp.bfloat16))
    with pytest.raises(ValueError, match="unknown precision"):
        resolve_precision("fp8")


def test_loss_scale_resolution():
    fp32, bf16 = resolve_precision("fp32"), resolve_precision("bf16")
    # fp32 NEVER scales — the scale arithmetic must not enter the
    # fp32 program (bit-identity).
    assert resolve_loss_scale("dynamic", fp32) is None
    assert resolve_loss_scale(None, bf16) is None
    dyn = resolve_loss_scale("dynamic", bf16)
    assert dyn.growth_factor == 2.0 and dyn.backoff_factor == 0.5
    static = resolve_loss_scale(1024.0, bf16)
    assert static.init_scale == static.min_scale == static.max_scale == 1024.0
    assert static.growth_factor == 1.0  # pinned: never moves
    with pytest.raises(ValueError, match="positive"):
        resolve_loss_scale(-1.0, bf16)
    with pytest.raises(ValueError, match="dynamic"):
        resolve_loss_scale("auto", bf16)


def test_cast_floating_skips_integers():
    tree = {"w": jnp.ones((2,), jnp.float32), "ids": jnp.ones((2,), jnp.int32)}
    out = cast_floating(tree, jnp.bfloat16)
    assert out["w"].dtype == jnp.bfloat16
    assert out["ids"].dtype == jnp.int32


def test_plan_grad_buckets_reverse_order_and_rule():
    from ml_trainer_tpu.parallel import plan_grad_buckets

    tree = {
        "a": jnp.zeros((16, 4)),   # sharded (16 % 8 == 0)
        "b": jnp.zeros((5,)),      # NOT sharded (5 % 8)
        "c": jnp.zeros((64,)),     # sharded
        "d": jnp.zeros((8, 8)),    # sharded
    }
    plan = plan_grad_buckets(tree, 8, bucket_bytes=300)
    assert plan.sharded == (True, False, True, True)
    # Reverse flatten order (backward production order), every sharded
    # leaf covered exactly once, bound respected (one leaf may exceed it).
    flat = [i for b in plan.buckets for i in b]
    assert flat == [3, 2, 0]
    assert sum(plan.bucket_bytes) == 16 * 4 * 4 + 64 * 4 + 8 * 8 * 4
    # Overlap: everything but the LAST bucket (earliest layers, produced
    # last in the backward) can hide under remaining compute.
    assert plan.overlap_fraction == pytest.approx(
        1.0 - plan.bucket_bytes[-1] / sum(plan.bucket_bytes)
    )
    # n=1 degenerates: nothing shards.
    plan1 = plan_grad_buckets(tree, 1, bucket_bytes=300)
    assert all(plan1.sharded)  # every dim-0 divides 1...
    assert plan_grad_buckets(tree, 7).sharded == (False, False, False, False)


# ----------------------------------------------- scaling x accum x guard
@pytest.mark.parametrize("composed_with", [
    pytest.param({"grad_accum_steps": 2}, id="grad-accum"),
    pytest.param({"mesh_shape": {"data": 8}, "dp_update": "sharded"},
                 id="sharded-dp-update"),
])
def test_dynamic_scale_halves_on_overflow_without_burning_rollback(
    tmp_path, composed_with
):
    """The satellite matrix: loss scaling x NaN guard x {grad accumulation,
    the bucketed sharded update on a data mesh}.  An injected non-finite
    step under bf16+dynamic scaling must (a) skip the update, (b) halve
    the scale, (c) land in the skipped-step ledger, (d) NOT advance the
    rollback streak — overflow is the scale's fault, not the run's — and
    (e) stay one compiled program: the backoff is where-selected, not
    branched."""
    with faults.injected("nan_grad@step=2"):
        t = make_trainer(tmp_path / "bf16", precision="bf16", **composed_with)
        s0 = float(t.state.loss_scale)
        t.fit()
    assert float(t.state.loss_scale) == s0 * 0.5
    assert t.skipped_steps == [1]
    assert int(jax.device_get(t.state.bad_streak)) == 0
    assert all(np.isfinite(t.train_losses))
    assert t._train_step._cache_size() == 1


@pytest.mark.parametrize("dp_update", ["fused", "sharded"])
@pytest.mark.parametrize("precision", ["fp32", "bf16"])
def test_dp_matrix_finite_loss_one_program_and_bucket_gauges(
    tmp_path, precision, dp_update
):
    """{fp32, bf16} x {fused psum, bucketed reduce-scatter + sharded
    update} on the 8-device data mesh, through the real Trainer: every
    cell trains finite, compiles one train step and nothing after the
    first epoch; the sharded cells leave one reduce-scatter and one
    all-gather entry a bucket in the comm ledger and publish the plan's
    overlap fraction."""
    from ml_trainer_tpu.data import SyntheticTokens
    from ml_trainer_tpu.models import get_model
    from ml_trainer_tpu.parallel.comm_stats import (
        comm_bucket_bytes,
        reset_comm_stats,
    )
    from ml_trainer_tpu.telemetry import compile_watch, default_registry

    ds = SyntheticTokens(size=32, seq_len=32, vocab_size=256, seed=0)
    reset_comm_stats()
    t = Trainer(
        get_model("gpt2_tiny", vocab_size=256), datasets=(ds, ds),
        epochs=2, batch_size=16, model_dir=str(tmp_path), metric=None,
        lr=1e-3, optimizer="adamw", mesh_shape={"data": 8},
        precision=precision, dp_update=dp_update, bucket_mb=0.25,
        telemetry=True, log_every_steps=2,
    )
    compile_watch.install()
    warm_before = compile_watch.post_warmup_count()
    t.fit()
    assert all(np.isfinite(t.train_losses)), t.train_losses
    assert t._train_step._cache_size() == 1
    assert compile_watch.post_warmup_count() == warm_before, (
        [e.as_dict() for e in compile_watch.events(last=4)]
    )
    if dp_update == "sharded":
        plan = t._bucket_plan
        assert len(plan.buckets) > 1
        ledger = comm_bucket_bytes()
        assert len(ledger["reduce_scatter"]) == len(plan.buckets)
        assert len(ledger["all_gather"]) == len(plan.buckets)
        snap = default_registry().snapshot()
        assert snap["train_overlap_fraction"] == pytest.approx(
            plan.overlap_fraction, abs=1e-9
        )
        assert any(k.startswith("comm_bucket_bytes_total{") for k in snap)


def test_fp32_ledger_unchanged_by_the_scaling_feature(tmp_path):
    """fp32 control: the same injected NaN advances skipped AND the
    rollback streak exactly as before the feature, and the state carries
    no scale leaves (fp32 checkpoints/pytree unchanged)."""
    with faults.injected("nan_grad@step=2"):
        t = make_trainer(tmp_path / "fp32", log_every_steps=100)
        t.fit()
    assert t.skipped_steps == [1]
    assert int(jax.device_get(t.state.bad_streak)) == 1
    assert t.state.loss_scale is None and t.state.good_steps is None


def test_dynamic_scale_grows_after_interval(tmp_path):
    t = make_trainer(
        tmp_path, precision="bf16",
        loss_scale=LossScaleConfig(init_scale=256.0, growth_interval=2),
        epochs=2,
    )
    t.fit()  # 4 finite steps at growth_interval=2 -> two doublings
    assert float(t.state.loss_scale) == 1024.0
    assert all(np.isfinite(t.train_losses))


def test_static_scale_never_moves(tmp_path):
    with faults.injected("nan_grad@step=1"):
        t = make_trainer(tmp_path, precision="bf16", loss_scale=512.0)
        t.fit()
    # Overflowed once AND trained on: a pinned scale stays pinned.
    assert float(t.state.loss_scale) == 512.0
    assert t.skipped_steps == [1]


def test_bf16_resume_keeps_scale(tmp_path):
    cfg = LossScaleConfig(init_scale=256.0, growth_interval=2)
    t = make_trainer(tmp_path, precision="bf16", loss_scale=cfg)
    t.fit()  # 2 steps -> one doubling to 512
    assert float(t.state.loss_scale) == 512.0
    t2 = make_trainer(tmp_path, precision="bf16", loss_scale=cfg, epochs=2)
    t2.fit(resume=True)
    # The restored run continued from the checkpointed 512, not a
    # re-seeded 256 (one more doubling in its second epoch).
    assert float(t2.state.loss_scale) == 1024.0


def test_scaling_requires_guard():
    with pytest.raises(ValueError, match="guard"):
        Trainer(
            MLModel(), precision="bf16", nonfinite_guard=False,
            model_dir=tempfile.mkdtemp(),
        )
    # Bare bf16 (no scaling) composes with a disabled guard.
    Trainer(
        MLModel(), precision="bf16", loss_scale=None, nonfinite_guard=False,
        model_dir=tempfile.mkdtemp(),
    )


def test_dp_update_validation():
    from ml_trainer_tpu.parallel import rules_for

    with pytest.raises(ValueError, match="fused.*sharded|sharded.*fused"):
        Trainer(MLModel(), dp_update="bucketed", model_dir=tempfile.mkdtemp())
    with pytest.raises(ValueError, match="pure data-parallel"):
        Trainer(
            MLModel(), dp_update="sharded", is_parallel=True, backend="cpu",
            mesh_shape={"data": 4, "tensor": 2},
            sharding_rules=rules_for("gpt2", "tp"),
            model_dir=tempfile.mkdtemp(),
        )
    with pytest.raises(ValueError, match="steps_per_execution"):
        Trainer(
            MLModel(), dp_update="sharded", is_parallel=True, backend="cpu",
            steps_per_execution=4, model_dir=tempfile.mkdtemp(),
        )
    # Single-replica mesh: nothing to shard -> documented fused fallback.
    t = Trainer(MLModel(), dp_update="sharded", model_dir=tempfile.mkdtemp())
    assert t.dp_update == "fused"
