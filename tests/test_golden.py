"""Golden-run regression check (VERDICT r1 #8).

The reference's committed notebook outputs (01 nb cell-12/16: per-epoch
loss/accuracy + throughput lines) act as its golden-run record.  Ours is
captured by ``GOLDEN_OUT=... python examples/01_local_training.py``
(synthetic CIFAR-10, the zero-egress stand-in): canonically
``tests/golden/local_run_tpu.json`` from the real chip, with
``local_cpu_run.json`` as the stand-in record until a chip run captures
one (the record notes its ``backend``).  This test re-runs the exact
same configuration on the CPU test mesh and asserts the trajectory still
lands where the committed record says, within tolerances generous enough
to absorb CPU-vs-TPU numerics but tight enough to catch real regressions
(broken schedule stepping, loss scaling, seeding, history schema).
"""

import json
import os

import pytest

# Integration layer: multi-epoch fits / trajectory equality / compiled
# programs — the CI fast lane is `-m 'not slow'` (see pyproject.toml).
pytestmark = pytest.mark.slow

_GOLDEN_DIR = os.path.join(os.path.dirname(__file__), "golden")
# The TPU capture is the canonical record; until a chip run produces
# it, the CPU capture (same config/seeds, backend noted inside) keeps the
# regression net ACTIVE rather than skipped.
_CANDIDATES = [
    os.path.join(_GOLDEN_DIR, "local_run_tpu.json"),
    os.path.join(_GOLDEN_DIR, "local_cpu_run.json"),
]
GOLDEN = next((p for p in _CANDIDATES if os.path.exists(p)), _CANDIDATES[0])

HISTORY_KEYS = {
    "epochs", "train_loss", "val_loss", "train_metric", "val_metric",
    "metric_type",
}


@pytest.fixture(scope="module")
def golden():
    if not os.path.exists(GOLDEN):
        pytest.skip("golden record not captured yet")
    with open(GOLDEN) as f:
        return json.load(f)


def test_golden_schema(golden):
    # Records captured after the resilience layer landed also carry the
    # per-epoch skipped_steps counts; both vintages stay valid.
    assert HISTORY_KEYS <= set(golden["history"]) <= (
        HISTORY_KEYS | {"skipped_steps"}
    )
    n = golden["epochs"]
    assert golden["history"]["epochs"] == list(range(1, n + 1))
    for k in ("train_loss", "val_loss", "train_metric", "val_metric"):
        assert len(golden["history"][k]) == n
    assert golden["history"]["metric_type"] == "accuracy"
    assert golden["train_samples_per_sec_incl_compile"] > 0


def test_golden_trajectory_reproduces(golden, tmp_path):
    """Same config, same seeds, CPU mesh — must match the TPU record."""
    from ml_trainer_tpu import MLModel, Loader, Trainer, load_model
    from ml_trainer_tpu.data import SyntheticCIFAR10
    from ml_trainer_tpu.utils.functions import custom_pre_process_function

    if not golden.get("synthetic"):
        pytest.skip("golden record was captured on real CIFAR-10, which "
                    "this machine may not have")
    transform = custom_pre_process_function()
    datasets = (
        SyntheticCIFAR10(size=golden["train_size"], transform=transform),
        SyntheticCIFAR10(size=512, transform=transform, seed=1),
    )
    trainer = Trainer(
        MLModel(), datasets=datasets, epochs=golden["epochs"], batch_size=32,
        save_history=True, seed=32, scheduler="CosineAnnealingWarmRestarts",
        optimizer="sgd", momentum=0.9, weight_decay=0.0, lr=0.001,
        criterion="cross_entropy", metric="accuracy", pred_function="softmax",
        model_dir=str(tmp_path),
    )
    trainer.fit()

    h, g = trainer.history, golden["history"]
    # The resilience ledger (skipped_steps from the nonfinite guard,
    # rollbacks from rollback-to-last-good — both added after the golden
    # record was captured) is compared only when the record carries it;
    # a healthy run's counts are all zero either way.
    ledger = {"skipped_steps", "rollbacks"}
    assert set(h) - ledger == set(g) - ledger
    assert h["skipped_steps"] == [0] * len(h["epochs"])
    assert h["rollbacks"] == 0
    assert h["epochs"] == g["epochs"]
    # Full per-epoch trajectory, not just the endpoint.
    for k, tol in (("train_loss", 0.2), ("val_loss", 0.2),
                   ("train_metric", 0.1), ("val_metric", 0.1)):
        for ours, theirs in zip(h[k], g[k]):
            assert abs(ours - theirs) < tol, (k, h[k], g[k])

    loaded = load_model(MLModel(), str(tmp_path))
    test_loader = Loader(datasets[1], batch_size=32, shuffle=True)
    test_loss, test_acc = trainer.test(loaded, test_loader)
    assert abs(float(test_loss) - golden["test_loss"]) < 0.2
    assert abs(float(test_acc) - golden["test_accuracy"]) < 0.1
