"""Author + execute the three user-workflow notebooks (SURVEY.md §1 L3).

The reference ships its workflow as notebooks with committed outputs
(01_ML_Training_local / 02_ML_Training_SageMaker_distributed /
03_ML_Testing); this script generates the TPU-native equivalents in
``notebooks/`` and executes them so the committed .ipynb files carry real
outputs — the golden-run record in notebook form.

    python scripts/make_notebooks.py            # author + execute all three
    python scripts/make_notebooks.py --no-exec  # author only

02 executes in CPU-mesh rehearsal mode (8 virtual devices — the analog of
the reference's SageMaker local_gpu/gloo path, SURVEY.md §4); on a real
multi-host TPU slice the same cells run unchanged.
"""

import argparse
import os
import sys

import nbformat as nbf

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(ROOT, "notebooks")


def _rehearsal_cell(default: str, devices: int = 0) -> str:
    """One shared backend-guard cell for all three notebooks.

    ``default`` — "1" for notebooks whose committed form runs rehearsed
    (02: the multi-chip flow needs a virtual mesh in this 1-chip
    environment), "0" for notebooks meant to run on the chip (01/03;
    NB_REHEARSAL=1 is their TPU-down fallback, and the committed outputs
    record whichever backend actually ran — check the cell output).
    ``devices`` > 0 also forces that many virtual host-CPU devices."""
    flags = ""
    if devices:
        flags = f"""
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count={devices}"
    ).strip()"""
    return f"""
import os
# Rehearsal mode (NB_REHEARSAL={default} here): pin the host-CPU backend.
# On a real TPU host set NB_REHEARSAL=0 and the mesh picks up the chips;
# the cell's output below records which backend this notebook really ran.
if os.environ.get("NB_REHEARSAL", "{default}") == "1":
    os.environ["JAX_PLATFORMS"] = "cpu"{flags}
import jax
if os.environ.get("NB_REHEARSAL", "{default}") == "1":
    # Pin through the config too (backends initialize lazily).
    jax.config.update("jax_platforms", "cpu")
jax.devices()
"""


def _nb(cells):
    nb = nbf.v4.new_notebook()
    nb.metadata.kernelspec = {
        "display_name": "Python 3", "language": "python", "name": "python3",
    }
    out = []
    for kind, src in cells:
        cell = (
            nbf.v4.new_markdown_cell(src.strip())
            if kind == "md"
            else nbf.v4.new_code_cell(src.strip())
        )
        out.append(cell)
    nb.cells = out
    return nb


NB01 = [
    ("md", """
# Local training — TPU-native

The `01_ML_Training_local` flow on a TPU chip: build datasets → config →
`Trainer(epochs=6, batch_size=32)` → `fit()` → save/load/plot history →
`load_model` → `test()`.  Same public surface as the reference
(`src/trainer.py:22-311`), internals are one compiled XLA step.
"""),
    ("code", _rehearsal_cell(default="0")),
    ("code", """
from ml_trainer_tpu import (
    MLModel, Loader, Trainer, load_history, load_model, plot_history,
)
from ml_trainer_tpu.data import CIFAR10, SyntheticCIFAR10
from ml_trainer_tpu.utils.functions import custom_pre_process_function
"""),
    ("code", """
# Real CIFAR-10 when the pickle batches are on disk, synthetic otherwise
# (this environment has no egress).
transform = custom_pre_process_function()
try:
    datasets = (CIFAR10("data", train=True, transform=transform),
                CIFAR10("data", train=False, transform=transform))
except FileNotFoundError:
    datasets = (SyntheticCIFAR10(size=2048, transform=transform),
                SyntheticCIFAR10(size=512, transform=transform, seed=1))
len(datasets[0]), len(datasets[1])
"""),
    ("code", """
# Label distribution (the reference notebook's class histogram cell).
import numpy as np
targets = np.asarray(datasets[0].targets)
dict(zip(*np.unique(targets, return_counts=True)))
"""),
    ("code", """
# A few training images after augmentation (reference image-grid cell).
import matplotlib.pyplot as plt
fig, axes = plt.subplots(2, 4, figsize=(8, 4))
for i, ax in enumerate(axes.flat):
    x, y = datasets[0][i]
    ax.imshow((np.asarray(x) * 0.25 + 0.5).clip(0, 1))
    ax.set_title(int(y)); ax.axis("off")
plt.tight_layout()
"""),
    ("code", """
config = {
    "seed": 32,
    "scheduler": "CosineAnnealingWarmRestarts",
    "optimizer": "sgd",
    "momentum": 0.9,
    "weight_decay": 0.0,
    "lr": 0.001,
    "criterion": "cross_entropy",
    "metric": "accuracy",
    "pred_function": "softmax",
    "model_dir": "model_output",
}
trainer = Trainer(MLModel(), datasets=datasets, epochs=6, batch_size=32,
                  save_history=True, **config)
"""),
    ("code", "trainer.fit()"),
    ("code", """
history = load_history("model_output")
{k: (v[-1] if isinstance(v, list) else v) for k, v in history.items()}
"""),
    ("code", "plot_history(history)"),
    ("code", """
loaded = load_model(MLModel(), "model_output")
test_loader = Loader(datasets[1], batch_size=32, shuffle=True)
test_loss, test_acc = trainer.test(loaded, test_loader)
print(f"test loss {test_loss:.4f}  accuracy {test_acc:.4f}")
"""),
]

NB02 = [
    ("md", """
# Distributed data-parallel training — TPU-native

Where the reference provisions SageMaker GPU instances and launches
`main.py` under SMDDP (02 nb cells 4-7), the TPU path is **one command per
TPU VM host** — `jax.distributed` auto-detects the slice and the mesh spans
every chip.  This notebook runs the same cells in CPU-mesh rehearsal mode
(8 virtual devices — the analog of the reference's `local_gpu`/gloo
rehearsal) so the full distributed path executes anywhere; on a TPU slice
the environment cell is a no-op and the mesh picks up the real chips.
"""),
    ("code", _rehearsal_cell(default="1", devices=8)),
    ("code", """
from ml_trainer_tpu import Trainer
from ml_trainer_tpu.data import SyntheticCIFAR10
from ml_trainer_tpu.models import get_model
from ml_trainer_tpu.parallel import rules_for
from ml_trainer_tpu.utils.functions import custom_pre_process_function

transform = custom_pre_process_function()
datasets = (SyntheticCIFAR10(size=4096, transform=transform),
            SyntheticCIFAR10(size=512, transform=transform, seed=1))
"""),
    ("code", """
# The reference's hyperparameters dict (02 nb cell-4), same keys; `backend`
# aliases smddp -> the TPU mesh backend (config.py).
config = {
    "seed": 32,
    "optimizer": "sgd",
    "momentum": 0.9,
    "lr": 0.01,
    "criterion": "cross_entropy",
    "metric": "accuracy",
    "pred_function": "softmax",
    "model_dir": "model_output_distributed",
    "backend": "smddp",
}
"""),
    ("code", """
# Pure DP over every device; set TP=2 for a dp*tp Megatron-sharded mesh —
# the knob the estimator's distribution dict never had.
TP = int(os.environ.get("TP", "1"))
mesh_shape = ({"data": jax.device_count() // TP, "tensor": TP}
              if TP > 1 else None)
sharding_rules = rules_for("resnet18", "tp") if TP > 1 else None
trainer = Trainer(get_model("resnet18"), datasets=datasets, epochs=2,
                  batch_size=256, is_parallel=True, save_history=True,
                  mesh_shape=mesh_shape, sharding_rules=sharding_rules,
                  **config)
trainer.mesh
"""),
    ("code", "trainer.fit()"),
    ("code", """
from ml_trainer_tpu import load_history
history = load_history("model_output_distributed")
{k: (v[-1] if isinstance(v, list) else v) for k, v in history.items()}
"""),
]

NB03 = [
    ("md", """
# Testing / inference-only — TPU-native

The `03_ML_Testing` flow: build a test loader → `load_model` → a
**dataset-less Trainer** (the "Testing only available" path, ref:
`src/trainer.py:66-71`) → `trainer.test(model, loader)`.  `load_model`
also accepts a reference torch `model.pth` (the `module.`-prefix-tolerant
import with OIHW→HWIO conversion, ref: `src/utils/utils.py:15-28`).
"""),
    ("code", _rehearsal_cell(default="0")),
    ("code", """
from ml_trainer_tpu import MLModel, Loader, Trainer, load_model
from ml_trainer_tpu.data import CIFAR10, SyntheticCIFAR10
from ml_trainer_tpu.utils.functions import custom_pre_process_function

transform = custom_pre_process_function()
try:
    val_set = CIFAR10("data", train=False, transform=transform)
except FileNotFoundError:
    val_set = SyntheticCIFAR10(size=512, transform=transform, seed=1)
test_loader = Loader(val_set, batch_size=32, shuffle=True)
"""),
    ("code", 'model = load_model(MLModel(), "model_output")  # .msgpack dir or torch .pth'),
    ("code", "trainer = Trainer(MLModel())  # no datasets: inference-only trainer"),
    ("code", """
test_loss, test_metric = trainer.test(model, test_loader)
print(f"loss {test_loss:.4f}  accuracy {test_metric:.4f}")
"""),
]


def build(execute=True, only=None):
    os.makedirs(OUT, exist_ok=True)
    books = {
        "01_ML_Training_local.ipynb": NB01,
        "02_ML_Training_distributed.ipynb": NB02,
        "03_ML_Testing.ipynb": NB03,
    }
    for name, cells in books.items():
        if only and only not in name:
            continue
        nb = _nb(cells)
        path = os.path.join(OUT, name)
        if execute:
            from nbclient import NotebookClient

            print(f"executing {name} ...", flush=True)
            client = NotebookClient(
                nb, timeout=1800, kernel_name="python3",
                resources={"metadata": {"path": ROOT}},
            )
            client.execute()
        nbf.write(nb, path)
        print(f"wrote {path}")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--no-exec", action="store_true")
    ap.add_argument("--only", default=None, help="substring filter")
    args = ap.parse_args()
    build(execute=not args.no_exec, only=args.only)
    sys.exit(0)
