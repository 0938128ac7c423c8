"""ResNet-50 MFU ledger — where the time goes, measured on the chip.

The 19.3% MFU headline row (BASELINE.md) was taken at bs=32 with no
breakdown.  This script measures the full train step at bs=32/128/256
and writes a roofline ledger per batch size:

* achieved FLOP/s vs the chip's bf16 peak (MFU),
* achieved HBM bytes/s vs the chip's peak bandwidth,
* the flops/byte arithmetic intensity of the compiled program,

which together say WHETHER each configuration is MXU-bound or HBM-bound
and how much the MXU fills as the batch grows — the evidence VERDICT r3
item 2 asks for.  Writes docs/resnet50_mfu_ledger.json and prints one
line per row.

Beside the analytic cross-check, the ledger now carries per-kernel
before/after columns from the ``ops/kernels/`` microbench artifact
(``docs/kernels_cpu.json``, regenerated with ``bench.py --kernels``):
reference-vs-fused microseconds and parity per kernel, so the roofline
rows and the kernel-level wins land in one document.  ``--kernels-only``
prints just that table (no chip needed).

    python scripts/mfu_ledger.py [--model resnet50] [--batches 32,128,256]
    python scripts/mfu_ledger.py --kernels-only
"""

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

def chip_peaks():
    """(peak FLOP/s, peak HBM B/s, matched-generation label).

    Both peak tables live in the telemetry spine (telemetry/flops.py) —
    one owner, so the ledger, bench.py, and the trainer's live MFU line
    can never disagree by hardware generation.  An unrecognized device
    kind raises there."""
    from ml_trainer_tpu.telemetry.flops import (
        chip_generation,
        chip_peak_flops,
        chip_peak_hbm_bytes,
    )

    return chip_peak_flops(), chip_peak_hbm_bytes(), chip_generation()


def measure(model_name: str, batch: int) -> dict:
    import optax

    from ml_trainer_tpu.models import get_model
    from ml_trainer_tpu.ops import get_criterion, get_optimizer
    from ml_trainer_tpu.train_state import TrainState
    from ml_trainer_tpu.utils.profiler import force

    model = get_model(model_name, dtype=jnp.bfloat16)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(batch, 224, 224, 3)), jnp.bfloat16)
    y = jnp.asarray(rng.integers(0, 10, batch), jnp.int32)
    jax.block_until_ready((x, y))
    variables = jax.jit(model.init, static_argnames="train")(
        {"params": jax.random.PRNGKey(0)}, x, train=False
    )
    params = variables["params"]
    tx = get_optimizer("adamw", 1e-4)
    criterion = get_criterion("cross_entropy")
    state = TrainState(
        step=jnp.zeros((), jnp.int32), params=params,
        opt_state=jax.jit(tx.init)(params),
        batch_stats=variables.get("batch_stats", {}),
        rng=jax.random.PRNGKey(1),
    )

    has_bs = bool(variables.get("batch_stats", {}))

    def step(state, x, y):
        def loss_fn(p):
            if not has_bs:  # ViT/BERT-class: no BatchNorm collection
                out = model.apply({"params": p}, x, train=True)
                return criterion(out, y), state.batch_stats
            out, mut = model.apply(
                {"params": p, "batch_stats": state.batch_stats},
                x, train=True, mutable=["batch_stats"],
            )
            return criterion(out, y), mut["batch_stats"]

        (loss, new_bs), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state.params
        )
        updates, opt_state = tx.update(grads, state.opt_state, state.params)
        return state.replace(
            step=state.step + 1,
            params=optax.apply_updates(state.params, updates),
            opt_state=opt_state,
            batch_stats=new_bs,
        ), loss

    compiled = jax.jit(step, donate_argnums=0).lower(state, x, y).compile()
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else (cost or {})
    flops = float(cost.get("flops", 0.0))
    bytes_accessed = float(cost.get("bytes accessed", 0.0))

    # Timing: chain iterations so in-order completion is provable (the
    # platform's block_until_ready can return early — utils/profiler.py).
    iters = 20
    for _ in range(3):
        state, loss = compiled(state, x, y)
    force(loss)
    t0 = time.perf_counter()
    for _ in range(iters):
        state, loss = compiled(state, x, y)
    force(loss)
    dt = (time.perf_counter() - t0) / iters

    peak_flops, peak_bw, hbm_generation = chip_peaks()
    achieved_flops = flops / dt if flops else None
    achieved_bw = bytes_accessed / dt if bytes_accessed else None
    # Analytic cross-checks (telemetry/flops.py + memory.py): when the
    # measured XLA number and the formula disagree wildly, one of them
    # is lying about the workload — worth seeing in the artifact.  The
    # memory column puts the ledger's peak prediction beside the chip
    # allocator's real peak, per batch size.
    from ml_trainer_tpu.telemetry import memory as _memory
    from ml_trainer_tpu.telemetry.flops import train_step_flops

    analytic = train_step_flops(model, (batch, 224, 224, 3))
    mem_live = _memory.live_memory_snapshot()
    mem_ledger = _memory.bench_step_ledger(state, model, (x, y))
    row = {
        "model": model_name,
        "batch": batch,
        "hbm_peak_generation": hbm_generation,
        "step_ms": round(dt * 1e3, 3),
        "samples_per_sec": round(batch / dt, 1),
        "flops_per_step": flops,
        "flops_per_step_analytic": analytic,
        "bytes_per_step": bytes_accessed,
        "arith_intensity_flops_per_byte": (
            round(flops / bytes_accessed, 1) if bytes_accessed else None
        ),
        "peak_hbm_bytes": int(mem_live["max_peak_bytes_in_use"]),
        "analytic_hbm_bytes": int(mem_ledger.peak_bytes()),
        "analytic_hbm_resident_bytes": int(mem_ledger.resident_bytes()),
        "mfu": round(achieved_flops / peak_flops, 4) if achieved_flops else None,
        "hbm_utilization": (
            round(achieved_bw / peak_bw, 4) if achieved_bw else None
        ),
        # The machine balance of the chip: programs below this intensity
        # cannot reach peak FLOP/s no matter how well they schedule.
        "machine_balance_flops_per_byte": round(peak_flops / peak_bw, 1),
        "backend": jax.default_backend(),
    }
    # The verdict: which wall is closer.
    if row["mfu"] is not None and row["hbm_utilization"] is not None:
        row["bound"] = (
            "hbm" if row["hbm_utilization"] > row["mfu"] else "mxu"
        )
    return row


def kernel_columns(path=None):
    """Per-kernel before/after columns from the ``ops/kernels/``
    microbench artifact (``bench.py --kernels``): one row per kernel —
    reference (pre-kernel program) vs fused dispatch microseconds, the
    speedup, and the bit-parity pin — plus the engine-level decode
    step-time pair.  Returns None when the artifact is absent."""
    path = path or os.path.join(ROOT, "docs", "kernels_cpu.json")
    try:
        data = json.load(open(path))
    except (OSError, ValueError):
        return None
    rows = {}
    for name, row in (data.get("kernels") or {}).items():
        rows[name] = {
            "before_us": row.get("reference_us"),
            "after_us": row.get("kernel_us"),
            "speedup": row.get("speedup"),
            "parity": bool(
                row.get("interpret_parity") or row.get("trajectory_parity")
            ),
        }
    decode = data.get("decode") or {}
    return {
        "artifact": os.path.basename(path),
        "measured_backend": data.get("backend"),
        "rows": rows,
        "decode_step": {
            "before_us": decode.get("gather_step_us"),
            "after_us": decode.get("kernel_step_us"),
            "speedup": decode.get("kernel_vs_gather"),
        },
        "note": data.get("note"),
    }


def print_kernel_columns(cols) -> None:
    if not cols:
        print("# kernels: no docs/kernels_cpu.json — run "
              "`python bench.py --kernels` first", flush=True)
        return
    for name, row in cols["rows"].items():
        print(
            f"# kernel {name:>16} before {row['before_us']:>9,.1f} us  "
            f"after {row['after_us']:>9,.1f} us  x{row['speedup']:.2f}  "
            f"parity={'ok' if row['parity'] else 'BROKEN'}  "
            f"({cols['measured_backend']})", flush=True,
        )
    d = cols["decode_step"]
    if d.get("before_us"):
        print(
            f"# kernel {'decode_step':>16} before {d['before_us']:>9,.1f}"
            f" us  after {d['after_us']:>9,.1f} us  x{d['speedup']:.2f}  "
            f"(real engine)", flush=True,
        )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="resnet50")
    ap.add_argument("--batches", default="32,128,256")
    ap.add_argument("--kernels-artifact", default=None, metavar="PATH",
                    help="kernel microbench artifact to read (default "
                    "docs/kernels_cpu.json)")
    ap.add_argument("--kernels-only", action="store_true",
                    help="print only the per-kernel before/after columns "
                    "from the kernels artifact and exit (no chip needed)")
    args = ap.parse_args()
    kernels = kernel_columns(args.kernels_artifact)
    if args.kernels_only:
        print_kernel_columns(kernels)
        sys.exit(0 if kernels else 1)
    assert jax.default_backend() == "tpu", (
        f"ledger needs the chip, got {jax.default_backend()}"
    )
    rows = []
    for b in (int(s) for s in args.batches.split(",")):
        row = measure(args.model, b)
        rows.append(row)
        print(json.dumps(row), flush=True)
    print_kernel_columns(kernels)
    out = os.path.join(ROOT, "docs", f"{args.model}_mfu_ledger.json")
    with open(out, "w") as fp:
        json.dump(
            {"device": str(jax.devices()[0]), "rows": rows,
             "kernels": kernels},
            fp, indent=1,
        )
    print(f"-> {out}")


if __name__ == "__main__":
    main()
