"""Flash-attention block-size sweep on the chip.

The kernel's ``block_q``/``block_k`` default to 128×128 — chosen for
tile legality, never measured.  This sweeps the grid over the GPT-2
north-star shape (and any ``--shape``), timing forward and
forward+backward per geometry, and records the table + the best choice
to docs/flash_block_tune.json.  If a non-default geometry wins by more
than ~5%, ops/attention.py's defaults should follow the data.

    python scripts/flash_tune.py
    python scripts/flash_tune.py --shape 8,12,1024,64 --blocks 128,256,512

``--paged`` sweeps the paged-attention DECODE kernel instead
(ops/kernels/paged_attention.py): the tunable geometry there is the
page size — each grid step fetches one [page, D] K/V block per
BlockSpec index_map, so the page size IS the kernel's block height.
Each row fixes the total context L and varies page_size (the pool's
``kv_page_size`` knob), timing the fused kernel against the gather+
attention reference at batch-decode shape; the table + best page size
land in docs/paged_decode_tune.json.

    python scripts/flash_tune.py --paged
    python scripts/flash_tune.py --paged --paged-shape 8,12,64,1024 \
        --page-sizes 8,16,32,64,128
"""

import argparse
import itertools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from ml_trainer_tpu.ops.attention import flash_attention  # noqa: E402
# ONE definition of the data-dependent chained timing harness (in-order
# completion cannot be assumed on this platform): reuse it, never fork it.
from validate_flash_tpu import bench  # noqa: E402


def run_paged(args) -> None:
    """Page-size sweep for the fused paged-attention decode kernel at a
    batch-decode shape: one [B, H, D] query row against L cached tokens
    scattered across pages.  Rows without the chip never run (the
    caller asserts the backend) — off-TPU parity is tests/'s job."""
    from ml_trainer_tpu.ops.kernels.paged_attention import (
        paged_attention,
        paged_attention_reference,
    )

    b, h, d, L = (int(x) for x in args.paged_shape.split(","))
    dtype = jnp.dtype(args.dtype)
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(b, h, d)) * 0.5, dtype)
    lengths = jnp.asarray(
        rng.integers(1, L + 1, size=b), jnp.int32
    ).at[0].set(L)  # one full row so every sweep touches all pages

    rows = []
    for ps in (int(x) for x in args.page_sizes.split(",")):
        if L % ps:
            continue
        P = L // ps
        n_pages = b * P + 1  # + trash page 0
        k_pool, v_pool = (
            jnp.asarray(rng.normal(size=(n_pages, h, ps, d)) * 0.5, dtype)
            for _ in range(2)
        )
        table = jnp.asarray(
            1 + rng.permutation(n_pages - 1).reshape(b, P), jnp.int32
        )

        def kern(q, kp, vp, tb, ln):
            return paged_attention(q, kp, vp, tb, ln,
                                   implementation="pallas")

        def ref(q, kp, vp, tb, ln):
            return paged_attention_reference(q, kp, vp, tb, ln)

        try:
            row = {
                "page_size": ps, "pages_per_seq": P,
                "kernel_ms": round(bench(
                    jax.jit(kern), q, k_pool, v_pool, table, lengths
                ) * 1e3, 3),
                "reference_ms": round(bench(
                    jax.jit(ref), q, k_pool, v_pool, table, lengths
                ) * 1e3, 3),
            }
            row["speedup"] = round(
                row["reference_ms"] / max(row["kernel_ms"], 1e-9), 3
            )
        except Exception as e:  # geometry rejected by Mosaic (VMEM etc.)
            row = {"page_size": ps, "pages_per_seq": P,
                   "error": str(e).splitlines()[0][:160]}
        rows.append(row)
        print(json.dumps(row), flush=True)

    timed = [r for r in rows if "kernel_ms" in r]
    best = min(timed, key=lambda r: r["kernel_ms"]) if timed else None
    record = {
        "device": str(jax.devices()[0]),
        "shape": {"batch": b, "heads": h, "head_dim": d, "context": L},
        "dtype": str(dtype),
        "rows": rows, "best": best,
    }
    out = os.path.join(ROOT, "docs", "paged_decode_tune.json")
    with open(out, "w") as fp:
        json.dump(record, fp, indent=1)
    print(f"-> {out} best={best}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", default="8,12,1024,64",
                    help="B,H,S,D (default: the GPT-2 124M bench shape)")
    ap.add_argument("--blocks", default="128,256,512")
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--paged", action="store_true",
                    help="sweep the paged-attention decode kernel's page "
                    "size instead of the flash block geometry")
    ap.add_argument("--paged-shape", default="8,12,64,1024",
                    help="B,H,D,L for --paged (default: GPT-2 124M "
                    "decode at 1024 cached tokens)")
    ap.add_argument("--page-sizes", default="8,16,32,64,128",
                    help="page sizes swept by --paged")
    args = ap.parse_args()
    assert jax.default_backend() == "tpu", (
        f"needs the chip, got {jax.default_backend()}"
    )
    if args.paged:
        run_paged(args)
        return
    b, h, s, d = (int(x) for x in args.shape.split(","))
    blocks = [int(x) for x in args.blocks.split(",")]
    dtype = jnp.dtype(args.dtype)
    rng = np.random.default_rng(0)
    q, k, v = (
        jnp.asarray(rng.normal(size=(b, h, s, d)) * 0.5, dtype)
        for _ in range(3)
    )

    rows = []
    for bq, bk in itertools.product(blocks, blocks):
        if s % bq or s % bk:
            continue

        def fwd(q, k, v, _bq=bq, _bk=bk):
            return flash_attention(q, k, v, None, True, None, _bq, _bk)

        def loss(q, k, v, _bq=bq, _bk=bk):
            return flash_attention(
                q, k, v, None, True, None, _bq, _bk
            ).sum().astype(jnp.float32)

        grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        try:
            row = {
                "block_q": bq, "block_k": bk,
                "fwd_ms": round(bench(jax.jit(fwd), q, k, v) * 1e3, 3),
                "fwd_bwd_ms": round(bench(grad, q, k, v) * 1e3, 3),
            }
        except Exception as e:  # geometry rejected by Mosaic (VMEM etc.)
            row = {"block_q": bq, "block_k": bk,
                   "error": str(e).splitlines()[0][:160]}
        rows.append(row)
        print(json.dumps(row), flush=True)

    timed = [r for r in rows if "fwd_bwd_ms" in r]
    best = min(timed, key=lambda r: r["fwd_bwd_ms"]) if timed else None
    record = {
        "device": str(jax.devices()[0]),
        "shape": [b, h, s, d], "dtype": str(dtype),
        "rows": rows, "best": best,
        "default": {"block_q": 128, "block_k": 128},
    }
    out = os.path.join(ROOT, "docs", "flash_block_tune.json")
    with open(out, "w") as fp:
        json.dump(record, fp, indent=1)
    print(f"-> {out} best={best}")


if __name__ == "__main__":
    main()
