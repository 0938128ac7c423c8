"""Flash-attention block sweep on the chip, a kernel at a time.

``ops/attention.py`` chooses ``block_q`` / ``block_k`` of its three kernels
from the shape (``_flash_blocks``), under caps that THIS script measured
(PERF.md section 6 has the table).  A geometry is
``block_q x block_k x sub_k``: what a grid step holds of q and of K/V, and
how many keys one step of the sweep inside it takes.  Each geometry runs
forward, dQ and dK/dV under the profiler and reads each kernel's own device
time by its name in the trace, so a kernel's time holds nothing of XLA's
layout copies round it; ``chosen`` is what the chooser picks.  Beside them
two yardsticks, each by the whole device time of its calls: JAX's own
Pallas TPU kernel and the XLA path (at 4 rows of the batch: the scores of
more do not fit).  Results go to standard output and
``chiprun_out/flash_tune.json``.

    python scripts/flash_tune.py
    python scripts/flash_tune.py --shape 1,20,512,64 --fwd-only \
        --geometries 128x128x128,256x512x512

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
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

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


def kernel_ms(fn, args, iters=5, carry=None):
    """Device milliseconds a call of ``fn``, by operation name, from a
    profiler trace of ``iters`` calls (the first, untraced, compiles).
    Where ``fn`` donates arguments, ``carry(result, args)`` gives the next
    call's."""
    import tempfile

    from benchmark.trace_reduce import find_xplane, load_xplane
    from ml_trainer_tpu.utils.profiler import force, trace

    def call(args):
        out = fn(*args)
        force(out)
        return carry(out, args) if carry else args

    args = call(args)
    with tempfile.TemporaryDirectory() as logdir:
        with trace(logdir):
            for _ in range(iters):
                args = call(args)
        events = next(iter(load_xplane(find_xplane(logdir))["devices"].values()))
    total = {}
    for name, _, dur in events:
        name = name.split("|")[0].split(".")[0]
        total[name] = total.get(name, 0.0) + dur / 1e6 / iters
    return total


def flash_fns(causal, block_q, block_k, fwd_only):
    """(forward, backward) over the module's own two entry points: the
    backward alone is the two backward kernels and nothing else."""
    from ml_trainer_tpu.ops import attention as A

    options = dict(causal=causal, block_q=block_q, block_k=block_k,
                   interpret=False)

    def fwd(q, k, v):
        return A._flash_forward(
            q, k, v, None, scale=q.shape[-1] ** -0.5, **options)

    def bwd(q, k, v, out, lse, g):
        return A._flash_backward(
            q, k, v, None, out, lse, g, scale=q.shape[-1] ** -0.5, **options)

    return jax.jit(fwd), None if fwd_only else jax.jit(bwd)


def yardsticks(q, k, v, fwd_only):
    """JAX's own kernel at its largest blocks that divide, and the XLA path
    on 4 rows: whole device time of a call, every operation counted."""
    from jax.experimental.pallas.ops.tpu import flash_attention as jfa

    from ml_trainer_tpu.ops.attention import dot_product_attention

    s, d = q.shape[2], q.shape[3]
    blk = max(m for m in (128, 256, 512) if s % m == 0)
    sizes = jfa.BlockSizes(
        block_q=blk, block_k_major=blk, block_k=blk, block_b=1,
        block_q_major_dkv=blk, block_k_major_dkv=blk, block_k_dkv=blk,
        block_q_dkv=blk, block_k_major_dq=blk, block_k_dq=blk,
        block_q_dq=blk)

    def jax_kernel(q, k, v):
        return jfa.flash_attention(
            q, k, v, causal=True, sm_scale=d ** -0.5, block_sizes=sizes)

    def xla(q, k, v):
        return dot_product_attention(q, k, v, causal=True)

    rows = []
    for name, fn, args in (
        (f"jax_pallas_flash_{blk}", jax_kernel, (q, k, v)),
        ("xla_4_rows", xla, (q[:4], k[:4], v[:4])),
    ):
        row = {"yardstick": name, "rows": int(args[0].shape[0])}
        try:
            row["fwd_ms"] = round(sum(kernel_ms(jax.jit(fn), args).values()), 3)
            if not fwd_only:
                grad = jax.jit(jax.grad(
                    lambda *a, fn=fn: fn(*a).astype(jnp.float32).sum(),
                    argnums=(0, 1, 2)))
                row["fwd_bwd_ms"] = round(
                    sum(kernel_ms(grad, args).values()), 3)
        except Exception as e:  # refused by Mosaic or out of memory
            row["error"] = str(e).splitlines()[0][:160]
        rows.append(row)
        print(json.dumps(row), flush=True)
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", default="32,12,1024,64",
                    help="B,H,S,D (default: gpt2-124m.pretrain-1k's)")
    ap.add_argument("--geometries",
                    default="128x128x128,256x256x256,512x512x512,"
                    "256x1024x512,512x1024x512,512x1024x256,512x1024x1024,"
                    "1024x1024x512",
                    help="block_q x block_k x sub_k, comma-separated")
    ap.add_argument("--fwd-only", action="store_true",
                    help="a serving shape: the forward alone")
    ap.add_argument("--no-yardsticks", action="store_true")
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
    from ml_trainer_tpu.ops import attention as A

    b, h, s, d = (int(x) for x in args.shape.split(","))
    dtype = jnp.dtype(args.dtype)
    rng = np.random.default_rng(0)
    q, k, v, g = (
        jnp.asarray(rng.normal(size=(b, h, s, d)) * 0.5, dtype)
        for _ in range(4)
    )
    chosen, chosen_sub = A._flash_blocks(s, s, d, dtype), A._SUB_K
    geometries = [("chosen", chosen, chosen_sub)] + [
        (geo, (bq, bk), sub)
        for geo in args.geometries.split(",")
        for bq, bk, sub in [map(int, geo.split("x"))]
        if s % bq == 0 and s % bk == 0 and bk % sub == 0
    ]
    names = {"flash_fwd": "fwd_ms", "flash_bwd_dq": "dq_ms",
             "flash_bwd_dkv": "dkv_ms"}
    rows = []
    for geo, blocks, sub in geometries:
        A._SUB_K = sub  # read when the call is traced
        row = {"geometry": geo, "blocks": blocks, "sub_k": sub}
        try:
            fwd, bwd = flash_fns(True, *blocks, args.fwd_only)
            times = kernel_ms(fwd, (q, k, v))
            if bwd is not None:
                out, lse = fwd(q, k, v)
                times.update(kernel_ms(bwd, (q, k, v, out, lse, g)))
            row.update({col: round(times[name], 3)
                        for name, col in names.items() if name in times})
        except Exception as e:  # geometry rejected by Mosaic (VMEM etc.)
            row["error"] = str(e).splitlines()[0][:160]
        rows.append(row)
        print(json.dumps(row), flush=True)
    A._SUB_K = chosen_sub

    record = {
        "device": str(jax.devices()[0]),
        "shape": [b, h, s, d], "dtype": str(dtype), "causal": True,
        "rows": rows, "chosen": chosen,
        "yardsticks": [] if args.no_yardsticks else yardsticks(
            q, k, v, args.fwd_only),
    }
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    out = os.path.join(out_dir, "flash_tune.json")
    history = json.load(open(out)) if os.path.exists(out) else []
    with open(out, "w") as fp:
        json.dump(history + [record], fp, indent=1)
    print(f"-> {out}")


if __name__ == "__main__":
    main()
