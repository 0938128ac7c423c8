"""Decode-attention block sweep on the chip.

``ops/kernels/decode_attention.py`` chooses how many cache positions a grid
step holds from the shape and dtype (``_decode_block``), under a cap that
THIS script measured (PERF.md section 6, PR 30 has the table).  At each of
the two serving shapes the benchmark runs (``gpt2-large``'s 32 slots of 20
heads of 64 over 1,024 positions; ``k-exaone``'s 64 slots, 64 query heads
over 8 key-value heads of 128, 2,048 positions) it draws the rows' lengths
from the steady state of the cell's own traffic (a request of prompt ``P``
and output ``O`` is met with probability ``O`` at a length uniform in
``P + 1 .. P + O``), runs the engine's XLA path and the kernel at each block
under the profiler and reads device time by operation name: the kernel's
own, and everything the call runs (the work list's small fusions beside it);
then a layer's whole decode step with the caches donated, as the write
kernel then the read ("pair") and as the one call that appends while it
reads ("fused", PR 35).
Beside each time: the share of the pool the block fetches
(``attended_positions``), the bytes a second that makes, and the largest
difference from the reference.  Results go to standard output and
``chiprun_out/decode_attention_tune.json``.

``--latent B,H,L,D,C`` sweeps ``latent_decode_attention`` instead, at
``kimi-linear-48b-ep8``'s latent pool by default (128 slots, 32 query heads
over one key-value head, 4,096 rows of 512 + 64 values, the first 512 the
values), lengths from ``reason-4k``'s steady state: XLA's masked read of
the pool as keys and values, then the kernel at each block, each alone and
after the latent row's write (``slot_row_write``, the pool donated) as the
layer's decode step makes them.

    python scripts/decode_attention_tune.py
    python scripts/decode_attention_tune.py --shapes 32,20,20,1024,64 \
        --blocks 128,256
    python scripts/decode_attention_tune.py --latent 128,32,4096,576,512 \
        --blocks 128,256,512
"""

import argparse
import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from flash_tune import kernel_ms  # noqa: E402

TRAFFIC = {1024: "batch-decode", 2048: "sharegpt-2k", 4096: "reason-4k"}


def steady_lengths(L: int, rows: int, rng) -> np.ndarray:
    """``rows`` cache lengths as the cell whose cache has ``L`` positions
    meets them on a decode step."""
    from benchmark.loadgen import base_block

    with open(os.path.join(
            ROOT, "benchmark", "traffic", TRAFFIC[L] + ".json")) as fp:
        base = base_block(json.load(fp))
    prompts, outputs = base["prompt_len"], base["output_len"]
    pick = rng.choice(len(outputs), size=rows, p=outputs / outputs.sum())
    return prompts[pick] + 1 + (rng.random(rows) * outputs[pick]).astype(int)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="32,20,20,1024,64;64,64,8,2048,128",
                    help="B,H,G,L,D; ... (default: the two serving cells')")
    ap.add_argument("--blocks", default="128,256,512,1024")
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--latent", default=None,
                    help="B,H,L,D,C: sweep latent_decode_attention instead")
    args = ap.parse_args()
    assert jax.default_backend() == "tpu", (
        f"needs the chip, got {jax.default_backend()}"
    )
    if args.latent:
        record = latent_sweep(
            *(int(x) for x in args.latent.split(",")),
            [int(x) for x in args.blocks.split(",")], jnp.dtype(args.dtype))
        save([record])
        return
    # the package exports a function of the module's name: import by path
    from ml_trainer_tpu.ops.kernels.decode_attention import (
        _decode_attention_pallas,
        _decode_block,
        attended_positions,
        decode_attention_reference,
    )
    from ml_trainer_tpu.ops.kernels.slot_cache_write import slot_cache_write

    dtype = jnp.dtype(args.dtype)
    records = []
    for shape in args.shapes.split(";"):
        b, h, g, L, d = (int(x) for x in shape.split(","))
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.normal(size=(b, h, 1, d)) * 0.5, dtype)
        k, v = (jnp.asarray(rng.normal(size=(b, g, L, d)) * 0.5, dtype)
                for _ in range(2))
        lens_np = np.minimum(steady_lengths(L, b, rng), L)
        lens = jnp.asarray(lens_np, jnp.int32)
        pool_bytes = 2 * b * g * L * d * dtype.itemsize
        reference = jax.jit(decode_attention_reference)
        want = np.asarray(reference(q, k, v, lens), np.float32)
        times = kernel_ms(reference, (q, k, v, lens))
        row = {"shape": [b, h, g, L, d], "path": "xla",
               "fill_pct": round(100 * lens_np.sum() / (b * L), 2),
               "call_ms": round(sum(times.values()), 4), "ops": {
                   n: round(t, 4) for n, t in sorted(
                       times.items(), key=lambda kv: -kv[1])[:4]}}
        row["GB_per_s"] = round(pool_bytes / row["call_ms"] / 1e6, 1)
        print(json.dumps(row), flush=True)
        rows = [row]
        chosen = _decode_block(g, L, d, dtype)
        # The decode step of a layer, the caches donated as the engine's
        # program donates them: the write kernel then the read ("pair", the
        # step before PR 35), and the one call that appends while it reads
        # ("fused").  A step writes at the row's length less one, so both
        # attend the same positions as the read alone above.
        k_new, v_new = (jnp.asarray(rng.normal(size=(b, g, 1, d)) * 0.5, dtype)
                        for _ in range(2))

        def pair(q, k_new, v_new, k, v, pos, block):
            k, v = slot_cache_write(k, v, k_new, v_new, pos,
                                    implementation="pallas")
            return _decode_attention_pallas(
                q, k, v, pos + 1, block, False), k, v

        def fused(q, k_new, v_new, k, v, pos, block):
            return _decode_attention_pallas(
                q, k, v, pos + 1, block, False, (k_new, v_new))

        def carry(out, args):          # the caches a call donated
            return args[:3] + tuple(out[1:]) + args[5:]

        for block in [0] + [int(x) for x in args.blocks.split(",")]:
            if block and L % block:
                continue
            at = block or chosen
            share = float(attended_positions(lens_np, L, at).sum()) / (b * L)
            row = {"shape": [b, h, g, L, d], "path": "kernel", "block": at,
                   "chosen": not block, "fetched_pct": round(100 * share, 2)}
            try:
                fn = jax.jit(lambda q, k, v, n, block=block:
                             _decode_attention_pallas(
                                 q, k, v, n, block, False))
                got = np.asarray(fn(q, k, v, lens), np.float32)
                times = kernel_ms(fn, (q, k, v, lens))
                row.update(
                    kernel_ms=round(times.get("decode_attention", 0.0), 4),
                    call_ms=round(sum(times.values()), 4),
                    max_abs_diff=float(np.abs(got - want).max()),
                    ops={n: round(t, 4) for n, t in sorted(
                        times.items(), key=lambda kv: -kv[1])[:5]})
                row["GB_per_s"] = round(
                    share * pool_bytes / row["kernel_ms"] / 1e6, 1)
                fixed, outs = (q, k_new, v_new), {}
                for name, step in (("pair", pair), ("fused", fused)):
                    fn = jax.jit(functools.partial(step, block=block),
                                 donate_argnums=(3, 4))
                    outs[name] = fn(*fixed, k + 0, v + 0, lens - 1)
                    times = kernel_ms(
                        fn, fixed + (k + 0, v + 0, lens - 1), carry=carry)
                    row[name] = {
                        "call_ms": round(sum(times.values()), 4),
                        "ops": {n: round(t, 4) for n, t in sorted(
                            times.items(), key=lambda kv: -kv[1])[:5]}}
                row["fused"]["max_abs_diff_from_pair"] = [
                    float(jnp.abs(x.astype(jnp.float32)
                                  - y.astype(jnp.float32)).max())
                    for x, y in zip(outs["fused"], outs["pair"])]
            except Exception as e:  # refused by Mosaic (VMEM and the like)
                row["error"] = str(e).splitlines()[0][:200]
            rows.append(row)
            print(json.dumps(row), flush=True)
        records.append({"device": str(jax.devices()[0]), "dtype": str(dtype),
                        "lengths": lens_np.tolist(), "rows": rows})
    save(records)


def latent_sweep(b, h, L, d, c, blocks, dtype):
    """XLA's read of the latent pool and the kernel at each block, alone
    and after the row's write; returns the record ``save`` keeps."""
    from ml_trainer_tpu.ops.kernels.decode_attention import attended_positions
    from ml_trainer_tpu.ops.kernels.latent_decode_attention import (
        _latent_decode_attention_pallas,
        latent_decode_attention_reference,
    )
    from ml_trainer_tpu.ops.kernels.slot_cache_write import slot_row_write

    rng = np.random.default_rng(0)
    scale = 192 ** -0.5                       # kimi-linear: nope + rope
    q = jnp.asarray(rng.normal(size=(b, h, 1, d)) * 0.5, dtype)
    pool = jnp.asarray(rng.normal(size=(b, 1, L, d)) * 0.5, dtype)
    new = jnp.asarray(rng.normal(size=(b, 1, 1, d)) * 0.5, dtype)
    lens_np = np.minimum(steady_lengths(L, b, rng), L)
    lens = jnp.asarray(lens_np, jnp.int32)
    pool_bytes = b * L * d * dtype.itemsize
    paths = {"xla": lambda q, p, n: latent_decode_attention_reference(
        q, p, n, scale, c)}
    paths.update({f"kernel_{block}": functools.partial(
        lambda q, p, n, block: _latent_decode_attention_pallas(
            q, p, n, scale, c, block, False), block=block)
        for block in blocks if L % block == 0})
    want = np.asarray(jax.jit(paths["xla"])(q, pool, lens), np.float32)
    rows = []
    for name, read in paths.items():
        block = int(name.split("_")[1]) if "_" in name else L
        share = float(attended_positions(lens_np, L, block).sum()) / (b * L)
        row = {"shape": [b, h, L, d, c], "path": name,
               "fill_pct": round(100 * lens_np.sum() / (b * L), 2),
               "fetched_pct": round(100 * share, 2)}
        try:
            fn = jax.jit(read)
            got = np.asarray(fn(q, pool, lens), np.float32)
            times = kernel_ms(fn, (q, pool, lens))
            row.update(
                kernel_ms=round(times.get("latent_decode_attention", 0.0), 4),
                call_ms=round(sum(times.values()), 4),
                max_abs_diff=float(np.abs(got - want).max()),
                ops={n: round(t, 4) for n, t in sorted(
                    times.items(), key=lambda kv: -kv[1])[:5]})
            row["GB_per_s"] = round(
                share * pool_bytes * (2 if name == "xla" else 1)
                / row["call_ms"] / 1e6, 1)

            def step(pool, q, new, pos, read=read):
                pool = slot_row_write(pool, new, pos, implementation="pallas")
                return read(q, pool, pos + 1), pool

            fn = jax.jit(step, donate_argnums=(0,))
            times = kernel_ms(fn, (pool + 0, q, new, lens - 1),
                              carry=lambda out, a: (out[1],) + a[1:])
            row["step_ms"] = round(sum(times.values()), 4)
        except Exception as e:  # refused by Mosaic (VMEM and the like)
            row["error"] = str(e).splitlines()[0][:200]
        rows.append(row)
        print(json.dumps(row), flush=True)
    return {"device": str(jax.devices()[0]), "dtype": str(dtype),
            "lengths": lens_np.tolist(), "rows": rows}


def save(records):
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    out = os.path.join(out_dir, "decode_attention_tune.json")
    history = json.load(open(out)) if os.path.exists(out) else []
    with open(out, "w") as fp:
        json.dump(history + records, fp, indent=1)
    print(f"-> {out}")


if __name__ == "__main__":
    main()
