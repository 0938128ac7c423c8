"""Validate the Pallas flash-attention kernels on REAL TPU hardware.

Closes the round-1 gap "flash kernel has no TPU validation on record"
(tests exercise interpret mode only): runs the Mosaic-compiled forward and
backward kernels on the chip, checks them against the XLA
dot_product_attention path (values + all three input grads), and times
both.  Writes a JSON record to docs/flash_tpu_validation.json so the
result is committed evidence, not a claim.

    python scripts/validate_flash_tpu.py
"""

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from ml_trainer_tpu.ops.attention import dot_product_attention, flash_attention  # noqa: E402


def bench(fn, *args, iters=20):
    from ml_trainer_tpu.utils.profiler import force

    # A lax.scan threading one output element back into the next
    # iteration's input chains every call inside ONE compiled program, so
    # the window times the kernels and not N host dispatches.
    @jax.jit
    def run_n(first, *rest):
        def body(carry, _):
            out = fn(carry, *rest)
            leaf = jnp.ravel(jax.tree.leaves(out)[0])[0]
            return first + (leaf * 0).astype(first.dtype), None

        carry, _ = jax.lax.scan(body, first, None, length=iters)
        return carry

    force(run_n(*args))  # compile + warm
    t0 = time.perf_counter()
    force(run_n(*args))
    return (time.perf_counter() - t0) / iters


def main():
    assert jax.default_backend() == "tpu", (
        f"needs the real TPU, got {jax.default_backend()}"
    )
    record = {"device": str(jax.devices()[0]), "cases": []}
    rng = np.random.default_rng(0)
    for (b, h, s, d), causal in [
        ((2, 4, 512, 64), False),
        ((2, 4, 512, 64), True),
        ((1, 12, 2048, 64), True),   # GPT-2-ish long context
    ]:
        q, k, v = (
            jnp.asarray(rng.normal(size=(b, h, s, d)) * 0.5, jnp.float32)
            for _ in range(3)
        )

        def loss_flash(q, k, v):
            return flash_attention(q, k, v, None, causal).sum()

        def loss_xla(q, k, v):
            return dot_product_attention(q, k, v, causal=causal).sum()

        f_fwd = jax.jit(lambda q, k, v: flash_attention(q, k, v, None, causal))
        x_fwd = jax.jit(lambda q, k, v: dot_product_attention(q, k, v, causal=causal))
        f_grad = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))
        x_grad = jax.jit(jax.grad(loss_xla, argnums=(0, 1, 2)))

        # On TPU the default f32 matmul runs in bf16 passes, so BOTH
        # implementations carry a precision noise floor that grows with S.
        # The honest reference is the XLA path traced under
        # float32-precision matmuls; flash passes if its error against
        # that reference is within a small factor of default-XLA's own —
        # i.e. flash is no less accurate than the baseline it replaces,
        # rather than holding flash to a threshold the baseline itself
        # cannot meet at long S.
        with jax.default_matmul_precision("float32"):
            ref_fwd = jax.jit(
                lambda q, k, v: dot_product_attention(q, k, v, causal=causal)
            )
            ref = ref_fwd(q, k, v)
            gref = jax.jit(jax.grad(loss_xla, argnums=(0, 1, 2)))(q, k, v)
        of, ox = f_fwd(q, k, v), x_fwd(q, k, v)
        fwd_err = float(jnp.max(jnp.abs(of - ref)))
        fwd_err_xla = float(jnp.max(jnp.abs(ox - ref)))
        gf, gx = f_grad(q, k, v), x_grad(q, k, v)
        grad_err = float(
            max(jnp.max(jnp.abs(a - b)) for a, b in zip(gf, gref))
        )
        grad_err_xla = float(
            max(jnp.max(jnp.abs(a - b)) for a, b in zip(gx, gref))
        )
        t_f = bench(f_fwd, q, k, v)
        t_x = bench(x_fwd, q, k, v)
        t_fg = bench(f_grad, q, k, v)
        t_xg = bench(x_grad, q, k, v)
        case = {
            "shape": [b, h, s, d], "causal": causal,
            "fwd_max_abs_err": fwd_err, "grad_max_abs_err": grad_err,
            "fwd_max_abs_err_xla_default": fwd_err_xla,
            "grad_max_abs_err_xla_default": grad_err_xla,
            "fwd_ms": {"flash": round(t_f * 1e3, 3), "xla": round(t_x * 1e3, 3)},
            "grad_ms": {"flash": round(t_fg * 1e3, 3), "xla": round(t_xg * 1e3, 3)},
            "pass": (
                fwd_err < max(2e-3, 3 * fwd_err_xla)
                and grad_err < max(2e-2, 3 * grad_err_xla)
            ),
        }
        record["cases"].append(case)
        print(case, flush=True)
    # Right-padded (kv_lens) path: BERT's inference mask family, fused in
    # the kernel — validated against the XLA path under the equivalent
    # boolean key mask (values + all three grads).
    b, h, s, d = 2, 4, 512, 64
    q, k, v = (
        jnp.asarray(rng.normal(size=(b, h, s, d)) * 0.5, jnp.float32)
        for _ in range(3)
    )
    kv_lens = jnp.asarray([s, 200], jnp.int32)
    bool_mask = (
        jnp.arange(s)[None, None, None, :] < kv_lens[:, None, None, None]
    )

    def loss_flash_pad(q, k, v):
        return flash_attention(q, k, v, kv_lens, False).sum()

    def loss_xla_pad(q, k, v):
        return dot_product_attention(q, k, v, mask=bool_mask).sum()

    of = jax.jit(lambda q, k, v: flash_attention(q, k, v, kv_lens, False))(
        q, k, v
    )
    ox = jax.jit(
        lambda q, k, v: dot_product_attention(q, k, v, mask=bool_mask)
    )(q, k, v)
    gf = jax.jit(jax.grad(loss_flash_pad, argnums=(0, 1, 2)))(q, k, v)
    gx = jax.jit(jax.grad(loss_xla_pad, argnums=(0, 1, 2)))(q, k, v)
    case = {
        "shape": [b, h, s, d], "kv_lens": [int(x) for x in kv_lens],
        "fwd_max_abs_err": float(jnp.max(jnp.abs(of - ox))),
        "grad_max_abs_err": float(
            max(jnp.max(jnp.abs(a - b_)) for a, b_ in zip(gf, gx))
        ),
    }
    case["pass"] = (
        case["fwd_max_abs_err"] < 2e-3 and case["grad_max_abs_err"] < 2e-2
    )
    record["cases"].append(case)
    print(case, flush=True)

    # Off-tile shapes through the padding wrapper (ViT-like S=197, head
    # dim not a multiple of 64) — the Mosaic-compiled padded path must
    # match the XLA path on values and grads.
    from ml_trainer_tpu.ops.attention import _flash_padded

    b, h, s, d = 2, 3, 197, 48
    q, k, v = (
        jnp.asarray(rng.normal(size=(b, h, s, d)) * 0.5, jnp.float32)
        for _ in range(3)
    )

    def loss_flash_off(q, k, v):
        return _flash_padded(q, k, v, None, True, None, 128, 128).sum()

    def loss_xla_off(q, k, v):
        return dot_product_attention(q, k, v, causal=True).sum()

    of = jax.jit(
        lambda q, k, v: _flash_padded(q, k, v, None, True, None, 128, 128)
    )(q, k, v)
    ox = jax.jit(
        lambda q, k, v: dot_product_attention(q, k, v, causal=True)
    )(q, k, v)
    gf = jax.jit(jax.grad(loss_flash_off, argnums=(0, 1, 2)))(q, k, v)
    gx = jax.jit(jax.grad(loss_xla_off, argnums=(0, 1, 2)))(q, k, v)
    # Same noise-floor methodology as the dense cases above: measure both
    # implementations against the float32-precision XLA reference.
    with jax.default_matmul_precision("float32"):
        ref = jax.jit(
            lambda q, k, v: dot_product_attention(q, k, v, causal=True)
        )(q, k, v)
        gref = jax.jit(jax.grad(loss_xla_off, argnums=(0, 1, 2)))(q, k, v)
    fwd_err = float(jnp.max(jnp.abs(of - ref)))
    fwd_err_xla = float(jnp.max(jnp.abs(ox - ref)))
    grad_err = float(max(jnp.max(jnp.abs(a - b_)) for a, b_ in zip(gf, gref)))
    grad_err_xla = float(
        max(jnp.max(jnp.abs(a - b_)) for a, b_ in zip(gx, gref))
    )
    case = {
        "shape": [b, h, s, d], "padded": True, "causal": True,
        "fwd_max_abs_err": fwd_err, "grad_max_abs_err": grad_err,
        "fwd_max_abs_err_xla_default": fwd_err_xla,
        "grad_max_abs_err_xla_default": grad_err_xla,
    }
    case["pass"] = (
        fwd_err < max(2e-3, 3 * fwd_err_xla)
        and grad_err < max(2e-2, 3 * grad_err_xla)
    )
    record["cases"].append(case)
    print(case, flush=True)

    # bf16 — the dtype every north-star model actually trains in.  The
    # kernel accumulates in f32 (scores and (o, m, l) scratch), so the
    # only bf16-specific error is the input/output rounding; tolerance
    # scales accordingly.
    b, h, s, d = 2, 4, 1024, 64
    q, k, v = (
        jnp.asarray(rng.normal(size=(b, h, s, d)) * 0.5, jnp.bfloat16)
        for _ in range(3)
    )

    def loss_flash_bf16(q, k, v):
        return flash_attention(q, k, v, None, True).sum().astype(jnp.float32)

    def loss_xla_bf16(q, k, v):
        return dot_product_attention(q, k, v, causal=True).sum().astype(
            jnp.float32
        )

    of = jax.jit(lambda q, k, v: flash_attention(q, k, v, None, True))(q, k, v)
    ox = jax.jit(lambda q, k, v: dot_product_attention(q, k, v, causal=True))(
        q, k, v
    )
    gf = jax.jit(jax.grad(loss_flash_bf16, argnums=(0, 1, 2)))(q, k, v)
    gx = jax.jit(jax.grad(loss_xla_bf16, argnums=(0, 1, 2)))(q, k, v)
    to_f32 = lambda t: jnp.asarray(t, jnp.float32)  # noqa: E731
    case = {
        "shape": [b, h, s, d], "dtype": "bfloat16", "causal": True,
        "fwd_max_abs_err": float(jnp.max(jnp.abs(to_f32(of) - to_f32(ox)))),
        "grad_max_abs_err": float(
            max(
                jnp.max(jnp.abs(to_f32(a) - to_f32(b_)))
                for a, b_ in zip(gf, gx)
            )
        ),
    }
    # bf16 has ~8 bits of mantissa; two implementations summing ~1K terms
    # in different orders legitimately differ by a few ULPs of the output.
    case["pass"] = (
        case["fwd_max_abs_err"] < 3e-2 and case["grad_max_abs_err"] < 3e-1
    )
    record["cases"].append(case)
    print(case, flush=True)

    # XLA-vs-flash crossover for OFF-TILE sequence lengths: the evidence
    # behind _AUTO_PAD_MIN_SEQ (ops/attention.py).  Each length is one
    # block-boundary + 1, the worst padding ratio for the flash path; the
    # table records fwd+grad time per step for both paths so the auto-pad
    # threshold is a measured choice, not a guess.
    crossover = []
    for s in (129, 257, 513, 1025, 2049):
        b, h, d = 2, 4, 48  # off-tile head dim too: always the padded path
        q, k, v = (
            jnp.asarray(rng.normal(size=(b, h, s, d)) * 0.5, jnp.bfloat16)
            for _ in range(3)
        )

        def loss_pad(q, k, v):
            return _flash_padded(
                q, k, v, None, True, None, 128, 128
            ).sum().astype(jnp.float32)

        def loss_x(q, k, v):
            return dot_product_attention(q, k, v, causal=True).sum().astype(
                jnp.float32
            )

        g_pad = jax.jit(jax.grad(loss_pad, argnums=(0, 1, 2)))
        g_x = jax.jit(jax.grad(loss_x, argnums=(0, 1, 2)))
        row = {
            "seq": s,
            "grad_ms": {
                "flash_padded": round(bench(g_pad, q, k, v) * 1e3, 3),
                "xla": round(bench(g_x, q, k, v) * 1e3, 3),
            },
        }
        row["flash_wins"] = (
            row["grad_ms"]["flash_padded"] < row["grad_ms"]["xla"]
        )
        crossover.append(row)
        print(row, flush=True)
    record["auto_pad_crossover"] = crossover

    record["all_pass"] = all(c["pass"] for c in record["cases"])
    out = os.path.join(ROOT, "docs", "flash_tpu_validation.json")
    with open(out, "w") as f:
        json.dump(record, f, indent=1)
    print(f"-> {out}  all_pass={record['all_pass']}")
    sys.exit(0 if record["all_pass"] else 1)


if __name__ == "__main__":
    main()
