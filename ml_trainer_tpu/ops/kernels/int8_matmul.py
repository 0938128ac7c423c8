"""Int8 weight-quantized matmul with per-output-channel scales.

Backs the opt-in quantized decode path (``Server(quant_int8=True)``):
the four LoRA-target Dense projections (qkv / proj / fc_in / fc_out)
store int8 weights + f32 per-column scales in a ``"quant"`` variable
collection built host-side by :func:`quantize_tree` — param paths and
checkpoints are untouched, and prefill stays fp32 (only the decode
model clone flips the knob).  Embeddings and the tied LM head stay
fp32 by design: they dominate the quality budget and are one matmul
each per step.

This is NOT a bit-parity path against fp32 — quantization changes the
math by construction.  The discipline here is:

* the lax reference and the Pallas kernel ARE pinned bit-for-bit
  against each other in interpret mode (tests/test_kernels.py): both
  upcast x and the int8 weights to f32, run the full-K dot, and apply
  the column scales to the f32 product;
* fp32 quality is gated end-to-end instead (argmax agreement >= 99.5%
  and bounded logit error, tests/test_kernels.py).

Symmetric per-output-channel quantization: ``scale[n] =
max(|w[:, n]|) / 127`` (all-zero columns get scale 1 so dequant is
exact), ``w_q = clip(round(w / scale), -127, 127)``.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

# Dense targets the quantized decode path covers — the same four the
# LoRA adapters attach to (models/layers.py::LORA_TARGETS; kept literal
# here to avoid an ops -> models import cycle).
QUANT_TARGETS = ("qkv", "proj", "fc_in", "fc_out")


def quantize_per_channel(w: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """[K, N] float weights -> (int8 [K, N], f32 scales [N])."""
    wf = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(wf), axis=0)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    w_q = jnp.clip(jnp.round(wf / scale), -127, 127).astype(jnp.int8)
    return w_q, scale


def _int8_reference(x, w_q, scale):
    x2 = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
    y = jax.lax.dot_general(
        x2, w_q.astype(jnp.float32),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale
    return y.reshape(*x.shape[:-1], w_q.shape[-1]).astype(x.dtype)


def _int8_kernel(x_ref, w_ref, s_ref, o_ref):
    y = jax.lax.dot_general(
        x_ref[...].astype(jnp.float32), w_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * s_ref[0]
    o_ref[...] = y.astype(o_ref.dtype)


def _int8_pallas(x, w_q, scale, block_n, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    x2 = x.reshape(-1, x.shape[-1])
    m, k = x2.shape
    n = w_q.shape[-1]
    # Largest lane-aligned block <= block_n that tiles N exactly (GPT-2's
    # qkv N=2304 takes 384); an N no 128-multiple divides rides whole.
    bn = next(
        (c for c in range(min(block_n, n) // 128 * 128, 0, -128)
         if n % c == 0),
        n,
    )
    y = pl.pallas_call(
        _int8_kernel,
        grid=(n // bn,),
        in_specs=[
            # Full-K blocks: the contraction is never split, so each
            # output element reduces in the reference's order.
            pl.BlockSpec((m, k), lambda j: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((k, bn), lambda j: (0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bn), lambda j: (0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((m, bn), lambda j: (0, j),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        interpret=interpret,
        name="int8_matmul",
    )(x2, w_q, scale.reshape(1, -1))
    return y.reshape(*x.shape[:-1], n)


def int8_matmul(
    x: jax.Array,
    w_q: jax.Array,
    scale: jax.Array,
    *,
    implementation: str = "auto",
    block_n: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """``x @ dequant(w_q, scale)`` computed as f32-dot(x, int8->f32 w)
    scaled per output column; returns x.dtype.  x: [..., K],
    w_q: [K, N] int8, scale: [N] f32."""
    if w_q.dtype != jnp.int8:
        raise ValueError(f"w_q must be int8, got {w_q.dtype}")
    if implementation == "auto":
        implementation = (
            "pallas" if jax.default_backend() == "tpu" else "reference"
        )
    if implementation == "reference":
        return _int8_reference(x, w_q, scale)
    if implementation != "pallas":
        raise ValueError(
            f"Unknown int8_matmul implementation {implementation!r}"
        )
    return _int8_pallas(x, w_q, scale, block_n, interpret)


def quantize_tree(params, targets=QUANT_TARGETS):
    """Build the ``"quant"`` collection from a params tree.

    Walks the (nested-dict) params pytree; every sub-dict named in
    ``targets`` that carries a Dense ``kernel`` contributes
    ``<name>_w`` / ``<name>_scale`` / ``<name>_b`` entries at its
    PARENT's scope — exactly where the owning module's
    ``self.variable("quant", ...)`` reads them — so the builder needs no
    knowledge of block naming.  Returns ``{}`` when nothing matched (the
    caller should refuse rather than serve un-quantized silently)."""
    if not isinstance(params, dict):
        raise TypeError(
            f"quantize_tree expects a nested-dict params tree, got "
            f"{type(params).__name__}"
        )

    def walk(d):
        out = {}
        for name, sub in d.items():
            if not isinstance(sub, dict):
                continue
            if name in targets and "kernel" in sub:
                w_q, scale = quantize_per_channel(sub["kernel"])
                out[f"{name}_w"] = w_q
                out[f"{name}_scale"] = scale
                out[f"{name}_b"] = jnp.asarray(
                    sub.get("bias", jnp.zeros((w_q.shape[-1],))),
                    jnp.float32,
                )
            else:
                inner = walk(sub)
                if inner:
                    out[name] = inner
        return out

    return walk(params)
