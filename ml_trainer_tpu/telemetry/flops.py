"""Analytic per-model FLOPs accounting + chip peak tables.

One place owns the numbers two kinds of math previously duplicated:

* **chip peaks** — published per-chip FLOP/s by compute dtype and HBM
  capacity by TPU generation;
* **per-step FLOPs** — analytical training-step FLOPs for every
  north-star family (mlmodel / resnet / vit / bert / gpt2 / llama),
  computed from the registry configs' module attributes, so an MFU
  estimate is available where XLA cost analysis is not (the trainer's
  live telemetry, CPU smoke runs).

Conventions (documented in docs/observability.md):

* matmul/conv FLOPs are ``2 * MACs`` (one multiply + one add);
* a training step is ``3x`` the forward (backward ≈ 2x: grads w.r.t.
  both activations and weights) — the standard MFU bookkeeping
  (PaLM appendix B); optimizer/elementwise work is ignored;
* attention scores count the FULL ``S x S`` interaction for causal and
  bidirectional models alike (the PaLM ``12 * L * d * S`` convention —
  causal masking halves the useful work but not the launched MACs).

These are ESTIMATES for MFU lines and dashboards.  Where a compiled
executable is at hand, XLA's measured ``cost_analysis()`` stays the
source of truth.
"""

from __future__ import annotations

from typing import Optional, Sequence

# Published peak numbers per chip, keyed by compute dtype.  The bf16 rows
# are the marketed MXU peaks; fp32 matmuls run through the same MXU at
# half rate (multi-pass accumulation), so an fp32 training run's
# attainable ceiling — and therefore an honest MFU denominator — is half
# the bf16 number.  Using the bf16 peak for an fp32 run understates MFU;
# using an fp32 peak for a bf16 run overstates it.
PEAK_FLOPS_BY_DTYPE = {
    "bf16": {
        "v6e": 918e12, "v6": 918e12,
        "v5p": 459e12,
        "v5e": 197e12, "v5 lite": 197e12, "v5lite": 197e12,
        "v4": 275e12,
    },
    "fp32": {
        "v6e": 459e12, "v6": 459e12,
        "v5p": 229.5e12,
        "v5e": 98.5e12, "v5 lite": 98.5e12, "v5lite": 98.5e12,
        "v4": 137.5e12,
    },
    # Int8 matmul peaks (the quantized-decode path's honest MFU
    # denominator, ops/kernels/int8_matmul.py): 2x the bf16 MXU rate on
    # generations with native int8 MACs; v4 has none and runs int8
    # operands through the bf16 pipeline at the bf16 rate.
    "int8": {
        "v6e": 1836e12, "v6": 1836e12,
        "v5p": 918e12,
        "v5e": 394e12, "v5 lite": 394e12, "v5lite": 394e12,
        "v4": 275e12,
    },
}
_DTYPE_ALIASES = {
    "bf16": "bf16", "bfloat16": "bf16",
    "fp32": "fp32", "float32": "fp32", "f32": "fp32",
    "int8": "int8", "i8": "int8",
}
# Back-compat alias (pre-dtype-keyed callers read the bf16 table).
PEAK_FLOPS = PEAK_FLOPS_BY_DTYPE["bf16"]
# HBM *capacity* per chip (bytes) — the denominator of the fit-or-OOM
# planner (telemetry/memory.py).
HBM_CAPACITY_BYTES = {
    "v6e": 32 * 2 ** 30, "v6": 32 * 2 ** 30,
    "v5p": 95 * 2 ** 30,
    "v5e": 16 * 2 ** 30, "v5 lite": 16 * 2 ** 30, "v5lite": 16 * 2 ** 30,
    "v4": 32 * 2 ** 30,
}


def chip_generation(generation: Optional[str] = None) -> str:
    """Key into the peak tables: ``generation`` when the caller names the
    chip (the planner pricing a topology this host does not have), else
    the local device's ``device_kind``.  A device that is not in the
    tables is an error, never a default."""
    if generation is not None:
        if generation not in PEAK_FLOPS:
            raise ValueError(
                f"unknown TPU generation {generation!r}; the peak tables "
                f"know {sorted(PEAK_FLOPS)}"
            )
        return generation
    import jax

    kind = jax.devices()[0].device_kind
    for key in PEAK_FLOPS:
        if key in kind.lower():
            return key
    raise ValueError(
        f"device_kind {kind!r} is not in the chip peak tables "
        f"(telemetry/flops.py: {sorted(PEAK_FLOPS)}); name the "
        "generation explicitly or add the device with its source"
    )


def chip_peak_flops(dtype: str = "bf16",
                    generation: Optional[str] = None) -> float:
    """Peak FLOP/s of one chip for ``dtype`` compute ('bf16' / 'fp32' /
    'int8', aliases accepted).  MFU must divide by the peak of the dtype
    the matmuls actually run in."""
    key = _DTYPE_ALIASES.get(str(dtype).lower())
    if key is None:
        raise ValueError(
            f"unknown compute dtype {dtype!r}; expected one of "
            f"{sorted(_DTYPE_ALIASES)}"
        )
    return PEAK_FLOPS_BY_DTYPE[key][chip_generation(generation)]


def chip_hbm_capacity_bytes(generation: Optional[str] = None) -> float:
    """HBM capacity in bytes of one chip — what an analytic memory
    ledger's peak prediction is judged against."""
    return float(HBM_CAPACITY_BYTES[chip_generation(generation)])


# -- forward-pass FLOPs per model family --------------------------------

def _transformer_fwd(batch: int, seq: int, depth: int, d: int,
                     mlp_dim: int, *, q_heads: int = 0, kv_heads: int = 0,
                     head_dim: int = 0, vocab_head: int = 0,
                     embed_gather: bool = False) -> float:
    """Forward FLOPs of a standard pre-norm transformer trunk.

    Projections: q (+out) at full width, k/v possibly narrower (GQA);
    attention: QK^T + AV over the full S x S window; MLP: in + out
    matmuls; head: one ``d x vocab_head`` matmul when > 0.  Embedding
    lookups are gathers (0 matmul FLOPs)."""
    if not head_dim:
        head_dim = d // max(q_heads or 1, 1)
    q_width = (q_heads or (d // head_dim)) * head_dim
    kv_width = (kv_heads or (q_heads or (d // head_dim))) * head_dim
    per_token = 0.0
    # q, out projections: d -> q_width and q_width -> d.
    per_token += 2.0 * d * q_width * 2
    # k, v projections: d -> kv_width each.
    per_token += 2.0 * d * kv_width * 2
    # attention scores + weighted sum: q_width MACs per (token, key) x2.
    per_token += 2.0 * seq * q_width * 2
    # MLP in + out.
    per_token += 2.0 * d * mlp_dim * 2
    trunk = batch * seq * depth * per_token
    head = batch * seq * 2.0 * d * vocab_head if vocab_head else 0.0
    return trunk + head


def _conv_fwd(h: int, w: int, c_in: int, c_out: int, k: int,
              stride: int = 1, padding: str = "SAME") -> tuple:
    """(FLOPs, h_out, w_out) of one conv on an ``h x w x c_in`` input."""
    if padding == "SAME":
        h_out = -(-h // stride)
        w_out = -(-w // stride)
    else:  # VALID
        h_out = (h - k) // stride + 1
        w_out = (w - k) // stride + 1
    return 2.0 * k * k * c_in * c_out * h_out * w_out, h_out, w_out


def _resnet_fwd(model, batch: int, h: int, w: int, c: int) -> float:
    """Stage-by-stage conv accounting from the module's config
    (stage_sizes + block class), mirroring models/resnet.py exactly."""
    total = 0.0
    if getattr(model, "cifar_stem", False):
        f, h, w = _conv_fwd(h, w, c, 64, 3)
        total += f
    else:
        f, h, w = _conv_fwd(h, w, c, 64, 7, stride=2)
        total += f
        h, w = -(-h // 2), -(-w // 2)  # 3x3/2 maxpool, SAME-ish padding
    c = 64
    bottleneck = model.block.__name__ == "BottleneckBlock"
    expansion = 4 if bottleneck else 1
    for stage, num_blocks in enumerate(model.stage_sizes):
        filters = 64 * 2 ** stage
        out_c = filters * expansion
        for b in range(num_blocks):
            stride = 2 if (stage > 0 and b == 0) else 1
            if bottleneck:
                f1, _, _ = _conv_fwd(h, w, c, filters, 1)
                f2, h2, w2 = _conv_fwd(h, w, filters, filters, 3,
                                       stride=stride)
                f3, _, _ = _conv_fwd(h2, w2, filters, out_c, 1)
                total += f1 + f2 + f3
            else:
                f1, h2, w2 = _conv_fwd(h, w, c, filters, 3, stride=stride)
                f2, _, _ = _conv_fwd(h2, w2, filters, filters, 3)
                total += f1 + f2
            if c != out_c or stride != 1:
                fd, _, _ = _conv_fwd(h, w, c, out_c, 1, stride=stride)
                total += fd
            h, w, c = h2, w2, out_c
    total += 2.0 * c * int(model.num_classes)  # head after global pool
    return batch * total


def _mlmodel_fwd(model, batch: int, h: int, w: int, c: int) -> float:
    """The reference LeNet (models/mlmodel.py), conv + dense, VALID."""
    total = 0.0
    f, h, w = _conv_fwd(h, w, c, 6, 5, padding="VALID")
    total += f
    h, w = h // 2, w // 2
    f, h, w = _conv_fwd(h, w, 6, 16, 5, padding="VALID")
    total += f
    h, w = h // 2, w // 2
    flat = h * w * 16
    total += 2.0 * (flat * 120 + 120 * 84 + 84 * int(model.num_classes))
    return batch * total


def fwd_flops(model, batch_shape: Sequence[int]) -> Optional[float]:
    """Analytic forward-pass FLOPs of ``model`` on one ``batch_shape``
    batch, from the module's registry config.  ``model`` may be a module
    instance or a registry name (built with defaults).  Returns None for
    families without an accounting rule — callers must treat that as
    "no MFU estimate", never as zero."""
    if isinstance(model, str):
        from ml_trainer_tpu.models.registry import get_model

        model = get_model(model)
    name = type(model).__name__
    batch = int(batch_shape[0])
    if name == "MLModel":
        _, h, w, c = batch_shape
        return _mlmodel_fwd(model, batch, h, w, c)
    if name == "ResNet":
        _, h, w, c = batch_shape
        return _resnet_fwd(model, batch, h, w, c)
    if name == "VisionTransformer":
        _, h, w, _c = batch_shape
        p = int(model.patch_size)
        seq = (h // p) * (w // p) + 1  # patches + cls token
        d = int(model.embed_dim)
        patch_proj = batch * 2.0 * (h // p) * (w // p) * (p * p *
                                                          batch_shape[3]) * d
        return patch_proj + _transformer_fwd(
            batch, seq, int(model.depth), d, int(model.mlp_dim),
            q_heads=int(model.num_heads),
            vocab_head=0,
        ) + batch * 2.0 * d * int(model.num_classes)
    if name == "BertEncoder":
        _, seq = batch_shape
        d = int(model.embed_dim)
        ncls = int(model.num_classes or 0)
        f = _transformer_fwd(
            batch, int(seq), int(model.depth), d, int(model.mlp_dim),
            q_heads=int(model.num_heads),
        )
        return f + (batch * 2.0 * (d * d + d * ncls) if ncls else 0.0)
    if name in ("GPT2", "GPT2Pipelined"):
        _, seq = batch_shape
        d = int(model.embed_dim)
        depth = int(getattr(model, "depth", 0))
        if not depth:  # pipelined trunk sizes by stages
            depth = int(getattr(model, "n_stages", 0)) * int(
                getattr(model, "blocks_per_stage", 1)
            )
        return _transformer_fwd(
            batch, int(seq), depth, d, 4 * d,
            q_heads=int(model.num_heads),
            vocab_head=int(model.vocab_size),  # tied LM head
        )
    if name == "LlamaLM":
        _, seq = batch_shape
        d = int(model.embed_dim)
        head_dim = d // int(model.num_heads)
        hidden = int(model.hidden_dim) or int(
            ((8 * d // 3) + 127) // 128 * 128
        )
        # SwiGLU MLP: three matmuls (gate, up, down) = 1.5x the pair.
        f = _transformer_fwd(
            batch, int(seq), int(model.depth), d, hidden,
            q_heads=int(model.num_heads),
            kv_heads=int(model.num_kv_heads), head_dim=head_dim,
            vocab_head=int(model.vocab_size),
        )
        extra_gate = (batch * int(seq) * int(model.depth)
                      * 2.0 * d * hidden)
        return f + extra_gate
    return None


def train_step_flops(model, batch_shape: Sequence[int]) -> Optional[float]:
    """Analytic FLOPs of ONE full training step (fwd + bwd ~= 3x fwd)
    on a ``batch_shape`` batch; None when the family has no rule."""
    f = fwd_flops(model, batch_shape)
    return 3.0 * f if f is not None else None
