"""Analytic HBM ledger: will this config fit, and what is resident?

The parallelism menu (DP/TP/FSDP/ZeRO-1/SP/PP/EP, paged serving KV)
makes "does it fit in HBM" a function of half a dozen knobs — and the
pjit/TPUv4 scaling playbook (arXiv 2204.06514) is explicit that
per-config memory budgeting is what makes those knobs tractable rather
than trial-and-error.  This module owns that budget:

* **component walk** — :func:`train_ledger` walks a built Trainer's
  actual state (shape/dtype/sharding METADATA only — no device reads):
  params / optimizer moments / EMA / batch_stats per-device bytes with
  the dtype- and sharding-aware division the placement implies (ZeRO-1
  and ``dp_update='sharded'`` moments ÷N, TP/FSDP shard factors via
  each leaf's ``shard_shape``), plus the transients the steady numbers
  hide: fp32 gradients, the chunked-LM-head logits peak
  (``loss_chunk``), the pipeline activation stash sized from
  ``parallel/pipeline.py``'s own ``stash_slots`` accounting, and the
  input batch with its prefetch depth;
* **formula walk** — :func:`plan_train_memory` computes the same ledger
  from a config alone (``jax.eval_shape`` of model + optimizer init, no
  state built), so peak HBM can be predicted for a topology this host
  does not have, judged against the chip capacity table
  ``telemetry/flops.py`` owns;
* **live cross-check** — :func:`live_memory_snapshot` reads per-device
  ``memory_stats()`` on TPU and falls back to live-array nbytes
  accounting on CPU; :func:`measured_tree_bytes` measures what a state
  tree actually holds per device, and :func:`cross_check` pins the
  analytic walk against it (tests/test_memory_goodput.py enforces 10%
  agreement);
* **exposition** — ``MemoryLedger.publish()`` emits
  ``mem_analytic_bytes{component=}`` gauges,
  :func:`publish_live_memory` emits ``mem_live_bytes{device=}`` /
  ``mem_live_peak_bytes{device=}``, and flight dumps attach
  :func:`memory_snapshot_payload` so OOM forensics name the resident
  components (``telemetry/flight.py`` context providers);
* **serving** — :func:`kv_pool_bytes` prices the paged KV pool
  (pages × H × page × D × dtype × layers × K/V) so the ledger covers
  the serving engine end to end (``serving_kv_pool_bytes{state=}``).

Everything here is host arithmetic over metadata: building a ledger
never allocates, syncs, or changes a compiled program.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ml_trainer_tpu.utils.logging import get_logger

logger = get_logger("ml_trainer_tpu.telemetry")

# Prefetch depth of the trainer's input pipeline (data/loader.py
# prefetch_to_device size=2) + the batch the step is consuming.
_BATCH_BUFFERS = 3


@dataclasses.dataclass
class Component:
    """One ledger line: per-device bytes of one memory consumer."""

    name: str
    bytes: float
    kind: str  # "resident" (steady-state) | "transient" (in-step peak)
    detail: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def as_dict(self) -> dict:
        return {
            "name": self.name,
            "bytes": int(self.bytes),
            "kind": self.kind,
            **({"detail": self.detail} if self.detail else {}),
        }


class MemoryLedger:
    """A per-device HBM budget: components + totals + exposition."""

    def __init__(self, components: Sequence[Component],
                 notes: Optional[List[str]] = None):
        self.components = list(components)
        self.notes = list(notes or [])

    def resident_bytes(self) -> float:
        return sum(c.bytes for c in self.components if c.kind == "resident")

    def transient_bytes(self) -> float:
        return sum(c.bytes for c in self.components if c.kind == "transient")

    def peak_bytes(self) -> float:
        """Predicted per-device peak: everything resident plus the
        in-step transients (they coexist at the backward's peak)."""
        return self.resident_bytes() + self.transient_bytes()

    def component(self, name: str) -> Optional[Component]:
        for c in self.components:
            if c.name == name:
                return c
        return None

    def as_dict(self) -> dict:
        return {
            "components": [c.as_dict() for c in self.components],
            "resident_bytes": int(self.resident_bytes()),
            "transient_bytes": int(self.transient_bytes()),
            "peak_bytes": int(self.peak_bytes()),
            "notes": self.notes,
        }

    def publish(self, registry=None) -> None:
        """Mirror the ledger into ``mem_analytic_bytes{component=}``
        gauges plus the resident/peak totals."""
        from ml_trainer_tpu.telemetry.registry import default_registry

        r = registry if registry is not None else default_registry()
        g = r.gauge(
            "mem_analytic_bytes",
            "analytic per-device HBM bytes by component "
            "(telemetry/memory.py ledger)",
            ("component",),
        )
        for c in self.components:
            g.labels(component=c.name).set(float(c.bytes))
        r.gauge(
            "mem_analytic_resident_bytes",
            "analytic per-device steady-state resident HBM bytes",
        ).set(self.resident_bytes())
        r.gauge(
            "mem_analytic_peak_bytes",
            "analytic per-device peak HBM bytes (resident + transients)",
        ).set(self.peak_bytes())


# ------------------------------------------------------------ tree walks
def nbytes_of(shape, dtype) -> int:
    """Bytes of one (shape, dtype) pair — the ledger's unit price.  The
    graft-lint donation audit (analysis/jaxpr_checks.py) prices
    undonated-but-aliasable buffers through this, so lint findings and
    ledger components quote the same arithmetic."""
    if dtype is None:
        return 0
    return int(np.prod(shape, initial=1)) * jnp.dtype(dtype).itemsize


def _leaf_bytes(leaf) -> float:
    """Global bytes of one shape/dtype carrier (array or ShapeDtypeStruct)."""
    return float(
        nbytes_of(getattr(leaf, "shape", ()), getattr(leaf, "dtype", None))
    )


def _leaf_device_bytes(leaf, sharding=None) -> float:
    """Per-device bytes of a leaf under its sharding (metadata only).

    A NamedSharding's ``shard_shape`` is exactly the dtype- and
    sharding-aware division: replicated dims keep their extent, mesh-
    partitioned dims divide by the axis size — so TP/FSDP/ZeRO-1/stage
    placement all price correctly through one call."""
    sh = sharding if sharding is not None else getattr(leaf, "sharding", None)
    shape = tuple(getattr(leaf, "shape", ()))
    dtype = getattr(leaf, "dtype", None)
    if dtype is None:
        return 0.0
    itemsize = jnp.dtype(dtype).itemsize
    if sh is not None and hasattr(sh, "shard_shape") and shape:
        try:
            shape = tuple(sh.shard_shape(shape))
        except Exception:
            pass
    return float(np.prod(shape, initial=1)) * itemsize


def tree_device_bytes(tree, shardings=None) -> float:
    """Analytic per-device bytes of a pytree (sharding-aware).  With
    ``shardings`` (a matching tree) those override the leaves' own."""
    if shardings is None:
        return sum(_leaf_device_bytes(l) for l in jax.tree.leaves(tree))
    return sum(
        _leaf_device_bytes(l, s)
        for l, s in zip(jax.tree.leaves(tree), jax.tree.leaves(shardings))
    )


def measured_tree_bytes(tree) -> Tuple[float, Dict[str, float]]:
    """MEASURED per-device bytes of a tree of live jax.Arrays: real
    ``addressable_shards`` buffer sizes summed per device.  Returns
    ``(max_per_device, {device_id: bytes})`` — the cross-check's ground
    truth (host numpy leaves count as replicated-everywhere)."""
    per_dev: Dict[str, float] = {}
    n_dev = max(jax.local_device_count(), 1)
    for leaf in jax.tree.leaves(tree):
        shards = getattr(leaf, "addressable_shards", None)
        if shards:
            for s in shards:
                key = str(getattr(s.device, "id", s.device))
                data = s.data
                per_dev[key] = per_dev.get(key, 0.0) + float(
                    getattr(data, "nbytes", 0)
                )
        else:  # host value: charge every device (it will replicate)
            b = _leaf_bytes(leaf)
            for d in range(n_dev):
                per_dev[str(d)] = per_dev.get(str(d), 0.0) + b
    return (max(per_dev.values()) if per_dev else 0.0), per_dev


def cross_check(analytic_bytes: float, measured_bytes: float,
                tolerance: float = 0.10) -> dict:
    """Agreement verdict between the analytic walk and a measurement.
    ``ratio`` is analytic/measured; ``ok`` within ``tolerance``."""
    measured = float(measured_bytes)
    analytic = float(analytic_bytes)
    ratio = analytic / measured if measured > 0 else float("inf")
    return {
        "analytic_bytes": int(analytic),
        "measured_bytes": int(measured),
        "ratio": round(ratio, 4),
        "tolerance": tolerance,
        "ok": bool(measured > 0 and abs(ratio - 1.0) <= tolerance),
    }


# ------------------------------------------------------------- live side
def live_memory_snapshot() -> dict:
    """Per-device live memory: TPU ``memory_stats()`` (bytes_in_use +
    peak_bytes_in_use) or, where the backend has no allocator stats
    (CPU), the sum of live jax.Array buffer bytes per device — the
    graceful fallback that keeps the cross-check meaningful on the
    virtual-device test meshes."""
    devices = jax.local_devices()
    per_dev: Dict[str, dict] = {}
    source = "memory_stats"
    for d in devices:
        stats = None
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        if stats and stats.get("bytes_in_use") is not None:
            per_dev[str(d.id)] = {
                "bytes_in_use": int(stats.get("bytes_in_use", 0)),
                "peak_bytes_in_use": int(
                    stats.get("peak_bytes_in_use",
                              stats.get("bytes_in_use", 0))
                ),
            }
        else:
            source = "live_arrays"
            per_dev = {}
            break
    if not per_dev:
        acc: Dict[str, float] = {str(d.id): 0.0 for d in devices}
        try:
            arrays = jax.live_arrays()
        except Exception:
            arrays = []
        for arr in arrays:
            for s in getattr(arr, "addressable_shards", []) or []:
                key = str(getattr(s.device, "id", s.device))
                if key in acc:
                    acc[key] += float(getattr(s.data, "nbytes", 0))
        per_dev = {
            k: {"bytes_in_use": int(v), "peak_bytes_in_use": int(v)}
            for k, v in acc.items()
        }
    return {
        "backend": jax.default_backend(),
        "source": source,
        "devices": per_dev,
        "max_bytes_in_use": max(
            (v["bytes_in_use"] for v in per_dev.values()), default=0
        ),
        "max_peak_bytes_in_use": max(
            (v["peak_bytes_in_use"] for v in per_dev.values()), default=0
        ),
    }


def publish_live_memory(snapshot: Optional[dict] = None,
                        registry=None) -> dict:
    """Emit the live snapshot as ``mem_live_bytes{device=}`` /
    ``mem_live_peak_bytes{device=}`` gauges; returns the snapshot."""
    from ml_trainer_tpu.telemetry.registry import default_registry

    snap = snapshot if snapshot is not None else live_memory_snapshot()
    r = registry if registry is not None else default_registry()
    g_now = r.gauge(
        "mem_live_bytes",
        f"live per-device bytes in use (source: {snap['source']})",
        ("device",),
    )
    g_peak = r.gauge(
        "mem_live_peak_bytes",
        "per-device peak bytes in use (TPU allocator; = live on the "
        "CPU live-array fallback)",
        ("device",),
    )
    for dev, v in snap["devices"].items():
        g_now.labels(device=dev).set(float(v["bytes_in_use"]))
        g_peak.labels(device=dev).set(float(v["peak_bytes_in_use"]))
    return snap


def memory_snapshot_payload() -> dict:
    """Small JSON-safe payload flight dumps attach: the live per-device
    view plus the last published analytic component split."""
    payload = {"live": live_memory_snapshot()}
    try:
        from ml_trainer_tpu.telemetry.registry import default_registry

        snap = default_registry().snapshot()
        comp = {
            k[len("mem_analytic_bytes{component="):-1]: v
            for k, v in snap.items()
            if k.startswith("mem_analytic_bytes{component=")
        }
        if comp:
            payload["analytic_components"] = comp
        for k in ("mem_analytic_resident_bytes", "mem_analytic_peak_bytes"):
            if k in snap:
                payload[k] = snap[k]
    except Exception:
        pass
    return payload


# -------------------------------------------------------- trainer ledger
def _batch_component(batch_shape, dtype, data_parallel: int) -> Component:
    itemsize = jnp.dtype(dtype).itemsize
    per_dev = (
        float(np.prod(batch_shape, initial=1)) * itemsize
        / max(data_parallel, 1)
    )
    return Component(
        "batch", per_dev * _BATCH_BUFFERS, "resident",
        {"shape": list(batch_shape), "dtype": str(jnp.dtype(dtype)),
         "buffers": _BATCH_BUFFERS},
    )


def _loss_chunk_component(model, batch_shape,
                          data_parallel: int) -> Optional[Component]:
    """Chunked-LM-head peak: one fp32 logits chunk [b, chunk, V] lives
    during the forward and again (with its cotangent) in the backward."""
    chunk = int(getattr(model, "loss_chunk", 0) or 0)
    vocab = int(getattr(model, "vocab_size", 0) or 0)
    if not chunk or not vocab or len(batch_shape) < 2:
        return None
    b_local = max(int(batch_shape[0]) // max(data_parallel, 1), 1)
    chunk = min(chunk, int(batch_shape[1]))
    bytes_ = float(b_local) * chunk * vocab * 4 * 2  # chunk + cotangent
    return Component(
        "loss_chunk_peak", bytes_, "transient",
        {"chunk": chunk, "vocab": vocab, "local_batch": b_local},
    )


def _pipeline_stash_component(model, batch_shape,
                              info: Optional[dict] = None
                              ) -> Optional[Component]:
    """Activation stash of the pipeline engine, sized from the SAME
    numbers ``parallel/pipeline.py`` records at trace time
    (``stash_slots`` for the remat table, the [V, M] boundary stash for
    the value pass) — or from the formula when no trace has run yet."""
    n_stages = int(getattr(model, "n_stages", 0) or 0)
    if not n_stages:
        return None
    n_micro = int(getattr(model, "n_microbatches", 0) or 0) or n_stages
    n_virtual = int(getattr(model, "n_virtual", 1) or 1)
    remat = bool(getattr(model, "remat", True))
    embed = int(getattr(model, "embed_dim", 0) or 0)
    if len(batch_shape) < 2 or not embed:
        return None
    if info is None:
        from ml_trainer_tpu.parallel.pipeline import pipeline_schedule_info

        pinfo = pipeline_schedule_info()
        sched = str(getattr(model, "schedule", "gpipe"))
        info = pinfo.get(sched)
    # Microbatch boundary activation: [B/M, S, d] at the model dtype.
    dtype = getattr(model, "dtype", jnp.float32)
    itemsize = jnp.dtype(dtype).itemsize
    mb_rows = max(int(batch_shape[0]) // max(n_micro, 1), 1)
    mb_bytes = float(mb_rows) * int(batch_shape[1]) * embed * itemsize
    if info and info.get("stash_slots"):
        slots = int(info["stash_slots"])
        src = "traced"
    elif info and info.get("boundary_stash_microbatches"):
        slots = int(info["boundary_stash_microbatches"]) * n_virtual
        src = "traced"
    else:
        # The engine's documented bounds: remat keeps ~S*V microbatches
        # in flight; the no-remat value pass stashes every [V, M]
        # boundary activation.
        slots = n_stages * n_virtual if remat else n_virtual * n_micro
        src = "formula"
    return Component(
        "pipeline_stash", mb_bytes * slots, "transient",
        {"slots": slots, "microbatch_bytes": int(mb_bytes),
         "source": src, "remat": remat},
    )


def train_ledger(trainer, batch_shape: Optional[Sequence[int]] = None,
                 batch_dtype=None) -> MemoryLedger:
    """Analytic per-device ledger of a BUILT Trainer — a pure metadata
    walk of its state tree + sharding specs plus the step transients.
    ``batch_shape`` defaults to the trainer's global batch geometry."""
    state = trainer.state
    if state is None:
        raise ValueError("trainer has no state (datasets were not given)")
    comps: List[Component] = []
    notes: List[str] = []
    shardings = trainer._state_shardings

    def add(name, tree, sh_tree, kind="resident", detail=None):
        if tree is None:
            return
        b = tree_device_bytes(tree, sh_tree)
        if b > 0:
            comps.append(Component(name, b, kind, detail or {}))

    add("params", state.params, shardings.params)
    add("opt_state", state.opt_state, shardings.opt_state,
        detail={"zero1": bool(trainer._shard_opt_state)})
    if state.batch_stats:
        add("batch_stats", state.batch_stats, shardings.batch_stats)
    if state.ema_params is not None:
        add("ema_params", state.ema_params, shardings.ema_params)
    # Gradients: live at full LOCAL param size in fp32 during the
    # backward on every path (the sharded update reduce-scatters them
    # AFTER they materialize), so the peak charges the fp32 mirror.
    grad_bytes = sum(
        _leaf_device_bytes(l, s) / jnp.dtype(l.dtype).itemsize * 4
        for l, s in zip(
            jax.tree.leaves(state.params),
            jax.tree.leaves(shardings.params),
        )
    )
    comps.append(Component("grads", grad_bytes, "transient",
                           {"dtype": "float32"}))
    if trainer._compute_dtype is not None:
        # bf16 policy: the cast compute copy of the params coexists with
        # the fp32 masters through the step.
        comps.append(Component(
            "bf16_param_cast", grad_bytes / 2.0, "transient",
            {"dtype": str(jnp.dtype(trainer._compute_dtype))},
        ))
    shape = tuple(
        batch_shape
        if batch_shape is not None
        else getattr(trainer, "_batch_geometry", ()) or ()
    )
    if len(shape) > 1:
        comps.append(_batch_component(
            shape,
            batch_dtype or getattr(trainer, "_batch_dtype", None)
            or jnp.float32,
            trainer._data_parallel,
        ))
        lc = _loss_chunk_component(trainer.model, shape,
                                   trainer._data_parallel)
        if lc is not None:
            comps.append(lc)
        ps = _pipeline_stash_component(trainer.model, shape)
        if ps is not None:
            comps.append(ps)
    else:
        notes.append("batch geometry unknown: batch/transient rows omitted")
    return MemoryLedger(comps, notes)


# -------------------------------------------------------- formula ledger
def _spec_factor(shape, spec, axis_sizes: Dict[str, int]) -> float:
    """Division factor a PartitionSpec implies for ``shape`` (pure
    arithmetic — no Mesh object, so the planner can price topologies
    this host cannot build)."""
    factor = 1.0
    for dim, axes in zip(shape, tuple(spec) + (None,) * len(shape)):
        if axes is None:
            continue
        axes = axes if isinstance(axes, tuple) else (axes,)
        size = int(np.prod([axis_sizes.get(a, 1) for a in axes], initial=1))
        if size > 1 and dim % size == 0:
            factor *= size
    return factor


def _resolve_rule_spec(path_name: str, rules) -> Optional[tuple]:
    for pat, spec in (rules or []):
        if re.search(pat, path_name):
            return tuple(spec)
    return None


def plan_train_memory(
    model, batch_shape: Sequence[int], *,
    optimizer: str = "adamw",
    mesh_shape: Optional[Dict[str, int]] = None,
    sharding_rules=None,
    shard_opt_state: bool = False,
    dp_update: str = "fused",
    precision: Optional[str] = None,
    ema: bool = False,
    grad_accum_steps: int = 1,
    batch_dtype=None,
) -> MemoryLedger:
    """Formula-driven per-device ledger — no state built, no device
    memory touched (``jax.eval_shape`` only), so a config can be priced
    BEFORE trying to allocate it.

    Division rules mirror the Trainer's placement exactly: params
    replicate over data axes and divide per ``sharding_rules`` on model
    axes; ZeRO-1 (``shard_opt_state`` / ``dp_update='sharded'``) moment
    leaves whose dim 0 divides the data degree go ÷N (the
    ``zero1_opt_shardings`` rule); the batch divides over data axes."""
    from ml_trainer_tpu.models.registry import get_model
    from ml_trainer_tpu.ops import get_optimizer
    from ml_trainer_tpu.parallel.sharding import path_str

    if isinstance(model, str):
        model = get_model(model)
    mesh_shape = dict(mesh_shape or {})
    axis_sizes = {a: int(n) for a, n in mesh_shape.items()}
    data_parallel = int(np.prod(
        [axis_sizes.get(a, 1) for a in ("data", "fsdp")], initial=1
    ))
    zero1 = bool(shard_opt_state) or dp_update == "sharded"
    notes: List[str] = []

    # Abstract init: parameter shapes without allocating anything.
    x_shape = jax.ShapeDtypeStruct(
        tuple(batch_shape),
        jnp.dtype(batch_dtype) if batch_dtype is not None else (
            jnp.int32 if len(batch_shape) == 2 else jnp.float32
        ),
    )
    import inspect

    init_kwargs = {}
    try:
        if "train" in inspect.signature(model.__call__).parameters:
            init_kwargs["train"] = False
    except (TypeError, ValueError):
        pass
    variables = jax.eval_shape(
        lambda r, x: model.init(r, x, **init_kwargs),
        jax.random.PRNGKey(0), x_shape,
    )
    params = variables["params"]
    batch_stats = variables.get("batch_stats", {})
    tx = get_optimizer(optimizer, 1e-3)
    opt_shapes = jax.eval_shape(tx.init, params)

    def param_leaf_bytes(path, leaf):
        spec = _resolve_rule_spec(path_str(path), sharding_rules)
        factor = (
            _spec_factor(leaf.shape, spec, axis_sizes) if spec else 1.0
        )
        return _leaf_bytes(leaf) / factor

    p_items = jax.tree_util.tree_flatten_with_path(params)[0]
    params_bytes = sum(param_leaf_bytes(p, l) for p, l in p_items)
    comps: List[Component] = [
        Component("params", params_bytes, "resident",
                  {"leaves": len(p_items)}),
    ]
    if batch_stats:
        comps.append(Component(
            "batch_stats",
            sum(_leaf_bytes(l) for l in jax.tree.leaves(batch_stats)),
            "resident",
        ))

    # Optimizer moments: a moment leaf inherits its param's rule-shard
    # factor (moments are born with the param's sharding); under ZeRO-1
    # a replicated leaf whose dim 0 divides N additionally goes ÷N.
    by_shape: Dict[tuple, float] = {}
    for p, l in p_items:
        spec = _resolve_rule_spec(path_str(p), sharding_rules)
        if spec:
            by_shape.setdefault(
                tuple(l.shape), _spec_factor(l.shape, spec, axis_sizes)
            )
    opt_bytes = 0.0
    for leaf in jax.tree.leaves(opt_shapes):
        b = _leaf_bytes(leaf)
        shape = tuple(getattr(leaf, "shape", ()))
        factor = by_shape.get(shape, 1.0)
        if (
            zero1 and factor == 1.0 and shape
            and data_parallel > 1 and shape[0] % data_parallel == 0
        ):
            factor = float(data_parallel)
        opt_bytes += b / factor
    comps.append(Component(
        "opt_state", opt_bytes, "resident",
        {"optimizer": optimizer, "zero1": zero1,
         "data_parallel": data_parallel},
    ))
    if ema:
        comps.append(Component("ema_params", params_bytes, "resident"))

    comps.append(Component("grads", params_bytes, "transient",
                           {"dtype": "float32"}))
    if precision not in (None, "fp32", "float32"):
        comps.append(Component(
            "bf16_param_cast", params_bytes / 2.0, "transient",
            {"dtype": str(precision)},
        ))
    comps.append(_batch_component(
        batch_shape, x_shape.dtype, data_parallel
    ))
    lc = _loss_chunk_component(model, batch_shape, data_parallel)
    if lc is not None:
        comps.append(lc)
    ps = _pipeline_stash_component(model, batch_shape, info={})
    if ps is not None:
        comps.append(ps)
    act = activation_bytes(model, batch_shape, data_parallel,
                           grad_accum_steps=grad_accum_steps)
    if act is not None:
        comps.append(Component(
            "activations_est", act, "transient",
            {"estimate": True, "grad_accum_steps": grad_accum_steps},
        ))
    else:
        notes.append(
            f"no activation model for {type(model).__name__}: peak "
            "underestimates the backward's stash"
        )
    return MemoryLedger(comps, notes)


def activation_bytes(model, batch_shape, data_parallel: int = 1,
                     grad_accum_steps: int = 1) -> Optional[float]:
    """Coarse transformer activation estimate for the planner: ~12
    boundary-sized tensors per block live for the backward (attention
    scores excluded — the flash path never materializes S×S).  Returns
    None for families without a rule (conv nets) — callers must treat
    that as "not modeled", never as zero."""
    name = type(model).__name__
    if name not in ("GPT2", "GPT2Pipelined", "BertEncoder", "LlamaLM",
                    "VisionTransformer"):
        return None
    d = int(getattr(model, "embed_dim", 0) or 0)
    depth = int(getattr(model, "depth", 0) or 0)
    if not depth:
        depth = int(getattr(model, "n_stages", 0) or 0) * int(
            getattr(model, "blocks_per_stage", 1) or 1
        )
    if not d or not depth or len(batch_shape) < 2:
        return None
    if name == "VisionTransformer":
        p = int(model.patch_size)
        seq = (int(batch_shape[1]) // p) * (int(batch_shape[2]) // p) + 1
    else:
        seq = int(batch_shape[1])
    b_local = max(
        int(batch_shape[0]) // max(data_parallel * grad_accum_steps, 1), 1
    )
    dtype = getattr(model, "dtype", jnp.float32)
    itemsize = jnp.dtype(dtype).itemsize
    return float(b_local) * seq * d * depth * 12 * itemsize


# ------------------------------------------------------------ serving KV
def kv_pool_bytes(n_pages: int, page_size: int, num_heads: int,
                  head_dim: int, n_layers: int,
                  dtype=jnp.float32) -> float:
    """Total device bytes of a paged KV pool: pages × H × page × D ×
    dtype, × n_layers × 2 (K and V) — the ``serving_kv_pool_bytes``
    geometry (the trash page 0 is device memory too, so it counts)."""
    itemsize = jnp.dtype(dtype).itemsize
    return (
        float(n_pages) * num_heads * page_size * head_dim
        * itemsize * n_layers * 2
    )


def adapter_pool_bytes(slots: int, rank: int, target_dims,
                       dtype=jnp.float32) -> float:
    """Total device bytes of a batched-LoRA adapter pool
    (serving/adapter_pool.py): per targeted projection instance
    (one ``(in_dim, out_dim)`` entry in ``target_dims`` PER LAYER) the
    pool holds stacks ``A [slots, in, rank]`` + ``B [slots, rank, out]``
    — so ``slots × rank × Σ(in + out) × itemsize``.  The trash slot 0
    is device memory too, so it counts (the ``kv_pool_bytes`` rule).
    """
    itemsize = jnp.dtype(dtype).itemsize
    total_dims = sum(int(i) + int(o) for i, o in target_dims)
    return float(slots) * rank * total_dims * itemsize


def gpt2_lora_target_dims(model, targets) -> List[Tuple[int, int]]:
    """The ``(in, out)`` pairs :func:`adapter_pool_bytes` needs for a
    GPT-2-family config: per layer, qkv ``E -> 3E``, proj ``E -> E``,
    fc_in ``E -> 4E``, fc_out ``4E -> E``."""
    e = int(model.embed_dim)
    per_layer = {
        "qkv": (e, 3 * e),
        "proj": (e, e),
        "fc_in": (e, 4 * e),
        "fc_out": (4 * e, e),
    }
    depth = int(getattr(model, "depth", 0) or 0)
    return [per_layer[t] for _ in range(depth) for t in targets]


def serving_kv_ledger(engine) -> MemoryLedger:
    """Per-device ledger of a serving engine's KV memory (paged pool or
    contiguous slots) measured from its cache tree metadata — plus the
    LoRA adapter pool's stacks when the engine serves adapters."""
    comps: List[Component] = []
    if getattr(engine, "_lora_on", False):
        pool = engine.adapters
        stack_bytes = sum(
            _leaf_bytes(l) for l in jax.tree.leaves(engine._lora_stacks)
        )
        comps.append(Component(
            "adapter_pool", stack_bytes, "resident",
            {"slots": pool.slots, "rank": pool.rank,
             "targets": list(pool.targets),
             "bytes_per_slot": int(stack_bytes / max(pool.slots, 1))},
        ))
    cache_bytes = tree_device_bytes(engine.cache)
    if getattr(engine, "paged", False):
        pool_leaves = [
            l for l in jax.tree.leaves(engine.cache)
            if getattr(l, "ndim", 0) >= 1
            and l.shape[0] == engine.kv_pages
        ]
        pool_bytes = sum(_leaf_bytes(l) for l in pool_leaves)
        comps.append(Component(
            "kv_pool", pool_bytes, "resident",
            {"pages": engine.kv_pages, "page_size": engine.kv_page_size,
             "bytes_per_page": int(pool_bytes / max(engine.kv_pages, 1))},
        ))
        other = cache_bytes - pool_bytes
        if other > 0:
            comps.append(Component("kv_cache_other", other, "resident"))
    else:
        comps.append(Component(
            "kv_slots", cache_bytes, "resident",
            {"max_batch": engine.max_batch, "max_len": engine.max_len},
        ))
    return MemoryLedger(comps)


# ---------------------------------------------------------------- planner
def fit_verdict(peak_bytes: float, capacity_bytes: Optional[float] = None,
                margin: float = 0.9,
                generation: Optional[str] = None) -> dict:
    """fit-or-OOM verdict: predicted peak vs chip HBM capacity.  "fits"
    under ``margin`` × capacity, "tight" under capacity, else "oom".
    The capacity is ``capacity_bytes``, else the HBM of ``generation``
    (a ``telemetry/flops.py`` table key, e.g. ``"v5e"`` — how a host
    without the chip prices one), else the local chip's; a device the
    table does not know is an error."""
    from ml_trainer_tpu.telemetry.flops import (
        chip_generation,
        chip_hbm_capacity_bytes,
    )

    cap = (
        float(capacity_bytes) if capacity_bytes is not None
        else chip_hbm_capacity_bytes(generation)
    )
    frac = peak_bytes / cap if cap > 0 else float("inf")
    verdict = "fits" if frac <= margin else ("tight" if frac <= 1.0 else "oom")
    return {
        "peak_bytes": int(peak_bytes),
        "capacity_bytes": int(cap),
        "chip": (
            "explicit-capacity" if capacity_bytes is not None
            else chip_generation(generation)
        ),
        "utilization": round(frac, 4),
        "margin": margin,
        "verdict": verdict,
    }
