"""Trainer — the config-driven fit/validate/test orchestrator, mesh-native.

Public surface parity with the reference ``Trainer``
(ref: src/trainer.py:22-311): same constructor signature
``Trainer(model, datasets, epochs, batch_size, is_parallel, save_history,
**config)`` with the same eleven whitelisted config keys, the same
``fit()`` / ``test()`` / ``save_model()`` / ``clear()`` /
``validate_kwargs()`` methods, the same history schema
(ref: src/trainer.py:265-272), per-epoch host-0 model saving
(ref: src/trainer.py:252-256) and the dataset-less "testing only" mode
(ref: src/trainer.py:66-71, 03 nb cell-7).

TPU-native internals (the deliberate re-design, SURVEY.md §7):

* the train step is ONE compiled XLA program — forward, loss, backward,
  gradient all-reduce and optimizer update fused by ``jax.jit`` under a
  device mesh.  The reference's per-batch ``loss.item()`` sync and host-side
  sklearn metric (ref: src/trainer.py:186, 164-166) are replaced by
  on-device accumulators fetched once per epoch;
* data parallelism is a sharding annotation, not a module wrapper: batches
  are placed with a ``NamedSharding`` over the mesh's data axis and XLA
  inserts the gradient psum — the DDP + SMDDP stack collapses into the
  compiler (ref: src/trainer.py:97-101, 43-44);
* LR schedules are functions of the on-device step counter (the host-side
  ``scheduler.step()`` calls of ref: src/trainer.py:189-199 would force
  syncs); ReduceLROnPlateau runs host-side at epoch boundaries — and
  actually steps, unlike the reference's dead instance (documented fix);
* checkpoints carry full training state and ``fit(resume=True)`` restarts
  from the latest epoch — the reference is save-only (SURVEY.md §5).
"""

from __future__ import annotations

import gc
import math
import os
import time
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P
from tqdm import tqdm

from ml_trainer_tpu import checkpoint as ckpt
from ml_trainer_tpu.config import TrainerConfig, validate_kwargs
from ml_trainer_tpu.data import Loader, ShardedSampler, prefetch_to_device
from ml_trainer_tpu.models.registry import get_model
from ml_trainer_tpu.ops import (
    decay_mask_matrices_only,
    get_criterion,
    get_metric,
    get_optimizer,
    get_prediction_function,
    make_lr_schedule,
    PlateauController,
)
from ml_trainer_tpu.ops.attention import kernel_mesh
from ml_trainer_tpu.parallel import (
    batch_sharding,
    create_mesh,
    fit_sharding_to_rank,
    replicated,
)
from ml_trainer_tpu.parallel.distributed import (
    initialize_distributed,
    is_primary,
    process_count,
    process_index,
)
from ml_trainer_tpu.train_state import TrainState
from ml_trainer_tpu.utils.logging import get_logger
from ml_trainer_tpu.utils.utils import LoadedModel

logger = get_logger("ml_trainer_tpu.trainer")

# Where the persistent XLA compile cache lives when JAX_COMPILATION_CACHE_DIR
# does not place it: one fixed, git-ignored directory in the checkout.  The
# directory is part of what a cache lookup keys on, so it never moves (no
# /tmp, pid, time or mkdtemp name).  The only in-code cache path in the repo.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


def cpu_pinned() -> bool:
    """True when the caller pinned the CPU platform explicitly: ``cpu`` is
    the FIRST platform the ``jax_platforms`` config (which
    ``JAX_PLATFORMS`` seeds) lists.  A machine that merely HAS no
    accelerator is not pinned, and neither is the TPU machine's
    ``tpu,cpu`` — there the CPU is only the second, host-side platform."""
    platforms = str(jax.config.jax_platforms or "").lower()
    return platforms.split(",")[0].strip() == "cpu"


def _check_platform(backend: str) -> None:
    """No fallback that hides the device: the platform JAX brought up must
    be the one ``backend`` names.  Without a chip JAX hands out the CPU
    backend with no error of its own, and a ``backend='tpu'`` run (the
    default) would train on the host.  An explicit CPU pin
    (``JAX_PLATFORMS=cpu`` — the test mesh) is the caller's decision and
    stays allowed under ``backend='tpu'``."""
    platform = jax.default_backend()
    if backend == "cpu" and platform != "cpu":
        # jax_platforms only takes effect before the backend initializes.
        raise RuntimeError(
            f"backend='cpu' requested after JAX initialized '{platform}'; "
            "set JAX_PLATFORMS=cpu before the first device use"
        )
    if backend == "tpu" and platform != "tpu" and not cpu_pinned():
        raise RuntimeError(
            f"backend='tpu' but JAX came up on '{platform}' "
            f"({jax.devices()[0].device_kind}): no TPU is attached.  Pass "
            "backend='cpu' (--backend cpu) or set JAX_PLATFORMS=cpu to "
            "train on the host deliberately."
        )


def enable_compilation_cache() -> None:
    """Persistent XLA compilation cache, shared across processes.

    The first compile of a big model costs minutes; without this every new
    CLI invocation pays it again (torch has no analog cost — XLA does, so
    the framework owns mitigating it).  Idempotent.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself and
    nothing here touches the directory.  Unset: ``COMPILE_CACHE_DIR``.
    CPU-pinned runs (tests, ``backend='cpu'``) take no in-code default:
    CPU compiles are fast, and the test suite's thousands of tiny
    programs do not belong in the checkout."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR") or cpu_pinned():
        return
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)


def _chunk_batches(loader, k: int, tail: list):
    """Yield [K, B, ...] stacks of full batches; ragged batches (and the
    final partial chunk) land in ``tail`` once the generator drains."""
    xs, ys = [], []
    full = None  # leading dim of a full batch (first seen)
    for x, y in loader:
        if full is None:
            full = x.shape[0]
        if x.shape[0] != full:
            # Ragged final batch (drop_last=False): un-stackable, so it
            # always goes through the per-batch tail path even when it
            # would land inside a full chunk.
            tail.append((x, y))
            continue
        xs.append(x)
        ys.append(y)
        if len(xs) == k:
            yield np.stack(xs), np.stack(ys)
            xs, ys = [], []
    tail.extend(zip(xs, ys))


def _module_takes_train(module) -> bool:
    import inspect

    try:
        return "train" in inspect.signature(module.__call__).parameters
    except (TypeError, ValueError):
        return False


def _module_takes_targets(module) -> bool:
    """Models that accept ``targets`` compute their own loss (e.g. GPT2's
    chunked LM head, which never materializes the logits tensor); the
    Trainer then feeds labels through the forward instead of applying the
    criterion to returned logits."""
    import inspect

    try:
        params = inspect.signature(module.__call__).parameters
    except (TypeError, ValueError):
        return False
    # Only engage for models that OPT IN to the self-loss path: accepting
    # the argument is not enough (a model might take targets for teacher
    # forcing and still return logits) — it must carry an active
    # ``loss_chunk`` attribute (GPT2: loss_chunk > 0).
    return "targets" in params and bool(getattr(module, "loss_chunk", 0))


class Trainer:
    def __init__(
        self,
        model,
        datasets=None,
        epochs: Optional[int] = None,
        batch_size: Optional[int] = None,
        is_parallel: bool = False,
        save_history: bool = False,
        mesh_shape: Optional[dict] = None,
        sharding_rules=None,
        grad_accum_steps: int = 1,
        loader: str = "auto",
        steps_per_execution: int = 1,
        shard_opt_state: bool = False,
        grad_clip_norm: Optional[float] = None,
        ema_decay: Optional[float] = None,
        moe_aux_weight: float = 0.01,
        early_stop_patience: Optional[int] = None,
        save_best: bool = False,
        decay_exclude_bias_norm: bool = False,
        label_smoothing: float = 0.0,
        sharded_checkpoint: Optional[bool] = None,
        nonfinite_guard: bool = True,
        rollback_bad_steps: Optional[int] = None,
        rollback_lr_backoff: float = 0.5,
        save_every_steps: Optional[int] = None,
        handle_preemption: bool = True,
        telemetry: bool = False,
        log_every_steps: Optional[int] = None,
        desync_every_steps: Optional[int] = None,
        straggler_factor: float = 2.0,
        precision: Any = None,
        loss_scale: Any = "dynamic",
        dp_update: str = "fused",
        fused_adam: Optional[bool] = None,
        bucket_mb: float = 4.0,
        pipeline_schedule: Optional[str] = None,
        elastic: Any = None,
        lora: Any = None,
        **config: Any,
    ):
        """``mesh_shape`` / ``sharding_rules`` are TPU-native extensions
        beyond the reference's DP-only surface (SURVEY.md §2C): e.g.
        ``mesh_shape={'data': 4, 'tensor': 2}`` with
        ``sharding_rules=parallel.tp_rules.TRANSFORMER_TP_RULES`` trains
        tensor-parallel; both default to pure data parallelism.

        ``grad_accum_steps`` splits each global batch into that many
        microbatches inside the compiled step (a ``lax.scan`` over gradient
        accumulation, one optimizer update per batch) — the GPT-2 north-star
        requirement (BASELINE.json configs[4]); effective batch semantics
        and the LR schedule's step count are unchanged.

        ``loader``: 'auto' (default) assembles batches through the C++
        NativeLoader (csrc/batch_worker.cpp — the torch DataLoader
        worker-pool role, SURVEY.md §2B) whenever the dataset+transform can
        run the fused native pipeline with identical semantics, else the
        Python Loader; 'native' requires it (raises if unsupported);
        'python' forces the Python path.

        ``steps_per_execution``: run that many optimizer steps per device
        dispatch (a ``lax.scan`` over stacked batches inside ONE compiled
        program).  The update sequence, PRNG stream, LR schedule, and
        history are bit-identical to ``steps_per_execution=1``; only the
        per-step Python/dispatch overhead is amortized — the lever that
        matters for small models, where the reference pays a full
        host round-trip per batch (ref: src/trainer.py:186).

        ``shard_opt_state``: ZeRO-1-style placement — replicated optimizer
        moments are partitioned over the ``data`` mesh axis (a sharding
        annotation; XLA inserts the implied collectives), cutting optimizer
        memory per device by the data-parallel degree with an identical
        update sequence.

        ``grad_clip_norm``: clip gradients to this global L2 norm before
        the optimizer update (``optax.clip_by_global_norm`` chained in
        front of the optimizer — with grad accumulation the clip applies
        to the averaged global-batch gradient, matching torch's
        ``clip_grad_norm_``-before-``step()`` placement).

        ``ema_decay``: maintain an exponential moving average of the
        parameters on-device (``ema = d*ema + (1-d)*params`` each step).
        When set, validation, ``test()`` and ``save_model`` use the EMA
        weights (the standard ViT/ImageNet recipe); the raw weights keep
        training and are what checkpoints resume from (both live in the
        checkpointed TrainState).

        ``moe_aux_weight``: coefficient on auxiliary losses the model sows
        into the ``losses`` collection (the Switch-Transformer load-balance
        loss from ``models.moe.MoEMLP``).  Captured inside the compiled
        train step and added to the training loss, so top-1 routing is
        actually pushed toward balanced expert assignment; dense models sow
        nothing and pay nothing.

        ``early_stop_patience``: stop ``fit()`` after this many epochs
        without a new best validation loss (the best/bad-epoch counters
        live in checkpoints, so a resumed run keeps counting).  ``None``
        (default) trains the full epoch budget like the reference.

        ``save_best``: additionally export the weights to
        ``<model_dir>/best`` whenever validation loss improves — the
        every-epoch save overwrites with the LAST weights (ref behavior);
        this keeps the best ones too.

        ``decay_exclude_bias_norm``: apply weight decay to matrices only
        (ndim >= 2), skipping biases and LayerNorm params — the standard
        transformer recipe.  Default False = torch/reference semantics
        (decay everything).

        ``label_smoothing``: mix each one-hot target with the uniform
        distribution at this weight (torch's
        ``CrossEntropyLoss(label_smoothing=...)``; the ViT/ResNet
        recipe).  Only valid with ``criterion='cross_entropy'``.

        ``sharded_checkpoint``: write full-state checkpoints in the
        per-host sharded format — every process saves exactly its
        addressable shards (ZeRO-1 moments, TP/FSDP params) instead of
        host 0 allgathering the full tree.  Restore stitches shards back
        per-device, including onto a DIFFERENT mesh/device count than the
        one that saved (elastic resume after preemption).  Requires the
        model_dir to be storage shared by all hosts.  Default ``None`` =
        auto: on whenever the run is multi-process AND the state has
        genuinely partitioned leaves — the combination where a host-0
        full-tree gather is not just a RAM spike but a deadlock (one
        process launching a global allgather the others never join).
        The reference's rank-0 save (ref: src/trainer.py:252-254)
        generalized to sharded state.

        Resilience knobs (docs/resilience.md):

        ``nonfinite_guard`` (default True): the compiled train step
        checks loss and every gradient leaf for finiteness ON-DEVICE and
        ``where``-selects the previous state when the check fails — the
        bad step is skipped with no recompilation and no host sync, the
        skipped/streak counters live in ``TrainState`` (fetched once per
        epoch into ``history['skipped_steps']``).  With all-finite math
        the trajectory is bit-identical to the unguarded step.

        ``rollback_bad_steps``: after this many CONSECUTIVE skipped
        steps, restore the newest checkpoint that verifies (corrupt ones
        are quarantined) and scale the LR by ``rollback_lr_backoff``
        (compounding per rollback) — the escape hatch for a diverged
        run that keeps producing NaNs from poisoned state.  Checked at
        the existing ``log_every`` sync points, so it adds no extra
        per-step host sync.  ``None`` (default) disables rollback.

        ``save_every_steps``: additionally checkpoint every N optimizer
        steps WITHIN an epoch, with the batch cursor and epoch
        accumulators in the manifest, so ``fit(resume=True)`` restarts
        mid-epoch bit-exactly (the resumed trajectory equals the
        uninterrupted one).  Requires ``steps_per_execution=1`` (the
        per-batch dispatch path owns the step cursor).

        ``telemetry`` (default False): training step telemetry
        (docs/observability.md) — grad-norm / param-norm / update-ratio
        stats computed ON-DEVICE inside the compiled train step (pure
        extra outputs: no host sync, no extra compiled programs, and the
        update trajectory is untouched), fetched at the existing
        ``log_every`` sync cadence and emitted as structured
        ``train_step_telemetry`` events, registry gauges
        (``telemetry.default_registry()``), and flight-recorder step
        records — plus samples/s, tokens/s and an analytic MFU estimate
        (``telemetry/flops.py``, TPU backend only).  Also arms the
        third observability pillar: the analytic per-device HBM ledger
        published as ``mem_*`` gauges with a live cross-check
        (``telemetry/memory.py``), goodput accounting — per-run
        wall-clock decomposed into data-wait / h2d / ckpt-stall /
        compile / rollback / preempt-gap buckets behind a
        ``train_goodput_fraction`` gauge (``telemetry/goodput.py``) —
        and recompile forensics (``telemetry/compile_watch.py``:
        ``compile_events_total{fn=}``, flight ``recompile`` events
        naming the offending shape after the first epoch closes
        warmup); flight dumps attach the device-memory snapshot and
        recent compile events.

        ``log_every_steps``: override the host-sync cadence (default 50
        steps) — the progress-bar fetch, rollback check, and telemetry
        emission all ride this clock, so lowering it trades throughput
        for observability granularity.

        ``desync_every_steps``: additionally run the cross-host
        replica-desync check every N optimizer steps (default None =
        epoch boundaries only, the PR-3 behavior).  Each check costs one
        scalar broadcast over DCN plus the local fingerprint fetch; on
        mismatch the diverging host records + dumps a flight event
        naming itself and the step before raising
        (``parallel/desync.py``).  No-op single-process.

        ``straggler_factor``: with ``telemetry=True``, a host whose
        fenced step-time p50 exceeds the cluster (lower-)median by this
        factor at an aggregation point fires
        ``cluster_straggler_events_total{host=...}`` and a flight event
        (``telemetry/cluster.py``; heartbeats allgather at epoch
        boundaries).  Must be > 1.

        Mixed precision / data-parallel hot path (docs/mixed_precision.md):

        ``precision``: ``None``/``'fp32'`` (default — the exact
        pre-policy program, bit-identical trajectory) or ``'bf16'`` / a
        ``precision.Precision`` — forward/backward compute in bf16
        against the fp32 master params in ``TrainState`` (cast once at
        the top of the loss function; the criterion and metrics read
        fp32 outputs).  Transformer-family modules additionally get
        their ``dtype`` knob set so module-internal casts agree.

        ``loss_scale`` (only with an active bf16 policy): ``'dynamic'``
        (default) scales the loss before backward and unscales the
        gradients, halving the scale on a non-finite step WITHOUT
        advancing the rollback streak (overflow is the scale's fault
        until it has backed off to its floor) and doubling it after
        ``GROWTH_INTERVAL`` consecutive finite steps; a float pins a
        static scale; ``None`` disables scaling (bare bf16).  Requires
        ``nonfinite_guard`` — the skip machinery is the backoff path.
        The scale and its growth counter live in ``TrainState``
        (``loss_scale`` / ``good_steps``), maintained on-device.

        ``dp_update``: ``'fused'`` (default) keeps the single implicit
        gradient psum XLA inserts behind the batch sharding and the
        replicated weight update.  ``'sharded'`` rewrites the pure-DP
        hot path per arXiv 2004.13336: gradients leave the backward
        through size-bounded per-bucket ``reduce_scatter`` collectives
        (reverse topological order, so each bucket's communication can
        hide under remaining backward compute), each replica applies the
        optimizer update only to its 1/N shard of grads/params/moments
        (ZeRO-1 moments are implied and forced on), and fresh weights
        return via bucketed ``all_gather`` — update FLOPs and optimizer
        memory drop by the data-parallel degree with the same math
        (trajectory-equality test-pinned).  Requires a pure-DP mesh
        (only a live ``data`` axis), no sharding_rules, no batch_stats
        models, and ``steps_per_execution=1``.

        ``bucket_mb``: reduce-scatter bucket size bound in MiB for the
        sharded path (default 4) — smaller buckets start communicating
        earlier but pay more per-collective latency.

        ``pipeline_schedule``: override the pipeline-parallel schedule of
        a pipelined model (``'gpipe'`` | ``'1f1b'`` | ``'interleaved'``
        | ``'zb'`` — ``parallel.pipeline.SCHEDULES``; docs/pipeline.md).
        The model must carry a ``schedule`` knob (``GPT2Pipelined``); it
        is cloned with the override, exactly like the precision dtype
        threading.  All schedules compute the same math — trajectories
        are schedule-invariant (test-pinned) — so this knob only moves
        WHERE/WHEN stage work runs: 1F1B bounds the activation stash,
        interleaved shrinks the bubble by the model's ``n_virtual``.
        ``None`` (default) keeps the model's own setting.

        ``handle_preemption`` (default True): ``fit()`` installs
        SIGTERM/SIGINT handlers (restored on exit) that finish the
        in-flight step, write an emergency mid-epoch checkpoint plus a
        clean-exit marker, and return with ``self.preempted = True`` —
        the preemptible-TPU contract.  ``fit(resume=True)`` picks the
        marker up and continues where the signal landed.

        ``elastic`` (docs/resilience.md "Elastic"): an int simulated
        host count or a ``resilience.elastic.ElasticConfig``.  The mesh
        decomposes into N equal host groups (contiguous blocks of data
        replicas); a ``host_kill``/``host_hang`` fault or a straggler
        verdict from ``telemetry/cluster.py`` whose factor reaches
        ``straggler_reshape_factor`` then drains the in-flight step,
        writes the emergency checkpoint, drops the lost host's devices,
        re-places the state in ONE ``place_tree`` program, rescales
        global batch / LR per ``batch_policy``, and continues the SAME
        ``fit()`` call — each event recorded in ``history['reshapes']``,
        a flight ``reshape`` event and the goodput ``reshape`` bucket.
        Single-process (simulated cluster) only: a real multi-process
        pod cannot reshape its process set in place, so there the same
        faults drive the drain→checkpoint→restart path and the
        topology-flexible restore continues the job at the new shape.
        Requires ``steps_per_execution=1`` (the drain needs the
        per-batch cursor).

        ``lora`` (docs/serving.md "Batched LoRA adapters"): a
        :class:`~ml_trainer_tpu.lora.LoraConfig` (or its kwargs dict)
        — the model clones with trainable low-rank A/B params on the
        targeted projections (B zero-init, so step 0 IS the base
        model), the BASE weights freeze through an optax
        ``multi_transform`` mask (frozen leaves carry no optimizer
        state, so optimizer memory divides by the frozen fraction —
        the memory ledger shows it), and ``export_lora(path)`` writes
        the adapter artifact the serving engine hot-loads.  Requires a
        model carrying the ``lora_*`` knobs (the GPT-2 family) and
        ``dp_update='fused'``."""
        logger.info("Config inputs.", config=config)
        cfg = TrainerConfig.from_kwargs(**config)
        self.config = cfg
        if cfg.backend == "cpu":
            # The gloo-analog host path (ref: main.py:73) pins the host
            # platform; _check_platform verifies it took.
            jax.config.update("jax_platforms", "cpu")
        # After the backend pin, so a backend='cpu' run is seen as CPU by
        # the cache gate.
        enable_compilation_cache()
        # Parity attribute names (ref: src/trainer.py:30-41).
        self.epochs = epochs
        self.scheduler_type = cfg.scheduler
        self.optimizer_type = cfg.optimizer
        self.momentum = cfg.momentum
        self.weight_decay = cfg.weight_decay
        self.lr = cfg.lr
        self.criterion_type = cfg.criterion
        self.metric = cfg.metric
        self.pred_function_type = cfg.pred_function
        self.model_dir = cfg.model_dir
        self.is_parallel = is_parallel
        self.save_history = save_history

        self.train_losses: list = []
        self.val_losses: list = []
        self.train_metrics: list = []
        self.val_metrics: list = []
        self.history: dict = {}
        # Host-sync cadence for progress-bar postfix updates.  The reference
        # fetches the loss every batch (ref: src/trainer.py:186) — a per-step
        # device sync we only pay every `log_every` steps.
        self.log_every = 50

        from ml_trainer_tpu.precision import (
            resolve_loss_scale,
            resolve_precision,
        )

        self.precision = resolve_precision(precision)
        self._compute_dtype = (
            self.precision.compute if self.precision.active else None
        )
        self._loss_scale_cfg = resolve_loss_scale(loss_scale, self.precision)
        if self._loss_scale_cfg is not None and not nonfinite_guard:
            raise ValueError(
                "loss scaling rides the non-finite guard (overflow steps "
                "are skipped by the same where-selects); pass "
                "loss_scale=None to run bare bf16 with nonfinite_guard "
                "disabled"
            )
        if dp_update not in ("fused", "sharded"):
            raise ValueError(
                f"dp_update must be 'fused' | 'sharded', got {dp_update!r}"
            )
        if bucket_mb <= 0:
            raise ValueError(f"bucket_mb must be positive, got {bucket_mb}")
        self.dp_update = dp_update
        # Fused unscale+clip+Adam kernels for the sharded optimizer tail
        # (ops/kernels/fused_adam.py; docs/kernels.md).  None = auto:
        # on exactly when the sharded step runs plain Adam with no
        # weight decay — the one config whose optax op chain the fused
        # kernels replicate bit-for-bit (trajectory test-pinned).
        # Explicit True on an ineligible config is an error, not a
        # silent fallback.
        if fused_adam:
            if dp_update != "sharded":
                raise ValueError(
                    "fused_adam=True needs dp_update='sharded': the "
                    "fused kernels replace the sharded step's optimizer "
                    "tail (the fused step keeps optax's single jit)"
                )
            if self.optimizer_type != "adam":
                raise ValueError(
                    "fused_adam=True supports optimizer='adam' only "
                    f"(got {self.optimizer_type!r}): the kernels "
                    "replicate optax.adam's exact op chain"
                )
            if self.weight_decay:
                raise ValueError(
                    "fused_adam=True needs weight_decay=0: coupled L2 "
                    "prepends add_decayed_weights, which the fused "
                    "kernels do not replicate"
                )
        self.fused_adam = (
            dp_update == "sharded" and self.optimizer_type == "adam"
            and not self.weight_decay and lora is None
        ) if fused_adam is None else bool(fused_adam)
        self.bucket_mb = float(bucket_mb)
        if pipeline_schedule is not None:
            from ml_trainer_tpu.parallel.pipeline import SCHEDULES

            if pipeline_schedule not in SCHEDULES:
                raise ValueError(
                    f"pipeline_schedule must be one of {SCHEDULES}, got "
                    f"{pipeline_schedule!r}"
                )
        if isinstance(model, str):
            model = get_model(model, precision=self.precision)
        elif (
            self._compute_dtype is not None
            and hasattr(model, "dtype")
            and hasattr(model, "clone")
            and jnp.dtype(model.dtype) != jnp.dtype(self._compute_dtype)
        ):
            # Thread the compute dtype onto modules that carry a dtype
            # knob (the transformer zoo) so module-internal casts agree
            # with the trainer-level policy; params stay fp32
            # (flax's separate param_dtype).
            model = model.clone(dtype=self._compute_dtype)
        if pipeline_schedule is not None:
            if not (hasattr(model, "schedule") and hasattr(model, "clone")):
                raise ValueError(
                    "pipeline_schedule requires a pipelined model with a "
                    f"'schedule' knob (e.g. gpt2_pipe); got "
                    f"{type(model).__name__}"
                )
            if model.schedule != pipeline_schedule:
                model = model.clone(schedule=pipeline_schedule)
        self.pipeline_schedule = pipeline_schedule
        self.lora = None
        if lora is not None:
            from ml_trainer_tpu.lora import LoraConfig

            if isinstance(lora, dict):
                lora = LoraConfig(**lora)
            if not isinstance(lora, LoraConfig):
                raise ValueError(
                    f"lora must be a LoraConfig (or its kwargs dict), "
                    f"got {type(lora).__name__}"
                )
            if dp_update == "sharded":
                raise ValueError(
                    "lora training uses the fused update: the sharded "
                    "path's dim-0 partition rule does not cover the "
                    "masked optimizer state (dp_update='fused')"
                )
            if not (hasattr(model, "lora_rank") and hasattr(model, "clone")):
                raise ValueError(
                    "lora requires a model carrying the lora_* knobs "
                    f"(the GPT-2 family); got {type(model).__name__}"
                )
            # lora_slots stays 0: train mode — one trainable adapter as
            # ordinary params (serving pools are the engine's business).
            model = model.clone(
                lora_rank=int(lora.rank), lora_alpha=float(lora.alpha),
                lora_targets=tuple(lora.targets), lora_slots=0,
            )
            self.lora = lora
        self.model = model
        self._takes_train = _module_takes_train(model)
        self._takes_targets = _module_takes_targets(model)

        logger.info("Loading the model.")
        self._sharding_rules = sharding_rules
        if loader not in ("auto", "native", "python"):
            raise ValueError(
                f"loader must be 'auto' | 'native' | 'python', got {loader!r}"
            )
        self._loader_kind = loader
        if grad_accum_steps < 1:
            raise ValueError(f"grad_accum_steps must be >= 1, got {grad_accum_steps}")
        self.grad_accum_steps = int(grad_accum_steps)
        if steps_per_execution < 1:
            raise ValueError(
                f"steps_per_execution must be >= 1, got {steps_per_execution}"
            )
        self.steps_per_execution = int(steps_per_execution)
        self._shard_opt_state = bool(shard_opt_state)
        if grad_clip_norm is not None and grad_clip_norm <= 0:
            raise ValueError(
                f"grad_clip_norm must be positive, got {grad_clip_norm}"
            )
        self.grad_clip_norm = grad_clip_norm
        if ema_decay is not None and not (0.0 < ema_decay < 1.0):
            raise ValueError(
                f"ema_decay must be in (0, 1), got {ema_decay}"
            )
        self.ema_decay = ema_decay
        if moe_aux_weight < 0:
            raise ValueError(
                f"moe_aux_weight must be >= 0, got {moe_aux_weight}"
            )
        self.moe_aux_weight = float(moe_aux_weight)
        if early_stop_patience is not None and early_stop_patience < 1:
            raise ValueError(
                f"early_stop_patience must be >= 1, got {early_stop_patience}"
            )
        self.early_stop_patience = early_stop_patience
        self.save_best = bool(save_best)
        self.decay_exclude_bias_norm = bool(decay_exclude_bias_norm)
        # Per-host sharded full-state checkpoints (format v3): each process
        # writes exactly its addressable shards — no host-0 gather, no host
        # ever holds the full tree.  Requires the checkpoint dir to be
        # shared storage across hosts (GCS/NFS, the normal pod setup).
        # None = resolve from the state's shardings once they exist.
        self._sharded_ckpt = sharded_checkpoint
        self.nonfinite_guard = bool(nonfinite_guard)
        if rollback_bad_steps is not None and rollback_bad_steps < 1:
            raise ValueError(
                f"rollback_bad_steps must be >= 1, got {rollback_bad_steps}"
            )
        self.rollback_bad_steps = rollback_bad_steps
        if not (0.0 < rollback_lr_backoff <= 1.0):
            raise ValueError(
                f"rollback_lr_backoff must be in (0, 1], got "
                f"{rollback_lr_backoff}"
            )
        self.rollback_lr_backoff = float(rollback_lr_backoff)
        if save_every_steps is not None:
            if save_every_steps < 1:
                raise ValueError(
                    f"save_every_steps must be >= 1, got {save_every_steps}"
                )
            if self.steps_per_execution > 1:
                raise ValueError(
                    "save_every_steps (step-granular mid-epoch checkpoints) "
                    "requires steps_per_execution=1: the multi-step scan "
                    "dispatch has no per-batch cursor to checkpoint"
                )
        self.save_every_steps = save_every_steps
        self.handle_preemption = bool(handle_preemption)
        self.telemetry = bool(telemetry)
        if log_every_steps is not None:
            if log_every_steps < 1:
                raise ValueError(
                    f"log_every_steps must be >= 1, got {log_every_steps}"
                )
            self.log_every = int(log_every_steps)
        if desync_every_steps is not None and desync_every_steps < 1:
            raise ValueError(
                f"desync_every_steps must be >= 1, got {desync_every_steps}"
            )
        self.desync_every_steps = desync_every_steps
        if straggler_factor <= 1.0:
            raise ValueError(
                f"straggler_factor must be > 1, got {straggler_factor}"
            )
        self.straggler_factor = float(straggler_factor)
        from ml_trainer_tpu.telemetry.flight import get_recorder
        from ml_trainer_tpu.telemetry.spans import (
            PROFILE_ENV,
            PROFILE_TRIGGER_ENV,
            StepProfiler,
        )

        self._flight = get_recorder()
        self._telemetry: Optional[Any] = None  # built with the loaders
        self._cluster: Optional[Any] = None  # built with the telemetry
        self._memory_ledger: Optional[Any] = None  # built with the state
        self._profiler = StepProfiler("train")
        if self.telemetry:
            # Recompile forensics (telemetry/compile_watch.py): installed
            # BEFORE the first model-init compile so the ledger covers
            # every program this trainer builds.  Pure host bookkeeping —
            # the compiled programs and trajectory are untouched
            # (test-pinned).
            from ml_trainer_tpu.telemetry import compile_watch

            compile_watch.install()
            # A new trainer legitimately compiles (init, train/eval
            # steps): re-open warmup so a previous run's warm flag does
            # not mis-flag this construction as recompile incidents.
            compile_watch.mark_cold()
        # Per-step profiler polling only when something can trigger it.
        self._profile_hook = bool(
            self.telemetry
            or os.environ.get(PROFILE_ENV)
            or os.environ.get(PROFILE_TRIGGER_ENV)
        )
        self.preempted = False
        self._preempt_requested = False
        from ml_trainer_tpu.resilience.elastic import resolve_elastic

        self.elastic = resolve_elastic(elastic)
        if self.elastic is not None and self.steps_per_execution > 1:
            raise ValueError(
                "elastic reshape requires steps_per_execution=1: the "
                "drain needs the per-batch cursor the multi-step scan "
                "dispatch does not keep"
            )
        self.reshapes: list = []  # elastic mesh-reshape records this run
        self._reshape_request = None  # pending drain (set between steps)
        self._reshape_pending: Optional[dict] = None  # drained; reshape due
        self._live_hosts: list = (
            list(range(self.elastic.n_hosts)) if self.elastic else []
        )
        self.rollbacks = 0  # rollback-to-last-good events this run
        self.skipped_steps: list = []  # per-epoch skipped-step counts
        self._skipped_base = 0  # cumulative counter at current epoch start
        self._resume_mid: Optional[dict] = None  # mid-epoch resume cursor
        self._best_val = math.inf
        self._bad_epochs = 0
        if self.is_parallel:
            # Rendezvous — the init_process_group analog (ref: src/trainer.py:59).
            initialize_distributed(cfg.backend)
        # After the rendezvous (which must precede backend init), before
        # anything is built on the devices.
        _check_platform(cfg.backend)
        if self.is_parallel:
            self.mesh = create_mesh(mesh_shape)
        elif mesh_shape is not None:
            # An explicit mesh is honored without the multi-host rendezvous —
            # the normal single-process multi-chip TPU VM setup.
            self.mesh = create_mesh(mesh_shape)
        else:
            self.mesh = create_mesh(devices=jax.devices()[:1])
        # Batch divides over the data-like axes only; tensor/sequence axes
        # replicate the batch and shard the model instead.
        self._data_parallel = int(
            np.prod(
                [
                    self.mesh.shape[a]
                    for a in ("data", "fsdp")
                    if a in self.mesh.axis_names
                ]
            )
        ) if any(a in self.mesh.axis_names for a in ("data", "fsdp")) else 1
        self._batch_sharding = batch_sharding(self.mesh)
        self._replicated = replicated(self.mesh)
        if self.elastic is not None and process_count() == 1:
            # Simulated host groups: data is the outermost mesh axis, so
            # each host must own an equal contiguous block of data
            # replicas for the post-kill grid to stay a valid mesh.
            n_hosts = self.elastic.n_hosts
            data = int(self.mesh.shape.get("data", 1))
            if data < n_hosts or data % n_hosts or (
                int(self.mesh.size) % n_hosts
            ):
                raise ValueError(
                    f"elastic n_hosts={n_hosts} needs the mesh's data "
                    f"axis (size {data} over {int(self.mesh.size)} "
                    "devices) to split into equal host groups; pass a "
                    "mesh_shape whose data axis is divisible by n_hosts"
                )
        if self.dp_update == "sharded":
            # Pure-DP only: the sharded update re-expresses the gradient
            # psum as explicit reduce-scatter/all-gather over the data
            # axis; model-parallel axes would need their own collectives
            # composed in (tracked as future work in docs).
            model_axes = [
                a for a in self.mesh.axis_names
                if a != "data" and self.mesh.shape[a] > 1
            ]
            if self._sharding_rules is not None or model_axes:
                raise ValueError(
                    "dp_update='sharded' requires a pure data-parallel "
                    f"mesh with no sharding_rules; got mesh axes "
                    f"{dict(self.mesh.shape)}"
                )
            if self.steps_per_execution > 1:
                raise ValueError(
                    "dp_update='sharded' requires steps_per_execution=1"
                )
            if "data" not in self.mesh.axis_names or (
                self.mesh.shape["data"] < 2
            ):
                logger.warning(
                    "dp_update='sharded' on a single-replica mesh has "
                    "nothing to shard; falling back to the fused step."
                )
                self.dp_update = "fused"
                self.fused_adam = False
            elif not self._shard_opt_state:
                # The sharded update owns 1/N of the moments by
                # construction — ZeRO-1 placement is implied.
                logger.info(
                    "dp_update='sharded' implies shard_opt_state=True "
                    "(ZeRO-1 moment placement)."
                )
                self._shard_opt_state = True

        logger.info(f"Training on device: {jax.default_backend()}.")

        self.rng = jax.random.PRNGKey(cfg.seed)
        if label_smoothing and self._takes_targets:
            raise ValueError(
                "label_smoothing is not supported for models that "
                "compute their own loss (the chunked LM head applies "
                "plain cross entropy inside the forward)"
            )
        self.criterion = get_criterion(
            cfg.criterion, label_smoothing=label_smoothing
        )
        self.pred_function = get_prediction_function(cfg.pred_function)
        self.metric_fn = get_metric(cfg.metric, self.pred_function)
        # Epoch finalizer for nonlinear report metrics (e.g. perplexity
        # accumulates mean NLL and exponentiates ONCE per epoch — see
        # ops/metrics.py METRICS); identity for the linear ones.
        _fin = getattr(self.metric_fn, "finalize", None)
        self._metric_finalize = (
            (lambda v: float(_fin(v))) if _fin is not None else (lambda v: v)
        )
        if self._takes_targets and self.metric_fn is not None:
            raise ValueError(
                "metric must be None for models that compute their own "
                "loss (the forward returns a scalar, not logits to score); "
                f"got metric={cfg.metric!r}"
            )

        self.state: Optional[TrainState] = None
        self.train_loader: Optional[Loader] = None
        self.val_loader: Optional[Loader] = None
        self._plateau: Optional[PlateauController] = None
        self._lr_scale = 1.0
        self._eval_cache: dict = {}

        if datasets:
            train_set, val_set = datasets
            # Retained for elastic reshapes: the 'per_device' batch
            # policy rebuilds the loaders at the shrunk global batch.
            self._datasets = (train_set, val_set)
            self._build_loaders(train_set, val_set, batch_size, cfg)
            self._build_state_and_steps(cfg)
        else:
            logger.warning("Testing only available. No datasets in arguments.")

    # ------------------------------------------------------------------ data
    def _build_loaders(self, train_set, val_set, batch_size, cfg) -> None:
        logger.info("Loading training and validation set.")
        logger.info("Preparing the data.")
        d = self._data_parallel * self.grad_accum_steps
        # Reference semantics: global batch ÷ world, floored at 1
        # (ref: src/trainer.py:63-64).  Here the division happens through the
        # mesh sharding, so we only round the global batch down to a multiple
        # of the data-parallel degree × grad-accum microbatch count (and up
        # to at least one sample per chip per microbatch).
        eff = max(batch_size // d, 1) * d
        if eff != batch_size:
            logger.warning(
                f"Global batch {batch_size} adjusted to {eff} to divide "
                f"across {d} data-parallel devices."
            )
        drop_last = d > 1  # static shapes across the mesh
        train_sampler = None
        if self.is_parallel:
            train_sampler = ShardedSampler(
                len(train_set) if hasattr(train_set, "__len__") else 0,
                num_replicas=process_count(),
                rank=process_index(),
                shuffle=True,
                seed=cfg.seed,
            )
        per_host = eff // process_count()
        self.global_batch = eff

        def build(dataset, shuffle, sampler, seed):
            plan = None
            if self._loader_kind in ("auto", "native"):
                from ml_trainer_tpu.data.native import (
                    native_available,
                    native_plan,
                )

                plan = native_plan(dataset)
                if plan is not None and not native_available():
                    plan = None
                if plan is not None and self._loader_kind == "auto":
                    # The native loader pads a ragged final batch by
                    # wrapping (repeats leading samples); the Python Loader
                    # yields a short batch.  'auto' must never change batch
                    # semantics, so fall back unless the split is exact.
                    n = len(sampler) if sampler is not None else len(dataset)
                    if not drop_last and n % per_host != 0:
                        plan = None
                if self._loader_kind == "native" and plan is None:
                    raise ValueError(
                        "loader='native' requires a uint8 NHWC ArrayDataset "
                        "with the reference augmentation pipeline (and a "
                        "working g++); got an unsupported dataset/transform"
                    )
            if plan is not None:
                from ml_trainer_tpu.data.native import NativeLoader

                logger.info("Using the native (C++) input pipeline.")
                return NativeLoader(
                    dataset, batch_size=per_host, shuffle=shuffle,
                    sampler=sampler, drop_last=drop_last, seed=seed, **plan,
                )
            return Loader(
                dataset, batch_size=per_host, shuffle=shuffle,
                sampler=sampler, drop_last=drop_last, seed=seed,
            )

        self.train_loader = build(
            train_set, train_sampler is None, train_sampler, cfg.seed
        )
        # The reference evaluates the FULL validation set on every rank with
        # shuffle=True (ref: src/trainer.py:79) — kept, modulo drop_last for
        # static shapes on a sharded mesh (documented divergence).
        self.val_loader = build(val_set, True, None, cfg.seed + 1)
        if len(self.train_loader) == 0 or len(self.val_loader) == 0:
            raise ValueError(
                f"Loader yields no batches (train {len(self.train_loader)}, "
                f"val {len(self.val_loader)}): dataset shard smaller than the "
                f"per-host batch {per_host} with drop_last={drop_last}. "
                "Reduce the global batch size or grow the dataset."
            )
        logger.debug(
            "Processes {}/{} ({:.0f}%) of train data".format(
                len(self.train_loader.sampler),
                len(self.train_loader.dataset),
                100.0
                * len(self.train_loader.sampler)
                / len(self.train_loader.dataset),
            )
        )
        logger.debug(
            "Processes {}/{} ({:.0f}%) of validation data".format(
                len(self.val_loader.sampler),
                len(self.val_loader.dataset),
                100.0
                * len(self.val_loader.sampler)
                / len(self.val_loader.dataset),
            )
        )

    # ----------------------------------------------------------------- state
    def _apply(self, variables, x, train: bool, rngs=None, mutable=False,
               targets=None):
        kwargs = {}
        if self._takes_train:
            kwargs["train"] = train
        if targets is not None:
            kwargs["targets"] = targets
        if mutable:
            if not isinstance(mutable, (list, tuple)):
                raise TypeError(
                    f"mutable must be False or a list of collection names, "
                    f"got {mutable!r}"
                )
            kwargs["mutable"] = list(mutable)
        with kernel_mesh(self.mesh):
            return self.model.apply(variables, x, rngs=rngs, **kwargs)

    def _build_state_and_steps(self, cfg) -> None:
        sample_x, _ = next(iter(self.train_loader))
        sample_x = jnp.asarray(sample_x[: max(self.global_batch // process_count(), 1)])
        self.rng, init_rng, dropout_rng = jax.random.split(self.rng, 3)
        init_kwargs = {"train": False} if self._takes_train else {}
        # jit the init: flax executes it eagerly by default, one device
        # dispatch per op.  Jitted it is one compile + one execution.
        init_fn = jax.jit(
            self.model.init,
            static_argnames="train" if self._takes_train else (),
        )
        variables = init_fn(
            {"params": init_rng, "dropout": dropout_rng}, sample_x, **init_kwargs
        )
        params = variables["params"]
        batch_stats = variables.get("batch_stats", {})
        self._has_batch_stats = bool(batch_stats)
        if self.dp_update == "sharded" and self._has_batch_stats:
            raise ValueError(
                "dp_update='sharded' does not support batch_stats models "
                "(per-replica BatchNorm statistics inside the shard_map "
                "body would diverge from the fused global-batch stats); "
                "use the fused step for BatchNorm models"
            )
        # Detect sown auxiliary losses (MoEMLP's load-balance term) with a
        # shape-only trace of the TRAIN-mode forward — init() runs at
        # train=False, which would miss losses gated on training (router
        # z-loss variants).  batch_stats must stay mutable during the probe
        # or BatchNorm models would fail the trace.  The train step then
        # captures and applies whatever the probe finds.
        probe_cols = ["losses"] + (["batch_stats"] if batch_stats else [])
        mut_shapes = jax.eval_shape(
            lambda v, r: self._apply(
                v, sample_x, train=True, rngs={"dropout": r},
                mutable=probe_cols,
            )[1],
            variables, dropout_rng,
        )
        self._has_aux_losses = bool(mut_shapes.get("losses"))

        self.steps_per_epoch = len(self.train_loader)
        self.lr_schedule = make_lr_schedule(
            cfg.scheduler, cfg.lr, self.steps_per_epoch,
            # epochs may be None (eval-only Trainer): the warmup schedules
            # then fall back to their documented fixed horizon.
            total_steps=(
                self.steps_per_epoch * self.epochs if self.epochs else None
            ),
        )
        self.tx = get_optimizer(
            cfg.optimizer, self.lr_schedule, cfg.momentum, cfg.weight_decay,
            decay_mask=(
                decay_mask_matrices_only
                if self.decay_exclude_bias_norm else None
            ),
        )
        # Always chain (both clip and identity carry EmptyState), so the
        # opt_state pytree structure — and therefore checkpoints — do not
        # depend on whether clipping is on: the flag can toggle across a
        # resume.  The sharded-update path keeps the identity slot and
        # clips manually instead: inside its step the optimizer sees 1/N
        # shards, so optax's clip would compute a per-replica norm — the
        # step psums the true global norm itself (same math, same
        # opt_state structure).
        self.tx = optax.chain(
            optax.clip_by_global_norm(self.grad_clip_norm)
            if (self.grad_clip_norm is not None
                and self.dp_update != "sharded")
            else optax.identity(),
            self.tx,
        )
        if self.lora is not None:
            # Freeze the base: only *_lora_A/*_lora_B leaves reach the
            # optimizer (clip included — the global norm is the
            # ADAPTER grads' norm); frozen leaves get set_to_zero
            # updates and, through optax's masking, NO optimizer state
            # — so moments shrink to the adapter fraction, which the
            # memory ledger's opt_state component makes visible.
            from ml_trainer_tpu.lora import lora_param_labels

            labels = lora_param_labels(params)
            n_lora = sum(
                1 for v in jax.tree.leaves(labels) if v == "lora"
            )
            if not n_lora:
                raise ValueError(
                    "Trainer(lora=...) found no *_lora_A/*_lora_B "
                    "params — do the configured targets exist on this "
                    "model?"
                )
            self.tx = optax.multi_transform(
                {"lora": self.tx, "frozen": optax.set_to_zero()},
                labels,
            )
            logger.info(
                f"LoRA: training {n_lora} adapter leaves (rank "
                f"{self.lora.rank}, targets {self.lora.targets}); "
                f"{len(jax.tree.leaves(labels)) - n_lora} base leaves "
                "frozen with no optimizer state."
            )
        if cfg.scheduler == "ReduceLROnPlateau":
            self._plateau = PlateauController(cfg.lr)

        self.rng, state_rng = jax.random.split(self.rng)
        # Place params per the sharding rules (replicated when rules=None —
        # the DDP initial-broadcast analog, ref: src/trainer.py:98).
        # Optimizer state is created FROM the placed params, so momenta etc.
        # inherit each param's sharding; leaves tx.init creates from scratch
        # (step counters) land on the default device and are re-placed
        # replicated so the whole state lives on the mesh.
        from ml_trainer_tpu.parallel import shard_params
        from ml_trainer_tpu.parallel.tp_rules import validate_tp_mesh

        if self._sharding_rules is not None:
            # Fail fast on head-splitting tensor degrees (GQA: tensor
            # must divide num_kv_heads) before any placement happens.
            validate_tp_mesh(self.model, self.mesh)
        params = shard_params(params, self.mesh, self._sharding_rules)
        if batch_stats:
            batch_stats = shard_params(
                batch_stats, self.mesh, self._sharding_rules
            )
        if self._shard_opt_state and self._sharding_rules is None:
            # Pure-DP ZeRO-1: decide shardings from SHAPES and jit-init with
            # out_shardings so the moments are BORN partitioned — the full
            # replicated tree never materializes (tx.init would otherwise be
            # the peak-memory moment on exactly the memory-bound runs this
            # flag exists for).
            from ml_trainer_tpu.parallel import zero1_opt_shardings

            out_sh = zero1_opt_shardings(
                jax.eval_shape(self.tx.init, params), self.mesh
            )
            opt_state = jax.jit(self.tx.init, out_shardings=out_sh)(params)
        else:
            if self._sharding_rules is not None:
                # Rule-sharded params (TP/FSDP): moments must INHERIT each
                # param's sharding (replicating them is the memory blowup
                # sharding exists to prevent).  jit alone erases the
                # shardings (zeros_like has no data dependence for GSPMD to
                # propagate) and eager init would crash on multi-host
                # non-addressable arrays — so jit with explicit
                # out_shardings, mapped from the params by shape (shapes
                # repeating across layers carry the same rule; ambiguous
                # shapes fall back replicated, a memory — not correctness —
                # concession).
                by_shape: dict = {}
                for p in jax.tree.leaves(params):
                    cur = by_shape.get(p.shape)
                    if cur is None:
                        by_shape[p.shape] = p.sharding
                    elif cur != p.sharding:
                        by_shape[p.shape] = self._replicated
                out_sh = jax.tree.map(
                    lambda l: by_shape.get(l.shape, self._replicated),
                    jax.eval_shape(self.tx.init, params),
                )
                opt_state = jax.jit(self.tx.init, out_shardings=out_sh)(params)
            else:
                # Replicated params (pure DP, incl. one chip): jit is safe,
                # the placement re-places everything replicated anyway.
                # place_tree, not per-leaf device_put: multi-host the leaf
                # storm is both O(leaves) DCN broadcasts and a gloo-CPU
                # abort (parallel/sharding.py).
                from ml_trainer_tpu.parallel import place_tree

                opt_raw = jax.jit(self.tx.init)(params)
                opt_state = place_tree(
                    opt_raw,
                    jax.tree.map(
                        lambda x: x.sharding
                        if isinstance(
                            getattr(x, "sharding", None),
                            jax.sharding.NamedSharding,
                        )
                        else self._replicated,
                        opt_raw,
                    ),
                )
            if self._shard_opt_state:
                # Model-sharded params (TP/FSDP rules): re-place only the
                # still-replicated leaves, leaving rule-sharded moments be.
                from ml_trainer_tpu.parallel import shard_opt_state as _shard_opt

                opt_state = _shard_opt(opt_state, self.mesh)
        # EMA weights start as a copy of the placed params (same shardings).
        ema_params = (
            jax.tree.map(jnp.copy, params) if self.ema_decay is not None
            else None
        )
        # The replicated host-side scalars (step/rng/guard counters) place
        # in ONE program — see place_tree for why per-leaf device_put is
        # not multi-host-safe.
        from ml_trainer_tpu.parallel import place_tree

        host_scalars = {
            "step": jnp.zeros((), jnp.int32),
            "rng": state_rng,
            "skipped": jnp.zeros((), jnp.int32),
            "streak": jnp.zeros((), jnp.int32),
        }
        if self._loss_scale_cfg is not None:
            # Dynamic loss scaling: the scale and its growth counter are
            # on-device state, updated by the same compiled step that
            # uses them (precision.py semantics).
            host_scalars["loss_scale"] = jnp.asarray(
                self._loss_scale_cfg.init_scale, jnp.float32
            )
            host_scalars["good"] = jnp.zeros((), jnp.int32)
        scalars = place_tree(
            host_scalars,
            {k: self._replicated for k in host_scalars},
        )
        self.state = TrainState(
            step=scalars["step"],
            params=params,
            opt_state=opt_state,
            batch_stats=batch_stats,
            rng=scalars["rng"],
            ema_params=ema_params,
            # Guard counters ride in the state so the compiled step can
            # maintain them without a host sync (fetched once per epoch).
            skipped_steps=scalars["skipped"],
            bad_streak=scalars["streak"],
            loss_scale=scalars.get("loss_scale"),
            good_steps=scalars.get("good"),
        )
        self._state_shardings = jax.tree.map(lambda x: x.sharding, self.state)
        if self._sharded_ckpt is None:
            # Auto: the host-0 v2 gather is a deadlock (not merely a RAM
            # spike) exactly when some leaf is partitioned across
            # processes — one process would launch a global allgather the
            # others never join.  Replicated-only multi-host state keeps
            # the reference's rank-0 format for compatibility.
            self._sharded_ckpt = process_count() > 1 and any(
                not leaf.is_fully_addressable
                and not getattr(leaf, "is_fully_replicated", False)
                for leaf in jax.tree.leaves(self.state)
            )
            if self._sharded_ckpt:
                logger.info(
                    "Partitioned multi-host state: using per-host sharded "
                    "checkpoints (sharded_checkpoint=True)."
                )
        self._bucket_plan = None
        if self.dp_update == "sharded":
            from ml_trainer_tpu.parallel import plan_grad_buckets

            self._bucket_plan = plan_grad_buckets(
                params, int(self.mesh.shape["data"]),
                bucket_bytes=int(self.bucket_mb * 2 ** 20),
            )
            logger.info(
                f"Sharded DP update: {len(self._bucket_plan.buckets)} "
                f"reduce-scatter buckets over data={self.mesh.shape['data']} "
                f"(bucket_mb={self.bucket_mb}, analytic overlap fraction "
                f"{self._bucket_plan.overlap_fraction:.2f})."
            )
        # Batch geometry for the telemetry spine AND the memory ledger
        # (set regardless of the telemetry flag so an on-demand
        # memory.train_ledger(trainer) can always price the batch).
        self._batch_geometry = (self.global_batch,) + tuple(sample_x.shape[1:])
        self._batch_dtype = sample_x.dtype
        if self.telemetry:
            from ml_trainer_tpu.telemetry.cluster import ClusterTelemetry
            from ml_trainer_tpu.telemetry.train_metrics import TrainTelemetry

            # Cluster aggregation rides the telemetry flag: host-local
            # heartbeats at every sync, ONE small allgather per epoch
            # (degenerate single-host publish when not distributed).
            self._cluster = ClusterTelemetry(
                flight=self._flight,
                straggler_factor=self.straggler_factor,
                # Straggler VERDICT hook: the elastic controller turns a
                # straggler past its reshape factor into a drain+reshape
                # request (self-gating — a no-op without elastic=).
                on_straggler=self._on_straggler_verdict,
            )
            self._telemetry = TrainTelemetry(
                model=self.model,
                model_name=type(self.model).__name__,
                global_batch=self.global_batch,
                batch_shape=self._batch_geometry,
                flight=self._flight,
                cluster=self._cluster,
                compute_dtype=self.precision.label(),
                overlap_fraction=(
                    self._bucket_plan.overlap_fraction
                    if self._bucket_plan is not None else None
                ),
            )
            # HBM ledger (telemetry/memory.py): a metadata-only walk of
            # the state just placed — published once here (the analytic
            # components never change during the run) and attached to
            # every flight dump, with the live per-device view, so OOM
            # forensics name the resident components.
            from ml_trainer_tpu.telemetry import (
                compile_watch,
                memory as _memory,
            )

            self._memory_ledger = _memory.train_ledger(self)
            self._memory_ledger.publish()
            self._flight.record(
                "memory_ledger",
                resident_bytes=int(self._memory_ledger.resident_bytes()),
                peak_bytes=int(self._memory_ledger.peak_bytes()),
                components={
                    c.name: int(c.bytes)
                    for c in self._memory_ledger.components
                },
            )
            self._flight.register_context_provider(
                "memory", _memory.memory_snapshot_payload
            )
            self._flight.register_context_provider(
                "compile_events",
                lambda: compile_watch.recent_events_payload(16),
            )
            # The committed graft-lint baseline's fingerprint rides every
            # dump: post-mortems know which static-contract set this
            # build was checked against (analysis/__init__.py).
            from ml_trainer_tpu.analysis import register_flight_context

            register_flight_context(self._flight)
            logger.info(
                "memory_ledger",
                resident_mb=round(
                    self._memory_ledger.resident_bytes() / 2 ** 20, 2
                ),
                peak_mb=round(
                    self._memory_ledger.peak_bytes() / 2 ** 20, 2
                ),
            )
        self._build_steps()

    def _build_steps(self) -> None:
        """(Re)build the compiled train/eval steps against the CURRENT
        mesh, shardings and bucket plan.  Split from
        ``_build_state_and_steps`` so an elastic reshape
        (``_perform_reshape``) can rebuild the programs after swapping
        the mesh under the same Trainer."""
        train_step = (
            self._make_sharded_train_step()
            if self.dp_update == "sharded" else self._make_train_step()
        )
        # Pin the output state to the SAME shardings it was born with: the
        # state's placement is a class invariant (resume/device_put, the
        # export path, and the v3 checkpoint writer all key off
        # _state_shardings).  Left unpinned, GSPMD may return some params
        # leaves data-PARTITIONED under ZeRO-1 (the sharded moments
        # propagate into the update), which silently turns the
        # weights-export into a cross-host collective — observed as a
        # deadlock against the v3 commit barrier.  Pinning restores ZeRO-1
        # semantics proper: the weight allgather happens INSIDE the
        # compiled step.
        step_out_shardings = (
            (self._state_shardings, None, None, None)
            if self.telemetry else (self._state_shardings, None, None)
        )
        self._train_step = jax.jit(
            train_step, donate_argnums=0, out_shardings=step_out_shardings
        )
        if self.steps_per_execution > 1:
            # K optimizer steps per dispatch: scan the SAME step function
            # over stacked batches [K, B, ...] — identical update sequence,
            # one host round-trip per K steps.
            def multi_step(state, xs, ys, lr_scale):
                def body(state, xy):
                    out = train_step(state, *xy, lr_scale)
                    return out[0], out[1:]

                state, outs = jax.lax.scan(body, state, (xs, ys))
                losses, metrics = outs[0], outs[1]
                if self.telemetry:
                    # The dispatch's LAST step's stats — what the host
                    # would have seen stepping per-batch at this cadence.
                    last_stats = jax.tree.map(lambda s: s[-1], outs[2])
                    return state, losses.sum(), metrics.sum(), last_stats
                return state, losses.sum(), metrics.sum()

            self._train_multi_step = jax.jit(
                multi_step, donate_argnums=0,
                out_shardings=step_out_shardings,
            )
            # Stacked batches put the step dim first: same data-axis split
            # on dim 1 (and sequence on dim 2 when live).
            spec = self._batch_sharding.spec
            self._stacked_sharding = jax.sharding.NamedSharding(
                self.mesh, P(None, *spec)
            )
        self._eval_step, self._eval_multi_step = self._make_eval_step(
            self.model, self._takes_train, self._has_batch_stats,
            multi=self.steps_per_execution > 1,
        )

    def _make_grads_for(self):
        """The shared forward/backward closure of both train-step flavors:
        ``grads_for(params, batch_stats, x, y, dropout_rng, scale=None)``
        returns ``(grads, new_bs, loss, metric_val)`` where ``grads``
        differentiate ``scale * loss`` (the caller unscales once, after
        any accumulation) and ``loss``/``metric_val`` are unscaled.  With
        an active bf16 policy, master params and float inputs cast to the
        compute dtype at the top (gradients come home fp32 through the
        cast's vjp) and outputs cast back to fp32 before the criterion;
        at fp32 the traced program is exactly the pre-policy one."""
        criterion, metric_fn = self.criterion, self.metric_fn
        has_bs, model_apply = self._has_batch_stats, self._apply
        takes_targets = self._takes_targets
        has_aux = getattr(self, "_has_aux_losses", False)
        aux_weight = self.moe_aux_weight
        compute_dtype = self._compute_dtype
        if compute_dtype is not None:
            from ml_trainer_tpu.precision import cast_floating, cast_like

        def grads_for(params, batch_stats, x, y, dropout_rng, scale=None):
            def loss_fn(params):
                if compute_dtype is not None:
                    p_apply = cast_floating(params, compute_dtype)
                    x_apply = (
                        x.astype(compute_dtype)
                        if jnp.issubdtype(x.dtype, jnp.inexact) else x
                    )
                else:
                    p_apply, x_apply = params, x
                variables = {"params": p_apply}
                if has_bs:
                    variables["batch_stats"] = batch_stats
                mutable_cols = (["batch_stats"] if has_bs else []) + (
                    ["losses"] if has_aux else []
                )
                # Self-loss models (GPT2 chunked LM head): labels go
                # through the forward, the output IS the loss.
                fwd_targets = y if takes_targets else None
                if mutable_cols:
                    out, mutated = model_apply(
                        variables, x_apply, train=True,
                        rngs={"dropout": dropout_rng}, mutable=mutable_cols,
                        targets=fwd_targets,
                    )
                    new_bs = mutated.get("batch_stats", batch_stats)
                    if compute_dtype is not None and has_bs:
                        # Stats mutated under bf16 come home at the state
                        # dtype (checkpoints and where-selects depend on
                        # dtype-stable state leaves).
                        new_bs = cast_like(new_bs, batch_stats)
                else:
                    out = model_apply(
                        variables, x_apply, train=True,
                        rngs={"dropout": dropout_rng}, targets=fwd_targets,
                    )
                    mutated = {}
                    new_bs = batch_stats
                if compute_dtype is not None and hasattr(out, "astype"):
                    # Precision.output: criterion/metrics read fp32.
                    out = out.astype(jnp.float32)
                loss = out if takes_targets else criterion(out, y)
                if has_aux:
                    # Sown auxiliary losses (e.g. MoE load-balance,
                    # models/moe.py): summed over layers, scaled once.
                    aux_terms = jax.tree.leaves(mutated.get("losses", {}))
                    if aux_terms:
                        loss = loss + aux_weight * sum(aux_terms)
                scaled = loss if scale is None else loss * scale
                return scaled, (loss, out, new_bs)

            (_, (loss, out, new_bs)), grads = jax.value_and_grad(
                loss_fn, has_aux=True
            )(params)
            metric_val = (
                metric_fn(out, y) if metric_fn is not None else jnp.zeros(())
            )
            return grads, new_bs, loss, metric_val

        return grads_for

    def _scale_streak_updates(self, state, ok, cfg, one, zero):
        """Shared guard bookkeeping for loss scaling: the bad-streak rule
        (an overflow is the scale's fault while it can still back off —
        it must NOT advance the rollback streak) and the dynamic
        scale/growth-counter arithmetic.  Returns
        ``(new_streak, replace_kwargs)``."""
        if cfg is None:
            return jnp.where(ok, zero, state.bad_streak + one), {}
        attributed = state.loss_scale > cfg.min_scale
        new_streak = jnp.where(
            ok, zero,
            jnp.where(attributed, state.bad_streak, state.bad_streak + one),
        )
        grown = state.good_steps + one >= cfg.growth_interval
        new_scale = jnp.where(
            ok,
            jnp.where(
                grown,
                jnp.minimum(
                    state.loss_scale * cfg.growth_factor, cfg.max_scale
                ),
                state.loss_scale,
            ),
            jnp.maximum(state.loss_scale * cfg.backoff_factor, cfg.min_scale),
        )
        new_good = jnp.where(
            ok & ~grown, state.good_steps + one, jnp.zeros_like(
                state.good_steps
            )
        )
        return new_streak, {"loss_scale": new_scale, "good_steps": new_good}

    def _make_train_step(self):
        tx = self.tx
        accum = self.grad_accum_steps
        ema_decay = self.ema_decay
        guard = self.nonfinite_guard
        telemetry = self.telemetry
        cfg = self._loss_scale_cfg
        grads_for = self._make_grads_for()

        def train_step(state: TrainState, x, y, lr_scale):
            rng, dropout_rng = jax.random.split(state.rng)
            scale = state.loss_scale if cfg is not None else None
            # Data-parallel gradient averaging happens implicitly in
            # grads_for: the batch is sharded over the mesh's data axis while
            # params are replicated, so XLA inserts the psum the reference
            # performs via DDP's bucketed all-reduce
            # (ref: src/trainer.py:98, 152-158).
            if accum == 1:
                grads, new_bs, loss, metric_val = grads_for(
                    state.params, state.batch_stats, x, y, dropout_rng, scale
                )
                if scale is not None:
                    grads = jax.tree.map(lambda g: g / scale, grads)
            else:
                # lax.scan over microbatches: gradients sum on-device, one
                # optimizer update per global batch (GPT-2 grad-accum
                # config, BASELINE.json configs[4]).
                micro = x.shape[0] // accum
                xm = x.reshape((accum, micro) + x.shape[1:])
                ym = y.reshape((accum, micro) + y.shape[1:])

                def body(carry, xy):
                    bs, g_sum, l_sum, m_sum, drng = carry
                    drng, sub = jax.random.split(drng)
                    g, bs, l, m = grads_for(state.params, bs, *xy, sub, scale)
                    g_sum = jax.tree.map(jnp.add, g_sum, g)
                    return (bs, g_sum, l_sum + l, m_sum + m, drng), None

                zeros = jax.tree.map(jnp.zeros_like, state.params)
                (new_bs, g_sum, l_sum, m_sum, _), _ = jax.lax.scan(
                    body,
                    (state.batch_stats, zeros, jnp.zeros(()), jnp.zeros(()),
                     dropout_rng),
                    (xm, ym),
                )
                if scale is None:
                    grads = jax.tree.map(lambda g: g / accum, g_sum)
                else:
                    # One unscale folds the microbatch mean and the loss
                    # scale (the scale was constant across the scan).
                    grads = jax.tree.map(lambda g: g / (accum * scale), g_sum)
                loss = l_sum / accum
                metric_val = m_sum / accum
            updates, new_opt = tx.update(grads, state.opt_state, state.params)
            updates = jax.tree.map(lambda u: u * lr_scale, updates)
            new_params = optax.apply_updates(state.params, updates)
            new_ema = (
                jax.tree.map(
                    lambda e, p: ema_decay * e + (1.0 - ema_decay) * p,
                    state.ema_params, new_params,
                )
                if ema_decay is not None else state.ema_params
            )
            new_skipped, new_streak = state.skipped_steps, state.bad_streak
            replace_kwargs = {}
            raw_loss = loss  # pre-guard: telemetry must SEE the NaN
            if guard:
                # On-device all-finite guard: a non-finite loss or any
                # non-finite gradient leaf reverts every learned quantity
                # to the pre-step value via `where` selects — same
                # compiled program either way (no lax.cond branch, no
                # recompile, no host sync).  step/rng still advance (the
                # batch was consumed; the LR schedule and dropout stream
                # stay aligned with the data), while the optimizer's
                # inner counters revert with the moments — the skipped
                # step never happened as far as Adam bias correction is
                # concerned.  When everything is finite, `where(ok, n, o)
                # == n` exactly, so guarded and unguarded trajectories
                # are bit-identical.
                ok = jnp.isfinite(loss)
                for g in jax.tree.leaves(grads):
                    ok = ok & jnp.all(jnp.isfinite(g))

                def sel(n, o):
                    return jax.tree.map(
                        lambda a, b: jnp.where(ok, a, b), n, o
                    )

                new_params = sel(new_params, state.params)
                new_opt = sel(new_opt, state.opt_state)
                new_bs = sel(new_bs, state.batch_stats)
                if ema_decay is not None:
                    new_ema = sel(new_ema, state.ema_params)
                one = jnp.ones((), jnp.int32)
                zero = jnp.zeros((), jnp.int32)
                new_skipped = state.skipped_steps + jnp.where(ok, zero, one)
                # Loss scaling folds into the guard here: an overflow
                # halves the scale WITHOUT advancing the rollback streak
                # (fp32 / no-scaling keeps the exact pre-policy streak).
                new_streak, replace_kwargs = self._scale_streak_updates(
                    state, ok, cfg, one, zero
                )
                # A skipped step contributes zero to the epoch sums so
                # one NaN cannot poison the whole epoch's history.
                loss = jnp.where(ok, loss, jnp.zeros_like(loss))
                metric_val = jnp.where(
                    ok, metric_val, jnp.zeros_like(metric_val)
                )
            new_state = state.replace(
                step=state.step + 1,
                params=new_params,
                opt_state=new_opt,
                batch_stats=new_bs,
                rng=rng,
                ema_params=new_ema,
                skipped_steps=new_skipped,
                bad_streak=new_streak,
                **replace_kwargs,
            )
            if telemetry:
                # On-device step stats (telemetry/train_metrics.py):
                # pure functions of values this program already holds —
                # same trajectory, same single compiled program, no
                # host sync; the host fetches them at the log cadence.
                from ml_trainer_tpu.telemetry.train_metrics import (
                    step_stats,
                )

                stats = step_stats(raw_loss, grads, updates, new_params)
                return new_state, loss, metric_val, stats
            return new_state, loss, metric_val

        return train_step

    def _make_sharded_train_step(self):
        """The bucketed reduce-scatter + cross-replica sharded-update step
        (dp_update='sharded'; arXiv 2004.13336 composed with TorchTitan's
        bucketed comm/compute overlap).

        One ``shard_map`` over the pure-DP data axis replaces the
        compiler-inserted tail psum with explicit structure:

        1. each replica runs forward/backward on its batch shard (local
           gradients, never globally reduced in full);
        2. gradients leave through per-bucket ``reduce_scatter`` calls in
           reverse topological order — each bucket's collective depends
           only on its own leaves' gradients, so the XLA latency-hiding
           scheduler can run it while earlier layers' gradients are
           still computing (a single fused psum serializes after the
           whole backward);
        3. the optimizer update runs on this replica's 1/N shard of
           grads/params/ZeRO-1 moments (update FLOPs and moment memory
           ÷ N); grad clipping psums the true global norm first;
        4. fresh weights return via per-bucket ``all_gather``.

        Math matches the fused step (trajectory-equality test-pinned):
        reduce-scatter of local-mean grads / N == the global-mean psum,
        and every optimizer in the zoo is elementwise per leaf."""
        from jax import lax, shard_map

        from ml_trainer_tpu.parallel import (
            bucketed_all_gather,
            bucketed_reduce_scatter,
            collectives as col,
        )
        from ml_trainer_tpu.telemetry.train_metrics import _global_norm

        mesh = self.mesh
        n = int(mesh.shape["data"])
        plan = self._bucket_plan
        tx = self.tx
        accum = self.grad_accum_steps
        ema_decay = self.ema_decay
        guard = self.nonfinite_guard
        telemetry = self.telemetry
        cfg = self._loss_scale_cfg
        clip = self.grad_clip_norm
        grads_for = self._make_grads_for()
        param_leaves = jax.tree.leaves(self.state.params)
        full_shapes = [leaf.shape for leaf in param_leaves]
        # Fused optimizer-tail kernels (ops/kernels/fused_adam.py):
        # eligibility was resolved in __init__ (plain Adam, wd=0).  The
        # fused path computes bit-for-bit the unfused optax chain —
        # pinned by the golden-trajectory test — while reading each
        # shard once per pass instead of once per optax op.
        use_fused = self.fused_adam
        lr_sched = self.lr_schedule
        if use_fused:
            from ml_trainer_tpu.ops.kernels.fused_adam import (
                adam_scalars,
                fused_adam_update,
                unscale_sqsum,
            )

        def split_sq(leaves):
            """(local-shard sq-sum, replicated sq-sum) of a mixed tree —
            the psum of the first plus the second is the global sq-norm."""
            loc = jnp.zeros((), jnp.float32)
            rep = jnp.zeros((), jnp.float32)
            for i, leaf in enumerate(leaves):
                s = jnp.sum(jnp.square(leaf.astype(jnp.float32)))
                loc, rep = (loc + s, rep) if plan.sharded[i] else (loc, rep + s)
            return loc, rep

        def body(state: TrainState, x, y, lr_scale):
            rng, dropout_rng = jax.random.split(state.rng)
            scale = state.loss_scale if cfg is not None else None
            if accum == 1:
                grads, _, loss, metric_val = grads_for(
                    state.params, state.batch_stats, x, y, dropout_rng, scale
                )
            else:
                micro = x.shape[0] // accum
                xm = x.reshape((accum, micro) + x.shape[1:])
                ym = y.reshape((accum, micro) + y.shape[1:])

                def accum_body(carry, xy):
                    bs, g_sum, l_sum, m_sum, drng = carry
                    drng, sub = jax.random.split(drng)
                    g, bs, l, m = grads_for(state.params, bs, *xy, sub, scale)
                    g_sum = jax.tree.map(jnp.add, g_sum, g)
                    return (bs, g_sum, l_sum + l, m_sum + m, drng), None

                zeros = jax.tree.map(jnp.zeros_like, state.params)
                (_, grads, l_sum, m_sum, _), _ = jax.lax.scan(
                    accum_body,
                    (state.batch_stats, zeros, jnp.zeros(()), jnp.zeros(()),
                     dropout_rng),
                    (xm, ym),
                )
                loss = l_sum / accum
                metric_val = m_sum / accum
            # Epoch accounting reads global means (what the fused step's
            # sharded-batch criterion computes implicitly).
            loss = col.pmean(loss, "data")
            metric_val = col.pmean(metric_val, "data")

            g_leaves, g_def = jax.tree.flatten(grads)
            # (2) bucketed reduce-scatter: one collective per bucket, in
            # reverse backward-production order; each replica keeps its
            # 1/N dim-0 shard, summed across replicas.
            g_leaves = bucketed_reduce_scatter(g_leaves, plan, "data")
            rep_idx = [
                i for i in range(len(g_leaves)) if not plan.sharded[i]
            ]
            if rep_idx:
                # Indivisible leaves (rare: odd-dim heads, scalars) keep a
                # replicated update — ONE fused psum over their concat.
                flat = col.psum(
                    jnp.concatenate(
                        [g_leaves[i].reshape(-1) for i in rep_idx]
                    ),
                    "data",
                )
                off = 0
                for i in rep_idx:
                    size = int(np.prod(g_leaves[i].shape, initial=1))
                    g_leaves[i] = flat[off:off + size].reshape(
                        g_leaves[i].shape
                    )
                    off += size
            # Scatter/psum SUMMED local-mean grads: /n folds the replica
            # mean, /accum the microbatch mean, /scale the loss scale.
            denom = float(n * accum)
            d = denom if scale is None else denom * scale
            need_sq = clip is not None or telemetry
            sq_loc = sq_rep = None
            if use_fused:
                # One read of each shard yields BOTH the unscaled grad
                # and its f32 squared-norm contribution (the unfused
                # path reads the shard again in split_sq below).
                sq_loc = jnp.zeros((), jnp.float32)
                sq_rep = jnp.zeros((), jnp.float32)
                unscaled = []
                for i, g in enumerate(g_leaves):
                    g_u, s = unscale_sqsum(g, d, compute_sq=need_sq)
                    unscaled.append(g_u)
                    if need_sq:
                        sq_loc, sq_rep = (
                            (sq_loc + s, sq_rep) if plan.sharded[i]
                            else (sq_loc, sq_rep + s)
                        )
                g_leaves = unscaled
            else:
                g_leaves = [g / d for g in g_leaves]

            # (3) this replica's parameter shards (dim-0 block at its
            # axis index), moments arrive pre-sharded via in_specs.
            idx = col.axis_index("data")
            p_mixed = []
            for i, p in enumerate(jax.tree.leaves(state.params)):
                if plan.sharded[i]:
                    blocks = p.reshape((n, p.shape[0] // n) + p.shape[1:])
                    p_mixed.append(
                        lax.dynamic_index_in_dim(
                            blocks, idx, axis=0, keepdims=False
                        )
                    )
                else:
                    p_mixed.append(p)
            params_mixed = jax.tree.unflatten(g_def, p_mixed)
            grads_mixed = jax.tree.unflatten(g_def, g_leaves)

            g_sq = None
            factor = None
            if need_sq:
                if use_fused:
                    loc, rep = sq_loc, sq_rep
                else:
                    loc, rep = split_sq(g_leaves)
                g_sq = col.psum(loc, "data") + rep
            if clip is not None:
                # optax.clip_by_global_norm math over the TRUE global
                # norm (the chained optax clip would see one shard).
                gnorm = jnp.sqrt(g_sq)
                factor = clip / jnp.maximum(gnorm, clip)
                if not use_fused:
                    grads_mixed = jax.tree.map(
                        lambda g: g * factor, grads_mixed
                    )

            if use_fused:
                # Fused tail: clip ×, Adam moments, bias corrections,
                # schedule step, lr_scale and the param write in ONE
                # pass per leaf shard; opt_state rebuilt in optax's
                # exact chain(identity, adam(schedule)) structure, so
                # checkpoints and the guard's where-selects are
                # untouched.  The clip factor folds into the kernel
                # instead of a separate grads multiply.
                _e, (adam_st, sched_st) = state.opt_state
                count_inc, bc1, bc2, step_size, sched_inc = adam_scalars(
                    adam_st.count, sched_st.count, lr_sched
                )
                outs = [
                    fused_adam_update(
                        g, p, mu, nu, bc1=bc1, bc2=bc2,
                        step_size=step_size, lr_scale=lr_scale,
                        factor=factor,
                    )
                    for g, p, mu, nu in zip(
                        jax.tree.leaves(grads_mixed),
                        jax.tree.leaves(params_mixed),
                        jax.tree.leaves(adam_st.mu),
                        jax.tree.leaves(adam_st.nu),
                    )
                ]
                new_params_mixed = jax.tree.unflatten(
                    g_def, [o[0] for o in outs]
                )
                new_opt = (
                    optax.EmptyState(),
                    (
                        optax.ScaleByAdamState(
                            count=count_inc,
                            mu=jax.tree.unflatten(
                                g_def, [o[1] for o in outs]
                            ),
                            nu=jax.tree.unflatten(
                                g_def, [o[2] for o in outs]
                            ),
                        ),
                        optax.ScaleByScheduleState(count=sched_inc),
                    ),
                )
                updates = jax.tree.unflatten(g_def, [o[3] for o in outs])
            else:
                updates, new_opt = tx.update(
                    grads_mixed, state.opt_state, params_mixed
                )
                updates = jax.tree.map(lambda u: u * lr_scale, updates)
                new_params_mixed = optax.apply_updates(params_mixed, updates)

            new_skipped, new_streak = state.skipped_steps, state.bad_streak
            replace_kwargs = {}
            raw_loss = loss
            if guard:
                ok = jnp.isfinite(loss)
                for g in jax.tree.leaves(grads_mixed):
                    ok = ok & jnp.all(jnp.isfinite(g))
                # Global consensus: a non-finite value lives only in the
                # shard of the replica that owns it — every replica must
                # take the same skip decision.
                ok = col.psum(jnp.where(ok, 1.0, 0.0), "data") > (n - 0.5)

                def sel(new, old):
                    return jax.tree.map(
                        lambda a, b: jnp.where(ok, a, b), new, old
                    )

                new_params_mixed = sel(new_params_mixed, params_mixed)
                new_opt = sel(new_opt, state.opt_state)
                one = jnp.ones((), jnp.int32)
                zero = jnp.zeros((), jnp.int32)
                new_skipped = state.skipped_steps + jnp.where(ok, zero, one)
                new_streak, replace_kwargs = self._scale_streak_updates(
                    state, ok, cfg, one, zero
                )
                loss = jnp.where(ok, loss, jnp.zeros_like(loss))
                metric_val = jnp.where(
                    ok, metric_val, jnp.zeros_like(metric_val)
                )
            # (4) fresh weights: bucketed all-gather of the (guarded)
            # shards back to the full replicated tree.
            full_leaves = bucketed_all_gather(
                jax.tree.leaves(new_params_mixed), plan, full_shapes, "data"
            )
            new_params = jax.tree.unflatten(g_def, full_leaves)
            new_ema = (
                jax.tree.map(
                    lambda e, p: ema_decay * e + (1.0 - ema_decay) * p,
                    state.ema_params, new_params,
                )
                if ema_decay is not None else state.ema_params
            )
            if guard and ema_decay is not None:
                new_ema = jax.tree.map(
                    lambda a, b: jnp.where(ok, a, b),
                    new_ema, state.ema_params,
                )
            new_state = state.replace(
                step=state.step + 1,
                params=new_params,
                opt_state=new_opt,
                batch_stats=state.batch_stats,
                rng=rng,
                ema_params=new_ema,
                skipped_steps=new_skipped,
                bad_streak=new_streak,
                **replace_kwargs,
            )
            if telemetry:
                u_loc, u_rep = split_sq(jax.tree.leaves(updates))
                un = jnp.sqrt(col.psum(u_loc, "data") + u_rep)
                pn = _global_norm(new_params)
                stats = {
                    "loss_raw": jnp.asarray(raw_loss, jnp.float32),
                    "grad_norm": jnp.sqrt(g_sq),
                    "param_norm": pn,
                    "update_norm": un,
                    "update_ratio": un / (pn + 1e-12),
                }
                return new_state, loss, metric_val, stats
            return new_state, loss, metric_val

        state_specs = jax.tree.map(lambda sh: sh.spec, self._state_shardings)
        batch_spec = self._batch_sharding.spec
        scalar_spec = P()
        out_specs = (
            (state_specs, scalar_spec, scalar_spec, scalar_spec)
            if telemetry else (state_specs, scalar_spec, scalar_spec)
        )
        mapped = shard_map(
            body, mesh=mesh,
            in_specs=(state_specs, batch_spec, batch_spec, scalar_spec),
            out_specs=out_specs,
            # Outputs declared P() are replicated by construction (the
            # all-gathered weights and pmean'd scalars are identical on
            # every replica); the checker cannot prove it through the
            # where-selects, so it is off.
            check_vma=False,
        )

        def sharded_train_step(state, x, y, lr_scale):
            return mapped(state, x, y, lr_scale)

        return sharded_train_step

    def _make_eval_step(self, module, takes_train, has_bs, multi=False):
        """Compiled eval step for ``module``; with ``multi`` also returns
        the K-batches-per-dispatch variant (scan), else None.  Pure — no
        trainer state is touched (test() builds steps for foreign modules
        through this too)."""
        criterion, metric_fn = self.criterion, self.metric_fn
        takes_targets = _module_takes_targets(module)
        compute_dtype = self._compute_dtype
        if compute_dtype is not None:
            from ml_trainer_tpu.precision import cast_floating
        if takes_targets and metric_fn is not None:
            # The constructor guard only covers the trainer's own model;
            # test() evaluates foreign modules through here too, and a
            # fabricated 0.0 metric must not masquerade as a measurement.
            raise ValueError(
                "metric must be None when evaluating a model that computes "
                "its own loss (its forward returns a scalar, not logits)"
            )

        def apply(variables, x, **kwargs):
            with kernel_mesh(self.mesh):
                return module.apply(variables, x, **kwargs)

        def eval_step(variables, x, y):
            kwargs = {"train": False} if takes_train else {}
            if compute_dtype is not None:
                # Same policy as training: compute in bf16 against the
                # fp32 masters, score losses/metrics in fp32.
                variables = dict(
                    variables,
                    params=cast_floating(variables["params"], compute_dtype),
                )
                if jnp.issubdtype(jnp.asarray(x).dtype, jnp.inexact):
                    x = jnp.asarray(x).astype(compute_dtype)
            if takes_targets:
                # Self-loss model: the forward returns the scalar loss
                # (metric is None for these — validated at construction).
                loss = apply(variables, x, targets=y, **kwargs)
                if compute_dtype is not None:
                    loss = loss.astype(jnp.float32)
                return loss, jnp.zeros(())
            out = apply(variables, x, **kwargs)
            if compute_dtype is not None:
                out = out.astype(jnp.float32)
            loss = criterion(out, y)
            metric_val = (
                metric_fn(out, y) if metric_fn is not None else jnp.zeros(())
            )
            return loss, metric_val

        eval_multi = None
        if multi:
            def eval_multi_fn(variables, xs, ys):
                def body(_, xy):
                    return 0, eval_step(variables, *xy)

                _, (losses, metrics) = jax.lax.scan(body, 0, (xs, ys))
                return losses.sum(), metrics.sum()

            eval_multi = jax.jit(eval_multi_fn)
        return jax.jit(eval_step), eval_multi

    def _state_variables(self, ema: Optional[bool] = None) -> dict:
        """Inference-time variables.  With ``ema_decay`` set the EMA weights
        are the model's public face (eval/test/save); pass ``ema=False`` for
        the raw training weights."""
        use_ema = self.ema_decay is not None if ema is None else ema
        params = (
            self.state.ema_params
            if use_ema and self.state.ema_params is not None
            else self.state.params
        )
        variables = {"params": params}
        if self._has_batch_stats:
            variables["batch_stats"] = self.state.batch_stats
        return variables

    def _postfix_metric(self, metric_sum, seen: int, n: int) -> float:
        """Progress-bar metric value.  Linear metrics keep the reference's
        running-average-over-full-epoch display quirk
        (ref: src/trainer.py:193-194); metrics with an epoch finalizer
        must divide by the batches actually SEEN before finalizing —
        exponentiating a partial sum over the full count would display a
        number with no interpretation (it would climb from ~exp(0) all
        epoch)."""
        if getattr(self.metric_fn, "finalize", None) is not None:
            return self._metric_finalize(float(metric_sum) / max(seen, 1))
        return float(metric_sum) / n

    # ------------------------------------------------------------------ loops
    def _train_one_epoch(self, epoch: int) -> None:
        self.train_loader.set_epoch(epoch - 1)
        n = len(self.train_loader)
        loss_sum = jnp.zeros(())
        metric_sum = jnp.zeros(())
        epoch_t0 = time.time()
        lr_scale = jnp.asarray(self._lr_scale, jnp.float32)
        if self.steps_per_execution > 1:
            loss_sum, metric_sum = self._train_one_epoch_multi(
                epoch, n, lr_scale
            )
            if self._preempt_requested:
                # Multi-step dispatch has no per-batch cursor: no
                # emergency mid-epoch save — resume restarts from the
                # last epoch-boundary checkpoint (documented trade).
                self.preempted = True
                return
        else:
            start_b = 0
            mid, self._resume_mid = self._resume_mid, None
            if mid is not None and int(mid["epoch"]) == epoch:
                # Mid-epoch resume: SKIP the batches the interrupted run
                # already trained on.  Skipping still consumes them from
                # the loader (the augmentation rng advances identically),
                # so the remaining steps see exactly the batches the
                # uninterrupted run would — bit-exact continuation.
                start_b = int(mid["batches_done"])
                # mid[...] is the resume manifest — host JSON, no sync.
                # graft-lint: host-value
                loss_sum = jnp.asarray(float(mid["loss_sum"]), jnp.float32)
                metric_sum = jnp.asarray(
                    float(mid["metric_sum"]), jnp.float32  # graft-lint: host-value
                )
                self._skipped_base = int(mid.get("skipped_base", 0))
                logger.info(
                    f"Mid-epoch resume: epoch {epoch} continues at batch "
                    f"{start_b + 1}/{n}."
                )
            it = iter(self.train_loader)
            for _ in range(start_b):
                next(it)
            from ml_trainer_tpu.resilience import faults
            from ml_trainer_tpu.telemetry.spans import span

            plan = faults.active_plan()
            batches = prefetch_to_device(
                it, size=2, sharding=self._batch_sharding
            )
            with tqdm(
                batches, total=n, initial=start_b, unit="batch"
            ) as tepoch:
                stats = None
                for i, (x, y) in enumerate(tepoch):
                    done = start_b + i + 1  # 1-based batch cursor
                    # 1-based global train step ((epoch-1)*steps_per_epoch
                    # + batch) — pure host arithmetic, no device sync;
                    # the fault-injection AND telemetry step coordinate.
                    gstep = (epoch - 1) * n + done
                    if plan is not None:
                        if plan.fire("preempt", step=gstep) is not None:
                            self._request_preemption("injected preempt")
                        if plan.fire("nan_grad", step=gstep) is not None:
                            x = self._poison_batch(x)
                        self._poll_host_faults(plan, gstep)
                    with span("train_step_dispatch", step=gstep):
                        out = self._train_step(self.state, x, y, lr_scale)
                    self.state, loss, metric_val = out[0], out[1], out[2]
                    if self.telemetry:
                        stats = out[3]
                    loss_sum = loss_sum + loss
                    metric_sum = metric_sum + metric_val
                    if self._profile_hook:
                        self._profiler.on_step(gstep)
                    if done % self.log_every == 0 or done == n:
                        # The only host syncs in the epoch (the reference
                        # pays one per batch, ref: src/trainer.py:186).
                        # Display matches the reference's running-average-
                        # over-full-epoch quirk (ref: src/trainer.py:193-194).
                        with span("train_log_sync", step=gstep):
                            if self.metric:
                                tepoch.set_postfix(
                                    loss=float(loss_sum) / n,  # graft-lint: sync-ok
                                    metric=self._postfix_metric(
                                        metric_sum, done, n
                                    ),
                                )
                            else:
                                # graft-lint: sync-ok (the log_every fence)
                                tepoch.set_postfix(loss=float(loss))
                        if self._telemetry is not None and stats is not None:
                            self._telemetry.on_sync(
                                gstep, stats, epoch=epoch,
                                skipped_total=self._skipped_now(),
                                lr_scale=self._lr_scale,
                                loss_scale=self._loss_scale_now(),
                            )
                        if self._maybe_rollback(gstep):
                            lr_scale = jnp.asarray(
                                self._lr_scale, jnp.float32
                            )
                    if (
                        self.desync_every_steps
                        and process_count() > 1
                        and gstep % self.desync_every_steps == 0
                    ):
                        # Step-granular desync forensics: same gstep on
                        # every host (loaders are length-identical), so
                        # all hosts enter the broadcast together.
                        from ml_trainer_tpu.parallel.desync import (
                            check_desync,
                        )

                        check_desync(
                            self.state.params, step=gstep,
                            flight=self._flight,
                        )
                    if (
                        self.save_every_steps
                        and done % self.save_every_steps == 0
                        and done < n
                    ):
                        self._save_mid_epoch(
                            epoch, done, loss_sum, metric_sum
                        )
                    if self._preempt_requested:
                        # The in-flight step finished above; emergency
                        # checkpoint with the batch cursor, then exit.
                        self._save_mid_epoch(
                            epoch, done, loss_sum, metric_sum
                        )
                        ckpt.wait_for_checkpoints()
                        self._preempt_info = {
                            "epoch": epoch, "batches_done": done,
                        }
                        self.preempted = True
                        break
                    if self._reshape_request is not None:
                        # Elastic drain: the in-flight step committed;
                        # emergency-checkpoint the cursor (crash safety
                        # while the mesh is being rebuilt), then hand
                        # the reshape to _fit's loop.
                        self._save_mid_epoch(
                            epoch, done, loss_sum, metric_sum
                        )
                        ckpt.wait_for_checkpoints()
                        req, self._reshape_request = (
                            self._reshape_request, None
                        )
                        self._reshape_pending = {
                            "request": req,
                            "epoch": epoch,
                            "step": gstep,
                            "batches_done": done,
                            # The drain fence: the in-flight step must
                            # land before the mesh is rebuilt.
                            "loss_sum": float(loss_sum),  # graft-lint: sync-ok
                            "metric_sum": float(metric_sum),  # graft-lint: sync-ok
                        }
                        break
            if self.preempted or self._reshape_pending is not None:
                return  # partial epoch: no history entry yet
        # float(loss_sum) above fenced the device work, so this timestamp
        # covers actual execution, not async dispatch.
        self.train_losses.append(float(loss_sum) / n)  # graft-lint: sync-ok
        if self.state.skipped_steps is not None:
            # graft-lint: sync-ok (epoch-boundary counter fetch)
            cum = int(jax.device_get(self.state.skipped_steps))
            self.skipped_steps.append(cum - self._skipped_base)
            self._skipped_base = cum
        dt = time.time() - epoch_t0
        logger.info(
            f"Epoch {epoch}: {n * self.global_batch / max(dt, 1e-9):,.0f} "
            f"samples/s ({dt:.1f}s, global batch {self.global_batch})"
        )
        if self.metric:
            self.train_metrics.append(
                self._metric_finalize(float(metric_sum) / n)  # graft-lint: sync-ok
            )

    def _train_one_epoch_multi(self, epoch: int, n: int, lr_scale):
        """Epoch driven K optimizer steps per dispatch: full chunks of
        ``steps_per_execution`` batches go through the scanned program, the
        ragged tail through the per-batch step — same trajectory either
        way."""
        from ml_trainer_tpu.telemetry.spans import span

        k = self.steps_per_execution
        loss_sum = jnp.zeros(())
        metric_sum = jnp.zeros(())
        tail: list = []  # ragged final batches, filled once chunks() drains

        stacked = prefetch_to_device(
            _chunk_batches(self.train_loader, k, tail),
            size=2, sharding=self._stacked_sharding,
        )
        with tqdm(total=n, unit="batch") as tepoch:
            done = 0

            def log(step_n, loss, stats):
                if done % max(self.log_every, k) < step_n or done == n:
                    with span("train_log_sync", step=(epoch - 1) * n + done):
                        if self.metric:
                            tepoch.set_postfix(
                                loss=float(loss_sum) / n,  # graft-lint: sync-ok
                                metric=self._postfix_metric(
                                    metric_sum, done, n
                                ),
                            )
                        else:
                            # Mean loss of the last dispatch — the
                            # multi-step analog of the single-step path's
                            # last-batch loss.
                            # graft-lint: sync-ok (per-dispatch fence)
                            tepoch.set_postfix(loss=float(loss) / step_n)
                    if self._telemetry is not None and stats is not None:
                        self._telemetry.on_sync(
                            (epoch - 1) * n + done, stats, epoch=epoch,
                            skipped_total=self._skipped_now(),
                            lr_scale=self._lr_scale,
                            loss_scale=self._loss_scale_now(),
                        )

            for xs, ys in stacked:
                out = self._train_multi_step(self.state, xs, ys, lr_scale)
                self.state, loss, metric_val = out[0], out[1], out[2]
                stats = out[3] if self.telemetry else None
                loss_sum = loss_sum + loss
                metric_sum = metric_sum + metric_val
                done += k
                if self._profile_hook:
                    self._profiler.on_step((epoch - 1) * n + done)
                tepoch.update(k)
                log(k, loss, stats)
                self._maybe_check_desync(epoch, n, done, k)
                if self._preempt_requested:
                    return loss_sum, metric_sum
            for x, y in prefetch_to_device(
                iter(tail), size=2, sharding=self._batch_sharding
            ):
                with span("train_step_dispatch",
                          step=(epoch - 1) * n + done + 1):
                    out = self._train_step(self.state, x, y, lr_scale)
                self.state, loss, metric_val = out[0], out[1], out[2]
                stats = out[3] if self.telemetry else None
                loss_sum = loss_sum + loss
                metric_sum = metric_sum + metric_val
                done += 1
                tepoch.update(1)
                log(1, loss, stats)
                self._maybe_check_desync(epoch, n, done, 1)
                if self._preempt_requested:
                    return loss_sum, metric_sum
        return loss_sum, metric_sum

    def _maybe_check_desync(self, epoch: int, n: int, done: int,
                            step_n: int) -> None:
        """Multi-step-path desync cadence: fire when a multiple of
        ``desync_every_steps`` landed inside the last dispatch of
        ``step_n`` steps.  ``done`` is host-deterministic, so every host
        joins the broadcast at the same dispatch."""
        if (
            self.desync_every_steps
            and process_count() > 1
            and done % self.desync_every_steps < step_n
        ):
            from ml_trainer_tpu.parallel.desync import check_desync

            check_desync(
                self.state.params, step=(epoch - 1) * n + done,
                flight=self._flight,
            )

    def _validate_one_epoch(self) -> None:
        n = len(self.val_loader)
        loss_sum = jnp.zeros(())
        metric_sum = jnp.zeros(())
        variables = self._state_variables()
        k = self.steps_per_execution
        if k > 1:
            tail: list = []
            with tqdm(total=n, unit="batch") as tepoch:
                done = 0

                def log(step_n, loss):
                    if done % max(self.log_every, k) < step_n or done == n:
                        if self.metric:
                            tepoch.set_postfix(
                                loss=float(loss_sum) / n,
                                metric=self._postfix_metric(metric_sum, done, n),
                            )
                        else:
                            # Mean loss of the last dispatch — the analog of
                            # the single-step path's last-batch loss.
                            tepoch.set_postfix(loss=float(loss) / step_n)

                for xs, ys in prefetch_to_device(
                    _chunk_batches(self.val_loader, k, tail),
                    size=2, sharding=self._stacked_sharding,
                ):
                    loss, metric_val = self._eval_multi_step(variables, xs, ys)
                    loss_sum = loss_sum + loss
                    metric_sum = metric_sum + metric_val
                    done += k
                    tepoch.update(k)
                    log(k, loss)
                for x, y in prefetch_to_device(
                    iter(tail), size=2, sharding=self._batch_sharding
                ):
                    loss, metric_val = self._eval_step(variables, x, y)
                    loss_sum = loss_sum + loss
                    metric_sum = metric_sum + metric_val
                    done += 1
                    tepoch.update(1)
                    log(1, loss)
        else:
            batches = prefetch_to_device(
                self.val_loader, size=2, sharding=self._batch_sharding
            )
            with tqdm(batches, total=n, unit="batch") as tepoch:
                for i, (x, y) in enumerate(tepoch):
                    loss, metric_val = self._eval_step(variables, x, y)
                    loss_sum = loss_sum + loss
                    metric_sum = metric_sum + metric_val
                    if (i + 1) % self.log_every == 0 or (i + 1) == n:
                        if self.metric:
                            tepoch.set_postfix(
                                loss=float(loss_sum) / n,
                                metric=self._postfix_metric(metric_sum, i + 1, n),
                            )
                        else:
                            tepoch.set_postfix(loss=float(loss))
        self.val_losses.append(float(loss_sum) / n)
        if self.metric:
            self.val_metrics.append(self._metric_finalize(float(metric_sum) / n))

    # ------------------------------------------------------------------- fit
    def fit(self, resume: bool = False) -> None:
        """Full training run (ref: src/trainer.py:243-275).  ``resume=True``
        restarts from the latest full checkpoint — a capability the
        reference lacks (SURVEY.md §5).  With ``handle_preemption`` (the
        default) SIGTERM/SIGINT trigger a clean preemption exit: finish
        the in-flight step, write an emergency checkpoint + exit marker,
        return with ``self.preempted = True``; ``fit(resume=True)`` then
        continues where the signal landed (bit-exactly mid-epoch when
        ``save_every_steps`` semantics apply)."""
        self.preempted = False
        self._preempt_requested = False
        self._preempt_info: Optional[dict] = None
        self._reshape_request = None
        self._reshape_pending = None
        prev_handlers = self._install_preempt_handlers()
        try:
            self._fit(resume)
        except Exception as e:
            # Crash forensics: the last N step records + the error, on
            # disk before the exception unwinds the process — followed by
            # a best-effort run report so the post-mortem starts from the
            # distilled numbers, not raw logs.
            self._flight.dump(
                "unhandled_exception", out_dir=self._flight_dir(),
                error=f"{type(e).__name__}: {e}",
            )
            self._write_run_report(f"crash: {type(e).__name__}: {e}")
            raise
        finally:
            self._restore_preempt_handlers(prev_handlers)
            if self.telemetry:
                # The recompile invariant is a property of THIS run's
                # steady state; whatever compiles after fit() returns
                # (test(), predict(), another trainer) is legitimate.
                from ml_trainer_tpu.telemetry import compile_watch

                compile_watch.mark_cold()

    def _install_preempt_handlers(self):
        if not self.handle_preemption:
            return {}
        import signal

        prev = {}
        try:
            for sig in (signal.SIGTERM, signal.SIGINT):
                prev[sig] = signal.signal(sig, self._on_preempt_signal)
        except ValueError:
            # Not the main thread: signals cannot be installed here; the
            # injected `preempt` fault path still works.
            return prev
        return prev

    def _restore_preempt_handlers(self, prev) -> None:
        import signal

        for sig, handler in prev.items():
            try:
                signal.signal(sig, handler)
            except (ValueError, TypeError):
                pass

    def _on_preempt_signal(self, signum, frame) -> None:
        self._request_preemption(f"signal {signum}")

    def _request_preemption(self, reason: str) -> None:
        if not self._preempt_requested:
            logger.warning(
                f"Preemption requested ({reason}): finishing the in-flight "
                "step, then writing an emergency checkpoint."
            )
        self._preempt_requested = True

    def _fit(self, resume: bool) -> None:
        logger.info("Start training..")
        start_epoch = 1
        ckpt_dir = os.path.join(self.model_dir, "checkpoints")
        if self.telemetry:
            # Goodput window: anchored here so every bucket (and the
            # compute remainder) is charged against THIS run's wall
            # clock; compile warmup re-opens for the programs this fit
            # legitimately builds (closed after the first epoch below).
            from ml_trainer_tpu.telemetry import compile_watch

            compile_watch.mark_cold()
            if self._telemetry is not None:
                self._telemetry.goodput.start()
        if resume:
            start_epoch = self._resume_from_latest(ckpt_dir)
        self._mark_warm_after_epoch = True
        for epoch in range(start_epoch, self.epochs + 1):
            # Checked at loop entry so a resumed run that comes back
            # already out of patience stops BEFORE training (and
            # overwriting the exported weights with) a wasted epoch.
            if self._out_of_patience():
                break
            logger.info(f"{'-' * 30} EPOCH {epoch} / {self.epochs} {'-' * 30}")
            self._train_one_epoch(epoch)
            while self._reshape_pending is not None:
                # Elastic reshape: the epoch drained mid-flight; rebuild
                # the mesh around the lost host and re-enter the SAME
                # epoch at the saved cursor (resilience/elastic.py).
                self._perform_reshape()
                self._train_one_epoch(epoch)
            if self.preempted:
                self._write_preempt_marker(ckpt_dir)
                self._flight.record(
                    "preemption", **(self._preempt_info or {"epoch": epoch})
                )
                self._flight.dump(
                    "preemption", out_dir=self._flight_dir(),
                    **(self._preempt_info or {"epoch": epoch}),
                )
                logger.warning(
                    "Preempted: emergency checkpoint committed; exiting "
                    "fit() cleanly (resume with fit(resume=True))."
                )
                break
            self.clear()
            self._validate_one_epoch()
            self.clear()
            if self._mark_warm_after_epoch:
                # Every program a steady-state epoch needs (train + eval,
                # full and ragged-tail shapes) has now compiled: any
                # compile from here on is a recompile incident the watch
                # records with flight forensics.  An elastic reshape
                # re-arms this flag — the reshaped mesh legitimately
                # compiles fresh programs for one epoch.
                self._mark_warm_after_epoch = False
                if self.telemetry:
                    from ml_trainer_tpu.telemetry import compile_watch

                    compile_watch.mark_warm()
            if self._plateau is not None:
                self._lr_scale = self._plateau.update(self.val_losses[-1])
            # Every host computes the same val loss, so `improved` (and the
            # stop decision) is globally consistent without a collective.
            improved = self.val_losses[-1] < self._best_val
            if improved:
                self._best_val = self.val_losses[-1]
                self._bad_epochs = 0
            else:
                self._bad_epochs += 1
            if process_count() > 1:
                # Cross-host replica-desync check (the "race detector",
                # SURVEY.md §5) — one scalar over DCN per epoch.
                from ml_trainer_tpu.parallel.desync import check_desync

                check_desync(
                    self.state.params, step=epoch * self.steps_per_epoch,
                    flight=self._flight,
                )
            if self._cluster is not None:
                # Cluster heartbeat aggregation: one tiny allgather per
                # epoch, every host at the same program point (the same
                # collective discipline as check_desync above).  After it,
                # host 0's /metrics and JSONL sink carry cluster_* series
                # for the whole pod.
                self._cluster.sync(step=epoch * self.steps_per_epoch)
            if self._reshape_request is not None:
                # Boundary reshape (a straggler verdict from the
                # epoch-end aggregation): the epoch is complete, so no
                # mid-epoch cursor carries over — the next epoch starts
                # on the reshaped mesh.
                req, self._reshape_request = self._reshape_request, None
                self._reshape_pending = {
                    "request": req, "epoch": epoch,
                    "step": epoch * self.steps_per_epoch,
                    "batches_done": None, "loss_sum": 0.0,
                    "metric_sum": 0.0,
                }
                self._perform_reshape()
            # Save on the primary host only (ref: src/trainer.py:252-254).
            # When params are genuinely PARTITIONED across hosts (TP/FSDP
            # multi-host), the fetch is a global allgather — a collective —
            # so every host must join it, or host 0 blocks in a gather the
            # others never enter (they'd already be in the v3 commit
            # barrier below).  Replicated params fetch locally and keep
            # the export primary-only.
            variables = self._state_variables()
            export_is_collective = process_count() > 1 and any(
                not leaf.is_fully_addressable
                and not getattr(leaf, "is_fully_replicated", False)
                for leaf in jax.tree.leaves(variables)
            )
            host_vars = (
                ckpt.fetch_to_host(variables)
                if (is_primary() or export_is_collective) else None
            )
            from ml_trainer_tpu.telemetry import goodput
            from ml_trainer_tpu.telemetry.spans import span

            if is_primary():
                logger.info("Saving the model.")
                from flax import serialization

                # One device fetch + serialization covers both exports
                # (the best/ copy is the same bytes on improving epochs).
                with span("model_export", epoch=epoch), \
                        goodput.timed("ckpt_stall"):
                    data = serialization.to_bytes(host_vars)
                    ckpt.write_model_bytes(self.model_dir, data)
                    # The export manifest carries the weights fingerprint
                    # a serving deploy keys KV portability on
                    # (docs/serving.md "Deploys").
                    ckpt.write_model_manifest(
                        self.model_dir, host_vars, data=data
                    )
                    if improved and self.save_best:
                        ckpt.write_model_bytes(
                            os.path.join(self.model_dir, "best"), data
                        )
                        ckpt.write_model_manifest(
                            os.path.join(self.model_dir, "best"),
                            host_vars, data=data,
                        )
            if self._sharded_ckpt:
                # COLLECTIVE: every process contributes its addressable
                # shards; no host gathers the full state (format v3).
                with span("ckpt_write", epoch=epoch, sharded=True), \
                        goodput.timed("ckpt_stall"):
                    ckpt.save_checkpoint_sharded(
                        ckpt_dir, self.state, self._partial_history(), epoch,
                        block=False,
                    )
            elif is_primary():
                # Async: the write lands on the background writer thread
                # while the next epoch trains (jax arrays are immutable, so
                # the snapshot is consistent); fit-end joins the queue.
                # The span covers the enqueue (the host-blocking part).
                with span("ckpt_write", epoch=epoch, sharded=False), \
                        goodput.timed("ckpt_stall"):
                    ckpt.save_checkpoint(
                        ckpt_dir, self.state, self._partial_history(), epoch,
                        block=False,
                    )
            if self.metric:
                logger.info(
                    f"train loss: {self.train_losses[-1]} - "
                    f"train {self.metric}: {self.train_metrics[-1]}"
                )
                logger.info(
                    f"valid loss: {self.val_losses[-1]} - "
                    f"valid {self.metric}: {self.val_metrics[-1]}\n\n"
                )
            else:
                logger.info(f"train loss: {self.train_losses[-1]}")
                logger.info(f"valid loss: {self.val_losses[-1]}\n\n")
            if self._out_of_patience():
                break
        self.history = {
            "epochs": [*range(1, len(self.train_losses) + 1)],
            "train_loss": self.train_losses,
            "val_loss": self.val_losses,
            "train_metric": self.train_metrics,
            "val_metric": self.val_metrics,
            "metric_type": self.metric,
            # Per-epoch count of steps the on-device all-finite guard
            # skipped (all zeros on a healthy run), the number of
            # rollback-to-last-good events, and the elastic mesh
            # reshapes survived — the resilience ledger.
            "skipped_steps": self.skipped_steps,
            "rollbacks": self.rollbacks,
            "reshapes": self.reshapes,
        }
        if self.save_history and is_primary():
            self.save_history_(self.model_dir)
        from ml_trainer_tpu.telemetry import goodput

        with goodput.timed("ckpt_stall"):
            ckpt.wait_for_checkpoints()
        self._write_run_report("preempted" if self.preempted else "completed")
        logger.info("Training Complete.")

    def _out_of_patience(self) -> bool:
        stop = (
            self.early_stop_patience is not None
            and self._bad_epochs >= self.early_stop_patience
        )
        if stop:
            logger.info(
                f"Early stop: no val-loss improvement in "
                f"{self._bad_epochs} epochs (best {self._best_val:.6f})."
            )
        return stop

    def _partial_history(self) -> dict:
        h = {
            "train_loss": self.train_losses,
            "val_loss": self.val_losses,
            "train_metric": self.train_metrics,
            "val_metric": self.val_metrics,
            "metric_type": self.metric,
            "lr_scale": self._lr_scale,
            "skipped_steps": self.skipped_steps,
            "rollbacks": self.rollbacks,
            "reshapes": self.reshapes,
        }
        if self._plateau is not None:
            h["plateau"] = {
                "best": self._plateau.best,
                "num_bad_epochs": self._plateau.num_bad_epochs,
                "scale": self._plateau.scale,
            }
        h["early_stop"] = {
            "best_val": self._best_val, "bad_epochs": self._bad_epochs,
        }
        return h

    def _apply_resume_scalars(self, saved: dict) -> None:
        """Re-install the host-side training scalars from a restored
        checkpoint's history dict (no broadcast — the caller guarantees
        every host sees identical ``saved``, e.g. via shared storage).
        The v2 multi-host resume path keeps its own inline scalar
        re-install: there the non-primary hosts have no ``saved`` dict and
        the values must travel by broadcast instead."""
        self.train_losses = list(saved.get("train_loss", []))
        self.val_losses = list(saved.get("val_loss", []))
        self.train_metrics = list(saved.get("train_metric", []))
        self.val_metrics = list(saved.get("val_metric", []))
        self.skipped_steps = list(saved.get("skipped_steps", []))
        self.rollbacks = int(saved.get("rollbacks", 0))
        self.reshapes = list(saved.get("reshapes", []))
        self._lr_scale = float(saved.get("lr_scale", 1.0))
        plateau = saved.get("plateau", {})
        if self._plateau is not None:
            self._plateau.best = float(plateau.get("best", np.inf))
            self._plateau.num_bad_epochs = int(plateau.get("num_bad_epochs", 0))
            self._plateau.scale = float(plateau.get("scale", 1.0))
        early = saved.get("early_stop", {})
        self._best_val = float(early.get("best_val", np.inf))
        self._bad_epochs = int(early.get("bad_epochs", 0))

    # ------------------------------------------------------------ resilience
    def _poll_host_faults(self, plan, gstep: int) -> None:
        """``host_kill`` / ``host_hang`` injection (resilience/faults.py).

        Multi-process: the MATCHING worker is the failing host — it
        hard-exits (kill: the SIGKILL'd pod host, no emergency
        checkpoint) or stalls (hang: a real straggler for the cluster
        telemetry to catch).  Single-process simulated cluster: the
        fault names a simulated host and the elastic controller drains
        and reshapes around it (without ``elastic=`` the fault degrades
        to a preemption request — the restart path)."""
        for kind in ("host_kill", "host_hang"):
            fault = plan.fire(kind, step=gstep)
            if fault is None:
                continue
            if process_count() > 1:
                if int(fault.host) == process_index():
                    if kind == "host_kill":
                        logger.error(
                            f"host_kill fault: host {fault.host} "
                            f"hard-exiting at step {gstep} (no emergency "
                            "checkpoint — the SIGKILL'd-host case)"
                        )
                        os._exit(113)
                    logger.warning(
                        f"host_hang fault: host {fault.host} stalling "
                        f"{fault.secs}s at step {gstep}"
                    )
                    time.sleep(float(fault.secs))
                continue
            if self.elastic is None:
                logger.warning(
                    f"{kind} fault without Trainer(elastic=...): treating "
                    "as a preemption (emergency checkpoint + clean exit)"
                )
                self._request_preemption(f"{kind} fault")
                continue
            self._request_reshape(kind, int(fault.host), step=gstep)

    def _on_straggler_verdict(self, *, host: int, factor: float,
                              step=None) -> None:
        """Straggler verdict from ``telemetry/cluster.py``: past the
        elastic reshape factor, request a drain+reshape around the
        straggling host (pure alarm otherwise)."""
        cfg = self.elastic
        if cfg is None or cfg.straggler_reshape_factor is None:
            return
        if factor >= cfg.straggler_reshape_factor:
            self._request_reshape(
                "straggler", int(host), step=step,
                detail={"factor": round(float(factor), 2)},
            )

    def _request_reshape(self, trigger: str, lost_host: int, step=None,
                         detail: Optional[dict] = None) -> None:
        """Queue one drain→reshape; consumed after the in-flight step."""
        from ml_trainer_tpu.resilience.elastic import ReshapeRequest

        if self.elastic is None or process_count() > 1:
            return
        if lost_host not in self._live_hosts:
            logger.warning(
                f"reshape request for host {lost_host} ignored: already "
                f"removed (live hosts {self._live_hosts})"
            )
            return
        if len(self._live_hosts) - 1 < self.elastic.min_hosts or (
            len(self.reshapes) >= self.elastic.max_reshapes
        ):
            logger.warning(
                f"reshape around host {lost_host} refused "
                f"(live={len(self._live_hosts)}, "
                f"min_hosts={self.elastic.min_hosts}, "
                f"reshapes={len(self.reshapes)}/"
                f"{self.elastic.max_reshapes}); treating as preemption"
            )
            self._request_preemption(f"{trigger} past elastic bounds")
            return
        if self._reshape_request is None and not self._preempt_requested:
            self._reshape_request = ReshapeRequest(
                trigger=trigger, lost_host=int(lost_host),
                step=step, detail=detail or {},
            )
            logger.warning(
                f"Elastic reshape requested ({trigger}, lost host "
                f"{lost_host}): draining the in-flight step."
            )

    def _perform_reshape(self) -> None:
        """Reshape the mesh around the lost host and keep training.

        The drained cursor (``_reshape_pending``) marks where the epoch
        stopped; this rebuilds the world — validated BEFORE any device
        allocates — and re-enters the same epoch via the mid-epoch
        resume machinery:

        1. ``precheck_topology``: the analytic memory ledger prices the
           target topology (structured ``TopologyError`` if it cannot
           fit);
        2. ``remap_state_shardings`` + ``validate_reshard``: per-leaf
           target placement with the ZeRO-1 shape rule re-applied
           (structured ``ReshardError`` naming the offending axis);
        3. ONE whole-tree host fetch + ``place_tree`` placement;
        4. batch/LR policy: ``'global'`` preserves the global batch
           (math unchanged — the trajectory equals the uninterrupted
           run's); ``'per_device'`` shrinks it by the survivor ratio
           and rescales the LR linearly;
        5. compiled steps, bucket plan, memory ledger rebuilt; compile
           warmup re-opens for the reshaped programs.

        The whole recovery is charged to the goodput ``reshape`` bucket
        and recorded in ``history['reshapes']`` + a flight ``reshape``
        event (old/new topology, trigger, steps-lost)."""
        from ml_trainer_tpu.parallel import create_mesh, place_tree
        from ml_trainer_tpu.resilience import elastic as el
        from ml_trainer_tpu.telemetry import goodput

        info, self._reshape_pending = self._reshape_pending, None
        req = info["request"]
        cfg = self.elastic
        t0 = time.perf_counter()
        with goodput.timed("reshape"):
            old_topology = {a: int(s) for a, s in self.mesh.shape.items()}
            old_devices = list(self.mesh.devices.flat)
            groups = el.host_groups(old_devices, len(self._live_hosts))
            pos = self._live_hosts.index(int(req.lost_host))
            new_devices = [
                d for gi, grp in enumerate(groups)
                for d in grp if gi != pos
            ]
            new_shape = el.shrink_mesh_shape(
                old_topology, len(old_devices), len(new_devices)
            )
            old_global = self.global_batch
            new_global = old_global
            if cfg.batch_policy == "per_device":
                new_global = max(
                    old_global * len(new_devices) // len(old_devices), 1
                )
            # (1) fit check from config alone — nothing has allocated.
            # Judged against cfg.capacity_bytes, else the local chip's
            # HBM; a host platform has neither, so nothing to judge.
            if cfg.capacity_bytes is not None or (
                jax.default_backend() == "tpu"
            ):
                el.precheck_topology(
                    self.model,
                    (new_global,) + tuple(self._batch_geometry[1:]),
                    mesh_shape=new_shape,
                    optimizer=self.optimizer_type,
                    sharding_rules=self._sharding_rules,
                    shard_opt_state=self._shard_opt_state,
                    dp_update=self.dp_update,
                    precision=(
                        self.precision.label() if self.precision.active else None
                    ),
                    ema=self.ema_decay is not None,
                    grad_accum_steps=self.grad_accum_steps,
                    batch_dtype=self._batch_dtype,
                    capacity_bytes=cfg.capacity_bytes,
                    margin=cfg.margin,
                )
            new_mesh = create_mesh(new_shape, devices=new_devices)
            # (2) per-leaf target placement, divisibility-validated.
            new_shardings = el.remap_state_shardings(
                self._state_shardings, self.state, new_mesh
            )
            el.validate_reshard(
                self.state, new_shardings,
                source_topology={"axes": old_topology},
            )
            # (3) one whole-tree fetch + placement.
            host_state = jax.device_get(self.state)
            self.mesh = new_mesh
            self._batch_sharding = batch_sharding(new_mesh)
            self._replicated = replicated(new_mesh)
            self._data_parallel = int(
                np.prod(
                    [
                        new_mesh.shape[a]
                        for a in ("data", "fsdp")
                        if a in new_mesh.axis_names
                    ],
                    initial=1,
                )
            )
            self.state = place_tree(host_state, new_shardings)
            self._state_shardings = new_shardings
            self._live_hosts.pop(pos)
            # (4) batch/LR policy.
            lr_before = self._lr_scale
            cursor = info.get("batches_done")
            if cfg.batch_policy == "per_device" and new_global != old_global:
                self._build_loaders(
                    self._datasets[0], self._datasets[1], new_global,
                    self.config,
                )
                self.steps_per_epoch = len(self.train_loader)
                # Linear scaling rule, in reverse: the LR follows the
                # global batch down so per-sample update magnitude holds.
                self._lr_scale *= self.global_batch / old_global
                if cursor is not None:
                    # Re-express the cursor in the new batch geometry
                    # (same shuffled sample order — the loader batches a
                    # seed-determined permutation sequentially).
                    cursor = (cursor * old_global) // self.global_batch
            # (5) rebuild the compiled programs on the new mesh.
            if self.dp_update == "sharded":
                from ml_trainer_tpu.parallel import plan_grad_buckets

                self._bucket_plan = plan_grad_buckets(
                    self.state.params, int(self.mesh.shape["data"]),
                    bucket_bytes=int(self.bucket_mb * 2 ** 20),
                )
            self._build_steps()
            if self.telemetry:
                from ml_trainer_tpu.telemetry import (
                    compile_watch,
                    memory as _memory,
                )

                # The reshaped programs legitimately compile: re-open
                # warmup (closed again after the next full epoch) and
                # re-publish the ledger for the new per-device split.
                compile_watch.mark_cold()
                self._mark_warm_after_epoch = True
                self._memory_ledger = _memory.train_ledger(self)
                self._memory_ledger.publish()
        downtime = time.perf_counter() - t0
        record = {
            "step": int(info.get("step") or 0),
            "epoch": int(info["epoch"]),
            "trigger": req.trigger,
            "lost_host": int(req.lost_host),
            "old_topology": old_topology,
            "new_topology": {a: int(s) for a, s in self.mesh.shape.items()},
            "old_global_batch": int(old_global),
            "global_batch": int(self.global_batch),
            "lr_scale": float(self._lr_scale),
            # The drain committed the in-flight step and the controller
            # continues from LIVE state: a clean reshape loses zero
            # steps (hard kills lose up to the save_every_steps cadence
            # instead — the restart path).
            "steps_lost": 0,
            "downtime_secs": round(downtime, 3),
        }
        if req.detail:
            record["detail"] = req.detail
        self.reshapes.append(record)
        self._flight.record("reshape", **record)
        if self._telemetry is not None:
            self._telemetry.registry.counter(
                "train_reshapes_total",
                "elastic mesh reshapes survived by this process",
            ).inc()
        if info.get("batches_done") is not None:
            self._resume_mid = {
                "epoch": int(info["epoch"]),
                "batches_done": int(cursor),
                "loss_sum": float(info["loss_sum"]),
                "metric_sum": float(info["metric_sum"]),
                "skipped_base": int(self._skipped_base),
            }
        logger.warning(
            f"Elastic reshape: lost host {req.lost_host} ({req.trigger}); "
            f"mesh {record['old_topology']} -> {record['new_topology']}, "
            f"global batch {old_global} -> {self.global_batch}, lr scale "
            f"{lr_before:.4g} -> {self._lr_scale:.4g}, downtime "
            f"{downtime:.2f}s."
        )

    @staticmethod
    def _poison_batch(x):
        """``nan_grad`` fault: NaN-fill a float batch so the compiled step
        produces non-finite loss/grads (the guard's job to absorb)."""
        if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating):
            return x * jnp.nan
        logger.warning(
            "nan_grad fault ignored: integer input batch cannot carry NaN"
        )
        return x

    def _save_mid_epoch(
        self, epoch: int, batches_done: int, loss_sum, metric_sum
    ) -> None:
        """Step-granular checkpoint: epoch ``epoch`` is IN PROGRESS with
        ``batches_done`` batches trained.  The manifest's ``mid_epoch``
        record carries the batch cursor plus the epoch accumulators so
        ``fit(resume=True)`` continues bit-exactly; the end-of-epoch save
        overwrites the same ``checkpoint_<epoch>`` directory.  Costs one
        scalar device sync per save (the accumulator fetch)."""
        hist = self._partial_history()
        hist["mid_epoch"] = {
            "epoch": int(epoch),
            "batches_done": int(batches_done),
            "loss_sum": float(loss_sum),
            "metric_sum": float(metric_sum),
            "skipped_base": int(self._skipped_base),
        }
        from ml_trainer_tpu.telemetry import goodput
        from ml_trainer_tpu.telemetry.spans import span

        ckpt_dir = os.path.join(self.model_dir, "checkpoints")
        if self._sharded_ckpt:
            with span("ckpt_write", epoch=epoch, batch=batches_done,
                      sharded=True), goodput.timed("ckpt_stall"):
                ckpt.save_checkpoint_sharded(
                    ckpt_dir, self.state, hist, epoch, block=False
                )
        elif is_primary():
            # Async: the writer thread serializes this with epoch-end
            # saves (single-queue FIFO), so same-epoch writes never race.
            with span("ckpt_write", epoch=epoch, batch=batches_done,
                      sharded=False), goodput.timed("ckpt_stall"):
                ckpt.save_checkpoint(
                    ckpt_dir, self.state, hist, epoch, block=False
                )

    def _skipped_now(self) -> int:
        """Cumulative on-device skipped-step count (one scalar fetch)."""
        if self.state is None or self.state.skipped_steps is None:
            return 0
        return int(jax.device_get(self.state.skipped_steps))

    def _loss_scale_now(self) -> Optional[float]:
        """Current dynamic loss scale (one scalar fetch; None when
        scaling is off — the gauge/event field then stays absent)."""
        if self.state is None or self.state.loss_scale is None:
            return None
        return float(jax.device_get(self.state.loss_scale))

    def _flight_dir(self) -> str:
        """Flight dumps land next to the checkpoints unless the env var
        redirects them (telemetry/flight.py resolution order)."""
        from ml_trainer_tpu.telemetry.flight import FLIGHT_DIR_ENV

        return os.environ.get(FLIGHT_DIR_ENV) or self.model_dir

    def _write_run_report(self, reason: str) -> None:
        """End-of-run distillation (docs/observability.md run-report
        schema): throughput/MFU, per-host heartbeats, comm bytes by op,
        the resilience ledger, checkpoint write times, straggler/desync
        events.  Primary host, telemetry runs only; never raises (the
        crash path calls this while an exception is in flight)."""
        if not self.telemetry or not is_primary():
            return
        try:
            from ml_trainer_tpu.telemetry.cluster import write_run_report
            from ml_trainer_tpu.telemetry.memory import publish_live_memory

            if self._telemetry is not None:
                # Final goodput decomposition + the live per-device
                # memory view, published so the report's sections read
                # the end-of-run numbers, not the last sync's.
                self._telemetry.goodput.finish()
            publish_live_memory()
            write_run_report(
                self.model_dir,
                history=self.history or self._partial_history(),
                flight=self._flight,
                reason=reason,
            )
        except Exception as e:  # the report documents the run, never ends it
            logger.warning(f"run report write failed: {e}")

    def _maybe_rollback(self, gstep: int = 0) -> bool:
        """Rollback-to-last-good: when ``rollback_bad_steps`` CONSECUTIVE
        steps were skipped as non-finite, restore the newest checkpoint
        that verifies (corrupt ones quarantined) and back the LR off by
        ``rollback_lr_backoff``.  Called at the ``log_every`` sync
        cadence; the check costs one scalar fetch and only runs when
        rollback is enabled."""
        if self.rollback_bad_steps is None or self.state.bad_streak is None:
            return False
        streak = int(jax.device_get(self.state.bad_streak))
        if streak < self.rollback_bad_steps:
            return False
        self._lr_scale *= self.rollback_lr_backoff
        self.rollbacks += 1
        # Crash forensics BEFORE the restore mutates the state: the ring
        # holds the step records leading in, and the rollback event names
        # the bad streak's boundaries (exact when log_every == 1).
        self._flight.record(
            "rollback", step=int(gstep), streak=streak,
            first_bad_step=int(gstep) - streak + 1,
            lr_scale=self._lr_scale,
        )
        if self._telemetry is not None:
            self._telemetry.c_rollbacks.inc()
        self._flight.dump(
            "nan_rollback", out_dir=self._flight_dir(),
            step=int(gstep), first_bad_step=int(gstep) - streak + 1,
            streak=streak,
        )
        zero = jax.device_put(jnp.zeros((), jnp.int32), self._replicated)
        ckpt_dir = os.path.join(self.model_dir, "checkpoints")
        from ml_trainer_tpu.telemetry import goodput

        with goodput.timed("rollback"):
            ckpt.wait_for_checkpoints()  # in-flight async writes must land
            latest = ckpt.latest_valid_checkpoint(
                ckpt_dir, quarantine=is_primary()
            )
            if latest is None:
                # The guard already reverted every bad update, so the live
                # params ARE the last good ones; just clear the streak.
                logger.warning(
                    f"Rollback: {streak} consecutive non-finite steps and "
                    f"no valid checkpoint; LR scale backed off to "
                    f"{self._lr_scale:.4g}, continuing from current params."
                )
                self.state = self.state.replace(bad_streak=zero)
                return True
            skipped_now = self.state.skipped_steps
            if ckpt.checkpoint_format(latest) == 3:
                state, _, _ = ckpt.restore_checkpoint(
                    latest, self.state, self._state_shardings
                )
                self.state = state
            else:
                state, _, _ = ckpt.restore_checkpoint(
                    latest, ckpt.fetch_to_host(self.state)
                )
                from ml_trainer_tpu.parallel import place_tree

                self.state = place_tree(state, self._state_shardings)
            # Keep the cumulative skipped count (diagnostics) but clear
            # the streak — the restored counters predate the event.
            self.state = self.state.replace(
                bad_streak=zero, skipped_steps=skipped_now
            )
            self._reseed_loss_scale()
        logger.warning(
            f"Rollback: {streak} consecutive non-finite steps; restored "
            f"{latest} and backed LR off to scale {self._lr_scale:.4g}."
        )
        return True

    def _write_preempt_marker(self, ckpt_dir: str) -> None:
        """Clean-exit marker: proves the process exited through the
        preemption path (emergency checkpoint committed) rather than
        crashing; ``fit(resume=True)`` logs and consumes it."""
        if not is_primary():
            return
        import json

        os.makedirs(ckpt_dir, exist_ok=True)
        info = dict(self._preempt_info or {})
        info["time"] = time.time()
        # The topology that wrote the emergency checkpoint: a resume at
        # a DIFFERENT shape (elastic restore) knows — and can report —
        # what the world looked like when the preemption landed.
        info["mesh"] = ckpt.state_mesh_topology(self.state)
        tmp = os.path.join(ckpt_dir, "PREEMPTED.json.tmp")
        with open(tmp, "w") as fp:
            json.dump(info, fp)
        os.replace(tmp, os.path.join(ckpt_dir, "PREEMPTED.json"))

    def _consume_preempt_marker(self, ckpt_dir: str) -> None:
        marker = os.path.join(ckpt_dir, "PREEMPTED.json")
        if not os.path.exists(marker):
            return
        import json

        try:
            with open(marker) as fp:
                info = json.load(fp)
        except (OSError, ValueError):
            info = {}
        logger.info(
            f"Clean preemption exit detected ({info}); resuming from the "
            "emergency checkpoint."
        )
        saved_mesh = (info.get("mesh") or {}).get("axes")
        current = ckpt.state_mesh_topology(self.state) if (
            self.state is not None
        ) else None
        if saved_mesh and current and saved_mesh != current.get("axes"):
            logger.info(
                f"Topology changed across the preemption: saved on "
                f"{saved_mesh}, resuming on {current.get('axes')} "
                "(elastic restore reshards the checkpoint)."
            )
        if info.get("time"):
            # Downtime attribution: the age of the marker is the gap the
            # preemption cost between exit and this resume — the
            # goodput ledger's preempt_gap bucket (clamped: clock skew
            # must not mint negative downtime).
            from ml_trainer_tpu.telemetry import goodput

            goodput.account(
                "preempt_gap", max(time.time() - float(info["time"]), 0.0)
            )
        if is_primary():
            try:
                os.remove(marker)
            except OSError:
                pass

    def _reseed_loss_scale(self) -> None:
        """After any restore: a checkpoint written before loss scaling
        existed (or by an fp32 run) lands a zero ``loss_scale`` through
        the compat shim — re-seed it to this run's configured initial
        scale (one scalar fetch; no-op when scaling is off)."""
        if self._loss_scale_cfg is None or self.state.loss_scale is None:
            return
        if float(jax.device_get(self.state.loss_scale)) <= 0.0:
            self.state = self.state.replace(
                loss_scale=jax.device_put(
                    jnp.asarray(
                        self._loss_scale_cfg.init_scale, jnp.float32
                    ),
                    self._replicated,
                ),
                good_steps=jax.device_put(
                    jnp.zeros((), jnp.int32), self._replicated
                ),
            )

    def _sync_skipped_base(self) -> None:
        """Re-anchor the per-epoch skipped-step delta after a restore (one
        scalar fetch; the mid-epoch marker overrides this with the value
        at the interrupted epoch's start)."""
        self._skipped_base = (
            int(jax.device_get(self.state.skipped_steps))
            if self.state.skipped_steps is not None else 0
        )

    def _require_mid_resume_support(self) -> None:
        if self.steps_per_execution > 1:
            raise ValueError(
                "the latest checkpoint is mid-epoch (written by "
                "save_every_steps or a preemption exit), which resumes "
                "through the per-batch dispatch path; restart with "
                "steps_per_execution=1 to continue it"
            )

    def _resume_from_latest(self, ckpt_dir: str) -> int:
        """Restore the latest full checkpoint, multi-host-safely.

        Checkpoints are written by the primary host only (the reference's
        rank-0 save, ref: src/trainer.py:252-254), so on a pod without a
        shared filesystem only host 0 may find one.  Host 0's decision and
        restored state are broadcast to every host so all processes start
        the same epoch with identical replicated state.
        """
        self._consume_preempt_marker(ckpt_dir)
        # Valid-only: corrupt checkpoints (CRC mismatch, missing leaves)
        # are quarantined (*.corrupt) by the primary and the scan falls
        # back to the newest one that verifies.
        latest = ckpt.latest_valid_checkpoint(
            ckpt_dir, quarantine=is_primary()
        )
        multi_host = process_count() > 1
        fmt = ckpt.checkpoint_format(latest) if latest is not None else 0
        epoch_in_name = (
            int(os.path.basename(latest).split("_")[-1].split(".")[0])
            if latest is not None else 0
        )
        if multi_host:
            from jax.experimental import multihost_utils

            # Follow host 0's decision — found, FORMAT and EPOCH — whatever
            # the local disk says: hosts disagreeing on the listing (NFS
            # attribute-cache lag) must still take the SAME branch, or one
            # host enters a broadcast the others never join.
            found, fmt, epoch_in_name = (
                int(v)
                for v in multihost_utils.broadcast_one_to_all(
                    jnp.asarray([
                        1 if latest is not None else 0, fmt, epoch_in_name,
                    ])
                )
            )
            if not found:
                return 1
            if fmt == 3:
                # v3 lives on shared storage: every host reads the epoch
                # host 0 picked (its local listing may lag).
                latest = os.path.join(
                    ckpt_dir, f"{ckpt.CHECKPOINT_PREFIX}{epoch_in_name}"
                )
        elif latest is None:
            return 1
        if fmt == 3:
            # Sharded (v3): every host reads its own shards from the shared
            # checkpoint storage and builds its addressable pieces directly
            # on the target mesh — which may DIFFER from the mesh that
            # saved (elastic resume).  No state broadcast: nothing here is
            # host-0-private, and the full tree never materializes.
            state, saved, done_epoch = ckpt.restore_checkpoint(
                latest, self.state, self._state_shardings
            )
            self.state = state
            self._apply_resume_scalars(saved)
            self._sync_skipped_base()
            self._reseed_loss_scale()
            mid = saved.get("mid_epoch")
            if mid is not None:
                self._require_mid_resume_support()
                self._resume_mid = dict(mid)
                logger.info(
                    f"Resuming mid-epoch {mid['epoch']} at batch "
                    f"{mid['batches_done']} ({latest}, sharded)."
                )
                return int(mid["epoch"])
            logger.info(
                f"Resuming from epoch {done_epoch + 1} ({latest}, sharded)."
            )
            return done_epoch + 1
        if latest is not None:
            state, saved, done_epoch = ckpt.restore_checkpoint(
                latest, ckpt.fetch_to_host(self.state)
            )
        else:  # non-primary host without the file; overwritten by broadcast
            state, saved, done_epoch = ckpt.fetch_to_host(self.state), {}, 0
        plateau = saved.get("plateau", {})
        early = saved.get("early_stop", {})
        mid = saved.get("mid_epoch") or {}
        scalars = np.asarray(
            [
                done_epoch,
                saved.get("lr_scale", 1.0),
                plateau.get("best", np.inf),
                plateau.get("num_bad_epochs", 0),
                plateau.get("scale", 1.0),
                early.get("best_val", np.inf),
                early.get("bad_epochs", 0),
                # Mid-epoch resume cursor (zeros when resuming from an
                # epoch boundary); float32 sums round-trip exactly
                # through float64, so bit-exact resume survives the
                # broadcast.
                1.0 if mid else 0.0,
                mid.get("batches_done", 0),
                mid.get("loss_sum", 0.0),
                mid.get("metric_sum", 0.0),
                mid.get("skipped_base", 0),
            ],
            dtype=np.float64,
        )
        if multi_host:
            from jax.experimental import multihost_utils

            state = multihost_utils.broadcast_one_to_all(state)
            scalars = np.asarray(multihost_utils.broadcast_one_to_all(scalars))
        from ml_trainer_tpu.parallel import place_tree

        self.state = place_tree(state, self._state_shardings)
        # History lists are only written from the primary host, which has
        # them from its local checkpoint (ref: src/trainer.py:252-254).
        self.train_losses = list(saved.get("train_loss", []))
        self.val_losses = list(saved.get("val_loss", []))
        self.train_metrics = list(saved.get("train_metric", []))
        self.val_metrics = list(saved.get("val_metric", []))
        self.skipped_steps = list(saved.get("skipped_steps", []))
        self.rollbacks = int(saved.get("rollbacks", 0))
        self.reshapes = list(saved.get("reshapes", []))
        done_epoch = int(scalars[0])
        self._lr_scale = float(scalars[1])
        if self._plateau is not None:
            self._plateau.best = float(scalars[2])
            self._plateau.num_bad_epochs = int(scalars[3])
            self._plateau.scale = float(scalars[4])
        self._best_val = float(scalars[5])
        self._bad_epochs = int(scalars[6])
        self._sync_skipped_base()
        self._reseed_loss_scale()
        if scalars[7]:
            # Mid-epoch checkpoint: re-enter the manifest's epoch at the
            # saved batch cursor instead of starting the next epoch.
            self._require_mid_resume_support()
            self._resume_mid = {
                "epoch": done_epoch,
                "batches_done": int(scalars[8]),
                "loss_sum": float(scalars[9]),
                "metric_sum": float(scalars[10]),
                "skipped_base": int(scalars[11]),
            }
            logger.info(
                f"Resuming mid-epoch {done_epoch} at batch "
                f"{int(scalars[8])} ({latest})."
            )
            return done_epoch
        start_epoch = done_epoch + 1
        logger.info(f"Resuming from epoch {start_epoch} ({latest}).")
        return start_epoch

    # ------------------------------------------------------------------ test
    def test(self, model=None, test_loader=None):
        """Inference over a loader with the trainer's criterion/metric
        config (ref: src/trainer.py:277-301 — config and weights are
        deliberately decoupled there too).  ``model`` may be a
        ``LoadedModel`` (from ``load_model``), a ``(module, variables)``
        pair, a variables dict for this trainer's module, or None to use the
        trained state."""
        logger.info("Testing..")
        module, variables = self._resolve_model(model)
        # Key by id(module) but keep a strong reference to the module in the
        # entry: a GC'd module's id can be recycled by a new module, which
        # would otherwise silently reuse a stale compiled step.
        key = id(module)
        entry = self._eval_cache.get(key)
        if entry is None or entry[0] is not module:
            takes_train = _module_takes_train(module)
            entry = (
                module,
                self._make_eval_step(
                    module, takes_train, has_bs="batch_stats" in variables
                )[0],
            )
            self._eval_cache[key] = entry
        eval_step = entry[1]
        n = len(test_loader)
        if n == 0:
            raise ValueError("test_loader yields no batches")
        loss_sum = jnp.zeros(())
        metric_sum = jnp.zeros(())
        variables = self._place_eval_variables(variables)
        batches = map(self._place_eval_batch, test_loader)
        with tqdm(batches, total=n, unit="batch") as tepoch:
            for i, (x, y) in enumerate(tepoch):
                loss, metric_val = eval_step(variables, x, y)
                loss_sum = loss_sum + loss
                metric_sum = metric_sum + metric_val
                if (i + 1) % self.log_every == 0 or (i + 1) == n:
                    if self.metric:
                        tepoch.set_postfix(
                            loss=float(loss_sum) / n,
                            metric=self._postfix_metric(metric_sum, i + 1, n),
                        )
                    else:
                        tepoch.set_postfix(loss=float(loss))
        test_loss = float(loss_sum) / n
        if self.metric:
            return test_loss, self._metric_finalize(float(metric_sum) / n)
        return test_loss

    def _place_eval_batch(self, batch):
        """Mesh placement for one eval/predict batch.  User-built loaders
        may have a ragged final batch (drop_last is their choice, ref:
        src/trainer.py:79 keeps all samples); replicate those instead of
        failing to split over the data axis — ONE rule for both APIs."""
        d = self._data_parallel
        sharding = (
            self._batch_sharding
            if d == 1 or batch[0].shape[0] % d == 0
            else self._replicated
        )
        return tuple(
            jax.device_put(a, fit_sharding_to_rank(sharding, np.ndim(a)))
            for a in batch
        )

    def predict(self, loader, model=None, apply_pred_function: bool = True):
        """Model outputs for every batch of ``loader``, in order — the
        inference companion to ``test()`` (which only reports loss/metric;
        the reference's 03-notebook flow has no outputs API at all).

        ``model`` resolves exactly as in ``test()`` (None = the trained
        state).  With ``apply_pred_function`` the trainer's configured
        prediction function (softmax/logsoftmax/None) maps the raw
        logits, matching what the metric engine scores.  Returns one
        stacked numpy array [N, ...].  Loaders may yield (x, y) pairs or
        bare x batches; labels are ignored.  Not available for self-loss
        models (their forward returns a scalar, not outputs)."""
        module, variables = self._resolve_model(model)
        if _module_takes_targets(module):
            raise ValueError(
                "predict() needs model outputs; this model computes its "
                "own loss (clone it with loss_chunk=0 for inference)"
            )
        # Same compiled-program cache as test() (module identity keyed,
        # strong ref against id reuse) so repeat predict() calls do not
        # retrace; apply_pred_function changes the program, so it keys.
        key = (id(module), "predict", bool(apply_pred_function))
        entry = self._eval_cache.get(key)
        if entry is None or entry[0] is not module:
            takes_train = _module_takes_train(module)
            pred_fn = self.pred_function if apply_pred_function else None

            @jax.jit
            def forward(variables, x):
                kwargs = {"train": False} if takes_train else {}
                with kernel_mesh(self.mesh):
                    out = module.apply(variables, x, **kwargs)
                return pred_fn(out) if pred_fn is not None else out

            entry = (module, forward)
            self._eval_cache[key] = entry
        forward = entry[1]

        variables = self._place_eval_variables(variables)
        outs = []
        for batch in loader:
            x = batch[0] if isinstance(batch, (tuple, list)) else batch
            (x,) = self._place_eval_batch((x,))
            outs.append(np.asarray(forward(variables, x)))
        if not outs:
            raise ValueError("loader yields no batches")
        return np.concatenate(outs, axis=0)

    def _place_eval_variables(self, variables):
        """Mesh placement for eval/test variables: leaves already carrying a
        NamedSharding — the trained state, possibly TP/FSDP-partitioned —
        KEEP it (forcing them replicated would all-gather the very params
        the sharding exists to split, and OOM exactly on the models that
        need sharding); only host-loaded leaves (checkpoints arrive as
        numpy) are placed, replicated."""
        def place(leaf):
            if isinstance(
                getattr(leaf, "sharding", None), jax.sharding.NamedSharding
            ):
                return leaf
            return jax.device_put(leaf, self._replicated)

        return jax.tree.map(place, variables)

    def _resolve_model(self, model) -> Tuple[Any, dict]:
        if model is None:
            return self.model, self._state_variables()
        if isinstance(model, LoadedModel):
            return model.module, model.variables
        if isinstance(model, tuple):
            return model
        if isinstance(model, dict):
            variables = model if "params" in model else {"params": model}
            return self.model, variables
        if hasattr(model, "apply"):  # bare flax module: use trainer's state
            return model, self._state_variables()
        raise TypeError(f"Cannot interpret model argument of type {type(model)}")

    # ----------------------------------------------------------- persistence
    def save_model(self, model_dir: str) -> None:
        """Weights-only export every epoch (ref: src/trainer.py:232-235).
        Unlike the reference, saving does NOT move the live model off the
        accelerator (the ref's ``.cpu()`` side effect is a quirk we fix)."""
        logger.info("Saving the model.")
        host_vars = ckpt.fetch_to_host(self._state_variables())
        ckpt.save_model_variables(model_dir, host_vars)
        ckpt.write_model_manifest(model_dir, host_vars)

    def export_lora(self, path: str, name: Optional[str] = None) -> dict:
        """Write the trained adapter as one ``.npz`` artifact — the unit
        the serving engine hot-loads (``Server.load_adapter``, docs/
        serving.md "Batched LoRA adapters"): every ``*_lora_A``/``_B``
        leaf plus a meta record (rank/alpha/targets and the frozen
        base's fingerprint, so a server can flag a base mismatch).
        Requires ``Trainer(lora=...)``.  Returns the meta."""
        if self.lora is None:
            raise ValueError(
                "export_lora requires Trainer(lora=LoraConfig(...))"
            )
        if self.state is None:
            raise ValueError("trainer has no state (datasets were not given)")
        from ml_trainer_tpu.lora import export_lora_artifact

        params = jax.device_get(self.state.params)
        meta = export_lora_artifact(params, self.lora, path, name=name)
        logger.info(
            f"LoRA adapter exported -> {path} "
            f"({meta['n_leaves']} leaves, rank {meta['rank']})."
        )
        return meta

    def export_torch(
        self, path: str, ddp_prefix: bool = False, spatial_inputs=None,
    ) -> str:
        """Write the trained weights as a torch-loadable ``model.pth`` —
        the migration-OUT counterpart of importing reference checkpoints
        (checkpoint/torch_export.py inverts every layout conversion;
        ``ddp_prefix=True`` writes the DDP ``module.``-prefixed key form).
        ``spatial_inputs`` maps layer name -> (C, H, W) for any dense
        layer that consumes a flattened conv output and therefore needs
        the H·W·C -> C·H·W input un-permute (default: MLModel's ``fc1``
        table — pass your own for other conv-to-dense models, or ``{}``
        for models without that boundary).  With ``ema_decay`` set,
        exports the EMA weights — the same public face ``save_model``
        and ``test`` present.

        COLLECTIVE when params are genuinely partitioned across hosts
        (multi-host TP/FSDP): the host fetch is then a global allgather,
        so EVERY process must call this method (mirroring fit()'s
        export guard) — calling it on the primary only would deadlock.
        All hosts fetch; only the primary writes, and secondaries return
        ``path`` without touching the filesystem."""
        from ml_trainer_tpu.parallel.distributed import is_primary, process_count

        variables = self._state_variables()
        export_is_collective = process_count() > 1 and any(
            not leaf.is_fully_addressable
            and not getattr(leaf, "is_fully_replicated", False)
            for leaf in jax.tree.leaves(variables)
        )
        if not is_primary() and not export_is_collective:
            return path  # replicated params: primary-only export
        host_vars = ckpt.fetch_to_host(variables)
        if not is_primary():
            return path  # joined the allgather; the primary writes
        return ckpt.save_torch_checkpoint(
            path, host_vars,
            spatial_inputs=spatial_inputs, ddp_prefix=ddp_prefix,
        )

    def save_history_(self, model_dir: str) -> None:
        """Pickle the history dict (ref: src/trainer.py:237-241) — same
        ``history.pkl`` name so ``load_history`` round-trips — plus a
        ``history.json`` mirror (JSON-safe scalars, including the
        skipped_steps / rollbacks resilience ledger) so offline tooling
        reads a run without unpickling; ``load_history`` prefers it."""
        logger.info("Saving the training history.")
        import json
        import pickle

        os.makedirs(model_dir, exist_ok=True)
        with open(os.path.join(model_dir, "history.pkl"), "wb") as fp:
            pickle.dump(self.history, fp)
        tmp = os.path.join(model_dir, "history.json.tmp")
        with open(tmp, "w", encoding="utf-8") as fp:
            # numpy scalars riding in the lists coerce through float().
            json.dump(self.history, fp, default=float, indent=1)
        os.replace(tmp, os.path.join(model_dir, "history.json"))

    def clear(self) -> None:
        """GC pass (ref: src/trainer.py:303-305).  XLA's arena allocator has
        no ``empty_cache`` analog to call — nothing to release."""
        gc.collect()

    def validate_kwargs(self, kwargs, allowed_kwargs,
                        error_message="Keyword argument not understood:"):
        """Parity shim (ref: src/trainer.py:307-311)."""
        validate_kwargs(kwargs, allowed_kwargs, error_message)
