"""Slot-based continuous-batching decode engine.

``generate()`` is one-shot: a whole batch prefills together, decodes in
lockstep, and every row waits for the slowest (the convoy effect); a new
batch shape means a new compile.  This engine serves requests that
arrive at arbitrary times through ONE preallocated KV-cache block and
ONE compiled per-token decode program:

* **Slots.**  The cache is the flax ``decode``-mode cache built at batch
  ``max_batch`` — per attention layer ``[max_batch, H, max_len, D]`` —
  with the scalar ``cache_index``/``pos_index`` leaves widened to
  per-row ``[max_batch]`` vectors (models/layers.py's slot-indexed
  path), so every row sits at its OWN sequence position.  A request owns
  one row (slot) for its lifetime.

* **Prefill.**  A new request prefills OUT OF BAND at batch 1: its
  prompt is right-padded to the next power-of-two bucket (at most
  log2(max_len) compiled prefill programs — ``generate_ragged``'s
  bucketing trick applied to length instead of batch), one batched
  causal forward fills a fresh batch-1 cache, the true-length logits
  sample token 0, and the rows are inserted into the slot cache with the
  index vectors set to the TRUE prompt length.  Padding garbage beyond
  the true length is never attended: the decode mask is
  ``arange(max_len) <= index[slot]`` and later tokens overwrite it.

* **Decode.**  All slots advance through a single compiled step —
  ``[max_batch, 1]`` tokens in, one forward, per-row sampling out.
  Requests join (prefill + insert) and leave (EOS / budget / deadline)
  at token boundaries with NO recompilation: shapes are static, inactive
  slots just compute masked garbage that nobody reads.  ``step()`` is
  dispatch, fence, deliver; the serving loop calls ``advance()``, the
  same turn with the landing one step behind the dispatch, so the
  device never waits for the host between two steps (docs/serving.md).

Sampling matches ``generate()`` token-for-token per request: greedy is
``argmax``; ``temperature > 0`` draws
``categorical(fold_in(rng, t), logits / temperature)`` with the
request's own rng and per-token counter ``t`` — byte-identical to a
standalone batch-1 ``generate()`` call for the same request.

Compiled programs (prefill buckets, the decode step, the slot insert)
live in the process-wide LRU shared with ``generate._COMPILED``, so one
bound covers every decode executable in the process.

* **Speculative mode** (``spec_k > 0``, see speculative.py and
  docs/serving.md): each step drafts ``spec_k`` tokens per slot (n-gram
  lookup over the request's own history, or a vocab-compatible draft
  model with its own slot cache) and ONE verify forward over a
  ``[max_batch, spec_k+1]`` window commits a variable 1..spec_k+1
  tokens per slot — still one static-shaped executable at fixed K, so
  join/leave semantics and the no-recompilation guarantee carry over
  unchanged.  Greedy slots stay byte-identical to ``generate()``.

* **Paged mode** (``kv_page_size > 0``, see kv_pool.py,
  prefix_cache.py and docs/serving.md): the per-slot contiguous
  ``[max_batch, H, max_len, D]`` regions become ONE pool of fixed-size
  pages addressed through host-owned per-slot page tables
  (models/layers.py's paged gather/scatter path — still one static
  executable, the table is an ordinary input).  What that buys:

  - memory tracks LIVE tokens, not ``max_batch × max_len`` worst case;
  - a radix prefix cache maps shared prompt prefixes to already-filled
    refcounted pages, so a prefix hit skips their prefill entirely —
    only the unshared suffix runs (a ``serve_prefill_paged``
    continuation window at the slot's dynamic offset).  The cache is
    NAMESPACED BY TENANT by default (``prefix_scope="tenant"``): cache
    residency is observable (TTFT, hit-rate metrics), so a shared trie
    would let one tenant probe another's prompt/generated content
    block-by-block; ``prefix_scope="global"`` opts trusted deployments
    back into cross-tenant sharing;
  - under page pressure the engine evicts cold prefix pages first, then
    PREEMPTS a victim request: its written pages are donated to the
    prefix cache, the rest freed, and the request re-queues with its
    generated tokens as a resumable prefix (flight-recorder ``preempt``
    event; a structured client error after ``max_preemptions``).

  Requests with no prefix hit still prefill through the SAME contiguous
  batch-1 program as the contiguous engine and are scatter-inserted
  into their pages bit-for-bit, which is what keeps greedy and
  speculative output byte-identical to the contiguous path.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import inspect
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ml_trainer_tpu.generate import _COMPILED, _cache_shapes, _empty_cache
from ml_trainer_tpu.serving.kv_pool import KVPagePool
from ml_trainer_tpu.serving.metrics import ServingMetrics
from ml_trainer_tpu.serving.param_cast import cast_at_use
from ml_trainer_tpu.serving.prefix_cache import PrefixCache
from ml_trainer_tpu.serving.scheduler import Request
from ml_trainer_tpu.telemetry.flight import get_recorder
from ml_trainer_tpu.telemetry.spans import StepProfiler, span
from ml_trainer_tpu.utils.logging import get_logger
from ml_trainer_tpu.speculative import (
    DraftModelDrafter,
    NgramDrafter,
    build_draft_scan,
    build_verify,
)


# The phases of an admission turn, as the serving loop's spans name them:
# the landing it forces (``Server._land_step``), each admission
# (``admit``), and the first dispatch after it with nothing ahead
# (``_turn``).  The benchmark's readers take the names from here.
ADMISSION_SPANS = ("serve_land", "serve_admit", "serve_restart")


def _as_key(rng) -> np.ndarray:
    """Normalize a request rng (None | int seed | PRNG key) to raw
    uint32[2] key data.  None matches ``generate()``'s PRNGKey(0)
    default so an rng-less sampled request reproduces the rng-less
    ``generate()`` call."""
    if rng is None:
        rng = 0
    if isinstance(rng, (int, np.integer)):
        rng = jax.random.PRNGKey(int(rng))
    key = np.asarray(rng, np.uint32).reshape(-1)
    if key.shape != (2,):
        raise ValueError(f"rng must be an int seed or a PRNG key, got {rng!r}")
    return key


def _sample_rows(last, temps, rngs, steps):
    """Per-row sampling: greedy argmax where ``temps == 0``, else
    ``categorical(fold_in(rng_row, t_row), last_row / temp_row)`` — the
    same draw ``generate()`` makes for that request at token ``t``."""
    greedy_tok = jnp.argmax(last, axis=-1)
    keys = jax.vmap(jax.random.fold_in)(rngs, steps)
    safe = jnp.where(temps > 0, temps, 1.0)[:, None]
    sampled = jax.vmap(jax.random.categorical)(keys, last / safe)
    return jnp.where(temps > 0, sampled, greedy_tok)


def _leaf_name(path) -> Optional[str]:
    """Last dict key of a tree path (None for non-dict paths)."""
    return getattr(path[-1], "key", None) if path else None


@dataclasses.dataclass
class _DecodeStep:
    """A decode step between its dispatch and its landing: who was in
    which slot when it was dispatched, and where its outputs are."""

    seq: int
    riders: Dict[int, Request]
    tok: jax.Array                  # [max_batch, 1]; its host copy started
    counted: Optional[dict]         # a ``step_counter_args`` model's counts
    dispatched_at: float
    secs: float = 0.0               # set at the fence (``_fence``)


class SlotDecodeEngine:
    """The slot cache plus its compiled programs.  Single-threaded by
    design: one worker (serving/api.py's loop) calls ``admit`` and
    ``advance`` (or the synchronous ``step``); thread-safe admission
    lives in the scheduler."""

    def __init__(self, model, variables: dict, max_batch: int = 8,
                 metrics: Optional[ServingMetrics] = None,
                 spec_k: int = 0, drafter="ngram",
                 draft_variables: Optional[dict] = None,
                 ngram_n: int = 3,
                 kv_page_size: int = 0, kv_pages: int = 0,
                 paged_kernel: bool = False,
                 quant_int8: bool = False,
                 prefix_cache: bool = True,
                 prefix_scope: str = "tenant",
                 max_preemptions: int = 8,
                 adapters=None,
                 prefill_chunk: int = 0):
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        if not getattr(model, "max_len", 0):
            raise ValueError(
                "serving needs a causal LM exposing decode/max_len "
                f"(got {type(model).__name__})"
            )
        if spec_k < 0 or spec_k >= int(model.max_len):
            raise ValueError(
                f"spec_k must be in [0, max_len={model.max_len}), "
                f"got {spec_k}"
            )
        self.model = model
        self.max_batch = max_batch
        self.max_len = int(model.max_len)
        self.vocab_size = int(model.vocab_size)
        self.metrics = metrics if metrics is not None else ServingMetrics()

        # -- paged KV mode (opt-in) -------------------------------------
        self.kv_page_size = int(kv_page_size)
        self.paged = self.kv_page_size > 0
        self.pool: Optional[KVPagePool] = None
        self._prefix: Optional[PrefixCache] = None
        self.max_preemptions = int(max_preemptions)
        if prefix_scope not in ("tenant", "global"):
            raise ValueError(
                f"prefix_scope must be 'tenant' or 'global', got "
                f"{prefix_scope!r}"
            )
        self.prefix_scope = prefix_scope
        self._preempted: List[Request] = []
        if self.paged:
            if self.max_len % self.kv_page_size:
                raise ValueError(
                    f"kv_page_size ({kv_page_size}) must divide max_len "
                    f"({self.max_len})"
                )
            pages_per_slot = self.max_len // self.kv_page_size
            # Default pool: full contiguous capacity + the trash page —
            # no oversubscription until the caller asks for it.
            self.kv_pages = int(kv_pages) or max_batch * pages_per_slot + 1
            self.pool = KVPagePool(
                self.kv_pages, self.kv_page_size, self.max_len, max_batch
            )
            if prefix_cache:
                self._prefix = PrefixCache(self.pool)
            # The model whose decode cache is paged: compiled decode /
            # verify / continuation programs key on THIS clone, so a
            # paged and a contiguous engine in one process never collide
            # in the compile cache.
            self._key_model = model.clone(
                kv_page_size=self.kv_page_size, kv_pages=self.kv_pages
            )
        else:
            if kv_pages:
                raise ValueError("kv_pages needs kv_page_size > 0")
            self.kv_pages = 0
            self._key_model = model

        # -- Pallas kernel knobs (ops/kernels/; docs/kernels.md) --------
        # paged_kernel fuses the page-table gather into the S == 1
        # decode attention; quant_int8 swaps the decode projections to
        # int8 weights + per-column scales (prefill and verify stay
        # fp32).  Both dispatch to lax references off-TPU, so CPU bytes
        # never change when a knob flips.
        self.paged_kernel = bool(paged_kernel)
        if self.paged_kernel:
            if not self.paged:
                raise ValueError(
                    "paged_kernel needs paged KV (kv_page_size > 0): "
                    "the kernel fuses the page-table gather into the "
                    "decode attention step"
                )
            try:
                self._key_model = self._key_model.clone(paged_kernel=True)
            except TypeError as e:
                raise ValueError(
                    f"{type(model).__name__} does not carry the "
                    "paged_kernel knob (only the GPT-2 family)"
                ) from e
        self.quant_int8 = bool(quant_int8)
        if self.quant_int8:
            if spec_k:
                raise ValueError(
                    "quant_int8 with spec_k > 0 is not supported: the "
                    "verify window runs the fp32 program, so acceptance "
                    "would compare int8 drafts against fp32 verify "
                    "(serve quantized with spec_k=0)"
                )
            if adapters is not None:
                raise ValueError(
                    "quant_int8 with adapters is not supported: LoRA "
                    "deltas attach to the fp32 projections the "
                    "quantized program does not read (serve quantized "
                    "without adapters)"
                )

        # -- chunked prefill (opt-in; page-aligned windows) --------------
        # Long prompts prefill in ``prefill_chunk``-token windows through
        # the paged continuation program, with decode ticks interleaved
        # between windows (serving/api.py advances one window per loop
        # iteration) — one long prompt can no longer head-of-line-block
        # every short request's TTFT.
        self.prefill_chunk = int(prefill_chunk)
        if self.prefill_chunk:
            if not self.paged:
                raise ValueError(
                    "prefill_chunk needs paged KV (kv_page_size > 0): "
                    "chunk windows are continuation-window prefills at "
                    "the slot's page-aligned offset"
                )
            if self.prefill_chunk % self.kv_page_size:
                raise ValueError(
                    f"prefill_chunk ({prefill_chunk}) must be a multiple "
                    f"of kv_page_size ({kv_page_size}): every window "
                    "boundary must land on a page boundary"
                )
            if spec_k:
                raise ValueError(
                    "prefill_chunk with spec_k > 0 is not supported yet: "
                    "the draft cache has no continuation-window prefill "
                    "(serve chunked prefill with spec_k=0)"
                )
        # Chunk-in-progress slots: slot -> dispatch state.  These hold
        # their slot (free_capacity counts them) but are not yet in
        # ``_active`` — decode steps skip them until the final window.
        self._chunked: Dict[int, dict] = {}

        # -- batched LoRA adapter pool (opt-in; docs/serving.md) --------
        # The model clones with ``lora_slots > 0``: every targeted Dense
        # gains pool stacks in the "lora" collection and a per-row
        # gathered delta — ONE program for any adapter mix, slot 0 the
        # all-zero trash adapter, so adapter=None rows stay
        # bit-identical to a LoRA-free engine.
        self.adapters = None
        self._lora_on = False
        self._prefill_model = model
        if adapters is not None:
            from ml_trainer_tpu.serving.adapter_pool import (
                AdapterConfig,
                AdapterPool,
            )

            if isinstance(adapters, dict):
                adapters = AdapterConfig(**adapters)
            if not isinstance(adapters, AdapterConfig):
                raise ValueError(
                    "adapters must be an AdapterConfig (or its kwargs "
                    f"dict), got {type(adapters).__name__}"
                )
            if spec_k:
                raise ValueError(
                    "adapters with spec_k > 0 is not supported yet: the "
                    "speculative verify window does not thread the "
                    "adapter gather (serve adapters with spec_k=0)"
                )
            lora_kw = dict(
                lora_rank=int(adapters.rank),
                lora_slots=int(adapters.slots),
                lora_targets=tuple(adapters.targets),
            )
            try:
                self._key_model = self._key_model.clone(**lora_kw)
                self._prefill_model = model.clone(**lora_kw)
            except TypeError as e:
                raise ValueError(
                    f"{type(model).__name__} does not carry the lora_* "
                    "knobs (only the GPT-2 family serves adapters)"
                ) from e
            self.adapters = AdapterPool(adapters)  # registers sources
            self._lora_on = True
        self.dm = self._key_model.clone(decode=True)
        # Prefill ALWAYS runs the contiguous batch-1 program (shared
        # with contiguous engines — and the anchor that keeps paged
        # output byte-identical): its cache is scatter-inserted into the
        # pages afterwards.  (With adapters the prefill model is the
        # lora clone: the adapter shapes the cached K/V, so the prefill
        # program gathers the request's adapter too.)
        self._dm_prefill = self._prefill_model.clone(decode=True)
        # What a model may offer beyond the GPT-2 family's call (the engine
        # knows no architecture by name): per-row counters of the decode
        # step, which its ``reduce_step_counters`` reduces over the rows in
        # flight inside the program and its ``step_counter_args`` turns
        # into arguments of the fence span, and a prefill that
        # is told the prompt's true length inside its padded bucket (a
        # cache that keeps the last positions only must not keep padding).
        self._counter_args = getattr(model, "step_counter_args", None)
        self._prefill_takes_len = "true_len" in inspect.signature(
            type(model).__call__).parameters
        self.params = (
            variables["params"] if "params" in variables else variables
        )
        # Identity of the weights this engine serves — KV migrated
        # between engines is only portable when the fingerprints match
        # (transfer.import_kv_slot refuses with WeightsMismatch
        # otherwise); a deploy's generation boundary is keyed on it.
        from ml_trainer_tpu.checkpoint import weights_fingerprint

        self.weights_fp = weights_fingerprint({"params": self.params})
        # Decode-only int8 clone + the host-built "quant" collection
        # (ops/kernels/quantize_tree): prefill / verify / continuation
        # windows keep running the fp32 ``self.dm`` programs — only the
        # S == 1 decode program reads the quantized weights.
        self._dm_quant = None
        self._quant = None
        if self.quant_int8:
            try:
                self._dm_quant = self.dm.clone(quant_int8=True)
            except TypeError as e:
                raise ValueError(
                    f"{type(model).__name__} does not carry the "
                    "quant_int8 knob (only the GPT-2 family)"
                ) from e
            from ml_trainer_tpu.ops.kernels.int8_matmul import quantize_tree

            self._quant = quantize_tree(self.params)
            if not self._quant:
                raise ValueError(
                    "quant_int8 found no quantizable projections in the "
                    "params tree (expected qkv/proj/fc_in/fc_out Dense "
                    "kernels)"
                )

        # Batch-1 cache shapes for prefill; slot cache at max_batch with
        # the scalar index leaves widened to [max_batch] vectors.
        self._shapes_b1 = _cache_shapes(self._dm_prefill, 1, jnp.int32)
        shapes_mb = _cache_shapes(self.dm, max_batch, jnp.int32)
        self.cache = jax.tree.map(
            lambda s: jnp.zeros(
                (max_batch,) if s.ndim == 0 else s.shape, s.dtype
            ),
            shapes_mb,
        )
        self.tok = jnp.zeros((max_batch, 1), jnp.int32)
        self._temps = np.zeros((max_batch,), np.float32)
        self._rngs = np.zeros((max_batch, 2), np.uint32)
        self._steps = np.zeros((max_batch,), np.int32)
        # Per-slot adapter index (0 = trash = base model) + the device
        # stacks the rows gather from.  Stacks are ordinary program
        # inputs: uploading an adapter into a slot row (the one compiled
        # scatter below) or repointing a row never recompiles.
        self._adapter_rows = np.zeros((max_batch,), np.int32)
        self._lora_stacks = None
        if self._lora_on:
            full_shapes = jax.eval_shape(
                lambda p: self.dm.init(
                    {"params": p}, jnp.zeros((max_batch, 1), jnp.int32),
                    train=False,
                ),
                jax.random.PRNGKey(0),
            )
            stack_shapes = {
                k: v for k, v in full_shapes["lora"].items()
                if k != "adapter_idx"
            }
            self._lora_stacks = jax.tree.map(
                lambda s: jnp.zeros(s.shape, s.dtype), stack_shapes
            )
            from jax import tree_util as _tu

            flat = _tu.tree_flatten_with_path(self._lora_stacks)
            self._stack_treedef = flat[1]
            self._stack_paths = [
                "/".join(str(getattr(k, "key", k)) for k in p)
                for p, _ in flat[0]
            ]
            self._stack_shapes = {
                path: tuple(leaf.shape)
                for path, (_, leaf) in zip(self._stack_paths, flat[0])
            }
            self._upload = self._program(
                ("adapter_upload", self._key_model, max_batch),
                self._build_adapter_upload,
            )
            # Warm the upload program NOW (zeros over the trash slot's
            # zeros — a no-op write), so the first real hot-load under
            # live traffic mints no compile.
            zero_rows = _tu.tree_unflatten(
                self._stack_treedef,
                [np.zeros(self._stack_shapes[p][1:], np.float32)
                 for p in self._stack_paths],
            )
            self._lora_stacks = self._upload(
                self._lora_stacks, zero_rows, np.int32(0)
            )
        self._active: Dict[int, Request] = {}
        self._step_seq = 0  # decode steps run (the decode_wedge fault clock)
        # Steps dispatched and not yet landed, oldest first: at most one
        # between two calls, two inside ``advance()``.
        self._flying: collections.deque = collections.deque()
        self._landed_at = 0.0  # perf_counter at the last landing's fence
        self._admitted = 0  # admissions since the last ``serve_restart``
        # Overload control (serving/overload.py, set via
        # Server.set_degradation): the active degradation-ladder rung
        # (0 = full service), the retry_after a shed client is told,
        # and whether speculative decode is enabled (rung 2 turns it
        # off WITHOUT recompiling — the vanilla decode program always
        # exists, and greedy streams are byte-identical either way).
        self.degradation_level = 0
        self.shed_retry_after = 2.0
        self.spec_enabled = True
        # Telemetry: flight ring for crash forensics (the watchdog dumps
        # it when the loop wedges) and the on-demand profile window the
        # admin endpoint arms (POST /admin/profile).
        self._flight = get_recorder()
        self._profiler = StepProfiler("serve")

        self._decode = self._program(
            ("serve_decode_int8" if self.quant_int8 else "serve_decode",
             self._key_model, max_batch),
            self._build_decode,
        )
        if self.paged:
            self._insert = self._program(
                ("serve_insert_paged", self._key_model, max_batch),
                self._build_insert_paged,
            )
        else:
            self._insert = self._program(
                ("serve_insert", model, max_batch), self._build_insert
            )
        # Host mirror of each slot's consumed-token count (device
        # ``cache_index``): spec mode always needs it for the verify
        # window; paged mode needs it for page allocation.
        self._pos = np.zeros((max_batch,), np.int32)

        # -- speculative decoding (opt-in; see speculative.py) ----------
        # Slots advance a variable 1..spec_k+1 tokens per verify step;
        # all shapes stay static at fixed spec_k, so ragged join/leave
        # traffic still never recompiles.
        self.spec_k = int(spec_k)
        self._ngram: Optional[NgramDrafter] = None
        self._draft: Optional[DraftModelDrafter] = None
        if self.spec_k:
            if drafter == "ngram":
                self._ngram = NgramDrafter(k=self.spec_k, n=ngram_n)
            elif isinstance(drafter, DraftModelDrafter):
                self._draft = drafter
            elif hasattr(drafter, "max_len"):
                if draft_variables is None:
                    raise ValueError(
                        "a draft model needs draft_variables (its params)"
                    )
                self._draft = DraftModelDrafter(drafter, draft_variables)
            else:
                raise ValueError(
                    "drafter must be 'ngram', a DraftModelDrafter or a "
                    f"registry model, got {drafter!r}"
                )
            self._verify = self._program(
                ("spec_verify", self._key_model, max_batch, self.spec_k + 1),
                lambda: build_verify(self._key_model, max_batch,
                                     self.spec_k + 1),
            )
            # Write caps per slot (the verify window writes spec_k+1
            # positions at pos, so pos is clamped to keep every write
            # inside max_len).
            self._caps = np.full(
                (max_batch,), self.max_len - self.spec_k - 1, np.int32
            )
            if self._draft is not None:
                self._draft.check_compatible(model)
                d_model = self._draft.model
                if int(d_model.max_len) < self.max_len:
                    raise ValueError(
                        f"draft model max_len ({d_model.max_len}) must "
                        f"cover the target's ({self.max_len})"
                    )
                # The draft model keeps the CONTIGUOUS slot cache: it is
                # sized tiny by design (gpt2_nano-class), so paging its
                # K/V buys nothing and would double the page machinery.
                self._draft_dm = d_model.clone(decode=True)
                self._draft_shapes_b1 = _cache_shapes(
                    self._draft_dm, 1, jnp.int32
                )
                d_shapes = _cache_shapes(self._draft_dm, max_batch, jnp.int32)
                self._draft_cache = jax.tree.map(
                    lambda s: jnp.zeros(
                        (max_batch,) if s.ndim == 0 else s.shape, s.dtype
                    ),
                    d_shapes,
                )
                self._draft_tok = jnp.zeros((max_batch, 1), jnp.int32)
                self._draft_scan = self._program(
                    ("spec_draft", d_model, max_batch, self.spec_k),
                    lambda: build_draft_scan(
                        d_model, max_batch, self.spec_k
                    ),
                )
                self._draft_insert = self._program(
                    ("serve_insert", d_model, max_batch), self._build_insert
                )
        self._draft_params = None
        self._serve_params()

    # -- compiled programs ----------------------------------------------

    def _program(self, key, build):
        run = _COMPILED.get(key)
        if run is None:
            run = build()
            _COMPILED[key] = run
        return run

    def _prefill_program(self, bucket: int):
        return self._program(
            ("serve_prefill", self._prefill_model, bucket),
            lambda: self._build_prefill(bucket, lora=self._lora_on),
        )

    def _draft_prefill_program(self, bucket: int):
        return self._program(
            ("serve_prefill", self._draft.model, bucket),
            lambda: self._build_prefill(
                bucket, self._draft_dm, self._draft_shapes_b1
            ),
        )

    def _decode_extra(self, rows) -> tuple:
        """The decode program's inputs after the sampling state."""
        return (
            (self._lora_vars(self._adapter_rows),) if self._lora_on
            else (self._quant,) if self.quant_int8
            else (rows,) if self._counter_args is not None
            else ()
        )

    def _serve_params(self) -> None:
        """Hold the trees the programs are handed as the programs use them
        (serving/param_cast.py): a leaf that the decode step and a prompt's
        prefill only ever cast to one narrower dtype is cast to it once,
        here, and the programs read half the bytes of a float32 weight that
        they round to bfloat16.  What identifies the weights
        (``weights_fp``) and the int8 collection were computed from the
        trees as handed."""
        t0 = time.perf_counter()
        bucket = min(2, self.max_len)     # the multi-token prefill path
        prompt_args = (
            np.zeros((1, bucket), np.int32), np.int32(bucket),
            np.float32(0.0), np.zeros((2,), np.uint32), np.int32(0),
        )
        self.params, cast = cast_at_use(self.params, [
            (self._decode, (self.cache, self.tok, self._temps, self._rngs,
                            self._steps,
                            *self._decode_extra(self._active_rows()))),
            (self._prefill_program(bucket), prompt_args + (
                (self._lora_vars(self._adapter_rows[:1]),)
                if self._lora_on else ())),
        ])
        self.cast_param_bytes = cast
        if self._draft is not None:
            self._draft_params, cast = cast_at_use(self._draft.params, [
                (self._draft_scan, (self._draft_cache, self.tok,
                                    jnp.asarray(self._pos))),
                (self._draft_prefill_program(bucket), prompt_args),
            ])
            self.cast_param_bytes += cast
        self.served_param_bytes = sum(
            int(leaf.nbytes) for leaf in jax.tree.leaves(
                [self.params, self._draft_params])
        )
        self.metrics.record_params(
            cast=self.cast_param_bytes, served=self.served_param_bytes)
        get_logger("ml_trainer_tpu.serving").info(
            "serving_engine", model=type(self.model).__name__,
            max_batch=self.max_batch, max_len=self.max_len,
            cast_param_bytes=self.cast_param_bytes,
            served_param_bytes=self.served_param_bytes,
            seconds=round(time.perf_counter() - t0, 3),
        )

    def _build_decode(self):
        dm = self.dm

        if self.quant_int8:
            qdm = self._dm_quant

            def step_quant(params, cache, tok, temps, rngs, steps, quant):
                # ``quant`` rides as an ordinary (non-donated) program
                # input, like the LoRA stacks: re-quantizing after a
                # weight hot-swap never recompiles.
                logits, mut = qdm.apply(
                    {"params": params, "cache": cache, "quant": quant},
                    tok, train=False, mutable=["cache"],
                )
                nxt = _sample_rows(logits[:, -1], temps, rngs, steps)
                return mut["cache"], nxt[:, None].astype(jnp.int32)

            return jax.jit(step_quant, donate_argnums=(1,))

        if self._lora_on:
            def step_lora(params, cache, tok, temps, rngs, steps, lora):
                logits, mut = dm.apply(
                    {"params": params, "cache": cache, "lora": lora},
                    tok, train=False, mutable=["cache"],
                )
                nxt = _sample_rows(logits[:, -1], temps, rngs, steps)
                return mut["cache"], nxt[:, None].astype(jnp.int32)

            return jax.jit(step_lora, donate_argnums=(1,))

        if self._counter_args is not None:
            def step_counted(params, cache, tok, temps, rngs, steps,
                             in_flight):
                # A model with ``step_counter_args`` sows per-row counts
                # (row axis first) in "step_counters"; the step returns
                # them as its ``reduce_step_counters`` reduces them over
                # the rows in flight (a sum; a smallest value), beside the
                # tokens.
                logits, mut = dm.apply(
                    {"params": params, "cache": cache}, tok,
                    train=False, mutable=["cache", "step_counters"],
                )
                nxt = _sample_rows(logits[:, -1], temps, rngs, steps)
                counted = dm.reduce_step_counters(
                    mut["step_counters"], in_flight)
                return mut["cache"], nxt[:, None].astype(jnp.int32), counted

            return jax.jit(step_counted, donate_argnums=(1,))

        def step(params, cache, tok, temps, rngs, steps):
            logits, mut = dm.apply(
                {"params": params, "cache": cache}, tok,
                train=False, mutable=["cache"],
            )
            nxt = _sample_rows(logits[:, -1], temps, rngs, steps)
            return mut["cache"], nxt[:, None].astype(jnp.int32)

        return jax.jit(step, donate_argnums=(1,))

    # -- batched LoRA adapters (serving/adapter_pool.py) -----------------

    def _build_adapter_upload(self):
        """The one compiled hot-load program: scatter a prepared A/B row
        set into slot ``slot`` of every stack leaf.  Stacks are donated
        (updated in place); static shapes, so loading adapter #1000
        reuses the program minted at warmup."""
        def upload(stacks, rows, slot):
            return jax.tree.map(
                lambda s, r: s.at[slot].set(jnp.asarray(r, s.dtype)),
                stacks, rows,
            )

        return jax.jit(upload, donate_argnums=(0,))

    def _lora_vars(self, idx) -> dict:
        """The "lora" collection for one dispatch: the shared stacks
        plus the caller's per-row adapter index vector."""
        return {
            **self._lora_stacks,
            "adapter_idx": jnp.asarray(idx, jnp.int32),
        }

    def _bind_adapter(self, req: Request, slot: int) -> None:
        """Pin ``req``'s adapter for its slot lifetime: residency hit
        repoints the row; a miss uploads the registered artifact into a
        (possibly LRU-evicted) slot through the warm upload program.
        Raises ``UnknownAdapter`` / ``AdapterPoolExhausted`` (structured
        — the caller maps them to a client error, never a hang)."""
        if not req.adapter:
            self._adapter_rows[slot] = 0
            return
        aslot, upload = self.adapters.acquire(req.adapter)
        if upload is not None:
            from jax import tree_util as _tu

            from ml_trainer_tpu.serving.adapter_pool import prepare_upload

            meta, leaves = upload
            rows = prepare_upload(
                meta, leaves, self._stack_shapes, self.adapters.rank
            )
            rows_tree = _tu.tree_unflatten(
                self._stack_treedef,
                [rows[p] for p in self._stack_paths],
            )
            self._lora_stacks = self._upload(
                self._lora_stacks, rows_tree, np.int32(aslot)
            )
            req.mark("adapter_loaded", adapter=req.adapter, slot=aslot)
        self._adapter_rows[slot] = aslot
        self._push_adapter_metrics()

    def _release_adapter(self, slot: int) -> None:
        """Drop the slot's adapter pin (idempotent — the row zeroes on
        release, and row 0 is the unpinned trash adapter)."""
        if self.adapters is None:
            return
        idx = int(self._adapter_rows[slot])
        if idx:
            self._adapter_rows[slot] = 0
            self.adapters.release(idx)
            self._push_adapter_metrics()

    def _adapter_bytes_per_slot(self) -> int:
        """Device bytes ONE adapter slot occupies across every stack
        leaf (A and B, all layers/targets) — the pricing behind
        ``serving_adapter_pool_bytes{state=}``."""
        cached = getattr(self, "_bytes_per_adapter_slot", None)
        if cached is not None:
            return cached
        total = sum(
            int(l.nbytes) for l in jax.tree.leaves(self._lora_stacks)
        )
        self._bytes_per_adapter_slot = total // max(self.adapters.slots, 1)
        return self._bytes_per_adapter_slot

    def _push_adapter_metrics(self) -> None:
        if self.adapters is None:
            return
        pool = self.adapters
        counters = pool.counters()
        self.metrics.record_adapters(
            free=pool.free_count(), used=pool.used_count(),
            total=pool.slots - 1, resident=pool.resident(),
            hits=counters["hits"], loads=counters["loads"],
            evictions=counters["evictions"],
            bytes_per_slot=self._adapter_bytes_per_slot(),
        )

    def _build_insert(self):
        def insert(cache_big, tok_big, cache1, tok0, slot, true_len):
            def leaf(big, small):
                if big.ndim == small.ndim:
                    # K/V row replace: [1, H, L, D] into row ``slot``.
                    start = (slot,) + (0,) * (big.ndim - 1)
                    return jax.lax.dynamic_update_slice(
                        big, small.astype(big.dtype), start
                    )
                # Index vector vs the prefill's scalar: the slot's
                # position is the TRUE prompt length, not the padded
                # bucket the scalar advanced to.
                return big.at[slot].set(jnp.asarray(true_len, big.dtype))

            cache_big = jax.tree.map(leaf, cache_big, cache1)
            tok_big = jax.lax.dynamic_update_slice(
                tok_big, tok0[:, None], (slot, 0)
            )
            return cache_big, tok_big

        return jax.jit(insert, donate_argnums=(0, 1))

    def _build_insert_paged(self):
        """Scatter a contiguous batch-1 prefill cache into a slot's
        pages: position ``j`` of the b1 cache lands in page
        ``page_row[j // page_size]`` at offset ``j % page_size`` — a pure
        data movement, so the paged slot holds bit-for-bit the K/V the
        contiguous engine would.  ``page_row`` is the slot's full table
        row (trash-0 past its chain, where the bucket's padding garbage
        harmlessly lands)."""
        ps, L = self.kv_page_size, self.max_len
        from jax import tree_util

        def insert(cache_big, tok_big, cache1, tok0, slot, true_len,
                   page_row):
            page_of_pos = jnp.repeat(page_row, ps)          # [L]
            offs = jnp.arange(L) % ps
            big_flat, treedef = tree_util.tree_flatten_with_path(cache_big)
            small = {
                tuple(getattr(k, "key", str(k)) for k in p): leaf
                for p, leaf in tree_util.tree_flatten_with_path(cache1)[0]
            }
            out = []
            for path, big in big_flat:
                if _leaf_name(path) == "page_table":
                    out.append(big.at[slot].set(page_row.astype(big.dtype)))
                    continue
                sm = small[tuple(getattr(k, "key", str(k)) for k in path)]
                if big.ndim == 4:
                    rows = sm[0].transpose(1, 0, 2).astype(big.dtype)  # [L,H,D]
                    out.append(big.at[page_of_pos, :, offs, :].set(rows))
                else:
                    out.append(
                        big.at[slot].set(jnp.asarray(true_len, big.dtype))
                    )
            cache_big = tree_util.tree_unflatten(treedef, out)
            tok_big = jax.lax.dynamic_update_slice(
                tok_big, tok0[:, None], (slot, 0)
            )
            return cache_big, tok_big

        return jax.jit(insert, donate_argnums=(0, 1))

    def _build_prefill(self, bucket: int, dm=None, shapes=None,
                       lora: bool = False):
        dm = dm if dm is not None else self._dm_prefill
        shapes = shapes if shapes is not None else self._shapes_b1

        if lora:
            def prefill_lora(params, prompt_pad, true_len, temp, rng,
                             step0, lora_vars):
                cache = _empty_cache(shapes)
                logits, mut = dm.apply(
                    {"params": params, "cache": cache, "lora": lora_vars},
                    prompt_pad, train=False, mutable=["cache"],
                )
                last = jax.lax.dynamic_index_in_dim(
                    logits, true_len - 1, axis=1, keepdims=False
                )
                tok = _sample_rows(last, temp[None], rng[None], step0[None])
                return mut["cache"], tok.astype(jnp.int32)

            return jax.jit(prefill_lora)

        told = self._prefill_takes_len and dm is self._dm_prefill

        def prefill(params, prompt_pad, true_len, temp, rng, step0):
            cache = _empty_cache(shapes)
            logits, mut = dm.apply(
                {"params": params, "cache": cache}, prompt_pad,
                train=False, mutable=["cache"],
                **({"true_len": true_len} if told else {}),
            )
            # Causal prefill: the padded tail cannot influence position
            # true_len-1, whose logits sample token 0 (fold counter
            # ``step0`` — 0 for fresh requests, the committed-token
            # count for a preempt-resume, so the sampled stream
            # continues generate()'s per-token fold sequence).
            last = jax.lax.dynamic_index_in_dim(
                logits, true_len - 1, axis=1, keepdims=False
            )
            tok = _sample_rows(last, temp[None], rng[None], step0[None])
            return mut["cache"], tok.astype(jnp.int32)

        return jax.jit(prefill)

    def _build_prefill_paged(self, bucket: int):
        """Continuation prefill for a PREFIX-CACHE hit: run only the
        unshared suffix (padded to ``bucket``) through the paged decode
        path at the slot's dynamic offset ``start`` — the suffix window
        attends the shared pages like a verify window attends committed
        tokens, writes its own K/V into the slot's fresh pages, and the
        true last position's logits sample the first new token.  The
        shared prefix's prefill is skipped entirely."""
        dm = self.dm
        lora_on = self._lora_on
        from jax import tree_util

        def run(cache_big, tok_big, params, window, true_len, start,
                page_row, temp, rng, step0, slot, *lora_rest):
            big_flat, treedef = tree_util.tree_flatten_with_path(cache_big)
            # Batch-1 view: shared pools as-is, this slot's table row and
            # start offset as the [1]-row metadata.
            view = []
            for path, leaf in big_flat:
                if leaf.ndim == 4:
                    view.append(leaf)
                elif _leaf_name(path) == "page_table":
                    view.append(page_row[None, :])
                else:
                    view.append(jnp.full((1,), start, leaf.dtype))
            cache1 = tree_util.tree_unflatten(treedef, view)
            variables = {"params": params, "cache": cache1}
            if lora_on:
                variables["lora"] = lora_rest[0]
            logits, mut = dm.apply(
                variables, window,
                train=False, mutable=["cache"],
            )
            last = jax.lax.dynamic_index_in_dim(
                logits, true_len - 1, axis=1, keepdims=False
            )
            tok = _sample_rows(
                last, temp[None], rng[None], step0[None]
            ).astype(jnp.int32)
            # Write back: pools carry the suffix K/V; slot metadata
            # advances to the full consumed length.
            mut_flat = tree_util.tree_flatten_with_path(mut["cache"])[0]
            out = []
            for (path, big), (_, new) in zip(big_flat, mut_flat):
                if big.ndim == 4:
                    out.append(new)
                elif _leaf_name(path) == "page_table":
                    out.append(big.at[slot].set(page_row.astype(big.dtype)))
                else:
                    out.append(
                        big.at[slot].set((start + true_len).astype(big.dtype))
                    )
            cache_big = tree_util.tree_unflatten(treedef, out)
            tok_big = jax.lax.dynamic_update_slice(
                tok_big, tok[:, None], (slot, 0)
            )
            return cache_big, tok_big, tok

        return jax.jit(run, donate_argnums=(0, 1))

    # -- paged memory management ----------------------------------------

    def _sync_table(self) -> None:
        """Upload the host page table into every layer's table leaf when
        it changed (slot freed / pages appended): a compiled step must
        never write through a stale device table into a recycled page.
        Each leaf gets its OWN device copy — donation-safe."""
        if not self.paged or not self.pool.dirty:
            return
        host = self.pool.page_table

        def leaf(l):
            if l.ndim == 2 and l.dtype == jnp.int32:
                return jnp.asarray(host)
            return l

        self.cache = jax.tree.map(leaf, self.cache)
        self.pool.dirty = False

    def _prefix_ns(self, req: Request) -> str:
        """Prefix-cache namespace for ``req``: its tenant by default, so
        whether a block is cached (observable via TTFT and the hit-rate
        metrics) never leaks one tenant's prompt or generated content to
        another; ``prefix_scope="global"`` opts a trusted deployment
        back into one shared trie.

        With adapters enabled the namespace ALWAYS also carries the
        request's adapter (even under prefix_scope="global"): cached
        K/V is a function of the adapter that prefilled it, so a hit
        under adapter X serving adapter Y would be silently-wrong
        logits, not just a side channel."""
        ns = req.tenant if self.prefix_scope == "tenant" else ""
        if self.adapters is not None:
            ns = f"{ns}\x1fadapter={req.adapter or ''}"
        return ns

    def _page_row(self, slot: int) -> np.ndarray:
        row = np.zeros((self.pool.pages_per_slot,), np.int32)
        chain = self.pool.slot_pages[slot]
        row[: len(chain)] = chain
        return row

    def _release_slot_pages(self, slot: int, req: Optional[Request] = None,
                            donate: bool = True) -> None:
        """Return a slot's pages to the pool (idempotent).  With
        ``donate``, its WRITTEN full blocks are first registered in the
        prefix cache — a finished request's prompt stays hot for the
        next user, and a preempted victim can re-pin its own pages on
        resume.  Also drops the slot's adapter pin (every slot-free
        path funnels through here, paged or contiguous)."""
        self._release_adapter(slot)
        if not self.paged:
            return
        chain = self.pool.slot_pages[slot]
        if chain and donate and self._prefix is not None and req is not None:
            blocks = int(self._pos[slot]) // self.kv_page_size
            if blocks:
                seq = np.concatenate([
                    np.asarray(req.prompt, np.int32).reshape(-1),
                    np.asarray(req.tokens, np.int32),
                ])
                self._prefix.insert(
                    seq, chain[:blocks], namespace=self._prefix_ns(req)
                )
        self.pool.reset_slot(slot)
        self._push_kv_metrics()

    def _kv_bytes_per_page(self) -> int:
        """Device bytes of ONE pool page across every layer's K and V:
        the page-geometry × dtype pricing behind
        ``serving_kv_pool_bytes{state=}`` (cached; the pool leaves are
        the cache entries whose leading dim is the page count)."""
        cached = getattr(self, "_bytes_per_page", None)
        if cached is not None:
            return cached
        pool_bytes = sum(
            int(l.nbytes)
            for l in jax.tree.leaves(self.cache)
            if getattr(l, "ndim", 0) >= 1 and l.shape[0] == self.kv_pages
        )
        self._bytes_per_page = pool_bytes // max(self.kv_pages, 1)
        return self._bytes_per_page

    def _push_kv_metrics(self) -> None:
        if not self.paged:
            return
        self.metrics.record_kv(
            self.pool.free_count(), self.pool.used_count(),
            self.kv_pages - 1,
            len(self._prefix) if self._prefix is not None else 0,
            bytes_per_page=self._kv_bytes_per_page(),
        )
        if self._prefix is not None:
            self.metrics.record_prefix_stats(
                self._prefix.hits, self._prefix.misses,
                self._prefix.hit_tokens, self._prefix.lookup_tokens,
            )

    def _pick_victim(self, exclude: int) -> Optional[int]:
        """Preemption victim: lowest priority first, youngest admission
        within a priority (losing the least completed work)."""
        candidates = [
            (req.priority, -(req.admitted_at or 0.0), slot)
            for slot, req in self._active.items()
            if slot != exclude
        ]
        if not candidates:
            return None
        candidates.sort()
        return candidates[0][2]

    def _preempt(self, slot: int, cause: str) -> None:
        """Evict ``slot``'s request under page pressure: donate its
        written blocks to the prefix cache, free the rest, and re-queue
        it (via ``drain_preempted``) with its generated tokens as a
        resumable prefix — or fail it with a structured error once it
        has been preempted ``max_preemptions`` times."""
        req = self._active.pop(slot)
        req.preemptions += 1
        req.mark("preempt", slot=slot, cause=cause)
        self._flight.record(
            "preempt", request=req.id, tenant=req.tenant, slot=slot,
            committed_tokens=len(req.tokens),
            preemptions=req.preemptions, cause=cause,
        )
        self.metrics.record_preemption(req.tenant)
        self._release_slot_pages(slot, req, donate=True)
        if req.preemptions > self.max_preemptions:
            req.finish(
                "error",
                f"request {req.id} (tenant '{req.tenant}') preempted "
                f"{req.preemptions}x under page pressure ({cause}); "
                f"giving up after max_preemptions={self.max_preemptions}",
            )
        else:
            self._preempted.append(req)

    def drain_preempted(self) -> List[Request]:
        """Preempted-but-resumable requests since the last call — the
        serving loop re-queues them (scheduler.requeue)."""
        out, self._preempted = self._preempted, []
        return out

    def _ensure_pages(self, window: int) -> List[int]:
        """Grow every active slot's page chain to cover its next
        ``window`` writes.  Under pressure: evict cold prefix pages
        first, then preempt victims (newest, lowest-priority first).
        Returns the slots freed by preemption."""
        freed: List[int] = []
        pool = self.pool
        for slot in sorted(self._active):
            if slot not in self._active:
                continue
            need_tokens = min(int(self._pos[slot]) + window, self.max_len)
            need = min(pool.pages_for(need_tokens), pool.pages_per_slot)
            short = need - pool.slot_page_count(slot)
            if short <= 0:
                continue
            pages = None
            while slot in self._active:
                pages = pool.allocate(short)
                if pages is not None:
                    break
                want = short - pool.free_count()
                cause = (
                    f"page_pressure: slot {slot} needs {short} page(s), "
                    f"{pool.free_count()} free of {self.kv_pages - 1}"
                )
                if (
                    self._prefix is not None
                    and self._prefix.evict(want) > 0
                ):
                    continue
                victim = self._pick_victim(exclude=slot)
                if victim is None:
                    # Nothing left to shed but this slot itself.
                    self._preempt(slot, cause)
                    freed.append(slot)
                    break
                self._preempt(victim, cause)
                freed.append(victim)
            if pages is not None and slot in self._active:
                pool.extend_slot(slot, pages)
        if freed:
            self._push_kv_metrics()
        return freed

    # -- KV migration (serving/transfer.py; disaggregated serving) -------

    def export_slot(self, slot: int):
        """Export ``slot``'s page chain + continuation state (the
        migration unit the router ships to a decode replica).  Read-only
        — the caller releases the slot afterwards if it migrates."""
        from ml_trainer_tpu.serving.transfer import export_kv_slot

        self._must_have_landed("export_slot")
        return export_kv_slot(self, slot)

    def import_slot(self, req: Request, slot: int, export) -> str:
        """Scatter an exported chain into ``slot`` bit-for-bit and
        register ``req`` as active; returns ``"active"`` or
        ``"no_memory"`` (target pool full — caller requeues ``req``,
        which resumes via the ordinary preempt-resume prefill)."""
        from ml_trainer_tpu.serving.transfer import import_kv_slot

        self._must_have_landed("import_slot")
        return import_kv_slot(self, req, slot, export)

    # -- serving ---------------------------------------------------------

    def free_capacity(self) -> int:
        return self.max_batch - len(self._active) - len(self._chunked)

    def active_count(self) -> int:
        return len(self._active)

    def chunking_count(self) -> int:
        return len(self._chunked)

    def admit(self, req: Request, slot: int) -> str:
        """``_admit`` (which see for the statuses returned) under its
        span: bookkeeping, prefill dispatch and the fence on the first
        token are one stall of every decoding slot."""
        self._must_have_landed("admit")
        self._admitted += 1
        with span("serve_admit", request=req.id, prompt_len=len(req.prompt)):
            return self._admit(req, slot)

    def _admit(self, req: Request, slot: int) -> str:
        """Prefill ``req`` into ``slot`` and emit its first token.
        Returns ``"active"`` (decoding), ``"finished"`` (EOS on token 0
        or a one-token budget — the caller recycles the slot),
        ``"no_memory"`` (paged mode: the pool cannot hold the prompt
        right now — the caller re-queues the request and retries once
        running requests free pages), or ``"chunking"`` (chunked
        prefill engaged: the slot is held and ``advance_chunks`` runs
        one window per serving-loop iteration until the request
        activates)."""
        if slot in self._active:
            raise ValueError(f"slot {slot} is already occupied")
        if req.adapter and self.adapters is None:
            # A pool-less engine silently serving an adapter-named
            # request with BASE weights would be wrong output, not a
            # capacity problem — structured refusal instead.
            req.finish(
                "error",
                f"request {req.id} names adapter '{req.adapter}' but "
                "this engine has no adapter pool "
                "(Server(adapters=AdapterConfig(...)))",
            )
            return "finished"
        # Effective prompt: original prompt plus any tokens committed
        # before a preemption — resume is just admission with a longer
        # prompt (and the fold counter picking up where it left off).
        prompt = np.asarray(req.prompt, np.int32).reshape(-1)
        done_tokens = len(req.tokens)
        if done_tokens:
            prompt = np.concatenate(
                [prompt, np.asarray(req.tokens, np.int32)]
            )
        p = prompt.shape[0]
        key = _as_key(req.rng)

        shared: List[int] = []
        c = 0
        if self.paged:
            if self._prefix is not None:
                # A retry of a previously blocked ("no_memory") admission
                # re-walks the trie but must not re-count stats or
                # re-heat this request's prefix pages' LRU stamps — the
                # serve loop retries every iteration under exactly the
                # page pressure that makes eviction order matter.
                shared, c = self._prefix.lookup(
                    prompt, (p - 1) // self.kv_page_size,
                    namespace=self._prefix_ns(req),
                    record=not req.kv_blocked,
                )
                req.prefix_hit_tokens = c
            if (
                self.degradation_level >= 3 and c == 0
                and done_tokens == 0 and self._prefix is not None
            ):
                # Rung 3 (hits_only): a FRESH prefix-cache miss is shed
                # with a structured 503 instead of spending a full
                # prefill the fleet cannot afford.  Resumes/preempted
                # requests (committed tokens) are never shed — the
                # byte-identity contract for running streams.
                if shared:
                    self.pool.release(shared)
                req.retry_after = self.shed_retry_after
                req.finish(
                    "shed",
                    f"request {req.id} (tenant '{req.tenant}') shed: "
                    "degradation rung hits_only admits prefix-cache "
                    f"hits only; retry after {self.shed_retry_after}s",
                )
                self.metrics.record_shed(req.tenant)
                return "finished"
            # Cover the prompt plus the first decode window so a fresh
            # admission cannot immediately trigger preemption.
            total_need = self.pool.pages_for(
                min(p + 1 + self.spec_k, self.max_len)
            )
            n_new = total_need - len(shared)
            pages = self.pool.allocate(n_new)
            if pages is None and self._prefix is not None:
                self._prefix.evict(n_new - self.pool.free_count())
                pages = self.pool.allocate(n_new)
            if pages is None:
                if shared:
                    self.pool.release(shared)
                if not self._active:
                    # Nothing running will ever free pages: the pool is
                    # simply too small for this request.  Structured
                    # error instead of an unserveable queue entry.
                    req.finish(
                        "error",
                        f"kv pool exhausted: request {req.id} (tenant "
                        f"'{req.tenant}') needs {n_new} page(s) beyond "
                        f"its prefix hit, pool has "
                        f"{self.pool.free_count()} of {self.kv_pages - 1}",
                    )
                    return "finished"
                self.metrics.record_admission_blocked()
                req.kv_blocked = True
                return "no_memory"
            self.pool.bind_slot(slot, shared + pages)
            req.kv_blocked = False

        if self.adapters is not None:
            from ml_trainer_tpu.serving.adapter_pool import (
                AdapterPoolExhausted,
                UnknownAdapter,
            )

            try:
                self._bind_adapter(req, slot)
            except (UnknownAdapter, AdapterPoolExhausted) as e:
                # Structured error naming the adapter — never a hang;
                # any KV pages bound above unwind with the slot.
                if self.paged:
                    self.pool.reset_slot(slot)
                    self._push_kv_metrics()
                req.finish("error", str(e))
                return "finished"

        req.slot = slot
        req.state = "active"
        if self.prefill_chunk and (p - c) > self.prefill_chunk:
            return self._admit_chunked(req, slot, prompt, c, key,
                                       done_tokens)
        req.mark(
            "prefill_start", slot=slot,
            kind="continuation" if (self.paged and c > 0) else "full",
            prefix_hit_tokens=c, resumed_tokens=done_tokens,
        )
        t0 = time.perf_counter()
        if self.paged and c > 0:
            tok0 = self._admit_paged_continuation(
                req, slot, prompt, c, key, done_tokens
            )
        else:
            tok0 = self._admit_full_prefill(
                req, slot, prompt, key, done_tokens
            )
        if self.spec_k:
            self._caps[slot] = min(
                p + (req.max_new_tokens - done_tokens) - 1,
                self.max_len - self.spec_k - 1,
            )
            if self._draft is not None:
                self._admit_draft(prompt, slot, key, req.temperature)
        with span("serve_prefill.fence"):
            tok0 = np.asarray(tok0)  # blocks until prefill + insert land
        prefill_dt = time.perf_counter() - t0
        # The host mirrors, the prefix insert, the first token to its
        # stream (which wakes its thread) and the admission's samples.
        with span("serve_admit.emit"):
            self._pos[slot] = p
            req.prefill_secs += prefill_dt
            req.mark("prefill_done", ms=round(prefill_dt * 1e3, 3))
            self.metrics.record_prefill(prefill_dt)
            self._temps[slot] = req.temperature
            self._rngs[slot] = key
            self._steps[slot] = done_tokens + 1
            if self.paged:
                if self._prefix is not None:
                    # Register the prompt's full blocks NOW (the prefill
                    # that fills them is already dispatched, and the device
                    # stream serializes) so the next same-prefix request —
                    # even one admitted this very batch — hits.
                    self._prefix.insert(
                        prompt,
                        self.pool.slot_pages[slot][: p // self.kv_page_size],
                        namespace=self._prefix_ns(req),
                    )
                self._push_kv_metrics()
            token = int(tok0.reshape(-1)[0])
            req.push_token(token)
            if done_tokens == 0:
                self.metrics.record_ttft(
                    time.monotonic() - req.submitted_at, tenant=req.tenant
                )
                if req.first_admitted_at is not None:
                    # The queueing half of TTFT (the prefill-compute half is
                    # record_prefill above), per-request, so a saturated
                    # queue and a slow prefill are attributable apart.
                    self.metrics.record_queue_wait(
                        req.first_admitted_at - req.submitted_at,
                        tenant=req.tenant,
                    )
            self._active[slot] = req
            if self._finished(req, token):
                return "finished"
            return "active"

    def _admit_full_prefill(self, req, slot, prompt, key, done_tokens):
        """The contiguous batch-1 prefill + slot insert (paged mode
        scatter-inserts the SAME program's cache into pages — the
        byte-identity anchor)."""
        p = prompt.shape[0]
        bucket = min(1 << (p - 1).bit_length(), self.max_len)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :p] = prompt
        run = self._prefill_program(bucket)
        extra = (
            (self._lora_vars(self._adapter_rows[slot: slot + 1]),)
            if self._lora_on else ()
        )
        with span("serve_prefill", prompt_len=p, bucket=bucket, slot=slot,
                  request=req.id, tenant=req.tenant, prompt_tokens=p,
                  bucket_tokens=bucket):
            cache1, tok0 = run(
                self.params, padded, np.int32(p),
                jnp.asarray(req.temperature, jnp.float32), key,
                np.int32(done_tokens), *extra,
            )
            if self.paged:
                self.cache, self.tok = self._insert(
                    self.cache, self.tok, cache1, tok0, np.int32(slot),
                    np.int32(p), jnp.asarray(self._page_row(slot)),
                )
            else:
                self.cache, self.tok = self._insert(
                    self.cache, self.tok, cache1, tok0, np.int32(slot),
                    np.int32(p)
                )
        return tok0

    # Continuation windows bucket to powers of two like prefill, but
    # floored: suffix lengths collapse from log2(max_len) buckets to a
    # handful (8, 16, 32, ...), so steady-state traffic — where a repeat
    # prompt can self-hit down to a 1-token suffix — stops minting new
    # compiles for every tiny suffix length.  Padding cost is at most 7
    # wasted window positions.
    _MIN_SUFFIX_BUCKET = 8

    def _admit_paged_continuation(self, req, slot, prompt, c, key,
                                  done_tokens):
        """Prefix hit: skip the shared ``c`` tokens entirely; run only
        the suffix window through the paged continuation program."""
        p = prompt.shape[0]
        su = p - c
        bucket = min(
            max(self._MIN_SUFFIX_BUCKET, 1 << (su - 1).bit_length()),
            self.max_len,
        )
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :su] = prompt[c:]
        run = self._program(
            ("serve_prefill_paged", self._key_model, bucket),
            lambda: self._build_prefill_paged(bucket),
        )
        extra = (
            (self._lora_vars(self._adapter_rows[slot: slot + 1]),)
            if self._lora_on else ()
        )
        with span("serve_prefill_paged", prompt_len=p, prefix_hit=c,
                  bucket=bucket, slot=slot, request=req.id,
                  tenant=req.tenant):
            self.cache, self.tok, tok0 = run(
                self.cache, self.tok, self.params, padded, np.int32(su),
                np.int32(c), jnp.asarray(self._page_row(slot)),
                jnp.asarray(req.temperature, jnp.float32), key,
                np.int32(done_tokens), np.int32(slot), *extra,
            )
        return tok0

    # -- chunked prefill (prefill_chunk mode) -----------------------------

    def _admit_chunked(self, req, slot, prompt, c, key, done_tokens):
        """Admit a long prompt through page-aligned prefill windows:
        dispatch the first window now (async — nothing blocks) and park
        the slot in ``_chunked``; the serving loop advances one window
        per iteration via ``advance_chunks``, decoding between windows.
        Byte identity holds because every window is the SAME paged
        continuation program a prefix-cache hit runs (at the slot's
        dynamic offset), and the sampling fold-in counter is
        non-consuming — intermediate windows' discarded samples cannot
        perturb the final window's draw."""
        p = prompt.shape[0]
        req.mark(
            "prefill_start", slot=slot, kind="chunked",
            prefix_hit_tokens=c, resumed_tokens=done_tokens,
            window=self.prefill_chunk,
        )
        self.metrics.record_chunked_admission()
        self._chunked[slot] = {
            "req": req, "prompt": prompt, "p": p, "key": key,
            "done_tokens": done_tokens, "next": c, "secs": 0.0,
        }
        self._dispatch_chunk(slot)
        return "chunking"

    def _dispatch_chunk(self, slot: int):
        """Run ONE prefill window for a chunk-in-progress slot.  The
        window start is always page-aligned (prefix hits are
        block-granular and ``prefill_chunk`` is a page multiple).
        Non-final windows return None WITHOUT blocking on the device —
        the interleaving win; the final window blocks and returns the
        request's first sampled token."""
        st = self._chunked[slot]
        req, prompt, p = st["req"], st["prompt"], st["p"]
        start = st["next"]
        w = min(self.prefill_chunk, p - start)
        final = start + w >= p
        t0 = time.perf_counter()
        bucket = min(
            max(self._MIN_SUFFIX_BUCKET, 1 << (w - 1).bit_length()),
            self.max_len,
        )
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :w] = prompt[start: start + w]
        run = self._program(
            ("serve_prefill_paged", self._key_model, bucket),
            lambda: self._build_prefill_paged(bucket),
        )
        extra = (
            (self._lora_vars(self._adapter_rows[slot: slot + 1]),)
            if self._lora_on else ()
        )
        with span("serve_prefill_chunk", prompt_len=p, start=start,
                  window=w, bucket=bucket, slot=slot, request=req.id,
                  tenant=req.tenant):
            self.cache, self.tok, tok0 = run(
                self.cache, self.tok, self.params, padded, np.int32(w),
                np.int32(start), jnp.asarray(self._page_row(slot)),
                jnp.asarray(req.temperature, jnp.float32), st["key"],
                np.int32(st["done_tokens"]), np.int32(slot), *extra,
            )
        st["next"] = start + w
        req.prefill_chunks += 1
        self.metrics.record_prefill_chunk()
        if not final:
            st["secs"] += time.perf_counter() - t0
            req.mark("prefill_chunk", start=start, window=w)
            return None
        tok0 = np.asarray(tok0)  # blocks until the last window lands
        st["secs"] += time.perf_counter() - t0
        return tok0

    def advance_chunks(self) -> List[tuple]:
        """Advance every chunk-in-progress slot by ONE window (the
        serving loop calls this once per iteration, AFTER admissions and
        before decode — short requests admit and decode between a long
        prompt's windows).  Returns ``(slot, req, status)`` tuples:
        ``"chunking"`` (more windows pending), ``"active"`` (final
        window landed, request now decoding), or ``"finished"``
        (completed/cancelled/expired on its first token — the caller
        recycles the slot)."""
        if not self._chunked:
            return []
        self._must_have_landed("advance_chunks")
        out: List[tuple] = []
        now = time.monotonic()
        for slot in sorted(self._chunked):
            st = self._chunked.get(slot)
            if st is None:
                continue
            req = st["req"]
            if req.cancel_requested:
                del self._chunked[slot]
                req.finish("error", "cancelled: hedge superseded")
                self.metrics.record_cancellation()
                self._release_slot_pages(slot, None, donate=False)
                out.append((slot, req, "finished"))
                continue
            if req.expired(now):
                del self._chunked[slot]
                req.finish("expired")
                self.metrics.record_expiry()
                self._release_slot_pages(slot, None, donate=False)
                out.append((slot, req, "finished"))
                continue
            tok0 = self._dispatch_chunk(slot)
            if tok0 is None:
                out.append((slot, req, "chunking"))
                continue
            out.append((slot, req, self._finalize_chunked(slot, req, tok0)))
        return out

    def _finalize_chunked(self, slot: int, req: Request, tok0) -> str:
        """The admit tail for a chunked admission: the last window
        landed, so the slot activates exactly as an unchunked admission
        would — position, sampler state, prefix registration, first
        token, TTFT."""
        st = self._chunked.pop(slot)
        prompt, p = st["prompt"], st["p"]
        done_tokens = st["done_tokens"]
        self._pos[slot] = p
        req.prefill_secs += st["secs"]
        req.mark("prefill_done", ms=round(st["secs"] * 1e3, 3),
                 chunks=req.prefill_chunks)
        self.metrics.record_prefill(st["secs"])
        self._temps[slot] = req.temperature
        self._rngs[slot] = st["key"]
        self._steps[slot] = done_tokens + 1
        if self._prefix is not None:
            self._prefix.insert(
                prompt,
                self.pool.slot_pages[slot][: p // self.kv_page_size],
                namespace=self._prefix_ns(req),
            )
        self._push_kv_metrics()
        token = int(tok0.reshape(-1)[0])
        req.push_token(token)
        if done_tokens == 0:
            self.metrics.record_ttft(
                time.monotonic() - req.submitted_at, tenant=req.tenant
            )
            if req.first_admitted_at is not None:
                self.metrics.record_queue_wait(
                    req.first_admitted_at - req.submitted_at,
                    tenant=req.tenant,
                )
        self._active[slot] = req
        if self._finished(req, token):
            return "finished"
        return "active"

    def abort_chunked(self, msg: str) -> List[int]:
        """Fail every chunk-in-progress request with a structured error
        (teardown/evacuation: their page chains are only partially
        written, so pages release WITHOUT prefix donation).  Returns the
        freed slots for the caller to recycle."""
        freed: List[int] = []
        for slot in list(self._chunked):
            st = self._chunked.pop(slot)
            st["req"].finish("error", msg)
            self._release_slot_pages(slot, None, donate=False)
            freed.append(slot)
        return freed

    def _admit_draft(self, prompt, slot, key, temperature):
        """Prefill the draft model's own (contiguous) slot cache with
        the same effective prompt; its sampled token is discarded — only
        the K/V state matters for drafting."""
        p = prompt.shape[0]
        bucket = min(1 << (p - 1).bit_length(), self.max_len)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :p] = prompt
        d_cache1, d_tok0 = self._draft_prefill_program(bucket)(
            self._draft_params, padded, np.int32(p),
            jnp.asarray(temperature, jnp.float32), key, np.int32(0),
        )
        self._draft_cache, self._draft_tok = self._draft_insert(
            self._draft_cache, self._draft_tok, d_cache1, d_tok0,
            np.int32(slot), np.int32(p),
        )

    def _active_rows(self) -> np.ndarray:
        """1 for each slot that holds a running request: the rows a
        decode step counts (a free slot computes garbage nobody counts)
        and whose ``fold_in`` counter its dispatch advances."""
        rows = np.zeros((self.max_batch,), np.int32)
        rows[list(self._active)] = 1
        return rows

    def _finished(self, req: Request, token: int) -> bool:
        """Finish-and-unbind if ``req`` just completed; True if so."""
        done = (
            req.eos_token_id is not None and token == req.eos_token_id
        ) or len(req.tokens) >= req.max_new_tokens
        if done:
            req.finish("done")
            self.metrics.record_completion()
            self._release_slot_pages(req.slot, req, donate=True)
            del self._active[req.slot]
        return done

    def _sweep_cancelled(self) -> List[int]:
        """Release slots whose request was cancelled (a hedging loser,
        serving/router.py): the router already stopped reading the
        stream and cleared the SLO observer, so the finish is purely a
        release — pages donated (the prefill work stays useful in the
        prefix cache), slot freed before the next dispatch wastes a
        step on it."""
        freed: List[int] = []
        for slot in [
            s for s, r in self._active.items() if r.cancel_requested
        ]:
            req = self._active.pop(slot)
            req.finish("error", "cancelled: hedge superseded")
            self.metrics.record_cancellation()
            self._release_slot_pages(slot, req, donate=True)
            freed.append(slot)
        return freed

    def step(self) -> List[int]:
        """One compiled decode step over all slots, synchronous: dispatch,
        fence, deliver, in that order; each active slot's token(s) are
        with its request on return.  Returns the slots freed this step
        (finished, expired, cancelled, or preempted).  In spec mode each
        slot advances 1..spec_k+1 tokens."""
        return self._turn(look_ahead=False)

    def advance(self) -> List[int]:
        """The serving loop's step: the same turn as ``step()`` with the
        landing one step behind.  While step n runs on the device the
        host prepares and dispatches step n+1, and only then reads and
        delivers step n's tokens, so the device goes from one step
        straight into the next and delivery happens under a busy device.
        Each call returns the slots freed by the step it LANDED (and by
        its own preparation); one step stays in flight until the next
        call or ``land()``."""
        return self._turn(look_ahead=True)

    def land(self) -> List[int]:
        """Fence and deliver the step in flight (none: nothing happens).
        Whatever is not a decode step calls this first, so an admission,
        a chunk window, an export or an import sees the engine as
        ``step()`` leaves it."""
        if not self._flying:
            return []
        landing = self._flying.popleft()
        with self._decode_span(landing.seq, landing.riders):
            toks = self._fence(landing)
        return self._deliver(landing, toks)

    def _decode_span(self, seq: int, riders: Dict[int, Request]):
        return span("serve_decode", engine_step=seq, active=len(riders),
                    requests=[req.id for _, req in sorted(riders.items())])

    def in_flight(self) -> bool:
        """A dispatched step has not been landed yet."""
        return bool(self._flying)

    def _must_have_landed(self, what: str) -> None:
        """Whatever is not a decode step reads or donates what a step in
        flight still owns (``tok``, the host mirrors, ``_active``)."""
        if self._flying:
            raise RuntimeError(
                f"{what} with a decode step in flight: land() it first"
            )

    def abandon(self) -> None:
        """Forget the steps in flight without delivering them: the
        caller is failing their requests (an engine error, a wedged
        device), and a token pushed after that would follow a gap."""
        self._flying.clear()

    def _turn(self, look_ahead: bool) -> List[int]:
        """One turn of the decode engine: prepare and dispatch a step,
        then fence and deliver the OLDEST step in flight, unless that is
        the step just dispatched and ``look_ahead`` leaves it to the
        next turn.

        The engine looks ahead only when nothing a step needs comes
        from the step before it, which it tells from its own state, once
        a turn: the speculative step drafts from the last tokens on the
        host, and a paged step may preempt a victim whose ``req.tokens``
        would be one short of what the device has written.  Both land
        first: the synchronous order is this code with nothing in
        flight when the step is prepared."""
        drafting = bool(self.spec_k and self.spec_enabled)
        look_ahead = look_ahead and not (self.paged or drafting)
        restart = look_ahead and bool(self._active) and not self._flying
        if restart:
            # The first dispatch after a landing (an admission's, as a
            # rule) has nothing ahead of it: ``serve_restart`` holds its
            # prepare and its dispatch, and counts the admissions since
            # the last one (those that shared the landing and this gap).
            admitted, self._admitted = self._admitted, 0
            around = span("serve_restart", engine_step=self._step_seq + 1,
                          admitted=admitted)
        else:
            around = contextlib.nullcontext()
        with around:
            freed = [] if look_ahead else self.land()
            go = False
            if self._active:
                with span("serve_prepare", engine_step=self._step_seq + 1):
                    prepared, go = self._prepare(drafting)
                freed = freed + prepared
            if not go:
                # Nothing to dispatch for: the last step lands alone.
                return freed + self.land()
            if drafting:
                return freed + self._step_spec()
            landing = None
            with self._decode_span(self._step_seq, self._active):
                self._dispatch()
                if len(self._flying) > 1 or not look_ahead:
                    landing = self._flying.popleft()
                    toks = self._fence(landing)
            if landing is not None:
                freed = freed + self._deliver(landing, toks)
            return freed

    def _prepare(self, drafting: bool):
        """What a step needs before its dispatch: the cancelled swept,
        the flight record, the profiler's and the fault plan's hooks,
        and in paged mode the pages of the step's writes.  Returns the
        slots it freed and whether anything is left to dispatch for."""
        freed = self._sweep_cancelled()
        if not self._active:
            return freed, False
        self._step_seq += 1
        # Flight record BEFORE the dispatch: when this step wedges,
        # the ring's newest decode_step record names the step — and
        # the REQUESTS riding it — that the watchdog dump blames.
        self._flight.record(
            "decode_step", engine_step=self._step_seq,
            active=len(self._active), spec=bool(self.spec_k),
            requests=[req.id for _, req in sorted(self._active.items())],
        )
        self._profiler.on_step(self._step_seq)
        # decode_wedge injection hook (resilience/faults.py): block
        # like a wedged device program would — the serving watchdog's
        # job is to fail the waiting clients while this thread is
        # stuck here.
        from ml_trainer_tpu.resilience.faults import active_plan

        plan = active_plan()
        if plan is not None:
            fault = plan.fire("decode_wedge", step=self._step_seq)
            if fault is not None:
                plan.hold_wedge(fault)
        if self.paged:
            freed = freed + self._ensure_pages(
                self.spec_k + 1 if drafting else 1
            )
            self._sync_table()
        return freed, bool(self._active)

    def _dispatch(self) -> None:
        """Enqueue one decode step for the slots active now and put it in
        flight.  Nothing here waits for the device: the step's input
        token is the last step's output, still on the device, and the
        host mirrors (``_steps``, the ``fold_in`` counter, and ``_pos``)
        advance HERE, so the next dispatch needs nothing this step
        produces."""
        rows = self._active_rows()
        extra = self._decode_extra(rows)
        with span("serve_decode.dispatch", engine_step=self._step_seq,
                  ahead=int(bool(self._flying))):
            # A step that starts before the last one landed starts its
            # clock at that landing (``_fence``).
            t0 = time.perf_counter()
            self.cache, self.tok, *counted = self._decode(
                self.params, self.cache, self.tok,
                self._temps, self._rngs, self._steps, *extra,
            )
            # The tokens leave as a plain transfer that waits for THIS
            # step only; a slice program issued later would queue behind
            # the step dispatched after it.
            self.tok.copy_to_host_async()
            for leaf in jax.tree.leaves(counted):
                leaf.copy_to_host_async()
        if self._flying:
            self.metrics.record_dispatch_ahead()
        self._flying.append(_DecodeStep(
            seq=self._step_seq, riders=dict(self._active), tok=self.tok,
            counted=counted[0] if counted else None, dispatched_at=t0,
        ))
        # New arrays, not updates in place: a step in flight may still be
        # reading the ones it was called with.
        self._steps = self._steps + rows
        self._pos = np.minimum(self._pos + 1, self.max_len).astype(np.int32)

    def _fence(self, landing: "_DecodeStep") -> np.ndarray:
        """Wait for ``landing``'s tokens.  The step's seconds run from
        the later of its own dispatch and the previous landing: in
        steady overlap the period from landing to landing.  Consecutive
        steps' seconds add up to first dispatch to last landing, so
        their sum is never less than the device's time for them (one
        sample can be: a landing the host came late to shortens the
        next), and none holds an admission, which lands everything
        first."""
        with span("serve_decode.fence",
                  engine_step=landing.seq) as fence_args:
            # The step's ONE fence: every later read of it is host
            # data.  # graft-lint: sync-ok
            toks = np.asarray(landing.tok)[:, 0]  # blocks: the step landed
            if landing.counted is not None:
                # The counters left the device with the tokens: the
                # same fence.  They count the rows the step was
                # dispatched for, a row dropped at delivery among them.
                # graft-lint: sync-ok
                fence_args.update(self._counter_args(
                    jax.device_get(landing.counted), len(landing.riders)))
        now = time.perf_counter()
        landing.secs = now - max(landing.dispatched_at, self._landed_at)
        self._landed_at = now
        return toks

    def _deliver(self, landing: "_DecodeStep", toks: np.ndarray) -> List[int]:
        """Hand each row's token to the request the step was dispatched
        for, if that request still holds the slot.  A row whose request
        finished, expired or was cancelled while the step was in flight
        computed a token nobody asked for: dropped, counted."""
        with span("serve_deliver", engine_step=landing.seq, emitted=0,
                  freed=0, dropped=0) as delivered:
            freed: List[int] = []
            emitted = dropped = 0
            now = time.monotonic()
            for slot, req in sorted(landing.riders.items()):
                if self._active.get(slot) is not req:
                    dropped += 1
                    continue
                if req.expired(now):
                    req.finish(
                        "expired",
                        f"deadline ({req.deadline}s) passed mid-decode "
                        f"after {len(req.tokens)} token(s)",
                    )
                    self.metrics.record_expiry()
                    self._release_slot_pages(slot, req, donate=True)
                    del self._active[slot]
                    freed.append(slot)
                    continue
                token = int(toks[slot])
                req.push_token(token)
                emitted += 1
                if self._finished(req, token):
                    freed.append(slot)
            self.metrics.record_step(
                landing.secs, len(landing.riders) - dropped, self.max_batch,
                emitted,
            )
            if dropped:
                self.metrics.record_dropped(dropped)
            delivered.update(
                emitted=emitted, freed=len(freed), dropped=dropped)
        return freed

    def _step_spec(self) -> List[int]:
        """One speculative verify step over all slots: draft spec_k
        tokens per slot (lookup or draft model), score the whole
        [max_batch, spec_k+1] window in ONE target forward, commit each
        slot's accepted prefix + 1.  Greedy slots reproduce the vanilla
        path byte-for-byte (longest-accepted-prefix); sampled slots use
        rejection sampling (same distribution, different draw stream
        than the vanilla per-token fold)."""
        active_before = len(self._active)
        k = self.spec_k
        step_requests = [req.id for _, req in sorted(self._active.items())]
        t0 = time.perf_counter()
        with span("serve_decode_spec", engine_step=self._step_seq,
                  active=active_before, k=k, requests=step_requests):
            if self._draft is not None:
                self._draft_cache, drafts_dev = self._draft_scan(
                    self._draft_params, self._draft_cache, self.tok,
                    jnp.asarray(self._pos),
                )
                # Draft fence: the verify window needs the drafted ids
                # on the host.  # graft-lint: sync-ok
                drafts = np.asarray(drafts_dev)
            else:
                # Per-slot draft state: the lookup history is the
                # request's own prompt + committed tokens.  Inactive
                # slots draft zeros — their rows compute masked garbage
                # nobody reads.
                drafts = np.zeros((self.max_batch, k), np.int32)
                for slot, req in self._active.items():
                    hist = np.concatenate([
                        np.asarray(req.prompt, np.int32).reshape(-1),
                        np.asarray(req.tokens, np.int32),
                    ])
                    drafts[slot] = self._ngram.draft_one(hist)
            window = jnp.concatenate(
                [self.tok, jnp.asarray(drafts, jnp.int32)], axis=1
            )
            self.cache, accepted, self.tok, _ = self._verify(
                self.params, self.cache, window, jnp.asarray(self._pos),
                jnp.asarray(self._caps), self._temps, self._rngs,
                self._steps,
            )
            acc = np.asarray(accepted)  # graft-lint: sync-ok
            # graft-lint: sync-ok (the verify step's one fence)
            toks = np.asarray(self.tok[:, 0])  # blocks: the step landed
        dt = time.perf_counter() - t0
        with span("serve_deliver", emitted=0, freed=0) as delivered:
            freed: List[int] = []
            emitted = 0
            acc_active: List[int] = []
            now = time.monotonic()
            for slot in sorted(self._active):
                req = self._active[slot]
                if req.expired(now):
                    req.finish(
                        "expired",
                        f"deadline ({req.deadline}s) passed mid-decode "
                        f"after {len(req.tokens)} token(s)",
                    )
                    self.metrics.record_expiry()
                    self._release_slot_pages(slot, req, donate=True)
                    del self._active[slot]
                    freed.append(slot)
                    continue
                n_acc = int(acc[slot])
                acc_active.append(n_acc)
                req.spec_steps += 1
                req.spec_accepted_tokens += n_acc
                committed = [int(t) for t in drafts[slot][:n_acc]]
                committed.append(int(toks[slot]))
                for token in committed:
                    self._steps[slot] += 1
                    req.push_token(token)
                    emitted += 1
                    if self._finished(req, token):
                        freed.append(slot)
                        break
            # Host mirrors the device's new_pos formula exactly.
            self._pos = np.minimum(
                self._pos + acc.astype(np.int32) + 1, self._caps
            ).astype(np.int32)
            self.metrics.record_step(
                dt, active_before, self.max_batch, emitted
            )
            self.metrics.record_spec(acc_active, k)
            delivered.update(emitted=emitted, freed=len(freed))
        return freed
