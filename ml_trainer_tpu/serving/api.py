"""Thread-safe serving front end over the slot decode engine.

``Server`` owns the engine, the FIFO scheduler, and one worker thread
driving the admit/step loop.  Callers interact through:

* ``submit(prompt, max_new_tokens, ...) -> TokenStream`` — non-blocking;
  the stream iterates tokens as they decode and ``result()`` blocks for
  the full sequence (prompt + continuation, ``generate()``'s layout);
* ``complete(...)`` — the blocking convenience wrapper;
* ``serve_http(port=...)`` — an OPTIONAL stdlib HTTP front end
  (``http.server``; no dependencies), started only when asked for
  (constructor flag ``http_port`` or an explicit call): POST
  ``/v1/generate`` with ``{"prompt": [ids...], "max_new_tokens": n,
  "temperature": t?, "seed": s?, "eos_token_id": e?, "deadline": d?,
  "tenant": name?, "priority": p?}``
  returns ``{"tokens": [...]}``; GET ``/metrics`` serves Prometheus
  text exposition of the process telemetry registry (serving gauges,
  lifecycle latency histograms and SLO attainment freshly published —
  what a scraper points at); GET ``/metrics.json``
  keeps the flat JSON snapshot shape; GET ``/slo`` the structured SLO
  attainment snapshot; GET ``/healthz`` liveness/health
  (503 when wedged or draining); POST ``/admin/profile``
  ``{"steps": K, "logdir"?: ...}`` arms an on-demand ``jax.profiler``
  window over the next K decode steps (telemetry/spans.py).
  Backpressure maps to HTTP 429, deadlines to 504.

Multi-replica surface (serving/router.py, docs/serving.md
"Disaggregated serving"): ``role`` labels the replica for the router
(advertised on ``/healthz`` with queue depth, free KV pages and active
slots — the placement signals), ``submit_request`` enqueues a
pre-built request (resume prefixes, migration sinks), and ``adopt``
accepts a KV migration exported by another replica's prefill
(serving/transfer.py) — imported bit-for-bit into a free slot by the
loop thread, falling back to requeue-and-reprefill under page
pressure.

Failure contract (docs/resilience.md): clients NEVER hang on a dead
engine.  A watchdog thread monitors the loop's heartbeat; a decode step
that wedges past ``watchdog_timeout`` (or an engine thread that dies)
fails every in-flight and queued request with a structured error,
marks the server unhealthy (``/healthz`` -> 503) and refuses new
admissions.  ``drain()`` is the graceful counterpart: stop admission,
finish what's in flight, then ``close()``.
"""

from __future__ import annotations

import base64
import collections
import json
import os
import queue
import threading
import time
from typing import Dict, Optional

import numpy as np

from ml_trainer_tpu.serving.engine import SlotDecodeEngine
from ml_trainer_tpu.serving.metrics import ServingMetrics
from ml_trainer_tpu.serving.overload import DegradationConfig, OverloadShed
from ml_trainer_tpu.serving.scheduler import (
    AdmissionError,
    DeadlineExceeded,
    EngineUnhealthy,
    Request,
    TenantScheduler,
    _DONE,
)
from ml_trainer_tpu.serving.slo import SloPolicy, SloTracker
from ml_trainer_tpu.telemetry import compile_watch, spans
from ml_trainer_tpu.telemetry.flight import get_recorder
from ml_trainer_tpu.utils.logging import get_logger

# Stream sentinel kind a migration sink pushes between tokens — the
# SAME literal serving/router.py's ``_MIGRATE`` uses (api.py must not
# import router; the string is the wire contract).  The fleet stream
# endpoint turns it into an ``{"m": <payload>}`` NDJSON line.
_KV_MIGRATE = "__kv_migrate__"

# Cross-process trace context rides the fleet RPCs as this header (a
# JSON object: trace_id / parent / origin_pid).  The wire meta carries
# the same dict inline for /v1/stream and /v1/adopt; the header is the
# fallback for clients that speak plain /v1/generate.
TRACE_HEADER = "X-Trace-Context"


def _trace_ctx_header(headers) -> Optional[dict]:
    """Parse ``X-Trace-Context`` into a trace-ctx dict (None when
    absent or malformed — a bad trace header must never fail a
    request)."""
    raw = headers.get(TRACE_HEADER, "")
    if not raw:
        return None
    try:
        ctx = json.loads(raw)
    except (TypeError, ValueError):
        return None
    return ctx if isinstance(ctx, dict) and ctx else None


class TokenStream:
    """Streaming view of one request: iterate tokens as they arrive, or
    ``result()`` for the whole sequence."""

    def __init__(self, req: Request, prompt: np.ndarray):
        self._req = req
        self._prompt = prompt
        self._drained = False

    @property
    def request(self) -> Request:
        return self._req

    def __iter__(self):
        while True:
            item = self._req._stream.get()
            if item == _DONE:
                self._drained = True
                self._raise_on_failure()
                return
            yield item

    def _raise_on_failure(self):
        if self._req.state == "expired":
            raise DeadlineExceeded(self._req.error or "deadline exceeded")
        if self._req.state == "shed":
            raise OverloadShed(
                self._req.error or "request shed under overload",
                retry_after=self._req.retry_after,
            )
        if self._req.state == "error":
            raise RuntimeError(self._req.error or "serving engine error")

    def result(self, timeout: Optional[float] = None) -> np.ndarray:
        """Block until the request finishes; returns
        ``[prompt + new tokens]`` (1-D int32).  Raises
        ``DeadlineExceeded`` / ``RuntimeError`` on failure states, and
        ``TimeoutError`` when ``timeout`` expires with the request still
        unfinished — including when the engine is wedged or dead, so a
        blocking caller always gets control back."""
        import queue as _q

        if not self._drained:
            deadline = (
                time.monotonic() + timeout if timeout is not None else None
            )
            while True:
                left = None
                if deadline is not None:
                    left = max(deadline - time.monotonic(), 1e-3)
                try:
                    item = self._req._stream.get(timeout=left)
                except _q.Empty:
                    raise TimeoutError(
                        f"request {self._req.id} not finished within "
                        f"{timeout}s ({len(self._req.tokens)} token(s) so "
                        "far; engine may be wedged — see Server.health())"
                    ) from None
                if item == _DONE:
                    self._drained = True
                    break
        self._raise_on_failure()
        return np.concatenate(
            [self._prompt, np.asarray(self._req.tokens, np.int32)]
        )

    @property
    def tokens(self) -> list:
        """Tokens decoded so far (no blocking)."""
        return list(self._req.tokens)


class Server:
    """Continuous-batching serving session: engine + scheduler + one
    worker thread.  Use as a context manager in tests/scripts so the
    thread is joined deterministically."""

    def __init__(self, model, variables: dict, max_batch: int = 8,
                 max_queue: int = 64,
                 metrics: Optional[ServingMetrics] = None,
                 idle_poll: float = 0.02,
                 http_port: Optional[int] = None,
                 spec_k: int = 0, drafter="ngram",
                 draft_variables: Optional[dict] = None,
                 watchdog_timeout: Optional[float] = 60.0,
                 kv_page_size: int = 0, kv_pages: int = 0,
                 paged_kernel: bool = False,
                 quant_int8: bool = False,
                 prefix_cache: bool = True,
                 prefix_scope: str = "tenant",
                 tenants: Optional[dict] = None,
                 max_preemptions: int = 8,
                 slo: Optional[SloPolicy] = None,
                 slo_timelines: int = 64,
                 role: str = "both",
                 adapters=None,
                 prefill_chunk: int = 0):
        """``watchdog_timeout``: seconds the engine loop may go without a
        heartbeat WHILE work is pending before the watchdog declares it
        wedged — fails every in-flight/queued request with a structured
        error, marks the server unhealthy and stops admission.  Size it
        well above the slowest single decode/prefill dispatch (first-hit
        XLA compiles run on this thread).  ``None`` disables the
        watchdog.

        ``kv_page_size > 0`` switches the engine to the PAGED KV cache
        (docs/serving.md): K/V lives in ``kv_pages`` fixed-size pages
        (0 = full contiguous capacity, i.e. no oversubscription) behind
        per-slot page tables; ``prefix_cache`` enables the radix prefix
        cache so shared prompt prefixes skip prefill; under page
        pressure long generations are preempted and re-queued (at most
        ``max_preemptions`` times each) with their generated tokens as
        a resumable prefix.  ``prefix_scope`` controls prefix sharing:
        ``"tenant"`` (default) keeps each tenant's cached blocks in its
        own namespace — cache residency is observable via TTFT and the
        hit-rate metrics, so a shared trie is a cross-tenant content
        side channel; ``"global"`` opts a trusted single-team
        deployment back into cross-tenant sharing.

        ``tenants`` maps tenant name -> :class:`TenantConfig` (weight,
        max_active, max_queued); requests name their tenant at
        ``submit``.  Unknown tenants get the default config.

        ``slo`` sets the :class:`SloPolicy` (TTFT/TPOT budgets + target)
        the always-on :class:`SloTracker` judges finished requests
        against (``server.slo`` — attainment/burn-rate on ``/metrics``
        and the ``/slo`` endpoint); ``slo_timelines`` bounds the
        last-N request-timeline ring attached to flight dumps.

        ``role`` labels this replica for the disaggregated router
        (serving/router.py): ``"prefill"``, ``"decode"`` or ``"both"``
        (the default — a standalone server serves everything).  The
        role is advertised on ``/healthz`` and is ROUTING POLICY only;
        the engine itself can always do both.

        ``adapters`` (docs/serving.md "Batched LoRA adapters"): an
        :class:`~ml_trainer_tpu.serving.adapter_pool.AdapterConfig`
        arming the batched-LoRA pool — requests then name an adapter at
        ``submit(adapter=...)`` (HTTP ``"adapter"``), each batch row
        gathers its own low-rank delta inside the one compiled decode
        program, and ``load_adapter`` hot-loads new artifacts under
        live traffic with zero recompiles.  ``adapter=None`` traffic
        reads the all-zero trash slot and stays byte-identical to an
        adapter-free server.

        ``prefill_chunk > 0`` (page multiple; needs paged KV) arms
        CHUNKED PREFILL: a prompt longer than the chunk admits through
        page-aligned continuation windows with decode ticks interleaved
        between windows, so one long prompt cannot head-of-line-block
        every short request's TTFT (docs/serving.md).

        ``paged_kernel`` (needs paged KV) runs the S == 1 decode step
        through the fused Pallas paged-attention kernel
        (ops/kernels/paged_attention.py; docs/kernels.md) — the
        page-table gather streams HBM->VMEM inside the kernel instead
        of materializing [B, H, L, D] twice per step.  Off-TPU the knob
        dispatches to the lax reference, which IS the gather path, so
        outputs stay byte-identical.

        ``quant_int8`` serves the decode step with int8-quantized
        qkv/proj/fc_in/fc_out weights + per-column scales
        (ops/kernels/int8_matmul.py; prefill stays fp32).  Opt-in and
        quality-gated (argmax agreement vs fp32, tests/test_kernels.py),
        NOT bit-identical to fp32; refused with ``spec_k > 0`` or
        ``adapters``."""
        if role not in ("prefill", "decode", "both"):
            raise ValueError(
                f"role must be 'prefill', 'decode' or 'both', got {role!r}"
            )
        self.role = role
        self.metrics = metrics if metrics is not None else ServingMetrics()
        self.slo = SloTracker(
            policy=slo, metrics=self.metrics, keep_timelines=slo_timelines,
        )
        self.engine = SlotDecodeEngine(
            model, variables, max_batch=max_batch, metrics=self.metrics,
            spec_k=spec_k, drafter=drafter, draft_variables=draft_variables,
            kv_page_size=kv_page_size, kv_pages=kv_pages,
            paged_kernel=paged_kernel, quant_int8=quant_int8,
            prefix_cache=prefix_cache, prefix_scope=prefix_scope,
            max_preemptions=max_preemptions, adapters=adapters,
            prefill_chunk=prefill_chunk,
        )
        self.scheduler = TenantScheduler(
            max_batch, max_queue=max_queue, metrics=self.metrics,
            tenants=tenants,
        )
        # Every flight dump (watchdog trip, engine death, preemption
        # storm) carries the last-N finished request timelines plus the
        # in-flight ones — the dump names the requests it hurt.
        self.engine._flight.register_context_provider(
            "serving_requests", self.slo.context_payload
        )
        # Watchtower TSDB (telemetry/watchtower.py): history behind this
        # process's registry, sampled on every /metrics publish — the
        # router's federation scrape doubles as the sampler — and served
        # as sparklines on GET /dash.  Pure host work: no device calls,
        # no compiled programs.
        from ml_trainer_tpu.telemetry.watchtower import (
            TimeSeriesStore, watch_context,
        )

        self.watchtower = TimeSeriesStore()
        self.engine._flight.register_context_provider(
            "watchtower", lambda: watch_context(self.watchtower)
        )
        self._idle_poll = idle_poll
        self._log = get_logger("ml_trainer_tpu.serving")
        self._wake = threading.Event()
        self._stopping = False
        self._draining = False
        self.healthy = True
        self._unhealthy_reason: Optional[str] = None
        self._health_lock = threading.Lock()
        self._last_beat = time.monotonic()
        self._admitting_req: Optional[Request] = None
        # KV adoptions landing from another replica's prefill (the
        # router's migration hand-off): (request, KVSlotExport) pairs
        # drained by the loop thread.  Plain deque — single consumer
        # (the loop), producers only append; both ends are atomic.
        self._adoptions: collections.deque = collections.deque()
        # Overload control (serving/overload.py): the active
        # degradation-ladder rung + config mirror the ladder applies;
        # level 0 is full service.
        self._degradation_level = 0
        self._degradation_cfg: Optional[DegradationConfig] = None
        # Router plumbing: the fleet index (chaos faults name replicas
        # by it), the slow-down latch the replica_slow fault arms, and
        # the evacuation sink a role reassignment installs (the loop
        # thread exports every active slot's KV through it).
        self.replica_index = 0
        self._slow_until = 0.0
        self._busy_iters = 0
        self._evacuate_sink = None
        self._evacuated = threading.Event()
        self._httpd = None
        self._http_thread = None
        # Fleet identity (serving/fleet.py): process birth time for
        # ``uptime_s``, and the transport this server is reached over —
        # "inproc" (a Python object in the caller's process) until the
        # fleet worker flips it to "http".
        self._started_at = time.monotonic()
        self.transport = "inproc"
        # Fleet-assigned replica name ("p0", "d1", ...): stamped by the
        # fleet worker main so trace lanes, stream-accept lines and
        # incident bundle entries attribute to the replica, not a pid.
        self.name = ""
        # Wire-id -> Request registry for the fleet stream endpoints
        # (/v1/stream, /v1/adopt): lets /v1/cancel reach a stream by the
        # ROUTER's id, which is stable across processes.
        self._wire_streams: Dict[int, Request] = {}
        self._wire_lock = threading.Lock()
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="serving-engine"
        )
        self._thread.start()
        self._watchdog_timeout = watchdog_timeout
        self._watchdog_thread = None
        if watchdog_timeout is not None:
            if watchdog_timeout <= 0:
                raise ValueError(
                    f"watchdog_timeout must be positive or None, got "
                    f"{watchdog_timeout}"
                )
            self._watchdog_thread = threading.Thread(
                target=self._watchdog, daemon=True, name="serving-watchdog"
            )
            self._watchdog_thread.start()
        if http_port is not None:
            self.serve_http(port=http_port)

    # -- client surface --------------------------------------------------

    def submit(self, prompt, max_new_tokens: int,
               temperature: float = 0.0, rng=None,
               eos_token_id: Optional[int] = None,
               deadline: Optional[float] = None,
               tenant: str = "default", priority: int = 0,
               adapter: Optional[str] = None,
               trace: Optional[dict] = None) -> TokenStream:
        """Enqueue one request (thread-safe).  Raises ``AdmissionError``
        when the queue (global or the tenant's) is at its watermark (or
        the server is draining), ``EngineUnhealthy`` when the engine is
        wedged/dead, and ``ValueError`` on a request the engine could
        never serve.  ``tenant``/``priority`` feed the multi-tenant
        scheduler (higher priority admits first within a tenant);
        ``adapter`` names the LoRA adapter to decode with (needs
        ``Server(adapters=...)``; None = the base model)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("prompt must be a non-empty 1-D token array")
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}"
            )
        if prompt.size + max_new_tokens + self.engine.spec_k > \
                self.engine.max_len:
            extra = (
                f" + spec_k ({self.engine.spec_k}) — the speculative "
                "verify window needs spec_k tokens of cache slack"
                if self.engine.spec_k else ""
            )
            raise ValueError(
                f"prompt ({prompt.size}) + new tokens ({max_new_tokens})"
                f"{extra} exceeds the model's max_len "
                f"({self.engine.max_len})"
            )
        if eos_token_id is not None and not (
            0 <= eos_token_id < self.engine.vocab_size
        ):
            raise ValueError(
                f"eos_token_id must be in [0, {self.engine.vocab_size}), "
                f"got {eos_token_id}"
            )
        if not isinstance(tenant, str) or not tenant:
            raise ValueError(f"tenant must be a non-empty string, got "
                             f"{tenant!r}")
        if adapter is not None:
            if not isinstance(adapter, str) or not adapter:
                raise ValueError(
                    f"adapter must be a non-empty string or None, got "
                    f"{adapter!r}"
                )
            if self.engine.adapters is None:
                raise ValueError(
                    f"request names adapter '{adapter}' but this server "
                    "has no adapter pool (construct with "
                    "Server(adapters=AdapterConfig(...)))"
                )
        req = Request(
            prompt=prompt, max_new_tokens=int(max_new_tokens),
            temperature=float(temperature), rng=rng,
            eos_token_id=eos_token_id, deadline=deadline,
            tenant=tenant, priority=int(priority), adapter=adapter,
        )
        if trace:
            req.trace_ctx = dict(trace)
        self.submit_request(req)
        return TokenStream(req, prompt)

    def load_adapter(self, name: str, source) -> dict:
        """Hot-load (or replace) a LoRA adapter artifact under live
        traffic (thread-safe).  Registration is host-only — the device
        upload runs in the engine loop at the adapter's next admission
        through the one warm compiled scatter, so a hot-load mints no
        compiles and never stalls running streams.  Returns the
        artifact meta.  Raises ``ValueError`` when the pool is absent
        or the artifact does not fit its rank bucket/targets."""
        if self.engine.adapters is None:
            raise ValueError(
                "this server has no adapter pool (construct with "
                "Server(adapters=AdapterConfig(...)))"
            )
        return self.engine.adapters.register(name, source)

    def submit_request(self, req: Request) -> None:
        """Enqueue a pre-built :class:`Request` (thread-safe) — the
        router's shadow-submission surface: the request may carry
        committed ``tokens`` (a resume / redistribution continues from
        them as a prefix) and a ``migration_sink`` (prefill-and-export
        instead of decoding in place).  The caller validated the
        request shape; this enforces only server state."""
        if self._stopping:
            raise RuntimeError("server is closed")
        if not self.healthy:
            raise EngineUnhealthy(
                self._unhealthy_reason or "serving engine unhealthy"
            )
        if self._draining:
            raise AdmissionError(
                "server is draining: admission stopped, in-flight "
                "requests are finishing"
            )
        # Degradation rungs act at SUBMISSION only (serving/overload.py):
        # a request already carrying committed tokens is a resume /
        # redistribution of a running stream and is never clamped or
        # shed — the byte-identity contract.
        level, cfg = self._degradation_level, self._degradation_cfg
        if level and cfg is not None and not req.tokens:
            if level >= 4 and req.priority < cfg.shed_below_priority:
                self.metrics.record_shed(req.tenant)
                raise OverloadShed(
                    f"request {req.id} (tenant '{req.tenant}', priority "
                    f"{req.priority}) shed at admission: degradation "
                    f"rung shed_queued rejects priority < "
                    f"{cfg.shed_below_priority}; retry after "
                    f"{cfg.retry_after_s}s",
                    retry_after=cfg.retry_after_s,
                )
            if req.max_new_tokens > cfg.clamp_tokens:
                req.max_new_tokens = cfg.clamp_tokens
                req.mark(
                    "degraded_clamp", level=level,
                    clamp=cfg.clamp_tokens,
                )
        # Observer installed BEFORE the enqueue so every terminal path —
        # including queued-expiry inside the scheduler — lands in the
        # SLO accounting; a rejected submit never enqueues, so its
        # observer simply never fires.
        req.observer = self.slo.observe
        self.scheduler.submit(req)
        self.slo.track(req)
        self._wake.set()

    def adopt(self, req: Request, export, resolver=None) -> None:
        """Accept a KV migration (thread-safe): ``req`` was prefilled on
        another replica and ``export`` is its slot's page payload
        (serving/transfer.py).  The loop thread imports it into a free
        slot bit-for-bit and decodes from there; if the pool cannot
        hold the chain the request falls back to requeue-and-reprefill
        from its committed tokens.  Raises ``EngineUnhealthy`` /
        ``RuntimeError`` when this replica cannot take work.

        ``resolver`` (fleet RPC, serving/fleet.py): a
        ``callable(status, detail)`` the loop thread invokes with the
        import outcome — ``"adopted"``, ``"corrupt"``, ``"no_memory"``,
        ``"error"``, ``"expired"``, ``"cancelled"``, ``"draining"`` or
        ``"unhealthy"``.  With a resolver installed, corrupt/no_memory
        outcomes are REPORTED instead of locally requeued: the remote
        router holds the payload and falls back to its next candidate
        (the cross-process twin of the in-process fallback loop)."""
        if self._stopping:
            raise RuntimeError("server is closed")
        if not self.healthy:
            raise EngineUnhealthy(
                self._unhealthy_reason or "serving engine unhealthy"
            )
        if self._draining:
            raise AdmissionError(
                "server is draining: admission stopped, in-flight "
                "requests are finishing"
            )
        # This replica's tracker owns the request's lifecycle from here
        # (the prefill replica forgot it at export).
        req.observer = self.slo.observe
        self.slo.track(req)
        self._adoptions.append((req, export, resolver))
        self._wake.set()

    def complete(self, prompt, max_new_tokens: int,
                 timeout: Optional[float] = None, **kwargs) -> np.ndarray:
        """Blocking one-shot: submit and wait for the full sequence."""
        return self.submit(prompt, max_new_tokens, **kwargs).result(
            timeout=timeout
        )

    # -- overload control (serving/overload.py) ---------------------------

    def set_degradation(self, level: int,
                        config: Optional[DegradationConfig] = None) -> None:
        """Apply a degradation-ladder rung (thread-safe, idempotent):
        0 full service, 1 clamp fresh token budgets, 2 speculative
        decode off, 3 prefix-cache hits only, 4 shed low-priority.
        Effects hit NEW admissions only; running streams finish
        undegraded (tests/test_overload.py pins the byte identity)."""
        cfg = config if config is not None else DegradationConfig()
        self._degradation_cfg = cfg
        self._degradation_level = int(level)
        eng = self.engine
        eng.degradation_level = int(level)
        eng.shed_retry_after = cfg.retry_after_s
        eng.spec_enabled = int(level) < 2

    def shed_queued(self, below_priority: int, retry_after: float,
                    cause: str = "overload") -> int:
        """Shed this server's queued requests below ``below_priority``
        (the ladder's rung-4 entry action); returns the count."""
        return self.scheduler.shed_queued(
            below_priority, retry_after, cause=cause
        )

    def cancel(self, req: Request) -> None:
        """Withdraw a request this server no longer needs to serve (the
        hedging loser, serving/router.py): the SLO tracker forgets it
        (a cancelled duplicate is not an SLO miss), the observer is
        cleared, and the loop thread drops it at the next boundary —
        queued entries never admit, active slots release with their
        pages donated."""
        self.slo.forget(req)
        req.observer = None
        req.cancel_requested = True
        self._wake.set()

    def evacuate(self, sink, timeout: float = 30.0) -> bool:
        """Drain this replica THROUGH the migration machinery (role
        reassignment, serving/autoscaler.py): the loop thread exports
        every active slot's KV and hands ``(request, export)`` to
        ``sink`` — the router adopts each onto another replica, so the
        streams keep flowing with their pages instead of re-prefilling —
        and every queued request fails with a retryable ``draining``
        error the router redistributes.  Blocks (up to ``timeout``)
        until the loop thread finished the sweep; returns True when it
        did.  The server stays healthy and keeps serving afterwards —
        the caller controls placement."""
        if not self.engine.paged:
            raise ValueError(
                "evacuate needs a paged engine: the page chain is the "
                "migration unit (kv_page_size > 0)"
            )
        self._evacuated.clear()
        self._evacuate_sink = sink
        self._wake.set()
        return self._evacuated.wait(timeout=timeout)

    def drain(self, timeout: Optional[float] = None) -> bool:
        """Graceful drain: stop admission (``submit`` raises
        ``AdmissionError``) and block until every queued + in-flight
        request finishes, or ``timeout`` passes, or the engine goes
        unhealthy.  Returns True when fully drained.  The usual shutdown
        sequence is ``drain(); close()``."""
        self._draining = True
        deadline = time.monotonic() + timeout if timeout is not None else None
        while self.healthy and not self._stopping:
            if (
                self.engine.active_count() == 0
                and self.scheduler.queue_depth() == 0
            ):
                return True
            if deadline is not None and time.monotonic() > deadline:
                return False
            time.sleep(min(self._idle_poll, 0.05))
        return (
            self.engine.active_count() == 0
            and self.scheduler.queue_depth() == 0
        )

    def health(self) -> dict:
        """Structured health snapshot (the ``/healthz`` payload).  The
        router places requests on these fields — ``role``,
        ``queue_depth``, ``kv_pages_free``, ``active_slots`` — instead
        of round-robin; the shape is pinned by a golden test in
        tests/test_serving.py."""
        from ml_trainer_tpu.resilience.faults import active_plan

        plan = active_plan()
        if plan is not None:
            # healthz_flap chaos: ONE poll looks dropped (the payload
            # says why) — the router's flap damping must absorb it
            # without a spurious drain-and-redistribute.
            fault = plan.fire("healthz_flap", host=self.replica_index)
            if fault is not None:
                return {
                    "ok": False, "healthy": False, "draining": False,
                    "closed": False, "flap": True,
                    "reason": "injected healthz flap (transient)",
                }
        engine = self.engine
        return {
            "ok": self.healthy and not self._draining and not self._stopping,
            "healthy": self.healthy,
            "draining": self._draining,
            "closed": self._stopping,
            "reason": self._unhealthy_reason,
            "role": self.role,
            # Process identity (fleet debugging, serving/fleet.py): which
            # OS process answered, how long it has been up, and whether
            # it is reached in-process or over a socket.
            "pid": os.getpid(),
            "uptime_s": round(time.monotonic() - self._started_at, 3),
            "transport": self.transport,
            # Fleet observability plane (serving/router.py): per-replica
            # recompile budget surfaced through the router's aggregated
            # /healthz, and the clock handshake the router uses to align
            # this process's trace lane (trace_now_us sampled while the
            # router brackets the poll with its own clock).
            "compile_events_post_warmup_total": (
                compile_watch.post_warmup_count()
                if compile_watch.installed() else None
            ),
            **spans.clock_payload(),
            "active_requests": engine.active_count() + engine.chunking_count(),
            "active_slots": engine.active_count() + engine.chunking_count(),
            "max_slots": engine.max_batch,
            "queued_requests": self.scheduler.queue_depth(),
            "queue_depth": self.scheduler.queue_depth(),
            "adoptions_pending": len(self._adoptions),
            "degradation_level": self._degradation_level,
            # Which weights this replica serves (deploy generations key
            # KV portability and placement on it).
            "weights_fp": getattr(engine, "weights_fp", None),
            "kv_pages_free": (
                engine.pool.free_count() if engine.paged else None
            ),
            "kv_pages_total": (
                engine.kv_pages - 1 if engine.paged else None
            ),
            # Adapter-aware router affinity reads this: same-adapter
            # traffic lands where the adapter is already resident.
            "adapters_resident": (
                engine.adapters.resident()
                if engine.adapters is not None else None
            ),
        }

    def close(self) -> None:
        self._stopping = True
        self._wake.set()
        self._thread.join(timeout=10.0)
        if self._httpd is not None:
            self._httpd.shutdown()
            self._httpd.server_close()
            self._httpd = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    # -- engine loop + watchdog ------------------------------------------

    def _fail_all(self, msg: str, release_slots: bool) -> None:
        """Fail every in-flight and queued request with a structured
        error.  ``release_slots=False`` is the watchdog path: the loop
        thread may still be wedged inside the engine, so only the
        request STREAMS are failed (unblocking clients) — engine/slot
        state is cleaned up by the loop thread if it ever returns."""
        engine, sched = self.engine, self.scheduler
        admitting = self._admitting_req
        if admitting is not None and admitting.state == "active":
            admitting.finish("error", msg)
            if release_slots:
                self._admitting_req = None
                if admitting.slot >= 0:
                    engine._release_slot_pages(admitting.slot, donate=False)
                    try:
                        sched.release(admitting.slot)
                    except ValueError:
                        pass
        for slot, req in list(engine._active.items()):
            if req.state == "active":
                req.finish("error", msg)
            if release_slots:
                engine._active.pop(slot, None)
                engine._release_slot_pages(slot, donate=False)
                try:
                    sched.release(slot)
                except ValueError:
                    pass
        if release_slots:
            for slot in engine.abort_chunked(msg):
                try:
                    sched.release(slot)
                except ValueError:
                    pass
        else:
            # Watchdog path: fail the chunk-in-progress STREAMS only —
            # the loop thread may be wedged mid-window.
            for st in list(engine._chunked.values()):
                if st["req"].state == "active":
                    st["req"].finish("error", msg)
        while self._adoptions:
            try:
                req, _, resolver = self._adoptions.popleft()
            except IndexError:
                break
            if req.state == "active" or req.state == "queued":
                req.finish("error", msg)
            if resolver is not None:
                # The remote router retries its other candidates with
                # its own payload copy — "unhealthy" is its retryable
                # fall-through signal.
                resolver("unhealthy", msg)
        for req in sched.drain_pending():
            req.finish("error", msg)
        for req in engine.drain_preempted():
            req.finish("error", msg)

    def _mark_unhealthy(self, reason: str) -> None:
        """Declare the engine dead/wedged: stop admission, fail every
        waiting client with a structured error (never hang), surface the
        reason through ``health()``/``/healthz``, and dump the flight
        recorder — its newest ``decode_step`` record names the engine
        step that wedged.  Idempotent."""
        with self._health_lock:
            if not self.healthy:
                return
            self.healthy = False
            self._unhealthy_reason = reason
        self._log.error("serving_unhealthy", reason=reason)
        from ml_trainer_tpu.telemetry.flight import get_recorder

        get_recorder().dump(
            f"serving_unhealthy: {reason}",
            engine_step=self.engine._step_seq,
            active_requests=self.engine.active_count(),
            queued_requests=self.scheduler.queue_depth(),
            # The dump NAMES the requests the wedge/death hurt; their
            # full lifecycle timelines ride in the serving_requests
            # context provider (SloTracker.context_payload).
            active_request_ids=[
                req.id for req in self.engine._active.values()
            ],
        )
        self._fail_all(f"serving engine unhealthy: {reason}",
                       release_slots=False)
        self._wake.set()

    def _watchdog(self) -> None:
        """Detect a wedged engine: work is pending but the loop thread
        has not heartbeaten within ``watchdog_timeout`` (it is stuck in a
        decode/prefill dispatch).  The watchdog cannot un-wedge the
        device program — it fails the CLIENTS fast and poisons the
        server so callers route around it."""
        poll = max(min(self._watchdog_timeout / 5.0, 1.0), 0.01)
        while not self._stopping and self.healthy:
            time.sleep(poll)
            busy = (
                self.engine.active_count() > 0
                or self.engine.chunking_count() > 0
                or self.scheduler.queue_depth() > 0
                or self._admitting_req is not None
                or len(self._adoptions) > 0
            )
            stale = time.monotonic() - self._last_beat
            if busy and stale > self._watchdog_timeout:
                self.metrics.record_watchdog_trip()
                self._mark_unhealthy(
                    f"decode engine wedged: no heartbeat for {stale:.1f}s "
                    f"with {self.engine.active_count()} active and "
                    f"{self.scheduler.queue_depth()} queued request(s)"
                )
                return

    def _loop(self) -> None:
        try:
            self._loop_inner()
        except BaseException as e:  # noqa: BLE001 — thread death is the event
            # The loop thread is dying on something even the per-iteration
            # handler does not catch: propagate to every waiting client
            # instead of leaving their streams blocked forever.
            self._mark_unhealthy(
                f"engine thread died: {type(e).__name__}: {e}"
            )
        finally:
            # Shutdown (or death): fail whatever is still in flight or
            # queued so no caller blocks forever on a stream the engine
            # will never feed.
            msg = (
                "server closed" if self.healthy
                else f"serving engine unhealthy: {self._unhealthy_reason}"
            )
            # A step still in flight: its tokens reach their requests
            # before those are failed, unless the engine is what failed
            # (a wedged device would hold this thread in the fence).
            if self.healthy:
                try:
                    self._land_step()
                except Exception as e:  # noqa: BLE001 — shutdown must finish
                    self._log.error(
                        "serving_engine_error",
                        error=f"{type(e).__name__}: {e}",
                    )
            self.engine.abandon()
            self._fail_all(msg, release_slots=True)

    def _land_step(self) -> int:
        """Fence and deliver the decode step in flight, if any (loop
        thread only); returns how many slots that freed.  The loop
        dispatches each step before it lands the one before
        (``engine.advance``); whatever is not a decode step (an
        admission, an adoption, a chunk window, an export, an
        evacuation, shutdown) lands first and so sees the engine as a
        synchronous ``engine.step()`` leaves it: ``serve_land`` holds
        that landing's ``serve_decode`` (a fence, no dispatch) and its
        ``serve_deliver``."""
        if not self.engine.in_flight():
            return 0
        with spans.span("serve_land", freed=0) as landed:
            freed = self.engine.land()
            for slot in freed:
                self.scheduler.release(slot)
            landed["freed"] = len(freed)
        return len(freed)

    def _drain_adoptions(self) -> bool:
        """Import queued KV adoptions into free slots (loop thread only).
        An adoption the pool cannot hold falls back to the ordinary
        requeue path — admission re-prefills from the request's
        committed tokens, the same resume preemption uses."""
        engine, sched = self.engine, self.scheduler
        progressed = False
        for _ in range(len(self._adoptions)):
            try:
                req, export, resolver = self._adoptions.popleft()
            except IndexError:
                break
            if req.expired():
                req.finish(
                    "expired",
                    f"deadline ({req.deadline}s) passed awaiting adoption",
                )
                self.metrics.record_expiry()
                if resolver is not None:
                    self.slo.forget(req)
                    resolver("expired", req.error)
                progressed = True
                continue
            if req.cancel_requested:
                req.finish("error", "cancelled: hedge superseded")
                self.metrics.record_cancellation()
                if resolver is not None:
                    resolver("cancelled", req.error)
                progressed = True
                continue
            slot = sched.acquire_direct(req)
            if slot is None:
                # No free slot right now: park it at the head so the
                # next free slot goes to the oldest adoption.
                self._adoptions.appendleft((req, export, resolver))
                break
            # Tracked like a prefill admission: a crash in the landing
            # (a device fence) or mid-import is visible to the
            # watchdog/error handler (the request is not in
            # engine._active yet) and fails its stream instead of
            # hanging the client.
            from ml_trainer_tpu.serving.transfer import (
                MigrationCorrupt,
                WeightsMismatch,
            )

            self._admitting_req = req
            try:
                self._land_step()
                status = engine.import_slot(req, slot, export)
            except MigrationCorrupt as e:
                # The payload failed its CRC gate AT import (the router
                # verifies at deserialization, so this is the last
                # line): refuse the pages, fall back to the ordinary
                # requeue-and-reprefill resume — never adopt garbage,
                # never poison the loop.  With a resolver (fleet RPC)
                # the corrupt verdict is REPORTED instead: the remote
                # router owns the payload and its fallback candidates.
                # A WeightsMismatch is the same refusal shape but its
                # own wire verdict — retrying other candidates of the
                # same generation cannot help, the router must
                # re-prefill instead.
                self._admitting_req = None
                sched.release(slot)
                verdict = (
                    "weights_mismatch"
                    if isinstance(e, WeightsMismatch) else "corrupt"
                )
                req.mark(f"adopt_{verdict}", error=str(e))
                self._log.error(
                    f"serving_adopt_{verdict}", request=req.id,
                    error=str(e),
                )
                if resolver is not None:
                    self.slo.forget(req)
                    resolver(verdict, str(e))
                else:
                    sched.requeue(req)
                progressed = True
                continue
            except Exception as e:  # noqa: BLE001 — the loop's handler ends it
                # The loop fails the request with the rest; a remote
                # router hears so now and not at its time-out.
                if resolver is not None:
                    resolver("error", f"{type(e).__name__}: {e}")
                raise
            self._admitting_req = None
            if status == "no_memory":
                sched.release(slot)
                req.mark("adopt_no_memory", kv_pages_free=(
                    engine.pool.free_count() if engine.paged else None
                ))
                if resolver is not None:
                    self.slo.forget(req)
                    resolver("no_memory", "kv pool cannot hold the chain")
                else:
                    sched.requeue(req)
            elif status == "error":
                # The import finished the request with a structured
                # error (e.g. an unregistered adapter on this replica);
                # nothing bound — just hand the slot back.
                sched.release(slot)
                if resolver is not None:
                    resolver("error", req.error)
            else:
                req.mark("adopted", slot=slot)
                if resolver is not None:
                    resolver("adopted", None)
            progressed = True
        return progressed

    def _export_for_migration(self, req: Request, slot: int) -> None:
        """Prefill-and-export hand-off (loop thread only): the request
        just prefilled into ``slot`` and carries a ``migration_sink`` —
        ship its KV to the router instead of decoding here.  The slot's
        pages release with the usual prefix-cache donation, so the
        prompt stays hot on this prefill replica for affinity-routed
        followers."""
        engine, sched = self.engine, self.scheduler
        export = engine.export_slot(slot)
        engine._active.pop(slot, None)
        engine._release_slot_pages(slot, req, donate=True)
        sched.release(slot)
        # The decode replica's tracker takes over at adopt(); before the
        # tracker forgets the request, emit this replica's fragment of
        # the cross-process trace (queue_wait + prefill on THIS lane) so
        # the merged fleet timeline shows where the prefill ran.
        self.slo.observe_export(req)
        self.slo.forget(req)
        req.mark(
            "kv_exported", pages=export.n_pages, kv_bytes=export.nbytes(),
        )
        sink, req.migration_sink = req.migration_sink, None
        try:
            sink(req, export)
        except Exception as e:  # noqa: BLE001 — the sink is router code
            req.finish(
                "error",
                f"kv migration sink failed: {type(e).__name__}: {e}",
            )

    def _fault_hooks(self) -> None:
        """Serving chaos injection (resilience/faults.py): a matching
        ``replica_slow`` fault latches a slow-down window — every loop
        iteration inside it sleeps, the in-process analog of a replica
        whose chips are being throttled.  The busy-iteration counter is
        the trigger clock, so the fault fires while the replica is
        actually serving, not while it idles."""
        from ml_trainer_tpu.resilience.faults import active_plan

        plan = active_plan()
        if plan is None:
            return
        busy = (
            self.engine.active_count() > 0
            or self.engine.chunking_count() > 0
            or self.scheduler.queue_depth() > 0
            or len(self._adoptions) > 0
        )
        if busy:
            self._busy_iters += 1
            fault = plan.fire(
                "replica_slow", step=self._busy_iters,
                host=self.replica_index,
            )
            if fault is not None:
                self._slow_until = time.monotonic() + fault.secs
        self._maybe_slow()

    def _maybe_slow(self) -> None:
        """Inside a ``replica_slow`` window every dispatch (admission,
        decode step, loop pass) pays ~0.5s — a brutally throttled
        replica whose queue genuinely GROWS under load, which the
        hedging/breaker/autoscaler machinery must route around, not
        wait politely for."""
        if time.monotonic() < self._slow_until:
            time.sleep(0.5)

    def _run_evacuation(self) -> None:
        """Role-reassignment drain (loop thread only): export every
        active slot through the migration machinery to the installed
        sink, hand pending adoptions along with their exports, and fail
        queued requests with a retryable ``draining`` error the router
        redistributes.  The replica is empty (and still healthy) when
        this returns."""
        sink, self._evacuate_sink = self._evacuate_sink, None
        engine, sched = self.engine, self.scheduler
        self._land_step()
        for slot in sorted(engine._active):
            req = engine._active[slot]
            export = engine.export_slot(slot)
            engine._active.pop(slot, None)
            engine._release_slot_pages(slot, req, donate=True)
            sched.release(slot)
            # The adopting replica's tracker takes over (Server.adopt).
            self.slo.forget(req)
            req.mark("evacuated", slot=slot, pages=export.n_pages)
            try:
                sink(req, export)
            except Exception as e:  # noqa: BLE001 — the sink is router code
                req.finish(
                    "error",
                    f"replica draining for role reassignment; evacuation "
                    f"sink failed: {type(e).__name__}: {e}",
                )
        # Chunk-in-progress prompts have no committed tokens yet: fail
        # them with the retryable ``draining`` error (the router
        # resubmits from scratch) instead of exporting half-written
        # pages.
        for st in engine._chunked.values():
            self.slo.forget(st["req"])
        for slot in engine.abort_chunked(
            "replica draining for role reassignment: request "
            "redistributed"
        ):
            try:
                sched.release(slot)
            except ValueError:
                pass
        while self._adoptions:
            try:
                req, export, resolver = self._adoptions.popleft()
            except IndexError:
                break
            self.slo.forget(req)
            if resolver is not None:
                # A fleet-RPC adoption still pending at evacuation: the
                # remote router holds the payload — report "draining"
                # and let it fall to its next candidate.
                resolver(
                    "draining",
                    "replica draining for role reassignment",
                )
                continue
            try:
                sink(req, export)
            except Exception as e:  # noqa: BLE001
                req.finish(
                    "error",
                    f"replica draining for role reassignment; evacuation "
                    f"sink failed: {type(e).__name__}: {e}",
                )
        for req in sched.drain_pending():
            self.slo.forget(req)
            req.finish(
                "error",
                "replica draining for role reassignment: request "
                "redistributed",
            )
        self._evacuated.set()

    def _loop_inner(self) -> None:
        engine, sched = self.engine, self.scheduler
        while not self._stopping and self.healthy:
            self._last_beat = time.monotonic()
            try:
                self._fault_hooks()
                if self._evacuate_sink is not None:
                    self._run_evacuation()
                # Adoptions first: they already spent a prefill on
                # another replica — making them wait behind fresh
                # admissions would waste that work under load.
                progressed = self._drain_adoptions()
                # Every free slot is filled, but for those that the
                # landing below frees: the step that frees them is one
                # the synchronous order runs AFTER this admission, so
                # they are filled an iteration later, after a decode
                # step.  Filled at once they would put two prefills into
                # one gap between tokens where the synchronous order has
                # one in each of two (the tail of the inter-token times).
                held_back = 0
                while engine.free_capacity() > held_back:
                    got = sched.acquire()
                    if got is None:
                        break
                    req, slot = got
                    # Tracked so a wedge or crash in the landing (a
                    # device fence) or DURING prefill (request popped
                    # from the queue, not yet in engine._active) is
                    # still visible to the watchdog/error handler and
                    # failed with the rest instead of hanging its stream.
                    self._admitting_req = req
                    held_back += self._land_step()
                    self._maybe_slow()
                    status = engine.admit(req, slot)
                    self._admitting_req = None
                    progressed = True
                    if status == "no_memory":
                        # Page pressure: hand the slot back, re-queue the
                        # request at the head of its tenant queue, and let
                        # the running requests free pages first.
                        sched.release(slot)
                        sched.requeue(req)
                        break
                    if status == "finished":
                        sched.release(slot)
                    elif status == "active" and req.migration_sink is not None:
                        self._export_for_migration(req, slot)
                    # "chunking" holds its slot: advance_chunks below
                    # runs one window per loop iteration.
                # One chunked-prefill window per slot per iteration,
                # AFTER admissions — short requests admit (and decode,
                # below) between a long prompt's windows instead of
                # waiting out its whole prefill.
                if engine.chunking_count():
                    self._land_step()
                for slot, req, status in engine.advance_chunks():
                    progressed = True
                    if status == "finished":
                        sched.release(slot)
                    elif status == "active" and req.migration_sink is not None:
                        self._export_for_migration(req, slot)
                if engine.active_count() or engine.in_flight():
                    # One step of lookahead: dispatch step n+1, THEN read
                    # and deliver step n's tokens, so the device never
                    # waits for the delivery or the next dispatch.  The
                    # last step lands here too, with nothing left active.
                    self._maybe_slow()
                    for slot in engine.advance():
                        sched.release(slot)
                    # Preempt-and-requeue victims resume from their
                    # committed tokens (head of their tenant queue).
                    for req in engine.drain_preempted():
                        sched.requeue(req)
                    progressed = True
                if not progressed:
                    # Idle with nothing to do, told from idle behind the
                    # host: one span a poll (50 a second at the default).
                    with spans.span("serve_wait"):
                        self._wake.wait(timeout=self._idle_poll)
                    self._wake.clear()
            except Exception as e:  # noqa: BLE001 — the loop must survive
                # Fail every in-flight request loudly rather than hang
                # their streams, then keep serving new ones.
                err = f"{type(e).__name__}: {e}"
                self._log.error("serving_engine_error", error=err)
                self.metrics.record_engine_error()
                # Their riders fail below: no token follows the error.
                engine.abandon()
                admitting, self._admitting_req = self._admitting_req, None
                if admitting is not None and admitting.state == "active":
                    # Crashed mid-prefill: not in engine._active yet, so
                    # the sweep below would miss it.
                    admitting.finish("error", err)
                    if admitting.slot >= 0:
                        engine._release_slot_pages(
                            admitting.slot, donate=False
                        )
                        try:
                            sched.release(admitting.slot)
                        except ValueError:
                            pass
                for slot, req in list(engine._active.items()):
                    if req.state == "active":
                        req.finish("error", err)
                    del engine._active[slot]
                    engine._release_slot_pages(slot, donate=False)
                    try:
                        sched.release(slot)
                    except ValueError:
                        pass
                for slot in engine.abort_chunked(err):
                    try:
                        sched.release(slot)
                    except ValueError:
                        pass
                for req in engine.drain_preempted():
                    req.finish("error", err)

    # -- HTTP front end --------------------------------------------------

    def _register_wire(self, wire_id, req: Request) -> None:
        with self._wire_lock:
            self._wire_streams[int(wire_id)] = req

    def _forget_wire(self, wire_id) -> None:
        with self._wire_lock:
            self._wire_streams.pop(int(wire_id), None)

    def serve_http(self, host: str = "127.0.0.1", port: int = 0):
        """Start the stdlib HTTP front end (daemon thread); returns the
        bound ``(host, port)``.  Explicitly opt-in — nothing listens
        unless this is called (or ``http_port`` was passed)."""
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

        server = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *args):  # quiet: we have metrics
                pass

            def _send(self, code: int, payload: dict,
                      retry_after: Optional[float] = None):
                body = json.dumps(payload).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                if retry_after is not None:
                    self.send_header(
                        "Retry-After",
                        str(max(1, int(round(retry_after)))),
                    )
                self.end_headers()
                self.wfile.write(body)

            def _send_text(self, code: int, text: str, content_type: str):
                body = text.encode()
                self.send_response(code)
                self.send_header("Content-Type", content_type)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            # -- fleet NDJSON streaming (serving/fleet.py) ------------
            # HTTP/1.0 close-delimited bodies: no Content-Length, the
            # connection closing marks the end of the stream — the
            # stdlib client reads line-by-line until EOF.

            def _ndjson_start(self):
                self.send_response(200)
                self.send_header("Content-Type", "application/x-ndjson")
                self.end_headers()

            def _ndjson(self, obj) -> bool:
                try:
                    self.wfile.write(json.dumps(obj).encode() + b"\n")
                    self.wfile.flush()
                    return True
                except (ConnectionError, OSError):
                    return False

            def _stream_tokens(self, req):
                """Pump ``req``'s stream to the socket as NDJSON lines
                until terminal: ``{"t": token}`` per token, ``{"m":
                b64(payload)}`` + ``{"done": {"state": "migrated"}}``
                when a migration sink fires, else a final ``{"done":
                {...}}``.  A vanished client cancels server-side."""
                from ml_trainer_tpu.serving import transfer

                while True:
                    try:
                        item = req._stream.get(timeout=600.0)
                    except queue.Empty:
                        server.cancel(req)
                        self._ndjson({"done": {
                            "state": "error",
                            "error": "serving engine unhealthy: stream "
                                     "stalled past 600s",
                        }})
                        return
                    if item == _DONE:
                        done = {"state": req.state}
                        if req.error is not None:
                            done["error"] = req.error
                        if req.retry_after is not None:
                            done["retry_after"] = req.retry_after
                        self._ndjson({"done": done})
                        return
                    if (isinstance(item, tuple) and len(item) == 2
                            and item[0] == _KV_MIGRATE):
                        payload = transfer.to_bytes(item[1])
                        if self._ndjson(
                            {"m": base64.b64encode(payload).decode()}
                        ):
                            self._ndjson({"done": {"state": "migrated"}})
                        return
                    if not self._ndjson({"t": int(item)}):
                        server.cancel(req)
                        return

            def _post_stream(self):
                """POST /v1/stream: the fleet's cross-process
                ``submit_request``.  The FIRST NDJSON line is the
                synchronous admission verdict (``accepted`` or a mapped
                structured refusal), then tokens stream."""
                from ml_trainer_tpu.serving.transfer import (
                    request_from_wire,
                )

                try:
                    n = int(self.headers.get("Content-Length", 0))
                    body = json.loads(self.rfile.read(n) or b"{}")
                    req = request_from_wire(body)
                except (KeyError, TypeError, ValueError,
                        json.JSONDecodeError) as e:
                    self._send(400, {"error": f"{type(e).__name__}: {e}"})
                    return
                if req.trace_ctx is None:
                    req.trace_ctx = _trace_ctx_header(self.headers)
                if body.get("migrate"):
                    # Prefill-and-export: the sink pushes the export
                    # into THIS stream, which ships it as an "m" line —
                    # the remote router adopts it elsewhere.
                    req.migration_sink = (
                        lambda r, exp: r._stream.put((_KV_MIGRATE, exp))
                    )
                wire_id = body.get("id", req.id)
                self._ndjson_start()
                try:
                    server.submit_request(req)
                except OverloadShed as e:
                    self._ndjson({"status": "shed", "error": str(e),
                                  "retry_after": e.retry_after})
                    return
                except AdmissionError as e:
                    self._ndjson({"status": "draining", "error": str(e)})
                    return
                except EngineUnhealthy as e:
                    self._ndjson({"status": "unhealthy",
                                  "error": str(e)})
                    return
                except RuntimeError as e:
                    self._ndjson({"status": "closed", "error": str(e)})
                    return
                server._register_wire(wire_id, req)
                try:
                    self._ndjson({"status": "accepted",
                                  "replica": server.name or None})
                    self._stream_tokens(req)
                finally:
                    server._forget_wire(wire_id)

            def _post_adopt(self):
                """POST /v1/adopt: the fleet's cross-process ``adopt``.
                The serialized ``KVSlotExport`` rides as the raw body
                (request identity in the ``X-Request-Meta`` header) and
                is CRC-VERIFIED HERE, at the receiving process; the
                first NDJSON line is the structured import verdict the
                remote router maps back into its fallback-candidate
                loop."""
                from ml_trainer_tpu.serving import transfer

                try:
                    meta = json.loads(
                        self.headers.get("X-Request-Meta", "{}")
                    )
                    n = int(self.headers.get("Content-Length", 0))
                    payload = self.rfile.read(n)
                    req = transfer.request_from_wire(meta)
                except (KeyError, TypeError, ValueError,
                        json.JSONDecodeError) as e:
                    self._send(400, {"error": f"{type(e).__name__}: {e}"})
                    return
                if req.trace_ctx is None:
                    req.trace_ctx = _trace_ctx_header(self.headers)
                self._ndjson_start()
                try:
                    export = transfer.from_bytes(payload, verify=True)
                except transfer.MigrationCorrupt as e:
                    self._ndjson({"status": "corrupt", "error": str(e)})
                    return
                resolved: queue.Queue = queue.Queue()
                try:
                    server.adopt(
                        req, export,
                        resolver=lambda s, d: resolved.put((s, d)),
                    )
                except AdmissionError as e:
                    self._ndjson({"status": "draining", "error": str(e)})
                    return
                except EngineUnhealthy as e:
                    self._ndjson({"status": "unhealthy",
                                  "error": str(e)})
                    return
                except RuntimeError as e:
                    self._ndjson({"status": "closed", "error": str(e)})
                    return
                wire_id = meta.get("id", req.id)
                server._register_wire(wire_id, req)
                try:
                    try:
                        status, detail = resolved.get(timeout=120.0)
                    except queue.Empty:
                        server.cancel(req)
                        self._ndjson({"status": "error",
                                      "error": "adoption timed out"})
                        return
                    if status != "adopted":
                        line = {"status": status}
                        if detail:
                            line["error"] = detail
                        self._ndjson(line)
                        return
                    self._ndjson({"status": "adopted"})
                    self._stream_tokens(req)
                finally:
                    server._forget_wire(wire_id)

            def _post_cancel(self):
                try:
                    n = int(self.headers.get("Content-Length", 0))
                    body = json.loads(self.rfile.read(n) or b"{}")
                    wire_id = int(body["id"])
                except (KeyError, TypeError, ValueError,
                        json.JSONDecodeError) as e:
                    self._send(400, {"error": f"{type(e).__name__}: {e}"})
                    return
                req = server._wire_streams.get(wire_id)
                if req is not None:
                    server.cancel(req)
                self._send(200, {"ok": req is not None})

            def _post_admin(self) -> bool:
                """Fleet control plane; True when the path matched."""
                try:
                    n = int(self.headers.get("Content-Length", 0))
                    body = json.loads(self.rfile.read(n) or b"{}")
                except (TypeError, ValueError, json.JSONDecodeError) as e:
                    self._send(400, {"error": f"{type(e).__name__}: {e}"})
                    return True
                path = self.path
                try:
                    if path == "/admin/role":
                        role = body["role"]
                        if role not in ("prefill", "decode", "both"):
                            raise ValueError(f"bad role {role!r}")
                        server.role = role
                        self._send(200, {"ok": True, "role": role})
                    elif path == "/admin/replica_index":
                        # Accept both key spellings: fleet.py's remote
                        # proxy historically posted "replica_index".
                        server.replica_index = int(
                            body["index"] if "index" in body
                            else body["replica_index"]
                        )
                        self._send(200, {"ok": True})
                    elif path == "/admin/degradation":
                        cfg = body.get("config")
                        server.set_degradation(
                            int(body.get("level", 0)),
                            DegradationConfig(**cfg) if cfg else None,
                        )
                        self._send(200, {"ok": True})
                    elif path == "/admin/shed_queued":
                        shed = server.shed_queued(
                            int(body.get("below_priority", 0)),
                            float(body.get("retry_after", 1.0)),
                            cause=str(body.get("cause", "overload")),
                        )
                        self._send(200, {"shed": shed})
                    elif path == "/admin/fail":
                        server._mark_unhealthy(
                            str(body.get("reason", "failed by admin"))
                        )
                        self._send(200, {"ok": True})
                    elif path == "/admin/faults":
                        # Arm a chaos plan in THIS process after spawn
                        # (resilience/faults.py spec syntax) — how the
                        # watchtower smoke injects replica_slow into a
                        # fleet worker once warmup is done.  An empty
                        # spec uninstalls.
                        from ml_trainer_tpu.resilience import faults

                        spec = str(body.get("spec", ""))
                        if spec:
                            faults.install(faults.FaultPlan.parse(spec))
                        else:
                            faults.uninstall()
                        self._send(200, {"ok": True, "spec": spec})
                    elif path == "/admin/evacuate":
                        # Stream-sink evacuation: each active slot's
                        # export rides its OWN open stream as an "m"
                        # line — the remote router's pumps adopt them.
                        ok = server.evacuate(
                            lambda req, exp: req._stream.put(
                                (_KV_MIGRATE, exp)
                            ),
                            timeout=float(body.get("timeout", 30.0)),
                        )
                        self._send(200, {"ok": ok})
                    elif path == "/admin/shutdown":
                        self._send(200, {"ok": True})
                        if getattr(server, "transport", "") == "http":
                            # A fleet worker process: exit outright
                            # once the response is on the wire.
                            def _die():
                                time.sleep(0.25)
                                os._exit(0)

                            threading.Thread(
                                target=_die, daemon=True
                            ).start()
                        threading.Thread(
                            target=server.close, daemon=True
                        ).start()
                    else:
                        return False
                except (KeyError, TypeError, ValueError) as e:
                    self._send(400, {"error": f"{type(e).__name__}: {e}"})
                return True

            def do_GET(self):
                if self.path == "/healthz":
                    payload = server.health()
                    # 503 while wedged/draining so load balancers stop
                    # routing here; the payload says why.
                    self._send(200 if payload["ok"] else 503, payload)
                elif self.path == "/v1/spec":
                    # Fleet geometry handshake (serving/fleet.py): what
                    # a RemoteServer proxy needs to stand in for the
                    # engine object, plus the process compile counter
                    # (the cross-process zero-recompile pin).
                    from ml_trainer_tpu.telemetry import compile_watch

                    eng = server.engine
                    self._send(200, {
                        "max_len": eng.max_len,
                        "vocab_size": eng.vocab_size,
                        "spec_k": eng.spec_k,
                        "kv_page_size": eng.kv_page_size,
                        "paged": eng.paged,
                        "prefill_chunk": eng.prefill_chunk,
                        "max_batch": eng.max_batch,
                        "max_queue": server.scheduler.max_queue,
                        "role": server.role,
                        "pid": os.getpid(),
                        "weights_fp": getattr(eng, "weights_fp", None),
                        "compiles": (
                            compile_watch.compile_count()
                            if compile_watch.installed() else None
                        ),
                    })
                elif self.path == "/metrics":
                    # Prometheus text exposition of the WHOLE process
                    # registry (trainer gauges included when co-resident),
                    # with the serving snapshot published fresh.
                    from ml_trainer_tpu.telemetry.registry import (
                        default_registry,
                    )

                    registry = default_registry()
                    server.metrics.publish(registry)
                    server.slo.publish(registry)
                    # Watchtower sampling rides the publish cadence: the
                    # scrape that reads the gauges also appends them to
                    # the history rings behind /dash.
                    server.watchtower.sample_registry(registry)
                    self._send_text(
                        200, registry.prometheus_text(),
                        "text/plain; version=0.0.4; charset=utf-8",
                    )
                elif self.path == "/metrics.json":
                    self._send(200, server.metrics.snapshot())
                elif self.path == "/trace":
                    # Fleet observability plane: this process's span
                    # buffer plus its clock identity — the router's
                    # save_fleet_trace() merges these into ONE
                    # clock-aligned Perfetto timeline with one lane per
                    # process.
                    self._send(200, spans.trace_payload(server.name))
                elif self.path == "/flight":
                    # The flight-recorder payload WITHOUT a local write:
                    # incident bundles pull a live worker's forensics
                    # over the wire.
                    self._send(
                        200, get_recorder().payload("fleet_fetch")
                    )
                elif self.path == "/slo":
                    # Structured SLO attainment (policy, per-tenant
                    # attainment + burn rate) — the JSON twin of the
                    # serving_slo_* series on /metrics.
                    self._send(200, server.slo.snapshot())
                elif self.path == "/dash":
                    # Watchtower live dashboard: the process's sampled
                    # series as self-contained HTML stat tiles +
                    # sparklines (stdlib only, no external assets).
                    from ml_trainer_tpu.telemetry.watchtower import (
                        render_dashboard,
                    )

                    self._send_text(
                        200,
                        render_dashboard(
                            server.watchtower,
                            title=server.name or server.role,
                        ),
                        "text/html; charset=utf-8",
                    )
                else:
                    self._send(404, {"error": "not found"})

            def do_POST(self):
                if self.path == "/admin/profile":
                    try:
                        n = int(self.headers.get("Content-Length", 0))
                        body = json.loads(self.rfile.read(n) or b"{}")
                        armed = server.engine._profiler.request(
                            int(body.get("steps", 10)),
                            body.get("logdir"),
                        )
                        self._send(
                            200 if armed else 409,
                            {"armed": armed,
                             "steps": int(body.get("steps", 10))},
                        )
                    except (TypeError, ValueError,
                            json.JSONDecodeError) as e:
                        self._send(
                            400, {"error": f"{type(e).__name__}: {e}"}
                        )
                    return
                if self.path == "/v1/stream":
                    self._post_stream()
                    return
                if self.path == "/v1/adopt":
                    self._post_adopt()
                    return
                if self.path == "/v1/cancel":
                    self._post_cancel()
                    return
                if self.path.startswith("/admin/") and self._post_admin():
                    return
                if self.path != "/v1/generate":
                    self._send(404, {"error": "not found"})
                    return
                try:
                    n = int(self.headers.get("Content-Length", 0))
                    body = json.loads(self.rfile.read(n) or b"{}")
                    deadline = body.get("deadline")
                    out = server.complete(
                        np.asarray(body["prompt"], np.int32),
                        int(body.get("max_new_tokens", 16)),
                        temperature=float(body.get("temperature", 0.0)),
                        rng=body.get("seed"),
                        eos_token_id=body.get("eos_token_id"),
                        deadline=deadline,
                        tenant=str(body.get("tenant", "default")),
                        priority=int(body.get("priority", 0)),
                        adapter=body.get("adapter"),
                        trace=_trace_ctx_header(self.headers),
                        # The HTTP wait is capped by the client's own
                        # deadline (plus engine slack): a deadline'd
                        # request gets its 504 near the deadline even
                        # when the engine misbehaves.
                        timeout=(
                            float(deadline) + 30.0
                            if deadline is not None else None
                        ),
                    )
                    self._send(200, {
                        "tokens": [int(t) for t in out],
                        "replica": server.name or None,
                    })
                except OverloadShed as e:
                    payload = {"error": str(e)}
                    if e.retry_after is not None:
                        payload["retry_after"] = e.retry_after
                    self._send(503, payload,
                               retry_after=e.retry_after)
                except AdmissionError as e:
                    self._send(429, {"error": str(e)})
                except EngineUnhealthy as e:
                    self._send(503, {"error": str(e)})
                except (DeadlineExceeded, TimeoutError) as e:
                    self._send(504, {"error": str(e)})
                except (KeyError, TypeError, ValueError,
                        json.JSONDecodeError) as e:
                    self._send(400, {"error": f"{type(e).__name__}: {e}"})
                except RuntimeError as e:
                    # Structured terminal errors (redistribution budget,
                    # engine give-ups) must reach the client as JSON,
                    # never a stdlib 500 HTML page.
                    self._send(503, {"error": str(e)})

        self._httpd = ThreadingHTTPServer((host, port), Handler)
        self._http_thread = threading.Thread(
            target=self._httpd.serve_forever, daemon=True,
            name="serving-http",
        )
        self._http_thread.start()
        return self._httpd.server_address
