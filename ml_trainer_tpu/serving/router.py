"""Disaggregated prefill/decode serving: a router over N engine replicas.

At production traffic, prefill (compute-bound, bursty) and decode
(memory-bandwidth-bound, steady) fight for the same chips; the
Gemma-on-TPU serving study (PAPERS.md, arXiv 2605.25645) argues the
economics favor splitting them onto role-specialized replicas.  This
module is that split, simulated multi-replica on CPU (each replica is a
full :class:`~ml_trainer_tpu.serving.api.Server` with its own engine,
scheduler, worker thread and optional HTTP front end — the in-process
analog of the mp_worker cluster harness):

* **Roles.**  Every replica advertises ``role`` (``prefill`` /
  ``decode`` / ``both``) on its ``/healthz``.  In DISAGGREGATED mode a
  request prefills on a prefill replica — whose slots turn over in one
  prefill's time, so TTFT stops queueing behind other requests' decode
  residency — then its KV migrates at page granularity
  (serving/transfer.py) to a decode replica that carries the stream to
  completion.  In COLOCATED mode (every replica ``both``) the same
  router serves the same traffic with no migration, so the two modes
  compare at an equal replica count.

* **Placement.**  Prefill placement is tenant-affinity-aware:
  consistent hashing (a vnode ring) on ``tenant + the prompt's first
  KV block``, so requests sharing a system prompt land on the same
  prefill replica and its radix prefix cache keeps its hit rate after
  the split.  Decode placement is least-loaded over live ``/healthz``
  data (``queue_depth``, ``active_slots``, ``kv_pages_free``), with
  SESSION STICKINESS: a ``session`` key pins a multi-turn stream to one
  decode replica until that replica dies.

* **Migration.**  The prefill replica emits the request's first token,
  exports the slot's refcounted pages + page-table row (bit-for-bit,
  trash-padded to a static shape so migration never mints compiles),
  releases the slot with the usual prefix-cache donation, and the
  router adopts the request into the decode replica — which scatters
  the pages in, re-donates the migrated blocks to ITS prefix cache, and
  continues the stream byte-identically (tests/test_router.py pins
  greedy and spec_k continuations against never-migrated runs).

* **Failure semantics.**  A health poller consumes every replica's
  ``/healthz``; a replica that dies (watchdog trip, engine-thread
  death, kill) fails its in-flight requests with structured errors,
  and the router REDISTRIBUTES them: each request resubmits on a
  surviving replica with its committed tokens as a resumable prefix —
  exactly the preemption-requeue resume, so redistributed streams stay
  byte-identical.  Requests that exhaust ``max_redistributes`` (and
  engine-side ``max_preemptions`` give-ups) surface as structured
  client errors; nothing ever hangs.

Telemetry rides the process registry: ``router_requests_total{role=,
replica=}``, ``router_kv_migrated_bytes_total``,
``router_replica_healthy{replica=}``, ``router_migrations_total``,
``router_redistributes_total``, plus per-replica SLO attainment
(``router_replica_slo_attainment{slo=,replica=}``) through each
replica's existing SloTracker, and the router's own request-level SLO
accounting on ``/slo``.
"""

from __future__ import annotations

import bisect
import dataclasses
import hashlib
import json
import os
import queue as _queue
import tempfile
import threading
import time
import urllib.error
import urllib.request
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ml_trainer_tpu.serving import transfer
from ml_trainer_tpu.serving.api import (
    Server,
    TokenStream,
    _trace_ctx_header,
)
from ml_trainer_tpu.serving.overload import (
    CircuitBreaker,
    DegradationConfig,
    DegradationLadder,
    OverloadShed,
    RollingQuantile,
)
from ml_trainer_tpu.serving.scheduler import (
    AdmissionError,
    EngineUnhealthy,
    Request,
    _DONE,
)
from ml_trainer_tpu.serving.slo import SloPolicy, SloTracker
from ml_trainer_tpu.serving.transfer import MigrationCorrupt
from ml_trainer_tpu.telemetry import federation, spans
from ml_trainer_tpu.telemetry.alerts import AlertEngine, AlertRule
from ml_trainer_tpu.telemetry.flight import get_recorder
from ml_trainer_tpu.telemetry.watchtower import (
    TimeSeriesStore,
    render_dashboard,
)
from ml_trainer_tpu.utils.logging import get_logger

# Stream sentinel kind the migration sink pushes between tokens: the
# request's pump adopts the export into the decode replica when it
# drains this item (tokens are plain ints, _DONE is ("done", None)).
_MIGRATE = "__kv_migrate__"

# Incident bundles (save_incident_bundle) land under this directory
# when no explicit ``incident_dir`` was configured; the flight-dump
# env var is a separate knob on purpose — a bundle COLLECTS flight
# dumps, it is not one.
INCIDENT_DIR_ENV = "ML_TRAINER_TPU_INCIDENT_DIR"


class Replica:
    """One engine replica behind the router: the in-process ``Server``
    plus its routing state (role, last health payload, liveness)."""

    def __init__(self, name: str, server: Server,
                 url: Optional[str] = None,
                 breaker: Optional[CircuitBreaker] = None,
                 generation: int = 0):
        self.name = name
        self.server = server
        self.url = url
        self.role = server.role
        # Deploy generation (serving/deploy.py): which weights wave this
        # replica belongs to.  Placement never mixes generations within
        # one stream — KV is not portable across weights — and the
        # canary traffic split selects the pool by generation.
        self.generation = int(generation)
        self.weights_fp = getattr(
            getattr(server, "engine", None), "weights_fp", None
        )
        self.healthy = True
        self.last_health: dict = {}
        # Placements since the last health refresh: the health payload
        # is a quarter-second stale under burst arrivals, so without
        # this every tie lands on the same replica until the next poll.
        self.pending = 0
        # Client-path hardening (serving/overload.py): the per-replica
        # circuit breaker (K consecutive failures open it — the router
        # stops placing here without waiting for the poller), the
        # consecutive-failed-poll counter behind flap damping, and the
        # drain latch a scale-down/role-flip sets while it empties.
        self.breaker = breaker if breaker is not None else CircuitBreaker()
        self.fail_polls = 0
        self.removing = False
        # Fleet observability plane: the replica's latest raw /metrics
        # exposition (the federation re-exports it with replica labels),
        # when it was scraped, and the per-process clock estimates the
        # trace merge aligns lanes with (telemetry/federation.py):
        # the exact monotonic-epoch shift and the NTP-style handshake
        # estimate (min-rtt filtered across health polls).
        self.metrics_text: Optional[str] = None
        self.metrics_scraped_at = 0.0
        self.epoch_shift_us: Optional[float] = None
        self.ntp_shift_us: Optional[float] = None
        self.ntp_rtt_us: Optional[float] = None

    def _note_clock(self, payload: dict, t0_us: float,
                    t1_us: float) -> None:
        """Clock handshake piggybacked on a health fetch: ``payload``
        carries the worker's trace-clock "now" and monotonic epoch
        (api.py health() via spans.clock_payload()); ``t0/t1`` bracket
        the HTTP round-trip on the ROUTER's trace clock."""
        worker_now = payload.get("trace_now_us")
        if worker_now is None:
            return
        rtt = t1_us - t0_us
        # NTP-style: the worker's reading maps to the bracket midpoint,
        # error <= rtt/2.  Keep the tightest bracket seen (min-rtt
        # filter) — a scheduling hiccup must not loosen the estimate.
        if self.ntp_rtt_us is None or rtt <= self.ntp_rtt_us:
            self.ntp_shift_us = (t0_us + t1_us) / 2.0 - float(worker_now)
            self.ntp_rtt_us = rtt
        mono_epoch = payload.get("mono_epoch")
        if mono_epoch is not None:
            # Exact when time.monotonic() is system-wide (CLOCK_MONOTONIC
            # on Linux): worker ts + this = ts on the router's clock.
            self.epoch_shift_us = (
                float(mono_epoch) - spans._MONO_EPOCH
            ) * 1e6

    def fetch_health(self, timeout: float = 2.0) -> dict:
        """The replica's ``/healthz`` payload — over HTTP when the
        replica exposes a front end (a 503 still carries the payload),
        else the in-process snapshot."""
        if self.url:
            t0 = spans._now_us()
            try:
                with urllib.request.urlopen(
                    f"{self.url}/healthz", timeout=timeout
                ) as resp:
                    payload = json.loads(resp.read())
                self._note_clock(payload, t0, spans._now_us())
                return payload
            except urllib.error.HTTPError as e:
                try:
                    payload = json.loads(e.read())
                    self._note_clock(payload, t0, spans._now_us())
                    return payload
                except Exception:
                    return {"ok": False, "healthy": False,
                            "reason": f"healthz HTTP {e.code}"}
            except Exception as e:
                return {"ok": False, "healthy": False,
                        "reason": f"healthz unreachable: {e}"}
        return self.server.health()

    def fetch_metrics_text(self, timeout: float = 2.0) -> Optional[str]:
        """Raw ``/metrics`` exposition over HTTP; None for in-process
        replicas (they share the router's registry already — federating
        them would double every series).  Raises on an unreachable
        process — the poller turns that into a scrape-error counter."""
        if not self.url:
            return None
        with urllib.request.urlopen(
            f"{self.url}/metrics", timeout=timeout
        ) as resp:
            return resp.read().decode("utf-8", errors="replace")

    def fetch_trace(self, timeout: float = 5.0) -> Optional[dict]:
        """The replica's ``GET /trace`` payload (span buffer + clock
        identity); None for in-process replicas (their spans are
        already in the router's own buffer)."""
        if not self.url:
            return None
        with urllib.request.urlopen(
            f"{self.url}/trace", timeout=timeout
        ) as resp:
            return json.loads(resp.read())

    def fetch_flight(self, timeout: float = 5.0) -> Optional[dict]:
        """The replica's live flight-recorder payload (``GET /flight``);
        None for in-process replicas (one process, one recorder — the
        router's own dump already has it)."""
        if not self.url:
            return None
        with urllib.request.urlopen(
            f"{self.url}/flight", timeout=timeout
        ) as resp:
            return json.loads(resp.read())

    def placeable(self) -> bool:
        """In the placement pool at all: alive, not draining for a
        scale-down/role-flip, and the breaker is not OPEN.  The
        half-open single-probe admission is enforced separately
        (``try_place`` consumes the probe slot)."""
        from ml_trainer_tpu.serving import overload

        return (
            self.healthy and not self.removing
            and self.breaker.state != overload.OPEN
        )

    def try_place(self) -> bool:
        """May a request land here RIGHT NOW — placeable, and if the
        breaker is half-open, this caller won the single probe slot."""
        return self.placeable() and self.breaker.allow()

    def load_score(self) -> tuple:
        """Least-loaded ordering key from the last health payload:
        occupied slots + queued + pending adoptions first, freest KV
        pool as the tie-break, name for determinism."""
        h = self.last_health or {}
        depth = (
            int(h.get("active_slots") or 0)
            + int(h.get("queue_depth") or 0)
            + int(h.get("adoptions_pending") or 0)
            + self.pending
        )
        return (depth, -(int(h.get("kv_pages_free") or 0)), self.name)


class _HashRing:
    """Consistent hashing with virtual nodes (sha1): the affinity key
    maps to the first clockwise vnode whose replica is alive, so a
    replica loss only remaps its own arc."""

    def __init__(self, names: Sequence[str], vnodes: int = 64):
        self._points: List[Tuple[int, str]] = sorted(
            (self._hash(f"{name}#{i}".encode()), name)
            for name in names for i in range(vnodes)
        )

    @staticmethod
    def _hash(key: bytes) -> int:
        return int(hashlib.sha1(key).hexdigest()[:16], 16)

    def place(self, key: bytes, alive) -> Optional[str]:
        if not self._points:
            return None
        h = self._hash(key)
        start = bisect.bisect_right(self._points, (h, ""))
        n = len(self._points)
        for i in range(n):
            name = self._points[(start + i) % n][1]
            if name in alive:
                return name
        return None


class RouterMetrics:
    """Thread-safe router counters (published as ``router_*`` series)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.requests_total: Dict[Tuple[str, str], int] = {}
        self.migrations_total = 0
        self.kv_migrated_bytes_total = 0
        self.redistributes_total = 0
        self.errors_total = 0
        self.replica_healthy: Dict[str, int] = {}
        # Overload/failure hardening counters (serving/overload.py,
        # docs/serving.md "Surviving overload"): hedged prefills fired
        # and won, CRC-rejected migration payloads, requests the ladder
        # shed at the router, and damped (absorbed) health-poll flaps.
        self.hedges_total = 0
        self.hedge_wins_total = 0
        self.migrations_corrupt_total = 0
        self.shed_total = 0
        self.flaps_damped_total = 0
        # Fleet plane: federation scrapes that failed (per replica) and
        # incident bundles assembled.
        self.scrape_errors_total: Dict[str, int] = {}
        self.incidents_total = 0

    def record_request(self, replica: str, role: str) -> None:
        with self._lock:
            key = (role, replica)
            self.requests_total[key] = self.requests_total.get(key, 0) + 1

    def record_migration(self, nbytes: int) -> None:
        with self._lock:
            self.migrations_total += 1
            self.kv_migrated_bytes_total += int(nbytes)

    def record_redistribute(self) -> None:
        with self._lock:
            self.redistributes_total += 1

    def record_hedge(self) -> None:
        with self._lock:
            self.hedges_total += 1

    def record_hedge_win(self) -> None:
        with self._lock:
            self.hedge_wins_total += 1

    def record_corrupt_migration(self) -> None:
        with self._lock:
            self.migrations_corrupt_total += 1

    def record_shed(self) -> None:
        with self._lock:
            self.shed_total += 1

    def record_flap_damped(self) -> None:
        with self._lock:
            self.flaps_damped_total += 1

    def record_scrape_error(self, replica: str) -> None:
        with self._lock:
            self.scrape_errors_total[replica] = (
                self.scrape_errors_total.get(replica, 0) + 1
            )

    def record_incident(self) -> None:
        with self._lock:
            self.incidents_total += 1

    def record_error(self) -> None:
        with self._lock:
            self.errors_total += 1

    def set_replica_health(self, name: str, ok: bool) -> None:
        with self._lock:
            self.replica_healthy[name] = int(ok)

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "requests_total": {
                    f"{role}/{rep}": n
                    for (role, rep), n in sorted(self.requests_total.items())
                },
                "migrations_total": self.migrations_total,
                "kv_migrated_bytes_total": self.kv_migrated_bytes_total,
                "redistributes_total": self.redistributes_total,
                "hedges_total": self.hedges_total,
                "hedge_wins_total": self.hedge_wins_total,
                "migrations_corrupt_total": self.migrations_corrupt_total,
                "shed_total": self.shed_total,
                "flaps_damped_total": self.flaps_damped_total,
                "scrape_errors_total": dict(sorted(
                    self.scrape_errors_total.items()
                )),
                "incidents_total": self.incidents_total,
                "errors_total": self.errors_total,
                "replica_healthy": dict(sorted(
                    self.replica_healthy.items()
                )),
            }


class Router:
    """The multi-replica front end: role-aware placement, KV migration,
    session stickiness, health polling, drain-and-redistribute.  Use as
    a context manager; ``Router.build`` constructs the replica fleet
    in-process."""

    def __init__(self, replicas: Dict[str, Server], *,
                 replica_urls: Optional[Dict[str, str]] = None,
                 max_redistributes: int = 8,
                 health_interval: float = 0.25,
                 admission_retry_s: float = 10.0,
                 max_inflight: Optional[int] = None,
                 slo: Optional[SloPolicy] = None,
                 slo_timelines: int = 256,
                 own_servers: bool = False,
                 unhealthy_after: int = 2,
                 breaker_threshold: Optional[int] = 3,
                 breaker_cooldown_s: float = 2.0,
                 hedging: bool = True,
                 hedge_quantile: float = 0.99,
                 hedge_factor: float = 1.5,
                 hedge_min_s: float = 0.05,
                 degradation: Optional[DegradationConfig] = None,
                 metrics_scrape_interval: float = 1.0,
                 incident_dir: Optional[str] = None,
                 incident_min_interval_s: float = 30.0,
                 alert_rules: Optional[Sequence[AlertRule]] = None):
        """Hardening knobs (docs/serving.md "Surviving overload"):

        ``unhealthy_after``: consecutive FAILED health polls before a
        replica is marked unhealthy (flap damping — one transient
        timeout must not trigger a spurious drain-and-redistribute).
        ``breaker_threshold``/``breaker_cooldown_s``: per-replica
        circuit breakers — K consecutive placement failures open the
        breaker without waiting for the poller; after the cooldown one
        half-open probe decides.  ``breaker_threshold=None`` disables
        breakers (chaos baselines).  ``hedging``: fire a duplicate
        prefill on another replica once a request has waited past
        ``hedge_factor`` x the rolling ``hedge_quantile`` first-result
        latency (floored at ``hedge_min_s``); first winner cancels the
        loser.  Only deterministic requests hedge (greedy, or sampled
        with an explicit seed — the duplicate then computes identical
        bytes, so the race cannot change the output).  ``degradation``
        configures the router's :class:`DegradationLadder`
        (``router.ladder``) applied fleet-wide.

        Fleet observability plane (docs/observability.md "Fleet
        plane"): ``metrics_scrape_interval`` paces the health poller's
        piggybacked ``/metrics`` scrape per replica (the federated
        exposition re-exports the latest snapshot);
        ``incident_dir``/``incident_min_interval_s`` place and throttle
        the ``incident_<ts>/`` bundles assembled on watchdog trips,
        replica deaths, deploy rollbacks and autoscaler repairs."""
        if not replicas:
            raise ValueError("router needs at least one replica")
        if unhealthy_after < 1:
            raise ValueError(
                f"unhealthy_after must be >= 1, got {unhealthy_after}"
            )
        urls = replica_urls or {}
        self.breaker_threshold = breaker_threshold
        self.breaker_cooldown_s = float(breaker_cooldown_s)
        self._replicas: Dict[str, Replica] = {
            name: Replica(name, srv, urls.get(name),
                          breaker=self._new_breaker())
            for name, srv in sorted(replicas.items())
        }
        roles = {r.role for r in self._replicas.values()}
        self.mode = "colocated" if roles == {"both"} else "disagg"
        engines = [r.server.engine for r in self._replicas.values()]
        e0 = engines[0]
        for name, rep in self._replicas.items():
            self._validate_geometry(name, rep.server)
        self.max_len = e0.max_len
        self.vocab_size = e0.vocab_size
        self._spec_slack = max(e.spec_k for e in engines)
        self._affinity_block = max(
            e0.kv_page_size, 1
        ) if e0.paged else 16
        self.max_redistributes = int(max_redistributes)
        self.admission_retry_s = float(admission_retry_s)
        self.max_inflight = (
            int(max_inflight) if max_inflight is not None
            else sum(
                r.server.scheduler.max_queue + r.server.engine.max_batch
                for r in self._replicas.values()
            )
        )
        self._own_servers = own_servers
        self.metrics = RouterMetrics()
        self.slo = SloTracker(policy=slo, keep_timelines=slo_timelines)
        self._log = get_logger("ml_trainer_tpu.serving.router")
        self._lock = threading.Lock()
        self._sessions: Dict[str, str] = {}
        self._inflight = 0
        # Deploy state (serving/deploy.py): the generation whose
        # replicas serve default traffic, the in-flight deployment's
        # target generation + tenant-hash fraction, an optional
        # finished-request tap (shadow replay sampling), and the fleet
        # launcher when one built this router (Router.deploy uses its
        # checkpoint-loading factory).
        self._serving_generation = 0
        self._deploy_generation: Optional[int] = None
        self._deploy_fraction = 0.0
        self._request_tap = None
        self.fleet = None
        self._stopping = False
        self._stop_event = threading.Event()
        self._httpd = None
        self._http_thread = None
        self.unhealthy_after = int(unhealthy_after)
        self.hedging = bool(hedging)
        self.hedge_quantile = float(hedge_quantile)
        self.hedge_factor = float(hedge_factor)
        self.hedge_min_s = float(hedge_min_s)
        # Rolling first-result latency (submit-attempt -> first token
        # or migration): the hedging clock.  Under overload the window
        # inflates with the queues, so hedges back off exactly when
        # duplicates would hurt most.
        self._first_result_lat = RollingQuantile(
            window=256, min_samples=8, default=1.0
        )
        # Fleet-wide degradation ladder: rungs apply to every replica
        # (current AND later-added) via Server.set_degradation.
        self.ladder = DegradationLadder(
            lambda: [r.server for r in self._replicas.values()],
            config=degradation, name="router",
        )
        # Fleet observability plane state: scrape pacing, incident
        # bundle placement + rate limit (one storm, one bundle).
        self.metrics_scrape_interval = float(metrics_scrape_interval)
        self.incident_dir = incident_dir
        self.incident_min_interval_s = float(incident_min_interval_s)
        self._incident_lock = threading.Lock()
        self._last_incident_at = 0.0
        self.last_incident_path: Optional[str] = None
        # Watchtower (telemetry/watchtower.py + alerts.py): the fleet
        # TSDB — every scraped worker exposition lands here with its
        # federation labels, beside the router's own registry sweep —
        # and the declarative alert engine evaluated on each poll tick.
        # Severity-`page` rules fire straight into trigger_incident, so
        # a rule firing assembles the same bundle a replica death does.
        self.watchtower = TimeSeriesStore()
        self.alerts = AlertEngine(
            alert_rules or (), store=self.watchtower,
            incident_trigger=self.trigger_incident,
        )
        self._wt_ingested: Dict[str, float] = {}
        self._wt_sampled_at = 0.0
        self._reindex_replicas()
        self._rebuild_ring()
        self._busy_polls = 0
        for rep in self._replicas.values():
            rep.last_health = rep.fetch_health()
            self.metrics.set_replica_health(rep.name, True)
        self._health_interval = float(health_interval)
        self._poller = threading.Thread(
            target=self._poll_health, daemon=True, name="router-health"
        )
        self._poller.start()

    def _new_breaker(self) -> CircuitBreaker:
        """A breaker per the router's config; threshold None = breakers
        disabled (a breaker that never opens)."""
        if self.breaker_threshold is None:
            return CircuitBreaker(threshold=10 ** 9, cooldown_s=1.0)
        return CircuitBreaker(
            threshold=self.breaker_threshold,
            cooldown_s=self.breaker_cooldown_s,
        )

    def _validate_geometry(self, name: str, server: Server) -> None:
        """One replica's engine against the fleet's reference geometry
        (the first replica's) — shared by __init__ and add_replica."""
        engines = [r.server.engine for r in self._replicas.values()]
        e0, e = engines[0], server.engine
        if e.max_len != e0.max_len or e.vocab_size != e0.vocab_size:
            raise ValueError(
                "replicas must share model geometry: got max_len "
                f"{e.max_len} vs {e0.max_len}, vocab {e.vocab_size} "
                f"vs {e0.vocab_size}"
            )
        if self.mode == "disagg":
            if not e.paged:
                raise ValueError(
                    f"disaggregated mode needs paged engines "
                    f"(kv_page_size > 0): replica '{name}' is "
                    "contiguous — pages are the migration unit"
                )
            if e.kv_page_size != e0.kv_page_size:
                raise ValueError(
                    "replicas must share kv_page_size for migration"
                )

    def _reindex_replicas(self) -> None:
        """Stable fleet indices (sorted-name order) — what the chaos
        faults' ``host=`` parameter names."""
        for i, name in enumerate(sorted(self._replicas)):
            self._replicas[name].server.replica_index = i

    def _rebuild_ring(self) -> None:
        prefill_names = [
            n for n, r in self._replicas.items()
            if r.role in ("prefill", "both")
        ] or list(self._replicas)
        self._ring = _HashRing(prefill_names)

    # -- construction -----------------------------------------------------

    @classmethod
    def build(cls, model, variables: dict, roles: Sequence[str],
              max_batch: int = 4, kv_page_size: int = 16,
              router_kwargs: Optional[dict] = None,
              **server_kwargs) -> "Router":
        """Build an in-process replica fleet: one ``Server`` per entry
        of ``roles`` (named ``prefill0``/``decode0``/``rep0``...), all
        sharing ``model``/``variables`` (and therefore the process
        compile cache), plus the router in front.  The router OWNS the
        servers — ``close()`` closes them."""
        counts: Dict[str, int] = {}
        replicas: Dict[str, Server] = {}
        for role in roles:
            stem = {"prefill": "prefill", "decode": "decode"}.get(
                role, "rep"
            )
            i = counts.get(stem, 0)
            counts[stem] = i + 1
            replicas[f"{stem}{i}"] = Server(
                model, variables, max_batch=max_batch,
                kv_page_size=kv_page_size, role=role, **server_kwargs
            )
        return cls(replicas, own_servers=True, **(router_kwargs or {}))

    def replica(self, name: str) -> Replica:
        return self._replicas[name]

    @property
    def replicas(self) -> Dict[str, Replica]:
        return dict(self._replicas)

    # -- client surface ---------------------------------------------------

    def submit(self, prompt, max_new_tokens: int,
               temperature: float = 0.0, rng=None,
               eos_token_id: Optional[int] = None,
               deadline: Optional[float] = None,
               tenant: str = "default", priority: int = 0,
               session: Optional[str] = None,
               adapter: Optional[str] = None,
               trace: Optional[dict] = None) -> TokenStream:
        """Route one request (thread-safe).  The returned stream is the
        same surface ``Server.submit`` gives — tokens arrive as the
        serving replicas produce them, across migration and
        redistribution transparently.  ``session`` pins the request's
        decode to a sticky replica for multi-turn streams; ``adapter``
        names the LoRA adapter (the affinity hash includes it, so
        same-adapter traffic lands where the adapter is resident);
        ``trace`` is an inbound trace context (``X-Trace-Context``) —
        absent one, the router originates the context itself, so every
        request's cross-process spans share one trace id."""
        if self._stopping:
            raise RuntimeError("router is closed")
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("prompt must be a non-empty 1-D token array")
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}"
            )
        if prompt.size + max_new_tokens + self._spec_slack > self.max_len:
            raise ValueError(
                f"prompt ({prompt.size}) + new tokens ({max_new_tokens}) "
                f"exceeds the fleet's max_len ({self.max_len})"
            )
        if eos_token_id is not None and not (
            0 <= eos_token_id < self.vocab_size
        ):
            raise ValueError(
                f"eos_token_id must be in [0, {self.vocab_size}), got "
                f"{eos_token_id}"
            )
        if not isinstance(tenant, str) or not tenant:
            raise ValueError(
                f"tenant must be a non-empty string, got {tenant!r}"
            )
        with self._lock:
            if self._inflight >= self.max_inflight:
                raise AdmissionError(
                    f"router at its in-flight watermark "
                    f"({self.max_inflight}); request rejected"
                )
            self._inflight += 1
        if adapter is not None and (
            not isinstance(adapter, str) or not adapter
        ):
            raise ValueError(
                f"adapter must be a non-empty string or None, got "
                f"{adapter!r}"
            )
        creq = Request(
            prompt=prompt, max_new_tokens=int(max_new_tokens),
            temperature=float(temperature), rng=rng,
            eos_token_id=eos_token_id, deadline=deadline,
            tenant=tenant, priority=int(priority), adapter=adapter,
        )
        # Trace origin: the router's creq id is the fleet-wide trace id
        # unless the client already carries one — every shadow attempt,
        # migration hop and adoption stamps its spans with this context.
        ctx = dict(trace) if trace else {}
        ctx.setdefault("trace_id", creq.id)
        ctx.setdefault("origin_pid", os.getpid())
        creq.trace_ctx = ctx
        creq.observer = self.slo.observe
        self.slo.track(creq)
        threading.Thread(
            target=self._run_request, args=(creq, session), daemon=True,
            name=f"router-req-{creq.id}",
        ).start()
        return TokenStream(creq, prompt)

    def complete(self, prompt, max_new_tokens: int,
                 timeout: Optional[float] = None, **kwargs) -> np.ndarray:
        """Blocking one-shot through the router."""
        return self.submit(prompt, max_new_tokens, **kwargs).result(
            timeout=timeout
        )

    @staticmethod
    def _serving_replica(creq: Request) -> Optional[str]:
        """The replica that carried (or is carrying) the DECODE of this
        request — the most recent migration/adoption/placement mark on
        its event log; None before placement."""
        for ev in reversed(creq.events):
            kind = ev.get("event")
            if kind in ("kv_migrated", "evac_adopted"):
                return ev.get("to")
            if kind == "routed":
                return ev.get("decode")
        return None

    def kill_replica(self, name: str) -> None:
        """Kill a replica (tests/chaos): the replica fails its
        in-flight work with structured errors — which the router
        redistributes — and leaves the placement pool.  Against a fleet
        process (serving/fleet.py, ``kill_process``) this is a REAL
        ``SIGKILL`` — no goodbye, streams sever mid-flight; in-process
        replicas are marked unhealthy instead (the simulation)."""
        rep = self._replicas[name]
        rep.healthy = False
        self.metrics.set_replica_health(name, False)
        kill = getattr(rep.server, "kill_process", None)
        if kill is not None:
            kill()
        rep.server._mark_unhealthy(f"replica '{name}' killed")
        self.trigger_incident(f"replica_killed: {name}", dead=(name,))

    # -- fleet management (serving/autoscaler.py) -------------------------

    def add_replica(self, name: str, server: Server,
                    url: Optional[str] = None,
                    generation: Optional[int] = None) -> None:
        """Grow the fleet by one replica (thread-safe; the autoscaler's
        scale-up action).  The new replica inherits the fleet's current
        degradation rung, joins the affinity ring/placement pools, and
        shares the process compile cache — adding capacity under load
        mints no compiles when the geometry matches (enforced).
        ``generation`` defaults to the serving generation, so autoscaler
        scale-ups/repairs during a deploy grow the STABLE fleet; the
        deploy machinery passes its target generation explicitly."""
        if name in self._replicas:
            raise ValueError(f"replica '{name}' already exists")
        if server.role not in ("prefill", "decode", "both"):
            raise ValueError(f"bad role {server.role!r}")
        if self.mode == "colocated" and server.role != "both":
            raise ValueError(
                "a colocated fleet only takes role='both' replicas"
            )
        self._validate_geometry(name, server)
        if url is None:
            # A fleet RemoteServer (serving/fleet.py) carries its own
            # base URL — the autoscaler's factory path adds replicas
            # without threading one through.
            url = getattr(server, "url", None)
        if generation is None:
            generation = self._serving_generation
        rep = Replica(name, server, url, breaker=self._new_breaker(),
                      generation=generation)
        server.set_degradation(self.ladder.level, self.ladder.config)
        rep.last_health = rep.fetch_health()
        with self._lock:
            self._replicas = {
                **self._replicas, name: rep,
            }
        self._reindex_replicas()
        self._rebuild_ring()
        self.metrics.set_replica_health(name, True)
        from ml_trainer_tpu.telemetry.flight import get_recorder

        get_recorder().record(
            "fleet_change", action="add_replica", replica=name,
            role=server.role, fleet=len(self._replicas),
            generation=generation,
        )
        self._log.info(
            "router_replica_added", replica=name, role=server.role
        )

    def remove_replica(self, name: str, timeout: float = 30.0,
                       close: Optional[bool] = None) -> bool:
        """Shrink the fleet by one replica (the autoscaler's scale-down
        action): stop placing work on it, wait for it to drain
        naturally (bounded by ``timeout``), then detach it (closing its
        server when the router owns the fleet, or when ``close=True``).
        Returns True when the replica drained clean; a False return
        means in-flight work was failed-and-redistributed at detach —
        clients still finish via the redistribute path."""
        rep = self._replicas[name]
        rep.removing = True  # leaves every placement pool immediately
        deadline = time.monotonic() + timeout
        drained = False
        while time.monotonic() < deadline and not self._stopping:
            h = rep.server.health() if not rep.url else rep.fetch_health()
            if (
                not h.get("active_slots")
                and not h.get("queue_depth")
                and not h.get("adoptions_pending")
            ):
                drained = True
                break
            self._stop_event.wait(0.05)
        with self._lock:
            reps = dict(self._replicas)
            reps.pop(name, None)
            self._replicas = reps
            self._sessions = {
                s: n for s, n in self._sessions.items() if n != name
            }
        self._reindex_replicas()
        self._rebuild_ring()
        if not drained:
            # Detaching with work in flight: fail it structured so the
            # pumps redistribute — never strand a stream.
            rep.server._mark_unhealthy(
                f"replica '{name}' removed by the autoscaler"
            )
        if close if close is not None else self._own_servers:
            rep.server.close()
        self.metrics.set_replica_health(name, False)
        from ml_trainer_tpu.telemetry.flight import get_recorder

        get_recorder().record(
            "fleet_change", action="remove_replica", replica=name,
            drained=drained, fleet=len(self._replicas),
        )
        self._log.info(
            "router_replica_removed", replica=name, drained=drained
        )
        return drained

    def reassign_role(self, name: str, role: str,
                      timeout: float = 30.0) -> bool:
        """Flip a replica's role prefill<->decode (the autoscaler's
        rebalance action) by DRAINING it through the PR 13 migration
        machinery first: the replica leaves the placement pools, its
        active slots' KV is exported page-granular and adopted onto
        other decode replicas (streams keep flowing — no re-prefill),
        its queued requests redistribute, and only then does the role
        flip and the affinity ring rebuild.  Returns True on success;
        False when the drain timed out (role unchanged, replica back in
        its old pools — a flip must never half-happen)."""
        if role not in ("prefill", "decode"):
            raise ValueError(
                f"role must be 'prefill' or 'decode', got {role!r}"
            )
        if self.mode != "disagg":
            raise ValueError("role reassignment needs a disagg fleet")
        rep = self._replicas[name]
        if rep.role == role:
            return True
        rep.removing = True
        evacuated = rep.server.evacuate(
            lambda req, export: self._adopt_evacuated(req, export, rep),
            timeout=timeout,
        )
        if not evacuated:
            rep.removing = False
            self._log.error(
                "router_role_flip_timeout", replica=name, role=role
            )
            return False
        rep.role = role
        rep.server.role = role
        rep.removing = False
        with self._lock:
            self._sessions = {
                s: n for s, n in self._sessions.items() if n != name
            }
        self._rebuild_ring()
        from ml_trainer_tpu.telemetry.flight import get_recorder

        get_recorder().record(
            "fleet_change", action="reassign_role", replica=name,
            role=role,
        )
        self._log.info(
            "router_role_reassigned", replica=name, role=role
        )
        return True

    def deploy(self, ckpt: str, canary: float = 0.05,
               shadow: bool = False, *, factory=None, config=None):
        """Roll the fleet onto new base weights under live traffic
        (serving/deploy.py, docs/serving.md "Deploys"): spawn
        new-generation replicas from the ``ckpt`` export (sharing the
        fleet's on-disk compile cache — no recompile storm), route the
        deterministic tenant-hash slice ``[0, canary)`` at them, watch
        the canary slice's SLO burn, and either ramp 5% -> 50% -> 100%
        and retire the old generation, or auto-roll-back through the
        drain/evacuate machinery with zero dropped streams.  With
        ``shadow=True`` a sampled fraction of live requests is replayed
        against the new replicas OFF the serving path and diffed into
        ``Deployment.shadow_report()`` before any real traffic moves.

        ``factory`` (role -> server) defaults to the attached fleet's
        checkpoint-loading factory (``Fleet.make_router`` wires
        ``router.fleet``); in-process callers pass their own.  Returns
        the started :class:`~ml_trainer_tpu.serving.deploy.Deployment`
        — ``wait()`` for the verdict, ``close()`` to stop watching."""
        from ml_trainer_tpu.serving.deploy import DeployConfig, Deployment

        active = getattr(self, "_deployment", None)
        if active is not None and not active.finished():
            raise RuntimeError(
                f"a deployment is already {active.state}; wait for it "
                "or close() it before starting another"
            )
        if factory is None:
            if self.fleet is None:
                raise ValueError(
                    "Router.deploy needs a server factory: attach a "
                    "Fleet (Fleet.make_router) or pass factory="
                )
            factory = self.fleet.deploy_factory(ckpt)
        cfg = config if config is not None else DeployConfig()
        if canary is not None:
            cfg = dataclasses.replace(cfg, canary=float(canary))
        if shadow:
            cfg = dataclasses.replace(cfg, shadow=True)
        self._deployment = Deployment(self, ckpt, factory, config=cfg)
        return self._deployment.start()

    def _adopt_evacuated(self, req: Request, export, source: Replica
                         ) -> None:
        """Adoption sink for a role-flip evacuation: land the exported
        slot on any other decode candidate (CRC-verified, fresh
        serialization per candidate).  When nobody can take it, the
        request fails with a retryable ``draining`` error and its pump
        redistributes — byte-identical either way."""
        for rep in self._decode_candidates(generation=source.generation):
            if rep is source or not rep.try_place():
                continue
            payload = transfer.to_bytes(export)
            try:
                adopt_payload = getattr(
                    rep.server, "adopt_payload", None
                )
                if adopt_payload is not None:
                    # Fleet RPC (serving/fleet.py): ship the bytes —
                    # CRC verification happens in the RECEIVING
                    # process, structured verdicts map back here.
                    adopt_payload(req, payload)
                else:
                    incoming = transfer.from_bytes(payload)
                    rep.server.adopt(req, incoming)
            except MigrationCorrupt:
                self.metrics.record_corrupt_migration()
                continue
            except (AdmissionError, EngineUnhealthy, RuntimeError):
                continue
            self.metrics.record_migration(len(payload))
            req.mark("evac_adopted", to=rep.name)
            return
        req.finish(
            "error",
            "replica draining for role reassignment: no candidate "
            "could adopt the evacuated KV; request redistributed",
        )

    def health(self) -> dict:
        """The router ``/healthz`` payload: aggregate liveness plus
        every replica's last health snapshot."""
        reps = {
            name: {
                "healthy": rep.healthy,
                "role": rep.role,
                "breaker": rep.breaker.state,
                **{
                    k: rep.last_health.get(k)
                    for k in ("active_slots", "queue_depth",
                              "kv_pages_free", "adoptions_pending",
                              "adapters_resident",
                              "compile_events_post_warmup_total",
                              "degradation_level")
                },
            }
            for name, rep in self._replicas.items()
        }
        n_alive = sum(1 for r in self._replicas.values() if r.healthy)
        with self._lock:
            inflight = self._inflight
        return {
            "ok": n_alive > 0 and not self._stopping,
            "mode": self.mode,
            "replicas_alive": n_alive,
            "replicas_total": len(self._replicas),
            "inflight": inflight,
            "sessions": len(self._sessions),
            "degradation_level": self.ladder.level,
            "replicas": reps,
        }

    def snapshot(self) -> dict:
        """Router metrics + health in one JSON-safe dict."""
        snap = self.metrics.snapshot()
        snap["mode"] = self.mode
        snap["degradation"] = self.ladder.snapshot()
        with self._lock:
            snap["inflight"] = self._inflight
            snap["sessions"] = len(self._sessions)
            snap["serving_generation"] = self._serving_generation
            snap["deploy_generation"] = self._deploy_generation
            snap["deploy_fraction"] = self._deploy_fraction
        return snap

    def close(self) -> None:
        self._stopping = True
        self._stop_event.set()
        deployment = getattr(self, "_deployment", None)
        if deployment is not None:
            deployment.close()
        if self._own_servers:
            for rep in self._replicas.values():
                rep.server.close()
        self._poller.join(timeout=10.0)
        if self._httpd is not None:
            self._httpd.shutdown()
            self._httpd.server_close()
            self._httpd = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    # -- placement --------------------------------------------------------

    def _alive(self) -> Dict[str, Replica]:
        return {
            n: r for n, r in self._replicas.items() if r.placeable()
        }

    # -- deploy traffic split (serving/deploy.py) --------------------------

    @staticmethod
    def tenant_slice(tenant: str) -> float:
        """Deterministic [0, 1) coordinate for a tenant: a deploy at
        fraction ``f`` routes exactly the tenants with
        ``tenant_slice(t) < f`` to the new generation — the same
        tenants on every poll, every process, every ramp stage (the
        canary slice is a stable cohort, not a coin flip per request)."""
        h = hashlib.sha1(b"deploy|" + tenant.encode()).hexdigest()[:8]
        return int(h, 16) / float(1 << 32)

    def set_deploy_split(self, generation: Optional[int],
                         fraction: float) -> None:
        """Point the tenant-hash slice ``[0, fraction)`` at
        ``generation`` (None tears the split down — all traffic back on
        the serving generation)."""
        with self._lock:
            self._deploy_generation = generation
            self._deploy_fraction = float(fraction)

    def promote_generation(self, generation: int) -> None:
        """Make ``generation`` the serving generation (deploy ramp
        completed): default traffic — and autoscaler-grown capacity —
        now lands there."""
        with self._lock:
            self._serving_generation = int(generation)
            self._deploy_generation = None
            self._deploy_fraction = 0.0

    def _target_generation(self, tenant: str) -> int:
        """Which generation serves this tenant right now."""
        gen, frac = self._deploy_generation, self._deploy_fraction
        if gen is not None and self.tenant_slice(tenant) < frac:
            return gen
        return self._serving_generation

    @staticmethod
    def _gen_pool(pool: Dict[str, Replica], generation: int
                  ) -> Dict[str, Replica]:
        """Restrict a placement pool to one deploy generation.  An
        empty restriction falls back to the full pool — serving
        somewhere beats refusing (the deploy monitors burn; it never
        relies on placement failing closed)."""
        sub = {
            n: r for n, r in pool.items() if r.generation == generation
        }
        return sub or pool

    def _affinity_key(self, tenant: str, prompt: np.ndarray,
                      adapter: Optional[str] = None) -> bytes:
        """Consistent-hash key on ``(tenant, adapter, first KV block)``:
        same-tenant shared prefixes keep hitting one prefill replica's
        prefix cache, and same-adapter traffic lands where the adapter
        is already resident (its pool slot warm, its prefix namespace
        populated) instead of minting a load on every replica."""
        block = np.asarray(
            prompt[: self._affinity_block], np.int32
        ).tobytes()
        return (
            tenant.encode() + b"\x1f" + (adapter or "").encode()
            + b"|" + block
        )

    def _place(self, creq: Request, session: Optional[str],
               exclude_prefill: Optional[str] = None
               ) -> Tuple[Replica, Replica]:
        """(prefill replica, decode replica) for this attempt, from live
        health, breaker-gated.  ``exclude_prefill`` skips the named
        replica (the hedging path never duplicates onto the replica it
        is hedging against).  Raises ``EngineUnhealthy`` when nothing
        is placeable."""
        alive = self._alive()
        if not alive:
            raise EngineUnhealthy("no healthy replica available")
        # Deploy split first: the whole attempt places within ONE
        # generation (prefill, decode, hedges, adoption candidates) —
        # KV never crosses a weights boundary mid-stream.
        alive = self._gen_pool(alive, self._target_generation(creq.tenant))
        key = self._affinity_key(creq.tenant, creq.prompt, creq.adapter)
        if self.mode == "colocated":
            pool = {
                n: r for n, r in alive.items() if n != exclude_prefill
            } or alive
            name = self._ring.place(key, pool) or sorted(pool)[0]
            rep = pool[name]
            if not rep.breaker.allow():
                others = sorted(
                    (r for r in pool.values()
                     if r is not rep and r.breaker.allow()),
                    key=Replica.load_score,
                )
                if not others:
                    raise EngineUnhealthy(
                        "no placeable replica: breakers open/probing"
                    )
                rep = others[0]
            return rep, rep
        prefill_pool = {
            n: r for n, r in alive.items()
            if r.role in ("prefill", "both")
        } or alive  # degraded: every engine CAN prefill
        decode_pool = {
            n: r for n, r in alive.items()
            if r.role in ("decode", "both")
        } or alive
        if exclude_prefill and len(prefill_pool) > 1:
            prefill_pool = {
                n: r for n, r in prefill_pool.items()
                if n != exclude_prefill
            }
        name = self._ring.place(key, prefill_pool) or sorted(prefill_pool)[0]
        prefill = prefill_pool[name]
        if not prefill.breaker.allow():
            others = sorted(
                (r for r in prefill_pool.values()
                 if r is not prefill and r.breaker.allow()),
                key=Replica.load_score,
            )
            if not others:
                raise EngineUnhealthy(
                    "no placeable prefill replica: breakers open/probing"
                )
            prefill = others[0]
        decode = None
        if session:
            with self._lock:
                sticky = self._sessions.get(session)
            if sticky in decode_pool and decode_pool[sticky].placeable():
                decode = decode_pool[sticky]
        if decode is None:
            decode = min(decode_pool.values(), key=Replica.load_score)
            if session:
                with self._lock:
                    self._sessions[session] = decode.name
        decode.pending += 1
        return prefill, decode

    def _decode_candidates(self, generation: Optional[int] = None
                           ) -> List[Replica]:
        alive = self._alive()
        if generation is not None:
            alive = {
                n: r for n, r in alive.items()
                if r.generation == generation
            }
        pool = [
            r for r in alive.values() if r.role in ("decode", "both")
        ] or list(alive.values())
        return sorted(pool, key=Replica.load_score)

    # -- the per-request state machine ------------------------------------

    def _run_request(self, creq: Request, session: Optional[str]) -> None:
        try:
            self._serve(creq, session)
        except Exception as e:  # noqa: BLE001 — never hang a client
            if creq.state in ("queued", "active"):
                self.metrics.record_error()
                creq.finish(
                    "error", f"router failure: {type(e).__name__}: {e}"
                )
        finally:
            with self._lock:
                self._inflight -= 1
            tap = self._request_tap
            if tap is not None:
                try:  # shadow-replay sampling (serving/deploy.py) —
                    # observability must never fail a served stream
                    tap(creq)
                except Exception:  # noqa: BLE001
                    pass

    def _remaining_deadline(self, creq: Request) -> Optional[float]:
        if creq.deadline is None:
            return None
        return creq.deadline - (time.monotonic() - creq.submitted_at)

    def _shadow(self, creq: Request, committed: List[int],
                deadline: Optional[float]) -> Request:
        """The per-attempt replica-local request: same prompt and
        sampling state, committed tokens preloaded (resume prefix), the
        remaining deadline budget, and the cumulative preemption count
        so engine give-ups stay structured across replicas."""
        shadow = Request(
            prompt=creq.prompt, max_new_tokens=creq.max_new_tokens,
            temperature=creq.temperature, rng=creq.rng,
            eos_token_id=creq.eos_token_id, deadline=deadline,
            tenant=creq.tenant, priority=creq.priority,
            adapter=creq.adapter,
        )
        shadow.tokens = [int(t) for t in committed]
        shadow.preemptions = creq.preemptions
        # The shadow gets a FRESH id per attempt; the trace context is
        # what keeps its spans on the originating request's causal
        # track across processes.
        if creq.trace_ctx:
            shadow.trace_ctx = dict(creq.trace_ctx)
        return shadow

    def _serve(self, creq: Request, session: Optional[str]) -> None:
        redistributes = 0
        while True:
            if self._stopping:
                creq.finish("error", "router is closed")
                return
            deadline = self._remaining_deadline(creq)
            if deadline is not None and deadline <= 0:
                creq.finish(
                    "expired",
                    f"deadline ({creq.deadline}s) passed while routing "
                    f"({redistributes} redistribution(s) consumed the "
                    "budget)",
                )
                return
            # Resume from what the CLIENT received, not what the shadow
            # recorded: a dying replica's last decode step can append a
            # token to the shadow after its stream was failed, and a
            # token the pump never forwarded must be recomputed (it is —
            # deterministically), never skipped.
            shadow = self._shadow(creq, list(creq.tokens), deadline)
            placed = self._submit_attempt(creq, shadow, session)
            if placed is None:
                return  # _submit_attempt finished creq with the reason
            prefill_rep, decode_rep = placed
            outcome, shadow, decode_rep = self._pump(
                creq, shadow, decode_rep, prefill_rep, session
            )
            if outcome == "done":
                creq.preemptions = shadow.preemptions
                decode_rep.breaker.record_success()
                creq.finish("done")
                return
            if outcome == "expired":
                creq.finish("expired", shadow.error)
                return
            if outcome == "shed":
                # A replica-side degradation rung shed the shadow: the
                # structured refusal propagates to the client verbatim
                # (503 + retry_after on the HTTP path).
                self.metrics.record_shed()
                creq.retry_after = shadow.retry_after
                creq.finish("shed", shadow.error)
                return
            if outcome == "retry":
                redistributes += 1
                self.metrics.record_redistribute()
                decode_rep.breaker.record_failure(
                    shadow.error or "stream failed"
                )
                creq.preemptions = shadow.preemptions + 1
                creq.mark(
                    "redistributed", attempt=redistributes,
                    committed_tokens=len(creq.tokens), error=shadow.error,
                )
                if redistributes > self.max_redistributes:
                    self.metrics.record_error()
                    creq.finish(
                        "error",
                        f"request {creq.id} (tenant '{creq.tenant}') "
                        f"redistributed {redistributes}x after replica "
                        f"failures; giving up after max_redistributes="
                        f"{self.max_redistributes} (last: {shadow.error})",
                    )
                    return
                continue
            self.metrics.record_error()
            creq.finish("error", shadow.error or "replica error")
            return

    def _submit_attempt(self, creq: Request, shadow: Request,
                        session: Optional[str],
                        exclude_prefill: Optional[str] = None,
                        quiet: bool = False
                        ) -> Optional[Tuple[Replica, Replica]]:
        """Place + submit one attempt.  Returns ``(prefill, decode)``
        replicas on success, or None after finishing ``creq`` with a
        structured error (placement/admission exhausted — unless
        ``quiet``, the hedging path, where failure just means no
        duplicate fires).  The retry window is capped by the request's
        remaining deadline: a 1-second-deadline request never spins the
        full admission retry budget."""
        give_up_at = time.monotonic() + self.admission_retry_s
        deadline_at = (
            creq.submitted_at + creq.deadline
            if creq.deadline is not None else None
        )
        if deadline_at is not None:
            give_up_at = min(give_up_at, deadline_at)
        last_err = "no healthy replica available"
        while not self._stopping:
            try:
                prefill_rep, decode_rep = self._place(
                    creq, session, exclude_prefill=exclude_prefill
                )
            except EngineUnhealthy as e:
                last_err = str(e)
                if time.monotonic() > give_up_at or quiet:
                    break
                self._stop_event.wait(0.05)
                continue
            disagg = prefill_rep is not decode_rep
            shadow.migration_sink = (
                (lambda r, exp: r._stream.put((_MIGRATE, exp)))
                if disagg else None
            )
            try:
                prefill_rep.server.submit_request(shadow)
            except OverloadShed as e:
                # The replica's degradation ladder refused it — a
                # structured terminal, not a placement failure.
                if quiet:
                    return None
                creq.retry_after = e.retry_after
                self.metrics.record_shed()
                creq.finish("shed", str(e))
                return None
            except AdmissionError as e:
                last_err = str(e)
                prefill_rep.breaker.record_success()  # alive, just full
                if time.monotonic() > give_up_at or quiet:
                    break
                self._stop_event.wait(0.02)
                continue
            except (EngineUnhealthy, RuntimeError) as e:
                # The poller will confirm, but don't wait for it.
                last_err = str(e)
                prefill_rep.breaker.record_failure(str(e))
                prefill_rep.healthy = False
                self.metrics.set_replica_health(prefill_rep.name, False)
                if time.monotonic() > give_up_at or quiet:
                    break
                continue
            creq.mark(
                "routed", prefill=prefill_rep.name,
                decode=decode_rep.name, disagg=disagg,
                hedge=bool(exclude_prefill),
            )
            self.metrics.record_request(
                prefill_rep.name, "prefill" if disagg else "colocated"
            )
            return prefill_rep, decode_rep
        if quiet:
            return None
        if (
            deadline_at is not None and time.monotonic() >= deadline_at
        ):
            creq.finish(
                "expired",
                f"deadline ({creq.deadline}s) passed while placing "
                f"request {creq.id}: {last_err}",
            )
            return None
        self.metrics.record_error()
        creq.finish(
            "error",
            f"router could not place request {creq.id} (tenant "
            f"'{creq.tenant}'): {last_err}",
        )
        return None

    def _hedge_after_s(self) -> float:
        """Seconds a request may wait for its first result before the
        router fires a duplicate prefill: ``hedge_factor`` x the
        rolling ``hedge_quantile`` first-result latency, floored."""
        return max(
            self.hedge_min_s,
            self.hedge_factor
            * self._first_result_lat.quantile(self.hedge_quantile),
        )

    def _hedge_eligible(self, creq: Request) -> bool:
        """Hedging duplicates work — it must never change bytes.  A
        greedy request is deterministic; a sampled request is only
        hedgeable when the caller pinned the seed (both replicas then
        compute the identical stream, so the race winner is
        irrelevant)."""
        return self.hedging and (
            creq.temperature == 0.0 or creq.rng is not None
        )

    def _pump(self, creq: Request, shadow: Request, decode_rep: Replica,
              prefill_rep: Replica, session: Optional[str]
              ) -> tuple:
        """Forward the shadow's stream to the client, adopting the KV
        export into the decode replica when it arrives, HEDGING the
        attempt onto another prefill replica when the first result is
        late.  Returns ``(outcome, winning_shadow)`` — outcome is
        ``done`` / ``expired`` / ``shed`` / ``retry`` (replica failure,
        redistribute) / ``error`` (structured terminal)."""
        t0 = time.monotonic()
        first_seen = False
        hedge_shadow: Optional[Request] = None
        hedge_pair: Optional[Tuple[Replica, Replica]] = None
        hedge_at = (
            t0 + self._hedge_after_s()
            if self._hedge_eligible(creq) else None
        )
        while True:
            # Before the first result arrives, poll at a cadence that
            # can notice the hedge deadline; afterwards the plain 0.5s
            # drain is enough.
            wait = 0.5
            if not first_seen and hedge_at is not None:
                wait = min(wait, max(hedge_at - time.monotonic(), 0.01))
            try:
                item = shadow._stream.get(timeout=wait)
            except _queue.Empty:
                if self._stopping:
                    shadow.error = shadow.error or "router is closed"
                    return "error", shadow, decode_rep
                if (
                    not first_seen and hedge_at is not None
                    and hedge_shadow is None
                    and time.monotonic() >= hedge_at
                ):
                    hedge_shadow, hedge_pair = self._fire_hedge(
                        creq, prefill_rep, session
                    )
                    if hedge_shadow is None:
                        # No idle capacity to duplicate onto right now;
                        # re-check at a gentle cadence — a slot may free
                        # up while this request is still stuck.
                        hedge_at = time.monotonic() + 0.25
                if hedge_shadow is not None and not first_seen:
                    # Race: whichever stream produces first wins.
                    try:
                        h_item = hedge_shadow._stream.get(timeout=0.02)
                    except _queue.Empty:
                        continue
                    # The hedge won: cancel the primary, swap streams.
                    self.metrics.record_hedge_win()
                    creq.mark(
                        "hedge_won", prefill=hedge_pair[0].name,
                        decode=hedge_pair[1].name,
                    )
                    self._cancel_attempt(prefill_rep, shadow)
                    shadow, hedge_shadow = hedge_shadow, None
                    prefill_rep, decode_rep = hedge_pair
                    item = h_item
                else:
                    continue
            if not first_seen:
                first_seen = True
                if hedge_at is None or time.monotonic() < hedge_at:
                    # Only un-hedged first results feed the hedge
                    # clock: a rescued attempt's (slow) latency would
                    # otherwise inflate the p99 and talk later hedges
                    # out of firing exactly while a replica is sick.
                    self._first_result_lat.observe(time.monotonic() - t0)
                if hedge_shadow is not None:
                    # The primary won the race: withdraw the duplicate.
                    self._cancel_attempt(hedge_pair[0], hedge_shadow)
                    hedge_shadow = None
            if item == _DONE:
                if shadow.state == "done":
                    return "done", shadow, decode_rep
                if shadow.state == "expired":
                    return "expired", shadow, decode_rep
                if shadow.state == "shed":
                    return "shed", shadow, decode_rep
                if self._stopping or not self._retryable(shadow.error):
                    return "error", shadow, decode_rep
                return "retry", shadow, decode_rep
            if isinstance(item, tuple) and item[0] == _MIGRATE:
                if not self._adopt(creq, shadow, decode_rep, item[1]):
                    return "retry", shadow, decode_rep
                continue
            creq.push_token(int(item))

    def _fire_hedge(self, creq: Request, primary_prefill: Replica,
                    session: Optional[str]):
        """Fire the duplicate prefill on a DIFFERENT prefill replica
        (quiet placement — no duplicate available just means no hedge).
        Returns ``(hedge_shadow, (prefill, decode))`` or ``(None,
        None)``.

        Hedges only target genuinely IDLE capacity: when every other
        replica is also loaded (uniform saturation), a duplicate just
        queues behind existing work and doubles the fleet's prefill
        load exactly when it can least afford it — the classic hedging
        anti-pattern.  The depth gate makes hedging self-throttling:
        it rescues requests stuck behind a sick replica while healthy
        capacity idles, and stands down when the whole fleet is the
        bottleneck (the degradation ladder's job, not hedging's)."""
        alive = self._alive()
        pool = [
            r for r in alive.values()
            if r.role in ("prefill", "both") and r is not primary_prefill
            and r.generation == primary_prefill.generation
        ]
        if not pool:
            return None, None
        best = min(pool, key=Replica.load_score)
        if best.load_score()[0] >= best.server.engine.max_batch:
            return None, None
        hedge_shadow = self._shadow(
            creq, list(creq.tokens), self._remaining_deadline(creq)
        )
        placed = self._submit_attempt(
            creq, hedge_shadow, session,
            exclude_prefill=primary_prefill.name, quiet=True,
        )
        if placed is None:
            return None, None
        if placed[0] is primary_prefill:
            # Only one prefill replica is placeable: a duplicate on the
            # same replica would just deepen its queue.
            self._cancel_attempt(placed[0], hedge_shadow)
            return None, None
        self.metrics.record_hedge()
        creq.mark(
            "hedged", prefill=placed[0].name, decode=placed[1].name,
            after_ms=round(self._hedge_after_s() * 1e3, 1),
        )
        return hedge_shadow, placed

    def _cancel_attempt(self, rep: Replica, shadow: Request) -> None:
        """Withdraw a raced attempt's losing shadow from its replica
        (best effort — the replica may already be failing it)."""
        try:
            rep.server.cancel(shadow)
        except Exception:  # noqa: BLE001 — the loser is abandoned anyway
            pass

    def _adopt(self, creq: Request, shadow: Request,
               decode_rep: Replica, export) -> bool:
        """Hand the exported KV to a decode replica — the placed one
        first, any healthy decode candidate as fallback.  Every
        candidate gets a FRESH serialization round-trip (the payload is
        transport-shaped and metered in real bytes), CRC32-verified on
        deserialization AND import: a corrupt payload (chaos
        ``migration_corrupt``, or a real transport flip) is refused
        with a structured error and the adoption retries on the next
        candidate instead of silently adopting garbage."""
        from ml_trainer_tpu.resilience.faults import active_plan

        # Fallback candidates stay within the exporting attempt's
        # generation: adopting onto other weights would be refused with
        # weights_mismatch anyway (transfer.import_kv_slot) — don't
        # burn serialization round-trips finding that out.
        candidates = [decode_rep] + [
            r for r in self._decode_candidates(
                generation=decode_rep.generation
            )
            if r is not decode_rep
        ]
        for rep in candidates:
            if not rep.try_place():
                continue
            wire_t0 = time.monotonic()
            payload = transfer.to_bytes(export)
            plan = active_plan()
            if plan is not None:
                fault = plan.fire("migration_corrupt")
                if fault is not None:
                    # One bit flipped in flight: the CRC gate below
                    # must catch it.
                    flipped = bytearray(payload)
                    flipped[len(flipped) // 2] ^= 0x40
                    payload = bytes(flipped)
            try:
                adopt_payload = getattr(
                    rep.server, "adopt_payload", None
                )
                if adopt_payload is not None:
                    # Fleet RPC (serving/fleet.py): POST the bytes to
                    # the replica PROCESS — the CRC gate runs at the
                    # receiving end (a bit flipped on this socket hop
                    # is caught there), and the structured verdict
                    # maps onto the same except arms below.
                    adopt_payload(shadow, payload)
                else:
                    incoming = transfer.from_bytes(payload)
                    rep.server.adopt(shadow, incoming)
            except MigrationCorrupt as e:
                self.metrics.record_corrupt_migration()
                self._log.error(
                    "router_migration_corrupt", replica=rep.name,
                    error=str(e),
                )
                creq.mark(
                    "migration_corrupt", to=rep.name, error=str(e),
                )
                continue  # fresh serialization for the next candidate
            except AdmissionError:
                continue
            except (EngineUnhealthy, RuntimeError) as e:
                rep.breaker.record_failure(str(e))
                rep.healthy = False
                self.metrics.set_replica_health(rep.name, False)
                continue
            self.metrics.record_migration(len(payload))
            self.metrics.record_request(rep.name, "decode")
            creq.mark(
                "kv_migrated", to=rep.name, kv_bytes=len(payload),
                pages=export.n_pages,
            )
            # The wire hop on the ROUTER's trace lane: serialize ->
            # adopted, bridging the prefill lane's span to the decode
            # lane's in the merged fleet timeline.
            ctx = creq.trace_ctx or {}
            spans.complete_event(
                f"kv_wire {ctx.get('trace_id', creq.id)}",
                wire_t0, time.monotonic(), category="router",
                request=creq.id,
                trace_id=ctx.get("trace_id", creq.id),
                to=rep.name, kv_bytes=len(payload),
            )
            return True
        shadow.error = (
            "serving engine unhealthy: no decode replica could adopt "
            "the migrated KV"
        )
        return False

    @staticmethod
    def _retryable(err: Optional[str]) -> bool:
        """Replica-level failures redistribute; the engine's structured
        give-ups (max_preemptions) and unknown errors surface to the
        client as-is."""
        if not err:
            return False
        if "max_preemptions" in err:
            return False
        return any(
            needle in err
            for needle in ("unhealthy", "server closed", "wedged",
                           "engine thread died", "killed", "draining")
        )

    # -- health polling ---------------------------------------------------

    def _poll_health(self) -> None:
        while not self._stopping:
            self._fire_chaos_kill()
            for rep in self._replicas.values():
                payload = rep.fetch_health()
                rep.last_health = payload
                rep.pending = 0
                ok = (
                    bool(payload.get("healthy"))
                    and not payload.get("draining")
                    and not payload.get("closed")
                )
                if ok:
                    rep.fail_polls = 0
                    if not rep.healthy:
                        # Recovered (or the flap cleared): rejoin the
                        # placement pool.
                        self._log.info(
                            "router_replica_recovered", replica=rep.name
                        )
                else:
                    rep.fail_polls += 1
                    if rep.fail_polls < self.unhealthy_after and rep.healthy:
                        # Flap damping: ONE dropped/failed poll is a
                        # transient until K consecutive confirm it —
                        # a spurious drain-and-redistribute costs far
                        # more than one poll interval of patience.
                        self.metrics.record_flap_damped()
                        self._log.info(
                            "router_healthz_flap_damped", replica=rep.name,
                            fail_polls=rep.fail_polls,
                            reason=payload.get("reason"),
                        )
                        continue
                if rep.healthy and not ok:
                    self._log.error(
                        "router_replica_unhealthy", replica=rep.name,
                        reason=payload.get("reason"),
                    )
                    # Watchdog trip / engine death / severed process:
                    # capture the fleet's state while it is still warm.
                    self.trigger_incident(
                        f"replica_unhealthy: {rep.name}: "
                        f"{payload.get('reason')}",
                        dead=(rep.name,),
                    )
                rep.healthy = ok
                self.metrics.set_replica_health(rep.name, ok)
            self.scrape_metrics()
            self._watchtower_tick()
            self._stop_event.wait(self._health_interval)

    def _fire_chaos_kill(self) -> None:
        """``replica_kill`` chaos hook (resilience/faults.py): at the
        matching BUSY poll (the fleet is serving traffic), kill the
        replica whose fleet index matches the fault's ``host`` — the
        real watchdog-death path, under real load."""
        from ml_trainer_tpu.resilience.faults import active_plan

        plan = active_plan()
        if plan is None:
            return
        with self._lock:
            busy = self._inflight > 0
        if not busy:
            return
        self._busy_polls += 1
        fault = plan.fire("replica_kill", step=self._busy_polls)
        if fault is None:
            return
        for name, rep in sorted(self._replicas.items()):
            if rep.server.replica_index == fault.host and rep.healthy:
                self._log.error(
                    "router_chaos_replica_kill", replica=name,
                    poll=self._busy_polls,
                )
                self.kill_replica(name)
                return

    # -- telemetry --------------------------------------------------------

    def _watchtower_tick(self) -> None:
        """One TSDB + alert sweep, riding the health poll: ingest every
        FRESH worker exposition (federation labels preserved), sample
        the router's own registry at the scrape cadence, then evaluate
        the declarative rules.  Best-effort — the poller never dies on
        observability work."""
        try:
            now = time.time()
            mono = time.monotonic()
            for name, rep in self._replicas.items():
                if rep.metrics_text is None:
                    continue
                # Only ingest a snapshot once: scrape pacing stamps
                # metrics_scraped_at, so an unchanged stamp means the
                # same bytes (replace, never re-append).
                if self._wt_ingested.get(name) == rep.metrics_scraped_at:
                    continue
                self._wt_ingested[name] = rep.metrics_scraped_at
                self.watchtower.ingest_exposition(
                    rep.metrics_text, t=now,
                    extra_labels={
                        "replica": name, "role": rep.role,
                        "generation": str(rep.generation),
                    },
                    force=True,
                )
            if mono - self._wt_sampled_at >= self.metrics_scrape_interval:
                self._wt_sampled_at = mono
                from ml_trainer_tpu.telemetry.registry import (
                    default_registry,
                )

                registry = default_registry()
                self.publish(registry)
                self.watchtower.sample_registry(
                    registry, t=now, force=True
                )
            self.alerts.evaluate(now=now)
        except Exception as e:  # noqa: BLE001 — poller survives anything
            self._log.info("router_watchtower_tick_failed", error=str(e))

    def add_alert_rule(self, rule: AlertRule) -> AlertRule:
        """Install one more declarative rule on the fleet engine (takes
        effect on the next poll tick)."""
        return self.alerts.add_rule(rule)

    def publish(self, registry=None) -> dict:
        """Mirror the router counters into the telemetry registry (and
        return the snapshot): ``router_requests_total{role=,replica=}``,
        ``router_kv_migrated_bytes_total``,
        ``router_replica_healthy{replica=}``, redistribution/migration
        totals, the router-level SLO attainment, and each replica's
        attainment re-labeled by replica through its existing
        SloTracker."""
        from ml_trainer_tpu.telemetry.registry import default_registry

        r = registry if registry is not None else default_registry()
        snap = self.metrics.snapshot()
        req = r.gauge(
            "router_requests_total",
            "requests placed by the router, by role and replica",
            labelnames=("role", "replica"),
        )
        for key, n in snap["requests_total"].items():
            role, replica = key.split("/", 1)
            req.labels(role=role, replica=replica).set(float(n))
        r.gauge(
            "router_kv_migrated_bytes_total",
            "serialized KV payload bytes migrated prefill -> decode",
        ).set(float(snap["kv_migrated_bytes_total"]))
        r.gauge(
            "router_migrations_total",
            "KV migrations adopted by decode replicas",
        ).set(float(snap["migrations_total"]))
        r.gauge(
            "router_redistributes_total",
            "in-flight requests redistributed off a failed replica",
        ).set(float(snap["redistributes_total"]))
        r.gauge(
            "router_hedges_total",
            "duplicate prefills fired after the rolling-p99 hedge clock",
        ).set(float(snap["hedges_total"]))
        r.gauge(
            "router_hedge_wins_total",
            "hedged duplicates that beat the primary attempt",
        ).set(float(snap["hedge_wins_total"]))
        r.gauge(
            "router_migrations_corrupt_total",
            "KV migration payloads refused by the CRC32 verify",
        ).set(float(snap["migrations_corrupt_total"]))
        r.gauge(
            "router_shed_total",
            "requests shed by the degradation ladder at the router",
        ).set(float(snap["shed_total"]))
        r.gauge(
            "router_flaps_damped_total",
            "failed health polls absorbed by flap damping",
        ).set(float(snap["flaps_damped_total"]))
        scrape_err = r.gauge(
            "router_replica_scrape_errors_total",
            "federation /metrics scrapes that failed, by replica",
            labelnames=("replica",),
        )
        for name, n in snap["scrape_errors_total"].items():
            scrape_err.labels(replica=name).set(float(n))
        r.gauge(
            "router_incidents_total",
            "incident bundles assembled (throttled triggers excluded)",
        ).set(float(snap["incidents_total"]))
        clock = r.gauge(
            "router_replica_clock_shift_us",
            "per-replica trace-clock shift onto the router's clock "
            "(epoch-exact or NTP-handshake estimate)",
            labelnames=("replica", "method"),
        )
        for name, rep in self._replicas.items():
            shift, method = federation.resolve_clock_shift(
                rep.epoch_shift_us, rep.ntp_shift_us, rep.ntp_rtt_us
            )
            if shift is not None:
                clock.labels(replica=name, method=method).set(shift)
        breaker = r.gauge(
            "router_breaker_state",
            "per-replica circuit breaker (0 closed, 1 half-open, 2 open)",
            labelnames=("replica",),
        )
        for name, rep in self._replicas.items():
            breaker.labels(replica=name).set(float(rep.breaker.gauge_value()))
        self.ladder.publish(r)
        healthy = r.gauge(
            "router_replica_healthy",
            "1 while the replica is placeable, 0 once it left the pool",
            labelnames=("replica",),
        )
        for name, ok in snap["replica_healthy"].items():
            healthy.labels(replica=name).set(float(ok))
        att = r.gauge(
            "router_replica_slo_attainment",
            "per-replica SLO attainment (each replica's own SloTracker)",
            labelnames=("slo", "replica"),
        )
        for name, rep in self._replicas.items():
            rep_snap = rep.server.slo.snapshot()
            for k in ("ttft", "tpot"):
                att.labels(slo=k, replica=name).set(
                    rep_snap["attainment"][k]
                )
        self.slo.publish(r)
        return snap

    # -- fleet observability plane ----------------------------------------
    # (docs/observability.md "Fleet plane": metrics federation, merged
    # cross-process traces, incident bundles.)

    def scrape_metrics(self, force: bool = False) -> None:
        """One federation sweep: fetch each url-replica's raw
        ``/metrics`` text (paced by ``metrics_scrape_interval`` per
        replica unless ``force``).  A failed scrape bumps
        ``router_replica_scrape_errors_total{replica=}`` and keeps the
        last good snapshot — the poller never crashes on a dead
        process."""
        now = time.monotonic()
        for rep in self._replicas.values():
            if not rep.url:
                continue
            if (
                not force
                and now - rep.metrics_scraped_at
                < self.metrics_scrape_interval
            ):
                continue
            rep.metrics_scraped_at = now
            try:
                rep.metrics_text = rep.fetch_metrics_text()
            except Exception as e:  # noqa: BLE001 — scrape is best effort
                self.metrics.record_scrape_error(rep.name)
                self._log.info(
                    "router_metrics_scrape_failed", replica=rep.name,
                    error=str(e),
                )

    def federated_metrics_text(self,
                               base_text: Optional[str] = None) -> str:
        """ONE Prometheus exposition for the whole fleet: the router's
        own registry plus every worker's latest scraped snapshot, each
        worker series re-labeled ``replica=``/``role=``/``generation=``
        (telemetry/federation.py).  Rendering always starts from the
        latest snapshots — replace, never accumulate — so scraping the
        router twice between worker scrapes returns identical bytes
        (no histogram double-counting)."""
        if base_text is None:
            from ml_trainer_tpu.telemetry.registry import default_registry

            registry = default_registry()
            self.publish(registry)
            base_text = registry.prometheus_text()
        sections = []
        for name, rep in sorted(self._replicas.items()):
            if rep.metrics_text is None:
                continue
            sections.append((rep.metrics_text, {
                "replica": name, "role": rep.role,
                "generation": str(rep.generation),
            }))
        return federation.federate_exposition(base_text, sections)

    def fleet_trace(self) -> dict:
        """The merged, clock-aligned Perfetto document: the router's
        own span buffer plus every reachable url-replica's ``GET
        /trace`` payload, each worker lane shifted onto the router's
        trace clock by the health poller's handshake estimates.  An
        unreachable replica is skipped (its lane is simply absent);
        an in-process replica needs no fetch — its spans already live
        in the router's buffer."""
        remotes = []
        for name, rep in sorted(self._replicas.items()):
            if not rep.url:
                continue
            try:
                payload = rep.fetch_trace()
            except Exception:  # noqa: BLE001 — dead process, no lane
                continue
            remotes.append({
                "name": name, "payload": payload,
                "epoch_shift_us": rep.epoch_shift_us,
                "ntp_shift_us": rep.ntp_shift_us,
                "rtt_us": rep.ntp_rtt_us,
            })
        return federation.merge_fleet_trace(
            spans.trace_events(), "router", os.getpid(), remotes
        )

    def save_fleet_trace(self, path: str) -> str:
        """Write :meth:`fleet_trace` as a ``chrome://tracing`` /
        Perfetto JSON file (atomic)."""
        doc = self.fleet_trace()
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w", encoding="utf-8") as fp:
            json.dump(doc, fp, default=str)
        os.replace(tmp, path)
        self._log.info(
            "router_fleet_trace_saved", path=path,
            events=len(doc["traceEvents"]),
        )
        return path

    def trigger_incident(self, reason: str,
                         dead: Sequence[str] = ()) -> None:
        """Fire-and-forget incident bundle assembly off the calling
        thread (the poller/kill paths must never block on N replica
        fetches).  Throttled inside :meth:`save_incident_bundle`."""
        threading.Thread(
            target=self._trigger_incident_body, args=(reason, tuple(dead)),
            daemon=True, name="router-incident",
        ).start()

    def _trigger_incident_body(self, reason: str,
                               dead: Tuple[str, ...]) -> None:
        try:
            self.save_incident_bundle(reason, dead=dead)
        except Exception as e:  # noqa: BLE001 — forensics never kill serving
            self._log.error("router_incident_failed", error=str(e))

    def save_incident_bundle(self, reason: str,
                             dead: Sequence[str] = (),
                             out_dir: Optional[str] = None,
                             force: bool = False) -> Optional[str]:
        """Assemble ``incident_<ts>_<pid>/`` — everything a post-mortem
        needs, captured while the fleet's state is still warm:

        * ``flight_router.json`` — the router process's flight payload;
        * ``flight_<replica>.json`` — each reachable url-replica's live
          flight payload (``GET /flight``; a dead process is skipped);
        * ``slo_timelines.json`` — the router tracker's last retained
          per-request timelines;
        * ``metrics.prom`` / ``router.json`` — the federated exposition
          and the router snapshot at capture time;
        * ``stderr_<replica>.txt`` — the dead workers' combined
          stdout+stderr tails (fleet workers only);
        * ``manifest.json`` — reason, trigger set, fleet health, files.

        Bundles are throttled (``incident_min_interval_s``) unless
        ``force`` — a flapping replica must not write one per poll.
        Directory resolves: ``out_dir`` arg, router ``incident_dir``,
        ``ML_TRAINER_TPU_INCIDENT_DIR``, the system temp dir.  Returns
        the bundle path, or None when throttled."""
        now = time.monotonic()
        with self._incident_lock:
            if (
                not force
                and now - self._last_incident_at
                < self.incident_min_interval_s
                and self._last_incident_at > 0.0
            ):
                return None
            self._last_incident_at = now
        d = (
            out_dir or self.incident_dir
            or os.environ.get(INCIDENT_DIR_ENV)
            or tempfile.gettempdir()
        )
        stem = os.path.join(
            d,
            f"incident_{time.strftime('%Y%m%d_%H%M%S')}_{os.getpid()}",
        )
        # Two incidents inside one wall-clock second (e.g. a forced
        # bundle right after a triggered one) must not overwrite each
        # other: uniquify with a suffix.
        bundle, n = stem, 1
        while True:
            try:
                os.makedirs(bundle, exist_ok=False)
                break
            except FileExistsError:
                bundle = f"{stem}_{n}"
                n += 1
        files: List[str] = []

        def _write(name: str, payload) -> None:
            try:
                path = os.path.join(bundle, name)
                with open(path, "w", encoding="utf-8") as fp:
                    if isinstance(payload, str):
                        fp.write(payload)
                    else:
                        json.dump(payload, fp, default=str)
                files.append(name)
            except Exception as e:  # noqa: BLE001 — partial bundle > none
                self._log.info(
                    "router_incident_artifact_failed", artifact=name,
                    error=str(e),
                )

        _write(
            "flight_router.json",
            get_recorder().payload(f"incident: {reason}"),
        )
        replica_flights: List[str] = []
        for name, rep in sorted(self._replicas.items()):
            try:
                payload = rep.fetch_flight()
            except Exception:  # noqa: BLE001 — dead process
                continue
            if payload is not None:
                _write(f"flight_{name}.json", payload)
                replica_flights.append(name)
        _write("slo_timelines.json", self.slo.timelines())
        _write("metrics.prom", self.federated_metrics_text())
        _write("router.json", self.snapshot())
        # Watchtower: the dashboard at capture time (the trend INTO the
        # incident, not just the instant) plus the full alert history.
        _write("dashboard.html", render_dashboard(
            self.watchtower, title=f"incident: {reason}",
            alerts=self.alerts.history(),
        ))
        _write("alerts.json", self.alerts.payload())
        for name in dead:
            rep = self._replicas.get(name)
            tail_fn = getattr(
                getattr(rep, "server", None), "stderr_tail", None
            )
            if tail_fn is None:
                continue
            try:
                tail = tail_fn()
            except Exception:  # noqa: BLE001
                tail = None
            if tail:
                _write(f"stderr_{name}.txt", tail)
        _write("manifest.json", {
            "reason": reason,
            "created_at": time.time(),
            "dead": list(dead),
            "replica_flights": replica_flights,
            "health": self.health(),
            "files": sorted(files),
        })
        self.metrics.record_incident()
        get_recorder().record(
            "incident_bundle", reason=reason, path=bundle,
            files=len(files),
        )
        self._log.error(
            "router_incident_bundle", reason=reason, path=bundle,
            files=sorted(files),
        )
        with self._incident_lock:
            self.last_incident_path = bundle
        return bundle

    # -- HTTP front end ---------------------------------------------------

    def serve_http(self, host: str = "127.0.0.1", port: int = 0):
        """The router's stdlib HTTP front end (same contract as
        ``Server.serve_http``): POST ``/v1/generate`` (plus an optional
        ``"session"`` key for stickiness), GET ``/healthz`` /
        ``/metrics`` / ``/metrics.json`` / ``/slo``."""
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

        from ml_trainer_tpu.serving.scheduler import DeadlineExceeded

        router = self

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

            def do_GET(self):
                if self.path == "/healthz":
                    payload = router.health()
                    self._send(200 if payload["ok"] else 503, payload)
                elif self.path == "/metrics":
                    # The FEDERATED exposition: the router's own
                    # registry plus every worker's latest scraped
                    # snapshot re-labeled replica=/role=/generation= —
                    # one scrape covers the whole fleet.
                    body = router.federated_metrics_text().encode()
                    self.send_response(200)
                    self.send_header(
                        "Content-Type",
                        "text/plain; version=0.0.4; charset=utf-8",
                    )
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                elif self.path == "/metrics.json":
                    self._send(200, router.snapshot())
                elif self.path == "/trace":
                    # The merged clock-aligned fleet timeline (load it
                    # straight into Perfetto / chrome://tracing).
                    self._send(200, router.fleet_trace())
                elif self.path == "/slo":
                    self._send(200, router.slo.snapshot())
                elif self.path == "/dash":
                    # Fleet-wide live dashboard: the router's TSDB holds
                    # every replica's series (replica=/role= labels) so
                    # one page shows the whole fleet's trends.
                    body = render_dashboard(
                        router.watchtower, title="router",
                        alerts=router.alerts.history(),
                    ).encode()
                    self.send_response(200)
                    self.send_header(
                        "Content-Type", "text/html; charset=utf-8"
                    )
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                else:
                    self._send(404, {"error": "not found"})

            def do_POST(self):
                if self.path != "/v1/generate":
                    self._send(404, {"error": "not found"})
                    return
                try:
                    n = int(self.headers.get("Content-Length", 0))
                    body = json.loads(self.rfile.read(n) or b"{}")
                    session = body.get("session")
                    deadline = body.get("deadline")
                    stream = router.submit(
                        np.asarray(body["prompt"], np.int32),
                        int(body.get("max_new_tokens", 16)),
                        temperature=float(body.get("temperature", 0.0)),
                        rng=body.get("seed"),
                        eos_token_id=body.get("eos_token_id"),
                        deadline=deadline,
                        tenant=str(body.get("tenant", "default")),
                        priority=int(body.get("priority", 0)),
                        session=str(session) if session else None,
                        adapter=body.get("adapter"),
                        trace=_trace_ctx_header(self.headers),
                    )
                    # The HTTP wait is capped by the client's own
                    # deadline (plus routing slack): a deadline'd
                    # request gets a timely 504, and the remaining
                    # budget decrements across every redistribute
                    # and hedge inside the router.
                    out = stream.result(timeout=(
                        float(deadline) + 30.0
                        if deadline is not None else None
                    ))
                    self._send(200, {
                        "tokens": [int(t) for t in out],
                        # Which replica actually served the decode —
                        # the last migration/placement mark on the
                        # request's event log (loadgen attributes its
                        # latency rows by this).
                        "replica": router._serving_replica(stream._req),
                    })
                except OverloadShed as e:
                    payload = {"error": str(e)}
                    if e.retry_after is not None:
                        payload["retry_after"] = e.retry_after
                    self._send(503, payload, retry_after=e.retry_after)
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
                    # Structured terminal errors (redistribution budget
                    # exhausted, engine give-ups) reach the client as
                    # JSON, never a stdlib 500 HTML page.
                    self._send(503, {"error": str(e)})

        self._httpd = ThreadingHTTPServer((host, port), Handler)
        self._http_thread = threading.Thread(
            target=self._httpd.serve_forever, daemon=True,
            name="router-http",
        )
        self._http_thread.start()
        return self._httpd.server_address
