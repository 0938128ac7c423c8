"""Recompile forensics: every XLA compile, named, timed, and explained.

A TPU run that recompiles in steady state is a production incident — a
shape leaked into a traced argument, a Python float toggled weak_type,
a cache key drifted — and the symptom (a multi-second stall every N
steps) points nowhere near the cause.  The repo used to pin "no silent
recompiles" through ad-hoc ``jit._cache_size() == 1`` asserts scattered
across tests; this module replaces those with one real instrument on
JAX's own compilation path:

* every backend compile is recorded as a :class:`CompileEvent` —
  function name, elapsed ms, timestamp — and counted in the registry as
  ``compile_events_total{fn=...}``;
* the tracing-cache-miss explanation JAX can produce
  (``jax_explain_cache_misses``) is captured and attached to the next
  compile event, so a post-warmup recompile names the offending
  argument and shape (``"at x, seen f32[4], but now given f32[8]"``);
* after :func:`mark_warm` (the Trainer calls it once its first epoch —
  train + eval — has compiled everything it legitimately needs), each
  further compile ALSO fires a flight-recorder ``recompile`` event and
  bumps ``compile_events_post_warmup_total``, so an OOM/wedge dump
  shows the compile storm right next to the steps it stalled;
* compile seconds feed the goodput ledger's ``compile`` bucket
  (``telemetry/goodput.py``) — wall-clock attribution, not just counts.

Mechanism (jax 0.9): :func:`install` registers a public
``jax.monitoring`` duration listener — JAX records every backend compile
under ``BACKEND_COMPILE_EVENT`` with the function's name as ``fun_name``
— and an event listener for the persistent cache's hit/miss events, and
puts a capturing filter on the loggers ``jax_explain_cache_misses``
writes to (``jax._src.interpreters.partial_eval`` for its ``TRACING
CACHE MISS`` explanations).  A compile served from the persistent cache
still counts as a compile event (its elapsed time is the retrieval);
:func:`persistent_cache_counts` tells the two apart.  The observed
programs are untouched: this is pure host-side bookkeeping, so the
compiled-step trajectory stays bit-identical with the watch installed
(test-pinned).
"""

from __future__ import annotations

import contextlib
import dataclasses
import logging
import threading
import time
from typing import Dict, List, Optional

from ml_trainer_tpu.utils.logging import get_logger

logger = get_logger("ml_trainer_tpu.telemetry")

# The jax.monitoring keys JAX records a backend compile and the
# persistent compilation cache's verdict on it under.
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
# Where jax_explain_cache_misses logs: its TRACING CACHE MISS text, and —
# once a persistent cache directory is set — a WARNING for every
# persistent-cache miss and every program too quick to be written back.
_EXPLAIN_LOGGERS = (
    "jax._src.interpreters.partial_eval", "jax._src.compiler",
    "jax._src.compilation_cache",
)

_MAX_EVENTS = 512  # bounded ring; a compile storm must not grow the host
_MAX_EXPLANATION = 2000  # chars kept of a cache-miss explanation


@dataclasses.dataclass
class CompileEvent:
    """One backend (XLA) compile."""

    seq: int
    fn: str
    elapsed_ms: float
    t: float  # time.time() at completion
    after_warmup: bool
    explanation: Optional[str] = None  # tracing-cache-miss forensics

    def as_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["elapsed_ms"] = round(d["elapsed_ms"], 3)
        return d


class _State:
    def __init__(self):
        self.lock = threading.Lock()
        self.installed = False
        self.events: List[CompileEvent] = []
        self.seq = 0
        self.total = 0
        self.post_warmup = 0
        self.warm = False
        self.by_fn: Dict[str, int] = {}
        self.pending_explanation: Optional[str] = None
        self.cache_hits = 0
        self.cache_misses = 0
        self.explain_filter: Optional[logging.Filter] = None
        self.explain_prev_config: Optional[bool] = None


_state = _State()


class _ExplainFilter(logging.Filter):
    """Captures — and swallows, so every first-seen-function trace does
    not spam the user's log — the ``TRACING CACHE MISS`` explanations JAX
    logs at WARNING when ``jax_explain_cache_misses`` is on, so the next
    compile event can name the offending argument/shape.  The flag's
    persistent-cache chatter is swallowed too (the monitoring events
    already count hits and misses).  Other records pass untouched."""

    def filter(self, record: logging.LogRecord) -> bool:
        try:
            msg = record.getMessage()
        except Exception:
            return True
        if record.levelno == logging.WARNING and msg.startswith((
            "PERSISTENT COMPILATION CACHE MISS",
            "Not writing persistent cache entry", "Writing ",
        )):
            return False
        if "TRACING CACHE MISS" not in msg:
            return True
        with _state.lock:
            _state.pending_explanation = msg[:_MAX_EXPLANATION]
        return False


def _on_compile(fn: str, elapsed_s: float) -> None:
    """One finished backend compile: ring + counters + (post-warmup)
    flight forensics + the goodput ledger's compile bucket."""
    now = time.time()
    with _state.lock:
        _state.seq += 1
        _state.total += 1
        _state.by_fn[fn] = _state.by_fn.get(fn, 0) + 1
        warm = _state.warm
        if warm:
            _state.post_warmup += 1
        explanation, _state.pending_explanation = (
            _state.pending_explanation, None
        )
        ev = CompileEvent(
            seq=_state.seq, fn=fn, elapsed_ms=elapsed_s * 1e3, t=now,
            after_warmup=warm, explanation=explanation,
        )
        _state.events.append(ev)
        del _state.events[:-_MAX_EVENTS]
    # Registry + goodput + flight OUTSIDE the lock (they take their own).
    try:
        from ml_trainer_tpu.telemetry.registry import default_registry

        r = default_registry()
        r.counter(
            "compile_events_total",
            "XLA backend compiles observed this process",
            ("fn",),
        ).labels(fn=fn).inc()
        if warm:
            r.counter(
                "compile_events_post_warmup_total",
                "compiles AFTER the owning loop declared warmup done — "
                "each one is a steady-state recompile to investigate",
            ).inc()
    except Exception:  # the instrument must never break a compile
        pass
    try:
        from ml_trainer_tpu.telemetry import goodput

        goodput.account("compile", elapsed_s)
    except Exception:
        pass
    if warm:
        try:
            from ml_trainer_tpu.telemetry.flight import get_recorder

            get_recorder().record(
                "recompile", fn=fn, elapsed_ms=round(elapsed_s * 1e3, 3),
                explanation=explanation,
            )
        except Exception:
            pass
        logger.warning(
            f"post-warmup recompile: {fn} ({elapsed_s * 1e3:.1f}ms)"
            + (f"\n{explanation}" if explanation else "")
        )


def _duration_listener(event: str, duration: float, **kwargs) -> None:
    if event == BACKEND_COMPILE_EVENT:
        _on_compile(str(kwargs.get("fun_name", "unknown")), float(duration))


def _event_listener(event: str, **kwargs) -> None:
    if event == CACHE_HIT_EVENT:
        with _state.lock:
            _state.cache_hits += 1
    elif event == CACHE_MISS_EVENT:
        with _state.lock:
            _state.cache_misses += 1


def install() -> None:
    """Install the compile watch (idempotent)."""
    with _state.lock:
        if _state.installed:
            return
        _state.installed = True
    # Register the post-warmup counter eagerly (at 0): the fleet's
    # metrics federation (serving/router.py) pins every worker's
    # ``compile_events_post_warmup_total`` in the merged exposition —
    # absence must mean "watch not installed", never "no recompile yet".
    from ml_trainer_tpu.telemetry.registry import default_registry

    default_registry().counter(
        "compile_events_post_warmup_total",
        "compiles AFTER the owning loop declared warmup done — "
        "each one is a steady-state recompile to investigate",
    )
    import jax

    jax.monitoring.register_event_duration_secs_listener(_duration_listener)
    jax.monitoring.register_event_listener(_event_listener)
    _state.explain_filter = _ExplainFilter()
    for name in _EXPLAIN_LOGGERS:
        logging.getLogger(name).addFilter(_state.explain_filter)
    _state.explain_prev_config = bool(jax.config.jax_explain_cache_misses)
    jax.config.update("jax_explain_cache_misses", True)
    logger.info("compile watch installed")


def uninstall() -> None:
    """Remove the watch and restore jax's hooks (tests only)."""
    with _state.lock:
        if not _state.installed:
            return
        _state.installed = False
    import jax

    jax.monitoring.unregister_event_duration_listener(_duration_listener)
    jax.monitoring.unregister_event_listener(_event_listener)
    for name in _EXPLAIN_LOGGERS:
        logging.getLogger(name).removeFilter(_state.explain_filter)
    _state.explain_filter = None
    jax.config.update("jax_explain_cache_misses", _state.explain_prev_config)


def installed() -> bool:
    with _state.lock:
        return _state.installed


def mark_warm() -> None:
    """Declare warmup over: every compile from here on is a steady-state
    recompile (flight ``recompile`` event + post-warmup counter)."""
    with _state.lock:
        _state.warm = True


def mark_cold() -> None:
    """Re-open warmup (a new model/config is about to compile on
    purpose — e.g. a second Trainer in the same process)."""
    with _state.lock:
        _state.warm = False


def is_warm() -> bool:
    with _state.lock:
        return _state.warm


def compile_count(fn: Optional[str] = None) -> int:
    """Total compiles observed (optionally for one function label)."""
    with _state.lock:
        if fn is None:
            return _state.total
        return _state.by_fn.get(fn, 0)


def post_warmup_count() -> int:
    with _state.lock:
        return _state.post_warmup


def persistent_cache_counts() -> Dict[str, int]:
    """Persistent-compilation-cache verdicts since install/reset: ``hits``
    are compile events served from ``jax_compilation_cache_dir``,
    ``misses`` went to the compiler and were written back.  Both 0 when
    no cache directory is configured (or a program compiled under the
    cache's minimum compile time)."""
    with _state.lock:
        return {"hits": _state.cache_hits, "misses": _state.cache_misses}


def counts_by_fn() -> Dict[str, int]:
    with _state.lock:
        return dict(_state.by_fn)


def events(last: Optional[int] = None) -> List[CompileEvent]:
    """The recorded compile events, oldest first (``last`` trims)."""
    with _state.lock:
        evs = list(_state.events)
    return evs[-last:] if last else evs


def recent_events_payload(last: int = 16) -> list:
    """JSON-safe tail of the compile ring — what a flight dump attaches
    so OOM/wedge forensics show the compile storm beside the steps."""
    return [e.as_dict() for e in events(last=last)]


def reset() -> None:
    """Clear counters/events (tests; the install state is untouched)."""
    with _state.lock:
        _state.events.clear()
        _state.seq = 0
        _state.total = 0
        _state.post_warmup = 0
        _state.warm = False
        _state.by_fn.clear()
        _state.pending_explanation = None
        _state.cache_hits = 0
        _state.cache_misses = 0


@contextlib.contextmanager
def expect_no_compiles(where: str = ""):
    """Assert a region compiles NOTHING — the steady-state invariant that
    replaces the old per-function ``_cache_size() == 1`` pins: stronger
    (process-wide, any function) and self-describing on failure."""
    if not installed():
        install()
    before = compile_count()
    yield
    after = compile_count()
    if after != before:
        fresh = events(last=after - before)
        detail = "; ".join(
            f"{e.fn} ({e.elapsed_ms:.1f}ms)"
            + (f" — {e.explanation.splitlines()[0]}" if e.explanation else "")
            for e in fresh
        )
        raise AssertionError(
            f"{after - before} unexpected compile(s)"
            + (f" in {where}" if where else "") + f": {detail}"
        )
