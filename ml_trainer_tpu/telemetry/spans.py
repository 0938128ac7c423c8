"""Host-side span tracing: Chrome/Perfetto trace events + profile windows.

``span("data_load")`` times a host region and records one Chrome
trace-event (``ph: "X"`` complete event) into a bounded process-wide
buffer; ``save_trace(path)`` writes the buffer as ``{"traceEvents":
[...]}`` JSON that chrome://tracing and ui.perfetto.dev load directly.
Events on the same thread nest by time containment, so a
``span("ckpt_write")`` inside a ``span("epoch")`` renders as a child.

Every span also enters ``utils.profiler.annotate`` (a
``jax.profiler.TraceAnnotation``), so when a ``jax.profiler`` device
trace is live the SAME names appear on the XLA timeline — host spans and
device traces line up by construction.

:class:`StepProfiler` is the on-demand ``jax.profiler`` window: a layer
calls ``on_step(step)`` once per step, and a window of K steps starts
when

* the env var ``ML_TRAINER_TPU_PROFILE`` is ``"<start>:<count>[:logdir]"``
  (armed at construction), or
* a trigger file named by ``ML_TRAINER_TPU_PROFILE_TRIGGER`` appears
  (its first line is ``<count>[:logdir]``; the file is consumed), or
* ``request(count, logdir)`` is called programmatically — the serving
  admin endpoint's path.
"""

from __future__ import annotations

import collections
import contextlib
import json
import os
import threading
import time
from typing import Optional

from ml_trainer_tpu.utils.logging import get_logger
from ml_trainer_tpu.utils.profiler import annotate

logger = get_logger("ml_trainer_tpu.telemetry")

# Trace clock: microseconds since process start (Chrome wants µs; a
# perf_counter epoch keeps values small and monotonic).  The monotonic
# epoch is captured in the same instant so timestamps recorded with
# ``time.monotonic()`` elsewhere (request lifecycle stamps — the
# deadline clock) can be converted onto the trace timeline.
_EPOCH = time.perf_counter()
_MONO_EPOCH = time.monotonic()

_MAX_EVENTS = 100_000
_events: collections.deque = collections.deque(maxlen=_MAX_EVENTS)
_events_lock = threading.Lock()


def _now_us() -> float:
    return (time.perf_counter() - _EPOCH) * 1e6


@contextlib.contextmanager
def span(name: str, category: str = "host", **args):
    """Time a host region: one Chrome complete event + an XLA trace
    annotation.  ``args`` (JSON-safe values) land in the event's
    ``args`` payload — visible in the Perfetto detail pane; the block
    gets the dict (``as out``) to set what only its end knows."""
    t0 = _now_us()
    with annotate(name):
        try:
            yield args
        finally:
            t1 = _now_us()
            ev = {
                "name": name,
                "cat": category,
                "ph": "X",
                "ts": t0,
                "dur": t1 - t0,
                "pid": os.getpid(),
                "tid": threading.get_ident(),
            }
            if args:
                ev["args"] = args
            with _events_lock:
                _events.append(ev)


def complete_event(name: str, start_mono: float, end_mono: float,
                   category: str = "host", **args) -> None:
    """Record a RETROSPECTIVE complete event from ``time.monotonic()``
    stamps — how a request's lifecycle (submit → queue → prefill →
    decode → finish), known only once it ends, lands on the trace
    timeline as properly nested spans.  Events emitted from one thread
    with containing timestamps nest in Perfetto exactly like live
    ``span()`` regions."""
    t0 = (start_mono - _MONO_EPOCH) * 1e6
    ev = {
        "name": name,
        "cat": category,
        "ph": "X",
        "ts": t0,
        "dur": max((end_mono - start_mono) * 1e6, 0.0),
        "pid": os.getpid(),
        "tid": threading.get_ident(),
    }
    if args:
        ev["args"] = args
    with _events_lock:
        _events.append(ev)


def trace_events() -> list:
    """Point-in-time copy of the buffered events (oldest first)."""
    with _events_lock:
        return list(_events)


def events_between(mono_t0: float, mono_t1: float, names=None) -> tuple:
    """``(events, wrapped)``: the complete events whose START lies in
    ``[mono_t0, mono_t1]`` (``time.monotonic()`` seconds, put on the
    trace clock as ``complete_event`` does), oldest start first, only
    those named in ``names`` when given.  ``wrapped`` says the ring was
    full and its oldest event ended after ``mono_t0``: events of the
    window may have been dropped, the answer is partial, and a reader
    that wants a statistic of the window must report nothing."""
    lo = (mono_t0 - _MONO_EPOCH) * 1e6
    hi = (mono_t1 - _MONO_EPOCH) * 1e6
    with _events_lock:  # copy, then filter: span writers wait on this lock
        events = list(_events)
        full = len(events) == _events.maxlen
    wrapped = full and events[0]["ts"] + events[0].get("dur", 0.0) > lo
    found = [
        ev for ev in events
        if ev["ph"] == "X" and lo <= ev["ts"] <= hi
        and (names is None or ev["name"] in names)
    ]
    found.sort(key=lambda ev: ev["ts"])
    return found, wrapped


def clock_payload() -> dict:
    """This process's trace-clock identity, JSON-safe — what the fleet
    clock handshake exchanges (telemetry/federation.py): the trace
    clock's "now" (the NTP-style sample a caller brackets with its own
    stamps) and the raw monotonic epoch (exact cross-process alignment
    when CLOCK_MONOTONIC is machine-shared, which Linux guarantees)."""
    return {
        "pid": os.getpid(),
        "trace_now_us": _now_us(),
        "mono_epoch": _MONO_EPOCH,
    }


def trace_payload(name: str = "") -> dict:
    """The span buffer plus clock identity — one process's reply to the
    fleet plane's ``GET /trace`` (serving/api.py): everything
    ``Router.save_fleet_trace()`` needs to place this process's lane on
    the merged timeline."""
    payload = clock_payload()
    payload["name"] = name
    payload["events"] = trace_events()
    return payload


def clear_trace() -> None:
    with _events_lock:
        _events.clear()


def save_trace(path: str) -> str:
    """Write the span buffer as Chrome/Perfetto trace-event JSON."""
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    payload = {
        "traceEvents": trace_events(),
        "displayTimeUnit": "ms",
    }
    tmp = path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as fp:
        json.dump(payload, fp)
    os.replace(tmp, path)
    return path


# -- on-demand jax.profiler windows -------------------------------------

PROFILE_ENV = "ML_TRAINER_TPU_PROFILE"
PROFILE_TRIGGER_ENV = "ML_TRAINER_TPU_PROFILE_TRIGGER"
_DEFAULT_LOGDIR = "/tmp/ml_trainer_tpu_profile"


class StepProfiler:
    """Profile steps N..N+K on demand, without restarting the job.

    Thread-safe: ``request()`` may come from any thread (the serving
    admin endpoint), ``on_step()`` from the step-driving thread.  Only
    one window runs at a time; overlapping requests are ignored with a
    log line (``jax.profiler`` cannot nest traces)."""

    def __init__(self, name: str = "train"):
        self.name = name
        self._lock = threading.Lock()
        self._pending: Optional[tuple] = None  # (count, logdir)
        self._active_left = 0
        self._active_logdir: Optional[str] = None
        env = os.environ.get(PROFILE_ENV, "")
        if env:
            try:
                parts = env.split(":", 2)
                start, count = int(parts[0]), int(parts[1])
                logdir = parts[2] if len(parts) > 2 else _DEFAULT_LOGDIR
                self._env_window = (start, count, logdir)
            except (ValueError, IndexError):
                logger.warning(
                    f"ignoring malformed {PROFILE_ENV}={env!r} "
                    "(expected start:count[:logdir])"
                )
                self._env_window = None
        else:
            self._env_window = None

    def request(self, count: int, logdir: Optional[str] = None) -> bool:
        """Arm a window: the next ``count`` steps are traced.  Returns
        False (and changes nothing) when a window is already pending or
        running."""
        if count < 1:
            raise ValueError(f"count must be >= 1, got {count}")
        with self._lock:
            if self._pending is not None or self._active_left > 0:
                return False
            self._pending = (int(count), logdir or _DEFAULT_LOGDIR)
            return True

    def _check_trigger_file(self) -> None:
        path = os.environ.get(PROFILE_TRIGGER_ENV, "")
        if not path or not os.path.exists(path):
            return
        try:
            with open(path) as fp:
                first = (fp.readline() or "").strip()
            os.remove(path)  # consumed — one window per touch
        except OSError:
            return
        count, _, logdir = first.partition(":")
        try:
            self.request(int(count or 1), logdir or None)
        except ValueError:
            logger.warning(
                f"ignoring malformed profile trigger {first!r} "
                "(expected count[:logdir])"
            )

    def on_step(self, step: int) -> None:
        """Called once per step by the owning loop.  Starts/stops the
        ``jax.profiler`` trace at window boundaries; free when idle."""
        if self._env_window is not None and step == self._env_window[0]:
            self.request(self._env_window[1], self._env_window[2])
        if os.environ.get(PROFILE_TRIGGER_ENV):
            self._check_trigger_file()
        with self._lock:
            start, stop = False, False
            if self._active_left > 0:
                self._active_left -= 1
                if self._active_left == 0:
                    stop = True
            elif self._pending is not None:
                count, logdir = self._pending
                self._pending = None
                self._active_left = count
                self._active_logdir = logdir
                start = True
        # The profiler calls run outside the lock: start_trace can block.
        if start:
            import jax

            logdir = os.path.join(
                self._active_logdir, f"{self.name}_step{step}"
            )
            try:
                jax.profiler.start_trace(logdir)
                logger.info(
                    "profile_window_start", step=step, logdir=logdir
                )
            except Exception as e:  # a live trace elsewhere: skip, don't die
                logger.warning(f"profile window failed to start: {e}")
                with self._lock:
                    self._active_left = 0
        if stop:
            import jax

            try:
                jax.profiler.stop_trace()
                logger.info("profile_window_stop", step=step)
            except Exception as e:
                logger.warning(f"profile window failed to stop: {e}")
