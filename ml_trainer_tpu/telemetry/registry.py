"""Metrics registry: thread-safe counters / gauges / histograms with labels.

The in-process analog of a Prometheus client library, dependency-free
(the container is zero-egress): every layer registers its instruments
against one :class:`MetricsRegistry` — usually the process-wide
:func:`default_registry` — and the exporters (``export.py``) turn the
whole registry into Prometheus text exposition or one JSONL record.

Design points:

* **Idempotent registration.**  ``registry.counter("x", ...)`` returns
  the existing instrument when ``x`` is already registered (with a type
  check), so the trainer, the serving engine, and tests can all say
  "give me the counter" without coordinating creation order.
* **Labels are call-site cheap.**  ``c.labels(model="gpt2").inc()``
  resolves to a child keyed by the label values; unlabeled instruments
  skip the child map entirely.
* **One lock per instrument**, not a global registry lock, so the
  serving engine's per-step ``inc`` never contends with the trainer's
  epoch-end gauge writes.
"""

from __future__ import annotations

import threading
from typing import Dict, Optional, Sequence, Tuple

# Prometheus-ish default latency buckets (seconds), wide enough to cover
# both a CPU LeNet step (~ms) and a large model's compile (~minutes).
DEFAULT_BUCKETS = (
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
    1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0,
)


def _validate_name(name: str) -> str:
    if not name or not all(c.isalnum() or c in "_:" for c in name):
        raise ValueError(
            f"metric name must be non-empty [a-zA-Z0-9_:]+, got {name!r}"
        )
    return name


class _Child:
    """One (instrument, label-values) time series."""

    __slots__ = ("_metric", "_key")

    def __init__(self, metric: "_Metric", key: Tuple[str, ...]):
        self._metric = metric
        self._key = key

    def inc(self, amount: float = 1.0) -> None:
        self._metric._inc(self._key, amount)

    def set(self, value: float) -> None:
        self._metric._set(self._key, value)

    def observe(self, value: float) -> None:
        self._metric._observe(self._key, value)

    def get(self):
        return self._metric._get(self._key)


class _Metric:
    """Base instrument: a dict of label-values -> series under one lock."""

    kind = "untyped"

    def __init__(self, name: str, help: str = "",
                 labelnames: Sequence[str] = ()):
        self.name = _validate_name(name)
        self.help = help
        self.labelnames = tuple(labelnames)
        for ln in self.labelnames:
            _validate_name(ln)
        self._lock = threading.Lock()
        self._series: Dict[Tuple[str, ...], object] = {}
        if not self.labelnames:
            # Pre-create the single unlabeled series so reads never miss.
            self._series[()] = self._new_series()

    def _new_series(self):
        return 0.0

    def labels(self, **labelvalues) -> _Child:
        if set(labelvalues) != set(self.labelnames):
            raise ValueError(
                f"{self.name}: labels() needs exactly {self.labelnames}, "
                f"got {tuple(labelvalues)}"
            )
        key = tuple(str(labelvalues[ln]) for ln in self.labelnames)
        with self._lock:
            if key not in self._series:
                self._series[key] = self._new_series()
        return _Child(self, key)

    def _require_unlabeled(self):
        if self.labelnames:
            raise ValueError(
                f"{self.name} carries labels {self.labelnames}; "
                "use .labels(...) first"
            )

    # Unlabeled conveniences -------------------------------------------
    def inc(self, amount: float = 1.0) -> None:
        self._require_unlabeled()
        self._inc((), amount)

    def set(self, value: float) -> None:
        self._require_unlabeled()
        self._set((), value)

    def observe(self, value: float) -> None:
        self._require_unlabeled()
        self._observe((), value)

    def get(self):
        self._require_unlabeled()
        return self._get((), )

    # Series ops (overridden per kind) ---------------------------------
    def _inc(self, key, amount):
        raise NotImplementedError

    def _set(self, key, value):
        raise NotImplementedError

    def _observe(self, key, value):
        raise NotImplementedError

    def _get(self, key):
        with self._lock:
            return self._series.get(key, 0.0)

    def series(self) -> Dict[Tuple[str, ...], object]:
        """Point-in-time copy of every (label-values -> value) series."""
        with self._lock:
            return dict(self._series)


class Counter(_Metric):
    """Monotonic count.  ``inc`` only; negative increments are rejected."""

    kind = "counter"

    def _inc(self, key, amount):
        if amount < 0:
            raise ValueError(f"{self.name}: counters only go up "
                             f"(inc {amount})")
        with self._lock:
            self._series[key] = self._series.get(key, 0.0) + amount

    def _set(self, key, value):
        raise TypeError(f"{self.name} is a counter; use inc()")

    def _observe(self, key, value):
        raise TypeError(f"{self.name} is a counter; use inc()")


class Gauge(_Metric):
    """A value that can go anywhere: set() or inc() (either sign)."""

    kind = "gauge"

    def _inc(self, key, amount):
        with self._lock:
            self._series[key] = self._series.get(key, 0.0) + amount

    def _set(self, key, value):
        with self._lock:
            self._series[key] = float(value)

    def _observe(self, key, value):
        raise TypeError(f"{self.name} is a gauge; use set()/inc()")


class _HistSeries:
    __slots__ = ("counts", "total", "count")

    def __init__(self, n_buckets: int):
        self.counts = [0] * n_buckets  # cumulative at exposition time
        self.total = 0.0
        self.count = 0


class Histogram(_Metric):
    """Bucketed distribution (Prometheus ``le`` semantics: each bucket
    counts observations <= its upper bound, plus the implicit +Inf)."""

    kind = "histogram"

    def __init__(self, name: str, help: str = "",
                 labelnames: Sequence[str] = (),
                 buckets: Sequence[float] = DEFAULT_BUCKETS):
        if not buckets or list(buckets) != sorted(buckets):
            raise ValueError(
                f"{name}: buckets must be a non-empty ascending sequence"
            )
        self.buckets = tuple(float(b) for b in buckets)
        super().__init__(name, help, labelnames)

    def _new_series(self):
        return _HistSeries(len(self.buckets))

    def _inc(self, key, amount):
        raise TypeError(f"{self.name} is a histogram; use observe()")

    def _set(self, key, value):
        raise TypeError(f"{self.name} is a histogram; use observe()")

    def _observe(self, key, value):
        value = float(value)
        with self._lock:
            s = self._series.get(key)
            if s is None:
                s = self._series[key] = self._new_series()
            for i, ub in enumerate(self.buckets):
                if value <= ub:
                    s.counts[i] += 1
                    break
            s.total += value
            s.count += 1

    def _get(self, key):
        with self._lock:
            s = self._series.get(key)
            if s is None:
                return None
            return {"count": s.count, "sum": s.total,
                    "buckets": list(s.counts)}


class MetricsRegistry:
    """A named collection of instruments with idempotent registration."""

    def __init__(self):
        self._lock = threading.Lock()
        self._metrics: Dict[str, _Metric] = {}

    def _register(self, cls, name, help, labelnames, **kw):
        with self._lock:
            existing = self._metrics.get(name)
            if existing is not None:
                if type(existing) is not cls or (
                    tuple(labelnames) != existing.labelnames
                ):
                    raise ValueError(
                        f"metric {name!r} already registered as "
                        f"{existing.kind} with labels {existing.labelnames}"
                    )
                return existing
            metric = cls(name, help, labelnames, **kw)
            self._metrics[name] = metric
            return metric

    def counter(self, name: str, help: str = "",
                labelnames: Sequence[str] = ()) -> Counter:
        return self._register(Counter, name, help, labelnames)

    def gauge(self, name: str, help: str = "",
              labelnames: Sequence[str] = ()) -> Gauge:
        return self._register(Gauge, name, help, labelnames)

    def histogram(self, name: str, help: str = "",
                  labelnames: Sequence[str] = (),
                  buckets: Sequence[float] = DEFAULT_BUCKETS) -> Histogram:
        return self._register(Histogram, name, help, labelnames,
                              buckets=buckets)

    def collect(self):
        """Instruments in registration order (stable exposition)."""
        with self._lock:
            return list(self._metrics.values())

    def snapshot(self) -> dict:
        """JSON-safe flat view: ``name`` (or ``name{a=b}``) -> value.
        Histograms flatten to ``name_count`` / ``name_sum``."""
        out: dict = {}
        for m in self.collect():
            for key, _ in sorted(m.series().items()):
                suffix = (
                    "{" + ",".join(
                        f"{ln}={lv}" for ln, lv in zip(m.labelnames, key)
                    ) + "}" if key else ""
                )
                if m.kind == "histogram":
                    h = m._get(key)
                    out[f"{m.name}_count{suffix}"] = h["count"]
                    out[f"{m.name}_sum{suffix}"] = round(h["sum"], 9)
                else:
                    out[f"{m.name}{suffix}"] = m._get(key)
        return out

    def prometheus_text(self) -> str:
        from ml_trainer_tpu.telemetry.export import prometheus_text

        return prometheus_text(self)


# -- process-wide default registry --------------------------------------
_default: Optional[MetricsRegistry] = None
_default_lock = threading.Lock()


def default_registry() -> MetricsRegistry:
    """The process-wide registry every layer publishes into by default."""
    global _default
    with _default_lock:
        if _default is None:
            _default = MetricsRegistry()
        return _default


def reset_default_registry() -> None:
    """Drop the process-wide registry (tests only — live handles held by
    long-lived objects keep publishing into the old one)."""
    global _default
    with _default_lock:
        _default = None
