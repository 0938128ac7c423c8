"""Unified telemetry spine — the one subsystem every layer reports into.

Four parts (docs/observability.md):

* **registry** — thread-safe counters / gauges / histograms with labels,
  a process-wide default registry, Prometheus text exposition and a
  JSONL sink (``registry.py`` / ``export.py``).  The trainer and the
  serving stack both publish here, so one scrape endpoint (or one JSONL
  tail) covers the whole process.
* **train step telemetry** — grad-norm / param-norm / update-ratio
  stats accumulated ON-DEVICE inside the compiled train step (same
  no-host-sync discipline as the all-finite guard; zero extra compiled
  programs), fetched at the trainer's existing ``log_every`` sync
  cadence and emitted as structured events + registry gauges alongside
  samples/s, tokens/s and an analytic MFU estimate
  (``train_metrics.py`` + ``flops.py``).
* **span tracing** — host-side spans emitting Chrome/Perfetto
  trace-event JSON, composable with ``utils.profiler.annotate`` so host
  spans and XLA device traces line up; plus on-demand ``jax.profiler``
  windows triggered by env/file flag or the serving admin endpoint
  (``spans.py``).
* **flight recorder** — a bounded ring of the last N step records and
  events, dumped to ``flight_<ts>.json`` on NaN-rollback, preemption,
  watchdog trip, or unhandled exception — the crash forensics a
  post-mortem needs when the logs are gone (``flight.py``).
* **cluster aggregation** — per-host heartbeats allgathered into
  ``cluster_*{host=...}`` gauges on every host, a straggler detector
  over the fenced step-time percentiles, desync forensics
  (``parallel/desync.py`` publishes fingerprints here), analytic
  collective-comms accounting (``parallel/comm_stats.py``), and the
  end-of-run ``run_report.json``/``.md`` distillation (``cluster.py``).
* **memory / goodput / recompile pillar** — the analytic per-device
  HBM ledger with live cross-check and fit-or-OOM planner
  (``memory.py``), the wall-clock-decomposition goodput ledger behind
  ``train_goodput_fraction`` (``goodput.py``), and compile forensics on
  JAX's own compilation path — ``compile_events_total{fn=}``, flight
  ``recompile`` events naming the offending shape
  (``compile_watch.py``).
* **watchtower** — the in-process time-series store: bounded per-series
  rings sampled from the registry at the existing publish cadences,
  windowed ``rate()`` / ``quantile_over_time()`` queries, declarative
  :class:`~.alerts.AlertRule` evaluation (threshold / rate-of-change /
  burn / absent-series; the autoscaler, deploy-canary and straggler
  watchers are rules on this engine), a stdlib-only live HTML dashboard
  (``GET /dash``), and snapshots into incident bundles and the run
  report (``watchtower.py`` / ``alerts.py``).
"""

from ml_trainer_tpu.telemetry.cluster import (
    HEARTBEAT_FIELDS,
    ClusterTelemetry,
    write_run_report,
)
from ml_trainer_tpu.telemetry.alerts import (
    AlertEngine,
    AlertRule,
    default_fleet_rules,
)
from ml_trainer_tpu.telemetry.export import (
    JsonlSink,
    prometheus_text,
    read_sink_records,
)
from ml_trainer_tpu.telemetry.flight import (
    FLIGHT_DIR_ENV,
    FlightRecorder,
    get_recorder,
)
from ml_trainer_tpu.telemetry import compile_watch, goodput, memory
from ml_trainer_tpu.telemetry.flops import (
    chip_hbm_capacity_bytes,
    chip_peak_flops,
    train_step_flops,
)
from ml_trainer_tpu.telemetry.goodput import GoodputMeter
from ml_trainer_tpu.telemetry.memory import (
    MemoryLedger,
    live_memory_snapshot,
    plan_train_memory,
    train_ledger,
)
from ml_trainer_tpu.telemetry.registry import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    default_registry,
)
from ml_trainer_tpu.telemetry.spans import (
    StepProfiler,
    save_trace,
    span,
    trace_events,
)
from ml_trainer_tpu.telemetry.train_metrics import TrainTelemetry
from ml_trainer_tpu.telemetry.watchtower import (
    TimeSeriesStore,
    default_store,
    install_flight_context,
    render_dashboard,
    reset_default_store,
    save_dashboard,
    watch_context,
)

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "default_registry",
    "prometheus_text",
    "JsonlSink",
    "span",
    "save_trace",
    "trace_events",
    "StepProfiler",
    "FlightRecorder",
    "get_recorder",
    "FLIGHT_DIR_ENV",
    "chip_peak_flops",
    "chip_hbm_capacity_bytes",
    "train_step_flops",
    "compile_watch",
    "goodput",
    "memory",
    "GoodputMeter",
    "MemoryLedger",
    "live_memory_snapshot",
    "plan_train_memory",
    "train_ledger",
    "TrainTelemetry",
    "ClusterTelemetry",
    "HEARTBEAT_FIELDS",
    "write_run_report",
    "read_sink_records",
    "TimeSeriesStore",
    "default_store",
    "reset_default_store",
    "watch_context",
    "install_flight_context",
    "render_dashboard",
    "save_dashboard",
    "AlertRule",
    "AlertEngine",
    "default_fleet_rules",
]
