"""Telemetry spine (ml_trainer_tpu/telemetry/).

The contracts worth pinning:

* registry: thread-safe under concurrent writers, idempotent
  registration, Prometheus text exposition matches a golden string;
* spans: Chrome/Perfetto trace-event JSON loads, and same-thread spans
  nest by time containment (how Perfetto renders parent/child);
* flight recorder: bounded ring; an injected ``nan_grad`` FaultPlan
  with rollback produces a dump naming the offending step; an injected
  ``decode_wedge`` produces a serving dump naming the wedged engine
  step;
* step telemetry: ZERO extra compiled programs — the instrumented
  trainer's step compiles exactly once, like the bare trainer's
  (test-pinned cache size), and the trajectory is bit-identical;
* StepTimer: per-step percentiles (fenced, warmup-excluded);
* history.json: JSON-safe mirror written next to the pickle,
  preferred by ``load_history``;
* distributed observability (telemetry/cluster.py): single-host
  degenerate aggregation, straggler detection over an injected pod
  matrix, trace-time collective-comms byte accounting, the run-report
  emission at fit() end, the serving spec-acceptance histogram's real
  Prometheus exposition, and the ``desync_every_steps`` knob (the real
  2-process paths live in tests/test_multiprocess.py).
"""

import json
import os
import threading

import jax
import numpy as np
import pytest

from ml_trainer_tpu import Trainer, MLModel, load_history
from ml_trainer_tpu.data import SyntheticCIFAR10
from ml_trainer_tpu.resilience import faults
from ml_trainer_tpu.telemetry import (
    FlightRecorder,
    MetricsRegistry,
    prometheus_text,
    save_trace,
    span,
)
from ml_trainer_tpu.telemetry.flight import get_recorder
from ml_trainer_tpu.utils.functions import custom_pre_process_function


def make_trainer(model_dir, epochs=1, size=64, **kw):
    t = custom_pre_process_function()  # float batches: NaN-poisonable
    return Trainer(
        MLModel(),
        datasets=(SyntheticCIFAR10(size=size, seed=0, transform=t),
                  SyntheticCIFAR10(size=32, seed=1, transform=t)),
        epochs=epochs, batch_size=16, model_dir=str(model_dir),
        metric=None, lr=0.01, **kw,
    )


# ---------------------------------------------------------------- registry
def test_registry_thread_safety():
    """N writer threads hammering one counter/gauge/histogram: the
    counter lands on the exact total (a lost update would undercount),
    the histogram's count matches its observations."""
    r = MetricsRegistry()
    c = r.counter("hits_total", "hits", ("worker",))
    g = r.gauge("level")
    h = r.histogram("lat", buckets=(0.5, 1.0))
    n_threads, n_iter = 8, 2000

    def worker(i):
        child = c.labels(worker=str(i % 2))
        for k in range(n_iter):
            child.inc()
            g.set(k)
            h.observe(0.25 if k % 2 else 0.75)

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    total = sum(
        c.labels(worker=str(w)).get() for w in (0, 1)
    )
    assert total == n_threads * n_iter
    assert h.get() is None or True  # labeled access below
    hist = h._get(())
    assert hist["count"] == n_threads * n_iter


def test_registry_idempotent_and_type_checked():
    r = MetricsRegistry()
    a = r.counter("x_total", "first")
    b = r.counter("x_total", "second registration returns the first")
    assert a is b
    with pytest.raises(ValueError, match="already registered"):
        r.gauge("x_total")
    with pytest.raises(ValueError, match="metric name"):
        r.counter("bad name")


def test_prometheus_exposition_golden():
    """Pinned text exposition: a scraper-visible format change must be a
    deliberate diff in this golden, not an accident."""
    r = MetricsRegistry()
    c = r.counter("requests_total", "served requests", ("code",))
    c.labels(code=200).inc(3)
    c.labels(code=500).inc()
    r.gauge("queue_depth", "pending requests").set(7)
    h = r.histogram("step_seconds", "step latency", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    golden = (
        "# HELP requests_total served requests\n"
        "# TYPE requests_total counter\n"
        'requests_total{code="200"} 3\n'
        'requests_total{code="500"} 1\n'
        "# HELP queue_depth pending requests\n"
        "# TYPE queue_depth gauge\n"
        "queue_depth 7\n"
        "# HELP step_seconds step latency\n"
        "# TYPE step_seconds histogram\n"
        'step_seconds_bucket{le="0.1"} 1\n'
        'step_seconds_bucket{le="1"} 2\n'
        'step_seconds_bucket{le="+Inf"} 3\n'
        "step_seconds_sum 5.55\n"
        "step_seconds_count 3\n"
    )
    assert prometheus_text(r) == golden


# ------------------------------------------------------------------- spans
def test_perfetto_trace_loads_and_nests(tmp_path):
    from ml_trainer_tpu.telemetry.spans import clear_trace

    clear_trace()
    with span("outer", step=1):
        with span("inner"):
            pass
    path = save_trace(str(tmp_path / "trace.json"))
    events = json.load(open(path))["traceEvents"]
    by_name = {e["name"]: e for e in events}
    outer, inner = by_name["outer"], by_name["inner"]
    for e in (outer, inner):
        assert e["ph"] == "X" and e["dur"] >= 0
    # Same thread, inner contained in outer: how Perfetto nests.
    assert inner["tid"] == outer["tid"]
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3
    assert outer["args"] == {"step": 1}


def test_events_between_takes_a_monotonic_window_and_tells_a_wrapped_ring(
        monkeypatch):
    import collections
    import time

    from ml_trainer_tpu.telemetry import spans

    monkeypatch.setattr(spans, "_events", collections.deque(maxlen=4))
    base = time.monotonic()
    for i, name in enumerate(["a", "b", "a"]):
        spans.complete_event(name, base + i, base + i + 0.25)
    spans._events.append({"name": "marker", "ph": "i",   # not a complete
                          "ts": (base + 1 - spans._MONO_EPOCH) * 1e6})
    events, wrapped = spans.events_between(base + 0.5, base + 2.0)
    assert [e["name"] for e in events] == ["b", "a"] and not wrapped
    # On the trace clock, as complete_event puts them there.
    assert events[0]["ts"] == pytest.approx(
        (base + 1 - spans._MONO_EPOCH) * 1e6)
    assert events[0]["dur"] == pytest.approx(0.25e6)
    only_a, _ = spans.events_between(base, base + 3.0, names=("a",))
    assert [e["ts"] for e in only_a] == sorted(e["ts"] for e in only_a)
    assert [e["name"] for e in only_a] == ["a", "a"]
    # The ring is full; its oldest event ended before a late window's
    # start, so nothing of that window can have been dropped ...
    assert spans.events_between(base + 1.0, base + 3.0)[1] is False
    # ... and one more event drops "a"@0: a window from 0.5 may have lost
    # events (the oldest kept ends at 1.25), one from 1.5 has not.
    spans.complete_event("c", base + 3, base + 3.5)
    assert spans.events_between(base + 0.5, base + 4.0)[1] is True
    events, wrapped = spans.events_between(base + 1.5, base + 4.0)
    assert [e["name"] for e in events] == ["a", "c"] and not wrapped
    # A live span lands on the same clock.
    t0 = time.monotonic()
    with span("live"):
        pass
    live, _ = spans.events_between(t0, time.monotonic(), names=("live",))
    assert len(live) == 1


def test_fit_gives_one_dispatch_span_a_step_and_a_log_sync(tmp_path):
    from ml_trainer_tpu.telemetry.spans import clear_trace, trace_events

    clear_trace()
    t = make_trainer(tmp_path / "m", size=32, log_every_steps=2)
    t.fit()                                        # 32 rows of 16: two steps
    events = [e for e in trace_events() if e["ph"] == "X"]
    steps = [e for e in events if e["name"] == "train_step_dispatch"]
    assert [e["args"]["step"] for e in steps] == [1, 2]
    assert steps[0]["tid"] == steps[1]["tid"]
    assert steps[0]["ts"] + steps[0]["dur"] <= steps[1]["ts"]
    syncs = [e for e in events if e["name"] == "train_log_sync"]
    assert [e["args"]["step"] for e in syncs] == [2]
    assert syncs[0]["ts"] >= steps[1]["ts"] + steps[1]["dur"]


# --------------------------------------------------------- flight recorder
def test_flight_ring_bounded_and_dump(tmp_path):
    fr = FlightRecorder(capacity=4)
    for i in range(10):
        fr.record("step", step=i)
    recs = fr.records()
    assert [r["step"] for r in recs] == [6, 7, 8, 9]
    path = fr.dump("unit_test", out_dir=str(tmp_path), extra_field=1)
    payload = json.load(open(path))
    assert payload["reason"] == "unit_test"
    assert payload["extra_field"] == 1
    assert len(payload["records"]) == 4


def test_nan_grad_fault_dumps_flight_naming_step(tmp_path, monkeypatch):
    """The acceptance scenario: an injected ``nan_grad`` (with rollback
    armed) must leave a flight dump on disk naming the offending step —
    through the env-var plumbing (flight-dir redirect, JSONL sink), with
    the run's gauges, host spans and history ledger behind it."""
    from ml_trainer_tpu.telemetry import default_registry
    from ml_trainer_tpu.telemetry.spans import clear_trace, trace_events

    monkeypatch.setenv("ML_TRAINER_TPU_FLIGHT_DIR", str(tmp_path))
    sink = tmp_path / "metrics.jsonl"
    monkeypatch.setenv("ML_TRAINER_TPU_METRICS_JSONL", str(sink))
    get_recorder().clear()
    clear_trace()
    with faults.injected("nan_grad@step=3"):
        t = make_trainer(
            tmp_path / "m", telemetry=True, log_every_steps=1,
            rollback_bad_steps=1, save_history=True,
        )
        t.fit()
    assert t.rollbacks == 1
    assert default_registry().snapshot()["train_skipped_steps_total"] >= 1
    text = prometheus_text(default_registry())
    assert "# TYPE train_grad_norm gauge" in text
    assert all(ln.startswith("#") or " " in ln for ln in text.splitlines())
    records = [json.loads(ln) for ln in open(sink) if ln.strip()]
    assert any(r.get("kind") == "train_step" for r in records)
    assert {"data_load", "h2d", "ckpt_write"} <= {
        e["name"] for e in trace_events()
    }
    hist = load_history(str(tmp_path / "m"))
    assert hist["rollbacks"] == 1 and sum(hist["skipped_steps"]) == 1
    report = json.load(open(tmp_path / "m" / "run_report.json"))
    assert report["resilience"]["rollbacks"] == 1
    dumps = sorted(
        f for f in os.listdir(tmp_path) if f.startswith("flight_")
    )
    assert dumps, "nan_grad rollback produced no flight dump"
    payload = json.load(open(tmp_path / dumps[0]))
    assert payload["reason"] == "nan_rollback"
    assert payload["first_bad_step"] == 3
    kinds = [r["kind"] for r in payload["records"]]
    assert "nonfinite_steps" in kinds and "rollback" in kinds
    nf = next(r for r in payload["records"] if r["kind"] == "nonfinite_steps")
    assert nf["step"] == 3


def test_decode_wedge_fault_dumps_flight_naming_engine_step(
    tmp_path, monkeypatch
):
    """A wedged decode step trips the watchdog, which dumps the flight
    ring — its newest decode_step record names the wedged step."""
    from ml_trainer_tpu.models import get_model
    from ml_trainer_tpu.serving import EngineUnhealthy, Server

    monkeypatch.setenv("ML_TRAINER_TPU_FLIGHT_DIR", str(tmp_path))
    get_recorder().clear()
    model = get_model("gpt2_tiny", max_len=64)
    variables = model.init(
        {"params": jax.random.PRNGKey(0)}, np.zeros((1, 8), np.int32),
        train=False,
    )
    # Warm the compiled programs through a throwaway server so the
    # watchdog timeout only has to cover the wedge, not a compile.
    with Server(model, variables, max_batch=2,
                watchdog_timeout=None) as warm:
        warm.complete(np.arange(1, 6, dtype=np.int32), 4, timeout=300)
    with faults.injected("decode_wedge@step=2,secs=30") as plan:
        server = Server(model, variables, max_batch=2,
                        watchdog_timeout=1.0)
        try:
            stream = server.submit(np.arange(1, 6, dtype=np.int32), 16)
            with pytest.raises((RuntimeError, EngineUnhealthy)):
                stream.result(timeout=60)
            assert not server.healthy
        finally:
            plan.release_wedge()
            server.close()
    dumps = sorted(
        f for f in os.listdir(tmp_path) if f.startswith("flight_")
    )
    assert dumps, "watchdog trip produced no flight dump"
    payload = json.load(open(tmp_path / dumps[-1]))
    assert payload["reason"].startswith("serving_unhealthy")
    assert payload["engine_step"] == 2
    steps = [r for r in payload["records"] if r["kind"] == "decode_step"]
    assert steps and steps[-1]["engine_step"] == 2


# ---------------------------------------------------- step telemetry cost
def test_step_telemetry_zero_recompiles_and_identical_trajectory(tmp_path):
    """The acceptance pin: the instrumented train step compiles exactly
    as many programs as the bare one (one), across a full multi-epoch
    fit — and produces the bit-identical parameter trajectory."""
    from ml_trainer_tpu.telemetry import compile_watch

    compile_watch.install()
    before = compile_watch.compile_count("jit(train_step)")
    pw_before = compile_watch.post_warmup_count()
    bare = make_trainer(tmp_path / "bare", epochs=2)
    bare.fit()
    instr = make_trainer(tmp_path / "instr", epochs=2, telemetry=True)
    instr.fit()
    # The real recompile instrument (telemetry/compile_watch.py) replaces
    # the per-function _cache_size() pin: each trainer compiled its train
    # step exactly once across the 2-epoch fit, and nothing compiled
    # after the instrumented run's first epoch closed warmup (deltas —
    # the counters are process-cumulative).
    assert compile_watch.compile_count("jit(train_step)") == before + 2, (
        compile_watch.counts_by_fn()
    )
    assert compile_watch.post_warmup_count() == pw_before, (
        [e.as_dict() for e in compile_watch.events(last=4)]
    )
    for a, b in zip(
        jax.tree.leaves(bare.state.params),
        jax.tree.leaves(instr.state.params),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # The telemetry actually ran: gauges were published.
    from ml_trainer_tpu.telemetry import default_registry

    snap = default_registry().snapshot()
    assert snap.get("train_steps_total", 0) >= instr.steps_per_epoch


def test_multi_step_dispatch_carries_stats(tmp_path):
    """steps_per_execution > 1: the scanned dispatch returns the last
    step's stats and telemetry still compiles one multi-step program."""
    from ml_trainer_tpu.telemetry import compile_watch

    compile_watch.install()
    before = compile_watch.compile_count("jit(multi_step)")
    t = make_trainer(
        tmp_path / "multi", size=128, telemetry=True,
        steps_per_execution=4,
    )
    t.fit()
    assert compile_watch.compile_count("jit(multi_step)") == before + 1, (
        compile_watch.counts_by_fn()
    )
    from ml_trainer_tpu.telemetry import default_registry

    assert default_registry().snapshot()["train_param_norm"] > 0


# ------------------------------------------------------------- StepTimer
def test_steptimer_percentiles():
    import time as _time

    from ml_trainer_tpu.utils.profiler import StepTimer

    timer = StepTimer(warmup=2, record_steps=True)
    delays = [0.001, 0.001, 0.005, 0.001, 0.02, 0.001, 0.001, 0.001]
    for d in delays:
        _time.sleep(d)
        timer.tick(np.zeros(1), 1)
    p50, p99 = timer.p50(), timer.p99()
    assert p50 is not None and p99 is not None
    assert p99 >= p50
    assert p99 >= 0.015  # the 20ms outlier is in the tail
    assert timer.rate() > 0
    # Default mode records nothing: p50 stays None.
    assert StepTimer(warmup=1).p50() is None


# ---------------------------------------------------------- history.json
def test_history_json_mirror_and_preference(tmp_path):
    t = make_trainer(tmp_path / "h", save_history=True)
    t.fit()
    d = str(tmp_path / "h")
    assert os.path.exists(os.path.join(d, "history.pkl"))
    jpath = os.path.join(d, "history.json")
    assert os.path.exists(jpath)
    hist = json.load(open(jpath))
    assert hist["train_loss"] and "skipped_steps" in hist
    assert hist["rollbacks"] == 0
    # load_history prefers the JSON mirror: poison it with a marker and
    # check the marker comes back (the pickle would not carry it).
    hist["marker"] = "json_wins"
    json.dump(hist, open(jpath, "w"))
    assert load_history(d)["marker"] == "json_wins"
    # Without the mirror, the pickle still loads (the reference path).
    os.remove(jpath)
    assert load_history(d)["train_loss"] == hist["train_loss"]


# ------------------------------------------------- distributed observability
def test_cluster_single_host_aggregation_and_report(tmp_path):
    """Degenerate one-host 'pod': heartbeat -> sync publishes
    cluster_*{host=0} without any collective, no straggler can fire, and
    the run report distills the registry into json + markdown."""
    from ml_trainer_tpu.telemetry import (
        ClusterTelemetry,
        HEARTBEAT_FIELDS,
        write_run_report,
    )

    r = MetricsRegistry()
    fr = FlightRecorder()
    ct = ClusterTelemetry(registry=r, flight=fr)
    ct.heartbeat(last_step=10, step_ms_p50=4.0, step_ms_p99=9.0,
                 samples_per_sec=1200.0)
    gathered = ct.sync(step=10)
    assert gathered.shape == (1, len(HEARTBEAT_FIELDS))
    snap = r.snapshot()
    assert snap["cluster_last_step{host=0}"] == 10.0
    assert snap["cluster_step_ms_p50{host=0}"] == 4.0
    assert snap["cluster_hosts"] == 1
    # One host: nothing to straggle behind.
    assert not any(
        k.startswith("cluster_straggler_events_total") for k in snap
    )
    report = write_run_report(
        str(tmp_path), history={"skipped_steps": [0], "rollbacks": 0},
        registry=r, flight=fr,
    )
    payload = json.load(open(tmp_path / "run_report.json"))
    assert payload["hosts"]["0"]["step_ms_p50"] == 4.0
    assert payload["resilience"]["rollbacks"] == 0
    md = open(tmp_path / "run_report.md").read()
    assert "Per-host heartbeat" in md and "Resilience ledger" in md
    assert report["paths"]["json"].endswith("run_report.json")

    with pytest.raises(ValueError, match="straggler_factor"):
        ClusterTelemetry(registry=r, straggler_factor=1.0)
    with pytest.raises(ValueError, match="unknown heartbeat"):
        ct.heartbeat(nonsense=1.0)


def test_cluster_straggler_detector_on_injected_pod():
    """A fabricated 2-host heartbeat matrix with one slow host must fire
    the counter + flight event naming that host; symmetric times must
    not.  The lower-median rule: on 2 hosts the slow one is compared
    against the FAST one."""
    import numpy as np

    from ml_trainer_tpu.telemetry import ClusterTelemetry, HEARTBEAT_FIELDS

    r = MetricsRegistry()
    fr = FlightRecorder()
    ct = ClusterTelemetry(registry=r, flight=fr, straggler_factor=2.0)
    f = len(HEARTBEAT_FIELDS)
    i50 = HEARTBEAT_FIELDS.index("step_ms_p50")
    even = np.zeros((2, f))
    even[:, i50] = (10.0, 11.0)
    ct._ingest(even, step=5)
    assert not any(
        k.startswith("cluster_straggler_events_total")
        for k in r.snapshot()
    )
    skewed = np.zeros((2, f))
    skewed[:, i50] = (10.0, 25.0)  # 2.5x the fast host
    ct._ingest(skewed, step=7)
    snap = r.snapshot()
    assert snap["cluster_straggler_events_total{host=1}"] == 1
    ev = [rec for rec in fr.records() if rec["kind"] == "straggler"]
    assert ev and ev[-1]["host"] == 1 and ev[-1]["step"] == 7
    assert ev[-1]["cluster_median_ms"] == 10.0
    # Hosts with no data (step_ms 0) neither straggle nor skew the median.
    sparse = np.zeros((2, f))
    sparse[0, i50] = 10.0
    ct._ingest(sparse, step=9)
    assert r.snapshot()["cluster_straggler_events_total{host=1}"] == 1


def test_comm_accounting_formulas_and_traced_bytes():
    """The analytic per-op byte formulas, and the trace-time recording
    through a real shard_map collective on the simulated mesh: zero
    runtime machinery, the gauges carry the analytic number."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ml_trainer_tpu.parallel import create_mesh
    from ml_trainer_tpu.parallel.collectives import psum
    from ml_trainer_tpu.parallel.comm_stats import (
        collective_bytes,
        comm_bytes,
        comm_calls,
        reset_comm_stats,
    )
    from jax import shard_map

    # Formula pins (size=1024 bytes, n=4).
    assert collective_bytes("psum", 1024, 4) == 2 * 1024 * 3 / 4
    assert collective_bytes("all_gather", 1024, 4) == 1024 * 3
    assert collective_bytes("reduce_scatter", 1024, 4) == 1024 * 3 / 4
    assert collective_bytes("ppermute", 1024, 4) == 1024
    assert collective_bytes("all_to_all", 1024, 4) == 1024 * 3 / 4
    assert collective_bytes("psum", 1024, 1) == 0.0  # no peers, no bytes
    with pytest.raises(ValueError, match="unknown collective"):
        collective_bytes("gossip", 1, 2)

    reset_comm_stats()
    mesh = create_mesh({"data": 4}, devices=jax.devices()[:4])
    step = jax.jit(shard_map(
        lambda x: psum(x, "data"), mesh=mesh,
        in_specs=P("data"), out_specs=P(),
    ))
    step(jnp.ones((8, 4), jnp.float32)).block_until_ready()
    # Per-shard input is (2, 4) f32 = 32 bytes -> ring all-reduce 48.
    assert comm_bytes() == {"psum": 48.0}
    assert comm_calls() == {"psum": 1}
    from ml_trainer_tpu.telemetry import default_registry

    assert default_registry().snapshot()[
        "comm_bytes_total{op=psum}"
    ] == 48.0
    reset_comm_stats()
    assert comm_bytes() == {}
    assert default_registry().snapshot()[
        "comm_bytes_total{op=psum}"
    ] == 0.0


def test_trainer_writes_run_report_and_desync_knob(tmp_path):
    """fit() with telemetry ends by writing run_report.json/.md (the
    degenerate single-host aggregation included); the desync knobs
    validate and are harmless no-ops single-process."""
    with pytest.raises(ValueError, match="desync_every_steps"):
        make_trainer(tmp_path / "bad", desync_every_steps=0)
    with pytest.raises(ValueError, match="straggler_factor"):
        make_trainer(tmp_path / "bad2", straggler_factor=1.0)
    t = make_trainer(
        tmp_path / "m", telemetry=True, desync_every_steps=2,
    )
    t.fit()
    payload = json.load(open(tmp_path / "m" / "run_report.json"))
    assert payload["reason"] == "completed"
    assert payload["hosts"]["0"]["last_step"] == t.steps_per_epoch
    assert payload["resilience"]["rollbacks"] == 0
    assert "checkpoint_writes" in payload
    assert os.path.exists(tmp_path / "m" / "run_report.md")
    from ml_trainer_tpu.telemetry import default_registry

    snap = default_registry().snapshot()
    assert snap["cluster_hosts"] == 1
    assert snap["cluster_syncs_total"] >= 1


def test_serving_spec_histogram_real_exposition():
    """The spec acceptance distribution publishes as the registry's REAL
    Histogram (cumulative le-buckets, histogram_quantile-able), and
    repeated publishes observe only deltas — no double counting."""
    from ml_trainer_tpu.serving.metrics import ServingMetrics

    m = ServingMetrics()
    m.record_spec([0, 2, 4], draft_k=4)
    r = MetricsRegistry()
    m.publish(r)
    h = r.snapshot()
    assert h["serving_spec_accept_count"] == 3
    assert h["serving_spec_accept_sum"] == 6.0
    m.publish(r)  # idempotent: same cumulative snapshot, no new samples
    assert r.snapshot()["serving_spec_accept_count"] == 3
    m.record_spec([4], draft_k=4)
    m.publish(r)
    assert r.snapshot()["serving_spec_accept_count"] == 4
    text = prometheus_text(r)
    assert "# TYPE serving_spec_accept histogram" in text
    assert 'serving_spec_accept_bucket{le="0"} 1' in text
    assert 'serving_spec_accept_bucket{le="+Inf"} 4' in text
    # The JSON snapshot shape is unchanged (dashboards keep working).
    assert m.snapshot()["spec_accept_hist"] == {"0": 1, "2": 1, "4": 2}


# ------------------------------------------------------------------ flops
def test_analytic_flops_plausible():
    """The analytic accounting must agree with the known published
    numbers within tolerance: ResNet-50 fwd ~8.2 GFLOPs/img @224 (2*MAC
    convention), ViT-B/16 ~35, GPT-2-124M train ~6N per token."""
    from ml_trainer_tpu.models import get_model
    from ml_trainer_tpu.telemetry.flops import (
        fwd_flops,
        train_step_flops,
    )

    r50 = fwd_flops(get_model("resnet50"), (1, 224, 224, 3))
    assert 7e9 < r50 < 9.5e9
    vit = fwd_flops(get_model("vit_b16"), (1, 224, 224, 3))
    assert 30e9 < vit < 40e9
    gpt2 = train_step_flops(get_model("gpt2"), (1, 1024))
    # 6 * ~163M matmul params (incl. the tied head) * 1024 tokens, plus
    # attention: the right order of magnitude band.
    assert 700e9 < gpt2 < 1200e9
    assert train_step_flops("mlmodel", (32, 32, 32, 3)) > 0
    # Unknown family: None, never zero.
    class Oddball:  # noqa: local stub, not a registered model
        pass

    assert train_step_flops(Oddball(), (1, 8)) is None
