"""Watchtower (telemetry/watchtower.py + alerts.py) — the fourth
observability pillar's contracts:

* TSDB: per-series rings are BOUNDED (oldest point evicted), the
  windowed arithmetic (``rate`` reset-aware, ``delta``, ``avg``,
  ``quantile_over_time`` via cumulative bucket deltas) matches hand
  computation, registry-sampled and exposition-ingested series share
  keys, and concurrent samplers/queriers never corrupt the store;
* alert engine: the level-rule state machine (pending -> firing after
  ``for_s``/``for_count`` -> resolved) on a fake clock, per-label-group
  evaluation, ``absent()`` rules, event-mode rules with action
  callbacks, and the engine instruments
  (``alert_active{rule=}`` / ``alerts_fired_total{rule=}``);
* watcher parity: the straggler watcher is a declarative event rule on
  the cluster engine — same counter/flight behavior PLUS alert history
  (the autoscaler/deploy re-expressions are pinned tick-by-tick by
  tests/test_overload.py and tests/test_deploy.py);
* dashboard: one self-contained HTML page (inline SVG sparklines, no
  assets), alert table included, hostile titles escaped;
* flight context: dumps carry the last-N trend of the allowlisted
  series;
* the trainer pin: a fit with telemetry on (which now samples the
  process store every log-sync) compiles NOTHING extra and yields the
  bit-identical trajectory, while the store actually fills;
* JSONL sink rotation: ``max_bytes`` rotates segments + sidecar index,
  and ``read_sink_records`` replays every segment in order;
* perf_diff (scripts/perf_diff.py): flatten/diff/categorize/format.
"""

import json
import os
import sys
import threading

import numpy as np
import pytest

from ml_trainer_tpu.telemetry import MetricsRegistry, prometheus_text
from ml_trainer_tpu.telemetry.alerts import AlertEngine, AlertRule
from ml_trainer_tpu.telemetry.flight import FlightRecorder
from ml_trainer_tpu.telemetry.watchtower import (
    TimeSeriesStore,
    bucket_quantile,
    install_flight_context,
    render_dashboard,
    watch_context,
)

SCRIPTS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "scripts"
)


# ---------------------------------------------------------------- TSDB


def test_ring_bounds_and_eviction():
    store = TimeSeriesStore(capacity=4)
    for i in range(10):
        store.append("g", float(i), t=float(i))
    points = store.last("g", n=10)
    assert len(points) == 4  # ring-bounded
    assert [v for _, v in points] == [6.0, 7.0, 8.0, 9.0]  # oldest out
    assert store.total_points() == 4
    assert len(store) == 1
    with pytest.raises(ValueError):
        TimeSeriesStore(capacity=1)  # can never answer a windowed query


def test_rate_delta_avg_hand_computed():
    store = TimeSeriesStore(capacity=64)
    # Counter with a restart at t=30: 0 -> 60 -> 90, then reset to 10.
    for t, v in [(0, 0.0), (10, 60.0), (20, 90.0), (30, 10.0)]:
        store.append("c_total", v, t=float(t))
    # Reset-aware increase: 60 + 30 + 10 = 100 over 30s.
    assert store.rate("c_total") == pytest.approx(100.0 / 30.0)
    # Windowed to the last 10s: only the reset sample's 10.
    assert store.rate("c_total", window_s=10.0, now=30.0) == (
        pytest.approx(1.0)
    )
    for t, v in [(0, 5.0), (10, 9.0), (20, 3.0)]:
        store.append("gauge", v, t=float(t))
    assert store.delta("gauge") == pytest.approx(-2.0)
    assert store.avg("gauge") == pytest.approx((5 + 9 + 3) / 3)
    assert store.minmax("gauge", max) == 9.0
    assert store.rate("lonely") is None  # absent series: no arithmetic
    store.append("lonely", 1.0, t=0.0)
    assert store.rate("lonely") is None  # <2 points


def test_quantile_over_time_hand_computed():
    r = MetricsRegistry()
    h = r.histogram("lat_seconds", buckets=(0.1, 1.0, 10.0))
    store = TimeSeriesStore(capacity=64)
    store.sample_registry(r, t=0.0, force=True)  # empty baseline
    for v in [0.05, 0.05, 0.5, 0.5, 0.5, 0.5, 5.0, 5.0]:
        h.observe(v)
    store.sample_registry(r, t=10.0, force=True)
    # 8 observations in-window: q50 target=4 lands in (0.1, 1.0] with
    # cum 2 below it and 4 in-bucket -> 0.1 + 0.9 * (4-2)/4 = 0.55.
    q50 = store.quantile_over_time("lat_seconds", 0.5, window_s=60.0,
                                   now=10.0)
    assert q50 == pytest.approx(0.1 + 0.9 * 0.5)
    # q99 lands in +Inf? no: cum(10.0)=8 >= 7.92 -> interpolate in
    # (1.0, 10.0]: 1.0 + 9.0 * (7.92-6)/2 = 9.64.
    q99 = store.quantile_over_time("lat_seconds", 0.99, window_s=60.0,
                                   now=10.0)
    assert q99 == pytest.approx(1.0 + 9.0 * (7.92 - 6) / 2)
    # A second sweep with no new observations: the window [10, 20] has
    # zero increase -> None, not 0.0.
    store.sample_registry(r, t=20.0, force=True)
    assert store.quantile_over_time("lat_seconds", 0.5, window_s=9.0,
                                    now=20.0) is None
    # bucket_quantile direct: everything in the first bucket.
    assert bucket_quantile({0.5: 4.0, float("inf"): 4.0}, 0.5) == (
        pytest.approx(0.25)
    )


def test_sample_and_ingest_share_series_keys():
    r = MetricsRegistry()
    r.gauge("depth", labelnames=("tenant",)).labels(tenant="a").set(3.0)
    h = r.histogram("lat_seconds", buckets=(0.5, 2.0))
    h.observe(0.2)
    sampled = TimeSeriesStore(capacity=8)
    sampled.sample_registry(r, t=1.0, force=True)
    ingested = TimeSeriesStore(capacity=8)
    ingested.ingest_exposition(
        prometheus_text(r), t=1.0, extra_labels={"replica": "w0"},
        force=True,
    )
    assert ingested.last_value("depth", {"tenant": "a"}) == 3.0
    # The merged federation label is queryable...
    assert ingested.last_value(
        "depth", {"tenant": "a", "replica": "w0"}
    ) == 3.0
    # ...and bucket keys line up between the two ingestion paths.
    for store, extra in ((sampled, {}), (ingested, {"replica": "w0"})):
        assert store.last_value(
            "lat_seconds_bucket", dict(extra, le="0.5")
        ) == 1.0
        assert store.last_value(
            "lat_seconds_bucket", dict(extra, le="+Inf")
        ) == 1.0
    # Ambiguous selections raise instead of silently picking one.
    r.gauge("depth", labelnames=("tenant",)).labels(tenant="b").set(4.0)
    sampled.sample_registry(r, t=2.0, force=True)
    with pytest.raises(ValueError):
        sampled.last_value("depth")


def test_concurrent_sample_vs_query_hammer():
    r = MetricsRegistry()
    g = r.gauge("hot", labelnames=("i",))
    c = r.counter("hits_total")
    store = TimeSeriesStore(capacity=32)
    stop = threading.Event()
    errors = []

    def writer():
        t = 0.0
        while not stop.is_set():
            for i in range(8):
                g.labels(i=str(i)).set(float(i))
            c.inc()
            store.sample_registry(r, t=t, force=True)
            t += 1.0

    def reader():
        while not stop.is_set():
            try:
                store.names()
                store.select("hot")
                store.rate("hits_total")
                store.dump()
            except Exception as e:  # noqa: BLE001
                errors.append(e)
                return

    threads = [threading.Thread(target=writer)] + [
        threading.Thread(target=reader) for _ in range(3)
    ]
    for t in threads:
        t.start()
    import time as _time

    _time.sleep(0.4)
    stop.set()
    for t in threads:
        t.join(timeout=10)
    assert not errors
    assert all(
        len(points) <= 32 for _, points in store.select("hot")
    )


def test_dump_load_roundtrip_exact(tmp_path):
    store = TimeSeriesStore(capacity=8)
    store.append("a", 1.5, {"x": "1"}, t=1.0)
    store.append("a", 2.5, {"x": "1"}, t=2.0)
    store.append("b", -3.0, t=2.0)
    path = store.save(str(tmp_path / "wt.json"))
    loaded = TimeSeriesStore.load(json.load(open(path)))
    assert loaded.dump() == store.dump()


# ---------------------------------------------------------------- alerts


def test_level_rule_state_machine_fake_clock():
    clock = [100.0]
    store = TimeSeriesStore(capacity=32)
    registry = MetricsRegistry()
    flight = FlightRecorder()
    engine = AlertEngine(
        store=store, registry=registry, flight=flight,
        clock=lambda: clock[0],
    )
    engine.add_rule(AlertRule(
        "hot_gauge", "pressure > 0.8", for_s=10.0, severity="warn",
    ))
    store.append("pressure", 0.5, t=clock[0])
    assert engine.evaluate() == []  # below threshold: nothing
    store.append("pressure", 0.9, t=clock[0])
    assert engine.evaluate() == []  # pending: breach younger than for_s
    assert not engine.rule("hot_gauge").firing()
    clock[0] += 11.0
    store.append("pressure", 0.95, t=clock[0])
    events = engine.evaluate()
    assert [e["state"] for e in events] == ["firing"]
    assert engine.rule("hot_gauge").firing()
    assert events[0]["value"] == 0.95
    # Instruments + flight took the one firing path.
    snap = registry.snapshot()
    assert snap["alerts_fired_total{rule=hot_gauge}"] == 1
    assert snap["alert_active{rule=hot_gauge}"] == 1.0
    assert [rec["rule"] for rec in flight.records()
            if rec["kind"] == "alert"] == ["hot_gauge"]
    # Still firing: no duplicate event, the streak just holds.
    clock[0] += 5.0
    store.append("pressure", 0.99, t=clock[0])
    assert engine.evaluate() == []
    # Recovery resolves exactly once.
    clock[0] += 5.0
    store.append("pressure", 0.1, t=clock[0])
    events = engine.evaluate()
    assert [e["state"] for e in events] == ["resolved"]
    assert not engine.rule("hot_gauge").firing()
    assert registry.snapshot()["alert_active{rule=hot_gauge}"] == 0.0
    assert [e["state"] for e in engine.history()
            if e["rule"] == "hot_gauge"] == ["firing", "resolved"]


def test_per_label_group_evaluation():
    clock = [0.0]
    store = TimeSeriesStore(capacity=32)
    engine = AlertEngine(store=store, clock=lambda: clock[0])
    engine.add_rule(AlertRule("deep", "queue_depth > 5"))
    store.append("queue_depth", 9.0, {"tenant": "a"}, t=0.0)
    store.append("queue_depth", 1.0, {"tenant": "b"}, t=0.0)
    events = engine.evaluate()
    assert [e["labels"] for e in events] == [{"tenant": "a"}]
    assert engine.rule("deep").firing({"tenant": "a"})
    assert not engine.rule("deep").firing({"tenant": "b"})
    assert engine.rule("deep").n_firing() == 1


def test_absent_series_rule():
    clock = [0.0]
    store = TimeSeriesStore(capacity=8)
    engine = AlertEngine(store=store, clock=lambda: clock[0])
    engine.add_rule(AlertRule(
        "no_heartbeat", "absent(train_goodput_fraction)",
        severity="warn",
    ))
    events = engine.evaluate()
    assert [e["state"] for e in events] == ["firing"]
    store.append("train_goodput_fraction", 0.9, t=0.0)
    events = engine.evaluate()
    assert [e["state"] for e in events] == ["resolved"]


def test_event_mode_rule_runs_actions_with_extra():
    seen = []
    engine = AlertEngine(clock=lambda: 0.0)
    engine.add_rule(AlertRule(
        "tick", mode="event", actions=(seen.append,),
    ))
    assert engine.observe("tick", True, value=2.0,
                          extra={"host": 3}) is True
    assert engine.observe("tick", False) is False
    assert engine.observe("tick", True, value=4.0,
                          extra={"host": 3}) is True
    assert [e["value"] for e in seen] == [2.0, 4.0]  # re-fires per event
    assert all(e["host"] == 3 and e["state"] == "event" for e in seen)


def test_expr_rate_and_quantile_predicates():
    clock = [60.0]
    store = TimeSeriesStore(capacity=32)
    engine = AlertEngine(store=store, clock=lambda: clock[0])
    engine.add_rule(AlertRule("errs", "rate(errors_total[60s]) > 0.5"))
    store.append("errors_total", 0.0, t=0.0)
    store.append("errors_total", 60.0, t=60.0)  # 1/s
    assert [e["rule"] for e in engine.evaluate()] == ["errs"]
    r = MetricsRegistry()
    h = r.histogram("lat_seconds", buckets=(0.1, 1.0))
    store2 = TimeSeriesStore(capacity=32)
    engine2 = AlertEngine(store=store2, clock=lambda: clock[0])
    engine2.add_rule(AlertRule(
        "slow", "quantile(0.5, lat_seconds[120s]) > 0.5"))
    store2.sample_registry(r, t=0.0, force=True)
    for _ in range(4):
        h.observe(0.9)
    store2.sample_registry(r, t=60.0, force=True)
    assert [e["rule"] for e in engine2.evaluate()] == ["slow"]


# ------------------------------------------------------- watcher parity


def test_straggler_watcher_is_declarative_event_rule():
    """PR 20 re-expression: the cluster straggler detector routes
    through the alert engine — legacy counter/flight/hook behavior
    intact (pinned by test_telemetry.py) PLUS the alert record."""
    from ml_trainer_tpu.telemetry import ClusterTelemetry, HEARTBEAT_FIELDS

    r = MetricsRegistry()
    fr = FlightRecorder()
    ct = ClusterTelemetry(registry=r, flight=fr, straggler_factor=2.0)
    rule = ct.alerts.rule("cluster_straggler")
    assert rule.mode == "event" and rule.severity == "warn"
    f = len(HEARTBEAT_FIELDS)
    i50 = HEARTBEAT_FIELDS.index("step_ms_p50")
    skewed = np.zeros((2, f))
    skewed[:, i50] = (10.0, 25.0)
    ct._ingest(skewed, step=7)
    # Legacy side effects still fire (the rule's action)...
    assert r.snapshot()["cluster_straggler_events_total{host=1}"] == 1
    legacy = [rec for rec in fr.records() if rec["kind"] == "straggler"]
    assert legacy and legacy[-1]["host"] == 1
    # ...and the ONE alerting path now also records it.
    alerts = [rec for rec in fr.records() if rec["kind"] == "alert"]
    assert alerts and alerts[-1]["rule"] == "cluster_straggler"
    assert alerts[-1]["labels"] == {"host": "1"}
    hist = [e for e in ct.alerts.history()
            if e["rule"] == "cluster_straggler"]
    assert hist and hist[-1]["factor"] == 2.5


def test_autoscaler_rules_live_on_router_engine():
    """The autoscaler registers its hysteresis watchers as named rules
    on the shared engine (tick-by-tick parity is pinned by
    tests/test_overload.py)."""
    from ml_trainer_tpu.serving.autoscaler import (
        Autoscaler, AutoscalerConfig,
    )

    class _Router:
        alerts = AlertEngine(clock=lambda: 0.0)
        ladder = None

        def fleet_slo_snapshot(self):
            return {"burn": None, "window_requests": 0, "now": 0.0}

    sc = Autoscaler(_Router(), None,
                    config=AutoscalerConfig(high_polls=3, low_polls=2))
    assert sc.alerts is _Router.alerts
    assert sc.alerts.rule("autoscaler_burn_high").for_count == 3
    assert sc.alerts.rule("autoscaler_burn_low").for_count == 2


# ------------------------------------------------------------ dashboard


def test_dashboard_golden_shape():
    store = TimeSeriesStore(capacity=16)
    for t in range(6):
        store.append("train_goodput_fraction", 0.8 + t / 100,
                     t=float(t))
    store.append("lat_seconds_bucket", 1.0, {"le": "0.5"}, t=0.0)
    alerts = [{
        "t": 3.0, "rule": "hot_gauge", "severity": "page",
        "state": "firing", "value": 0.97, "labels": {"tenant": "a"},
    }]
    html = render_dashboard(
        store, title='<run "7">', alerts=alerts,
    )
    assert html.startswith("<!doctype html>")
    assert "&lt;run &quot;7&quot;&gt;" in html  # hostile title escaped
    assert "train_goodput_fraction" in html
    assert "<polyline points=" in html  # inline sparkline, no assets
    assert 'class="state-firing"' in html and "hot_gauge" in html
    assert "lat_seconds_bucket" not in html  # buckets folded away
    assert "http://" not in html and "src=" not in html


def test_flight_context_carries_trend():
    store = TimeSeriesStore(capacity=64)
    for t in range(40):
        store.append("train_goodput_fraction", t / 40, t=float(t))
    store.append("unrelated_gauge", 1.0, t=0.0)
    ctx = watch_context(store, n=32)
    assert list(ctx) == ["train_goodput_fraction"]
    assert len(ctx["train_goodput_fraction"]) == 32  # last-N only
    fr = FlightRecorder()
    install_flight_context(store=store, recorder=fr)
    fr.record("step", step=1)
    dump = fr.payload(reason="unit")
    assert "watchtower" in dump.get("context", {})


# ----------------------------------------------- trainer pin (slow-ish)


def test_trainer_fit_fills_store_zero_extra_compiles(tmp_path):
    """Watchtower ON changes nothing the step computes: same compile
    count as the bare fit, bit-identical params — while the process
    store actually accumulates trainer series at the log-sync cadence."""
    from ml_trainer_tpu import Trainer, MLModel
    from ml_trainer_tpu.data import SyntheticCIFAR10
    from ml_trainer_tpu.telemetry import compile_watch
    from ml_trainer_tpu.telemetry.watchtower import (
        default_store, reset_default_store,
    )
    from ml_trainer_tpu.utils.functions import custom_pre_process_function
    import jax

    def make(model_dir, **kw):
        t = custom_pre_process_function()
        return Trainer(
            MLModel(),
            datasets=(SyntheticCIFAR10(size=64, seed=0, transform=t),
                      SyntheticCIFAR10(size=32, seed=1, transform=t)),
            epochs=1, batch_size=16, model_dir=str(model_dir),
            metric=None, lr=0.01, **kw,
        )

    compile_watch.install()
    pw_before = compile_watch.post_warmup_count()
    bare = make(tmp_path / "bare")
    bare.fit()
    reset_default_store()
    try:
        instr = make(tmp_path / "instr", telemetry=True)
        instr.fit()
        store = default_store()
        assert store.last_value("train_goodput_fraction") is not None
        assert store.total_points() > 0
    finally:
        reset_default_store()
    assert compile_watch.post_warmup_count() == pw_before
    for a, b in zip(
        jax.tree.leaves(bare.state.params),
        jax.tree.leaves(instr.state.params),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------ sink rotation


def test_jsonl_sink_rotation_and_replay(tmp_path):
    from ml_trainer_tpu.telemetry.export import (
        JsonlSink, read_sink_records,
    )

    path = str(tmp_path / "metrics.jsonl")
    sink = JsonlSink(path, max_bytes=400)
    for i in range(40):
        sink.write({"i": i, "pad": "x" * 24})
    sink.close()
    idx = json.load(open(path + ".index.json"))
    assert len(idx["rotated"]) >= 2  # it DID rotate
    for seg in idx["rotated"]:
        assert os.path.exists(seg["path"])
        assert os.path.getsize(seg["path"]) <= 400 + 200  # record slop
    # Replay covers every segment, in write order, live tail last.
    records = read_sink_records(path)
    assert [rec["i"] for rec in records] == list(range(40))
    # A re-opened sink resumes the segment counter (no overwrite).
    sink2 = JsonlSink(path, max_bytes=400)
    for i in range(40, 60):
        sink2.write({"i": i, "pad": "x" * 24})
    sink2.close()
    records = read_sink_records(path)
    assert [rec["i"] for rec in records] == list(range(60))


# -------------------------------------------------------- perf_diff


@pytest.fixture()
def perf_diff():
    sys.path.insert(0, SCRIPTS)
    try:
        import perf_diff as mod

        yield mod
    finally:
        sys.path.remove(SCRIPTS)


def test_perf_diff_flatten_and_attribution(perf_diff):
    old = {
        "decode_tokens_per_sec": 100.0,
        "legs": [{"name": "serve", "p99_ms": 20.0}],
        "compile_events_post_warmup_total": 0,
        "written_at": 111.0,
    }
    new = {
        "decode_tokens_per_sec": 80.0,
        "legs": [{"name": "serve", "p99_ms": 30.0}],
        "compile_events_post_warmup_total": 2,
        "written_at": 999.0,  # timestamp churn must not show up
        "kv_pages_free": 5,
    }
    rows = perf_diff.diff_leaves(
        perf_diff.flatten(old), perf_diff.flatten(new)
    )
    by_key = {r["key"]: r for r in rows}
    assert "written_at" not in by_key
    assert by_key["decode_tokens_per_sec"]["pct"] == pytest.approx(20.0)
    assert by_key["decode_tokens_per_sec"]["category"] == "throughput"
    assert by_key["legs[serve].p99_ms"]["category"] == "latency"
    assert by_key["compile_events_post_warmup_total"]["category"] == (
        "compiles"
    )
    assert by_key["kv_pages_free"]["note"] == "appeared"
    table = perf_diff.format_table(rows, top=10)
    assert "legs[serve].p99_ms" in table
    assert "changed leaves" in table  # the per-ledger rollup line


def test_perf_diff_reads_tsdb_dumps(perf_diff, tmp_path):
    a, b = TimeSeriesStore(capacity=8), TimeSeriesStore(capacity=8)
    for store, v in ((a, 10.0), (b, 40.0)):
        store.append("queue_depth", 1.0, {"tenant": "x"}, t=0.0)
        store.append("queue_depth", v, {"tenant": "x"}, t=5.0)
    pa = a.save(str(tmp_path / "a.json"))
    pb = b.save(str(tmp_path / "b.json"))
    rows = perf_diff.diff_files(pa, pb)
    assert [r["key"] for r in rows] == ["queue_depth{tenant=x}"]
    assert rows[0]["old"] == 10.0 and rows[0]["new"] == 40.0
