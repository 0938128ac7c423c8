"""Request-lifecycle tracing, SLO attainment telemetry, and the
open-loop load harness (serving/slo.py, serving/loadgen.py).

Ground truths pinned here: the lifecycle latency histograms expose
exact Prometheus ``_bucket``/``_sum``/``_count`` semantics and never
double-count across publishes; the SLO arithmetic (attainment, burn
rate) matches hand-computed values on synthetic timelines; a seeded
load schedule is byte-reproducible (the property that makes sweeps
comparable); steady-state open-loop traffic mints ZERO compiles
(compile_watch-pinned); and a forced preemption's flight dump names the
hurt request ids with their timelines attached (the forensics
acceptance criterion)."""

import threading
import time

import jax
import numpy as np
import pytest

from ml_trainer_tpu.models import get_model
from ml_trainer_tpu.serving import (
    Server,
    SloPolicy,
    SloTracker,
    TenantLoad,
    poisson_schedule,
    run_open_loop,
    schedule_from_trace,
)
from ml_trainer_tpu.serving.loadgen import schedule_to_records
from ml_trainer_tpu.serving.metrics import ServingMetrics
from ml_trainer_tpu.serving.scheduler import Request
from ml_trainer_tpu.serving.slo import aggregate_timelines
from ml_trainer_tpu.telemetry import spans
from ml_trainer_tpu.telemetry.registry import MetricsRegistry


@pytest.fixture(scope="module")
def model_and_vars():
    model = get_model("gpt2_tiny", max_len=64)
    variables = model.init(
        {"params": jax.random.PRNGKey(0)}, np.zeros((1, 8), np.int32),
        train=False,
    )
    return model, variables


def _finished_request(tenant="default", ttft_s=0.01, tpot_s=0.005,
                      n_tokens=4, state="done"):
    """A synthetic finished Request with a fabricated timeline: known
    queue wait (1ms), TTFT and inter-token gaps, so the SLO arithmetic
    is checkable by hand."""
    req = Request(prompt=np.asarray([1, 2, 3], np.int32),
                  max_new_tokens=n_tokens, tenant=tenant)
    t0 = req.submitted_at
    req.first_admitted_at = t0 + 1e-3
    req.admitted_at = req.first_admitted_at
    req.prefill_secs = max(ttft_s - 1e-3, 0.0)
    req.token_times = [
        t0 + ttft_s + i * tpot_s for i in range(n_tokens)
    ]
    req.first_token_at = req.token_times[0]
    req.tokens = list(range(n_tokens))
    req.state = state
    req.finished_at = req.token_times[-1]
    return req


def test_slo_policy_validation():
    with pytest.raises(ValueError, match="positive"):
        SloPolicy(ttft_ms=0)
    with pytest.raises(ValueError, match="target"):
        SloPolicy(target=1.0)
    with pytest.raises(ValueError, match="keep_timelines"):
        SloTracker(keep_timelines=0)


def test_latency_histogram_golden_exposition():
    """The promoted TTFT/TPOT histograms expose exact cumulative
    ``le`` buckets + ``_sum``/``_count``, and a second publish never
    double-counts (the delta-observed pattern)."""
    m = ServingMetrics()
    for v in (0.0005, 0.003, 0.003, 0.2):
        m.record_ttft(v, tenant="t0")
    m.record_tpot([0.004, 0.09], tenant="t0")
    reg = MetricsRegistry()
    m.publish(reg)
    first = reg.prometheus_text()
    # Cumulative buckets: 0.0005 -> le=0.001 holds 1; the two 3ms
    # observations land at le=0.005 (cumulative 3); 0.2 at le=0.25
    # (cumulative 4 from there up).
    assert 'serving_ttft_seconds_bucket{tenant="t0",le="0.001"} 1' in first
    assert 'serving_ttft_seconds_bucket{tenant="t0",le="0.0025"} 1' in first
    assert 'serving_ttft_seconds_bucket{tenant="t0",le="0.005"} 3' in first
    assert 'serving_ttft_seconds_bucket{tenant="t0",le="0.1"} 3' in first
    assert 'serving_ttft_seconds_bucket{tenant="t0",le="0.25"} 4' in first
    assert 'serving_ttft_seconds_bucket{tenant="t0",le="+Inf"} 4' in first
    assert 'serving_ttft_seconds_sum{tenant="t0"} 0.2065' in first
    assert 'serving_ttft_seconds_count{tenant="t0"} 4' in first
    assert 'serving_tpot_seconds_bucket{tenant="t0",le="0.005"} 1' in first
    assert 'serving_tpot_seconds_count{tenant="t0"} 2' in first
    # Publish again with no new observations: identical exposition.
    m.publish(reg)
    assert reg.prometheus_text() == first
    # New observation after the second publish: count moves by one.
    m.record_ttft(0.0005, tenant="t0")
    m.publish(reg)
    assert 'serving_ttft_seconds_count{tenant="t0"} 5' \
        in reg.prometheus_text()


def test_attainment_and_burn_rate_arithmetic():
    """3 of 4 requests meet TTFT, all meet TPOT, target 0.9 =>
    attainment 0.75 / burn 2.5 on ttft, 1.0 / 0.0 on tpot; a failed
    request misses both SLOs by definition."""
    tracker = SloTracker(policy=SloPolicy(ttft_ms=50.0, tpot_ms=20.0,
                                          target=0.9))
    for _ in range(3):
        tracker.observe(_finished_request(ttft_s=0.01))
    tracker.observe(_finished_request(ttft_s=0.5))  # misses TTFT
    snap = tracker.snapshot()
    assert snap["requests_observed"] == 4
    assert snap["attainment"] == {"ttft": 0.75, "tpot": 1.0}
    assert snap["burn_rate"]["ttft"] == pytest.approx(2.5)
    assert snap["burn_rate"]["tpot"] == 0.0
    tracker.observe(_finished_request(ttft_s=0.01, state="error"))
    snap = tracker.snapshot()
    assert snap["requests_failed"] == 1
    assert snap["attainment"]["ttft"] == 0.6  # 3 of 5
    assert snap["attainment"]["tpot"] == 0.8  # failed request misses
    # aggregate_timelines (the harness's window-scoped view) agrees.
    agg = aggregate_timelines(tracker.timelines(), tracker.policy)
    assert agg["attainment"] == snap["attainment"]
    assert agg["n_failed"] == 1
    # Publish: per-tenant + aggregate series land in the registry.
    reg = MetricsRegistry()
    tracker.publish(reg)
    text = reg.prometheus_text()
    assert 'serving_slo_attainment{slo="ttft",tenant="all"} 0.6' in text
    assert 'serving_slo_burn_rate{slo="ttft",tenant="default"}' in text
    assert 'serving_slo_target_ms{slo="tpot"} 20' in text


def test_timeline_decomposes_ttft():
    """queue_wait + prefill ~= ttft on the synthetic timeline, and the
    tpot stats match the fabricated gaps."""
    req = _finished_request(ttft_s=0.02, tpot_s=0.004, n_tokens=5)
    tl = req.timeline()
    assert tl["queue_wait_ms"] == pytest.approx(1.0, abs=1e-6)
    assert tl["prefill_ms"] == pytest.approx(19.0, abs=1e-6)
    assert tl["ttft_ms"] == pytest.approx(20.0, abs=1e-3)
    assert tl["queue_wait_ms"] + tl["prefill_ms"] == pytest.approx(
        tl["ttft_ms"], abs=1e-3
    )
    assert tl["tpot_ms"]["mean"] == pytest.approx(4.0, abs=1e-3)
    assert tl["tpot_ms"]["p50"] == pytest.approx(4.0, abs=1e-3)
    assert tl["new_tokens"] == 5


def test_tracker_concurrent_observe_vs_snapshot_hammer():
    """The SLO accounting's concurrency contract: observe() from many
    threads while snapshot()/publish()/context_payload() scrape — no
    crashes, and the final count equals the observations made."""
    tracker = SloTracker(policy=SloPolicy(ttft_ms=50.0, tpot_ms=20.0))
    stop = threading.Event()
    errors, observed = [], []

    def producer(seed):
        rng = np.random.default_rng(seed)
        try:
            while not stop.is_set():
                req = _finished_request(
                    tenant=f"t{seed}", ttft_s=float(rng.random() * 0.1)
                )
                tracker.track(req)
                tracker.observe(req)
                observed.append(1)
        except Exception as e:  # pragma: no cover - the failure signal
            errors.append(e)

    def scraper():
        reg = MetricsRegistry()
        try:
            while not stop.is_set():
                snap = tracker.snapshot()
                assert 0.0 <= snap["attainment"]["ttft"] <= 1.0
                tracker.publish(reg)
                tracker.context_payload()
        except Exception as e:  # pragma: no cover - the failure signal
            errors.append(e)

    threads = [threading.Thread(target=producer, args=(i,))
               for i in range(3)]
    threads += [threading.Thread(target=scraper) for _ in range(2)]
    for t in threads:
        t.start()
    time.sleep(1.0)
    stop.set()
    for t in threads:
        t.join(timeout=30)
    assert not errors, errors
    assert tracker.snapshot()["requests_observed"] == len(observed)


def test_loadgen_schedule_deterministic():
    """Same seed => byte-identical schedule (arrivals, tenants, prompts,
    budgets); a different seed differs; shared prefixes are applied."""
    mix = {
        "pro": TenantLoad(weight=2.0, shared_prefix_len=8,
                          shared_frac=1.0),
        "free": TenantLoad(),
    }
    a = poisson_schedule(50.0, 24, 1024, tenants=mix, seed=7)
    b = poisson_schedule(50.0, 24, 1024, tenants=mix, seed=7)
    c = poisson_schedule(50.0, 24, 1024, tenants=mix, seed=8)
    assert len(a) == len(b) == 24
    for x, y in zip(a, b):
        assert x.arrival_s == y.arrival_s
        assert x.tenant == y.tenant
        assert x.max_new_tokens == y.max_new_tokens
        np.testing.assert_array_equal(x.prompt, y.prompt)
    assert any(
        x.arrival_s != y.arrival_s
        or not np.array_equal(x.prompt, y.prompt)
        for x, y in zip(a, c)
    )
    # Arrivals are sorted (a fixed open-loop schedule) and every "pro"
    # prompt opens with the tenant's shared prefix.
    assert all(
        a[i].arrival_s <= a[i + 1].arrival_s for i in range(len(a) - 1)
    )
    pro = [s for s in a if s.tenant == "pro"]
    assert pro, "weighted mix produced no pro arrivals"
    head = pro[0].prompt[:8]
    assert all(np.array_equal(s.prompt[:8], head) for s in pro)
    with pytest.raises(ValueError, match="rate_rps"):
        poisson_schedule(0.0, 4, 1024)


def test_loadgen_trace_round_trip(tmp_path):
    sched = poisson_schedule(20.0, 6, 512, seed=3)
    records = schedule_to_records(sched)
    path = tmp_path / "trace.json"
    import json

    path.write_text(json.dumps(records))
    back = schedule_from_trace(str(path))
    assert len(back) == len(sched)
    for x, y in zip(sched, back):
        assert x.arrival_s == pytest.approx(y.arrival_s, abs=1e-6)
        assert (x.tenant, x.max_new_tokens) == (y.tenant, y.max_new_tokens)
        np.testing.assert_array_equal(x.prompt, y.prompt)


def test_open_loop_populates_slo_accounting(model_and_vars):
    """A small in-process open-loop run: every request completes, the
    tracker observed each, its lifecycle spans nest on the trace, the
    snapshot carries the TTFT decomposition fields (with the legacy keys
    intact), and attainment is computed."""
    model, variables = model_and_vars
    sched = poisson_schedule(
        40.0, 6, model.vocab_size,
        tenants={"default": TenantLoad(prompt_len=(5, 9),
                                       output_len=(2, 4))},
        seed=1,
    )
    spans.clear_trace()
    with Server(model, variables, max_batch=2, max_queue=16,
                slo=SloPolicy(ttft_ms=60_000, tpot_ms=60_000)) as srv:
        report = run_open_loop(sched, server=srv, timeout=300)
        snap = srv.metrics.snapshot()
        slo = srv.slo.snapshot()
    assert report["n_completed"] == 6 and report["n_errors"] == 0
    # Every finished request is a ``request N`` span on the trace whose
    # queue_wait / prefill / decode children nest by time containment.
    events = spans.trace_events()
    parents = {
        e["args"]["request"]: e for e in events
        if e["name"].startswith("request ") and "args" in e
    }
    kids = [
        e for e in events
        if e["name"] in ("queue_wait", "prefill", "decode")
        and e.get("args", {}).get("request") in parents
    ]
    assert len(parents) == 6 and len(kids) >= 12
    for k in kids:
        parent = parents[k["args"]["request"]]
        assert parent["ts"] - 1 <= k["ts"], (k, parent)
        assert k["ts"] + k["dur"] <= parent["ts"] + parent["dur"] + 1
    assert report["tokens_per_sec"] > 0
    assert slo["requests_observed"] == 6
    assert slo["attainment"] == {"ttft": 1.0, "tpot": 1.0}
    # TTFT decomposition + new percentile fields, legacy shape intact.
    for key in ("ttft_p50_ms", "prefill_p50_ms", "queue_wait_p50_ms",
                "queue_wait_p99_ms", "tpot_p50_ms", "e2e_p99_ms",
                "tokens_per_sec_busy", "requests_completed"):
        assert key in snap, key
    assert snap["queue_wait_p50_ms"] >= 0
    assert snap["e2e_p50_ms"] >= snap["ttft_p50_ms"]


def test_zero_recompiles_at_steady_state_load(model_and_vars):
    """The load harness's compile discipline: after one warm pass over
    a schedule, replaying it mints ZERO compiles (compile_watch-pinned,
    process-wide)."""
    from ml_trainer_tpu.telemetry import compile_watch

    model, variables = model_and_vars
    sched = poisson_schedule(
        60.0, 6, model.vocab_size,
        tenants={"default": TenantLoad(prompt_len=(5, 9),
                                       output_len=(2, 4))},
        seed=2,
    )
    with Server(model, variables, max_batch=2, max_queue=16) as srv:
        run_open_loop(sched, server=srv, time_scale=0.0, timeout=300)
        with compile_watch.expect_no_compiles("steady-state load"):
            run_open_loop(sched, server=srv, timeout=300)


def test_preemption_flight_dump_names_requests(model_and_vars, tmp_path):
    """The forensics acceptance criterion: a forced preemption under
    load yields a flight dump whose ring names the preempted request id
    and whose context attaches that request's lifecycle timeline
    (including its preempt event)."""
    import json

    from ml_trainer_tpu.telemetry.flight import get_recorder

    model, variables = model_and_vars
    rng = np.random.default_rng(5)
    p1 = rng.integers(0, 1024, 9).astype(np.int32)
    p2 = rng.integers(0, 1024, 11).astype(np.int32)
    get_recorder().clear()
    with Server(model, variables, max_batch=2, kv_page_size=8,
                kv_pages=13, prefix_cache=False) as srv:
        s1 = srv.submit(p1, 40, tenant="victim")
        s2 = srv.submit(p2, 40, tenant="victim")
        s1.result(timeout=300)
        s2.result(timeout=300)
        assert srv.metrics.snapshot()["preemptions_total"] >= 1
        path = get_recorder().dump("test preemption", out_dir=str(tmp_path))
    dump = json.loads(open(path).read())
    preempts = [r for r in dump["records"] if r["kind"] == "preempt"]
    assert preempts and isinstance(preempts[0]["request"], int)
    hurt = preempts[0]["request"]
    # decode_step flight records name the requests riding each step.
    steps = [r for r in dump["records"] if r["kind"] == "decode_step"]
    assert steps and any(hurt in r.get("requests", []) for r in steps)
    ctx = dump["context"]["serving_requests"]
    tl = next(
        t for t in ctx["recent"] + ctx["active"] if t["id"] == hurt
    )
    events = [e["event"] for e in tl["events"]]
    assert "preempt" in events and "requeued" in events
    assert events.count("admitted") >= 2  # original + resume
    assert tl["preemptions"] >= 1 and tl["state"] == "done"


def test_slo_http_endpoint_and_unhealthy_dump_names_requests(
        model_and_vars):
    """GET /slo serves the attainment snapshot over the real HTTP front
    end, and an engine-death dump carries the active request ids."""
    import json
    import urllib.request

    model, variables = model_and_vars
    with Server(model, variables, max_batch=2) as srv:
        srv.complete(np.asarray([3, 1, 4], np.int32), 3, timeout=300)
        host, port = srv.serve_http(port=0)
        with urllib.request.urlopen(
            f"http://{host}:{port}/slo", timeout=30
        ) as resp:
            slo = json.loads(resp.read())
    assert slo["requests_observed"] == 1
    assert set(slo["attainment"]) == {"ttft", "tpot"}
    assert "policy" in slo and slo["policy"]["target"] == 0.99
