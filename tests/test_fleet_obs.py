"""Fleet observability plane (ml_trainer_tpu/telemetry/federation.py +
the router's fleet plane in serving/router.py).

The pure federation/merge core is pinned with golden text and synthetic
multi-pid payloads (fast, no processes); the router-side plumbing —
scrape, re-label, aggregate ``/healthz``, trace context over the wire,
incident bundles — is pinned with in-process servers behind REAL HTTP
sockets (the test_fleet.py idiom: the socket is real, the processes are
not).  The true multi-process run lives in scripts/fleet_obs_smoke.py.
"""

import json
import os
import time

import jax
import numpy as np
import pytest

from ml_trainer_tpu.generate import generate
from ml_trainer_tpu.models import get_model
from ml_trainer_tpu.serving import Router, Server
from ml_trainer_tpu.serving.fleet import RemoteServer
from ml_trainer_tpu.telemetry import compile_watch, federation, spans
from ml_trainer_tpu.telemetry.export import sink_path_for_worker

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- federation: pure text-rewrite core -----------------------------------

WORKER_TEXT = (
    "# HELP serving_requests_total requests\n"
    "# TYPE serving_requests_total counter\n"
    'serving_requests_total{tenant="a"} 3\n'
    "# HELP ttft_ms time to first token\n"
    "# TYPE ttft_ms histogram\n"
    'ttft_ms_bucket{le="1"} 2\n'
    'ttft_ms_bucket{le="+Inf"} 4\n'
    "ttft_ms_sum 5.5\n"
    "ttft_ms_count 4\n"
    "# HELP compile_events_post_warmup_total recompiles\n"
    "# TYPE compile_events_post_warmup_total counter\n"
    "compile_events_post_warmup_total 0\n"
)
BASE_TEXT = (
    "# HELP router_inflight in flight\n"
    "# TYPE router_inflight gauge\n"
    "router_inflight 2\n"
)


def test_inject_labels_shapes():
    extra = {"replica": "d0", "role": "decode", "generation": 1}
    assert federation.inject_labels('up 1', extra) == (
        'up{replica="d0",role="decode",generation="1"} 1'
    )
    assert federation.inject_labels('x{tenant="a"} 3', extra) == (
        'x{tenant="a",replica="d0",role="decode",generation="1"} 3'
    )
    # Existing labels win — never a duplicated label name.
    assert federation.inject_labels('x{replica="w"} 1', extra) == (
        'x{replica="w",role="decode",generation="1"} 1'
    )
    # Comments and blanks pass through untouched.
    assert federation.inject_labels("# HELP x y", extra) == "# HELP x y"
    assert federation.inject_labels("", extra) == ""


def test_federate_exposition_golden_shape():
    fed = federation.federate_exposition(BASE_TEXT, [
        (WORKER_TEXT, {"replica": "p0", "role": "prefill",
                       "generation": 0}),
        (WORKER_TEXT, {"replica": "d0", "role": "decode",
                       "generation": 0}),
    ])
    lines = fed.splitlines()
    # One HELP/TYPE header per family, first writer wins.
    assert lines.count("# TYPE serving_requests_total counter") == 1
    assert lines.count("# TYPE ttft_ms histogram") == 1
    # Both replicas' samples present, labels injected.
    for rep, role in (("p0", "prefill"), ("d0", "decode")):
        assert (
            f'serving_requests_total{{tenant="a",replica="{rep}",'
            f'role="{role}",generation="0"}} 3'
        ) in lines
        assert (
            f'compile_events_post_warmup_total{{replica="{rep}",'
            f'role="{role}",generation="0"}} 0'
        ) in lines
    # Histogram children stay grouped under their family's one TYPE
    # header (no second "# TYPE ttft_ms" anywhere after samples).
    idx = lines.index("# TYPE ttft_ms histogram")
    block = lines[idx + 1:idx + 9]
    assert sum(
        1 for ln in block if ln.startswith("ttft_ms_bucket{")
    ) == 4
    # The router's own series survive unlabeled.
    assert "router_inflight 2" in lines


def test_federate_rerender_idempotent():
    """Rendering twice from the same snapshots returns the same bytes —
    the replace-never-accumulate property that makes re-scraping safe
    (a histogram count is what the worker last reported, not a running
    sum of scrapes)."""
    sections = [
        (WORKER_TEXT, {"replica": "p0", "role": "prefill",
                       "generation": 0}),
    ]
    a = federation.federate_exposition(BASE_TEXT, sections)
    b = federation.federate_exposition(BASE_TEXT, sections)
    assert a == b
    assert a.count('ttft_ms_count{replica="p0"') == 1


def test_resolve_clock_shift():
    # No estimate at all: visible, not a guess.
    assert federation.resolve_clock_shift(None, None, None) == (
        None, "none"
    )
    assert federation.resolve_clock_shift(42.0, None, None) == (
        42.0, "epoch"
    )
    assert federation.resolve_clock_shift(None, 17.0, 100.0) == (
        17.0, "ntp"
    )
    # Agreement within rtt/2 + slack: shared clock -> exact epoch shift.
    shift, method = federation.resolve_clock_shift(1000.0, 990.0, 200.0)
    assert (shift, method) == (1000.0, "epoch")
    # Disagreement: distinct clocks -> trust the handshake.
    shift, method = federation.resolve_clock_shift(
        50_000.0, 100.0, 200.0
    )
    assert (shift, method) == (100.0, "ntp")


def test_merge_fleet_trace_lanes_and_causal_order():
    """Synthetic multi-pid merge: a migrated request's prefill fragment
    (worker A's epoch) must land BEFORE its decode span (worker B's
    epoch) on the merged clock — per-process shifts applied, one lane
    per pid, every lane named."""
    local = [{
        "name": "kv_wire 7", "ph": "X", "ts": 900.0, "dur": 50.0,
        "pid": 100, "tid": 1, "args": {},
    }]
    remotes = [
        {
            "name": "p0",
            "payload": {"pid": 200, "events": [{
                "name": "request 7 (prefill)", "ph": "X", "ts": 10.0,
                "dur": 500.0, "pid": 200, "tid": 1, "args": {},
            }]},
            "epoch_shift_us": 400.0, "ntp_shift_us": 395.0,
            "rtt_us": 100.0,
        },
        {
            "name": "d0",
            "payload": {"pid": 300, "events": [{
                "name": "request 7", "ph": "X", "ts": 5.0, "dur": 400.0,
                "pid": 300, "tid": 1, "args": {},
            }]},
            "epoch_shift_us": 1000.0, "ntp_shift_us": 998.0,
            "rtt_us": 80.0,
        },
    ]
    merged = federation.merge_fleet_trace(local, "router", 100, remotes)
    events = merged["traceEvents"]
    lanes = {e["pid"] for e in events if e.get("ph") != "M"}
    assert lanes == {100, 200, 300}
    names = {
        e["args"]["name"] for e in events if e.get("ph") == "M"
    }
    assert names == {"router", "p0", "d0"}
    pre = next(e for e in events
               if e["name"] == "request 7 (prefill)")
    dec = next(e for e in events
               if e["name"] == "request 7" and e["pid"] == 300)
    assert pre["ts"] == pytest.approx(410.0)   # 10 + epoch shift 400
    assert dec["ts"] == pytest.approx(1005.0)  # 5 + epoch shift 1000
    assert dec["ts"] >= pre["ts"] + pre["dur"]  # causal on ONE clock
    assert merged["fleetClock"]["p0"]["method"] == "epoch"
    assert merged["fleetClock"]["d0"]["method"] == "epoch"
    assert merged["fleetClock"]["router"]["method"] == "local"
    # The source payloads were not mutated by the shift.
    assert remotes[0]["payload"]["events"][0]["ts"] == 10.0


def test_merge_fleet_trace_no_clock_is_visible_not_dropped():
    merged = federation.merge_fleet_trace([], "router", 1, [{
        "name": "w0",
        "payload": {"pid": 2, "events": [{
            "name": "x", "ph": "X", "ts": 123.0, "dur": 1.0, "pid": 2,
            "tid": 1,
        }]},
        "epoch_shift_us": None, "ntp_shift_us": None, "rtt_us": None,
    }])
    assert merged["fleetClock"]["w0"]["method"] == "none"
    ev = next(e for e in merged["traceEvents"] if e.get("name") == "x")
    assert ev["ts"] == 123.0  # unshifted, lane still present


def test_sink_path_for_worker():
    assert sink_path_for_worker("/x/m.jsonl", "decode0") == (
        "/x/m.decode0.jsonl"
    )
    assert sink_path_for_worker("/x/metrics", "w1") == "/x/metrics.w1"


# -- router-side plumbing over real sockets -------------------------------

@pytest.fixture(scope="module")
def socket_fleet():
    model = get_model("gpt2_tiny", max_len=64)
    variables = model.init(
        {"params": jax.random.PRNGKey(0)}, np.zeros((1, 8), np.int32),
        train=False,
    )
    compile_watch.install()  # workers install it; here: shared process
    # The watch's state belongs to the process: a Trainer with telemetry in
    # a file this worker ran earlier leaves it "warm", and this fleet's own
    # first compiles would then count as recompiles after warm-up.
    compile_watch.reset()
    servers, remotes = {}, {}
    router = None
    try:
        for name, role in (("prefill0", "prefill"),
                           ("decode0", "decode")):
            srv = Server(model, variables, max_batch=2, kv_page_size=8,
                         role=role, prefill_chunk=16)
            srv.name = name
            host, port = srv.serve_http(port=0)
            servers[name] = srv
            remotes[name] = RemoteServer(
                f"http://{host}:{port}", name=name
            )
        router = Router(
            dict(remotes),
            replica_urls={n: r.url for n, r in remotes.items()},
            hedging=False, metrics_scrape_interval=0.05,
            incident_min_interval_s=30.0,
        )
        yield model, variables, servers, router
    finally:
        if router is not None:
            router.close()
        for srv in servers.values():
            srv.close()


def test_federated_scrape_labels_and_idempotency(socket_fleet):
    model, variables, servers, router = socket_fleet
    p = np.random.default_rng(0).integers(0, 1024, 24).astype(np.int32)
    ref = np.asarray(generate(model, variables, p[None], 8))[0]
    out = np.asarray(router.complete(p, 8, timeout=120))
    np.testing.assert_array_equal(out, ref)
    # Warm render first: the router's publish() registers its series
    # in the (shared, in this in-process setup) registry, and the
    # workers' scraped text must settle before the idempotency pair.
    router.federated_metrics_text()
    router.scrape_metrics(force=True)
    fed = router.federated_metrics_text()

    def worker_lines(text):
        # The in-process servers share the router's registry, so the
        # router's own router_* series leak into the scraped "worker"
        # text and grow as the router publishes between renders; filter
        # them to the worker-owned families (a real fleet worker has
        # its own process registry — the multi-process idempotency is
        # pinned by scripts/fleet_obs_smoke.py).
        return [ln for ln in text.splitlines()
                if ln and not ln.startswith(("#", "router_"))
                and 'replica="' in ln]

    lines = worker_lines(fed)
    for name, role in (("prefill0", "prefill"), ("decode0", "decode")):
        assert any(
            ln.startswith("compile_events_post_warmup_total{")
            and f'replica="{name}"' in ln and f'role="{role}"' in ln
            and 'generation="0"' in ln
            for ln in lines
        ), f"{name}'s post-warmup counter missing from the federation"
    # Worker histograms present WITH labels (the exposition stays one
    # valid document — child samples grouped under their family).
    assert any(
        "_bucket{" in ln and 'replica="' in ln for ln in lines
    )
    # Re-scrape + re-render until quiescent: consecutive renders become
    # identical (snapshots replace — a histogram cannot double-count
    # across scrapes; an accumulate bug would grow EVERY re-scrape and
    # never stabilise).  Gauges are excluded, and the request's late
    # async bookkeeping (the in-process worker's TTFT observation can
    # land ms after the stream returns) is absorbed by the settle loop.
    def counting_lines(lns):
        return [ln for ln in lns if "_bucket{" in ln or "_sum{" in ln
                or "_count{" in ln or "_total{" in ln]

    def rescrape():
        router.scrape_metrics(force=True)
        return counting_lines(worker_lines(router.federated_metrics_text()))

    prev, deadline = rescrape(), time.monotonic() + 15.0
    while time.monotonic() < deadline:
        cur = rescrape()
        if cur == prev:
            break
        prev = cur
    assert rescrape() == prev


def test_aggregated_healthz_names_fleet_keys(socket_fleet):
    _, _, _, router = socket_fleet
    for rep in router.replicas.values():
        rep.last_health = rep.fetch_health()
    hz = router.health()
    for name, h in hz["replicas"].items():
        assert "compile_events_post_warmup_total" in h, name
        assert "degradation_level" in h, name
        assert h["compile_events_post_warmup_total"] == 0
    # The worker health payload itself carries the clock handshake
    # fields the poller's NTP estimate needs.
    raw = router.replicas["decode0"].last_health
    assert "trace_now_us" in raw and "mono_epoch" in raw


def test_scrape_error_bumps_counter_not_poller(socket_fleet):
    _, _, _, router = socket_fleet
    rep = router.replicas["decode0"]
    good_url, good_text = rep.url, rep.metrics_text
    before = router.snapshot()["scrape_errors_total"].get("decode0", 0)
    try:
        rep.url = "http://127.0.0.1:9"  # discard port: nothing listens
        router.scrape_metrics(force=True)  # must not raise
    finally:
        rep.url = good_url
    snap = router.snapshot()
    assert snap["scrape_errors_total"]["decode0"] == before + 1
    # The last good snapshot is kept — the federation does not lose
    # the replica's section while it flaps.
    assert rep.metrics_text == good_text


def test_trace_context_rides_the_socket_wire(socket_fleet):
    """A client trace id survives router -> prefill -> adopt -> decode
    across real HTTP hops: the prefill-side fragment, the router's
    kv_wire span, and the decode-side request span all carry it (the
    in-process servers share this process's span buffer, so the whole
    causal chain is visible locally)."""
    model, variables, _, router = socket_fleet
    p = np.random.default_rng(1).integers(0, 1024, 24).astype(np.int32)
    ref = np.asarray(generate(model, variables, p[None], 8))[0]
    out = np.asarray(router.complete(
        p, 8, timeout=120, trace={"trace_id": 424242},
    ))
    np.testing.assert_array_equal(out, ref)
    names = [e.get("name", "") for e in spans.trace_events()]
    assert "request 424242 (prefill)" in names
    assert "kv_wire 424242" in names
    assert "request 424242" in names


def test_incident_bundle_contents_and_throttle(socket_fleet, tmp_path):
    _, _, _, router = socket_fleet
    out = str(tmp_path)
    b1 = router.save_incident_bundle("unit: first", out_dir=out)
    assert b1 is not None
    have = set(os.listdir(b1))
    assert {"flight_router.json", "flight_prefill0.json",
            "flight_decode0.json", "slo_timelines.json", "metrics.prom",
            "router.json", "manifest.json"} <= have
    manifest = json.load(open(os.path.join(b1, "manifest.json")))
    assert manifest["reason"] == "unit: first"
    assert set(manifest["replica_flights"]) == {"prefill0", "decode0"}
    # The manifest inventories every artifact written BEFORE itself.
    assert set(manifest["files"]) == have - {"manifest.json"}
    flight = json.load(open(os.path.join(b1, "flight_decode0.json")))
    assert flight.get("reason") == "fleet_fetch"
    prom = open(os.path.join(b1, "metrics.prom")).read()
    assert 'replica="decode0"' in prom
    # Throttled: a flapping replica must not write one bundle per poll.
    assert router.save_incident_bundle("unit: second") is None
    b3 = router.save_incident_bundle("unit: third", out_dir=out,
                                     force=True)
    assert b3 is not None and b3 != b1
    assert router.last_incident_path == b3
    assert router.snapshot()["incidents_total"] >= 2
