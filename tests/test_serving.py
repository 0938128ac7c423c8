"""Continuous-batching serving engine (ml_trainer_tpu/serving/).

Ground truth is ``generate()``: a request served through the slot engine
— joining and leaving a running batch at arbitrary token boundaries —
must reproduce its standalone batch-1 ``generate()`` output
byte-for-byte, greedy and seeded-sampling alike.  Around that core:
slot recycling on EOS, admission backpressure, deadlines, metrics, and
the stdlib HTTP front end.
"""

import os
import time

import jax
import numpy as np
import pytest

from ml_trainer_tpu.generate import generate
from ml_trainer_tpu.models import get_model
from ml_trainer_tpu.serving import (
    AdmissionError,
    DeadlineExceeded,
    Server,
)


@pytest.fixture(scope="module")
def model_and_vars():
    model = get_model("gpt2_tiny", max_len=64)
    variables = model.init(
        {"params": jax.random.PRNGKey(0)}, np.zeros((1, 8), np.int32),
        train=False,
    )
    return model, variables


def _prompt(seed, n):
    return np.asarray(
        np.random.default_rng(seed).integers(0, 1024, n), np.int32
    )


def test_join_mid_decode_matches_generate_token_for_token(model_and_vars):
    """The acceptance scenario: two requests submitted MID-STREAM of a
    running decode; all three outputs byte-identical to standalone
    generate() calls (greedy and seeded sampling)."""
    model, variables = model_and_vars
    pA, pB, pC = _prompt(0, 5), _prompt(1, 3), _prompt(2, 7)
    refA = np.asarray(generate(model, variables, pA[None], 24))[0]
    refB = np.asarray(generate(model, variables, pB[None], 8))[0]
    refC = np.asarray(
        generate(model, variables, pC[None], 8, temperature=0.7,
                 rng=jax.random.PRNGKey(42))
    )[0]

    with Server(model, variables, max_batch=4) as server:
        sA = server.submit(pA, 24)
        # Consume A's first token: A is prefillled and actively decoding
        # when B and C join.
        itA = iter(sA)
        next(itA)
        sB = server.submit(pB, 8)
        sC = server.submit(pC, 8, temperature=0.7, rng=42)
        outA = sA.result(timeout=120)
        outB = sB.result(timeout=120)
        outC = sC.result(timeout=120)
        snap = server.metrics.snapshot()

    np.testing.assert_array_equal(outA, refA)
    np.testing.assert_array_equal(outB, refB)
    np.testing.assert_array_equal(outC, refC)
    # Continuous batching actually happened: the engine held more than
    # one active slot at some decode step.
    assert snap["max_active_slots"] >= 2


@pytest.mark.parametrize("write,max_len", [
    ("auto", 32), ("pallas", 32), ("pallas", 64)], ids=str)
def test_freed_slot_whose_index_ran_past_the_cache_is_served_again(
        write, max_len, monkeypatch):
    """The decode program advances EVERY row's ``cache_index``, a free
    row's too: while one long request decodes alone, the freed slot's index
    runs past the cache's last position and its write must land, clamped,
    in its own row.  The long request, and two admitted afterwards into
    both slots, serve what ``generate()`` gives.  ``pallas`` steers the
    decode step's call site to the kernel in interpret mode (at 32 positions
    the position lies on the sublanes of its tile, at 64 on the lanes);
    ``auto`` is the call site as every other test on the CPU runs it."""
    import functools

    from ml_trainer_tpu.models import layers
    from ml_trainer_tpu.ops.kernels.decode_attention import (
        decode_attention_append)
    from ml_trainer_tpu.ops.kernels.slot_cache_write import (
        _position_on_lanes)

    if write == "pallas":
        monkeypatch.setattr(
            layers, "decode_attention_append", functools.partial(
                decode_attention_append, implementation="pallas",
                interpret=True))
    # A width of its own, so that no other test's compiled decode program
    # is reused here, nor this one's there.
    model = get_model("gpt2_tiny", max_len=max_len,
                      embed_dim=64 if write == "pallas" else 96,
                      num_heads=2)
    assert _position_on_lanes(max_len, 32) == (max_len == 64)
    variables = model.init(
        {"params": jax.random.PRNGKey(1)}, np.zeros((1, 8), np.int32),
        train=False,
    )
    long_new = max_len - 4
    pA, pB, pC, pD = (_prompt(s, n) for s, n in
                      ((10, 5), (11, 3), (12, 6), (13, 4)))
    refs = [np.asarray(generate(model, variables, p[None], n))[0]
            for p, n in ((pA, 3), (pB, long_new), (pC, 7), (pD, 9))]
    with Server(model, variables, max_batch=2) as server:
        sA = server.submit(pA, 3)
        next(iter(sA))                      # A decodes before B joins
        sB = server.submit(pB, long_new)
        outs = [sA.result(timeout=120), sB.result(timeout=120)]
        leaves = jax.tree_util.tree_leaves_with_path(server.engine.cache)
        index = max(int(np.asarray(v).max()) for path, v in leaves
                    if "cache_index" in jax.tree_util.keystr(path))
        assert index >= max_len, index      # past the last position, L - 1
        sC, sD = server.submit(pC, 7), server.submit(pD, 9)
        outs += [sC.result(timeout=120), sD.result(timeout=120)]
    for out, ref in zip(outs, refs):
        np.testing.assert_array_equal(out, ref)


def test_streaming_iterator_yields_generates_tokens(model_and_vars):
    model, variables = model_and_vars
    p = _prompt(3, 4)
    ref = np.asarray(generate(model, variables, p[None], 6))[0]
    with Server(model, variables, max_batch=2) as server:
        toks = list(server.submit(p, 6))
    np.testing.assert_array_equal(np.asarray(toks, np.int32), ref[4:])


def test_eos_frees_slot_and_truncates(model_and_vars):
    """A request that hits EOS stops there (its output is generate()'s,
    cut after the EOS token) and its slot returns to the pool."""
    model, variables = model_and_vars
    # EOS := a generated token whose FIRST occurrence is past token 0,
    # so the request demonstrably decodes a few tokens before stopping.
    # Greedy decode from a random init can collapse to one repeated
    # token, so scan prompt seeds for one that yields a usable EOS.
    for seed in range(4, 64):
        p = _prompt(seed, 6)
        ref = np.asarray(generate(model, variables, p[None], 12))[0]
        gen = ref[6:]
        k = next(
            (i for i in range(1, 12) if gen[i] not in gen[:i]), None
        )
        if k is not None:
            break
    else:
        pytest.skip("no prompt produced a distinct mid-stream token")
    eos = int(gen[k])
    with Server(model, variables, max_batch=2) as server:
        out = server.complete(p, 12, eos_token_id=eos, timeout=120)
        # Slot recycled: engine drains and the slot returns to the pool
        # (poll — the loop thread releases just after the step returns).
        deadline = time.monotonic() + 10
        while (server.scheduler.free_slot_count() < 2
               and time.monotonic() < deadline):
            time.sleep(0.01)
        assert server.engine.free_capacity() == 2
        assert server.scheduler.free_slot_count() == 2
    np.testing.assert_array_equal(out, ref[: 6 + k + 1])
    assert out[-1] == eos


def test_backpressure_rejects_past_watermark(model_and_vars):
    model, variables = model_and_vars
    with Server(model, variables, max_batch=1, max_queue=2) as server:
        # One long request occupies the only slot...
        first = server.submit(_prompt(5, 4), 48)
        iter_first = iter(first)
        next(iter_first)  # it is actively decoding
        # ...two more fill the queue; the fourth must be rejected.
        q1 = server.submit(_prompt(6, 4), 4)
        q2 = server.submit(_prompt(7, 4), 4)
        with pytest.raises(AdmissionError, match="watermark"):
            server.submit(_prompt(8, 4), 4)
        assert server.metrics.snapshot()["requests_rejected"] == 1
        for s in (first, q1, q2):
            s.result(timeout=120)


def test_deadline_expires_queued_request(model_and_vars):
    model, variables = model_and_vars
    with Server(model, variables, max_batch=1, max_queue=4) as server:
        blocker = server.submit(_prompt(9, 4), 48)
        next(iter(blocker))
        # Deadline far shorter than the blocker's remaining decode.
        doomed = server.submit(_prompt(10, 4), 4, deadline=1e-3)
        with pytest.raises(DeadlineExceeded):
            doomed.result(timeout=120)
        blocker.result(timeout=120)


def test_metrics_populated(model_and_vars):
    model, variables = model_and_vars
    with Server(model, variables, max_batch=2) as server:
        server.complete(_prompt(11, 5), 8, timeout=120)
        server.complete(_prompt(12, 3), 8, timeout=120)
        snap = server.metrics.log()
    assert snap["requests_completed"] == 2
    assert snap["ttft_p50_ms"] > 0
    assert snap["tokens_per_sec_busy"] > 0
    assert snap["decode_steps_total"] >= 7  # 2 requests x 7 decode steps
    assert snap["tokens_total"] == 16
    assert 0 < snap["slot_occupancy_mean"] <= 1


def test_submit_validates_requests(model_and_vars):
    model, variables = model_and_vars
    with Server(model, variables, max_batch=1) as server:
        with pytest.raises(ValueError, match="non-empty"):
            server.submit(np.asarray([], np.int32), 4)
        with pytest.raises(ValueError, match="max_len"):
            server.submit(_prompt(13, 8), 1000)
        with pytest.raises(ValueError, match="max_new_tokens"):
            server.submit(_prompt(13, 8), 0)
        with pytest.raises(ValueError, match="eos_token_id"):
            server.submit(_prompt(13, 8), 4, eos_token_id=50_000)


def test_prefill_bucketing_compiles_once_per_bucket(model_and_vars):
    """Prompt lengths sharing a power-of-two bucket share one compiled
    prefill program (the compile cache holds one entry per bucket)."""
    from ml_trainer_tpu.generate import _COMPILED

    model, variables = model_and_vars
    with Server(model, variables, max_batch=2) as server:
        for n in (5, 6, 7, 8):  # all in the 8-bucket
            server.complete(_prompt(n, n), 2, timeout=120)
    buckets = [
        k[2] for k in _COMPILED._data if k[0] == "serve_prefill"
        and k[1] == model
    ]
    assert buckets.count(8) == 1


def test_http_front_end_round_trip(model_and_vars):
    import json
    import urllib.request

    model, variables = model_and_vars
    p = _prompt(14, 4)
    ref = np.asarray(generate(model, variables, p[None], 6))[0]
    with Server(model, variables, max_batch=2) as server:
        host, port = server.serve_http(port=0)
        base = f"http://{host}:{port}"
        body = json.dumps(
            {"prompt": [int(t) for t in p], "max_new_tokens": 6}
        ).encode()
        req = urllib.request.Request(
            f"{base}/v1/generate", data=body,
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=120) as resp:
            out = json.loads(resp.read())
        with urllib.request.urlopen(
            f"{base}/metrics.json", timeout=30
        ) as resp:
            snap = json.loads(resp.read())
        with urllib.request.urlopen(f"{base}/metrics", timeout=30) as resp:
            assert resp.headers["Content-Type"].startswith("text/plain")
            prom = resp.read().decode()
        with urllib.request.urlopen(f"{base}/healthz", timeout=30) as resp:
            health = json.loads(resp.read())
            assert health["ok"] is True and health["healthy"] is True
    np.testing.assert_array_equal(np.asarray(out["tokens"], np.int32), ref)
    assert snap["requests_completed"] >= 1
    # /metrics is Prometheus text exposition now (the telemetry spine);
    # the resilience counters must be scrapeable.
    assert "# TYPE serving_requests_completed gauge" in prom
    assert "serving_watchdog_trips 0" in prom
    for line in prom.splitlines():
        assert line.startswith("#") or " " in line, line


def test_health_payload_golden_shape(model_and_vars):
    """The /healthz payload the router places requests on: the field
    set (and the placement-critical types) is a compatibility surface —
    role, queue_depth, kv_pages_free and active_slots must exist with
    live values on both paged and contiguous servers."""
    model, variables = model_and_vars
    with Server(model, variables, max_batch=2, kv_page_size=8,
                role="decode") as server:
        server.complete(_prompt(30, 5), 4, timeout=120)
        payload = server.health()
    assert sorted(payload) == [
        "active_requests", "active_slots", "adapters_resident",
        "adoptions_pending", "closed", "compile_events_post_warmup_total",
        "degradation_level", "draining", "healthy", "kv_pages_free",
        "kv_pages_total", "max_slots", "mono_epoch", "ok", "pid",
        "queue_depth", "queued_requests", "reason", "role",
        "trace_now_us", "transport", "uptime_s", "weights_fp",
    ]
    assert payload["ok"] is True and payload["role"] == "decode"
    # Deploys key KV portability on this: same-process servers sharing
    # variables must report the same fingerprint.
    assert payload["weights_fp"].startswith("w:")
    # Process-identity fields (serving/fleet.py routes on these to tell
    # a worker process from an in-process replica).
    assert payload["pid"] == os.getpid()
    assert payload["transport"] == "inproc"
    assert payload["uptime_s"] >= 0
    assert payload["active_slots"] == 0 and payload["queue_depth"] == 0
    assert payload["max_slots"] == 2
    # Paged server: the pool gauges are live numbers the router ranks on.
    assert payload["kv_pages_total"] == 2 * (64 // 8)
    assert 0 < payload["kv_pages_free"] <= payload["kv_pages_total"]
    # No adapter pool on this server: the field exists (the router reads
    # it unconditionally) but is None, like kv_pages_free on contiguous.
    assert payload["adapters_resident"] is None
    with Server(model, variables, max_batch=1) as contig:
        p2 = contig.health()
    assert p2["role"] == "both" and p2["kv_pages_free"] is None


def test_close_fails_inflight_requests_instead_of_hanging(model_and_vars):
    """close() with work still queued/active must fail those streams
    loudly — a blocked result() after shutdown would hang forever."""
    model, variables = model_and_vars
    server = Server(model, variables, max_batch=1, max_queue=4)
    active = server.submit(_prompt(15, 4), 48)
    next(iter(active))  # occupying the only slot
    queued = server.submit(_prompt(16, 4), 4)
    server.close()
    for s in (active, queued):
        with pytest.raises(RuntimeError, match="server closed"):
            s.result(timeout=30)


def test_lru_bounds_compiled_programs():
    from ml_trainer_tpu.utils.utils import LRUCache

    lru = LRUCache(maxsize=3)
    for i in range(5):
        lru[i] = i * 10
    assert len(lru) == 3
    assert lru.get(0) is None and lru.get(1) is None
    assert lru.get(4) == 40
    # get() refreshes recency: 2 survives the next insert, 3 does not.
    assert lru.get(2) == 20
    lru[5] = 50
    assert lru.get(3) is None and lru.get(2) == 20


def test_metrics_snapshot_hammer_under_concurrent_recording():
    """The crash-fix hunt for ServingMetrics.snapshot(): every record_*
    path hammered from threads while snapshot()/log()/publish() scrape
    concurrently.  Pins the concurrency contract — no ZeroDivisionError
    on empty windows (fresh instance, spec hist empty, zero busy time),
    no mutated-during-iteration crashes, and values stay finite."""
    import threading

    from ml_trainer_tpu.serving.metrics import ServingMetrics
    from ml_trainer_tpu.telemetry.registry import MetricsRegistry

    m = ServingMetrics(window=8)  # tiny window: rollover under fire
    stop = threading.Event()
    errors = []

    def recorder(seed):
        rng = np.random.default_rng(seed)
        try:
            while not stop.is_set():
                m.record_ttft(float(rng.random()))
                m.record_prefill(float(rng.random()) * 1e-3)
                m.record_step(float(rng.random()) * 1e-3,
                              int(rng.integers(0, 5)), 4, 1)
                m.record_admission(int(rng.integers(0, 9)))
                m.record_completion()
                m.record_spec([int(a) for a in rng.integers(0, 4, 3)], 3)
                m.record_queue_depth(int(rng.integers(0, 9)))
        except Exception as e:  # pragma: no cover - the failure signal
            errors.append(e)

    def scraper():
        reg = MetricsRegistry()
        try:
            while not stop.is_set():
                snap = m.snapshot()
                assert snap["slot_occupancy_mean"] <= 1.0
                m.publish(reg)
        except Exception as e:  # pragma: no cover - the failure signal
            errors.append(e)

    # An EMPTY metrics object must snapshot cleanly too (every divisor
    # has a zero-denominator guard).
    empty = ServingMetrics().snapshot()
    assert empty["tokens_per_sec_busy"] == 0.0
    assert empty["spec_acceptance_rate"] == 0.0
    assert empty["spec_tokens_per_step"] == 0.0
    with pytest.raises(ValueError, match="window"):
        ServingMetrics(window=0)

    threads = [threading.Thread(target=recorder, args=(i,))
               for i in range(3)]
    threads += [threading.Thread(target=scraper) for _ in range(2)]
    for t in threads:
        t.start()
    time.sleep(1.0)
    stop.set()
    for t in threads:
        t.join(timeout=30)
    assert not errors, errors
    final = m.snapshot()
    assert final["requests_completed"] > 0
    assert final["spec_acceptance_rate"] <= 1.0


def test_decode_step_and_admission_are_cut_into_phase_spans(model_and_vars):
    """The spans of the engine's one thread, under the names the
    benchmark's readers use.  A turn of the decode engine is
    ``serve_prepare`` (a dispatch follows), ``serve_decode`` holding
    ``.dispatch`` and/or ``.fence``, ``serve_deliver`` (a fence came
    before); no sibling overlaps the next.  Through the serving loop the
    turn dispatches step n+1 (``ahead``: 1) and fences step n; before an
    admission, and with nothing left active, it only fences; after one it
    only dispatches.  Through ``engine.step()`` both are the same step.
    Per admission ``serve_admit`` holds the prefill, its fence and the
    first token's emission, with every dispatched step fenced before it.

    An admission turn is wrapped (``engine.ADMISSION_SPANS``): the
    landing it forces is ``serve_land`` round that fence-only
    ``serve_decode`` and its ``serve_deliver``; the first dispatch after
    it, with nothing ahead, is ``serve_restart`` round its
    ``serve_prepare`` and ``serve_decode``, counting the admissions
    since the last one.  A steady turn has neither wrapper, and
    ``engine.step()`` records neither."""
    from ml_trainer_tpu.serving.engine import (
        ADMISSION_SPANS,
        SlotDecodeEngine,
    )
    from ml_trainer_tpu.serving.scheduler import Request
    from ml_trainer_tpu.telemetry.spans import clear_trace, trace_events

    model, variables = model_and_vars

    def end(e):
        return e["ts"] + e["dur"]

    def engine_spans():
        events = sorted(
            (e for e in trace_events()
             if e["ph"] == "X" and e["name"].startswith("serve_")
             and e["name"] != "serve_wait"),
            key=lambda e: (e["ts"], -e["dur"]))
        assert len({e["tid"] for e in events}) == 1
        tops = [e for e in events if "." not in e["name"]
                and not e["name"].startswith("serve_prefill")
                and e["name"] not in wrappers]
        for a, b in zip(tops, tops[1:]):
            assert end(a) <= b["ts"]               # siblings never overlap
        return events, tops

    def inside(events, parent):
        return [e for e in events if e is not parent
                and parent["ts"] <= e["ts"] and end(e) <= end(parent)]

    land, admit, restart = ADMISSION_SPANS
    wrappers = (land, restart)

    def admission_turns(events, tops):
        """The loop's top level with the wrappers in it: each wrapper
        holds what the grammar says, the admissions between two
        restarts are the next restart's ``admitted``, and every dispatch
        with nothing ahead is a restart's.  Returns the top level."""
        wraps = [e for e in events if e["name"] in wrappers]
        level = sorted(wraps + [t for t in tops if not any(
            t in inside(events, w) for w in wraps)],
            key=lambda e: e["ts"])
        for a, b in zip(level, level[1:]):
            assert end(a) <= b["ts"]
        since = 0
        for i, e in enumerate(level):
            kids = inside(events, e)
            names = [k["name"] for k in kids]
            if e["name"] == land:
                assert names == ["serve_decode", "serve_decode.fence",
                                 "serve_deliver"]
                assert e["args"]["freed"] == kids[2]["args"]["freed"]
                # an admission follows, or the loop is shutting down
                assert i == len(level) - 1 or level[i + 1]["name"] == admit
            elif e["name"] == admit:
                assert names == ["serve_prefill", "serve_prefill.fence",
                                 "serve_admit.emit"]
                since += 1
            elif e["name"] == restart:
                assert names == ["serve_prepare", "serve_decode",
                                 "serve_decode.dispatch"]
                assert kids[2]["args"]["ahead"] == 0
                assert (e["args"]["engine_step"]
                        == kids[2]["args"]["engine_step"])
                assert e["args"]["admitted"] == since
                since = 0
        dispatches = [e for e in events
                      if e["name"] == "serve_decode.dispatch"]
        in_restart = [d for d in dispatches if any(
            d in inside(events, w) for w in wraps
            if w["name"] == restart)]
        # a steady turn (its step dispatched ahead) has neither wrapper
        assert [d["args"]["ahead"] for d in dispatches if d not in
                in_restart] == [1] * (len(dispatches) - len(in_restart))
        return level

    def turns(events, tops):
        """Each ``serve_decode`` with its dispatch, its fence, and the
        top-level spans just before and after it."""
        for i, decode in enumerate(tops):
            if decode["name"] != "serve_decode":
                continue
            kids = inside(events, decode)
            names = [k["name"] for k in kids]
            assert names in (
                ["serve_decode.dispatch", "serve_decode.fence"],
                ["serve_decode.dispatch"], ["serve_decode.fence"])
            by = {k["name"].split(".")[1]: k for k in kids}
            if "dispatch" in by:
                before = tops[i - 1]
                assert before["name"] == "serve_prepare"
                assert (before["args"]["engine_step"]
                        == decode["args"]["engine_step"]
                        == by["dispatch"]["args"]["engine_step"])
            if "fence" in by:
                after = tops[i + 1]
                assert after["name"] == "serve_deliver"
                assert (after["args"]["engine_step"]
                        == by["fence"]["args"]["engine_step"])
                if "dispatch" not in by:
                    assert (decode["args"]["engine_step"]
                            == by["fence"]["args"]["engine_step"])
            yield by.get("dispatch"), by.get("fence")

    # The synchronous order: one step a turn, dispatched and fenced in it.
    clear_trace()
    engine = SlotDecodeEngine(model, variables, max_batch=2)
    assert engine.admit(Request(prompt=_prompt(5, 4), max_new_tokens=4),
                        0) == "active"
    while engine.active_count():
        engine.step()
    events, tops = engine_spans()
    assert [t["name"] for t in tops[1:]] == [
        "serve_prepare", "serve_decode", "serve_deliver"] * 3
    assert not any(e["name"] in wrappers for e in events)
    for dispatch, fence in turns(events, tops):
        assert dispatch["args"]["ahead"] == 0
        assert dispatch["args"]["engine_step"] == fence["args"]["engine_step"]

    # The serving loop: the fence is one step behind the dispatch.
    clear_trace()
    with Server(model, variables, max_batch=2) as server:
        first = server.submit(_prompt(3, 5), 6)
        second = server.submit(_prompt(4, 9), 4)
        first.result(timeout=120)
        second.result(timeout=120)
    events, tops = engine_spans()
    dispatched, fenced, overlapped = [], [], 0
    for dispatch, fence in turns(events, tops):
        if dispatch is not None:
            step = dispatch["args"]["engine_step"]
            # Ahead exactly when the step before it is still in flight.
            assert dispatch["args"]["ahead"] == int(
                bool(dispatched) and dispatched[-1] not in fenced)
            dispatched.append(step)
        if fence is not None:
            fenced.append(fence["args"]["engine_step"])
            if dispatch is not None:
                assert fenced[-1] == dispatched[-1] - 1
                overlapped += 1
    # Every step dispatched once and fenced once, in order; most of them
    # with the next one already on its way.
    assert dispatched == fenced == list(range(
        dispatched[0], dispatched[0] + len(dispatched)))
    assert overlapped >= 3
    delivers = [e for e in tops if e["name"] == "serve_deliver"]
    assert len(delivers) == len(fenced)
    assert all(0 <= e["args"]["emitted"] <= 2 for e in delivers)
    # A request's first token is its prefill's; a request that ends on a
    # decode step leaves one row in the step dispatched behind it.
    assert sum(e["args"]["emitted"] for e in delivers) == (6 - 1) + (4 - 1)
    assert sum(e["args"]["freed"] for e in delivers) == 2
    assert 1 <= sum(e["args"]["dropped"] for e in delivers) <= 2
    admits = [e for e in tops if e["name"] == "serve_admit"]
    assert [a["args"]["prompt_len"] for a in admits] == [5, 9]
    for a in admits:
        assert [e["name"] for e in inside(events, a)] == [
            "serve_prefill", "serve_prefill.fence", "serve_admit.emit"]
        # Landed before anything that is not a decode step.
        earlier = [e for e in events if e["ts"] < a["ts"]]
        assert (sum(e["name"] == "serve_decode.dispatch" for e in earlier)
                == sum(e["name"] == "serve_decode.fence" for e in earlier))
    level = admission_turns(events, tops)
    assert level[0]["name"] == admit               # nothing to land yet
    assert sum(e["name"] == restart for e in level) in (1, 2)

    # One slot, three requests: each later one is admitted by a turn
    # that lands the step in flight, and shares it with no other.
    clear_trace()
    with Server(model, variables, max_batch=1) as server:
        streams = [server.submit(_prompt(6 + i, 4), 3) for i in range(3)]
        for stream in streams:
            stream.result(timeout=120)
    events, tops = engine_spans()
    level = admission_turns(events, tops)
    grammar = [e["name"] for e in level
               if e["name"] in ADMISSION_SPANS][:8]
    assert grammar == [admit, restart, land, admit, restart,
                       land, admit, restart]
    assert [e["args"]["admitted"] for e in level
            if e["name"] == restart] == [1, 1, 1]
