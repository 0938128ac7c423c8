"""Disaggregated prefill/decode serving (serving/router.py, transfer.py).

Ground truth stays ``generate()``: a request whose KV migrates between
replicas at page granularity — prefill on one engine, decode on another,
even a replica DEATH mid-stream with redistribution to a survivor —
must reproduce its standalone batch-1 ``generate()`` output
byte-for-byte, greedy and spec mode alike.  Around that core: the
export/import bit-identity unit (pool -> fresh pool, greedy AND spec_k
continuations), serialization round-trip, affinity placement, session
stickiness, drain-and-redistribute with structured errors past the
redistribution budget, and router metrics on the registry.
"""

import time

import jax
import numpy as np
import pytest

from ml_trainer_tpu.generate import generate
from ml_trainer_tpu.models import get_model
from ml_trainer_tpu.serving import Router, Server
from ml_trainer_tpu.serving import transfer
from ml_trainer_tpu.serving.engine import SlotDecodeEngine
from ml_trainer_tpu.serving.scheduler import Request

PS = 8  # page size used throughout (max_len=64 -> 8 pages per slot)


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


def _drain(engine):
    """Step an engine until every active request finishes."""
    while engine.active_count():
        engine.step()


# ------------------------------------------------------- transfer unit


def test_migration_bit_identity_greedy_mid_stream(model_and_vars):
    """The satellite pin: export a MID-STREAM slot's pages + table from
    one pool, import into a fresh pool, and the greedy continuation is
    byte-identical to the never-migrated run."""
    model, variables = model_and_vars
    p = _prompt(0, 9)
    ref = np.asarray(generate(model, variables, p[None], 20))[0]

    src = SlotDecodeEngine(model, variables, max_batch=2, kv_page_size=PS)
    req = Request(prompt=p, max_new_tokens=20)
    assert src.admit(req, 0) == "active"
    for _ in range(6):
        src.step()
    mid_tokens = list(req.tokens)
    assert 1 < len(mid_tokens) < 20  # genuinely mid-stream
    exp = src.export_slot(0)
    assert exp.n_pages == src.pool.slot_page_count(0)
    assert exp.pos == int(src._pos[0])

    dst = SlotDecodeEngine(model, variables, max_batch=2, kv_page_size=PS)
    cont = Request(prompt=p, max_new_tokens=20)
    cont.tokens = mid_tokens
    assert dst.import_slot(cont, 1, exp) == "active"
    _drain(dst)
    out = np.concatenate([p, np.asarray(cont.tokens, np.int32)])
    np.testing.assert_array_equal(out, ref)
    # The source engine still holds its own copy untouched — export is
    # read-only: finishing the source run stays byte-identical too.
    _drain(src)
    np.testing.assert_array_equal(
        np.concatenate([p, np.asarray(req.tokens, np.int32)]), ref
    )


def test_migration_bit_identity_spec_continuation(model_and_vars):
    """Spec-mode continuation after migration: the verify window reads
    the imported pages and commits byte-identically to generate()."""
    model, variables = model_and_vars
    p = _prompt(1, 11)
    ref = np.asarray(generate(model, variables, p[None], 16))[0]

    src = SlotDecodeEngine(model, variables, max_batch=2,
                           kv_page_size=PS, spec_k=4)
    req = Request(prompt=p, max_new_tokens=16)
    assert src.admit(req, 0) == "active"
    for _ in range(2):
        src.step()
    assert 0 < len(req.tokens) < 16
    exp = src.export_slot(0)

    dst = SlotDecodeEngine(model, variables, max_batch=2,
                           kv_page_size=PS, spec_k=4)
    cont = Request(prompt=p, max_new_tokens=16)
    cont.tokens = list(req.tokens)
    assert dst.import_slot(cont, 0, exp) == "active"
    assert dst._caps[0] == min(p.size + 16 - 1, dst.max_len - 4 - 1)
    _drain(dst)
    out = np.concatenate([p, np.asarray(cont.tokens, np.int32)])
    np.testing.assert_array_equal(out, ref)


def test_transfer_serialization_round_trip(model_and_vars):
    """to_bytes/from_bytes is lossless — the payload is transport-ready
    and the byte count the router meters is the real moved volume."""
    model, variables = model_and_vars
    p = _prompt(2, 10)
    eng = SlotDecodeEngine(model, variables, max_batch=2, kv_page_size=PS)
    req = Request(prompt=p, max_new_tokens=8, temperature=0.7, rng=42)
    eng.admit(req, 0)
    exp = eng.export_slot(0)
    payload = transfer.to_bytes(exp)
    assert len(payload) >= exp.nbytes()
    back = transfer.from_bytes(payload)
    for field in ("page_size", "pages_per_slot", "max_len", "n_pages",
                  "pos", "tokens", "last_token", "step_counter"):
        assert getattr(back, field) == getattr(exp, field), field
    assert back.temperature == pytest.approx(exp.temperature)
    np.testing.assert_array_equal(back.prompt, exp.prompt)
    np.testing.assert_array_equal(back.rng_key, exp.rng_key)
    assert len(back.layers) == len(exp.layers)
    for a, b in zip(back.layers, exp.layers):
        np.testing.assert_array_equal(a, b)


def test_import_geometry_mismatch_is_structured(model_and_vars):
    model, variables = model_and_vars
    eng = SlotDecodeEngine(model, variables, max_batch=2, kv_page_size=PS)
    req = Request(prompt=_prompt(3, 9), max_new_tokens=4)
    eng.admit(req, 0)
    exp = eng.export_slot(0)
    other = SlotDecodeEngine(model, variables, max_batch=2,
                             kv_page_size=16)
    cont = Request(prompt=exp.prompt, max_new_tokens=4)
    with pytest.raises(ValueError, match="geometry"):
        other.import_slot(cont, 0, exp)
    contig = SlotDecodeEngine(model, variables, max_batch=2)
    with pytest.raises(ValueError, match="paged"):
        contig.import_slot(cont, 0, exp)


def test_import_no_memory_reports_instead_of_wedging(model_and_vars):
    """A target pool too small for the chain returns "no_memory" (the
    server falls back to requeue-and-reprefill) without corrupting the
    pool: nothing stays bound."""
    model, variables = model_and_vars
    src = SlotDecodeEngine(model, variables, max_batch=2, kv_page_size=PS)
    req = Request(prompt=_prompt(4, 30), max_new_tokens=4)
    src.admit(req, 0)
    exp = src.export_slot(0)
    dst = SlotDecodeEngine(model, variables, max_batch=2,
                           kv_page_size=PS, kv_pages=exp.n_pages,
                           prefix_cache=False)  # 1 allocatable short
    cont = Request(prompt=exp.prompt, max_new_tokens=4)
    assert dst.import_slot(cont, 0, exp) == "no_memory"
    assert dst.pool.slot_page_count(0) == 0
    assert dst.active_count() == 0


# ----------------------------------------------------- router end to end


def test_router_disagg_byte_identity_greedy_and_sampled(model_and_vars):
    """Requests routed prefill -> migrate -> decode reproduce their
    standalone generate() outputs, greedy and seeded sampling alike,
    migrations actually happened, and once the trace has been served
    (cold, then with its prefixes cached) replaying it compiles
    nothing: export, wire, import and decode are steady programs."""
    from ml_trainer_tpu.telemetry import compile_watch

    model, variables = model_and_vars
    pA, pB, pC = _prompt(5, 9), _prompt(6, 5), _prompt(7, 12)
    refA = np.asarray(generate(model, variables, pA[None], 16))[0]
    refB = np.asarray(generate(model, variables, pB[None], 10))[0]
    refC = np.asarray(
        generate(model, variables, pC[None], 10, temperature=0.7,
                 rng=jax.random.PRNGKey(42))
    )[0]
    with Router.build(model, variables, roles=["prefill", "decode"],
                      max_batch=2, kv_page_size=PS) as router:
        sA = router.submit(pA, 16)
        sB = router.submit(pB, 10)
        sC = router.submit(pC, 10, temperature=0.7, rng=42)
        outs = [s.result(timeout=180) for s in (sA, sB, sC)]
        snap = router.snapshot()
        router.complete(pA, 16, timeout=180)  # warms the prefix-hit path
        with compile_watch.expect_no_compiles("router steady state"):
            again = router.complete(pA, 16, timeout=180)
    np.testing.assert_array_equal(outs[0], refA)
    np.testing.assert_array_equal(outs[1], refB)
    np.testing.assert_array_equal(outs[2], refC)
    np.testing.assert_array_equal(again, refA)
    assert snap["migrations_total"] >= 3
    assert snap["kv_migrated_bytes_total"] > 0
    assert snap["mode"] == "disagg"


def test_router_colocated_matches_disagg(model_and_vars):
    """Colocated mode (every replica both roles, no migration) serves
    the same trace byte-identically at an equal replica count."""
    model, variables = model_and_vars
    prompts = [_prompt(s, 6 + s % 5) for s in (8, 9, 10)]
    refs = [
        np.asarray(generate(model, variables, p[None], 8))[0]
        for p in prompts
    ]
    with Router.build(model, variables, roles=["both", "both"],
                      max_batch=2, kv_page_size=PS) as router:
        outs = [router.complete(p, 8, timeout=180) for p in prompts]
        snap = router.snapshot()
    for out, ref in zip(outs, refs):
        np.testing.assert_array_equal(out, ref)
    assert snap["mode"] == "colocated"
    assert snap["migrations_total"] == 0


def test_affinity_routes_same_prefix_to_same_prefill_replica(
        model_and_vars):
    """Consistent hashing on tenant + first KV block: requests sharing
    a system prompt land on ONE prefill replica (its prefix cache keeps
    the hit rate), different prefixes may spread."""
    model, variables = model_and_vars
    shared = _prompt(11, PS)  # one full block, the affinity key
    with Router.build(model, variables,
                      roles=["prefill", "prefill", "decode"],
                      max_batch=2, kv_page_size=PS) as router:
        suffixes = [_prompt(100 + i, 4) for i in range(4)]
        for sfx in suffixes:
            router.complete(
                np.concatenate([shared, sfx]), 2, timeout=180,
                tenant="affine",
            )
        snap = router.snapshot()
        hits = router.replica("prefill0").server.engine._prefix.hits \
            + router.replica("prefill1").server.engine._prefix.hits
    placed = {
        key: n for key, n in snap["requests_total"].items()
        if key.startswith("prefill/")
    }
    # All four identical-prefix requests prefilled on the same replica...
    assert len(placed) == 1 and sum(placed.values()) == 4, placed
    # ...so after the first, every one hit that replica's prefix cache.
    assert hits >= 3


def test_session_stickiness_pins_decode_replica(model_and_vars):
    model, variables = model_and_vars
    with Router.build(model, variables,
                      roles=["prefill", "decode", "decode"],
                      max_batch=2, kv_page_size=PS) as router:
        for i in range(3):
            router.complete(_prompt(20 + i, 6), 3, timeout=180,
                            session="chat-1")
        snap = router.snapshot()
    decode_placed = {
        key: n for key, n in snap["requests_total"].items()
        if key.startswith("decode/")
    }
    assert len(decode_placed) == 1 and sum(decode_placed.values()) == 3, \
        decode_placed
    assert snap["sessions"] == 1


def test_replica_kill_redistributes_in_flight(model_and_vars):
    """The acceptance pin: a decode replica dies MID-STREAM; the router
    redistributes its in-flight requests to a survivor, the job
    completes, and every output stays byte-identical — and the failure
    it recorded opens the dead replica's circuit breaker without
    waiting for the health poller."""
    model, variables = model_and_vars
    prompts = [_prompt(30 + i, 7 + i) for i in range(4)]
    refs = [
        np.asarray(generate(model, variables, p[None], 28))[0]
        for p in prompts
    ]
    with Router.build(model, variables,
                      roles=["prefill", "decode", "decode"],
                      max_batch=2, kv_page_size=PS,
                      router_kwargs={"breaker_threshold": 1}) as router:
        streams = [router.submit(p, 28) for p in prompts]
        deadline = time.monotonic() + 120
        while any(len(s.tokens) < 2 for s in streams):
            assert time.monotonic() < deadline, "streams never started"
            time.sleep(0.02)
        router.kill_replica("decode0")
        outs = [np.asarray(s.result(timeout=180)) for s in streams]
        snap = router.snapshot()
        breakers = {n: r.breaker.state for n, r in router.replicas.items()}
    for out, ref in zip(outs, refs):
        np.testing.assert_array_equal(out, ref)
    assert snap["redistributes_total"] >= 1
    assert snap["replica_healthy"]["decode0"] == 0
    assert snap["replica_healthy"]["decode1"] == 1
    assert breakers["decode0"] == "open" and breakers["decode1"] == "closed"


def test_redistribution_budget_exhaustion_is_structured(model_and_vars):
    """Past the redistribution budget the client gets a STRUCTURED
    error naming the request, the budget and the root cause — never a
    hang."""
    model, variables = model_and_vars
    with Router.build(model, variables, roles=["prefill", "decode"],
                      max_batch=2, kv_page_size=PS,
                      router_kwargs={"max_redistributes": 0,
                                     "admission_retry_s": 2.0},
                      ) as router:
        s = router.submit(_prompt(40, 8), 40)
        deadline = time.monotonic() + 120
        while len(s.tokens) < 2:
            assert time.monotonic() < deadline
            time.sleep(0.02)
        router.kill_replica("decode0")
        with pytest.raises(RuntimeError, match="max_redistributes"):
            s.result(timeout=180)


def test_router_metrics_on_registry(model_and_vars):
    """router_* series land on the registry with their labels — what
    a /metrics scrape of the router's HTTP front serves."""
    from ml_trainer_tpu.telemetry.registry import MetricsRegistry

    model, variables = model_and_vars
    with Router.build(model, variables, roles=["prefill", "decode"],
                      max_batch=2, kv_page_size=PS) as router:
        router.complete(_prompt(50, 6), 4, timeout=180)
        reg = MetricsRegistry()
        router.publish(reg)
        text = reg.prometheus_text()
    assert 'router_requests_total{replica="prefill0",role="prefill"}' \
        in text or \
        'router_requests_total{role="prefill",replica="prefill0"}' in text
    assert "router_kv_migrated_bytes_total" in text
    assert 'router_replica_healthy{replica="decode0"} 1' in text
    assert 'router_replica_slo_attainment{' in text
    assert "router_redistributes_total" in text
    assert "router_migrations_total" in text
    # The overload stack's series ride the same scrape.
    assert "serving_degradation_level" in text
    assert "router_hedges_total" in text
    assert "router_flaps_damped_total" in text
    assert 'router_breaker_state{replica="decode0"} 0' in text


def test_router_rejects_heterogeneous_or_contiguous_fleet(model_and_vars):
    model, variables = model_and_vars
    srv_paged = Server(model, variables, max_batch=2, kv_page_size=PS,
                       role="prefill")
    srv_contig = Server(model, variables, max_batch=2, role="decode")
    try:
        with pytest.raises(ValueError, match="paged"):
            Router({"p0": srv_paged, "d0": srv_contig})
        with pytest.raises(ValueError, match="role"):
            Server(model, variables, max_batch=2, role="router")
    finally:
        srv_paged.close()
        srv_contig.close()


def test_router_validates_requests(model_and_vars):
    model, variables = model_and_vars
    with Router.build(model, variables, roles=["both"],
                      max_batch=2, kv_page_size=PS) as router:
        with pytest.raises(ValueError, match="non-empty"):
            router.submit(np.asarray([], np.int32), 4)
        with pytest.raises(ValueError, match="max_len"):
            router.submit(_prompt(60, 8), 1000)
        with pytest.raises(ValueError, match="eos_token_id"):
            router.submit(_prompt(60, 8), 4, eos_token_id=10**6)
