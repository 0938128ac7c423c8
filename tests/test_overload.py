"""Overload survival + chaos-proofed migration (serving/overload.py,
serving/autoscaler.py, the hardened router).

Ground truth stays ``generate()`` and the byte-identity contract: every
degradation rung acts at admission only, so a request already streaming
when a rung engages finishes byte-identical to its un-degraded prefix;
shed requests get the STRUCTURED 503 + retry_after, never a hang.
Around that core: breaker/quantile units, CRC-verified migration with
a bit-flipped payload, fault-injected corrupt adoption retrying on a
fallback candidate, health-poll flap damping, deadline budgets
decrementing across redistributes, hedged prefills (winner cancels
loser), autoscaler repair/hysteresis, and role reassignment draining
through the migration machinery.
"""

import time

import jax
import numpy as np
import pytest

from ml_trainer_tpu.generate import generate
from ml_trainer_tpu.models import get_model
from ml_trainer_tpu.resilience import faults
from ml_trainer_tpu.serving import (
    Autoscaler,
    AutoscalerConfig,
    CircuitBreaker,
    DegradationConfig,
    DegradationLadder,
    MigrationCorrupt,
    OverloadShed,
    RollingQuantile,
    Router,
    Server,
    transfer,
)
from ml_trainer_tpu.serving.engine import SlotDecodeEngine
from ml_trainer_tpu.serving.scheduler import Request

PS = 8  # page size (max_len=64 -> 8 pages per slot)


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


# ------------------------------------------------------------- units


def test_circuit_breaker_state_machine():
    """closed -K failures-> open -cooldown-> half-open (ONE probe) ->
    closed on success / re-open on failure."""
    t = [0.0]
    b = CircuitBreaker(threshold=2, cooldown_s=5.0, clock=lambda: t[0])
    assert b.state == "closed" and b.allow()
    b.record_failure()
    assert b.state == "closed"       # one failure is not an outage
    b.record_failure()
    assert b.state == "open" and not b.allow()
    t[0] = 4.9
    assert not b.allow()             # cooldown not elapsed
    t[0] = 5.1
    assert b.state == "half_open"
    assert b.allow()                 # the single probe
    assert not b.allow()             # second caller blocked
    b.record_failure("probe died")
    assert b.state == "open"
    t[0] = 10.3
    assert b.allow()
    b.record_success()
    assert b.state == "closed" and b.allow()
    assert [tr["to"] for tr in b.transitions] == [
        "open", "half_open", "open", "half_open", "closed",
    ]


def test_rolling_quantile_floor_and_window():
    q = RollingQuantile(window=16, min_samples=4, default=2.5)
    assert q.quantile(0.99) == 2.5   # cold: the default, never 0
    for v in (0.1, 0.2, 0.3, 0.4):
        q.observe(v)
    assert q.quantile(0.99) == pytest.approx(0.4)
    assert q.quantile(0.5) == pytest.approx(0.3)  # nearest-rank
    for _ in range(16):
        q.observe(1.0)               # window slides: old values age out
    assert q.quantile(0.5) == pytest.approx(1.0)


def test_ladder_validation_and_history():
    srv_calls = []

    class _FakeServer:
        def set_degradation(self, level, cfg):
            srv_calls.append(level)

        def shed_queued(self, below, retry_after, cause=""):
            srv_calls.append(("shed", below))
            return 2

    with pytest.raises(ValueError, match="clamp_tokens"):
        DegradationConfig(clamp_tokens=0)
    ladder = DegradationLadder(
        [_FakeServer()], DegradationConfig(shed_below_priority=1)
    )
    assert ladder.level == 0 and ladder.rung == "normal"
    ladder.step_up("burn")
    ladder.set_level(4, "burn worse")
    assert ladder.rung == "shed_queued"
    assert ("shed", 1) in srv_calls      # rung-4 entry sheds the backlog
    ladder.step_down()
    snap = ladder.snapshot()
    assert snap["level"] == 3 and snap["transitions"] == 3
    assert snap["shed_total"] == 2
    assert [r["to"] for r in snap["history"]] == [1, 4, 3]


# ----------------------------------------- degradation byte identity


def test_clamp_rung_spares_running_stream(model_and_vars):
    """Rung 1 engages while a request streams: the RUNNING request
    keeps its full budget and finishes byte-identical to generate();
    a fresh request gets the clamped budget — and its (shorter) output
    is byte-identical to its un-degraded prefix."""
    model, variables = model_and_vars
    pA, pB = _prompt(0, 9), _prompt(1, 7)
    refA = np.asarray(generate(model, variables, pA[None], 24))[0]
    refB = np.asarray(generate(model, variables, pB[None], 24))[0]
    with Server(model, variables, max_batch=2, kv_page_size=PS) as server:
        ladder = DegradationLadder(
            [server], DegradationConfig(clamp_tokens=5)
        )
        sA = server.submit(pA, 24)
        deadline = time.monotonic() + 60
        while len(sA.tokens) < 3:
            assert time.monotonic() < deadline
            time.sleep(0.01)
        ladder.set_level(1, "test burn")
        sB = server.submit(pB, 24)
        outA = np.asarray(sA.result(timeout=120))
        outB = np.asarray(sB.result(timeout=120))
    np.testing.assert_array_equal(outA, refA)       # running: undegraded
    assert outB.size == pB.size + 5                 # fresh: clamped
    np.testing.assert_array_equal(outB, refB[: outB.size])


def test_spec_off_mid_stream_stays_byte_identical(model_and_vars):
    """Rung 2 (spec off) engages mid-stream: greedy speculative decode
    equals vanilla greedy by construction, so the stream crossing the
    transition finishes byte-identical to generate() — and the engine
    really did switch to the vanilla step."""
    model, variables = model_and_vars
    p = _prompt(2, 9)
    ref = np.asarray(generate(model, variables, p[None], 20))[0]
    with Server(model, variables, max_batch=2, kv_page_size=PS,
                spec_k=4) as server:
        ladder = DegradationLadder([server])
        s = server.submit(p, 20)
        deadline = time.monotonic() + 60
        while len(s.tokens) < 4:
            assert time.monotonic() < deadline
            time.sleep(0.01)
        ladder.set_level(2, "test burn")
        assert server.engine.spec_enabled is False
        spec_steps_at_switch = server.metrics.snapshot()[
            "spec_steps_total"
        ]
        out = np.asarray(s.result(timeout=120))
        # At most the ONE in-flight verify step finishes after the rung
        # engages; every later step is the vanilla program.
        assert server.metrics.snapshot()["spec_steps_total"] <= \
            spec_steps_at_switch + 1
    np.testing.assert_array_equal(out, ref)


def test_hits_only_rung_sheds_misses_structured(model_and_vars):
    """Rung 3: a fresh prefix-cache MISS is shed with OverloadShed +
    retry_after; a request sharing a cached prefix still serves."""
    model, variables = model_and_vars
    shared = _prompt(3, 2 * PS + 4)  # two full blocks + suffix
    miss = _prompt(4, 20)
    with Server(model, variables, max_batch=2, kv_page_size=PS) as server:
        server.complete(shared, 4, timeout=120)     # prime the cache
        ladder = DegradationLadder(
            [server], DegradationConfig(retry_after_s=1.5)
        )
        ladder.set_level(3, "test burn")
        hit_out = server.complete(
            np.concatenate([shared[: 2 * PS], _prompt(5, 4)]), 3,
            timeout=120,
        )
        assert hit_out.size == 2 * PS + 4 + 3
        with pytest.raises(OverloadShed, match="hits_only") as ei:
            server.complete(miss, 4, timeout=120)
        assert ei.value.retry_after == pytest.approx(1.5)
        assert server.metrics.snapshot()["requests_shed"] == 1


def test_shed_queued_rung_keeps_priority_traffic(model_and_vars):
    """Rung 4 entry sheds LOW-priority queued requests (structured,
    retry_after) while higher-priority queued work survives and the
    running stream finishes undegraded; fresh low-priority submissions
    are refused at admission.  Rungs are cumulative, so the surviving
    queued request must be a prefix-cache HIT to clear rung 3 — it
    shares the running request's cached prompt blocks."""
    model, variables = model_and_vars
    pLong = _prompt(6, 2 * PS + 4)                  # 2 full cached blocks
    pLo = _prompt(7, 8)
    pHi = np.concatenate([pLong[: 2 * PS], _prompt(8, 4)])
    refLong = np.asarray(generate(model, variables, pLong[None], 24))[0]
    refHi = np.asarray(generate(model, variables, pHi[None], 4))[0]
    with Server(model, variables, max_batch=1, kv_page_size=PS) as server:
        sLong = server.submit(pLong, 24)            # occupies the slot
        deadline = time.monotonic() + 60
        while len(sLong.tokens) < 2:
            assert time.monotonic() < deadline
            time.sleep(0.01)
        sLo = server.submit(pLo, 4, priority=0)     # queued
        sHi = server.submit(pHi, 4, priority=1)     # queued, prioritized
        ladder = DegradationLadder(
            [server], DegradationConfig(retry_after_s=2.0,
                                        shed_below_priority=1)
        )
        ladder.set_level(4, "test burn")
        with pytest.raises(OverloadShed, match="shed") as ei:
            sLo.result(timeout=120)
        assert ei.value.retry_after == pytest.approx(2.0)
        with pytest.raises(OverloadShed, match="priority"):
            server.submit(_prompt(9, 8), 4, priority=0)
        np.testing.assert_array_equal(
            np.asarray(sLong.result(timeout=120)), refLong
        )
        np.testing.assert_array_equal(
            np.asarray(sHi.result(timeout=120)), refHi
        )
        assert ladder.snapshot()["shed_total"] == 1


def test_shed_maps_to_http_503_with_retry_after(model_and_vars):
    """The structured refusal over the wire: 503, JSON body naming the
    rung, retry_after in body AND Retry-After header."""
    import json
    import urllib.error
    import urllib.request

    model, variables = model_and_vars
    with Server(model, variables, max_batch=2, kv_page_size=PS) as server:
        DegradationLadder(
            [server], DegradationConfig(retry_after_s=3.0)
        ).set_level(4, "test")
        host, port = server.serve_http(port=0)
        body = json.dumps({
            "prompt": [int(t) for t in _prompt(10, 8)],
            "max_new_tokens": 4,
        }).encode()
        req = urllib.request.Request(
            f"http://{host}:{port}/v1/generate", data=body,
            headers={"Content-Type": "application/json"},
        )
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=60)
        err = ei.value
        assert err.code == 503
        assert err.headers["Retry-After"] == "3"
        payload = json.loads(err.read())
        assert "shed" in payload["error"]
        assert payload["retry_after"] == pytest.approx(3.0)


# -------------------------------------------------- CRC'd migration


def test_migration_payload_bit_flip_is_refused(model_and_vars):
    """A bit-flipped serialized payload raises the structured
    MigrationCorrupt (satellite regression test), and a tampered
    in-memory export is refused at import before any page scatters."""
    model, variables = model_and_vars
    eng = SlotDecodeEngine(model, variables, max_batch=2, kv_page_size=PS)
    req = Request(prompt=_prompt(11, 10), max_new_tokens=4)
    eng.admit(req, 0)
    exp = eng.export_slot(0)
    assert exp.crc32s and len(exp.crc32s) == len(exp.layers)
    payload = transfer.to_bytes(exp)
    # Clean round trip verifies.
    transfer.from_bytes(payload)
    flipped = bytearray(payload)
    flipped[len(flipped) // 2] ^= 0x10
    with pytest.raises(MigrationCorrupt, match="corrupt"):
        transfer.from_bytes(bytes(flipped))
    # In-memory tamper: import refuses before binding anything.
    exp.layers[0] = exp.layers[0].copy()
    exp.layers[0].flat[0] += 1
    dst = SlotDecodeEngine(model, variables, max_batch=2, kv_page_size=PS)
    cont = Request(prompt=exp.prompt, max_new_tokens=4)
    with pytest.raises(MigrationCorrupt, match="layer 0"):
        dst.import_slot(cont, 0, exp)
    assert dst.pool.slot_page_count(0) == 0
    assert dst.active_count() == 0


def test_corrupt_migration_retries_on_fallback_candidate(model_and_vars):
    """The migration_corrupt fault flips one payload in flight: the CRC
    gate refuses it, the router retries the adoption on a fallback
    decode candidate with a fresh serialization, and the stream stays
    byte-identical."""
    model, variables = model_and_vars
    p = _prompt(12, 9)
    ref = np.asarray(generate(model, variables, p[None], 14))[0]
    with Router.build(model, variables,
                      roles=["prefill", "decode", "decode"],
                      max_batch=2, kv_page_size=PS) as router:
        with faults.injected("migration_corrupt"):
            out = np.asarray(router.complete(p, 14, timeout=180))
        snap = router.snapshot()
    np.testing.assert_array_equal(out, ref)
    assert snap["migrations_corrupt_total"] == 1
    assert snap["migrations_total"] >= 1


# ------------------------------------------------------ flap damping


def test_single_dropped_health_poll_causes_no_redistribution(
        model_and_vars):
    """The satellite pin: ONE failed/dropped poll (healthz_flap) is
    damped — the replica stays in the pool and nothing redistributes."""
    model, variables = model_and_vars
    with Router.build(model, variables, roles=["prefill", "decode"],
                      max_batch=2, kv_page_size=PS) as router:
        # Sorted fleet: decode0 -> index 0.
        assert router.replica("decode0").server.replica_index == 0
        s = router.submit(_prompt(13, 8), 16)
        with faults.injected("healthz_flap@host=0"):
            time.sleep(4 * router._health_interval)
            out = np.asarray(s.result(timeout=180))
        snap = router.snapshot()
        assert router.replica("decode0").healthy
    assert out.size == 8 + 16
    assert snap["redistributes_total"] == 0
    assert snap["flaps_damped_total"] >= 1
    assert snap["replica_healthy"]["decode0"] == 1


# ------------------------------------------------- deadline budgets


def test_deadline_budget_survives_placement_retries(model_and_vars):
    """The deadline satellite: when every replica dies mid-stream and
    placement keeps failing, the request expires AT its deadline —
    the remaining budget decrements across redistributes instead of
    spinning the full admission-retry window."""
    from ml_trainer_tpu.serving import DeadlineExceeded

    model, variables = model_and_vars
    with Router.build(model, variables, roles=["prefill", "decode"],
                      max_batch=2, kv_page_size=PS,
                      router_kwargs={"admission_retry_s": 30.0},
                      ) as router:
        s = router.submit(_prompt(14, 8), 40, deadline=2.0)
        deadline = time.monotonic() + 60
        while len(s.tokens) < 2:
            assert time.monotonic() < deadline
            time.sleep(0.01)
        t0 = time.monotonic()
        router.kill_replica("prefill0")
        router.kill_replica("decode0")
        with pytest.raises(DeadlineExceeded):
            s.result(timeout=60)
        elapsed = time.monotonic() - t0
    # Expired near the (2s) deadline — nowhere near the 30s admission
    # retry window the un-fixed path would spin.
    assert elapsed < 10.0


def test_shadow_deadline_decrements(model_and_vars):
    """The per-attempt shadow carries the REMAINING budget, not the
    original: after time passes, a redistribute's shadow deadline is
    strictly smaller."""
    model, variables = model_and_vars
    with Router.build(model, variables, roles=["both"],
                      max_batch=2, kv_page_size=PS) as router:
        creq = Request(prompt=_prompt(15, 6), max_new_tokens=4,
                       deadline=10.0)
        time.sleep(0.25)
        remaining = router._remaining_deadline(creq)
        shadow = router._shadow(creq, [], remaining)
        assert shadow.deadline < 10.0
        assert shadow.deadline == pytest.approx(remaining, abs=0.05)
        assert remaining <= 9.8


# ----------------------------------------------------- hedged prefill


def test_hedged_prefill_wins_and_cancels_loser(model_and_vars):
    """A slow prefill replica: after the rolling-p99 clock the router
    fires a duplicate on the OTHER prefill replica, the duplicate wins,
    the loser is cancelled, and the output is byte-identical."""
    model, variables = model_and_vars
    p = _prompt(16, 9)
    ref = np.asarray(generate(model, variables, p[None], 10))[0]
    with Router.build(model, variables,
                      roles=["prefill", "prefill", "decode"],
                      max_batch=2, kv_page_size=PS,
                      router_kwargs={"hedge_min_s": 0.05},
                      ) as router:
        # Warm the hedge clock so p99 is tiny and the floor dominates.
        for _ in range(12):
            router._first_result_lat.observe(0.01)
        # The affinity ring decides the primary: slow exactly it.
        key = router._affinity_key("default", p)
        primary = router._ring.place(
            key, {n: r for n, r in router.replicas.items()
                  if r.role == "prefill"},
        )
        idx = router.replica(primary).server.replica_index
        with faults.injected(f"replica_slow@step=1,host={idx},secs=3"):
            out = np.asarray(router.complete(p, 10, timeout=180))
        snap = router.snapshot()
        # The loser was withdrawn: nothing stays active anywhere.
        deadline = time.monotonic() + 30
        while any(
            r.server.engine.active_count()
            or r.server.scheduler.queue_depth()
            for r in router.replicas.values()
        ):
            assert time.monotonic() < deadline, "loser never cancelled"
            time.sleep(0.05)
    np.testing.assert_array_equal(out, ref)
    assert snap["hedges_total"] >= 1
    assert snap["hedge_wins_total"] >= 1


def test_unseeded_sampled_requests_never_hedge(model_and_vars):
    model, variables = model_and_vars
    with Router.build(model, variables, roles=["prefill", "decode"],
                      max_batch=2, kv_page_size=PS) as router:
        greedy = Request(prompt=_prompt(17, 6), max_new_tokens=4)
        seeded = Request(prompt=_prompt(17, 6), max_new_tokens=4,
                         temperature=0.8, rng=7)
        unseeded = Request(prompt=_prompt(17, 6), max_new_tokens=4,
                           temperature=0.8)
        assert router._hedge_eligible(greedy)
        assert router._hedge_eligible(seeded)
        assert not router._hedge_eligible(unseeded)


# ------------------------------------------------------- autoscaler


def test_autoscaler_replaces_dead_replica(model_and_vars):
    """Repair rule: a replica death drops the decode fleet below its
    floor — the next tick adds a replacement (no hysteresis wait), and
    the fleet serves again."""
    from ml_trainer_tpu.telemetry.registry import MetricsRegistry

    model, variables = model_and_vars
    p = _prompt(18, 8)
    ref = np.asarray(generate(model, variables, p[None], 8))[0]
    with Router.build(model, variables,
                      roles=["prefill", "decode", "decode"],
                      max_batch=2, kv_page_size=PS) as router:
        asc = Autoscaler(
            router,
            lambda role: Server(model, variables, max_batch=2,
                                kv_page_size=PS, role=role),
            AutoscalerConfig(min_decode=2),
        )
        assert asc.tick() is None            # healthy fleet: no action
        router.kill_replica("decode0")
        assert asc.tick() == "scale_up"
        assert "auto1" in router.replicas
        assert router.replica("auto1").role == "decode"
        out = np.asarray(router.complete(p, 8, timeout=180))
        summary = asc.summary()
        reg = MetricsRegistry()
        asc.publish(reg)
        text = reg.prometheus_text()
    np.testing.assert_array_equal(out, ref)
    assert summary["counts"]["scale_up"] == 1
    assert summary["actions"][0]["cause"].startswith("decode fleet")
    assert 'autoscaler_actions_total{action="scale_up"} 1' in text
    assert "autoscaler_replicas{" in text


def test_autoscaler_hysteresis_cooldown_and_ladder(model_and_vars):
    """The control law, on a fake clock and a stubbed fleet view: burn
    must stay high for high_polls CONSECUTIVE ticks, actions respect
    the cooldown, at max_replicas the ladder steps up, and recovery
    walks the ladder back down before scaling down."""
    model, variables = model_and_vars
    with Router.build(model, variables, roles=["both"],
                      max_batch=2, kv_page_size=PS) as router:
        t = [0.0]
        asc = Autoscaler(
            router, lambda role: None,
            AutoscalerConfig(
                burn_high=2.0, burn_low=0.25, high_polls=2, low_polls=2,
                cooldown_s=4.0, max_replicas=1, role_flip=False,
                scale_down=False,
            ),
            clock=lambda: t[0],
        )
        burn = [5.0]

        def fake_fleet():
            reps = list(router.replicas.values())
            return {
                "now": t[0], "alive": reps, "total": len(reps),
                "prefill": reps, "decode": reps,
                "prefill_pressure": 4, "decode_pressure": 4,
                "burn": burn[0], "window_requests": 20,
            }

        asc._fleet = fake_fleet
        assert asc.tick() is None            # 1 high poll: hysteresis
        assert asc.tick() == "degrade"       # 2nd consecutive: rung 1
        assert router.ladder.level == 1
        assert asc.tick() is None            # cooldown holds the streak
        t[0] = 5.0
        assert asc.tick() == "degrade"       # cooldown over: rung 2
        assert router.ladder.level == 2
        burn[0] = 1.0                        # inside the band
        t[0] = 10.0
        assert asc.tick() is None            # streaks decay in-band
        burn[0] = 0.0                        # recovered
        assert asc.tick() is None            # 1 low poll
        assert asc.tick() == "undegrade"     # 2nd: rung back down
        assert router.ladder.level == 1
        t[0] = 15.0
        assert asc.tick() is None
        assert asc.tick() == "undegrade"
        assert router.ladder.level == 0


def test_role_reassignment_drains_through_migration(model_and_vars):
    """The role flip exports a busy replica's active slots through the
    migration machinery (streams keep flowing on the adopter, byte-
    identical) before the role changes."""
    model, variables = model_and_vars
    p = _prompt(19, 8)
    ref = np.asarray(generate(model, variables, p[None], 40))[0]
    # Built BEFORE the stream starts so the flip happens mid-stream.
    d2 = Server(model, variables, max_batch=2, kv_page_size=PS,
                role="decode")
    with Router.build(model, variables, roles=["prefill", "decode"],
                      max_batch=2, kv_page_size=PS) as router:
        s = router.submit(p, 40)
        deadline = time.monotonic() + 60
        while len(s.tokens) < 3:
            assert time.monotonic() < deadline
            time.sleep(0.01)
        migrations_before = router.snapshot()["migrations_total"]
        router.add_replica("d2", d2)
        assert router.reassign_role("decode0", "prefill", timeout=30.0)
        assert router.replica("decode0").role == "prefill"
        assert router.replica("decode0").server.role == "prefill"
        out = np.asarray(s.result(timeout=180))
        snap = router.snapshot()
    np.testing.assert_array_equal(out, ref)
    # The evacuation itself moved KV (beyond the original admission).
    assert snap["migrations_total"] > migrations_before
    assert snap["redistributes_total"] == 0  # drained, not failed over
