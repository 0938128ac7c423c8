"""Live base-model rollout (serving/deploy.py, docs/serving.md
"Deploys") + the weights-fingerprint KV-portability gate
(serving/transfer.py, checkpoint/).

The pins: a healthy deploy ramps canary -> 100% and promotes — after
which the router serves the NEW weights byte-identically to
``generate()`` on them; a forced-regression canary (wedged new-gen
replicas) auto-rolls-back within one poll window with zero dropped
streams and byte-identical output on the stable fleet; KV never
migrates across weights (``WeightsMismatch``, keyed on the checkpoint
manifest's fingerprint); shadow mode diffs outputs before any real
traffic moves.  The train-to-serve loop closes with
``Trainer.fit() -> save_model -> Router.deploy``.
"""

import time

import jax
import numpy as np
import pytest

from ml_trainer_tpu.checkpoint import (
    load_model_manifest,
    weights_fingerprint,
    weights_structure_digest,
    write_model_manifest,
)
from ml_trainer_tpu.generate import generate
from ml_trainer_tpu.models import get_model
from ml_trainer_tpu.serving import (
    DeployConfig,
    Deployment,
    Router,
    Server,
    WeightsMismatch,
    transfer,
)
from ml_trainer_tpu.serving.deploy import TERMINAL_STATES
from ml_trainer_tpu.serving.engine import SlotDecodeEngine
from ml_trainer_tpu.serving.scheduler import Request
from ml_trainer_tpu.serving.slo import SloPolicy

PS = 8
VOCAB = 256  # small vocab keeps in-process compiles cheap


@pytest.fixture(scope="module")
def model_and_two_weights():
    """One architecture, two weight sets — generations 0 and 1."""
    model = get_model("gpt2_tiny", vocab_size=VOCAB, max_len=64)
    x = np.zeros((1, 8), np.int32)
    v0 = model.init({"params": jax.random.PRNGKey(0)}, x, train=False)
    v1 = model.init({"params": jax.random.PRNGKey(1)}, x, train=False)
    return model, v0, v1


def _prompt(seed, n):
    return np.asarray(
        np.random.default_rng(seed).integers(0, VOCAB, n), np.int32
    )


def _tenants(fraction, canary, n=6):
    """The first ``n`` tenant names whose deterministic slice falls
    inside (canary=True) / outside the ``[0, fraction)`` split."""
    out = []
    i = 0
    while len(out) < n:
        t = f"tenant{i}"
        if (Router.tenant_slice(t) < fraction) == canary:
            out.append(t)
        i += 1
    return out


# ------------------------------------------------ weights fingerprint


def test_fingerprint_distinguishes_weights_not_structure(
        model_and_two_weights):
    _, v0, v1 = model_and_two_weights
    assert weights_fingerprint(v0) != weights_fingerprint(v1)
    assert weights_structure_digest(v0) == weights_structure_digest(v1)
    # Deterministic: same tree, same digest, every call.
    assert weights_fingerprint(v0) == weights_fingerprint(v0)
    assert weights_fingerprint(v0).startswith("w:")
    assert weights_structure_digest(v0).startswith("cfg:")


def test_model_manifest_records_fingerprint(tmp_path,
                                            model_and_two_weights):
    _, v0, _ = model_and_two_weights
    meta = write_model_manifest(str(tmp_path), v0)
    loaded = load_model_manifest(str(tmp_path))
    assert loaded == meta
    assert loaded["weights_fingerprint"] == weights_fingerprint(v0)
    assert loaded["structure_digest"] == weights_structure_digest(v0)
    empty = tmp_path / "empty"
    empty.mkdir()
    assert load_model_manifest(str(empty)) is None  # pre-manifest export


def test_kv_import_refuses_cross_weights(model_and_two_weights):
    """The KV-portability rule: a slot exported under one weights
    fingerprint must never be adopted by an engine serving another —
    structured ``weights_mismatch`` refusal, not silent garbage."""
    model, v0, v1 = model_and_two_weights
    e0 = SlotDecodeEngine(model, v0, max_batch=2, kv_page_size=PS)
    e1 = SlotDecodeEngine(model, v1, max_batch=2, kv_page_size=PS)
    assert e0.weights_fp != e1.weights_fp
    assert e0.weights_fp == weights_fingerprint({"params": v0["params"]})

    req = Request(prompt=_prompt(0, 9), max_new_tokens=12)
    assert e0.admit(req, 0) == "active"
    for _ in range(4):
        e0.step()
    exp = transfer.export_kv_slot(e0, 0)
    assert exp.weights_fp == e0.weights_fp

    cont = Request(prompt=req.prompt, max_new_tokens=12)
    cont.tokens = list(req.tokens)
    with pytest.raises(WeightsMismatch, match="weights_mismatch"):
        transfer.import_kv_slot(e1, cont, 1, exp)
    # Same weights (a FRESH engine on v0): adoption proceeds.
    e0b = SlotDecodeEngine(model, v0, max_batch=2, kv_page_size=PS)
    assert transfer.import_kv_slot(e0b, cont, 1, exp) == "active"


def test_transfer_bytes_carry_weights_fp(model_and_two_weights):
    model, v0, _ = model_and_two_weights
    eng = SlotDecodeEngine(model, v0, max_batch=2, kv_page_size=PS)
    req = Request(prompt=_prompt(1, 8), max_new_tokens=8)
    assert eng.admit(req, 0) == "active"
    eng.step()
    exp = transfer.export_kv_slot(eng, 0)
    back = transfer.from_bytes(transfer.to_bytes(exp))
    assert back.weights_fp == exp.weights_fp == eng.weights_fp


# ------------------------------------------------ deterministic split


def test_tenant_slice_is_deterministic_and_bounded():
    seen = [Router.tenant_slice(f"t{i}") for i in range(512)]
    assert all(0.0 <= s < 1.0 for s in seen)
    assert seen == [Router.tenant_slice(f"t{i}") for i in range(512)]
    # Roughly uniform: a 25% split captures SOME but not all tenants.
    inside = sum(1 for s in seen if s < 0.25)
    assert 0 < inside < len(seen)


def test_generation_split_routes_canary_cohort(model_and_two_weights):
    """With a split active, canary-slice tenants place on the new
    generation and everyone else stays on stable — per placement, not
    per coin flip."""
    model, v0, v1 = model_and_two_weights
    with Router.build(model, v0, roles=["both"], max_batch=2,
                      kv_page_size=PS,
                      router_kwargs=dict(hedging=False)) as router:
        new_server = Server(model, v1, max_batch=2, kv_page_size=PS,
                            role="both")
        router.add_replica("deploy1-both0", new_server, generation=1)
        router.set_deploy_split(1, 0.25)
        canary_t = _tenants(0.25, True, n=2)
        stable_t = _tenants(0.25, False, n=2)
        p = _prompt(2, 8)
        ref0 = np.asarray(generate(model, v0, p[None], 6))[0]
        ref1 = np.asarray(generate(model, v1, p[None], 6))[0]
        for t in canary_t:
            np.testing.assert_array_equal(
                router.complete(p, 6, timeout=180, tenant=t), ref1
            )
        for t in stable_t:
            np.testing.assert_array_equal(
                router.complete(p, 6, timeout=180, tenant=t), ref0
            )
        counts = router.snapshot()["requests_total"]
    assert counts.get("colocated/deploy1-both0") == len(canary_t)
    assert counts.get("colocated/rep0") == len(stable_t)


# ------------------------------------------------------- deployments


def _deploy_router(model, variables, **slo_kw):
    policy = SloPolicy(**{**dict(ttft_ms=60_000.0, tpot_ms=60_000.0,
                                 target=0.9), **slo_kw})
    return Router.build(
        model, variables, roles=["both", "both"], max_batch=2,
        kv_page_size=PS,
        router_kwargs=dict(hedging=False, slo=policy),
    )


def _server_factory(model, variables, wedge_s=0.0):
    def factory(role):
        server = Server(model, variables, max_batch=2, kv_page_size=PS,
                        role=role)
        if wedge_s:
            inner = server.submit_request

            def wedged(req, _inner=inner):
                time.sleep(wedge_s)
                _inner(req)

            server.submit_request = wedged
        return server

    return factory


def test_deploy_ramps_and_promotes(model_and_two_weights):
    """Healthy rollout: staging spawns a full new generation, traffic
    walks canary -> 100%, the new generation is promoted and the old
    one retires — and the fleet then serves the new weights
    byte-identically to generate() on them, all on the programs the
    stable fleet had already compiled."""
    from ml_trainer_tpu.telemetry import compile_watch

    model, v0, v1 = model_and_two_weights
    p = _prompt(3, 8)
    ref1 = np.asarray(generate(model, v1, p[None], 6))[0]
    cfg = DeployConfig(canary=0.25, stages=(1.0,), hold_s=0.05,
                       min_window_requests=1, drain_timeout_s=30.0)
    with _deploy_router(model, v0) as router:
        router.complete(p, 4, timeout=180)  # warm the stable fleet
        # The new generation shares the warm fleet's compiled programs
        # (weights are arguments): a rollout compiles nothing.
        with compile_watch.expect_no_compiles("deploy on a warm fleet"):
            dep = Deployment(router, "ckpt-v1",
                             _server_factory(model, v1), config=cfg)
            assert dep.tick() == "canary"
            assert router._deploy_generation == 1
            assert router._deploy_fraction == pytest.approx(0.25)
            assert len(dep.new_replicas) == 2  # mirrors the stable role mix
            assert dep.weights_fp != dep.old_weights_fp
            for t in _tenants(0.25, True, n=2):
                router.complete(p, 6, timeout=180, tenant=t)
            time.sleep(cfg.hold_s + 0.01)
            assert dep.tick() == "ramping"
            assert router._deploy_fraction == pytest.approx(1.0)
            time.sleep(cfg.hold_s + 0.01)
            assert dep.tick() == "done"
            # Promoted: default traffic serves the new weights...
            assert router._serving_generation == 1
            assert router._deploy_generation is None
            np.testing.assert_array_equal(
                router.complete(p, 6, timeout=180), ref1
            )
        # ...and the old generation is fully retired.
        assert set(router.replicas) == set(dep.new_replicas)
        actions = [e["action"] for e in dep.events]
    assert "staged" in actions and "promoted" in actions
    assert dep.report()["state"] == "done"


def test_stage_min_requests_holds_until_slice_reports(
        model_and_two_weights):
    """With ``stage_min_requests`` set, a stage may NOT advance on the
    hold timer alone: the canary window must report finished requests
    first, so a slice whose requests are all still in flight (a slow
    regression) cannot outrun the watch."""
    model, v0, v1 = model_and_two_weights
    p = _prompt(9, 8)
    cfg = DeployConfig(canary=0.25, stages=(1.0,), hold_s=0.0,
                       min_window_requests=1, stage_min_requests=1)
    with _deploy_router(model, v0) as router:
        dep = Deployment(router, "ckpt-v1",
                         _server_factory(model, v1), config=cfg)
        assert dep.tick() == "canary"
        # Hold expired, but the slice has not reported: no advance.
        assert dep.tick() == "canary"
        assert dep.tick() == "canary"
        router.complete(p, 4, timeout=180,
                        tenant=_tenants(0.25, True, n=1)[0])
        assert dep.tick() == "ramping"  # the slice reported: advance
        assert dep.tick() == "done"     # window still holds the report
        assert router._serving_generation == 1


def test_forced_regression_canary_rolls_back(model_and_two_weights):
    """The satellite pin: wedge ONLY the canary (new-generation)
    replicas; the canary slice's burn trips the threshold and the
    deployment rolls back within one poll — zero dropped streams,
    stable-fleet output byte-identical throughout, split torn down."""
    model, v0, v1 = model_and_two_weights
    p = _prompt(4, 8)
    ref0 = np.asarray(generate(model, v0, p[None], 6))[0]
    cfg = DeployConfig(canary=0.25, stages=(1.0,), hold_s=60.0,
                       burn_threshold=2.0, high_polls=1,
                       min_window_requests=2, drain_timeout_s=60.0)
    with _deploy_router(model, v0, ttft_ms=250.0) as router:
        for t in _tenants(0.25, False, n=2):  # warm stable, pre-split
            router.complete(p, 4, timeout=180, tenant=t)
        dep = Deployment(router, "ckpt-wedged",
                         _server_factory(model, v1, wedge_s=0.6),
                         config=cfg)
        assert dep.tick() == "canary"
        canary_t = _tenants(0.25, True, n=3)
        stable_t = _tenants(0.25, False, n=3)
        stable_streams = [
            router.submit(p, 6, tenant=t) for t in stable_t
        ]
        canary_streams = [
            router.submit(p, 6, tenant=t) for t in canary_t
        ]
        canary_out = [s.result(timeout=180) for s in canary_streams]
        # One more canary stream still in flight when rollback fires:
        # it must drain or redistribute, never drop.
        inflight = router.submit(p, 6, tenant=canary_t[0])
        assert dep.tick() == "rolled_back"  # one poll, not a window
        assert dep.last_burn >= cfg.burn_threshold
        assert "canary burn" in dep.rollback_cause
        # Split torn down, new generation drained out of the fleet.
        assert router._deploy_generation is None
        assert router._deploy_fraction == 0.0
        assert set(router.replicas) == {"rep0", "rep1"}
        # Zero dropped streams: everything in flight completed.
        assert np.asarray(inflight.result(timeout=180)).size > 0
        for s, out in zip(stable_streams,
                          (s.result(timeout=180) for s in stable_streams)):
            np.testing.assert_array_equal(out, ref0)
        assert all(np.asarray(o).size > 0 for o in canary_out)
        # And the stable fleet still serves byte-identical output.
        np.testing.assert_array_equal(
            router.complete(p, 6, timeout=180, tenant=stable_t[0]), ref0
        )
    assert dep.report()["state"] == "rolled_back"


def test_shadow_mismatch_rolls_back_before_traffic_moves(
        model_and_two_weights):
    """Shadow mode replays live requests against the new weights OFF
    the serving path; different tokens -> rollback with the traffic
    split never having been raised."""
    model, v0, v1 = model_and_two_weights
    cfg = DeployConfig(shadow=True, shadow_fraction=1.0,
                       shadow_min_requests=1)
    with _deploy_router(model, v0) as router:
        dep = Deployment(router, "ckpt-diff",
                         _server_factory(model, v1), config=cfg)
        assert dep.tick() == "shadowing"
        assert router._request_tap is not None
        router.complete(_prompt(5, 8), 6, timeout=180, tenant="live")
        assert dep.tick() == "rolled_back"
        report = dep.shadow_report()
        assert report["n_token_mismatch"] >= 1
        assert "shadow diff" in dep.rollback_cause
        # No real traffic ever moved: no stage event, split never set.
        assert all(e["action"] != "stage" for e in dep.events)
        assert router._deploy_fraction == 0.0
        assert router._request_tap is None


def test_shadow_clean_proceeds_to_canary(model_and_two_weights):
    """Same weights shadow-side: replayed tokens match, latency is
    diffed into the report, and the rollout proceeds to canary."""
    model, v0, _ = model_and_two_weights
    cfg = DeployConfig(shadow=True, shadow_fraction=1.0,
                       shadow_min_requests=1, canary=0.25,
                       min_window_requests=10_000)
    with _deploy_router(model, v0) as router:
        dep = Deployment(router, "ckpt-same",
                         _server_factory(model, v0), config=cfg)
        assert dep.tick() == "shadowing"
        router.complete(_prompt(6, 8), 6, timeout=180, tenant="live")
        assert dep.tick() == "canary"
        report = dep.shadow_report()
        assert report["n_compared"] >= 1
        assert report["n_token_mismatch"] == 0
        assert report["shadow_e2e_ms_p50"] is not None
        assert router._deploy_fraction == pytest.approx(0.25)
        dep.close()


def test_deploy_guards(model_and_two_weights):
    model, v0, _ = model_and_two_weights
    with _deploy_router(model, v0) as router:
        with pytest.raises(ValueError, match="factory"):
            router.deploy("some-ckpt")  # no fleet, no factory
        dep = Deployment(router, "x", _server_factory(model, v0))
        router._deployment = dep  # unfinished: a second deploy refuses
        assert not dep.finished() and dep.state not in TERMINAL_STATES
        with pytest.raises(RuntimeError, match="already"):
            router.deploy("y", factory=_server_factory(model, v0))
        router._deployment = None


def test_deploy_flight_events_and_gauges(model_and_two_weights):
    model, v0, v1 = model_and_two_weights
    from ml_trainer_tpu.telemetry.flight import get_recorder
    from ml_trainer_tpu.telemetry.registry import default_registry

    cfg = DeployConfig(canary=0.25, stages=(1.0,), hold_s=0.0,
                       min_window_requests=10_000)
    with _deploy_router(model, v0) as router:
        dep = Deployment(router, "ckpt-v1",
                         _server_factory(model, v1), config=cfg)
        while not dep.finished():
            dep.tick()
        assert dep.state == "done"
    rows = [r for r in get_recorder().records() if r["kind"] == "deploy"]
    assert any(r.get("action") == "transition" and r.get("to") == "done"
               for r in rows)
    assert any(r.get("action") == "stage" for r in rows)
    snap = default_registry().snapshot()
    assert snap["serving_deploy_state{state=done}"] == 1.0
    assert snap["serving_deploy_generation"] == 1.0
    assert snap["serving_deploy_fraction"] == 0.0  # promoted: split down


# --------------------------------------- autoscaler stderr post-mortem


def test_replace_dead_attaches_stderr_tail(model_and_two_weights,
                                           tmp_path):
    """Satellite pin: a worker that dies AFTER readiness loses its
    stderr — the autoscaler's replace-dead flight event carries a
    bounded tail of the dead process's log instead."""
    from ml_trainer_tpu.serving import Autoscaler, AutoscalerConfig

    model, v0, _ = model_and_two_weights

    class _DeadProc:
        returncode = -9

        def poll(self):
            return -9

    with _deploy_router(model, v0) as router:
        rep = router.replica("rep0")
        rep.healthy = False
        rep.server.proc = _DeadProc()
        rep.server.stderr_tail = (
            lambda max_bytes=2048: "boom: fake traceback tail\n"
        )
        auto = Autoscaler(
            router, _server_factory(model, v0),
            config=AutoscalerConfig(min_replicas=3),
        )
        assert auto._scale_up("both", "replica rep0 found dead",
                              auto._clock(), repair=True)
        action = auto.actions[-1]
    assert action["action"] == "scale_up"
    assert "boom: fake traceback tail" in action["dead_stderr"]["rep0"]


# --------------------------------------------- train -> export -> deploy


@pytest.mark.slow
def test_trainer_fit_export_deploy_loop(tmp_path):
    """The full loop: fit a tiny gpt2, export (manifest + fingerprint),
    deploy the export onto a live in-process fleet serving the seed
    init, and verify the promoted fleet serves the TRAINED weights
    byte-identically to generate() on the loaded export."""
    from ml_trainer_tpu import Trainer
    from ml_trainer_tpu.checkpoint import load_model_variables
    from ml_trainer_tpu.data import SyntheticTokens

    model = get_model("gpt2_tiny", vocab_size=VOCAB, max_len=64)
    ds = SyntheticTokens(size=32, seq_len=16, vocab_size=VOCAB, seed=0)
    trainer = Trainer(
        model, datasets=(ds, ds), epochs=1, batch_size=8, metric=None,
        model_dir=str(tmp_path), seed=7, lr=0.01,
    )
    trainer.fit()
    manifest = load_model_manifest(str(tmp_path))
    assert manifest and manifest["weights_fingerprint"].startswith("w:")

    trained = load_model_variables(str(tmp_path))
    p = _prompt(7, 8)
    ref = np.asarray(generate(model, trained, p[None], 6))[0]
    x = np.zeros((1, 8), np.int32)
    seed_vars = model.init(
        {"params": jax.random.PRNGKey(0)}, x, train=False
    )
    cfg = DeployConfig(canary=0.25, stages=(1.0,), hold_s=0.0,
                       min_window_requests=10_000)

    def factory(role):
        return Server(model, load_model_variables(str(tmp_path)),
                      max_batch=2, kv_page_size=PS, role=role)

    with _deploy_router(model, seed_vars) as router:
        dep = Deployment(router, str(tmp_path), factory, config=cfg)
        while not dep.finished():
            dep.tick()
        assert dep.state == "done"
        # The export's manifest fingerprint IS the serving fingerprint.
        assert dep.weights_fp == manifest["weights_fingerprint"]
        np.testing.assert_array_equal(
            router.complete(p, 6, timeout=180), ref
        )
