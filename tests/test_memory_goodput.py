"""Memory ledger + goodput accounting + recompile forensics
(telemetry/memory.py, goodput.py, compile_watch.py).

The contracts worth pinning:

* **analytic memory model**: the formula walk (``plan_train_memory`` —
  ``jax.eval_shape`` only) agrees with the REAL per-device buffer bytes
  of a built Trainer's state (``measured_tree_bytes`` over
  ``addressable_shards``) for mlmodel and gpt2 across pure-DP, ZeRO-1,
  sharded-dp and pipeline-stash configs — and the division knobs are
  VISIBLE (ZeRO-1 state strictly smaller than replicated);
* **goodput bucket arithmetic**: buckets + the compute remainder
  reconstruct the wall-clock exactly, fractions clamp sanely, unknown
  buckets are rejected;
* **compile-event counter**: a fresh trainer compiles exactly the
  expected programs (named in the counter), steady state compiles
  ZERO; post-warmup compiles produce flight ``recompile`` events
  naming the offending shape;
* **flight context**: dumps attach the registered providers' payloads
  (device-memory snapshot, recent compile events);
* **serving KV pricing**: page geometry × dtype arithmetic and the
  ``serving_kv_pool_bytes{state=}`` gauges.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ml_trainer_tpu import Trainer, MLModel
from ml_trainer_tpu.data import SyntheticCIFAR10, SyntheticTokens
from ml_trainer_tpu.telemetry import MetricsRegistry, compile_watch, goodput
from ml_trainer_tpu.telemetry import memory as M
from ml_trainer_tpu.telemetry.flight import FlightRecorder
from ml_trainer_tpu.utils.functions import custom_pre_process_function

TOL = 0.10


def _image_trainer(model_dir, epochs=1, **kw):
    t0 = custom_pre_process_function()
    return Trainer(
        MLModel(),
        datasets=(SyntheticCIFAR10(size=64, seed=0, transform=t0),
                  SyntheticCIFAR10(size=32, seed=1, transform=t0)),
        epochs=epochs, batch_size=16, model_dir=str(model_dir),
        metric=None, lr=0.01, optimizer="adamw", **kw,
    )


def _state_measured(trainer) -> float:
    measured, _ = M.measured_tree_bytes({
        "params": trainer.state.params,
        "opt_state": trainer.state.opt_state,
        "batch_stats": trainer.state.batch_stats,
    })
    return measured


def _state_analytic(ledger) -> float:
    return sum(
        c.bytes for c in ledger.components
        if c.name in ("params", "opt_state", "batch_stats")
    )


# ------------------------------------------------------- analytic ledger
@pytest.mark.parametrize("config", [
    {},  # pure DP
    {"shard_opt_state": True},  # ZeRO-1 placement
    {"dp_update": "sharded"},  # sharded update (implies ZeRO-1)
])
def test_mlmodel_analytic_vs_measured(tmp_path, config):
    """Formula ledger vs real buffer bytes across the DP flavors on the
    virtual 8-device data mesh."""
    t = _image_trainer(
        tmp_path / "m", mesh_shape={"data": 8}, **config
    )
    plan = M.plan_train_memory(
        MLModel(), t._batch_geometry, optimizer="adamw",
        mesh_shape={"data": 8},
        shard_opt_state=config.get("shard_opt_state", False),
        dp_update=config.get("dp_update", "fused"),
    )
    check = M.cross_check(_state_analytic(plan), _state_measured(t), TOL)
    assert check["ok"], (config, check)


def test_zero1_division_is_visible(tmp_path):
    """The ÷N is real: ZeRO-1 measured state bytes are strictly below
    the replicated layout's, and the analytic ledger predicts both."""
    rep = _image_trainer(tmp_path / "rep", mesh_shape={"data": 8})
    z1 = _image_trainer(
        tmp_path / "z1", mesh_shape={"data": 8}, shard_opt_state=True
    )
    m_rep, m_z1 = _state_measured(rep), _state_measured(z1)
    assert m_z1 < m_rep
    a_rep = _state_analytic(M.plan_train_memory(
        MLModel(), rep._batch_geometry, optimizer="adamw",
        mesh_shape={"data": 8},
    ))
    a_z1 = _state_analytic(M.plan_train_memory(
        MLModel(), z1._batch_geometry, optimizer="adamw",
        mesh_shape={"data": 8}, shard_opt_state=True,
    ))
    assert a_z1 < a_rep
    assert M.cross_check(a_rep, m_rep, TOL)["ok"]
    assert M.cross_check(a_z1, m_z1, TOL)["ok"]


def test_gpt2_pipeline_stash_ledger(tmp_path):
    """gpt2 pipeline config: stage-sharded stacked params priced within
    10% of the measured buffers, and the trainer's own ledger carries a
    pipeline_stash component sized from the engine's stash accounting."""
    from ml_trainer_tpu.models import get_model
    from ml_trainer_tpu.parallel import create_mesh, rules_for

    ds = SyntheticTokens(size=16, seq_len=32, vocab_size=256, seed=0)
    mesh = create_mesh({"data": 2, "stage": 4})
    t = Trainer(
        get_model("gpt2_pipe_tiny", mesh=mesh, n_microbatches=4),
        datasets=(ds, ds), epochs=1, batch_size=8, metric=None, lr=0.01,
        optimizer="adamw", model_dir=str(tmp_path / "pp"),
        mesh_shape={"data": 2, "stage": 4},
        sharding_rules=rules_for("gpt2", "pp"),
        pipeline_schedule="1f1b", telemetry=True,
    )
    plan = M.plan_train_memory(
        get_model("gpt2_pipe_tiny", n_microbatches=4),
        t._batch_geometry, optimizer="adamw",
        mesh_shape={"data": 2, "stage": 4},
        sharding_rules=rules_for("gpt2", "pp"),
    )
    check = M.cross_check(_state_analytic(plan), _state_measured(t), TOL)
    assert check["ok"], check
    stash = t._memory_ledger.component("pipeline_stash")
    assert stash is not None and stash.bytes > 0
    # gpt2 also prices the chunked-LM-head peak when loss_chunk is on.
    gpt2 = get_model("gpt2_tiny", vocab_size=256, loss_chunk=8)
    led = M.plan_train_memory(gpt2, (4, 32), optimizer="adamw")
    lc = led.component("loss_chunk_peak")
    assert lc is not None
    assert lc.bytes == 4 * 8 * 256 * 4 * 2  # b x chunk x V x f32 x fwd+bwd


def test_ledger_publish_and_live_snapshot():
    r = MetricsRegistry()
    led = M.MemoryLedger([
        M.Component("params", 1000, "resident"),
        M.Component("grads", 500, "transient"),
    ])
    assert led.resident_bytes() == 1000
    assert led.peak_bytes() == 1500
    led.publish(registry=r)
    snap = r.snapshot()
    assert snap["mem_analytic_bytes{component=params}"] == 1000
    assert snap["mem_analytic_resident_bytes"] == 1000
    assert snap["mem_analytic_peak_bytes"] == 1500
    anchor = jnp.ones((1024,), jnp.float32)  # guarantee a live buffer
    anchor.block_until_ready()
    live = M.publish_live_memory(registry=r)
    assert live["devices"], live
    assert live["max_bytes_in_use"] > 0
    assert any(
        k.startswith("mem_live_bytes{device=") for k in r.snapshot()
    )


def test_fit_verdict_and_capacity_table():
    from ml_trainer_tpu.telemetry.flops import chip_hbm_capacity_bytes

    cap = chip_hbm_capacity_bytes("v5e")
    assert cap > 2 ** 30
    assert M.fit_verdict(0.5 * cap, generation="v5e")["verdict"] == "fits"
    assert M.fit_verdict(0.95 * cap, generation="v5e")["verdict"] == "tight"
    oom = M.fit_verdict(1.5 * cap, generation="v5e")
    assert oom["verdict"] == "oom" and oom["utilization"] > 1.0
    # No default generation: the CPU mesh is not in the table, and an
    # unknown device is an error, not a v5e guess.
    with pytest.raises(ValueError, match="not in the chip peak tables"):
        M.fit_verdict(0.5 * cap)
    with pytest.raises(ValueError, match="unknown TPU generation"):
        chip_hbm_capacity_bytes("v99")


# ------------------------------------------------------- goodput buckets
def test_goodput_bucket_arithmetic():
    """Buckets + compute remainder == wall-clock, exactly."""
    base = goodput.snapshot()
    goodput.account("data_wait", 1.0)
    goodput.account("compile", 2.5)
    goodput.account("ckpt_stall", 0.5)
    d = goodput.decompose(10.0, base=base)
    assert d["buckets_secs"]["data_wait"] == pytest.approx(1.0)
    assert d["compute_secs"] == pytest.approx(6.0)
    assert d["goodput_fraction"] == pytest.approx(0.6)
    recon = d["compute_secs"] + sum(d["buckets_secs"].values())
    assert recon == pytest.approx(d["wall_secs"])
    # Overlapping accounting cannot go negative — it is surfaced.
    d2 = goodput.decompose(2.0, base=base)
    assert d2["compute_secs"] == 0.0
    assert d2["overshoot_secs"] == pytest.approx(2.0)
    with pytest.raises(ValueError, match="unknown goodput bucket"):
        goodput.account("nonsense", 1.0)


def test_goodput_timed_and_meter():
    import time as _time

    base = goodput.snapshot()
    with goodput.timed("h2d"):
        _time.sleep(0.01)
    now = goodput.snapshot()
    assert now["h2d"] - base["h2d"] >= 0.009
    r = MetricsRegistry()
    meter = goodput.GoodputMeter(registry=r)
    assert meter.report() is None  # not started
    meter.start()
    _time.sleep(0.005)
    d = meter.report()
    assert 0.0 <= d["goodput_fraction"] <= 1.0
    snap = r.snapshot()
    assert "train_goodput_fraction" in snap
    assert "train_goodput_seconds_total{bucket=h2d}" in snap


# --------------------------------------------------- compile forensics
def test_compile_counter_fresh_vs_steady(tmp_path):
    """A fresh telemetry trainer compiles its train step exactly once
    (named in the counter); a second epoch compiles NOTHING."""
    compile_watch.install()
    before = compile_watch.compile_count("jit(train_step)")
    pw_before = compile_watch.post_warmup_count()
    compile_secs_before = goodput.snapshot()["compile"]
    t = _image_trainer(tmp_path / "cw", epochs=2, telemetry=True)
    t.fit()
    assert compile_watch.compile_count("jit(train_step)") == before + 1, (
        compile_watch.counts_by_fn()
    )
    assert compile_watch.post_warmup_count() == pw_before
    # The fresh compile was charged to the goodput ledger's bucket.
    assert goodput.snapshot()["compile"] > compile_secs_before
    # The labeled counter reached the registry.
    from ml_trainer_tpu.telemetry import default_registry

    snap = default_registry().snapshot()
    assert snap.get("compile_events_total{fn=jit(train_step)}", 0) >= 1


def test_recompile_event_names_offending_shape():
    """A post-warmup compile fires a flight ``recompile`` record whose
    explanation names the argument and shape that missed the cache."""
    compile_watch.install()
    from ml_trainer_tpu.telemetry.flight import get_recorder

    rec = get_recorder()

    @jax.jit
    def poked(x):
        return x * 3.0

    # Inputs built BEFORE warmup closes: jnp.ones itself compiles tiny
    # helper programs that must not pollute the post-warmup count.
    a4 = jnp.ones((4,), jnp.float32)
    a6 = jnp.ones((6,), jnp.float32)
    poked(a4)  # warmup compile
    compile_watch.mark_warm()
    try:
        before = compile_watch.post_warmup_count()
        poked(a4)  # cached: no event
        assert compile_watch.post_warmup_count() == before
        poked(a6)  # shape change: recompile
        assert compile_watch.post_warmup_count() == before + 1
        events = [r for r in rec.records() if r["kind"] == "recompile"]
        assert events, "no flight recompile record"
        last = events[-1]
        assert "poked" in last["fn"]
        assert last["explanation"] and "f32[6]" in last["explanation"], last
    finally:
        compile_watch.mark_cold()


def test_expect_no_compiles_guard():
    compile_watch.install()

    @jax.jit
    def g(x):
        return x + 1

    g(jnp.ones((3,)))
    with compile_watch.expect_no_compiles("steady"):
        g(jnp.ones((3,)))  # cached — fine
    with pytest.raises(AssertionError, match="unexpected compile"):
        with compile_watch.expect_no_compiles("steady"):
            g(jnp.ones((5,)))


# ------------------------------------------------------- flight context
def test_flight_dump_attaches_context(tmp_path):
    rec = FlightRecorder(capacity=8)
    rec.record("step", n=1)
    rec.register_context_provider("memory", M.memory_snapshot_payload)
    rec.register_context_provider(
        "compile_events", lambda: compile_watch.recent_events_payload(4)
    )
    rec.register_context_provider(
        "broken", lambda: (_ for _ in ()).throw(RuntimeError("boom"))
    )
    path = rec.dump("test", out_dir=str(tmp_path))
    import json

    payload = json.load(open(path))
    ctx = payload["context"]
    assert "live" in ctx["memory"]
    assert isinstance(ctx["compile_events"], list)
    assert "boom" in ctx["broken"]  # a broken provider never kills a dump


# ------------------------------------------------------- serving pricing
def test_kv_pool_bytes_and_gauges():
    assert M.kv_pool_bytes(
        n_pages=10, page_size=16, num_heads=2, head_dim=8, n_layers=3,
        dtype=jnp.float32,
    ) == 10 * 2 * 16 * 8 * 4 * 3 * 2
    from ml_trainer_tpu.serving.metrics import ServingMetrics

    m = ServingMetrics()
    m.record_kv(free=3, used=2, total=5, prefix_nodes=0,
                bytes_per_page=1024)
    snap = m.snapshot()
    assert snap["kv_pool_bytes"] == {
        "free": 3072, "used": 2048, "total": 5120,
    }
    r = MetricsRegistry()
    m.publish(registry=r)
    rsnap = r.snapshot()
    assert rsnap["serving_kv_pool_bytes{state=free}"] == 3072
    assert rsnap["serving_kv_pool_bytes{state=used}"] == 2048


# ------------------------------------------------------- run report ride
def test_run_report_has_memory_goodput_compile_sections(tmp_path):
    t = _image_trainer(tmp_path / "rr", telemetry=True)
    t.fit()
    import json
    import os

    report = json.load(
        open(os.path.join(str(tmp_path / "rr"), "run_report.json"))
    )
    assert "analytic_components" in report["memory"]
    assert report["memory"]["analytic_components"].get("params", 0) > 0
    gp = report["goodput"]
    assert 0.0 <= gp["goodput_fraction"] <= 1.0
    assert "compile" in gp["buckets_secs"]
    assert report["compiles"]["total"] >= 1
    assert "jit(train_step)" in report["compiles"]["by_fn"]
    # The heartbeat schema grew the goodput field.
    from ml_trainer_tpu.telemetry import default_registry

    snap = default_registry().snapshot()
    assert "cluster_goodput_fraction{host=0}" in snap
