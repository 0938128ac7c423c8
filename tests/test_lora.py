"""Batched LoRA adapters (ml_trainer_tpu/lora.py, serving/adapter_pool.py).

Ground truths: (1) ``adapter=None`` traffic through a LoRA-enabled
engine is byte-identical to ``generate()`` on the base model — slot 0's
all-zero trash adapter makes the delta an exact float zero; (2) the
frozen base never moves — ``Trainer(lora=...)`` trains only the
``*_lora_A/B`` leaves and the export→hot-load round trip serves the
SAME base bytes; (3) one rank bucket means mixed-rank adapter traffic
and hot-loads mint zero programs after warmup; (4) a prefix-cache hit
under adapter X never serves adapter Y's K/V.
"""

import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ml_trainer_tpu.generate import _COMPILED, generate
from ml_trainer_tpu.lora import (
    LoraConfig,
    export_lora_artifact,
    load_lora_artifact,
    strip_lora_params,
)
from ml_trainer_tpu.models import get_model
from ml_trainer_tpu.serving import (
    AdapterConfig,
    AdapterPool,
    AdapterPoolExhausted,
    Server,
    TenantLoad,
    UnknownAdapter,
    poisson_schedule,
    schedule_from_trace,
    schedule_to_records,
)

PS = 8  # kv page size for the paged legs


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


def _make_artifact(model, path, *, name, rank=4, alpha=8.0,
                   targets=("qkv", "proj"), seed=0, scale=2.0):
    """Fabricate a plausible adapter artifact: init the TRAIN-mode lora
    model (A ~ N(0, 0.01²), B zero) and give B real mass so the adapter
    visibly moves logits."""
    cfg = LoraConfig(rank=rank, alpha=alpha, targets=targets)
    lm = model.clone(lora_rank=rank, lora_alpha=alpha,
                     lora_targets=tuple(targets))
    params = jax.device_get(lm.init(
        {"params": jax.random.PRNGKey(7)}, np.zeros((1, 8), np.int32),
        train=False,
    )["params"])
    key = jax.random.PRNGKey(seed)

    def bump(node):
        out = {}
        for k, v in node.items():
            if hasattr(v, "items"):
                out[k] = bump(v)
            elif "_lora_B" in k:
                nonlocal key
                key, sub = jax.random.split(key)
                out[k] = np.asarray(
                    jax.random.normal(sub, v.shape), np.float32
                ) * scale
            else:
                out[k] = v
        return out

    export_lora_artifact(bump(dict(params)), cfg, path, name=name)
    return path


# ------------------------------------------------ pool mechanics (host)


def test_pool_refcount_eviction_and_exhaustion(model_and_vars, tmp_path):
    model, _ = model_and_vars
    paths = {
        n: _make_artifact(model, str(tmp_path / f"{n}.npz"), name=n,
                          seed=i)
        for i, n in enumerate(("a", "b", "c"))
    }
    pool = AdapterPool(AdapterConfig(
        slots=3, rank=8, targets=("qkv", "proj"),
        sources={n: p for n, p in paths.items()},
    ))
    # 2 loadable slots.  Load a and b; both held.
    slot_a, up_a = pool.acquire("a")
    slot_b, up_b = pool.acquire("b")
    assert up_a is not None and up_b is not None
    assert sorted((slot_a, slot_b)) == [1, 2]
    # Eviction REFUSED while both slots are held: c cannot load.
    with pytest.raises(AdapterPoolExhausted, match="'c'"):
        pool.acquire("c")
    # Residency hit: a second holder of "a" pins the same slot.
    slot_a2, up = pool.acquire("a")
    assert slot_a2 == slot_a and up is None
    assert pool.counters()["hits"] == 1
    # Release a fully; it STAYS resident (warm) until c needs the slot.
    pool.release(slot_a)
    pool.release(slot_a)
    assert pool.resident() == ["a", "b"]
    slot_c, up_c = pool.acquire("c")
    assert slot_c == slot_a and up_c is not None  # LRU victim was a
    assert pool.counters()["evictions"] == 1
    assert pool.resident() == ["b", "c"]
    with pytest.raises(UnknownAdapter, match="'nope'"):
        pool.acquire("nope")
    # Trash slot releases are no-ops; double release of a real pin is
    # refused.
    pool.release(0)
    pool.release(slot_b)
    with pytest.raises(ValueError, match="unheld"):
        pool.release(slot_b)


def test_pool_config_validation(model_and_vars, tmp_path):
    model, _ = model_and_vars
    with pytest.raises(ValueError, match="slots"):
        AdapterConfig(slots=1)
    with pytest.raises(ValueError, match="subset"):
        AdapterConfig(targets=("qkv", "nonsense"))
    # An artifact above the pool's rank bucket is refused at register.
    path = _make_artifact(model, str(tmp_path / "big.npz"), name="big",
                          rank=16)
    pool = AdapterPool(AdapterConfig(slots=3, rank=8))
    with pytest.raises(ValueError, match="rank 16 exceeds"):
        pool.register("big", path)


def test_artifact_round_trip(model_and_vars, tmp_path):
    model, _ = model_and_vars
    path = _make_artifact(model, str(tmp_path / "x.npz"), name="x")
    meta, leaves = load_lora_artifact(path)
    assert meta["rank"] == 4 and meta["n_leaves"] == len(leaves) == 8
    assert all("_lora_" in k for k in leaves)


# --------------------------------------------- serving byte disciplines


def test_adapter_none_bit_identical_and_adapter_changes_logits(
        model_and_vars, tmp_path):
    """The acceptance core: base traffic through a LoRA-enabled server
    (contiguous AND paged) reproduces generate() byte-for-byte, while
    adapter-carrying rows in the SAME decode batch get their own
    deltas."""
    model, variables = model_and_vars
    path = _make_artifact(model, str(tmp_path / "x.npz"), name="x")
    prompts = [_prompt(i, 5 + 3 * i) for i in range(3)]
    refs = [
        np.asarray(generate(model, variables, p[None], 6))[0]
        for p in prompts
    ]
    for paged in (False, True):
        kwargs = {"kv_page_size": PS} if paged else {}
        with Server(model, variables, max_batch=4,
                    adapters=AdapterConfig(
                        slots=4, rank=8, targets=("qkv", "proj"),
                        sources={"x": path},
                    ), **kwargs) as srv:
            # Mixed batch: base + adapter rows decode TOGETHER.
            streams = [srv.submit(p, 6) for p in prompts]
            sx = srv.submit(prompts[0], 6, adapter="x")
            outs = [np.asarray(s.result(timeout=300)) for s in streams]
            out_x = np.asarray(sx.result(timeout=300))
        for got, ref in zip(outs, refs):
            np.testing.assert_array_equal(got, ref)
        assert not np.array_equal(out_x, refs[0]), (
            "adapter delta did not reach the logits"
        )


def test_adapter_unknown_and_no_pool_are_structured(model_and_vars):
    model, variables = model_and_vars
    with Server(model, variables, max_batch=2) as srv:
        with pytest.raises(ValueError, match="no adapter pool"):
            srv.submit(_prompt(0, 5), 4, adapter="x")
    with Server(model, variables, max_batch=2,
                adapters=AdapterConfig(slots=3, rank=8)) as srv:
        stream = srv.submit(_prompt(0, 5), 4, adapter="ghost")
        with pytest.raises(RuntimeError, match="unknown adapter 'ghost'"):
            stream.result(timeout=60)


def test_pool_exhaustion_is_structured_error_naming_adapter(
        model_and_vars, tmp_path):
    """Every loadable slot held by an active stream: the next adapter's
    admission fails with a structured error naming it (and the pool
    recovers once a holder finishes)."""
    model, variables = model_and_vars
    pa = _make_artifact(model, str(tmp_path / "a.npz"), name="a", seed=1)
    pb = _make_artifact(model, str(tmp_path / "b.npz"), name="b", seed=2)
    with Server(model, variables, max_batch=3,
                adapters=AdapterConfig(slots=2, rank=8,
                                       sources={"a": pa, "b": pb})) as srv:
        sa = srv.submit(_prompt(0, 5), 40, adapter="a")
        next(iter(sa))          # "a" is resident AND held
        sb = srv.submit(_prompt(1, 5), 4, adapter="b")
        with pytest.raises(RuntimeError,
                           match="adapter pool exhausted loading 'b'"):
            sb.result(timeout=120)
        sa.result(timeout=300)  # the holder finishes -> slot free
        out = np.asarray(
            srv.complete(_prompt(1, 5), 4, adapter="b", timeout=300)
        )
        assert out.size == 9


def test_prefix_cache_isolated_per_adapter(model_and_vars, tmp_path):
    """A cross-adapter probe of a cached prompt gets a MISS: adapter
    K/V differs, so sharing would be wrong logits, not just a side
    channel.  Same-adapter repeats still hit."""
    model, variables = model_and_vars
    path = _make_artifact(model, str(tmp_path / "x.npz"), name="x")
    p = np.concatenate([_prompt(3, 2 * PS), _prompt(4, 3)])
    ref = np.asarray(generate(model, variables, p[None], 4))[0]
    with Server(model, variables, max_batch=2, kv_page_size=PS,
                adapters=AdapterConfig(slots=3, rank=8,
                                       sources={"x": path})) as srv:
        eng = srv.engine
        base1 = np.asarray(srv.complete(p, 4, timeout=300))
        h0, m0 = eng._prefix.hits, eng._prefix.misses
        # Cross-adapter probe of the SAME prompt: a miss, own namespace.
        out_x = np.asarray(srv.complete(p, 4, adapter="x", timeout=300))
        assert (eng._prefix.hits, eng._prefix.misses) == (h0, m0 + 1)
        # Same-adapter repeat: a hit inside the adapter's namespace.
        out_x2 = np.asarray(srv.complete(p, 4, adapter="x", timeout=300))
        assert eng._prefix.hits == h0 + 1
        # Base repeat after the adapter traffic: still hits ITS pages
        # and still reproduces generate() byte-for-byte.
        base2 = np.asarray(srv.complete(p, 4, timeout=300))
    np.testing.assert_array_equal(base1, ref)
    np.testing.assert_array_equal(base2, ref)
    np.testing.assert_array_equal(out_x, out_x2)
    assert not np.array_equal(out_x, base1)


def test_mixed_rank_hot_load_zero_recompiles(model_and_vars, tmp_path):
    """The rank-bucket discipline: after one warmup wave, traffic over
    adapters of DIFFERENT trained ranks plus a mid-run hot-load of a
    brand-new adapter mints zero compiled programs."""
    model, variables = model_and_vars
    r2 = _make_artifact(model, str(tmp_path / "r2.npz"), name="r2",
                        rank=2, seed=1)
    r4 = _make_artifact(model, str(tmp_path / "r4.npz"), name="r4",
                        rank=4, seed=2)
    r8 = _make_artifact(model, str(tmp_path / "r8.npz"), name="r8",
                        rank=8, seed=3)
    with Server(model, variables, max_batch=2, kv_page_size=PS,
                adapters=AdapterConfig(slots=8, rank=8,
                                       sources={"r2": r2, "r4": r4})
                ) as srv:
        p = _prompt(9, 7)
        for a in (None, "r2", "r4"):
            srv.complete(p, 4, adapter=a, timeout=300)
        n_warm = len(_COMPILED._data)
        # Mixed-rank wave + a hot-load under (simulated) traffic.
        srv.complete(_prompt(10, 7), 5, adapter="r2", timeout=300)
        srv.complete(_prompt(11, 7), 5, adapter="r4", timeout=300)
        srv.load_adapter("r8", r8)
        out = np.asarray(
            srv.complete(_prompt(12, 7), 5, adapter="r8", timeout=300)
        )
        n_after = len(_COMPILED._data)
    assert out.size == 12
    assert n_after == n_warm, (
        f"mixed-rank/hot-load traffic compiled {n_after - n_warm} new "
        "program(s)"
    )


def test_eviction_reload_bit_identical(model_and_vars, tmp_path):
    """Evict-then-reload serves the same bytes: the registry keeps the
    host copy, so residency is pure caching."""
    model, variables = model_and_vars
    pa = _make_artifact(model, str(tmp_path / "a.npz"), name="a", seed=1)
    pb = _make_artifact(model, str(tmp_path / "b.npz"), name="b", seed=2)
    p = _prompt(5, 6)
    with Server(model, variables, max_batch=2,
                adapters=AdapterConfig(slots=2, rank=8,
                                       sources={"a": pa, "b": pb})) as srv:
        out_a1 = np.asarray(srv.complete(p, 5, adapter="a", timeout=300))
        # Only ONE loadable slot: b's load evicts idle a.
        srv.complete(p, 5, adapter="b", timeout=300)
        assert srv.engine.adapters.counters()["evictions"] == 1
        out_a2 = np.asarray(srv.complete(p, 5, adapter="a", timeout=300))
    np.testing.assert_array_equal(out_a1, out_a2)


def test_spec_k_with_adapters_refused(model_and_vars):
    model, variables = model_and_vars
    from ml_trainer_tpu.serving import SlotDecodeEngine

    with pytest.raises(ValueError, match="spec_k"):
        SlotDecodeEngine(model, variables, max_batch=2, spec_k=2,
                         adapters=AdapterConfig(slots=3, rank=4))


# ------------------------------------------------- telemetry satellites


def test_adapter_gauges_and_health(model_and_vars, tmp_path):
    model, variables = model_and_vars
    path = _make_artifact(model, str(tmp_path / "x.npz"), name="x")
    from ml_trainer_tpu.telemetry.registry import default_registry

    with Server(model, variables, max_batch=2,
                adapters=AdapterConfig(slots=4, rank=8,
                                       sources={"x": path})) as srv:
        srv.complete(_prompt(0, 5), 4, adapter="x", timeout=300)
        health = srv.health()
        registry = default_registry()
        srv.metrics.publish(registry)
        text = registry.prometheus_text()
        snap = srv.metrics.snapshot()
    assert health["adapters_resident"] == ["x"]
    assert snap["adapter_loads_total"] == 1
    assert snap["adapter_slots_used"] == 1
    assert snap["adapter_pool_bytes"]["used"] > 0
    assert 'serving_adapter_pool_bytes{state="used"}' in text
    assert "serving_adapter_hits_total" in text
    assert "serving_adapter_loads_total 1" in text
    assert "serving_adapter_evictions_total 0" in text


def test_adapter_pool_priced_by_memory_ledger(model_and_vars):
    """The analytic ``adapter_pool_bytes`` formula equals the measured
    device stacks, and the serving ledger carries the component beside
    kv_pool."""
    model, variables = model_and_vars
    from ml_trainer_tpu.serving import SlotDecodeEngine
    from ml_trainer_tpu.telemetry.memory import (
        adapter_pool_bytes,
        gpt2_lora_target_dims,
        serving_kv_ledger,
    )

    targets = ("qkv", "proj", "fc_in", "fc_out")
    eng = SlotDecodeEngine(
        model, variables, max_batch=2, kv_page_size=PS,
        adapters=AdapterConfig(slots=5, rank=4, targets=targets),
    )
    measured = sum(
        int(l.nbytes) for l in jax.tree.leaves(eng._lora_stacks)
    )
    analytic = adapter_pool_bytes(
        5, 4, gpt2_lora_target_dims(model, targets), jnp.float32
    )
    assert analytic == measured
    ledger = serving_kv_ledger(eng)
    comp = ledger.component("adapter_pool")
    assert comp is not None and int(comp.bytes) == measured
    assert ledger.component("kv_pool") is not None


# -------------------------------------------- train -> export -> serve


def test_trainer_lora_round_trip_frozen_base_bit_identity(tmp_path):
    """Trainer(lora=...) freezes the base (bit-identical after fit),
    shrinks optimizer state to the adapter fraction (memory ledger),
    and the exported artifact hot-loads into a server whose base path
    reproduces generate() on the frozen base byte-for-byte."""
    import jax.tree_util as tu

    from ml_trainer_tpu import LoraConfig as TopLoraConfig
    from ml_trainer_tpu import Trainer
    from ml_trainer_tpu.data import SyntheticTokens
    from ml_trainer_tpu.lora import is_lora_path
    from ml_trainer_tpu.telemetry.memory import train_ledger

    model = get_model("gpt2_tiny", vocab_size=256)
    ds = SyntheticTokens(size=16, seq_len=16, vocab_size=256, seed=0)
    t = Trainer(
        model, datasets=(ds, ds), epochs=2, batch_size=8,
        model_dir=str(tmp_path), metric=None, optimizer="adamw",
        lr=0.05, criterion="cross_entropy",
        lora=TopLoraConfig(rank=4, alpha=8.0, targets=("qkv", "proj")),
    )
    init_params = jax.device_get(t.state.params)
    ledger = train_ledger(t)
    # Frozen leaves carry no moments: opt_state ≪ 2x params (adamw's
    # replicated mu+nu would be ~2x).
    assert ledger.component("opt_state").bytes < (
        0.2 * 2 * ledger.component("params").bytes
    )
    t.fit()
    final_params = jax.device_get(t.state.params)
    n_lora_changed = 0
    finals = {
        tu.keystr(p): v
        for p, v in tu.tree_leaves_with_path(final_params)
    }
    for p, v in tu.tree_leaves_with_path(init_params):
        k = tu.keystr(p)
        if is_lora_path(k):
            n_lora_changed += int(
                not np.array_equal(np.asarray(v), np.asarray(finals[k]))
            )
        else:
            np.testing.assert_array_equal(
                np.asarray(v), np.asarray(finals[k]),
                err_msg=f"frozen base leaf changed: {k}",
            )
    assert n_lora_changed >= 4
    path = str(tmp_path / "adapter.npz")
    meta = t.export_lora(path, name="trained")
    assert meta["n_leaves"] == 8

    base_params = strip_lora_params(final_params)
    prompts = [
        np.random.default_rng(i).integers(0, 256, 9).astype(np.int32)
        for i in range(2)
    ]
    base_refs = [
        np.asarray(generate(model, {"params": base_params}, p[None], 4))[0]
        for p in prompts
    ]
    # Train-mode greedy decode of the SAME trained adapter — the
    # served pool path must agree token-for-token.
    lora_refs = [
        np.asarray(
            generate(t.model, {"params": final_params}, p[None], 4)
        )[0]
        for p in prompts
    ]
    with Server(model, {"params": base_params}, max_batch=2,
                adapters=AdapterConfig(slots=3, rank=8,
                                       targets=("qkv", "proj"))) as srv:
        srv.load_adapter("trained", path)
        for p, rb, rl in zip(prompts, base_refs, lora_refs):
            np.testing.assert_array_equal(
                np.asarray(srv.complete(p, 4, timeout=300)), rb,
                err_msg="frozen-base serve path diverged",
            )
            np.testing.assert_array_equal(
                np.asarray(srv.complete(p, 4, adapter="trained",
                                        timeout=300)), rl,
                err_msg="served adapter diverged from train-mode decode",
            )


# --------------------------------------------------- router + loadgen


def test_router_adapter_affinity(model_and_vars, tmp_path):
    """Same (tenant, adapter) traffic consistently lands on ONE prefill
    replica — the residency-affinity property the consistent hash
    exists for."""
    model, variables = model_and_vars
    from ml_trainer_tpu.serving import Router

    path = _make_artifact(model, str(tmp_path / "x.npz"), name="x")
    router = Router.build(
        model, variables, roles=["both", "both"], max_batch=2,
        kv_page_size=PS,
        adapters=AdapterConfig(slots=3, rank=8, sources={"x": path}),
        # Placement alone is under test: a hedge (a duplicate prefill on
        # the OTHER replica when the first result is slow, as the first,
        # compiling request is on a loaded host) is counted there too.
        router_kwargs={"hedging": False},
    )
    try:
        p = _prompt(0, 2 * PS)
        for _ in range(4):
            router.complete(p, 4, adapter="x", timeout=300)
        snap = router.snapshot()
        placed = {
            k: v for k, v in snap["requests_total"].items() if v
        }
        assert len(placed) == 1, (
            f"same (tenant, adapter) traffic split across replicas: "
            f"{placed}"
        )
        health = router.health()
        rep = list(health["replicas"].values())[0]
        assert "adapters_resident" in rep
    finally:
        router.close()


def test_loadgen_adapter_mix_rides_recorded_traces():
    load = {
        "pro": TenantLoad(weight=1.0, adapters=("a", "b", None)),
    }
    s1 = poisson_schedule(50.0, 24, 1024, tenants=load, seed=3)
    s2 = poisson_schedule(50.0, 24, 1024, tenants=load, seed=3)
    assert [s.adapter for s in s1] == [s.adapter for s in s2]
    drawn = {s.adapter for s in s1}
    assert {"a", "b", None} <= drawn
    records = schedule_to_records(s1)
    replay = schedule_from_trace(records)
    assert [s.adapter for s in replay] == [s.adapter for s in s1]
    with pytest.raises(ValueError, match="adapters entries"):
        TenantLoad(adapters=("a", ""))
