"""``brumby`` through the slot engine against its plain reference
(``benchmark/references/brumby.py``: the layer's QUADRATIC form, no state),
at a tiny size: four layers, 10 query heads over 2 key-value heads of 16
(groups of five, a state of 16 x 144 a head: phi's 136 entries in nine
rows of 16 lanes), chunks of 8, a context of 64.

Tolerances.  The program in float32 and the reference compute the same
equations on the same bfloat16-valued weights and differ by the order of
their float32 sums alone: a state carried and read through ``phi`` against a
sum over the tokens of squared scores, 4 layers (3e-7 to 6e-7 read here on
logits of 0.6).  The limit is 2e-5 of the largest logit.  The program in
bfloat16 (8 bits of mantissa) reads 1e-2 and fails it by two orders and
more, which is what "a lower precision would fail" asks for; so does the
float32 program with only the state rounded to bfloat16.
"""

import json
import time
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import mm_highest, seed_key
from benchmark.references import brumby as reference
from ml_trainer_tpu.generate import generate
from ml_trainer_tpu.models import get_model
from ml_trainer_tpu.ops.power_retention import phi
from ml_trainer_tpu.serving.api import Server
from ml_trainer_tpu.serving.engine import SlotDecodeEngine
from ml_trainer_tpu.serving.scheduler import Request
from ml_trainer_tpu.telemetry import spans

CHUNK, BUCKET, VOCAB = 8, 16, 256
REL_TOL = 2e-5
SIZES = dict(
    vocab=VOCAB, positions=64, width=64, layers=4, heads=10, kv_heads=2,
    head_dim=16, dense_width=96, rope_theta=1e6, eps=1e-6,
    published_layers=40)


@pytest.fixture(scope="module")
def weights():
    return reference.make_weights(seed_key(2**31 + 5), **SIZES)


def prompt(n, seed):
    return np.random.default_rng(seed).integers(0, VOCAB, size=n).astype(
        np.int32)


def request(tokens, budget):
    return Request(prompt=tokens, max_new_tokens=budget, temperature=0.0)


def slot_engine_gaps(weights, dtype):
    """Three requests in one slot engine of four rows.  Two are admitted
    three steps apart (rows at different positions), with prompts longer
    than a chunk and shorter than their bucket of 16; the first is short
    and ends, and a third request is then admitted into ITS row, over the
    state it left.  Before each step, what the decode program's model call
    makes of the engine's own slot cache and pending tokens (without
    advancing either) is kept; afterwards one reference pass over each
    request's whole sequence (causal, so every step's logits are in it)
    gives the largest |program - reference| logit, relative to the largest
    reference logit, over every decode step."""
    model = get_model("brumby_tiny", dtype=dtype)
    engine = SlotDecodeEngine(model, {"params": weights}, max_batch=4)
    peek = jax.jit(lambda params, cache, tok: engine.dm.apply(
        {"params": params, "cache": cache}, tok, train=False,
        mutable=["cache", "step_counters"])[0][:, 0])
    first, second, third = (request(prompt(11, 1), 6),
                            request(prompt(13, 2), 24),
                            request(prompt(9, 3), 12))
    running = {0: first}
    assert engine.admit(first, 0) == "active"
    seen = []
    for step in range(22):
        if step == 3:
            assert engine.admit(second, 2) == "active"
            running[2] = second
        if step == 8:
            assert first.state == "done" and 0 not in engine._active
            assert engine.admit(third, 0) == "active"    # the row REUSED
            running[0] = third
        live = {slot: r for slot, r in running.items() if r.state == "active"}
        got = np.asarray(peek(engine.params, engine.cache, engine.tok))
        seen += [(r, len(r.prompt) + len(r.tokens) - 1, got[slot])
                 for slot, r in live.items()]
        engine.step()
        assert all(r.tokens[-1] == int(np.argmax(got[slot]))
                   for slot, r in live.items())
    assert [len(r.tokens) for r in (first, second, third)] == [6, 20, 12]
    assert 13 + 20 > 4 * CHUNK
    want = {}
    for r in (first, second, third):
        seq = np.zeros((1, 40), np.int32)
        seq[0, :len(r.prompt) + len(r.tokens)] = np.concatenate(
            [r.prompt, r.tokens])
        want[id(r)] = np.asarray(reference.logits(weights, seq, SIZES))[0]
    return max(
        np.abs(got - want[id(r)][at]).max() / np.abs(want[id(r)][at]).max()
        for r, at, got in seen)


def test_slot_engine_agrees_with_the_reference_and_bfloat16_would_not(weights):
    assert slot_engine_gaps(weights, jnp.float32) < REL_TOL
    assert slot_engine_gaps(weights, jnp.bfloat16) > 100 * REL_TOL


def test_a_state_carried_in_bfloat16_fails_the_float32_comparison(
        weights, monkeypatch):
    """The state and the normaliser alone rounded to bfloat16 after every
    update, in a program otherwise float32: over ten times the tolerance."""
    from ml_trainer_tpu.models import brumby
    from ml_trainer_tpu.serving import engine

    def rounded(fn):
        def run(*args, **kw):
            o, state, norm, *rest = fn(*args, **kw)
            state, norm = (t.astype(jnp.bfloat16).astype(jnp.float32)
                           for t in (state, norm))
            return (o, state, norm, *rest)
        return run

    monkeypatch.setattr(engine, "_COMPILED", {})    # trace anew, patched
    for name in ("retention_step", "retention_chunked"):
        monkeypatch.setattr(brumby, name, rounded(getattr(brumby, name)))
    assert slot_engine_gaps(weights, jnp.float32) > 10 * REL_TOL


@pytest.mark.parametrize("true_len", [2, CHUNK - 1, CHUNK, CHUNK + 3, BUCKET])
def test_the_state_comes_out_as_it_stood_at_the_true_length(
        weights, true_len):
    """A prompt padded to its bucket by the engine's prefill: layer 0's
    state reads, at any probe, what the reference's weights over the TRUE
    tokens give (``phi(a)^T S = sum_s decay_s (a . k_s)^2 / d v_s``, the
    normaliser the same sum without the values), not over the bucket's; the
    row's rotary index is the true length; the other row is untouched."""
    tokens = prompt(true_len, true_len)
    engine = SlotDecodeEngine(
        get_model("brumby_tiny"), {"params": weights}, max_batch=2)
    engine.admit(request(tokens, 4), 1)
    x = reference._rms(reference.embed(weights, jnp.asarray(tokens)),
                       weights["block0"]["attn_norm"]["scale"], SIZES["eps"])
    _, k, v, log_g = (np.asarray(t, np.float64) for t in
                      reference.retention_inputs(
                          weights["block0"]["attn"], x, SIZES, mm_highest))
    cum = np.cumsum(log_g, axis=0)
    decay = np.exp(cum[-1][None] - cum)                      # [toks, g]
    probes = np.random.default_rng(0).normal(size=(5, 16))
    layer = engine.cache["block0"]["attn"]
    assert np.asarray(layer["state"]).shape == (2, 2, 16, 144)
    assert np.asarray(layer["norm"]).shape == (2, 2, 144)
    lifted = np.asarray(phi(jnp.asarray(probes, jnp.float32)), np.float64)
    for j in range(2):
        w = decay[:, j][None] * (probes @ k[:, j].T) ** 2 / 16  # [5, toks]
        np.testing.assert_allclose(
            lifted @ np.asarray(layer["state"], np.float64)[1, j].T,
            w @ v[:, j], atol=2e-5)
        np.testing.assert_allclose(
            lifted @ np.asarray(layer["norm"], np.float64)[1, j],
            w.sum(-1), atol=2e-5)
    assert np.asarray(layer["cache_index"]).tolist() == [0, true_len]
    assert not np.asarray(layer["state"])[0].any()     # the other row: free


def test_a_free_row_stays_finite_and_the_next_request_finds_it_fresh(weights):
    """A free row is stepped with every other: its state is gated and added
    to with whatever its pending token holds, every step.  After 200 steps
    (its index long past the context) every leaf of it is finite, and a
    request admitted into it replies exactly as in an engine that has never
    run: the insert replaces the row's state, normaliser and index, so a
    REUSED slot starts from a zero state."""
    model = get_model("brumby_tiny", dtype=jnp.bfloat16)
    tokens = prompt(13, 5)
    fresh = SlotDecodeEngine(model, {"params": weights}, max_batch=2)
    alone = request(tokens, 16)
    fresh.admit(alone, 1)
    while alone.state == "active":
        fresh.step()
    engine = SlotDecodeEngine(model, {"params": weights}, max_batch=2)
    # Row 1 free and far from zero: a state and a normaliser of 1e3 and a
    # token, stepped 200 times beside a running row.
    engine.cache = jax.tree.map(
        lambda leaf: (leaf if leaf.dtype == jnp.int32
                      else leaf.at[1].set(1e3)), engine.cache)
    engine.tok = engine.tok.at[1, 0].set(77)
    busy = request(prompt(9, 6), 64 - 9)
    engine.admit(busy, 0)
    for _ in range(200):
        if busy.state != "active":
            busy = request(prompt(9, 6), 64 - 9)
            engine.admit(busy, 0)
        engine.step()
    leaves = jax.tree.leaves(engine.cache)
    assert all(np.isfinite(np.asarray(leaf, np.float32)[1]).all()
               for leaf in leaves if leaf.dtype != jnp.int32)
    state = np.abs(np.asarray(engine.cache["block0"]["attn"]["state"])[1])
    assert 0 < state.max() < 1e3            # gated away, never blown up
    again = request(tokens, 16)
    engine.admit(again, 1)
    while again.state == "active":
        engine.step()
    assert again.tokens == alone.tokens and len(again.tokens) == 16


def test_lookahead_and_the_synchronous_step_give_the_same_tokens(weights):
    """The serving loop dispatches step n+1 before it reads step n
    (``engine.advance()``); driven a step at a time (``engine.step()``) the
    same requests get the same tokens.  The loop's fences carry the
    retention's counters."""
    model = get_model("brumby_tiny", dtype=jnp.bfloat16)
    asked = [(prompt(11, 21), 14), (prompt(19, 22), 9), (prompt(5, 23), 20)]
    engine = SlotDecodeEngine(model, {"params": weights}, max_batch=2)
    reqs = [request(p, n) for p, n in asked]
    waiting, slots = list(reqs), {}
    while waiting or slots:
        for slot in range(2):
            if slot not in engine._active and waiting:
                slots[slot] = waiting.pop(0)
                engine.admit(slots[slot], slot)
        engine.step()
        slots = {s: r for s, r in slots.items() if r.state == "active"}
    t0 = time.monotonic()
    with Server(model, {"params": weights}, max_batch=2) as server:
        streams = [server.submit(p, n) for p, n in asked]
        got = [list(s.result(timeout=300)) for s in streams]
    assert [g[len(p):] for g, (p, _) in zip(got, asked)] == [
        list(r.tokens) for r in reqs]
    events, wrapped = spans.events_between(
        t0, time.monotonic(),
        names=["serve_decode.dispatch", "serve_decode.fence",
               "serve_prefill"])
    assert not wrapped
    by_name = {}
    for e in events:
        by_name.setdefault(e["name"], []).append(e["args"])
    assert any(a["ahead"] for a in by_name["serve_decode.dispatch"])
    fences = [a for a in by_name["serve_decode.fence"] if "gate_mean" in a]
    assert fences and all(0.0 < a["gate_mean"] < 1.0 for a in fences)
    assert all(0.0 <= a["norm_min"] < np.inf for a in fences)
    assert sorted(a["prompt_tokens"] for a in by_name["serve_prefill"]) == [
        5, 11, 19]


def test_the_counters_are_the_steps_own_over_the_rows_in_flight(weights):
    """``gate_mean`` and ``norm_min`` of a step with one row in flight are
    that row's, whatever the free rows hold: the mean of its 4 x 2 gates
    and the smallest of its 4 x 10 divisors."""
    model = get_model("brumby_tiny")
    engine = SlotDecodeEngine(model, {"params": weights}, max_batch=3)
    engine.cache = jax.tree.map(
        lambda leaf: (leaf if leaf.dtype == jnp.int32
                      else leaf.at[0].set(1e-9)), engine.cache)
    engine.admit(request(prompt(9, 31), 8), 1)
    _, mut = engine.dm.apply(
        {"params": engine.params, "cache": engine.cache}, engine.tok,
        train=False, mutable=["cache", "step_counters"])
    gate = np.asarray(mut["step_counters"]["gate"][0])
    divisor = np.asarray(mut["step_counters"]["divisor"][0])
    assert gate.shape == (3, 8) and divisor.shape == (3, 40)
    assert divisor[0].min() < divisor[1].min()      # a free row's is lower
    t0 = time.monotonic()
    engine.step()
    events, _ = spans.events_between(
        t0, time.monotonic(), names=["serve_decode.fence"])
    args = events[-1]["args"]
    assert args["gate_mean"] == pytest.approx(gate[1].mean(), rel=1e-5)
    assert args["norm_min"] == pytest.approx(divisor[1].min(), rel=1e-5)


def test_served_over_http_as_generate_computes_it_and_refused_as_others(
        weights):
    model = get_model("brumby_tiny")
    tokens = prompt(19, 5)
    want = np.asarray(
        generate(model, {"params": weights}, tokens[None], 12))[0]
    with Server(model, {"params": weights}, max_batch=16, max_queue=64,
                watchdog_timeout=900.0) as server:
        host, port = server.serve_http(port=0)
        body = json.dumps({"prompt": tokens.tolist(),
                           "max_new_tokens": 12}).encode()
        with urllib.request.urlopen(urllib.request.Request(
                f"http://{host}:{port}/v1/generate", data=body,
                headers={"Content-Type": "application/json"}),
                timeout=300) as resp:
            out = json.loads(resp.read())
        with urllib.request.urlopen(urllib.request.Request(
                f"http://{host}:{port}/v1/stream", data=body,
                headers={"Content-Type": "application/json"}),
                timeout=300) as resp:
            lines = [json.loads(line) for line in resp.read().splitlines()]
    np.testing.assert_array_equal(np.asarray(out["tokens"], np.int32), want)
    assert [ln["t"] for ln in lines if "t" in ln] == want[19:].tolist()
    assert lines[-1]["done"]["state"] == "done"
    # What serves only the GPT-2 family says so to this class as to any
    # other: the knob is not the module's.
    variables = {"params": weights}
    for options in ({"kv_page_size": 8}, {"quant_int8": True},
                    {"adapters": {"rank": 2, "slots": 2}}):
        with pytest.raises((TypeError, ValueError)) as refused:
            SlotDecodeEngine(model, variables, max_batch=2, **options)
        assert any(word in str(refused.value) for word in
                   ("GPT-2 family", "unexpected keyword"))
    spec = SlotDecodeEngine(model, variables, max_batch=2, spec_k=2)
    spec.admit(request(tokens, 8), 0)
    with pytest.raises(ValueError, match="GPT-2 family"):
        spec.step()


def test_importing_the_module_lowers_and_allocates_nothing():
    """``models/registry.py`` imports every family: the module's import is
    definitions alone (no jitted call, no array, no Pallas lowering)."""
    import os
    import subprocess
    import sys

    code = (
        "import jax\n"
        "made = []\n"
        "real = jax.numpy.zeros\n"
        "jax.numpy.zeros = lambda *a, **k: made.append(a) or real(*a, **k)\n"
        "import ml_trainer_tpu.models.brumby as m\n"
        "import ml_trainer_tpu.ops.power_retention\n"
        "assert not made, made\n"
        "assert not jax.live_arrays(), jax.live_arrays()\n"
        "print('clean')\n")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.stdout.strip().endswith("clean"), out.stderr[-2000:]
