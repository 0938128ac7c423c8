"""``kimi_linear`` through the slot engine against its plain reference
(``benchmark/references/kimi_linear.py``), at a tiny size with every kind of
layer: two periods of three KDA layers and a latent one (``KKKM KKKM``), the
dense layer leading, 16 experts of which 4 (a share) or all are held, top 2,
heads of 16, a latent row of 32 + 8, chunks of 8, a context of 64.

Tolerances.  The program in float32 and the reference compute the same
equations on the same bfloat16-valued weights and differ by the order of
their float32 sums alone: the chunked form against a token-by-token scan,
the absorbed attention against the expanded one, 8 layers (3e-7 to 2e-6 read
here on logits of 0.6).  The limit is 2e-5 of the largest logit.  The program
in bfloat16 (8 bits of mantissa) reads 1e-2 to 2e-2 and fails it by three
orders, which is what "a lower precision would fail" asks for.
"""

import json
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import mm_highest, seed_key
from benchmark.references import kimi_linear as reference
from ml_trainer_tpu.generate import generate
from ml_trainer_tpu.models import get_model, moe
from ml_trainer_tpu.models.kimi_linear import LatentAttention
from ml_trainer_tpu.models.moe import HeldExpertsMoE
from ml_trainer_tpu.serving.api import Server
from ml_trainer_tpu.serving.engine import SlotDecodeEngine
from ml_trainer_tpu.serving.scheduler import Request

CHUNK, BUCKET, VOCAB = 8, 16, 256
REL_TOL = 2e-5


def sizes(held):
    period = (("kda", True),) * 3 + (("mla", True),)
    return dict(
        vocab=VOCAB, positions=64, width=64,
        layer_kinds=(("kda", False),) + period[1:] + period, heads=4,
        head_dim=16, taps=4, mla_heads=4, latent=32, nope=16, rope=8,
        v_dim=16, dense_width=96, expert_width=32, experts=16,
        experts_held=held, top_k=2, scaling=2.446, shared=1, eps=1e-5,
        published_layers=27)


@pytest.fixture(scope="module", params=[(0, 4), (0, 16)],
                ids=["share-of-4", "all-16"])
def held(request):
    s = sizes(request.param)
    return s, reference.make_weights(seed_key(2**31 + 5), **s)


def prompt(n, seed):
    return np.random.default_rng(seed).integers(0, VOCAB, size=n).astype(
        np.int32)


def request(tokens, budget):
    return Request(prompt=tokens, max_new_tokens=budget, temperature=0.0)


def slot_engine_gaps(s, weights, dtype):
    """Three requests in one slot engine of four rows.  Two are admitted
    three steps apart (rows at different positions), with prompts longer
    than a chunk and shorter than their bucket of 16; the first is short
    and ends, and a third request is then admitted into ITS row, over the
    state, the tails and the latent rows it left.  Before each step, what
    the decode program's model call makes of the engine's own slot cache
    and pending tokens (without advancing either) is kept; afterwards one
    reference pass over each request's whole sequence (causal, so every
    step's logits are in it) gives the largest |program - reference| logit,
    relative to the largest reference logit, over every decode step."""
    model = get_model("kimi_linear_tiny", experts_held=s["experts_held"],
                      dtype=dtype)
    engine = SlotDecodeEngine(model, {"params": weights}, max_batch=4)
    peek = jax.jit(lambda params, cache, tok: engine.dm.apply(
        {"params": params, "cache": cache}, tok, train=False,
        mutable=["cache"])[0][:, 0])
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
        want[id(r)] = np.asarray(reference.logits(weights, seq, s))[0]
    return max(
        np.abs(got - want[id(r)][at]).max() / np.abs(want[id(r)][at]).max()
        for r, at, got in seen)


def test_slot_engine_agrees_with_the_reference_and_bfloat16_would_not(
        held, monkeypatch):
    s, weights = held
    if s["experts_held"] == (0, 4):
        # this share through the grouped products at every size (the tiny
        # shapes alone would take the every-expert form, which the other
        # share takes)
        monkeypatch.setattr(moe, "EVERY_EXPERT_ROWS", 0)
    assert slot_engine_gaps(s, weights, jnp.float32) < REL_TOL
    assert slot_engine_gaps(s, weights, jnp.bfloat16) > 100 * REL_TOL


def test_a_state_carried_in_bfloat16_fails_the_float32_comparison(monkeypatch):
    """The recurrent state alone rounded to bfloat16 after every update, in
    a program otherwise float32: two orders over the tolerance.  (On the
    chip the cell's ``correct`` cannot tell it: under bfloat16 weights the
    served-token gap is routing near-ties, PERF.md section 2.)"""
    from ml_trainer_tpu.models import kimi_linear
    from ml_trainer_tpu.serving import engine

    def rounded(fn):
        def run(*args, **kw):
            out, state = fn(*args, **kw)
            return out, state.astype(jnp.bfloat16).astype(jnp.float32)
        return run

    s = sizes((0, 4))
    weights = reference.make_weights(seed_key(2**31 + 5), **s)
    monkeypatch.setattr(engine, "_COMPILED", {})    # trace anew, patched
    for name in ("gated_delta_step", "gated_delta_chunked"):
        monkeypatch.setattr(kimi_linear, name,
                            rounded(getattr(kimi_linear, name)))
    assert slot_engine_gaps(s, weights, jnp.float32) > 100 * REL_TOL


def test_the_absorbed_step_is_the_expanded_form_over_the_same_rows():
    """One latent layer alone: a sequence through the expanded form, and
    its last token through the absorbed step over the latent rows the first
    tokens left (scalar index, then per-row indices at different
    positions): the same output, float32 sums in another order."""
    layer = LatentAttention(4, 16, 8, 16, 32, decode=True, decode_max_len=24)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 64))
    variables = layer.init(jax.random.PRNGKey(1), x)
    params = {"params": variables["params"]}
    plain = layer.clone(decode=False).apply(params, x)
    empty = jax.tree.map(jnp.zeros_like, variables["cache"])
    _, mut = layer.apply({**params, "cache": empty}, x[:, :11],
                         mutable=["cache"])
    assert np.asarray(mut["cache"]["latent"]).shape == (2, 1, 24, 40)
    out, after = layer.apply({**params, "cache": mut["cache"]}, x[:, 11:],
                             mutable=["cache"])
    assert int(after["cache"]["cache_index"]) == 12
    np.testing.assert_allclose(
        np.asarray(out[:, 0]), np.asarray(plain[:, 11]), atol=2e-6)
    # the slot engine's form: row 0 after 11 tokens, row 1 after 7
    _, short = layer.apply({**params, "cache": empty}, x[:, :7],
                           mutable=["cache"])
    latent = jnp.stack([mut["cache"]["latent"][0],
                        short["cache"]["latent"][1]])
    rows = {"latent": latent, "cache_index": jnp.asarray([11, 7], jnp.int32)}
    last = jnp.stack([x[0, 11:12], x[1, 7:8]])
    out, after = layer.apply({**params, "cache": rows}, last,
                             mutable=["cache"])
    assert np.asarray(after["cache"]["cache_index"]).tolist() == [12, 8]
    want = np.stack([np.asarray(plain[0, 11]), np.asarray(layer.clone(
        decode=False).apply(params, x[:, :8])[1, 7])])
    np.testing.assert_allclose(np.asarray(out[:, 0]), want, atol=2e-6)


def moe_params(weights, block, first, count):
    p = weights[block]["moe"]
    return {"router": p["router"], "router_bias": p["router_bias"],
            **{k: p[k][first:first + count] for k in ("wg", "wu", "wd")}}


@pytest.mark.parametrize("toks", [24, 160], ids=["every-expert", "grouped"])
def test_the_shares_add_up_to_the_uncut_layer(toks):
    """Four chips of four experts each, through THIS model's block (its
    router scaling, its layout): their routed parts, plus the shared expert
    counted once, are the reference's whole layer."""
    assert 2 * 24 <= moe.EVERY_EXPERT_ROWS < 2 * 160
    s = sizes((0, 16))
    weights = reference.make_weights(seed_key(7), **s)
    h = jax.random.normal(jax.random.PRNGKey(3), (2, toks, 64), jnp.float32)
    block = weights["block2"]
    parts, rows = [], []
    for first in (0, 4, 8, 12):
        out, landed = HeldExpertsMoE(
            16, 32, 2, experts_held=(first, 4), routed_scaling=2.446,
        ).apply({"params": moe_params(weights, "block2", first, 4)}, h)
        parts.append(np.asarray(out))
        rows.append(np.asarray(landed))
    # every assignment landed on exactly one share
    assert np.concatenate(rows, axis=1).sum() == 2 * toks * 2
    for row in range(2):
        shared = np.asarray(reference.gated_ffn(
            block["shared"], h[row], 32, mm_highest))
        routed = np.asarray(reference.routed_ffn(
            block["moe"], h[row], s, mm_highest))
        got = sum(p[row] for p in parts) + shared
        assert (np.abs(got - (routed + shared)).max()
                < REL_TOL * np.abs(routed + shared).max())
        # and a single share is NOT the layer
        assert np.abs(parts[0][row] - routed).max() > 0.1 * np.abs(routed).max()


@pytest.mark.parametrize("true_len", [2, CHUNK - 1, CHUNK, CHUNK + 3, BUCKET])
def test_state_and_tail_come_out_as_they_stood_at_the_true_length(true_len):
    """A prompt padded to its bucket by the engine's prefill: layer 0's
    state is the reference's recurrence after ``true_len`` tokens, not
    after the bucket's, its tail holds the last three TRUE inputs of the
    convolutions (zeros before position 0), and the latent layer's index
    is the true length."""
    s = sizes((0, 4))
    weights = reference.make_weights(seed_key(11), **s)
    tokens = prompt(true_len, true_len)
    engine = SlotDecodeEngine(
        get_model("kimi_linear_tiny", experts_held=(0, 4)),
        {"params": weights}, max_batch=2)
    engine.admit(request(tokens, 4), 1)
    p = weights["block0"]["attn"]
    x = reference._rms(reference.embed(weights, jnp.asarray(tokens)),
                       weights["block0"]["attn_norm"]["scale"], s["eps"])
    _, want = reference.delta_rule_scan(
        *reference.kda_inputs(p, x, s, mm_highest), scale=0.25)
    layer = engine.cache["block0"]["attn"]
    assert np.asarray(layer["state"]).shape == (2, 4, 16, 16)
    np.testing.assert_allclose(
        np.asarray(layer["state"])[1], np.asarray(want), atol=2e-6)
    mixed = np.concatenate(
        [np.asarray(mm_highest(x, p[n]["kernel"])) for n in "qkv"], axis=-1)
    tail = np.concatenate([np.zeros((3, 192), np.float32), mixed])[-3:]
    np.testing.assert_allclose(
        np.asarray(layer["conv_tail"])[1], tail, atol=2e-6)
    index = np.asarray(engine.cache["block3"]["attn"]["cache_index"])
    assert index.tolist()[1] == true_len
    assert not np.asarray(layer["state"])[0].any()     # the other row: free


def test_a_free_row_stays_finite_and_the_next_request_finds_it_fresh():
    """A free row is stepped with every other: its state is updated with
    whatever its pending token and its tails hold, every step.  After 200
    steps (its index long past the context) every leaf of it is finite, and
    a request admitted into it replies exactly as in an engine that has
    never run: the insert replaces the row's state, tails and latent rows."""
    s = sizes((0, 4))
    weights = reference.make_weights(seed_key(13), **s)
    model = get_model("kimi_linear_tiny", experts_held=(0, 4),
                      dtype=jnp.bfloat16)
    tokens = prompt(13, 5)
    fresh = SlotDecodeEngine(model, {"params": weights}, max_batch=2)
    alone = request(tokens, 16)
    fresh.admit(alone, 1)
    while alone.state == "active":
        fresh.step()
    engine = SlotDecodeEngine(model, {"params": weights}, max_batch=2)
    # Row 1 free and far from zero: a state of 1e3, tails of 1e2, a latent
    # cache of 1e2 and a token, stepped 200 times beside a running row.
    engine.cache = jax.tree.map(
        lambda leaf: (leaf if leaf.dtype == jnp.int32
                      else leaf.at[1].set(1e3 if leaf.ndim == 4
                                          and leaf.dtype == jnp.float32
                                          else 1e2)), engine.cache)
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
    assert 0 < state.max() < 1e3       # decayed, corrected, never blown up
    again = request(tokens, 16)
    engine.admit(again, 1)
    while again.state == "active":
        engine.step()
    assert again.tokens == alone.tokens and len(again.tokens) == 16


def test_served_over_http_as_generate_computes_it_and_refused_as_others():
    s = sizes((0, 4))
    weights = reference.make_weights(seed_key(13), **s)
    model = get_model("kimi_linear_tiny", experts_held=[0, 4],
                      kda_layers=[1, 2, 3, 5, 6, 7])
    assert hash(model) == hash(get_model(
        "kimi_linear_tiny", experts_held=(0, 4), kda_layers=(1, 2, 3, 5, 6, 7)))
    tokens = prompt(19, 5)
    want = np.asarray(generate(model, {"params": weights}, tokens[None], 12))[0]
    with Server(model, {"params": weights}, max_batch=128, max_queue=256,
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
        "import ml_trainer_tpu.models.kimi_linear as m\n"
        "import ml_trainer_tpu.ops.delta_rule\n"
        "assert not made, made\n"
        "assert not jax.live_arrays(), jax.live_arrays()\n"
        "print('clean')\n")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.stdout.strip().endswith("clean"), out.stderr[-2000:]
