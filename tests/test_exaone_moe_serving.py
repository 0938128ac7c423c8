"""``exaone_moe`` through the slot engine against its plain reference
(``benchmark/references/exaone_moe.py``), at a tiny size with every kind of
layer: two periods of three window layers (window 8) and a full one, the
dense layer leading, 16 experts of which 4 (a share) or all are held, top 2.

Tolerances.  The program in float32 and the reference compute the same
equations on the same bfloat16-valued weights and differ by the order of
their float32 sums alone (8 layers, sums of at most 96 terms; 2e-7 to 6e-7
read here on logits of 0.6): the limit is 1e-5 of the largest logit.  The
program in bfloat16 (8 bits of mantissa) reads 3e-3 to 8e-3 and fails it by
two orders, which is what "a lower precision would fail" asks for.
"""

import json
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import seed_key
from benchmark.references import exaone_moe as reference
from ml_trainer_tpu.generate import generate
from ml_trainer_tpu.models import get_model, moe
from ml_trainer_tpu.models.moe import HeldExpertsMoE
from ml_trainer_tpu.ops.attention import attention, dot_product_attention
from ml_trainer_tpu.serving.api import Server
from ml_trainer_tpu.serving.engine import SlotDecodeEngine
from ml_trainer_tpu.serving.scheduler import Request

WINDOW, BUCKET, VOCAB = 8, 16, 256
PERIOD = ("sliding_attention",) * 3 + ("full_attention",)
REL_TOL = 1e-5


def sizes(held):
    return dict(
        vocab=VOCAB, positions=64, width=64, heads=4, kv_heads=2, head_dim=16,
        layer_types=PERIOD * 2, mlp_layer_types=("dense",) + ("sparse",) * 7,
        window=WINDOW, dense_width=96, expert_width=32, experts=16,
        experts_held=held, top_k=2, scaling=2.5, shared=1, rope_theta=1e6,
        eps=1e-5, published_layers=48)


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
    """Two requests in one slot engine, the second admitted three steps
    after the first (rows at different positions); prompts longer than the
    window and shorter than their bucket; decode steps until both rings
    have wrapped.  Before each step, what the decode program's model call
    makes of the engine's own slot cache and pending tokens (without
    advancing either) is kept; afterwards one reference pass over each
    row's whole sequence (causal, so every step's logits are in it) gives
    the largest |program - reference| logit, relative to the largest
    reference logit, over every decode step of both rows."""
    model = get_model("exaone_moe_tiny", experts_held=s["experts_held"],
                      dtype=dtype)
    engine = SlotDecodeEngine(model, {"params": weights}, max_batch=4)
    peek = jax.jit(lambda params, cache, tok: engine.dm.apply(
        {"params": params, "cache": cache}, tok, train=False,
        mutable=["cache"])[0][:, 0])
    rows = {0: request(prompt(11, 1), 40), 2: request(prompt(13, 2), 40)}
    assert engine.admit(rows[0], 0) == "active"
    seen, admitted = [], [0]
    for step in range(14):
        if step == 3:
            assert engine.admit(rows[2], 2) == "active"
            admitted.append(2)
        got = np.asarray(peek(engine.params, engine.cache, engine.tok))
        seen += [(slot, len(rows[slot].prompt) + len(rows[slot].tokens) - 1,
                  got[slot]) for slot in admitted]
        engine.step()
        assert all(rows[slot].tokens[-1] == int(np.argmax(got[slot]))
                   for slot in admitted)
    assert len(rows[0].tokens) == 15 and 11 + 15 > 3 * WINDOW
    want = {}
    for slot, r in rows.items():
        seq = np.zeros((1, 32), np.int32)
        seq[0, :len(r.prompt) + len(r.tokens)] = np.concatenate(
            [r.prompt, r.tokens])
        want[slot] = np.asarray(reference.logits(weights, seq, s))[0]
    return max(np.abs(got - want[slot][at]).max() / np.abs(want[slot][at]).max()
               for slot, at, got in seen)


def test_slot_engine_agrees_with_the_reference_and_bfloat16_would_not(
        held, monkeypatch):
    s, weights = held
    if s["experts_held"] == (0, 4):
        # this share through the grouped products at every size (the tiny
        # shapes alone would take the every-expert form, which the other
        # share takes)
        monkeypatch.setattr(moe, "EVERY_EXPERT_ROWS", 0)
    assert slot_engine_gaps(s, weights, jnp.float32) < REL_TOL
    assert slot_engine_gaps(s, weights, jnp.bfloat16) > 30 * REL_TOL


def moe_params(weights, block, first, count):
    p = weights[block]["moe"]
    return {"router": p["router"], "router_bias": p["router_bias"],
            **{k: p[k][first:first + count] for k in ("wg", "wu", "wd")}}


# Tokens a row: 2 x 24 take the every-expert form, 2 x 160 the grouped one.
FORMS = pytest.mark.parametrize("toks", [24, 160], ids=["every-expert", "grouped"])


@FORMS
def test_the_shares_add_up_to_the_uncut_layer(toks):
    """Four chips of four experts each: their routed parts, plus the shared
    expert counted once, are the reference's whole layer."""
    assert 2 * 24 <= moe.EVERY_EXPERT_ROWS < 2 * 160
    s = sizes((0, 16))
    weights = reference.make_weights(seed_key(7), **s)
    h = jax.random.normal(jax.random.PRNGKey(3), (2, toks, 64), jnp.float32)
    block = weights["block2"]
    parts, rows = [], []
    for first in (0, 4, 8, 12):
        out, landed = HeldExpertsMoE(
            16, 32, 2, experts_held=(first, 4), routed_scaling=2.5,
        ).apply({"params": moe_params(weights, "block2", first, 4)}, h)
        parts.append(np.asarray(out))
        rows.append(np.asarray(landed))
    # every assignment landed on exactly one share
    assert np.concatenate(rows, axis=1).sum() == 2 * toks * 2
    for row in range(2):
        want = (reference.routed_ffn(block["moe"], h[row], s,
                                     reference.mm_highest)
                + reference.gated_ffn(block["shared"], h[row], 32,
                                      reference.mm_highest))
        got = sum(p[row] for p in parts) + np.asarray(reference.gated_ffn(
            block["shared"], h[row], 32, reference.mm_highest))
        assert np.abs(got - want).max() < REL_TOL * np.abs(want).max()
        # and a single share is NOT the layer
        routed = np.asarray(reference.routed_ffn(
            block["moe"], h[row], s, reference.mm_highest))
        assert np.abs(parts[0][row] - routed).max() > 0.1 * np.abs(routed).max()


@FORMS
def test_no_token_is_dropped_under_the_worst_imbalance(toks):
    """A selection bias that sends every token to held expert 1: every row on
    one expert of four (of 48, a capacity rule at 1.25 would keep 15)."""
    s = sizes((0, 4))
    weights = reference.make_weights(seed_key(9), **s)
    params = moe_params(weights, "block1", 0, 4)
    params["router_bias"] = params["router_bias"].at[1].add(10.0)
    h = jax.random.normal(jax.random.PRNGKey(4), (2, toks, 64), jnp.float32)
    out, landed = HeldExpertsMoE(
        16, 32, 2, experts_held=(0, 4), routed_scaling=2.5,
    ).apply({"params": params}, h)
    assert np.asarray(landed)[:, 1].tolist() == [toks, toks]
    for row in range(2):
        want = np.asarray(reference.routed_ffn(
            {**weights["block1"]["moe"], "router_bias": params["router_bias"]},
            h[row], s, reference.mm_highest))
        assert np.abs(want).max() > 0
        assert (np.abs(np.asarray(out[row]) - want).max()
                < REL_TOL * np.abs(want).max())


@pytest.mark.parametrize("true_len", [WINDOW - 1, WINDOW, WINDOW + 3, BUCKET])
def test_the_ring_holds_the_last_true_positions_after_a_padded_prefill(
        true_len):
    """Layer 0's keys depend on the tokens alone, so a model whose ring is
    as long as the context gives the key of every position; the engine's
    ring (window 8), filled by a prefill padded to its bucket, holds at slot
    p mod 8 the key of each of the last min(true_len, 8) true positions and
    of no padding."""
    s = sizes((0, 4))
    weights = reference.make_weights(seed_key(11), **s)
    tokens = prompt(true_len, true_len)
    wide = get_model("exaone_moe_tiny", experts_held=(0, 4), window=64,
                     decode=True)
    _, mut = wide.apply(
        {"params": weights, "cache": jax.tree.map(
            jnp.zeros_like, wide.init(
                {"params": jax.random.PRNGKey(0)},
                jnp.zeros((1, 1), jnp.int32))["cache"])},
        jnp.asarray(tokens[None]), mutable=["cache"])
    keys = np.asarray(mut["cache"]["block0"]["attn"]["cached_key"])[0]
    engine = SlotDecodeEngine(
        get_model("exaone_moe_tiny", experts_held=(0, 4)),
        {"params": weights}, max_batch=2)
    engine.admit(request(tokens, 4), 1)
    layer = engine.cache["block0"]["attn"]
    ring = np.asarray(layer["cached_key"])[1]
    assert ring.shape == (2, WINDOW, 16)
    assert np.asarray(layer["cache_index"]).tolist()[1] == true_len
    held_positions = range(max(0, true_len - WINDOW), true_len)
    for p in held_positions:
        np.testing.assert_allclose(ring[:, p % WINDOW], keys[:, p], atol=1e-6)
    assert len(held_positions) == min(true_len, WINDOW)
    # a full layer keeps every position where it is
    full = np.asarray(engine.cache["block3"]["attn"]["cached_key"])
    assert full.shape == (2, 2, 64, 16) and np.abs(full[1, :, 0]).max() > 0


def test_a_window_is_the_band_of_the_causal_mask():
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (1, 2, 12, 8))
               for i in range(3))
    t, j = np.arange(12)[:, None], np.arange(12)[None, :]
    band = jnp.asarray((j <= t) & (t - j < 5))[None, None]
    want = dot_product_attention(q, k, v, mask=band)
    got = attention(q, k, v, causal=True, window=5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
    whole = attention(q, k, v, causal=True, window=12)
    np.testing.assert_allclose(
        np.asarray(whole), np.asarray(attention(q, k, v, causal=True)),
        atol=1e-6)
    with pytest.raises(ValueError, match="window"):
        attention(q, k, v, causal=True, window=5, implementation="flash")
    with pytest.raises(ValueError, match="window"):
        attention(q, k, v, causal=False, window=5)


def test_only_the_full_layers_read_through_decode_attention(monkeypatch):
    """The slot engine's decode step calls the kernel's entry point (since
    PR 35 the one that appends this step's row while it reads) in the full
    layers (a cache of ``positions`` a row) and not in the window layers,
    whose ring is one block and full after ``window`` tokens; with the
    parent's expressions (PR 24's scatter, PR 29's read) behind that entry
    point the streams keep their bytes (off the TPU the call's reference IS
    those expressions)."""
    from ml_trainer_tpu.models import exaone_moe
    from ml_trainer_tpu.ops.kernels import slot_cache_write_reference
    from ml_trainer_tpu.serving import engine

    s = sizes((0, 4))
    variables = {"params": reference.make_weights(seed_key(13), **s)}
    model = get_model("exaone_moe_tiny", experts_held=(0, 4))
    calls = []

    def parent(q, k_new, v_new, k_cache, v_cache, idx):
        calls.append((q.shape, k_cache.shape))
        k_cache, v_cache = slot_cache_write_reference(
            k_cache, v_cache, k_new, v_new, idx)
        slots = jnp.arange(k_cache.shape[2])[None, :]
        return exaone_moe.grouped_decode_attention(
            q, k_cache, v_cache, slots <= idx[:, None]), k_cache, v_cache

    def streams():
        with Server(model, variables, max_batch=3) as server:
            return [np.asarray(server.submit(prompt(n, n), 12).result(
                timeout=300)) for n in (5, 11, 19)]

    plain = streams()
    monkeypatch.setattr(engine, "_COMPILED", {})   # trace anew under the spy
    monkeypatch.setattr(exaone_moe, "decode_attention_append", parent)
    for a, b in zip(plain, streams()):
        np.testing.assert_array_equal(a, b)
    full = sum(kind == "full_attention" for kind in s["layer_types"])
    assert calls and len(calls) % full == 0
    assert {k for _, k in calls} == {
        (3, s["kv_heads"], s["positions"], s["head_dim"])}
    assert {q for q, _ in calls} == {(3, s["heads"], 1, s["head_dim"])}
    # generate() decodes under a scalar index: the grouped XLA read
    del calls[:]
    generate(model, variables, prompt(9, 3)[None], 4)
    assert not calls


def test_served_over_http_as_generate_computes_it_and_refused_as_others():
    s = sizes((0, 4))
    weights = reference.make_weights(seed_key(13), **s)
    model = get_model("exaone_moe_tiny", experts_held=[0, 4],
                      layer_types=list(PERIOD * 2))
    assert hash(model) == hash(get_model("exaone_moe_tiny",
                                         experts_held=(0, 4)))
    tokens = prompt(19, 5)
    want = np.asarray(generate(model, {"params": weights}, tokens[None], 12))[0]
    with Server(model, {"params": weights}, max_batch=64,
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
