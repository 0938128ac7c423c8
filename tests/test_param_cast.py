"""The tree a serving engine's programs are handed (serving/param_cast.py).

A leaf that every use in the decode and prefill programs casts to one
narrower dtype is held in that dtype, cast once when the engine is built.
The programs apply the same rounding to the same numbers either way, so
what they compute is bit-equal to running them on the tree as handed; what
identifies the weights and the int8 collection still read that tree.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ml_trainer_tpu.checkpoint import weights_fingerprint
from ml_trainer_tpu.models import get_model
from ml_trainer_tpu.ops.kernels.int8_matmul import quantize_tree
from ml_trainer_tpu.serving.engine import SlotDecodeEngine
from ml_trainer_tpu.serving.param_cast import cast_at_use, cast_targets
from ml_trainer_tpu.serving.scheduler import Request

PROJECTIONS = ("qkv", "proj", "fc_in", "fc_out")


@pytest.fixture(scope="module")
def bf16_gpt2():
    """``gpt2_tiny`` computing in bfloat16 over float32 parameters, as
    ``init`` makes them: the ``gpt2-large`` configuration's precision."""
    model = get_model("gpt2_tiny", dtype=jnp.bfloat16, max_len=64)
    variables = model.init(
        {"params": jax.random.PRNGKey(0)}, np.zeros((1, 8), np.int32),
        train=False)
    return model, variables


def by_path(tree):
    return {jax.tree_util.keystr(p): leaf
            for p, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


def by_hand(params):
    """The tree with the four projections' kernels and biases cast to
    bfloat16 by name: what the rule should find, written out."""
    def leaf(path, x):
        names = [getattr(k, "key", None) for k in path]
        return x.astype(jnp.bfloat16) if any(
            n in PROJECTIONS for n in names) else x
    return jax.tree_util.tree_map_with_path(leaf, params)


def prompt(seed, n):
    return np.random.default_rng(seed).integers(0, 1024, n).astype(np.int32)


# -- the rule, on small functions ------------------------------------------

def _kernel_use(p, x):
    return x @ p.astype(jnp.bfloat16)


@pytest.mark.parametrize("use,expected", [
    (_kernel_use, jnp.bfloat16),
    # read through a jitted call, a rematerialised one and a scan's
    # constant: followed
    (lambda p, x: jax.jit(_kernel_use)(p, x), jnp.bfloat16),
    (lambda p, x: jax.checkpoint(_kernel_use)(p, x), jnp.bfloat16),
    (lambda p, x: jax.lax.scan(
        lambda c, _: (_kernel_use(p, c), None), x, None, length=2)[0],
     jnp.bfloat16),
    # cast AND read as it is (the tied head of GPT-2's embedding)
    (lambda p, x: (_kernel_use(p, x), x.astype(jnp.float32) @ p), None),
    # cast to two dtypes
    (lambda p, x: (_kernel_use(p, x), p.astype(jnp.float16)), None),
    # handed back whole, and never read
    (lambda p, x: p, None),
    (lambda p, x: x, None),
], ids=["cast", "in_jit", "in_remat", "scan_const", "cast_and_read", "two_dtypes",
        "returned", "unused"])
def test_a_leaf_is_held_in_the_one_dtype_every_use_casts_it_to(use, expected):
    p = jnp.ones((8, 8), jnp.float32)
    x = jnp.ones((2, 8), jnp.bfloat16)
    got = cast_targets({"w": p}, [(lambda t, x: use(t["w"], x), (x,))])
    assert got == [None if expected is None else np.dtype(expected)]


def test_a_cast_to_a_wider_dtype_is_left_to_the_program():
    """A bfloat16 leaf every use reads as float32 (kimi-linear's short
    convolution): held as float32 it would be read at twice the bytes."""
    p = jnp.ones((8, 8), jnp.bfloat16)
    got = cast_targets(
        p, [(lambda p, x: x @ p.astype(jnp.float32), (jnp.ones((2, 8)),))])
    assert got == [None]


def test_the_cast_leaves_the_callers_arrays_as_they_are():
    tree = {"w": jnp.arange(16.0).reshape(4, 4) / 3, "b": jnp.ones((4,))}
    before = jax.tree.map(np.asarray, tree)
    served, cast = cast_at_use(tree, [(
        lambda t, x: x @ t["w"].astype(jnp.bfloat16) + t["b"],
        (jnp.ones((2, 4), jnp.bfloat16),))])
    assert cast == tree["w"].nbytes
    assert served["w"].dtype == jnp.bfloat16 and served["b"] is tree["b"]
    np.testing.assert_array_equal(
        np.asarray(served["w"]), np.asarray(tree["w"].astype(jnp.bfloat16)))
    for k in tree:
        assert not tree[k].is_deleted()
        np.testing.assert_array_equal(np.asarray(tree[k]), before[k])


# -- the engine ------------------------------------------------------------

def test_the_engine_holds_the_projections_in_bfloat16(bf16_gpt2):
    model, variables = bf16_gpt2
    engine = SlotDecodeEngine(model, variables, max_batch=2)
    handed, served = by_path(variables["params"]), by_path(engine.params)
    cast = {k for k in handed if served[k] is not handed[k]}
    assert cast == {k for k in handed
                    if any(f"['{n}']" in k for n in PROJECTIONS)}
    assert all(k.endswith(("['kernel']", "['bias']")) for k in cast)
    assert all(served[k].dtype == jnp.bfloat16 for k in cast)
    for k in set(handed) - cast:   # ln*, pos_embed, tok_embed
        assert served[k] is handed[k] and served[k].dtype == jnp.float32
    snap = engine.metrics.snapshot()
    assert snap["cast_param_bytes"] == sum(handed[k].nbytes for k in cast)
    assert snap["served_param_bytes"] == sum(
        leaf.nbytes for leaf in served.values())
    assert engine.cast_param_bytes == snap["cast_param_bytes"] > 0


@pytest.mark.parametrize("temperature", [0.0, 0.8], ids=["greedy", "seeded"])
def test_a_tree_cast_by_hand_serves_the_same_tokens(bf16_gpt2, temperature):
    """An engine handed the float32 tree and one handed the tree already
    cast (which finds nothing left to cast): two requests, a prefill each
    and 20 decode steps, token for token."""
    model, variables = bf16_gpt2
    pre = {"params": by_hand(variables["params"])}
    runs = []
    for tree in (variables, pre):
        engine = SlotDecodeEngine(model, tree, max_batch=2)
        reqs = [Request(prompt=prompt(s, n), max_new_tokens=21,
                        temperature=temperature, rng=s)
                for s, n in ((1, 7), (2, 12))]
        for slot, req in enumerate(reqs):
            assert engine.admit(req, slot) == "active"
        for _ in range(20):
            engine.step()
        runs.append([list(r.tokens) for r in reqs])
        if tree is pre:
            assert engine.cast_param_bytes == 0
    assert all(len(t) == 21 for t in runs[0])
    assert runs[0] == runs[1]


def test_the_programs_compute_what_they_did_on_the_float32_tree(bf16_gpt2):
    """The parent's way of running: the same decode and prefill programs
    handed the float32 tree.  Outputs bit-equal, cache and tokens."""
    model, variables = bf16_gpt2
    engine = SlotDecodeEngine(model, variables, max_batch=2)
    assert engine.admit(Request(prompt=prompt(3, 9), max_new_tokens=8,
                                temperature=0.0), 0) == "active"
    engine.step()
    args = (engine._temps, engine._rngs, engine._steps)
    outs = [engine._decode(params, jax.tree.map(jnp.copy, engine.cache),
                           engine.tok, *args)
            for params in (variables["params"], engine.params)]
    prefill = engine._prefill_program(16)
    padded = np.zeros((1, 16), np.int32)
    padded[0, :11] = prompt(4, 11)
    outs += [prefill(params, padded, np.int32(11), np.float32(0.0),
                     np.zeros((2,), np.uint32), np.int32(0))
             for params in (variables["params"], engine.params)]
    for a, b in ((outs[0], outs[1]), (outs[2], outs[3])):
        jax.tree.map(lambda x, y: np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y)), a, b)


def test_identity_and_int8_still_read_the_tree_as_handed(bf16_gpt2):
    model, variables = bf16_gpt2
    engine = SlotDecodeEngine(model, variables, max_batch=2,
                              quant_int8=True)
    assert engine.cast_param_bytes > 0
    assert engine.weights_fp == weights_fingerprint(
        {"params": variables["params"]})
    want = quantize_tree(variables["params"])
    assert jax.tree.structure(engine._quant) == jax.tree.structure(want)
    jax.tree.map(lambda x, y: np.testing.assert_array_equal(
        np.asarray(x), np.asarray(y)), engine._quant, want)


def test_a_float32_model_serves_the_callers_tree():
    """Nothing to cast where the model computes in the parameters' dtype:
    the programs are handed the caller's own arrays."""
    model = get_model("gpt2_tiny", max_len=64)
    variables = model.init(
        {"params": jax.random.PRNGKey(0)}, np.zeros((1, 8), np.int32),
        train=False)
    engine = SlotDecodeEngine(model, variables, max_batch=2)
    assert engine.params is variables["params"]
    assert engine.metrics.snapshot()["cast_param_bytes"] == 0


def _family(name):
    """A tiny preset and its tree as the benchmark makes it (the
    reference's ``make_weights``: matrices AS bfloat16, a few float32
    leaves), at the sizes of the family's own serving tests."""
    import importlib

    from benchmark.reference import seed_key

    tests = importlib.import_module(f"test_{name}_serving")
    reference = importlib.import_module(f"benchmark.references.{name}")
    if name == "brumby":
        sizes, kw = tests.SIZES, {}
    else:
        sizes = tests.sizes((0, 4))
        kw = {"experts_held": sizes["experts_held"]}
    weights = reference.make_weights(seed_key(2**31 + 5), **sizes)
    return get_model(f"{name}_tiny", dtype=jnp.bfloat16, **kw), weights


@pytest.mark.parametrize("name", ["exaone_moe", "kimi_linear", "brumby"])
def test_a_tree_made_as_bfloat16_is_served_as_handed(name):
    """Their float32 leaves (norm scales, the router, kimi's ``A_log`` and
    ``dt_bias``) are read as float32, and kimi's bfloat16 convolution is
    read as float32 (a wider cast, left to the program): nothing is cast,
    the programs are handed the caller's own arrays, and the decode
    program lowers to the text it lowers to on the tree as handed."""
    model, weights = _family(name)
    engine = SlotDecodeEngine(model, {"params": weights}, max_batch=2)
    assert engine.metrics.snapshot()["cast_param_bytes"] == 0
    assert engine.params is weights
    assert {leaf.dtype for leaf in jax.tree.leaves(weights)} == {
        jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)}
    args = (engine.cache, engine.tok, engine._temps, engine._rngs,
            engine._steps, *engine._decode_extra(engine._active_rows()))
    copy = jax.tree.map(jnp.copy, weights)
    assert (engine._decode.lower(engine.params, *args).as_text()
            == engine._decode.lower(copy, *args).as_text())
