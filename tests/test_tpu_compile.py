"""Kernels of the serving path compiled, at the benchmark's shapes, by the
TPU's own compiler for a chip that is described and not attached.

Interpret mode cannot show what XLA does AROUND a Mosaic call: the kernel
wants its operands in the order written, and XLA keeps an array in the
layout that pads least, so a kernel handed the wrong view compiles, runs
right, and copies the whole operand in and out on every call.  Nothing
runs here and no number of the device comes out of it.

The topology is described inside a fixture, never at import: only the
worker that is given this file loads the TPU's library.
"""

import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from ml_trainer_tpu.ops.kernels.decode_attention import (
    decode_attention,
    decode_attention_append,
    grouped_decode_attention,
)
from ml_trainer_tpu.ops.kernels.slot_cache_write import (
    _position_on_lanes,
    slot_cache_write,
    slot_row_write,
)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("shape,layout", [
    ((32, 20, 1024, 64), "{2,3,1,0"),    # gpt2-large.batch-decode: 20 x 64
    ((32, 8, 1024, 128), "{3,2,1,0"),    # heads of 128: the order as written
], ids=["position_on_lanes", "position_on_sublanes"])
def test_slot_cache_write_leaves_the_donated_cache_in_place(
        one_chip, shape, layout):
    """The write compiled with the cache donated: XLA's layout of the cache
    is the one ``_position_on_lanes`` foresees, the Mosaic call is there,
    and no copy, no scatter and no temporary of the cache's size is."""
    b, h, L, d = shape

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(
        lambda kc, vc, kn, vn, pos: slot_cache_write(
            kc, vc, kn, vn, pos, implementation="pallas"),
        donate_argnums=(0, 1),
    ).lower(spec(shape), spec(shape), spec((b, h, 1, d)), spec((b, h, 1, d)),
            spec((b,), jnp.int32)).compile()
    text = compiled.as_text()
    entry = re.search(r"entry_computation_layout=\{\((.*?)\)->", text).group(1)
    assert entry.startswith(f"bf16[{b},{h},{L},{d}]{layout}"), entry[:80]
    assert _position_on_lanes(L, d) == (layout == "{2,3,1,0")
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "slot_cache_write" in text
    cache = rf"bf16\[{b},{h},(?:{L},{d}|{d},{L})\]"
    assert not re.findall(rf"= {cache}\S* (?:copy|scatter)\(", text)
    assert " while(" not in text
    cache_bytes = 2 * b * h * L * d
    assert compiled.memory_analysis().temp_size_in_bytes < cache_bytes // 64


@pytest.mark.parametrize("heads,shape,layout", [
    (20, (32, 20, 1024, 64), "{2,3,1,0"),   # gpt2-large.batch-decode
    (64, (64, 8, 2048, 128), "{3,2,1,0"),   # k-exaone's full layers: 8 a group
], ids=["position_on_lanes", "position_on_sublanes"])
def test_decode_step_reads_the_cache_where_the_write_left_it(
        one_chip, heads, shape, layout):
    """A layer's decode step as the slot engine states it, the write then
    the read of each row's live blocks, compiled with the cache donated:
    two Mosaic calls under their names, the cache in the layout both
    foresee, and no copy or transpose of a cache-sized operand round
    either (a wrong guess of the layout would cost two a call)."""
    b, g, L, d = shape

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def step(kc, vc, q, kn, vn, idx):
        kc, vc = slot_cache_write(kc, vc, kn, vn, idx,
                                  implementation="pallas")
        return kc, vc, decode_attention(q, kc, vc, idx + 1,
                                        implementation="pallas")

    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
        spec(shape), spec(shape), spec((b, heads, 1, d)),
        spec((b, g, 1, d)), spec((b, g, 1, d)), spec((b,), jnp.int32),
    ).compile()
    text = compiled.as_text()
    entry = re.search(r"entry_computation_layout=\{\((.*?)\)->", text).group(1)
    assert entry.startswith(f"bf16[{b},{g},{L},{d}]{layout}"), entry[:80]
    calls = re.findall(
        r"%(\S+) = .*? custom-call\(.*custom_call_target=\"tpu_custom_call\"",
        text)
    assert len(calls) == 2, calls
    for kernel in ("slot_cache_write", "decode_attention"):
        assert sum(kernel in name for name in calls) == 1, (kernel, calls)
    cache = rf"bf16\[{b},{g},(?:{L},{d}|{d},{L})\]"
    assert not re.findall(rf"= {cache}\S* (?:copy|transpose|scatter)\(", text)
    assert " while(" not in text
    cache_bytes = 2 * b * g * L * d
    assert compiled.memory_analysis().temp_size_in_bytes < cache_bytes // 16


@pytest.mark.parametrize("heads,shape,layout", [
    (20, (32, 20, 1024, 64), "{2,3,1,0"),   # gpt2-large.batch-decode
    (64, (64, 8, 2048, 128), "{3,2,1,0"),   # k-exaone's full layers: 8 a group
], ids=["position_on_lanes", "position_on_sublanes"])
def test_decode_step_appends_where_it_reads(one_chip, heads, shape, layout):
    """A layer's decode step as the slot engine states it since PR 35, the
    ONE call that puts this step's rows into the block it holds and writes
    their tile back, compiled with the cache donated: one Mosaic call whose
    name starts with ``decode_attention`` (the handle of
    ``decode_attention_share_pct``) and none of the write kernel's, the
    cache in the layout ``_position_on_lanes`` foresees, and no copy,
    transpose, scatter or loop of a cache-sized operand round it."""
    b, g, L, d = shape

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def step(kc, vc, q, kn, vn, idx):
        out, kc, vc = decode_attention_append(
            q, kn, vn, kc, vc, idx, implementation="pallas")
        return kc, vc, out

    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
        spec(shape), spec(shape), spec((b, heads, 1, d)),
        spec((b, g, 1, d)), spec((b, g, 1, d)), spec((b,), jnp.int32),
    ).compile()
    text = compiled.as_text()
    entry = re.search(r"entry_computation_layout=\{\((.*?)\)->", text).group(1)
    assert entry.startswith(f"bf16[{b},{g},{L},{d}]{layout}"), entry[:80]
    assert _position_on_lanes(L, d) == (layout == "{2,3,1,0")
    calls = re.findall(
        r"%(\S+) = .*? custom-call\(.*custom_call_target=\"tpu_custom_call\"",
        text)
    assert len(calls) == 1 and calls[0].startswith("decode_attention"), calls
    cache = rf"bf16\[{b},{g},(?:{L},{d}|{d},{L})\]"
    assert not re.findall(rf"= {cache}\S* (?:copy|transpose|scatter)\(", text)
    assert " while(" not in text
    memory = compiled.memory_analysis()
    cache_bytes = 2 * b * g * L * d
    assert memory.alias_size_in_bytes >= 2 * cache_bytes    # both in place
    assert memory.temp_size_in_bytes < cache_bytes // 16


def test_a_latent_cache_is_written_and_read_where_it_lies(one_chip):
    """``kimi-linear-48b-ep8``'s latent layer, 128 slots of 4,096 rows of
    512 + 64 values under ONE head, as the slot engine states a decode step:
    ``slot_row_write`` (the kernel's call with one cache) then XLA's masked
    read of the leaf as keys AND values by 32 query heads.  A row of 576 pads
    less on the sublanes than on the lanes, so the position goes on the
    lanes; one Mosaic call, the cache donated and never copied, transposed
    or scattered, and the temporaries are the scores, not a cache."""
    b, L, d, heads = 128, 4096, 576, 32

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def step(cache, q, new, idx):
        cache = slot_row_write(cache, new, idx, implementation="pallas")
        valid = jnp.arange(L)[None, :] <= idx[:, None]
        return cache, grouped_decode_attention(
            q, cache, cache, valid, scale=192 ** -0.5)

    compiled = jax.jit(step, donate_argnums=(0,)).lower(
        spec((b, 1, L, d)), spec((b, heads, 1, d)), spec((b, 1, 1, d)),
        spec((b,), jnp.int32)).compile()
    text = compiled.as_text()
    entry = re.search(r"entry_computation_layout=\{\((.*?)\)->", text).group(1)
    assert entry.startswith(f"bf16[{b},1,{L},{d}]{{2,3,1,0"), entry[:80]
    assert _position_on_lanes(L, d)
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "slot_cache_write" in text
    cache = rf"bf16\[{b},1,(?:{L},{d}|{d},{L})\]"
    assert not re.findall(rf"= {cache}\S* (?:copy|transpose|scatter)\(", text)
    assert " while(" not in text
    scores = 4 * b * heads * L
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5 * scores


def test_the_latent_layers_decode_step_reads_each_pool_in_one_call(
        one_chip, monkeypatch):
    """``kimi-linear-48b-ep8``'s latent layers at their widths (32 heads,
    a row of 512 + 64, 128 slots of 4,096 rows), two of them over dense
    feed-forwards, as the slot engine states a decode step with the cache
    donated: a layer is the row's write and ONE ``latent_decode_attention``
    call, the pool stays in the layout ``_position_on_lanes`` foresees
    (position on the lanes), and no copy, transpose or fusion of XLA's
    makes an array of the pool's shape (a relayout round the call would
    cost more than the read it replaces)."""
    import functools

    from ml_trainer_tpu.models import get_model, kimi_linear

    # 'auto' asks jax.default_backend(), which is the CPU here: the test
    # steers the model to the path a TPU takes
    for name in ("latent_decode_attention", "slot_row_write"):
        monkeypatch.setattr(kimi_linear, name, functools.partial(
            getattr(kimi_linear, name), implementation="pallas"))
    slots, L, d, layers = 128, 4096, 576, 2
    model = get_model("kimi_linear", num_layers=layers, kda_layers=(),
                      full_attn_layers=(1, 2), first_k_dense_replace=layers,
                      vocab_rows=1024, max_len=L, dtype=jnp.bfloat16)
    dm = model.clone(decode=True)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    made = jax.eval_shape(lambda: dm.init(
        {"params": jax.random.PRNGKey(0)}, jnp.zeros((slots, 1), jnp.int32),
        train=False))
    params = jax.tree.map(lambda s: spec(s.shape, s.dtype), made["params"])
    # the slot engine's cache: the scalar index widened to a row a slot
    cache = jax.tree.map(lambda s: spec(s.shape or (slots,), s.dtype),
                         made["cache"])
    assert cache["block0"]["attn"]["latent"].shape == (slots, 1, L, d)

    def step(params, cache, tok):
        logits, mut = dm.apply({"params": params, "cache": cache}, tok,
                               train=False, mutable=["cache"])
        return mut["cache"], jnp.argmax(logits[:, -1], -1)

    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, cache, spec((slots, 1), jnp.int32)).compile()
    text = compiled.as_text()
    assert re.findall(rf"bf16\[{slots},1,{L},{d}\]\{{2,3,1,0", text)
    calls = re.findall(
        r"%(\S+) = .*? custom-call\(.*custom_call_target=\"tpu_custom_call\"",
        text)
    assert len(calls) == 2 * layers, calls
    for kernel in ("slot_cache_write", "latent_decode_attention"):
        assert sum(name.startswith(kernel) for name in calls) == layers, (
            kernel, calls)
    pool = rf"bf16\[{slots},1,(?:{L},{d}|{d},{L})\]"
    assert not re.findall(
        rf"= {pool}\S* (?:copy|transpose|fusion|scatter)\(", text)
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= layers * slots * L * d * 2
    # the scores of XLA's read (4 B x heads x L a slot) are no longer made
    assert memory.temp_size_in_bytes < 4 * slots * 32 * L


def test_flash_kernels_compile_at_the_training_shape(one_chip):
    """Forward and backward at ``gpt2-124m.pretrain-1k``'s attention, 32 x 12
    heads of 64 over 1,024 positions in bfloat16 under the causal mask, with
    the blocks left to the chooser and the operands in the order written
    (what a kernel is handed inside the step): three Mosaic calls under
    their three names, nothing copied round them, and the per-row
    statistics dense (positions on the lanes), not a [.., S, 1] column that
    the tiled layout pads 128-fold."""
    from jax.experimental.layout import Format, Layout

    from ml_trainer_tpu.ops.attention import flash_attention

    b, h, s, d = 32, 12, 1024, 64
    as_written = Format(Layout(major_to_minor=(0, 1, 2, 3)), one_chip)
    q = jax.ShapeDtypeStruct((b, h, s, d), jnp.bfloat16, sharding=as_written)

    def grads(q, k, v):
        return jax.grad(
            lambda *a: flash_attention(*a, None, True)
            .astype(jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)

    compiled = jax.jit(grads, out_shardings=(as_written,) * 3).lower(
        q, q, q).compile()
    text = compiled.as_text()
    calls = re.findall(
        r"%(\S+) = (.*?) custom-call\(.*custom_call_target=\"tpu_custom_call\"",
        text)
    assert len(calls) == 3, [name for name, _ in calls]
    for kernel in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert sum(kernel in name for name, _ in calls) == 1, (kernel, calls)
    assert not re.findall(r" copy(?:-start)?\(", text)
    (fwd_result,) = [result for name, result in calls if "flash_fwd" in name]
    lse = re.search(r"f32\[([\d,]+)\]\{[\d,]+:T\((\d+),128\)", fwd_result)
    dims, sublanes = [int(n) for n in lse.group(1).split(",")], int(lse.group(2))
    assert dims[-1] == 128 and math.prod(dims) == b * h * s, fwd_result
    assert dims[-2] % sublanes == 0, fwd_result            # nothing padded
    assert f"f32[{b * h},{s},1]" not in text
    operand = 2 * b * h * s * d
    # the output cotangent, `out`, and two rows of statistics a position
    assert compiled.memory_analysis().temp_size_in_bytes < 2.1 * operand


def test_brumby_decode_and_prefill_fit_the_chip_with_one_state_pool(
        one_chip, monkeypatch):
    """``brumby-14b-l8`` at its published widths, 8 layers, as the slot
    engine states them: the decode program over 16 slots with the cache
    donated, and the batch-1 prefill of the largest bucket the cell's
    prompts reach (2,048, one chunk).  The state pool (16 x 8 x 34.35 MB)
    exists ONCE: all of it is aliased to the outputs; weights, pool and the
    larger program's temporaries fit 15.75 GB with the prefill's batch-1
    state beside them; the pool is passed over by ONE Mosaic call a layer
    (``retention_state_step``), in place, and by no fusion, copy or
    transpose of XLA's; the prefill computes the head on one position, not
    on 2,048 x 151,936 logits."""
    import functools

    from ml_trainer_tpu.models import brumby, get_model

    # 'auto' asks jax.default_backend(), which is the CPU here: the test
    # steers the model to the path a TPU takes
    monkeypatch.setattr(brumby, "retention_state_step", functools.partial(
        brumby.retention_state_step, implementation="pallas"))

    slots, bucket, layers = 16, 2048, 8
    model = get_model("brumby", num_layers=layers, max_len=4096,
                      dtype=jnp.bfloat16)
    dm = model.clone(decode=True)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def shapes(rows):
        made = jax.eval_shape(lambda: dm.init(
            {"params": jax.random.PRNGKey(0)},
            jnp.zeros((rows, 1), jnp.int32), train=False))
        return (jax.tree.map(lambda s: spec(s.shape, s.dtype), made["params"]),
                made["cache"])

    params, cache = shapes(slots)
    # the slot engine's cache: the scalar index widened to a row a slot
    cache = jax.tree.map(
        lambda s: spec(s.shape or (slots,), s.dtype), cache)
    pool = sum(math.prod(s.shape) * 4 for s in jax.tree.leaves(cache))
    assert pool == slots * layers * (8 * 8320 * 129 * 4) + layers * slots * 4

    def step(params, cache, tok, in_flight):
        logits, mut = dm.apply(
            {"params": params, "cache": cache}, tok, train=False,
            mutable=["cache", "step_counters"])
        return (mut["cache"], jnp.argmax(logits[:, -1], -1),
                model.reduce_step_counters(mut["step_counters"], in_flight))

    decode = jax.jit(step, donate_argnums=(1,)).lower(
        params, cache, spec((slots, 1), jnp.int32),
        spec((slots,), jnp.int32)).compile()
    memory = decode.memory_analysis()
    assert memory.alias_size_in_bytes >= pool                  # ONE copy
    text = decode.as_text()
    state = r"f32\[16,8,128,8320\]"
    assert not re.findall(
        rf"= {state}\S* (?:copy|transpose|fusion)\(", text)
    calls = re.findall(
        r"%(\S+) = .*? custom-call\(.*custom_call_target=\"tpu_custom_call\"",
        text)
    assert len(calls) == layers, calls
    assert all("retention_state_step" in name for name in calls)

    _, cache1 = shapes(1)

    def prefill(params, ids, true_len):
        empty = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), cache1)
        logits, mut = dm.apply(
            {"params": params, "cache": empty}, ids, train=False,
            mutable=["cache"], true_len=true_len)
        return mut["cache"], jnp.argmax(jax.lax.dynamic_index_in_dim(
            logits, true_len - 1, axis=1, keepdims=False), -1)

    prompt = jax.jit(prefill).lower(
        params, spec((1, bucket), jnp.int32), spec((), jnp.int32)).compile()
    temps = prompt.memory_analysis()
    logits = bucket * 151936 * 4
    assert temps.temp_size_in_bytes < 0.6 * logits
    held = memory.argument_size_in_bytes          # weights and the pool
    assert 12.75e9 < held < 12.85e9
    assert held + max(
        memory.temp_size_in_bytes,
        temps.temp_size_in_bytes + temps.output_size_in_bytes) < 14.5e9


@pytest.mark.parametrize("served", [False, True], ids=["as_handed", "served"])
def test_the_decode_step_reads_the_block_weights_at_two_bytes(
        one_chip, monkeypatch, served):
    """``gpt2-large.batch-decode``'s decode step at its widths, 2 layers,
    32 slots of 1,024 positions, as the slot engine states it, over float32
    parameters as ``init`` makes them.  Handed the tree the engine serves
    (``serving/param_cast.py``'s rule, read off this very step), the block
    weights are arguments of 2 bytes a value and no convert in the optimized
    program makes a bfloat16 array of a weight's or a bias's shape; handed
    the tree as made (the parent's way), every step converts each kernel."""
    import functools

    from ml_trainer_tpu.generate import _cache_shapes
    from ml_trainer_tpu.models import get_model, layers
    from ml_trainer_tpu.serving.param_cast import cast_targets

    monkeypatch.setattr(layers, "decode_attention_append", functools.partial(
        layers.decode_attention_append, implementation="pallas"))
    slots, width, depth = 32, 1280, 2
    model = get_model("gpt2_large", depth=depth, dtype=jnp.bfloat16)
    dm = model.clone(decode=True)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.eval_shape(lambda: model.init(
        {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 8), jnp.int32),
        train=False))["params"]
    cache = jax.tree.map(lambda s: spec(s.shape or (slots,), s.dtype),
                         _cache_shapes(dm, slots, jnp.int32))
    tok = spec((slots, 1), jnp.int32)

    def step(params, cache, tok):
        logits, mut = dm.apply({"params": params, "cache": cache}, tok,
                               train=False, mutable=["cache"])
        return mut["cache"], jnp.argmax(logits[:, -1], -1)

    held = cast_targets(params, [(step, (cache, tok))])
    leaves, treedef = jax.tree.flatten(params)
    args = jax.tree.unflatten(treedef, [
        spec(s.shape, t if served and t is not None else s.dtype)
        for s, t in zip(leaves, held)])
    weights = {(width, 3 * width), (width, width), (width, 4 * width),
               (4 * width, width)}
    block = depth * sum(a * b + b for a, b in weights)
    assert sum(math.prod(s.shape) for s, t in zip(leaves, held)
               if t is not None) == block               # kernels and biases
    assert {jnp.dtype(t) for t in held if t is not None} == {
        jnp.dtype(jnp.bfloat16)}
    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        args, cache, tok).compile()
    other = sum(math.prod(s.shape) for s in leaves) - block
    state = sum(math.prod(s.shape) * s.dtype.itemsize
                for s in jax.tree.leaves((cache, tok)))
    # within the tiles' padding (82 KB as handed), far under the 79 MB
    # between two and four bytes a value of the blocks
    laid = compiled.memory_analysis().argument_size_in_bytes
    assert abs(laid - ((2 if served else 4) * block + 4 * other + state)) < (
        block // 100)
    # what a convert makes, by shape: the activations' converts stay
    made = {tuple(int(n) for n in dims.split(","))
            for dims in re.findall(r"= bf16\[([\d,]+)\]\S* convert\(",
                                   compiled.as_text())}
    biases = {(b,) for _, b in weights}
    assert not made & (weights | biases) if served else weights <= made
