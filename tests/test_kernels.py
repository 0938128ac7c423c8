"""Pallas kernel layer (ml_trainer_tpu/ops/kernels/).

Every kernel ships pinned to a lax reference: the Pallas body run in
interpret mode must equal the reference BIT-FOR-BIT on CPU (both sides
under jit — the mode every caller runs in; eager-vs-traced differs by
FMA fusion noise no real path sees).  On top of the kernel-level pins:
the real Server streams identical bytes with ``paged_kernel`` on/off,
the real Trainer walks a bit-identical trajectory with the fused Adam
tail on/off, opt-in knobs refuse unsupported configs up front, and the
int8 decode path clears the argmax-agreement quality gate on a
peaked-logit model.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ml_trainer_tpu.models import get_model
from ml_trainer_tpu.ops.kernels import (
    adam_scalars,
    fused_adam_update,
    int8_matmul,
    paged_attention,
    paged_attention_reference,
    quantize_per_channel,
    quantize_tree,
    unscale_sqsum,
)
from ml_trainer_tpu.ops.attention import dot_product_attention
from ml_trainer_tpu.ops.kernels.decode_attention import (
    _decode_attention_pallas,
    _decode_block,
    _work_list,
    attended_positions,
    decode_attention,
    decode_attention_append,
    decode_attention_append_reference,
    decode_attention_reference,
    grouped_decode_attention,
)
from ml_trainer_tpu.ops.kernels.latent_decode_attention import (
    _latent_decode_attention_pallas,
    latent_decode_attention,
    latent_decode_attention_reference,
)
from ml_trainer_tpu.ops.kernels.slot_cache_write import (
    _position_on_lanes,
    landing_position,
    slot_cache_write,
    slot_cache_write_reference,
)


def _jrun(fn, *args, **kw):
    return jax.jit(lambda *a: fn(*a, **kw))(*args)


def _bits_equal(a, b):
    return all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
    )


def _paged_case(rng, b, h, d, ps, P, dtype, lengths):
    n_pages = b * P + 1  # + trash page 0
    q = jnp.asarray(rng.normal(size=(b, h, d)) * 0.5, dtype)
    kp, vp = (
        jnp.asarray(rng.normal(size=(n_pages, h, ps, d)) * 0.5, dtype)
        for _ in range(2)
    )
    table = jnp.asarray(
        1 + rng.permutation(n_pages - 1).reshape(b, P), jnp.int32
    )
    return q, kp, vp, table, jnp.asarray(lengths, jnp.int32)


# --------------------------------------------------- kernel-level pins
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,h,d,ps,P",
    [(2, 2, 8, 8, 2), (3, 4, 32, 16, 4)],  # VPU-lane and MXU-ish buckets
)
def test_paged_attention_interpret_parity(dtype, b, h, d, ps, P):
    """Ragged lengths — full row, length-1 (trash-page reads masked),
    partial last page — bit-equal to the gather reference."""
    rng = np.random.default_rng(0)
    lengths = [ps * P, 1, ps + 1][:b] + [ps * P] * max(0, b - 3)
    q, kp, vp, table, ln = _paged_case(rng, b, h, d, ps, P, dtype, lengths)
    got = _jrun(paged_attention, q, kp, vp, table, ln,
                implementation="pallas", interpret=True)
    want = _jrun(paged_attention_reference, q, kp, vp, table, ln)
    assert got.dtype == want.dtype
    assert _bits_equal(got, want)


def test_paged_attention_chain_fills_table():
    """Every non-trash page referenced exactly once (the pool exactly
    sized, nothing spare) and an all-trash table row: the mask, not the
    table contents, must decide what contributes."""
    rng = np.random.default_rng(1)
    q, kp, vp, table, ln = _paged_case(
        rng, 4, 2, 16, 8, 3, jnp.float32, [24, 24, 24, 1]
    )
    # Row 3 reads only token 0 of its first page; point the REST of its
    # row at the trash page — contents must not matter.
    table = table.at[3, 1:].set(0)
    got = _jrun(paged_attention, q, kp, vp, table, ln,
                implementation="pallas", interpret=True)
    want = _jrun(paged_attention_reference, q, kp, vp, table, ln)
    assert _bits_equal(got, want)


@pytest.mark.parametrize(
    "shape", [(7,), (128,), (3, 5), (64, 16), (2, 3, 4)]
)
def test_unscale_sqsum_shape_sweep(shape):
    """The division matches bitwise and the square-sum reduces in the
    reference's association order — including multi-axis leaves, whose
    per-axis reduction is shape-sensitive."""
    rng = np.random.default_rng(2)
    g = jnp.asarray(rng.normal(size=shape), jnp.float32)
    for denom in (2.0, jnp.float32(8.0)):
        for compute_sq in (True, False):
            got = _jrun(unscale_sqsum, g, denom, compute_sq=compute_sq,
                        implementation="pallas", interpret=True)
            want = _jrun(unscale_sqsum, g, denom, compute_sq=compute_sq,
                         implementation="reference")
            assert _bits_equal(got, want)
            assert (got[1] is None) == (not compute_sq)


def test_fused_adam_trajectory_matches_optax():
    """8 jitted steps of the fused tail (unscale -> global clip ->
    adam_scalars -> fused_adam_update -> opt_state rebuild) vs the
    unfused optax chain: params AND opt_state bit-identical at every
    step, so checkpoints are interchangeable mid-run."""
    shapes = {"w": (32, 16), "b": (16,), "emb": (64, 8)}
    keys = jax.random.split(jax.random.PRNGKey(4), len(shapes) + 1)
    params = {
        n: jax.random.normal(k, s, jnp.float32) * 0.02
        for (n, s), k in zip(shapes.items(), keys)
    }
    lr, clip, denom = 1e-2, 1.0, 4.0

    def sched(_count):
        return jnp.asarray(lr, jnp.float32)

    tx = optax.chain(optax.identity(), optax.adam(sched))
    one = jnp.asarray(1.0, jnp.float32)

    @jax.jit
    def ref_tail(g, p, st):
        g = jax.tree.map(lambda t: t / denom, g)
        sq = sum(
            jnp.sum(jnp.square(t.astype(jnp.float32)))
            for t in jax.tree.leaves(g)
        )
        factor = clip / jnp.maximum(jnp.sqrt(sq), clip)
        g = jax.tree.map(lambda t: t * factor, g)
        updates, new_st = tx.update(g, st, p)
        return optax.apply_updates(p, updates), new_st

    @jax.jit
    def fused_tail(g, p, st):
        _e, (adam_st, sched_st) = st
        g_def = jax.tree.structure(g)
        gs, sq = [], 0.0
        for t in jax.tree.leaves(g):
            th, s = unscale_sqsum(t, denom, compute_sq=True)
            gs.append(th)
            sq = sq + s
        factor = clip / jnp.maximum(jnp.sqrt(sq), clip)
        count_inc, bc1, bc2, step_size, sched_inc = adam_scalars(
            adam_st.count, sched_st.count, sched
        )
        outs = [
            fused_adam_update(t, pv, mu, nu, bc1=bc1, bc2=bc2,
                              step_size=step_size, lr_scale=one,
                              factor=factor)
            for t, pv, mu, nu in zip(
                gs, jax.tree.leaves(p),
                jax.tree.leaves(adam_st.mu), jax.tree.leaves(adam_st.nu),
            )
        ]
        new_p = jax.tree.unflatten(g_def, [o[0] for o in outs])
        new_st = (optax.EmptyState(), (
            optax.ScaleByAdamState(
                count=count_inc,
                mu=jax.tree.unflatten(g_def, [o[1] for o in outs]),
                nu=jax.tree.unflatten(g_def, [o[2] for o in outs]),
            ),
            optax.ScaleByScheduleState(count=sched_inc),
        ))
        return new_p, new_st

    p_ref = p_fused = params
    st_ref = st_fused = tx.init(params)
    for step in range(8):
        grads = {
            n: jax.random.normal(
                jax.random.fold_in(keys[-1], step * 10 + i), s,
                jnp.float32,
            )
            for i, (n, s) in enumerate(shapes.items())
        }
        p_ref, st_ref = ref_tail(grads, p_ref, st_ref)
        p_fused, st_fused = fused_tail(grads, p_fused, st_fused)
        assert _bits_equal(p_ref, p_fused), f"params diverged at {step}"
        assert _bits_equal(st_ref, st_fused), f"state diverged at {step}"


def test_int8_matmul_parity_and_quantize():
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(4, 32)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(32, 48)) * 0.1, jnp.float32)
    w = w.at[:, 0].set(0.0)  # all-zero column: scale must stay finite
    w_q, scale = quantize_per_channel(w)
    assert w_q.dtype == jnp.int8 and scale.shape == (48,)
    assert np.all(np.asarray(scale) > 0)
    # Symmetric per-channel round-trip: within half a quantization step.
    err = np.abs(np.asarray(w) - np.asarray(w_q, np.float32) * scale)
    assert np.all(err <= np.asarray(scale) * 0.5 + 1e-7)
    got = _jrun(int8_matmul, x, w_q, scale, implementation="pallas",
                interpret=True)
    want = _jrun(int8_matmul, x, w_q, scale, implementation="reference")
    assert _bits_equal(got, want)
    with pytest.raises(ValueError, match="int8"):
        int8_matmul(x, w.astype(jnp.float32), scale)


# ---------------------------------------------------- slot_cache_write
# [B, H, L, D] per layout the kernel can meet: XLA puts the position on the
# lanes where the head dimension pads more than the length (GPT-2's heads of
# 64; here 16 under 256 positions), and leaves it on the sublanes otherwise.
_WRITE_SHAPES = {"lanes": (4, 3, 256, 16), "sublanes": (4, 2, 64, 128)}
_WRITES = {}


def _slot_write(layout, dtype):
    """One cache, one pair of new rows and the two jitted writes a layout
    and dtype: the positions are arguments, so every case shares them."""
    key = (layout, jnp.dtype(dtype).name)
    if key not in _WRITES:
        b, h, L, d = _WRITE_SHAPES[layout]
        assert _position_on_lanes(L, d) == (layout == "lanes")
        rng = np.random.default_rng(len(_WRITES))
        arrays = tuple(
            jnp.asarray(rng.normal(size=shape), dtype)
            for shape in [(b + 2, h, L, d)] * 2 + [(b, h, 1, d)] * 2)
        _WRITES[key] = arrays, jax.jit(slot_cache_write_reference), jax.jit(
            lambda *a: slot_cache_write(
                *a, implementation="pallas", interpret=True))
    return _WRITES[key]


def _bits(x):
    x = np.asarray(x)
    return x.view({2: np.uint16, 4: np.uint32}[x.dtype.itemsize])


@pytest.mark.parametrize("layout", sorted(_WRITE_SHAPES))
@pytest.mark.parametrize("dtype,pos", [
    *[(jnp.bfloat16, p) for p in (
        0, 1, 15, 16, 17, 127, 128, "L-1", "L", "L+500", -3, -500,
        (0, 17, "L-1", "L+500"), (16, 16, 15, 1))],
    (jnp.float32, (7, 8, "L", 0)),
], ids=str)
def test_slot_cache_write_is_the_scatter_bit_for_bit(layout, dtype, pos):
    """The kernel (interpret mode) against ``jax.vmap`` of
    ``dynamic_update_slice``, K and V in one call: a position either side of
    every tile edge, odd and even (two bfloat16 share a sublane), the clamp
    at both ends, rows at different positions in one call; every other
    position and every other row keep their bits.  ``rows`` sends the writes
    to chosen rows of a pool with more rows than the call has."""
    (k_cache, v_cache, k_new, v_new), reference, kernel = _slot_write(
        layout, dtype)
    n, h, L, d = k_cache.shape
    b = k_new.shape[0]
    at = {"L-1": L - 1, "L": L, "L+500": L + 500}
    pos = pos if isinstance(pos, tuple) else (pos,) * b
    pos = [at.get(p, p) for p in pos]
    rows = jnp.asarray([5, 0, 3, 2], jnp.int32)
    args = (k_cache, v_cache, k_new, v_new, jnp.asarray(pos, jnp.int32), rows)
    want, got = reference(*args), kernel(*args)
    for cache, new, w, g in zip((k_cache, v_cache), (k_new, v_new), want, got):
        assert np.array_equal(_bits(g), _bits(w))
        expect = _bits(cache).copy()
        for row, p, r in zip(np.asarray(rows), pos, _bits(new)):
            p = p + L if p < 0 else p          # as JAX reads a start
            expect[row, :, min(max(p, 0), L - 1), :] = r[:, 0, :]
        assert np.array_equal(_bits(g), expect)
    # without ``rows`` the call writes row b of a cache of b rows
    plain = tuple(a[:b] for a in args[:2]) + args[2:5]
    assert _bits_equal(kernel(*plain), reference(*plain))


def test_slot_cache_write_refusals():
    (k_cache, v_cache, k_new, v_new), _, _ = _slot_write("lanes", jnp.float32)
    pos = jnp.zeros((k_new.shape[0],), jnp.int32)
    with pytest.raises(ValueError, match="no `rows`"):
        slot_cache_write(k_cache, v_cache, k_new, v_new, pos)
    with pytest.raises(ValueError, match="one position a row"):
        slot_cache_write(k_cache, v_cache, k_new[:, :, 0], v_new, pos, pos)
    with pytest.raises(ValueError, match="differ"):
        slot_cache_write(k_cache, v_cache[:, :1], k_new, v_new, pos, pos)
    with pytest.raises(ValueError, match="Unknown"):
        slot_cache_write(k_cache, v_cache, k_new, v_new, pos, pos,
                         implementation="scatter")


@pytest.mark.parametrize("shape", [(4, 1, 256, 72), (4, 1, 64, 128)],
                         ids=["lanes", "sublanes"])
def test_slot_row_write_is_the_same_write_with_one_cache(shape):
    """A latent cache is ONE leaf under one head (``models/kimi_linear.py``:
    a row of 512 + 64 values, here 72 and 128): the kernel called with one
    cache against the scatter, bit for bit, in both layouts, rows at
    different positions and the clamp past the end."""
    from ml_trainer_tpu.ops.kernels import slot_row_write

    b, h, L, d = shape
    assert _position_on_lanes(L, d) == (d == 72)
    rng = np.random.default_rng(5)
    cache = jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
    new = jnp.asarray(rng.normal(size=(b, h, 1, d)), jnp.bfloat16)
    pos = jnp.asarray([0, 17, L - 1, L + 500], jnp.int32)
    want = slot_row_write(cache, new, pos, implementation="reference")
    got = slot_row_write(cache, new, pos, implementation="pallas",
                         interpret=True)
    assert np.array_equal(_bits(got), _bits(want))
    expect = _bits(cache).copy()
    for row, p in enumerate([0, 17, L - 1, L - 1]):
        expect[row, :, p, :] = _bits(new)[row, :, 0, :]
    assert np.array_equal(_bits(got), expect)
    with pytest.raises(ValueError, match="one position a row"):
        slot_row_write(cache, new[:, :, 0], pos)
    with pytest.raises(ValueError, match="Unknown slot_row_write"):
        slot_row_write(cache, new, pos, implementation="scatter")


# ---------------------------------------------------- decode_attention
# [B, G, L, D] per layout, as for the write above; blocks of 128 positions,
# so a row meets one, two or all three of its blocks.
_DECODE_SHAPES = {"lanes": (6, 2, 384, 16), "sublanes": (6, 2, 384, 128)}
_DECODE_BLOCK = 128
_DECODE_TOL = {"float32": 1e-5, "bfloat16": 1e-2}
_DECODES = {}


def _decode_case(layout, dtype, rep):
    """q, K, V of a layout, dtype and group size, with the two jitted
    paths: the lengths are an argument, so every case shares them."""
    key = (layout, jnp.dtype(dtype).name, rep)
    if key not in _DECODES:
        b, g, L, d = _DECODE_SHAPES[layout]
        assert _position_on_lanes(L, d) == (layout == "lanes")
        rng = np.random.default_rng(len(_DECODES))
        arrays = tuple(
            jnp.asarray(rng.normal(size=shape), dtype)
            for shape in [(b, g * rep, 1, d)] + [(b, g, L, d)] * 2)
        _DECODES[key] = arrays, jax.jit(decode_attention_reference), jax.jit(
            lambda *a: _decode_attention_pallas(*a, _DECODE_BLOCK, True))
    return _DECODES[key]


@pytest.mark.parametrize("layout", sorted(_DECODE_SHAPES))
@pytest.mark.parametrize("rep", [1, 8], ids=["G==H", "H/G==8"])
@pytest.mark.parametrize("dtype,lengths", [
    (jnp.bfloat16, (1, 127, 128, 129, "L", "L+500")),
    (jnp.bfloat16, (256, 257, 2, "L-1", 0, -4)),
    (jnp.float32, (129, 1, "L+500", 255, "L", 128)),
], ids=str)
def test_decode_attention_is_the_masked_attention(layout, rep, dtype,
                                                  lengths):
    """The kernel (interpret mode) against the engine's masked attention
    over all ``L`` positions, both under jit: lengths of 1, either side of
    a block edge and on it, ``L``, and past ``L`` or under 1 (clamped), rows
    of different lengths in one call, one query head a key-value head and
    eight.  By tolerance: the online softmax sums in another order
    (docs/kernels.md)."""
    (q, k, v), reference, kernel = _decode_case(layout, dtype, rep)
    L = k.shape[2]
    at = {"L-1": L - 1, "L": L, "L+500": L + 500}
    lengths = jnp.asarray([at.get(n, n) for n in lengths], jnp.int32)
    want, got = reference(q, k, v, lengths), kernel(q, k, v, lengths)
    assert got.shape == q.shape and got.dtype == q.dtype
    tol = _DECODE_TOL[jnp.dtype(dtype).name]
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=tol, rtol=tol)


@pytest.mark.parametrize("layout", sorted(_DECODE_SHAPES))
@pytest.mark.parametrize("poison", [np.nan, 1e30], ids=["nan", "1e30"])
def test_decode_attention_never_sees_past_a_rows_length(layout, poison):
    """Exact: what lies past a row's length, in the block the length
    crosses and in the blocks never fetched, changes no bit of the
    output (a freed slot leaves its old tokens there)."""
    (q, k, v), _, kernel = _decode_case(layout, jnp.bfloat16, 8)
    L = k.shape[2]
    lengths = jnp.asarray([1, 127, 128, 129, L - 1, L], jnp.int32)
    dead = (jnp.arange(L)[None, :] >= lengths[:, None])[:, None, :, None]
    clean = kernel(q, k, v, lengths)
    dirty = kernel(q, jnp.where(dead, poison, k).astype(k.dtype),
                   jnp.where(dead, poison, v).astype(v.dtype), lengths)
    assert np.isfinite(np.asarray(clean, np.float32)).all()
    assert np.array_equal(_bits(dirty), _bits(clean))


@pytest.mark.parametrize("layout", sorted(_DECODE_SHAPES))
def test_decode_attention_of_a_full_row_is_the_unmasked_attention(layout):
    """At length ``L`` no position is masked: the kernel's bits there are
    its bits at any length past ``L`` (exact), and both paths are
    ``dot_product_attention`` with no mask (to float32 rounding: XLA fuses
    an all-true mask's sums in another order); the public call with the
    chooser's block (one block at this size) agrees with both."""
    (q, k, v), reference, kernel = _decode_case(layout, jnp.float32, 1)
    b, _, L, _ = k.shape
    full = jnp.full((b,), L, jnp.int32)
    assert np.array_equal(
        _bits(kernel(q, k, v, full)), _bits(kernel(q, k, v, full + 77)))
    unmasked = jax.jit(dot_product_attention)(q, k, v)
    np.testing.assert_allclose(reference(q, k, v, full), unmasked, atol=1e-6)
    np.testing.assert_allclose(kernel(q, k, v, full), unmasked, atol=1e-5)
    chosen = _jrun(decode_attention, q, k, v, full,
                   implementation="pallas", interpret=True)
    np.testing.assert_allclose(chosen, reference(q, k, v, full), atol=1e-5)
    np.testing.assert_allclose(chosen, kernel(q, k, v, full), atol=1e-5)
    # off the TPU 'auto' IS the reference
    assert np.array_equal(
        _bits(_jrun(decode_attention, q, k, v, full - 5)),
        _bits(reference(q, k, v, full - 5)))


def test_decode_attention_refusals():
    (q, k, v), _, _ = _decode_case("lanes", jnp.float32, 8)
    lengths = jnp.ones((q.shape[0],), jnp.int32)
    with pytest.raises(ValueError, match="differ"):
        decode_attention(q, k, v[:, :1], lengths)
    with pytest.raises(ValueError, match="differ"):
        decode_attention(q, k, v.astype(jnp.bfloat16), lengths)
    with pytest.raises(ValueError, match="one position a row"):
        decode_attention(q[:, :, 0], k, v, lengths)
    with pytest.raises(ValueError, match="one position a row"):
        decode_attention(jnp.concatenate([q, q], axis=2), k, v, lengths)
    with pytest.raises(ValueError, match="query heads over"):
        decode_attention(q[:, :15], k, v, lengths)
    with pytest.raises(ValueError, match="one a cache row"):
        decode_attention(q, k, v, lengths[:-1])
    with pytest.raises(ValueError, match="Unknown"):
        decode_attention(q, k, v, lengths, implementation="flash")


# ----------------------------------------------- decode_attention_append
_APPENDS = {}


def _append_case(layout, dtype, rep):
    """q, this step's K and V and the caches of a layout, dtype and group
    size, with three jitted steps: the reference (the scatter, then the
    masked attention), the PAIR of kernels the call joins (interpret mode)
    and the fused call (interpret mode), all at ``_DECODE_BLOCK``.  The
    positions are an argument, so every case shares them."""
    key = (layout, jnp.dtype(dtype).name, rep)
    if key not in _APPENDS:
        b, g, L, d = _DECODE_SHAPES[layout]
        rng = np.random.default_rng(
            [100, layout == "lanes", jnp.dtype(dtype).itemsize, rep])
        arrays = tuple(
            jnp.asarray(rng.normal(size=shape), dtype)
            for shape in [(b, g * rep, 1, d)] + [(b, g, 1, d)] * 2
            + [(b, g, L, d)] * 2)

        def pair(q, k_new, v_new, k_cache, v_cache, pos):
            k_cache, v_cache = slot_cache_write(
                k_cache, v_cache, k_new, v_new, pos,
                implementation="pallas", interpret=True)
            return _decode_attention_pallas(
                q, k_cache, v_cache, landing_position(pos, L) + 1,
                _DECODE_BLOCK, True), k_cache, v_cache

        def fused(q, k_new, v_new, k_cache, v_cache, pos):
            return _decode_attention_pallas(
                q, k_cache, v_cache, landing_position(pos, L) + 1,
                _DECODE_BLOCK, True, (k_new, v_new))

        _APPENDS[key] = (arrays, jax.jit(decode_attention_append_reference),
                         jax.jit(pair), jax.jit(fused))
    return _APPENDS[key]


def _positions(pos, b, L):
    at = {"L-1": L - 1, "L": L, "L+500": L + 500}
    pos = pos if isinstance(pos, tuple) else (pos,) * b
    return jnp.asarray([at.get(p, p) for p in pos], jnp.int32)


@pytest.mark.parametrize("layout", sorted(_DECODE_SHAPES))
@pytest.mark.parametrize("rep", [1, 8], ids=["G==H", "H/G==8"])
@pytest.mark.parametrize("dtype,pos", [
    *[(jnp.bfloat16, p) for p in (
        0, 127, 128, 255, 256, "L-1", "L+500",
        (0, 15, 16, 127, 256, "L+500"), (129, 1, "L-1", 255, "L", 383))],
    (jnp.float32, (7, 128, "L", 0, 255, "L+500")),
], ids=str)
def test_decode_attention_append_is_the_write_then_the_read(layout, rep,
                                                            dtype, pos):
    """The fused call (interpret mode) against the pair it joins: the
    caches bit for bit ``slot_cache_write_reference``'s (a position either
    side of every block's and tile's edge, the cache's two ends, a free
    row's position past ``L - 1``, clamped onto the row's own last
    position; every other position and row keep their bits), and the output
    that of ``decode_attention`` run after ``slot_cache_write``, both
    kernels in interpret mode: the fused body runs the read's operations in
    the read's order on the bits a read-back would see.  BIT FOR BIT on the
    lanes (the VPU body attends the merged tile from the tile it writes
    back); on the sublanes by the tolerance ``decode_attention`` is held to,
    because there the new row enters the block under a select that feeds
    the product, and XLA's CPU compiler may then sum that product in
    another order (seen: one bfloat16 of 9,216 a float32 rounding off, at
    one query head a key-value head; the chip's MXU takes the same bits
    either way).  Against the reference (the masked attention over all
    ``L`` positions) by that tolerance in both."""
    arrays, reference, pair, fused = _append_case(layout, dtype, rep)
    b, _, L, _ = arrays[3].shape
    pos = _positions(pos, b, L)
    want, two, got = (f(*arrays, pos) for f in (reference, pair, fused))
    assert got[0].shape == arrays[0].shape and got[0].dtype == arrays[0].dtype
    for cache, new, w, g in zip(arrays[3:], arrays[1:3], want[1:], got[1:]):
        assert np.array_equal(_bits(g), _bits(w))
        expect = _bits(cache).copy()
        for row, p in enumerate(np.asarray(pos)):
            expect[row, :, min(p, L - 1), :] = _bits(new)[row, :, 0, :]
        assert np.array_equal(_bits(g), expect)
    tol = _DECODE_TOL[jnp.dtype(dtype).name]
    if layout == "lanes":
        assert np.array_equal(_bits(got[0]), _bits(two[0]))
    for other in (two[0], want[0]):
        np.testing.assert_allclose(
            np.asarray(got[0], np.float32), np.asarray(other, np.float32),
            atol=tol, rtol=tol)


@pytest.mark.parametrize("layout", sorted(_DECODE_SHAPES))
@pytest.mark.parametrize("poison", [np.nan, 1e30], ids=["nan", "1e30"])
def test_decode_attention_append_never_sees_what_it_replaces(layout, poison):
    """Exact: what a freed slot left AT the position the step writes and
    past it, in the block the position lies in and in the blocks never
    fetched, changes no bit of the output, and is still there after the
    call everywhere but at the position."""
    arrays, reference, _, fused = _append_case(layout, jnp.bfloat16, 8)
    q, k_new, v_new, k, v = arrays
    b, _, L, _ = k.shape
    pos = _positions((0, 127, 128, 255, "L-1", "L+500"), b, L)
    dead = (jnp.arange(L)[None, :] >= jnp.minimum(pos, L - 1)[:, None])[
        :, None, :, None]
    dirty = [jnp.where(dead, poison, c).astype(c.dtype) for c in (k, v)]
    clean_out = fused(*arrays, pos)[0]
    out, k_after, v_after = fused(q, k_new, v_new, *dirty, pos)
    assert np.isfinite(np.asarray(clean_out, np.float32)).all()
    assert np.array_equal(_bits(out), _bits(clean_out))
    want = reference(q, k_new, v_new, *dirty, pos)
    assert np.array_equal(_bits(k_after), _bits(want[1]))
    assert np.array_equal(_bits(v_after), _bits(want[2]))


def test_decode_attention_append_entry_point_and_refusals():
    """The public call: off the TPU 'auto' IS the reference, and the
    reference IS the pair the engine made before (the scatter at ``pos``,
    the masked attention at ``pos + 1``); with the chooser's block the
    kernel agrees with it; both parents' shape checks refuse."""
    (q, k_new, v_new, k, v), reference, _, fused = _append_case(
        "lanes", jnp.float32, 8)
    b, _, L, _ = k.shape
    pos = _positions((0, 127, 128, 255, "L-1", "L+500"), b, L)
    want = reference(q, k_new, v_new, k, v, pos)
    written = jax.jit(slot_cache_write_reference)(k, v, k_new, v_new, pos)
    assert _bits_equal(want[1:], written)
    assert np.array_equal(_bits(want[0]), _bits(jax.jit(
        decode_attention_reference)(q, *written, pos + 1)))
    assert _bits_equal(
        _jrun(decode_attention_append, q, k_new, v_new, k, v, pos), want)
    chosen = _jrun(decode_attention_append, q, k_new, v_new, k, v, pos,
                   implementation="pallas", interpret=True)
    assert _bits_equal(chosen[1:], want[1:])
    np.testing.assert_allclose(chosen[0], want[0], atol=1e-5)
    # a float32 row into a bfloat16 cache is rounded BEFORE it is attended
    half = [c.astype(jnp.bfloat16) for c in (k, v)]
    rounded = fused(q, k_new.astype(jnp.bfloat16),
                    v_new.astype(jnp.bfloat16), *half, pos)
    got = _jrun(decode_attention_append, q, k_new, v_new, *half, pos,
                implementation="pallas", interpret=True)
    assert _bits_equal(got[1:], rounded[1:])
    np.testing.assert_allclose(got[0], rounded[0], atol=1e-5)

    with pytest.raises(ValueError, match="differ"):
        decode_attention_append(q, k_new, v_new, k, v[:, :1], pos)
    with pytest.raises(ValueError, match="differ"):
        decode_attention_append(q, k_new, v_new, k, half[1], pos)
    with pytest.raises(ValueError, match="one position a row of the cache"):
        decode_attention_append(q[:, :, 0], k_new, v_new, k, v, pos)
    with pytest.raises(ValueError, match="query heads over"):
        decode_attention_append(q[:, :15], k_new, v_new, k, v, pos)
    with pytest.raises(ValueError, match="one position a row"):
        decode_attention_append(q, k_new[:, :, 0], v_new, k, v, pos)
    with pytest.raises(ValueError, match="one position a row"):
        decode_attention_append(q, k_new, v_new[:, :1], k, v, pos)
    with pytest.raises(ValueError, match="one a cache row"):
        decode_attention_append(q, k_new, v_new, k, v, pos[:-1])
    with pytest.raises(ValueError, match="Unknown decode_attention_append"):
        decode_attention_append(q, k_new, v_new, k, v, pos,
                                implementation="scatter")


def test_decode_block_is_chosen_from_shape_and_dtype():
    """Bytes of K a grid step, not positions: the two serving cells' caches
    get the same block, float32 half of bfloat16, and a length 128 does not
    divide (the interpreter's) is one block."""
    assert _decode_block(20, 1024, 64, jnp.bfloat16) == 256
    assert _decode_block(8, 2048, 128, jnp.bfloat16) == 256
    assert _decode_block(20, 1024, 64, jnp.float32) == 128
    assert _decode_block(96, 1024, 128, jnp.bfloat16) == 128
    assert _decode_block(2, 384, 16, jnp.float32) == 384
    assert _decode_block(2, 200, 16, jnp.float32) == 200


def test_attended_positions_on_the_benchmark_traffic():
    """What the kernel fetches of the pool in the steady state of
    ``benchmark/traffic/batch-decode.json`` (a request of prompt P and
    output O holds P + 1 .. P + O positions, a decode step each): the live
    share plus about half a block a row.  PERF.md quotes these."""
    import json
    import os

    from benchmark.loadgen import base_block

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, "benchmark", "traffic", "batch-decode.json")) as fp:
        base = base_block(json.load(fp))
    lengths = np.concatenate([
        p + 1 + np.arange(o)
        for p, o in zip(base["prompt_len"], base["output_len"])])
    L = 1024
    share = {block: attended_positions(lengths, L, block).mean() / L
             for block in (1, 128, 256, 512, 1024)}
    assert share[1] == pytest.approx(0.4246, abs=1e-4)      # the live share
    assert share[128] == pytest.approx(0.4861, abs=1e-4)
    assert share[256] == pytest.approx(0.5482, abs=1e-4)
    assert share[512] == pytest.approx(0.6840, abs=1e-4)
    assert share[1024] == 1.0
    # the clamp, and a traced vector in, a traced vector out
    assert attended_positions(np.array([0, -3, 1, 128, 129, 5000]), 1024,
                              128).tolist() == [128, 128, 128, 128, 256, 1024]
    traced = jax.jit(lambda n: attended_positions(n, 1024, 256))(
        jnp.asarray([1, 257, 2000]))
    assert traced.tolist() == [256, 512, 1024]


# --------------------------------------------- latent_decode_attention
# [B, 1, L, c + r] per layout (a row of 72 pads less on the sublanes, so the
# position goes on the lanes; a row of 128 keeps the order as written), 8
# query heads on the one key-value head, the first c of a row the values;
# blocks of 128 positions, so a row meets one, two or all three of them.
_LATENT_SHAPES = {"lanes": (6, 8, 384, 72, 64), "sublanes": (6, 8, 384, 128, 96)}
_LATENT_SCALE = 24 ** -0.5
_LATENTS = {}


def _latent_case(layout, dtype):
    """q and the pool of a layout and dtype, with the two jitted paths: the
    lengths are an argument, so every case shares them."""
    key = (layout, jnp.dtype(dtype).name)
    if key not in _LATENTS:
        b, h, L, d, c = _LATENT_SHAPES[layout]
        assert _position_on_lanes(L, d) == (layout == "lanes")
        rng = np.random.default_rng(len(_LATENTS) + 40)
        arrays = tuple(jnp.asarray(rng.normal(size=shape), dtype)
                       for shape in ((b, h, 1, d), (b, 1, L, d)))
        _LATENTS[key] = arrays, jax.jit(
            lambda q, pool, n: latent_decode_attention_reference(
                q, pool, n, _LATENT_SCALE, c)), jax.jit(
            lambda q, pool, n: _latent_decode_attention_pallas(
                q, pool, n, _LATENT_SCALE, c, _DECODE_BLOCK, True))
    return _LATENTS[key]


@pytest.mark.parametrize("layout", sorted(_LATENT_SHAPES))
@pytest.mark.parametrize("dtype,positions", [
    (jnp.bfloat16, (0, 126, 127, 128, "L-1", "L+500")),
    (jnp.bfloat16, (255, 256, 1, "L-2", 383, "L")),
    (jnp.float32, (128, 0, "L+500", 254, "L-1", 127)),
], ids=str)
def test_latent_decode_attention_is_the_grouped_read_of_the_pool(
        layout, dtype, positions):
    """The kernel (interpret mode) against ``grouped_decode_attention`` over
    the pool as keys AND values, the first ``c`` of each head kept, both
    under jit, at the lengths the layer hands (``landing_position`` of the
    row's index, plus one): 1, either side of a block edge and on it, ``L``,
    and a free row's index past ``L - 1``, which lands on its last
    position and attends ``L``.  By tolerance: the online softmax sums in
    another order."""
    (q, pool), reference, kernel = _latent_case(layout, dtype)
    b, h, L, d, c = _LATENT_SHAPES[layout]
    at = {"L-2": L - 2, "L-1": L - 1, "L": L, "L+500": L + 500}
    pos = jnp.asarray([at.get(n, n) for n in positions], jnp.int32)
    lengths = landing_position(pos, L) + 1
    valid = jnp.arange(L)[None, :] <= pos[:, None]
    grouped = jax.jit(lambda q, pool: grouped_decode_attention(
        q, pool, pool, valid, scale=_LATENT_SCALE)[..., :c])(q, pool)
    want, got = reference(q, pool, lengths), kernel(q, pool, lengths)
    assert np.array_equal(_bits(want), _bits(grouped))
    assert got.shape == (b, h, 1, c) and got.dtype == q.dtype
    tol = _DECODE_TOL[jnp.dtype(dtype).name]
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=tol, rtol=tol)


@pytest.mark.parametrize("layout", sorted(_LATENT_SHAPES))
@pytest.mark.parametrize("poison", [np.nan, 1e30], ids=["nan", "1e30"])
def test_latent_decode_attention_never_sees_past_a_rows_length(
        layout, poison):
    """Exact: what lies past a row's length, in the block the length
    crosses (as a key AND as a value) and in the blocks never fetched,
    changes no bit of the output (a freed slot leaves its old rows)."""
    (q, pool), _, kernel = _latent_case(layout, jnp.bfloat16)
    L = pool.shape[2]
    lengths = jnp.asarray([1, 127, 128, 129, L - 1, L], jnp.int32)
    dead = (jnp.arange(L)[None, :] >= lengths[:, None])[:, None, :, None]
    clean = kernel(q, pool, lengths)
    dirty = kernel(q, jnp.where(dead, poison, pool).astype(pool.dtype),
                   lengths)
    assert np.isfinite(np.asarray(clean, np.float32)).all()
    assert np.array_equal(_bits(dirty), _bits(clean))


def test_latent_decode_attention_when_every_row_is_short():
    """Every row inside its first block: the work list is one step a row,
    far shorter than the grid of ``B x L / block`` a dense read would be,
    and the steps past its end are never run (their rows' outputs are the
    reference's, not what a repeated step would leave)."""
    (q, pool), reference, kernel = _latent_case("lanes", jnp.float32)
    b, _, L, _, _ = _LATENT_SHAPES["lanes"]
    lengths = jnp.asarray([1, 5, 64, 127, 128, 3], jnp.int32)
    row_of, block_of, steps = _work_list(lengths, L, _DECODE_BLOCK)
    assert int(steps) == b < b * L // _DECODE_BLOCK
    assert row_of[:b].tolist() == list(range(b))
    assert not block_of[:b].any()
    np.testing.assert_allclose(
        np.asarray(kernel(q, pool, lengths)),
        np.asarray(reference(q, pool, lengths)), atol=1e-5, rtol=1e-5)


def test_latent_decode_attention_entry_point_and_refusals():
    (q, pool), reference, kernel = _latent_case("lanes", jnp.float32)
    b, h, L, d, c = _LATENT_SHAPES["lanes"]
    lengths = jnp.asarray([1, 2, 200, L, 300, 129], jnp.int32)
    kw = dict(scale=_LATENT_SCALE, value_dim=c)
    chosen = _jrun(latent_decode_attention, q, pool, lengths,
                   implementation="pallas", interpret=True, **kw)
    np.testing.assert_allclose(chosen, reference(q, pool, lengths),
                               atol=1e-5)
    # off the TPU 'auto' IS the reference
    assert np.array_equal(
        _bits(_jrun(latent_decode_attention, q, pool, lengths, **kw)),
        _bits(reference(q, pool, lengths)))
    with pytest.raises(ValueError, match="one position a row"):
        latent_decode_attention(q[..., :-8], pool, lengths, **kw)
    with pytest.raises(ValueError, match="one position a row"):
        latent_decode_attention(q[:, :, 0], pool, lengths, **kw)
    with pytest.raises(ValueError, match="one position a row"):
        latent_decode_attention(q[:-1], pool, lengths, **kw)
    with pytest.raises(ValueError, match="value_dim 73 must lie"):
        latent_decode_attention(q, pool, lengths, scale=1.0, value_dim=d + 1)
    with pytest.raises(ValueError, match="value_dim 0 must lie"):
        latent_decode_attention(q, pool, lengths, scale=1.0, value_dim=0)
    with pytest.raises(ValueError, match="one key-value head"):
        latent_decode_attention(
            q, jnp.concatenate([pool, pool], axis=1), lengths, **kw)
    with pytest.raises(ValueError, match="one a pool row"):
        latent_decode_attention(q, pool, lengths[:-1], **kw)
    with pytest.raises(ValueError, match="Unknown latent_decode_attention"):
        latent_decode_attention(q, pool, lengths, implementation="mosaic",
                                **kw)


def _kernel_calls():
    rng = np.random.default_rng(9)
    g = jnp.asarray(rng.normal(size=(64, 16)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(4, 32)), jnp.float32)
    w_q, scale = quantize_per_channel(
        jnp.asarray(rng.normal(size=(32, 48)), jnp.float32))
    kw = dict(implementation="pallas", interpret=True)
    return {
        "paged_attention_decode": lambda: paged_attention(
            *_paged_case(rng, 2, 2, 8, 8, 2, jnp.float32, [5, 16]), **kw),
        "int8_matmul": lambda: int8_matmul(x, w_q, scale, **kw),
        "slot_cache_write": lambda: slot_cache_write(
            *_slot_write("lanes", jnp.float32)[0],
            jnp.zeros((4,), jnp.int32), jnp.arange(4), **kw),
        "decode_attention": lambda: decode_attention(
            *_decode_case("lanes", jnp.float32, 1)[0],
            jnp.ones((6,), jnp.int32), **kw),
        "decode_attention_append": lambda: decode_attention_append(
            *_append_case("lanes", jnp.float32, 1)[0],
            jnp.ones((6,), jnp.int32), **kw),
        "retention_state_step": lambda: _retention_state_step_call(**kw),
        "latent_decode_attention": lambda: latent_decode_attention(
            *_latent_case("lanes", jnp.float32)[0], jnp.ones((6,), jnp.int32),
            scale=1.0, value_dim=64, **kw),
        "fused_adam_norm": lambda: unscale_sqsum(g, 2.0, **kw),
        "fused_adam_update": lambda: fused_adam_update(
            g, g, g, jnp.abs(g), bc1=0.1, bc2=0.001, step_size=-1e-3,
            lr_scale=1.0, **kw),
    }


@pytest.mark.parametrize("name", ["paged_attention_decode", "int8_matmul",
                                  "fused_adam_norm", "fused_adam_update",
                                  "slot_cache_write", "decode_attention",
                                  "decode_attention_append",
                                  "retention_state_step",
                                  "latent_decode_attention"])
def test_each_kernel_carries_the_name_the_profiler_shows(name):
    """docs/kernels.md: a Pallas call's ``name`` is the instruction's name
    on the device trace, the handle a per-kernel metric finds it by."""
    text = str(jax.make_jaxpr(_kernel_calls()[name])())
    assert f"name={name}\n" in text or f"name={name} " in text, text[:400]


def test_quantize_tree_structure():
    model = get_model("gpt2_tiny", max_len=32)
    variables = model.init(
        {"params": jax.random.PRNGKey(0)}, np.zeros((1, 8), np.int32),
        train=False,
    )
    quant = quantize_tree(variables["params"])

    def leaf_keys(d, out):
        for k, v in d.items():
            (leaf_keys(v, out) if isinstance(v, dict) else out.add(k))
        return out

    names = leaf_keys(quant, set())
    # Every target contributed its w/scale/b triple somewhere.
    for t in ("qkv", "proj", "fc_in", "fc_out"):
        assert {f"{t}_w", f"{t}_scale", f"{t}_b"} <= names, names
    # Nothing matched -> {} (callers refuse, never serve unquantized).
    assert quantize_tree(variables["params"], targets=("nope",)) == {}
    with pytest.raises(TypeError):
        quantize_tree([1, 2, 3])


# ------------------------------------------------ engine + trainer pins
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


def _run_requests(model, variables, **server_kw):
    from ml_trainer_tpu.serving import Server

    prompts = [_prompt(s, n) for s, n in
               ((0, 5), (1, 3), (2, 12), (3, 7), (4, 17), (5, 9))]
    outs = []
    with Server(model, variables, max_batch=4, kv_page_size=16,
                **server_kw) as server:
        streams = [
            server.submit(p, 10, temperature=0.7, rng=42)
            if i == 3 else server.submit(p, 10)
            for i, p in enumerate(prompts)
        ]
        for s in streams:
            outs.append(np.asarray(s.result(timeout=300)))
    return outs


def _parent_decode_step(calls):
    """``layers.py``'s decode step at a per-row index as it stood before
    the kernels (PR 24's write, PR 29's read), written out, behind the fused
    call's signature; keeps what it was called with."""
    from ml_trainer_tpu.ops.attention import attention

    def spy(q, k_new, v_new, k_cache, v_cache, idx):
        calls.append((q.shape, k_cache.shape, idx.shape))
        k_cache, v_cache = slot_cache_write_reference(
            k_cache, v_cache, k_new, v_new, idx)
        s, L = q.shape[2], k_cache.shape[2]
        valid = (
            jnp.arange(L)[None, None, :]
            <= idx[:, None, None] + jnp.arange(s)[None, :, None]
        )[:, None, :, :]
        return attention(q, k_cache, v_cache, causal=False, mask=valid,
                         implementation="xla"), k_cache, v_cache

    return spy


def _slot_streams(model, variables, **server_kw):
    from ml_trainer_tpu.serving import Server

    prompts = [_prompt(s, n) for s, n in ((0, 5), (1, 3), (2, 12), (3, 7))]
    with Server(model, variables, max_batch=3, **server_kw) as server:
        streams = [server.submit(p, 9) for p in prompts]
        return [np.asarray(s.result(timeout=300)) for s in streams]


def test_slot_engine_reads_through_decode_attention_and_streams_the_same(
        model_and_vars, monkeypatch):
    """The slot engine's streams are byte for byte what the parent's
    expression gives (off the TPU the fused call's reference IS that
    expression), and ``_decode_step`` takes ``decode_attention_append`` only
    at one position a row with a per-row index: the speculative verify
    window and ``generate()``'s scalar index keep the scatter and the masked
    XLA attention."""
    from ml_trainer_tpu.generate import generate
    from ml_trainer_tpu.models import layers
    from ml_trainer_tpu.serving import engine

    model, variables = model_and_vars
    plain = _slot_streams(model, variables)
    calls, steps = [], []
    # the engines' programs are kept by model: trace them anew under the spy
    monkeypatch.setattr(engine, "_COMPILED", {})
    decode_step = layers.MultiHeadAttention._decode_step
    monkeypatch.setattr(layers, "decode_attention_append",
                        _parent_decode_step(calls))
    monkeypatch.setattr(
        layers.MultiHeadAttention, "_decode_step",
        lambda self, q, k, v: (steps.append(q.shape[2]),
                               decode_step(self, q, k, v))[1])
    for a, b in zip(plain, _slot_streams(model, variables)):
        np.testing.assert_array_equal(a, b)
    assert calls and all(
        q[2] == 1 and q[0] == 3 and lengths == (3,)
        for q, _, lengths in calls)
    assert all(k == (3, q[1], 64, q[3]) for q, k, _ in calls)

    # generate(): one position a step too, under a SCALAR index
    del calls[:], steps[:]
    tokens = generate(model, variables, _prompt(7, 6)[None], 5,
                      eos_token_id=None)
    assert tokens.shape == (1, 11) and 1 in steps and not calls

    # the verify window (s = spec_k + 1) goes round the kernel
    del calls[:], steps[:]
    for a, b in zip(plain, _slot_streams(model, variables, spec_k=3)):
        np.testing.assert_array_equal(a, b)
    assert 4 in steps and not calls


def test_slot_engine_streams_the_same_through_the_fused_call_as_the_pair(
        model_and_vars, monkeypatch):
    """The slot engine with the KERNELS behind its decode step (interpret
    mode; on the CPU 'auto' is the reference): the one call that appends
    while it reads streams token for token what the write kernel then the
    read kernel stream, and what the reference streams, four requests over
    three slots, so one is admitted into a freed slot whose index ran on
    while it was free."""
    import functools

    from ml_trainer_tpu.models import layers
    from ml_trainer_tpu.serving import engine

    model, variables = model_and_vars
    plain = _slot_streams(model, variables)
    kw = dict(implementation="pallas", interpret=True)

    def pair(q, k_new, v_new, k_cache, v_cache, idx):
        k_cache, v_cache = slot_cache_write(
            k_cache, v_cache, k_new, v_new, idx, **kw)
        return (decode_attention(q, k_cache, v_cache, idx + 1, **kw),
                k_cache, v_cache)

    for step in (pair, functools.partial(decode_attention_append, **kw)):
        # the engines' programs are kept by model: trace them anew
        monkeypatch.setattr(engine, "_COMPILED", {})
        monkeypatch.setattr(layers, "decode_attention_append", step)
        for a, b in zip(plain, _slot_streams(model, variables)):
            np.testing.assert_array_equal(a, b)


def test_server_paged_kernel_byte_identity(model_and_vars):
    """The fused-gather decode program streams the same bytes as the
    gather+flash program across ragged join/leave traffic, and its
    steady-state decode loop compiles nothing."""
    from ml_trainer_tpu.serving.engine import SlotDecodeEngine
    from ml_trainer_tpu.telemetry import compile_watch

    model, variables = model_and_vars
    base = _run_requests(model, variables, paged_kernel=False)
    paged = _run_requests(model, variables, paged_kernel=True)
    for a, b in zip(base, paged):
        np.testing.assert_array_equal(a, b)

    eng = SlotDecodeEngine(model, variables, max_batch=4,
                           kv_page_size=16, paged_kernel=True)
    cache, tok = eng.cache, eng.tok
    for _ in range(2):  # warmup builds the decode program
        cache, tok = eng._decode(
            eng.params, cache, tok, eng._temps, eng._rngs, eng._steps
        )
    jax.block_until_ready(tok)
    with compile_watch.expect_no_compiles("paged_kernel decode loop"):
        for _ in range(6):
            cache, tok = eng._decode(
                eng.params, cache, tok, eng._temps, eng._rngs,
                eng._steps,
            )
        jax.block_until_ready(tok)


def test_server_quant_int8_serves_deterministically(model_and_vars):
    """The int8 decode program is a different program (different bytes
    are fine — quantization changes the math) but a stable one: two
    identical runs stream identical bytes."""
    model, variables = model_and_vars
    a = _run_requests(model, variables, quant_int8=True)
    b = _run_requests(model, variables, quant_int8=True)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


def test_kernel_knob_refusals(model_and_vars):
    from ml_trainer_tpu.serving.engine import SlotDecodeEngine

    model, variables = model_and_vars
    with pytest.raises(ValueError, match="paged_kernel needs paged KV"):
        SlotDecodeEngine(model, variables, max_batch=2, paged_kernel=True)
    with pytest.raises(ValueError, match="spec_k"):
        SlotDecodeEngine(model, variables, max_batch=2, kv_page_size=16,
                         quant_int8=True, spec_k=2)
    with pytest.raises(ValueError, match="adapters"):
        SlotDecodeEngine(model, variables, max_batch=2, kv_page_size=16,
                         quant_int8=True, adapters=object())


def test_trainer_fused_adam_refusals(tmp_path):
    from ml_trainer_tpu import Trainer
    from ml_trainer_tpu.data import SyntheticTokens

    ds = SyntheticTokens(size=32, seq_len=32, vocab_size=256, seed=0)
    common = dict(datasets=(ds, ds), epochs=1, batch_size=16,
                  metric=None, backend="cpu")
    with pytest.raises(ValueError, match="dp_update='sharded'"):
        Trainer(get_model("gpt2_tiny", vocab_size=256),
                model_dir=str(tmp_path / "a"), fused_adam=True,
                optimizer="adam", **common)
    with pytest.raises(ValueError, match="optimizer='adam'"):
        Trainer(get_model("gpt2_tiny", vocab_size=256),
                model_dir=str(tmp_path / "b"), fused_adam=True,
                optimizer="adamw", is_parallel=True,
                dp_update="sharded", **common)
    with pytest.raises(ValueError, match="weight_decay"):
        Trainer(get_model("gpt2_tiny", vocab_size=256),
                model_dir=str(tmp_path / "c"), fused_adam=True,
                optimizer="adam", weight_decay=0.1, is_parallel=True,
                dp_update="sharded", **common)


def test_trainer_fused_adam_golden_and_checkpoint_roundtrip(tmp_path):
    """sharded+adam auto-enables the fused tail; the trajectory — every
    loss AND every param bit — is identical to the unfused optax tail,
    one compiled program, and the fused run's state round-trips through
    the v2 checkpoint format unchanged (opt_state layout untouched)."""
    from ml_trainer_tpu import Trainer
    from ml_trainer_tpu.checkpoint import checkpoint as ckpt
    from ml_trainer_tpu.data import SyntheticTokens

    ds = SyntheticTokens(size=64, seq_len=32, vocab_size=256, seed=0)
    common = dict(
        datasets=(ds, ds), epochs=2, batch_size=16, seed=3, lr=0.01,
        optimizer="adam", metric=None, is_parallel=True, backend="cpu",
        dp_update="sharded",
    )
    t_ref = Trainer(get_model("gpt2_tiny", vocab_size=256),
                    model_dir=str(tmp_path / "ref"), fused_adam=False,
                    **common)
    assert not t_ref.fused_adam
    t_ref.fit()
    t_fused = Trainer(get_model("gpt2_tiny", vocab_size=256),
                      model_dir=str(tmp_path / "fused"), **common)
    assert t_fused.fused_adam  # None -> auto: eligible config
    t_fused.fit()
    assert t_fused._train_step._cache_size() == 1
    assert t_ref.train_losses == t_fused.train_losses
    assert _bits_equal(t_ref.state.params, t_fused.state.params)
    assert _bits_equal(t_ref.state.opt_state, t_fused.state.opt_state)

    path = ckpt.save_checkpoint(
        str(tmp_path / "ckpt"), t_fused.state, {"train_loss": []}, epoch=2
    )
    restored, _, _ = ckpt.restore_checkpoint(path, t_ref.state)
    assert _bits_equal(t_fused.state.params, restored.params)
    assert _bits_equal(t_fused.state.opt_state, restored.opt_state)


def test_int8_quality_gate(tmp_path):
    """Argmax agreement >= 99.5% with bounded relative logit error on a
    model with real top-1 margins: gpt2_tiny memorizes a deterministic
    successor map in 4 epochs (random next-token targets leave logits
    near-tied, which measures tie-breaking, not the kernel)."""
    from ml_trainer_tpu import Trainer
    from ml_trainer_tpu.data.datasets import ArrayDataset

    rng = np.random.default_rng(0)
    V, S, N = 64, 32, 64
    succ = rng.permutation(V)
    data = np.zeros((N, S), np.int32)
    data[:, 0] = rng.integers(0, V, N)
    for t in range(1, S):
        data[:, t] = succ[data[:, t - 1]]
    model = get_model("gpt2_tiny", vocab_size=V)
    trainer = Trainer(
        model,
        datasets=(ArrayDataset(data, np.roll(data, -1, axis=1), None),) * 2,
        model_dir=str(tmp_path / "q"), epochs=4, batch_size=16, seed=3,
        lr=0.01, optimizer="adamw", metric=None, backend="cpu",
    )
    trainer.fit()
    params = trainer.state.params
    toks = jnp.asarray(data[:8])
    lf = model.apply({"params": params}, toks, train=False)
    lq = model.clone(quant_int8=True).apply(
        {"params": params, "quant": quantize_tree(params)}, toks,
        train=False,
    )
    agreement = float((jnp.argmax(lf, -1) == jnp.argmax(lq, -1)).mean())
    rel_err = float(jnp.max(jnp.abs(lf - lq)) / jnp.max(jnp.abs(lf)))
    assert agreement >= 0.995, f"argmax agreement {agreement}"
    assert rel_err <= 0.02, f"relative logit error {rel_err}"


# -- retention_state_step ---------------------------------------------------

def _retention_state_step_call(**kw):
    from ml_trainer_tpu.ops.kernels.retention_state_step import (
        retention_state_step,
    )

    return retention_state_step(
        *_retention_step_args(0, 1, 1, 5, 16, 8), **kw)


def _retention_step_args(seed, b, n, heads, d, d_v):
    from ml_trainer_tpu.ops.power_retention import phi_padded

    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    normal = lambda i, *shape: jax.random.normal(keys[i], shape)  # noqa: E731
    state = normal(4, b, n, d_v, phi_padded(d))
    # what lies under phi's padding is nought, and has to stay so
    state = state.at[..., phi_padded(d) - d // 2:].set(0.0)
    norm = jnp.abs(normal(5, b, n, phi_padded(d))).at[
        ..., phi_padded(d) - d // 2:].set(0.0)
    return (normal(0, b, n, heads, d), normal(1, b, n, d),
            normal(2, b, n, d_v), jax.random.uniform(keys[3], (b, n)),
            state, norm)


@pytest.mark.parametrize("b,n,heads,d,d_v", [
    (2, 2, 5, 128, 128),     # the published group: five query heads of 128
    (1, 3, 8, 128, 64),      # a full vreg of heads, two blocks of value rows
    (3, 1, 1, 16, 8),        # one head, a tiny state (interpret mode only)
], ids=["five-of-128", "eight-of-128", "one-of-16"])
def test_retention_state_step_is_the_two_xla_passes(b, n, heads, d, d_v):
    """One pass over the pool in place against XLA's read and update: the
    same four results, the sums over ``D`` in another order (lane by lane,
    then one reduction: 65 terms a lane of order 1 at heads of 128, read
    here to 3e-5 on reads of order 100); the padding under ``phi``'s last
    half row stays nought."""
    from ml_trainer_tpu.ops.kernels.retention_state_step import (
        retention_state_step,
    )
    from ml_trainer_tpu.ops.power_retention import phi_padded

    args = _retention_step_args(b + heads, b, n, heads, d, d_v)
    want = retention_state_step(*args, implementation="reference")
    got = retention_state_step(*args, implementation="pallas", interpret=True)
    for name, w, g in zip(("read", "z_read", "state", "norm"), want, got):
        assert g.shape == w.shape and g.dtype == jnp.float32, name
        scale = float(jnp.abs(w).max())
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), atol=2e-6 * scale, err_msg=name)
    pad = slice(phi_padded(d) - d // 2, None)
    assert not np.asarray(got[2])[..., pad].any()
    assert not np.asarray(got[3])[..., pad].any()


def test_retention_state_step_refusals():
    from ml_trainer_tpu.ops.kernels.retention_state_step import (
        retention_state_step,
    )

    args = _retention_step_args(0, 1, 1, 9, 16, 8)
    with pytest.raises(ValueError, match="at most 8"):
        retention_state_step(*args, implementation="pallas", interpret=True)
    with pytest.raises(ValueError, match="Unknown retention_state_step"):
        retention_state_step(*args, implementation="mosaic")
    with pytest.raises(ValueError, match="phi's 144 entries"):
        retention_state_step(*args[:4], args[4][..., :136], args[5])
    # off the TPU, or at heads the kernel does not take, 'auto' is XLA's
    want = retention_state_step(*args, implementation="reference")
    got = retention_state_step(*args)
    np.testing.assert_array_equal(np.asarray(got[2]), np.asarray(want[2]))
