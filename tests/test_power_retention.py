"""``ops/power_retention.py``: ``phi``, the decode step and the prompt's
chunked form against the layer's quadratic statement (attention with ``(q .
k)^2 / d`` times the gates between two tokens for weights, divided by their
sum), float32 on the CPU.  Tolerances are float32 sums in another order.  An
output is a ratio of two sums of up to 40 weights ``(q . k)^2 / d``; where
``q . k`` cancels to a hundredth of its terms the weight keeps five digits,
and a divisor made of such weights passes that on: 1.5e-5 at the worst of
6,400 outputs of order 1 read here, the limit 3e-5.  The states are sums of
as many rank-one terms of order 1 to 10: 3e-5."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ml_trainer_tpu.ops.power_retention import (
    EPS,
    phi,
    phi_dim,
    phi_padded,
    retention_chunked,
    retention_step,
)

B, G, R, T, D_K, D_V = 2, 2, 5, 40, 16, 8
TOL = 3e-5


def draw(seed, t=T, gate=(0.5, 0.999)):
    """q ``[B, G, R, T, d]``, k, v, and log-gates with ``g`` uniform in
    ``gate``."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(keys[0], (B, G, R, t, D_K))
    k = jax.random.normal(keys[1], (B, G, t, D_K))
    v = jax.random.normal(keys[2], (B, G, t, D_V))
    g = jax.random.uniform(keys[3], (B, G, t), minval=gate[0], maxval=gate[1])
    return q, k, v, jnp.log(g)


def quadratic(q, k, v, log_g):
    """The layer as attention without a softmax, float64 on the host."""
    q, k, v, log_g = (np.asarray(x, np.float64) for x in (q, k, v, log_g))
    t = q.shape[3]
    cum = np.cumsum(log_g, axis=-1)
    s = np.einsum("bgrtd,bgsd->bgrts", q, k) ** 2 / q.shape[-1]
    seen = np.arange(t)[:, None] >= np.arange(t)[None, :]
    w = np.where(seen, s * np.exp(np.where(
        seen, cum[..., :, None] - cum[..., None, :], 0.0))[:, :, None], 0.0)
    return (np.einsum("bgrts,bgsv->bgrtv", w, v)
            / (w.sum(-1, keepdims=True) + EPS))


def by_steps(q, k, v, log_g, upto=None):
    d = q.shape[-1]
    state = jnp.zeros((B, G, v.shape[-1], phi_padded(d)))
    norm = jnp.zeros((B, G, phi_padded(d)))
    out = []
    for t in range(upto or q.shape[3]):
        o, state, norm, den = retention_step(
            q[:, :, :, t], k[:, :, t], v[:, :, t], log_g[:, :, t], state,
            norm)
        assert (np.asarray(den) >= 0).all()
        out.append(o)
    return jnp.stack(out, axis=3), state, norm


@pytest.mark.parametrize("d", [16, 128])
def test_phi_is_the_symmetric_second_power(d):
    a, b = jax.random.normal(jax.random.PRNGKey(d), (2, 3, d))
    assert phi(a).shape == (3, phi_padded(d))
    assert (phi_dim(128), phi_padded(128)) == (8256, 8320)
    # whole rows of d lanes: the second half of the last row is nought
    assert np.count_nonzero(np.asarray(phi(a))) == 3 * phi_dim(d)
    assert not np.asarray(phi(a))[:, phi_padded(d) - d // 2:].any()
    got = np.asarray(jnp.sum(phi(a) * phi(b), axis=-1), np.float64)
    want = np.asarray(jnp.sum(a * b, axis=-1), np.float64) ** 2 / d
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-6)
    # every unordered pair once: the squares, then sqrt 2 the products
    one_hot = np.asarray(phi(jnp.eye(d)[3] + jnp.eye(d)[7])) * d ** 0.5
    np.testing.assert_allclose(
        sorted(one_hot[one_hot != 0]), [1.0, 1.0, 2 ** 0.5], rtol=1e-6)


@pytest.mark.parametrize("chunk", [4, 8, 16, 40, 64])
def test_steps_chunks_and_the_quadratic_form_agree(chunk):
    q, k, v, log_g = draw(chunk)
    want = quadratic(q, k, v, log_g)
    stepped, state, norm = by_steps(q, k, v, log_g)
    np.testing.assert_allclose(np.asarray(stepped), want, atol=TOL)
    out, s, z = retention_chunked(q, k, v, log_g, chunk=chunk, q_block=8)
    np.testing.assert_allclose(np.asarray(out), want, atol=TOL)
    np.testing.assert_allclose(np.asarray(s), np.asarray(state), atol=TOL)
    np.testing.assert_allclose(np.asarray(z), np.asarray(norm), atol=TOL)


@pytest.mark.parametrize("true_len", [1, 7, 8, 9, 31, 40])
def test_padding_past_the_true_length_is_an_identity_update(true_len):
    q, k, v, log_g = draw(3)
    want, state, norm = by_steps(q, k, v, log_g, upto=true_len)
    # what lies past the true length is anything at all
    junk = lambda x, axis: jnp.where(  # noqa: E731
        (jnp.arange(T) < true_len).reshape(
            (-1,) + (1,) * (x.ndim - axis - 1)), x, 1e4)
    out, s, z = jax.jit(
        lambda n: retention_chunked(
            junk(q, 3), junk(k, 2), junk(v, 2), junk(log_g, 2),
            true_len=n, chunk=8))(true_len)
    np.testing.assert_allclose(
        np.asarray(out)[:, :, :, :true_len], np.asarray(want), atol=TOL)
    np.testing.assert_allclose(np.asarray(s), np.asarray(state), atol=TOL)
    np.testing.assert_allclose(np.asarray(z), np.asarray(norm), atol=TOL)
    # the chunks the loop never reaches come out as zeros
    reached = -(-true_len // 8) * 8
    assert not np.asarray(out)[:, :, :, reached:].any()


def test_a_carried_state_is_read_and_handed_on():
    """A prompt continued from the state its first half left: the second
    half's outputs and the state after it are the whole prompt's."""
    q, k, v, log_g = draw(5)
    whole, state, norm = retention_chunked(q, k, v, log_g, chunk=8)
    _, s0, z0 = retention_chunked(
        q[:, :, :, :24], k[:, :, :24], v[:, :, :24], log_g[:, :, :24],
        chunk=8)
    out, s, z = retention_chunked(
        q[:, :, :, 24:], k[:, :, 24:], v[:, :, 24:], log_g[:, :, 24:],
        s0, z0, chunk=16)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(whole)[:, :, :, 24:], atol=TOL)
    np.testing.assert_allclose(np.asarray(s), np.asarray(state), atol=TOL)
    np.testing.assert_allclose(np.asarray(z), np.asarray(norm), atol=TOL)


@pytest.mark.parametrize("gate", [(1e-6, 1e-5), (0.999999, 1.0)],
                         ids=["near-0", "near-1"])
def test_gates_at_both_ends(gate):
    """A gate near 0 forgets everything but the newest token (the output is
    its value, whatever the query); a gate of 1 is the plain sum.  Neither
    overflows in the chunked form."""
    q, k, v, log_g = draw(9, gate=gate)
    want = quadratic(q, k, v, log_g)
    out, s, z = retention_chunked(q, k, v, log_g, chunk=8)
    stepped, state, _ = by_steps(q, k, v, log_g)
    assert np.isfinite(np.asarray(s)).all()
    assert np.isfinite(np.asarray(z)).all()
    np.testing.assert_allclose(np.asarray(out), want, atol=TOL)
    np.testing.assert_allclose(np.asarray(stepped), want, atol=TOL)
    np.testing.assert_allclose(np.asarray(s), np.asarray(state), atol=TOL)
    if gate[1] < 0.5:
        # where the newest token's own weight is not itself near nothing
        own = np.einsum("bgrtd,bgtd->bgrt", q, k) ** 2 / D_K
        newest = np.broadcast_to(np.asarray(v)[:, :, None], want.shape)
        heavy = own > 0.1
        assert heavy.mean() > 0.5
        np.testing.assert_allclose(want[heavy], newest[heavy], atol=1e-3)


def test_the_query_heads_of_a_group_read_one_state():
    """The update does not see the queries, and each of the five heads reads
    what it would read alone."""
    q, k, v, log_g = draw(11, t=6)
    _, state, norm = by_steps(q, k, v, log_g, upto=5)
    args = (k[:, :, 5], v[:, :, 5], log_g[:, :, 5], state, norm)
    o, s, z, den = retention_step(q[:, :, :, 5], *args)
    assert o.shape == (B, G, R, D_V) and den.shape == (B, G, R)
    assert s.shape == (B, G, D_V, phi_padded(D_K))
    for h in range(R):
        alone, s_h, z_h, _ = retention_step(q[:, :, h:h + 1, 5], *args)
        np.testing.assert_allclose(
            np.asarray(alone[:, :, 0]), np.asarray(o[:, :, h]), atol=1e-6)
        np.testing.assert_array_equal(np.asarray(s_h), np.asarray(s))
        np.testing.assert_array_equal(np.asarray(z_h), np.asarray(z))
