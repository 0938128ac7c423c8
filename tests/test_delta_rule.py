"""``ops/delta_rule.py``: the chunked form of the gated delta rule against the
recurrence itself, a token at a time (a ``lax.scan`` of the one-token form,
which is the three lines of the module's docstring), float32 on the CPU.

Tolerance.  Both forms compute the same float32 sums in another order (a
triangular solve and products a chunk against a chain of rank-one updates):
5e-7 to 4e-6 read here on outputs and states of order 1; the limit is 2e-5
of the largest value.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ml_trainer_tpu.ops.delta_rule import (
    gated_delta_chunked,
    gated_delta_step,
)

CHUNK, SCALE = 8, 0.25
B, H, DK, DV = 2, 3, 16, 12
# Log-decays a token: from "forgets nothing" to "forgets everything", where
# the factored form (k exp G)(k exp -G)^T would overflow float32 inside one
# chunk (exp(8 x 20)).
DECAYS = {"near-one": (-1e-4, -1e-6), "mixed": (-3.0, -1e-3),
          "near-zero": (-20.0, -5.0)}


def inputs(t, decay, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(ks[0], (B, H, t, DK))
    k = jax.random.normal(ks[1], (B, H, t, DK))
    q, k = (x / jnp.linalg.norm(x, axis=-1, keepdims=True) for x in (q, k))
    v = jax.random.normal(ks[2], (B, H, t, DV))
    lo, hi = DECAYS[decay]
    g = jax.random.uniform(ks[3], (B, H, t, DK), minval=lo, maxval=hi)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, H, t)))
    state = jax.random.normal(ks[5], (B, H, DK, DV))
    return q, k, v, g, beta, state


def token_by_token(q, k, v, g, beta, state):
    def token(s, now):
        out, s = gated_delta_step(*now, s, scale=SCALE)
        return s, out

    state, out = jax.lax.scan(
        token, state, tuple(jnp.moveaxis(x, 2, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(out, 0, 2), state


def close(got, want):
    return float(jnp.abs(got - want).max()) <= 2e-5 * max(
        1.0, float(jnp.abs(want).max()))


@pytest.mark.parametrize("decay", list(DECAYS))
@pytest.mark.parametrize("t", [1, CHUNK - 1, CHUNK, CHUNK + 3, 3 * CHUNK])
def test_chunked_form_is_the_recurrence(t, decay):
    args = inputs(t, decay, seed=t)
    want_out, want_state = token_by_token(*args)
    out, state = gated_delta_chunked(*args, scale=SCALE, chunk=CHUNK)
    assert out.shape == want_out.shape == (B, H, t, DV)
    assert close(out, want_out) and close(state, want_state)
    assert np.isfinite(np.asarray(out)).all()


@pytest.mark.parametrize("decay", ["mixed", "near-zero"])
@pytest.mark.parametrize("t", [1, CHUNK - 1, CHUNK, CHUNK + 3, 3 * CHUNK])
def test_padding_past_the_true_length_changes_nothing(t, decay):
    """A prompt padded to a bucket of 32 with garbage (tokens the engine
    pads with, through every projection): the state that comes out is the
    state at the true length, the outputs before it are the unpadded
    run's, the chunks past it are never computed (zeros), and the true
    length is an input of ONE program."""
    args = inputs(t, decay, seed=100 + t)
    want_out, want_state = token_by_token(*args)
    garbage = inputs(32, decay, seed=7)
    padded = tuple(
        jnp.concatenate([x, junk[:, :, t:]], axis=2)
        for x, junk in zip(args[:5], garbage[:5])) + (args[5],)
    run = jax.jit(lambda *a: gated_delta_chunked(
        *a[:-1], scale=SCALE, chunk=CHUNK, true_len=a[-1]))
    out, state = run(*padded, jnp.int32(t))
    assert close(out[:, :, :t], want_out) and close(state, want_state)
    computed = -(-t // CHUNK) * CHUNK
    assert not np.asarray(out[:, :, computed:]).any()
    if t > 1:
        again, _ = run(*padded, jnp.int32(t - 1))
        assert run._cache_size() == 1
        assert close(again[:, :, :t - 1], want_out[:, :, :t - 1])


def test_one_token_form_is_the_three_lines():
    """``S' = Diag(alpha) S; S_t = S' + beta k (v - S'^T k)^T; o = scale
    S_t^T q``, written out, against the form that reads the state once."""
    q, k, v, g, beta, state = (
        x[:, :, 0] if i < 5 else x
        for i, x in enumerate(inputs(1, "mixed", seed=3)))
    decayed = jnp.exp(g)[..., None] * state
    read = jnp.einsum("bhkv,bhk->bhv", decayed, k)
    want = decayed + (beta[..., None, None] * k[..., None]
                      * (v - read)[..., None, :])
    want_out = SCALE * jnp.einsum("bhkv,bhk->bhv", want, q)
    out, new = gated_delta_step(q, k, v, g, beta, state, scale=SCALE)
    assert close(new, want) and close(out, want_out)
