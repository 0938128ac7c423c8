"""The gated delta rule with a decay a CHANNEL of the key (Kimi Delta
Attention's recurrence, arXiv:2510.26692), in the two forms a server needs.

One head holds a state ``S`` (``d_k x d_v``, float32).  A token brings a
query ``q`` and a key ``k`` (``d_k``), a value ``v`` (``d_v``), a log-decay
``g <= 0`` a channel of the key (``alpha = exp(g)``) and a write strength
``beta`` in [0, 1]:

    S'  = Diag(alpha) S                       (forget, a channel at its rate)
    S_t = S' + beta k (v - S'^T k)^T          (correct what k reads to v)
    o_t = scale * S_t^T q

* ``gated_delta_step``: one token of every row, the state replaced.  Stated
  so that the state is read twice and written once whatever the compiler
  does: ``S'^T k = S^T (alpha k)`` and ``S_t^T q = S^T (alpha q) + (k.q) u``
  with ``u = beta (v - S'^T k)``, so both reductions read ``S`` as it
  arrived, in one pass, and the second pass writes ``alpha S + k u^T``.
* ``gated_delta_chunked``: a prompt, in chunks of ``chunk`` tokens.  Inside
  a chunk, with ``G_i`` the log-decay cumulated from the chunk's start, the
  corrections obey ``(I + A) U = beta (V - (K exp G) S_0)`` where ``A_ij =
  beta_i sum_c k_ic k_jc exp(G_ic - G_jc)`` for ``i > j`` (never an
  overflow: ``G_i <= G_j`` there) and 0 elsewhere, so ``U`` comes from one
  triangular solve; then ``o_i = scale ((q_i exp G_i) S_0 + sum_{j<=i} P_ij
  u_j)`` with ``P_ij = sum_c q_ic k_jc exp(G_ic - G_jc)``, and the state a
  chunk hands on is ``Diag(exp G_C) S_0 + (K exp(G_C - G))^T U``.  The
  pairwise ``exp(G_i - G_j)`` is taken as written, a ``[chunk, chunk, d_k]``
  term that XLA reduces where it makes it: the factored form ``(k exp G)
  (k exp -G)^T`` overflows float32 under a strong decay.

  ``true_len``: the positions at or past it are padding (the engine pads a
  prompt to a bucket).  They get ``beta = 0`` and ``g = 0``, an identity
  update, so the state returned is the state AT the true length; the loop
  runs over the chunks that hold a true position and no further (a traced
  trip count: one program a bucket, whatever the length), and the outputs
  of the chunks it never reaches are zeros.

Everything here is float32 ``jax.numpy``/``lax`` with products at
``Precision.HIGHEST``; nothing runs at import.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def gated_delta_step(q, k, v, g, beta, state, *, scale: float):
    """One token a row.  q, k, g: ``[B, H, d_k]``; v: ``[B, H, d_v]``; beta:
    ``[B, H]``; state: ``[B, H, d_k, d_v]`` float32.  Returns ``(o [B, H,
    d_v], new state)``, float32."""
    q, k, v, g, beta = (t.astype(jnp.float32) for t in (q, k, v, g, beta))
    alpha = jnp.exp(g)
    # One pass over the state as it arrived: what the decayed state reads
    # at k and at q.
    read_k = jnp.sum(state * (alpha * k)[..., None], axis=-2)
    read_q = jnp.sum(state * (alpha * q)[..., None], axis=-2)
    u = beta[..., None] * (v - read_k)
    new = alpha[..., None] * state + k[..., None] * u[..., None, :]
    o = read_q + jnp.sum(q * k, axis=-1, keepdims=True) * u
    return scale * o, new


def _chunk(q, k, v, g, beta, state, scale):
    """One chunk.  q, k, g: ``[B, H, C, d_k]``; v: ``[B, H, C, d_v]``; beta:
    ``[B, H, C]``; state ``[B, H, d_k, d_v]``."""
    c = q.shape[2]
    cum = jnp.cumsum(g, axis=2)                            # G_i
    i, j = jnp.arange(c)[:, None], jnp.arange(c)[None, :]
    # exp(G_i - G_j) where i >= j, 0 above the diagonal: masked BEFORE the
    # exponential, whose argument is positive there.
    decay = jnp.exp(jnp.where(
        (i >= j)[:, :, None], cum[:, :, :, None, :] - cum[:, :, None, :, :],
        -jnp.inf))                                         # [B, H, C, C, d_k]
    k_decayed = k[:, :, None, :, :] * decay
    a = jnp.sum(k[:, :, :, None, :] * k_decayed, axis=-1)  # [B, H, C, C]
    p = jnp.sum(q[:, :, :, None, :] * k_decayed, axis=-1)
    a = jnp.where(i > j, a * beta[..., None], 0.0)
    rhs = jnp.concatenate(
        [beta[..., None] * k * jnp.exp(cum), beta[..., None] * v], axis=-1)
    solved = jax.scipy.linalg.solve_triangular(
        a + jnp.eye(c, dtype=a.dtype), rhs, lower=True, unit_diagonal=True)
    w, u = solved[..., :k.shape[-1]], solved[..., k.shape[-1]:]
    u = u - jnp.einsum("bhck,bhkv->bhcv", w, state, precision=HIGHEST)
    o = jnp.einsum("bhck,bhkv->bhcv", q * jnp.exp(cum), state,
                   precision=HIGHEST)
    o = o + jnp.einsum("bhij,bhjv->bhiv", p, u, precision=HIGHEST)
    last = cum[:, :, -1:, :]                               # G_C
    new = jnp.exp(last)[:, :, 0, :, None] * state + jnp.einsum(
        "bhck,bhcv->bhkv", k * jnp.exp(last - cum), u, precision=HIGHEST)
    return scale * o, new


def gated_delta_chunked(q, k, v, g, beta, state, *, scale: float,
                        true_len=None, chunk: int = 64):
    """A sequence from ``state``.  q, k, g: ``[B, H, T, d_k]``; v: ``[B, H,
    T, d_v]``; beta: ``[B, H, T]``; state ``[B, H, d_k, d_v]`` float32;
    ``true_len``: a scalar, the positions from it on are padding (None: all
    ``T`` are true).  Returns ``(o [B, H, T, d_v], state at true_len)``,
    float32.  See the module docstring."""
    q, k, v, g, beta = (t.astype(jnp.float32) for t in (q, k, v, g, beta))
    b, h, t, _ = q.shape
    n = -(-t // chunk)
    if true_len is None:
        true_len = t
    true = jnp.arange(n * chunk) < true_len

    def chunks(x, keep):
        """``[B, H, T, ...]`` -> ``[N, B, H, chunk, ...]``, padding and the
        positions past ``true_len`` zeroed where ``keep`` says so."""
        x = jnp.pad(x, ((0, 0), (0, 0), (0, n * chunk - t))
                    + ((0, 0),) * (x.ndim - 3))
        if keep:
            x = jnp.where(true.reshape((-1,) + (1,) * (x.ndim - 3)), x, 0.0)
        x = x.reshape(b, h, n, chunk, *x.shape[3:])
        return jnp.moveaxis(x, 2, 0)

    parts = (chunks(q, False), chunks(k, False), chunks(v, False),
             chunks(g, True), chunks(beta, True))

    def body(at, carry):
        state, out = carry
        o, state = _chunk(*(jax.lax.dynamic_index_in_dim(
            x, at, axis=0, keepdims=False) for x in parts), state, scale)
        return state, jax.lax.dynamic_update_index_in_dim(out, o, at, axis=0)

    live = jnp.minimum(-(-jnp.asarray(true_len, jnp.int32) // chunk), n)
    state, out = jax.lax.fori_loop(
        0, live, body,
        (state.astype(jnp.float32),
         jnp.zeros((n, b, h, chunk, v.shape[-1]), jnp.float32)))
    out = jnp.moveaxis(out, 0, 2).reshape(b, h, n * chunk, v.shape[-1])
    return out[:, :, :t], state
