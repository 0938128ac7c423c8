"""Power retention with a gate ("Scaling Context Requires Rethinking
Attention", arXiv:2507.04239), power 2, in the two forms a server needs.

A key-value head holds a state ``S`` (``D`` by ``d_v``, float32) and a
normaliser ``z`` (``D``), ``D = d (d + 1) / 2`` for keys of ``d`` values.  A
token brings a key ``k`` and a value ``v``, a log-gate ``log g <= 0`` (one
scalar a key-value head) and the queries ``q_h`` of the ``R`` query heads of
the head's group:

    S_t = g_t S_t-1 + phi(k_t) v_t^T          z_t = g_t z_t-1 + phi(k_t)
    o_h,t = S_t^T phi(q_h,t) / (z_t . phi(q_h,t) + eps)

``phi`` is the symmetric second power: ``phi(a) . phi(b) = (a . b)^2 / d``,
so the layer is attention with the weights ``(q . k)^2 / d`` times the gates
between the two tokens in the softmax's place, divided by their sum.

* ``phi``: the ``d`` squares and the ``d (d - 1) / 2`` products ``sqrt(2)
  a_i a_j`` as ``d / 2 + 1`` ROWS of ``d`` lanes, no gather: entry ``r d +
  i`` is ``c_r a_i a_(i + r) mod d`` (``c_0 = 1``, else ``sqrt 2``, all over
  ``sqrt d``); of the last row the second half is ZERO (a pair at distance
  ``d / 2`` would come twice), so ``phi`` has ``phi_padded(d) = (d / 2 + 1)
  d`` entries of which ``phi_dim(d)`` are not nought: 8,320 and 8,256 at
  ``d = 128``, 65 whole rows of 128 lanes.  ``q`` and ``k`` share the order.
* THE STATE'S LAYOUT is ``[B, G, d_v, phi_padded]``: ``phi``'s entries on
  the LANES, so that a row of ``phi(q)`` multiplies a tile of the state as
  it lies and the sum over ``D`` is taken lane by lane and reduced once
  (``ops/kernels/retention_state_step.py``); 0.8% of it is the padding.
* ``retention_step``: one token of every row.  Stated so that the pool is
  read as it arrived and written once: ``o = (g S^T phi(q) + (phi(q) .
  phi(k)) v) / (g z . phi(q) + phi(q) . phi(k) + eps)`` reads ``S`` and ``z``
  BEFORE the update, and ``g S + phi(k) v^T`` writes them; the ``R`` query
  heads of a group read the one ``S``.  ``phi(q) . phi(k)`` is taken as ``(q
  . k)^2 / d``.  The pass over the pool is ``state_step``: XLA's here
  (``state_step_reference``: a fusion that reads the pool and one that reads
  and writes it), the kernel's where the caller hands it in.
* ``retention_chunked``: a prompt, in chunks.  Inside a chunk the layer is
  the quadratic form, scores ``(q_t . k_s)^2 / d`` times ``exp(G_t - G_s)``
  (``G`` the log-gate cumulated from the chunk's start; never an overflow:
  ``s <= t``), a block of queries at a time, ``phi`` not built; across
  chunks ``exp(G_t) phi(q_t)`` reads the carried ``S_0``, ``z_0``, a row of
  ``phi`` at a time, and the chunk hands on ``exp(G_C) S_0 + sum_s exp(G_C -
  G_s) phi(k_s) v_s^T``, built a row of ``phi`` at a time (one product of
  ``[d_v, C] x [C, d]`` each).  By count a token inside a chunk of ``C``
  costs ``4 C d`` a query head against ``2 D d`` for reading a carried
  state: equal at ``C = D / 2``.  ``state=None`` is a FRESH slot: the first
  chunk reads nothing carried.

  ``true_len``: the positions at or past it are padding (the engine pads a
  prompt to a bucket).  They get ``g = 1`` and a zero key and value, an
  identity update, so the state returned is the state AT the true length;
  the loop runs over the chunks that hold a true position and no further
  (a traced trip count: one program a bucket), and the outputs of the
  chunks it never reaches are zeros.

Everything here is float32 ``jax.numpy``/``lax`` with products at
``Precision.HIGHEST``; nothing runs at import.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
EPS = 1e-6


def phi_dim(d: int) -> int:
    """The entries of ``phi`` that are not nought by construction."""
    return d * (d + 1) // 2


def phi_padded(d: int) -> int:
    """``phi``'s length: whole rows of ``d``."""
    return (d // 2 + 1) * d


def _coef(r, d: int):
    """``c_r / sqrt d`` of row ``r`` of ``phi``, by lane: nought on the
    second half of the last row."""
    lane = jnp.arange(d)
    r = jnp.asarray(r)[..., None]
    return jnp.where(
        (r == d // 2) & (lane >= d // 2), 0.0,
        jnp.where(r == 0, 1.0, math.sqrt(2.0)) * d ** -0.5)


def _phi_row(a, doubled, r):
    """Row ``r`` of ``phi(a)`` (``d`` lanes), ``r`` traced; ``doubled`` is
    ``[a; a]``."""
    d = a.shape[-1]
    return _coef(r, d) * a * jax.lax.dynamic_slice_in_dim(
        doubled, r, d, axis=-1)


def phi(a):
    """``[..., d]`` -> ``[..., phi_padded(d)]``, float32 (``d`` even)."""
    d = a.shape[-1]
    if d % 2:
        raise ValueError(f"phi wants an even head size, got {d}")
    a = a.astype(jnp.float32)
    # Row r is ``a`` turned by r lanes: ``a`` laid end to end and read in
    # rows of d + 1 (entry r (d + 1) + i is a_(i + r) mod d): broadcasts
    # and reshapes, which XLA fuses, where a stack of slices is an
    # operation a row.
    n, lead = d // 2 + 1, a.shape[:-1]
    copies = -(-n * (d + 1) // d)
    turned = jnp.broadcast_to(a[..., None, :], (*lead, copies, d)).reshape(
        *lead, copies * d)[..., :n * (d + 1)].reshape(*lead, n, d + 1)
    rows = turned[..., :d] * a[..., None, :] * _coef(jnp.arange(n), d)
    return rows.reshape(*lead, n * d)


def state_step_reference(q, k, v, g, state, norm):
    """The pass over the pool, in XLA.  q: ``[B, G, R, d]``; k: ``[B, G,
    d]``; v: ``[B, G, d_v]``; g: ``[B, G]`` (the gate itself); state: ``[B,
    G, d_v, P]`` and norm: ``[B, G, P]``, float32.  Returns what the state
    and the normaliser AS THEY ARRIVED read at ``phi(q)`` (``[B, G, R,
    d_v]``, ``[B, G, R]``) and both after ``g . + phi(k) v^T``."""
    pq, pk = phi(q), phi(k)
    read = jnp.sum(state[:, :, None] * pq[:, :, :, None, :], axis=-1)
    z_read = jnp.sum(norm[:, :, None] * pq, axis=-1)
    state = g[..., None, None] * state + v[..., None] * pk[:, :, None, :]
    norm = g[..., None] * norm + pk
    return read, z_read, state, norm


def retention_step(q, k, v, log_g, state, norm, *,
                   state_step=state_step_reference):
    """One token a row.  q: ``[B, G, R, d]``; k: ``[B, G, d]``; v: ``[B, G,
    d_v]``; log_g: ``[B, G]``; state: ``[B, G, d_v, P]`` and norm: ``[B, G,
    P]``, float32.  Returns ``(o [B, G, R, d_v], state, norm, den [B, G,
    R])``, ``den`` the divisor before ``eps``."""
    q, k, v, log_g = (t.astype(jnp.float32) for t in (q, k, v, log_g))
    g = jnp.exp(log_g)
    read, z_read, state, norm = state_step(q, k, v, g, state, norm)
    qk = jnp.sum(q * k[:, :, None], axis=-1) ** 2 / q.shape[-1]
    den = g[..., None] * z_read + qk
    o = ((g[..., None, None] * read + qk[..., None] * v[:, :, None])
         / (den[..., None] + EPS))
    return o, state, norm, den


def _chunk(q, k, v, log_g, state, norm, q_block: int):
    """One chunk.  q: ``[B, G, R, C, d]``; k: ``[B, G, C, d]``; v: ``[B, G,
    C, d_v]``; log_g: ``[B, G, C]``; ``state``/``norm`` None for a fresh
    slot."""
    b, g, r, c, d = q.shape
    rows = d // 2 + 1
    cum = jnp.cumsum(log_g, axis=-1)                          # G_t
    at = jnp.arange(c)

    def scores(block):
        """A block of queries against the chunk's keys."""
        q_b, cum_b, at_b = block
        s = jnp.einsum("bgrqd,bgcd->bgrqc", q_b, k, precision=HIGHEST)
        # masked BEFORE the exponential, whose argument is positive there
        decay = jnp.exp(jnp.where(
            at_b[:, None] >= at[None, :],
            cum_b[..., :, None] - cum[..., None, :], -jnp.inf))
        w = s * s * decay[:, :, None] / d
        return (jnp.einsum("bgrqc,bgcv->bgrqv", w, v, precision=HIGHEST),
                jnp.sum(w, axis=-1))

    n = -(-c // q_block)
    over = n * q_block - c         # queries past the chunk: cut off below
    num, den = jax.lax.map(scores, (
        jnp.moveaxis(jnp.pad(q, ((0, 0),) * 3 + ((0, over), (0, 0))).reshape(
            b, g, r, n, q_block, d), 3, 0),
        jnp.moveaxis(jnp.pad(cum, ((0, 0), (0, 0), (0, over))).reshape(
            b, g, n, q_block), 2, 0),
        jnp.arange(n * q_block).reshape(n, q_block)))
    num = jnp.moveaxis(num, 0, 3).reshape(
        b, g, r, n * q_block, -1)[:, :, :, :c]
    den = jnp.moveaxis(den, 0, 3).reshape(b, g, r, n * q_block)[:, :, :, :c]

    if state is not None:
        # exp(G_t) phi(q_t) reads the carried state, a row of phi at a time.
        s_rows = state.reshape(*state.shape[:3], rows, d)
        z_rows = norm.reshape(*norm.shape[:2], rows, d)
        doubled = jnp.concatenate([q, q], axis=-1)

        def read(i, acc):
            pq = _phi_row(q, doubled, i)
            pick = lambda x: jax.lax.dynamic_index_in_dim(  # noqa: E731
                x, i, axis=-2, keepdims=False)
            return (acc[0] + jnp.einsum("bgrcd,bgvd->bgrcv", pq, pick(s_rows),
                                        precision=HIGHEST),
                    acc[1] + jnp.einsum("bgrcd,bgd->bgrc", pq, pick(z_rows),
                                        precision=HIGHEST))

        carried = jax.lax.fori_loop(
            0, rows, read, (jnp.zeros_like(num), jnp.zeros_like(den)))
        since = jnp.exp(cum)[:, :, None]
        num = num + since[..., None] * carried[0]
        den = den + since * carried[1]

    last = cum[..., -1:]                                      # G_C
    k_left = k * jnp.exp(last - cum)[..., None]
    doubled = jnp.concatenate([k, k], axis=-1)

    def build(i):
        # exp(G_C - G_s) phi(k_s): the weight once, on one factor of the pair
        pk = _phi_row(k_left, doubled, i)
        return (jnp.einsum("bgcv,bgcd->bgvd", v, pk, precision=HIGHEST),
                jnp.sum(pk, axis=2))

    s_add, z_add = jax.lax.map(build, jnp.arange(rows))
    s_add = jnp.moveaxis(s_add, 0, 3).reshape(b, g, -1, rows * d)
    z_add = jnp.moveaxis(z_add, 0, 2).reshape(b, g, rows * d)
    if state is not None:
        kept = jnp.exp(last)
        s_add = kept[..., None] * state + s_add
        z_add = kept * norm + z_add
    return num / (den[..., None] + EPS), s_add, z_add


def retention_chunked(q, k, v, log_g, state=None, norm=None, *,
                      true_len=None, chunk: int = 2048, q_block: int = 512):
    """A sequence.  q: ``[B, G, R, T, d]``; k: ``[B, G, T, d]``; v: ``[B, G,
    T, d_v]``; log_g: ``[B, G, T]``; ``state [B, G, d_v, P]`` and ``norm [B,
    G, P]`` float32 (``P = phi_padded(d)``), or both None for a fresh slot;
    ``true_len``: a scalar, the positions from it on are padding (None: all
    ``T`` are true).
    Returns ``(o [B, G, R, T, d_v], state, norm)`` at ``true_len``,
    float32.  See the module docstring."""
    q, k, v, log_g = (t.astype(jnp.float32) for t in (q, k, v, log_g))
    b, g, r, t, d = q.shape
    chunk = min(chunk, t)
    q_block = min(q_block, chunk)
    n = -(-t // chunk)
    if true_len is None:
        true_len = t
    true = jnp.arange(n * chunk) < true_len

    def chunks(x, axis, keep):
        """The sequence axis -> ``[N, ..., chunk, ...]``, padding and the
        positions past ``true_len`` zeroed where ``keep`` says so."""
        pad = [(0, 0)] * x.ndim
        pad[axis] = (0, n * chunk - t)
        x = jnp.pad(x, pad)
        if keep:
            x = jnp.where(
                true.reshape((-1,) + (1,) * (x.ndim - axis - 1)), x, 0.0)
        x = x.reshape(*x.shape[:axis], n, chunk, *x.shape[axis + 1:])
        return jnp.moveaxis(x, axis, 0)

    parts = (chunks(q, 3, False), chunks(k, 2, True), chunks(v, 2, True),
             chunks(log_g, 2, True))
    first, state, norm = _chunk(
        *(x[0] for x in parts), state, norm, q_block)
    if n == 1:
        return first[:, :, :, :t], state, norm

    def body(at, carry):
        state, norm, out = carry
        o, state, norm = _chunk(*(jax.lax.dynamic_index_in_dim(
            x, at, axis=0, keepdims=False) for x in parts),
            state, norm, q_block)
        return state, norm, jax.lax.dynamic_update_index_in_dim(
            out, o, at, axis=0)

    live = jnp.minimum(-(-jnp.asarray(true_len, jnp.int32) // chunk), n)
    out = jnp.zeros((n,) + first.shape, jnp.float32).at[0].set(first)
    state, norm, out = jax.lax.fori_loop(1, live, body, (state, norm, out))
    out = jnp.moveaxis(out, 0, 3).reshape(b, g, r, n * chunk, -1)
    return out[:, :, :, :t], state, norm
