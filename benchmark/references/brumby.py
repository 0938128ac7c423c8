"""The plain reference of one architecture: ``brumby`` (Brumby-14B-Base;
power retention, "Scaling Context Requires Rethinking Attention",
arXiv:2507.04239) in float32 ``jax.numpy`` under ``Precision.HIGHEST``.

Keeps the contract at the top of ``benchmark/references/gpt2.py``
(``sizes_of``, ``make_weights``, ``served_token_gaps``; served only), imports
nothing from ``ml_trainer_tpu`` and takes nothing the program has made.  The
norm, the rotation, the gated feed-forward in blocks, the head and the gaps
are the ones ``references/exaone_moe.py`` states, imported from there and
not restated.

The equations, one layer, token ``x_t`` (``~x = RMSNorm(x)``, learned scale,
``eps``; no bias anywhere; the model's ``config.json`` gives every width and
nothing of the layer beyond the heads: what it does not say is ``assumed``
in the configuration's file and marked (A) here):

* (A) pre-norm: ``h = x + Attn(~x)``, ``x' = h + FFN(~h)``.
* ``q = RoPE_t(RMSNorm(Wq ~x))`` a query head, ``k = RoPE_t(RMSNorm(Wk
  ~x))`` and ``v = Wv ~x`` a key-value head ((A) the family's query-key norm
  a head and rotate-half rotation at ``rope_theta``, kept).
* (A) ``log g_t = log sigmoid(wg_j . ~x_t)``: one scalar a key-value head
  ``j`` a token, float32.
* the QUADRATIC form of the retention, power 2 (A): query head ``h`` of
  group ``j`` at token ``t`` weighs every ``s <= t`` by ``w_ts = (q_h,t .
  k_j,s)^2 / d x prod_{r = s+1 .. t} g_j,r`` and ``o_h,t = sum_s w_ts v_j,s
  / (sum_s w_ts + 1e-6)``: attention without a softmax.  No state, no
  ``phi``: what the program carries from token to token (``S``, ``z``) is
  this sum factored, and its chunked and one-token forms are the program's
  own.  A key-value group and a block of queries at a time, so that the
  weights stay ``[5, block, tokens]`` beside the configuration's weights.
* ``Attn = Wo concat_h(o_h)``; ``FFN = Wd (silu(Wg ~h) * Wu ~h)``.
* after the last layer RMSNorm and the untied head.

Every weight product goes through one ``mm`` (``benchmark/reference.py``):
the reference's, or the control's, which rounds both operands of every
block's weight products (the gate's too) to float8.  The head and the
retention's own products stay at the reference's precision.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import MM
from benchmark.references.exaone_moe import (_gap_rows, _hashable, _rms,
                                             _rotate_half, embed, gated_ffn,
                                             head)

HIGHEST = jax.lax.Precision.HIGHEST
RETENTION_EPS = 1e-6
QUERY_BLOCK = 512     # queries a block of weights, where it divides the tokens
FFN_BLOCK = 2176      # columns of the feed-forward upcast at a time
_STATIC = ("vocab", "positions", "width", "layers", "heads", "kv_heads",
           "head_dim", "dense_width", "rope_theta", "eps",
           "published_layers")


def sizes_of(config: dict) -> dict:
    """What the reference and its work module need, from the file's keys:
    the published ones, cut as ``reduced`` says."""
    return {
        "vocab": int(config["vocab_size"]),
        "positions": int(config["program"]["model_options"]["max_len"]),
        "width": int(config["hidden_size"]),
        "layers": int(config["num_hidden_layers"]),
        "heads": int(config["num_attention_heads"]),
        "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config["head_dim"]),
        "dense_width": int(config["intermediate_size"]),
        "rope_theta": float(config["rope_theta"]),
        "eps": float(config["rms_norm_eps"]),
        "published_layers": int(config["published"]["num_hidden_layers"]),
    }


@functools.partial(jax.jit, static_argnames=_STATIC)
def make_weights(key, **s):
    """All weights from one key in one call, in the layout of
    ``models/brumby.py``'s parameters and in the precision the configuration
    states: the matrices are drawn AS bfloat16, never float32 first; norm
    scales are float32.  Normal 0.02 (the family's); the projections that
    write to the residual stream scaled by 1/sqrt(2 x the PUBLISHED depth);
    every norm scale ``1 + normal 0.02``, so that nothing is multiplied by
    an exact 1; the gate's weights normal 0.02 like any projection (``wg .
    ~x`` then has a deviation of ``0.02 sqrt(width)``, 1.4 at 5,120: gates
    from 0.05 to 0.95, a mean of a half)."""
    keys = iter(jax.random.split(key, 16 * s["layers"] + 4))
    width, d = s["width"], s["head_dim"]
    resid = 0.02 / math.sqrt(2 * s["published_layers"])

    def kernel(i, o, std=0.02):
        return {"kernel": (std * jax.random.normal(
            next(keys), (i, o), jnp.bfloat16)).astype(jnp.bfloat16)}

    def scale(n):
        return {"scale": 1.0 + 0.02 * jax.random.normal(
            next(keys), (n,), jnp.float32)}

    params = {
        "tok_embed": {"embedding": kernel(s["vocab"], width)["kernel"]},
        "final_norm": scale(width),
        "lm_head": kernel(width, s["vocab"])["kernel"],
    }
    for i in range(s["layers"]):
        params[f"block{i}"] = {
            "attn_norm": scale(width), "mlp_norm": scale(width),
            "attn": {
                "q": kernel(width, s["heads"] * d),
                "k": kernel(width, s["kv_heads"] * d),
                "v": kernel(width, s["kv_heads"] * d),
                "gate": kernel(width, s["kv_heads"]),
                "o": kernel(s["heads"] * d, width, resid),
                "q_norm": scale(d), "k_norm": scale(d),
            },
            "mlp": {"gate": kernel(width, s["dense_width"]),
                    "up": kernel(width, s["dense_width"]),
                    "down": kernel(s["dense_width"], width, resid)},
        }
    return params


# ------------------------------------------------------------- arithmetic
def retention_inputs(p, x, s: dict, mm):
    """What the layer is fed: q ``[tokens, heads, d]``, k and v ``[tokens,
    kv_heads, d]`` and the log-gates ``[tokens, kv_heads]``."""
    toks, d = x.shape[0], s["head_dim"]
    q = _rms(mm(x, p["q"]["kernel"]).reshape(toks, s["heads"], d),
             p["q_norm"]["scale"], s["eps"])
    k = _rms(mm(x, p["k"]["kernel"]).reshape(toks, s["kv_heads"], d),
             p["k_norm"]["scale"], s["eps"])
    v = mm(x, p["v"]["kernel"]).reshape(toks, s["kv_heads"], d)
    log_g = jax.nn.log_sigmoid(mm(x, p["gate"]["kernel"]))
    return (_rotate_half(q, s["rope_theta"]),
            _rotate_half(k, s["rope_theta"]), v, log_g)


def retention_quadratic(q, k, v, log_g):
    """The layer as attention without a softmax.  Returns ``[tokens, heads,
    d]``; the weights of a key-value group and a block of queries at a
    time."""
    toks, h, d = q.shape
    g = k.shape[1]
    block = QUERY_BLOCK if toks % QUERY_BLOCK == 0 else toks
    cum = jnp.cumsum(log_g, axis=0)                          # [toks, g]
    at = jnp.arange(toks)

    def group(args):
        q_g, k_g, v_g, cum_g = args      # [toks, r, d], [toks, d] x 2, [toks]

        def queries(part):
            q_b, cum_b, at_b = part
            seen = at_b[:, None] >= at[None, :]
            scores = jnp.einsum("qrd,ud->rqu", q_b, k_g, precision=HIGHEST)
            # the gates between the two tokens: s < t, so never over 1
            between = jnp.exp(jnp.where(
                seen, cum_b[:, None] - cum_g[None, :], -jnp.inf))
            w = jnp.square(scores) / d * between
            return (jnp.einsum("rqu,ud->qrd", w, v_g, precision=HIGHEST)
                    / (jnp.sum(w, axis=-1).T[..., None] + RETENTION_EPS))

        out = jax.lax.map(queries, (
            q_g.reshape(toks // block, block, h // g, d),
            cum_g.reshape(toks // block, block),
            at.reshape(toks // block, block)))
        return out.reshape(toks, h // g, d)

    out = jax.lax.map(group, (
        q.reshape(toks, g, h // g, d).transpose(1, 0, 2, 3),
        k.transpose(1, 0, 2), v.transpose(1, 0, 2), cum.T))  # [g, toks, r, d]
    return out.transpose(1, 0, 2, 3).reshape(toks, h, d)


def retention_branch(p, x, s: dict, mm):
    """``Wo concat(o)`` over one sequence ``x`` [tokens, width]."""
    out = retention_quadratic(*retention_inputs(p, x, s, mm))
    return mm(out.reshape(x.shape[0], -1), p["o"]["kernel"])


def layer(p, x, s: dict, mm):
    """One layer over one sequence ``x`` [tokens, width], float32."""
    h = x + retention_branch(
        p["attn"], _rms(x, p["attn_norm"]["scale"], s["eps"]), s, mm)
    return h + gated_ffn(
        p["mlp"], _rms(h, p["mlp_norm"]["scale"], s["eps"]), FFN_BLOCK, mm)


@functools.partial(jax.jit, static_argnames=("sizes", "lower"))
def _layer_jit(p, x, *, sizes, lower):
    return layer(p, x, dict(sizes), MM[lower])


def logits(params, ids, sizes: dict, lower: str = None):
    """[rows, tokens] ids -> [rows, tokens, vocab] logits, a row and a
    layer at a time (small sizes: the tests)."""
    out = []
    for row in np.asarray(ids):
        x = embed(params, jnp.asarray(row))
        for i in range(sizes["layers"]):
            x = _layer_jit(params[f"block{i}"], x, sizes=_hashable(sizes),
                           lower=lower)
        out.append(head(params, x, sizes))
    return jnp.stack(out)


# ------------------------------------------------------ serving comparison
def served_token_gaps(params, sizes: dict, prompt, served,
                      lower: str = None):
    """One reference pass over ``prompt`` followed by its ``served`` tokens,
    layer by layer, padded to the context the configuration serves (one
    length, so one program; the layer is causal, so the padding changes
    nothing before it).  Returns the gap of every served token: the
    reference's best logit minus the served token's.  With ``lower``
    ('fp8') the control stands in the program's place: the gaps are those
    of the tokens a pass in that precision puts first at the same
    positions."""
    pad_to = sizes["positions"]
    prompt = np.asarray(prompt, np.int32).reshape(-1)
    served = np.asarray(served, np.int32).reshape(-1)
    n = prompt.size + served.size
    if served.size == 0 or n > pad_to:
        raise ValueError(f"cannot compare {served.size} served tokens after "
                         f"{prompt.size} prompt tokens at length {pad_to}")
    ids = np.zeros((pad_to,), np.int32)
    ids[:prompt.size] = prompt
    ids[prompt.size:n] = served
    nxt = np.zeros((pad_to,), np.int32)
    nxt[:n - 1] = ids[1:n]
    key = _hashable(sizes)
    x = embed(params, jnp.asarray(ids))
    xc = x if lower else None
    for i in range(sizes["layers"]):
        p = params[f"block{i}"]
        x = _layer_jit(p, x, sizes=key, lower=None)
        if lower:
            xc = _layer_jit(p, xc, sizes=key, lower=lower)
    head_params = {k: params[k] for k in ("final_norm", "lm_head")}
    gaps = jax.device_get(
        _gap_rows(head_params, x, xc, jnp.asarray(nxt), sizes=key))
    # the positions that predict a served token
    return gaps[prompt.size - 1:n - 1]
