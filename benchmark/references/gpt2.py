"""The plain reference of one architecture: GPT-2 in float32 ``jax.numpy``.

THE CONTRACT A REFERENCE MODULE KEEPS.  A configuration's file names it
(``"reference": "<name>"``) and the harness, the drivers and
``calibrate.py`` reach it only through that name (``Cell.reference``):

- ``sizes_of(config) -> dict`` with at least ``vocab`` (the ids the traffic
  draws from: the slice held, where the vocabulary is sliced) and
  ``positions`` (the context this configuration serves or trains, which the
  module may take from the program's options and not from the published
  maximum).  Whatever else it holds is the module's own and its work
  module's (``work/<name>.py``); the harness hands it back unread.
- ``make_weights(key, **sizes)``: all weights from one key in one jitted
  call on the device, in the program's layout and in the precision the
  configuration states.  The program is handed them; the reference keeps
  them.
- a served configuration: ``served_token_gaps(weights, sizes, prompt,
  served, lower=None)``, the gap of every served token below the
  reference's best logit; the module pads and blocks as it needs.
  ``lower`` names the control's precision.
- a trained one: ``train_steps(params, batches, sizes, lr, weight_decay,
  rows_per_block, lower=None, half_batch=False)`` -> ``{"losses",
  "first_grads", "grad_norms", "delta"}``.

It imports nothing from ``ml_trainer_tpu`` and takes nothing the program has
made; what no architecture owns it imports from ``benchmark/reference.py``.

GPT-2, written from the published description (Radford et al. 2019; the
``config.json`` of ``openai-community/gpt2``): learned positions, pre-LN
blocks, fused QKV, tanh-GELU feed-forward of four times the width, a final
LayerNorm and a head tied to the token embedding.  Two departures, both to
follow the program's modules, which are what is served and trained:
LayerNorm's epsilon is flax's 1e-6 (published: 1e-5), and the next-token
target of the last position wraps to the row's first token
(``SyntheticTokens``: ``np.roll(data, -1)``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import (MM, adamw_update, leaf_norms, mm_highest,
                                 tree_sub)

LN_EPS = 1e-6


def sizes_of(config: dict) -> dict:
    """The sizes the reference needs, from a configuration file's
    published keys."""
    return {
        "vocab": int(config["vocab_size"]),
        "positions": int(config["n_positions"]),
        "width": int(config["n_embd"]),
        "layers": int(config["n_layer"]),
        "heads": int(config["n_head"]),
    }


@functools.partial(jax.jit, static_argnames=("vocab", "positions", "width",
                                             "layers", "heads"))
def make_weights(key, *, vocab, positions, width, layers, heads):
    """All weights from one key, float32, in the layout of
    ``models/gpt2.py``'s parameters.  Published initialisation (normal
    0.02, residual projections scaled by 1/sqrt(2·layers)); biases and
    LayerNorm parameters are drawn too, so that no term of the arithmetic
    is multiplied by an exact 0 or 1."""
    del heads
    n = iter(jax.random.split(key, 4 + 12 * layers))

    def normal(shape, std):
        return std * jax.random.normal(next(n), shape, jnp.float32)

    def ln():
        return {"scale": 1.0 + normal((width,), 0.02),
                "bias": normal((width,), 0.02)}

    def dense(i, o, std):
        return {"kernel": normal((i, o), std), "bias": normal((o,), 0.02)}

    resid = 0.02 / math.sqrt(2 * layers)
    params = {
        "tok_embed": {"embedding": normal((vocab, width), 0.02)},
        "pos_embed": normal((1, positions, width), 0.01),
        "ln_final": ln(),
    }
    for i in range(layers):
        params[f"block{i}"] = {
            "ln1": ln(),
            "attn": {"qkv": dense(width, 3 * width, 0.02),
                     "proj": dense(width, width, resid)},
            "ln2": ln(),
            "mlp": {"fc_in": dense(width, 4 * width, 0.02),
                    "fc_out": dense(4 * width, width, resid)},
        }
    return params


# ------------------------------------------------------------- arithmetic
def _layer_norm(x, p):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def block(p, x, heads: int, mm):
    """One pre-LN block over ``x`` [rows, tokens, width], causal."""
    rows, toks, width = x.shape
    hd = width // heads
    h = _layer_norm(x, p["ln1"])
    qkv = mm(h, p["attn"]["qkv"]["kernel"]) + p["attn"]["qkv"]["bias"]
    q, k, v = (t.reshape(rows, toks, heads, hd).transpose(0, 2, 1, 3)
               for t in jnp.split(qkv, 3, axis=-1))
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        precision=jax.lax.Precision.HIGHEST) * hd ** -0.5
    causal = jnp.tril(jnp.ones((toks, toks), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    att = jnp.einsum("bhqk,bhkd->bhqd", probs, v,
                     precision=jax.lax.Precision.HIGHEST)
    att = att.transpose(0, 2, 1, 3).reshape(rows, toks, width)
    x = x + mm(att, p["attn"]["proj"]["kernel"]) + p["attn"]["proj"]["bias"]
    h = _layer_norm(x, p["ln2"])
    h = _gelu_tanh(mm(h, p["mlp"]["fc_in"]["kernel"])
                   + p["mlp"]["fc_in"]["bias"])
    return x + mm(h, p["mlp"]["fc_out"]["kernel"]) + p["mlp"]["fc_out"]["bias"]


def embed(params, ids):
    toks = ids.shape[-1]
    return (params["tok_embed"]["embedding"][ids]
            + params["pos_embed"][:, :toks])


def head(params, x):
    """Final LayerNorm and the tied head, always at the reference's
    precision: the control lowers the blocks' products only."""
    return mm_highest(_layer_norm(x, params["ln_final"]),
                      params["tok_embed"]["embedding"].T)


def n_layers(params) -> int:
    return sum(1 for k in params if k.startswith("block"))


def logits(params, ids, heads: int, mm=mm_highest):
    """[rows, tokens] ids -> [rows, tokens, vocab] logits, whole model in
    one trace (small sizes; the chip-size paths below go layer by layer)."""
    x = embed(params, ids)
    for i in range(n_layers(params)):
        x = block(params[f"block{i}"], x, heads, mm)
    return head(params, x)


# ------------------------------------------------------ serving comparison
@functools.partial(jax.jit, static_argnames=("heads", "lower"))
def _block_jit(p, x, *, heads, lower):
    return block(p, x, heads, MM[lower])


@jax.jit
def _gap_rows(params, x, x_judged, next_ids):
    """Per position: how far the judged token's reference logit lies below
    the reference's best.  The judged token is the served next token, or,
    where ``x_judged`` comes from a pass in a lower precision, the token
    that pass puts first."""
    ref = head(params, x)[0]
    if x_judged is not None:
        next_ids = jnp.argmax(head(params, x_judged)[0], axis=-1)
    judged = jnp.take_along_axis(ref, next_ids[:, None], axis=-1)[:, 0]
    return jnp.max(ref, axis=-1) - judged


def served_token_gaps(params, sizes: dict, prompt, served,
                      lower: str = None):
    """One reference pass over ``prompt`` followed by its ``served``
    tokens, layer by layer, padded to the context the configuration serves
    (one fixed length, so one program; causal, so the padding changes
    nothing before it).  Returns the gap of every served token:
    the reference's best logit minus the served token's.  With ``lower``
    ('fp8') the control stands in the program's place: the gaps are those
    of the tokens a pass in that precision puts first at the same
    positions."""
    heads, pad_to = sizes["heads"], sizes["positions"]
    prompt = np.asarray(prompt, np.int32).reshape(-1)
    served = np.asarray(served, np.int32).reshape(-1)
    n = prompt.size + served.size
    if served.size == 0 or n > pad_to:
        raise ValueError(f"cannot compare {served.size} served tokens after "
                         f"{prompt.size} prompt tokens at length {pad_to}")
    ids = np.zeros((1, pad_to), np.int32)
    ids[0, :prompt.size] = prompt
    ids[0, prompt.size:n] = served
    nxt = np.zeros((pad_to,), np.int32)
    nxt[:n - 1] = ids[0, 1:n]
    x = embed(params, jnp.asarray(ids))
    xc = x if lower else None
    for i in range(n_layers(params)):
        p = params[f"block{i}"]
        x = _block_jit(p, x, heads=heads, lower=None)
        if lower:
            xc = _block_jit(p, xc, heads=heads, lower=lower)
    head_params = {k: params[k] for k in ("ln_final", "tok_embed")}
    gaps = jax.device_get(_gap_rows(head_params, x, xc, jnp.asarray(nxt)))
    # the positions that predict a served token
    return gaps[prompt.size - 1:n - 1]


# ----------------------------------------------------- training comparison
def lm_loss_sum(params, x, y, heads: int, mm):
    """Summed next-token cross entropy over a block of rows."""
    lg = logits(params, x, heads, mm)
    logz = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, y[..., None], axis=-1)[..., 0]
    return jnp.sum(logz - picked)


@functools.partial(jax.jit, static_argnames=("heads", "lower"))
def _block_grads(params, x, y, *, heads, lower):
    return jax.value_and_grad(lm_loss_sum)(params, x, y, heads, MM[lower])


def loss_and_grads(params, x, y, heads: int, rows_per_block: int,
                   lower: str = None):
    """Mean loss and its gradient over the whole batch, accumulated over
    blocks of rows so that float32 logits of 50,257 columns fit."""
    x = np.asarray(x)
    y = np.asarray(y)
    total, grads = None, None
    for lo in range(0, x.shape[0], rows_per_block):
        l, g = _block_grads(params, jnp.asarray(x[lo:lo + rows_per_block]),
                            jnp.asarray(y[lo:lo + rows_per_block]),
                            heads=heads, lower=lower)
        total = l if total is None else total + l
        grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    n = x.shape[0] * x.shape[1]
    return total / n, jax.tree.map(lambda g: g / n, grads)


def train_steps(params, batches, sizes: dict, lr: float, weight_decay: float,
                rows_per_block: int, lower: str = None,
                half_batch: bool = False) -> dict:
    """Follow the first steps of training from ``params`` over ``batches``
    (a list of (x, y) row blocks as the loader fed them).  Returns each
    step's loss, the first gradient (tree and per-leaf norms) and the
    parameters' change after the last step (tree).  ``lower`` names the
    control's precision ('fp8'); None is the reference.

    ``half_batch`` plants the fault of a step that leaves out half of its
    rows and takes the mean over the rest."""
    p0 = params
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    count = jnp.zeros((), jnp.float32)
    losses, first = [], None
    for x, y in batches:
        if half_batch:
            x, y = x[: len(x) // 2], y[: len(y) // 2]
        loss, grads = loss_and_grads(params, x, y, sizes["heads"],
                                     rows_per_block, lower=lower)
        if first is None:
            first = grads
        params, mu, nu, count = adamw_update(
            params, mu, nu, grads, count, lr=lr, weight_decay=weight_decay)
        losses.append(float(loss))
    return {"losses": losses, "first_grads": first,
            "grad_norms": jax.device_get(leaf_norms(first)),
            "delta": tree_sub(params, p0)}
