"""The plain reference of one architecture: ``exaone_moe`` (K-EXAONE-236B-A23B)
in float32 ``jax.numpy`` under ``Precision.HIGHEST``.

Keeps the contract at the top of ``benchmark/references/gpt2.py``
(``sizes_of``, ``make_weights``, ``served_token_gaps``; served only), imports
nothing from ``ml_trainer_tpu`` and takes nothing the program has made.

The equations, one layer, token ``x`` at position ``t``, no bias anywhere
(written from the keys of the model's ``config.json``; what the keys do not
say is ``assumed`` in the configuration's file and marked (A) here):

* ``q = Wq x`` (heads x head_dim), ``k = Wk x``, ``v = Wv x`` (kv_heads x
  head_dim).  (A) RMSNorm with a learned scale over each head of ``q`` and
  ``k``, before any rotation.  (A) A ``sliding_attention`` layer rotates
  ``q`` and ``k`` (rotate-half, base ``rope_theta``) and a ``full_attention``
  layer applies no positional rotation.  Scores ``q.k / sqrt(head_dim)``
  over ``j <= t`` and, on a sliding layer, ``t - j < window``; query head
  ``h`` reads key-value head ``h // (heads / kv_heads)``.
* (A) ``h = x + RMSNorm(Wo attention)`` and ``x' = h + RMSNorm(f(h))``:
  the norm is on each branch's OUTPUT.
* ``dense`` layer: ``f(h) = Wd (silu(Wg h) * Wu h)``.
* ``sparse`` layer: ``s = sigmoid(Wr h)``; ``S`` = the ``top_k`` largest of
  ``s + b`` ((A) a selection bias ``b``: it selects, it does not weigh);
  ``w_e = scaling * s_e / sum_{e' in S} s_e'``; ``f(h) = sum_{e in S, e
  held} w_e E_e(h) + E_shared(h)``, every ``E`` the gated feed-forward.
* after the last layer RMSNorm and the untied head.

DEPARTURES, both the cut the configuration states (model-configs guide,
section 4), made in the program alike: only the experts ``experts_held =
(first, count)`` of the router's ``experts`` are here, what the absent ones
would add is left out and the partial sum goes on to the next layer; the
vocabulary is the rows held (ids, logits and the head are over them).

Every weight product goes through one ``mm`` (``benchmark/reference.py``):
the reference's, or the control's, which rounds both operands of the
experts', the router's and every other block's weight products to float8.
The head and the attention's own products stay at the reference's precision.
A product never sees more than one expert-sized block of weights in float32
(151 MB at the published widths), so the pass fits beside weights that fill
the chip.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import MM, mm_highest

HIGHEST = jax.lax.Precision.HIGHEST
_STATIC = ("vocab", "positions", "width", "heads", "kv_heads", "head_dim",
           "layer_types", "mlp_layer_types", "window", "dense_width",
           "expert_width", "experts", "experts_held", "top_k", "scaling",
           "shared", "rope_theta", "eps", "published_layers")


def sizes_of(config: dict) -> dict:
    """What the reference and its work module need, from the file's keys:
    the published ones, cut as ``reduced`` says.  ``experts`` is the router's
    published width, ``experts_held`` the (first, count) held here."""
    n = int(config["num_hidden_layers"])
    options = config["program"]["model_options"]
    return {
        "vocab": int(config["vocab_size"]),
        "positions": int(options["max_len"]),
        "width": int(config["hidden_size"]),
        "heads": int(config["num_attention_heads"]),
        "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config["head_dim"]),
        "layer_types": tuple(config["layer_types"][:n]),
        "mlp_layer_types": tuple(config["mlp_layer_types"][:n]),
        "window": int(config["sliding_window"]),
        "dense_width": int(config["intermediate_size"]),
        "expert_width": int(config["moe_intermediate_size"]),
        "experts": int(config["published"]["num_experts"]),
        "experts_held": (int(config["experts_held_first"]),
                         int(config["num_experts"])),
        "top_k": int(config["num_experts_per_tok"]),
        "scaling": float(config["routed_scaling_factor"]),
        "shared": int(config["num_shared_experts"]),
        "rope_theta": float(config["rope_parameters"]["rope_theta"]),
        "eps": float(config["rms_norm_eps"]),
        "published_layers": int(config["published"]["num_hidden_layers"]),
    }


@functools.partial(jax.jit, static_argnames=_STATIC)
def make_weights(key, **s):
    """All weights from one key in one call, in the layout of
    ``models/exaone_moe.py``'s parameters and in the precision the
    configuration states: the matrices are drawn AS bfloat16 (never float32
    first), norm scales (1), the router and its bias are float32.  Normal
    0.02; the projections that write to the residual stream scaled by
    1/sqrt(2 x the PUBLISHED depth); the selection bias normal 0.01, so
    that its path is run."""
    keys = iter(jax.random.split(key, 16 * len(s["layer_types"]) + 4))
    width, d = s["width"], s["head_dim"]
    resid = 0.02 / math.sqrt(2 * s["published_layers"])

    def normal(shape, std, dtype=jnp.bfloat16):
        return (std * jax.random.normal(next(keys), shape, dtype)).astype(
            dtype)

    def kernel(i, o, std=0.02):
        return {"kernel": normal((i, o), std)}

    def ones(n):
        return {"scale": jnp.ones((n,), jnp.float32)}

    def gated(hidden):
        return {"gate": kernel(width, hidden), "up": kernel(width, hidden),
                "down": kernel(hidden, width, resid)}

    count, hidden = s["experts_held"][1], s["expert_width"]
    params = {
        "tok_embed": {"embedding": normal((s["vocab"], width), 0.02)},
        "final_norm": ones(width),
        "lm_head": normal((width, s["vocab"]), 0.02),
    }
    for i, mlp in enumerate(s["mlp_layer_types"]):
        block = {
            "attn": {"q": kernel(width, s["heads"] * d),
                     "k": kernel(width, s["kv_heads"] * d),
                     "v": kernel(width, s["kv_heads"] * d),
                     "o": kernel(s["heads"] * d, width, resid),
                     "q_norm": ones(d), "k_norm": ones(d)},
            "post_attn_norm": ones(width),
            "post_mlp_norm": ones(width),
        }
        if mlp == "sparse":
            block["moe"] = {
                "router": normal((width, s["experts"]), 0.02, jnp.float32),
                "router_bias": normal((s["experts"],), 0.01, jnp.float32),
                "wg": normal((count, width, hidden), 0.02),
                "wu": normal((count, width, hidden), 0.02),
                "wd": normal((count, hidden, width), resid),
            }
            if s["shared"]:
                block["shared"] = gated(hidden * s["shared"])
        else:
            block["mlp"] = gated(s["dense_width"])
        params[f"block{i}"] = block
    return params


# ------------------------------------------------------------- arithmetic
def _rms(x, scale, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _rotate_half(x, theta):
    """x: [tokens, heads, head_dim] at positions 0..tokens-1."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention_branch(p, x, s: dict, kind: str, mm):
    """``Wo attention(x)`` over one sequence ``x`` [tokens, width]."""
    toks = x.shape[0]
    h, g, d = s["heads"], s["kv_heads"], s["head_dim"]
    q = _rms(mm(x, p["q"]["kernel"]).reshape(toks, h, d),
             p["q_norm"]["scale"], s["eps"])
    k = _rms(mm(x, p["k"]["kernel"]).reshape(toks, g, d),
             p["k_norm"]["scale"], s["eps"])
    v = mm(x, p["v"]["kernel"]).reshape(toks, g, d)
    t, j = jnp.arange(toks)[:, None], jnp.arange(toks)[None, :]
    seen = j <= t
    if kind == "sliding_attention":
        q, k = _rotate_half(q, s["rope_theta"]), _rotate_half(k, s["rope_theta"])
        seen &= t - j < s["window"]

    def group(qkv):
        """One key-value head and the query heads that read it; a group at
        a time, so that the scores stay [heads / kv_heads, tokens, tokens]."""
        qg, kg, vg = qkv                       # [toks, r, d], [toks, d] x 2
        scores = jnp.einsum("trd,ud->rtu", qg, kg, precision=HIGHEST)
        scores = jnp.where(seen, scores * d ** -0.5, -jnp.inf)
        return jnp.einsum("rtu,ud->trd", jax.nn.softmax(scores, axis=-1),
                          vg, precision=HIGHEST)

    out = jax.lax.map(group, (
        q.reshape(toks, g, h // g, d).transpose(1, 0, 2, 3),
        k.transpose(1, 0, 2), v.transpose(1, 0, 2)))     # [g, toks, r, d]
    return mm(out.transpose(1, 0, 2, 3).reshape(toks, h * d),
              p["o"]["kernel"])


def _gated_sum(x, wg, wu, wd, weights, mm):
    """``sum_e weights[:, e] * Wd_e (silu(Wg_e x) * Wu_e x)`` over stacked
    feed-forwards ``[n, ...]``, one at a time: a scan step upcasts one
    expert-sized block and no more."""
    def one(total, block):
        g, u, d, w = block
        y = mm(jax.nn.silu(mm(x, g)) * mm(x, u), d)
        return total + w[:, None] * y, None

    total, _ = jax.lax.scan(
        one, jnp.zeros(x.shape, jnp.float32), (wg, wu, wd, weights.T))
    return total


def gated_ffn(p, x, block: int, mm):
    """A gated feed-forward, its hidden width cut into blocks of ``block``
    columns (the sum over blocks IS the product) so that the dense layer of
    18,432 is nine expert-sized pieces."""
    width, hidden = p["gate"]["kernel"].shape
    n = hidden // block if hidden % block == 0 else 1

    def cols(w):                                   # [width, hidden]
        return w.reshape(width, n, hidden // n).transpose(1, 0, 2)

    return _gated_sum(
        x, cols(p["gate"]["kernel"]), cols(p["up"]["kernel"]),
        p["down"]["kernel"].reshape(n, hidden // n, width),
        jnp.ones((x.shape[0], n), jnp.float32), mm)


def routed_ffn(p, x, s: dict, mm):
    """The held experts' part of the routed sum (not the shared expert)."""
    first, count = s["experts_held"]
    scores = jax.nn.sigmoid(mm(x, p["router"]))               # [toks, E]
    _, chosen = jax.lax.top_k(scores + p["router_bias"], s["top_k"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    gates = s["scaling"] * picked / jnp.sum(picked, axis=-1, keepdims=True)
    # [toks, count]: a token's weight on each held expert, 0 where unchosen
    weights = jnp.sum(
        gates[:, :, None]
        * (chosen[:, :, None] - first == jnp.arange(count)), axis=1)
    return _gated_sum(x, p["wg"], p["wu"], p["wd"], weights, mm)


def layer(p, x, s: dict, kind: str, mlp: str, mm):
    """One layer over one sequence ``x`` [tokens, width], float32."""
    a = attention_branch(p["attn"], x, s, kind, mm)
    h = x + _rms(a, p["post_attn_norm"]["scale"], s["eps"])
    if mlp == "sparse":
        f = routed_ffn(p["moe"], h, s, mm)
        if s["shared"]:
            f = f + gated_ffn(p["shared"], h, s["expert_width"], mm)
    else:
        f = gated_ffn(p["mlp"], h, s["expert_width"], mm)
    return h + _rms(f, p["post_mlp_norm"]["scale"], s["eps"])


def embed(params, ids):
    return params["tok_embed"]["embedding"][ids].astype(jnp.float32)


def head(params, x, s: dict):
    """Final RMSNorm and the untied head, always at the reference's
    precision: the control lowers the blocks' products only."""
    return mm_highest(_rms(x, params["final_norm"]["scale"], s["eps"]),
                      params["lm_head"])


def _hashable(sizes: dict) -> tuple:
    return tuple(sorted(sizes.items()))


@functools.partial(jax.jit, static_argnames=("sizes", "kind", "mlp", "lower"))
def _layer_jit(p, x, *, sizes, kind, mlp, lower):
    return layer(p, x, dict(sizes), kind, mlp, MM[lower])


def logits(params, ids, sizes: dict, lower: str = None):
    """[rows, tokens] ids -> [rows, tokens, vocab] logits, a row and a
    layer at a time (small sizes: the tests)."""
    out = []
    for row in np.asarray(ids):
        x = embed(params, jnp.asarray(row))
        for i, (kind, mlp) in enumerate(zip(sizes["layer_types"],
                                            sizes["mlp_layer_types"])):
            x = _layer_jit(params[f"block{i}"], x, sizes=_hashable(sizes),
                           kind=kind, mlp=mlp, lower=lower)
        out.append(head(params, x, sizes))
    return jnp.stack(out)


# ------------------------------------------------------ serving comparison
@functools.partial(jax.jit, static_argnames=("sizes",))
def _gap_rows(head_params, x, x_judged, next_ids, *, sizes):
    """Per position: how far the judged token's reference logit lies below
    the reference's best (``references/gpt2.py::_gap_rows``)."""
    ref = head(head_params, x, dict(sizes))
    if x_judged is not None:
        next_ids = jnp.argmax(head(head_params, x_judged, dict(sizes)), -1)
    judged = jnp.take_along_axis(ref, next_ids[:, None], axis=-1)[:, 0]
    return jnp.max(ref, axis=-1) - judged


def served_token_gaps(params, sizes: dict, prompt, served,
                      lower: str = None):
    """One reference pass over ``prompt`` followed by its ``served`` tokens,
    layer by layer, padded to the context the configuration serves (one
    length, so one program a kind of layer; causal, so the padding changes
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
    for i, (kind, mlp) in enumerate(zip(sizes["layer_types"],
                                        sizes["mlp_layer_types"])):
        p = params[f"block{i}"]
        x = _layer_jit(p, x, sizes=key, kind=kind, mlp=mlp, lower=None)
        if lower:
            xc = _layer_jit(p, xc, sizes=key, kind=kind, mlp=mlp, lower=lower)
    head_params = {k: params[k] for k in ("final_norm", "lm_head")}
    gaps = jax.device_get(
        _gap_rows(head_params, x, xc, jnp.asarray(nxt), sizes=key))
    # the positions that predict a served token
    return gaps[prompt.size - 1:n - 1]
