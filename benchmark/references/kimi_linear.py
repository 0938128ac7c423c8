"""The plain reference of one architecture: ``kimi_linear``
(Kimi-Linear-48B-A3B-Instruct; Kimi Linear: An Expressive, Efficient
Attention Architecture, arXiv:2510.26692) in float32 ``jax.numpy`` under
``Precision.HIGHEST``.

Keeps the contract at the top of ``benchmark/references/gpt2.py``
(``sizes_of``, ``make_weights``, ``served_token_gaps``; served only), imports
nothing from ``ml_trainer_tpu`` and takes nothing the program has made.  The
routed feed-forward, the gated feed-forward in blocks, the norm, the head
and the gaps are the ones ``references/exaone_moe.py`` states (the same
router: sigmoid scores, a selection bias, 8 of N renormalised and scaled, a
shared expert), imported from there and not restated.

The equations, one layer, token ``x_t`` (``~x = RMSNorm(x)``, learned scale,
``eps``; no bias anywhere; written from the keys of the model's
``config.json``; what the keys do not say is ``assumed`` in the
configuration's file and marked (A) here):

* (A) pre-norm: ``h = x + Attn(~x)``, ``x' = h + FFN(~h)``.
* **KDA layer** (``linear_attn_config``: ``num_heads`` heads, keys and
  values of ``head_dim``, ``short_conv_kernel_size`` taps).  ``q~ = Wq ~x``,
  ``k~ = Wk ~x``, ``v~ = Wv ~x``.  (A) A causal depthwise convolution and
  SiLU on each: ``q^_t[c] = silu(sum_j w[c, j] q~_{t-taps+1+j}[c])``, inputs
  before position 0 zero, no bias.  (A) A head's ``q = q^_h / |q^_h|``, ``k
  = k^_h / |k^_h|`` (the root taken of the sum of squares plus 1e-6), ``v =
  v^_h``.  (A) Decay a channel of the key: ``g_t = -exp(A_log[h])
  softplus(Wf2 Wf1 ~x + dt_bias)``, both gates through a rank of
  ``head_dim``; write strength ``beta_t = sigmoid(Wb ~x)[h]``.  The state
  ``S`` (``head_dim x head_dim``, zero before the first token), A TOKEN AT A
  TIME (``lax.scan``: the recurrence itself, the plainest statement, and
  independent of the program's chunked form):
  ``S' = Diag(exp g_t) S``; ``S_t = S' + beta_t k_t (v_t - S'^T k_t)^T``;
  ``o_t = S_t^T q_t / sqrt(head_dim)``.  (A) ``y_h = RMSNorm(o_h)
  sigmoid(Wg2 Wg1 ~x)_h``, one learned scale shared by the heads; ``Attn =
  Wo concat(y)``.
* **MLA layer** in its EXPANDED form only (``mla_use_nope``: no positional
  rotation, the ``qk_rope_head_dim`` values are plain values).  ``q = Wq
  ~x`` (heads x (nope + rope)); ``[c; k_r] = Wa ~x``; ``c^ = RMSNorm(c)``; a
  head's ``[k_n; v] = Wb_h c^``, its key ``[k_n; k_r]``; scores ``q . k /
  sqrt(nope + rope)`` over ``j <= t``, softmax; ``Attn = Wo concat_h(sum_j
  p_j v_j)``.  A head at a time, so that the scores stay ``[tokens,
  tokens]``.
* the first ``first_k_dense_replace`` layers: ``Wd (silu(Wg ~h) * Wu
  ~h)``; the others the routed layer of ``references/exaone_moe.py`` and one
  shared expert.
* after the last layer RMSNorm and the untied head.

DEPARTURES, both the cut the configuration states (model-configs guide,
section 4), made in the program alike: only the experts ``experts_held =
(first, count)`` of the router's ``experts`` are here and what the absent
ones would add is left out; the vocabulary is the rows held.

Every weight product goes through one ``mm`` (``benchmark/reference.py``):
the reference's, or the control's, which rounds both operands of every
block's weight products (the experts', the router's and both low-rank gates'
too) to float8.  The head, the recurrence and the attention's own products
stay at the reference's precision.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import MM
from benchmark.references.exaone_moe import (_gap_rows, _hashable, _rms,
                                             embed, gated_ffn, head,
                                             routed_ffn)

HIGHEST = jax.lax.Precision.HIGHEST
L2_EPS = 1e-6
_STATIC = ("vocab", "positions", "width", "layer_kinds", "heads", "head_dim",
           "taps", "mla_heads", "latent", "nope", "rope", "v_dim",
           "dense_width", "expert_width", "experts", "experts_held", "top_k",
           "scaling", "shared", "eps", "published_layers")


def sizes_of(config: dict) -> dict:
    """What the reference and its work module need, from the file's keys:
    the published ones, cut as ``reduced`` says.  ``layer_kinds``: ``(kind,
    sparse)`` of each layer kept, in order, ``kda`` or ``mla`` by the two
    published lists (1-indexed, read up to the depth kept) and whether its
    feed-forward is the routed one; ``experts`` is the router's published
    width, ``experts_held`` the (first, count) held here."""
    linear = config["linear_attn_config"]
    dense = int(config["first_k_dense_replace"])
    kinds = []
    for i in range(1, int(config["num_hidden_layers"]) + 1):
        if (i in linear["kda_layers"]) == (i in linear["full_attn_layers"]):
            raise ValueError(f"layer {i} is in both or neither list of layers")
        kinds.append(("kda" if i in linear["kda_layers"] else "mla",
                      i > dense))
    return {
        "vocab": int(config["vocab_size"]),
        "positions": int(config["program"]["model_options"]["max_len"]),
        "width": int(config["hidden_size"]),
        "layer_kinds": tuple(kinds),
        "heads": int(linear["num_heads"]),
        "head_dim": int(linear["head_dim"]),
        "taps": int(linear["short_conv_kernel_size"]),
        "mla_heads": int(config["num_attention_heads"]),
        "latent": int(config["kv_lora_rank"]),
        "nope": int(config["qk_nope_head_dim"]),
        "rope": int(config["qk_rope_head_dim"]),
        "v_dim": int(config["v_head_dim"]),
        "dense_width": int(config["intermediate_size"]),
        "expert_width": int(config["moe_intermediate_size"]),
        "experts": int(config["published"]["num_experts"]),
        "experts_held": (int(config["experts_held_first"]),
                         int(config["num_experts"])),
        "top_k": int(config["num_experts_per_token"]),
        "scaling": float(config["routed_scaling_factor"]),
        "shared": int(config["num_shared_experts"]),
        "eps": float(config["rms_norm_eps"]),
        "published_layers": int(config["published"]["num_hidden_layers"]),
    }


@functools.partial(jax.jit, static_argnames=_STATIC)
def make_weights(key, **s):
    """All weights from one key in one call, in the layout of
    ``models/kimi_linear.py``'s parameters and in the precision the
    configuration states: the matrices (the convolutions' taps among them)
    are drawn AS bfloat16, never float32 first; norm scales (1), ``A_log``,
    ``dt_bias``, the router and its bias are float32.  Normal 0.02; the
    projections that write to the residual stream scaled by 1/sqrt(2 x the
    PUBLISHED depth); the selection bias normal 0.01.  The KDA leaves so
    that every path is run: ``A_log = log u``, ``u`` uniform in [1, 16];
    ``dt_bias`` the inverse softplus of a step log-uniform in [0.001, 0.1];
    taps normal 0.02 x sqrt(width / taps), so that a convolution keeps its
    input's scale."""
    keys = iter(jax.random.split(key, 24 * len(s["layer_kinds"]) + 4))
    width, d, taps = s["width"], s["head_dim"], s["taps"]
    wide = s["heads"] * d
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

    def kda():
        step = jnp.exp(jax.random.uniform(
            next(keys), (wide,), jnp.float32, math.log(1e-3), math.log(0.1)))
        return {
            "q": kernel(width, wide), "k": kernel(width, wide),
            "v": kernel(width, wide), "o": kernel(wide, width, resid),
            "conv": normal((3 * wide, taps), 0.02 * math.sqrt(width / taps)),
            "f_a": kernel(width, d), "f_b": kernel(d, wide),
            "g_a": kernel(width, d), "g_b": kernel(d, wide),
            "b": kernel(width, s["heads"]),
            "A_log": jnp.log(jax.random.uniform(
                next(keys), (s["heads"],), jnp.float32, 1.0, 16.0)),
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "o_norm": ones(d),
        }

    def mla():
        h = s["mla_heads"]
        return {
            "q": kernel(width, h * (s["nope"] + s["rope"])),
            "kv_down": kernel(width, s["latent"] + s["rope"]),
            "kv_norm": ones(s["latent"]),
            "kv_up": normal((s["latent"], h * (s["nope"] + s["v_dim"])), 0.02),
            "o": kernel(h * s["v_dim"], width, resid),
        }

    count, hidden = s["experts_held"][1], s["expert_width"]
    params = {
        "tok_embed": {"embedding": normal((s["vocab"], width), 0.02)},
        "final_norm": ones(width),
        "lm_head": normal((width, s["vocab"]), 0.02),
    }
    for i, (kind, sparse) in enumerate(s["layer_kinds"]):
        block = {"attn": kda() if kind == "kda" else mla(),
                 "attn_norm": ones(width), "mlp_norm": ones(width)}
        if sparse:
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
def short_conv(taps, x):
    """``silu`` of the causal depthwise convolution: ``x`` [tokens,
    channels], ``taps`` [channels, n], inputs before position 0 zero."""
    toks, n = x.shape[0], taps.shape[1]
    padded = jnp.pad(x, ((n - 1, 0), (0, 0)))
    taps = taps.astype(jnp.float32)
    return jax.nn.silu(sum(
        taps[:, j] * padded[j:j + toks] for j in range(n)))


def delta_rule_scan(q, k, v, g, beta, scale):
    """The recurrence, a token at a time.  q, k, g: [tokens, heads, d_k];
    v: [tokens, heads, d_v]; beta: [tokens, heads]; the state starts at
    zero.  Returns the outputs [tokens, heads, d_v] and the last state."""
    def token(state, now):
        q_t, k_t, v_t, g_t, beta_t = now
        decayed = jnp.exp(g_t)[:, :, None] * state             # S'
        read = jnp.einsum("hkv,hk->hv", decayed, k_t, precision=HIGHEST)
        state = decayed + (beta_t[:, None, None] * k_t[:, :, None]
                           * (v_t - read)[:, None, :])
        out = jnp.einsum("hkv,hk->hv", state, q_t, precision=HIGHEST)
        return state, scale * out

    zero = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), jnp.float32)
    state, out = jax.lax.scan(token, zero, (q, k, v, g, beta))
    return out, state


def kda_inputs(p, x, s: dict, mm):
    """What the recurrence is fed: q, k (unit length), v, the log-decay and
    the write strength of every token and head."""
    toks, h, d = x.shape[0], s["heads"], s["head_dim"]
    mixed = jnp.concatenate(
        [mm(x, p[name]["kernel"]) for name in ("q", "k", "v")], axis=-1)
    q, k, v = (t.reshape(toks, h, d) for t in jnp.split(
        short_conv(p["conv"], mixed), 3, axis=-1))
    q, k = (t * jax.lax.rsqrt(
        jnp.sum(t * t, axis=-1, keepdims=True) + L2_EPS) for t in (q, k))
    rate = mm(mm(x, p["f_a"]["kernel"]), p["f_b"]["kernel"])
    g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(
        rate + p["dt_bias"]).reshape(toks, h, d)
    beta = jax.nn.sigmoid(mm(x, p["b"]["kernel"]))
    return q, k, v, g, beta


def kda_branch(p, x, s: dict, mm):
    """``Wo concat(y)`` over one sequence ``x`` [tokens, width]."""
    toks, h, d = x.shape[0], s["heads"], s["head_dim"]
    out, _ = delta_rule_scan(*kda_inputs(p, x, s, mm), scale=d ** -0.5)
    gate = jax.nn.sigmoid(
        mm(mm(x, p["g_a"]["kernel"]), p["g_b"]["kernel"]))
    y = _rms(out, p["o_norm"]["scale"], s["eps"]).reshape(toks, h * d) * gate
    return mm(y, p["o"]["kernel"])


def mla_branch(p, x, s: dict, mm):
    """``Wo attention(x)``, expanded: every key and value from its latent."""
    toks, h = x.shape[0], s["mla_heads"]
    n, r, dv, c = s["nope"], s["rope"], s["v_dim"], s["latent"]
    q = mm(x, p["q"]["kernel"]).reshape(toks, h, n + r)
    down = mm(x, p["kv_down"]["kernel"])
    latent = _rms(down[:, :c], p["kv_norm"]["scale"], s["eps"])
    kv = mm(latent, p["kv_up"]).reshape(toks, h, n + dv)
    k = jnp.concatenate(
        [kv[:, :, :n], jnp.broadcast_to(down[:, None, c:], (toks, h, r))],
        axis=-1)
    seen = jnp.arange(toks)[None, :] <= jnp.arange(toks)[:, None]

    def one_head(qkv):
        q_h, k_h, v_h = qkv                          # [toks, n + r] x 2, dv
        scores = jnp.einsum("td,ud->tu", q_h, k_h, precision=HIGHEST)
        scores = jnp.where(seen, scores * (n + r) ** -0.5, -jnp.inf)
        return jnp.einsum("tu,ud->td", jax.nn.softmax(scores, axis=-1), v_h,
                          precision=HIGHEST)

    out = jax.lax.map(one_head, (
        q.transpose(1, 0, 2), k.transpose(1, 0, 2),
        kv[:, :, n:].transpose(1, 0, 2)))              # [h, toks, dv]
    return mm(out.transpose(1, 0, 2).reshape(toks, h * dv), p["o"]["kernel"])


def layer(p, x, s: dict, kind: str, sparse: bool, mm):
    """One layer over one sequence ``x`` [tokens, width], float32."""
    branch = kda_branch if kind == "kda" else mla_branch
    h = x + branch(p["attn"], _rms(x, p["attn_norm"]["scale"], s["eps"]),
                   s, mm)
    inner = _rms(h, p["mlp_norm"]["scale"], s["eps"])
    if sparse:
        f = routed_ffn(p["moe"], inner, s, mm)
        if s["shared"]:
            f = f + gated_ffn(p["shared"], inner, s["expert_width"], mm)
    else:
        f = gated_ffn(p["mlp"], inner, s["expert_width"], mm)
    return h + f


@functools.partial(jax.jit, static_argnames=("sizes", "kind", "sparse",
                                             "lower"))
def _layer_jit(p, x, *, sizes, kind, sparse, lower):
    return layer(p, x, dict(sizes), kind, sparse, MM[lower])


def logits(params, ids, sizes: dict, lower: str = None):
    """[rows, tokens] ids -> [rows, tokens, vocab] logits, a row and a
    layer at a time (small sizes: the tests)."""
    out = []
    for row in np.asarray(ids):
        x = embed(params, jnp.asarray(row))
        for i, (kind, sparse) in enumerate(sizes["layer_kinds"]):
            x = _layer_jit(params[f"block{i}"], x, sizes=_hashable(sizes),
                           kind=kind, sparse=sparse, lower=lower)
        out.append(head(params, x, sizes))
    return jnp.stack(out)


# ------------------------------------------------------ serving comparison
def served_token_gaps(params, sizes: dict, prompt, served,
                      lower: str = None):
    """One reference pass over ``prompt`` followed by its ``served`` tokens,
    layer by layer, padded to the context the configuration serves (one
    length, so one program a kind of layer; every layer is causal, the
    recurrence too, so the padding changes nothing before it).  Returns the
    gap of every served token: the reference's best logit minus the served
    token's.  With ``lower`` ('fp8') the control stands in the program's
    place: the gaps are those of the tokens a pass in that precision puts
    first at the same positions."""
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
    for i, (kind, sparse) in enumerate(sizes["layer_kinds"]):
        p = params[f"block{i}"]
        x = _layer_jit(p, x, sizes=key, kind=kind, sparse=sparse, lower=None)
        if lower:
            xc = _layer_jit(p, xc, sizes=key, kind=kind, sparse=sparse,
                            lower=lower)
    head_params = {k: params[k] for k in ("final_norm", "lm_head")}
    gaps = jax.device_get(
        _gap_rows(head_params, x, xc, jnp.asarray(nxt), sizes=key))
    # the positions that predict a served token
    return gaps[prompt.size - 1:n - 1]
