"""Operations and bytes of one architecture, ``kimi_linear`` (Kimi Linear),
from shapes.  Keeps the contract at the top of ``benchmark/work/gpt2.py``;
``sizes`` is what ``references/kimi_linear.py::sizes_of`` gives.

Counts the work the algorithm needs on THIS chip, whatever implements it: a
product is two operations a multiply-add.  A KDA layer's core is counted as
the recurrence needs it, a token at a time, ``6 d_k d_v`` a head a token
(what the decayed state reads at ``k``, the rank-one correction, what the
new state reads at ``q``; the decay's own multiply and the convolutions'
``2 x taps`` a channel are beside it), whatever a chunked form spends on its
triangular solve.  An MLA layer is counted over the true keys (``t + 1``),
ABSORBED at a decode step (scores over the ``latent + rope`` values of a
latent row, the weighted sum over its ``latent`` values, both halves of
``Wb`` once a token) and EXPANDED over a prompt (scores over ``nope +
rope``, the sum over ``v_dim``).  The feed-forward is the dense layer, the
shared expert and the EXPECTED held assignments, ``top_k x held / experts``
a token a layer (1 at 8 of 256 with 32 held), an expectation under even
routing.
"""

from __future__ import annotations

BYTES = 2   # bfloat16: matrices, activations, latent cache, tails
STATE = 4   # float32: the recurrent state


def _wide(s: dict) -> int:
    return s["heads"] * s["head_dim"]


def kda_params(s: dict) -> int:
    """Wq, Wk, Wv, Wo, both low-rank gates and the write strength (the
    matrices a token is multiplied by; the taps are counted beside)."""
    w, d = s["width"], s["head_dim"]
    return 4 * w * _wide(s) + 2 * (w * d + d * _wide(s)) + w * s["heads"]


def kda_core_flops(s: dict) -> int:
    """The recurrence and the three convolutions, one token, all heads."""
    return (6 * s["heads"] * s["head_dim"] ** 2
            + 2 * s["taps"] * 3 * _wide(s))


def mla_params(s: dict) -> int:
    """Wq, Wa (down), Wb (up), Wo."""
    h = s["mla_heads"]
    return (s["width"] * h * (s["nope"] + s["rope"])
            + s["width"] * (s["latent"] + s["rope"])
            + s["latent"] * h * (s["nope"] + s["v_dim"])
            + h * s["v_dim"] * s["width"])


def mla_attention_flops(s: dict, keys: int, absorbed: bool) -> int:
    """One query token against ``keys`` keys, all heads of a layer."""
    if absorbed:
        per_key = 2 * s["latent"] + s["rope"]
    else:
        per_key = s["nope"] + s["rope"] + s["v_dim"]
    return 2 * s["mla_heads"] * per_key * keys


def gated_params(s: dict, hidden: int) -> int:
    return 3 * s["width"] * hidden


def expected_held(s: dict) -> float:
    """Assignments a token that land on this chip's experts, even routing."""
    return s["top_k"] * s["experts_held"][1] / s["experts"]


def ffn_flops_per_token(s: dict, sparse: bool) -> float:
    if not sparse:
        return 2 * gated_params(s, s["dense_width"])
    return 2 * (s["width"] * s["experts"] + (s["shared"] + expected_held(s))
                * gated_params(s, s["expert_width"]))


def layer_flops_per_token(s: dict, kind: str, sparse: bool) -> float:
    """One layer, one token, but an MLA layer's attention over its keys."""
    mixer = (2 * kda_params(s) + kda_core_flops(s) if kind == "kda"
             else 2 * mla_params(s))
    return mixer + ffn_flops_per_token(s, sparse)


def head_flops_per_token(s: dict) -> int:
    return 2 * s["width"] * s["vocab"]


def _count(s: dict, kind: str) -> int:
    return sum(k == kind for k, _ in s["layer_kinds"])


def decode_flops(s: dict, context: int) -> float:
    """One output token generated with ``context`` tokens before it."""
    return (head_flops_per_token(s)
            + sum(layer_flops_per_token(s, *layer)
                  for layer in s["layer_kinds"])
            + _count(s, "mla") * mla_attention_flops(s, context + 1, True))


def prefill_flops(s: dict, prompt_len: int) -> float:
    """A prompt from an empty context, the head on its last token."""
    n = prompt_len
    return (head_flops_per_token(s)
            + n * sum(layer_flops_per_token(s, *layer)
                      for layer in s["layer_kinds"])
            + _count(s, "mla") * mla_attention_flops(
                s, n * (n + 1) // 2, False))


def train_flops_per_token(s: dict, seq_len: int) -> float:
    raise NotImplementedError(
        "kimi_linear is served only: the backward of the chunked recurrence "
        "is not written (benchmark/configs/kimi-linear-48b-ep8.json)")


def step_weight_bytes(s: dict) -> int:
    """Every weight a decode step reads whatever the rows: all of them but
    the token embedding (a row a token, counted with the rows)."""
    count = s["experts_held"][1]
    total = s["width"] * s["vocab"] * BYTES + 4 * s["width"]    # head, norm
    for kind, sparse in s["layer_kinds"]:
        total += 4 * 2 * s["width"]                       # the block's norms
        if kind == "kda":
            total += (kda_params(s) + 3 * _wide(s) * s["taps"]) * BYTES
            # A_log, dt_bias, the output norm's scale
            total += 4 * (s["heads"] + _wide(s) + s["head_dim"])
        else:
            total += mla_params(s) * BYTES + 4 * s["latent"]
        if sparse:
            total += 4 * (s["width"] + 1) * s["experts"]          # router, b
            total += ((count + s["shared"])
                      * gated_params(s, s["expert_width"]) * BYTES)
        else:
            total += gated_params(s, s["dense_width"]) * BYTES
    return total


def state_bytes(s: dict) -> int:
    """One slot's recurrent state and convolution tails, every KDA layer."""
    return _count(s, "kda") * (
        s["heads"] * s["head_dim"] ** 2 * STATE
        + (s["taps"] - 1) * 3 * _wide(s) * BYTES)


def latent_bytes_read(s: dict, context: int) -> int:
    """The live latent rows one row's decode step attends."""
    return (_count(s, "mla") * (context + 1)
            * (s["latent"] + s["rope"]) * BYTES)


def decode_step_work(ctx: dict) -> tuple:
    """``state_decode_step_roofline``: (operations, bytes) of ONE decode
    step, the window's mean: every weight but the embedding's unread rows
    once, EVERY slot's recurrent state and tails read once and written once
    (the step replaces them whether the slot holds a request or not), the
    live latent positions of the rows in flight once, the operations their
    tokens need.  The tokens are those the clients received in the window
    after their request's first (which a prefill made), each at its own
    context; the steps are the engine's own samples of the window."""
    s = ctx["sizes"]
    t0, t1 = ctx["window"]
    steps = len((ctx.get("samples") or {}).get("step_secs") or [])
    ops = moved = tokens = 0
    for r in ctx.get("records") or []:
        for i, t in enumerate(r["times"]):
            if i and t0 <= t <= t1:
                context = r["prompt_len"] + i - 1
                ops += decode_flops(s, context)
                moved += latent_bytes_read(s, context)
                tokens += 1
    if not steps or not tokens:
        return 0, 0
    moved += tokens * s["width"] * BYTES                   # embedding rows
    return ops / steps, (step_weight_bytes(s)
                         + 2 * ctx["slots"] * state_bytes(s) + moved / steps)
