"""Operations and bytes of one architecture, ``brumby`` (power retention),
from shapes.  Keeps the contract at the top of ``benchmark/work/gpt2.py``;
``sizes`` is what ``references/brumby.py::sizes_of`` gives.

Counts the work the algorithm needs on THIS chip, whatever implements it: a
product is two operations a multiply-add.  ``D = d (d + 1) / 2`` is the
state's rows for keys of ``d`` values (8,256 at 128), whatever a program
pads it to.  The retention's core has two statements and each count takes
the one that fits:

* a token AT A TIME (a decode step; ``retention_step_flops``): every query
  head reads the state and the normaliser (``2 D (d + 1)``), every
  key-value head gates them and adds a rank-one term (``3 D (d + 1)``), and
  each head's ``phi`` is two multiplies an entry (``2 D``).  The same at
  every context: the state does not grow.
* a PROMPT of ``n`` tokens from a fresh state (``retention_prompt_flops``):
  the cheaper of that recurrence ``n`` times and the quadratic form, in
  which a query head's token scores the ``t + 1`` tokens up to it (``2 d``
  a score, 3 for the square, the gate and the divisor's sum, ``2 d`` for
  the weighted sum) and the state is then built once (``2 D (d + 1)`` a
  key-value head a token, and its ``phi``).  The quadratic form is the
  cheaper up to 9,162 tokens at the published sizes, so at every prompt a
  context of 4,096 admits.
"""

from __future__ import annotations

from benchmark import trace_reduce

BYTES = 2   # bfloat16: matrices, activations
STATE = 4   # float32: the state and the normaliser


def state_rows(s: dict) -> int:
    return s["head_dim"] * (s["head_dim"] + 1) // 2


def layer_params(s: dict) -> int:
    """Wq, Wk, Wv, the gate's projection, Wo and the gated feed-forward:
    the matrices a token is multiplied by."""
    w, d = s["width"], s["head_dim"]
    return (2 * w * s["heads"] * d + 2 * w * s["kv_heads"] * d
            + w * s["kv_heads"] + 3 * w * s["dense_width"])


def retention_step_flops(s: dict) -> int:
    """One token through one layer's recurrence, all heads."""
    rows, d = state_rows(s), s["head_dim"]
    return (s["heads"] * 2 * rows * (d + 1)
            + s["kv_heads"] * 3 * rows * (d + 1)
            + (s["heads"] + s["kv_heads"]) * 2 * rows)


def retention_prompt_flops(s: dict, n: int) -> int:
    """A prompt of ``n`` tokens through one layer from a fresh state: the
    cheaper of the two statements (the module's docstring)."""
    rows, d = state_rows(s), s["head_dim"]
    quadratic = (s["heads"] * (4 * d + 3) * n * (n + 1) // 2
                 + s["kv_heads"] * n * (2 * rows * (d + 1) + 2 * rows))
    return min(quadratic, n * retention_step_flops(s))


def head_flops_per_token(s: dict) -> int:
    return 2 * s["width"] * s["vocab"]


def decode_flops(s: dict, context: int) -> int:
    """One output token generated with ``context`` tokens before it: the
    same at every context."""
    del context
    return (head_flops_per_token(s)
            + s["layers"] * (2 * layer_params(s) + retention_step_flops(s)))


def prefill_flops(s: dict, prompt_len: int) -> int:
    """A prompt from an empty context, the head on its last token."""
    n = prompt_len
    return (head_flops_per_token(s)
            + s["layers"] * (2 * n * layer_params(s)
                             + retention_prompt_flops(s, n)))


def train_flops_per_token(s: dict, seq_len: int) -> float:
    raise NotImplementedError(
        "brumby is served only: the backward of the chunked retention is "
        "not written (benchmark/configs/brumby-14b-l8.json)")


def step_weight_bytes(s: dict) -> int:
    """Every weight a decode step reads whatever the rows: all of them but
    the token embedding (a row a token, counted with the rows).  Norm
    scales are float32 leaves."""
    scales = 2 * s["width"] + 2 * s["head_dim"]
    return (s["width"] * s["vocab"] * BYTES + 4 * s["width"]
            + s["layers"] * (layer_params(s) * BYTES + 4 * scales))


def state_bytes(s: dict, as_laid_out: bool = False) -> int:
    """One slot's state and normaliser, every layer: the ``D`` entries the
    algorithm needs (what a roofline counts), or as the program lays them
    out, in whole rows of ``d`` lanes (``d / 2 + 1`` of them: 8,320 for
    8,256), which is what the pool takes of the chip's memory."""
    d = s["head_dim"]
    rows = (d // 2 + 1) * d if as_laid_out else state_rows(s)
    return s["layers"] * s["kv_heads"] * rows * (d + 1) * STATE


def decode_step_work(ctx: dict) -> tuple:
    """``retention_decode_step_roofline``: (operations, bytes) of ONE decode
    step, the window's mean: every weight but the embedding's unread rows
    once, EVERY slot's state and normaliser read once and written once (the
    step replaces them whether the slot holds a request or not), the
    operations of the rows in flight.  The tokens are those the clients
    received in the window after their request's first (which a prefill
    made); the steps are the engine's own samples of the window."""
    s = ctx["sizes"]
    t0, t1 = ctx["window"]
    steps = len((ctx.get("samples") or {}).get("step_secs") or [])
    tokens = sum(1 for r in ctx.get("records") or []
                 for i, t in enumerate(r["times"]) if i and t0 <= t <= t1)
    if not steps or not tokens:
        return 0, 0
    per_step = tokens / steps
    return (per_step * decode_flops(s, 0),
            step_weight_bytes(s) + 2 * ctx["slots"] * state_bytes(s)
            + per_step * s["width"] * BYTES)


STATE_STEP_KERNEL = r"^retention_state_step[^|]*\|tpu_custom_call$"


def retention_state_step(ctx: dict) -> tuple:
    """``retention_state_step_roofline``: (operations, bytes) of all the
    kernel's calls in the traced slice.  A call is one layer of one decode
    step: EVERY slot's state and normaliser read once and written once at
    ``D`` entries a head (what the program pads them to is not work), and
    the recurrence's operations for every slot (a free row is stepped with
    the others)."""
    s = ctx["sizes"]
    _, calls = trace_reduce.op_seconds(ctx["trace"], STATE_STEP_KERNEL)
    return (calls * ctx["slots"] * retention_step_flops(s),
            calls * 2 * ctx["slots"] * state_bytes(s) // s["layers"])
