"""Operations and bytes of one architecture, GPT-2, computed from shapes.

THE CONTRACT A WORK MODULE KEEPS.  A configuration's file names it
(``"work": "<name>"``); the drivers and the readers reach it only through
that name (``Cell.work``, ``ctx["work"]``).  ``sizes`` is what the
configuration's reference gives (``sizes_of``):

- ``prefill_flops(sizes, prompt_len)``, ``decode_flops(sizes, context)``:
  what ``serve_mfu`` adds up over the window's prompts and tokens;
- ``train_flops_per_token(sizes, seq_len)``: what ``train_mfu`` multiplies
  by the rate, and what sizes the training driver's one long epoch;
- one function ``(ctx) -> (operations, bytes)`` for each kernel whose
  roofline a metric reads, found by the name in that metric's
  ``params.work`` (``flash_train`` below): the work of all the kernel's
  calls in the traced window.

Counts the work the algorithm needs, whatever implements it: a matrix
product is two operations a multiply-add; causal attention is counted over
the true lengths (a token at position t attends t+1 keys), not over a padded
block or the masked half of a square; recomputation is never counted.
"""

from __future__ import annotations


def block_matmul_flops_per_token(width: int) -> int:
    """QKV (3E²), output projection (E²) and the 4x feed-forward (8E²)."""
    return 2 * 12 * width * width


def head_flops_per_token(width: int, vocab: int) -> int:
    return 2 * width * vocab


def attention_flops(width: int, keys: int) -> int:
    """One query token against ``keys`` keys, all heads of one layer:
    scores and the weighted sum, two products of width x keys."""
    return 4 * width * keys


def causal_attention_flops(width: int, tokens: int) -> int:
    """A whole sequence of one layer, forward: sum over t of (t+1) keys."""
    return 4 * width * tokens * (tokens + 1) // 2


def forward_flops_sequence(s: dict, tokens: int, head_tokens: int) -> int:
    """Forward pass over ``tokens`` new tokens from an empty context, the
    head applied to ``head_tokens`` of them (all in training, the last one
    in a prefill)."""
    per_layer = (tokens * block_matmul_flops_per_token(s["width"])
                 + causal_attention_flops(s["width"], tokens))
    return (s["layers"] * per_layer
            + head_tokens * head_flops_per_token(s["width"], s["vocab"]))


def train_flops_per_token(s: dict, seq_len: int) -> float:
    """Forward and backward (twice the forward) a token, no recomputation."""
    return 3.0 * forward_flops_sequence(s, seq_len, seq_len) / seq_len


def decode_flops(s: dict, context: int) -> int:
    """One output token generated with ``context`` tokens before it."""
    per_layer = (block_matmul_flops_per_token(s["width"])
                 + attention_flops(s["width"], context + 1))
    return (s["layers"] * per_layer
            + head_flops_per_token(s["width"], s["vocab"]))


def prefill_flops(s: dict, prompt_len: int) -> int:
    return forward_flops_sequence(s, prompt_len, 1)


# -- kernels: (operations, bytes) of one layer's attention calls ----------
def flash_forward_work(s: dict, rows: int, tokens: int,
                       bytes_per_value: int) -> tuple:
    """Causal attention forward over ``rows`` sequences of ``tokens``: q, k
    and v read once and the output written once."""
    ops = rows * causal_attention_flops(s["width"], tokens)
    moved = rows * 4 * tokens * s["width"] * bytes_per_value
    return ops, moved


def flash_train_work(s: dict, rows: int, tokens: int,
                     bytes_per_value: int) -> tuple:
    """Forward and backward of one layer: the backward's four products
    (dV, dP, dQ, dK) are twice the forward's two; it reads q, k, v, the
    output and its gradient and writes three gradients."""
    f_ops, f_bytes = flash_forward_work(s, rows, tokens, bytes_per_value)
    return 3 * f_ops, f_bytes + 2 * f_bytes


def flash_train(ctx: dict) -> tuple:
    """``flash_train_roofline``: every layer's forward and backward over the
    job's rows, in each of the traced steps."""
    train = ctx["train"]
    o, b = flash_train_work(
        ctx["sizes"], train["batch"], train["seq_len"],
        ctx["bytes_per_value"])
    n = ctx["sizes"]["layers"] * train["traced_steps"]
    return o * n, b * n
