"""Operations and bytes of one architecture, ``exaone_moe`` (K-EXAONE), from
shapes.  Keeps the contract at the top of ``benchmark/work/gpt2.py``;
``sizes`` is what ``references/exaone_moe.py::sizes_of`` gives.

Counts the work the algorithm needs on THIS chip, whatever implements it: a
product is two operations a multiply-add; attention is counted over the true
keys (``t + 1`` on a full layer, ``min(t + 1, window)`` on a window layer);
the feed-forward over the dense layer, the shared expert and the EXPECTED
held assignments, ``top_k x held / experts`` a token a layer (1 at 8 of 128
with 16 held): an expectation under even routing, which the counters
``expert_rows_per_step`` and ``expert_held_share_pct`` hold to the run.
"""

from __future__ import annotations

BYTES = 2  # bfloat16: matrices, activations, cache


def _heads_width(s: dict) -> int:
    return s["heads"] * s["head_dim"]


def _kv_width(s: dict) -> int:
    return s["kv_heads"] * s["head_dim"]


def attention_params(s: dict) -> int:
    """Wq, Wk, Wv, Wo."""
    return s["width"] * (2 * _heads_width(s) + 2 * _kv_width(s))


def gated_params(s: dict, hidden: int) -> int:
    return 3 * s["width"] * hidden


def expected_held(s: dict) -> float:
    """Assignments a token that land on this chip's experts, even routing."""
    return s["top_k"] * s["experts_held"][1] / s["experts"]


def layer_matmul_flops_per_token(s: dict, mlp: str) -> float:
    """The weight products of one layer for one token."""
    if mlp == "dense":
        ffn = gated_params(s, s["dense_width"])
    else:
        ffn = (s["width"] * s["experts"]
               + (s["shared"] + expected_held(s))
               * gated_params(s, s["expert_width"]))
    return 2 * (attention_params(s) + ffn)


def keys_seen(s: dict, kind: str, position: int) -> int:
    """Keys the token at ``position`` attends in a layer of ``kind``."""
    seen = position + 1
    return min(seen, s["window"]) if kind == "sliding_attention" else seen


def attention_flops(s: dict, keys: int) -> int:
    """One query token against ``keys`` keys, all query heads of a layer:
    scores and the weighted sum."""
    return 4 * _heads_width(s) * keys


def head_flops_per_token(s: dict) -> int:
    return 2 * s["width"] * s["vocab"]


def decode_flops(s: dict, context: int) -> float:
    """One output token generated with ``context`` tokens before it."""
    return head_flops_per_token(s) + sum(
        layer_matmul_flops_per_token(s, mlp)
        + attention_flops(s, keys_seen(s, kind, context))
        for kind, mlp in zip(s["layer_types"], s["mlp_layer_types"]))


def prefill_flops(s: dict, prompt_len: int) -> float:
    """A prompt from an empty context, the head on its last token."""
    n, w = prompt_len, s["window"]
    full_keys = n * (n + 1) // 2
    band_keys = full_keys if n <= w else w * (w + 1) // 2 + (n - w) * w
    return head_flops_per_token(s) + sum(
        n * layer_matmul_flops_per_token(s, mlp) + attention_flops(
            s, band_keys if kind == "sliding_attention" else full_keys)
        for kind, mlp in zip(s["layer_types"], s["mlp_layer_types"]))


def train_flops_per_token(s: dict, seq_len: int) -> float:
    raise NotImplementedError(
        "exaone_moe is served only: 16 bytes a parameter fit under no "
        "allowed cut (benchmark/configs/k-exaone-236b-ep8.json)")


def step_weight_bytes(s: dict) -> int:
    """Every weight a decode step reads whatever the rows: all of them but
    the token embedding (a row a token, counted with the rows)."""
    count = s["experts_held"][1]
    total = s["width"] * s["vocab"] * BYTES + 4 * s["width"]    # head, norm
    for mlp in s["mlp_layer_types"]:
        total += attention_params(s) * BYTES
        total += 4 * (2 * s["head_dim"] + 2 * s["width"])        # norm scales
        if mlp == "dense":
            total += gated_params(s, s["dense_width"]) * BYTES
        else:
            total += 4 * (s["width"] + 1) * s["experts"]          # router, b
            total += ((count + s["shared"])
                      * gated_params(s, s["expert_width"]) * BYTES)
    return total


def cache_bytes_read(s: dict, context: int) -> int:
    """K and V of the live positions one row's decode step attends."""
    per_position = 2 * _kv_width(s) * BYTES
    return per_position * sum(
        keys_seen(s, kind, context) for kind in s["layer_types"])


def decode_step_work(ctx: dict) -> tuple:
    """``decode_step_roofline``: (operations, bytes) of ONE decode step, the
    window's mean: every weight but the embedding's unread rows once, the
    live cache positions of the rows in flight, the operations their tokens
    need.  The tokens are those the clients received in the window after
    their request's first (which a prefill made), each at its own context;
    the steps are the engine's own samples of the window."""
    s = ctx["sizes"]
    t0, t1 = ctx["window"]
    steps = len((ctx.get("samples") or {}).get("step_secs") or [])
    ops = moved = tokens = 0
    for r in ctx.get("records") or []:
        for i, t in enumerate(r["times"]):
            if i and t0 <= t <= t1:
                context = r["prompt_len"] + i - 1
                ops += decode_flops(s, context)
                moved += cache_bytes_read(s, context)
                tokens += 1
    if not steps or not tokens:
        return 0, 0
    moved += tokens * s["width"] * BYTES                   # embedding rows
    return ops / steps, step_weight_bytes(s) + moved / steps
