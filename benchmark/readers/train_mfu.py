"""The whole training step's share of the chip's peak: forward and backward
operations a token (attention counted over the causal half, recomputation
not counted; the configuration's work counts) times the tokens per second of
the window, in percent."""


def read(ctx):
    train = ctx.get("train") or {}
    if not train.get("tokens_per_s"):
        return None
    per_token = ctx["work"].train_flops_per_token(
        ctx["sizes"], train["seq_len"])
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["chips"]
    return 100.0 * per_token * train["tokens_per_s"] / peak
