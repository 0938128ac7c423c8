"""The whole serving step's share of the chip's peak: the operations needed
by every prompt prefilled and every output token generated in the window
(the configuration's work counts), over window x chips x peak, in percent."""


def read(ctx):
    t0, t1 = ctx["window"]
    work, sizes, total = ctx["work"], ctx["sizes"], 0
    for r in ctx.get("records") or []:
        p = r["prompt_len"]
        for i, t in enumerate(r["times"]):
            if t0 <= t <= t1:
                total += (work.prefill_flops(sizes, p) if i == 0
                          else work.decode_flops(sizes, p + i - 1))
    if total == 0:
        return None
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["chips"]
    return 100.0 * total / ((t1 - t0) * peak)
