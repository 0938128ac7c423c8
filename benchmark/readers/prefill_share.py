"""Prefill seconds over prefill plus decode-step seconds, from the engine's
own samples of the window, in percent."""


def read(ctx):
    samples = ctx.get("samples") or {}
    prefill = sum(samples.get("prefill_secs") or [])
    step = sum(samples.get("step_secs") or [])
    if prefill + step <= 0:
        return None
    return 100.0 * prefill / (prefill + step)
