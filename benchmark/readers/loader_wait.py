"""Seconds the step loop was blocked in the input pipeline
(``data.loader.loader_wait_snapshot``) over the window, in percent."""


def read(ctx):
    train = ctx.get("train") or {}
    if "loader_wait_s" not in train:
        return None
    return 100.0 * train["loader_wait_s"] / train["window_s"]
