"""A statistic of what the clients saw (``loadgen.client_stats``)."""


def read(ctx, key):
    return (ctx.get("client") or {}).get(key)
