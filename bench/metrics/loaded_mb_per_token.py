"""Expert bytes staged host to device per output token: the window's
difference of the loader's ``loaded_bytes`` (its own count, 16-bit for hi),
over the window's output tokens, in MB."""


def read(ctx):
    n = sum(c["tokens"] for c in ctx.calls if c["in_win"])
    return ctx.delta("loaded_bytes") / n / 1e6 if n else None
