"""Output tokens the scheduler received in the window (first tokens
included), over the window's seconds."""


def read(ctx):
    n = sum(c["tokens"] for c in ctx.calls if c["in_win"])
    return n / ctx.seconds if n else None
