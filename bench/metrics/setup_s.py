"""Process start to the start of the window: weights, host expert copies,
compile (or compile-cache loads), warm-up calls."""


def read(ctx):
    return ctx.setup_s
