"""Device time per call of the engine's ``grouped_ffn`` jit, from the
trace's program events."""

PATTERN = r"grouped_ffn"


def read(ctx):
    if ctx.trace is None:
        return None
    t, n = ctx.trace.module_time(PATTERN)
    return t / n * 1e3 if n else None
