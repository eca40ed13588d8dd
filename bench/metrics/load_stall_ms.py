"""Expert-load stall per decode step: the window's difference of the
staging engine's ``load_stall_s``, over the window's decode steps."""


def read(ctx):
    n = len(ctx.window_calls("step"))
    return ctx.delta("load_stall_s") / n * 1e3 if n else None
