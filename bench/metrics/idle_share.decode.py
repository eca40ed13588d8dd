"""Share of the traced window in which no operation ran on the device."""


def read(ctx):
    if ctx.trace is None:
        return None
    lo, hi = ctx.trace.window()
    return 100.0 * (1.0 - ctx.trace.busy_s() / (hi - lo))
