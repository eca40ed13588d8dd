"""Share of its roofline paged flash decode reaches: the least time of the
KV the window's live rows attend over (plus q and o), over the kernel's
summed device time."""

PATTERN = r"paged_flash_decode"


def read(ctx):
    if ctx.trace is None or ctx.peak is None:
        return None
    least = 0.0
    layers = int(ctx.m["num_layers"])
    for c in ctx.window_calls("step"):
        if c["lengths"]:
            f, b = ctx.counts.paged_attn_cost(ctx.m, c["lengths"])
            least += layers * ctx.counts.least_time(f, b, ctx.peak)
    t, n = ctx.trace.op_time(PATTERN)
    return 100.0 * least / t if least and t else None
