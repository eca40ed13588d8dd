"""Share of their roofline the two lo kernels reach
(``grouped_dequant_matmul`` for wi, ``grouped_dequant_combine`` for wo):
the least time of the window's real (non-pad) lo pairs, from their int4
bytes, scales, activations and operations, over the kernels' summed device
time."""

PATTERN = r"grouped_dequant"


def read(ctx):
    if ctx.trace is None or ctx.peak is None:
        return None
    least = 0.0
    for c in ctx.window_calls("step"):
        for pairs in c["lo_pairs"]:
            if pairs:
                f, b = ctx.counts.lo_pair_cost(ctx.m, ctx.eng, pairs, c["rows"])
                least += ctx.counts.least_time(f, b, ctx.peak)
    t, n = ctx.trace.op_time(PATTERN)
    return 100.0 * least / t if least and t else None
