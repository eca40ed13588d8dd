"""Of all routed expert computations (live row x MoE layer x top-k rank) in
the window's decode steps, the share served in low precision or skipped."""


def read(ctx):
    steps = ctx.window_calls("step")
    tot = sum(sum(c["prec"].values()) for c in steps)
    if not tot:
        return None
    lo = sum(c["prec"][1] + c["prec"][2] for c in steps)
    return 100.0 * lo / tot
