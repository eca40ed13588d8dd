"""Union-overflow experts reloaded per decode step, across all MoE layers:
the window's difference of the program's ``union_reloads``.  Each is one
expert written into a device overflow slot (a same-layer neighbour's
admission evicted it)."""

from program_stats import per_step


def read(ctx):
    return per_step(ctx, "union_reloads")
