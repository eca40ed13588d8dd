"""Prefetch copies dropped per decode step, across all MoE layers: the
window's difference of the staging engine's ``dropped_prefetch``.  A copy is
dropped when its pool has no free or evictable slot at submit time (or, on
a precision downgrade, when the lo pool has none either)."""

from program_stats import per_step


def read(ctx):
    return per_step(ctx, "dropped_prefetch")
