"""Decode step time from the program's own root span: the window's
difference of ``hobbit.decode``'s seconds over that of its count, in ms (the
host clock of ``decode_step_ms`` also holds the benchmark's proxy)."""

from program_stats import ROOT, reports


def read(ctx):
    if not ctx.window_calls("step") or not reports(ctx, "spans", ROOT):
        return None
    n = ctx.delta("spans", ROOT, "n")
    return ctx.delta("spans", ROOT, "s") / n * 1e3 if n else None
