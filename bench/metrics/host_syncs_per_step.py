"""Device-to-host reads per decode step: the window's difference of the
program's ``host_syncs``."""

from program_stats import per_step


def read(ctx):
    return per_step(ctx, "host_syncs")
