"""Of the routed computations (live row x MoE layer x rank) the program
counts in the window's decode steps, the share it computed in low precision
or skipped: window differences of ``served_lo`` and ``served_skip`` over
those of all ``served_*`` (the program's own record; ``lo_share`` is the
benchmark's)."""

from program_stats import per_step


def read(ctx):
    n = {k: per_step(ctx, f"served_{k}") for k in ("hi", "lo", "skip")}
    if None in n.values() or not sum(n.values()):
        return None
    return 100.0 * (n["lo"] + n["skip"]) / sum(n.values())
