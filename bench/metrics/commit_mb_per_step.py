"""Staged expert bytes the pool commits hand to the device per decode step
(hi in float32): the window's difference of the program's
``commit_shipped_bytes``, in MB (1e6 bytes)."""

from program_stats import per_step


def read(ctx):
    b = per_step(ctx, "commit_shipped_bytes")
    return None if b is None else b / 1e6
