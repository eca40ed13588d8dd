"""Overflow buffer bytes handed to the device per decode step: the window's
difference of the program's ``overflow_shipped_bytes``, in MB (1e6 bytes)."""

from program_stats import per_step


def read(ctx):
    b = per_step(ctx, "overflow_shipped_bytes")
    return None if b is None else b / 1e6
