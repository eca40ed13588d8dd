"""Prefetched copies per decode step that left their pool slot (evicted,
cancelled or flushed) before any probe found them: the window's difference
of the program's ``prefetch_unused``.  A probe that finds the copy still in
flight counts as a use.  A program without the counter reads None."""

from program_stats import per_step


def read(ctx):
    return per_step(ctx, "prefetch_unused")
