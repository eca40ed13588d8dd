"""Share of hi lookups the expert cache served from a resident copy, from
the window's differences of ``hits_hi`` and ``misses_hi``."""


def read(ctx):
    hits = ctx.delta("cache", "hits_hi")
    misses = ctx.delta("cache", "misses_hi")
    return 100.0 * hits / (hits + misses) if hits + misses else None
