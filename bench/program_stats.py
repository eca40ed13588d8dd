"""The program's own span totals and counters over the measured window,
for the per-layer metrics that read them: ``stats()["spans"]`` (``{name:
{"s": seconds, "n": count}}``), ``overflow_shipped_bytes``,
``commit_shipped_bytes``, ``host_syncs`` and ``served_*``.

Each is a window difference (``ctx.delta``) over the window's decode steps.
A program that does not report the counter reads None, as does a window
without decode steps."""

# the decode step's root span and its direct children
ROOT = "hobbit.decode"
CHILDREN = ("hobbit.decode.prologue", "hobbit.layer.attn", "hobbit.layer.gate",
            "hobbit.layer.route", "hobbit.layer.prefetch",
            "hobbit.layer.stage_wait", "hobbit.layer.plan",
            "hobbit.layer.ship_overflow", "hobbit.layer.ffn",
            "hobbit.decode.logits")


def reports(ctx, *path) -> bool:
    """Whether both window snapshots hold the counter at ``path``."""
    for s in (ctx.stats_open, ctx.stats_close):
        for p in path:
            if not isinstance(s, dict) or p not in s:
                return False
            s = s[p]
    return True


def per_step(ctx, *path):
    """The window difference of the counter at ``path`` per decode step."""
    steps = len(ctx.window_calls("step"))
    if not steps or not reports(ctx, *path):
        return None
    return ctx.delta(*path) / steps


def span_ms(ctx, name: str):
    """Milliseconds per decode step inside span ``name``."""
    s = per_step(ctx, "spans", name, "s")
    return None if s is None else s * 1e3
