"""Share of the decode step's root span (``hobbit.decode``) that none of its
direct children covers, over the window: 1 - the children's seconds over
the root's."""

from program_stats import CHILDREN, ROOT, per_step


def read(ctx):
    root = per_step(ctx, "spans", ROOT, "s")
    kids = [per_step(ctx, "spans", c, "s") for c in CHILDREN]
    if not root or None in kids:
        return None
    return 100.0 * (1.0 - sum(kids) / root)
