"""95th percentile of every gap between consecutive output tokens of one
request, both inside the window, over all requests."""

import numpy as np


def read(ctx):
    gaps = []
    for r in ctx.reqs:
        t = [x for x in r.times if ctx.t_open <= x <= ctx.t_close]
        gaps.extend(np.diff(t))
    return float(np.percentile(gaps, 95)) * 1e3 if gaps else None
