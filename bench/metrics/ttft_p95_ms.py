"""95th percentile, over requests whose first token arrived in the window,
of the time from when the client sent the request to its first token."""

import numpy as np


def read(ctx):
    ttft = [r.first - r.sent for r in ctx.reqs
            if r.first is not None and ctx.t_open <= r.first <= ctx.t_close]
    return float(np.percentile(ttft, 95)) * 1e3 if ttft else None
