"""Median ``Request.queue_wait_s`` (submit to admission) of the requests
admitted in the window."""

import numpy as np


def read(ctx):
    w = [r.request.queue_wait_s for r in ctx.reqs
         if r.begun is not None and ctx.t_open <= r.begun <= ctx.t_close]
    return float(np.median(w)) * 1e3 if w else None
