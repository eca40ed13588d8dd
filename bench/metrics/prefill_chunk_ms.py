"""Median host time of the window's ``join_step`` calls (one prefill chunk
for every admission in progress; logits come back to the host)."""

import numpy as np


def read(ctx):
    d = [c["t1"] - c["t0"] for c in ctx.window_calls("join_step")]
    return float(np.median(d)) * 1e3 if d else None
