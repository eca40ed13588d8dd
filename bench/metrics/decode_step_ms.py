"""Median host time of the window's decode steps (``step`` returns host
logits, so the device has finished)."""

import numpy as np


def read(ctx):
    d = [c["t1"] - c["t0"] for c in ctx.window_calls("step")]
    return float(np.median(d)) * 1e3 if d else None
