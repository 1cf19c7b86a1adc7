"""Median time, in ms, the service tier took per request: the harness's
span around each ``query_many`` call (no queueing)."""
import numpy as np


def read(ctx):
    t = [r.end - r.start for r in ctx.completed]
    return float(np.median(t) * 1e3) if t else None
