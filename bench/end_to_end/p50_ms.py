"""Median, in ms, of every request of the window, each timed from its due
time to its answer (open loop: queueing counts)."""
import numpy as np


def read(ctx):
    lat = [r.end - r.due for r in ctx.completed]
    return float(np.percentile(lat, 50) * 1e3) if lat else None
