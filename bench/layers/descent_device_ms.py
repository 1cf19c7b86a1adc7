"""Device time, in ms, of the k²-tree descent's programs per request of
the traced window (``bench/kernels/*.json`` whose layer is the descent)."""
LAYER = "k2-tree descent"


def read(ctx):
    t = ctx.trace
    if t is None or not t.layer_launches.get(LAYER) or not ctx.completed:
        return None
    return t.layer_s[LAYER] * 1e3 / len(ctx.completed)
