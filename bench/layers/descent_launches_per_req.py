"""Device launches of the k²-tree descent's programs per request of the
traced window."""
LAYER = "k2-tree descent"


def read(ctx):
    t = ctx.trace
    if t is None or not t.layer_launches.get(LAYER) or not ctx.completed:
        return None
    return t.layer_launches[LAYER] / len(ctx.completed)
