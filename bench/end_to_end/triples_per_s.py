"""Result triples returned by requests that ended inside the window,
over the window's seconds."""


def read(ctx):
    n = sum(r.n_triples for r in ctx.completed if r.end <= ctx.seconds)
    return n / ctx.seconds if n else None
