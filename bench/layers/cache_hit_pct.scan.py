"""Share, in %, of the window's ``?p?`` requests that the result cache's
predicate segment answered: the delta of ``cache_stats().predicate_hits``
over the window, over the ``?p?`` requests sent. Each such request makes
one lookup, on the shard that owns its predicate."""


def read(ctx):
    d = ctx.cache_delta
    scans = sum(1 for r in ctx.completed if r.kind == "?p?")
    if not d or "predicate_hits" not in d or scans == 0:
        return None
    return 100.0 * d["predicate_hits"] / scans
