"""Share of the engine's value-cache lookups that hit, over the window."""


def read(ctx):
    d = ctx["db"]
    n = d["cache_hits"] + d["cache_misses"]
    if not n:
        return None
    return 100.0 * d["cache_hits"] / n
