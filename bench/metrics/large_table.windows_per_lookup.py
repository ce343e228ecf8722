"""Index windows read per per-key windowed lookup in the Large Table, over
the window: about 1 when the first window lands on the key.  A store
without the counters, or a window with no per-key lookup, reads nothing."""


def read(ctx):
    d = ctx["db"]
    lookups = d.get("windowed_lookups")
    if not lookups:
        return None
    return d["windowed_reads"] / lookups
