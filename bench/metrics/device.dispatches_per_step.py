"""Jitted device calls per served step: the store's lookup_dispatches +
bloom_dispatches over the server's steps_served, over the window (one
lookup call per 256 queries, one Bloom probe per exists batch)."""


def read(ctx):
    db, steps = ctx["db"], ctx["srv"].get("steps_served")
    if "lookup_dispatches" not in db or not steps:
        return None
    return (db["lookup_dispatches"] + db["bloom_dispatches"]) / steps
