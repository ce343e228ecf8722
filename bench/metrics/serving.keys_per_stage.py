"""Requests served per batched engine call of ``KvBatchServer``:
(keys_served + writes_served) / batches_served over the window."""


def read(ctx):
    s = ctx["srv"]
    if not s.get("batches_served"):
        return None
    return (s["keys_served"] + s["writes_served"]) / s["batches_served"]
