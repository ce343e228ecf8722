"""Bytes the device forms copy back to the host per request served: the
results ``lookup_indices_batch`` and ``probe_cells_batch`` take back with
``np.asarray``, padding included (the store's ``d2h_bytes``), over
keys_served + writes_served, over the window."""


def read(ctx):
    served = (ctx["srv"].get("keys_served", 0)
              + ctx["srv"].get("writes_served", 0))
    if "d2h_bytes" not in ctx["db"] or not served:
        return None
    return ctx["db"]["d2h_bytes"] / served
