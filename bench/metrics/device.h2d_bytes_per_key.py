"""Bytes the device forms copy to the device per request served: the
padded arrays ``lookup_indices_batch`` and ``probe_cells_batch`` hand to
``jnp.asarray`` (the store's ``h2d_bytes``), over keys_served +
writes_served, over the window."""


def read(ctx):
    served = (ctx["srv"].get("keys_served", 0)
              + ctx["srv"].get("writes_served", 0))
    if "h2d_bytes" not in ctx["db"] or not served:
        return None
    return ctx["db"]["h2d_bytes"] / served
