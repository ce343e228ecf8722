"""Bytes relocation re-appended per byte the window's puts wrote: the
store's relocated_bytes over the server's write_bytes.  0 where expiry
alone reclaims the space."""


def read(ctx):
    d, s = ctx["db"], ctx["srv"]
    if "relocated_bytes" not in d or not s.get("write_bytes"):
        return None
    return d["relocated_bytes"] / s["write_bytes"]
