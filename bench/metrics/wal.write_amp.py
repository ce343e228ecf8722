"""Bytes written to the logs per byte of keys and values the window's
writes carried."""


def read(ctx):
    d = ctx["db"]
    if not d["bytes_written_app"]:
        return None
    return d["bytes_written_disk"] / d["bytes_written_app"]
