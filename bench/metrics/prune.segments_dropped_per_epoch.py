"""WAL segments epoch expiry dropped per epoch the window started: the
store's segments_pruned over the rise of the epoch the server writes
with (``write_epoch``, which the closed loop sets), so the epochs are the
ones the loop served.  None where the server does not report its epoch
or the window started none."""


def read(ctx):
    d, epochs = ctx["db"], ctx["srv"].get("write_epoch")
    if "segments_pruned" not in d or not epochs:
        return None
    return d["segments_pruned"] / epochs
