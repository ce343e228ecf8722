"""Share of the Large Table's whole-cell index resolutions served from the
parsed-blob cache rather than by a pread and parse, over the window."""


def read(ctx):
    d = ctx["db"]
    n = d["blob_cache_hits"] + d["batched_blob_reads"]
    if not n:
        return None
    return 100.0 * d["blob_cache_hits"] / n
