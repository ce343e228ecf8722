"""Share of the lookup kernel's queries it left to the host's binary
search, over the window."""


def read(ctx):
    d = ctx["db"]
    if not d["batched_kernel_lookups"]:
        return None
    return 100.0 * d["kernel_unresolved"] / d["batched_kernel_lookups"]
