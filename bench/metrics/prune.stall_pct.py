"""Share of the serving steps' wall time the serving thread spent inside
the store's reclamation slices: 100 × prune_step_s / step_wall_s over the
window, both read by ``KvBatchServer`` (the slice runs after every served
stage, inside the step).  None where the server does not time its slices."""


def read(ctx):
    s = ctx["srv"]
    if "prune_step_s" not in s or not s.get("step_wall_s"):
        return None
    return 100.0 * s["prune_step_s"] / s["step_wall_s"]
