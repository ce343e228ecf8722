"""Share of the serving steps' wall time the serving thread spent off the
CPU (on the GIL, on I/O or waiting for the device): 100 × (1 −
step_cpu_s / step_wall_s) over the window, both read by
``KvBatchServer`` around each step that served requests."""


def read(ctx):
    s = ctx["srv"]
    if not s.get("step_wall_s"):
        return None
    return 100.0 * (1.0 - s["step_cpu_s"] / s["step_wall_s"])
