"""99th percentile of the gets completed in the traced window, from
``submit_get`` to the return of the ``step()`` that completed each."""
import numpy as np


def read(ctx):
    lat = ctx["latency_ms"]["get"]
    return float(np.percentile(lat, 99)) if lat.size else None
