"""Roofline share of the optimistic lookup: the bytes its calls are
defined to move (``bench/roofline.py``) at the chip's HBM peak, over the
device time of its jitted program in the trace."""
from bench.roofline import lookup_bytes, share_pct

WRAP = ("repro.kernels.optimistic_lookup.ops", "lookup_indices_batch")
PROGRAM = "jit_optimistic_lookup"


def shape(args, kwargs):
    return {"queries": len(args[0]), "window": kwargs["window"]}


def read(ctx):
    calls = ctx["calls"].get(WRAP)
    prog = ctx["trace"]["modules"].get(PROGRAM) if ctx["trace"] else None
    if not calls or not prog:
        return None
    nbytes = sum(lookup_bytes(c["queries"], c["window"]) for c in calls)
    return share_pct(nbytes, prog["seconds"], ctx["peak"])
