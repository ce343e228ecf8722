"""Roofline share of the fused Bloom probe: the bytes its calls are
defined to move (``bench/roofline.py``) at the chip's HBM peak, over the
device time of its jitted program in the trace."""
from bench.roofline import bloom_bytes, share_pct

WRAP = ("repro.kernels.bloom_check.ops", "probe_cells_batch")
PROGRAM = "jit_bloom_check_ragged"


def shape(args, kwargs):
    return {"queries": len(args[0]), "k": kwargs.get("k", 7)}


def read(ctx):
    calls = ctx["calls"].get(WRAP)
    prog = ctx["trace"]["modules"].get(PROGRAM) if ctx["trace"] else None
    if not calls or not prog:
        return None
    nbytes = sum(bloom_bytes(c["queries"], c["k"]) for c in calls)
    return share_pct(nbytes, prog["seconds"], ctx["peak"])
