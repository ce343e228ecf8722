"""Benchmark orchestrator — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines.  Scaled to minutes on one
CPU core; ratios and curve shapes (not absolute ops/s) are the paper-
reproduction targets — see DESIGN.md §9.
"""
from __future__ import annotations

import argparse
import os
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset: kv,kvbatch,kvshard,kvwrite,"
                         "kvexists,reloc,index,recovery,faults,overload,"
                         "system,kernels,roofline")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None

    from repro import compile_cache
    compile_cache.enable(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

    from . import (faults, index_formats, kernel_bench, kv_exists,
                   kv_throughput, kv_write, overload, recovery, relocation,
                   roofline_report, system_keyspace)

    suites = [
        ("kv", kv_throughput.run),          # Figures 1, 6, 7, 8
        ("kvbatch", kv_throughput.run_batched),  # batched read pipeline
        ("kvshard", kv_throughput.run_sharded),  # shard-parallel multi_get
        ("kvwrite", kv_write.run),          # vectorized write pipeline
        ("kvexists", kv_exists.run),        # fused existence-path probes
        ("reloc", relocation.run),          # Figure 9
        ("index", index_formats.run),       # Figure 10 / §6.3
        ("recovery", recovery.run),         # §3.3–3.4
        ("faults", faults.run),             # fault fuzz + scrub + degraded
        ("overload", overload.run),         # admission control loop
        ("system", system_keyspace.run),    # __system observation overhead
        ("kernels", kernel_bench.run),      # Pallas kernels
        ("roofline", roofline_report.run),  # dry-run roofline table
    ]
    print("name,us_per_call,derived")
    for name, fn in suites:
        if only and name not in only:
            continue
        t0 = time.time()
        try:
            fn(csv=print)
        except Exception as e:  # pragma: no cover
            import traceback
            traceback.print_exc()
            print(f"{name}.ERROR,0,{e}")
        print(f"{name}.suite_wall_s,{(time.time()-t0)*1e6:.0f},"
              f"{time.time()-t0:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
