"""Runs one cell of the benchmark once and prints its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, on a machine with a TPU.  The cell, its
configuration, its traffic and its metrics are found by name from
``BENCHMARK.json``.  The store is made from ``--seed`` in a fresh directory
under the system temp dir and removed at exit; JAX's compilation cache is
kept in ``.jax_cache`` at the checkout's root.

With ``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from counters and from a
profiler trace of the window.  The last line of standard output is the
result as one JSON object; the last lines of standard error are the
numbers compared with the reference, each beside its limit.  Without a TPU,
or with fewer chips than the cell asks for, it exits with 2 and prints no
result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))


def log(msg: str, err: bool = False) -> None:
    print(msg, file=sys.stderr if err else sys.stdout, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness
    cell = harness.load_cell(args.workload)

    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        log(f"bench: needs a TPU, JAX's backend is {backend!r}", err=True)
        return 2
    if len(jax.devices()) < cell.chips:
        log(f"bench: {cell.name} needs {cell.chips} chips, JAX finds "
            f"{len(jax.devices())}", err=True)
        return 2
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), t_process=T_PROCESS, log=log)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
