"""The control of the correctness check: the reference put in the store's
place, with the configurations' stated guarantee broken, must come out
not correct.

The configurations state exact answers and that an acknowledged write is
visible to every later read at once.  ``ControlEngine`` answers from the
loaded records alone and breaks both in the way a shortcut would:

- a get returns the value as loaded: writes are acknowledged but never
  seen (a stale answer); and it finds the key by its first four bytes
  alone, the index's u32 key column, without the full-key check: where
  two loaded keys share those bytes it returns the value of the smaller
  (a wrong answer; about 116 pairs among 1 M uniform keys);
- an exists is answered by a Bloom filter of the loaded keys alone, with
  the store's filter parameters (10 bits per key rounded up to a power of
  two, 7 probes), so its false positives stand (an approximate answer);
- a put is acknowledged and dropped.

Run it on the chip at the cell's own size, one process per call:

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 5

Each seed loads its own store and serves the cell's traffic through
``KvBatchServer`` with the control behind it; the last line is a JSON
object of every seed's compared numbers.  The benchmark's own runs never
run it.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import hashlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _hashes(keys) -> tuple[np.ndarray, np.ndarray]:
    d = np.frombuffer(b"".join(hashlib.blake2b(k, digest_size=8).digest()
                               for k in keys), "<u4").reshape(-1, 2)
    return d[:, 0].copy(), d[:, 1] | np.uint32(1)


class BloomOnly:
    """One Bloom filter over ``keys``: double hashing of a blake2b digest,
    ``(h1 + i·h2) mod 2³² mod nbits`` for i < k."""

    def __init__(self, keys, bits_per_key: int = 10, k: int = 7):
        raw = max(64, len(keys) * bits_per_key)
        self.nbits = 1 << (raw - 1).bit_length()
        self.k = k
        self.bits = np.zeros(self.nbits // 32, np.uint32)
        for idx in self._probes(keys):
            np.bitwise_or.at(self.bits, idx >> 5,
                             np.uint32(1) << (idx & np.uint32(31)))

    def _probes(self, keys):
        h1, h2 = _hashes(keys)
        for i in range(self.k):
            yield (h1 + np.uint32(i) * h2) % np.uint32(self.nbits)

    def might_contain(self, keys) -> np.ndarray:
        ok = np.ones(len(keys), bool)
        for idx in self._probes(keys):
            ok &= ((self.bits[idx >> 5] >> (idx & np.uint32(31)))
                   & np.uint32(1)).astype(bool)
        return ok


class ControlEngine:
    """The reference in the store's place with the guarantee broken; every
    other attribute forwards to the store, so the server treats it as the
    engine it replaces.  ``prefix_bytes`` is the width of the key prefix
    a get is found by."""

    def __init__(self, db, data, prefix_bytes: int = 4):
        self._db = db
        self._p = prefix_bytes
        self._by_prefix: dict = {}
        for k, v in sorted(zip(data.keys, data.values)):
            self._by_prefix.setdefault(k[:prefix_bytes], v)
        self._bloom = BloomOnly(data.keys)

    def __getattr__(self, name):
        return getattr(self._db, name)

    def multi_get(self, keys, keyspace=0, opts=None):
        return [self._by_prefix.get(k[:self._p]) for k in keys]

    def multi_exists(self, keys, keyspace=0, opts=None):
        return self._bloom.might_contain(list(keys)).tolist()

    def put_many(self, items, keyspace=0, epoch=0, opts=None):
        return [0] * len(items)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one store each")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    from bench import harness
    if jax.default_backend() != "tpu":
        print(f"control: needs a TPU, JAX's backend is "
              f"{jax.default_backend()!r}", file=sys.stderr)
        return 2
    cell = harness.load_cell(args.workload)
    readings = {}
    for seed in (int(s) for s in args.seeds.split(",")):
        res = harness.run_cell(
            cell, seed, args.seconds, False, t_process=time.perf_counter(),
            log=lambda m, err=False: print(m, flush=True),
            engine_wrap=ControlEngine)
        readings[seed] = {"correct": res["correct"], **{
            k: v["value"] for k, v in res["checks"].items()}}
        print(f"control: {cell.name} seed={seed} {readings[seed]}",
              flush=True)
    print(json.dumps({"workload": cell.name, "control": readings}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
