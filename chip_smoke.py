"""Smoke run of TideDB's served read path on one TPU chip.

Loads ``N_KEYS`` (1 M) uniform 32-byte hash keys with 1 KiB values (the
paper's §6 shape) through ``put_many``, flushes and reopens the store so
every cell is cold and backed by its index blob, then serves through
``KvBatchServer`` on one ``TideDB``:

- one ``exists`` batch over present and absent keys, large enough for the
  fused Bloom probe on the device (``bloom_check_ragged``, an XLA gather);
- ``get`` batches large enough for the optimistic lookup Pallas kernel;
- puts of new keys and overwrites, then reads of them.

Every answer is checked against a dict oracle.  Both device forms must
have dispatched, the lookup kernel compiled by Mosaic; the Bloom probe must
have rejected the absent keys and match the numpy probe bit for bit; the
lookup kernel must have resolved at least 99 % of the present keys by
itself.

Run from the repo root on a machine with a TPU::

    python3 chip_smoke.py [--seed 0]

Without a TPU it exits non-zero before doing any work.  The figures it
prints are smoke figures, not benchmark numbers; the last line of stdout is
the JSON verdict.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

from repro.core.tidestore import DbConfig, TideDB  # noqa: E402
from repro.core.tidestore.bloom import (  # noqa: E402
    key_hashes_many, probe_cells)
from repro.serving.engine import KvBatchServer  # noqa: E402

N_KEYS = 1_000_000
KEY_BYTES, VALUE_BYTES = 32, 1024
MIN_KERNEL_RESOLVED = 0.99
MIN_BLOOM_NEGATIVE = 0.95      # of absent keys; the filters' FP rate is ~0.1 %


class SmokeFailure(RuntimeError):
    pass


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def _drain(srv, reqs: list) -> float:
    """Serve everything queued; returns the host seconds it took."""
    t = time.perf_counter()
    while srv.step():
        pass
    dt = time.perf_counter() - t
    _check(all(r.done for r in reqs), "a request was never served")
    errs = [r.error for r in reqs if r.error is not None]
    _check(not errs, f"{len(errs)} requests failed, first: {errs[:1]!r}")
    return dt


@contextlib.contextmanager
def _compile_clock():
    """Sums the seconds JAX spends in backend compiles while open."""
    from jax import monitoring
    total = [0.0]

    def on_duration(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            total[0] += duration

    monitoring.register_event_duration_secs_listener(on_duration)
    try:
        yield total
    finally:
        monitoring.unregister_event_duration_listener(on_duration)


def smoke(path: str, *, n_keys: int, seed: int, cfg=None,
          get_batch: int = 4096, get_batches: int = 8,
          exists_batch: int = 32768, n_puts: int = 512,
          log=print) -> dict:
    """Load, reopen cold, serve and check; returns the smoke figures.
    Raises ``SmokeFailure`` on the first answer or counter that is wrong."""
    with _compile_clock() as compile_s:
        figures = _serve(path, n_keys, seed, cfg or DbConfig(), get_batch,
                         get_batches, exists_batch, n_puts, log)
    figures["compile_s"] = compile_s[0]
    log(f"smoke: bloom_check_ragged dispatches={figures['bloom_dispatches']} "
        f"absent keys rejected={figures['bloom_rejected']:.6f} "
        f"optimistic_lookup queries={figures['kernel_lookups']} "
        f"resolved by the kernel={figures['kernel_resolved']:.6f}")
    log(f"smoke: served exists {figures['exists_s']:.3f} s, "
        f"gets {figures['get_s']:.3f} s, puts+reads {figures['put_s']:.3f} s "
        f"(host clock, first compiles included); backend compiles "
        f"{figures['compile_s']:.3f} s")
    return figures


def _serve(path, n_keys, seed, cfg, get_batch, get_batches, exists_batch,
           n_puts, log) -> dict:
    rng = np.random.default_rng(seed)
    raw = rng.bytes(n_keys * KEY_BYTES)
    keys = [raw[i:i + KEY_BYTES] for i in range(0, len(raw), KEY_BYTES)]
    raw = rng.bytes(n_keys * VALUE_BYTES)
    values = [raw[i:i + VALUE_BYTES] for i in range(0, len(raw), VALUE_BYTES)]
    del raw
    oracle = dict(zip(keys, values))
    _check(len(oracle) == n_keys, "duplicate keys drawn")

    t = time.perf_counter()
    db = TideDB(path, cfg)
    for i in range(0, n_keys, 4096):
        db.put_many(list(zip(keys[i:i + 4096], values[i:i + 4096])))
    db.close()                                  # flushes every cell's index
    load_s = time.perf_counter() - t
    log(f"smoke: loaded {n_keys} keys, {n_keys * (KEY_BYTES + VALUE_BYTES)} "
        f"bytes of keys and values, in {load_s:.3f} s")

    t = time.perf_counter()
    db = TideDB(path, cfg)
    srv = KvBatchServer(db, max_batch=max(get_batch, exists_batch, n_puts))
    try:
        log(f"smoke: reopened cold in {time.perf_counter() - t:.3f} s")
        figures = {"keys": n_keys}

        # exists: present and absent keys, every cold cell behind one
        # fused Bloom probe.
        present = [keys[i] for i in rng.choice(n_keys, exists_batch // 2,
                                                replace=False)]
        raw = rng.bytes((exists_batch - len(present)) * KEY_BYTES)
        absent = [raw[i:i + KEY_BYTES] for i in range(0, len(raw), KEY_BYTES)]
        probe = present + absent
        order = rng.permutation(len(probe))
        probe = [probe[i] for i in order]
        bloom0 = db.stats()["bloom_dispatches"]
        neg0 = db.stats()["bloom_negative"]
        reqs = [srv.submit_exists(k) for k in probe]
        figures["exists_s"] = _drain(srv, reqs)
        wrong = sum(r.found != (r.key in oracle) for r in reqs)
        _check(wrong == 0, f"{wrong} exists answers differ from the oracle")
        figures["bloom_dispatches"] = db.stats()["bloom_dispatches"] - bloom0
        _check(figures["bloom_dispatches"] >= 1,
               "the Bloom probe never dispatched to the device")
        # Every probe positive goes on to the index, so the answers above
        # cannot see a probe that lets absent keys through: check that it
        # filtered them, and that its bits equal the numpy probe's.
        negative = db.stats()["bloom_negative"] - neg0
        figures["bloom_rejected"] = negative / len(absent)
        _check(figures["bloom_rejected"] >= MIN_BLOOM_NEGATIVE,
               f"the Bloom probe rejected {negative} of {len(absent)} "
               f"absent keys")
        _check(_bloom_parity(db, probe), "the Bloom probe's bits differ "
               "from the numpy probe's on the store's filters")

        # gets of present keys: the optimistic lookup kernel's route.
        m0 = db.stats()
        figures["get_s"] = 0.0
        picks = rng.choice(n_keys, get_batch * get_batches, replace=False)
        for b in range(get_batches):
            batch = [keys[i] for i in picks[b * get_batch:(b + 1) * get_batch]]
            reqs = [srv.submit_get(k) for k in batch]
            figures["get_s"] += _drain(srv, reqs)
            wrong = sum(r.value != oracle[r.key] for r in reqs)
            _check(wrong == 0, f"{wrong} get answers differ from the oracle")
        m1 = db.stats()
        looked = m1["batched_kernel_lookups"] - m0["batched_kernel_lookups"]
        left = m1["kernel_unresolved"] - m0["kernel_unresolved"]
        _check(looked > 0, "the lookup kernel never dispatched")
        figures["kernel_lookups"] = looked
        figures["kernel_resolved"] = 1.0 - left / looked
        _check(figures["kernel_resolved"] >= MIN_KERNEL_RESOLVED,
               f"the lookup kernel resolved only "
               f"{figures['kernel_resolved']:.4f} of present keys")

        # puts of new keys and overwrites, then reads of them.
        raw = rng.bytes(n_puts * KEY_BYTES)
        fresh = [raw[i:i + KEY_BYTES] for i in range(0, len(raw), KEY_BYTES)]
        written = fresh + [keys[i] for i in rng.choice(n_keys, n_puts,
                                                        replace=False)]
        raw = rng.bytes(len(written) * VALUE_BYTES)
        for j, k in enumerate(written):
            oracle[k] = raw[j * VALUE_BYTES:(j + 1) * VALUE_BYTES]
        reqs = [srv.submit_put(k, oracle[k]) for k in written]
        figures["put_s"] = _drain(srv, reqs)
        reqs = [srv.submit_get(k) for k in written]
        figures["put_s"] += _drain(srv, reqs)
        wrong = sum(r.value != oracle[r.key] for r in reqs)
        _check(wrong == 0, f"{wrong} reads of puts differ from the oracle")

        _check(srv.serve_errors == 0, f"serve_errors={srv.serve_errors}")
        _check(db.health == "ok", f"store health is {db.health!r}")
    finally:
        db.close()
    return figures


def _bloom_parity(db, keys: list) -> bool:
    """One device probe of ``keys`` against every cell's filter, compared
    bit for bit with the numpy probe of the same packed batch."""
    ks = db.table.ks(0)
    h1, h2 = key_hashes_many(keys)
    q32 = np.frombuffer(b"".join(k[:4] for k in keys), ">u4")
    cell = ks.uniform_split(q32.astype(np.uint32))[0]
    ids = sorted(ks.cells)
    blooms = [ks.cells[c].bloom for c in ids]
    groups = [np.flatnonzero(cell == c) for c in ids]
    device, copies = probe_cells(blooms, h1, h2, groups)
    _check(copies.dispatches > 0,
           "the parity probe did not dispatch to the device")
    host, _ = probe_cells(blooms, h1, h2, groups, use_kernel=False)
    return bool((device == host).all())


def _mosaic_compiled() -> bool:
    """Whether the lookup kernel, in the mode this backend gives it,
    compiles to a Mosaic custom call rather than interpreted XLA."""
    from repro.kernels.optimistic_lookup.kernel import optimistic_lookup

    def spec(dtype, n=256):
        return jax.ShapeDtypeStruct((n,), dtype)

    u32, i32 = jnp.uint32, jnp.int32
    lookup = jax.jit(lambda q, k, n, b, c, f: optimistic_lookup(
        q, k, n, (b, c, f), window=800)).lower(
            spec(u32), spec(u32, 4096), jax.ShapeDtypeStruct((), i32),
            spec(i32), spec(i32), spec(u32))
    return "tpu_custom_call" in lookup.compile().as_text()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if jax.default_backend() != "tpu":
        print(f"chip_smoke: needs a TPU, JAX's backend is "
              f"{jax.default_backend()!r}", file=sys.stderr)
        return 2
    from repro import compile_cache
    cache_dir = compile_cache.enable(ROOT)
    dev = jax.devices()[0]
    print(f"smoke: device_kind={dev.device_kind} devices={len(jax.devices())} "
          f"compile cache at {cache_dir}")
    _check(_mosaic_compiled(), "the lookup kernel did not compile with Mosaic")
    print("smoke: the lookup kernel compiles to a Mosaic custom call")
    with tempfile.TemporaryDirectory(prefix="tidedb-smoke-") as d:
        smoke(d, n_keys=N_KEYS, seed=args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
