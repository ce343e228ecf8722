"""Kernel microbenchmarks (interpret mode on CPU — structural metrics).

Wall-clock timings of interpret-mode Pallas are NOT TPU timings; the
meaningful numbers reported here are the *structural* ones that transfer:
bytes staged into VMEM per lookup as a function of window size (the Fig 10
trade-off), iteration counts, and oracle-vs-kernel agreement rates.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.optimistic_lookup.kernel import TILE, optimistic_lookup
from repro.kernels.tide_attention.kernel import tide_attention
from repro.kernels.tide_attention.ref import tide_attention_ref


def run(csv=print) -> None:
    rng = np.random.default_rng(5)
    # --- optimistic_lookup window sweep (device analogue of Fig 10) ---
    N, Q = 100_000, 512
    keys = np.unique(rng.integers(0, 2**32, N, dtype=np.uint32))
    queries = jnp.asarray(rng.integers(0, 2**32, Q, dtype=np.uint32))
    kj = jnp.asarray(keys)
    for w in (1024, 2048, 4096):         # windows are whole 1024-key tiles
        idx, found, iters = jax.block_until_ready(
            optimistic_lookup(queries, kj, window=w))
        it = np.asarray(iters)
        resolved = (np.asarray(idx) >= 0).mean()
        bytes_per_lookup = int(it.mean() * -(-w // TILE) * TILE * 4)
        csv(f"kernel.optimistic.w{w},{it.mean():.3f},"
            f"iters/lookup bytes_staged={bytes_per_lookup} "
            f"resolved={resolved:.3f}")

    # --- tide_attention: kernel vs ref agreement + HBM-traffic model ---
    B, H, KH, dk, NB, blk = 4, 8, 4, 128, 16, 128
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (B, H, dk), jnp.float32)
    ak = jax.random.normal(key, (B, NB, blk, KH, dk), jnp.float32)
    av = jax.random.normal(key, (B, NB, blk, KH, dk), jnp.float32)
    table = jnp.broadcast_to(jnp.arange(NB, dtype=jnp.int32), (B, NB))
    lens = jnp.full((B,), NB * blk, jnp.int32)
    live = jnp.zeros((B,), jnp.int32)
    t0 = time.perf_counter()
    out = jax.block_until_ready(tide_attention(
        q, ak, av, table, lens, live, interpret=True))
    dt = time.perf_counter() - t0
    ref = tide_attention_ref(q, ak, av, table, lens, live)
    err = float(jnp.max(jnp.abs(out - ref)))
    # HBM bytes: kernel streams each K/V block exactly once per kv-head;
    # reference path materializes a full gathered copy first (2× traffic).
    kernel_bytes = 2 * B * NB * blk * KH * dk * 4
    ref_bytes = 2 * kernel_bytes
    csv(f"kernel.tide_attention.allclose,{err:.2e},"
        f"max|err| vs oracle (interp {dt*1e3:.0f}ms)")
    csv(f"kernel.tide_attention.hbm_bytes,{kernel_bytes},"
        f"vs reference-path {ref_bytes} (gather copy eliminated)")
