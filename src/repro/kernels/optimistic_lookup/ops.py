"""Host-facing entry of the optimistic lookup, used by the storage engine's
batched read pipeline (``TideDB.multi_get``): numpy in, numpy out.

Both axes pad to power-of-two buckets so repeated calls over cells of
slightly different sizes reuse the same compiled kernel; the real key
count travels as data, so padding never moves the kernel's estimate.  The
host searches only the queries the kernel left unresolved.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.tracing import span

from .kernel import optimistic_lookup
from ..padding import Copies, next_pow2

_PAD_KEY = np.uint32(0xFFFFFFFF)

_lookup = jax.jit(optimistic_lookup, static_argnames=("window", "max_iters"))

# Fixed per-call query width: every kernel invocation sees Q=_Q_CHUNK, so
# the jit cache holds one entry per key-count bucket instead of one per
# (batch size × key count) combination.
_Q_CHUNK = 256


def _pad(a: np.ndarray, size: int, fill) -> np.ndarray:
    return np.concatenate([a, np.full(size - len(a), fill, a.dtype)])


def lookup_indices_batch(queries: np.ndarray, keys: np.ndarray, *,
                         segments=None, window: int = 2048,
                         max_iters: int = 4
                         ) -> tuple[np.ndarray, np.ndarray, int, Copies]:
    """Batched index resolution: queries (Q,) u32, keys (N,) u32 sorted →
    (idx (Q,) i64, found (Q,) bool, unresolved, copies), where
    ``unresolved`` counts the queries the kernel left to the host's binary
    search and ``copies`` the jitted calls (one per 256 queries) and the
    padded arrays copied to the device and the results copied back.

    ``segments`` is ``(base, count, frac)`` per query, as the kernel takes
    it: the slice of ``keys`` the query's key can lie in and the key's
    fractional position (u32) in that slice's key range.  None makes the
    whole column one segment.  Queries run through the kernel in
    fixed-width chunks of ``_Q_CHUNK``; keys pad to the next power of two
    with 0xFFFFFFFF sentinels (preserving sort order), and hits never land
    in the padding.
    """
    q, n = len(queries), len(keys)
    if q == 0 or n == 0:
        return np.zeros(q, np.int64), np.zeros(q, dtype=bool), 0, Copies()
    queries = np.asarray(queries, np.uint32)
    idx_parts, found_parts = [], []
    with span("lookup.device"):
        # Floor the key bucket at 4096 so workloads whose touched-cell total
        # hovers around a power-of-two boundary don't recompile every few
        # calls.
        keys_p = _pad(keys, max(4096, next_pow2(n)), _PAD_KEY)
        keys_j = jnp.asarray(keys_p)
        n_j = jnp.int32(n)
        qp = -(-q // _Q_CHUNK) * _Q_CHUNK
        cols = [_pad(queries, qp, 0)]
        if segments is not None:
            cols += [_pad(np.asarray(a, dt), qp, 0) for a, dt in
                     zip(segments, (np.int32, np.int32, np.uint32))]
        for off in range(0, qp, _Q_CHUNK):
            qc, *seg = (jnp.asarray(c[off:off + _Q_CHUNK]) for c in cols)
            idx, found, _ = _lookup(qc, keys_j, n_j, tuple(seg) or None,
                                    window=window, max_iters=max_iters)
            idx_parts.append(np.asarray(idx))
            found_parts.append(np.asarray(found))
    copies = Copies(len(idx_parts),
                    keys_p.nbytes + n_j.nbytes + sum(c.nbytes for c in cols),
                    sum(a.nbytes for a in idx_parts + found_parts))
    idx = np.concatenate(idx_parts)[:q].astype(np.int64)
    found = np.concatenate(found_parts)[:q]
    miss = np.flatnonzero(idx < 0)
    if miss.size:
        with span("lookup.host_search"):
            sub = queries[miss]
            at = np.searchsorted(keys, sub, side="left")
            idx[miss] = at
            found[miss] = (at < n) & (keys[np.minimum(at, n - 1)] == sub)
    return idx, found, int(miss.size), copies
