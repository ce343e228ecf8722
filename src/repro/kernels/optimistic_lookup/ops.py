"""Host-facing entry of the optimistic lookup, used by the storage engine's
batched read pipeline (``TideDB.multi_get``): numpy in, numpy out.

Both axes pad to power-of-two buckets (queries in whole chunks of
``_Q_CHUNK``) so repeated calls over batches and cells of slightly
different sizes reuse the same compiled program; the real key and query
counts travel as data, so padding never moves the kernel's estimate and
padded chunks are never searched.  One call is one dispatch: each column
is copied in once and the results come back in one fetch.  The host
searches only the queries the kernel left unresolved.
"""
from __future__ import annotations

import jax
import numpy as np

from repro.tracing import span

from .kernel import _Q_CHUNK, optimistic_lookup
from ..padding import Copies, next_pow2

_PAD_KEY = np.uint32(0xFFFFFFFF)

_lookup = jax.jit(optimistic_lookup, static_argnames=("window", "max_iters"))


def _pad(a: np.ndarray, size: int, fill) -> np.ndarray:
    return np.concatenate([a, np.full(size - len(a), fill, a.dtype)])


def lookup_indices_batch(queries: np.ndarray, keys: np.ndarray, *,
                         segments=None, window: int = 2048,
                         max_iters: int = 4
                         ) -> tuple[np.ndarray, np.ndarray, int, Copies]:
    """Batched index resolution: queries (Q,) u32, keys (N,) u32 sorted →
    (idx (Q,) i64, found (Q,) bool, unresolved, copies), where
    ``unresolved`` counts the queries the kernel left to the host's binary
    search and ``copies`` the one jitted call, the padded arrays copied to
    the device and the results copied back.

    ``segments`` is ``(base, count, frac)`` per query, as the kernel takes
    it: the slice of ``keys`` the query's key can lie in and the key's
    fractional position (u32) in that slice's key range.  None makes the
    whole column one segment.  Query columns pad to a power-of-two number
    of ``_Q_CHUNK``-query chunks; keys pad to the next power of two with
    0xFFFFFFFF sentinels (preserving sort order), and hits never land in
    the padding.
    """
    q, n = len(queries), len(keys)
    if q == 0 or n == 0:
        return np.zeros(q, np.int64), np.zeros(q, dtype=bool), 0, Copies()
    queries = np.asarray(queries, np.uint32)
    with span("lookup.device"):
        # Floor the key bucket at 4096 so workloads whose touched-cell total
        # hovers around a power-of-two boundary don't recompile every few
        # calls.
        keys_p = _pad(keys, max(4096, next_pow2(n)), _PAD_KEY)
        qp = next_pow2(-(-q // _Q_CHUNK)) * _Q_CHUNK
        cols = [_pad(queries, qp, 0)]
        if segments is not None:
            cols += [_pad(np.asarray(a, dt), qp, 0) for a, dt in
                     zip(segments, (np.int32, np.int32, np.uint32))]
        n_j, q_j = np.int32(n), np.int32(q)
        idx, found, _ = _lookup(cols[0], keys_p, n_j, tuple(cols[1:]) or None,
                                q_j, window=window, max_iters=max_iters)
        idx, found = jax.device_get((idx, found))
    copies = Copies(1, keys_p.nbytes + n_j.nbytes + q_j.nbytes
                    + sum(c.nbytes for c in cols), idx.nbytes + found.nbytes)
    idx = idx[:q].astype(np.int64)
    found = found[:q].copy()          # device_get hands back read-only arrays
    miss = np.flatnonzero(idx < 0)
    if miss.size:
        with span("lookup.host_search"):
            sub = queries[miss]
            at = np.searchsorted(keys, sub, side="left")
            idx[miss] = at
            found[miss] = (at < n) & (keys[np.minimum(at, n - 1)] == sub)
    return idx, found, int(miss.size), copies
