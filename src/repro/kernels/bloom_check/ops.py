"""Host-facing entry of the fused Bloom probe.

``probe_cells_batch`` is what the storage engine's existence path uses:
every touched cell's bit array packed into one buffer, every (key, cell)
pair probed in ONE dispatch.  Numpy in / numpy out, with query-count and
bitset-word padding to power-of-two buckets so the jit cache stays small
across cells of different sizes.  Each call returns, beside its answer,
the dispatch it made and the bytes it copied each way (``Copies``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.tracing import traced

from .kernel import bloom_check_ragged
from ..padding import Copies, next_pow2

_probe = jax.jit(bloom_check_ragged, static_argnames=("k",))


@traced("bloom.device")
def probe_cells_batch(h1: np.ndarray, h2: np.ndarray, off: np.ndarray,
                      nbits: np.ndarray, bits: np.ndarray, *,
                      k: int = 7) -> tuple[np.ndarray, Copies]:
    """Fused ragged membership: h1/h2 (Q,) u32, off (Q,) i32 word bases,
    nbits (Q,) u32 per-query moduli, bits (total_words,) u32 packed cells
    → ((Q,) bool, copies), in ONE kernel dispatch; ``copies`` counts it and
    the padded arrays copied each way.

    Padding queries probe slot 0 of word 0 with a modulus of 32 (always a
    valid index into any non-empty packed buffer) and are sliced off;
    padded bitset words are never indexed because each query's ``nbits``
    bounds its probes inside its own cell.
    """
    q = len(h1)
    if q == 0:
        return np.zeros(0, dtype=bool), Copies()
    qp = next_pow2(q)
    if qp != q:
        pad = qp - q
        h1 = np.concatenate([h1, np.zeros(pad, np.uint32)])
        h2 = np.concatenate([h2, np.ones(pad, np.uint32)])
        off = np.concatenate([off, np.zeros(pad, np.int32)])
        nbits = np.concatenate([nbits, np.full(pad, 32, np.uint32)])
    wp = next_pow2(bits.shape[0])
    if wp != bits.shape[0]:
        bits = np.concatenate([bits, np.zeros(wp - bits.shape[0], np.uint32)])
    args = (np.asarray(h1, np.uint32), np.asarray(h2, np.uint32),
            np.asarray(off, np.int32), np.asarray(nbits, np.uint32),
            np.asarray(bits, np.uint32))
    out = np.asarray(_probe(*(jnp.asarray(a) for a in args), k=k))
    return out[:q], Copies(1, sum(a.nbytes for a in args), out.nbytes)
