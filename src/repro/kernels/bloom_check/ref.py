"""Bitset builder for bloom_check tests.  The reference probe is the store's
numpy pass, ``repro.core.tidestore.bloom._probe_host``: it shares no code
with the device form."""
from __future__ import annotations

import jax.numpy as jnp


def bloom_add_ref(h1, h2, bits, *, k: int = 7, nbits=None):
    """Host-side add: returns updated bitset.  Uses np.bitwise_or.at so
    duplicate word indices within one batch accumulate correctly."""
    import numpy as np
    nbits = nbits if nbits is not None else bits.shape[0] * 32
    b = np.asarray(bits).copy()
    h1n, h2n = np.asarray(h1), np.asarray(h2)
    for i in range(k):
        idx = (h1n + np.uint32(i) * h2n) % np.uint32(nbits)
        np.bitwise_or.at(b, (idx >> np.uint32(5)).astype(np.int64),
                         np.uint32(1) << (idx & np.uint32(31)))
    return jnp.asarray(b)
