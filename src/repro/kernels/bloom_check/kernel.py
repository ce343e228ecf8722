"""bloom_check — k-probe Bloom-filter membership on the device.

Per-cell Bloom filters resolve negative lookups without touching the index
(§3.2 step 2, the 15.6× existence-check win).  Queries arrive as (h1, h2)
64-bit hash halves and probe k derived slots of their cell's bitset:
idx_i = (h1 + i·h2) mod nbits, word = idx >> 5, bit = idx & 31.

The probe is a plain ``jnp`` gather that XLA compiles for the device; no
Pallas kernel.  A 1-D gather does not lower in Mosaic, so a Pallas form
has to DMA the 4 KiB tile holding each probed word into VMEM and block its
queries to bound VMEM; XLA keeps the bitset in HBM and bounds VMEM itself.
PERF.md records the chip comparison of the two forms.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def bloom_check_ragged(h1: jax.Array, h2: jax.Array, off: jax.Array,
                       nbits: jax.Array, bits: jax.Array, *,
                       k: int = 7) -> jax.Array:
    """Fused multi-cell membership: probe every query against ITS OWN cell's
    bitset in one dispatch.

    The per-cell bit arrays are packed back to back into one ``bits``
    buffer; each query carries the word offset of its cell (``off``, i32)
    and that cell's true modulus (``nbits``, u32).  The modulus is
    per-query data, so the jit cache keys only on (Q, nwords, k) buckets.

    h1, h2, off, nbits (Q,); bits (total_words,) u32 → (Q,) bool.
    """
    h1 = jnp.asarray(h1, jnp.uint32)
    h2 = jnp.asarray(h2, jnp.uint32)
    nbits = jnp.asarray(nbits, jnp.uint32)
    off = jnp.asarray(off, jnp.int32)
    result = jnp.ones(h1.shape, jnp.bool_)
    for i in range(k):
        idx = (h1 + jnp.uint32(i) * h2) % nbits
        word = bits[off + (idx >> jnp.uint32(5)).astype(jnp.int32)]
        result = result & (((word >> (idx & jnp.uint32(31)))
                            & jnp.uint32(1)) == jnp.uint32(1))
    return result
