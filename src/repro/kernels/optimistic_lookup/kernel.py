"""optimistic_lookup — the paper's §4.2 interpolation search on TPU.

The sorted u32 key column stays in HBM as (N/1024, 8, 128) tiles of 1024
keys.  Each grid step resolves one query:

1. estimate the key's position by interpolation inside its segment:
   est = base + frac·count/2³² (§4.2).  A segment is the slice of the
   column that the key can lie in — one Large Table cell when the column
   is a concatenation of cells — and frac is the key's fractional position
   inside that segment's key range.  Without segments the whole column is
   one segment of the real key count and frac is the key itself, so
   padding past the real keys never skews the estimate,
2. DMA a window of whole tiles around est from HBM into VMEM (the analogue
   of the 32 KB SSD read),
3. count the window's entries below and at the key; if the key lies
   outside the window, step the window toward it and repeat, within a
   fixed budget of ``max_iters`` windows,
4. rank = window start + entries below the key: the searchsorted-left
   insertion point over the whole column — or a place inside a run of
   equal keys when that run crosses the window's start.

Returns (index, found, windows-used) per query.  ``index`` is -1 where the
budget ran out (rare, non-uniform adversarial input); the host resolves
only those queries, mirroring the engine's linear-probe → bisection
fallback.

One jitted program takes any number of queries: a loop inside it runs the
kernel on ``_Q_CHUNK`` queries at a time, as many chunks as the real query
count needs, so a batch costs one dispatch while each Pallas invocation
keeps its per-query columns in SMEM at the chunk's size.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import platform

TILE = 1024                       # keys per (8, 128) i32 tile
_Q_CHUNK = 256                    # queries per Pallas invocation
_SIGN = -2**31                    # u32 order == i32 order after x ^ _SIGN


def _srl(x, s: int):
    return jax.lax.shift_right_logical(x, jnp.int32(s))


def _mul_hi(a, b):
    """High word of the u32 product a·b (both as i32 bit patterns), within
    2 of exact: the 64-bit product from 16-bit halves, minus the low×low
    carry — the chip's scalar unit has no 64-bit multiply."""
    ah, al = _srl(a, 16), a & 0xFFFF
    bh, bl = _srl(b, 16), b & 0xFFFF
    return ah * bh + _srl(ah * bl, 16) + _srl(al * bh, 16)


def _kernel(n_ref, key_ref, base_ref, count_ref, frac_ref, keys_hbm,
            idx_ref, found_ref, iters_ref, win, sem, red,
            *, tiles: int, max_iters: int):
    qi = pl.program_id(0)
    n = n_ref[0]
    key = key_ref[qi] ^ _SIGN
    est = base_ref[qi] + _mul_hi(frac_ref[qi], count_ref[qi])
    width = tiles * TILE
    n_tiles = (n + TILE - 1) // TILE
    max_t0 = jnp.maximum(n_tiles - tiles, 0)
    # First window: the tile boundary nearest est - width/2, so est sits
    # at least half a tile from either edge.
    t0 = jnp.minimum(jnp.maximum(est - width // 2 + TILE // 2, 0) // TILE,
                     max_t0)

    def cond(c):
        used, _, done, _, _ = c
        return (used < max_iters) & (done == 0)

    def body(c):
        used, t0, _, _, _ = c
        copy = pltpu.make_async_copy(keys_hbm.at[pl.ds(t0, tiles)], win, sem)
        copy.start()
        copy.wait()
        w = win[...]
        red[0] = jnp.sum((w < key).astype(jnp.int32))
        red[1] = jnp.sum((w <= key).astype(jnp.int32))
        below, at_or_below = red[0], red[1]
        lo_ok = (t0 == 0) | (at_or_below > 0)          # w[0] <= key
        hi_ok = (t0 + tiles >= n_tiles) | (below < width)  # key <= w[-1]
        inside = lo_ok & hi_ok
        rank = t0 * TILE + below
        hit = (at_or_below > below) & (rank < n)
        step = jnp.where(lo_ok, t0 + tiles, t0 - tiles)
        return (used + 1,
                jnp.where(inside, t0, jnp.clip(step, 0, max_t0)),
                inside.astype(jnp.int32), rank, hit.astype(jnp.int32))

    used, _, done, rank, hit = jax.lax.while_loop(
        cond, body, (jnp.int32(0), t0, jnp.int32(0), jnp.int32(0),
                     jnp.int32(0)))
    idx_ref[qi] = jnp.where(done == 1, rank, -1)
    found_ref[qi] = hit * done
    iters_ref[qi] = used


def _as_i32(x):
    x = jnp.asarray(x)
    if x.dtype == jnp.uint32:
        return jax.lax.bitcast_convert_type(x, jnp.int32)
    return x.astype(jnp.int32)


def _lookup_chunk(n, queries, base, count, frac, keys, *, tiles: int,
                  max_iters: int):
    """The kernel over one chunk of ``_Q_CHUNK`` queries: its five
    scalar-prefetch operands and three outputs live in SMEM."""
    kernel = functools.partial(_kernel, tiles=tiles, max_iters=max_iters)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,        # n, queries, base, count, frac
            grid=(_Q_CHUNK,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],  # column in HBM
            out_specs=[smem, smem, smem],
            scratch_shapes=[pltpu.VMEM((tiles, 8, 128), jnp.int32),
                            pltpu.SemaphoreType.DMA(()),
                            pltpu.SMEM((2,), jnp.int32)]),
        out_shape=[jax.ShapeDtypeStruct((_Q_CHUNK,), jnp.int32)] * 3,
        interpret=platform.interpret(),
    )(n, queries, base, count, frac, keys)


def optimistic_lookup(queries: jax.Array, keys: jax.Array, n_keys=None,
                      segments=None, n_queries=None, *, window: int = 2048,
                      max_iters: int = 4):
    """queries (Q,) u32; keys (N,) u32 sorted ascending, of which the first
    ``n_keys`` (an i32 scalar; default N) are real and the rest padding
    that sorts last.  ``segments`` is ``(base, count, frac)``, three (Q,)
    arrays: query q's key lies in ``keys[base:base+count]`` at fractional
    position ``frac/2³²`` of that slice's key range.  Of the queries the
    first ``n_queries`` (an i32 scalar; default Q) are real: the chunks of
    ``_Q_CHUNK`` that hold none of them are not searched and come back
    unresolved.  ``window`` rounds up to whole tiles of 1024 keys.

    → (idx (Q,) i32 [-1 if unresolved], found (Q,) bool, iters (Q,) i32).
    """
    Q, N = queries.shape[0], keys.shape[0]
    n = N if n_keys is None else n_keys
    n = jnp.reshape(jnp.asarray(n, jnp.int32), (1,))
    if segments is None:
        segments = (jnp.zeros(Q, jnp.int32), jnp.broadcast_to(n, (Q,)),
                    queries)
    q_pad = -(-Q // _Q_CHUNK) * _Q_CHUNK
    cols = [jnp.pad(_as_i32(a), (0, q_pad - Q))
            for a in (queries, *segments)]
    n_q = Q if n_queries is None else n_queries
    chunks = (jnp.asarray(n_q, jnp.int32) + _Q_CHUNK - 1) // _Q_CHUNK
    n_pad = -(-N // TILE) * TILE
    keys = jnp.pad(_as_i32(keys) ^ _SIGN, (0, n_pad - N),
                   constant_values=2**31 - 1).reshape(n_pad // TILE, 8, 128)
    tiles = min(max(1, -(-window // TILE)), n_pad // TILE)

    def chunk(c, outs):
        off = c * _Q_CHUNK
        got = _lookup_chunk(
            n, *(jax.lax.dynamic_slice_in_dim(a, off, _Q_CHUNK)
                 for a in cols), keys, tiles=tiles, max_iters=max_iters)
        return tuple(jax.lax.dynamic_update_slice_in_dim(o, g, off, 0)
                     for o, g in zip(outs, got))

    idx, found, iters = jax.lax.fori_loop(
        0, chunks, chunk, (jnp.full(q_pad, -1, jnp.int32),
                           jnp.zeros(q_pad, jnp.int32),
                           jnp.zeros(q_pad, jnp.int32)))
    return idx[:Q], found[:Q].astype(jnp.bool_), iters[:Q]
