"""Compile rehearsals of the store's kernels for a TPU v5e.

The chip's compiler is installed even where no chip is attached, and
compiles for a described topology: each test lowers a device form at the
shapes ``chip_smoke.py`` or a benchmark cell dispatches.  The lookup is a
Pallas kernel, so its tests check that Mosaic compiled it into the program
(a ``tpu_custom_call``); the Bloom probe is a plain gather that XLA compiles.
Nothing runs, so these say nothing about results or times.

The process's backend is still the CPU, where the lookup kernel would
choose the interpreter, so its test tells it the backend is a TPU.  The topology
is described inside a fixture, never at import: only one process may load
the TPU library, and pytest workers import every test file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.bloom_check.kernel import bloom_check_ragged
from repro.kernels.optimistic_lookup.kernel import optimistic_lookup


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # An entry compiled for a described chip cannot be read back without
    # one, so keep these compiles out of any persistent cache.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture()
def mosaic(monkeypatch):
    """Lower the Pallas kernel as it lowers on a TPU backend."""
    from repro.kernels import platform
    monkeypatch.setattr(platform, "interpret", lambda: False)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *specs) -> str:
    return jax.jit(fn).lower(*specs).compile().as_text()


# chip_smoke.py: 1 M keys over 256 cells → the touched cells' key column
# pads to 2^20; a call of up to 256 queries runs one chunk;
# window_entries=800 is one tile.
@pytest.mark.parametrize("n_pad", [2**20, 2**22])
def test_optimistic_lookup_compiles_for_v5e(one_chip, mosaic, n_pad):
    q = 256
    fn = functools.partial(optimistic_lookup, window=800, max_iters=4)
    text = _compiled_text(
        lambda qs, keys, n, base, count, frac:
            fn(qs, keys, n, (base, count, frac)),
        _spec((q,), jnp.uint32, one_chip), _spec((n_pad,), jnp.uint32,
                                                 one_chip),
        _spec((), jnp.int32, one_chip), _spec((q,), jnp.int32, one_chip),
        _spec((q,), jnp.int32, one_chip), _spec((q,), jnp.uint32, one_chip))
    assert "tpu_custom_call" in text


# kv1k-uniform.exists: ~19.5 k Bloom positives a step pad to 128 chunks of
# 256 queries, run by one program whose loop takes the real query count as
# data; each chunk's kernel keeps its columns in SMEM at 256 queries.
def test_one_program_lookup_compiles_for_v5e(one_chip, mosaic):
    q, n_pad = 128 * 256, 2**20
    fn = functools.partial(optimistic_lookup, window=800, max_iters=4)
    text = _compiled_text(
        lambda qs, keys, n, base, count, frac, n_q:
            fn(qs, keys, n, (base, count, frac), n_q),
        _spec((q,), jnp.uint32, one_chip), _spec((n_pad,), jnp.uint32,
                                                 one_chip),
        _spec((), jnp.int32, one_chip), _spec((q,), jnp.int32, one_chip),
        _spec((q,), jnp.int32, one_chip), _spec((q,), jnp.uint32, one_chip),
        _spec((), jnp.int32, one_chip))
    assert "tpu_custom_call" in text and "while" in text


# chip_smoke.py: a 32768-key exists batch over 256 cells of 2048 words each
# (3.9 k keys × 10 bits, rounded up to a power of two) → 2^19 packed
# words; plus a bitset 4× larger, and one small batch.
@pytest.mark.parametrize("q,words", [(32768, 2**19), (32768, 2**21),
                                     (256, 2**19)])
def test_bloom_check_ragged_compiles_for_v5e(one_chip, q, words):
    fn = functools.partial(bloom_check_ragged, k=7)
    text = _compiled_text(
        fn, _spec((q,), jnp.uint32, one_chip),
        _spec((q,), jnp.uint32, one_chip), _spec((q,), jnp.int32, one_chip),
        _spec((q,), jnp.uint32, one_chip),
        _spec((words,), jnp.uint32, one_chip))
    assert "gather" in text and "tpu_custom_call" not in text
