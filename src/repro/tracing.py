"""Host spans of the store, on the profiler's clock.

``span(name)`` is a ``jax.profiler.TraceAnnotation``: while a profiler
session runs, its begin and end land in the same trace as the device's
operations, on the host line of the thread that opened it; with no session
it costs well under a microsecond.  ``traced(name)`` wraps a whole function
in one.  Spans open once per step, stage, batch, uncached index blob or
background tick, never once per key or per kernel chunk, so a served step
opens a few dozen.  Counts go in ``Metrics`` counters, never in names.

A name's prefix is its layer; ``SPANS`` lists every name the program
opens, so a trace reader can keep exactly these.
"""
from __future__ import annotations

import functools

from jax.profiler import TraceAnnotation

SPANS = (
    # serving (serving/engine.py, KvBatchServer.step)
    "serve.step", "serve.schedule", "serve.reads", "serve.writes",
    # engine (core/tidestore/db.py)
    "db.multi_get", "db.multi_exists", "db.put_many", "db.cache_sweep",
    "db.cache_fill",
    # large table (core/tidestore/large_table.py)
    "table.resolve", "table.bloom_pass", "table.blob_load", "table.verify",
    "table.perkey", "table.apply_many",
    # WAL (core/tidestore/wal.py; index preads of whole blobs)
    "wal.value_read", "wal.index_pread", "wal.append_many",
    # device forms (kernels/*/ops.py)
    "lookup.device", "lookup.host_search", "bloom.device",
    # reclamation (core/tidestore/relocate.py; the served slice in
    # serving/engine.py)
    "prune.step", "prune.expire", "prune.relocate",
    # background threads (snapshot tick, flusher pool, prune thread)
    "bg.snapshot", "bg.flush_cell", "bg.prune",
)


def span(name: str) -> TraceAnnotation:
    """A context manager that records ``name`` as a host span."""
    return TraceAnnotation(name)


def traced(name: str):
    """Decorator: every call of the function is one ``name`` span."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with TraceAnnotation(name):
                return fn(*args, **kwargs)
        return inner
    return wrap
