"""The store's host spans and device counters: every span the program
opens is listed in ``repro.tracing.SPANS`` and opened once per batch, the
device forms count the bytes they copy, and ``KvBatchServer`` times its
steps on the wall clock and on the serving thread's CPU clock."""
import ast
import hashlib
import os
import shutil
import tempfile
import time

import numpy as np
import pytest

from repro import tracing
from repro.core.tidestore import DbConfig, KeyspaceConfig, TideDB
from repro.core.tidestore.wal import WalConfig
from repro.kernels.bloom_check.ops import probe_cells_batch
from repro.kernels.padding import Copies
from repro.kernels.optimistic_lookup.ops import lookup_indices_batch
from repro.serving.engine import KvBatchServer

SRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src", "repro")


def _opened_names():
    """(file, name) of every span(...) / traced(...) call under src/repro;
    a conditional name counts both of its branches."""
    out = []
    for d, _, files in os.walk(SRC):
        for f in files:
            if not f.endswith(".py"):
                continue
            path = os.path.join(d, f)
            with open(path) as fh:
                tree = ast.parse(fh.read())
            for node in ast.walk(tree):
                if not (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Name)
                        and node.func.id in ("span", "traced")
                        and node.args):
                    continue
                arg = node.args[0]
                leaves = ([arg.body, arg.orelse] if isinstance(arg, ast.IfExp)
                          else [arg])
                for leaf in leaves:
                    name = (leaf.value if isinstance(leaf, ast.Constant)
                            else None)
                    out.append((os.path.relpath(path, SRC), name))
    return out


def test_every_span_opened_is_listed_and_every_listed_span_is_opened():
    opened = _opened_names()
    # tracing.py's own helpers take the name as a parameter
    named = [(f, n) for f, n in opened if f != "tracing.py"]
    assert all(isinstance(n, str) for _, n in named), named
    assert {n for _, n in named} == set(tracing.SPANS)
    assert len(set(tracing.SPANS)) == len(tracing.SPANS)


def test_h2d_bytes_of_a_lookup_are_its_padded_arrays():
    rng = np.random.default_rng(3)
    keys = np.unique(rng.integers(0, 2**32, 5000, dtype=np.uint32))
    queries = rng.choice(keys, 300).astype(np.uint32)
    n, q = len(keys), len(queries)
    seg = (np.zeros(q, np.int32), np.full(q, n, np.int32),
           np.zeros(q, np.uint32))
    idx, found, _, copies = lookup_indices_batch(queries, keys,
                                                 segments=seg, window=512)
    assert found.all()
    # keys pad to 8192 u32, the key and query counts are one i32 each, and
    # four query columns pad to two 256-wide chunks of 4-byte words
    assert copies.h2d_bytes == 8192 * 4 + 4 + 4 + 4 * 512 * 4
    # one program runs both chunks
    assert copies.dispatches == 1
    # one fetch brings back 512 i32 indices and 512 found flags
    assert copies.d2h_bytes == 512 * (4 + 1)


def test_h2d_bytes_of_a_bloom_probe_are_its_padded_arrays():
    rng = np.random.default_rng(4)
    q, words = 100, 1000
    h1 = rng.integers(0, 2**32, q, dtype=np.uint32)
    h2 = rng.integers(0, 2**32, q, dtype=np.uint32) | np.uint32(1)
    bits = rng.integers(0, 2**32, words, dtype=np.uint32)
    found, copies = probe_cells_batch(
        h1, h2, np.zeros(q, np.int32), np.full(q, words * 32, np.uint32),
        bits, k=7)
    assert found.shape == (q,)
    # four per-query columns pad to 128, the packed words to 1024
    assert copies == Copies(dispatches=1, h2d_bytes=4 * 128 * 4 + 1024 * 4,
                            d2h_bytes=128)


def _keys(n, tag):
    return [hashlib.sha256(f"{tag}{i}".encode()).digest() for i in range(n)]


@pytest.fixture()
def db():
    d = tempfile.mkdtemp(prefix="tide-tracing-")
    cfg = DbConfig(keyspaces=[KeyspaceConfig("default", n_cells=8)],
                   wal=WalConfig(segment_size=1 << 20, background=False),
                   index_wal=WalConfig(segment_size=1 << 20,
                                       background=False),
                   background_snapshots=False, cache_bytes=0,
                   blob_cache_bytes=0)
    store = TideDB(d, cfg)
    try:
        yield store
    finally:
        store.close()
        shutil.rmtree(d, ignore_errors=True)


def test_store_counts_device_dispatches_and_bytes(db):
    present = _keys(1024, "p")
    db.put_many([(k, b"v" * 16) for k in present])
    db.snapshot_now(flush_threshold=1)           # every cell on disk
    s0 = db.stats()
    got = db.multi_exists(present + _keys(1024, "a"))
    s1 = db.stats()
    assert got == [True] * 1024 + [False] * 1024
    assert s1["bloom_dispatches"] - s0["bloom_dispatches"] == 1
    # ~1024 Bloom positives go to the lookup kernel, all in one call
    lookups = s1["batched_kernel_lookups"] - s0["batched_kernel_lookups"]
    assert lookups > 256
    assert s1["lookup_dispatches"] - s0["lookup_dispatches"] == 1
    assert s1["h2d_bytes"] > s0["h2d_bytes"]
    assert s1["d2h_bytes"] > s0["d2h_bytes"]


def test_server_times_its_steps_on_both_clocks(db):
    srv = KvBatchServer(db, max_batch=64)
    assert srv.step() == 0                       # an idle step is not timed
    assert srv.stats()["steps_served"] == 0
    # The thread CPU clock ticks at 10 ms on some hosts: serve until the
    # steps cover several ticks, and allow a tick between the two clocks.
    tick = max(time.get_clock_info("thread_time").resolution, 0.01)
    t0 = time.perf_counter()
    reqs, steps = [], 0
    while srv.stats()["step_wall_s"] < 5 * tick:
        keys = _keys(8, f"w{steps}-")
        reqs += [srv.submit_put(k, b"x") for k in keys]
        reqs += [srv.submit_get(k) for k in keys]
        assert srv.step() == 16
        steps += 1
    t1 = time.perf_counter()
    st = srv.stats()
    assert st["steps_served"] == steps
    assert st["step_wall_s"] <= t1 - t0
    assert 0 < st["step_cpu_s"] <= st["step_wall_s"] + tick
    # requests carry the same monotonic clock
    assert all(t0 <= r.t_submit <= r.t_done <= t1 for r in reqs)
