"""The per-key windowed index lookup places its first window from the key's
position inside its own cell's key range, so a present key of a uniform
cell is found in about one window read, at any cell count.  Answers are
checked against a dict: batched reads small enough that every cell takes
the per-key path, scalar get and exists, and ``prev`` across cell
boundaries (where earlier cells are probed with the largest key)."""
import bisect
import hashlib
import shutil
import tempfile

import pytest

from repro.core.tidestore import DbConfig, KeyspaceConfig, TideDB
from repro.core.tidestore.index import OptimisticLookup, serialize_optimistic
from repro.core.tidestore.large_table import Keyspace
from repro.core.tidestore.util import Metrics
from repro.core.tidestore.wal import WalConfig

PER_CELL = 300          # entries a cell, ~6 windows of WINDOW entries
WINDOW = 48
KEY_LEN = 32


def _cfg(n_cells):
    return DbConfig(
        keyspaces=[KeyspaceConfig("default", n_cells=n_cells,
                                  window_entries=WINDOW,
                                  dirty_flush_threshold=1 << 20)],
        wal=WalConfig(segment_size=4 * 1024 * 1024, background=False),
        index_wal=WalConfig(segment_size=4 * 1024 * 1024, background=False),
        background_snapshots=False, system_stats=False)


def _keys(n, tag):
    return [hashlib.sha256(f"{tag}{i}".encode()).digest() for i in range(n)]


@pytest.fixture(scope="module", params=[1, 16, 256])
def cold_store(request):
    """A uniform store of ``PER_CELL`` keys a cell, flushed and reopened
    cold: every cell unloaded, its index on disk."""
    n_cells = request.param
    path = tempfile.mkdtemp(prefix="tide-perkey-")
    keys = _keys(PER_CELL * n_cells, "k")
    data = {k: b"v" + k[:7] for k in keys}
    db = TideDB(path, _cfg(n_cells))
    for i in range(0, len(keys), 4096):
        db.put_many(list(data.items())[i:i + 4096])
    db.close()
    db = TideDB(path, _cfg(n_cells))
    yield n_cells, db, data
    db.close()
    shutil.rmtree(path, ignore_errors=True)


def _by_cell(db, keys):
    space = db.table.ks(0)
    out: dict = {}
    for k in keys:
        out.setdefault(space.cell_id_for_key(k), []).append(k)
    return out


def _windows(db, before):
    m = db.metrics
    return (m.windowed_lookups - before[0], m.windowed_reads - before[1])


def _mark(db):
    return db.metrics.windowed_lookups, db.metrics.windowed_reads


def test_multi_get_reads_one_window_per_present_key(cold_store):
    n_cells, db, data = cold_store
    cells = _by_cell(db, sorted(data))
    # Two keys a cell a batch, a few batches: each cell's misses stay under
    # the whole-blob threshold (PER_CELL / WINDOW), so all go per key.  Only
    # the lower half of each cell is read here, the upper half stays out of
    # the value cache for the scalar reads.
    per_cell = min(PER_CELL // 2, max(2, 512 // n_cells))
    asked = []
    mark, blob_reads = _mark(db), db.metrics.batched_blob_reads
    for j in range(0, per_cell, 2):
        batch = [k for run in cells.values() for k in run[j:j + 2]]
        assert db.multi_get(batch) == [data[k] for k in batch]
        asked += batch
    lookups, reads = _windows(db, mark)
    assert db.metrics.batched_blob_reads == blob_reads
    assert lookups == len(asked)
    assert reads / lookups <= 1.2, (n_cells, reads / lookups)


def test_multi_get_absent_keys_are_none(cold_store):
    n_cells, db, data = cold_store
    absent = _keys(2 * n_cells, "absent")
    present = [run[0] for run in _by_cell(db, sorted(data)).values()]
    batch = absent + present
    assert db.multi_get(batch) == [None] * len(absent) + \
        [data[k] for k in present]


def test_scalar_get_and_exists(cold_store):
    n_cells, db, data = cold_store
    upper = [k for run in _by_cell(db, sorted(data)).values()
             for k in run[PER_CELL // 2:]]
    present = upper[::len(upper) // 64][:64]
    absent = _keys(64, "gone")
    mark = _mark(db)
    for k in present:
        assert db.get(k) == data[k]
    lookups, reads = _windows(db, mark)
    assert lookups == len(present)
    assert reads / lookups <= 1.2, (n_cells, reads / lookups)
    for k in present:
        assert db.exists(k)
    for k in absent:
        assert db.get(k) is None
        assert not db.exists(k)


def test_prev_across_cell_boundaries(cold_store):
    n_cells, db, data = cold_store
    ordered = sorted(data)
    space = db.table.ks(0)

    def expect(probe):
        i = bisect.bisect_left(ordered, probe)
        return (ordered[i - 1], data[ordered[i - 1]]) if i else None

    # The first key of each cell: its predecessor is the last key of the
    # cell before, found through the b"\xff" * key_len probe.
    firsts = [run[0] for run in _by_cell(db, ordered).values()][:32]
    # The lowest possible key of each cell, and the very top of the space.
    lows = [space.key_range(c)[0].to_bytes(8, "big").ljust(KEY_LEN, b"\x00")
            for c in range(0, n_cells, max(1, n_cells // 32))]
    for probe in firsts + lows + [b"\xff" * KEY_LEN, b"\x00" * KEY_LEN]:
        assert db.prev(probe) == expect(probe), probe.hex()


@pytest.mark.parametrize("n_cells", [1, 16, 256])
def test_cell_key_ranges_tile_the_keyspace(n_cells):
    space = Keyspace(0, KeyspaceConfig("d", n_cells=n_cells), Metrics())
    ranges = [space.key_range(c) for c in range(n_cells)]
    assert ranges[0][0] == 0 and ranges[-1][1] == 1 << 64
    assert all(a[1] == b[0] for a, b in zip(ranges, ranges[1:]))
    for k in _keys(2000, "r"):
        lo, hi = ranges[space.cell_id_for_key(k)]
        assert lo <= int.from_bytes(k[:8], "big") < hi
    for c, (lo, hi) in enumerate(ranges):
        assert space.cell_id_for_key(lo.to_bytes(8, "big")) == c
        assert space.cell_id_for_key((hi - 1).to_bytes(8, "big")) == c


def test_segment_estimate_clamps_keys_outside_the_range():
    """A blob holding one slice of the keyspace: keys inside are found in
    one window; a key above or below the slice lands on the last or first
    window, and the predecessor answers stay exact."""
    lo, hi = 5 << 60, 6 << 60
    inside = [(lo + (hi - lo) * i // 4000).to_bytes(8, "big") + bytes(24)
              for i in range(1, 4000, 3)]
    blob, n = serialize_optimistic({k: i for i, k in enumerate(inside)},
                                   KEY_LEN)

    def pread(off, ln):
        return blob[off:off + ln]

    lk = OptimisticLookup(pread, n, KEY_LEN, window_entries=64,
                          segment=(lo, hi))
    ordered = sorted(inside)
    for k in ordered[::37]:
        pos, iters = lk.lookup(k)
        assert pos == inside.index(k) and iters == 1
    top, _, iters = lk.predecessor(b"\xff" * KEY_LEN)
    assert top == ordered[-1] and iters == 1
    bottom, _, iters = lk.predecessor(b"\x00" * KEY_LEN)
    assert bottom is None and iters == 1
