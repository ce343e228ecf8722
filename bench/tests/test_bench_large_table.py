"""The reader of the Large Table's per-key windowed lookups."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402


def _read(db):
    return harness.load_reader("large_table.windows_per_lookup").read(
        {"db": db, "srv": {}})


def test_windows_per_lookup_reader():
    assert _read({"windowed_lookups": 400,
                  "windowed_reads": 420}) == pytest.approx(1.05)
    # a store without the counters, or a window with no per-key lookup
    assert _read({"index_lookups": 7}) is None
    assert _read({"windowed_lookups": 0, "windowed_reads": 0}) is None
