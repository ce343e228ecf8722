"""Peaks of the chip and the bytes each device form is defined to move.

The byte counts are functions of call shapes and count the work the
operation is defined to do, so they read the same whatever implements it.
Both forms are integer gathers with no arithmetic to speak of, so their
roofline is the HBM bandwidth: ideal time = bytes / peak bytes per second.
"""
from __future__ import annotations

import json
import os

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")

QUERY_IN_BYTES = 16          # four 4-byte inputs per query


def peaks(device_kind: str, path: str = PEAKS) -> dict:
    """The peaks of ``device_kind``; a kind missing from the table raises."""
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}")
    return table[device_kind]


def lookup_bytes(queries: int, window_entries: int) -> int:
    """Optimistic lookup (paper §4.2): per query its inputs (the u32 key
    prefix and the segment's base, count and fraction), its outputs (a
    4-byte index and a 1-byte found flag) and one window of
    ``window_entries`` 4-byte keys, the single-roundtrip read."""
    return queries * (QUERY_IN_BYTES + 4 + 1 + 4 * window_entries)


def bloom_bytes(queries: int, k: int) -> int:
    """Ragged Bloom probe: per query its inputs (h1, h2, word base and
    modulus), ``k`` probed 4-byte words and a 1-byte answer."""
    return queries * (QUERY_IN_BYTES + 4 * k + 1)


def share_pct(nbytes: float, device_s: float, peak: dict):
    """Ideal time over measured device time, in percent; None when the
    form never ran on the device."""
    if not nbytes or not device_s:
        return None
    return 100.0 * (nbytes / peak["hbm_bytes_per_s"]) / device_s
