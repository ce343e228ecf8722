"""Shared host-side helpers for the kernel ops wrappers: shape padding, and
what one call of a wrapper copied between host and device."""
from __future__ import annotations

from typing import NamedTuple


def next_pow2(n: int) -> int:
    """Smallest power of two >= n (1 for n <= 1)."""
    return 1 << max(0, int(n - 1).bit_length())


class Copies(NamedTuple):
    """Jitted calls made, bytes handed to ``jnp.asarray`` (after padding)
    and bytes taken back with ``np.asarray``."""
    dispatches: int = 0
    h2d_bytes: int = 0
    d2h_bytes: int = 0
