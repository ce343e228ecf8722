"""Where the store's Pallas kernels run: compiled by Mosaic on a TPU,
through the Pallas interpreter on every other backend.  The mode follows
the backend; no caller chooses it."""
from __future__ import annotations

import jax


def interpret() -> bool:
    return jax.default_backend() != "tpu"
