"""Production mesh builders.

Functions, not module-level constants, so importing this module never
touches jax device state (device count is locked at first jax init).
"""
from __future__ import annotations

import jax

from repro.parallel.sharding import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 (2 pods, 512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(model: int = 1):
    """Small mesh over whatever devices exist (tests / CPU smoke)."""
    n = jax.device_count()
    assert n % model == 0
    return make_mesh((n // model, model), ("data", "model"))
