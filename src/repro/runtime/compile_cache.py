"""Where an entry point keeps JAX's persistent compilation cache.

The serving step at the paper's widths takes tens of seconds to compile
per (slot bucket, window bucket) jit entry, so every entry point
(`launch/serve.py`, `benchmarks/*`, `chip_smoke.py`) calls
`use_persistent_cache()` before its first compile.  Library code never
does: importing a module must not change global JAX state.
"""
from __future__ import annotations

import os
import pathlib

import jax

# a fixed place inside the checkout: the directory is listed in
# .gitignore, and a path that moved from run to run would never hit
CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_persistent_cache() -> str:
    """Turn the persistent cache on and return its directory.  A
    `JAX_COMPILATION_CACHE_DIR` set by the caller's environment wins:
    JAX reads it itself, and nothing is set here."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
