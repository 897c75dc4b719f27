"""JAX's persistent compilation cache, placed from outside or at one fixed path.

Entry points (``chip_smoke.py``, ``repro.launch.fl_train``,
``repro.launch.assign_serve``, ``benchmarks/run.py``) call
:func:`enable_compile_cache` once, before their first compile.  Tests do
not, so a test run never reads or writes a cache.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# The checkout's own cache directory (gitignored).  A fixed path on purpose:
# the path is part of the cache key, so a directory derived from a temporary
# name, a PID or the time would never hit.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it, and it
    stays the only location: nothing is set in code.  Otherwise the cache
    goes to :data:`DEFAULT_DIR`, inside the checkout.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
