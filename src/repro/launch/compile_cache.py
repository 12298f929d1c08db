"""JAX's persistent compilation cache, placed by the entry points.

The entry points (``launch/serve.py``, ``launch/train.py``,
``examples/discover_topology.py``, ``chip_smoke.py``) call
``enable_compile_cache()`` at the start of ``main``; nothing calls it at
import.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
this sets no directory.  Otherwise the cache goes to ``.jax_cache`` at the
root of the checkout — always the same path, so a later run finds what an
earlier one stored.
"""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    # Cache every compile: the probe kernels take about a second each,
    # below JAX's default one-second floor.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
