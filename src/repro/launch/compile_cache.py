"""JAX's persistent compilation cache for the entry points.

    from repro.launch.compile_cache import enable_compile_cache
    print("compile cache:", enable_compile_cache())

Called by ``chip_smoke.py``, ``launch/train.py`` and
``examples/serve_dcnn.py`` at start-up, never at import time: a set
``JAX_COMPILATION_CACHE_DIR`` is left to JAX itself; otherwise the cache
lives at one fixed directory of the checkout (``.jax_cache/``), so a later
run from the same checkout finds what an earlier one compiled.
"""

from __future__ import annotations

import os
import pathlib

# src/repro/launch/compile_cache.py -> the checkout root
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env                      # JAX reads the variable itself
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
