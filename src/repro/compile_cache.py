"""Where JAX's persistent compilation cache lives.

Entry points (``chip_smoke.py``, the benchmark scripts) call
:func:`enable_compile_cache` once before they compile anything; importing the
library never does, so the tests keep their uncached behaviour.

The directory is part of what a cache entry is found by, so it never moves:
``JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads that variable itself
and nothing here overrides it), else ``.jax_cache`` at the root of this
checkout (git-ignored).
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    # discovery programs are many and each compiles in well under the
    # default one-second floor: cache them all
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir
