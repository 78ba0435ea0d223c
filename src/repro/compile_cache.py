"""JAX's persistent compilation cache, for the entry-point scripts.

``enable_compile_cache()`` is called by scripts (``chip_smoke.py``,
``benchmarks/run.py``), never at import time, so the library itself
changes no global JAX state. Where ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX reads it and the cache lives there; no other directory is set in
code. Otherwise the cache lives at the fixed ``.jax_cache/`` of this
checkout (listed in ``.gitignore``): the directory is part of what a
later run looks up, so it is never derived from a temp name, a PID or
the time.
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on for every compile (kernels compile
    in well under JAX's default 1 s threshold); returns its directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
