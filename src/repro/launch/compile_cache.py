"""JAX's persistent compilation cache: one rule for every entry point.

Called by ``chip_smoke.py``, ``repro.launch.serve``, ``repro.launch.server``
and ``benchmarks.run`` when they start, never when a module is imported.
"""
from __future__ import annotations

import os
from pathlib import Path

# <checkout>/.jax_cache — fixed, because the path is part of each cache
# entry's key: a directory that moved between runs would never hit
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.
    ``JAX_COMPILATION_CACHE_DIR``, where set, is used as it is (JAX reads
    it itself); otherwise the cache lives in ``DEFAULT_DIR``."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
