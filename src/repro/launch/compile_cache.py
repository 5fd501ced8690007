"""JAX's persistent compilation cache for the entry points.

Called from ``main`` of each entry point (``chip_smoke.py``,
``repro.launch.serve``, ``benchmarks.run``), never on import.  Where
``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and nothing is
overridden; otherwise the cache lives at ``<repo>/.jax_cache`` — a fixed
path, because the directory is part of what a later process must find.
"""
from __future__ import annotations

import os
from pathlib import Path

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the cache on; returns the directory in use."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
