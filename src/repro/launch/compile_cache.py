"""Where the entry points keep JAX's persistent compilation cache.

A cache only hits when it sits at the same path from run to run (the path
is part of the key), so it lives at a fixed ``.jax_cache/`` at the
checkout root — never a temp, pid or time-based directory.  When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
set here.  Only entry points call :func:`enable`; library code and tests
never turn the cache on.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on for this process; returns its
    directory."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
