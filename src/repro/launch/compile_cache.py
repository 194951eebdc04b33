"""JAX's persistent compile cache, placeable from outside.

Entry points (chip_smoke.py, launch/serve.py, launch/train.py,
benchmarks/run.py, examples/) call ``configure()`` once before they
compile; library code never does. Where ``JAX_COMPILATION_CACHE_DIR`` is
set, JAX reads it itself and nothing is set here. Otherwise the cache
lives at a fixed ``.jax_cache/`` in the checkout: the path is part of the
cache key, so a directory that moved (a temp, pid or time path) would
never hit.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def configure() -> str:
    """Returns the cache directory in use."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
