"""JAX's persistent compilation cache, placed from outside or at a fixed path.

Entry points that run on a chip (``chip_smoke.py``, ``benchmarks/run.py``,
the examples) call :func:`enable` once, before their first compile.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing else
is set.  Otherwise the cache lives in ``<checkout>/.jax_cache``: the path
is part of each entry's key, so it carries no temporary name, pid or time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["enable", "DEFAULT_DIR"]

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; return the directory it writes to."""
    path = os.environ.get(ENV)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
