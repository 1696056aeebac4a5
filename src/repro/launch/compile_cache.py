"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py`` and the ``repro.launch`` CLIs) call
:func:`enable` before their first compile; importing ``repro`` never
touches the cache.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
already reads it and nothing here sets another path.  Otherwise the
cache lives at the fixed ``<checkout>/.jax_cache``: a cache entry is only
found again at the path it was written to, so the path never depends on
a temp name, a pid or the time.
"""
from __future__ import annotations

import os
import pathlib

import jax

DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Point JAX's persistent compilation cache at ``$JAX_COMPILATION_
    CACHE_DIR`` if set, else at ``<checkout>/.jax_cache``; returns the
    directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
